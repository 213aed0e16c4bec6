"""The facade's glue as compiled programs (``core/fleet.py``).

A scanned call is three programs: the copy of the caller's resume state
(``_copy_tree``), the engine (``jit_run``) and the finish
(``_finish_program``: the window cuts, both compliance reports and the
wear report).  The eager ``compliance.report_from_observers`` and
``health.report`` stay the reference: the compiled finish equals them on
the same observers and state, bit for bit except for the roundings a fused
multiply-add saves (``_FMA_FIELDS``), for a fresh campus, a resumed one,
one with a ragged tail, and each campus of a four-device sharded region (a
fresh process, as four forced CPU devices need one).
"""
import json
import logging
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import compliance, fleet, health as hlt, pdu
from repro.power import scenario as SC

_SPEC = compliance.GridSpec.create()
_HZ = 200.0
_K = 1000  # samples per 5 s controller interval at 200 Hz


def _campus(n_racks=4, duration_s=30.0):
    return SC.mixed_campus(
        n_racks, ("llama3_2_1b", "whisper_large_v3"), duration_s=duration_s,
        sample_hz=_HZ, seed=2, fault_at_s=duration_s * 0.6, noise_seed=7)


def _cfg(hz=_HZ):
    return pdu.make_pdu(sample_dt=1.0 / hz, track_health=True)


def _call(scen, cfg, state, start, stop, chunk_intervals=2):
    return jax.block_until_ready(fleet.condition(
        scen, cfg, _SPEC, stream=fleet.StreamOptions(
            chunk_intervals=chunk_intervals, state=state,
            start_sample=start, stop_sample=stop)))


# Fields whose eager computation rounds a product before the add or
# subtract that takes it.  XLA:CPU contracts such a pair inside a fused
# kernel into one fused multiply-add (rounded once), whatever barrier the
# program holds, so there the compiled finish may differ from the eager
# reference by that one rounding: a few ulps, bounded below.  Every other
# field is bitwise.
_FMA_FIELDS = {
    "report_rack": {"worst_high_freq_mag"},  # sqrt(re * re + im * im)
    "report_grid": {"worst_high_freq_mag"},
    # q * (c / eta_c + d * eta_d); t + g * (s * dt - s_ref * t); their sum
    # and the lifetime from it
    "health": {"throughput_s", "calendar_life_frac", "capacity_fade",
               "projected_life_s", "soc_std"},
}
_FMA_ULPS = 4
_EPS = float(np.finfo(np.float32).eps)


def _bitwise(a, b):
    return (np.asarray(a).dtype == np.asarray(b).dtype
            and np.array_equal(np.asarray(a), np.asarray(b)))


def _within_fma(name, f, got, want, health):
    """``got`` is ``want`` up to the roundings a fused multiply-add saves."""
    a, b = np.asarray(got), np.asarray(want)
    if a.dtype != b.dtype or a.shape != b.shape:
        return False
    if f == "soc_std":
        # sqrt(q - m * m): the variance's one rounding of m * m moves it by
        # at most eps * q, and the square root adds eps relative.
        n = np.maximum(np.asarray(health.samples, np.float64), 1.0)
        q = np.asarray(health.soc_sq_sum, np.float64) / n
        d = np.abs(a.astype(np.float64) ** 2 - b.astype(np.float64) ** 2)
        return bool(np.all(d <= 4 * _EPS * q))
    try:
        np.testing.assert_array_max_ulp(a, b, maxulp=_FMA_ULPS)
    except AssertionError:
        return False
    return True


def mismatches(cfg, r, t_total, n_ctrl):
    """Fields of ``r`` that differ from the eager reference computed on its
    own observers and final state: bitwise (dtype included), or, where XLA
    forms a fused multiply-add, within its rounding."""
    obs = r.observers
    want = {
        "report_rack": compliance.report_from_observers(
            r.grid_spec, obs.ramp_rack, r.bank, obs.spec_rack),
        "report_grid": compliance.report_from_observers(
            r.grid_spec, obs.ramp_grid, r.bank, obs.spec_grid),
        "health": hlt.report(fleet._health_params(cfg), cfg.ess_params,
                             r.state.health, cfg.sample_dt),
    }
    bad = []
    for name, w in want.items():
        got = getattr(r, name)
        for f in got._fields:
            a, b = getattr(got, f), getattr(w, f)
            if a is None or b is None:
                same = a is b
            elif f in _FMA_FIELDS[name]:
                same = _within_fma(name, f, a, b, r.state.health)
            else:
                same = _bitwise(a, b)
            if not same:
                bad.append(f"{name}.{f}")
    for name, n in (("campus_rack", t_total), ("campus_grid", t_total),
                    ("soc_mean", n_ctrl), ("ess_online_frac", n_ctrl)):
        if getattr(r, name).shape[-1] != n:
            bad.append(f"{name}.shape")
    return bad


_REGION_SCRIPT = r"""
import json, sys
sys.path[:0] = [{tests!r}, {src!r}]
import jax
from repro.core import compliance, fleet, grid, pdu
from repro.sharding import rules
from test_fleet_finish import mismatches

assert len(jax.devices()) == 4
hz = 20.0
reg = grid.checkpoint_region(4, 4, duration_s=60.0, sample_hz=hz)
cfg = pdu.make_pdu(sample_dt=1.0 / hz, track_health=True)
mesh = rules.region_mesh(4)
home = list(rules.campus_rows(mesh)[:, 0])
win = 2 * int(round(float(cfg.controller.dt) * hz))
out = {{"mismatch": [], "misplaced": []}}
state = None
for start in (0, win):  # a fresh call, then one from the carried states
    r = jax.block_until_ready(fleet.condition(
        reg, cfg, compliance.GridSpec.create(), mesh=mesh,
        stream=dict(chunk_intervals=2, state=state, start_sample=start,
                    stop_sample=start + win)))
    state = r.state
    for c, p in enumerate(r.per_campus):
        out["mismatch"] += [f"{{start}}.{{c}}.{{m}}"
                            for m in mismatches(cfg, p, win, 2)]
        for f in ("report_rack", "report_grid", "health", "campus_rack"):
            if any(x.devices() != {{home[c]}}
                   for x in jax.tree_util.tree_leaves(getattr(p, f))):
                out["misplaced"].append(f"{{start}}.{{c}}.{{f}}")
print(json.dumps(out))
"""


def _region_campus_mismatches():
    here = os.path.dirname(os.path.abspath(__file__))
    script = _REGION_SCRIPT.format(
        tests=here, src=os.path.join(os.path.dirname(here), "src"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    return out["mismatch"] + out["misplaced"]


@pytest.mark.parametrize("case", ["fresh", "resumed", "ragged", "region_campus"])
def test_compiled_finish_equals_eager_reports(case):
    if case == "region_campus":
        # Each campus's finish runs on its own device.
        assert _region_campus_mismatches() == []
        return
    cfg = _cfg()
    if case == "ragged":  # 32.5 s: three 2-interval chunks, a 500-sample tail
        r = _call(_campus(duration_s=32.5), cfg, None, 0, None)
        assert mismatches(cfg, r, 6500, 7) == []
        return
    scen = _campus()
    r = _call(scen, cfg, None, 0, 2 * _K)
    if case == "resumed":
        r = _call(scen, cfg, r.state, 2 * _K, 4 * _K)
    assert mismatches(cfg, r, 2 * _K, 2) == []


def test_facade_call_is_three_programs():
    """A call from a caller's state runs the state copy, the engine and the
    finish, and nothing eager in between."""
    cfg, scen = _cfg(), _campus(n_racks=3)
    state = pdu.init_state(cfg, SC.render(scen, 0, 1)[0])

    def call(st):
        r = fleet.condition(scen, cfg, _SPEC, stream=dict(
            chunk_intervals=2, state=st, start_sample=2 * _K,
            stop_sample=4 * _K))
        return (r.campus_rack, r.campus_grid, r.soc_mean, r.report_rack,
                r.report_grid, r.health, r.state)

    eqns = jax.make_jaxpr(call)(state).jaxpr.eqns
    assert [e.params["name"] for e in eqns] == ["_copy_tree", "run", "finish"]


def test_second_call_of_a_geometry_compiles_nothing(caplog):
    cfg, scen = _cfg(), _campus()
    state = pdu.init_state(cfg, SC.render(scen, 0, 1)[0])
    r = _call(scen, cfg, state, 0, 2 * _K)
    r = _call(scen, cfg, r.state, 2 * _K, 4 * _K)  # a carried state
    cached = len(fleet._ENGINE_CACHE)
    with caplog.at_level(logging.WARNING, logger="jax"), jax.log_compiles():
        _call(scen, cfg, r.state, 4 * _K, 6 * _K)
    compiled = [m for m in caplog.messages if "Compiling" in m or "tracing" in m]
    assert compiled == []
    assert len(fleet._ENGINE_CACHE) == cached


def test_spectrum_norm_is_the_eager_hann_norm():
    # Traced, the window's cos and mean may fold at compile time to another
    # ulp: at 1000 samples they do on the CPU, so the compiled reports take
    # the eagerly computed norm instead.
    bank = compliance.make_bank(1000, 1.0 / _HZ, 2.0)
    norm = fleet._spectrum_norm(bank)
    assert norm.dtype == np.float32
    assert norm == np.asarray(compliance.hann_norm(1000))
    assert fleet._spectrum_norm(compliance.make_online_bank(1.0 / _HZ, 2.0)) is None
