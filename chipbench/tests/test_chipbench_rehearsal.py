"""CPU rehearsals of whole runs at a few racks: the campus stream with
the wrap, the comparison with its committed limits, the control, the
faults the comparison has to catch, and the cross-chip path.

A rehearsal skips only the harness's look for a TPU; the stream, the
reference and the comparison run as on the chip.  A region, added to a
copy of the benchmark as files alone, runs in a subprocess on four
virtual CPU devices.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

CAMPUS_SMALL = {"racks": 16, "duration_s": 160.0, "sample_hz": 20.0}
SEED = 3000000019  # past 2**31: seeds need not fit 32 signed bits


def _run(capsys, workload, overrides, fault=None, seconds="2"):
    from chipbench import run

    rc = run.main(["--workload", workload, "--seed", str(SEED), "--seconds", seconds,
                   "--trace", "0"], device_check=False, overrides=overrides, fault=fault)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def _fresh_engines():
    from repro.core import fleet

    fleet._ENGINE_CACHE.clear()


def test_campus_stream_rehearsal_wraps_and_is_correct(capsys):
    _fresh_engines()
    line = _run(capsys, "campus.stream", CAMPUS_SMALL)
    # 2 calls per lap at this size: a 2 s window wraps many times.
    assert line["attempted"] > 6
    assert line["correct"] is True and line["failed"] == 0, line["checks"]
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {"rack_s_per_s", "call_ms.p95", "setup_s"}
    assert all(c["value"] <= c["limit"] for c in line["checks"].values())


def _state_unchanged(system):
    call = system.call

    def broken(state, *a):
        return call(state, *a)._replace(state=state)

    system.call = broken


def _answer_altered(system):
    """One sample of each call's conditioned campus power steps by 0.1 of
    rated power: the transient the conditioner exists to remove."""
    call = system.call

    def broken(state, *a):
        res = call(state, *a)
        return res._replace(campus_grid=res.campus_grid.at[..., 3].add(0.1))

    system.call = broken


class _HalfMean:
    """``jax.numpy`` with means over the first half of axis 1 only: the
    campus means leave half of the racks out."""

    def __init__(self, jnp):
        self._jnp = jnp

    def __getattr__(self, name):
        return getattr(self._jnp, name)

    def mean(self, x, axis=None, **kw):
        if axis == 1 and x.ndim == 2:
            x = x[:, : max(x.shape[1] // 2, 1)]
        return self._jnp.mean(x, axis=axis, **kw)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "answer_altered"])
def test_a_broken_campus_step_is_not_correct(capsys, monkeypatch, fault):
    from repro.core import pdu

    _fresh_engines()
    hook = None
    if fault == "state_unchanged":
        hook = _state_unchanged
    elif fault == "answer_altered":
        hook = _answer_altered
    else:
        monkeypatch.setattr(pdu, "jnp", _HalfMean(pdu.jnp))
    try:
        line = _run(capsys, "campus.stream", CAMPUS_SMALL, fault=hook, seconds="1")
    finally:
        _fresh_engines()
    assert line["correct"] is False
    assert line["failed"] > 0


def test_the_control_is_not_correct():
    """The reference one precision down (bfloat16, matrix products at
    HIGH) in the program's place fails the committed limits."""
    from chipbench import control, spec

    limits = spec.resolve(ROOT, "campus.stream").limits
    got = control.readings("campus.stream", SEED, overrides=CAMPUS_SMALL)
    assert got["correct"] is False and got["failed"] > 0
    assert any(v > limits[n] for n, v in got["control"].items()), (got, limits)


# A region of lockstep-checkpointing campuses at one point of
# interconnection, added to a copy of the benchmark as new files only.
# It rehearses the harness's cross-chip path (one campus per device, the
# POI folded by psum); its sizes and limits are a fixture, not a
# benchmark configuration.
_REGION_WORKLOAD = {
    "t_start_s": 0.0, "t_end_s": 1e30, "fault_at_s": 1e30, "fault_duration_s": 20.0,
    "p_fault": 0.02, "diurnal_period_s": 1e30, "diurnal_amp": 0.0, "diurnal_phase_s": 0.0,
    "scale": 1.0, "noise_std": 0.01, "iteration_period_s": 22.0, "comm_fraction": 0.0,
    "p_compute": 0.92, "p_comm": 0.92, "dip_period_s": 8.0, "dip_duration_s": 2.0,
    "p_dip": 0.12, "warmup_s": 2.0, "p_idle": 0.1,
}
_REGION_LIMITS = {"rack": 1.1e-4, "grid": 5.5e-3, "soc": 3.7e-3, "plant": 6.8e-3,
                  "qp_residual": 0.012, "ramp": 0.062, "spectrum": 0.046, "poi": 5.4e-3,
                  "swing": 4.2e-4, "modes": 0.88}


def _region_checkout(tmp_path):
    base = tmp_path / "chipbench"
    shutil.copytree(os.path.join(ROOT, "chipbench"), base,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    campus = json.load(open(os.path.join(ROOT, bench["configs"][0]["file"])))
    config = {
        "name": "region_rehearsal", "builder": "lockstep_region", "campuses": 4, "racks": 8,
        "sample_hz": 50.0, "duration_s": 60.0, "qp_iters": 30,
        "pdu": {**campus["pdu"], "track_health": False},
        "workloads": {"lockstep": _REGION_WORKLOAD},
        "mix": {"workload": "lockstep", "edge_time_s": 0.25, "edge_pad": "clamp",
                "noise_seed": 0},
        "poi": {"inertia_s": 8.0, "damping": 1.5, "f0_hz": 60.0, "v_sens": 0.05,
                "region_fraction": 0.01},
        "bands": [["inter_area", 0.1, 1.0, 0.005], ["local_plant", 1.0, 3.0, 0.005]],
    }
    (base / "configs" / "region_rehearsal.json").write_text(json.dumps(config))
    (base / "traffic" / "window20s.json").write_text(json.dumps(
        {"window_intervals": 4, "chunk_intervals": 4, "warmup_calls": 2, "trace_seconds": 2}))
    (base / "limits" / "region.rehearsal.json").write_text(json.dumps(
        {"limits": {k: {"limit": v} for k, v in _REGION_LIMITS.items()}}))
    bench["configs"].append({"name": "region_rehearsal", "source": "x", "reduced": [],
                             "file": "chipbench/configs/region_rehearsal.json", "why": "x"})
    bench["workloads"].append({"name": "region.rehearsal", "config": "region_rehearsal",
                               "traffic": "window20s", "chips": 4, "why": "x"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return base


_REGION_SCRIPT = r"""
import sys
sys.path[:0] = [{root!r}, {src!r}]
import jax
from chipbench import run
fault = sys.argv[1]
if fault == "no_exchange":
    # Each chip keeps its own campus's share of the POI.
    jax.lax.psum = lambda x, axis_name, **kw: x
run.main(["--workload", "region.rehearsal", "--seed", "{seed}", "--seconds", "3",
          "--trace", "0"], device_check=False)
"""


@pytest.mark.parametrize("fault", ["none", "no_exchange"])
def test_region_window_rehearsal_on_four_devices(tmp_path, fault):
    base = _region_checkout(tmp_path)
    script = _REGION_SCRIPT.format(root=str(base.parent), src=os.path.join(ROOT, "src"),
                                   seed=SEED)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", script, fault], capture_output=True,
                       text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["device"]["count"] == 4
    assert "0 compilations inside the window" in p.stderr
    if fault == "none":
        assert line["attempted"] > 3  # 3 calls per lap: the window wraps
        assert line["correct"] is True, line["checks"]
    else:
        assert line["correct"] is False
        assert line["checks"]["poi"]["value"] > line["checks"]["poi"]["limit"]
