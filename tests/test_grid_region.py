"""Grid-region tests (ISSUE 8): POI aggregation, swing coupling, mode-band
verdicts, the ``fleet.condition`` facade's target forms and engines, and
campus sharding.

Bitwise contract: the sequential region engine routes every campus through
the same trivial (campus=1, data=1) ``shard_map`` mesh the sharded engine
compiles, and the POI is a left-to-right float32 weighted sum matching the
in-scan ``psum`` order — so sequential vs sharded agreement is exact array
equality, not a tolerance.  The multi-device half of that claim runs in a
subprocess with ``--xla_force_host_platform_device_count=8`` (this process
has already initialized a 1-CPU backend).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compliance, fleet, grid, pdu
from repro.power import scenario as SC
from repro.sharding import rules

pytestmark = pytest.mark.grid

_HZ = 50.0
_SPEC = compliance.GridSpec.create()


def _cfg(**kw):
    return pdu.make_pdu(sample_dt=1.0 / _HZ, **kw)


def _small_region(n_campuses=3, n_racks=4, duration_s=60.0, **kw):
    return grid.checkpoint_region(
        n_campuses, n_racks, duration_s=duration_s, sample_hz=_HZ, **kw)


def _campus(n_racks=4, duration_s=60.0, seed=2, noise_seed=7):
    return SC.mixed_campus(
        n_racks,
        ("llama3_2_1b", "whisper_large_v3"),
        duration_s=duration_s,
        sample_hz=_HZ,
        seed=seed,
        noise_seed=noise_seed,
    )


def _tree_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ----------------------------------------------------------------- builders


def test_region_rejects_mismatched_clock():
    a = _campus(duration_s=60.0)
    b = _campus(duration_s=40.0)
    with pytest.raises(ValueError, match="one POI clock"):
        grid.region([a, b])


def test_region_default_weights_follow_rack_share():
    reg = grid.region([_campus(n_racks=4), _campus(n_racks=6, seed=3)])
    np.testing.assert_allclose(np.asarray(reg.weights), [0.4, 0.6], atol=1e-7)
    assert reg.n_racks == (4, 6)
    assert reg.names == ("campus0", "campus1")
    assert reg.n_campuses == 2
    assert reg.sample_hz == _HZ


def test_region_validates_weights_and_names():
    c = [_campus(), _campus(seed=3)]
    with pytest.raises(ValueError, match="weights shape"):
        grid.region(c, weights=np.ones((3,), np.float32))
    with pytest.raises(ValueError, match="names"):
        grid.region(c, names=("only-one",))
    with pytest.raises(ValueError, match="at least one campus"):
        grid.region([])


def test_region_salts_noise_per_campus():
    # Same workload spec + same static noise_seed: the builder must salt
    # each campus so the measurement noise decorrelates across the region.
    reg = _small_region(n_campuses=2, duration_s=20.0, noise_seed=5)
    salts = [c.noise_salt for c in reg.campuses]
    assert salts[0] is not None and salts[1] is not None
    assert int(np.asarray(salts[0])) != int(np.asarray(salts[1]))
    r0 = np.asarray(SC.render(reg.campuses[0], 0, 200))
    r1 = np.asarray(SC.render(reg.campuses[1], 0, 200))
    assert not np.array_equal(r0, r1)

    # Without noise there is nothing to salt.
    clean = _small_region(n_campuses=2, duration_s=20.0, noise_seed=None)
    assert all(c.noise_salt is None for c in clean.campuses)


# ---------------------------------------------------------------- POI model


def test_poi_response_flat_trace_is_quiet():
    r = grid.poi_response(jnp.full((500,), 0.5), grid.POIConfig(), 1.0 / _HZ)
    np.testing.assert_array_equal(np.asarray(r.freq_dev_hz), 0.0)
    np.testing.assert_array_equal(np.asarray(r.volt_dev), 0.0)
    assert float(r.max_freq_dev_hz) == 0.0


def test_poi_response_step_signs_and_linearity():
    # A sustained load increase must depress both frequency and voltage.
    # Post-step span of 60 s ≈ 11 swing time constants (M/D ≈ 5.3 s), so
    # the tail sits at the analytic steady state.
    p = jnp.concatenate([jnp.full((250,), 0.5), jnp.full((3000,), 0.7)])
    poi = grid.POIConfig()
    r = grid.poi_response(p, poi, 1.0 / _HZ, p_ref=jnp.float32(0.5))
    assert float(r.freq_dev_hz[-1]) < 0.0
    assert float(r.volt_dev[-1]) < 0.0
    np.testing.assert_allclose(
        float(r.volt_dev[-1]), -poi.v_sens * 0.2, rtol=1e-5)
    # Steady state of M df/dt = -(k*dp + D f) is f = -k*dp/D (per unit).
    expect = -poi.region_fraction * 0.2 / poi.damping * poi.f0_hz
    np.testing.assert_allclose(float(r.freq_dev_hz[-1]), expect, rtol=1e-3)
    # The swing recurrence is linear: doubling the coupling doubles freq.
    r2 = grid.poi_response(
        p, grid.POIConfig(region_fraction=2 * poi.region_fraction),
        1.0 / _HZ, p_ref=jnp.float32(0.5))
    np.testing.assert_allclose(
        np.asarray(r2.freq_dev_hz), 2.0 * np.asarray(r.freq_dev_hz),
        atol=1e-6)


# ------------------------------------------------------------ mode detector


def test_mode_bank_lines_cover_every_band():
    n = int(100.0 * _HZ)
    bank = grid.mode_bank(n, 1.0 / _HZ)
    freqs = bank.freqs
    for b in grid.DEFAULT_MODE_BANDS:
        sel = (freqs >= b.lo_hz) & (freqs < b.hi_hz)
        assert np.any(sel), f"no monitored line in {b.name}"
    lo = min(b.lo_hz for b in grid.DEFAULT_MODE_BANDS)
    hi = max(b.hi_hz for b in grid.DEFAULT_MODE_BANDS)
    # The bank monitors the inclusive band-edge bin; verdicts select
    # half-open [lo, hi) per band.
    assert np.all((freqs >= lo) & (freqs <= hi))


def test_mode_verdicts_flag_injected_tone():
    n = int(100.0 * _HZ)
    dt = 1.0 / _HZ
    t = np.arange(n) * dt
    tone = jnp.asarray(0.5 + 0.02 * np.sin(2 * np.pi * 0.5 * t), jnp.float32)
    bank = grid.mode_bank(n, dt)
    obs = compliance.spectrum_observer_update(
        bank, compliance.spectrum_observer_init(bank), tone)
    mags, ok = grid.mode_verdicts(bank, obs, grid.DEFAULT_MODE_BANDS)
    mags, ok = np.asarray(mags), np.asarray(ok)
    assert not ok[0] and mags[0] == pytest.approx(0.02, rel=0.05)
    assert ok[1] and mags[1] < 1e-3

    quiet = jnp.full((n,), 0.5)
    obs_q = compliance.spectrum_observer_update(
        bank, compliance.spectrum_observer_init(bank), quiet)
    _, ok_q = grid.mode_verdicts(bank, obs_q, grid.DEFAULT_MODE_BANDS)
    assert np.all(np.asarray(ok_q))


def test_mode_verdicts_empty_band_passes():
    # A 4 s trace cannot resolve the 0.1-1 Hz band's lower end with bins
    # strictly inside [0.1, 1.0) only if lines exist; shrink to a band
    # below the fundamental so no DFT bin lands inside it.
    n = int(4.0 * _HZ)
    bank = grid.mode_bank(
        n, 1.0 / _HZ, bands=(grid.ModeBand("sub", 0.01, 0.2, 1e-9),))
    narrow = (grid.ModeBand("none", 0.0101, 0.0102, 1e-9),)
    obs = compliance.spectrum_observer_update(
        bank, compliance.spectrum_observer_init(bank),
        jnp.ones((n,), jnp.float32))
    mags, ok = grid.mode_verdicts(bank, obs, narrow)
    assert float(mags[0]) == 0.0 and bool(ok[0])


@pytest.mark.slow
def test_synchronized_checkpoints_ring_staggered_cancel():
    # The paper-level finding: lockstep checkpoint stalls across campuses
    # excite a sub-Hz inter-area mode at the POI; staggering the same
    # schedule cancels it.  Runs the full conditioning stack.
    cfg = _cfg()
    sync = grid.synchronized_region(
        n_campuses=4, n_racks=6, duration_s=100.0, sample_hz=_HZ)
    stag = grid.staggered_region(
        n_campuses=4, n_racks=6, duration_s=100.0, sample_hz=_HZ)
    rs = fleet.condition(sync, cfg, _SPEC)
    rt = fleet.condition(stag, cfg, _SPEC)
    assert not bool(rs.report_poi.modes_ok)
    assert not bool(np.asarray(rs.report_poi.mode_ok)[0])  # inter-area band
    assert bool(rt.report_poi.modes_ok)
    # An order of magnitude of separation, not a marginal verdict.
    assert float(rs.report_poi.mode_mags[0]) > 10 * float(
        rt.report_poi.mode_mags[0])
    # The verdict folds into the region-level ok and the facade's report().
    assert not bool(rs.report_grid.ok)
    assert not bool(rs.report("poi").modes_ok)
    # Physically plausible excursions at 1% regional penetration.
    assert float(np.max(np.abs(np.asarray(rs.poi_freq_dev)))) < 1.0


# ------------------------------------------------------------------ facade


def _assert_bitwise(a, b):
    """Every populated array field of two ConditioningResults is equal."""
    for f in fleet.ConditioningResult._fields:
        va, vb = getattr(a, f), getattr(b, f)
        assert (va is None) == (vb is None), f
        if va is None or f in ("bank", "grid_spec", "per_campus"):
            continue
        _tree_equal(va, vb)


def test_oneshot_on_array_matches_oneshot_on_scenario():
    """The one-shot engine on a Scenario is the same call as on the (T, R)
    array that Scenario renders."""
    cfg = _cfg()
    scen = _campus(duration_s=30.0)
    from_scen = fleet.condition(scen, cfg, _SPEC, engine="oneshot")
    traces = SC.render(scen, 0, scen.total_samples)
    from_array = fleet.condition(traces, cfg, _SPEC, engine="oneshot")
    _assert_bitwise(from_scen, from_array)
    assert from_array.grid_traces.shape == traces.shape


def test_result_aliases_and_report():
    res = fleet.condition(_campus(), _cfg(), _SPEC)
    assert isinstance(res, fleet.ConditioningResult)
    # ``.report()`` runs the program the finish traced: every field equal.
    for which in ("rack", "grid"):
        rep, stored = res.report(which), getattr(res, f"report_{which}")
        for f in rep._fields:
            a, b = getattr(rep, f), getattr(stored, f)
            assert (a is None) == (b is None), f
            if a is not None:
                assert np.asarray(a).dtype == np.asarray(b).dtype, f
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b), f)
    with pytest.raises(ValueError):
        res.report("nope")


@pytest.mark.parametrize("target, engine", [
    pytest.param("scenario", "host", id="host-scenario"),
    pytest.param("array", "host", id="host-array"),
    pytest.param("callable", "scanned", id="callable"),
])
def test_facade_rejects_removed_engine_and_target_forms(target, engine):
    """The host loop and chunk providers are gone: the facade names the
    two engines left and how to reach them."""
    scen = _campus(duration_s=20.0)
    tr = SC.render(scen, 0, scen.total_samples)
    target = {"scenario": scen, "array": tr,
              "callable": lambda t0, n: tr[t0:t0 + n]}[target]
    with pytest.raises(ValueError, match="'scanned'.*'oneshot'") as e:
        fleet.condition(target, _cfg(), _SPEC, engine=engine)
    assert "render a Scenario" in str(e.value)
    assert "(T, R)" in str(e.value)


def test_facade_rejects_bad_engines_and_stream_options():
    cfg = _cfg()
    reg = _small_region(duration_s=20.0)
    with pytest.raises(ValueError, match="scanned engine only"):
        fleet.condition(reg, cfg, _SPEC, engine="oneshot")
    with pytest.raises(TypeError, match="total_samples"):
        fleet.condition(reg, cfg, _SPEC, stream=dict(total_samples=100))
    with pytest.raises(ValueError, match="not supported by the 'oneshot'"):
        fleet.condition(_campus(), cfg, _SPEC, engine="oneshot",
                        stream=dict(start_sample=0, stop_sample=500))
    with pytest.raises(ValueError, match="unknown engine"):
        fleet.condition(SC.render(_campus(), 0, 500), cfg, _SPEC,
                        engine="warp")
    with pytest.raises(TypeError):
        fleet.condition(_campus(), cfg, _SPEC, stream=42)


# ---------------------------------------------------------- region engines


@pytest.fixture(scope="module")
def region_result():
    cfg = _cfg()
    reg = _small_region(duration_s=60.0, noise_seed=3)
    return reg, fleet.condition(reg, cfg, _SPEC)


def test_region_result_shapes(region_result):
    reg, res = region_result
    c, t = reg.n_campuses, int(reg.total_samples)
    assert np.asarray(res.campus_rack).shape == (c, t)
    assert np.asarray(res.campus_grid).shape == (c, t)
    assert np.asarray(res.poi_rack).shape == (t,)
    assert np.asarray(res.poi_grid).shape == (t,)
    assert np.asarray(res.poi_freq_dev).shape == (t,)
    assert np.asarray(res.poi_volt_dev).shape == (t,)
    assert len(res.per_campus) == c and len(res.state) == c
    assert res.report_grid is res.report_poi
    assert res.health is None  # per-campus health lives in per_campus
    assert all(r.health is not None for r in res.per_campus)


def test_region_poi_is_left_to_right_weighted_sum(region_result):
    reg, res = region_result
    w = np.asarray(res.weights)
    for name in ("campus_rack", "campus_grid"):
        per = [getattr(r, name) for r in res.per_campus]
        acc = jnp.float32(w[0]) * per[0]
        for c in range(1, reg.n_campuses):
            acc = acc + jnp.float32(w[c]) * per[c]
        got = getattr(res, "poi_rack" if name == "campus_rack" else "poi_grid")
        np.testing.assert_array_equal(np.asarray(acc), np.asarray(got))


def test_region_per_campus_matches_stacked_aggregates(region_result):
    reg, res = region_result
    for c in range(reg.n_campuses):
        np.testing.assert_array_equal(
            np.asarray(res.per_campus[c].campus_rack),
            np.asarray(res.campus_rack)[c])
        np.testing.assert_array_equal(
            np.asarray(res.per_campus[c].campus_grid),
            np.asarray(res.campus_grid)[c])


def test_region_poi_freq_matches_direct_poi_response(region_result):
    reg, res = region_result
    r = grid.poi_response(res.poi_grid, reg.poi, 1.0 / reg.sample_hz)
    np.testing.assert_array_equal(
        np.asarray(r.freq_dev_hz), np.asarray(res.poi_freq_dev))
    np.testing.assert_array_equal(
        np.asarray(r.volt_dev), np.asarray(res.poi_volt_dev))


def test_region_heterogeneous_rack_counts():
    cfg = _cfg()
    reg = grid.region(
        [_campus(n_racks=3, seed=2), _campus(n_racks=5, seed=4)])
    res = fleet.condition(reg, cfg, _SPEC)
    assert np.asarray(res.campus_rack).shape[0] == 2
    np.testing.assert_allclose(
        np.asarray(res.weights), [3 / 8, 5 / 8], atol=1e-7)


def test_region_windowed_resume_is_bitwise(region_result):
    reg, full = region_result
    cfg = _cfg()
    k = int(round(float(cfg.controller.dt) * _HZ))  # samples per interval
    cut = 4 * k
    a = fleet.condition(reg, cfg, _SPEC, stream=dict(stop_sample=cut))
    b = fleet.condition(
        reg, cfg, _SPEC,
        stream=dict(state=a.state, start_sample=cut))
    for f in ("campus_rack", "campus_grid", "poi_rack", "poi_grid"):
        cat = np.concatenate(
            [np.asarray(getattr(a, f)), np.asarray(getattr(b, f))], axis=-1)
        np.testing.assert_array_equal(cat, np.asarray(getattr(full, f)))
    _tree_equal(b.state, full.state)

    with pytest.raises(ValueError, match="multiple of"):
        fleet.condition(reg, cfg, _SPEC, stream=dict(start_sample=7))


def test_region_sharded_one_device_mesh_is_noop():
    # A 1-campus region through the public sharded entry on a trivial
    # (campus=1, data=1) mesh must equal the sequential loop bitwise.
    cfg = _cfg()
    reg = grid.region([_campus(seed=5)])
    mesh = rules.region_mesh(1, devices=jax.devices()[:1])
    seq = grid.condition_region_sequential(cfg, reg, _SPEC)
    shd = grid.condition_region_sharded(cfg, reg, _SPEC, mesh)
    _assert_bitwise(seq, shd)
    _tree_equal(seq.state, shd.state)


def test_region_sharded_validates_mesh():
    cfg = _cfg()
    reg = _small_region(n_campuses=2, duration_s=20.0)
    no_campus = rules.make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="campus"):
        grid.condition_region_sharded(cfg, reg, _SPEC, no_campus)


_PARITY_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8")
import jax
import numpy as np
from repro.core import compliance, fleet, grid, pdu
from repro.sharding import rules

assert len(jax.devices()) == 8
hz = 50.0
cfg = pdu.make_pdu(sample_dt=1.0 / hz)
spec = compliance.GridSpec.create()
reg = grid.synchronized_region(
    n_campuses=4, n_racks=4, duration_s=40.0, sample_hz=hz)
mesh = rules.region_mesh(4)  # (campus=4, data=2) over 8 forced devices
seq = grid.condition_region_sequential(cfg, reg, spec)
shd = grid.condition_region_sharded(cfg, reg, spec, mesh)
for f in ("campus_rack", "campus_grid", "soc_mean", "ess_online_frac",
          "health_trace", "poi_rack", "poi_grid", "poi_freq_dev",
          "poi_volt_dev", "max_qp_residual"):
    a, b = getattr(seq, f), getattr(shd, f)
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb), err_msg=f)
for la, lb in zip(jax.tree_util.tree_leaves(seq.state),
                  jax.tree_util.tree_leaves(shd.state)):
    np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
np.testing.assert_array_equal(np.asarray(seq.report_poi.mode_mags),
                              np.asarray(shd.report_poi.mode_mags))
assert bool(seq.report_poi.modes_ok) == bool(shd.report_poi.modes_ok)
print("PARITY-OK")
"""


@pytest.mark.slow
def test_sharded_region_matches_sequential_on_8_devices():
    # jax pins the device count at backend init, so the 8-device half of
    # the bitwise contract needs a fresh process.
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    out = subprocess.run(
        [sys.executable, "-c", _PARITY_SCRIPT], env=env,
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "PARITY-OK" in out.stdout


# ----------------------------------------------------------------- service


@pytest.mark.service
def test_service_runs_grid_region(tmp_path):
    from repro.serve import conditioner as SRV

    cfg = _cfg()
    reg = _small_region(n_campuses=3, n_racks=4, duration_s=60.0)
    svc = SRV.ConditionerService(
        cfg, reg, _SPEC, chunk_intervals=4,
        audit_path=tmp_path / "audit.jsonl")
    assert svc.n_racks == 12
    svc.advance()
    # Global rack index 5 lives in campus 1 (racks 4-7) as local rack 1.
    svc.inject_fault([5])
    assert float(np.asarray(svc.state[1].ess_online)[1]) == 0.0
    st = svc.status()
    assert st["manual_offline_racks"] == [5]
    assert st["region"]["campus_racks"] == [4, 4, 4]
    assert {"peak_power_pu", "max_freq_dev_hz", "mode_bands"} <= set(
        st["poi"])
    assert len(st["campuses"]) == 3
    svc.clear_fault([5])

    ck = svc.checkpoint(tmp_path / "ck")
    r_live = svc.advance()
    svc2 = SRV.ConditionerService(cfg, reg, _SPEC, chunk_intervals=4)
    svc2.restore(ck)
    r_resumed = svc2.advance()
    for f in ("poi_grid", "campus_rack", "poi_freq_dev"):
        np.testing.assert_array_equal(
            np.asarray(getattr(r_live, f)), np.asarray(getattr(r_resumed, f)))
    _tree_equal(svc.state, svc2.state)

    while not svc.exhausted:
        svc.advance()
    events = {e["event"] for e in svc.audit.tail(10_000)}
    # Synchronized checkpoint campuses ring the inter-area band; the
    # violation must land in the audit log as a first-class event.
    assert "mode_band_violation" in events
    mv = [e for e in svc.audit.tail(10_000)
          if e["event"] == "mode_band_violation"]
    assert all(e["band"] == "inter_area" for e in mv)
    assert all(e["magnitude"] > e["threshold"] for e in mv)
