"""Health-aware telemetry spine (ISSUE 5): online battery aging, cycle
counting, and streaming compliance.

Three contracts pinned here:

  * The scan-carried half-cycle counter matches a NumPy turning-point
    (rainflow-equivalent) reference on synthetic traces, and the whole
    ``HealthState`` is bit-identical under any chunking of the SoC stream
    — through raw ``health.update`` folds, chunked ``pdu.condition``
    calls, and both fleet engines (incl. ragged tails and resume).
  * The streaming compliance observers reproduce the whole-trace oracles:
    the cross-chunk ramp observer equals ``max_abs_ramp`` bit-for-bit
    (including a worst-case step placed exactly on a chunk boundary — the
    regression the per-chunk ``jnp.diff`` blind spot would miss), and the
    Goertzel bank matches ``normalized_spectrum`` at every monitored line
    to <= 1e-5.
  * The health-aware outer loop (``wear_gain``) is bit-identical to the
    wear-blind policy at gain 0 and shrinks storage-mode excursions as
    cycle damage grows.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compliance, controller as ctrl, ess, fleet, health as H, pdu
from repro.power import scenario as SC

_HZ = 200.0
_SPEC = compliance.GridSpec.create()


# ------------------------------------------------- NumPy reference rainflow


def ref_half_cycles(soc, init, eps=0.0, g=0.6, soc_ref=0.5, kappa=2.0):
    """Turning-point half-cycle extraction (rainflow-equivalent on
    monotone-segment waves): every direction reversal closes a half cycle
    spanning the previous and current extremum.  Mirrors the documented
    ``health.update`` semantics but written as plain Python over floats."""
    prev, ext, d = float(init), float(init), 0.0
    hc, dmg, maxd, depths = 0, 0.0, 0.0, []
    for cur in np.asarray(soc, np.float64):
        delta = cur - prev
        sd = 1.0 if delta > eps else (-1.0 if delta < -eps else 0.0)
        if sd * d < 0.0:
            depth = abs(prev - ext)
            mid = 0.5 * (prev + ext)
            w = max(1.0 + g * (mid - soc_ref), 0.0)
            dmg += 0.5 * w * depth**kappa
            hc += 1
            maxd = max(maxd, depth)
            depths.append(depth)
            ext = prev
        if sd != 0.0:
            d = sd
        prev = cur
    return hc, dmg, maxd, depths


def _fold(p, soc, init, splits=None):
    st = H.init_state(jnp.asarray(init, jnp.float32))
    bounds = [0] + list(splits or []) + [len(soc)]
    for a, b in zip(bounds[:-1], bounds[1:]):
        if b > a:
            st = H.update(p, st, jnp.asarray(soc[a:b], jnp.float32), 1.0 / _HZ)
    return st


def _sawtooth(n=4000, periods=10, lo=0.35, hi=0.65):
    t = np.arange(n) * (2.0 * periods / n)  # triangle period = 2.0 in t
    return (lo + (hi - lo) * np.abs((t % 2.0) - 1.0)).astype(np.float32)


def _iteration_wave(n=4000, period=137):
    # square-ish compute/communicate wave with ramped edges, like a
    # training iteration's power cycle integrated into SoC
    t = np.arange(n)
    tri = np.abs(((t / period) % 2.0) - 1.0)
    return (0.45 + 0.1 * np.clip(2.0 * tri - 0.5, 0.0, 1.0)).astype(np.float32)


def _mixed_walk(n=4000, seed=0):
    rng = np.random.default_rng(seed)
    steps = rng.normal(0.0, 2e-4, n) + 3e-4 * np.sin(np.arange(n) / 60.0)
    soc = 0.5 + np.cumsum(steps)
    # plateaus: zero-delta runs must not close cycles
    soc[1200:1300] = soc[1200]
    return np.clip(soc, 0.1, 0.9).astype(np.float32)


@pytest.mark.parametrize(
    "trace", [_sawtooth(), _iteration_wave(), _mixed_walk()],
    ids=["sawtooth", "iteration_wave", "mixed"],
)
def test_half_cycles_match_numpy_reference(trace):
    p = H.HealthParams.create()
    st = _fold(p, trace, trace[0])
    hc, dmg, maxd, _ = ref_half_cycles(trace, trace[0])
    assert int(st.half_cycles) == hc
    np.testing.assert_allclose(float(st.cycle_damage), dmg, rtol=1e-4, atol=1e-9)
    np.testing.assert_allclose(float(st.max_dod), maxd, rtol=1e-5, atol=1e-7)


def test_sawtooth_counts_are_the_analytic_rainflow():
    """10 triangle periods = 20 monotone segments: 19 closed half cycles at
    full range (the final segment stays open) + the initial half segment."""
    tr = _sawtooth(n=4000, periods=10)
    st = _fold(H.HealthParams.create(), tr, tr[0])
    assert int(st.half_cycles) == 19
    np.testing.assert_allclose(float(st.max_dod), 0.3, atol=1e-3)
    # EFC: total |dSoC|/2 = 10 periods * 2*0.3 swing / 2 (the sampled
    # triangle misses the exact peaks by up to one sample step)
    np.testing.assert_allclose(
        float(H.equivalent_full_cycles(st)), 3.0, rtol=1e-3
    )


_SCAN_LEAVES = (  # carried sample-by-sample: bitwise under ANY split
    "prev_soc", "last_ext", "direction", "half_cycles", "cycle_damage",
    "max_dod", "samples",
)


def test_update_split_invariance():
    """Scan-carried leaves are bitwise under any split; the block-reduction
    leaves (charge/discharge/SoC sums) are bitwise whenever the blocks
    match — the engines always fold one controller interval per block —
    and agree to float tolerance under any other split."""
    p = H.HealthParams.create()
    tr = _mixed_walk(seed=3)
    whole = _fold(p, tr, 0.5)
    for splits in ([1], [7, 13, 14, 1999], list(range(100, 4000, 100))):
        parts = _fold(p, tr, 0.5, splits=splits)
        for name, a, b in zip(whole._fields, whole, parts):
            if name in _SCAN_LEAVES:
                assert np.array_equal(np.asarray(a), np.asarray(b)), name
            else:
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7,
                    err_msg=name,
                )
    # identical blocks => identical bits, reduction leaves included
    a = _fold(p, tr, 0.5, splits=[1000, 2000, 3000])
    b = _fold(p, tr, 0.5, splits=[1000, 2000, 3000])
    for name, x, y in zip(a._fields, a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y)), name


def test_update_batched_matches_per_rack():
    p = H.HealthParams.create()
    tr = np.stack([_sawtooth(), _iteration_wave(), _mixed_walk()], axis=1)
    st = H.init_state(jnp.asarray(tr[0]))
    st = H.update(p, st, jnp.asarray(tr), 1.0 / _HZ)
    for r in range(tr.shape[1]):
        single = _fold(p, tr[:, r], tr[0, r])
        for name, a, b in zip(st._fields, st, single):
            if name in _SCAN_LEAVES:
                np.testing.assert_array_equal(
                    np.asarray(a)[r], np.asarray(b), err_msg=f"{name} rack {r}"
                )
            else:  # block reductions: order differs with the batch shape
                np.testing.assert_allclose(
                    np.asarray(a)[r], np.asarray(b), rtol=1e-6,
                    err_msg=f"{name} rack {r}",
                )


def test_battery_power_from_soc_delta_roundtrip():
    ep = ess.ESSParams.create()
    dt = 5e-3
    power = jnp.asarray([-0.8, -1e-4, 0.0, 3e-4, 0.9], jnp.float32)
    d_soc = ess.soc_increment(ep, power, dt)
    back = ess.battery_power_from_soc_delta(ep, d_soc, dt)
    np.testing.assert_allclose(np.asarray(back), np.asarray(power), rtol=1e-5, atol=1e-9)


def test_report_derivations():
    p = H.HealthParams.create()
    ep = ess.ESSParams.create()
    tr = _sawtooth()
    st = _fold(p, tr, tr[0])
    rep = H.report(p, ep, st, 1.0 / _HZ)
    assert float(rep.elapsed_s) == pytest.approx(4000 / _HZ)
    assert float(rep.mean_soc) == pytest.approx(float(np.mean(tr)), rel=1e-4)
    assert float(rep.soc_std) == pytest.approx(float(np.std(tr)), rel=1e-3)
    assert float(rep.capacity_fade) > 0.0
    assert np.isfinite(float(rep.projected_life_s))
    # zero-history state: no damage, infinite projected life
    rep0 = H.report(p, ep, H.init_state(0.5), 1.0 / _HZ)
    assert float(rep0.capacity_fade) == 0.0
    assert np.isposinf(float(rep0.projected_life_s))


# ------------------------------------------------ health through the engines


def _campus(n_racks=4, duration_s=44.0):
    return SC.mixed_campus(
        n_racks,
        ("llama3_2_1b", "whisper_large_v3"),
        duration_s=duration_s,
        sample_hz=_HZ,
        seed=2,
        fault_at_s=duration_s * 0.6,
        noise_seed=7,
    )


def _cfg(**kw):
    kw.setdefault("track_health", True)
    return pdu.make_pdu(sample_dt=1.0 / _HZ, **kw)


def _scanned(cfg, s, *, chunk_intervals=16, state=None, start_sample=0,
             stop_sample=None, **kw):
    return fleet.condition(
        s, cfg, _SPEC, stream=fleet.StreamOptions(
            chunk_intervals=chunk_intervals, state=state,
            start_sample=start_sample, stop_sample=stop_sample), **kw)


def _assert_health_equal(ha, hb, what=""):
    for name, a, b in zip(ha._fields, ha, hb):
        assert np.array_equal(np.asarray(a), np.asarray(b)), f"{what}{name}"


def test_condition_chunked_equals_one_shot_health():
    """pdu-level: conditioning in interval-aligned chunks folds the same
    HealthState bit-for-bit as one whole-trace call."""
    cfg = _cfg()
    k = int(round(float(cfg.controller.dt) * _HZ))
    tr = SC.render(_campus(3), 0, 6 * k)
    st = pdu.init_state(cfg, tr[0])
    _, whole, _ = pdu.condition(cfg, st, tr, qp_iters=10)
    st2 = pdu.init_state(cfg, tr[0])
    for a in range(0, 6 * k, 2 * k):
        _, st2, _ = pdu.condition(cfg, st2, tr[a : a + 2 * k], qp_iters=10)
    _assert_health_equal(whole.health, st2.health)


@pytest.mark.parametrize("duration_s", [44.0, 32.5])
def test_engines_agree_on_health(duration_s):
    """scanned == one-shot for every health accumulator, bitwise —
    including a ragged tail shorter than one controller interval (32.5 s
    against k = 1000 chunks)."""
    s = _campus(4, duration_s)
    cfg = _cfg()
    a = _scanned(cfg, s, qp_iters=10, chunk_intervals=2)
    full = SC.render(s, 0, s.total_samples)
    st0 = pdu.init_state(cfg, full[0])
    _, st_f, _ = pdu.condition(cfg, st0, full, qp_iters=10)
    _assert_health_equal(a.state.health, st_f.health, "scanned vs one-shot: ")
    # derived fade agrees too (pure function of bitwise-equal accumulators)
    np.testing.assert_allclose(
        np.asarray(a.health.capacity_fade),
        np.asarray(H.report(cfg.health, cfg.ess_params, st_f.health,
                            cfg.sample_dt).capacity_fade),
        atol=1e-5,
    )


def test_health_is_chunk_size_invariant_and_resume_safe():
    s = _campus(4)
    cfg = _cfg()
    a = _scanned(cfg, s, qp_iters=10, chunk_intervals=2)
    b = _scanned(cfg, s, qp_iters=10, chunk_intervals=4)
    _assert_health_equal(a.state.health, b.state.health, "chunk size: ")
    k = int(round(float(cfg.controller.dt) * _HZ))
    first = _scanned(
        cfg, s, qp_iters=10, chunk_intervals=2, stop_sample=4 * k
    )
    rest = _scanned(
        cfg, s, qp_iters=10, chunk_intervals=2,
        state=first.state, start_sample=4 * k,
    )
    _assert_health_equal(a.state.health, rest.state.health, "resume: ")


@pytest.mark.pallas
def test_megakernel_fold_matches_hybrid_update_bitwise():
    """The interval-resident Pallas megakernel (interpret mode) folds the
    exact HealthState the shipped hybrid path produces — ``pdu_sim`` +
    ``update_consts`` per interval — bit for bit, including across an
    interval-aligned resume split."""
    from repro.kernels import ops, ref

    cfg = _cfg()
    k = int(round(float(cfg.controller.dt) * _HZ))
    tr = SC.render(_campus(4), 0, 2 * k)
    st = pdu.init_state(cfg, tr[0])
    ep = cfg.ess_params
    filt = st.filter_obj
    kw = dict(
        beta=float(ep.beta), dt=1.0 / _HZ, q_max=float(ep.q_max),
        eta_c=float(ep.eta_c), eta_d=float(ep.eta_d), p_max=float(ep.p_max),
        soc_min=float(ep.soc_safe_min), soc_max=float(ep.soc_safe_max),
    )
    hc = H.step_consts(cfg.health)
    zero = jnp.zeros_like(st.ess_state.g_filter)

    def hybrid(chunk, g0, soc0, x0, hstate):
        _, soc_t, fin = ref.pdu_sim(
            chunk, g0, soc0, x0, filt.ad, filt.bd, filt.c[0],
            corrective=jnp.zeros_like(chunk), **kw
        )
        return fin, H.update_consts(hc, H.HealthState(*hstate), soc_t)

    def kernel(chunk, g0, soc0, x0, hstate):
        _, _, fin, h2 = ops.pdu_health_sim(
            chunk, g0, soc0, x0, filt.ad, filt.bd, filt.c[0],
            corrective=0.0, health=(hc, tuple(hstate)), force="pallas", **kw
        )
        return fin, h2

    for fold in (hybrid, kernel):
        g0, soc0, x0, hs = st.ess_state.g_filter, st.ess_state.soc, st.filter_state, st.health
        for a in range(0, 2 * k, k):  # one controller interval per block
            (g0, soc0, x0), hs = fold(tr[a : a + k], g0, soc0, x0, hs)
        if fold is hybrid:
            want = H.HealthState(*hs)
        else:
            _assert_health_equal(want, H.HealthState(*hs), "megakernel vs hybrid: ")


def test_health_trace_monotone_and_disabled_is_zero():
    s = _campus(3)
    res = _scanned(_cfg(), s, qp_iters=10, chunk_intervals=2)
    ht = np.asarray(res.health_trace)
    assert ht.shape[1] == 3
    # accumulators only grow chunk over chunk
    assert np.all(np.diff(ht[:, 0]) >= 0)  # mean EFC
    assert np.all(np.diff(ht[:, 1]) >= 0)  # max fade
    assert float(ht[-1, 0]) > 0
    off = _scanned(
        pdu.make_pdu(sample_dt=1.0 / _HZ), s, qp_iters=10, chunk_intervals=2
    )
    assert np.all(np.asarray(off.health_trace) == 0.0)
    assert float(np.max(np.asarray(off.health.capacity_fade))) == 0.0


# -------------------------------------------------- health-aware outer loop


def test_wear_gain_zero_is_bit_identical():
    cfg = ctrl.ControllerConfig.create()
    es = ess.ESSParams.create()
    idle = jnp.asarray(1e6)
    t0 = ctrl.select_target(cfg, es, idle, 0.0)
    t1 = ctrl.select_target(cfg, es, idle, 0.73)  # wear ignored at gain 0
    np.testing.assert_array_equal(np.asarray(t0), np.asarray(t1))


def test_wear_gain_narrows_storage_excursion():
    cfg = ctrl.ControllerConfig.create(
        s_idle=0.1, delta_s_max=0.15, wear_gain=1.0
    )
    es = ess.ESSParams.create()
    idle = jnp.asarray(1e6)
    fresh = float(ctrl.select_target(cfg, es, idle, 0.0))
    worn = float(ctrl.select_target(cfg, es, idle, 0.5))
    dead = float(ctrl.select_target(cfg, es, idle, 1.0))
    assert fresh == pytest.approx(0.35)  # s_mid - delta_s_max
    assert worn == pytest.approx(0.425)  # excursion halved
    assert dead == pytest.approx(0.5)  # no excursion left -> stays at S_mid
    # negative gain widens instead (calendar-dominated installs)
    cfg_w = ctrl.ControllerConfig.create(
        s_idle=0.1, delta_s_max=0.15, wear_gain=-1.0
    )
    wider = float(ctrl.select_target(cfg_w, es, idle, 0.5))
    assert wider == pytest.approx(0.275)
    # per-rack wear vector -> per-rack targets
    t = ctrl.select_target(cfg, es, idle, jnp.asarray([0.0, 0.5, 1.0]))
    np.testing.assert_allclose(np.asarray(t), [0.35, 0.425, 0.5], atol=1e-6)


# ------------------------------------------------------ streaming compliance


def test_ramp_observer_matches_whole_trace_bitwise():
    rng = np.random.default_rng(0)
    tr = rng.uniform(0.2, 1.0, 5000).astype(np.float32)
    dt = 1.0 / _HZ
    whole = compliance.max_abs_ramp(jnp.asarray(tr), dt)
    obs = compliance.ramp_observer_init()
    for a in (0, 700, 701, 2500, 4999):
        b = {0: 700, 700: 701, 701: 2500, 2500: 4999, 4999: 5000}[a]
        obs = compliance.ramp_observer_update(obs, jnp.asarray(tr[a:b]), dt)
    assert np.asarray(obs.max_ramp) == np.asarray(whole)
    assert int(obs.n) == 5000


def test_boundary_step_is_not_dropped():
    """Regression (ISSUE 5 satellite): the worst-case step placed EXACTLY on
    a chunk boundary.  A per-chunk ``jnp.diff`` never sees it; the observer
    must."""
    dt = 1.0 / _HZ
    chunk = 1000
    tr = np.full(4000, 0.2, np.float32)
    tr[2 * chunk :] = 1.0  # step between sample 1999 and 2000: a boundary
    chunks = [jnp.asarray(tr[a : a + chunk]) for a in range(0, 4000, chunk)]
    naive = max(float(jnp.max(jnp.abs(jnp.diff(c)))) / dt for c in chunks)
    assert naive == 0.0  # the blind spot: each chunk is flat
    obs = compliance.ramp_observer_init()
    for c in chunks:
        obs = compliance.ramp_observer_update(obs, c, dt)
    expected = float(compliance.max_abs_ramp(jnp.asarray(tr), dt))
    assert float(obs.max_ramp) == expected > 100.0


def test_streaming_engine_sees_boundary_step():
    """End-to-end: a raw campus step landing exactly on the streaming
    chunk boundary shows up in the engine's rack-side report."""
    cfg = pdu.make_pdu(sample_dt=1.0 / _HZ)
    k = int(round(float(cfg.controller.dt) * _HZ))
    chunk = 2 * k  # chunk_intervals=2
    # two racks at 0.3 pu for one chunk, then 0.9: the step sits exactly
    # at the chunk boundary (no edge smoothing, no noise)
    s = SC.from_phase_timeline(
        [chunk / _HZ, chunk / _HZ], [[0.3, 0.9], [0.3, 0.9]], _HZ,
        edge_time_s=0.0)
    tr = np.asarray(SC.render(s, 0, s.total_samples))
    assert s.total_samples == 2 * chunk
    assert np.all(tr[:chunk] == np.float32(0.3))
    assert np.all(tr[chunk:] == np.float32(0.9))
    res = _scanned(cfg, s, qp_iters=5, chunk_intervals=2)
    expected = float(compliance.max_abs_ramp(jnp.mean(jnp.asarray(tr), axis=1), 1.0 / _HZ))
    assert float(res.report_rack.max_ramp) == pytest.approx(expected)
    assert not bool(res.report_rack.ramp_ok)


@pytest.mark.parametrize("chunk", [997, 4000])
def test_goertzel_bank_matches_normalized_spectrum(chunk):
    """Chunk-folded Goertzel == whole-trace windowed FFT at every monitored
    line, <= 1e-5 (the streaming spectral-compliance contract)."""
    sp_mod = __import__("repro.power.trace", fromlist=["trace"])
    rack, dt = sp_mod.testbench_trace(
        sp_mod.TestbenchSpec(duration_s=60.0, sample_hz=_HZ), jax.random.key(0)
    )
    tr = np.asarray(rack)
    n = tr.shape[0]
    bank = compliance.make_bank(n, dt, float(_SPEC.f_c))
    obs = compliance.spectrum_observer_init(bank)
    for a in range(0, n, chunk):
        obs = compliance.spectrum_observer_update(bank, obs, jnp.asarray(tr[a : a + chunk]))
    freqs, s_obs = compliance.spectrum_observer_finalize(bank, obs)
    _, s_fft = compliance.normalized_spectrum(jnp.asarray(tr), dt)
    ref = np.asarray(s_fft)[np.asarray(bank.bins)]
    np.testing.assert_allclose(np.asarray(s_obs), ref, atol=1e-5)
    assert np.all(freqs >= float(_SPEC.f_c) - 1e-9)


def test_goertzel_rect_window_online_mode():
    """Open-ended (total length unknown) banks: rectangular window, lines
    snapped to the test trace's bins for an exact FFT comparison."""
    rng = np.random.default_rng(1)
    n = 1 << 13
    dt = 1.0 / _HZ
    tr = (0.5 + 0.1 * rng.standard_normal(n)).astype(np.float32)
    bank = compliance.make_online_bank(dt, 2.0, modulus=n)
    obs = compliance.spectrum_observer_init(bank)
    for a in range(0, n, 600):
        obs = compliance.spectrum_observer_update(bank, obs, jnp.asarray(tr[a : a + 600]))
    _, s_obs = compliance.spectrum_observer_finalize(bank, obs)
    _, s_fft = compliance.normalized_spectrum(jnp.asarray(tr), dt, window=None)
    ref = np.asarray(s_fft)[np.asarray(bank.bins)]
    np.testing.assert_allclose(np.asarray(s_obs), ref, atol=1e-5)


def test_streaming_report_matches_whole_trace_compliance():
    """The mixed-campus acceptance check at test scale: the scanned
    engine's observer-built report reproduces the whole-trace oracle —
    ramp exactly, spectral lines <= 1e-5."""
    s = _campus(4)
    cfg = _cfg()
    res = _scanned(cfg, s, qp_iters=10, chunk_intervals=2)
    camp = np.asarray(res.campus_grid)
    assert float(res.report_grid.max_ramp) == float(
        compliance.max_abs_ramp(jnp.asarray(camp), 1.0 / _HZ)
    )
    bank = compliance.make_bank(len(camp), 1.0 / _HZ, float(_SPEC.f_c))
    _, s_fft = compliance.normalized_spectrum(jnp.asarray(camp), 1.0 / _HZ)
    worst_lines = float(np.max(np.asarray(s_fft)[np.asarray(bank.bins)]))
    assert float(res.report_grid.worst_high_freq_mag) == pytest.approx(
        worst_lines, abs=1e-5
    )


def test_powersim_reports_health_and_boundary_ramp():
    from repro.power.integration import PowerSim, PowerSimConfig
    from repro.power import phases

    cost = phases.StepCost(flops=5e17, hbm_bytes=2e14, collective_bytes=5e13)
    sim = PowerSim(
        cost, phases.HardwareConstants(),
        phases.PhaseModel(checkpoint_every_steps=0),
        PowerSimConfig(),
    )
    k = sim._k
    lo = jnp.full((k,), 0.3, jnp.float32)
    hi = jnp.full((k,), 0.9, jnp.float32)
    sim._condition(lo, 1.0 / _HZ)
    sim._condition(hi, 1.0 / _HZ)  # step exactly at the conditioned-chunk seam
    rep = sim.report()
    expected = 0.6 * _HZ
    assert rep["rack_max_ramp"] == pytest.approx(expected, rel=1e-5)
    assert rep["battery_efc"] >= 0.0
    assert 0.0 <= rep["battery_capacity_fade"] < 1.0
    assert rep["battery_projected_life_years"] > 0.0


# ------------------------------------------------------------- bench gating


def test_bench_gate_records():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.run import gate_records

    baseline = {"a": 100.0, "quick:a": 10.0, "b": 200.0}
    # pass: within threshold; new bench without baseline is skipped
    assert gate_records({"a": 110.0, "c": 999.0}, baseline, 25.0, quick=False) == []
    # fail: >25% regression, reported with the offending numbers
    fails = gate_records({"a": 140.0}, baseline, 25.0, quick=False)
    assert len(fails) == 1 and "a:" in fails[0]
    # quick mode compares against the quick: namespace
    assert gate_records({"a": 11.0}, baseline, 25.0, quick=True) == []
    assert len(gate_records({"a": 14.0}, baseline, 25.0, quick=True)) == 1
