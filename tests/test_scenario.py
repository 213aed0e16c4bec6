"""Scenario-engine tests: legacy golden equivalence, chunk bit-identity,
model-derived workloads, and the heterogeneous mixed campus."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compliance, fleet, pdu
from repro.power import phases, scenario as SC, trace


SPECS = {
    "default": trace.TestbenchSpec(duration_s=66.0, sample_hz=500.0),
    "choukse": trace.choukse_spec(),
    "titanx": trace.titanx_spec(),
    "cluster_fault": trace.cluster_fault_spec(),
}


# ------------------------------------------------------ golden: legacy parity


@pytest.mark.parametrize("name", sorted(SPECS))
def test_render_matches_legacy_testbench(name):
    """The scenario-wrapped testbenches must reproduce the legacy host-side
    implementation to float32 tolerance (diff = summation order of the edge
    boxcar only)."""
    spec = SPECS[name]
    got, dt_g = trace.testbench_trace(spec, None)
    want, dt_w = trace.testbench_trace_reference(spec, None)
    assert dt_g == dt_w
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_render_matches_legacy_with_noise_key():
    """The wrapper keeps the legacy whole-trace noise draw bit-compatible."""
    spec = trace.choukse_spec()
    got, _ = trace.testbench_trace(spec, jax.random.key(0))
    want, _ = trace.testbench_trace_reference(spec, jax.random.key(0))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_phase_timeline_matches_legacy():
    durs = np.array([0.5, 0.25, 1.0, 0.125])
    pows = np.array([1.0, 0.3, 0.9, 0.1], np.float32)
    got, _ = trace.phase_timeline_trace(durs, pows, 200.0, edge_time_s=0.1)
    want, _ = trace.phase_timeline_trace_reference(durs, pows, 200.0, edge_time_s=0.1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_training_timeline_matches_legacy_loop():
    """The vectorized timeline compiler must equal the original O(n_steps)
    Python-list construction exactly."""
    hw = phases.HardwareConstants(chips=8)
    cost = phases.StepCost(flops=1e15, hbm_bytes=1e12, collective_bytes=1e11)
    model = phases.PhaseModel(checkpoint_every_steps=5, checkpoint_stall_s=2.0)

    def legacy(n_steps, warmup_s, warmup_levels, end_idle_s):
        d = model.device
        p_idle = d.p_idle_w / d.p_peak_w
        durs, pows = [], []
        step_d, step_p = phases.step_phases(cost, hw, model)
        p_avg = float(np.sum(step_d * step_p) / np.sum(step_d))
        for i in range(warmup_levels):
            durs.append(warmup_s / warmup_levels)
            pows.append(p_idle + (p_avg - p_idle) * (i + 1) / warmup_levels)
        for s in range(n_steps):
            durs.extend(step_d.tolist())
            pows.extend(step_p.tolist())
            if model.checkpoint_every_steps and (s + 1) % model.checkpoint_every_steps == 0:
                durs.append(model.checkpoint_stall_s)
                pows.append(p_idle)
        durs.append(end_idle_s)
        pows.append(p_idle)
        return np.asarray(durs), np.asarray(pows, np.float32)

    for n_steps in (1, 5, 10, 17):
        d1, p1 = phases.training_timeline(cost, hw, model, n_steps,
                                          warmup_s=1.0, warmup_levels=3)
        d2, p2 = legacy(n_steps, 1.0, 3, 10.0)
        np.testing.assert_allclose(d1, d2, rtol=1e-12)
        np.testing.assert_allclose(p1, p2, rtol=1e-6)


def test_training_scenario_renders_like_phase_timeline():
    hw = phases.HardwareConstants(chips=8)
    cost = phases.StepCost(flops=1e15, hbm_bytes=1e12, collective_bytes=1e11)
    model = phases.PhaseModel(checkpoint_every_steps=4, checkpoint_stall_s=2.0)
    s = phases.training_scenario(cost, hw, model, 8, sample_hz=100.0)
    got, dt = SC.render_trace(s)
    durs, pows = phases.training_timeline(cost, hw, model, 8)
    want, _ = trace.phase_timeline_trace_reference(durs, pows, 100.0)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# ------------------------------------------------------- chunk bit-identity


@pytest.mark.parametrize("chunk_n", [257, 1999])
def test_chunked_render_bit_identical_parametric(chunk_n):
    """Chunked rendering concatenated == whole-trace rendering, bit-for-bit
    (including counter-based noise)."""
    s = trace.scenario_from_testbench(trace.titanx_spec(), noise_seed=3)
    whole = SC.render(s, 0, s.total_samples)
    parts = [
        SC.render(s, t0, min(chunk_n, s.total_samples - t0))
        for t0 in range(0, s.total_samples, chunk_n)
    ]
    assert bool(jnp.all(jnp.concatenate(parts) == whole))


def test_chunked_render_bit_identical_segments():
    durs = np.array([0.5, 0.25, 1.0, 0.125, 2.0])
    pows = np.array([1.0, 0.3, 0.9, 0.1, 0.8], np.float32)
    s = SC.from_phase_timeline(durs, pows, 400.0, edge_time_s=0.1, noise_seed=7)
    whole = SC.render(s, 0, s.total_samples)
    parts = [
        SC.render(s, t0, min(301, s.total_samples - t0))
        for t0 in range(0, s.total_samples, 301)
    ]
    assert bool(jnp.all(jnp.concatenate(parts) == whole))


def test_segment_noise_seed_is_not_a_noop():
    """Segment-table scenarios must honor noise_seed (regression: the noise
    std used to be forced to 0 whenever params was None)."""
    durs = np.array([0.5, 0.5])
    pows = np.array([0.9, 0.3], np.float32)
    quiet = SC.from_phase_timeline(durs, pows, 200.0, edge_time_s=0.0)
    noisy = SC.from_phase_timeline(durs, pows, 200.0, edge_time_s=0.0, noise_seed=7)
    a = SC.render(quiet, 0, quiet.total_samples)
    b = SC.render(noisy, 0, noisy.total_samples)
    assert float(jnp.max(jnp.abs(a - b))) > 1e-4
    assert float(jnp.std(b - a)) == pytest.approx(0.01, rel=0.2)


def test_never_dip_period_disables_dips():
    """dip_period_s=NEVER must fully disable dips (regression: mod(te, NEVER)
    == te used to fire a spurious dip for the first dip_duration_s)."""
    base = dict(warmup_s=0.0, noise_std=0.0, comm_fraction=0.0)
    no_dip = SC.make_scenario(
        SC.workload(dip_period_s=SC.NEVER, dip_duration_s=3.0, **base),
        duration_s=10.0, sample_hz=100.0, edge_time_s=0.0,
    )
    p = SC.render(no_dip, 0, no_dip.total_samples)
    # p_compute everywhere (sample 0 is the warmup ramp's t=0 idle point)
    assert float(jnp.min(p[1:])) == pytest.approx(0.92)


def test_batched_render_matches_per_rack_scalar_renders():
    """Heterogeneous fleets are just vmapped parameter pytrees: column r of
    the batched render equals the scalar render of rack r's params."""
    s = SC.mixed_campus(
        5, ("llama3_2_1b", "stablelm_12b"), duration_s=30.0, sample_hz=100.0, seed=1
    )
    batched = SC.render(s, 0, s.total_samples)
    assert batched.shape == (s.total_samples, 5)
    for r in (0, 3, 4):
        one = dataclasses.replace(
            s, params=jax.tree_util.tree_map(lambda x: x[r], s.params)
        )
        col = SC.render(one, 0, s.total_samples)
        assert bool(jnp.all(col == batched[:, r]))


# ------------------------------------------------- model-derived workloads


def test_workload_from_model_covers_all_archs():
    from repro.configs.registry import ARCH_IDS

    periods = {}
    for arch in ARCH_IDS:
        w = SC.workload_from_model(arch)
        period = float(w.iteration_period_s)
        assert 0.01 < period < 120.0
        assert 0.0 < float(w.comm_fraction) < 0.5
        assert float(w.p_comm) < float(w.p_compute) <= 1.0
        periods[arch] = round(period, 4)
    # the 10 assigned configs give genuinely heterogeneous workloads
    assert len(set(periods.values())) >= 8


def test_scenario_from_model_renders():
    s = SC.scenario_from_model("qwen1_5_4b", duration_s=30.0, sample_hz=100.0)
    p, dt = SC.render_trace(s)
    assert p.shape == (3000,)
    assert float(p.max()) > 0.9 and float(p.min()) < 0.5  # wave + warmup from idle


# ------------------------------------------------------------- mixed campus


def test_mixed_campus_structure():
    duration = 60.0
    s = SC.mixed_campus(
        12,
        ("llama3_2_1b", "deepseek_v3_671b"),
        duration_s=duration,
        sample_hz=100.0,
        seed=0,
        inference_fraction=0.25,
        stagger_s=20.0,
        fault_rack_fraction=0.25,
        fault_at_s=30.0,
        fault_duration_s=20.0,
    )
    p = np.asarray(SC.render(s, 0, s.total_samples))
    assert p.shape == (6000, 12)
    # staggered starts: racks are still idling at t=1s while others ramped
    starts = np.asarray(s.params.t_start_s)
    assert starts.std() > 1.0
    # fault cascade: faulted racks sit at ~p_fault inside their window
    fault_at = np.asarray(s.params.fault_at_s)
    faulted = np.where(fault_at < SC.NEVER / 2)[0]
    assert len(faulted) == 3
    for r in faulted:
        i = int((fault_at[r] + 1.0) * 100)
        assert p[i, r] == pytest.approx(float(s.params.p_fault[r]) * float(s.params.scale[r]), abs=1e-5)
    # cascade ripples: fault onsets differ across the faulted range
    assert fault_at[faulted].std() > 0.1
    # diurnal inference racks swing slowly: their envelope varies far more
    # over minutes than a training rack's mean power
    amp = np.asarray(s.params.diurnal_amp)
    inf_racks = np.where(amp > 0)[0]
    assert len(inf_racks) == 3


def test_mixed_campus_streams_end_to_end():
    """Acceptance path (scaled down): heterogeneous campus with staggered
    starts + fault cascade conditions through the scanned engine (chunks
    rendered on-device) and comes out grid-compliant."""
    hz = 200.0
    s = SC.mixed_campus(
        8,
        ("llama3_2_1b", "chatglm3_6b", "whisper_large_v3"),
        duration_s=60.0,
        sample_hz=hz,
        seed=2,
        fault_rack_fraction=0.25,
        fault_at_s=35.0,
        noise_seed=7,
    )
    cfg = pdu.make_pdu(sample_dt=1.0 / hz)
    spec = compliance.GridSpec.create()
    res = fleet.condition(s, cfg, spec, qp_iters=20, stream=dict(chunk_intervals=4))
    assert res.campus_grid.shape == (s.total_samples,)
    assert not bool(res.report_rack.ramp_ok)  # raw campus violates beta
    assert bool(res.report_grid.ramp_ok)  # conditioned campus complies
    assert bool(res.report_grid.ok)


def test_condition_scenario_streaming_checks_sample_rate():
    s = SC.scenario_from_model("llama3_2_1b", duration_s=10.0, sample_hz=100.0)
    cfg = pdu.make_pdu(sample_dt=1e-3)
    with pytest.raises(ValueError, match="sample rate"):
        fleet.condition(s, cfg, compliance.GridSpec.create())


# ------------------------------------------------------- PowerSim satellites


def test_powersim_config_device_is_a_real_field():
    """`device` was a shared class attribute (no annotation); it must be a
    proper per-instance dataclass field threaded into phase rendering."""
    from repro.power.device import TITAN_X
    from repro.power.integration import PowerSim, PowerSimConfig

    names = {f.name for f in dataclasses.fields(PowerSimConfig)}
    assert "device" in names
    c1 = PowerSimConfig(device=TITAN_X)
    c2 = PowerSimConfig()
    assert c1.device is TITAN_X and c2.device is None

    cost = phases.StepCost(flops=5e18, hbm_bytes=2e15, collective_bytes=5e14)
    sim = PowerSim(cost, phases.HardwareConstants(), phases.PhaseModel(), c1)
    assert sim.model.device is TITAN_X  # threaded into phase rendering


def test_powersim_consumes_scenario_chunks():
    cost = phases.StepCost(flops=5e17, hbm_bytes=2e14, collective_bytes=5e13)
    from repro.power.integration import PowerSim

    sim = PowerSim(cost, phases.HardwareConstants(), phases.PhaseModel(checkpoint_every_steps=0))
    for _ in range(6):
        sim.on_step()
    rep = sim.report()
    assert rep["grid_max_ramp"] <= 0.1 + 1e-3
    assert rep["rack_max_ramp"] > rep["grid_max_ramp"]
