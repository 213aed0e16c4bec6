"""Fleet aggregation tests (paper Appendix D, Fig. 13)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compliance, fleet, pdu
from repro.power import trace


def test_synchronous_spectrum_scales_linearly():
    """Eq. 20: per-unit spectrum of N lockstep racks equals one rack's."""
    sp = trace.TestbenchSpec(duration_s=60.0, sample_hz=200.0)
    t1, dt = trace.testbench_trace(sp, None)
    fleet_traces = jnp.tile(t1[:, None], (1, 4))
    campus = jnp.mean(fleet_traces, axis=1)
    f1, s1 = compliance.normalized_spectrum(t1, dt)
    f2, s2 = compliance.normalized_spectrum(campus, dt)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-6)


def test_staggered_fleet_shapes_and_offsets():
    t1, dt = trace.testbench_trace(trace.TestbenchSpec(duration_s=30.0, sample_hz=100.0), None)
    traces = fleet.staggered_fleet(t1, 8, jax.random.key(0), max_offset_samples=50)
    assert traces.shape == (t1.shape[0], 8)


def test_staggering_reduces_campus_swing():
    """Desynchronized racks partially cancel — aggregate swing shrinks."""
    sp = trace.TestbenchSpec(duration_s=88.0, sample_hz=100.0, noise_std=0.0)
    t1, dt = trace.testbench_trace(sp, None)
    sync = fleet.staggered_fleet(t1, 16, jax.random.key(1), max_offset_samples=0)
    desync = fleet.staggered_fleet(t1, 16, jax.random.key(1), max_offset_samples=2200)
    swing = lambda x: float(jnp.ptp(jnp.mean(x, axis=1)))
    assert swing(desync) < swing(sync)


def test_fleet_conditioning_composes(tmp_path):
    """Per-rack EasyRider conditioning makes the campus compliant
    (the paper's composition argument)."""
    sp = trace.TestbenchSpec(duration_s=66.0, sample_hz=250.0)
    t1, dt = trace.testbench_trace(sp, jax.random.key(2))
    traces = fleet.staggered_fleet(t1, 4, jax.random.key(3), max_offset_samples=500,
                                   scale_jitter=0.05)
    traces = jnp.clip(traces, 0.0, 1.0)
    cfg = pdu.make_pdu(sample_dt=dt)
    spec = compliance.GridSpec.create()
    res = fleet.condition(traces, cfg, spec, engine="oneshot", qp_iters=15)
    assert not bool(res.report_rack.ramp_ok)
    assert bool(res.report_grid.ramp_ok)
    assert bool(res.report_grid.ok)


def test_rack_failure_mid_trace():
    """Fig. 13: a fault drops rack power near-instantly; conditioned campus
    ramp stays within beta even though the failure is unannounced."""
    sp = trace.TestbenchSpec(duration_s=66.0, sample_hz=250.0, noise_std=0.0)
    t1, dt = trace.testbench_trace(sp, None)
    traces = jnp.tile(t1[:, None], (1, 3))
    fails = jnp.asarray([-1, 8000, -1])
    traces = fleet.apply_failures(traces, fails, p_idle=0.02)
    cfg = pdu.make_pdu(sample_dt=dt)
    spec = compliance.GridSpec.create()
    res = fleet.condition(traces, cfg, spec, engine="oneshot", qp_iters=15)
    assert bool(res.report_grid.ramp_ok)
    # the failed rack's own conditioned trace tapers instead of stepping:
    failed_grid = np.asarray(res.grid_traces[:, 1])
    assert float(np.max(np.abs(np.diff(failed_grid)))) / dt <= 0.1 + 1e-4
