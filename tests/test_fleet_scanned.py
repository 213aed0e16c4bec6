"""Engine-equivalence tests: the scanned engine vs the one-shot reference.

The scanned engine (`fleet.condition(scenario, ...)`) fuses on-device
chunk rendering and the chunk loop into one `lax.scan`-ned jit; the
one-shot engine (`engine="oneshot"`, or `pdu.condition` on the rendered
trace) conditions the whole trace in one call and is the reference.  A
one-campus grid region runs the same chunk scan (`fleet._scan_chunks`)
and must equal the campus engine bit for bit.

Tolerance contract: XLA CPU contracts mul+add chains into FMAs differently
depending on the fusion context, so quantities that pass through the LC
filter recurrence (`campus_grid`, filter / warm-QP state) may differ by a
few ulps between the engines' separately compiled programs.  Aggregates
that do not touch the filter chain (`campus_rack`, `soc_mean`) must match
bit-for-bit, and everything else must agree to ~1e-6 absolute.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compliance, fleet, pdu
from repro.power import scenario as SC

_ULP = 1e-6  # few-ulp FMA-contraction slack for filter-chain outputs
_SPEC = compliance.GridSpec.create()
_HZ = 200.0


def _campus(n_racks=6, duration_s=44.0, seed=2, noise_seed=7):
    return SC.mixed_campus(
        n_racks,
        ("llama3_2_1b", "whisper_large_v3"),
        duration_s=duration_s,
        sample_hz=_HZ,
        seed=seed,
        fault_at_s=duration_s * 0.6,
        noise_seed=noise_seed,
    )


def _cfg():
    return pdu.make_pdu(sample_dt=1.0 / _HZ)


def _scanned(cfg, s, *, chunk_intervals=16, state=None, start_sample=0,
             stop_sample=None, **kw):
    return fleet.condition(
        s, cfg, _SPEC, stream=fleet.StreamOptions(
            chunk_intervals=chunk_intervals, state=state,
            start_sample=start_sample, stop_sample=stop_sample), **kw)


def _assert_results_match(a, b, *, grid_atol=_ULP):
    np.testing.assert_array_equal(np.asarray(a.campus_rack), np.asarray(b.campus_rack))
    np.testing.assert_array_equal(np.asarray(a.soc_mean), np.asarray(b.soc_mean))
    np.testing.assert_allclose(
        np.asarray(a.campus_grid), np.asarray(b.campus_grid), atol=grid_atol
    )
    np.testing.assert_allclose(
        np.asarray(a.max_qp_residual), np.asarray(b.max_qp_residual), atol=grid_atol
    )


def _assert_states_match(sa, sb, *, atol=_ULP):
    for la, lb in zip(jax.tree_util.tree_leaves(sa), jax.tree_util.tree_leaves(sb)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb), atol=atol)


@pytest.mark.slow
def test_scanned_matches_one_shot_condition_fleet():
    """Chunked-with-carried-warm-state == one whole-trace call at equal
    qp_iters (the PR-1 streaming contract, now via the scanned engine)."""
    s = _campus()
    cfg = _cfg()
    a = _scanned(cfg, s, qp_iters=20, chunk_intervals=2)
    full = SC.render(s, 0, s.total_samples)
    res = fleet.condition(full, cfg, _SPEC, engine="oneshot", qp_iters=20)
    np.testing.assert_array_equal(np.asarray(a.campus_rack), np.asarray(res.campus_rack))
    np.testing.assert_allclose(
        np.asarray(a.campus_grid), np.asarray(res.campus_grid), atol=1e-5
    )
    # and the states match the one-shot pdu-level call
    st0 = pdu.init_state(cfg, full[0])
    _, st_f, _ = pdu.condition(cfg, st0, full, qp_iters=20)
    _assert_states_match(a.state, st_f, atol=1e-5)


def _one_shot_reference(cfg, s, qp_iters):
    """The whole trace through ``pdu.condition`` in one call, reduced to
    the campus aggregates the engines return, and its final state."""
    full = SC.render(s, 0, s.total_samples)
    grid, st_f, telem = pdu.condition(
        cfg, pdu.init_state(cfg, full[0]), full, qp_iters=qp_iters)
    return fleet.ConditioningResult(
        campus_rack=jnp.mean(full, axis=1),
        campus_grid=jnp.mean(grid, axis=1),
        soc_mean=jnp.mean(telem.soc, axis=1),
        max_qp_residual=jnp.max(telem.qp_residual),
        state=st_f,
    )


@pytest.mark.slow
@pytest.mark.parametrize("duration_s, chunk_intervals", [
    pytest.param(32.5, 2, id="32.5"),
    pytest.param(37.3, 2, id="37.3"),
    pytest.param(37.3, 3, id="37.3-chunk3"),
    pytest.param(46.5, 3, id="46.5-chunk3"),
])
def test_scanned_ragged_final_chunk(duration_s, chunk_intervals):
    """The epilogue step conditions the ragged tail at its natural length,
    so the chunked run reproduces the one-shot whole-trace call —
    including a tail shorter than one controller interval (32.5 s at two
    intervals a chunk and 46.5 s at three: 500- and 300-sample tails
    against k = 1000)."""
    s = _campus(n_racks=4, duration_s=duration_s)
    cfg = _cfg()
    a = _scanned(cfg, s, qp_iters=15, chunk_intervals=chunk_intervals)
    b = _one_shot_reference(cfg, s, qp_iters=15)
    k = int(round(float(cfg.controller.dt) * _HZ))
    assert a.campus_grid.shape == (s.total_samples,)
    assert a.soc_mean.shape == (-(-s.total_samples // k),)
    _assert_results_match(a, b)
    _assert_states_match(a.state, b.state)


@pytest.mark.slow
def test_scanned_chunk_intervals_invariance():
    """The warm ADMM state rides in PDUState across chunk boundaries, so
    the chunk size must not change the result."""
    s = _campus(n_racks=4)
    cfg = _cfg()
    a = _scanned(cfg, s, qp_iters=15, chunk_intervals=2)
    b = _scanned(cfg, s, qp_iters=15, chunk_intervals=4)
    _assert_results_match(a, b)
    _assert_states_match(a.state, b.state)


def test_scanned_resume_from_returned_state():
    """Splitting a scenario at a chunk boundary and resuming from the
    returned state must reproduce the unsplit run."""
    s = _campus(n_racks=4)
    cfg = _cfg()
    full = _scanned(cfg, s, qp_iters=15, chunk_intervals=2)
    k = int(round(float(cfg.controller.dt) * _HZ))
    t_cut = 2 * 2 * k  # two chunks
    first = _scanned(
        cfg, s, qp_iters=15, chunk_intervals=2, stop_sample=t_cut
    )
    rest = _scanned(
        cfg, s, qp_iters=15, chunk_intervals=2,
        state=first.state, start_sample=t_cut,
    )
    assert rest.campus_grid.shape == (s.total_samples - t_cut,)
    glued = np.concatenate([np.asarray(first.campus_rack), np.asarray(rest.campus_rack)])
    np.testing.assert_array_equal(glued, np.asarray(full.campus_rack))
    glued = np.concatenate([np.asarray(first.campus_grid), np.asarray(rest.campus_grid)])
    np.testing.assert_allclose(glued, np.asarray(full.campus_grid), atol=_ULP)
    glued = np.concatenate([np.asarray(first.soc_mean), np.asarray(rest.soc_mean)])
    np.testing.assert_allclose(glued, np.asarray(full.soc_mean), atol=_ULP)
    _assert_states_match(rest.state, full.state)


def test_scanned_resume_past_end_raises():
    s = _campus(n_racks=2, duration_s=20.0)
    with pytest.raises(ValueError, match="past the scenario end"):
        _scanned(
            _cfg(), s, start_sample=s.total_samples
        )


def test_scanned_start_sample_must_be_interval_aligned():
    s = _campus(n_racks=2, duration_s=20.0)
    cfg = _cfg()
    for bad in (-1000, 137):  # negative, and not a multiple of k=1000
        with pytest.raises(ValueError, match="multiple of the controller interval"):
            _scanned(cfg, s, start_sample=bad)


def test_resume_state_is_not_consumed():
    """The engines donate their state argument internally, but a caller's
    checkpoint must survive to seed several continuations."""
    s = _campus(n_racks=2, duration_s=30.0)
    cfg = _cfg()
    k = int(round(float(cfg.controller.dt) * _HZ))
    first = _scanned(
        cfg, s, qp_iters=10, chunk_intervals=2, stop_sample=2 * k
    )
    a = _scanned(
        cfg, s, qp_iters=10, chunk_intervals=2,
        state=first.state, start_sample=2 * k,
    )
    b = _scanned(  # same checkpoint, second use
        cfg, s, qp_iters=10, chunk_intervals=2,
        state=first.state, start_sample=2 * k,
    )
    np.testing.assert_array_equal(np.asarray(a.campus_grid), np.asarray(b.campus_grid))


def test_scanned_unbatched_scenario_lifts_to_one_rack():
    s = SC.scenario_from_model("llama3_2_1b", duration_s=20.0, sample_hz=_HZ)
    res = _scanned(_cfg(), s, qp_iters=10)
    assert res.campus_grid.shape == (s.total_samples,)
    assert np.all(np.isfinite(np.asarray(res.campus_grid)))


def test_condition_scenario_streaming_rejects_unknown_engine():
    s = _campus(n_racks=2, duration_s=20.0)
    with pytest.raises(ValueError, match="unknown engine"):
        fleet.condition(s, _cfg(), _SPEC, engine="warp")


def test_condition_campus_is_the_reduced_condition():
    """pdu.condition_campus == pdu.condition + campus reductions."""
    cfg = _cfg()
    key = jax.random.key(0)
    tr = 0.5 + 0.3 * jax.random.uniform(key, (2400, 3))
    st = pdu.init_state(cfg, tr[0])
    st_a, ch = pdu.condition_campus(cfg, st, tr, qp_iters=10)
    st_b = pdu.init_state(cfg, tr[0])
    grid, st_b, telem = pdu.condition(cfg, st_b, tr, qp_iters=10)
    np.testing.assert_array_equal(np.asarray(ch.campus_rack), np.asarray(jnp.mean(tr, axis=1)))
    np.testing.assert_allclose(
        np.asarray(ch.campus_grid), np.asarray(jnp.mean(grid, axis=1)), atol=_ULP
    )
    np.testing.assert_allclose(
        np.asarray(ch.soc_mean), np.asarray(jnp.mean(telem.soc, axis=1)), atol=_ULP
    )
    assert ch.max_qp_residual.shape == ()


def test_render_padded_holds_final_sample():
    """In-range samples bit-match `render`; past-the-end rows hold the last
    in-range sample (the streaming engines' ZOH pad)."""
    s = _campus(n_racks=3, duration_s=10.0)
    t = s.total_samples
    chunk = 512
    t0 = t - 100  # 100 real samples, 412 pad rows
    padded = SC.render_padded(s, t0, chunk)
    plain = SC.render(s, t0, 100)
    np.testing.assert_array_equal(np.asarray(padded[:100]), np.asarray(plain))
    np.testing.assert_array_equal(
        np.asarray(padded[100:]),
        np.broadcast_to(np.asarray(plain[-1:]), (chunk - 100,) + plain.shape[1:]),
    )
    # traced t0 (the in-scan case) agrees with the static call (up to
    # FMA-contraction ulps: the wrapping jit compiles a different fusion)
    traced = jax.jit(lambda i: SC.render_padded(s, i, chunk))(jnp.int32(t0))
    np.testing.assert_allclose(np.asarray(traced), np.asarray(padded), atol=1e-7)


def test_chunk_count():
    s = _campus(n_racks=2, duration_s=10.0)  # 2000 samples
    assert SC.chunk_count(s, 500) == 4
    assert SC.chunk_count(s, 600) == 4
    assert SC.chunk_count(s, 2000) == 1
    with pytest.raises(ValueError):
        SC.chunk_count(s, 0)


def test_make_condition_step_is_cached_per_config():
    cfg = _cfg()
    a = fleet.make_condition_step(cfg, qp_iters=25)
    b = fleet.make_condition_step(pdu.make_pdu(sample_dt=1.0 / _HZ), qp_iters=25)
    c = fleet.make_condition_step(cfg, qp_iters=30)
    assert a is b  # equal config values -> same cached step
    assert a is not c


def test_shard_racks_in_jit_single_device_is_noop():
    """On a 1-device mesh the in-jit sharding constraint must not change
    the result (matches `rules.constrain_to_mesh`'s guard)."""
    from repro.sharding.rules import make_mesh

    s = _campus(n_racks=4, duration_s=20.0)
    cfg = _cfg()
    mesh = make_mesh((1,), ("data",))
    a = _scanned(
        cfg, s, qp_iters=10, chunk_intervals=2, mesh=mesh
    )
    b = _scanned(cfg, s, qp_iters=10, chunk_intervals=2)
    np.testing.assert_array_equal(np.asarray(a.campus_rack), np.asarray(b.campus_rack))
    np.testing.assert_allclose(
        np.asarray(a.campus_grid), np.asarray(b.campus_grid), atol=_ULP
    )


# ----------------------------------------------- degraded mode (ISSUE 6)


def _faulty_campus(n_racks=5, duration_s=40.0, seed=2):
    from repro.power import faults as FLT

    s = _campus(n_racks=n_racks, duration_s=duration_s, seed=seed)
    proc = FLT.FaultProcess.create(
        rack_mtbf_s=30.0, rack_mttr_s=10.0,
        ess_mtbf_s=25.0, ess_mttr_s=8.0,
        sensor_mtbf_s=20.0, sensor_mttr_s=4.0,
    )
    return SC.attach_faults(s, proc, seed=13)


def _deg_cfg():
    return pdu.make_pdu(sample_dt=1.0 / _HZ, degraded_mode=True)


def test_faulty_scenario_requires_degraded_mode():
    s = _faulty_campus()
    with pytest.raises(ValueError):
        _scanned(_cfg(), s)
    with pytest.raises(ValueError):
        fleet.condition(s, _cfg(), _SPEC, engine="oneshot")


@pytest.mark.slow
def test_degraded_engines_match_under_stochastic_schedule():
    """scanned == one-shot under a stochastic fault schedule, to the
    repo's standing tolerance contract (rack/mask aggregates bitwise;
    filter-chain outputs within FMA-contraction slack)."""
    from repro.power import faults as FLT

    s = _faulty_campus()
    cfg = _deg_cfg()
    a = _scanned(cfg, s, qp_iters=20, chunk_intervals=2)

    k = int(round(float(cfg.controller.dt) * _HZ))
    n_ctrl = -(-s.total_samples // k)
    on = FLT.interval_online(s.faults, 0, n_ctrl, k)
    wt = FLT.ess_weight(s.faults, 0, s.total_samples, s.edge_width)
    tr = SC.render(s, 0, s.total_samples)
    res = fleet.condition(
        tr, cfg, _SPEC, engine="oneshot", qp_iters=20, ess_online=on,
        ess_weight=wt,
    )
    np.testing.assert_array_equal(
        np.asarray(a.campus_rack), np.asarray(res.campus_rack)
    )
    np.testing.assert_array_equal(
        np.asarray(a.ess_online_frac), np.asarray(res.ess_online_frac)
    )
    np.testing.assert_allclose(
        np.asarray(a.campus_grid), np.asarray(res.campus_grid), atol=1e-5
    )
    # masks really tripped something, and every output stayed finite
    assert float(np.asarray(a.ess_online_frac).min()) < 1.0
    assert np.all(np.isfinite(np.asarray(a.campus_grid)))
    assert np.all(np.isfinite(np.asarray(a.campus_rack)))


def test_degraded_fault_on_chunk_boundary():
    """A deterministic ESS outage whose edges land exactly on chunk
    boundaries must render identically at any chunking."""
    from repro.power import faults as FLT

    s = _campus(n_racks=4, duration_s=24.0)
    k = int(round(5.0 * _HZ))  # controller interval in samples
    chunk = 2 * k
    sched = FLT.schedule_from_episodes(
        4, ess=[(1, chunk, 2 * chunk), (2, 2 * chunk, 3 * chunk)],
        sensor=[(3, chunk, chunk + k)],
    )
    s = SC.attach_faults(s, sched)
    cfg = _deg_cfg()
    a = _scanned(cfg, s, qp_iters=15, chunk_intervals=2)
    b = _scanned(cfg, s, qp_iters=15, chunk_intervals=4)
    _assert_results_match(a, b)
    np.testing.assert_array_equal(
        np.asarray(a.ess_online_frac), np.asarray(b.ess_online_frac)
    )
    # the scheduled outage shows in the mask at exactly the right intervals:
    # interval 2 has rack 1's ESS tripped AND rack 3 measurement-blind
    # (finite-guard), then rack 1 alone, then rack 2 alone.
    np.testing.assert_array_equal(
        np.asarray(a.ess_online_frac), [1.0, 1.0, 0.5, 0.75, 0.75]
    )


@pytest.mark.slow
def test_degraded_resume_mid_outage():
    """Stop/resume inside an active fault episode: the glued stream must be
    bitwise identical to the uninterrupted run (mask and bridge state are
    pure in the absolute sample index; last_good rides in PDUState)."""
    s = _faulty_campus()
    cfg = _deg_cfg()
    k = int(round(float(cfg.controller.dt) * _HZ))
    full = _scanned(cfg, s, qp_iters=20, chunk_intervals=2)
    cut = 4 * k  # resume point: interval-aligned, inside the fault soup
    a = _scanned(
        cfg, s, qp_iters=20, chunk_intervals=2, stop_sample=cut
    )
    b = _scanned(
        cfg, s, qp_iters=20, chunk_intervals=2,
        state=a.state, start_sample=cut,
    )
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(a.campus_rack), np.asarray(b.campus_rack)]),
        np.asarray(full.campus_rack),
    )
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(a.ess_online_frac), np.asarray(b.ess_online_frac)]),
        np.asarray(full.ess_online_frac),
    )
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(a.soc_mean), np.asarray(b.soc_mean)]),
        np.asarray(full.soc_mean),
    )


def test_fleet_summary_json_safe_round_trip():
    """An untracked config's infinite projected life must JSON-serialize
    under allow_nan=False once clamped."""
    import json

    from repro.core import health as hlt

    s = _campus(n_racks=3, duration_s=20.0)
    cfg = _cfg()  # track_health off -> empty history -> inf lifetime
    tr = SC.render(s, 0, s.total_samples)
    res = fleet.condition(tr, cfg, _SPEC, engine="oneshot", qp_iters=10)
    raw = hlt.fleet_summary(res.health)
    assert raw["projected_life_years_min"] == float("inf")
    with pytest.raises(ValueError):
        json.dumps(raw, allow_nan=False)
    safe = hlt.fleet_summary(res.health, json_safe=True)
    assert safe["projected_life_years_min"] is None
    assert json.loads(json.dumps(safe, allow_nan=False)) == safe


# ------------------------------------- one chunk scan for campus and region


def _one_campus_region(s):
    from repro.core import grid

    return grid.region([s], salt_noise=False)


@pytest.mark.parametrize("case", ["44.0", "37.3", "health", "degraded"])
def test_one_campus_region_is_the_campus_engine(case):
    """A one-campus grid region runs the campus engine's chunk scan: its
    campus aggregates and final state equal the scanned campus engine's
    bit for bit."""
    if case == "degraded":
        s, cfg = _faulty_campus(), _deg_cfg()
    else:
        s = _campus(n_racks=4, duration_s=37.3 if case == "37.3" else 44.0)
        cfg = pdu.make_pdu(sample_dt=1.0 / _HZ, track_health=case == "health")
    a = _scanned(cfg, s, qp_iters=15, chunk_intervals=2)
    r = fleet.condition(_one_campus_region(s), cfg, _SPEC, qp_iters=15,
                        stream=dict(chunk_intervals=2))
    for f in ("campus_rack", "campus_grid", "soc_mean", "ess_online_frac",
              "health_trace", "safemode_trace"):
        np.testing.assert_array_equal(
            np.asarray(getattr(r, f))[0], np.asarray(getattr(a, f)), err_msg=f)
    np.testing.assert_array_equal(
        np.asarray(r.max_qp_residual), np.asarray(a.max_qp_residual))
    (st,) = r.state
    for la, lb in zip(jax.tree_util.tree_leaves(st),
                      jax.tree_util.tree_leaves(a.state)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
