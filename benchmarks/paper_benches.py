"""One benchmark per paper table/figure (see DESIGN.md §8 index).

Each function returns (name, us_per_call, derived) where ``derived`` is the
paper-comparable headline number(s) as a compact string.
"""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import burn, compliance, controller as ctrl, ess, filters, fleet, pdu, sizing
from repro.power import scenario as SC, trace

# CI smoke mode (`benchmarks/run.py --quick`): shrink fleet sizes and trace
# durations so the whole harness doubles as a fast smoke run.
QUICK = os.environ.get("REPRO_BENCH_QUICK", "") == "1"

# Per-bench workload sizes, registered by the bench functions as they run:
# {bench_name: {"racks": R, "samples": total campus samples}}.  run.py uses
# these to derive us/rack and samples/s next to the raw wall-clock, so the
# perf trajectory is readable across PRs without decoding each derived
# string.
UNITS: dict[str, dict] = {}


def _q(full, quick):
    return quick if QUICK else full


def _timeit(fn, *args, n=3):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6, out


def _conditioned(sample_hz=500.0, duration=None, key=0):
    duration = duration or _q(240.0, 60.0)
    spec = compliance.GridSpec.create()
    cfg = pdu.make_pdu(sample_dt=1.0 / sample_hz)
    sp = trace.TestbenchSpec(duration_s=duration, sample_hz=sample_hz, terminate_at_s=duration - 30)
    rack, dt = trace.testbench_trace(sp, jax.random.key(key))
    st = pdu.init_state(cfg, rack[0])
    f = jax.jit(lambda s, r: pdu.condition(cfg, s, r, qp_iters=40)[0])
    us, grid = _timeit(f, st, rack)
    return spec, cfg, rack, grid, dt, us


def bench_fig9_ramp_rate():
    """Fig. 9: conditioned ramp rate stays within beta = 0.1/s."""
    spec, cfg, rack, grid, dt, us = _conditioned()
    rr = float(compliance.max_abs_ramp(rack, dt))
    rg = float(compliance.max_abs_ramp(grid, dt))
    return "fig9_ramp_rate", us, (
        f"rack_ramp={rr:.1f}/s grid_ramp={rg:.4f}/s beta=0.1 ok={rg <= 0.1}"
    )


def bench_fig10_spectrum():
    """Fig. 10: conditioned spectrum below alpha above f_c."""
    spec, cfg, rack, grid, dt, us = _conditioned(key=1)
    _, sr = compliance.normalized_spectrum(rack, dt)
    fr, sg = compliance.normalized_spectrum(grid, dt)
    above = np.asarray(fr) >= 2.0
    worst_r = float(np.max(np.asarray(sr)[above]))
    worst_g = float(np.max(np.asarray(sg)[above]))
    return "fig10_spectrum", us, (
        f"rack_hf={worst_r:.2e} grid_hf={worst_g:.2e} alpha=1e-4 ok={worst_g <= 1e-4}"
    )


def bench_fig7_frequency_response():
    """Fig. 7: combined response = LC x ESS, -20 then -40 dB/dec."""
    cfg = pdu.make_pdu()
    f = jnp.logspace(-4, 3, 400)
    t0 = time.perf_counter()
    h = pdu.combined_transfer_function(cfg, f)
    us = (time.perf_counter() - t0) * 1e6
    h = np.asarray(h)
    fb = float(cfg.ess_params.cutoff_hz())
    ff = float(cfg.filter_params.cutoff_hz())
    i1, i2 = np.searchsorted(np.asarray(f), [1.0, 10.0])
    slope_mid = np.log10(h[i2] / h[i1])  # ~ -1 (ESS only band)
    i3, i4 = np.searchsorted(np.asarray(f), [30.0, 300.0])
    slope_hi = np.log10(h[i4] / h[i3])  # ~ -3 (ESS+LC)
    return "fig7_response", us, (
        f"f_b={fb:.4f}Hz f_f={ff:.1f}Hz slope(1-10Hz)={slope_mid:.2f}dec "
        f"slope(30-300Hz)={slope_hi:.2f}dec"
    )


def bench_fig11_burn_energy():
    """Fig. 11 / §7.3: software burn vs EasyRider energy overhead."""
    tb, dt = trace.titanx_testbench(jax.random.key(2))
    cal = burn.calibrate(jax.random.key(3), p_idle=0.06, p_peak=1.0)
    cfg = pdu.make_pdu(sample_dt=dt)
    st = pdu.init_state(cfg, tb[0])
    f = jax.jit(lambda s, r: pdu.condition(cfg, s, r, qp_iters=40))
    us, (gez, _, telem) = _timeit(f, st, tb)
    sched = burn.burn_schedule(tb, dt, beta=0.1, cal=cal)
    nwarm = sched.conditioned.shape[0] - tb.shape[0]
    soc = np.asarray(telem.soc)
    cmp = burn.compare_energy(
        tb, gez, sched.conditioned[nwarm:], dt,
        soc_delta=float(soc[-1]) - 0.5, q_max_seconds=float(cfg.ess_params.q_max),
    )
    return "fig11_burn_energy", us, (
        f"burn_overhead={float(cmp['burn_overhead_frac'])*100:.1f}% "
        f"easyrider_overhead={float(cmp['easyrider_overhead_frac'])*100:.2f}% "
        f"burn_vs_easyrider={float(cmp['burn_vs_easyrider_frac'])*100:.1f}% (paper: 19%)"
    )


def bench_fig12_soc_management():
    """Fig. 12: SoC drift corrected to S_mid within ~20 min."""
    cfg = ctrl.ControllerConfig.create(i_max=4e-3)
    es = ess.ESSParams.create(q_max_seconds=40.0)
    n_steps = _q(400, 80)
    f = jax.jit(lambda: ctrl.simulate_soc_management(cfg, es, 0.62, n_steps=n_steps, qp_iters=80)["soc"])
    us, soc = _timeit(f)
    soc = np.asarray(soc)
    hit = int(np.argmax(np.abs(soc - 0.5) <= float(cfg.deadband)))
    return "fig12_soc", us, (
        f"soc 0.62->{soc[-1]:.3f} converge={hit * 5 / 60:.1f}min (paper ~20min)"
    )


def bench_fig13_cluster_fault():
    """Fig. 13: 40 MW cluster with a computation fault at ~400 s."""
    import dataclasses
    spec = trace.cluster_fault_spec()
    if QUICK:
        spec = dataclasses.replace(spec, duration_s=150.0, warmup_s=10.0,
                                   fault_at_s=80.0, terminate_at_s=130.0)
    rack, dt = trace.testbench_trace(spec, jax.random.key(4))
    cfg = pdu.make_pdu(sample_dt=dt)
    st = pdu.init_state(cfg, rack[0])
    f = jax.jit(lambda s, r: pdu.condition(cfg, s, r, qp_iters=20)[0])
    us, grid = _timeit(f, st, rack)
    # paper's 193.7 MW/s is measured over the fault's ~200 ms fall window
    w = max(int(0.2 / dt), 1)
    rr = float(jnp.max(jnp.abs(rack[w:] - rack[:-w]))) / 0.2 * 40  # MW/s at 40 MW
    rg = float(compliance.max_abs_ramp(grid, dt)) * 40
    return "fig13_cluster_fault", us, (
        f"unconditioned={rr:.1f}MW/s (paper 193.7) conditioned={rg:.2f}MW/s "
        f"ok={float(compliance.max_abs_ramp(grid, dt)) <= 0.1}"
    )


def bench_table1_mitigation_space():
    """Table 1: energy + compliance across mitigation approaches."""
    tb, dt = trace.titanx_testbench(jax.random.key(5))
    spec = compliance.GridSpec.create()
    results = {}
    # none
    results["none"] = (float(jnp.sum(tb)) * dt, bool(compliance.check(tb, dt, spec).ramp_ok))
    # burn
    cal = burn.calibrate(jax.random.key(6), 0.06, 1.0)
    sched = burn.burn_schedule(tb, dt, beta=0.1, cal=cal)
    nwarm = sched.conditioned.shape[0] - tb.shape[0]
    bt = sched.conditioned[nwarm:]
    results["sw_burn"] = (float(jnp.sum(bt)) * dt, bool(compliance.check(bt, dt, spec).ramp_ok))
    # easyrider hw-only and hw+sw
    t0 = time.perf_counter()
    for name, sw in (("easyrider_hw", False), ("easyrider_hw_sw", True)):
        cfg = pdu.make_pdu(sample_dt=dt, software_enabled=sw)
        st = pdu.init_state(cfg, tb[0])
        g, _, _ = pdu.condition(cfg, st, tb, qp_iters=20)
        results[name] = (float(jnp.sum(g)) * dt, bool(compliance.check(g, dt, spec).ramp_ok))
    us = (time.perf_counter() - t0) * 1e6
    base = results["none"][0]
    derived = " ".join(
        f"{k}:E={v[0]/base:.3f}x,ramp_ok={v[1]}" for k, v in results.items()
    )
    return "table1_mitigation", us, derived


def bench_appendixA_sizing():
    """Appendix A.1: sizing table for prototype + 1 MW racks."""
    t0 = time.perf_counter()
    proto = sizing.size_system(sizing.prototype_rack(), beta=0.1)
    mw = sizing.size_system(sizing.mw_rack(), beta=0.1)
    us = (time.perf_counter() - t0) * 1e6
    return "appendixA_sizing", us, (
        f"proto:E_B={proto.battery_energy_j/1e3:.0f}kJ({proto.battery_capacity_ah:.1f}Ah<74Ah)"
        f" P_B={proto.battery_power_w/1e3:.0f}kW | 1MW:E_B={mw.battery_energy_j/1e6:.1f}MJ"
        f" P_B={mw.battery_power_w/1e6:.1f}MW"
    )


def bench_fleet_scale():
    """Appendix D at campus scale: 1024 racks, cold-start (seed per-interval
    build + factor + vmapped solve, 120 iters) vs the factor-once
    warm-started batched plan (30 iters) at matched QP primal residual."""
    n_racks = _q(1024, 64)
    sp = trace.TestbenchSpec(duration_s=44.0, sample_hz=200.0)
    t1, dt = trace.testbench_trace(sp, jax.random.key(7))
    racks = fleet.staggered_fleet(t1, n_racks, jax.random.key(8), max_offset_samples=800)
    cfg = pdu.make_pdu(sample_dt=dt)

    def run(tr, use_plan, iters):
        st = pdu.init_state(cfg, tr[0])
        grid, _, telem = pdu.condition(cfg, st, tr, qp_iters=iters, use_plan=use_plan)
        return jnp.mean(grid, axis=1), jnp.max(telem.qp_residual)

    f_cold = jax.jit(lambda tr: run(tr, False, 120))
    f_warm = jax.jit(lambda tr: run(tr, True, 30))
    us_cold, (campus_c, resid_c) = _timeit(f_cold, racks, n=1)
    us_warm, (campus_w, resid_w) = _timeit(f_warm, racks, n=1)
    UNITS["fleet_1024racks"] = dict(racks=n_racks, samples=t1.shape[0] * n_racks)
    rg = float(compliance.max_abs_ramp(campus_w, dt))
    speedup = us_cold / us_warm
    return "fleet_1024racks", us_warm, (
        f"campus_ramp={rg:.4f}/s ok={rg <= 0.1} "
        f"cold_us_per_rack={us_cold / n_racks:.0f} "
        f"warm_us_per_rack={us_warm / n_racks:.0f} speedup={speedup:.1f}x "
        f"qp_resid_cold={float(resid_c):.2e} qp_resid_warm={float(resid_w):.2e}"
    )


def bench_controller_throughput():
    """Controller-layer throughput: rack-solves/s, seed cold-start path
    (per-rack _build_qp + cho_factor + 120-iter ADMM, vmapped) vs the
    factor-once plan (one batched 30-iter ADMM, warm-started)."""
    n_racks = _q(2048, 128)
    n_steps = 4
    cfg = ctrl.ControllerConfig.create()
    es = ess.ESSParams.create(q_max_seconds=40.0)
    socs = 0.3 + 0.4 * jax.random.uniform(jax.random.key(12), (n_racks,))
    tgt = jnp.asarray(0.5)
    ups = jnp.zeros((n_racks,))

    UNITS["controller_throughput"] = dict(racks=n_racks)
    cold = jax.jit(
        jax.vmap(
            lambda s, u: ctrl.inner_loop_step(
                cfg, es, s, tgt, u, qp_iters=120
            ).corrective_power
        )
    )
    us_cold, _ = _timeit(cold, socs, ups, n=1)

    plan = ctrl.make_plan(cfg, es)

    def warm_steps(s0):
        def body(carry, _):
            soc, up, warm = carry
            out, warm2 = ctrl.inner_loop_step_plan(
                cfg, es, plan, soc, tgt, up, warm, qp_iters=30
            )
            soc2 = soc - out.corrective_power * cfg.dt / es.q_max
            return (soc2, out.corrective_power / cfg.i_max, warm2), (
                out.qp_primal_residual
            )

        carry0 = (s0, jnp.zeros_like(s0), ctrl.init_warm(plan, s0.shape))
        _, resid = jax.lax.scan(body, carry0, None, length=n_steps)
        return resid

    warm = jax.jit(warm_steps)
    us_warm_total, resid = _timeit(warm, socs, n=1)
    us_warm = us_warm_total / n_steps  # per control interval
    sps_cold = n_racks / (us_cold / 1e6)
    sps_warm = n_racks / (us_warm / 1e6)
    return "controller_throughput", us_warm, (
        f"racksolves_per_s cold={sps_cold:.0f} warm={sps_warm:.0f} "
        f"speedup={sps_warm / sps_cold:.1f}x "
        f"warm_resid={float(jnp.max(resid[-1])):.2e}"
    )


def bench_scenario_render():
    """Scenario-engine synthesis throughput: host-materialized one-shot
    (T, R) render vs on-device chunked rendering (the streaming conditioner's
    chunk provider path).  Derived number is samples/s of campus trace."""
    n_racks = _q(256, 32)
    duration = _q(120.0, 30.0)
    hz = 200.0
    s = SC.mixed_campus(
        n_racks,
        ("llama3_2_1b", "deepseek_v3_671b", "whisper_large_v3"),
        duration_s=duration,
        sample_hz=hz,
        seed=0,
        noise_seed=1,
    )
    t_total = s.total_samples
    chunk = 4000

    one_shot = lambda: np.asarray(SC.render(s, 0, t_total))  # host-materialized
    us_full, _ = _timeit(one_shot, n=1)

    def chunked():
        outs = [SC.render(s, t0, min(chunk, t_total - t0))
                for t0 in range(0, t_total, chunk)]
        jax.block_until_ready(outs)
        return outs

    us_chunk, _ = _timeit(chunked, n=1)
    total = t_total * n_racks
    UNITS["scenario_render"] = dict(racks=n_racks, samples=total)
    return "scenario_render", us_chunk, (
        f"samples_per_s host={total / (us_full / 1e6):.2e} "
        f"chunked={total / (us_chunk / 1e6):.2e} racks={n_racks} T={t_total}"
    )


def mixed_campus_scenario(n_racks, duration, hz):
    """The heterogeneous acceptance campus: 4 model workloads plus the
    inference-diurnal block, staggered starts, a mid-trace fault cascade."""
    return SC.mixed_campus(
        n_racks,
        ("llama3_2_1b", "deepseek_v3_671b", "chatglm3_6b", "whisper_large_v3"),
        duration_s=duration,
        sample_hz=hz,
        seed=3,
        fault_at_s=duration * 0.6,
        noise_seed=2,
    )


# Cross-bench wall-clock records (e.g. mixed_campus_health reports its
# overhead against the same run's mixed_campus_fleet timing).
LAST_US: dict[str, float] = {}


def _best_of(run, ready, n=3):
    """Min-of-n wall clock: this container's timings drift ±15-20% with
    background load, so single-shot numbers routinely fake both
    regressions and speedups.  Applies in QUICK mode too — that is the
    mode ``--quick --gate`` times, and a gate fed single-shot numbers
    would flap (quick workloads are small, so the extra reps are cheap)."""
    best, out = float("inf"), None
    for _ in range(n):
        t0 = time.perf_counter()
        r = run()
        jax.block_until_ready(ready(r))
        best, out = min(best, (time.perf_counter() - t0) * 1e6), r
    return best, out


def bench_mixed_campus():
    """The heterogeneous-campus acceptance scenario: 1024 racks running 4
    model-derived workloads + an inference-diurnal block, staggered job
    starts/stops, and a mid-trace fault cascade — conditioned end-to-end by
    the scanned engine (render + chunk loop fused into ONE dispatch, no
    (T, R) host materialization ever).  The engine-equivalence contract
    against the one-shot reference is pinned by the tier-1 tests."""
    n_racks = _q(1024, 64)
    duration = _q(88.0, 30.0)
    hz = 200.0
    s = mixed_campus_scenario(n_racks, duration, hz)
    cfg = pdu.make_pdu(sample_dt=1.0 / hz)
    spec = compliance.GridSpec.create()
    run = lambda: fleet.condition(
        s, cfg, spec, qp_iters=30, stream=fleet.StreamOptions(chunk_intervals=4)
    )
    run()  # compile
    us, res = _best_of(run, lambda r: r.campus_grid)
    UNITS["mixed_campus_fleet"] = dict(racks=n_racks, samples=s.total_samples * n_racks)

    rg = float(res.report_grid.max_ramp)
    LAST_US["mixed_campus_fleet"] = us
    return "mixed_campus_fleet", us, (
        f"racks={n_racks} workloads=5 campus_ramp={rg:.4f}/s "
        f"ok={bool(res.report_grid.ramp_ok)} raw_ok={bool(res.report_rack.ramp_ok)} "
        f"us_per_rack={us / n_racks:.0f} qp_resid={float(res.max_qp_residual):.2e}"
    )


def bench_mixed_campus_health():
    """Observer overhead: the PR-3 acceptance campus re-run with the full
    health-aware telemetry spine enabled — per-sample battery wear state
    machine (`core.health`) folded into the conditioning scan plus the
    streaming compliance observers — must stay within ~10% of the
    telemetry-free `mixed_campus_fleet` wall clock."""
    from repro.core import health as hlt

    n_racks = _q(1024, 64)
    duration = _q(88.0, 30.0)
    hz = 200.0
    s = mixed_campus_scenario(n_racks, duration, hz)
    cfg = pdu.make_pdu(sample_dt=1.0 / hz, track_health=True)
    spec = compliance.GridSpec.create()
    run = lambda: fleet.condition(
        s, cfg, spec, qp_iters=30, stream=fleet.StreamOptions(chunk_intervals=4)
    )
    run()  # compile
    us, res = _best_of(run, lambda r: r.campus_grid)
    UNITS["mixed_campus_health"] = dict(racks=n_racks, samples=s.total_samples * n_racks)

    if QUICK:
        # Megakernel-vs-ref agreement ride-along: one controller interval of
        # THIS campus through the interpret-mode Pallas megakernel vs the
        # jnp reference the engines run on CPU.  SoC path + every health
        # leaf bitwise, grid bitwise on the (sublane-aligned) interval.
        from repro.core import health as _h
        from repro.kernels import ops as _ops, ref as _kref

        k = int(round(cfg.controller.dt * hz))
        chunk = jax.jit(lambda: SC.render(s, 0, k))()
        st = pdu.init_state(cfg, chunk[0])
        ep = cfg.ess_params
        kkw = dict(
            beta=float(ep.beta), dt=1.0 / hz, q_max=float(ep.q_max),
            eta_c=float(ep.eta_c), eta_d=float(ep.eta_d),
            p_max=float(ep.p_max), soc_min=float(ep.soc_safe_min),
            soc_max=float(ep.soc_safe_max),
        )
        filt = st.filter_obj
        a = (chunk, st.ess_state.g_filter, st.ess_state.soc, st.filter_state,
             filt.ad, filt.bd, filt.c[0])
        hin = (_h.step_consts(cfg.health), tuple(st.health))
        r_ref = _kref.pdu_health_sim(*a, health=hin, **kkw)
        r_pl = _ops.pdu_health_sim(*a, health=hin, force="pallas", **kkw)
        np.testing.assert_array_equal(np.asarray(r_ref[1]), np.asarray(r_pl[1]))
        np.testing.assert_array_equal(np.asarray(r_ref[0]), np.asarray(r_pl[0]))
        for lf_r, lf_p in zip(r_ref[3], r_pl[3]):
            np.testing.assert_array_equal(np.asarray(lf_r), np.asarray(lf_p))

    base = LAST_US.get("mixed_campus_fleet")
    overhead = f"{(us / base - 1) * 100:+.1f}%" if base else "-"
    h = hlt.fleet_summary(res.health)
    LAST_US["mixed_campus_health"] = us
    return "mixed_campus_health", us, (
        f"racks={n_racks} overhead_vs_fleet={overhead} "
        f"efc_mean={h['efc_mean']:.3f} half_cycles={h['half_cycles_mean']:.0f} "
        f"worst_dod={h['worst_dod']:.3f} fade_max={h['fade_max']:.2e} "
        f"life_min={h['projected_life_years_min']:.1f}y "
        f"hf_lines_ok={bool(res.report_grid.spectrum_ok)}"
        + (" megakernel_agrees=True" if QUICK else "")
    )


def bench_mixed_campus_safemode():
    """Supervision overhead (ISSUE 9): the health-telemetry acceptance
    campus re-run with the full safe-mode control plane live — per-rack
    sanitizer sweep over every carried leaf, in-kernel output guard, ADMM
    divergence watchdog, and the supervisor state machine folded into the
    interval scan.  Must stay within 10% of the unsupervised
    ``mixed_campus_health`` wall clock from the same run (asserted — a
    gated run fails if supervision stops being effectively free)."""
    n_racks = _q(1024, 64)
    duration = _q(88.0, 30.0)
    hz = 200.0
    s = mixed_campus_scenario(n_racks, duration, hz)
    cfg_off = pdu.make_pdu(sample_dt=1.0 / hz, track_health=True)
    cfg_on = pdu.make_pdu(sample_dt=1.0 / hz, track_health=True, safemode=True)
    spec = compliance.GridSpec.create()
    run = lambda c: fleet.condition(
        s, c, spec, qp_iters=30, stream=fleet.StreamOptions(chunk_intervals=4)
    )
    run(cfg_off), run(cfg_on)  # compile both
    # The two configs are timed INTERLEAVED (not vs the earlier
    # mixed_campus_health record): this container's wall clock drifts
    # between benches, and an overhead *assert* fed cross-bench timings
    # would flap on load spikes.  Interleaving keeps both sides under the
    # same drift.
    us_off = us = float("inf")
    res = None
    for _ in range(3):
        t0 = time.perf_counter()
        r = run(cfg_off)
        jax.block_until_ready(r.campus_grid)
        us_off = min(us_off, (time.perf_counter() - t0) * 1e6)
        t0 = time.perf_counter()
        r = run(cfg_on)
        jax.block_until_ready(r.campus_grid)
        us, res = min(us, (time.perf_counter() - t0) * 1e6), r
    UNITS["mixed_campus_safemode"] = dict(
        racks=n_racks, samples=s.total_samples * n_racks
    )
    LAST_US["mixed_campus_safemode"] = us

    trace = np.asarray(res.safemode_trace)
    assert np.all(trace[:, 0] == 1.0), "clean campus tripped the supervisor"
    summ = res.safemode_summary()
    overhead = (us / us_off - 1) * 100
    assert us < 1.10 * us_off, (
        f"safe-mode supervision overhead {overhead:+.1f}% exceeds the "
        f"10% budget vs the unsupervised run ({us_off:.0f}us -> {us:.0f}us)"
    )
    return "mixed_campus_safemode", us, (
        f"racks={n_racks} overhead_interleaved={overhead:+.1f}% "
        f"n_normal={summ['n_normal']} entries="
        f"{summ['passthrough_entries'] + summ['quarantine_entries']} "
        f"worst_streak={summ['worst_resid_streak']} "
        f"ramp_ok={bool(res.report_grid.ramp_ok)} budget_ok=True"
    )


def faulty_campus_scenario(n_racks, duration, hz):
    """The fault-soup acceptance campus of ``bench_mixed_campus_faulty``:
    the mixed campus with a stochastic fault schedule (about 30% of ESS
    units offline at the worst interval, rack power losses, sensor-dropout
    NaN windows) plus one scripted mid-trace rack cascade, attached."""
    from repro.power import faults as FLT

    s = SC.mixed_campus(
        n_racks,
        ("llama3_2_1b", "deepseek_v3_671b", "chatglm3_6b", "whisper_large_v3"),
        duration_s=duration,
        sample_hz=hz,
        seed=3,
        fault_rack_fraction=0.0,  # the cascade rides in the fault schedule
        edge_pad="clamp",
        noise_seed=2,
    )
    # ESS steady-state offline fraction = mttr/(mtbf+mttr) = 0.3: the
    # acceptance claim is a campus that holds the ramp spec with roughly a
    # third of its conditioning fleet dark at the worst interval.
    proc = FLT.FaultProcess.create(
        rack_mtbf_s=duration * 4.0, rack_mttr_s=duration * 0.25,
        ess_mtbf_s=duration * 1.75, ess_mttr_s=duration * 0.75,
        sensor_mtbf_s=duration * 3.0, sensor_mttr_s=duration * 0.1,
    )
    sched = FLT.sample_schedule(
        proc, n_racks, s.total_samples, hz, seed=6
    )
    # One cascade: rack power loss ripples across a contiguous tenth of
    # the fleet over ~5 s, 20 s outages, starting at 60% of the trace.
    n_cas = max(n_racks // 10, 1)
    lo = n_racks // 3
    t0f = int(0.6 * duration * hz)
    step = max(int(5.0 * hz) // max(n_cas - 1, 1), 1)
    durf = int(20.0 * hz)
    sched = FLT.inject_episodes(sched, rack=[
        (lo + i, t0f + i * step, min(t0f + i * step + durf, s.total_samples))
        for i in range(n_cas)
    ])
    return SC.attach_faults(s, sched)


def bench_mixed_campus_faulty():
    """Fault-soup acceptance campus: the 1024-rack heterogeneous fleet under a
    stochastic fault soup (ESS trips ~30% of units offline at the worst
    interval, rack power losses, sensor-dropout NaN windows) plus one
    scripted mid-trace cascade injected into the fault engine's rack
    channel — conditioned end-to-end by the degraded-mode scanned engine,
    with the availability mask derived in-jit from the schedule's episode
    table.  Asserts the campus still meets the ramp spec with a third of
    the conditioning fleet dark (the honest claim rides in
    min_online_frac), and in ``--quick`` mode cross-checks the megakernel
    against its jnp reference on one interval of this campus.

    The campus renders with ``edge_pad='clamp'`` — the legacy zero-padded
    smoothing window fabricates a fleet-synchronized half-power decay at
    the trace boundaries, which no spec-compliant campus should be judged
    on."""
    n_racks = _q(1024, 256)  # quick stays large enough for fleet statistics
    duration = _q(88.0, 30.0)
    hz = 200.0
    s = faulty_campus_scenario(n_racks, duration, hz)
    sched = s.faults
    cfg = pdu.make_pdu(sample_dt=1.0 / hz, degraded_mode=True)
    spec = compliance.GridSpec.create()
    run = lambda: fleet.condition(
        s, cfg, spec, qp_iters=30, stream=fleet.StreamOptions(chunk_intervals=4)
    )
    run()  # compile
    us, res = _best_of(run, lambda r: r.campus_grid)
    UNITS["mixed_campus_faulty"] = dict(racks=n_racks, samples=s.total_samples * n_racks)

    if QUICK:
        # Megakernel-vs-ref ride-along on the fused weight operand
        # (mirrors bench_mixed_campus_health's QUICK block): one mid-trace
        # controller interval of THIS campus, with the ESS availability
        # weight rendered IN-KERNEL from the schedule's boundary-event
        # tables, through the interpret-mode Pallas megakernel vs the jnp
        # reference the engines run on CPU.  SoC path, grid, and machine
        # state bitwise.
        from repro.kernels import ops as _ops, ref as _kref

        k = int(round(cfg.controller.dt * hz))
        t0q = (s.total_samples // (2 * k)) * k
        chunk = jnp.nan_to_num(jax.jit(lambda: SC.render(s, t0q, k))(), nan=0.0)
        st = pdu.init_state(cfg, chunk[0])
        ep = cfg.ess_params
        filt = st.filter_obj
        kkw = dict(
            beta=float(ep.beta), dt=1.0 / hz, q_max=float(ep.q_max),
            eta_c=float(ep.eta_c), eta_d=float(ep.eta_d),
            p_max=float(ep.p_max), soc_min=float(ep.soc_safe_min),
            soc_max=float(ep.soc_safe_max),
        )
        ev = (
            sched.ess_start.T, sched.ess_end.T,
            jnp.ones((n_racks,), jnp.float32),
            jnp.asarray(t0q, jnp.int32), jnp.asarray(t0q + k - 1, jnp.int32),
        )
        a = (chunk, st.ess_state.g_filter, st.ess_state.soc, st.filter_state,
             filt.ad, filt.bd, filt.c[0])
        ekw = dict(ess_events=ev, ess_edge=max(s.edge_width, 1), **kkw)
        r_ref = _kref.pdu_health_sim(*a, **ekw)
        r_pl = _ops.pdu_health_sim(*a, force="pallas", **ekw)
        np.testing.assert_array_equal(np.asarray(r_ref[1]), np.asarray(r_pl[1]))
        np.testing.assert_array_equal(np.asarray(r_ref[0]), np.asarray(r_pl[0]))
        for lf_r, lf_p in zip(
            jax.tree_util.tree_leaves(r_ref[2]), jax.tree_util.tree_leaves(r_pl[2])
        ):
            np.testing.assert_array_equal(np.asarray(lf_r), np.asarray(lf_p))

    frac = np.asarray(res.ess_online_frac)
    assert np.all(np.isfinite(np.asarray(res.campus_grid))), (
        "sensor-dropout NaN leaked into the conditioned campus trace"
    )
    assert bool(res.report_grid.ramp_ok), (
        f"degraded campus failed the ramp spec at min_online_frac="
        f"{float(frac.min()):.2f}"
    )
    base = LAST_US.get("mixed_campus_fleet")
    overhead = f"{(us / base - 1) * 100:+.1f}%" if base else "-"
    return "mixed_campus_faulty", us, (
        f"racks={n_racks} min_online_frac={float(frac.min()):.2f} "
        f"mean_online_frac={float(frac.mean()):.2f} "
        f"campus_ramp={float(res.report_grid.max_ramp):.4f}/s "
        f"ok={bool(res.report_grid.ramp_ok)} "
        f"overhead_vs_clean={overhead} us_per_rack={us / n_racks:.0f}"
        + (" megakernel_agrees=True" if QUICK else "")
    )


def bench_grid_region():
    """ISSUE-8 acceptance region: 4 campuses x 256 racks of synchronized
    checkpoint stalls aggregated at one point of interconnection and
    conditioned by the region engine (per-campus scanned conditioning +
    in-scan POI fold + wide-area Goertzel mode bank in one program).  The
    headline is the POI view: ramp rate at the interconnection, the
    swing-model frequency excursion, and the inter-area mode verdict —
    lockstep checkpoints must ring the 0.1-1 Hz band (the staggered twin
    of this scenario passes; see EXPERIMENTS §Grid-region).  In ``--quick``
    mode the in-scan psum POI is re-derived host-side as the left-to-right
    weighted sum of the per-campus aggregates and asserted bitwise — the
    same engine-agreement contract the sharded parity test holds across
    8 forced devices."""
    from repro.core import grid

    n_campuses = 4
    n_racks = _q(256, 32)
    duration = _q(200.0, 100.0)
    hz = 50.0
    reg = grid.synchronized_region(
        n_campuses=n_campuses, n_racks=n_racks, duration_s=duration,
        sample_hz=hz,
    )
    cfg = pdu.make_pdu(sample_dt=1.0 / hz)
    spec = compliance.GridSpec.create()
    run = lambda: fleet.condition(reg, cfg, spec)
    run()  # compile
    us, res = _best_of(run, lambda r: r.poi_grid)
    total_racks = n_campuses * n_racks
    UNITS["grid_region"] = dict(
        racks=total_racks, samples=reg.total_samples * total_racks)

    if QUICK:
        w = np.asarray(res.weights)
        acc = jnp.float32(w[0]) * res.per_campus[0].campus_grid
        for c in range(1, n_campuses):
            acc = acc + jnp.float32(w[c]) * res.per_campus[c].campus_grid
        np.testing.assert_array_equal(np.asarray(acc), np.asarray(res.poi_grid))

    rep = res.report_poi
    mags = np.asarray(rep.mode_mags)
    assert not bool(rep.modes_ok), (
        "synchronized checkpoint region failed to ring the inter-area band"
    )
    assert bool(rep.ramp_ok), "region POI trace broke the ramp spec"
    return "grid_region", us, (
        f"campuses={n_campuses} racks={total_racks} "
        f"poi_ramp={float(rep.max_ramp):.4f}/s ramp_ok={bool(rep.ramp_ok)} "
        f"inter_area_mag={mags[0]:.4f} modes_ok={bool(rep.modes_ok)} "
        f"max_freq_dev={float(np.max(np.abs(np.asarray(res.poi_freq_dev)))):.3f}Hz "
        f"us_per_rack={us / total_racks:.0f}"
        + (" engines_agree=True" if QUICK else "")
    )


ALL = [
    bench_fig7_frequency_response,
    bench_fig9_ramp_rate,
    bench_fig10_spectrum,
    bench_fig11_burn_energy,
    bench_fig12_soc_management,
    bench_fig13_cluster_fault,
    bench_table1_mitigation_space,
    bench_appendixA_sizing,
    bench_controller_throughput,
    bench_fleet_scale,
    bench_scenario_render,
    bench_mixed_campus,
    bench_mixed_campus_health,
    bench_mixed_campus_safemode,
    bench_mixed_campus_faulty,
    bench_grid_region,
]
