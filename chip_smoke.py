"""Smoke run of the campus-conditioning main path on a TPU.

    python chip_smoke.py            # one chip: device, kernels, campus, operator loop
    python chip_smoke.py --region   # four chips: the sharded grid region only

Phases (one chip):

1. Device: a TPU, the Pallas kernels compiled (not the jnp references and
   not interpret mode), the persistent compile cache placed.
2. Kernels against their jnp references at the fleet design point
   (k = 1000 samples, 1024 racks): the interval megakernel in its three
   engine variants and the batched ADMM loop of the controller QP.
3. The 1024-rack acceptance campus through ``fleet.condition``, scanned
   engine against the one-shot reference engine.
4. The operator loop (``ConditionerService``) on the faulted campus:
   three windows, checkpoint, restore into a new service, one more window,
   bitwise against the uninterrupted run.

``--region`` runs 4 campuses x 1024 racks sharded one per chip against the
sequential engine on chip 0, and nothing else.

One process owns the chip(s) and starts no other.  Each phase checks its
own results and raises on a failure, so a failed phase exits non-zero.
The last line of standard output is one JSON object naming the device,
printed only when every phase passed.  Times printed are those of one
smoke run, not a benchmark.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import paper_benches as PB  # noqa: E402
from repro.core import compliance, controller as ctrl, fleet, grid, health as hlt, pdu  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.power import scenario as SC  # noqa: E402
from repro.serve.conditioner import ConditionerService  # noqa: E402
from repro.sharding import rules  # noqa: E402
from repro.utils import compile_cache  # noqa: E402

HZ = 200.0  # campus sample rate: k = 1000 samples per 5 s controller interval
REGION_HZ = 50.0
QP_ITERS = 30
GRID_TOL = 1e-5  # megakernel grid output and LC state (pdu_health.py docstring)
ADMM_TOL = 2e-5  # batched ADMM iterates (tests/test_pdu_health_kernel.py)
ENGINE_MAX_ULP = 4  # scanned vs oneshot campus_rack
ENGINE_GRID_TOL = 1e-6  # scanned vs oneshot campus_grid
REGION_TOL = 1e-5  # sharded vs sequential campus_grid, poi_grid, poi_freq_dev


@dataclasses.dataclass(frozen=True)
class Size:
    racks: int  # campus racks (phases 2-4)
    campus_s: float  # campus trace length [s] at HZ
    kernel_t: int  # samples in the kernel phase's interval
    window: int  # controller intervals per service window
    region_racks: int  # racks per campus of the region
    region_s: float  # region trace length [s] at REGION_HZ


# The acceptance campus of benchmarks/paper_benches.py at full width.
FULL = Size(racks=1024, campus_s=88.0, kernel_t=1000, window=4,
            region_racks=1024, region_s=200.0)
# For the CPU tests of the phases, which run the same code on tiny shapes.
SMALL = Size(racks=64, campus_s=30.0, kernel_t=40, window=1,
             region_racks=32, region_s=20.0)


def _say(*parts) -> None:
    print(*parts, flush=True)


def _timed(fn):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return time.perf_counter() - t0, out


def _max_abs(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    if a.shape != b.shape:
        raise AssertionError(f"shape {a.shape} != {b.shape}")
    return float(np.max(np.abs(a - b))) if a.size else 0.0


def _bitwise(a, b) -> bool:
    return np.array_equal(np.asarray(a), np.asarray(b))


def _all_finite(tree) -> bool:
    return all(
        bool(np.all(np.isfinite(np.asarray(x))))
        for x in jax.tree_util.tree_leaves(tree)
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)
    )


def _within(name: str, d: float, tol: float) -> None:
    if not d <= tol:  # also catches NaN
        raise AssertionError(f"{name}: max |diff| {d:.3e} exceeds {tol:.0e}")


@functools.lru_cache(maxsize=None)
def _mixed_campus(n_racks: int, duration: float):
    return PB.mixed_campus_scenario(n_racks, duration, HZ)


@functools.lru_cache(maxsize=None)
def _faulty_campus(n_racks: int, duration: float):
    return PB.faulty_campus_scenario(n_racks, duration, HZ)


# ------------------------------------------------------------------ phase 1


def phase_device(n_chips: int) -> dict:
    """Refuse anything but a TPU, then place the compile cache before the
    first compile.  Returns the device record of the last line."""
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU chip: JAX found {len(devices)} "
            f"{d0.platform} device(s) ({d0.device_kind}); this run needs a "
            "TPU and never falls back to the CPU")
    if len(devices) < n_chips:
        raise SystemExit(
            f"chip_smoke: needs {n_chips} TPU chips, JAX found {len(devices)}")
    cache = compile_cache.configure()
    n_cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    if ops._mode(None) != (True, False):
        raise AssertionError(
            f"kernels would not run as compiled Pallas: {ops._mode(None)}")
    _say(f"device: {d0.device_kind} x{len(devices)} platform={d0.platform} "
         f"jax={jax.__version__} compile_cache={cache} "
         f"(entries at start: {n_cached})")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


# ------------------------------------------------------------------ phase 2


def _megakernel_case(variant: str, size: Size):
    """Arguments of one engine-shaped megakernel call: one controller
    interval of the acceptance campus (the faulted one for ess_events)."""
    n, t = size.racks, size.kernel_t
    cfg = pdu.make_pdu(sample_dt=1.0 / HZ, track_health=True)
    if variant == "ess_events":
        s = _faulty_campus(n, size.campus_s)
        t0 = (s.total_samples // (2 * t)) * t  # an interval mid-trace
        chunk = jnp.nan_to_num(jax.jit(lambda: SC.render(s, t0, t))(), nan=0.0)
    else:
        s = _mixed_campus(n, size.campus_s)
        chunk = jax.jit(lambda: SC.render(s, 0, t))()
    st = pdu.init_state(cfg, chunk[0])
    ep, filt = cfg.ess_params, st.filter_obj
    key = jax.random.key(11)
    target = 0.02 * jax.random.normal(key, (n,), jnp.float32)
    kw = dict(
        beta=float(ep.beta), dt=1.0 / HZ, q_max=float(ep.q_max),
        eta_c=float(ep.eta_c), eta_d=float(ep.eta_d), p_max=float(ep.p_max),
        soc_min=float(ep.soc_safe_min), soc_max=float(ep.soc_safe_max),
        slew=(jnp.zeros((n,), jnp.float32), target),
    )
    if variant == "slew_health":
        kw["health"] = (hlt.step_consts(cfg.health), tuple(st.health))
    elif variant == "ess_events":
        f = s.faults
        kw["ess_events"] = (
            f.ess_start.T, f.ess_end.T, jnp.ones((n,), jnp.float32),
            jnp.asarray(t0, jnp.int32), jnp.asarray(t0 + t - 1, jnp.int32),
        )
        kw["ess_edge"] = max(s.edge_width, 1)
    elif variant == "ess_on_2d":
        kw["ess_on"] = jax.random.uniform(jax.random.key(12), (t, n))
    else:
        raise ValueError(variant)
    args = (chunk, st.ess_state.g_filter, st.ess_state.soc, st.filter_state,
            filt.ad, filt.bd, filt.c[0])
    return args, kw


def _assert_mosaic(fn, *args) -> None:
    text = jax.jit(fn).lower(*args).as_text()
    if "tpu_custom_call" not in text:
        raise AssertionError("the lowered program holds no Pallas TPU kernel")


def phase_kernels(size: Size, force: str | None = None) -> dict:
    """Megakernel (three variants) and batched ADMM against their jnp
    references.  ``force`` selects the kernel path as in ``ops``: None is
    the compiled kernel on a TPU; the CPU tests pass "pallas" (interpret
    mode).  Returns {output: max |diff|} for every compared output."""
    compiled = ops._mode(force) == (True, False)
    diffs = {}
    for variant in ("slew_health", "ess_events", "ess_on_2d"):
        args, kw = _megakernel_case(variant, size)
        kern = functools.partial(ops.pdu_health_sim, force=force, **kw)
        if compiled:
            _assert_mosaic(kern, *args)
        got = jax.block_until_ready(kern(*args))
        want = jax.block_until_ready(
            jax.jit(functools.partial(ref.pdu_health_sim, **kw))(*args))
        (grid_k, soc_k, (g_k, sf_k, x_k), h_k) = got
        (grid_r, soc_r, (g_r, sf_r, x_r), h_r) = want
        if not _all_finite(got):
            raise AssertionError(f"megakernel[{variant}]: non-finite output")
        pairs = [("grid", grid_k, grid_r), ("lc_state", x_k, x_r),
                 ("soc_path", soc_k, soc_r), ("ess_filter", g_k, g_r),
                 ("soc_final", sf_k, sf_r)]
        if h_r is not None:
            pairs += [(f"health.{nm}", a, b)
                      for nm, a, b in zip(hlt.HealthState._fields, h_k, h_r)]
        parts = []
        for nm, a, b in pairs:
            d = _max_abs(a, b)
            diffs[f"pdu_health_sim[{variant}].{nm}"] = d
            parts.append(f"{nm}={d:.3e}" + (" (bitwise)" if _bitwise(a, b) else ""))
        _say(f"kernel pdu_health_sim[{variant}] t={args[0].shape[0]} "
             f"racks={args[0].shape[1]} max|kernel-ref|: " + " ".join(parts))
        _within(f"pdu_health_sim[{variant}] grid", diffs[
            f"pdu_health_sim[{variant}].grid"], GRID_TOL)
        _within(f"pdu_health_sim[{variant}] LC state", diffs[
            f"pdu_health_sim[{variant}].lc_state"], GRID_TOL)

    cfg = pdu.make_pdu(sample_dt=1.0 / HZ)
    plan = ctrl.make_plan(cfg.controller, cfg.ess_params)
    n = size.racks
    k1, k2 = jax.random.split(jax.random.key(13))
    soc = jnp.clip(0.5 + 0.2 * jax.random.normal(k1, (n,)), 0.15, 0.85)
    u_prev = 0.3 * jax.random.normal(k2, (n,))
    q, lo, hi = ctrl._qp_state_terms(plan, soc, jnp.float32(0.5), u_prev)
    x0 = jnp.zeros_like(q)
    z0 = jnp.clip(plan.a_mat @ x0, lo, hi)
    args = (
        jnp.concatenate([plan.kkt_inv_sigma, plan.kkt_inv_at], axis=1),
        plan.a_mat[2 * plan.horizon:], plan.kkt_inv @ q, lo, hi,
        x0, z0, jnp.zeros_like(z0),
    )
    kern = functools.partial(
        ops.admm_iterate, rho=plan.rho, iters=QP_ITERS, force=force)
    if compiled:
        _assert_mosaic(kern, *args)
    got = jax.block_until_ready(kern(*args))
    want = jax.block_until_ready(jax.jit(functools.partial(
        ref.admm_iterate, rho=plan.rho, iters=QP_ITERS))(*args))
    parts = []
    for nm, a, b in zip("xzy", got, want):
        d = _max_abs(a, b)
        diffs[f"admm_iterate.{nm}"] = d
        parts.append(f"{nm}={d:.3e}" + (" (bitwise)" if _bitwise(a, b) else ""))
        _within(f"admm_iterate {nm}", d, ADMM_TOL)
    _say(f"kernel admm_iterate h={plan.horizon} racks={n} iters={QP_ITERS} "
         f"max|kernel-ref|: " + " ".join(parts))
    return diffs


# ------------------------------------------------------------------ phase 3


def phase_campus(size: Size) -> dict:
    """The acceptance campus through ``fleet.condition``: scanned engine,
    then the one-shot reference engine on the same campus."""
    s = _mixed_campus(size.racks, size.campus_s)
    cfg = pdu.make_pdu(sample_dt=1.0 / HZ, track_health=True)
    spec = compliance.GridSpec.create()
    runs = {
        "scanned": lambda: fleet.condition(
            s, cfg, spec, qp_iters=QP_ITERS,
            stream=fleet.StreamOptions(chunk_intervals=4)),
        "oneshot": lambda: fleet.condition(
            s, cfg, spec, engine="oneshot", qp_iters=QP_ITERS),
    }
    results = {}
    for engine, run in runs.items():
        cold, _ = _timed(run)
        warm, results[engine] = _timed(run)
        _say(f"campus {engine} engine (one smoke run, not a benchmark): "
             f"racks={size.racks} samples={s.total_samples} first call "
             f"{cold:.3f}s, warm call {warm:.3f}s, compile ~{cold - warm:.3f}s")
    scanned, oneshot = results["scanned"], results["oneshot"]

    ep = cfg.ess_params
    if not _all_finite((scanned.soc_mean, scanned.health_trace, scanned.state)):
        raise AssertionError("campus scanned: non-finite output")
    soc = np.asarray(scanned.state.ess_state.soc)
    sm = np.asarray(scanned.soc_mean)
    lo, hi = float(ep.soc_safe_min), float(ep.soc_safe_max)
    if not (np.all((soc >= lo) & (soc <= hi))
            and np.all((sm >= lo) & (sm <= hi))):
        raise AssertionError(f"campus scanned: SoC left [{lo}, {hi}]")
    for engine, r in results.items():
        if not _all_finite((r.campus_rack, r.campus_grid)):
            raise AssertionError(f"campus {engine}: non-finite output")
        if not bool(r.report_grid.ramp_ok):
            raise AssertionError(
                f"campus {engine}: conditioned campus fails the ramp spec "
                f"(max ramp {float(r.report_grid.max_ramp):.4f}/s)")

    a, b = np.asarray(scanned.campus_rack), np.asarray(oneshot.campus_rack)
    out = {"campus_rack": _max_abs(a, b)}
    np.testing.assert_array_max_ulp(a, b, maxulp=ENGINE_MAX_ULP)
    out["campus_grid"] = _max_abs(scanned.campus_grid, oneshot.campus_grid)
    _within("campus_grid scanned vs oneshot", out["campus_grid"], ENGINE_GRID_TOL)
    _say(f"campus engines agree: campus_rack {out['campus_rack']:.3e}"
         f"{' (bitwise)' if _bitwise(a, b) else ''}"
         f", campus_grid {out['campus_grid']:.3e}; ramp_ok=True max_ramp="
         f"{float(scanned.report_grid.max_ramp):.4f}/s raw_ramp_ok="
         f"{bool(scanned.report_rack.ramp_ok)} "
         f"qp_resid={float(scanned.max_qp_residual):.2e}")
    return out


# ------------------------------------------------------------------ phase 4


def phase_service(size: Size, ckpt_dir: str) -> None:
    """Operator loop on the faulted campus: advance three windows,
    checkpoint, restore into a new service, advance one window; bitwise
    against the uninterrupted service."""
    s = _faulty_campus(size.racks, size.campus_s)
    cfg = pdu.make_pdu(sample_dt=1.0 / HZ, degraded_mode=True)
    spec = compliance.GridSpec.create()
    make = lambda: ConditionerService(
        cfg, s, spec, chunk_intervals=size.window, qp_iters=QP_ITERS)

    svc = make()
    t0 = time.perf_counter()
    wins = [svc.advance() for _ in range(3)]
    jax.block_until_ready(wins)
    t_three = time.perf_counter() - t0
    os.makedirs(ckpt_dir, exist_ok=True)
    path = svc.checkpoint(os.path.join(ckpt_dir, "service.npz"))
    t_fourth, fourth = _timed(svc.advance)

    resumed_svc = make()
    resumed_svc.restore(path)
    resumed = jax.block_until_ready(resumed_svc.advance())

    for i, w in enumerate(wins + [fourth]):
        if not _all_finite((w.campus_rack, w.campus_grid, w.soc_mean,
                            w.ess_online_frac, w.state)):
            raise AssertionError(f"service window {i}: non-finite output")
    fields = ("campus_rack", "campus_grid", "soc_mean", "ess_online_frac")
    for nm in fields:
        if not _bitwise(getattr(fourth, nm), getattr(resumed, nm)):
            raise AssertionError(
                f"restored service drifts on {nm}: max |diff| "
                f"{_max_abs(getattr(fourth, nm), getattr(resumed, nm)):.3e}")
    for a, b in zip(jax.tree_util.tree_leaves(fourth.state),
                    jax.tree_util.tree_leaves(resumed.state)):
        if not _bitwise(a, b):
            raise AssertionError("restored service drifts on the carried state")
    frac = [float(np.asarray(w.ess_online_frac).min()) for w in wins + [fourth]]
    ramp = [bool(w.report_grid.ramp_ok) for w in wins + [fourth]]
    _say(f"service on faulted campus (one smoke run, not a benchmark): "
         f"racks={size.racks} window={size.window} intervals; 3 windows "
         f"{t_three:.3f}s (first compiles), 4th window {t_fourth:.3f}s; "
         f"min_online_frac per window {frac}; ramp_ok {ramp}; "
         f"restore+advance bitwise on {', '.join(fields)} and the state")


# ------------------------------------------------------------------ region


def phase_region(size: Size, n_campuses: int) -> dict:
    """The synchronized region sharded one campus per device against the
    sequential engine (every campus in turn on device 0)."""
    reg = grid.synchronized_region(
        n_campuses=n_campuses, n_racks=size.region_racks,
        duration_s=size.region_s, sample_hz=REGION_HZ)
    cfg = pdu.make_pdu(sample_dt=1.0 / REGION_HZ)
    spec = compliance.GridSpec.create()
    devices = jax.devices()[:n_campuses]
    mesh = rules.region_mesh(n_campuses, devices=devices)

    cold, _ = _timed(lambda: fleet.condition(reg, cfg, spec, mesh=mesh))
    warm, sharded = _timed(lambda: fleet.condition(reg, cfg, spec, mesh=mesh))
    # Where the work landed: one campus row of the result per device, and
    # (where the backend reports it) device memory used on every device.
    shards = sharded.campus_rack.addressable_shards
    placed = sorted(sh.device.id for sh in shards)
    if placed != sorted(d.id for d in devices) or any(
            sh.data.shape[0] != 1 for sh in shards):
        raise AssertionError(
            f"campus_rack is not one campus per device: "
            f"{[(sh.device.id, sh.data.shape) for sh in shards]}")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    if all(p is not None for p in peaks) and min(peaks) < 1 << 20:
        raise AssertionError(f"a device held under 1 MiB: peaks {peaks}")
    _say(f"region sharded (one smoke run, not a benchmark): "
         f"{n_campuses} campuses x {size.region_racks} racks, "
         f"samples={reg.total_samples}, first call {cold:.3f}s, warm call "
         f"{warm:.3f}s; campus rows on devices {placed}; peak bytes per "
         f"device {peaks}")

    s_cold, _ = _timed(lambda: fleet.condition(reg, cfg, spec))
    s_warm, seq = _timed(lambda: fleet.condition(reg, cfg, spec))
    _say(f"region sequential on device 0: first call {s_cold:.3f}s, "
         f"warm call {s_warm:.3f}s")

    if not _all_finite((sharded.campus_grid, sharded.poi_grid,
                        sharded.poi_freq_dev)):
        raise AssertionError("region: non-finite output")
    out = {"campus_rack": _max_abs(sharded.campus_rack, seq.campus_rack)}
    if not _bitwise(sharded.campus_rack, seq.campus_rack):
        raise AssertionError(
            f"region campus_rack not bitwise: {out['campus_rack']:.3e}")
    for nm in ("campus_grid", "poi_grid", "poi_freq_dev"):
        out[nm] = _max_abs(getattr(sharded, nm), getattr(seq, nm))
        _within(f"region {nm}", out[nm], REGION_TOL)
    mode_sh = np.asarray(sharded.report_poi.mode_ok)
    mode_seq = np.asarray(seq.report_poi.mode_ok)
    if not np.array_equal(mode_sh, mode_seq):
        raise AssertionError(f"region mode_ok {mode_sh} != {mode_seq}")
    _say(f"region sharded vs sequential: campus_rack bitwise, "
         f"campus_grid {out['campus_grid']:.3e}"
         f"{' (bitwise)' if _bitwise(sharded.campus_grid, seq.campus_grid) else ''}"
         f", poi_grid {out['poi_grid']:.3e}"
         f"{' (bitwise)' if _bitwise(sharded.poi_grid, seq.poi_grid) else ''}"
         f", poi_freq_dev {out['poi_freq_dev']:.3e}; mode_ok "
         f"{mode_sh.tolist()} both; poi ramp_ok="
         f"{bool(sharded.report_poi.ramp_ok)}")
    return out


# --------------------------------------------------------------------- main


def main(argv=None, *, size: Size = FULL) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--region", action="store_true",
        help="run only the grid region sharded over four chips, against "
        "the sequential engine")
    args = parser.parse_args(argv)
    n_chips = 4 if args.region else 1
    t0 = time.perf_counter()
    device = phase_device(n_chips)
    if args.region:
        phase_region(size, n_chips)
    else:
        phase_kernels(size)
        phase_campus(size)
        phase_service(size, os.path.join(ROOT, ".chip_smoke"))
    _say(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    _say(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
