"""Run one cell of BENCHMARK.json on the TPU chips this process holds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's deployment from the seed, its first state and
every program the window runs (warm-up calls, counted in ``setup_s``).
The window then drives the stream for ``--seconds`` (``--trace 1``: the
traffic file's ``trace_seconds``, under the profiler).  Afterwards the
reference recomputes every position the window reached and each call is
compared with it.  The last line of standard output is one JSON object:
``correct``, ``attempted`` (calls), ``failed`` (calls off the reference),
``metrics``, ``device``, ``breakdown`` (traced runs) and ``checks``, each
compared number beside its limit, which standard error also ends with.

Exits non-zero, and prints no result, where JAX finds no TPU or fewer
chips than the cell asks for.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)


def _say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Run:
    dep: object
    window: object
    call_seconds: float
    setup_s: float


@dataclasses.dataclass
class TraceContext:
    trace: object
    calls: int
    devices: list
    k: int
    racks_per_chip: int
    intervals_per_call: int
    wear: bool
    horizon: int
    qp_iters: int
    peaks: dict


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def require_chips(jax, chips: int) -> list:
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise SystemExit(
            f"chipbench: no TPU: JAX found {len(devices)} {d0.platform} device(s); "
            "the benchmark never falls back to another platform")
    if len(devices) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX found {len(devices)}")
    return devices


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks)) if peaks else 0


def _compile_counter(jax):
    count = [0]

    def listener(event, duration, **kw):
        if "backend_compile" in event:
            count[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    return count


def main(argv=None, *, device_check: bool = True, overrides: dict | None = None,
         fault=None) -> int:
    """``device_check=False``, ``overrides`` (configuration keys, e.g. a
    few racks) and ``fault`` (a function applied to the program object
    before the window) serve the CPU rehearsals of the tests."""
    args = parse(argv)
    t_start = time.perf_counter()
    import jax

    from chipbench import compare, roofline, spec, stream

    cell = spec.resolve(ROOT, args.workload)
    devices = require_chips(jax, cell.chips) if device_check else jax.devices()
    devices = devices[:cell.chips] if len(devices) >= cell.chips else devices

    cache = None
    if device_check:
        from repro.utils import compile_cache

        cache = compile_cache.configure()
        # Keep even the facade's small eager programs, so that only a
        # cell's first run in a checkout compiles.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from chipbench import program

    config = {**cell.config, **(overrides or {})}
    dep = spec.builder(config["builder"]).build(config, args.seed)
    system = program.System(dep, devices)
    if fault is not None:
        fault(system)
    w, laps = stream.geometry(dep, cell.traffic)
    compiles = _compile_counter(jax)
    warm = stream.warm_up(system, cell.traffic, compiles)
    setup_s = time.perf_counter() - t_start
    # The deployment and one call's working set; the window's peak adds
    # every call's result, which the comparison keeps.
    setup_peak = _memory_peak(devices)
    _say(f"set-up {setup_s:.3f}s: {dep.n_racks} racks x {dep.total_samples} samples, "
         f"{w} samples per call, {laps} calls per lap, {warm} warm-up calls, "
         f"{compiles[0]} programs loaded, memory peak {setup_peak} bytes, "
         f"compile cache {cache}")
    compiles[0] = 0
    trace_dir = None
    if args.trace:
        seconds = min(args.seconds, float(cell.traffic["trace_seconds"]))
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            win = stream.drive(system, cell.traffic, seconds, annotate=True)
        jax.profiler.stop_trace()
    else:
        win = stream.drive(system, cell.traffic, args.seconds)
    n_compiles = compiles[0]
    memory_peak = _memory_peak(devices)
    _say(f"window {win.wall:.3f}s: {len(win.positions)} calls, "
         f"{n_compiles} compilations inside the window")

    # What the window produced, to the host; then free the program's state
    # before the reference runs.
    prog = [program.outputs(r, dep) for r in win.results]
    win.results.clear()
    system = None

    from chipbench.reference import conditioner

    ref = conditioner.Reference(dep)
    t_ref = time.perf_counter()
    n_ref = max(win.positions) // w + 1
    ref_calls = ref.run(n_ref, w)
    per_call, at = [], {}
    for pos, out in zip(win.positions, prog):
        j = pos // w
        if j not in at:  # a position the window reached once per lap
            at[j] = (compare.reference_reports(dep, ref_calls[j]),
                     ref.wear_snapshot(ref_calls[j]["state"]))
        per_call.append(compare.numbers(dep, out, ref_calls[j], *at[j]))
    correct, failed, worst = compare.judge(per_call, cell.limits)
    _say(f"reference: {n_ref} calls in {time.perf_counter() - t_ref:.3f}s")

    d0 = jax.devices()[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    run = Run(dep=dep, window=win, call_seconds=w * dep.dt, setup_s=setup_s)
    metrics = {}
    breakdown = None
    if args.trace:
        from chipbench import trace as T

        try:
            tr = T.load(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        used = [d.id for d in devices]
        ctx = TraceContext(
            trace=tr, calls=len(win.positions), devices=[i for i in used if i in tr.devices],
            k=dep.k, racks_per_chip=dep.campuses[0].n_racks,
            intervals_per_call=int(cell.traffic["window_intervals"]),
            wear=bool(dep.pdu["track_health"]), horizon=int(dep.pdu["controller"]["horizon"]),
            qp_iters=dep.qp_iters,
            peaks=roofline.peaks(d0.device_kind) if device_check else {"flops": 1.0, "bytes_per_s": 1.0},
        )
        for m in cell.per_layer:
            v = spec.reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        lo, hi = tr.window
        busy = [T.busy_ns(tr.devices[i], tr.window) for i in ctx.devices]
        device["busy_s"] = (sum(busy) / len(busy) if busy else 0.0) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        for i in ctx.devices:
            per = {m: spec.reader(m).read(dataclasses.replace(ctx, devices=[i]))
                   for m in ("idle_share", "megakernel_ms", "xla_ms")}
            _say(f"device {i}: " + " ".join(f"{k}={v}" for k, v in per.items()))
        breakdown = _breakdown(T, tr, ctx.devices)
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": spec.end_to_end(m["name"]).read(run),
                                  "unit": m["unit"]}

    checks = {n: {"value": worst[n], "limit": cell.limits.get(n)} for n in worst}
    for n, c in checks.items():
        _say(f"check {n}: {c['value']!r} limit {c['limit']!r}")
    _say(f"correct={correct} attempted={len(per_call)} failed={failed}")
    line = {"correct": correct, "attempted": len(per_call), "failed": failed,
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    print(json.dumps(line), flush=True)
    return 0


def _breakdown(T, tr, devices) -> dict:
    """Device ops by self time and idle time by host span, averaged over
    the chips used, ten of each."""
    import collections

    ops, idle = collections.Counter(), collections.Counter()
    for i in devices:
        dev = tr.devices[i]
        ops.update(T.self_times(T.clip(dev.ops, tr.window)))
        idle.update(T.idle_by_span(dev, tr))
    n = max(len(devices), 1)
    return {
        "device_ops": [[k, v * 1e-9 / n] for k, v in ops.most_common(10)],
        "idle_gaps": [[k, v * 1e-9 / n] for k, v in idle.most_common(10)],
    }


if __name__ == "__main__":
    sys.exit(main())
