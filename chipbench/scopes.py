"""Split a traced window by the program's own spans and scopes.

    python3 chipbench/scopes.py --workload <cell> --seed <n> [--keep <dir>]

The program names its host phases with ``repro.*`` spans and the device
work of its engines with ``repro.*`` scopes; a scope reaches the device
trace as the HLO ``op_name`` path of each op it emitted.  ``trace.py``
keeps only the benchmark's ``bench.*`` spans and each op's short name, so
this module reads the profiler's trace file itself.

The command runs the cell's set-up as ``run.py`` does, then two windows of
the traffic's ``trace_seconds``: one without the profiler, one under it.
The last line of standard output is one JSON object: each window's calls,
rate and call times; per call and averaged over the cell's chips, each
scope's self time (``qp_prep`` is the controller scope without the ADMM
kernel), the device busy time, the two kernels' time, the idle time whose
gap middle falls inside ``repro.condition``, and the idle time by the
innermost span open on the host; and per call, the number of each span
and its host time.
``--keep`` copies the trace file into a directory.  Like ``run.py`` it
exits non-zero where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import glob
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import trace as T  # noqa: E402

PROGRAM = "repro."
SPAN_PREFIXES = (T.HOST_SPAN_PREFIX, PROGRAM)
# The stat of a device op's event metadata that holds its HLO ``op_name``.
SCOPE_STAT = "tf_op"
SCOPES = {"render_ms": ("render", ()), "observers_ms": ("observers", ()),
          "qp_prep_ms": ("controller", ("admm_iterate",))}


@dataclasses.dataclass
class Scoped:
    ops: dict  # device index -> [(op_name, start_ns, end_ns, scope_path)]
    spans: list  # (name, start_ns, end_ns): the bench.* and repro.* host spans
    window: tuple  # (start_ns, end_ns) of the traced window


def _varint(b, i):
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b):
    """(field number, value) of a protobuf message: ints for varints,
    bytes for length-delimited fields; fixed-width fields are skipped."""
    i = 0
    while i < len(b):
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(b, i)
        elif kind == 2:
            n, i = _varint(b, i)
            v, i = b[i:i + n], i + n
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield key >> 3, v


def op_scopes(xspace: bytes) -> dict:
    """HLO text of each device op -> its scope path (``op_name``).

    ``ProfileData`` shows an event's own stats only; the op_name is a stat
    of the event's metadata, so this reads the device planes' metadata
    from the serialized ``XSpace`` (tsl ``xplane.proto``: XSpace.planes 1;
    XPlane.name 2, event_metadata 4, stat_metadata 5; XEventMetadata.name
    2, display_name 4, stats 5; XStat.metadata_id 1, str_value 5,
    ref_value 7).  A text two ops share with different paths maps to ''.
    """
    out = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != 1:
            continue
        fields = list(_fields(plane))
        name = next((bytes(v).decode() for g, v in fields if g == 2), "")
        if not T.DEVICE_PLANE.match(name):
            continue
        stat_names = {}
        for g, entry in fields:
            if g == 5:
                value = next(v for k, v in _fields(entry) if k == 2)
                meta = dict(_fields(value))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        for g, entry in fields:
            if g != 4:
                continue
            meta = list(_fields(next(v for k, v in _fields(entry) if k == 2)))
            path = ""
            for k, stat in meta:
                st = dict(_fields(stat)) if k == 5 else {}
                if stat_names.get(st.get(1)) == SCOPE_STAT:
                    path = bytes(st[5]).decode() if 5 in st else stat_names.get(st.get(7), "")
            for k, text in meta:
                if k in (2, 4):
                    text = bytes(text).decode()
                    out[text] = path if out.get(text, path) == path else ""
    return out


def load(path: str, window_span: str = "bench.window") -> Scoped:
    """Read the newest ``.xplane.pb`` under ``path``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no profiler trace under {path}")
    with open(files[-1], "rb") as f:
        raw = f.read()
    pd = ProfileData.from_serialized_xspace(raw)
    scope_of = op_scopes(raw)
    ops, spans = {}, []
    for plane in pd.planes:
        m = T.DEVICE_PLANE.match(plane.name)
        if m:
            ops[int(m.group(1))] = [
                (T.op_name(e.name), int(e.start_ns), int(e.end_ns), scope_of.get(e.name, ""))
                for line in plane.lines if line.name == "XLA Ops" for e in line.events]
        elif plane.name.startswith("/host:"):
            spans += [(e.name, int(e.start_ns), int(e.end_ns)) for line in plane.lines
                      for e in line.events if e.name.startswith(SPAN_PREFIXES)]
    win = [(s, e) for n, s, e in spans if n == window_span]
    if not win:
        raise ValueError(f"the trace holds no {window_span!r} span")
    return Scoped(ops=ops, spans=spans, window=win[0])


def _clip(ops, window):
    lo, hi = window
    return [(n, max(s, lo), min(e, hi), p) for n, s, e, p in ops if e > lo and s < hi]


def scope_ns(ops, window, scope: str, exclude=()):
    """Self time (net of the ops nested in each) of the ops whose scope
    path holds ``repro.<scope>``, less those named in ``exclude``; None
    where no op did."""
    evs = _clip(ops, window)
    own = T.self_times([(i, s, e) for i, (_, s, e, _) in enumerate(evs)])
    tag = PROGRAM + scope
    mine = [own[i] for i, (n, _, _, p) in enumerate(evs)
            if tag in p.split("/") and n not in exclude]
    return sum(mine) if mine else None


def _idle_gaps(ops, window):
    return T.gaps([(s, e) for _, s, e, _ in _clip(ops, window)], *window)


def idle_under(ops, window, spans, name: str):
    """Device idle time whose gap middle falls inside a span ``name``;
    None where no such span is open in the window."""
    mine = [(a, b) for n, a, b in spans if n == name]
    if not mine:
        return None
    return sum(e - s for s, e in _idle_gaps(ops, window)
               if any(a <= (s + e) // 2 < b for a, b in mine))


def idle_by_span(ops, window, spans, window_span: str = "bench.window") -> dict:
    """Device idle time by the innermost host span, benchmark's or
    program's, open at the middle of each gap."""
    inner = sorted((s for s in spans if s[0] != window_span), key=lambda s: s[2] - s[1])
    out = collections.Counter()
    for s, e in _idle_gaps(ops, window):
        mid = (s + e) // 2
        out[next((n for n, a, b in inner if a <= mid < b), "outside any span")] += e - s
    return dict(out)


def per_call(tr: Scoped, devices, calls: int) -> dict:
    """Milliseconds per call of each reading, averaged over ``devices``;
    a reading no device had is left out."""
    from chipbench.metrics import _common as C

    def mean(vals):
        vals = [v for v in vals if v is not None]
        return sum(vals) / len(vals) / calls / 1e6 if vals and calls else None

    readings = {}
    for name, (scope, exclude) in SCOPES.items():
        readings[name] = mean(scope_ns(tr.ops[i], tr.window, scope, exclude)
                              for i in devices)
    readings["facade_idle_ms"] = mean(
        idle_under(tr.ops[i], tr.window, tr.spans, PROGRAM + "condition") for i in devices)
    readings["busy_ms"] = mean(
        T.union_ns([(s, e) for _, s, e, _ in _clip(tr.ops[i], tr.window)]) for i in devices)
    readings["kernels_ms"] = mean(
        sum(e - s for n, s, e, _ in _clip(tr.ops[i], tr.window)
            if n in (C.MEGAKERNEL, C.ADMM)) for i in devices)
    return {k: v for k, v in readings.items() if v is not None}


def _window(win, dep, call_seconds) -> dict:
    import numpy as np

    lat = np.asarray(win.latencies) * 1e3
    return {"calls": len(lat), "wall_s": win.wall,
            "rack_s_per_s": dep.n_racks * call_seconds * len(lat) / win.wall,
            "call_ms_p50": float(np.percentile(lat, 50)),
            "call_ms_p95": float(np.percentile(lat, 95))}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--keep", default=None, help="copy the trace file into this directory")
    args = p.parse_args(argv)
    import jax

    from chipbench import program, run, spec, stream

    cell = spec.resolve(ROOT, args.workload)
    devices = run.require_chips(jax, cell.chips)[:cell.chips]
    from repro.utils import compile_cache

    compile_cache.configure()
    dep = spec.builder(cell.config["builder"]).build(cell.config, args.seed)
    system = program.System(dep, devices)
    w, _ = stream.geometry(dep, cell.traffic)
    stream.warm_up(system, cell.traffic, run._compile_counter(jax))
    seconds = float(cell.traffic["trace_seconds"])
    plain = stream.drive(system, cell.traffic, seconds)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-scopes-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            traced = stream.drive(system, cell.traffic, seconds, annotate=True)
        jax.profiler.stop_trace()
        tr = load(trace_dir)
        if args.keep:
            os.makedirs(args.keep, exist_ok=True)
            for f in glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True):
                shutil.copy(f, args.keep)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    used = [i for i in (d.id for d in devices) if i in tr.ops]
    calls = len(traced.positions)
    idle = collections.Counter()
    for i in used:
        idle.update(idle_by_span(tr.ops[i], tr.window, tr.spans))
    count, host_ns = collections.Counter(), collections.Counter()
    for n, s, e in T.clip(tr.spans, tr.window):
        count[n] += 1
        host_ns[n] += e - s
    line = {
        "device": jax.devices()[0].device_kind,
        "windows": {"plain": _window(plain, dep, w * dep.dt),
                    "traced": _window(traced, dep, w * dep.dt)},
        "per_call_ms": per_call(tr, used, calls),
        "idle_by_span_ms": {k: v / max(len(used), 1) / calls / 1e6
                            for k, v in idle.most_common()},
        "spans_per_call": {k: {"n": count[k] / calls, "ms": host_ns[k] / calls / 1e6}
                           for k in sorted(count)},
    }
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
