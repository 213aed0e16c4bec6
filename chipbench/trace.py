"""Reduce a JAX profiler trace to what the per-layer metrics read.

On a TPU the trace holds one plane per chip (``/device:TPU:<i>``) with an
``XLA Ops`` line (one event per executed HLO op, nested: a loop's event
covers its body's) and an ``XLA Modules`` line (one event per program
execution), and a ``/host:CPU`` plane whose threads carry the benchmark's
own ``TraceAnnotation`` spans (``bench.*``), all on one clock in
nanoseconds.  An op event is named by its HLO text, ``%<name>.<n> =
...``; ``op_name`` keeps ``<name>``.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)*(?: = |$)")
HOST_SPAN_PREFIX = "bench."


def op_name(hlo_text: str) -> str:
    m = OP_NAME.match(hlo_text)
    return m.group(1) if m else hlo_text.split(" ", 1)[0]


def union_ns(intervals) -> int:
    """Length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: int, hi: int):
    """The [start, end) stretches of [lo, hi) that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def self_times(events) -> dict:
    """Time per op name net of the ops nested inside it (a loop's own
    overhead, not its body's)."""
    own = collections.Counter()
    stack = []  # (name, end) of the ops that enclose the current one
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        own[name] += e - s
        if stack:
            own[stack[-1][0]] -= e - s
        stack.append((name, e))
    return dict(own)


@dataclasses.dataclass
class Device:
    ops: list  # (op_name, start_ns, end_ns)
    modules: list  # (module_name, start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    devices: dict  # device index -> Device
    spans: list  # (span_name, start_ns, end_ns) of the benchmark's host spans
    window: tuple  # (start_ns, end_ns) of the traced window


def load(path: str, window_span: str = "bench.window") -> Trace:
    """Read the newest ``.xplane.pb`` under ``path``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no profiler trace under {path}")
    pd = ProfileData.from_file(files[-1])
    devices, spans = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = Device(ops=[], modules=[])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev.ops = [(op_name(e.name), int(e.start_ns), int(e.end_ns))
                               for e in line.events]
                elif line.name == "XLA Modules":
                    dev.modules = [(e.name.split("(")[0], int(e.start_ns), int(e.end_ns))
                                   for e in line.events]
            devices[int(m.group(1))] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_SPAN_PREFIX):
                        spans.append((e.name, int(e.start_ns), int(e.end_ns)))
    win = [(s, e) for n, s, e in spans if n == window_span]
    if not win:
        raise ValueError(f"the trace holds no {window_span!r} span")
    return Trace(devices=devices, spans=spans, window=win[0])


def clip(events, window):
    lo, hi = window
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events if e > lo and s < hi]


def busy_ns(dev: Device, window) -> int:
    return union_ns([(s, e) for _, s, e in clip(dev.ops, window)])


def idle_by_span(dev: Device, trace: Trace) -> dict:
    """Device idle time inside the window, by the innermost benchmark span
    open on the host at the middle of each gap."""
    lo, hi = trace.window
    spans = sorted((s for s in trace.spans if s[0] != "bench.window"),
                   key=lambda s: s[2] - s[1])
    out = collections.Counter()
    for s, e in gaps([(a, b) for _, a, b in clip(dev.ops, trace.window)], lo, hi):
        mid = (s + e) // 2
        name = next((n for n, a, b in spans if a <= mid < b), "outside any span")
        out[name] += e - s
    return dict(out)
