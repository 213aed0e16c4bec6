"""The one traffic generator: a closed-loop replay of the campus stream.

An operator replays (or catches up on) a campus stream in windows of
``window_intervals`` controller intervals: each call conditions the next
window from the state the previous call returned, and is issued when the
previous one has returned.  At the end of the stream the replay wraps to
sample 0 and restarts from the set-up state.  A traffic file sets:

* ``window_intervals``: controller intervals per call;
* ``chunk_intervals``: the engine's chunk (intervals per scan step);
* ``warmup_calls``: the least number of calls made in set-up, from the
  stream's start; set-up goes on calling while a call still loads a
  program (a carried state can come back with another placement);
* ``trace_seconds``: how long a ``--trace 1`` run traces.

The stream's length must hold a whole number of windows, so that every
call has one shape.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import jax


@dataclasses.dataclass
class Window:
    positions: list  # start sample of each call
    latencies: list  # seconds from issue to the whole result ready
    results: list  # the facade's result of each call
    wall: float  # seconds from the first issue to the last result


def geometry(dep, traffic: dict) -> tuple:
    """(samples per call, calls per lap of the stream)."""
    w = int(traffic["window_intervals"]) * dep.k
    if dep.total_samples % w:
        raise ValueError(
            f"the stream's {dep.total_samples} samples do not hold a whole "
            f"number of {w}-sample windows")
    return w, dep.total_samples // w


def drive(system, traffic: dict, seconds: float, *, annotate=False) -> Window:
    """Calls back to back for ``seconds`` (the call in flight at the end
    completes and counts), from sample 0 and the set-up state."""
    from chipbench import program

    w, laps = geometry(system.dep, traffic)
    chunk = int(traffic["chunk_intervals"])
    span = jax.profiler.TraceAnnotation if annotate else (lambda name: contextlib.nullcontext())
    state = system.state0
    win = Window(positions=[], latencies=[], results=[], wall=0.0)
    j = 0
    t_first = time.perf_counter()
    while True:
        pos = (j % laps) * w
        t0 = time.perf_counter()
        with span("bench.call"):
            res = system.call(state, pos, pos + w, chunk)
        with span("bench.wait"):
            program.block(res)
        t1 = time.perf_counter()
        with span("bench.wrap"):
            win.positions.append(pos)
            win.latencies.append(t1 - t0)
            win.results.append(res)
            j += 1
            state = system.state0 if j % laps == 0 else res.state
        if t1 - t_first >= seconds:
            break
    win.wall = t1 - t_first
    return win


def warm_up(system, traffic: dict, compiles: list, most: int = 8) -> int:
    """Set-up calls along the stream until one loads no program; returns
    how many were made.  ``compiles`` counts programs loaded so far."""
    from chipbench import program

    w, laps = geometry(system.dep, traffic)
    chunk = int(traffic["chunk_intervals"])
    state, n = system.state0, 0
    while n < most:
        before = compiles[0]
        pos = (n % laps) * w
        res = system.call(state, pos, pos + w, chunk)
        program.block(res)
        n += 1
        state = system.state0 if n % laps == 0 else res.state
        if n >= int(traffic["warmup_calls"]) and compiles[0] == before:
            break
    return n
