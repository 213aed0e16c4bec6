"""The program's tracing: named spans on the host, named scopes on the device.

Two primitives, each under the ``repro.`` prefix so a trace reader can
tell the program's names from anyone else's:

* ``span(name)``: a ``jax.profiler.TraceAnnotation`` for host code.  It
  writes a TraceMe event on the profiler's host plane, on the same clock
  as the device's ``XLA Ops`` line, so a reader can name the device's idle
  gaps by the host phase that was running.  It never blocks: it times the
  host's own work (checks, dispatch, eager glue), and the device runs on
  behind it.  With no profiler session open a TraceMe costs next to
  nothing, so there is no switch.
* ``scope(name)``: ``jax.named_scope`` for code traced inside a jit.  The
  name lands in the ``op_name`` metadata of every HLO op the body emits
  (``jit(run)/while/body/.../repro.render/...``), inside scanned and
  looped bodies too.  It is metadata only: it changes neither the
  compiled program's numerics nor its run time.

PERF.md's "Spans and scopes" table lists each span and scope with the
measurement that reads it.
"""
from __future__ import annotations

import jax

PREFIX = "repro."


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span ``repro.<name>`` around the enclosed code."""
    return jax.profiler.TraceAnnotation(PREFIX + name)


def scope(name: str):
    """A device scope ``repro.<name>`` for the ops traced in the body."""
    return jax.named_scope(PREFIX + name)
