"""Per-layer metric readers, one file per metric, found by name.

Each module has ``read(ctx) -> float | None``; ``None`` means the trace
held nothing to read and the metric is left out of the line.
"""
