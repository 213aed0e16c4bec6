"""End-to-end metrics, one file per metric, found by name.  Each module
has ``read(run) -> float``, taken over all the calls and all the time of
the measured window (``run.window``), on the host clock."""
