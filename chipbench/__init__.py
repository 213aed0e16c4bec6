"""Chip benchmark of the campus power conditioner.

``python chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the TPU it is
started on.  Everything that belongs to one configuration, traffic mix or
per-layer metric lives in a file of its own, found by name:

* ``configs/<config>.json``: the deployment as it is run, with its source;
  ``builders/<builder>.py`` turns it into per-rack data from the seed.
* ``traffic/<mix>.json``: parameters of the one stream generator
  (``stream.py``).
* ``metrics/<metric>.py``: a reader of one per-layer metric.
* ``limits/<cell>.json``: the limits of the comparison that decides
  ``correct``, with the readings they were set from.

``reference/`` is the plain reference the comparison runs against; it
imports nothing of the program.
"""
