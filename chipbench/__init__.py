"""chipbench — the repo's chip benchmark: one cell, one run, one JSON line.

``python -m chipbench.run --workload <name> --seed <n> --seconds <s>
--trace <0|1>`` runs one entry of ``BENCHMARK.json``'s ``workloads`` once on
the TPU.  Everything that decides a number lives here, where a PR that
claims a gain cannot edit it: traffic generation (``traffic.py``), the rate
arithmetic (``timing.py``), the trace reduction (``trace.py``), the table of
peaks (``peaks.json``), operation and byte counts (``work.py``), the plain
references (``reference/``) and the comparison that decides ``correct``
(``correct.py``).  From ``mxnet_tpu`` it takes only the system under test.

A cell, a configuration, a traffic mix and a per-layer metric are each a
file found by its name in ``BENCHMARK.json`` (``manifest.py``); adding one
edits no file that is here.
"""
