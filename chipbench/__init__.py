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
edits no file that is here.  To bring a configuration, a later PR adds:

1. ``configs/<name>.json``: the sizes as run; ``builder``, ``reference`` and
   ``counts`` (``"module:function"`` of its FLOPs a training sample and
   bytes a decode tick); ``limits`` by loop driver, each ``{"value", "why"}``
   set from chip readings (``python -m chipbench.control`` gives the upper
   one); each ``reduced`` key, a count of layers or experts, beside its cut.
2. ``reference/<family>.py`` and, for a new kind of model, a counts module.
3. ``traffic/<mix>.json``: ``driver``, shapes, ``mesh`` (axis sizes) if the
   cell shards more than the batch, ``trace_seconds`` / ``trace_ticks``.  A
   new loop is ``drivers/<driver>.py`` with ``run`` and ``control_case``.
4. Entries appended to ``BENCHMARK.json``: the configuration, the cell, and
   the cell's name on the ``workloads`` list of each metric it reports.
5. ``tests/chipbench/test_<name>.py``: its published numbers pinned, and its
   reference against the system at a tiny size; no test that is here changes.
"""
