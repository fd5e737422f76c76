"""What the program says of its own start and of its compiles
(``mxnet_tpu/obs/startup.py``): the gauge ``mx_setup_seconds{phase}`` and
the counters ``mx_compile_seconds{program, stage}`` and
``mx_compiles_total{program, cache}``, read off ``obs.registry.snapshot()``
when a reader asks (``facts["registry"]`` where a test hands one in).  A
program without the families (the parent of the PR that added them) reads
0.0 everywhere, never None: the metrics move ``setup_s``, which every cell
reports.
"""
from __future__ import annotations

EAGER = "(eager)"
OUTSIDE = "(outside)"


def snapshot(facts):
    if "registry" not in facts:
        from mxnet_tpu import obs

        facts["registry"] = obs.registry.snapshot()
    return facts["registry"]


def rows(facts, family):
    """``[(labels, value)]`` of one family; empty where it is not there."""
    fam = snapshot(facts).get(family)
    return [(r["labels"], float(r["value"]))
            for r in fam["series"]] if fam else []


def setup_seconds(facts, prefix):
    """Seconds of the phases whose name begins with ``prefix``."""
    return sum((v for labels, v in rows(facts, "mx_setup_seconds")
                if labels["phase"].startswith(prefix)), 0.0)


def compile_seconds(facts, stages, programs=None):
    """Seconds of ``stages`` booked under a program's name or ``(eager)``
    (``programs``: ``"named"`` or ``"eager"`` for one side alone); what ran
    outside the program (the harness's weights, the reference) is left
    out."""
    total = 0.0
    for labels, v in rows(facts, "mx_compile_seconds"):
        who = labels["program"]
        if who == OUTSIDE or labels["stage"] not in stages:
            continue
        if programs is None or (who == EAGER) == (programs == "eager"):
            total += v
    return total


def compiles_per_program(facts):
    """Backend compiles and cache reads under a program's name, over the
    distinct names: 1 where every program was loaded once."""
    by = {}
    for labels, v in rows(facts, "mx_compiles_total"):
        if labels["program"] not in (EAGER, OUTSIDE):
            by[labels["program"]] = by.get(labels["program"], 0.0) + v
    return sum(by.values()) / len(by) if by else 0.0


def named_pct(facts):
    """Of the compile-stage seconds that are the program's, the share under
    a program's name."""
    stages = ("trace", "lower", "compile", "cache_read")
    named = compile_seconds(facts, stages, "named")
    whole = named + compile_seconds(facts, stages, "eager")
    return 100.0 * named / whole if whole else 0.0
