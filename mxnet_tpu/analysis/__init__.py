"""Static program analysis over jaxprs, lowered StableHLO and compiled HLO.

The framework's performance headlines are *program-level invariants* —
fewer collective bytes than the GSPMD baseline, donated buffers aliased in
place, zero host syncs per step, O(1)-in-prefix decode FLOPs, one trace
per program shape.  This package turns each into a static audit that runs
on the 8-virtual-device CPU mesh, so a sharding-rule edit or a jit
cache-key drift fails CI instead of waiting for the TPU rig:

* :mod:`~mxnet_tpu.analysis.hlo_parse` — the text parsing layer (grown
  out of ``parallel/hlo_stats.py``, which re-exports it);
* :mod:`~mxnet_tpu.analysis.artifact` — :class:`ProgramArtifact`, one
  canonical program's jaxpr/StableHLO/HLO surfaces + metadata;
* :mod:`~mxnet_tpu.analysis.framework` — :class:`Pass`,
  :class:`Finding`, suppression matching and :func:`run_passes`;
* :mod:`~mxnet_tpu.analysis.passes` — the shipped passes (donation,
  collective budget, retrace, host sync, FLOP/dtype, cache bytes, tuner
  coverage, sharding coverage, drift) plus the drift-snapshot
  record/hash helpers;
* :mod:`~mxnet_tpu.analysis.schedule` — the compiled-HLO schedule model
  (async start/done pairing + compute shadows) and the schedule pass;
* :mod:`~mxnet_tpu.analysis.retrace` — :class:`RetraceAuditor` for
  instrumenting arbitrary jitted functions;
* :mod:`~mxnet_tpu.analysis.programs` — builders for the five canonical
  programs ``tools/mxlint.py`` audits.

Entry point: ``tools/mxlint.py`` (CLI, bench JSON contract, ``--smoke``
tier-1 hook); library use::

    from mxnet_tpu import analysis
    report = analysis.run_passes([module.program_artifacts()["train_step"]],
                                 budgets=analysis.load_budgets())
    assert report.ok(), report.format_text()
"""
from __future__ import annotations

import json
import os

from .artifact import ProgramArtifact, artifact_from_jit
from .cost import artifact_cost, aval_bytes, program_cost
from .framework import (Finding, Pass, Report, SEVERITIES, default_passes,
                        run_passes)
from .passes import (CacheBytesPass, CollectiveBudgetPass, DonationPass,
                     DriftPass, FlopDtypePass, HostSyncPass, RetracePass,
                     ShardingCoveragePass, record_snapshot, snapshot_hash)
from .retrace import RetraceAuditor, arg_signature, signature_diff
from .schedule import ScheduleModel, SchedulePass, parse_schedule

__all__ = [
    "CacheBytesPass", "CollectiveBudgetPass", "DonationPass", "DriftPass",
    "Finding", "FlopDtypePass", "HostSyncPass", "Pass", "ProgramArtifact",
    "Report", "RetraceAuditor", "RetracePass", "SEVERITIES",
    "ScheduleModel", "SchedulePass", "ShardingCoveragePass",
    "arg_signature", "artifact_cost",
    "artifact_from_jit", "aval_bytes", "default_passes", "load_budgets",
    "load_snapshot", "parse_schedule", "program_cost", "record_snapshot",
    "resolve_budgets_path", "run_passes", "signature_diff",
    "snapshot_hash",
]

_DEFAULT_BUDGETS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "benchmarks", "budgets.json")


def resolve_budgets_path(path=None):
    """The budget file location: explicit ``path`` argument, the
    ``MXNET_ANALYSIS_BUDGETS`` env knob, the repo default — the ONE
    resolution rule, shared by :func:`load_budgets` and
    ``tools/mxlint.py --update-budgets`` so reads and writes cannot
    diverge."""
    from .. import config as _config

    return path or _config.get("MXNET_ANALYSIS_BUDGETS") or _DEFAULT_BUDGETS


def load_budgets(path=None):
    """Parse the committed budget file (``benchmarks/budgets.json``).

    Resolved via :func:`resolve_budgets_path`.  A missing file returns
    ``{}`` — the budget pass then reports per-program "no committed
    budget" findings rather than crashing.
    """
    path = resolve_budgets_path(path)
    if not os.path.exists(path):
        return {}
    with open(path) as f:
        return json.load(f)


def load_snapshot(path):
    """Parse a drift snapshot (``mxlint --record`` output) and verify
    its content hash.

    A mismatch raises ``ValueError``: the baseline was hand-edited, and
    a gate whose baseline can be quietly nudged is no gate — intentional
    changes re-record through the tool.
    """
    from .passes import snapshot_hash

    with open(path) as f:
        snap = json.load(f)
    want = snap.get("content_hash")
    have = snapshot_hash(snap)
    if want != have:
        raise ValueError(
            "drift snapshot %s content hash mismatch (recorded %s, "
            "computed %s) — the file was edited by hand; re-record it "
            "with tools/mxlint.py --record" % (path, want, have))
    return snap
