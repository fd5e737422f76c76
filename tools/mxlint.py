#!/usr/bin/env python
"""mxlint — static program-analysis lint over the framework's canonical
compiled programs.

Builds the thirteen canonical programs on the current backend (``--smoke``
forces the 8-virtual-device CPU platform so the ring×TP and
expert-parallel MoE mesh programs exist on one box; the speculative
trio — draft_step / verify_step / decode_step_q — is driven by a real
mixed-length speculative serve, the paged pair — paged_decode_step /
paged_verify_step — by a real shared-prefix paged serve, ckpt_train_step
by a real fit under async fenced checkpointing, and moe_train_step by a
real top-2 capacity-routed MoE LM step whose explicit all-to-all
dispatch the collective pass budgets), snapshots each as a
:class:`~mxnet_tpu.analysis.artifact.ProgramArtifact` (jaxpr + lowered
StableHLO + compiled HLO + donation/retrace/dtype/cache metadata), and
runs the nine analysis passes against the committed budget file:

==================  =====================================================
pass                invariant it pins
==================  =====================================================
donation            donated buffers alias in compiled input_output_alias
collective-budget   collective counts/bytes <= benchmarks/budgets.json
retrace             one jit trace per program shape (no cache-key drift)
host-sync           no host-callback primitives / host-transfer HLO ops
flop-dtype          dot_flops coverage; no f32 dots in bf16 programs
cache-bytes         decode KV-cache bytes <= ceiling; quantized configs
                    store narrow data planes
schedule            async -start/-done pairs matched; compute shadows
                    above the per-program ``overlap`` floors
sharding-coverage   every bound param resolves to a rule match or an
                    INTENTIONAL replicate; silent degrades are errors
drift               priced quantities (FLOPs, collective/cache bytes,
                    donation map) vs a recorded snapshot (``--check``)
==================  =====================================================

Output follows the benches' contract (:func:`contract_line`): ONE json
line on stdout —
``{"metric": "mxlint_unsuppressed_findings", "value", "unit",
"vs_baseline", ...}`` — with per-finding detail on stderr in the
``--format`` of choice (default ``jsonl``: one json object per line).

Exit-code contract (unit-tested in tests/test_analysis.py):

* **0** — clean, or info-only findings (info never fails a run);
* **1** — at least one unsuppressed *error* finding survived;
* **2** — usage / input error (unknown flag, unreadable or
  hash-mismatched ``--check`` snapshot), the argparse convention.

Workflow (docs/static_analysis.md):

* ``tools/mxlint.py --smoke``           — the tier-1 CI entry
  (tests/test_bench_contract.py invokes it, with ``--check`` against
  the committed ``benchmarks/mxlint_snapshot.json``);
* ``tools/mxlint.py --update-budgets``  — re-measure and rewrite the
  budget ceilings after an *intentional* sharding/collective change
  (preserves the file's suppressions list);
* ``tools/mxlint.py --smoke --record benchmarks/mxlint_snapshot.json``
  — re-record the drift baseline after an intentional perf change;
* ``tools/mxlint.py --smoke --check benchmarks/mxlint_snapshot.json``
  — the differential gate: a PR that regresses a priced quantity
  beyond tolerance fails here, naming the program and the quantity;
* ``tools/mxlint.py --programs decode_step --format text``  —
  human-readable audit of a subset while iterating;
* ``tools/mxlint.py --smoke --format github`` — CI annotations
  (``::error file=...``) on stderr for unsuppressed findings.

Suppressions: ``pass[:program[:code]]`` globs, from the budget file's
``suppressions`` list, ``MXNET_ANALYSIS_SUPPRESS``, or ``--suppress``.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

SMOKE = "--smoke" in sys.argv

# the virtual-device mesh must exist BEFORE jax initializes its backend
# (same dance as benchmarks/bench_long_context.py / tests/conftest.py)
if SMOKE:
    os.environ["JAX_PLATFORMS"] = "cpu"
if os.environ.get("JAX_PLATFORMS", "") == "cpu" and \
        "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()


def contract_line(metric, value, unit, vs_baseline, **extra):
    """The one-line stdout JSON contract the benches and this CLI emit,
    so CI consumes one schema:
    {"metric", "value", "unit", "vs_baseline", ...extras}."""
    row = {"metric": metric, "value": value, "unit": unit,
           "vs_baseline": vs_baseline}
    row.update(extra)
    return json.dumps(row)


def _parse_args(argv):
    ap = argparse.ArgumentParser(
        prog="mxlint", description="static analysis over the canonical "
        "compiled programs (see docs/static_analysis.md)")
    ap.add_argument("--smoke", action="store_true",
                    help="tier-1 CI mode: force the 8-virtual-device CPU "
                    "platform and audit all thirteen programs")
    ap.add_argument("--programs", default="",
                    help="comma-filter of canonical programs (default all)")
    ap.add_argument("--budgets", default="",
                    help="budget file path (default: MXNET_ANALYSIS_BUDGETS "
                    "or benchmarks/budgets.json)")
    ap.add_argument("--suppress", default="",
                    help="extra suppression patterns, comma-separated")
    ap.add_argument("--update-budgets", action="store_true",
                    help="rewrite the budget file's per-program collective "
                    "ceilings from this run's measurements and exit")
    ap.add_argument("--record", default="", metavar="PATH",
                    help="write a content-addressed drift snapshot of this "
                    "run's priced quantities to PATH (the --check baseline)")
    ap.add_argument("--check", default="", metavar="PATH",
                    help="load a drift snapshot and arm the drift pass: a "
                    "priced quantity regressing beyond its tolerance is an "
                    "error naming the program and quantity")
    ap.add_argument("--format", default="", dest="fmt",
                    choices=("jsonl", "json", "github", "text"),
                    help="stderr finding format: jsonl (default; one json "
                    "object per line), json (one report document), github "
                    "(::error/::warning workflow annotations for "
                    "unsuppressed findings), text (human-readable)")
    ap.add_argument("--text", action="store_true",
                    help="alias for --format text")
    ap.add_argument("--list", action="store_true", dest="list_only",
                    help="list canonical programs and passes, then exit")
    args = ap.parse_args(argv)
    if not args.fmt:
        args.fmt = "text" if args.text else "jsonl"
    return args


def format_github(report, file="benchmarks/budgets.json"):
    """GitHub workflow-command annotation lines for every unsuppressed
    error/warning finding (info rows are advisory and stay off the PR).
    ``file`` anchors the annotation — findings describe compiled
    programs, not source lines, so the budget file (where the waiver or
    ceiling would change) is the natural place to hang them."""
    lines = []
    for f in report.unsuppressed:
        title = "%s(%s)%s" % (f.pass_name, f.program,
                              ":" + f.code if f.code else "")
        # workflow-command escaping: %, CR, LF in the data
        msg = (f.message.replace("%", "%25").replace("\r", "%0D")
               .replace("\n", "%0A"))
        lines.append("::%s file=%s,line=1,title=%s::%s"
                     % (f.severity, file, title, msg))
    return lines


def _exit_code(report):
    """The documented contract: 0 clean/info-only, 1 on unsuppressed
    errors (usage/input failures exit 2 before a report exists)."""
    return 1 if report.errors else 0


def main(argv=None):
    args = _parse_args(argv if argv is not None else sys.argv[1:])

    if args.smoke and not SMOKE:
        # platform forcing happens at import, keyed off sys.argv; a
        # programmatic main(["--smoke"]) after the backend initialized
        # cannot deliver the promised 8-device CPU audit — fail loudly
        # instead of silently skipping ring_tp_step
        import jax

        if jax.devices()[0].platform != "cpu" or len(jax.devices()) < 8:
            sys.exit("--smoke requires the 8-virtual-device CPU platform, "
                     "which must be forced before jax initializes: run "
                     "tools/mxlint.py as a script, not via main()")

    from mxnet_tpu import analysis
    from mxnet_tpu.analysis.hlo_parse import collective_stats
    from mxnet_tpu.analysis.schedule import parse_schedule
    from mxnet_tpu.programs import registry as progreg
    import mxnet_tpu.analysis.programs  # noqa: F401 — registers the
    # canonical builder groups with the program registry; --list,
    # --programs and the audit below all enumerate the registry

    if args.list_only:
        for name in progreg.canonical_names():
            print("program:", name)
        for p in analysis.default_passes():
            print("pass:", p.name)
        return 0

    snapshot = None
    if args.check:
        try:
            snapshot = analysis.load_snapshot(args.check)
        except (OSError, ValueError, json.JSONDecodeError) as e:
            print("mxlint: --check: %s" % e, file=sys.stderr)
            return 2

    names = [n for n in args.programs.split(",") if n] or None
    artifacts, notes = progreg.build_canonical(names)
    for prog, reason in notes.items():
        print(json.dumps({"skipped_program": prog, "reason": reason}),
              file=sys.stderr)

    budgets_path = args.budgets or None
    budgets = analysis.load_budgets(budgets_path)

    if args.update_budgets:
        # same resolution as the read above — reads and writes must agree
        path = analysis.resolve_budgets_path(budgets_path)
        programs = budgets.setdefault("programs", {})
        for art in artifacts:
            if art.meta.get("cache_bytes") is not None:
                programs.setdefault(art.name, {})["cache_bytes"] = \
                    art.meta["cache_bytes"]
            if art.compiled_text is None:
                continue
            stats = collective_stats(art.compiled_text)
            ceilings = {op: dict(v) for op, v in stats.items()
                        if op != "overlappable"}
            programs.setdefault(art.name, {})["collectives"] = ceilings
        with open(path, "w") as f:
            json.dump(budgets, f, indent=2, sort_keys=True)
            f.write("\n")
        print(json.dumps({"updated": os.path.relpath(path),
                          "programs": sorted(p for p in programs)}),
              file=sys.stderr)
        return 0

    report = analysis.run_passes(artifacts, budgets=budgets,
                                 suppressions=args.suppress,
                                 snapshot=snapshot)

    if args.record:
        snap = analysis.record_snapshot(artifacts, report)
        with open(args.record, "w") as f:
            json.dump(snap, f, indent=2, sort_keys=True)
            f.write("\n")
        print(json.dumps({"recorded": args.record,
                          "programs": sorted(snap["programs"]),
                          "content_hash": snap["content_hash"]}),
              file=sys.stderr)

    if args.fmt == "text":
        print(report.format_text(), file=sys.stderr)
    elif args.fmt == "json":
        print(report.to_json(), file=sys.stderr)
    elif args.fmt == "github":
        for line in format_github(report):
            print(line, file=sys.stderr)
    else:
        for f in report.findings:
            print(json.dumps(f.to_dict()), file=sys.stderr)

    # schedule/drift aggregates for the bench contract line, so overlap
    # structure and drift state ride the same trend lines as the byte
    # ceilings
    sched = {"pairs": 0, "unpaired": 0, "serialized": 0}
    for art in artifacts:
        if art.compiled_text is not None:
            s = parse_schedule(art.compiled_text).summary()
            for k in sched:
                sched[k] += s[k]
    drifted = sum(1 for f in report.findings
                  if f.pass_name == "drift"
                  and f.code.startswith("drift:") and not f.suppressed)

    s = report.summary()
    unsup = len(report.unsuppressed)
    print(contract_line(
        "mxlint_unsuppressed_findings", unsup, "findings",
        1.0 if unsup == 0 else 0.0,
        errors=s["errors"], warnings=s["warnings"],
        suppressed=s["suppressed"], programs=s["programs"],
        passes=s["passes"], skipped_programs=sorted(notes),
        schedule_pairs=sched["pairs"],
        schedule_unpaired=sched["unpaired"],
        schedule_serialized=sched["serialized"],
        drift_checked=len(artifacts) if snapshot is not None else 0,
        drifted=drifted))
    return _exit_code(report)


if __name__ == "__main__":
    sys.exit(main())
