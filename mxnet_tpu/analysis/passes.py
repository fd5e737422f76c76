"""The shipped analysis passes (ten with ``schedule.SchedulePass``).

Each pass statically audits one performance invariant the framework's PRs
established, so a sharding-rule edit or a jit cache-key drift fails CI on
the 8-virtual-device CPU mesh instead of silently regressing a headline:

* :class:`DonationPass` — every donated buffer must survive to compiled
  ``input_output_alias`` (dropped donation = steady-state allocation).
* :class:`CollectiveBudgetPass` — collective counts/bytes per program
  stay within the committed ``benchmarks/budgets.json`` ceilings (a
  GSPMD-inserted all-gather from a sharding-spec regression trips it).
* :class:`RetracePass` — each canonical program traces exactly once per
  shape (weak-type/dtype drift = recompiles mid-loop).
* :class:`HostSyncPass` — no host-callback primitives inside device
  programs (the static half; ``fit()``'s ``MXNET_TRANSFER_GUARD`` runtime
  guard is the dynamic half).
* :class:`FlopDtypePass` — ``dot_flops`` coverage (uncounted dot-like ops
  are an error, not a silent zero) and f32 dots inside bf16 programs.
* :class:`CacheBytesPass` — decode KV-cache bytes (data + scale planes,
  sized through the f8/sub-byte-aware width table) stay within the
  committed ceiling, and a quantized config must actually store narrow
  data (an f32 data plane under MXNET_KV_DTYPE is an error — decode is
  bandwidth-bound on exactly these bytes).  Paged layouts are understood:
  the budget is the shared POOL's bytes (the whole serving HBM bill, not
  per-slot rings), and a dense-ring allocation under ``MXNET_KV_PAGED=1``
  is an error — the config promises paged memory management the program
  no longer performs.
* :class:`ShardingCoveragePass` — partition-rule coverage over the bound
  param tree (``meta['sharding_coverage']``): every leaf resolves to a
  rule match or an *intentional* replicate; the placement degrade paths
  (rank mismatch / indivisible dims in ``programs/partition.py`` and the
  executor's TP rules) are errors naming the param, and the grouped-K/V
  degrade (``tp_rules._kv_head_axis``, ``meta['replicated_degrades']``)
  lints as a visible info row instead of a 4x HBM surprise.
* :class:`DriftPass` — the differential gate: each program's priced
  quantities (:func:`~mxnet_tpu.analysis.cost.artifact_cost`) compared
  against a content-addressed snapshot (``mxlint --record/--check``)
  within tolerance, so a PR that regresses dot FLOPs / collective bytes
  / cache bytes / donation without re-recording fails tier-1.

:class:`~mxnet_tpu.analysis.schedule.SchedulePass` (async-overlap
shadows) lives in :mod:`~mxnet_tpu.analysis.schedule` with its parser.
"""
from __future__ import annotations

from .framework import Pass
from .hlo_parse import (collective_stats, dot_flops_report,
                        input_output_aliases, shape_bytes_report)

__all__ = ["DonationPass", "CollectiveBudgetPass", "RetracePass",
           "HostSyncPass", "FlopDtypePass", "CacheBytesPass",
           "ShardingCoveragePass", "DriftPass",
           "record_snapshot", "snapshot_hash"]


class DonationPass(Pass):
    """Donated buffers must appear in compiled ``input_output_alias``.

    The fused train step, eval step and decode step donate params / slots /
    caches so XLA updates them in place; a dtype or shape drift between a
    donated input and its updated output silently drops the alias and the
    steady-state step starts allocating (and copying) every call.  The
    artifact records how many buffers were donated at trace time; the
    compiled module header records how many XLA actually aliased.
    """

    name = "donation"
    requires = ("compiled",)

    def run(self, artifact, context):
        if not artifact.donated_leaves:
            return [self.finding(
                artifact, "info", "program donates nothing; pass skipped",
                code="no-donation")]
        aliases = input_output_aliases(artifact.compiled_text)
        aliased_params = {param for _, param in aliases}
        n = len(aliased_params)
        if n >= artifact.donated_leaves:
            return [self.finding(
                artifact, "info",
                "%d/%d donated buffers aliased" % (n, artifact.donated_leaves),
                code="aliased", aliased=n,
                donated=artifact.donated_leaves)]
        return [self.finding(
            artifact, "error",
            "dropped donation: %d buffers donated but only %d aliased in "
            "compiled input_output_alias — the step allocates fresh "
            "buffers (and copies) every call" % (artifact.donated_leaves, n),
            code="dropped-donation", aliased=n,
            donated=artifact.donated_leaves,
            alias_entries=[[list(path), param]
                           for path, param in aliases])]


class CollectiveBudgetPass(Pass):
    """Collective counts/bytes vs the committed budget ceilings.

    Budget layout (``benchmarks/budgets.json``)::

        {"programs": {"<program>": {"collectives": {
            "total": {"count": N, "bytes": B},
            "all-gather": {"count": N, "bytes": B}, ...}}},
         "suppressions": ["pass[:program[:code]]", ...]}

    Every ceiling is inclusive (measured == budget passes).  Collective
    ops present in the program but absent from its budget are errors —
    a GSPMD regression typically shows up as a brand-new all-gather, not
    as growth of an existing entry.  Byte ceilings more than 2x the
    measurement emit an info row suggesting the budget be re-tightened
    (``tools/mxlint.py --update-budgets``).
    """

    name = "collective-budget"
    requires = ("compiled",)

    def run(self, artifact, context):
        budget = context.budget_for(artifact.name) or {}
        ceilings = budget.get("collectives")
        stats = collective_stats(artifact.compiled_text)
        if ceilings is None:
            sev = "info" if stats["total"]["count"] == 0 else "warning"
            return [self.finding(
                artifact, sev,
                "no committed collective budget for this program "
                "(measured: %d collectives, %d bytes) — run "
                "tools/mxlint.py --update-budgets" %
                (stats["total"]["count"], stats["total"]["bytes"]),
                code="no-budget", measured=stats)]
        findings = []
        for op, measured in stats.items():
            if op == "overlappable":
                continue
            ceiling = ceilings.get(op)
            if ceiling is None:
                if op != "total" and measured["count"] > 0:
                    findings.append(self.finding(
                        artifact, "error",
                        "unbudgeted collective %r: %d op(s), %d bytes — a "
                        "sharding-spec regression inserted a collective "
                        "this program never had" %
                        (op, measured["count"], measured["bytes"]),
                        code="unbudgeted-op", op=op, measured=measured))
                continue
            for key in ("count", "bytes"):
                if key in ceiling and measured[key] > ceiling[key]:
                    findings.append(self.finding(
                        artifact, "error",
                        "collective %s %s over budget: %d > %d" %
                        (op, key, measured[key], ceiling[key]),
                        code="over-budget", op=op, kind=key,
                        measured=measured[key], budget=ceiling[key]))
            if "bytes" in ceiling and ceiling["bytes"] > 0 and \
                    ceiling["bytes"] > 2 * max(measured["bytes"], 1):
                findings.append(self.finding(
                    artifact, "info",
                    "collective %s byte budget %d is >2x the measured %d; "
                    "consider --update-budgets" %
                    (op, ceiling["bytes"], measured["bytes"]),
                    code="slack-budget", op=op))
        # ops budgeted but absent from the program: the ceiling is stale
        # headroom a future regression could silently refill — surface it
        for op, ceiling in ceilings.items():
            if op in stats or ceiling.get("count", 0) == 0:
                continue
            findings.append(self.finding(
                artifact, "info",
                "budgeted collective %r no longer appears in the program "
                "(%d op(s) / %d bytes of stale headroom); tighten with "
                "--update-budgets" %
                (op, ceiling.get("count", 0), ceiling.get("bytes", 0)),
                code="stale-budget", op=op, budget=ceiling))
        if not findings:
            findings.append(self.finding(
                artifact, "info",
                "within budget: %d collectives, %d bytes" %
                (stats["total"]["count"], stats["total"]["bytes"]),
                code="within-budget", measured=stats["total"]))
        return findings


class RetracePass(Pass):
    """Each canonical program traces exactly once per distinct shape.

    The artifact's ``trace_count`` comes from the step programs' built-in
    python-level trace counters (``CompiledTrainStep.trace_count``,
    ``DecodePredictor.trace_counts``) or a
    :class:`~mxnet_tpu.analysis.retrace.RetraceAuditor`; the builder
    drives every program at least twice at identical shapes before
    snapshotting, so a count above ``expected_traces`` is a cache miss at
    "the same" signature — dtype/weak-type drift.  The auditor's recorded
    signature diffs (``meta['retrace']``) say which leaf moved.
    """

    name = "retrace"
    requires = ()

    def run(self, artifact, context):
        if artifact.trace_count is None:
            return [self.finding(
                artifact, "info", "no retrace instrumentation on this "
                "artifact", code="no-instrumentation")]
        record = artifact.meta.get("retrace") or {}
        if artifact.meta.get("aot") and artifact.trace_count == 0:
            # AOT-prepared programs dispatch a deserialized (or
            # probe-compiled) executable: zero python-level traces is
            # the DESIGNED state, not missing instrumentation — surface
            # the provenance so "every host runs the canonical program,
            # not a local retrace" reads straight off the lint
            return [self.finding(
                artifact, "info",
                "0 traces: program dispatches an AOT %s executable "
                "(mxnet_tpu.programs.aot)" % artifact.meta["aot"],
                code="aot-loaded", source=artifact.meta["aot"])]
        if artifact.trace_count <= artifact.expected_traces:
            return [self.finding(
                artifact, "info",
                "traced %d time(s), %d expected" %
                (artifact.trace_count, artifact.expected_traces),
                code="no-retrace")]
        diffs = record.get("diffs") or []
        diff_text = "; ".join("|".join(d) for d in diffs if d) \
            or "no signature diff recorded"
        return [self.finding(
            artifact, "error",
            "retraced: %d traces for %d expected shape variant(s) — the "
            "jit cache key drifted (%s)" %
            (artifact.trace_count, artifact.expected_traces, diff_text),
            code="retrace", traces=artifact.trace_count,
            expected=artifact.expected_traces, record=record)]


# jaxpr primitives that round-trip through the host; any of them inside a
# hot-path program serializes the device on every step
# jax.debug.print is its own primitive (debug_print); jax.debug.callback
# keeps debug_callback
_CALLBACK_PRIMS = ("pure_callback", "io_callback", "debug_callback",
                   "debug_print")
# compiled-HLO ops that move data to/from the host mid-program.  send/recv
# are deliberately NOT listed: they also carry device-to-device channel
# traffic (cross-partition collectives can legalize through them).
_HLO_HOST_OPS = ("outfeed(", "infeed(")


class HostSyncPass(Pass):
    """No host round-trips inside device programs.

    Static scan: jaxpr callback primitives (``pure_callback`` /
    ``io_callback`` / ``debug_callback`` / ``debug_print`` — a stray
    ``jax.debug.print`` left in an op implementation lands here) and
    compiled-HLO host transfer ops.  The runtime half is ``MXNET_TRANSFER_GUARD``, which
    arms ``jax.transfer_guard_device_to_host`` around ``fit()``'s hot
    loop (docs/static_analysis.md).

    Sanctioned transfers: an artifact may carry
    ``meta['host_sync_allow']`` — a list of finding codes its owner
    declares intentional (the elastic checkpoint fence's d2h is the
    canonical case: the snapshot copies leave the program, the writer
    thread materializes them, and the sync-save fallback wraps its d2h in
    an explicit ``transfer_guard`` allow scope).  A matching finding is
    downgraded to an *info* row with a ``sanctioned:`` code prefix, so
    the waiver stays visible in reports instead of silently vanishing —
    the same philosophy as budget-file suppressions, but declared at the
    program, where the sanction's reason lives.
    """

    name = "host-sync"
    requires = ("jaxpr",)

    def run(self, artifact, context):
        findings = []
        sanctioned = set(artifact.meta.get("host_sync_allow") or ())

        def emit(code, message, **detail):
            if code in sanctioned:
                findings.append(self.finding(
                    artifact, "info",
                    "sanctioned host transfer (%s): %s" % (code, message),
                    code="sanctioned:" + code, **detail))
            else:
                findings.append(self.finding(artifact, "error", message,
                                             code=code, **detail))

        text = artifact.jaxpr_text
        for prim in _CALLBACK_PRIMS:
            n = text.count(prim)
            if n:
                emit(prim, "%d %s primitive(s) in the jaxpr: the program "
                     "round-trips through the host every step" % (n, prim),
                     count=n)
        if artifact.compiled_text is not None:
            for op in _HLO_HOST_OPS:
                n = sum(line.count(op)
                        for line in artifact.compiled_text.splitlines()
                        if "=" in line)
                if n:
                    emit("hlo-" + op.rstrip("("),
                         "%d %r op(s) in compiled HLO: host transfer "
                         "inside the program" % (n, op.rstrip("(")),
                         count=n)
        if not findings:
            findings.append(self.finding(
                artifact, "info", "no host callbacks or host transfers",
                code="clean"))
        return findings


class FlopDtypePass(Pass):
    """FLOP-counter coverage + unintended f32 upcasts in bf16 programs.

    Coverage: ``dot_flops`` underpins the O(1)-in-prefix decode assertion
    and the bench MFU numbers; a program containing dot-like ops the
    counter cannot parse (``uncounted_ops``) makes every one of those
    numbers a silent undercount — an error here.  Unknown element types
    in the program's shapes (the ``shape_bytes`` width table) are
    reported the same way.

    Dtype: in a program whose declared compute dtype is bfloat16/float16,
    every dot whose result element type is f32 is flagged (warning) — the
    classic symptom of a cast that re-promoted the MXU path.  Checked on
    the *lowered StableHLO*, which reflects what was asked for; backend
    legalization (XLA:CPU rewrites bf16 dots through f32) happens later
    and is out of scope.

    Pallas-decode tripwire: a decode/verify artifact whose trace took a
    Pallas kernel over the live blocks at some attention node
    (``ops.attention.DECODE_PATH`` read ``decode-kernel``, chosen by
    ``decode_kernel_selected`` from the call's shapes, or ``chunk-kernel``,
    by ``chunk_kernel_selected``) carries
    ``meta['pallas_decode']``: the dispatch PROMISED
    ``ops/pallas_decode.py``'s kernel, the live blocks' pages read once.
    The promise is checked at the artifact level: the traced jaxpr must
    contain a ``pallas_call`` (interpret or compiled) or the lowered
    StableHLO a TPU custom-call.  A program that counted the kernel and
    lowered without it is an *error* here.
    """

    name = "flop-dtype"
    requires = ("stablehlo",)

    _PALLAS_PROMISES = (
        ("pallas_decode", "DECODE_PATH decode-kernel", "pallas-decode",
         "the decode row's Pallas kernel present (the dispatch's "
         "decode-kernel path lowered)",
         "the dispatch took the decode row's Pallas kernel "
         "(DECODE_PATH decode-kernel) but no pallas_call lowered into "
         "this program: the live blocks are attended some other way "
         "(dispatch regression)"),
    )

    def run(self, artifact, context):
        findings = []
        for key, _knob, ok_code, ok_msg, fail_msg in self._PALLAS_PROMISES:
            if not artifact.meta.get(key):
                continue
            jaxpr = artifact.jaxpr_text or ""
            shlo = artifact.stablehlo_text or ""
            if "pallas_call" in jaxpr or "tpu_custom_call" in shlo:
                findings.append(self.finding(
                    artifact, "info", ok_msg, code=ok_code))
            else:
                findings.append(self.finding(
                    artifact, "error", fail_msg, code="pallas-fallback"))
        report = dot_flops_report(artifact.stablehlo_text)
        for rec in report["uncounted_ops"]:
            findings.append(self.finding(
                artifact, "error",
                "%d %r op(s) not modeled by dot_flops: FLOP totals for "
                "this program are undercounts" % (rec["count"], rec["op"]),
                code="uncounted:" + rec["op"], **rec))
        # unknown element types are scanned in the compiled HLO, whose
        # 'dtype[dims]' shape syntax is what shape_bytes parses (StableHLO
        # writes tensor<...> shapes)
        unknown = []
        if artifact.compiled_text is not None:
            _, unknown = shape_bytes_report(artifact.compiled_text)
        if unknown:
            findings.append(self.finding(
                artifact, "warning",
                "element types %s missing from the shape_bytes width "
                "table: byte accounting skips them" % (unknown,),
                code="unknown-dtype", dtypes=unknown))
        cd = (artifact.compute_dtype or "").lower()
        if cd in ("bfloat16", "bf16", "float16", "f16"):
            low = {"bfloat16": "bf16", "bf16": "bf16",
                   "float16": "f16", "f16": "f16"}[cd]
            bad = [d for d in report["dots"] if d["dtype"] == "f32"]
            if bad:
                findings.append(self.finding(
                    artifact, "warning",
                    "%d of %d dots compute in f32 inside a %s program — "
                    "an upcast re-promoted the matmul path (first: %s)" %
                    (len(bad), len(report["dots"]), low,
                     bad[0]["line"][:160]),
                    code="f32-dot", count=len(bad),
                    total_dots=len(report["dots"]),
                    lines=[d["line"][:160] for d in bad[:8]]))
        if not findings:
            findings.append(self.finding(
                artifact, "info",
                "%d dot(s), %d FLOPs, full coverage" %
                (len(report["dots"]), report["flops"]),
                code="covered", flops=report["flops"]))
        return findings


# dtypes a cache DATA plane may use under a quantized MXNET_KV_DTYPE; the
# fp32 scale plane rides separately and is counted in cache_bytes
_NARROW_CACHE_DTYPES = ("int8", "float8_e4m3fn", "float8_e5m2",
                        "float8_e4m3fnuz", "float8_e5m2fnuz", "int4")


class CacheBytesPass(Pass):
    """Decode KV-cache bytes vs the committed ceiling; quantized configs
    must store narrow data; paged configs must store pages.

    Decode is bandwidth-bound on the cache: every step streams the whole
    (B, C, E) K/V per layer, so cache bytes ARE the serving-cost
    denominator (``bench_decode.py``'s tokens/s/GB headline).  The
    decode-layer artifacts record ``meta['cache_bytes']`` — data plus
    per-(token, head) scale planes, sized statically through
    ``hlo_parse.shape_bytes``'s width table (f8/sub-byte aware) — plus
    ``meta['kv_dtype']``/``meta['cache_data_dtypes']`` and
    ``meta['cache_layout']`` ('dense' ring buffers per slot, or 'paged':
    shared page pools whose recorded bytes are the POOL total — the
    serving HBM bill a page-table regression would silently re-inflate).
    Budget layout::

        {"programs": {"<program>": {"cache_bytes": N}}}

    Findings: bytes over the ceiling = error (a dtype regression silently
    doubling the cache); a quantized ``kv_dtype`` whose data planes are
    full-precision = error (the quantize plumbing got dropped — the
    config promises narrow reads it no longer performs); a dense-ring
    allocation under a paged config (``meta['kv_paged']``) = error (the
    page-pool plumbing got dropped — HBM scales with slots x max-context
    again); no committed ceiling = warning nudging ``--update-budgets``
    hygiene.  Programs without cache metadata (training steps) skip with
    an info row.
    """

    name = "cache-bytes"
    requires = ()

    def run(self, artifact, context):
        cache_bytes = artifact.meta.get("cache_bytes")
        if cache_bytes is None:
            return [self.finding(
                artifact, "info", "no KV-cache metadata; pass skipped",
                code="no-cache")]
        findings = []
        kv_dtype = artifact.meta.get("kv_dtype")
        data_dtypes = artifact.meta.get("cache_data_dtypes") or []
        layout = artifact.meta.get("cache_layout")
        if artifact.meta.get("kv_paged") and layout == "dense":
            findings.append(self.finding(
                artifact, "error",
                "MXNET_KV_PAGED promises paged KV caches but this program "
                "allocates dense ring buffers — the page-pool plumbing "
                "was dropped and serving HBM scales with "
                "slots x max-context again",
                code="dense-under-paged", layout=layout))
        if kv_dtype:
            wide = [d for d in data_dtypes
                    if d not in _NARROW_CACHE_DTYPES]
            if wide:
                findings.append(self.finding(
                    artifact, "error",
                    "kv_dtype=%s promises quantized caches but data "
                    "planes store %s — the quantize path was dropped and "
                    "every decode step streams full-precision bytes"
                    % (kv_dtype, wide),
                    code="f32-cache", kv_dtype=kv_dtype, wide=wide))
        # grouped-K/V promise (meta['num_kv_heads'] from a GQA config):
        # every cache/pool plane must be H_kv head slices wide — an H_q-
        # wide allocation means the num_kv_heads plumbing was dropped and
        # the G× pool shrink silently forfeited
        if artifact.meta.get("num_kv_heads"):
            widths = artifact.meta.get("cache_kv_dims") or []
            for dims in artifact.meta.get("attn_dims") or []:
                q_dim = dims.get("q_dim")
                kv_dim = dims.get("kv_dim")
                if dims.get("num_kv_heads") == dims.get("num_heads") \
                        or q_dim == kv_dim or kv_dim is None:
                    continue
                if q_dim in widths:
                    findings.append(self.finding(
                        artifact, "error",
                        "config promises grouped K/V (num_kv_heads=%s < "
                        "num_heads=%s) but a cache/pool plane allocates "
                        "the full q width %d (expected %d) — the grouped "
                        "layout was dropped and the pool is G× too large"
                        % (dims.get("num_kv_heads"),
                           dims.get("num_heads"), q_dim, kv_dim),
                        code="mha-under-gqa", q_dim=q_dim, kv_dim=kv_dim))
        budget = context.budget_for(artifact.name) or {}
        ceiling = budget.get("cache_bytes")
        if ceiling is None:
            findings.append(self.finding(
                artifact, "warning",
                "no committed cache-byte budget for this program "
                "(measured: %d bytes) — run tools/mxlint.py "
                "--update-budgets" % cache_bytes,
                code="no-budget", measured=cache_bytes))
        elif cache_bytes > ceiling:
            findings.append(self.finding(
                artifact, "error",
                "cache bytes over budget: %d > %d — the per-token "
                "bandwidth bill grew (dtype or shape regression in the "
                "ring buffers)" % (cache_bytes, ceiling),
                code="over-budget", measured=cache_bytes, budget=ceiling))
        if not findings:
            findings.append(self.finding(
                artifact, "info",
                "cache within budget: %d <= %d bytes (kv_dtype=%s, %s)"
                % (cache_bytes, ceiling, kv_dtype or "full-precision",
                   layout or "dense"),
                code="within-budget", measured=cache_bytes,
                budget=ceiling, kv_dtype=kv_dtype, layout=layout))
        return findings


class ShardingCoveragePass(Pass):
    """Partition-rule coverage over the bound param tree.

    Mesh-bound programs stamp ``meta['sharding_coverage']`` — per-leaf
    records written at placement time by ``programs/partition.
    build_shardings`` (decode) and ``module/executor_group.
    _param_sharding`` (train)::

        {"mesh": {"data": 2, "model": 2},
         "leaves": {"<param>": {"shape": [...],
                                "source": "rule|plan|mesh_axes|naive|"
                                          "default|scalar",
                                "spec": [...],        # when sharded
                                "degrade": "rank-mismatch|indivisible"}}}

    Findings:

    * a leaf a rule/plan MATCHED but the divisibility guard silently
      replicated (``degrade``) is an **error naming the param** — the
      intended placement was lost, every shard now holds the whole
      tensor (the 4x-HBM surprise this pass exists to catch);
    * the grouped-K/V cache degrade (``meta['replicated_degrades']``
      from ``tp_rules._kv_head_axis`` — ``H_kv % model != 0``) is a
      visible *info* row: legitimate, but never silent;
    * an UNMATCHED >=2-D leaf replicating by default is an **error**
      when the program's budget opts into strict coverage
      (``{"sharding": {"strict": true}}``) and a visible *info*
      otherwise — scalars and 1-D per-feature vectors always count as
      intentional replicates.

    Programs without a mesh (or predating the stamping) skip with an
    info row.
    """

    name = "sharding-coverage"
    requires = ()

    def run(self, artifact, context):
        findings = []
        for rec in artifact.meta.get("replicated_degrades") or []:
            findings.append(self.finding(
                artifact, "info",
                "%s degraded to replicated K/V sharding: %s — each "
                "model shard holds the full grouped K/V (visible "
                "degrade, see parallel/tp_rules._kv_head_axis)"
                % (rec.get("site", "kv sharding"),
                   rec.get("reason", "?")),
                code="kv-replicated-degrade", **rec))
        cov = artifact.meta.get("sharding_coverage")
        if cov is None:
            if not findings:
                return [self.finding(
                    artifact, "info",
                    "no sharding-coverage metadata (unmeshed program); "
                    "pass skipped", code="no-mesh")]
            return findings
        mesh = cov.get("mesh") or {}
        leaves = cov.get("leaves") or {}
        strict = bool(((context.budget_for(artifact.name) or {})
                       .get("sharding") or {}).get("strict"))
        meshed = any(int(v) > 1 for v in mesh.values())
        matched = unmatched_big = intentional = 0
        for name in sorted(leaves):
            rec = leaves[name]
            shape = rec.get("shape") or []
            degrade = rec.get("degrade")
            source = rec.get("source")
            if degrade:
                findings.append(self.finding(
                    artifact, "error",
                    "param %r matched a partition rule but DEGRADED to "
                    "full replication (%s, shape %s on mesh %s) — every "
                    "shard holds the whole tensor; fix the rule or the "
                    "shape, or waive it explicitly in the budget file"
                    % (name, degrade, shape, mesh),
                    code="replicated-degrade", param=name,
                    degrade=degrade, shape=shape))
            elif source in ("rule", "plan", "mesh_axes", "naive") \
                    and rec.get("spec"):
                matched += 1
            elif source == "default" and meshed \
                    and sum(1 for d in shape if int(d) > 1) >= 2:
                # effective rank counts dims > 1: a [1, 1, 16] LN gain
                # is a per-feature vector (always an intentional
                # replicate), a [1, 16, 16] embedding table is not
                unmatched_big += 1
                findings.append(self.finding(
                    artifact, "error" if strict else "info",
                    "param %r (shape %s) matched NO partition rule and "
                    "fully replicates on mesh %s — declare a rule or an "
                    "intentional replicate%s"
                    % (name, shape, mesh,
                       "" if strict else " (info: budget has no "
                       "{'sharding': {'strict': true}})"),
                    code="unmatched-param", param=name, shape=shape))
            else:
                intentional += 1
        if not findings:
            findings.append(self.finding(
                artifact, "info",
                "%d leaves covered: %d sharded by rule, %d intentional "
                "replicates, 0 degrades on mesh %s"
                % (len(leaves), matched, intentional, mesh),
                code="covered", leaves=len(leaves), sharded=matched,
                replicated=intentional))
        return findings


# ---------------------------------------------------------------------------
# drift snapshots (mxlint --record / --check)
# ---------------------------------------------------------------------------

# quantities compared EXACTLY (structural integers: a donation map or a
# collective count has no tolerance band)
_DRIFT_EXACT = ("donated", "aliased", "collective_count")
# quantities compared within the snapshot's relative tolerance
_DRIFT_PRICED = ("dot_flops", "collective_bytes", "gather_bytes",
                 "sort_scatter_bytes", "cache_bytes")
_SNAPSHOT_VERSION = 1


def snapshot_hash(snapshot):
    """Content address of a drift snapshot: a digest over its canonical
    JSON minus the hash field itself.  ``load_snapshot`` refuses a file
    whose recorded hash no longer matches — hand-edited baselines must
    go through ``mxlint --record``, not a text editor."""
    import hashlib
    import json

    body = {k: v for k, v in snapshot.items() if k != "content_hash"}
    blob = json.dumps(body, sort_keys=True, default=str)
    return hashlib.blake2b(blob.encode(), digest_size=16).hexdigest()


def record_snapshot(artifacts, report=None, tolerance=0.02):
    """Build a drift snapshot dict over built artifacts.

    Per program: the priced quantities
    (:func:`~mxnet_tpu.analysis.cost.artifact_cost`), the program
    fingerprint when known, and the pass-finding severity counts from
    ``report`` (so a baseline records what lint state it was taken in).
    ``tolerance`` is the relative band the check applies to priced
    quantities; structural integers compare exactly."""
    from .cost import artifact_cost

    per_prog = {}
    if report is not None:
        for f in report.findings:
            row = per_prog.setdefault(f.program,
                                      {"errors": 0, "warnings": 0})
            if f.severity != "info" and not f.suppressed:
                row[f.severity + "s"] += 1
    programs = {}
    for art in artifacts:
        row = artifact_cost(art)
        row["fingerprint"] = art.fingerprint
        if art.name in per_prog:
            row["findings"] = per_prog[art.name]
        programs[art.name] = row
    snap = {"version": _SNAPSHOT_VERSION, "tolerance": tolerance,
            "programs": programs}
    snap["content_hash"] = snapshot_hash(snap)
    return snap


class DriftPass(Pass):
    """The differential gate: priced quantities vs a recorded snapshot.

    ``mxlint --record <snapshot.json>`` writes the content-addressed
    baseline; ``mxlint --check <snapshot.json>`` loads it into the
    context and this pass compares each program's measured
    :func:`~mxnet_tpu.analysis.cost.artifact_cost` row against it:

    * a priced quantity (dot FLOPs, collective/gather/sort-scatter
      bytes, cache bytes) GROWN beyond the snapshot's relative
      tolerance is an **error naming the program and the quantity** —
      the regression gate the bench trajectory never had;
    * a structural integer (donated, aliased, collective count) compares
      exactly;
    * a quantity that SHRANK beyond tolerance is an *info* row (an
      improvement to bank: re-record so the gate tightens);
    * a program missing from the snapshot (or a snapshot program that
      was not built) is a **warning** — the baseline is stale and must
      be re-recorded;
    * a changed fingerprint alone is an *info* row (fingerprints move
      with any intentional retrace; the priced quantities decide).

    No snapshot loaded -> one info row per program.
    """

    name = "drift"
    requires = ()

    def run(self, artifact, context):
        from .cost import artifact_cost

        snap = context.snapshot
        if not snap:
            return [self.finding(
                artifact, "info",
                "no drift snapshot loaded; record one with "
                "tools/mxlint.py --record <snapshot.json>",
                code="no-snapshot")]
        findings = []
        recorded = snap.get("programs", {})
        row = recorded.get(artifact.name)
        if row is None:
            findings.append(self.finding(
                artifact, "warning",
                "program absent from the drift snapshot — re-record "
                "the baseline (tools/mxlint.py --record)",
                code="new-program"))
            return findings
        measured = artifact_cost(artifact)
        tol = float(snap.get("tolerance", 0.02))
        drifted = []
        for key in _DRIFT_EXACT + _DRIFT_PRICED:
            was, now = row.get(key), measured.get(key)
            if was is None and now is None:
                continue
            if was is None or now is None:
                findings.append(self.finding(
                    artifact, "warning",
                    "quantity %r %s the snapshot but %s this run — "
                    "surfaces changed; re-record the baseline"
                    % (key, "missing from" if was is None else "in",
                       "measured" if was is None else "unmeasured"),
                    code="asymmetric-quantity", quantity=key,
                    recorded=was, measured=now))
                continue
            if key in _DRIFT_EXACT:
                if now != was:
                    drifted.append((key, was, now, "error"))
                continue
            band = tol * max(abs(was), 1)
            if now > was + band:
                drifted.append((key, was, now, "error"))
            elif now < was - band:
                drifted.append((key, was, now, "info"))
        for key, was, now, sev in drifted:
            pct = 100.0 * (now - was) / max(abs(was), 1)
            if sev == "error":
                findings.append(self.finding(
                    artifact, "error",
                    "%s drifted %+.1f%% (%d -> %d) beyond the %.0f%% "
                    "tolerance without a re-recorded baseline — an "
                    "intentional change ships with tools/mxlint.py "
                    "--record, a regression gets fixed"
                    % (key, pct, was, now, 100 * tol),
                    code="drift:" + key, quantity=key, recorded=was,
                    measured=now, tolerance=tol))
            else:
                findings.append(self.finding(
                    artifact, "info",
                    "%s improved %+.1f%% (%d -> %d); re-record so the "
                    "gate banks the win" % (key, pct, was, now),
                    code="improved:" + key, quantity=key, recorded=was,
                    measured=now))
        if row.get("fingerprint") and artifact.fingerprint \
                and row["fingerprint"] != artifact.fingerprint:
            findings.append(self.finding(
                artifact, "info",
                "program fingerprint changed (%s -> %s); priced "
                "quantities decide whether it matters"
                % (row["fingerprint"][:12], artifact.fingerprint[:12]),
                code="fingerprint-changed"))
        if not findings:
            findings.append(self.finding(
                artifact, "info",
                "all priced quantities within %.0f%% of the snapshot"
                % (100 * tol), code="within-tolerance"))
        return findings
