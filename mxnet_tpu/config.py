"""Runtime environment-variable configuration registry.

TPU-native analog of the reference's ~25 ``dmlc::GetEnv`` runtime knobs
catalogued in ``docs/how_to/env_var.md:8-94`` (engine threads, memory-pool
reserve, bulk-exec caps, ...).  Most of those knobs configure machinery XLA
subsumes (thread pools, memory planner), so the registry here is smaller but
the *mechanism* is the same: every runtime flag is declared in one place with
a type, default and docstring, read once, and discoverable via
``config.describe()`` instead of scattered ``os.environ`` reads.

Variables keep the ``MXNET_`` prefix for reference compatibility.
"""
from __future__ import annotations

import contextlib
import os

__all__ = ["EnvVar", "register", "get", "describe", "refresh",
           "overrides"]

_REGISTRY = {}


def _parse_bool(s):
    return str(s).lower() in ("1", "true", "yes", "on")


class EnvVar:
    """One declared runtime flag."""

    __slots__ = ("name", "type", "default", "doc", "_value", "_loaded")

    def __init__(self, name, type, default, doc):
        self.name = name
        self.type = type
        self.default = default
        self.doc = doc
        self._value = None
        self._loaded = False

    def get(self):
        if not self._loaded:
            raw = os.environ.get(self.name)
            if raw is None:
                self._value = self.default
            elif self.type is bool:
                self._value = _parse_bool(raw)
            else:
                self._value = self.type(raw)
            self._loaded = True
        return self._value

    def reset(self):
        self._loaded = False


def register(name, type, default, doc):
    """Declare a runtime flag; returns the EnvVar."""
    var = EnvVar(name, type, default, doc)
    _REGISTRY[name] = var
    return var


def get(name):
    """Read a declared flag (cached after first read)."""
    return _REGISTRY[name].get()


def refresh(name=None):
    """Drop the cached value(s) so the next get() re-reads the environment."""
    if name is not None:
        _REGISTRY[name].reset()
    else:
        for var in _REGISTRY.values():
            var.reset()


@contextlib.contextmanager
def overrides(**knobs):
    """Temporarily pin declared flags through the environment.

    ``with config.overrides(MXNET_PALLAS_INTERPRET="1"):`` sets each env
    var (``None`` unsets it), refreshes the registry cache so the new
    values are live inside the block, and restores BOTH the environment
    and the cache on exit — the save/set/refresh/restore dance that
    benches, the canonical-program drives and tests otherwise each
    hand-roll.  Values are written with ``str()``; booleans should be
    passed as "1"/"0" strings to match how the environment spells them.
    """
    saved = {k: os.environ.get(k) for k in knobs}
    try:
        for k, v in knobs.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = str(v)
        refresh()
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        refresh()


def describe():
    """Human-readable catalog of every declared flag (env_var.md analog)."""
    lines = []
    for name in sorted(_REGISTRY):
        var = _REGISTRY[name]
        lines.append("%s (%s, default=%r)\n    %s"
                     % (name, var.type.__name__, var.default, var.doc))
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Declared flags.  Reference counterparts cited where one exists.
# ---------------------------------------------------------------------------
register("MXNET_COMPUTE_DTYPE", str, "",
         "Default compute dtype for compiled train steps ('bfloat16', "
         "'float32', ...). Empty = float32. Master weights stay float32. "
         "TPU-era replacement for the reference's fp16 casting idiom.")
register("MXNET_FUSED_TRAIN_STEP", bool, True,
         "Fuse forward+backward+optimizer into one donated XLA program in "
         "Module when the optimizer supports it (analog of the reference's "
         "bulk-exec segments, graph_executor.cc:678-756).")
register("MXNET_EXEC_BULK_EXEC_INFERENCE", bool, True,
         "Jit-compile whole inference graphs (reference env_var.md: bulk "
         "execution for inference). Off = per-op eager interpretation for "
         "debugging, the NaiveEngine analog.")
register("MXNET_BACKWARD_DO_MIRROR", bool, False,
         "Trade compute for memory by rematerializing activations in the "
         "backward pass via jax.checkpoint (reference env_var.md mirror).")
register("MXNET_ENGINE_TYPE", str, "",
         "Set to 'NaiveEngine' to force eager, per-op execution for "
         "debugging (reference src/engine/engine.cc:13-39).")
register("MXNET_PROFILER_AUTOSTART", bool, False,
         "Start the profiler at import time (reference env_var.md:71-79).")
register("MXNET_MOE_DISPATCH", str, "sort",
         "Capacity-slot assignment algorithm for the sparse MoE "
         "dispatch (ops/moe.py): 'sort' (default) ranks the (token, "
         "rank-k choice) pairs by argsort over a composite "
         "(expert, priority) key and derives each choice's capacity "
         "position from its index within the sorted expert group "
         "(MegaBlocks-style sort/scatter dispatch — no (N*k, E) one-hot "
         "cumsum ever materializes); 'onehot' restores the one-hot "
         "cumsum pack for A/B comparison.  Both produce BIT-IDENTICAL "
         "slot assignments, outputs, grads and drop sets (tier-1 "
         "asserted); the dispatch intermediates they materialize "
         "differ, priced by analysis/cost.py sort/scatter accounting.")
register("MXNET_KV_LAYOUT", str, "",
         "Device minor-to-major layout requested for decode KV cache "
         "buffers at allocation, as a comma-separated major_to_minor "
         "permutation (e.g. '0,1,2' is row-major).  Set from the winning "
         "row of benchmarks/layout_probe.py --kv, which times decode "
         "attention under each candidate pool layout on the bench chip.  "
         "Empty (default) = the backend's native layout.  Backends "
         "without jax.experimental.layout support (the CPU harness) "
         "ignore it with a one-time warning.")
register("MXNET_PALLAS_INTERPRET", bool, False,
         "Run Pallas kernels in interpret mode on non-TPU backends instead "
         "of falling back to einsum (slow; for testing the kernel dispatch "
         "path on CPU).")
register("MXNET_RING_ATTENTION", bool, True,
         "Under a mesh whose 'seq' axis is sharded (and 'model' is not), "
         "dot_product_attention dispatches to explicit-collective ring "
         "attention (parallel/ring.py) inside the executor program: K/V "
         "blocks rotate via ppermute with O(T/n) memory per device, and "
         "the per-hop compute is the Pallas flash kernel on TPU.  Set 0 "
         "to restore the GSPMD einsum path (the partitioner's all-gather "
         "plan) for A/B comparison.")
register("MXNET_RING_DOUBLE_BUFFER", bool, True,
         "Communication schedule for ring attention (parallel/ring.py): "
         "1 (default) double-buffers the ring — each hop's K/V ppermute "
         "(and the backward ring's traveling dK/dV rotation) is issued "
         "BEFORE the hop's flash/streaming kernel, so backends with "
         "async collectives (TPU: collective-permute-start/done) overlap "
         "the wire time with compute.  0 restores the serial issue order "
         "for A/B measurement (benchmarks/bench_long_context.py records "
         "both).  Schedules are bit-identical in outputs and gradients.")
register("MXNET_MOE_TOPK", int, 0,
         "Override the MoEFFN op's num_experts_per_tok attribute at trace "
         "time (top-k routing: each token is dispatched to its k highest-"
         "probability experts, gates renormalized over the chosen k when "
         "k > 1).  0 (default) keeps the per-op attribute; k = 1 is the "
         "classic switch (top-1) routing with the raw chosen probability "
         "as the gate.")
register("MXNET_MOE_DROPLESS", bool, False,
         "Force the sparse MoE dispatch's overflow policy to 'dropless': "
         "per-device capacity stretches to the worst case (every local "
         "choice fits, padding-masked slots carry the slack) so no token "
         "is ever dropped — at the cost of expert-FFN compute/memory that "
         "scales like the dense path's worst case.  0 (default) keeps the "
         "per-op 'overflow' attribute (Switch drop semantics unless the "
         "symbol says otherwise).")
register("MXNET_MOE_CAPACITY", float, 0.0,
         "Override the MoEFFN op's capacity_factor attribute at trace "
         "time: > 0 arms the sparse capacity-slot dispatch with per-"
         "(group, expert) capacity ceil(cf * k * group_tokens / E).  "
         "0 (default) keeps the per-op attribute.  Under an 'expert' mesh "
         "the sparse path is the explicit all-to-all shard_map program "
         "(docs/moe.md).")
register("MXNET_TP_MODE", str, "megatron",
         "Tensor-parallel sharding plan over the 'model' mesh axis: "
         "'megatron' (default) pairs column-parallel with row-parallel "
         "weights from a graph walk (parallel/tp_rules.py) so one psum per "
         "pair replaces per-layer all-gathers; 'naive' restores the "
         "round-3 blanket dim-0 sharding (for A/B comparison — "
         "tests/test_tensor_parallel.py measures the collective-count "
         "difference from compiled HLO).")
register("MXNET_METRIC_SYNC_PERIOD", int, 0,
         "With device-side metric accumulation active, pull the metric "
         "accumulators to the host every N training steps.  0 (default) "
         "syncs only at natural boundaries (epoch end, or whenever a "
         "callback reads the metric), eliminating the per-step "
         "device->host round trip of the classic loop.")
register("MXNET_DEVICE_METRICS", bool, True,
         "Fold loss/accuracy accumulation into the donated train-step "
         "program as extra donated state for metrics that implement the "
         "device protocol (metric.py device_batch).  The training loop "
         "then never materializes per-step outputs on the host; 0 "
         "restores the classic host-side metric.update path.")
register("MXNET_MAX_STEPS_IN_FLIGHT", int, 2,
         "Upper bound on dispatched-but-unfinished training steps in "
         "fit(): the loop rides JAX's async dispatch and blocks on the "
         "step-K-behind result rather than the current one, overlapping "
         "host-side batch prep with device compute while bounding live "
         "device buffers.  1 = fully synchronous loop (the dependency-"
         "engine analog of the reference's NaiveEngine).")
register("MXNET_PREFETCH_DEPTH", int, 2,
         "How many batches DevicePrefetchIter keeps device-resident "
         "ahead of the consumer (the dmlc::ThreadedIter capacity analog, "
         "moved past the host->device DMA).")
register("MXNET_DEVICE_PREFETCH", bool, True,
         "Let fit() wrap the training iterator in a DevicePrefetchIter "
         "when a fused train step is active, so the next batches are "
         "device_put with the executor group's input sharding on a "
         "background thread while the current step runs.  0 = feed "
         "batches from the host thread as the reference does.")
register("MXNET_DECODE_SLOTS", int, 8,
         "Batch width of the continuous-batching serving loop "
         "(decode.DecodeServer): the decode-step program always runs this "
         "many in-flight sequence slots at a fixed shape, so admitting or "
         "retiring a request never retraces.  Free slots refill from the "
         "request queue after every step (Orca-style iteration-level "
         "scheduling).")
register("MXNET_DECODE_DONATE", bool, True,
         "Donate the KV caches (and per-slot lengths) into the jitted "
         "decode-step program so XLA appends in place — zero steady-state "
         "allocation in the token loop.  0 keeps the inputs alive across "
         "the call for debugging (inspect a cache mid-generation).")
register("MXNET_KV_DTYPE", str, "",
         "Storage dtype for the decode KV caches (decode.DecodePredictor): "
         "'int8', 'float8_e4m3fn' ('f8e4m3') or 'float8_e5m2' ('f8e5m2') "
         "quantize K/V in cache_append with per-(token, head) fp32 scales "
         "and dequantize inside sdpa_decode/sdpa_verify, halving or "
         "quartering the bytes every decode step streams from the cache — "
         "decode's bandwidth bound.  Empty (default) stores full-precision "
         "K/V.  The mxlint cache-bytes pass budgets the resulting cache "
         "size and flags an f32 cache in a quantized config.")
register("MXNET_KV_PAGED", bool, False,
         "Store decode KV caches as fixed-size pages in one shared device "
         "pool per attention node instead of a dense ring buffer per slot "
         "(decode.DecodePredictor paged mode + the mxnet_tpu.serve memory "
         "manager): per-slot page tables are traced DATA, so admissions, "
         "copy-on-write prefix forks and retirements never retrace, and "
         "HBM scales with tokens actually live instead of "
         "slots x max-context (vLLM's PagedAttention plan).  Arms prefix "
         "sharing (matching prompts map their leading pages to shared "
         "refcounted pages and prefill only the tail) and chunked prefill.")
register("MXNET_KV_PAGE_TOKENS", int, 16,
         "Tokens per KV page in paged mode.  Smaller pages waste less "
         "memory on the last partial page per sequence and share prefixes "
         "at finer granularity; larger pages mean fewer gather indices and "
         "less page-table overhead.  cache_len must divide by it.")
register("MXNET_KV_POOL_PAGES", int, 0,
         "Total pages in the shared KV pool (page id 0 is reserved as the "
         "scratch page).  0 (default) sizes the pool to fit every slot at "
         "full capacity (slots x cache_len/page_tokens + 1) — safe but no "
         "memory win; production serving sizes it to the live-token "
         "working set and lets admission backpressure (mxnet_tpu.serve."
         "PageAllocator reservations) queue requests that do not fit.")
register("MXNET_PREFILL_CHUNK", int, 0,
         "Chunk width for paged-mode prefill: prompts are admitted in "
         "fixed-size chunks of this many tokens, interleaved with decode "
         "steps, so a long prompt does not stall the whole serving batch "
         "(one traced chunk program per width — still zero retraces).  "
         "0 (default) prefills each prompt's tail in one chunk sized to "
         "the admission window.")
register("MXNET_SPEC_K", int, 0,
         "Tokens drafted per speculative-decoding step (decode.DecodeServer "
         "/ DecodePredictor.generate_speculative).  A proposer drafts k "
         "tokens, ONE fixed-shape verify pass through the target scores "
         "all k+1 positions, and the acceptance-rejection rule keeps the "
         "output distribution exactly the target's — each step commits "
         "1..k+1 tokens for one target forward.  0 (default) disables "
         "speculation; the serving loop then takes the plain one-token "
         "decode step.")
register("MXNET_SPEC_NGRAM", int, 2,
         "Suffix length the model-free n-gram proposer (decode."
         "NGramProposer) matches against each sequence's own history "
         "(prompt-lookup / self-speculation).  Longer suffixes propose "
         "more conservatively: fewer matches, higher acceptance when one "
         "hits.")
register("MXNET_DECODE_MAX_NEW", int, 256,
         "Default cap on generated tokens per request in the serving loop "
         "when the caller gives no explicit max_new_tokens (a sequence "
         "with no EOS must retire eventually so its slot can refill).")
register("MXNET_TRANSFER_GUARD", str, "off",
         "Arm jax.transfer_guard_device_to_host around fit()'s hot loop: "
         "'log' reports and 'disallow' raises on a device->host transfer "
         "inside the training epoch, making the async loop's zero-per-"
         "step-host-syncs invariant a runtime-checked guarantee on real "
         "accelerators (same-device CPU 'transfers' are free and never "
         "trip it; the static half is analysis.HostSyncPass).  'off' "
         "(default) leaves the loop unguarded — required for the classic "
         "host-metric path, which reads outputs every step.")
register("MXNET_ANALYSIS_SUPPRESS", str, "",
         "Comma-separated suppression patterns for static-analysis "
         "findings: 'pass-name[:program[:code]]' with '*' wildcards "
         "(e.g. 'flop-dtype:decode_step:f32-dot').  Applied on top of "
         "the budget file's suppressions list; suppressed findings stay "
         "in reports, marked, so waivers are visible.")
register("MXNET_ANALYSIS_BUDGETS", str, "",
         "Path to the static-analysis budget file consumed by "
         "analysis.load_budgets / tools/mxlint.py.  Empty (default) = "
         "the committed benchmarks/budgets.json.")
register("MXNET_CKPT_DIR", str, "",
         "Directory for elastic fence checkpoints (mxnet_tpu.elastic).  "
         "Set together with MXNET_CKPT_PERIOD to arm fit()-integrated "
         "async fenced checkpointing: at every period-th step fence the "
         "donated params/slots/aux chain is snapshotted on device (cheap "
         "async copies) and written as a committed orbax step directory "
         "by a background writer thread, with a sidecar carrying the loop "
         "state (epoch/step, RNG chain, metric sums, iterator cursor) for "
         "deterministic resume.  Empty (default) = no automatic "
         "checkpointing; an explicit elastic.ElasticController passed to "
         "fit() overrides the environment.")
register("MXNET_CKPT_PERIOD", int, 0,
         "Steps between elastic fence checkpoints (0 = off).  Snapshots "
         "ride the in-flight step machinery: the copy dispatch depends on "
         "the latest dispatched step, so the loop never blocks on the "
         "device to checkpoint.")
register("MXNET_CKPT_ASYNC", bool, True,
         "Write fence checkpoints on a background writer thread (at most "
         "ONE write in flight; a fence landing while a write is busy is "
         "skipped, not queued — the next fence writes).  0 = synchronous "
         "saves on the loop thread, the A/B baseline whose stall the "
         "checkpoint_stall_fraction bench field quantifies (its d2h is "
         "the sanctioned fence transfer, exempt from "
         "MXNET_TRANSFER_GUARD).")
register("MXNET_CKPT_KEEP", int, 2,
         "Committed fence checkpoints to retain (older step directories "
         "are pruned after each commit; 0 = keep all).  Two is the "
         "crash-safe minimum floor: the newest commit plus its "
         "predecessor, in case a torn successor must be discarded.")
register("MXNET_CKPT_RESUME", bool, True,
         "Auto-resume: when the checkpoint directory already holds a "
         "committed step at fit() start, restore it (params, optimizer "
         "slots, RNG chain, metric sums, iterator cursor) and continue "
         "from the recorded epoch/step instead of training from scratch.  "
         "0 = always start fresh (the directory still receives new "
         "checkpoints).")
register("MXNET_ELASTIC_POLL", int, 1,
         "Poll the failure monitor every N step fences (elastic liveness "
         "protocol).  Each poll is num_workers stat/read calls on the "
         "heartbeat directory — no device work.")
register("MXNET_ELASTIC_TIMEOUT", float, 10.0,
         "Heartbeat staleness threshold (seconds) for the elastic "
         "FailureMonitor: a rank whose stamp is older is declared dead "
         "and the mesh shrinks off its data rows at the next fence.")
register("MXNET_ELASTIC_GRACE", float, 30.0,
         "Startup allowance (seconds) for registered-but-not-yet-stamped "
         "workers: within this window of the heartbeat directory's epoch "
         "a missing first stamp does not read as dead.")
register("MXNET_TELEMETRY", bool, True,
         "Arm the unified telemetry subsystem (mxnet_tpu.obs): timed "
         "dispatch wrappers on the compiled programs (a span a "
         "dispatch), always-on timeline spans and instant events "
         "(bounded ring buffer), and the lazy readers of each "
         "program's HLO.  Purely host-side — compiled HLO is byte-identical "
         "on or off (tests/test_obs.py pins it).  The step_stats loop "
         "counters predate the subsystem and stay on regardless.")
register("MXNET_TRACE_BUFFER", int, 65536,
         "Capacity (events) of the always-on trace-timeline ring buffer "
         "(mxnet_tpu.obs.timeline).  Oldest events are evicted first, so "
         "an armed timeline costs bounded memory however long the "
         "process lives; profiler.dump_profile exports whatever is "
         "retained as Chrome-trace JSON.")
register("MXNET_METRICS_EXPORT", str, "",
         "Path for the metrics registry's JSON-lines snapshot exporter: "
         "with MXNET_METRICS_EXPORT_PERIOD > 0, a background thread "
         "appends one {ts, metrics} line per period.  Empty (default) = "
         "no file export; the registry is still readable in-process "
         "(obs.registry.snapshot) and over HTTP (MXNET_METRICS_PORT).")
register("MXNET_METRICS_EXPORT_PERIOD", float, 0.0,
         "Seconds between JSON-lines metric snapshots written to "
         "MXNET_METRICS_EXPORT (0 = off).")
register("MXNET_METRICS_PORT", int, 0,
         "Serve the metrics registry over HTTP from decode.DecodeServer "
         "(obs.MetricsServer, 127.0.0.1): /metrics is the Prometheus "
         "text format, /metrics.json the snapshot, /trace the current "
         "timeline as Chrome-trace JSON.  0 (default) = no server.")
register("MXNET_FLEET_SWAP", bool, True,
         "Arm preemption/swap in the paged serving loop "
         "(decode.DecodeServer / serve.swap): when the page pool cannot "
         "admit the queue head for MXNET_FLEET_DECODE_BOUND consecutive "
         "decode iterations, the lowest-priority (then longest-running) "
         "slot's pages move to host RAM as a restorable record, the "
         "waiter admits on the freed pages, and the victim re-queues — "
         "readmitted later by restoring its pages bit-exactly at the "
         "same ring positions, so a long decode can no longer wedge "
         "admission.  0 = classic backpressure only (the queue waits "
         "for retirements).")
register("MXNET_FLEET_DECODE_BOUND", int, 8,
         "Fair-admission bound for the paged serving loop: consecutive "
         "pool-gate-blocked decode iterations tolerated before a "
         "preemption swap-out (MXNET_FLEET_SWAP) makes room for the "
         "queue head.  Bounds the admission-starvation tail a long "
         "wrapped decode can inflict (single host AND fleet p95 TTFT); "
         "equal-priority thrash is bounded to one swap per this many "
         "iterations — round-robin time slicing, every request still "
         "finishes.  0 disables the bound (swap never triggers on "
         "fairness grounds).")
register("MXNET_FLEET_PREFILL_THRESHOLD", float, 0.5,
         "Disaggregation routing threshold (serve.fleet.Router): a "
         "prompt whose best cache-aware chain match covers at least "
         "this fraction of its tokens admits DIRECTLY on the matching "
         "decode host (its chunked prefill computes only the tail); "
         "colder prompts go to a dedicated prefill worker, whose "
         "committed pages migrate to the least-loaded decode host "
         "(DistServe-style prefill/decode split).  Only consulted when "
         "the router has prefill workers.")
register("MXNET_AOT", bool, False,
         "Arm the AOT-serialized program pipeline (mxnet_tpu.programs."
         "aot): DecodeServer.serve_open prepares every paged serving "
         "program (chunk prefill, decode, verify, commit, fork, page "
         "extract/install) through the content-addressed program cache "
         "— a cache hit DESERIALIZES the compiled executable "
         "(milliseconds) instead of trace+lower+compile (seconds to "
         "minutes per host), and a miss compiles once and saves the "
         "executable back for the next host's cold start.  Loaded "
         "programs are byte-identical to the JIT path (same lowering) "
         "and dispatch with ZERO traces; an argument signature the "
         "executable was not compiled for falls back to JIT with a "
         "visible warning.  Covers paged single-host predictors; "
         "mesh-sharded and dense predictors keep the JIT path (logged "
         "at serve_open — serialized executables pin device layouts).  "
         "0 (default) = classic JIT-on-first-call.")
register("MXNET_PROGRAM_CACHE", str, "",
         "Directory of the content-addressed AOT program cache "
         "(mxnet_tpu.programs.aot): <fingerprint>.aotx serialized "
         "executables plus .json sidecars, keyed over (abstract args, "
         "donation map, partition rules, jax version, backend, mesh "
         "shape, model graph digest) — any drift is a key miss, never "
         "a wrong program.  Empty (default) = <checkout>/"
         ".mxnet_programs (cache_dirs.PROGRAM_CACHE).  Shared read-only across fleet hosts; equal keys "
         "prove byte-identical programs (docs/programs.md).")
register("MXNET_HEARTBEAT_DIR", str, "",
         "Shared directory for worker liveness heartbeats (failure "
         "detection, parallel/health.py; reference ps-lite heartbeats). "
         "Read dynamically at KVStore creation, not cached here.")
register("MXNET_IS_RECOVERY", bool, False,
         "Mark this worker as a restart: startup-only barriers are skipped "
         "(reference kvstore_dist.h is_recovery).  Read dynamically at "
         "each startup barrier, not cached here.")
