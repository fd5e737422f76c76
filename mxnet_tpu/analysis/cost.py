"""Static roofline cost of one compiled program — FLOPs + traffic bytes.

What the analyzer's passes, ``benchmarks/bench_moe.py`` and the tests
price a program with —

* **FLOPs** from :func:`~mxnet_tpu.analysis.hlo_parse.dot_flops` over
  the LOWERED StableHLO (what the program asked for, before backend
  legalization — the same accounting the flop-dtype pass audits and the
  decode bench's O(1)-in-prefix assertion uses);
* **traffic bytes** as the sum of argument + output aval bytes through
  :func:`~mxnet_tpu.analysis.hlo_parse.shape_bytes`'s width table
  (f8/sub-byte aware — the same table that prices KV caches) PLUS the
  program's collective wire bytes
  (:func:`~mxnet_tpu.analysis.hlo_parse.stablehlo_collective_stats`
  over the same lowered text — the MoE all-to-all dispatch/combine,
  ring ppermutes and Megatron psums all land here, so an
  expert-parallel step's cost prices its exchanges).  This is
  the program's memory-traffic FLOOR: every operand read once, every
  result written once, every collective payload moved once;
  intermediates that spill past on-chip memory add to it, so
  achieved-bytes/s against HBM peak is a lower bound.

Everything here is trace+lower only — no compile, no execution, no
device work — and never runs on a hot path.
"""
from __future__ import annotations

__all__ = ["artifact_cost", "aval_bytes", "program_cost"]


def aval_bytes(tree):
    """Total bytes of every array leaf in ``tree`` (arrays or
    ShapeDtypeStructs), sized through the analysis width table."""
    import jax.tree_util as jtu

    from .hlo_parse import shape_bytes, shape_str

    return sum(shape_bytes(shape_str(leaf.shape, leaf.dtype))
               for leaf in jtu.tree_leaves(tree))


def program_cost(fn, args):
    """``{"flops", "bytes", "collective_bytes", "gather_bytes",
    "sort_scatter_bytes"}`` of a ``jax.jit``-wrapped callable at
    ``args`` (abstract or concrete): dot FLOPs from one trace→lower,
    arg+output bytes from the avals, collective wire bytes from the
    lowered StableHLO's explicit collectives, materialized-gather
    intermediate bytes
    (:func:`~mxnet_tpu.analysis.hlo_parse.stablehlo_gather_stats`:
    2x each gather result — one write, one re-read), and materialized
    sort/scatter intermediate bytes
    (:func:`~mxnet_tpu.analysis.hlo_parse.stablehlo_sort_scatter_stats`,
    same 2x rule).  The gather term is what prices the einsum decode
    path honestly: ``paged_gather``'s (B, M*page_tokens, E) dense-ring
    view of the KV pool is the largest intermediate in the serving
    system and is invisible to arg/output accounting, which understated
    decode bytes and OVERstated decode MFU until ISSUE-11 (a view longer
    than one block is gathered by a loop whose trip count is data,
    ``ops.attention._attend_live_blocks``: a static count prices ONE
    step of it, and the caller scales by the steps it assumes).  The
    sort/scatter term does the same for the MoE dispatch algorithms
    (``MXNET_MOE_DISPATCH``): the sort path's key sort and slot scatter
    are priced, so the two compare honestly against the one-hot
    cumsum pack it replaced.  All extras fold into ``bytes`` and break
    out separately so a caller can show them.
    Callers holding trace-counting instrumentation must arm their
    probing flag around this (the trace here is a probe, same economics
    as ``artifact_from_jit``)."""
    import jax

    from .hlo_parse import (dot_flops, stablehlo_collective_stats,
                            stablehlo_gather_stats,
                            stablehlo_sort_scatter_stats)

    lowered = fn.trace(*args).lower().as_text()
    flops = dot_flops(lowered)
    coll = stablehlo_collective_stats(lowered)["total"]["bytes"]
    gath = stablehlo_gather_stats(lowered)["bytes"]
    srtsc = stablehlo_sort_scatter_stats(lowered)["total"]["bytes"]
    out = jax.eval_shape(fn, *args)
    return {"flops": int(flops),
            "bytes": int(aval_bytes((args, out))) + int(coll) + int(gath)
            + int(srtsc),
            "collective_bytes": int(coll),
            "gather_bytes": int(gath),
            "sort_scatter_bytes": int(srtsc)}


def artifact_cost(artifact):
    """Priced quantities of a BUILT artifact — one drift-snapshot row.

    Unlike :func:`program_cost` this needs no callable: everything is
    re-derived from the artifact's recorded text surfaces and metadata,
    so the drift gate (``analysis.passes.DriftPass`` + ``mxlint
    --record/--check``) compares exactly what the other passes audit.
    Quantities from a missing surface are simply absent — the pass
    reports the asymmetry instead of guessing zero."""
    from .hlo_parse import (collective_stats, dot_flops,
                            input_output_aliases, stablehlo_gather_stats,
                            stablehlo_sort_scatter_stats)

    row = {"donated": int(artifact.donated_leaves or 0)}
    if artifact.stablehlo_text is not None:
        row["dot_flops"] = int(dot_flops(artifact.stablehlo_text))
        row["gather_bytes"] = int(
            stablehlo_gather_stats(artifact.stablehlo_text)["bytes"])
        row["sort_scatter_bytes"] = int(stablehlo_sort_scatter_stats(
            artifact.stablehlo_text)["total"]["bytes"])
    if artifact.compiled_text is not None:
        stats = collective_stats(artifact.compiled_text)
        row["collective_count"] = int(stats["total"]["count"])
        row["collective_bytes"] = int(stats["total"]["bytes"])
        row["aliased"] = len({param for _, param in
                              input_output_aliases(artifact.compiled_text)})
    if artifact.meta.get("cache_bytes") is not None:
        row["cache_bytes"] = int(artifact.meta["cache_bytes"])
    return row
