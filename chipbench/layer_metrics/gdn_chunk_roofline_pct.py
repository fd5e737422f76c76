"""The chunk program's Gated DeltaNet as a share of its roofline: for the
chunks of the window (the real tokens of each, from the ``serve.prefill``
spans), ``max(FLOPs / peak FLOP/s, bytes / HBM peak)`` by
``work_gdn.chunk_work`` (the matrix form: products, one mask, one solve a
block) over the delta layers run, the mean a chunk, over the chunk program's
device time under ``mx.gdn/chunk`` and ``mx.gdn/solve`` (the block's
triangular solve) a run.
"""

from chipbench import work_gdn, work_ssm


def read(facts):
    chunks = work_ssm.noted(facts, "serve.prefill", "tokens")
    took = work_ssm.scope_seconds(facts, r"chunk_impl",
                                  {"gdn/chunk", "gdn/solve"})
    if not chunks or not took or not took[0]:
        return None
    seconds, runs = took
    cfg, peaks = facts["config"], facts["peaks"]
    floor = 0.0
    for tokens in chunks:
        flops, moved = work_gdn.chunk_work(cfg, tokens)
        floor += max(flops / peaks["bf16_flops_per_s"],
                     moved / peaks["hbm_bytes_per_s"])
    floor *= work_gdn.delta_layers(cfg) / len(chunks)
    return 100.0 * floor / (seconds / runs)
