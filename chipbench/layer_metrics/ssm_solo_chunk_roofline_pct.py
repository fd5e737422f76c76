"""The chunk program's convolution and chunked scan as a share of their
roofline, where the mixer stands alone in its layers: for the chunks of the
window (the real tokens of each, from the ``serve.prefill`` spans),
``max(FLOPs / peak FLOP/s, bytes / HBM peak)`` by
``work_nemotron_h.chunk_scan_work`` over the ``M`` layers run (and no other),
the mean a chunk, over the chunk program's device time under ``mx.ssm/scan``
and ``mx.ssm/conv`` a run.
"""

from chipbench import work_nemotron_h as work


def read(facts):
    cfg, peaks = facts["config"], facts["peaks"]
    if "hybrid_override_pattern" not in cfg:
        return None
    chunks = work.noted(facts, "serve.prefill", "tokens")
    took = work.scope_seconds(facts, r"chunk_impl", {"ssm/scan", "ssm/conv"})
    if not chunks or not took or not took[0]:
        return None
    seconds, runs = took
    floor = 0.0
    for tokens in chunks:
        flops, moved = work.chunk_scan_work(cfg, tokens)
        floor += max(flops / peaks["bf16_flops_per_s"],
                     moved / peaks["hbm_bytes_per_s"])
    floor *= work.letters(cfg).count("M") / len(chunks)
    return 100.0 * floor / (seconds / runs)
