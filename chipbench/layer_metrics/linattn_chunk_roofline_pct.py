"""The chunk program's lightning recurrence as a share of its roofline: for
the chunks of the window (the real tokens of each, from the ``serve.prefill``
spans), ``max(FLOPs / peak FLOP/s, bytes / HBM peak)`` by
``work_sala.lightning_chunk_work`` over the lightning layers run, the mean a
chunk, over the chunk program's device time under ``mx.linattn/chunk`` a run.
"""

from chipbench import work_sala, work_ssm


def read(facts):
    chunks = work_ssm.noted(facts, "serve.prefill", "tokens")
    took = work_ssm.scope_seconds(facts, r"chunk_impl", {"linattn/chunk"})
    if not chunks or not took or not took[0]:
        return None
    seconds, runs = took
    cfg, peaks = facts["config"], facts["peaks"]
    floor = 0.0
    for tokens in chunks:
        flops, moved = work_sala.lightning_chunk_work(cfg, tokens)
        floor += max(flops / peaks["bf16_flops_per_s"],
                     moved / peaks["hbm_bytes_per_s"])
    floor *= work_sala.layers_run(cfg)[1] / len(chunks)
    return 100.0 * floor / (seconds / runs)
