"""The chunk program's latent attention (the expanded form) as a share of the
chip's bf16 peak: for the chunks of the window (each one's first position and
real tokens, from the ``serve.prefill`` spans),
``work_mla.expanded_chunk_flops`` (every live position expanded once, causal
QK and PV over them, in every layer run), the mean a chunk, over the chunk
program's device time a run in latent attention (``work_mla.device_seconds``:
under the ``mx.attn_latent`` scopes, and in the compiler's moves between two
of them).
"""

from chipbench import work_mla, work_ssm


def read(facts):
    chunks = list(zip(work_ssm.noted(facts, "serve.prefill", "pos"),
                      work_ssm.noted(facts, "serve.prefill", "tokens")))
    took = work_mla.device_seconds(facts, r"chunk_impl")
    if not chunks or not took or not took[0]:
        return None
    seconds, runs = took
    flops = sum(work_mla.expanded_chunk_flops(facts["config"], pos, tokens)
                for pos, tokens in chunks) / len(chunks)
    return 100.0 * flops / facts["peaks"]["bf16_flops_per_s"] \
        / (seconds / runs)
