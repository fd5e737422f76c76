"""Blocks the decode step's sparse layers attended as a share of the blocks
their contexts hold: ``sparse_blocks_chosen / sparse_blocks_live`` of what
the decode program counted beside its tokens (the arguments of
``serve.readback``), the mean over the window's ticks.  Lower is sparser:
``topk`` of a context's blocks past ``dense_len``, 100 under it.
"""

from chipbench import work_ssm


def read(facts):
    chosen = work_ssm.noted(facts, "serve.readback", "sparse_blocks_chosen")
    live = work_ssm.noted(facts, "serve.readback", "sparse_blocks_live")
    shares = [100.0 * c / l for c, l in zip(chosen, live) if l]
    return sum(shares) / len(shares) if shares else None
