"""Forward + backward FLOPs the model requires per sample x samples/s over
chips x the chip's bf16 peak.  An end-to-end utilisation, not a kernel's
roofline share.
"""

from chipbench import work


def read(facts):
    if "rate" not in facts or "batch" not in facts:
        return None
    flops = work.train_flops_per_sample(facts["config"], facts["traffic"])
    return 100.0 * flops * facts["rate"] / (
        facts["chips"] * facts["peaks"]["bf16_flops_per_s"])
