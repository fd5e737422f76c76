"""Bytes a decode tick must read (the weights once, the live keys and
values) over the decode program's device time, as a share of the chip's HBM
peak.
"""

from chipbench import trace, work

MODULE = r"paged_decode"


def read(facts):
    ms = trace.median(trace.module_ms(facts["trace"], MODULE))
    if ms is None or "mean_live_tokens" not in facts:
        return None
    need = work.decode_step_bytes(facts["config"], facts["traffic"],
                                  facts["mean_live_tokens"])
    return 100.0 * need / (ms / 1e3) / facts["peaks"]["hbm_bytes_per_s"]
