"""The routed product's share of its roofline, experts of two matrices: the
expert weights a decode tick must read over the decode program's device time
under ``mx.moe/experts`` a tick, as a share of the chip's HBM peak.  What
must be read is what the window routed: the held experts that at least one
of a tick's tokens chose, summed over the ``E`` layers (the decode program's
own count a tick, ``moe_expert_visits`` of the ``serve.readback`` spans,
averaged over the window's ticks), times one expert's bytes
(``work_nemotron_h.expert_bytes``: two matrices of ``moe_intermediate_size``,
whatever the stored layout pads).  A program that reads every held expert
whatever was chosen, or padding, reads more than that and shows it here.
"""

from chipbench import work_nemotron_h as work


def read(facts):
    cfg = facts["config"]
    if "hybrid_override_pattern" not in cfg:
        return None
    visits = work.noted(facts, "serve.readback", "moe_expert_visits")
    took = work.scope_seconds(facts, r"paged_decode", {"moe/experts"})
    if not visits or not took or not took[0]:
        return None
    seconds, runs = took
    need = sum(visits) / len(visits) * work.expert_bytes(cfg)
    return 100.0 * need / (seconds / runs) \
        / facts["peaks"]["hbm_bytes_per_s"]
