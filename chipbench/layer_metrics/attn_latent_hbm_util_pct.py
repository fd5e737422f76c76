"""The decode step's latent attention (the absorbed form) as a share of its
roofline: what the tick's own count says it must read
(``work_mla.absorbed_step_bytes``: the ``latent_rows`` of ``serve.readback``,
(slot, cached position, layer) triples, once each, the mean over the window's
ticks, and each layer's up-projection), over the decode program's device time
a run in latent attention (``work_mla.device_seconds``: under the
``mx.attn_latent`` scopes, and in the compiler's moves between two of them),
as a share of the chip's HBM peak.  A walk that gathers a copy of the rows,
lays it out again and reads it twice shows all of it here.
"""

from chipbench import work_mla, work_ssm


def read(facts):
    rows = work_ssm.noted(facts, "serve.readback", "latent_rows")
    took = work_mla.device_seconds(facts, r"paged_decode")
    if not rows or not took or not took[0]:
        return None
    seconds, runs = took
    need = work_mla.absorbed_step_bytes(facts["config"],
                                        sum(rows) / len(rows))
    return 100.0 * need / (seconds / runs) \
        / facts["peaks"]["hbm_bytes_per_s"]
