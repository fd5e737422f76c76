"""(slot, cached position, latent layer) triples a decode tick attended in
the absorbed form: the mean over the window's ticks of what the serving loop
counted from its own lengths as it queued the step (``latent_rows`` in the
arguments of ``serve.readback``; idle and mid-prefill slots left out).  A
triple is ``work_mla.row_values`` values read.  The chunks' rows, the
expanded form's, are ``mx_attn_latent_rows_total{form=expanded}`` in the side
file's counters.
"""

from chipbench import work_ssm


def read(facts):
    rows = work_ssm.noted(facts, "serve.readback", "latent_rows")
    return sum(rows) / len(rows) if rows else None
