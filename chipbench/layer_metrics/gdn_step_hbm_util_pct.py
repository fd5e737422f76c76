"""The decode step's delta rule as a share of its roofline: the rows a tick
advanced (``gdn_rows``, the mean over the window's ticks) times what one row
must move (``work_gdn.state_step_bytes``: the matrix state and the conv
tail, read once and written once), over the decode program's device time
under ``mx.gdn/step`` a run, as a share of the chip's HBM peak.  A program
that passes over the state twice, or copies it, moves more than that and
shows it here.
"""

from chipbench import work_gdn, work_ssm


def read(facts):
    rows = work_ssm.noted(facts, "serve.readback", "gdn_rows")
    took = work_ssm.scope_seconds(facts, r"paged_decode", {"gdn/step"})
    if not rows or not took or not took[0]:
        return None
    seconds, runs = took
    need = sum(rows) / len(rows) * work_gdn.state_step_bytes(facts["config"])
    return 100.0 * need / (seconds / runs) \
        / facts["peaks"]["hbm_bytes_per_s"]
