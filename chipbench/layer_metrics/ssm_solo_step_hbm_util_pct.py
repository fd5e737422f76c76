"""The decode step's recurrence as a share of its roofline, where the mixer
stands alone in its layers: the rows a tick advanced (``ssm_rows``, the mean
over the window's ticks) times what one row must move
(``work_nemotron_h.state_step_bytes``: the state and the conv tail, read once
and written once), over the decode program's device time under
``mx.ssm/step`` a run, as a share of the chip's HBM peak.  A program that
reads the state twice, or copies it, moves more than that and shows it here.
"""

from chipbench import work_nemotron_h as work


def read(facts):
    cfg = facts["config"]
    if "hybrid_override_pattern" not in cfg:
        return None
    rows = work.noted(facts, "serve.readback", "ssm_rows")
    took = work.scope_seconds(facts, r"paged_decode", {"ssm/step"})
    if not rows or not took or not took[0]:
        return None
    seconds, runs = took
    need = sum(rows) / len(rows) * work.state_step_bytes(cfg)
    return 100.0 * need / (seconds / runs) \
        / facts["peaks"]["hbm_bytes_per_s"]
