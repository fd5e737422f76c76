"""(slot, delta layer) rows whose matrix state a decode tick advanced: the
mean over the window's ticks of what the decode program counted beside its
tokens (``gdn_rows`` in the arguments of ``serve.readback``; idle and
mid-prefill slots left out).  Full is slots x delta layers run; a row is
``work_gdn.state_step_bytes`` read and written.
"""

from chipbench import work_ssm


def read(facts):
    rows = work_ssm.noted(facts, "serve.readback", "gdn_rows")
    return sum(rows) / len(rows) if rows else None
