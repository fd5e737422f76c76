"""(slot, mixer layer) rows whose recurrent state a decode tick advanced:
the mean over the window's ticks of what the decode program counted beside
its tokens (``ssm_rows`` in the arguments of ``serve.readback``; idle and
mid-prefill slots left out).  Full is slots x layers run.
"""

from chipbench import work_ssm


def read(facts):
    rows = work_ssm.noted(facts, "serve.readback", "ssm_rows")
    return sum(rows) / len(rows) if rows else None
