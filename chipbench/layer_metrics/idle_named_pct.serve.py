"""Share of the window's device idle time whose gap lies under a program
span below the loop's top span (a child of ``serve.tick``), once the program's
stamps are on the trace's clock.
"""

from chipbench import spans


def read(facts):
    return spans.idle_named_pct(facts, "serve")
