"""Mean time of one ``serve_tick()`` in which the device ran nothing: the
harness's span around the tick less the device's busy time inside it.
"""

from chipbench import trace


def read(facts):
    rows = trace.host_busy_inside(facts["trace"], "chipbench:serve_tick")
    if not rows:
        return None
    return sum(span - busy for span, busy in rows) / len(rows)
