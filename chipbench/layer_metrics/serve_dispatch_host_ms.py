"""Mean per tick of the program's ``serve.admit`` + ``serve.prefill`` +
``serve.decode_dispatch`` spans, less the device's busy time inside them:
host time spent getting work to an idle device.
"""

from chipbench import spans, trace

PHASES = ("serve.admit", "serve.prefill", "serve.decode_dispatch")


def read(facts):
    al = spans.aligned(facts, "serve")
    if al is None or not facts["trace"].get("devices"):
        return None
    total = 0
    for kids in spans.children(al, PHASES).values():
        iv = trace.merge([(s, e) for _, s, e, _ in kids])
        total += trace.length(iv) - spans.device_busy_inside(
            facts["trace"], iv)
    return total / len(al["tops"]) / 1e6
