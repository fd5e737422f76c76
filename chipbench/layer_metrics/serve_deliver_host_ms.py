"""Mean per tick of the program's ``serve.deliver`` span (tokens to
requests, histories, retirement): the device has nothing queued meanwhile.
"""

from chipbench import spans


def read(facts):
    al = spans.aligned(facts, "serve")
    if al is None:
        return None
    kids = spans.children(al, ("serve.deliver",))
    return sum(e - s for k in kids.values() for _, s, e, _ in k) \
        / len(al["tops"]) / 1e6
