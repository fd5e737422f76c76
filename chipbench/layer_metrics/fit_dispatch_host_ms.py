"""Mean host time of one ``fit_step`` span outside its ``input_wait`` and
``host_wait`` children: what the loop itself costs an iteration (dispatch of
the step, metric update, callback), from the program's own spans.
"""

from chipbench import spans


def read(facts):
    al = spans.aligned(facts, "train")
    if al is None:
        return None
    waits = spans.children(al, ("input_wait", "host_wait"))
    own = [t1 - t0 - sum(e - s for _, s, e, _ in waits[i])
           for i, (_, t0, t1, _) in enumerate(al["tops"])]
    return sum(own) / len(own) / 1e6
