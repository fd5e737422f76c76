"""Drafts the stack accepted of those the prediction block offered, over the
window: ``spec_accepted`` / ``spec_proposed`` summed over the arguments of the
program's ``serve.readback`` spans inside the window, one a tick (a draft a
live slot a tick).  None where the program notes neither (no self-drafting
server ran, or the program has no such counter).
"""

from chipbench import work_moe


def read(facts):
    proposed = sum(work_moe.routed(facts, "spec_proposed"))
    if not proposed:
        return None
    return 100.0 * sum(work_moe.routed(facts, "spec_accepted")) / proposed
