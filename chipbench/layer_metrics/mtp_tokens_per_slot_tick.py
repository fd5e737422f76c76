"""Tokens a live slot committed a tick: ``tokens_committed`` over
``spec_proposed`` (one draft a live slot a tick) from the arguments of the
window's ``serve.readback`` spans; 1 + the acceptance.  None where the
program notes neither.
"""

from chipbench import work_moe


def read(facts):
    slot_ticks = sum(work_moe.routed(facts, "spec_proposed"))
    if not slot_ticks:
        return None
    return sum(work_moe.routed(facts, "tokens_committed")) / slot_ticks
