"""Share of the first chip's busy time in the window spent under the
``mx.attn`` scopes of the train step (scores, softmax, both einsums, forward
and backward): ``XLA Ops`` events joined to the program's scope map.
"""

from chipbench import scopes


def read(facts):
    return scopes.layer_pct(facts, "attn")
