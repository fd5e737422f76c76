"""Share of the first chip's busy time in the window spent under
``mx.head_loss``: the vocabulary projection and the softmax loss, forward
and backward.
"""

from chipbench import scopes


def read(facts):
    return scopes.layer_pct(facts, "head_loss")
