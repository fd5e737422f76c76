"""Share of the first chip's busy time in the window spent under
``mx.norm`` (BatchNorm; the LM's LayerNorm statistics), forward and
backward.
"""

from chipbench import scopes


def read(facts):
    return scopes.layer_pct(facts, "norm")
