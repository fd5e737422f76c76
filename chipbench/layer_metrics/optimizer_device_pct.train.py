"""Share of the first chip's busy time in the window spent under
``mx.optimizer``: the parameter update inside the train step.
"""

from chipbench import scopes


def read(facts):
    return scopes.layer_pct(facts, "optimizer")
