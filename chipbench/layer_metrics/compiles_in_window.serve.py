"""Backend compiles inside the measured window, from jax's monitoring
events.  Should be 0.
"""


def read(facts):
    return facts["compiles_in_window"]
