"""Seconds this process spent in backend compiles (a persistent-cache read
counts, as the time to fetch it), from jax's monitoring events.
"""


def read(facts):
    return facts["compile_s"]
