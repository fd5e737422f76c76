"""Seconds tracing and lowering, under a program's name or ``(eager)``, the
whole process up to now: what jax's persistent cache does not save.
"""
from chipbench import startup


def read(facts):
    return startup.compile_seconds(facts, ("trace", "lower"))
