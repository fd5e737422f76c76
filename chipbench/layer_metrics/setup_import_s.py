"""Seconds the process spent importing: ``mx_setup_seconds{phase="import*"}``
summed (``import.self``, ``import.jax``, ``import.pallas``).
"""
from chipbench import startup


def read(facts):
    return startup.setup_seconds(facts, "import")
