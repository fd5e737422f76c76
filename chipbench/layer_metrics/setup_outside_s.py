"""Seconds before the loop's first top span that no phase of the program and
no compile stage covers: the interpreter's start, the embedding program's own
work and the backend's start (``mx_setup_seconds{phase="outside"}``).
"""
from chipbench import startup


def read(facts):
    return startup.setup_seconds(facts, "outside")
