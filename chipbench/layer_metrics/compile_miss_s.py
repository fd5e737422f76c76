"""Seconds in backend compiles the persistent cache did not answer, under a
program's name or ``(eager)``, the whole process up to now (0 in a warm run).
"""
from chipbench import startup


def read(facts):
    return startup.compile_seconds(facts, ("compile",))
