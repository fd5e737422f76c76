"""Seconds in backend-compile steps that the persistent cache answered, under a
program's name or ``(eager)``, the whole process up to now.
"""
from chipbench import startup


def read(facts):
    return startup.compile_seconds(facts, ("cache_read",))
