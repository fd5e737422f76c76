"""Of the program's compile-stage seconds, the share booked under a program's
name and not ``(eager)``: how far the compile metrics can be trusted.
"""
from chipbench import startup


def read(facts):
    return startup.named_pct(facts)
