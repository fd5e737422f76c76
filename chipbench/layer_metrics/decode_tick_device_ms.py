"""Median device duration of the paged decode program.
"""

from chipbench import trace

MODULE = r"paged_decode"


def read(facts):
    return trace.median(trace.module_ms(facts["trace"], MODULE))
