"""Median device duration of the prefill-chunk program.
"""

from chipbench import trace

MODULE = r"chunk_impl"


def read(facts):
    return trace.median(trace.module_ms(facts["trace"], MODULE))
