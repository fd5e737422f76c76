"""Median device duration of the compiled train step: the ``jit_step``
events on the first chip's ``XLA Modules`` line.
"""

from chipbench import trace


def read(facts):
    return trace.median(trace.module_ms(facts["trace"], r"^jit_step$"))
