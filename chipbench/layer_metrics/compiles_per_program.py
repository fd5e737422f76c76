"""Backend compiles and cache reads under a program's name, over the distinct
names (``mx_compiles_total``): 1 where every program was loaded once.
"""
from chipbench import startup


def read(facts):
    return startup.compiles_per_program(facts)
