"""Seconds in the program's own set-up calls: ``mx_setup_seconds{phase=
"build*"}`` summed (symbol, bind, parameters, optimizer, predictor, server,
session), compile stages inside them taken out.
"""
from chipbench import startup


def read(facts):
    return startup.setup_seconds(facts, "build")
