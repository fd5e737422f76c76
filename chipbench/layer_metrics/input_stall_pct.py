"""Share of the window the loop waited for its next batch, from the
program's own counter (``profiler.step_stats``).
"""


def read(facts):
    stats = facts.get("step_stats")
    return None if not stats else 100.0 * stats["input_stall_fraction"]
