"""Metric device-to-host reads per step inside the window, from the
program's own counter (``profiler.step_stats``).
"""


def read(facts):
    stats = facts.get("step_stats")
    return None if not stats else stats["host_syncs_per_step"]
