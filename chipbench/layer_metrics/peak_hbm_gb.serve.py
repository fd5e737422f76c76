"""``peak_bytes_in_use`` of the fullest chip's ``memory_stats()`` after the
window, in GB (1e9 bytes).
"""


def read(facts):
    return facts["memory_peak_bytes"] / 1e9
