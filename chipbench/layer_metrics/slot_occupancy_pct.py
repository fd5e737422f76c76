"""Mean share of the server's slots that held a decoding request when a
tick began.
"""


def read(facts):
    if "mean_active" not in facts:
        return None
    return 100.0 * facts["mean_active"] / facts["slots"]
