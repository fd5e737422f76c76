"""Share of the first chip's busy time in the window spent under the
``mx.attn_window`` scopes of the serving programs: the sliding-window
attention nodes, which ``attn_device_pct.serve`` (``mx.attn``: attention
over the whole context) leaves out.
"""

from chipbench import scopes


def read(facts):
    return scopes.layer_pct(facts, "attn_window")
