"""Share of the first chip's busy time in the window spent under the
``mx.attn_sparse`` scopes of the serving programs: the attention nodes with
sparse selection (``index_append``, ``select``, ``kv_gather``, ``scores``,
``kv_append``, ``kv_dequant``), which ``attn_device_pct.serve`` (``mx.attn``:
attention over the whole context) leaves out.
"""

from chipbench import scopes


def read(facts):
    t = scopes.table(facts)
    return None if t is None or "attn_sparse" not in t["layers"] \
        else t["layers"]["attn_sparse"]
