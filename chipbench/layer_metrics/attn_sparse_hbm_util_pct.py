"""The decode step's sparse attention as a share of its roofline: what the
tick's own counts say it must read of the pools
(``work_sala.selected_bytes``: the pages of the ``sparse_blocks_chosen`` and
the compressed keys of the ``sparse_blocks_live``, the mean over the window's
ticks), over the decode program's device time under the ``mx.attn_sparse``
scopes a run, as a share of the chip's HBM peak.  A step that reads by the
context's length, or gathers more than it chose, shows it here.
"""

from chipbench import work_sala, work_ssm

SCOPES = {"attn_sparse"} | {"attn_sparse/" + s for s in (
    "index_append", "select", "kv_gather", "scores", "kv_append",
    "kv_dequant", "rope")}


def read(facts):
    chosen = work_ssm.noted(facts, "serve.readback", "sparse_blocks_chosen")
    live = work_ssm.noted(facts, "serve.readback", "sparse_blocks_live")
    took = work_ssm.scope_seconds(facts, r"paged_decode", SCOPES)
    if not chosen or len(live) != len(chosen) or not took or not took[0]:
        return None
    seconds, runs = took
    kv = 1 if facts["traffic"].get("kv_dtype") == "int8" else 2
    need = work_sala.selected_bytes(
        facts["config"], sum(chosen) / len(chosen), sum(live) / len(live), kv)
    return 100.0 * need / (seconds / runs) \
        / facts["peaks"]["hbm_bytes_per_s"]
