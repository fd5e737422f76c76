"""Sparse selection in ``ops.attention`` and ``models.decoder_lm``'s two new
mixers (attention that chooses its blocks from an index of compressed keys;
lightning linear attention) against the plain reference of the
``minicpm_sala`` family (``chipbench/reference/minicpm_sala.py``), on a toy of
the published shape: layers 2-4 of 8 (``minicpm4`` at 3, lightning at 2 and
4), 4 query heads and 2 KV heads of 8, windows of 8 positions every 4 (the
page), blocks of 16, 4 blocks a KV group of which the first and the last 2
are always taken, dense up to 64 positions.

The system is compared with the reference on the chosen blocks and on
log-probabilities through ``Module`` forward (a whole sequence), through
``DecodePredictor.prefill`` / ``step`` as the benchmark's comparison drives
them (chunks of 32, then decode, across ``dense_len``) and through
``DecodeServer``.

Tolerances.  ``FLOAT_ATOL`` 1e-4: system and reference both compute in
float32 on the CPU and differ in the order of their sums (2e-5 measured).
``INT8_ATOL`` 1.0: an int8 pool's keys and values at heads of 8 read 0.12 to
0.14 over 50 positions where every row chooses the reference's blocks, and
0.43 to 0.45 where one row's single free choice flips between two blocks
whose scores the rounding of the keys reorders (the choice is discrete, as an
expert's routing is; one seed in three at this size).  Attending everything
where the reference selects reads 5.5 to 6.7, and every other part of the
equations dropped far more than the float tolerance, so both separate right
from wrong.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from chipbench import harness, manifest, weights
from chipbench.reference import minicpm_sala as ref
from mxnet_tpu.base import MXNetError
from mxnet_tpu.decode import DecodePredictor, DecodeServer
from mxnet_tpu.ops import attention as attn

FLOAT_ATOL, INT8_ATOL = 1e-4, 1.0
T, PROMPT, CHUNK, PAGE, CACHE = 200, 150, 32, 4, 256

TOY = dict(vocab_size=50, hidden_size=32, num_hidden_layers=8,
           num_attention_heads=4, num_key_value_heads=2, head_dim=8,
           intermediate_size=48, lightning_nh=4, lightning_head_dim=8,
           dim_model_base=8, max_position_embeddings=512,
           mixer_types=["minicpm4", "lightning-attn", "lightning-attn",
                        "minicpm4", "lightning-attn", "lightning-attn",
                        "lightning-attn", "minicpm4"],
           sparse_config=dict(kernel_size=8, kernel_stride=4, init_blocks=1,
                              block_size=16, window_size=32, topk=4,
                              dense_len=64),
           serve_first_layer=2, serve_num_hidden_layers=3,
           serve_dtype="float32")
# matrices around 1 / sqrt(fan-in), the q and k of the selecting layer three
# times that: its softmax is peaked, so that which blocks are chosen shows
TOY_INIT = [
    {"match": "_gamma$", "dist": "normal", "mean": 1.0, "std": 0.1},
    {"match": "^embed_weight$", "dist": "normal", "std": 0.0833},
    {"match": "^head_weight$", "dist": "normal", "std": 1.0},
    {"match": "layer[0-9]+_(q|k)_weight$", "dist": "normal", "std": 0.55},
    {"match": "_attout_weight$", "dist": "normal", "std": 1.5},
    {"match": "_lin_out_weight$", "dist": "normal", "std": 0.8},
    {"match": "_ffn_down_weight$", "dist": "normal", "std": 0.4},
    {"match": "_weight$", "dist": "normal", "std": 0.18},
]
SPEC = attn.SparseSpec(topk=4, block=16, kernel=8, stride=4, init_blocks=1,
                       window=32, dense_len=64)


def toy_config(**over):
    cfg = manifest.load_json(manifest.ROOT,
                             "chipbench/configs/minicpm-sala.json")
    return dict(cfg, init=TOY_INIT, **dict(TOY, **over))


def build(cfg, seed=7):
    sym = harness.build_symbol(cfg)
    arg_shapes, _, _ = sym.infer_shape(data=(1, 64), softmax_label=(1, 64))
    shapes = {n: s for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in ("data", "softmax_label")}
    return sym, weights.make_params(shapes, cfg, seed, "float32")


def predictor(sym, params, kv_dtype="", **kw):
    args = dict(cache_len=CACHE, ctx=mx.cpu(), temperature=0.0, paged=True,
                page_tokens=PAGE, prefill_chunk=CHUNK, kv_dtype=kv_dtype)
    args.update(kw)
    return DecodePredictor(
        sym, {n: mx.nd.NDArray(v, mx.cpu()) for n, v in params.items()},
        **args)


def ref_logp(cfg, params, toks):
    return np.asarray(jax.nn.log_softmax(
        ref.forward(params, cfg, np.asarray(toks)[None, :])[0], -1))


@pytest.fixture(scope="module")
def toy():
    cfg = toy_config()
    sym, params = build(cfg)
    toks = np.random.default_rng(3).integers(0, cfg["vocab_size"], size=T)
    return cfg, sym, params, toks, ref_logp(cfg, params, toks)


def qk(seed, t=T):
    r = np.random.default_rng(seed)
    return (jnp.asarray(r.normal(size=(1, t, 4 * 8)), jnp.float32),
            jnp.asarray(r.normal(size=(1, t, 2 * 8)), jnp.float32))


# -- the selection -----------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_chosen_blocks_are_the_references(seed):
    """Every query row of a 200-token sequence, both sides of ``dense_len``:
    the program's mask (``sparse_block_mask``, the chunk's and the
    sequence's form) and its list (``sparse_choose``, the decode row's) hold
    the blocks the reference chooses.  Rows where the last block taken and
    the first left out score within 1e-6 of each other without being equal
    are left out (none at these seeds).  Exact ties are common and stay in:
    two neighbouring blocks share the window that straddles them, and where
    that window is the best of both they score the same; both sides then
    take the earlier block."""
    q, k = qk(seed)
    cfg = toy_config()
    n = jnp.arange(1, T + 1)[None, :]
    blocks = -(-T // SPEC.block)
    kbar = attn.compress_keys(k, SPEC)
    want = np.asarray(ref.chosen_blocks(
        q.reshape(1, T, 4, 8),
        ref.compressed_keys(k.reshape(1, T, 2, 8), cfg), T, cfg,
        jnp.arange(T)))
    assert np.abs(np.asarray(kbar).reshape(1, -1, 2, 8) - np.asarray(
        ref.compressed_keys(k.reshape(1, T, 2, 8), cfg))).max() < 1e-6
    score = attn.sparse_block_scores(q, kbar, n, SPEC, blocks, 4, 2)
    ranked = np.sort(np.clip(np.asarray(score), -1.0, 1e9), -1)[..., ::-1]
    gap = ranked[..., SPEC.topk - 1] - ranked[..., SPEC.topk]
    clear = (gap > 1e-6) | (gap == 0) \
        | (np.asarray(n)[:, None, :] <= SPEC.dense_len)
    assert clear.mean() > 0.95 and (gap == 0).mean() > 0.02
    mask = np.asarray(attn.sparse_block_mask(q, kbar, n, SPEC, blocks, 4, 2,
                                             rows=64))
    assert mask.shape == want.shape == (1, 2, T, blocks)
    assert np.array_equal(mask[clear], want[clear])
    got, valid = (np.asarray(x) for x in attn.sparse_choose(
        score, n, SPEC, SPEC.list_width))
    listed = np.zeros_like(mask)
    np.put_along_axis(listed, got, valid, axis=-1)
    assert np.array_equal(listed[clear], want[clear])
    # in rows of 256 the mask is the same
    assert np.array_equal(mask, np.asarray(attn.sparse_block_mask(
        q, kbar, n, SPEC, blocks, 4, 2)))


def test_dense_at_and_under_dense_len_sparse_one_token_past_it():
    q, k = qk(5)
    kbar = attn.compress_keys(k, SPEC)
    blocks = -(-T // SPEC.block)
    n = jnp.asarray([[SPEC.dense_len - 1, SPEC.dense_len,
                      SPEC.dense_len + 1, T]])
    rows = q[:, np.asarray(n[0]) - 1]
    mask = np.asarray(attn.sparse_block_mask(rows, kbar, n, SPEC, blocks, 4,
                                             2))
    own = (np.asarray(n[0]) - 1) // SPEC.block
    for i, count in enumerate((own[0] + 1, own[1] + 1, SPEC.topk,
                               SPEC.topk)):
        assert (mask[0, :, i].sum(-1) == count).all(), i
        assert not mask[0, :, i, own[i] + 1:].any()
        # the first block and the two that end at the row's own: always
        assert mask[0, :, i, 0].all() and mask[0, :, i, own[i]].all() \
            and mask[0, :, i, own[i] - 1].all()
    # the decode row's list is as wide as a dense context's blocks, the
    # first topk of it where the row selects
    assert SPEC.list_width == 4 and attn.SparseSpec(
        64, 64, 32, 16, 1, 2048, 8192).list_width == 128


def test_a_selection_the_sizes_cannot_carry_is_refused():
    with pytest.raises(ValueError, match="two strides"):
        attn.sparse_spec(dict(sparse_topk=4, sparse_kernel=12,
                              sparse_stride=4, sparse_block=16,
                              sparse_window=32, sparse_init_blocks=1,
                              sparse_dense_len=64))
    assert attn.sparse_spec({"sparse_topk": 0}) is None
    spec = attn.sparse_spec({"sparse_topk": 64})
    assert spec == attn.SparseSpec(64, 64, 32, 16, 1, 2048, 8192)


# -- the model ----------------------------------------------------------------

def test_the_graph_builds_the_published_slice(toy):
    cfg, sym, params, _, _ = toy
    names = set(params)
    assert {"layer2_lin_q_weight", "layer3_q_weight", "layer3_gate_weight",
            "layer4_lin_out_norm_gamma"} <= names
    assert not [n for n in names if n.startswith(("layer0_", "layer5_"))]
    nodes = {n.name: n for n in sym._topo() if not n.is_variable}
    att = nodes["layer3_att"].parsed_attrs()
    assert att["rotary_dim"] == 0 and att["sparse_topk"] == 4 \
        and att["sparse_dense_len"] == 64
    assert nodes["layer3_att"].attrs["__layer__"] == "attn_sparse"
    # the decay's depth factor reads the published index and depth
    assert nodes["layer2_lin"].parsed_attrs()["slope_scale"] \
        == pytest.approx(1 - 2 / 7 + 1e-5)
    assert nodes["layer4_lin"].parsed_attrs()["slope_scale"] \
        == pytest.approx(1 - 4 / 7 + 1e-5)


def test_full_forward_matches_the_reference(toy):
    cfg, sym, params, toks, want = toy
    ex = sym.simple_bind(mx.cpu(), grad_req="null", data=(1, T),
                         softmax_label=(1, T))
    for n, v in params.items():
        ex.arg_dict[n]._set_data(v)
    ex.arg_dict["data"]._set_data(jnp.asarray(toks[None, :], jnp.float32))
    ex.forward(is_train=False)
    got = np.log(np.asarray(ex.outputs[0].data))
    assert np.abs(got - want).max() < FLOAT_ATOL


@pytest.mark.parametrize("dropped", [
    "topk", "scale_depth", "dim_model_base", "scale_emb", "qk_norm",
    "use_output_norm", "use_output_gate", "attn_use_output_gate",
    "lightning_use_rope", "serve_first_layer"])
def test_each_part_of_the_equations_dropped_fails_the_tolerance(toy, dropped):
    """The reference with one part of the model changed (everything
    attended; a muP scalar at 1; a norm, a gate or the rotation off; the
    slice built from index 0: other decays) is off by far more than the
    tolerance: the comparison sees each."""
    cfg, _, params, toks, want = toy
    other = dict(cfg)
    if dropped == "topk":
        other["sparse_config"] = dict(cfg["sparse_config"], dense_len=10 ** 6)
    elif dropped == "serve_first_layer":
        other["serve_first_layer"] = 0
        other["mixer_types"] = cfg["mixer_types"][2:] + ["", ""]
        params = {n.replace("layer%d_" % l, "layer%d_" % (l - 2))
                  if n.startswith("layer") else n: v
                  for l in (2, 3, 4) for n, v in params.items()
                  if n.startswith("layer%d_" % l) or not n.startswith("layer")}
    elif dropped in ("scale_depth", "scale_emb"):
        other[dropped] = 1.0 if dropped == "scale_emb" \
            else cfg["num_hidden_layers"] ** 0.5
    elif dropped == "dim_model_base":
        other[dropped] = cfg["hidden_size"]
    else:
        other[dropped] = False
    got = ref_logp(other, params, toks)
    assert np.abs(got - want).max() > 10 * FLOAT_ATOL, dropped


def forced_decode(pred, toks, prompt):
    """Chunked prefill of ``toks[:prompt]`` in slot 0 (slot 1 holds one
    token), then one decode step a remaining token, fed the sequence's own:
    log-probabilities at positions ``prompt - 1 ..``."""
    batch = np.zeros((2, prompt), np.float32)
    batch[0], batch[1, 0] = toks[:prompt], 3
    state, probs = pred.prefill(batch, np.asarray([prompt, 1]))
    got = [np.asarray(probs[0])]
    for tok in toks[prompt:]:
        state = state._replace(tok=jnp.asarray([[tok], [1]], jnp.int32))
        state, probs = pred.step(state)
        got.append(np.asarray(probs[0]))
    return np.log(np.stack(got)), state


@pytest.mark.parametrize("kv_dtype,atol,walk", [
    ("", FLOAT_ATOL, False), ("", FLOAT_ATOL, True),
    ("int8", INT8_ATOL, False), ("int8", INT8_ATOL, True)])
def test_paged_prefill_in_chunks_then_decode_matches_the_reference(
        toy, kv_dtype, atol, walk, monkeypatch):
    """150 prompt tokens in five chunks of 32 (the last 22 real), then 50
    decoded positions, against the reference's one forward pass over the
    200: both layer kinds, ``dense_len`` 64 crossed inside the third chunk,
    the index written by chunks and by steps.  ``walk``: the chunk's mask is
    laid over the live-block walk (blocks of 32) and not over a view
    gathered whole."""
    cfg, sym, params, toks, want = toy
    if walk:
        monkeypatch.setattr(attn, "LIVE_BLOCK_TOKENS", {0: 32})
    pred = predictor(sym, params, kv_dtype)
    got, state = forced_decode(pred, toks, PROMPT)
    worst = np.abs(got[:-1] - want[PROMPT - 1:T - 1]).max()
    assert worst < atol, worst
    # what the last step counted: both slots' lightning rows, and slot 0's
    # four blocks a KV group of its thirteen (slot 1 is dense: 4 of 4)
    assert int(state.counts["linattn_rows"]) == 2 * 2
    assert int(state.counts["sparse_blocks_chosen"]) == 2 * (4 + 4)
    assert int(state.counts["sparse_blocks_live"]) == 2 * (13 + 4)
    assert state.ssm is None


def test_the_decode_step_gathers_what_it_chose_and_no_more(toy):
    """At one query row a slot the pages read follow the selection: with
    every slot past ``dense_len`` the step's gathers take ``topk`` blocks a
    KV group, whatever the context's length (the wide branch, a list as
    long as a dense context, is taken only while a slot under ``dense_len``
    needs it)."""
    cfg, sym, params, toks, _ = toy
    pred = predictor(sym, params, "int8")
    taken = []
    real = attn._attend_block_list

    def spy(q, k_pool, v_pool, table, blocks, valid, *rest):
        taken.append(blocks.shape[-1])
        return real(q, k_pool, v_pool, table, blocks, valid, *rest)

    attn._attend_block_list = spy
    try:
        forced_decode(pred, toks[:PROMPT + 1], PROMPT)
    finally:
        attn._attend_block_list = real
    # topk 4 == dense_len / block: one width, no second branch traced
    assert set(taken) == {SPEC.topk}
    wide = attn.SparseSpec(4, 16, 8, 4, 1, 32, 128)
    assert wide.list_width == 8


def test_server_counts_and_refusals(toy):
    """Through ``DecodeServer``: requests admitted at different ticks give
    the reference's tokens' log-probabilities (the first decoded token of
    each is its chunked prefill's), the tick's counts ride the
    ``serve.readback`` span, and what a state row and an index cannot carry
    is refused by name."""
    from mxnet_tpu import obs

    cfg, sym, params, toks, want = toy
    pred = predictor(sym, params)
    server = DecodeServer(pred, max_prefill=PROMPT, slots=2, spec_k=0)
    prompts = [toks[:PROMPT], toks[:70]]
    rids = [server.submit(p, max_new_tokens=6) for p in prompts]
    results = server.run()
    for rid, p in zip(rids, prompts):
        first = results[rid][0]
        assert first == int(np.argmax(want[len(p) - 1])), rid
    notes = [e.get("args", {}) for e in obs.timeline.events()
             if e.get("name") == "serve.readback"]
    notes = [a for a in notes if "sparse_blocks_chosen" in a]
    assert notes and all(a["linattn_rows"] in (2, 4) for a in notes)
    assert all(0 < a["sparse_blocks_chosen"] <= a["sparse_blocks_live"]
               for a in notes)
    snap = obs.registry.snapshot()
    assert snap["mx_linattn_rows_total"]["series"][0]["value"] > 0
    assert snap["mx_linattn_state_bytes"]["series"][0]["value"] \
        == 2 * 2 * 4 * 8 * 8 * 4
    kinds = {r["labels"]["kind"]: r["value"]
             for r in snap["mx_attn_sparse_blocks_total"]["series"]}
    assert 0 < kinds["chosen"] <= kinds["live"]
    assert [g.kind for g in pred.unshared_groups] == ["state"]
    with pytest.raises(MXNetError, match="recurrent state"):
        DecodeServer(predictor(sym, params), max_prefill=PROMPT, slots=2,
                     spec_k=2, proposer="ngram")
    with pytest.raises(MXNetError, match="served paged only"):
        predictor(sym, params, paged=False)
    lay = pred.cache_layouts()
    assert [(l.kind, l.index) for l in lay] == [
        ("state", 0), ("full", 4), ("state", 0)]
    assert pred.state_row_bytes() == pred.state_row_bytes("linattn_rows") \
        == 2 * 4 * 8 * 8 * 4 and pred.state_row_bytes("ssm_rows") == 0


def test_the_index_row_of_a_page_taken_again_is_rebuilt(toy):
    """One slot, two requests one after the other: the second takes the
    pages (and the index rows) the first left, longer than ``dense_len``, and
    reads what a fresh server reads.  The index plane itself: the rows of
    the second request's complete windows are the means of ITS keys."""
    cfg, sym, params, toks, _ = toy
    other = np.random.default_rng(9).integers(0, cfg["vocab_size"], size=T)

    def serve(pred, prompts):
        server = DecodeServer(pred, max_prefill=T, slots=1, spec_k=0)
        rids = [server.submit(p, max_new_tokens=4) for p in prompts]
        got = server.run()
        return [got[r] for r in rids]

    fresh = serve(predictor(sym, params), [other[:120]])[0]
    pred = predictor(sym, params)
    again = serve(pred, [toks[:140], other[:120]])[1]
    assert list(again) == list(fresh)
    # the page that held the first request's window j now holds the
    # second's: prefill the second alone and compare the index rows
    one = predictor(sym, params)
    state, _ = one.prefill(other[None, :120].astype(np.float32),
                           np.asarray([120]))
    table = np.asarray(one._manager.groups[0].tables[0])
    index = np.asarray(state.caches[1][2])
    k_pool = np.asarray(state.caches[1][0])
    complete = (120 - 8) // 4 + 1
    for j in (0, 7, complete - 1):
        keys = np.concatenate([k_pool[table[j]], k_pool[table[j + 1]]])
        assert np.abs(index[table[j]] - keys.mean(0)).max() < 1e-6, j


def test_serving_avals_and_pool_bytes_know_the_index(toy):
    cfg, sym, params, _, _ = toy
    pred = predictor(sym, params, "int8")
    avals = pred.serving_avals(2, chunk_w=CHUNK)
    pools = avals["decode"][1].caches
    pages = 2 * (CACHE // PAGE) + 1
    assert [tuple(a.shape) for a in jax.tree_util.tree_leaves(pools[1])] == [
        (pages, PAGE, 16), (pages, PAGE * 2 * 2), (pages, PAGE, 16),
        (pages, 16)]
    assert [tuple(a.shape) for a in pools[0]] == [(2, 4, 8, 8)]
    assert "fork" not in avals          # two groups: pages and state rows
    state = pred.paged_batch_state(2)
    assert jax.tree_util.tree_structure(state.caches) \
        == jax.tree_util.tree_structure(pools)
    assert pred.pool_bytes() == sum(
        int(np.prod(a.shape)) * a.dtype.itemsize
        for a in jax.tree_util.tree_leaves(state.caches))
