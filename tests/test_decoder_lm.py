"""``models.decoder_lm`` against the plain reference of the ``mimo_v2`` family
(``chipbench/reference/mimo_v2.py``) on a 7-layer toy of the published
pattern: one dense full-attention layer, then six expert layers of which five
window and one full; 4 heads of 24/16, 1 KV head on full layers and 2 on
window layers, window 8, 16 experts top 4 of which this "chip" holds 4.

Tolerances.  ``FLOAT_ATOL`` 1e-4 on log-probabilities: system and reference
both compute in float32 on the CPU and differ in the order of their sums only
(1.4e-6 measured).  Every mechanism of the model moves the log-probabilities
by far more when it is dropped (the parametrised test holds each to ten times
the tolerance; the least, the bias kept in the weight, moves them 0.065), so
the tolerance separates right from wrong.  ``INT8_ATOL`` 3e-2: an int8 pool
stores each key and value off by up to 1/254 of its head's largest (0.011
measured), still under half of what the least mechanism moves.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from chipbench import correct, harness, manifest, weights
from chipbench.reference import mimo_v2 as ref
from mxnet_tpu.base import MXNetError
from mxnet_tpu.decode import DecodePredictor, DecodeServer

FLOAT_ATOL, INT8_ATOL = 1e-4, 3e-2
T, PROMPT, CHUNK, PAGE = 40, 30, 8, 4

TOY = dict(vocab_size=96, hidden_size=64, num_attention_heads=4, head_dim=24,
           v_head_dim=16, swa_head_dim=24, swa_v_head_dim=16,
           num_key_value_heads=1, swa_num_key_value_heads=2,
           sliding_window=8, intermediate_size=128, moe_intermediate_size=32,
           n_routed_experts=16, num_experts_per_tok=4,
           held_n_routed_experts=4, first_held_expert=4,
           max_position_embeddings=64)


def toy_config(**over):
    """The published configuration cut to the toy's sizes.  Its weights are
    drawn wider than the cell's 0.02 (0.08: towards 1 / sqrt(hidden 64)),
    so that attention logits and router scores are large enough for every
    mechanism to move the output (0.06 to 1.1 in log-probability, the
    bfloat16 reference 0.015)."""
    cfg = manifest.load_json(manifest.ROOT, "chipbench/configs/mimo-v2.5.json")
    init = [dict(r, std=0.08) if r["match"] == "_weight$" else r
            for r in cfg["init"]]
    return dict(cfg, init=init, **dict(TOY, **over))


def build(cfg, seed=7):
    sym = harness.build_symbol(cfg)
    arg_shapes, _, _ = sym.infer_shape(data=(1, 64), softmax_label=(1, 64))
    shapes = {n: s for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in ("data", "softmax_label")}
    return sym, weights.make_params(shapes, cfg, seed, "float32")


def system_probs(sym, params, toks):
    ex = sym.simple_bind(mx.cpu(), grad_req="null", data=toks.shape,
                         softmax_label=toks.shape)
    for n, v in params.items():
        ex.arg_dict[n]._set_data(v)
    ex.arg_dict["data"]._set_data(jnp.asarray(toks, jnp.float32))
    ex.forward(is_train=False)
    return ex.outputs[0].data


def padded_rows(seqs):
    """Sequences of several lengths as the rows of one batch, zeros behind
    their ends: a causal model's positions do not see their padding, so one
    pass over the batch is one pass over each."""
    batch = np.zeros((len(seqs), max(s.size for s in seqs)), seqs[0].dtype)
    for row, seq in zip(batch, seqs):
        row[:seq.size] = seq
    return batch


@pytest.fixture(scope="module")
def toy():
    cfg = toy_config()
    sym, params = build(cfg)
    toks = np.random.default_rng(0).integers(0, cfg["vocab_size"],
                                             size=(1, T))
    return cfg, sym, params, toks, system_probs(sym, params, toks)


_SHARED = {}


def shared(sym, params, kv_dtype=""):
    """The toy's paged predictor of one cache type, built once: what it
    compiled serves every test that does not count its traces (a server,
    like ``prefill``, opens fresh pools and a fresh manager over it)."""
    key = (id(params), kv_dtype)
    if key not in _SHARED:
        nd = {n: mx.nd.NDArray(v, mx.cpu()) for n, v in params.items()}
        _SHARED[key] = (params, DecodePredictor(
            sym, nd, cache_len=64, ctx=mx.cpu(), paged=True,
            page_tokens=PAGE, kv_dtype=kv_dtype, prefill_chunk=CHUNK))
    return _SHARED[key][1]


def test_pattern_and_shapes(toy):
    cfg, sym, params, _, _ = toy
    assert cfg["hybrid_layer_pattern"][:7] == [0, 1, 1, 1, 1, 0, 1]
    assert cfg["moe_layer_freq"][:7] == [0, 1, 1, 1, 1, 1, 1]
    assert params["layer0_k_weight"].shape == (1 * 24, 64)      # full
    assert params["layer1_k_weight"].shape == (2 * 24, 64)      # window
    assert params["layer1_v_weight"].shape == (2 * 16, 64)
    assert params["layer1_att_sink"].shape == (4,)
    assert "layer0_att_sink" not in params and "layer5_att_sink" not in params
    assert params["layer0_ffn_gate_weight"].shape == (128, 64)
    assert params["layer1_moe_gate_weight"].shape == (64, 16)   # all experts
    assert params["layer1_moe_expert_gate_weight"].shape == (4, 64, 32)
    assert not [n for n in params if n.endswith("_bias")
                and "moe_gate" not in n]


def test_full_forward_matches_the_reference(toy):
    cfg, _, params, toks, probs = toy
    out = correct.compare_logp(probs, ref.forward(params, cfg, toks)[0],
                               FLOAT_ATOL)
    assert out["ok"] and out["positions"] == T, out


def _b_in_the_weight(p, n, cfg, x):
    """``ref._experts`` with the correction bias left in the weight."""
    first, held = ref.share(cfg)
    s = jax.nn.sigmoid(x @ p[n + "moe_gate_weight"]) + p[n + "moe_gate_bias"]
    w, chosen = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    y = jnp.zeros_like(x)
    for e in range(held):
        we = jnp.sum(jnp.where(chosen == first + e, w, 0.0), -1,
                     keepdims=True)
        y = y + we * ((jax.nn.silu(x @ p[n + "moe_expert_gate_weight"][e])
                       * (x @ p[n + "moe_expert_up_weight"][e]))
                      @ p[n + "moe_expert_down_weight"][e])
    return y


@pytest.mark.parametrize("dropped", [
    "sink", "window", "partial_rotary", "rotary", "sigmoid_router",
    "selection_only_bias", "value_scale", "share", "float32"])
def test_each_mechanism_dropped_fails_the_tolerance(toy, dropped,
                                                    monkeypatch):
    """The comparison sees every mechanism: the system as built, against a
    reference (or the reference as written, against a system) that lacks
    one, is off by more than ten times ``FLOAT_ATOL``."""
    cfg, sym, params, toks, probs = toy
    ref_cfg, ref_params = dict(cfg), params
    if dropped == "sink":
        ref_cfg["add_swa_attention_sink_bias"] = False
    elif dropped == "window":
        ref_cfg["sliding_window"] = 10 ** 6
    elif dropped == "partial_rotary":
        ref_cfg["partial_rotary_factor"] = 1.0          # all 24 dims turn
    elif dropped == "rotary":
        ref_cfg.update(rope_theta=1e30, swa_rope_theta=1e30)    # no turn
    elif dropped == "sigmoid_router":
        probs = system_probs(
            harness.build_symbol(dict(cfg, scoring_func="softmax")), params,
            toks)
    elif dropped == "selection_only_bias":
        monkeypatch.setattr(ref, "_experts", _b_in_the_weight)
    elif dropped == "value_scale":
        ref_cfg["attention_value_scale"] = 1.0
    elif dropped == "share":
        ref_cfg["first_held_expert"] = 0        # another chip's experts
    elif dropped == "float32":
        ref_params = {n: v.astype(jnp.bfloat16).astype(jnp.float32)
                      for n, v in params.items()}
    out = correct.compare_logp(probs,
                               ref.forward(ref_params, ref_cfg, toks)[0],
                               FLOAT_ATOL)
    assert not out["ok"] and out["max_abs_dlogp"] > 10 * FLOAT_ATOL, out


@pytest.mark.parametrize("kv_dtype,atol", [("", FLOAT_ATOL),
                                           ("int8", INT8_ATOL)])
def test_chunked_prefill_and_decode_past_the_rings_wrap(toy, kv_dtype, atol):
    """Through ``DecodePredictor`` with two cache groups: a 30-token prompt
    in chunks of 8, then 10 decoded positions, against ONE forward pass of
    the reference.  The window group's ring is 8 + 8 = 16 positions, so it
    wraps inside the prompt and again while decoding."""
    cfg, sym, params, toks, _ = toy
    pred = DecodePredictor(
        sym, {n: mx.nd.NDArray(v, mx.cpu()) for n, v in params.items()},
        cache_len=64, ctx=mx.cpu(), paged=True, page_tokens=PAGE,
        kv_dtype=kv_dtype, prefill_chunk=CHUNK)
    assert [(g.kind, g.capacity, g.nodes) for g in pred._groups] == [
        ("full", 64, (0, 5)), ("window", 16, (1, 2, 3, 4, 6))]
    state, probs = pred.prefill(toks[:, :PROMPT].astype(np.float32),
                                np.array([PROMPT]))
    got = [probs[0]]
    for i in range(PROMPT, T - 1):
        # feed the sequence's own next token, whatever was sampled
        state = state._replace(tok=jnp.asarray(toks[:, i:i + 1], jnp.int32))
        state, probs = pred.step(state)
        got.append(probs[0])
    want = ref.forward(params, cfg, toks)[0, PROMPT - 1:T - 1]
    out = correct.compare_logp(jnp.stack(got), want, atol)
    assert out["ok"] and out["positions"] == T - PROMPT, out
    layouts = pred.cache_layouts()
    assert [(l.kind, l.kv_heads, l.capacity, l.key_width, l.value_width)
            for l in layouts[:2]] == [("full", 1, 64, 24, 16),
                                      ("window", 2, 16, 48, 32)]
    # one trace of each program served every chunk and every step
    assert pred.trace_counts["chunk"] == 1
    assert pred.trace_counts["decode"] == 1
    stats = pred._manager.stats()["groups"]
    assert stats["window"]["used_pages"] <= 16 // PAGE
    assert stats["full"]["used_pages"] == -(-(T - 1) // PAGE)


def test_a_chunk_takes_the_grouped_experts_and_a_decode_row_the_dense(
        monkeypatch):
    """The routed product's form follows a program's rows: with the crossover
    at the chunk's width (and Pallas interpreted) the chunk program's six
    expert layers take the grouped form, the decode program's the dense, each
    program's record says so, and the distributions are the reference's."""
    from mxnet_tpu import config
    from mxnet_tpu.ops import moe

    # the grouped form wants widths of whole lane tiles
    cfg = toy_config(hidden_size=128, moe_intermediate_size=128)
    sym, params = build(cfg)
    toks = np.random.default_rng(1).integers(0, cfg["vocab_size"],
                                             size=(1, T))
    monkeypatch.setattr(moe, "GROUPED_MIN_ROWS", CHUNK)
    with config.overrides(MXNET_PALLAS_INTERPRET="1"):
        pred = DecodePredictor(
            sym, {n: mx.nd.NDArray(v, mx.cpu()) for n, v in params.items()},
            cache_len=64, ctx=mx.cpu(), paged=True, page_tokens=PAGE,
            prefill_chunk=CHUNK)
        state, probs = pred.prefill(toks[:, :PROMPT].astype(np.float32),
                                    np.array([PROMPT]))
        got = [probs[0]]
        for i in range(PROMPT, PROMPT + 3):
            state = state._replace(
                tok=jnp.asarray(toks[:, i:i + 1], jnp.int32))
            state, probs = pred.step(state)
            got.append(probs[0])
        art = pred.decode_artifact(state)
    assert pred._moe_forms[CHUNK] == ["held_grouped"] * 6
    assert pred._moe_forms[1] == ["held_dense"] * 6
    assert art.meta["moe_forms"] == ["held_dense"] * 6
    want = ref.forward(params, cfg, toks)[0, PROMPT - 1:PROMPT + 3]
    out = correct.compare_logp(jnp.stack(got), want, FLOAT_ATOL)
    assert out["ok"] and out["positions"] == 4, out


def test_server_matches_generate_and_counts_moe_rows(toy):
    """The serving loop over both groups gives each request the tokens of
    its own ``generate``; the MoE counters count (token, choice) pairs."""
    cfg, sym, params, _, _ = toy
    pred = shared(sym, params)
    server = DecodeServer(pred, max_prefill=32, slots=3, spec_k=0)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 96, size=n) for n in (5, 19, 26, 9, 30)]
    from mxnet_tpu import obs

    def held_rows():
        fam = obs.registry.snapshot().get("mx_moe_rows_total", {})
        return {tuple(sorted(r["labels"].items())): r["value"]
                for r in fam.get("series", ())}

    def noted():
        return [e["args"] for e in obs.timeline.events()
                if e["name"] == "serve.readback" and e.get("args")]

    before, seen = held_rows(), len(noted())
    rids = [server.submit(p, max_new_tokens=12) for p in prompts]
    results = server.run()
    for rid, p in zip(rids, prompts):
        want = pred.generate(p[None].astype(np.float32), p.size,
                              max_new_tokens=12)[0]
        assert np.array_equal(results[rid], want), rid
    after = held_rows()
    key = lambda program, where: (("program", program), ("where", where))
    moved = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    # 6 MoE layers x 4 choices a token, counted over the rows that are
    # tokens: every prompt token once through a chunk (its padding never),
    # every later token once through a decode step (idle slots never)
    decode = moved[key("decode", "held")] + moved[key("decode", "elsewhere")]
    assert decode == 6 * 4 * len(prompts) * (12 - 1)
    chunk = moved[key("chunk", "held")] + moved[key("chunk", "elsewhere")]
    assert chunk == 6 * 4 * sum(p.size for p in prompts)
    assert 0 < moved[key("decode", "held")] < decode
    # each decode tick's own counts ride its serve.readback span
    ticks = noted()[seen:]
    assert sum(a["moe_rows_held"] + a["moe_rows_elsewhere"]
               for a in ticks) == decode
    assert all(0 <= a["moe_expert_visits"] <= 6 * 4 for a in ticks)
    # beside them, the blocks the tick attended of its full-context tables:
    # two full nodes whose 64 positions are one block a slot, attended whole
    assert pred.attn_walk(3) == [(64, 64)] * 2
    assert all(a["attn_blocks_live"] == a["attn_blocks_view"] == 2 * 3
               for a in ticks)
    gauges = obs.registry.snapshot()["mx_kv_pages_total"]["series"]
    assert {r["labels"]["group"] for r in gauges} >= {"full", "window"}


@pytest.mark.parametrize("kv_dtype,eos", [("", False), ("int8", False),
                                          ("", True)])
def test_reading_behind_gives_the_tokens_of_reading_first(toy, kv_dtype,
                                                          eos):
    """Window rings and gated experts under the loop that reads a tick
    behind: six requests of mixed lengths through two slots (each reused, a
    ring recycled under a step still queued) get the tokens of the loop
    that reads first, as many as their caps; with an ``eos_id`` one answer
    ends in its middle, a step late, and no token moves."""
    from mxnet_tpu.test_utils import check_reading_behind

    cfg, sym, params, _, _ = toy
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 96, size=n) for n in (5, 19, 26, 9, 30, 12)]

    def make_server(eos_id):
        return DecodeServer(shared(sym, params, kv_dtype), max_prefill=32,
                            slots=2, spec_k=0, eos_id=eos_id)

    check_reading_behind(make_server, prompts, (9, 3, 12, 1, 6, 8), eos)


def test_what_a_ring_cannot_carry_is_refused_by_name(toy):
    cfg, sym, params, _, _ = toy
    pred = shared(sym, params)
    assert pred.has_window_group
    # a verify step of k + 1 rows needs that many ring positions beyond the
    # window (PR 46): this ring of 16 has 8, and k = 8 is refused by name
    with pytest.raises(MXNetError, match="'window' cache group"):
        DecodeServer(pred, max_prefill=32, slots=2, spec_k=8)
    server = DecodeServer(pred, max_prefill=32, slots=2, spec_k=0)
    assert not server._swap_armed
    with pytest.raises(MXNetError, match="'window' cache group"):
        server.inject(object())
    # no prefix cache: a repeated prompt is computed again, never shared
    server.submit(np.arange(20), max_new_tokens=4)
    server.submit(np.arange(20), max_new_tokens=4)
    out = server.run()
    assert pred._manager is None or pred._manager.prefix_cache is None
    assert np.array_equal(out[0], out[1])


# ---------------------------------------------------------------------------
# delta layers behind a layer of gated attention (``solar_open2``'s keys)
# ---------------------------------------------------------------------------
DELTA_TOY = dict(
    vocab_size=96, hidden_size=64, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16,
    linear_attn_config=dict(short_conv_kernel_size=4, head_dim=16,
                            num_heads=4, num_kv_heads=None),
    intermediate_size=128, moe_intermediate_size=32, n_routed_experts=16,
    num_experts_per_tok=4, held_n_routed_experts=4, first_held_expert=4,
    serve_num_hidden_layers=5, max_position_embeddings=64)


def delta_config(**over):
    """``solar-open2-250b`` cut to the toy's sizes: five layers, so that the
    period (attention at 0 and 4, delta layers between) closes once."""
    cfg = manifest.load_json(manifest.ROOT,
                             "chipbench/configs/solar-open2-250b.json")
    wider = {"_weight$": dict(std=0.08),
             "_kda_dt_bias$": dict(low=-3.0, high=0.0)}
    init = [dict(r, **wider.get(r["match"], {})) for r in cfg["init"]]
    return dict(cfg, init=init, **dict(DELTA_TOY, **over))


GDN_TOY = dict(
    vocab_size=96, hidden_size=64, num_attention_heads=3,
    num_key_value_heads=3, head_dim=16, linear_num_key_heads=3,
    linear_num_value_heads=3, linear_key_head_dim=8, linear_value_head_dim=16,
    intermediate_size=128, serve_num_hidden_layers=4,
    max_position_embeddings=64)


def gdn_config(**over):
    """``olmo-hybrid-7b`` cut to the toy's sizes: one period (three Gated
    DeltaNet layers, then attention of three KV heads: an int8 pool's scale
    row is padded, 6 floats a token to 8)."""
    cfg = manifest.load_json(manifest.ROOT,
                             "chipbench/configs/olmo-hybrid-7b.json")
    wider = {"_weight$": dict(std=0.08), "_gdn_a_weight$": dict(std=0.05),
             "_gdn_b_weight$": dict(std=0.2),
             "_gdn_dt_bias$": dict(low=-3.0, high=0.0)}
    init = [dict(r, **wider.get(r["match"], {})) for r in cfg["init"]]
    return dict(cfg, init=init, **dict(GDN_TOY, **over))


@pytest.fixture(scope="module")
def delta_toy():
    cfg = delta_config()
    sym, params = build(cfg)
    toks = np.random.default_rng(0).integers(0, cfg["vocab_size"],
                                             size=(1, T))
    return cfg, sym, params, toks, system_probs(sym, params, toks)


def test_the_mixer_and_the_feed_forward_part_are_chosen_apart(delta_toy):
    """``gqa_layers`` / ``linear_attn_config`` choose a layer's mixer,
    ``first_k_dense_replace`` its feed-forward part: routed experts stand
    behind the recurrent layers as behind the attention."""
    cfg, sym, params, _, _ = delta_toy
    kinds = {}
    for node in sym._topo():
        if not node.is_variable and node.op.name in (
                "dot_product_attention", "KimiDeltaAttention", "MoEFFN"):
            kinds.setdefault(node.name.split("_")[0], []).append(
                node.op.name)
    assert kinds == dict(
        {"layer%d" % l: ["KimiDeltaAttention", "MoEFFN"] for l in (1, 2, 3)},
        layer0=["dot_product_attention", "MoEFFN"],
        layer4=["dot_product_attention", "MoEFFN"])
    att = next(n for n in sym._topo() if n.name == "layer0_att")
    assert int(att.parsed_attrs().get("rotary_dim", 0)) == 0    # use_rope
    assert params["layer0_gate_weight"].shape == (4 * 16, 64)   # elementwise
    assert params["layer0_k_weight"].shape == (2 * 16, 64)
    assert params["layer1_kda_f_a_weight"].shape == (16, 64)    # rank D
    assert params["layer1_kda_g_b_weight"].shape == (64, 16)
    assert params["layer1_kda_beta_weight"].shape == (4, 64)
    assert params["layer1_kda_conv_weight"].shape == (3 * 64, 4)
    assert params["layer1_moe_expert_gate_weight"].shape == (4, 64, 32)
    assert params["layer1_moe_shared_up_weight"].shape == (64, 32)
    assert not [n for n in params if "_ffn_gate" in n or "layer1_q_" in n]
    with pytest.raises(ValueError, match="kda_use_full_proj"):
        harness.build_symbol(dict(cfg, kda_use_full_proj=True))
    with pytest.raises(ValueError, match="kda_allow_neg_eigval"):
        harness.build_symbol(dict(cfg, kda_allow_neg_eigval=False))
    # the other families' configurations name none of it
    assert "use_gqa_gate" not in toy_config()["symbol_args"]


@pytest.mark.parametrize("dropped", [None, "gqa_gate", "neg_eigval",
                                     "experts_behind_delta"])
def test_delta_layers_match_their_reference(delta_toy, dropped):
    from chipbench.reference import solar_open2

    cfg, _, params, toks, probs = delta_toy
    faulty = {"gqa_gate": dict(use_gqa_gate=False),
              "neg_eigval": dict(kda_allow_neg_eigval=False),
              # the reference with layers 1-3 taken for attention layers
              # would need other weights: drop their experts' share instead
              "experts_behind_delta": dict(n_shared_experts=0)}.get(
                  dropped, {})
    out = correct.compare_logp(
        probs, solar_open2.forward(params, dict(cfg, **faulty), toks)[0],
        FLOAT_ATOL)
    assert out["positions"] == T
    if dropped is None:
        assert out["ok"], out
    else:
        assert out["max_abs_dlogp"] > 10 * FLOAT_ATOL, out


# ---------------------------------------------------------------------------
# OLMo's block: the norm after the sublayer, q and k normed over the whole
# projection, Gated DeltaNet chosen by ``layer_types`` (PR 57)
# ---------------------------------------------------------------------------
PARENT_NODES = [
    ("Embedding", "embed"), ("RMSNorm", "layer0_att_norm"),
    ("FullyConnected", "layer0_q"), ("Reshape", "reshape0"),
    ("RMSNorm", "layer0_q_norm"), ("Reshape", "reshape1"),
    ("FullyConnected", "layer0_k"), ("Reshape", "reshape2"),
    ("RMSNorm", "layer0_k_norm"), ("Reshape", "reshape3"),
    ("FullyConnected", "layer0_v"), ("dot_product_attention", "layer0_att"),
    ("FullyConnected", "layer0_attout"), ("_plus", "plus0"),
    ("RMSNorm", "layer0_ffn_norm"), ("FullyConnected", "layer0_ffn_gate"),
    ("Activation", "activation0"), ("FullyConnected", "layer0_ffn_up"),
    ("_mul", "mul0"), ("FullyConnected", "layer0_ffn_down"),
    ("_plus", "plus1"), ("RMSNorm", "final_norm"), ("Reshape", "reshape4"),
    ("FullyConnected", "head"), ("Reshape", "reshape5"),
    ("SoftmaxOutput", "softmax")]
OLMO = dict(vocab_size=32, hidden_size=16, num_layers=1,
            num_attention_heads=2, head_dim=8, intermediate_size=24)


def _nodes(sym):
    return [(n.op.name, n.name) for n in sym._topo() if not n.is_variable]


def test_a_graph_with_defaults_has_the_parents_node_list():
    """The new arguments at their defaults change nothing: the nodes of a
    one-layer graph with a per-head q/k norm, in order and by name, as the
    tree before PR 57 built them (``benchmarks/runs/hashes.py`` holds
    the accepted cells' serving programs to the parent's text)."""
    from mxnet_tpu.base import NameManager
    from mxnet_tpu.models import decoder_lm

    with NameManager():
        sym = decoder_lm.get_symbol(attn_qk_norm=True, **OLMO)
    assert _nodes(sym) == PARENT_NODES
    with NameManager():
        after = decoder_lm.get_symbol(attn_qk_norm="projection",
                                      norm_after=True, **OLMO)
    # the same names, the norms after their sublayers, no reshape round a
    # q/k norm
    assert [n for _, n in _nodes(after) if n.endswith("_norm")] == [
        "layer0_q_norm", "layer0_k_norm", "layer0_att_norm",
        "layer0_ffn_norm", "final_norm"]
    shapes = dict(zip(after.list_arguments(), after.infer_shape(
        data=(1, 4), softmax_label=(1, 4))[0]))
    assert shapes["layer0_q_norm_gamma"] == (16,)
    with pytest.raises(ValueError, match="norm_after beside the parallel"):
        decoder_lm.get_symbol(norm_after=True, mamba_d_ssm=8, mamba_n_heads=2,
                              mamba_d_head=4, mamba_d_state=4, **OLMO)


def test_norm_after_and_the_whole_projection_norm_by_hand():
    """``x + RMS(f(x))`` and q, k normed over the whole projection against
    the equations in plain ``jax.numpy``, no rotation (``rope_theta``
    null)."""
    from mxnet_tpu.models import decoder_lm

    sym = decoder_lm.get_symbol(
        attn_qk_norm="projection", norm_after=True,
        rope_parameters={"rope_theta": None}, layernorm_epsilon=1e-6, **OLMO)
    t = 6
    args = sym.list_arguments()
    shapes = dict(zip(args, sym.infer_shape(data=(1, t),
                                            softmax_label=(1, t))[0]))
    rng = np.random.default_rng(3)
    p = {n: jnp.asarray((1.0 + 0.2 * rng.standard_normal(s)) if len(s) == 1
                        else 0.4 * rng.standard_normal(s), jnp.float32)
         for n, s in shapes.items() if n not in ("data", "softmax_label")}
    toks = rng.integers(0, 32, size=(1, t))
    got = system_probs(sym, p, toks)
    rms = lambda x, g: x * jax.lax.rsqrt(
        jnp.mean(x * x, -1, keepdims=True) + 1e-6) * g
    fc = lambda x, n: x @ p[n + "_weight"].T
    x = p["embed_weight"][toks]                             # (1, t, 16)
    q = rms(fc(x, "layer0_q"), p["layer0_q_norm_gamma"]).reshape(1, t, 2, 8)
    k = rms(fc(x, "layer0_k"), p["layer0_k_norm_gamma"]).reshape(1, t, 2, 8)
    v = fc(x, "layer0_v").reshape(1, t, 2, 8)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(8.0)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    h = x + rms(fc(att.reshape(1, t, 16), "layer0_attout"),
                p["layer0_att_norm_gamma"])
    mlp = fc(jax.nn.silu(fc(h, "layer0_ffn_gate")) * fc(h, "layer0_ffn_up"),
             "layer0_ffn_down")
    h = h + rms(mlp, p["layer0_ffn_norm_gamma"])
    want = jax.nn.softmax(fc(rms(h, p["final_norm_gamma"]), "head"), -1)
    assert float(jnp.max(jnp.abs(got - want[0]))) < 1e-5
    # and not the pre-norm block over the same weights
    pre = decoder_lm.get_symbol(
        attn_qk_norm="projection", rope_parameters={"rope_theta": None},
        layernorm_epsilon=1e-6, **OLMO)
    assert float(jnp.max(jnp.abs(system_probs(pre, p, toks) - want[0]))) \
        > 1e-2


def test_layer_types_choose_gated_deltanet_where_the_linear_keys_are_given():
    from mxnet_tpu.models import decoder_lm

    kinds = ("linear_attention", "full_attention", "sliding_attention")
    lin = dict(linear_num_key_heads=2, linear_num_value_heads=2,
               linear_key_head_dim=4, linear_value_head_dim=8,
               linear_allow_neg_eigval=True)
    sym = decoder_lm.get_symbol(layer_types=kinds, sliding_window=4,
                                **dict(OLMO, num_layers=3), **lin)
    ops = [op for op, _ in _nodes(sym)]
    assert ops.count("GatedDeltaNet") == 1 \
        and ops.count("dot_product_attention") == 2
    att = [n.parsed_attrs() for n in sym._topo() if not n.is_variable
           and n.op.name == "dot_product_attention"]
    assert [int(a.get("window", 0) or 0) for a in att] == [0, 4]
    # without the linear keys, layer_types keeps its meaning: attention
    plain = decoder_lm.get_symbol(layer_types=kinds, sliding_window=4,
                                  **dict(OLMO, num_layers=3))
    assert "GatedDeltaNet" not in [op for op, _ in _nodes(plain)]
    for bad, match in ((dict(linear_allow_neg_eigval=False), "neg_eigval"),
                       (dict(linear_num_value_heads=4), "value_heads")):
        with pytest.raises(ValueError, match=match):
            decoder_lm.get_symbol(layer_types=kinds, sliding_window=4,
                                  **dict(OLMO, num_layers=3),
                                  **dict(lin, **bad))


# ---------------------------------------------------------------------------
# Nemotron-H's letters: every layer ONE sublayer under its own norm
# (``nemotron-3-nano-30b``'s keys at a toy size)
# ---------------------------------------------------------------------------
LETTER_TOY = dict(
    vocab_size=96, hidden_size=64, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, intermediate_size=40,
    moe_intermediate_size=48, moe_shared_expert_intermediate_size=80,
    n_routed_experts=8, num_experts_per_tok=3, held_n_routed_experts=4,
    first_held_expert=4, mamba_num_heads=4, mamba_head_dim=8,
    ssm_state_size=16, n_groups=2, chunk_size=8,
    hybrid_override_pattern="ME*-EM", num_hidden_layers=6,
    serve_num_hidden_layers=6, max_position_embeddings=64)


def letter_config(**over):
    """``nemotron-3-nano-30b`` cut to the toy's sizes: a layer of each
    letter, stateless layers (``E``, ``-``) between stateful ones (``M``,
    ``*``)."""
    cfg = manifest.load_json(manifest.ROOT,
                             "chipbench/configs/nemotron-3-nano-30b.json")
    wider = {"_(q|k)_weight$": 0.16, "_ssm_in_weight$": 0.16}
    init = [dict(r, std=wider.get(r["match"], 0.08))
            if r["match"].endswith("_weight$") and r["dist"] == "normal"
            and "conv" not in r["match"] and "embed" not in r["match"]
            else r for r in cfg["init"]]
    return dict(cfg, init=init, **dict(LETTER_TOY, **over))


def test_every_layer_is_the_one_sublayer_its_letter_names():
    """``hybrid_override_pattern`` builds ``x + f(RMSNorm(x))`` with one
    ``f`` a layer: the mixer alone (no attention node beside it), the
    two-matrix relu^2 experts with a shared MLP of its own width, attention
    of 4 / 2 heads without rotation, a dense relu^2 MLP; and the whole agrees
    with the plain reference."""
    from chipbench.reference import nemotron_h

    cfg = letter_config()
    sym, params = build(cfg)
    kinds = {}
    for node in sym._topo():
        if not node.is_variable and node.op.name in (
                "dot_product_attention", "SelectiveSSM", "MoEFFN",
                "RMSNorm"):
            kinds.setdefault(node.name.split("_")[0], []).append(
                node.op.name)
    assert [kinds["layer%d" % l] for l in range(6)] == [
        ["RMSNorm", "SelectiveSSM"], ["RMSNorm", "MoEFFN"],
        ["RMSNorm", "dot_product_attention"], ["RMSNorm"],
        ["RMSNorm", "MoEFFN"], ["RMSNorm", "SelectiveSSM"]]
    assert "square" in [n.op.name for n in sym._topo() if not n.is_variable]
    assert [n for n in params if n.endswith("_norm_gamma")
            and not n.startswith(("final", "layer0_ssm", "layer5_ssm"))] \
        == ["layer%d_norm_gamma" % l for l in range(6)]
    att = next(n for n in sym._topo() if n.name == "layer2_att")
    assert int(att.parsed_attrs().get("rotary_dim", 0)) == 0
    moe_node = next(n for n in sym._topo() if n.name == "layer1_moe")
    attrs = moe_node.parsed_attrs()
    assert attrs["expert_act"] == "relu2" and attrs["shared_hidden_size"] == 80
    assert float(attrs["routed_scaling_factor"]) == 2.5
    assert params["layer1_moe_expert_up_weight"].shape == (4, 48, 64)
    assert params["layer1_moe_shared_down_weight"].shape == (80, 64)
    assert params["layer3_ffn_up_weight"].shape == (40, 64)
    assert params["layer0_ssm_in_weight"].shape == (2 * 32 + 2 * 32 + 4, 64)
    assert not [n for n in params if "_ffn_gate" in n or "_att_norm" in n
                or "_ffn_norm" in n or "moe_expert_gate" in n]
    toks = np.random.default_rng(0).integers(0, 96, size=(1, T))
    probs = system_probs(sym, params, toks)
    want = jax.nn.log_softmax(nemotron_h.forward(params, cfg, toks)[0], -1)
    assert float(jnp.max(jnp.abs(jnp.log(probs) - want))) < 2e-5
    # the rotation the file's rope_theta would give is another model
    turned = system_probs(harness.build_symbol(
        dict(cfg, attn_use_rope=True)), params, toks)
    assert float(jnp.max(jnp.abs(jnp.log(turned) - want))) > 1e-3


def test_what_the_letters_cannot_say_is_refused_by_name():
    from mxnet_tpu.models import decoder_lm

    cfg = letter_config()
    with pytest.raises(ValueError, match="hybrid_override_pattern 'MXM"):
        harness.build_symbol(dict(cfg, hybrid_override_pattern="MXM*EM"))
    with pytest.raises(ValueError, match="6 letters"):
        harness.build_symbol(dict(cfg, hybrid_override_pattern="ME*"))
    with pytest.raises(ValueError, match="mlp_hidden_act 'gelu'"):
        harness.build_symbol(dict(cfg, mlp_hidden_act="gelu"))
    with pytest.raises(ValueError, match="mamba_conv_bias"):
        harness.build_symbol(dict(cfg, use_conv_bias=False))
    with pytest.raises(ValueError, match="parallel block"):
        decoder_lm.get_symbol(hybrid_override_pattern="M", mamba_d_ssm=8,
                              mamba_n_heads=2, mamba_d_head=4,
                              mamba_d_state=4, **OLMO)
    # the other families' configurations name none of it, and silu keeps
    # the gated MLP of three matrices
    assert "hybrid_override_pattern" not in toy_config()["symbol_args"]
    with_gate = harness.build_symbol(dict(
        cfg, mlp_hidden_act="silu", hybrid_override_pattern="M-M-M-"))
    assert "layer1_ffn_gate_weight" in with_gate.list_arguments()
