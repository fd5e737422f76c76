"""A server that drafts with its graph's own multi-token-prediction block:
one program a tick verifies the last draft over two rows a slot, commits one
or two tokens and leaves the next draft, and the loop still reads one tick
behind.  The oracle throughout is the same graph served without drafts.

Greedy sampling makes acceptance a fact and not a chance (a draft is taken
iff it is the stack's argmax), so the tests put the drafts they want into the
device state between ticks: the oracle's own next token (accepted for sure),
or another (rejected for sure).
"""
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import obs
from mxnet_tpu.base import MXNetError
from mxnet_tpu.decode import DecodePredictor, DecodeServer
from mxnet_tpu.models import decoder_lm

VOCAB, CACHE, PAGE, CHUNK = 64, 64, 4, 8
FREE = ("data", "softmax_label", "mtp_data", "mtp_label")


def toy_symbol(**over):
    """Four layers (window, window, full, window; the first dense, the rest
    experts with a shared one), q/k norms, no positions on the full layer,
    and the prediction block."""
    args = dict(
        vocab_size=VOCAB, hidden_size=32, num_layers=4,
        num_attention_heads=4, head_dim=8, num_key_value_heads=2,
        swa_num_key_value_heads=2,
        layer_types=["sliding_attention", "sliding_attention",
                     "full_attention", "sliding_attention"],
        mlp_layer_types=["dense", "sparse", "sparse", "sparse"],
        intermediate_size=48, moe_intermediate_size=16, n_routed_experts=8,
        num_experts_per_tok=2, scoring_func="sigmoid",
        topk_method="noaux_tc", sliding_window=4,
        rope_parameters={"rope_theta": 1e6}, attn_qk_norm=True,
        full_attn_use_rope=False, n_shared_experts=1,
        routed_scaling_factor=2.5, num_nextn_predict_layers=1)
    args.update(over)
    return decoder_lm.get_symbol(**args)


def toy_params(sym, seed=0, head_std=0.15):
    shapes, _, _ = sym.infer_shape(**{n: (1, CACHE) for n in FREE})
    rng = np.random.default_rng(seed)
    out = {}
    for name, shape in zip(sym.list_arguments(), shapes):
        if name in FREE:
            continue
        z = rng.standard_normal(shape).astype(np.float32)
        out[name] = mx.nd.NDArray(jnp.asarray(
            1.0 + 0.1 * z if name.endswith("gamma")
            else 0.01 * z if name.endswith("gate_bias")
            else head_std * z if name == "head_weight" else 0.15 * z),
            mx.cpu())
    return out


@pytest.fixture(scope="module")
def toy():
    sym = toy_symbol()
    return sym, toy_params(sym)


@pytest.fixture(scope="module")
def flat(toy):
    """The toy under a flat head: at temperature 1 most drafts are taken."""
    sym, _ = toy
    return sym, toy_params(sym, head_std=0.02)


_PREDICTORS = {}


def make_server(toy, spec_k, eos=None, temperature=0.0, kv_dtype="",
                slots=2, seed=0, fresh=False):
    """A server over the toy.  Servers of one toy, temperature and cache
    type share a predictor and so what it compiled: a server opens its own
    session (fresh pools, a fresh manager) over it.  ``fresh`` builds a
    predictor of the test's own, for one that counts its traces or reads
    the maps of what it dispatched first."""
    sym, params = toy
    key = (id(params), temperature, kv_dtype)
    pred = None if fresh else _PREDICTORS.get(key, (None, None))[1]
    if pred is None:
        pred = DecodePredictor(sym, params, cache_len=CACHE, ctx=mx.cpu(),
                               paged=True, page_tokens=PAGE,
                               kv_dtype=kv_dtype, prefill_chunk=CHUNK,
                               temperature=temperature)
        if not fresh:
            _PREDICTORS[key] = (params, pred)   # params held: the id stays
    return pred, DecodeServer(pred, max_prefill=32, slots=slots,
                              spec_k=spec_k, eos_id=eos, seed=seed)


PROMPTS = [np.random.default_rng(5).integers(0, VOCAB, size=n)
           for n in (5, 19, 26, 9, 30, 12, 7)]
CAPS = (9, 3, 12, 1, 6, 8, 2)


_ORACLES = {}


def oracle(toy, eos=None, kv_dtype=""):
    """What the toy served without drafts gives: once an EOS and a cache
    type (greedy: the same tokens every time)."""
    key = (id(toy), eos, kv_dtype)
    if key not in _ORACLES:
        _, server = make_server(toy, 0, eos, kv_dtype=kv_dtype)
        for p, c in zip(PROMPTS, CAPS):
            server.submit(p, max_new_tokens=c)
        _ORACLES[key] = (toy, server.run())
    return _ORACLES[key][1]


def drive(server, want, schedule):
    """Run the self-drafting server tick by tick.  ``schedule(tick)`` says
    what each tick's drafts are: "accept" puts the oracle's next token of
    every live slot into the device state, "reject" another token, None
    leaves what the block drafted.  The test keeps the count of tokens each
    slot has committed ON THE DEVICE (the host's record lags a tick): one at
    the commit, then two a forced accept, one a forced reject."""
    for p, c in zip(PROMPTS, CAPS):
        server.submit(p, max_new_tokens=c)
    ps = server.serve_open()
    on_device, tick = {}, 0
    while server.has_work:
        how = schedule(tick)
        live = dict(ps["active"])
        for slot in list(on_device):
            if live.get(slot) is not on_device[slot][0]:
                del on_device[slot]
        for slot, rec in live.items():
            on_device.setdefault(slot, [rec, 1])
        if how and live:
            draft = np.asarray(ps["state"].draft).copy()
            for slot, (rec, n) in on_device.items():
                ref = want[rec["rid"]]
                nxt = int(ref[n]) if n < len(ref) else 0
                draft[slot, 0] = nxt if how == "accept" \
                    else (nxt + 1) % VOCAB
            ps["state"] = ps["state"]._replace(draft=jax.device_put(
                jnp.asarray(draft), mx.cpu().jax_device))
        for slot, pair in on_device.items():
            known = pair[1] < len(want[pair[0]["rid"]])
            pair[1] += 2 if how == "accept" and known else 1
        if not how:
            on_device.clear()       # the block's own drafts: nothing forced
        server.serve_tick()
        tick += 1
    return server.serve_results()


SCHEDULES = {
    "block": lambda t: None,
    "accept": lambda t: "accept",
    "reject": lambda t: "reject",
    "alternate": lambda t: "accept" if t % 2 else "reject",
    "mostly_accept": lambda t: "reject" if t % 5 == 3 else "accept",
}


@pytest.mark.parametrize("kv_dtype", ["", "int8"])
@pytest.mark.parametrize("how", sorted(SCHEDULES))
def test_greedy_tokens_are_those_of_a_server_that_never_drafts(toy, how,
                                                               kv_dtype):
    """Token for token, whatever was accepted and rejected on the way: a
    rejected draft's keys in the window rings, the full pool and the block's
    own cache are hidden or overwritten before anything reads them, and a
    cap inside an accepted pair cuts the pair."""
    want = oracle(toy, kv_dtype=kv_dtype)
    pred, server = make_server(toy, 1, kv_dtype=kv_dtype)
    got = drive(server, want, SCHEDULES[how])
    assert sorted(got) == sorted(want)
    for rid in want:
        assert got[rid].tolist() == want[rid].tolist(), (how, rid)
    assert [len(got[r]) for r in sorted(got)] == list(CAPS)
    assert server.tokens_out == sum(CAPS)
    if how == "accept":
        assert server.accepted > 10
    if how == "reject":
        assert server.accepted == 0
    assert server.accept_rate == server.accepted / max(server.proposed, 1)
    assert server.spec_steps == server.steps > 0


@pytest.mark.parametrize("how", ["accept", "alternate", "block"])
@pytest.mark.parametrize("at", [1, 2, 4, 5])
def test_an_eos_inside_an_accepted_pair_ends_the_request_there(toy, how, at):
    """The EOS is token ``at`` of the longest answer: first or second of a
    pair by the schedule, and the request ends AT it either way."""
    free = oracle(toy)
    eos = int(free[2][at])
    want = oracle(toy, eos=eos)
    assert len(want[2]) <= at + 1 and want[2][-1] == eos
    _, server = make_server(toy, 1, eos=eos)
    got = drive(server, want, SCHEDULES[how])
    for rid in want:
        assert got[rid].tolist() == want[rid].tolist(), (how, at, rid)
        assert len(got[rid]) <= CAPS[rid]


def test_the_self_drafting_tick_is_read_behind_and_traced_once(toy):
    ticks = obs.registry.get("mx_serve_ticks_total")
    before = {r: ticks.labels(read=r).get() for r in ("behind", "first")}
    proposed = obs.registry.get("mx_spec_proposed").get()
    pred, server = make_server(toy, 1, fresh=True)
    want = oracle(toy)
    got = drive(server, want, SCHEDULES["accept"])
    assert got.keys() == want.keys()
    behind = ticks.labels(read="behind").get() - before["behind"]
    first = ticks.labels(read="first").get() - before["first"]
    assert behind > 10 and first <= 2, (behind, first)
    assert obs.registry.get("mx_spec_proposed").get() - proposed \
        == server.proposed > 0
    # one tick program, one chunk program, one commit: nothing retraced as
    # slots filled, emptied and accepted or rejected
    assert pred.trace_counts["decode"] == 1
    assert pred.trace_counts["chunk"] == 1
    assert pred.trace_counts["commit"] == 1
    assert pred.trace_counts["verify"] == 0
    # what the tick's span says of itself
    notes = [e for e in obs.timeline.events()
             if e.get("name") == "serve.readback"
             and "spec_proposed" in (e.get("args") or {})]
    assert notes
    for e in notes[-5:]:
        a = e["args"]
        assert a["tokens_committed"] == a["spec_proposed"] \
            + a["spec_accepted"]
        assert "moe_rows_held" in a and "mtp_moe_expert_visits" in a


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sampled_requests_get_exactly_their_caps(flat, seed):
    """At temperature 1 with a flat head most drafts are accepted: pairs
    everywhere, and no request receives a token beyond its cap."""
    _, server = make_server(flat, 1, temperature=1.0, seed=seed)
    for p, c in zip(PROMPTS, CAPS):
        server.submit(p, max_new_tokens=c)
    got = server.run()
    assert [len(got[r]) for r in sorted(got)] == list(CAPS)
    assert all(0 <= t < VOCAB for toks in got.values() for t in toks)
    assert 0.3 < server.accept_rate <= 1.0
    assert server.tokens_out == sum(CAPS)


@pytest.mark.parametrize("seed", range(4))
def test_the_acceptance_rule_keeps_the_targets_distribution(seed):
    """On a tiny vocabulary, exactly: a draft d ~ q accepted with min(1, p(d)
    / q(d)), else a token from the residual, is a token from p."""
    from mxnet_tpu.ops.sample import residual_probs

    rng = np.random.default_rng(seed)
    p = rng.dirichlet(np.ones(6)).astype(np.float32)
    q = rng.dirichlet(np.ones(6)).astype(np.float32)
    taken = np.minimum(p, q)                    # q(d) min(1, p(d) / q(d))
    rest = np.asarray(residual_probs(jnp.asarray(p)[None],
                                     jnp.asarray(q)[None]))[0]
    assert np.allclose(taken + (1.0 - taken.sum()) * rest, p, atol=1e-6)


def test_a_request_that_would_wrap_is_refused_at_submit(toy):
    _, server = make_server(toy, 1)
    with pytest.raises(MXNetError, match="keeps a whole request"):
        server.submit(np.arange(30), max_new_tokens=CACHE - 30)
    server.submit(np.arange(30), max_new_tokens=CACHE - 32)


def test_more_than_one_draft_a_tick_is_refused(toy):
    sym, params = toy
    pred = DecodePredictor(sym, params, cache_len=CACHE, ctx=mx.cpu(),
                           paged=True, page_tokens=PAGE, prefill_chunk=CHUNK)
    assert pred.self_drafting
    with pytest.raises(MXNetError, match="one token a tick"):
        DecodeServer(pred, max_prefill=32, slots=2, spec_k=2)


def test_a_ring_without_slack_still_refuses_by_name(toy):
    """Window 4 in pages of 4 with no chunk to make room for: the ring is
    the window itself, and a verify step's two rows have nowhere to go."""
    sym, params = toy
    pred = DecodePredictor(sym, params, cache_len=CACHE, ctx=mx.cpu(),
                           paged=True, page_tokens=1, prefill_chunk=1)
    ring = next(g for g in pred._groups if g.kind == "window")
    assert pred.ring_slack(ring) < 2
    with pytest.raises(MXNetError, match="'window' cache group.*fewer "
                                         "positions beyond its window"):
        DecodeServer(pred, max_prefill=32, slots=2, spec_k=1)
    # with chunks of 8 the ring holds 12 and the same server is built
    make_server(toy, 1)


def test_a_state_group_still_refuses_speculation_by_name():
    from chipbench import harness, manifest, weights

    cfg = dict(manifest.load_json(
        manifest.ROOT, "chipbench/configs/falcon-h1-34b.json"),
        vocab_size=96, hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, intermediate_size=128,
        mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16, mamba_d_state=8,
        mamba_n_groups=2, mamba_chunk_size=8, max_position_embeddings=64,
        serve_num_hidden_layers=2)
    sym = harness.build_symbol(cfg)
    shapes, _, _ = sym.infer_shape(data=(1, 64), softmax_label=(1, 64))
    params = weights.make_params(
        {n: s for n, s in zip(sym.list_arguments(), shapes)
         if n not in FREE}, cfg, 3, "float32")
    pred = DecodePredictor(
        sym, {n: mx.nd.NDArray(v, mx.cpu()) for n, v in params.items()},
        cache_len=64, ctx=mx.cpu(), paged=True, page_tokens=PAGE,
        prefill_chunk=CHUNK)
    with pytest.raises(MXNetError, match="'state' cache group.*rejected "
                                         "draft has already advanced"):
        DecodeServer(pred, max_prefill=32, slots=2, spec_k=1)


def test_a_proposer_drafts_over_a_ring_with_slack(toy):
    """What lifted the refusal is the ring's slack, not who drafts: an
    n-gram proposer over the same window rings gives the oracle's tokens."""
    from mxnet_tpu.decode import NGramProposer

    sym, params = toy
    plain = (toy_symbol(num_nextn_predict_layers=0),
             {n: v for n, v in params.items() if not n.startswith("mtp_")})
    want = None
    for proposer in (None, NGramProposer(2)):
        pred = DecodePredictor(plain[0], plain[1], cache_len=CACHE,
                               ctx=mx.cpu(), paged=True, page_tokens=PAGE,
                               prefill_chunk=CHUNK)
        server = DecodeServer(pred, max_prefill=32, slots=2, spec_k=0,
                              proposer=proposer)
        for p, c in zip(PROMPTS, CAPS):
            server.submit(p, max_new_tokens=c)
        got = server.run()
        if want is None:
            want = got
    assert {r: v.tolist() for r, v in got.items()} \
        == {r: v.tolist() for r, v in want.items()}


# ---------------------------------------------------------------------------
# The accepted serving cells' programs are the text they were: sha256[:16] of
# ``str(jax.make_jaxpr(program)(avals))`` of the paged chunk and decode
# programs of ``decoder_lm`` at toy widths of the three configurations that
# share the builder, the op and the walk with this PR's, taken on the commit
# before PR 46 (65b7e2c) with the very code below, under this directory's
# conftest (the text of a jaxpr depends on jax's configuration).  A PR that
# means to change one replaces its hash and says so.  PR 47 replaced the
# three chunk programs' (the head on the one row that is read, behind a
# conditional); the decode programs' are the ones of 65b7e2c.
# ---------------------------------------------------------------------------
ACCEPTED_TOYS = {
    "mimo-v2.5": dict(
        vocab_size=96, hidden_size=64, num_attention_heads=4, head_dim=24,
        v_head_dim=16, num_key_value_heads=1, swa_num_key_value_heads=2,
        sliding_window=8, intermediate_size=128, moe_intermediate_size=32,
        n_routed_experts=16, num_experts_per_tok=4, held_n_routed_experts=4,
        first_held_expert=4, max_position_embeddings=64),
    "falcon-h1-34b": dict(
        vocab_size=96, hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, intermediate_size=128,
        mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16, mamba_d_state=8,
        mamba_n_groups=2, mamba_chunk_size=8, max_position_embeddings=64,
        serve_num_hidden_layers=3),
    "minicpm-sala": dict(
        vocab_size=96, hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, intermediate_size=128,
        lightning_nh=4, lightning_head_dim=16, dim_model_base=4,
        max_position_embeddings=128,
        sparse_config=dict(kernel_size=8, kernel_stride=4, init_blocks=1,
                           block_size=8, window_size=16, topk=4,
                           dense_len=24),
        serve_num_hidden_layers=3),
}
ACCEPTED_PROGRAMS = {
    "mimo-v2.5.chunk": "4c3218d7fcab1fd6",
    "mimo-v2.5.decode": "be3b0206035659b4",
    "falcon-h1-34b.chunk": "1573e8f198e7a1e2",
    "falcon-h1-34b.decode": "3cfe71bb2005f08a",
    "minicpm-sala.chunk": "7944ed24335f46a1",
    "minicpm-sala.decode": "e8395fda7e5399e5",
}


@pytest.mark.parametrize("which", sorted(ACCEPTED_PROGRAMS))
def test_an_accepted_cells_program_is_the_text_it_was(which):
    from chipbench import harness, manifest
    from mxnet_tpu.programs.spec import probing

    name, kind = which.rsplit(".", 1)
    cfg = dict(manifest.load_json(manifest.ROOT,
                                  "chipbench/configs/%s.json" % name),
               **ACCEPTED_TOYS[name])
    sym = harness.build_symbol(cfg)
    shapes, _, _ = sym.infer_shape(data=(1, 64), softmax_label=(1, 64))
    params = {n: mx.nd.NDArray(jnp.zeros(s, "float32"), mx.cpu())
              for n, s in zip(sym.list_arguments(), shapes)
              if n not in FREE}
    pred = DecodePredictor(sym, params, cache_len=64, ctx=mx.cpu(),
                           paged=True, page_tokens=4, kv_dtype="int8",
                           prefill_chunk=8)
    assert not getattr(pred, "self_drafting", False)
    fn = {"chunk": pred._chunk_impl, "decode": pred._paged_decode_impl}[kind]
    with probing(pred):
        text = str(jax.make_jaxpr(fn)(*pred.serving_avals(2, chunk_w=8)[kind]))
    got = hashlib.sha256(text.encode()).hexdigest()[:16]
    assert got == ACCEPTED_PROGRAMS[which], json.dumps({which: got})


@pytest.fixture(scope="module")
def drafted(toy):
    """A self-drafting predictor of its own that has served the prompts:
    its first dispatches registered its programs' readers."""
    obs.programs.reset(clear_static=True)
    pred, server = make_server(toy, 1, kv_dtype="int8", fresh=True)
    for p, c in zip(PROMPTS, CAPS):
        server.submit(p, max_new_tokens=c)
    server.run()
    yield pred
    obs.programs.reset(clear_static=True)


@pytest.mark.parametrize("program,stem", [
    ("paged_decode_mtp_step", "jit__paged_decode_mtp_impl"),
    ("prefill_chunk_mtp", "jit__mtp_chunk_impl"),
    ("slot_commit_mtp", "jit__commit_mtp_impl")])
def test_the_scope_maps_are_the_self_drafting_programs_that_ran(drafted,
                                                                program,
                                                                stem):
    """``obs.programs``' maps of the tick, the chunk and the commit are read
    off the executables the loop dispatches, with nothing compiled; the
    block's nodes are filed under ``mtp`` and the stack's shared expert under
    ``moe/shared``."""
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **_: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    entry = obs.programs.instruction_maps()[stem]
    assert not compiles and entry["source"] == "dispatched"
    scopes = {v["scope"] for v in entry["instructions"].values()}
    if program != "slot_commit_mtp":
        assert {"mtp", "mtp/experts", "mtp/shared", "mtp/kv_append",
                "moe/shared", "moe/experts", "attn_window/kv_append"} \
            <= scopes, sorted(scopes)


def test_a_sampling_servers_key_split_is_a_program_with_a_map(flat):
    """Every tick of a sampling server splits its key on the device: one
    named program, read off the executable that ran, so that a traced cell's
    busy time is joined to a map to the last tick."""
    obs.programs.reset(clear_static=True)
    _, server = make_server(flat, 1, temperature=1.0, fresh=True)
    for p, c in zip(PROMPTS, CAPS):
        server.submit(p, max_new_tokens=c)
    server.run()
    maps = obs.programs.instruction_maps()
    assert maps["jit__split_key"]["source"] == "dispatched"
    obs.programs.reset(clear_static=True)


# ---------------------------------------------------------------------------
# PR 47: a chunk program runs each output's head on the one row that is read,
# behind a conditional that holds only in the chunk that ends its prompt.
# The oracle is the walk without the cut (``_run(head_rows=None)``, the row
# taken afterwards): the chunk programs as they were before this PR.
# ---------------------------------------------------------------------------

def _old_chunk(pred):
    """The chunk program of the commit before PR 47, over the same operands
    (the seventh unread by a graph without a block): every row through the
    head, then the chunk's last real row taken."""
    from mxnet_tpu.ops.moe import collecting

    def program(env, caches, table1, toks, pos0, nvalid, seventh, key):
        width = toks.shape[1]
        nvalid = jnp.asarray(nvalid, jnp.int32).reshape(-1)
        last = jnp.clip(nvalid - 1, 0, width - 1)
        at_last = lambda x: jnp.take_along_axis(
            x, last[:, None, None], axis=1)[:, 0]
        real = lambda: jnp.arange(width)[None, :] < nvalid[:, None]
        run = dict(tables=table1, valid=nvalid,
                   active=jnp.ones((toks.shape[0],), jnp.int32))
        if not pred.self_drafting:
            with collecting(real=real) as moe:
                probs3, caches = pred._run(env, toks, caches, pos0, **run)
            probs = at_last(probs3)
            return (caches, probs, pred._sample(key, probs)) \
                + ((sum(moe),) if moe else ())
        k_tok, k_draft = jax.random.split(key)
        got = {}

        def between(probs3):
            got["probs"] = probs = at_last(probs3)
            got["tok"] = tok = pred._sample(k_tok, probs)
            nxt = jnp.asarray(seventh, jnp.int32).reshape(-1, 1)
            nxt = jnp.where(nxt < 0, tok, nxt).astype(toks.dtype)
            shifted = jnp.concatenate(
                [toks[:, 1:], jnp.zeros_like(toks[:, :1])], axis=1)
            return jnp.where(jnp.arange(width)[None, :] == last[:, None],
                             nxt, shifted)

        with collecting(real=real) as moe:
            _, caches = pred._run(env, toks, caches, pos0, between=between,
                                  **run)
        block = at_last(pred._mtp_probs)
        return (caches, got["probs"], got["tok"]) \
            + pred._draft_of(k_draft, block) + (block,) \
            + ((sum(moe),) if moe else ())

    return jax.jit(program)


def _attention_lm(vocab, layers):
    from mxnet_tpu.models import attention_lm

    sym = attention_lm.get_symbol(vocab_size=vocab, seq_len=64,
                                  num_layers=layers, embed=32, heads=4,
                                  ffn_hidden=64)
    shapes, _, _ = sym.infer_shape(data=(1, 64), softmax_label=(1, 64))
    rng = np.random.default_rng(3)
    params = {n: mx.nd.array(rng.normal(size=s).astype(np.float32) * 0.1)
              for n, s in zip(sym.list_arguments(), shapes) if n not in FREE}
    return DecodePredictor(sym, params, cache_len=64, ctx=mx.cpu(),
                           paged=True, page_tokens=4, prefill_chunk=8)


def _cut_graph(which):
    """``(predictor, vocabulary)`` of one of the graphs the cut has to fit,
    at a toy size, float weights drawn by the configuration's own rules; a
    vocabulary of 101, which is no other width of any of them."""
    from chipbench import harness, manifest, weights

    kw = dict(cache_len=64, ctx=mx.cpu(), paged=True, page_tokens=4,
              prefill_chunk=8)
    if which == "self-drafting":
        sym = toy_symbol(vocab_size=101)
        return DecodePredictor(sym, toy_params(sym), kv_dtype="int8",
                               **kw), 101
    if which == "attention_lm":
        return _attention_lm(101, 2), 101
    cfg = dict(manifest.load_json(manifest.ROOT,
                                  "chipbench/configs/%s.json" % which),
               **dict(ACCEPTED_TOYS[which], vocab_size=101))
    sym = harness.build_symbol(cfg)
    shapes, _, _ = sym.infer_shape(data=(1, 64), softmax_label=(1, 64))
    drawn = weights.make_params(
        {n: s for n, s in zip(sym.list_arguments(), shapes)
         if n not in FREE}, cfg, 11, "float32")
    params = {n: mx.nd.NDArray(v, mx.cpu()) for n, v in drawn.items()}
    return DecodePredictor(sym, params, kv_dtype="int8", **kw), 101


CUT_GRAPHS = ["attention_lm", "mimo-v2.5", "falcon-h1-34b", "minicpm-sala",
              "self-drafting"]


def _fed(pred, prompt, program, key):
    """``prompt`` through ``program`` in chunks of 8 from a fresh state: what
    each chunk returned, every leaf on the host."""
    state = pred.paged_batch_state(1, drafting=pred.self_drafting)
    mgr = pred._manager
    gate = mgr.gate(prompt, prompt.size, 64, 1, budget_wrap_forks=False)
    mgr.map_slot(0, gate[1], gate[2])
    caches, outs = state.caches, []
    for pos in range(0, prompt.size, 8):
        end = min(pos + 8, prompt.size)
        copies = mgr.ensure(0, pos, end)
        assert not copies
        ends = end >= prompt.size
        seventh = [(-1 if ends else prompt[end]) if pred.self_drafting
                   else int(ends)]
        out = program(pred._env, caches,
                      *pred._chunk_operands(0, prompt[pos:end], pos, 8),
                      np.asarray(seventh, np.int32),
                      jax.random.fold_in(key, pos))
        caches = out[0]
        outs.append(jax.tree_util.tree_map(np.asarray, out))
    return outs


@pytest.mark.parametrize("temperature", [0.0, 1.0])
@pytest.mark.parametrize("which", CUT_GRAPHS)
def test_a_chunks_head_runs_on_the_row_that_is_read(which, temperature):
    """A prompt of 20 tokens in 3 chunks: after every chunk the pools, the
    recurrent states, the index planes and the experts' row counts are
    bit-identical to the walk's without the cut; after the last, the first
    token (and the first draft) is equal and its distribution (and the
    block's) equal to float32 rounding: a product of one row may be blocked
    otherwise than one of eight, so rtol 1e-6 (atol 1e-9: a probability under
    1e-3 of the largest carries its rounding absolutely)."""
    pred, vocab = _cut_graph(which)
    pred._temperature = temperature
    prompt = np.random.default_rng(9).integers(0, vocab, size=20)
    key = jax.random.PRNGKey(4)
    new = pred._chunk_mtp_fn if pred.self_drafting else pred._chunk_fn
    want = _fed(pred, prompt, _old_chunk(pred), key)
    got = _fed(pred, prompt, new, key)
    assert len(got) == len(want) == 3
    # caches first, and the experts' counts last where the graph has them
    counted = len(want[0]) > (6 if pred.self_drafting else 3)
    for n, (a, b) in enumerate(zip(got, want)):
        assert len(a) == len(b)
        leaves_a, leaves_b = (jax.tree_util.tree_leaves(x[0])
                              for x in (a, b))
        assert len(leaves_a) == len(leaves_b) > 0
        for x, y in zip(leaves_a, leaves_b):
            np.testing.assert_array_equal(x, y, err_msg="chunk %d" % n)
        if counted:
            np.testing.assert_array_equal(a[-1], b[-1])
    if which in ("mimo-v2.5", "self-drafting"):
        assert counted
    a, b = got[-1], want[-1]
    np.testing.assert_array_equal(a[2], b[2])           # the first token
    np.testing.assert_allclose(a[1], b[1], rtol=1e-6, atol=1e-9)
    assert a[1].shape == (1, vocab) and abs(a[1].sum() - 1) < 1e-5
    if pred.self_drafting:
        np.testing.assert_array_equal(a[3], b[3])       # the first draft
        np.testing.assert_allclose(a[5], b[5], rtol=1e-6, atol=1e-9)
        if temperature:
            np.testing.assert_allclose(a[4], b[4], rtol=1e-6, atol=1e-9)
    # a chunk that does not end its prompt hands back zeros, unread
    for early in got[:-1]:
        assert not early[1].any() and not early[2].any()


def _eqns(jaxpr, inside=False):
    """``(eqn, inside a cond)`` of a jaxpr and every jaxpr inside it."""
    for eqn in jaxpr.eqns:
        yield eqn, inside
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub, inside or eqn.primitive.name == "cond")


@pytest.mark.parametrize("which", CUT_GRAPHS)
def test_a_chunk_program_multiplies_one_row_by_the_vocabulary(which):
    """No product of the chunk program has a row a position and the
    vocabulary's width; the products that have the vocabulary's width have
    one row and sit inside a conditional, which alone reads the head's
    matrix: a chunk that does not end its prompt never touches it."""
    from mxnet_tpu.programs.spec import probing

    pred, vocab = _cut_graph(which)
    kind, fn = ("mtp_chunk", pred._mtp_chunk_impl) if pred.self_drafting \
        else ("chunk", pred._chunk_impl)
    avals = pred.serving_avals(2, chunk_w=8)[kind]
    with probing(pred):
        closed = jax.make_jaxpr(fn)(*avals)
    heads = []
    for eqn, inside in _eqns(closed.jaxpr):
        if eqn.primitive.name != "dot_general":
            continue
        shape = eqn.outvars[0].aval.shape
        if shape[-1] == vocab:
            assert int(np.prod(shape[:-1])) == 1 and inside, shape
            heads.append(shape)
    assert len(heads) == (2 if pred.self_drafting else 1)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(avals)[0]]
    matrix = closed.jaxpr.invars[
        next(i for i, p in enumerate(paths) if "head_weight" in p)]
    readers = [eqn.primitive.name for eqn in closed.jaxpr.eqns
               if matrix in eqn.invars]
    assert readers == ["cond"] * len(heads)


@pytest.mark.parametrize("output", ["SoftmaxOutput", "softmax"])
def test_a_graph_that_names_no_head_still_chunks(monkeypatch, output):
    """A user's symbol that names no ``head_loss`` layer: its
    ``SoftmaxOutput`` is of that layer by its kind and is the whole region
    (the product runs over every row, the softmax over one); with a plain
    ``softmax`` for an output there is no region, the walk runs every node
    over every row as it did and the chunk's row is taken from what it
    computed.  Either way the first token, its distribution and the pools are
    those of the graph that names its head."""
    from mxnet_tpu import symbol
    from mxnet_tpu.models import attention_lm

    build = lambda: _attention_lm(50, 1)

    named = build()
    # the two Reshapes, the product and the softmax
    assert len(named._heads[0]) == 4
    monkeypatch.setattr(attention_lm, "_HEAD", {})
    if output == "softmax":
        monkeypatch.setattr(
            symbol, "SoftmaxOutput", lambda data, label, name, **_:
            symbol.softmax(data, axis=-1, name=name) + 0 * symbol.sum(label))
    plain = build()
    assert len(plain._heads[0]) == (1 if output == "SoftmaxOutput" else 0)
    toks = np.random.default_rng(1).integers(0, 50, size=(1, 20))
    state_a, probs_a = named.prefill(toks.astype(np.float32))
    state_b, probs_b = plain.prefill(toks.astype(np.float32))
    np.testing.assert_array_equal(state_a.tok, state_b.tok)
    np.testing.assert_allclose(probs_a, probs_b, rtol=1e-6, atol=1e-9)
    for x, y in zip(jax.tree_util.tree_leaves(state_a.caches),
                    jax.tree_util.tree_leaves(state_b.caches)):
        np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("drafting", [False, True])
def test_the_loop_counts_the_chunks_that_ran_the_head(toy, drafting):
    """A prompt of 20 tokens in chunks of 8: two chunks skip the head, the
    third runs it; the counter and the ``serve.prefill`` spans say so."""
    _, server = make_server(toy, int(drafting), slots=1)
    chunks = obs.registry.get("mx_serve_chunks_total")
    before = {h: chunks.labels(head=h).get() for h in ("run", "skipped")}
    seen = len(obs.timeline.events())
    server.submit(np.random.default_rng(2).integers(0, VOCAB, size=20),
                  max_new_tokens=3)
    assert len(server.run()[0]) == 3
    assert chunks.labels(head="skipped").get() - before["skipped"] == 2
    assert chunks.labels(head="run").get() - before["run"] == 1
    spans = [e["args"] for e in obs.timeline.events()[seen:]
             if e.get("name") == "serve.prefill"]
    assert [(a["pos"], a["tokens"], a["head"]) for a in spans] == [
        (0, 8, False), (8, 8, False), (16, 4, True)]


# sha256[:16] of the jaxprs of the programs that pass ``_run`` no
# ``head_rows``, for the toy graph with a prediction block, taken on the
# commit before PR 47 (c7bdf15) with the very code below: the cut is an
# argument that only the two chunk programs pass.
UNCUT_PROGRAMS = {"mtp_step": "c9b3515ae947beaa",
                  "decode": "e11e1b96aaea3a43",
                  "verify": "7e6f23d8ab6ab61f"}


@pytest.mark.parametrize("kind", sorted(UNCUT_PROGRAMS))
def test_a_program_that_passes_no_rows_is_the_text_it_was(toy, kind):
    from mxnet_tpu.programs.spec import probing

    pred, _ = make_server(toy, 1, kv_dtype="int8")
    fn = {"mtp_step": pred._paged_decode_mtp_impl,
          "decode": pred._paged_decode_impl,
          "verify": pred._paged_verify_impl}[kind]
    with probing(pred):
        text = str(jax.make_jaxpr(fn)(
            *pred.serving_avals(2, chunk_w=8, spec_k=2)[kind]))
    got = hashlib.sha256(text.encode()).hexdigest()[:16]
    assert got == UNCUT_PROGRAMS[kind], json.dumps({kind: got})
