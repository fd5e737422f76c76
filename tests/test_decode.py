"""KV-cached incremental decoding (mxnet_tpu.decode + ops.attention decode
kernels).

Covers the PR-4 acceptance surface: prefill+decode logits match the full
forward pass (fp32 tolerance), cache-append masking stays correct at
ring-buffer wrap (sliding-window reference), sampling is deterministic
under a fixed PRNGKey, the TP-sharded cache on the (2, 2, 2) virtual mesh
reproduces the unsharded logits, and the batched serving loop retires /
refills slots without changing results.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.decode import DecodePredictor, DecodeServer
from mxnet_tpu.models import attention_lm
from mxnet_tpu.ops import attention as attn
from mxnet_tpu.ops.sample import sample_tokens

VOCAB, T, EMBED, HEADS = 17, 16, 8, 2
B = 2


def _lm_and_params(seed=0, seq_len=T):
    sym = attention_lm.get_symbol(VOCAB, seq_len, num_layers=2, embed=EMBED,
                                  heads=HEADS, ffn_hidden=16)
    rng = np.random.RandomState(seed)
    arg_shapes, _, _ = sym.infer_shape(data=(B, seq_len),
                                       softmax_label=(B, seq_len))
    params = {}
    for name, shape in zip(sym.list_arguments(), arg_shapes):
        if name in ("data", "softmax_label"):
            continue
        params[name] = rng.normal(0, 0.5, shape).astype(np.float32)
    return sym, params


def _full_forward_probs(sym, params, x):
    exe = sym.simple_bind(mx.cpu(), grad_req="null", data=x.shape,
                          softmax_label=x.shape)
    exe.copy_params_from({k: mx.nd.array(v) for k, v in params.items()},
                         allow_extra_params=True)
    outs = exe.forward(is_train=False, data=mx.nd.array(x),
                       softmax_label=mx.nd.array(
                           np.zeros(x.shape, np.float32)))
    return outs[0].asnumpy().reshape(x.shape[0], x.shape[1], VOCAB)


def test_prefill_plus_decode_matches_full_forward():
    """Teacher-forced decode: the step-t distribution equals the full
    forward pass's position-t output, for every t past the prefill."""
    sym, params = _lm_and_params()
    rng = np.random.RandomState(1)
    x = rng.randint(0, VOCAB, (B, T)).astype(np.float32)
    full = _full_forward_probs(sym, params, x)

    pred = DecodePredictor(sym, params, cache_len=T)
    prefill = T // 2
    state, probs = pred.prefill(x[:, :prefill], prefill)
    np.testing.assert_allclose(np.asarray(probs), full[:, prefill - 1],
                               rtol=1e-5, atol=1e-6)
    for t in range(prefill, T):
        state = state._replace(tok=jnp.asarray(x[:, t:t + 1], jnp.int32))
        state, probs = pred.step(state)
        np.testing.assert_allclose(np.asarray(probs), full[:, t],
                                   rtol=1e-5, atol=1e-6)
    # the per-sequence lengths advanced with the cache
    assert np.asarray(state.lens).tolist() == [T] * B


def test_prefill_respects_padded_prompt_lengths():
    """Rows of one padded batch prefill to DIFFERENT lengths; each row's
    first distribution matches the full forward at ITS last position."""
    sym, params = _lm_and_params()
    rng = np.random.RandomState(2)
    x = rng.randint(0, VOCAB, (B, T)).astype(np.float32)
    full = _full_forward_probs(sym, params, x)

    pred = DecodePredictor(sym, params, cache_len=T)
    lens = np.array([5, 9], np.int32)
    padded = x.copy()
    for b in range(B):
        padded[b, lens[b]:] = 0.0  # garbage past the prompt
    # reference rows come from per-row full forwards over the REAL prefix
    _, probs = pred.prefill(padded, lens)
    for b in range(B):
        ref = _full_forward_probs(sym, params, x[b:b + 1])[0, lens[b] - 1]
        np.testing.assert_allclose(np.asarray(probs)[b], ref,
                                   rtol=1e-5, atol=1e-6)


def test_cache_append_masking_at_ring_wrap():
    """Once generation passes cache_len, the ring keeps the latest C
    tokens: decode attention must equal dense attention over exactly that
    sliding window — slot order is scrambled by the wrap, masking must
    not be."""
    rng = np.random.RandomState(3)
    c, e, total = 8, EMBED, 13
    ks = rng.normal(size=(1, total, e)).astype(np.float32)
    vs = rng.normal(size=(1, total, e)).astype(np.float32)
    qs = rng.normal(size=(1, total, e)).astype(np.float32)

    kc = jnp.zeros((1, c, e), jnp.float32)
    vc = jnp.zeros((1, c, e), jnp.float32)
    for t in range(total):
        kc = attn.cache_append(kc, jnp.asarray(ks[:, t:t + 1]), t)
        vc = attn.cache_append(vc, jnp.asarray(vs[:, t:t + 1]), t)
        out = attn.sdpa_decode(jnp.asarray(qs[:, t:t + 1]), kc, vc, t + 1,
                               num_heads=HEADS)
        lo = max(0, t + 1 - c)
        ref = attn.sdpa(jnp.asarray(qs[:, t:t + 1]),
                        jnp.asarray(ks[:, lo:t + 1]),
                        jnp.asarray(vs[:, lo:t + 1]), num_heads=HEADS)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6,
                                   err_msg="wrap mismatch at t=%d" % t)


def test_generation_past_cache_len_stays_finite():
    """End-to-end ring wrap: a cache shorter than the generation run keeps
    producing valid distributions (no NaN from a masking hole)."""
    sym, params = _lm_and_params()
    rng = np.random.RandomState(4)
    x = rng.randint(0, VOCAB, (B, 6)).astype(np.float32)
    pred = DecodePredictor(sym, params, cache_len=8)
    state, _ = pred.prefill(x, 6)
    for _ in range(10):  # wraps at total=8
        state, probs = pred.step(state)
        p = np.asarray(probs)
        assert np.isfinite(p).all()
        np.testing.assert_allclose(p.sum(axis=-1), 1.0, rtol=1e-4)


def test_sampling_determinism_under_fixed_key():
    """Same PRNGKey -> bit-identical token sequences, greedy AND
    temperature/top-k; different keys actually vary (non-degenerate)."""
    sym, params = _lm_and_params()
    rng = np.random.RandomState(5)
    x = rng.randint(0, VOCAB, (B, 8)).astype(np.float32)

    greedy = DecodePredictor(sym, params, cache_len=T)
    g1 = greedy.generate(x, 8, max_new_tokens=6, seed=11)
    g2 = greedy.generate(x, 8, max_new_tokens=6, seed=11)
    np.testing.assert_array_equal(g1, g2)

    hot = DecodePredictor(sym, params, cache_len=T, temperature=1.0,
                          top_k=5)
    s1 = hot.generate(x, 8, max_new_tokens=8, seed=11)
    s2 = hot.generate(x, 8, max_new_tokens=8, seed=11)
    np.testing.assert_array_equal(s1, s2)
    draws = {tuple(hot.generate(x, 8, max_new_tokens=8, seed=s)[0])
             for s in range(6)}
    assert len(draws) > 1, "temperature sampling never varied across seeds"


def test_sample_tokens_top_k_support():
    """top-k truncation: ids outside the k largest logits never sampled."""
    logits = jnp.asarray(np.log([[0.05, 0.1, 0.4, 0.3, 0.15]] * 4,
                                dtype=np.float32))
    key = jax.random.PRNGKey(0)
    for i in range(20):
        ids = np.asarray(sample_tokens(jax.random.fold_in(key, i), logits,
                                       temperature=1.0, top_k=2))
        assert set(ids.tolist()) <= {2, 3}
    np.testing.assert_array_equal(
        np.asarray(sample_tokens(key, logits, temperature=0.0)), [2] * 4)


def test_tp_sharded_cache_parity_on_222_mesh():
    """DecodePredictor on the (data=2, seq=2, model=2) virtual mesh —
    params on the Megatron plan, KV caches E-sharded on 'model' — must
    reproduce the unsharded logits and samples."""
    from mxnet_tpu.parallel import MeshConfig, build_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-virtual-device harness")
    mesh = build_mesh(MeshConfig(data=2, seq=2, model=2))

    sym, params = _lm_and_params()
    rng = np.random.RandomState(6)
    x = rng.randint(0, VOCAB, (B, T)).astype(np.float32)

    plain = DecodePredictor(sym, params, cache_len=T)
    shard = DecodePredictor(sym, params, cache_len=T, mesh=mesh)
    # the cache really is model-sharded (not silently replicated)
    s_state, s_probs = shard.prefill(x[:, :8], 8)
    kc = s_state.caches[0][0]
    specs = {kc.sharding.spec for (kc, vc) in s_state.caches}
    assert all("model" in tuple(s) for s in specs), specs

    p_state, p_probs = plain.prefill(x[:, :8], 8)
    np.testing.assert_allclose(np.asarray(s_probs), np.asarray(p_probs),
                               rtol=1e-4, atol=1e-5)
    for _ in range(4):
        s_state, s_probs = shard.step(s_state)
        p_state, p_probs = plain.step(p_state)
        np.testing.assert_allclose(np.asarray(s_probs),
                                   np.asarray(p_probs),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(s_state.tok),
                                      np.asarray(p_state.tok))


def test_serving_loop_continuous_batching():
    """More requests than slots: every request completes, each result
    equals the single-sequence greedy generation for its prompt, and
    admission happened through slot reuse (retire -> refill)."""
    sym, params = _lm_and_params()
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, VOCAB, (n,)) for n in (5, 7, 4, 6, 5)]
    max_new = 5

    pred = DecodePredictor(sym, params, cache_len=T)
    refs = {}
    for i, p in enumerate(prompts):
        refs[i] = pred.generate(p[None].astype(np.float32), p.size,
                                max_new_tokens=max_new, seed=0)[0]

    server = DecodeServer(pred, max_prefill=T, slots=2,
                          max_new_tokens=max_new)
    ids = [server.submit(p) for p in prompts]
    results = server.run()
    assert sorted(results) == sorted(ids)
    assert server.steps > 0 and server.tokens_out == max_new * len(prompts)
    for rid, p in zip(ids, prompts):
        np.testing.assert_array_equal(results[rid], refs[rid])


def test_serving_loop_eos_retirement():
    """A slot retires the moment its sequence emits EOS and the freed slot
    serves the next queued request."""
    sym, params = _lm_and_params()
    rng = np.random.RandomState(8)
    pred = DecodePredictor(sym, params, cache_len=T)
    prompt = rng.randint(0, VOCAB, (6,))
    # learn what greedy emits first, then use THAT id as "EOS"
    first = int(pred.generate(prompt[None].astype(np.float32), 6,
                              max_new_tokens=1)[0, 0])
    server = DecodeServer(pred, max_prefill=T, slots=1, eos_id=first,
                          max_new_tokens=64)
    ids = [server.submit(prompt) for _ in range(3)]
    results = server.run()
    for rid in ids:
        assert results[rid][-1] == first and results[rid].size <= 64


def test_decode_step_dot_flops_are_prefix_independent():
    """The HLO-level O(1) property: the decode-step program's matmul FLOPs
    are identical at any prefix position, and a fraction of the
    recompute-the-prefix (full forward) program's, which itself grows
    with T."""
    from mxnet_tpu.parallel.hlo_stats import dot_flops

    sym, params = _lm_and_params()
    rng = np.random.RandomState(9)
    x = rng.randint(0, VOCAB, (B, T)).astype(np.float32)
    pred = DecodePredictor(sym, params, cache_len=T)

    state, _ = pred.prefill(x[:, :4], 4)
    early = dot_flops(pred.decode_step_text(state))
    for _ in range(8):
        state, _ = pred.step(state)
    late = dot_flops(pred.decode_step_text(state))
    assert early == late > 0
    f_full = dot_flops(pred.prefill_text(B, T))
    f_half = dot_flops(pred.prefill_text(B, T // 2))
    assert f_full >= 1.5 * f_half
    assert f_full >= 4 * early


def test_predictor_reshape_shares_bind_cache():
    """Satellite: reshape() clones share one executor cache keyed by input
    shapes — flipping back to a seen shape rebinds nothing."""
    from mxnet_tpu.predictor import Predictor

    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(0)
    params = {"fc_weight": rng.normal(size=(4, 8)).astype(np.float32),
              "fc_bias": np.zeros(4, np.float32)}
    pred = Predictor(net, params, {"data": (2, 8)})
    assert not hasattr(pred, "_jit_fn")  # dead attribute really dropped
    big = pred.reshape({"data": (6, 8)})
    assert big._exec is not pred._exec
    again = big.reshape({"data": (2, 8)})
    assert again._exec is pred._exec  # cache hit, no re-bind
    x = rng.normal(size=(6, 8)).astype(np.float32)
    o_big = big.forward(data=x)[0].asnumpy()
    o_small = again.forward(data=x[:2])[0].asnumpy()
    np.testing.assert_allclose(o_big[:2], o_small, rtol=1e-5, atol=1e-6)


def test_prefill_wider_than_cache_rejected():
    """A prompt window wider than the cache would wrap padded rows over
    real tokens — refused up front (decode itself may still wrap)."""
    sym, params = _lm_and_params()
    pred = DecodePredictor(sym, params, cache_len=8)
    with pytest.raises(mx.MXNetError, match="cache_len"):
        pred.prefill(np.zeros((B, 12), np.float32), 4)
    with pytest.raises(mx.MXNetError, match="cache_len"):
        DecodeServer(pred, max_prefill=12)


def test_server_honors_small_explicit_caps():
    """max_new_tokens=1 (and an explicit 0) must not balloon to the
    MXNET_DECODE_MAX_NEW default."""
    sym, params = _lm_and_params()
    rng = np.random.RandomState(10)
    pred = DecodePredictor(sym, params, cache_len=T)
    server = DecodeServer(pred, max_prefill=T, slots=2, max_new_tokens=0)
    a = server.submit(rng.randint(0, VOCAB, (4,)), max_new_tokens=1)
    b = server.submit(rng.randint(0, VOCAB, (4,)))
    results = server.run()
    assert results[a].size == 1
    assert results[b].size <= 1


def test_cache_append_multi_token_wrap_keeps_latest():
    """A single multi-position append longer than the cache must land the
    LATEST C tokens deterministically (scatter indices stay unique)."""
    c, e = 4, 6
    rng = np.random.RandomState(11)
    new = rng.normal(size=(1, 7, e)).astype(np.float32)
    cache = attn.cache_append(jnp.zeros((1, c, e), jnp.float32),
                              jnp.asarray(new), 0)
    got = np.asarray(cache)
    # token at position p (3..6) sits at slot p % c
    for p in range(7 - c, 7):
        np.testing.assert_array_equal(got[0, p % c], new[0, p])


# ---------------------------------------------------------------------------
# Speculative decoding (PR 6): distribution preservation, padded batches,
# ring-wrap gating, serving-loop interaction.
# ---------------------------------------------------------------------------

def test_speculative_greedy_matches_plain_generate():
    """Greedy speculative decoding emits EXACTLY the target-only greedy
    sequence — n-gram proposer AND draft-model proposer, on a padded
    batch whose rows prefill to different lengths (the padded-prefill x
    speculative-verify interaction)."""
    sym, params = _lm_and_params()
    rng = np.random.RandomState(20)
    x = rng.randint(0, VOCAB, (B, 8)).astype(np.float32)
    pred = DecodePredictor(sym, params, cache_len=2 * T)

    ref = pred.generate(x, 8, max_new_tokens=8, seed=3)
    got = pred.generate_speculative(x, 8, max_new_tokens=8, seed=3, k=3)
    np.testing.assert_array_equal(ref, got)

    # a smaller draft model over the same vocabulary
    dsym, dparams = _lm_and_params(seed=9)
    draft = DecodePredictor(dsym, dparams, cache_len=2 * T)
    got_d = pred.generate_speculative(x, 8, max_new_tokens=8, seed=3, k=3,
                                      draft=draft)
    np.testing.assert_array_equal(ref, got_d)
    # the draft's decode program traced exactly once across the run
    assert draft.trace_counts["decode"] == 1

    # padded batch: rows of different real lengths
    lens = np.array([5, 8], np.int32)
    xp = x.copy()
    xp[0, 5:] = 0.0
    ref_p = pred.generate(xp, lens, max_new_tokens=8, seed=3)
    got_p = pred.generate_speculative(xp, lens, max_new_tokens=8, seed=3,
                                      k=3)
    np.testing.assert_array_equal(ref_p, got_p)


def test_generate_speculative_eos_discards_window_tail():
    """A row that hits EOS mid-speculation-window retires AT the EOS:
    tokens match plain greedy through the EOS, and the row pads with its
    last token afterwards (the window tail is discarded, same rule as
    the serving loop)."""
    sym, params = _lm_and_params()
    rng = np.random.RandomState(32)
    x = rng.randint(0, VOCAB, (B, 6)).astype(np.float32)
    pred = DecodePredictor(sym, params, cache_len=4 * T)
    ref = pred.generate(x, 6, max_new_tokens=10, seed=2)
    eos = next(int(ref[0][i]) for i in range(1, 10)
               if ref[0][i] != ref[0][0])
    got = pred.generate_speculative(x, 6, max_new_tokens=10, seed=2, k=3,
                                    eos_id=eos)
    e0 = int(np.flatnonzero(ref[0] == eos)[0])
    np.testing.assert_array_equal(got[0, :e0 + 1], ref[0, :e0 + 1])
    assert (got[0, e0:] == eos).all()


def test_speculative_gates_off_at_ring_wrap_boundary():
    """With a cache too short for the whole generation, speculation must
    fall back to plain steps near the wrap boundary — and still equal
    plain greedy generation token for token (the fallback shares its
    programs, so nothing retraces either)."""
    sym, params = _lm_and_params()
    rng = np.random.RandomState(21)
    x = rng.randint(0, VOCAB, (B, 6)).astype(np.float32)
    pred = DecodePredictor(sym, params, cache_len=12)
    ref = pred.generate(x, 6, max_new_tokens=10, seed=1)
    got = pred.generate_speculative(x, 6, max_new_tokens=10, seed=1, k=3)
    np.testing.assert_array_equal(ref, got)
    assert pred.trace_counts["verify"] <= 1
    assert pred.trace_counts["decode"] == 1


def test_residual_probs_identity():
    """The acceptance-rejection identity that makes speculative sampling
    exact: q(v) min(1, p(v)/q(v)) + P(reject) res(v) == p(v)."""
    from mxnet_tpu.ops.sample import residual_probs

    rng = np.random.RandomState(3)
    for _ in range(16):
        p = rng.dirichlet(np.ones(7)).astype(np.float32)
        q = rng.dirichlet(np.ones(7)).astype(np.float32)
        res = np.asarray(residual_probs(jnp.asarray(p), jnp.asarray(q)))
        accept = q * np.minimum(1.0, p / q)
        marginal = accept + (1.0 - accept.sum()) * res
        np.testing.assert_allclose(marginal, p, rtol=1e-4, atol=1e-6)


def test_speculative_accept_preserves_target_distribution():
    """Monte-Carlo identity check on the kernel itself: over many keys,
    the FIRST emitted token's empirical distribution equals the target's
    row-0 distribution — for a stochastic draft whose tokens are DRAWN
    from q (the theorem's precondition) and for a deterministic proposer
    (delta q, any fixed proposal)."""
    from mxnet_tpu.ops.sample import speculative_accept

    rng = np.random.RandomState(4)
    v, k, n = 5, 2, 4000
    p = jnp.asarray(rng.dirichlet(np.ones(v), size=(1, k + 1))[None, 0]
                    .reshape(1, k + 1, v).astype(np.float32))
    q = jnp.asarray(rng.dirichlet(np.ones(v), size=(1, k))
                    .reshape(1, k, v).astype(np.float32))
    fixed_draft = jnp.asarray(rng.randint(0, v, (1, k)), jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(0), n)

    def first_tok_stochastic(key):
        kd, ka = jax.random.split(key)
        draft = jax.vmap(
            lambda kk, row: jax.random.categorical(kk, jnp.log(row)))(
                jax.random.split(kd, k), q[0]).astype(jnp.int32)[None]
        return speculative_accept(ka, p, draft, q, greedy=False)[1][0, 0]

    def first_tok_delta(key):
        return speculative_accept(key, p, fixed_draft, None,
                                  greedy=False)[1][0, 0]

    for name, fn in (("q-drawn", first_tok_stochastic),
                     ("delta", first_tok_delta)):
        toks = np.asarray(jax.jit(jax.vmap(fn))(keys))
        emp = np.bincount(toks, minlength=v) / n
        np.testing.assert_allclose(emp, np.asarray(p)[0, 0], atol=0.035,
                                   err_msg=name)


def test_speculative_stochastic_determinism():
    """Fixed seed -> bit-identical speculative samples; seeds vary."""
    sym, params = _lm_and_params()
    rng = np.random.RandomState(22)
    x = rng.randint(0, VOCAB, (B, 8)).astype(np.float32)
    hot = DecodePredictor(sym, params, cache_len=2 * T, temperature=1.0,
                          top_k=5)
    s1 = hot.generate_speculative(x, 8, max_new_tokens=8, seed=11, k=3)
    s2 = hot.generate_speculative(x, 8, max_new_tokens=8, seed=11, k=3)
    np.testing.assert_array_equal(s1, s2)
    draws = {tuple(hot.generate_speculative(x, 8, max_new_tokens=8,
                                            seed=s, k=3)[0])
             for s in range(5)}
    assert len(draws) > 1, "speculative sampling never varied across seeds"


# ---------------------------------------------------------------------------
# Quantized KV caches (PR 6): parity, ring wrap, byte accounting.
# ---------------------------------------------------------------------------

# documented logit-parity tolerances (docs/inference.md): max |delta p|
# against the f32 cache on teacher-forced decode
_KV_TOLS = {"int8": 2e-3, "float8_e4m3fn": 1e-2, "float8_e5m2": 3e-2}


@pytest.mark.parametrize("kv_dtype", sorted(_KV_TOLS))
def test_quantized_cache_logit_parity(kv_dtype):
    """int8/fp8 caches reproduce the f32-cache output distributions
    within the documented tolerance, prefill AND teacher-forced decode."""
    sym, params = _lm_and_params()
    rng = np.random.RandomState(23)
    x = rng.randint(0, VOCAB, (B, T)).astype(np.float32)
    pred = DecodePredictor(sym, params, cache_len=T)
    qpred = DecodePredictor(sym, params, cache_len=T, kv_dtype=kv_dtype)
    tol = _KV_TOLS[kv_dtype]
    s0, p0 = pred.prefill(x[:, :8], 8)
    s1, p1 = qpred.prefill(x[:, :8], 8)
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p0), atol=tol)
    for t in range(8, 12):
        # two copies: each step donates its own state's token buffer
        s0 = s0._replace(tok=jnp.asarray(x[:, t:t + 1], jnp.int32))
        s1 = s1._replace(tok=jnp.asarray(x[:, t:t + 1], jnp.int32))
        s0, p0 = pred.step(s0)
        s1, p1 = qpred.step(s1)
        np.testing.assert_allclose(np.asarray(p1), np.asarray(p0),
                                   atol=tol, err_msg="t=%d" % t)
    # the caches really store narrow data (not silently f32)
    kc = s1.caches[0][0]
    assert isinstance(kc, attn.QuantKV)
    assert str(kc.data.dtype) == kv_dtype
    assert kc.scale.dtype == jnp.float32
    # and the static byte accounting sees the shrink
    assert qpred.cache_bytes(s1) < pred.cache_bytes(s0)


def test_quantized_cache_scale_replicates_when_heads_dont_divide():
    """E % model == 0 but heads % model != 0 (legal for the f32 cache —
    an E-split finer than a head split): the quantized data plane still
    E-splits while the (B, C, H) scale plane REPLICATES instead of
    erroring at trace time, and logits match the unsharded predictor."""
    from mxnet_tpu.parallel import MeshConfig, build_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-virtual-device harness")
    mesh = build_mesh(MeshConfig(data=2, seq=1, model=4))  # heads=2 % 4 != 0

    sym, params = _lm_and_params()
    rng = np.random.RandomState(31)
    x = rng.randint(0, VOCAB, (B, T)).astype(np.float32)
    plain = DecodePredictor(sym, params, cache_len=T, kv_dtype="int8")
    shard = DecodePredictor(sym, params, cache_len=T, kv_dtype="int8",
                            mesh=mesh)
    s_state, s_probs = shard.prefill(x[:, :8], 8)
    p_state, p_probs = plain.prefill(x[:, :8], 8)
    kc = s_state.caches[0][0]
    assert "model" in tuple(kc.data.sharding.spec), kc.data.sharding
    assert "model" not in tuple(kc.scale.sharding.spec), kc.scale.sharding
    np.testing.assert_allclose(np.asarray(s_probs), np.asarray(p_probs),
                               rtol=1e-4, atol=1e-5)
    for _ in range(3):
        s_state, s_probs = shard.step(s_state)
        p_state, p_probs = plain.step(p_state)
        np.testing.assert_allclose(np.asarray(s_probs),
                                   np.asarray(p_probs),
                                   rtol=1e-4, atol=1e-5)


def test_quantized_ring_wrap_matches_dense_window():
    """Sliding-window parity at ring wrap with a QUANTIZED cache: decode
    attention over the wrapped int8 ring equals dense attention over the
    dequantized window — bit-for-bit the same numerics, only the storage
    is narrow."""
    rng = np.random.RandomState(24)
    c, e, total = 8, EMBED, 13
    ks = rng.normal(size=(1, total, e)).astype(np.float32)
    vs = rng.normal(size=(1, total, e)).astype(np.float32)
    qs = rng.normal(size=(1, total, e)).astype(np.float32)

    kc = attn.QuantKV(jnp.zeros((1, c, e), jnp.int8),
                      jnp.zeros((1, c, HEADS), jnp.float32))
    vc = attn.QuantKV(jnp.zeros((1, c, e), jnp.int8),
                      jnp.zeros((1, c, HEADS), jnp.float32))
    for t in range(total):
        kc = attn.cache_append(kc, jnp.asarray(ks[:, t:t + 1]), t,
                               num_heads=HEADS)
        vc = attn.cache_append(vc, jnp.asarray(vs[:, t:t + 1]), t,
                               num_heads=HEADS)
        out = attn.sdpa_decode(jnp.asarray(qs[:, t:t + 1]), kc, vc, t + 1,
                               num_heads=HEADS)
        # reference: dense attention over the DEQUANTIZED live window
        lo = max(0, t + 1 - c)
        kd = np.asarray(attn.dequantize_kv(kc, HEADS))
        vd = np.asarray(attn.dequantize_kv(vc, HEADS))
        win_k = np.stack([kd[0, p % c] for p in range(lo, t + 1)])[None]
        win_v = np.stack([vd[0, p % c] for p in range(lo, t + 1)])[None]
        ref = attn.sdpa(jnp.asarray(qs[:, t:t + 1]), jnp.asarray(win_k),
                        jnp.asarray(win_v), num_heads=HEADS)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6,
                                   err_msg="wrap mismatch at t=%d" % t)


def _cache_attention_dequantized_first(q, k, v, total_len, num_heads, kvh):
    """Cache attention as it was before QuantKV scales were folded in:
    the caches are float (B, C, E) buffers (``dequantize_kv`` came
    first), one einsum per product.  The reference for
    ``test_quantized_attend_folds_scales``, and the jaxpr a float cache
    must still trace."""
    b, tq, e = q.shape
    c, ev, hd, g = k.shape[1], v.shape[2], e // num_heads, num_heads // kvh
    heads, hx = ((num_heads,), "h") if g == 1 else ((kvh, g), "hg")
    ones = (1,) * len(heads)
    qh = q.reshape((b, tq) + heads + (hd,))
    kh = k.reshape(b, c, kvh, hd)
    vh = v.reshape(b, c, kvh, ev // kvh)
    logits = jnp.einsum("bq%sd,bkhd->b%sqk" % (hx, hx), qh,
                        kh).astype(jnp.float32) * (1.0 / np.sqrt(hd))
    total = jnp.asarray(total_len, jnp.int32).reshape((-1, 1, 1) + ones)
    qpos = jnp.arange(tq, dtype=jnp.int32).reshape((1,) + ones + (tq, 1))
    limit = jnp.minimum(total - (tq - 1) + qpos, c)
    slot = jnp.arange(c, dtype=jnp.int32).reshape((1, 1) + ones + (c,))
    logits = jnp.where(slot < limit, logits, jnp.finfo(jnp.float32).min)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("b%sqk,bkhe->bq%se" % (hx, hx), p.astype(vh.dtype), vh)
    return out.reshape(b, tq, num_heads * (ev // kvh))


def _eqn_outputs(jaxpr):
    """Every intermediate a jaxpr computes, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield from eqn.outvars
        for sub in eqn.params.values():
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield from _eqn_outputs(sub)


@pytest.mark.parametrize("q_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("window", ["decode", "verify", "wrapped"])
@pytest.mark.parametrize("kvh", [8, 2], ids=["ungrouped", "grouped4"])
@pytest.mark.parametrize("kv_dtype", sorted(_KV_TOLS))
def test_quantized_attend_folds_scales(kv_dtype, kvh, window, q_dtype):
    """``_sdpa_cache`` attends a QuantKV cache as it is stored — the
    narrow plane into the products, the per-(token, kv-head) scales onto
    the float32 logits and probabilities — and equals attention over
    ``dequantize_kv``'s output: a single query row (block-diagonal
    products), the verify window (per-head einsums) and a wrapped ring,
    ungrouped and grouped.  With a bfloat16 query it is no further from
    the float32 answer than dequantizing first, and builds no float32
    array of the cache's size; a float cache traces the jaxpr it always
    did."""
    heads, hd, c = 8, 32, 32
    tq, total = {"decode": (1, [9, 20]), "verify": (4, [12, 32]),
                 "wrapped": (1, [c + 5, c + 17])}[window]
    rng = np.random.RandomState(40)
    q = jnp.asarray(rng.normal(size=(B, tq, heads * hd)), q_dtype)
    kc, vc = (attn.quantize_kv(
        jnp.asarray(rng.normal(size=(B, c, kvh * hd)), jnp.float32),
        kv_dtype, kvh) for _ in range(2))
    total = jnp.asarray(total, jnp.int32)

    def attend(q_, k_, v_):
        return attn._sdpa_cache(q_, k_, v_, total, heads, None,
                                num_kv_heads=kvh)

    out = attend(q, kc, vc)
    # the sums inside are float32; rows of many positions leave in the
    # queries' type, one row a slot as it is summed (attn._out_dtype)
    assert out.dtype == (jnp.dtype(q_dtype) if tq > 1 else jnp.float32)
    kf, vf = attn.dequantize_kv(kc, kvh), attn.dequantize_kv(vc, kvh)
    ref = np.asarray(_cache_attention_dequantized_first(
        q.astype(jnp.float32), kf, vf, total, heads, kvh))
    # one side quantized, the other a float buffer in the query's dtype:
    # each goes by its own type (a bfloat16 V makes a bfloat16 output)
    tol = dict(rtol=1e-5, atol=1e-6) if q_dtype == "float32" \
        else dict(rtol=2e-2, atol=5e-2)
    kq, vq = kf.astype(q_dtype), vf.astype(q_dtype)
    for (k_, v_), (k32, v32) in (((kc, vq), (kf, vq.astype(jnp.float32))),
                                 ((kq, vc), (kq.astype(jnp.float32), vf))):
        np.testing.assert_allclose(
            np.asarray(attend(q, k_, v_).astype(jnp.float32)),
            np.asarray(_cache_attention_dequantized_first(
                q.astype(jnp.float32), k32, v32, total, heads, kvh)), **tol)
    if q_dtype == "float32":
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5,
                                   atol=1e-6)
    else:
        # dequantize-first in the query's dtype: what one MXU pass makes
        # of float32 operands
        first = _cache_attention_dequantized_first(q, kq, vq, total, heads,
                                                   kvh)
        err_first = np.abs(np.asarray(first.astype(jnp.float32)) - ref).max()
        # (half a bfloat16 step of the largest output: the one rounding of
        # the float32 sums on the way out)
        assert np.abs(np.asarray(out.astype(jnp.float32)) - ref).max() \
            <= err_first + 2.0 ** -9 * np.abs(ref).max()
        big = B * c * kvh * hd
        made = [v.aval for v in _eqn_outputs(
            jax.make_jaxpr(attend)(q, kc, vc).jaxpr)]
        assert made and not [a for a in made if a.dtype == jnp.float32
                             and a.size >= big], made
    # a float cache: the same jaxpr as ever
    before = jax.make_jaxpr(
        lambda q_, k_, v_: _cache_attention_dequantized_first(
            q_, k_, v_, total, heads, kvh))(q, kq, vq)
    assert str(jax.make_jaxpr(attend)(q, kq, vq)) == str(before)


def test_quantize_dequantize_roundtrip_error_bound():
    """Per-(token, head) scales bound the int8 roundtrip error by
    amax_head / 127 per element."""
    rng = np.random.RandomState(25)
    x = rng.normal(size=(2, 5, EMBED)).astype(np.float32) * 3.0
    q = attn.quantize_kv(jnp.asarray(x), jnp.int8, num_heads=HEADS)
    back = np.asarray(attn.dequantize_kv(q, HEADS))
    amax = np.abs(x.reshape(2, 5, HEADS, -1)).max(-1, keepdims=True)
    bound = np.broadcast_to(amax / 127.0 * 0.5 + 1e-6,
                            x.reshape(2, 5, HEADS, -1).shape)
    assert (np.abs(back.reshape(2, 5, HEADS, -1)
                   - x.reshape(2, 5, HEADS, -1)) <= bound).all()


# ---------------------------------------------------------------------------
# Serving-loop speculation (PR 6): equality, EOS mid-window, accounting.
# ---------------------------------------------------------------------------

def test_spec_quant_server_matches_plain_generation():
    """The speculative server over quantized caches returns EXACTLY what
    single-sequence greedy generation (same quantized predictor) returns
    for every prompt — slot reuse, mixed lengths and all."""
    sym, params = _lm_and_params()
    rng = np.random.RandomState(26)
    prompts = [rng.randint(0, VOCAB, (n,)) for n in (5, 7, 4, 6, 5)]
    max_new = 5
    qpred = DecodePredictor(sym, params, cache_len=T, kv_dtype="int8")
    refs = [qpred.generate(p[None].astype(np.float32), p.size,
                           max_new_tokens=max_new, seed=0)[0]
            for p in prompts]
    server = DecodeServer(qpred, max_prefill=T, slots=2,
                          max_new_tokens=max_new, spec_k=3)
    ids = [server.submit(p) for p in prompts]
    results = server.run()
    for rid, ref in zip(ids, refs):
        np.testing.assert_array_equal(results[rid], ref)
    assert server.spec_steps > 0
    assert server.proposed == 3 * server.spec_steps * 2 or \
        server.proposed > 0      # slots may idle on the last drain
    assert 0.0 <= server.accept_rate <= 1.0
    # the verify program traced exactly once across the whole serve
    assert qpred.trace_counts["verify"] == 1


def test_draft_catch_up_keeps_self_draft_acceptance_perfect():
    """Draft == target: with a COMPLETE draft cache every window fully
    accepts (accept_rate exactly 1).  A draft that misses committed
    K/V — the k-th token of a fully-accepted window, or fallback-era
    tokens — diverges from the target and breaks perfection, so this
    pins the DraftProposer teacher-forced catch-up."""
    sym, params = _lm_and_params()
    rng = np.random.RandomState(30)
    pred = DecodePredictor(sym, params, cache_len=4 * T)
    draft = DecodePredictor(sym, params, cache_len=4 * T)
    prompts = [rng.randint(0, VOCAB, (n,)) for n in (5, 7, 6, 4)]
    refs = [pred.generate(p[None].astype(np.float32), p.size,
                          max_new_tokens=20, seed=0)[0] for p in prompts]
    server = DecodeServer(pred, max_prefill=T, slots=2,
                          max_new_tokens=20, spec_k=3, draft=draft)
    ids = [server.submit(p) for p in prompts]
    results = server.run()
    for rid, ref in zip(ids, refs):
        np.testing.assert_array_equal(results[rid], ref)
    assert server.spec_steps > 0
    assert server.accept_rate == 1.0, server.accept_rate


def test_server_eos_retirement_mid_speculation_window():
    """EOS emitted MID-window: the request retires with the window's
    later tokens discarded, the freed slot serves the next request, and
    token accounting counts only delivered tokens."""
    sym, params = _lm_and_params()
    rng = np.random.RandomState(27)
    pred = DecodePredictor(sym, params, cache_len=T)
    prompt = rng.randint(0, VOCAB, (6,))
    # greedy continuation: pick as "EOS" the first token that differs
    # from the prefill's, so it is emitted inside a k=4 speculation
    # window (not at admission) and the window's tail must be discarded
    ref = pred.generate(prompt[None].astype(np.float32), 6,
                        max_new_tokens=8)[0]
    eos = next(int(ref[i]) for i in range(1, len(ref))
               if ref[i] != ref[0])
    ref_len = int(np.flatnonzero(ref == eos)[0]) + 1
    server = DecodeServer(pred, max_prefill=T, slots=1, eos_id=eos,
                          max_new_tokens=64, spec_k=4)
    ids = [server.submit(prompt) for _ in range(3)]
    results = server.run()
    for rid in ids:
        np.testing.assert_array_equal(results[rid], ref[:ref_len])
        assert results[rid][-1] == eos
    assert server.tokens_out == 3 * ref_len
    assert server.spec_steps > 0


def test_sample_tokens_greedy_bypass_is_key_independent():
    """Satellite: temperature=0 AND top_k=1 both take the pure-argmax
    path — bit-identical across PRNG keys (no fold-in on the hot
    path)."""
    logits = jnp.asarray(np.log([[0.05, 0.1, 0.4, 0.3, 0.15]] * 3,
                                dtype=np.float32))
    outs = set()
    for s in range(5):
        key = jax.random.PRNGKey(s)
        outs.add(tuple(np.asarray(sample_tokens(key, logits,
                                                temperature=0.0))))
        outs.add(tuple(np.asarray(sample_tokens(key, logits,
                                                temperature=0.7,
                                                top_k=1))))
    assert outs == {(2, 2, 2)}


# ---------------------------------------------------------------------------
# A graph with none of the attributes PR 35 added (window, sink, rotary,
# value scale, gated experts) traces the programs it traced before them
# ---------------------------------------------------------------------------

# sha256[:16] of ``str(jax.make_jaxpr(program)(avals))``, taken on the commit
# before PR 35 (b2cc690) with the very code below, under this directory's
# conftest (the text of a jaxpr depends on jax's configuration).  A
# PR that means to change one of these programs replaces its hash, and says
# so; one that does not has changed what every accepted serving cell runs.
# PR 42 replaced the three paged int8 programs' (a node's pools keep their
# scales in one plane, read and written by rows); the dense prefill and the
# float pools' programs are the ones of b2cc690.  PR 47 replaced the two chunk
# programs' (the head on the one row that is read, behind a conditional, and
# one more small operand); decode, verify and prefill are as they were.
PLAIN_PROGRAMS = {
    "mha_int8": {"chunk": "a3e1ef33a85058fc", "decode": "ae3f5eda93fe8a7a",
                 "verify": "844c2c86a9ae31ae", "prefill": "88b686fb4db16476"},
    "gqa_float": {"chunk": "10aae20472856a52", "decode": "e11356bce6bbbc2d",
                  "verify": "88735220e64c4515", "prefill": "ecb1cf4999e7e7a2"},
}
PLAIN_OPS = {"attn_mha": "cf554ea5f46f3f2f", "attn_gqa": "46ba4ccd9ef3b64f",
             "moe_dense": "19b10ae9c8469cd3", "moe_sparse": "d710ac04554bceab"}


def _jaxpr_hash(fn, avals):
    import hashlib

    return hashlib.sha256(
        str(jax.make_jaxpr(fn)(*avals)).encode()).hexdigest()[:16]


def _plain_predictors(kv_dtype, num_kv_heads):
    sym = attention_lm.get_symbol(vocab_size=50, seq_len=32, num_layers=2,
                                  embed=32, heads=4, ffn_hidden=64,
                                  num_kv_heads=num_kv_heads)
    arg_shapes, _, _ = sym.infer_shape(data=(1, 32), softmax_label=(1, 32))
    rng = np.random.RandomState(0)
    params = {n: mx.nd.array(rng.normal(size=s).astype(np.float32) * 0.05)
              for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in ("data", "softmax_label")}
    paged = DecodePredictor(sym, params, cache_len=32, paged=True,
                            page_tokens=4, kv_dtype=kv_dtype,
                            prefill_chunk=8)
    dense = DecodePredictor(sym, params, cache_len=32, paged=False,
                            kv_dtype=kv_dtype)
    return paged, dense


@pytest.mark.parametrize("name,kv_dtype,num_kv_heads", [
    ("mha_int8", "int8", 0), ("gqa_float", "", 2)])
def test_plain_graph_traces_the_serving_programs_it_traced_before(
        name, kv_dtype, num_kv_heads):
    from mxnet_tpu.programs.spec import probing

    paged, dense = _plain_predictors(kv_dtype, num_kv_heads)
    avals = paged.serving_avals(4, chunk_w=8, spec_k=3)
    assert sorted(avals) == ["chunk", "commit", "decode", "extract", "fork",
                             "install", "verify"]
    fns = paged._aot_dispatches()
    got = {}
    with probing(paged):
        for kind in ("chunk", "decode", "verify"):
            got[kind] = _jaxpr_hash(fns[kind], avals[kind])
    with probing(dense):
        got["prefill"] = _jaxpr_hash(dense._prefill_impl,
                                     dense._prefill_args(2, 16))
    assert got == PLAIN_PROGRAMS[name]


def test_plain_graph_builds_one_group_with_todays_page_count():
    from mxnet_tpu.serve import PagedKVManager

    paged, dense = _plain_predictors("int8", 0)
    assert [(g.kind, g.capacity, g.nodes) for g in paged._groups] == [
        ("full", 32, (0, 1))]
    assert not paged.has_window_group and not dense.has_window_group
    state = paged.paged_batch_state(4)
    mgr = paged._manager
    assert type(mgr) is PagedKVManager and mgr.groups == [mgr]
    assert mgr.pool_pages == 4 * (32 // 4) + 1
    assert mgr.prefix_cache is not None
    assert mgr.tables.shape == (4, 8)
    assert state.moe is None and len(jax.tree_util.tree_leaves(state)) \
        == 2 * 3 + 2        # 2 nodes x (k data, v data, the scales of both)
    for (kc, vc) in state.caches:
        # one scale plane a node, a page a row: page_tokens x (k, v) x heads
        assert kc.data.shape == vc.data.shape == (33, 4, 32)
        assert kc.scale.shape == (33, 4 * 2 * 4) and vc.scale is None
    assert isinstance(paged._tables_of(mgr), jax.Array)
    assert paged.pool_bytes() == 2 * 2 * (33 * 4 * 32 + 33 * 4 * 4 * 4)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


@pytest.mark.parametrize("kind", ["decode", "chunk", "verify"])
def test_no_serving_program_moves_a_whole_scale_plane(kind):
    """A count, not a time: nothing in a paged int8 program reshapes,
    transposes, slices or copies an array as large as a node's scale plane.
    A reshape of a POOL is what lets XLA:TPU's layout assignment carry a
    consumer's layout back through the gather and convert the whole plane in
    every program (PERF.md, PR 42): the plane is only gathered from, by
    rows, and scattered into, by rows."""
    from mxnet_tpu.obs.scopes import instruction_map
    from mxnet_tpu.programs.spec import probing

    paged, _ = _plain_predictors("int8", 0)
    avals = paged.serving_avals(4, chunk_w=8, spec_k=3)[kind]
    plane = avals[1].caches[0][0].scale if kind != "chunk" \
        else avals[1][0][0].scale
    assert plane.shape == (33, 32)
    count = int(np.prod(plane.shape))
    fn = paged._aot_dispatches()[kind].fn
    with probing(paged):
        jaxpr = jax.make_jaxpr(fn)(*avals)
        text = fn.lower(*avals).compile().as_text()
    # as traced: the plane goes into gathers, scatters and the loops and
    # calls that hold them, and nowhere else
    takes = {"gather", "scatter", "while", "pjit", "jit", "cond",
             "custom_jvp_call", "closed_call"}
    for eqn in _eqns(jaxpr.jaxpr):
        for v in eqn.invars:
            shape = getattr(getattr(v, "aval", None), "shape", None)
            if shape == plane.shape and v.aval.dtype == plane.dtype:
                assert eqn.primitive.name in takes, eqn
    # as compiled (here for the CPU): no instruction that only moves data
    # gives out an array of the plane's size
    _, rows = instruction_map(text)
    moved = [(name, r["opcode"], r["shape"]) for name, r in rows.items()
             if r["moves"] and r["shape"].startswith("f32[")
             and "(" not in r["shape"]
             and int(np.prod([int(d) for d in r["shape"][4:].split("]")[0]
                              .split(",") if d])) == count]
    assert not moved, moved


def test_plain_ops_trace_the_training_jaxprs_they_traced_before():
    """Forward and backward of the two ops PR 35 extended, called with none
    of the new attributes: what the training steps are made of."""
    from mxnet_tpu.registry import OpContext, get_op

    def grad_hash(op, attrs, shapes):
        f = lambda *xs: op.fcompute(attrs, list(xs), [], OpContext())[0][0]
        g = lambda *xs: jax.grad(lambda *ys: f(*ys).sum(),
                                 argnums=tuple(range(len(shapes))))(*xs)
        return _jaxpr_hash(g, [jax.ShapeDtypeStruct(s, jnp.float32)
                               for s in shapes])

    got = {}
    op = get_op("dot_product_attention")
    for name, kw, shapes in (
            ("mha", dict(num_heads=4, causal=True), [(2, 16, 32)] * 3),
            ("gqa", dict(num_heads=4, num_kv_heads=2, causal=True),
             [(2, 16, 32), (2, 16, 16), (2, 16, 16)])):
        got["attn_" + name] = grad_hash(op, op.parse_attrs(kw), shapes)
    op = get_op("MoEFFN")
    for name, kw in (("dense", {}),
                     ("sparse", dict(capacity_factor=1.5,
                                     num_experts_per_tok=2))):
        got["moe_" + name] = grad_hash(
            op, op.parse_attrs(dict(num_experts=4, hidden_size=10, **kw)),
            [(12, 6), (6, 4), (4, 6, 10), (4, 10), (4, 10, 6), (4, 6)])
    assert got == PLAIN_OPS
