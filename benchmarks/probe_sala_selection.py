"""What the comparison that decides ``correct`` in ``sala_serve_longctx``
reads when the SELECTION is at fault, at the cell's own size on the chip.

For each seed (and each standard deviation tried for the sparse layers'
output matrix, ``--attout``; ``--qk`` for their q and k matrices), on the
cell's seeded weights and the tokens the cell's own comparison uses
(``serve_ticks.check_against_reference``: 12,288 prompt tokens in chunks of
2048, then 8 decoded), every reading against the plain float32 reference
over the same 9 rows:

``sound``
    the serving programs as they are (what a run's ``checks:`` prints);
``forced``
    the serving programs with every sparse layer's choice of blocks put to
    the reference's own (its ``chosen_blocks`` for each layer and row, laid
    over ``ops.attention.sparse_block_scores``): what is left is rounding
    alone, so ``sound`` less ``forced`` is what blocks that flip cost
    (decoded from the sound run's tokens, so all 9 rows answer one
    sequence);
``fault_dense``
    the reference with ``dense_len`` past every context (a program that
    never selects and attends every position);
``fault_lowest``
    the reference choosing by the NEGATED query (the blocks that score
    lowest: a selection that reads the wrong blocks);
``fault_no_init``
    the reference with ``init_blocks`` 0 (block 0 taken only where it
    scores: ONE block of a row's 64 at fault, as large as one flip in every
    row; the least fault the comparison could be asked to see);
``control``
    the reference with its matrices rounded to ``float8_e4m3fn``
    (``chipbench.control``'s rounding, here over the run's own tokens).

A limit that sees the mechanism lies over every ``sound`` and under every
``fault_*`` and ``control``.  One process, the chip's; one JSON line a
(seed, attout); nothing of the benchmark calls this.

    chiprun -- python3 benchmarks/probe_sala_selection.py \
        --seeds 4300000301,4300000302 --attout 0.1,0.8

``--readings none`` takes the sound reading alone (about a minute a seed).
"""
import argparse
import contextlib
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

import mxnet_tpu as mx
from chipbench import control, correct, harness, manifest
from chipbench import traffic as traffic_mod
from chipbench.drivers import serve_ticks, serve_ticks_by_leaf
from chipbench.reference import minicpm_sala as ref
from mxnet_tpu.decode import DecodePredictor
from mxnet_tpu.ops import attention as attn

CELL = "sala_serve_longctx"


ATTOUT, QK = "_attout_weight$", "layer[0-9]+_(q|k)_weight$"


def with_std(cfg, match, std):
    """``cfg`` with the init rule ``match`` drawn at ``std``."""
    assert any(r["match"] == match for r in cfg["init"]), match
    return dict(cfg, init=[dict(r, std=std) if r["match"] == match else r
                           for r in cfg["init"]])


def system_rows(sym, cfg, traffic, params, seed, ctx, feed=None):
    """``(probabilities (9, V), tokens (1, T), counts)``: what
    ``serve_ticks.check_against_reference`` hands the comparison, from a
    predictor of its own (a new trace: :func:`forcing` acts at trace
    time).  ``feed``: the tokens to decode from, in place of the row's own
    greedy ones (an earlier run's, so that both answer one sequence)."""
    pred = DecodePredictor(
        sym, {n: mx.nd.NDArray(v, ctx) for n, v in params.items()},
        cache_len=int(traffic["cache_len"]), ctx=ctx, temperature=0.0,
        paged=True, page_tokens=int(traffic["page_tokens"]),
        kv_dtype=traffic["kv_dtype"],
        prefill_chunk=int(traffic["prefill_chunk"]))
    slots, steps = int(traffic["slots"]), int(traffic["check_decode"])
    plen = int(traffic["check_prompt"])
    rng = traffic_mod.rng_of(seed, 4)
    prompt = rng.integers(0, cfg["vocab_size"], size=plen)
    toks = np.zeros((slots, plen), np.float32)
    toks[0] = prompt
    toks[1:, 0] = rng.integers(0, cfg["vocab_size"], size=slots - 1)
    lens = np.ones(slots, np.int64)
    lens[0] = plen
    def sampled(state):
        if feed is not None:
            state = state._replace(
                tok=state.tok.at[0, 0].set(int(feed[len(fed)])))
        fed.append(int(np.asarray(state.tok)[0, 0]))
        return state

    state, probs = pred.prefill(toks, lens)
    got, fed = [probs[0]], []
    state = sampled(state)
    for _ in range(steps):
        state, probs = pred.step(state)
        got.append(probs[0])
        state = sampled(state)
    counts = {k: int(v) for k, v in (state.counts or {}).items()}
    del state, pred
    gc.collect()
    return jnp.stack(got), \
        np.concatenate([prompt, np.asarray(fed[:-1])])[None, :], counts


def reference_with_masks(cfg, plen):
    """``fwd(params, seq) -> (rows (9, V), [mask (T, H_kv, blocks) a sparse
    layer])``: the reference's forward pass, and beside it the blocks each
    of its sparse layers chose for every row."""
    inner = ref._sparse_attention

    def fwd(p, x):
        masks = []

        def noting(p_, n, cfg_, u):
            b, t, _ = u.shape
            heads, kvh, hd = (cfg_["num_attention_heads"],
                              cfg_["num_key_value_heads"], cfg_["head_dim"])
            q = (u @ ref._f32(p_[n + "q_weight"]).T).reshape(b, t, heads, hd)
            k = (u @ ref._f32(p_[n + "k_weight"]).T).reshape(b, t, kvh, hd)
            kbar = ref.compressed_keys(k, cfg_)
            pad = -t % ref.QUERY_BLOCK
            qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
            got = jax.lax.map(
                lambda a: ref.chosen_blocks(a[0], kbar, t, cfg_, a[1]),
                (jnp.moveaxis(qp.reshape(b, -1, ref.QUERY_BLOCK, heads, hd),
                              1, 0),
                 jnp.arange(t + pad).reshape(-1, ref.QUERY_BLOCK)))
            # (parts, B, H_kv, Q, blocks) -> (T, H_kv, blocks) of row 0
            masks.append(jnp.moveaxis(got[:, 0], 1, 2).reshape(
                t + pad, kvh, -1)[:t])
            return inner(p_, n, cfg_, u)

        ref._sparse_attention = noting
        try:
            rows = ref.forward(p, cfg, x)[0, plen - 1:]
        finally:
            ref._sparse_attention = inner
        return rows, masks

    return jax.jit(fwd)


def reference_rows(cfg, plen, negate_query=False):
    """As ``serve_ticks.reference_rows``; ``negate_query``: the selection
    (and only it) scores with ``-q``."""
    inner = ref.chosen_blocks

    def fwd(p, x):
        if negate_query:
            ref.chosen_blocks = lambda q, *a: inner(-q, *a)
        try:
            return ref.forward(p, cfg, x)[0, plen - 1:]
        finally:
            ref.chosen_blocks = inner

    return jax.jit(fwd)


@contextlib.contextmanager
def forcing(masks):
    """While it lasts, every program traced chooses, in its i-th sparse
    layer, the blocks ``masks[i]`` (T, H_kv, blocks) gives the row's
    position; a row still under ``dense_len`` and the blocks always taken
    are the program's own (the reference takes the same)."""
    scores, attend = attn.sparse_block_scores, attn.paged_attend_sparse
    at = {"layer": -1}

    def forced_scores(q, kbar, n, spec, blocks, *a, **kw):
        score = scores(q, kbar, n, spec, blocks, *a, **kw)
        m = masks[at["layer"] % len(masks)]
        b, tq = q.shape[:2]
        pos = jnp.clip(jnp.broadcast_to(jnp.asarray(n, jnp.int32).reshape(
            b, -1), (b, tq)) - 1, 0, m.shape[0] - 1)
        want = jnp.moveaxis(m[pos], 2, 1)[..., :blocks]   # (B, H_kv, tq, nb)
        want = jnp.pad(want, ((0, 0),) * 3 + ((0, blocks - want.shape[-1]),))
        return jnp.where(jnp.isinf(score), score, want.astype(jnp.float32))

    def counting(*a, **kw):
        at["layer"] += 1
        return attend(*a, **kw)

    attn.sparse_block_scores, attn.paged_attend_sparse = \
        forced_scores, counting
    try:
        yield
    finally:
        attn.sparse_block_scores, attn.paged_attend_sparse = scores, attend


def reading(got_probs, want_rows):
    """``(max, [max a row])`` of ``|log p - log p_ref|``."""
    d = jnp.abs(correct.logp_of_probs(got_probs)
                - jax.nn.log_softmax(want_rows.astype(jnp.float32), -1))
    rows = [round(float(v), 4) for v in jnp.max(d, axis=-1)]
    return max(rows), rows


def probe(cfg, traffic, seed, ctx, fns, readings):
    """One JSON-able dict of the ``readings`` asked for, one seed."""
    sym = harness.build_symbol(cfg)
    params = serve_ticks_by_leaf.make_params(
        serve_ticks.weight_shapes(sym, cfg), cfg, seed, cfg["serve_dtype"])
    jax.block_until_ready(params)
    got, seq, counts = system_rows(sym, cfg, traffic, params, seed, ctx)
    want, masks = fns["masks"](params, seq)
    want = jax.block_until_ready(want)
    out = {"seed": seed, "counts": counts}
    out["sound"], out["sound_rows"] = reading(got, want)
    del got
    soft = lambda rows: jax.nn.softmax(rows.astype(jnp.float32), axis=-1)
    if "forced" in readings:
        with forcing(masks):
            got, seq_f, out["forced_counts"] = system_rows(
                sym, cfg, traffic, params, seed, ctx,
                feed=seq[0, int(traffic["check_prompt"]):].tolist() + [0])
        assert (seq_f == seq).all()
        out["forced"], out["forced_rows"] = reading(got, want)
        del got
    del masks
    for name in ("fault_dense", "fault_lowest", "fault_no_init"):
        if name in readings:
            out[name], out[name + "_rows"] = reading(
                soft(fns[name](params, seq)), want)
    if "control" in readings:
        below = jnp.dtype(control.BELOW[cfg["serve_dtype"]])
        for k in sorted(params):            # in place: two trees do not fit
            if params[k].ndim >= 2:
                params[k] = params[k].astype(below).astype(params[k].dtype)
        out["control"], out["control_rows"] = reading(
            soft(fns["plain"](params, seq)), want)
    return out


def reference_fns(cfg, traffic):
    plen = int(traffic["check_prompt"])
    never = dict(cfg, sparse_config=dict(cfg["sparse_config"],
                                         dense_len=1 << 30))
    no_init = dict(cfg, sparse_config=dict(cfg["sparse_config"],
                                           init_blocks=0))
    return {"masks": reference_with_masks(cfg, plen),
            "fault_no_init": reference_rows(no_init, plen),
            "plain": reference_rows(cfg, plen),
            "fault_dense": reference_rows(never, plen),
            "fault_lowest": reference_rows(cfg, plen, negate_query=True)}


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--seeds", required=True)
    p.add_argument("--attout", default="",
                   help="comma-separated stds of the sparse layers' output "
                        "matrix; empty: the configuration's own")
    p.add_argument("--qk", type=float, default=0.0,
                   help="std of the sparse layers' q and k matrices; 0: "
                        "the configuration's own")
    p.add_argument("--readings",
                   default="forced,fault_dense,fault_lowest,fault_no_init,"
                           "control")
    args = p.parse_args(argv)
    from mxnet_tpu.cache_dirs import arm_compile_cache

    arm_compile_cache()
    cell = manifest.load_cell(CELL)
    cfg, traffic = cell["config"], cell["traffic"]
    ctx = mx.tpu() if jax.devices()[0].platform == "tpu" else mx.cpu()
    fns = reference_fns(cfg, traffic)     # the forward pass reads no init
    if args.qk:
        cfg = with_std(cfg, QK, args.qk)
    own = next(r["std"] for r in cfg["init"] if r["match"] == ATTOUT)
    for std in [float(s) for s in args.attout.split(",") if s] or [own]:
        for seed in [int(s) for s in args.seeds.split(",")]:
            out = probe(with_std(cfg, ATTOUT, std), traffic, seed, ctx, fns,
                        args.readings.split(","))
            print(json.dumps(dict(out, attout_std=std, qk_std=next(
                r["std"] for r in cfg["init"] if r["match"] == QK))),
                flush=True)
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
