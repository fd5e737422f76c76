"""``serve_ticks_mtp``: the ``serve_ticks`` loop for a model that drafts for
itself with its own multi-token-prediction block.

The window, its fences, the traffic and the timing are ``serve_ticks``' own
code: a gap is still the time between a slot's deliveries, one tick, weighted
by the slots active, and tokens per second count the tokens that reached
requests, one or two a slot a tick.  This module puts three things in that
module's place while a run lasts: the weights are drawn a leaf at a time
(``serve_ticks_by_leaf``: 6.2 G parameters do not fit one float32 draw), the
server samples at the traffic file's ``temperature`` and drafts ``spec_k``
tokens a tick (``DecodeServer(spec_k=1)`` over a graph with a prediction
block), and the comparison with the reference reads both of the tick's
distributions.

The comparison, by the programs that were timed and at their sizes: the
check prompt in chunks, then ``check_decode`` self-drafting ticks, and
against one ``forward`` and one ``forward_mtp`` of the plain reference over
the sequence that was committed (a) the stack's log-probabilities at every
committed position, on ticks that accepted their draft and on ticks that
rejected it (a tick's second row counts where its draft was accepted: else
it conditions on a token that is not in the sequence), and (b) the block's
log-probabilities at every position a draft was drawn from; each under its
own limit, ``limits.serve_ticks_mtp`` of the configuration.
"""
from __future__ import annotations

import contextlib
import types

import jax
import jax.numpy as jnp
import numpy as np

from mxnet_tpu.decode import DecodePredictor, DecodeServer

from .. import correct, harness, traffic as traffic_mod
from . import serve_ticks
from .serve_ticks_by_leaf import make_params

NAME = "serve_ticks_mtp"


def build_server(sym, traffic, params, ctx):
    pred = DecodePredictor(
        sym, params, cache_len=int(traffic["cache_len"]), ctx=ctx,
        temperature=float(traffic["temperature"]), paged=True,
        page_tokens=int(traffic["page_tokens"]),
        kv_dtype=traffic["kv_dtype"],
        prefill_chunk=int(traffic["prefill_chunk"]))
    server = DecodeServer(pred, max_prefill=int(traffic["max_prefill"]),
                          slots=int(traffic["slots"]),
                          spec_k=int(traffic["spec_k"]))
    return pred, server


def weight_shapes(sym, cfg):
    if "mtp_data" not in sym.list_arguments():
        # a program whose builder knows no prediction block fails here, at
        # once, before a weight is drawn
        raise RuntimeError(
            "%s built no multi-token-prediction block (no input mtp_data): "
            "this driver serves a graph that drafts for itself"
            % cfg["builder"])
    t = (1, int(cfg["max_position_embeddings"]))
    free = {"data": t, "softmax_label": t, "mtp_data": t, "mtp_label": t}
    arg_shapes, _, _ = sym.infer_shape(**free)
    return {n: s for n, s in zip(sym.list_arguments(), arg_shapes)
            if n not in free}


def reference_rows(cfg, plen):
    """``both(params, seq)``: the reference's two sets of logits over
    ``seq`` from one pass over the stack (one program to compile), the
    stack's and the block's, from position ``plen - 1`` on: the prompt's
    last row is the first the comparison reads of either."""
    ref = correct.reference_of(cfg)
    since = int(plen) - 1

    def both(p, x):
        main, block = ref.forward_both(p, cfg, x, since=since)
        return main[0], block[0]

    return jax.jit(both)


def control_case(cfg, traffic, seed):
    """For ``chipbench.control``: the seeded weights (on the host: the
    control rounds a second copy of the tree), ``forward(params)`` as this
    driver's comparison calls the reference over seeded tokens (the stack's
    rows, then the block's), and the type the cell computes in."""
    params = jax.device_get(make_params(
        weight_shapes(harness.build_symbol(cfg), cfg), cfg, seed,
        cfg["serve_dtype"]))
    plen = int(traffic["check_prompt"])
    n = plen + 2 * int(traffic["check_decode"])
    seq = traffic_mod.rng_of(seed, 4).integers(0, cfg["vocab_size"],
                                               size=(1, n))
    both = reference_rows(cfg, plen)
    return {"params": params, "dtype": cfg["serve_dtype"],
            "forward": lambda p: jnp.concatenate(both(p, seq), 0)}


def system_rows(pred, cfg, traffic, seed, drafts=None):
    """The check prompt through the chunk program and ``check_decode``
    self-drafting ticks through the tick's: ``(seq, main, block, accepted)``
    — the committed sequence, ``[(position, probs (V,))]`` of the stack at
    every committed position and of the block at every position a draft was
    drawn from, and each tick's count of accepted drafts.  ``drafts(tick,
    state)`` may replace a tick's state before it runs (a test forces a
    rejection so)."""
    slots, steps = int(traffic["slots"]), int(traffic["check_decode"])
    plen = int(traffic["check_prompt"])
    rng = traffic_mod.rng_of(seed, 4)
    prompt = rng.integers(0, cfg["vocab_size"], size=plen)
    # one real row; the other rows of the serving batch get one token each
    toks = np.zeros((slots, plen), np.float32)
    toks[0] = prompt
    toks[1:, 0] = rng.integers(0, cfg["vocab_size"], size=slots - 1)
    lens = np.ones(slots, np.int64)
    lens[0] = plen
    key = traffic_mod.device_key(seed, 5)
    state, probs, block = pred.mtp_prefill(toks, lens, key)
    seq = list(prompt) + [int(np.asarray(state.tok)[0, 0])]
    main_rows = [(plen - 1, probs[0])]
    block_rows = [(plen - 1, block[0])]
    accepted = []
    for tick in range(steps):
        if drafts is not None:
            state = drafts(tick, state)
        at = len(seq) - 1           # the position of the slot's last token
        state, out, counts, probs3, block = pred.mtp_step(
            state, jax.random.fold_in(key, tick))
        n = int(np.asarray(counts)[0])
        seq += [int(t) for t in np.asarray(out)[0, :n]]
        main_rows += [(at + j, probs3[0, j]) for j in range(n)]
        block_rows.append((at + n - 1, block[0]))
        accepted.append(n - 1)
    del state
    return np.asarray(seq), main_rows, block_rows, accepted


STATISTIC = "row_rms_median"


def compare_rows(system_probs, ref_logits, limit):
    """System probabilities ``(N, V)`` against reference logits ``(N, V)``:
    ``ok`` by the median over the N rows of a row's root-mean-square
    difference in log-probability over the vocabulary (``STATISTIC``),
    beside the other readings of the same difference.  A rounding that
    flips one of a token's chosen experts moves that row by a whole expert's
    part, and the largest of 5 M differences then says how unlucky the
    worst row was; a mechanism at fault moves every row, which the median
    row shows and the maximum hides (``PERF.md`` section 6, PR 46)."""
    got = correct.logp_of_probs(system_probs)
    want = jax.nn.log_softmax(jnp.asarray(ref_logits, jnp.float32), axis=-1)
    diff = got - want
    row_rms = jnp.sqrt(jnp.mean(diff * diff, axis=-1))
    out = {"max_abs_dlogp": float(jnp.max(jnp.abs(diff))),
           "row_rms_median": float(jnp.median(row_rms)),
           "row_rms_mean": float(jnp.mean(row_rms)),
           "row_rms_max": float(jnp.max(row_rms)),
           "positions": int(got.shape[0]), "limit": limit,
           "statistic": STATISTIC}
    out["ok"] = bool(np.isfinite(out["max_abs_dlogp"])
                     and out[STATISTIC] <= limit)
    return out


def check_against_reference(pred, cfg, traffic, params, seed, atol,
                            drafts=None):
    seq, main_rows, block_rows, accepted = system_rows(pred, cfg, traffic,
                                                       seed, drafts)
    plen = int(traffic["check_prompt"])
    draft_atol = correct.limit(cfg, NAME,
                               "draft_logp_atol." + traffic["kv_dtype"])
    # the block's row at a position needs the token after it: the last
    # committed token has none, and no draft row lies there
    want_main, want_block = reference_rows(cfg, plen)(params, seq[None, :])
    take = lambda want, rows: jnp.stack(
        [want[pos - (plen - 1)] for pos, _ in rows])
    stack = lambda rows: jnp.stack([p for _, p in rows])
    return [
        dict(compare_rows(stack(main_rows), take(want_main, main_rows),
                          atol),
             what="stack", ticks_accepted=int(sum(accepted)),
             ticks_rejected=int(len(accepted) - sum(accepted))),
        dict(compare_rows(stack(block_rows), take(want_block, block_rows),
                          draft_atol), what="block")]


@contextlib.contextmanager
def _in_place():
    before = (serve_ticks.weights, serve_ticks.build_server,
              serve_ticks.weight_shapes, serve_ticks.check_against_reference)
    serve_ticks.weights = types.SimpleNamespace(make_params=make_params)
    serve_ticks.build_server = build_server
    serve_ticks.weight_shapes = weight_shapes
    serve_ticks.check_against_reference = check_against_reference
    try:
        yield
    finally:
        (serve_ticks.weights, serve_ticks.build_server,
         serve_ticks.weight_shapes,
         serve_ticks.check_against_reference) = before


def run(job):
    from mxnet_tpu import obs

    with _in_place():
        result = serve_ticks.run(job)
    # the whole process's drafts (the filling of the slots and the
    # comparison included; the window's own are mtp_accept_pct's)
    counted = {n: obs.registry.get(n).get()
               for n in ("mx_spec_proposed", "mx_spec_accepted")}
    result["side"]["spec_process"] = counted
    print("self-drafting, whole process: %s" % counted, flush=True)
    return result
