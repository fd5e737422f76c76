"""The one general traffic generator: a traffic file's parameters and a seed
in, the inputs of a run out.  The program under test receives only what this
makes; a new mix is a new ``traffic/<name>.json`` and no code.

Two shapes of traffic, chosen by the file's ``driver`` key:

``train_fit``    a closed loop of fixed-shape batches.  ``batch`` samples a
                 step; images of ``image_shape`` or ``seq_len`` tokens.
                 ``pool`` distinct batches, made on the device, are cycled.
``serve_ticks``  a backlog of requests queued before the first tick.  Prompt
                 and output lengths are log-uniform between their bounds.

Every seed offers the same work: the lengths are the fixed quantile points
of the log-uniform law, dealt in blocks of ``block`` requests in an order the
traffic file fixes (``order_seed``), and ``--seed`` draws the token ids.  A
window sees only the first few dozen requests of a backlog, so even another
order of the same sizes would be other work: when the first slots retire
decides how many ticks carry a prefill chunk (PERF.md, Findings, PR 23).
"""
from __future__ import annotations

import math

import numpy as np


def rng_of(seed, stream=0):
    """A numpy generator from any non-negative whole ``seed`` (the driver's
    are above 2**31) and a stream number."""
    return np.random.default_rng([int(seed), int(stream)])


def log_uniform_points(lo, hi, n):
    """``n`` lengths at the mid-quantiles of the log-uniform law on
    [lo, hi], rounded to whole tokens."""
    q = (np.arange(n) + 0.5) / n
    return np.rint(np.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))
                   ).astype(np.int64)


def backlog(traffic, vocab, seed):
    """``[(prompt_tokens int64[len], output_len), ...]`` — the whole queue of
    a ``serve_ticks`` run, ``traffic["requests"]`` long."""
    n, block = int(traffic["requests"]), int(traffic["block"])
    if n % block:
        raise ValueError("requests %d is not a multiple of block %d"
                         % (n, block))
    prompts = log_uniform_points(traffic["prompt_min"],
                                 traffic["prompt_max"], block)
    outputs = log_uniform_points(traffic["output_min"],
                                 traffic["output_max"], block)
    order, rng = rng_of(traffic["order_seed"], 1), rng_of(seed, 1)
    # prompt and output lengths are independent: pair them by two
    # permutations of the same quantile points in every block
    out = []
    for _ in range(n // block):
        pi, oi = order.permutation(block), order.permutation(block)
        for a, b in zip(pi, oi):
            out.append((rng.integers(0, vocab, size=int(prompts[a]),
                                     dtype=np.int64), int(outputs[b])))
    return out


def train_batches(traffic, cfg, seed):
    """A jitted maker of the ``pool`` synthetic batches of a ``train_fit``
    run, on the default device, from the seed: ``make() -> [(data, label),
    ...]`` as float32 arrays in the layout ``Module.fit`` binds."""
    import jax
    import jax.numpy as jnp

    batch, pool = int(traffic["batch"]), int(traffic.get("pool", 2))

    def one(k):
        kd, kl = jax.random.split(k)
        if "seq_len" in traffic:
            t = int(traffic["seq_len"])
            toks = jax.random.randint(kd, (batch, t + 1), 0,
                                      cfg["vocab_size"])
            # next-token labels; every position has a real target
            return (toks[:, :-1].astype(jnp.float32),
                    toks[:, 1:].astype(jnp.float32))
        shape = (batch,) + tuple(cfg["image_shape"])
        return (jax.random.uniform(kd, shape, jnp.float32, -1.0, 1.0),
                jax.random.randint(kl, (batch,), 0, cfg["num_classes"]
                                   ).astype(jnp.float32))

    @jax.jit
    def make(key):
        return [one(k) for k in jax.random.split(key, pool)]

    # the key is an argument, not a constant: one compiled program serves
    # every seed, so a new seed finds it in the compile cache
    return lambda: make(device_key(seed, 2))


def device_key(seed, stream):
    """A jax PRNG key from any non-negative whole seed, without x64: the
    seed is folded in as two 31-bit halves."""
    import jax

    key = jax.random.key(int(stream), impl="rbg")
    seed = int(seed)
    return jax.random.fold_in(jax.random.fold_in(key, seed & 0x7FFFFFFF),
                              seed >> 31)
