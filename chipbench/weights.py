"""Seeded random weights, made on the device in one jitted call, in the type
they are used in.  A configuration's ``init`` list says how: the first rule
whose ``match`` (a regex, searched in the parameter's name) fits decides the
distribution; ``tie`` copies one parameter's values into another.
"""
from __future__ import annotations

import math
import re

from .traffic import device_key


def _fan_in(shape):
    return int(math.prod(shape[1:])) if len(shape) > 1 else int(shape[0])


def _rule_for(name, rules):
    for rule in rules:
        if re.search(rule["match"], name):
            return rule
    raise KeyError("no init rule matches parameter %r" % name)


def make_params(shapes, cfg, seed, dtype):
    """``{name: array}`` for every ``{name: shape}``, seeded, of ``dtype``,
    on the default device; one compiled program makes them all."""
    import jax
    import jax.numpy as jnp

    names = sorted(shapes)
    rules = cfg["init"]
    tie = cfg.get("tie", {})

    # the key is an argument, not a constant: one compiled program serves
    # every seed, so a new seed finds it in the compile cache
    @jax.jit
    def make(key):
        live = [n for n in names if n not in tie]
        plan = [(n, tuple(shapes[n]), _rule_for(n, rules)) for n in live]
        size = lambda shape: int(math.prod(shape))
        # one draw of each law for the whole tree, cut into the leaves: a
        # draw per leaf compiles for minutes at 400 leaves
        kn, ku = jax.random.split(key)
        n_normal = sum(size(s) for _, s, r in plan
                       if r["dist"] in ("normal", "he_normal"))
        n_uniform = sum(size(s) for _, s, r in plan
                        if r["dist"] == "uniform")
        normal = jax.random.normal(kn, (max(n_normal, 1),), jnp.float32)
        uniform = jax.random.uniform(ku, (max(n_uniform, 1),), jnp.float32)
        out, at_n, at_u = {}, 0, 0
        for name, shape, rule in plan:
            dist, n = rule["dist"], size(shape)
            if dist == "const":
                x = jnp.full(shape, rule["value"], jnp.float32)
            elif dist in ("normal", "he_normal"):
                z = jax.lax.dynamic_slice(normal, (at_n,), (n,)).reshape(
                    shape)
                at_n += n
                x = math.sqrt(2.0 / _fan_in(shape)) * z \
                    if dist == "he_normal" \
                    else rule.get("mean", 0.0) + rule["std"] * z
            elif dist == "uniform":
                u = jax.lax.dynamic_slice(uniform, (at_u,), (n,)).reshape(
                    shape)
                at_u += n
                x = rule["low"] + (rule["high"] - rule["low"]) * u
            else:
                raise ValueError("unknown dist %r" % dist)
            out[name] = x.astype(dtype)
        for dst, src in tie.items():
            out[dst] = out[src].reshape(shapes[dst])
        return out

    return make(device_key(seed, 3))
