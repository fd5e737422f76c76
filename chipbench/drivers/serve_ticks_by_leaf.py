"""``serve_ticks_by_leaf``: the ``serve_ticks`` loop for a model whose
weights do not fit one float32 draw.

``weights.make_params`` draws the whole tree's normals as one float32 array
and cuts it into the leaves: at 4.5 G parameters that array is 18 GB on a
chip of 16, and longer than an int32 index reaches.  Here each leaf is drawn
by itself, in float32, and cast to the serving type before the next is made
(one compiled program for each distinct shape and rule, the key an argument),
by the configuration's same ``init`` rules; the widest transient is one
leaf.  The window, its fences, the traffic, the timing and the comparison
with the reference are ``serve_ticks``' own code: this module only puts its
way of making weights in that module's place while a run lasts.

``control_case`` hands the control its weights on the host: the control
rounds a second copy of the tree, and two copies do not fit the chip beside
each other.  Rounding 4.5 G values there takes about a minute.
"""
from __future__ import annotations

import contextlib
import math
import types

from .. import weights
from ..traffic import device_key
from . import serve_ticks


def _draw(shape, rule, dtype):
    import jax
    import jax.numpy as jnp

    dist = rule["dist"]

    def make(key):
        if dist == "const":
            x = jnp.full(shape, rule["value"], jnp.float32)
        elif dist in ("normal", "he_normal"):
            z = jax.random.normal(key, shape, jnp.float32)
            x = math.sqrt(2.0 / weights._fan_in(shape)) * z \
                if dist == "he_normal" \
                else rule.get("mean", 0.0) + rule["std"] * z
        elif dist == "uniform":
            x = rule["low"] + (rule["high"] - rule["low"]) \
                * jax.random.uniform(key, shape, jnp.float32)
        else:
            raise ValueError("unknown dist %r" % dist)
        return x.astype(dtype)

    return jax.jit(make)


def make_params(shapes, cfg, seed, dtype):
    """``{name: array}`` as ``weights.make_params`` gives, a leaf at a
    time: the same rules, another stream of draws."""
    import jax

    tie = cfg.get("tie", {})
    key = device_key(seed, 3)
    programs, out = {}, {}
    for i, name in enumerate(sorted(shapes)):
        if name in tie:
            continue
        shape = tuple(shapes[name])
        rule = weights._rule_for(name, cfg["init"])
        sig = (shape, tuple(sorted(rule.items())))
        if sig not in programs:
            programs[sig] = _draw(shape, rule, dtype)
        out[name] = programs[sig](jax.random.fold_in(key, i))
    for dst, src in tie.items():
        out[dst] = out[src].reshape(shapes[dst])
    return out


@contextlib.contextmanager
def _by_leaf():
    before = serve_ticks.weights
    serve_ticks.weights = types.SimpleNamespace(make_params=make_params)
    try:
        yield
    finally:
        serve_ticks.weights = before


def run(job):
    with _by_leaf():
        return serve_ticks.run(job)


def control_case(cfg, traffic, seed):
    import jax

    with _by_leaf():
        case = serve_ticks.control_case(cfg, traffic, seed)
    case["params"] = jax.device_get(case["params"])
    return case
