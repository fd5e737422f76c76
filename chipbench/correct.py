"""The comparison that decides ``correct``: the system's outputs against the
plain reference on the same seeded weights, on the chip, outside the window.

Tolerances are on log-probabilities (a softmax's log is the logits up to the
row's normalising constant, which both sides compute).  The system computes
in bfloat16 (8 bits of mantissa: each rounding is off by up to 2**-9
relative) and the reference in float32 at ``highest`` precision, so the
difference is bf16's rounding accumulated through the depth of the model.

The limits themselves are the configuration's: its file states each under
``limits``, by the loop driver that makes the comparison, as ``{"value",
"why"}`` with the readings it was set from.  Here are only the comparison
functions; a configuration that states no limit has none (``limit`` raises,
``manifest.validate`` reports it), never a default.
"""
from __future__ import annotations

import importlib

import numpy as np


def limit(cfg, driver, name):
    """The limit ``name`` that ``cfg`` states for ``driver``'s comparison."""
    try:
        return float(cfg["limits"][driver][name]["value"])
    except KeyError:
        raise KeyError("the configuration states no limit %r for driver %r "
                       "under its 'limits' key" % (name, driver)) from None


def reference_of(cfg):
    return importlib.import_module(cfg["reference"])


def logp_of_probs(probs):
    import jax.numpy as jnp

    return jnp.log(jnp.maximum(jnp.asarray(probs, jnp.float32), 1e-30))


def compare_logp(system_probs, ref_logits, atol):
    """``{"ok", "max_abs_dlogp", "atol", "positions"}`` for system
    probabilities ``(N, V)`` against reference logits ``(N, V)``."""
    import jax
    import jax.numpy as jnp

    got = logp_of_probs(system_probs)
    want = jax.nn.log_softmax(jnp.asarray(ref_logits, jnp.float32), axis=-1)
    worst = float(jnp.max(jnp.abs(got - want)))
    return {"ok": bool(np.isfinite(worst) and worst <= atol),
            "max_abs_dlogp": worst, "atol": atol,
            "positions": int(got.shape[0])}


def compare_loss(system_probs, ref_logits, labels, rtol):
    import jax
    import jax.numpy as jnp

    labels = jnp.asarray(labels, jnp.int32).reshape(-1, 1)
    got = -jnp.mean(jnp.take_along_axis(logp_of_probs(system_probs),
                                        labels, -1))
    want = -jnp.mean(jnp.take_along_axis(
        jax.nn.log_softmax(jnp.asarray(ref_logits, jnp.float32), -1),
        labels, -1))
    got, want = float(got), float(want)
    return {"ok": bool(np.isfinite(got) and
                       abs(got - want) <= rtol * abs(want)),
            "loss": got, "ref_loss": want, "rtol": rtol}
