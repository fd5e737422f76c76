"""The comparison that decides ``correct``: the system's outputs against the
plain reference on the same seeded weights, on the chip, outside the window.

Tolerances are on log-probabilities (a softmax's log is the logits up to the
row's normalising constant, which both sides compute).  The system computes
in bfloat16 (8 bits of mantissa: each rounding is off by up to 2**-9
relative) and the reference in float32 at ``highest`` precision, so the
difference is bf16's rounding accumulated through the depth of the model.
The limits are about three times what the chip read on seeded weights when
they were set (my chip runs, PR 23: ResNet-50 0.043 to 0.047 over 3 seeds,
OPT at the training depth 0.033, OPT at full depth through the int8 pool
0.030 to 0.034 over 5 seeds): wide enough for another seed, and far under
what a wrong mask, a dropped layer, a stale cache page or arithmetic in
fewer bits gives (the CPU tests show those move log-probabilities by 0.3 to
several units).
"""
from __future__ import annotations

import importlib

import numpy as np

# max |log p_system - log p_reference| over the compared positions
LOGP_ATOL = {
    # ResNet-50, bf16 activations through 53 convolutions, batch statistics
    "resnet": 0.15,
    # OPT logits through the training depth, bf16 activations
    "decoder_lm": 0.1,
    # OPT at full depth through int8 keys and values (each stored value off
    # by up to 1/254 of its head's largest) and bf16 probabilities
    "decoder_lm.int8_kv": 0.1,
}
LOSS_RTOL = 0.01


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


def compare_loss(system_probs, ref_logits, labels):
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
                       abs(got - want) <= LOSS_RTOL * abs(want)),
            "loss": got, "ref_loss": want, "rtol": LOSS_RTOL}
