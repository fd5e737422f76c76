"""ResNet-50 as the reference's ``train_imagenet.py --network resnet
--num-layers 50`` builds it: the pre-activation bottleneck network of He et
al., "Identity Mappings in Deep Residual Networks" (arXiv:1603.05027), units
(3, 4, 6, 3), filters (64, 256, 512, 1024, 2048), stride on the 3x3.

    x = BN_fixed_gamma(image); conv 7x7/2; BN; ReLU; maxpool 3x3/2
    unit: a = ReLU(BN(x)); y = conv1x1(a); y = conv3x3/s(ReLU(BN(y)));
          y = conv1x1(ReLU(BN(y))); x = y + (x if same shape else conv1x1/s(a))
    ReLU(BN(x)); global average pool; fully connected; softmax

BatchNorm epsilon 2e-5; ``training=False`` uses the stored moving statistics,
``training=True`` the batch's own (biased variance).  The first BatchNorm's
gamma is fixed at 1.  NCHW images, OIHW kernels.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

BN_EPS = 2e-5


def _bn(x, p, name, training, fix_gamma=False):
    if training:
        mean = jnp.mean(x, axis=(0, 2, 3))
        var = jnp.mean(jnp.square(x - mean[None, :, None, None]),
                       axis=(0, 2, 3))
    else:
        mean, var = p[name + "_moving_mean"], p[name + "_moving_var"]
    gamma = jnp.ones_like(mean) if fix_gamma else p[name + "_gamma"]
    scale = gamma / jnp.sqrt(var + BN_EPS)
    return (x - mean[None, :, None, None]) * scale[None, :, None, None] \
        + p[name + "_beta"][None, :, None, None]


def _conv(x, w, stride, pad):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def forward(params, cfg, images, layers=None, training=False):
    """Logits ``(B, classes)`` of ``images (B, 3, H, W)``; float32."""
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    x = jnp.asarray(images, jnp.float32)
    relu = jax.nn.relu
    with jax.default_matmul_precision("highest"):
        x = _bn(x, p, "bn_data", training, fix_gamma=True)
        x = _conv(x, p["conv0_weight"], 2, 3)
        x = relu(_bn(x, p, "bn0", training))
        x = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
            [(0, 0), (0, 0), (1, 1), (1, 1)])
        for stage, n in enumerate(cfg["units"]):
            for unit in range(n):
                name = "stage%d_unit%d_" % (stage + 1, unit + 1)
                stride = 2 if (unit == 0 and stage > 0) else 1
                a = relu(_bn(x, p, name + "bn1", training))
                y = _conv(a, p[name + "conv1_weight"], 1, 0)
                y = relu(_bn(y, p, name + "bn2", training))
                y = _conv(y, p[name + "conv2_weight"], stride, 1)
                y = relu(_bn(y, p, name + "bn3", training))
                y = _conv(y, p[name + "conv3_weight"], 1, 0)
                if unit == 0:
                    x = _conv(a, p[name + "sc_weight"], stride, 0)
                x = x + y
        x = relu(_bn(x, p, "bn1", training))
        x = jnp.mean(x, axis=(2, 3))
        return x @ p["fc1_weight"].T + p["fc1_bias"]


def loss(params, cfg, images, labels, layers=None, training=False):
    """Mean softmax cross-entropy."""
    logp = jax.nn.log_softmax(
        forward(params, cfg, images, layers, training), axis=-1)
    labels = jnp.asarray(labels, jnp.int32)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], -1))
