"""Operations and bytes a cell's work needs, computed from its sizes.

Used for utilisation metrics only; kept with the benchmark so that no later
PR can count differently.  Counts are of the mathematics the model requires:
recomputation, padding and optimizer arithmetic are not work.

A configuration file names its own counting functions under ``counts``
(``"module:function"``, found as ``builder`` is): ``train_flops_per_sample``
and ``decode_step_bytes`` below only look them up, so a model of a new
kind (sparse experts: active FLOPs, expert-weight reads) brings its counts
in a file of its own and the readers need no edit.
"""
from __future__ import annotations

from .manifest import load_named


def resnet_fwd_flops(cfg):
    """Multiply-adds x 2 of one image's forward pass through the bottleneck
    ResNet of ``cfg`` (convolutions and the classifier; BatchNorm, ReLU and
    pooling are not counted)."""
    c, h, w = cfg["image_shape"]
    units, filters = cfg["units"], cfg["filter_list"]
    h, w = (h + 1) // 2, (w + 1) // 2                 # conv0 7x7 stride 2
    macs = 49 * c * filters[0] * h * w
    h, w = (h + 1) // 2, (w + 1) // 2                 # 3x3 max pool stride 2
    cin = filters[0]
    for stage, (n, cout) in enumerate(zip(units, filters[1:])):
        mid = cout // 4
        for unit in range(n):
            stride = 2 if (unit == 0 and stage > 0) else 1
            macs += cin * mid * h * w                  # 1x1 before the stride
            ho, wo = (h + stride - 1) // stride, (w + stride - 1) // stride
            macs += 9 * mid * mid * ho * wo            # 3x3 carries the stride
            macs += mid * cout * ho * wo               # 1x1
            if unit == 0:
                macs += cin * cout * ho * wo           # projection shortcut
            h, w, cin = ho, wo, cout
    macs += cin * cfg["num_classes"]
    return 2 * macs


def lm_fwd_flops_per_token(cfg, layers, context):
    """Forward FLOPs of one token of a dense pre-LN decoder at sequence
    length ``context`` with causal attention: the projections, the two
    attention matmuls over the causal half of the context, the feed-forward
    pair and the output head.  The embedding lookup is a gather."""
    d, f, v = cfg["hidden_size"], cfg["ffn_dim"], cfg["vocab_size"]
    per_layer = 2 * (4 * d * d + 2 * d * f)            # q k v out, ffn1 ffn2
    per_layer += 2 * 2 * d * (context + 1) / 2.0       # QK^T and PV, causal
    return layers * per_layer + 2 * d * v


def resnet_train_flops_per_sample(cfg, traffic):
    """Forward + backward FLOPs of one image (backward = 2 x forward)."""
    return 3 * resnet_fwd_flops(cfg)


def dense_lm_train_flops_per_sample(cfg, traffic):
    """Forward + backward FLOPs of one sequence of ``seq_len`` tokens
    through a dense decoder (backward = 2 x forward)."""
    t = traffic["seq_len"]
    layers = cfg[traffic.get("layers_key", "num_hidden_layers")]
    return 3 * t * lm_fwd_flops_per_token(cfg, layers, t)


def count_of(cfg, what):
    """The counting function ``cfg`` names for ``what``."""
    try:
        return load_named(cfg["counts"][what])
    except KeyError:
        raise KeyError("the configuration names no count %r under its "
                       "'counts' key" % what) from None


def train_flops_per_sample(cfg, traffic):
    """Forward + backward FLOPs of one training sample, by the function the
    configuration names."""
    return count_of(cfg, "train_flops_per_sample")(cfg, traffic)


def lm_weight_bytes(cfg, layers, bytes_per_param):
    """Bytes of the weights one decode step must read: every layer's
    matrices and the output head (the embedding table is gathered by row)."""
    d, f, v = cfg["hidden_size"], cfg["ffn_dim"], cfg["vocab_size"]
    return bytes_per_param * (layers * (4 * d * d + 2 * d * f) + d * v)


def kv_bytes_per_token(cfg, layers, kv_bytes, scale_bytes=4):
    """Bytes of cached keys and values one context token holds: 2 x layers x
    hidden at ``kv_bytes`` each, plus one scale per (token, head) for a
    quantised pool."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    per = 2 * layers * d * kv_bytes
    if kv_bytes < 2:
        per += 2 * layers * heads * scale_bytes
    return per


def decode_step_bytes(cfg, traffic, live_tokens):
    """Bytes one decode tick must read from HBM, by the function the
    configuration names."""
    return count_of(cfg, "decode_step_bytes")(cfg, traffic, live_tokens)


def dense_lm_decode_step_bytes(cfg, traffic, live_tokens):
    """Bytes one decode tick of a dense decoder must read from HBM: the
    weights once, and the keys and values of every live context token."""
    layers = cfg["num_hidden_layers"]
    kv = 1 if traffic.get("kv_dtype") == "int8" else 2
    return lm_weight_bytes(cfg, layers, 2) + \
        live_tokens * kv_bytes_per_token(cfg, layers, kv)
