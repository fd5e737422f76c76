"""Decoder-only attention language model — the TPU-era LM family.

No reference analog (its LM story is the unrolled/fused LSTM,
example/rnn): this is the leapfrog model built from the framework's
attention primitives.  Pre-norm transformer blocks with causal
multi-head attention (``dot_product_attention``), optionally
mixture-of-experts FFNs (``MoEFFN``).  Composes with every mesh axis:
batch on 'data', time on 'seq' (bind with layout-'NT' DataDescs),
projection weights on 'model', expert stacks on 'expert'.
"""
from __future__ import annotations

import numpy as np

from .. import obs as _obs
from .. import symbol as sym
from ..base import AttrScope
from ..obs.scopes import LAYER_ATTR

# the element-wise nodes LayerNorm is built from would read as "other":
# name their layer for the device-time breakdown (obs.scopes)
_NORM = {LAYER_ATTR: "norm"}
_HEAD = {LAYER_ATTR: "head_loss"}


def layer_norm(data, embed, name):
    """LayerNorm over the last axis, built from registry ops (mean/var
    through broadcast arithmetic; gamma/beta as 1-wide FC is avoided — the
    scale/shift ride as learnable broadcast params via elementwise ops)."""
    gamma = sym.Variable(name + "_ln_gamma", shape=(1, 1, embed))
    beta = sym.Variable(name + "_ln_beta", shape=(1, 1, embed))
    with AttrScope(**_NORM):
        mean = sym.mean(data, axis=-1, keepdims=True)
        centered = sym.broadcast_sub(data, mean)
        var = sym.mean(sym.square(centered), axis=-1, keepdims=True)
        inv = sym.rsqrt(var + 1e-5)
        normed = sym.broadcast_mul(centered, inv)
        return sym.broadcast_add(sym.broadcast_mul(normed, gamma), beta)


def block(data, embed, heads, ffn_hidden, name, moe_experts=0,
          moe_capacity_factor=0.0, moe_top_k=1, num_kv_heads=0):
    """One pre-norm decoder block.

    ``num_kv_heads`` < ``heads`` emits grouped-query attention: the K/V
    projections are physically ``num_kv_heads * head_dim`` wide (same
    ``_k``/``_v`` param names — a GQA checkpoint loads by name with the
    grouped shapes) and the attention op maps each q-head to kv group
    ``h // G``.  0 (default) keeps the MHA graph byte-identical."""
    kv_heads = int(num_kv_heads) or heads
    if heads % kv_heads:
        raise ValueError(
            "attention_lm.block: num_heads=%d not divisible by "
            "num_kv_heads=%d" % (heads, kv_heads))
    kv_hidden = kv_heads * (embed // heads)
    attn_in = layer_norm(data, embed, name + "_att")
    q = sym.FullyConnected(attn_in, num_hidden=embed, flatten=False,
                           name=name + "_q")
    k = sym.FullyConnected(attn_in, num_hidden=kv_hidden, flatten=False,
                           name=name + "_k")
    v = sym.FullyConnected(attn_in, num_hidden=kv_hidden, flatten=False,
                           name=name + "_v")
    if kv_heads != heads:
        att = sym.dot_product_attention(q, k, v, num_heads=heads,
                                        num_kv_heads=kv_heads, causal=True)
    else:
        att = sym.dot_product_attention(q, k, v, num_heads=heads,
                                        causal=True)
    att = sym.FullyConnected(att, num_hidden=embed, flatten=False,
                             name=name + "_attout")
    data = data + att

    ffn_in = layer_norm(data, embed, name + "_ffn")
    if moe_experts > 0:
        # MoEFFN routes tokens over the trailing axis; (B, T, E) in/out.
        # capacity_factor > 0 arms the sparse capacity-slot dispatch
        # (the explicit all-to-all program under an 'expert' mesh);
        # moe_top_k routes each token to its k best experts.
        ffn = sym.MoEFFN(ffn_in, num_experts=moe_experts,
                         hidden_size=ffn_hidden,
                         capacity_factor=moe_capacity_factor,
                         num_experts_per_tok=moe_top_k,
                         name=name + "_moe")
    else:
        h = sym.FullyConnected(ffn_in, num_hidden=ffn_hidden, flatten=False,
                               name=name + "_ffn1")
        h = sym.Activation(h, act_type="relu")
        ffn = sym.FullyConnected(h, num_hidden=embed, flatten=False,
                                 name=name + "_ffn2")
    return data + ffn


@_obs.phased("build.symbol")
def get_symbol(vocab_size, seq_len, num_layers=2, embed=128, heads=4,
               ffn_hidden=512, moe_experts=0, moe_capacity_factor=0.0,
               moe_top_k=1, num_kv_heads=0, **kwargs):
    """Decoder-only LM: data (B, T) int tokens, softmax over vocab at every
    position; labels (B, T) next tokens (pad = -1 ignored).

    ``num_kv_heads`` (0 = ``heads``) emits grouped-query K/V projections
    G = heads/num_kv_heads times narrower; the G=1 graph is unchanged."""
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    net = sym.Embedding(data, input_dim=vocab_size, output_dim=embed,
                        name="embed")
    # learned positional embedding, broadcast over the batch
    pos = sym.Variable("pos_embed_weight", shape=(1, seq_len, embed))
    net = sym.broadcast_add(net, pos)
    for i in range(num_layers):
        net = block(net, embed, heads, ffn_hidden, "layer%d" % i,
                    moe_experts=moe_experts,
                    moe_capacity_factor=moe_capacity_factor,
                    moe_top_k=moe_top_k, num_kv_heads=num_kv_heads)
    net = layer_norm(net, embed, "final")
    with AttrScope(**_HEAD):
        logits = sym.FullyConnected(sym.Reshape(net, shape=(-1, embed)),
                                    num_hidden=vocab_size, name="head")
        flat_label = sym.Reshape(label, shape=(-1,))
        return sym.SoftmaxOutput(logits, flat_label, use_ignore=True,
                                 ignore_label=-1, name="softmax")
