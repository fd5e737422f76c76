"""A decoder-only language model built from the keys of a published
``config.json``: pre-norm residual blocks of RMSNorm, rotary attention whose
kind (full or sliding-window) is chosen a layer, and a gated MLP or a routed
mixture of gated experts chosen a layer.

``models.attention_lm`` builds one fixed block (LayerNorm, learned
positions, ReLU MLP); this builder reads what varies between published
decoders off arguments named as their configurations name them, so a new
model of the family is a configuration file and no code:

* ``hybrid_layer_pattern[l]``: 0 = full causal attention, 1 = a sliding
  window of ``sliding_window`` positions; each kind has its own KV heads
  (``num_key_value_heads`` / ``swa_num_key_value_heads``), rotary base
  (``rope_theta`` / ``swa_rope_theta``) and learned sink
  (``add_full_attention_sink_bias`` / ``add_swa_attention_sink_bias``);
* ``head_dim`` / ``v_head_dim``: the q/k and the value head widths;
  ``partial_rotary_factor``: the share of ``head_dim`` that is rotated
  (rounded down to an even number of dims);
* ``moe_layer_freq[l]``: 0 = a dense gated MLP of ``intermediate_size``,
  1 = ``n_routed_experts`` gated experts of ``moe_intermediate_size``,
  ``num_experts_per_tok`` a token, scored by ``scoring_func`` with a
  selection-only bias (``topk_method`` ``noaux_tc``);
* ``num_held`` / ``first_held``: the experts this chip holds of every MoE
  layer (expert parallelism's share; 0 = all): the router keeps all its
  outputs, the layer adds its own experts' part.

The first ``num_layers`` entries of the two per-layer lists are built.
"""
from __future__ import annotations

from .. import symbol as sym
from ..base import AttrScope
from ..obs.scopes import LAYER_ATTR

# what the device-time breakdown files each node under (obs.scopes): window
# attention apart from full, the gate's activation with its matrices
_WINDOW = {LAYER_ATTR: "attn_window"}
_MLP = {LAYER_ATTR: "linear"}
_HEAD = {LAYER_ATTR: "head_loss"}


def rotary_dims(head_dim, partial_rotary_factor):
    """Dims of each head that rotate: the factor's share of ``head_dim``,
    rounded down to an even number."""
    return int(head_dim * float(partial_rotary_factor)) // 2 * 2


def attention(data, name, window, hidden, heads, kv_heads, head_dim,
              v_head_dim, rotary_dim, theta, value_scale, sink):
    q = sym.FullyConnected(data, num_hidden=heads * head_dim, no_bias=True,
                           flatten=False, name=name + "_q")
    k = sym.FullyConnected(data, num_hidden=kv_heads * head_dim,
                           no_bias=True, flatten=False, name=name + "_k")
    v = sym.FullyConnected(data, num_hidden=kv_heads * v_head_dim,
                           no_bias=True, flatten=False, name=name + "_v")
    with AttrScope(**(_WINDOW if window else {})):
        att = sym.dot_product_attention(
            q, k, v, num_heads=heads, num_kv_heads=kv_heads, causal=True,
            window=window, sink=sink, rotary_dim=rotary_dim,
            rope_theta=theta, value_scale=value_scale, name=name + "_att")
    return sym.FullyConnected(att, num_hidden=hidden, no_bias=True,
                              flatten=False, name=name + "_attout")


def gated_mlp(data, name, hidden, width):
    gate = sym.FullyConnected(data, num_hidden=width, no_bias=True,
                              flatten=False, name=name + "_ffn_gate")
    up = sym.FullyConnected(data, num_hidden=width, no_bias=True,
                            flatten=False, name=name + "_ffn_up")
    with AttrScope(**_MLP):
        h = sym.Activation(gate, act_type="silu") * up
    return sym.FullyConnected(h, num_hidden=hidden, no_bias=True,
                              flatten=False, name=name + "_ffn_down")


def get_symbol(vocab_size, hidden_size, num_layers, num_attention_heads,
               head_dim, hybrid_layer_pattern, moe_layer_freq,
               intermediate_size, num_key_value_heads=0, v_head_dim=0,
               swa_num_key_value_heads=0, sliding_window=0,
               partial_rotary_factor=1.0, rope_theta=10000.0,
               swa_rope_theta=0.0, attention_value_scale=1.0,
               add_full_attention_sink_bias=False,
               add_swa_attention_sink_bias=False, layernorm_epsilon=1e-5,
               moe_intermediate_size=0, n_routed_experts=0,
               num_experts_per_tok=1, scoring_func="softmax",
               norm_topk_prob=True, topk_method="greedy", num_held=0,
               first_held=0, **kwargs):
    """data (B, T) int tokens -> softmax over the vocabulary at every
    position (``softmax_label`` (B, T) next tokens, pad = -1 ignored)."""
    heads = int(num_attention_heads)
    v_head_dim = int(v_head_dim) or int(head_dim)
    rotary = rotary_dims(head_dim, partial_rotary_factor)
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    net = sym.Embedding(data, input_dim=vocab_size, output_dim=hidden_size,
                        name="embed")
    for i in range(int(num_layers)):
        name = "layer%d" % i
        windowed = bool(hybrid_layer_pattern[i])
        normed = sym.RMSNorm(net, eps=layernorm_epsilon,
                             name=name + "_att_norm")
        net = net + attention(
            normed, name,
            window=int(sliding_window) if windowed else 0,
            hidden=hidden_size, heads=heads,
            kv_heads=int((swa_num_key_value_heads if windowed
                          else num_key_value_heads) or heads),
            head_dim=int(head_dim), v_head_dim=v_head_dim,
            rotary_dim=rotary,
            theta=float((swa_rope_theta if windowed else 0.0)
                        or rope_theta),
            value_scale=float(attention_value_scale),
            sink=bool(add_swa_attention_sink_bias if windowed
                      else add_full_attention_sink_bias))
        normed = sym.RMSNorm(net, eps=layernorm_epsilon,
                             name=name + "_ffn_norm")
        if moe_layer_freq[i]:
            ffn = sym.MoEFFN(
                normed, num_experts=int(n_routed_experts),
                hidden_size=int(moe_intermediate_size), gated=True,
                num_experts_per_tok=int(num_experts_per_tok),
                score_func=scoring_func,
                score_bias=topk_method == "noaux_tc",
                norm_topk=bool(norm_topk_prob), num_held=int(num_held),
                first_held=int(first_held), name=name + "_moe")
        else:
            ffn = gated_mlp(normed, name, hidden_size,
                            int(intermediate_size))
        net = net + ffn
    net = sym.RMSNorm(net, eps=layernorm_epsilon, name="final_norm")
    with AttrScope(**_HEAD):
        logits = sym.FullyConnected(
            sym.Reshape(net, shape=(-1, hidden_size)), num_hidden=vocab_size,
            no_bias=True, name="head")
        flat_label = sym.Reshape(label, shape=(-1,))
        return sym.SoftmaxOutput(logits, flat_label, use_ignore=True,
                                 ignore_label=-1, name="softmax")
