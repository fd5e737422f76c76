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
  outputs, the layer adds its own experts' part;
* ``mamba_d_ssm`` > 0: every block is parallel.  A state-space mixer
  (``ops.ssm``: ``mamba_n_heads`` heads of ``mamba_d_head`` channels, state
  ``mamba_d_state``, ``mamba_n_groups`` groups of B and C, a convolution of
  ``mamba_d_conv``, the chunked scan at ``mamba_chunk_size``, its state kept
  in ``ssm_state_dtype``) and the attention read one normed input and are
  summed before the residual.  Its two projections are ``FullyConnected``
  nodes (``mamba_proj_bias``) and only what lies between them is the op;
* the muP multipliers, each a scalar multiply in the graph and none where
  it is 1: ``embedding_multiplier`` on the embedding rows,
  ``lm_head_multiplier`` on the logits, ``attention_in_multiplier`` /
  ``attention_out_multiplier`` and ``ssm_in_multiplier`` /
  ``ssm_out_multiplier`` around the two mixers, ``ssm_multipliers`` on the
  segments z, x, B, C, dt of the mixer's projected stream,
  ``mlp_multipliers`` on the MLP's gate and on its output, and
  ``key_multiplier`` on the keys (folded into the attention node's
  ``scale``, where it is the same product).

* ``mixer_types[l]``: ``"minicpm4"`` = attention that selects its blocks
  (InfLLM-v2: ``sparse_config``'s ``kernel_size``, ``kernel_stride``,
  ``init_blocks``, ``block_size``, ``window_size``, ``topk``, ``dense_len``;
  rotary only where ``attn_use_rope``; a sigmoid gate on its output where
  ``attn_use_output_gate``), ``"lightning-attn"`` = lightning linear
  attention (``ops.linattn``: ``lightning_nh`` heads of
  ``lightning_head_dim``, a matrix state a head; ``qk_norm``,
  ``lightning_use_rope``, ``use_output_norm``, ``use_output_gate``; its
  decay rates scaled by ``1 - l / (total_layers - 1) + 1e-5``), anything
  else the attention ``hybrid_layer_pattern`` says;
* ``first_layer`` / ``total_layers``: the ``num_layers`` layers built are
  the published layers ``first_layer ..``, named and indexed into the
  per-layer lists as such, of a model of ``total_layers`` (0 =
  ``num_layers``);
* MiniCPM's muP: ``scale_depth`` (every residual branch x ``scale_depth /
  sqrt(total_layers)``; 0 = none) and ``dim_model_base`` (the logits /
  ``hidden_size / dim_model_base``; 0 = none); ``scale_emb`` is
  ``embedding_multiplier``.

* ``layer_types[l]`` (``"sliding_attention"`` | ``"full_attention"``) and
  ``mlp_layer_types[l]`` (``"dense"`` | ``"sparse"``): the same two choices
  under the names newer configurations give them (``first_k_dense_replace``
  k likewise: layers k .. take the experts); ``rope_parameters``
  (``{"rope_theta": ...}``) likewise for ``rope_theta``;
* ``attn_qk_norm``: every q and k head is normed by an RMSNorm with one
  learned gain of ``head_dim`` a layer (``_q_norm`` / ``_k_norm``), before
  the rotation; ``full_attn_use_rope`` false: the full-attention layers
  take no positions, the window layers keep their rotary;
* ``n_shared_experts`` / ``routed_scaling_factor``: an always-on gated MLP
  of ``n_shared_experts x moe_intermediate_size`` beside the routed experts
  of every MoE layer (inside the op and its share), and the routed weights
  x the factor after normalising;
* ``num_nextn_predict_layers`` 1: a multi-token-prediction block after the
  stack, every node under the scope ``mtp``.  At position i it reads the
  stack's last hidden state (before the final norm) and the embedding of
  token i + 1 (the variable ``mtp_data``, (B, T)): ``u = W_p [RMSNorm_e(
  Emb[t[i+1]]) ; RMSNorm_h(x_L[i])]``, one block of the expert layers' kind
  with full attention, then the main model's head over ``RMSNorm_m``: the
  distribution of token i + 2 (``mtp_softmax``, labels ``mtp_label``).
  Embedding and head are the main model's own matrices.  The symbol is
  then a group of two outputs, the main softmax first.

* ``kv_lora_rank`` > 0: every attention is multi-head latent attention
  (``ops.attention.LATENT_OP``; DeepSeek-V2's keys).  The query goes through
  a rank of ``q_lora_rank`` with an RMSNorm between (``_q_a``, ``_q_a_norm``,
  ``_q_b``; a ``q_lora_rank`` of 0, one matrix, is refused until a
  configuration brings it) to heads of ``qk_nope_head_dim +
  qk_rope_head_dim``; ``_kv_a`` gives the latent of ``kv_lora_rank``, normed
  (``_kv_a_norm``), and ONE rotary key part of ``qk_rope_head_dim`` for all
  heads; the node holds the up-projection to every head's ``qk_nope_head_dim``
  key dims and ``v_head_dim`` values (``_latt_kv_b_weight``).  Its rotation
  reads ``rope_parameters`` whole: ``rope_type`` "yarn" (``factor``,
  ``beta_fast``, ``beta_slow``, ``original_max_position_embeddings``,
  ``mscale``, ``mscale_all_dim``) and ``llama_4_scaling_beta`` (the query x 1
  + beta ln(1 + floor(p / original_max)));  ``rope_interleave``: rotated
  pairs are adjacent dims (false is refused likewise).

* ``linear_attn_config`` (``num_heads``, ``head_dim``,
  ``short_conv_kernel_size``) with ``gqa_layers``: every layer NOT listed in
  ``gqa_layers`` is Kimi delta attention (``ops.kda``: a delta rule over a
  matrix state a head with a decay a channel, a convolution on q, k and v;
  ``kda_allow_neg_eigval`` true: beta in (0, 2); ``kda_use_full_proj`` false:
  the decay's and the output gate's paths are low-rank pairs of rank
  ``head_dim``; the other value of either is refused by name), the listed ones the attention ``hybrid_layer_pattern``
  says, with an elementwise sigmoid gate on its output where
  ``use_gqa_gate``.  The feed-forward part of such a layer is chosen as any
  other's (``moe_layer_freq`` and its other names);

* ``linear_key_head_dim`` > 0 (with ``linear_num_key_heads``,
  ``linear_num_value_heads``, ``linear_value_head_dim``,
  ``linear_conv_kernel_dim``): the layers ``layer_types`` calls
  ``"linear_attention"`` are Gated DeltaNet (``ops.gdn``: the delta rule
  over a ``linear_key_head_dim`` x ``linear_value_head_dim`` matrix state a
  head with ONE decay a head, a convolution on q, k and v, a silu gate from a
  full matrix; ``linear_allow_neg_eigval`` true: beta in (0, 2); false, and
  value heads that outnumber the key heads, are refused by name), those it
  calls ``"full_attention"`` the attention ``hybrid_layer_pattern`` says.  A
  ``rope_parameters`` whose ``rope_theta`` is null gives no base, so no
  angle: the full-attention layers take no positions, as under
  ``full_attn_use_rope`` false;
* ``norm_after``: OLMo 2's reordered norm, ``x + RMSNorm(f(x))`` in place
  of ``x + f(RMSNorm(x))``, under the same node names (``_att_norm`` /
  ``_ffn_norm``); refused beside the parallel block;
* ``attn_qk_norm`` ``"projection"``: q and k are normed over the WHOLE
  projection, before the heads are cut, a gain of ``heads x head_dim`` each
  (OLMo 2), where ``True`` norms a head;

* ``hybrid_override_pattern`` (Nemotron-H: one letter a layer): every layer
  is ONE sublayer under one norm (``_norm``), ``x + f(RMSNorm(x))``: ``M`` the
  state-space mixer alone (the ``mamba_*`` sizes above; ``mamba_d_ssm`` 0:
  there is no parallel block), ``E`` the routed experts, ``*`` the attention
  ``hybrid_layer_pattern`` says, ``-`` a dense MLP of ``intermediate_size``.
  Any other letter is refused by name;
* ``mlp_hidden_act`` ``"relu2"``: every expert, the shared one and the dense
  MLP are ``relu(x W_u)^2 W_d``, two matrices and no gate (``"silu"``: the
  gated MLP of three); ``moe_shared_expert_intermediate_size``: the shared
  expert's width where it is not ``n_shared_experts x
  moe_intermediate_size``.

``hybrid_layer_pattern`` and ``moe_layer_freq`` default to zeros: full
attention and a dense MLP in every layer.  Entries ``first_layer ..
first_layer + num_layers - 1`` of the per-layer lists are built.
"""
from __future__ import annotations

from .. import obs as _obs
from .. import symbol as sym
from ..base import AttrScope
from ..obs.scopes import LAYER_ATTR

# what the device-time breakdown files each node under (obs.scopes): window
# attention apart from full, the gate's activation with its matrices
_WINDOW = {LAYER_ATTR: "attn_window"}
_SPARSE = {LAYER_ATTR: "attn_sparse"}
_MLP = {LAYER_ATTR: "linear"}
_HEAD = {LAYER_ATTR: "head_loss"}
_MTP = {LAYER_ATTR: "mtp"}
_LATENT = {LAYER_ATTR: "attn_latent"}


def rotary_dims(head_dim, partial_rotary_factor):
    """Dims of each head that rotate: the factor's share of ``head_dim``,
    rounded down to an even number."""
    return int(head_dim * float(partial_rotary_factor)) // 2 * 2


def times(x, multiplier):
    """``x * multiplier`` as a graph op; ``x`` itself where it is 1."""
    return x if float(multiplier) == 1.0 else x * float(multiplier)


def sparse_attrs(sparse_config):
    """A published ``sparse_config`` as the attention node's attributes."""
    names = {"topk": "sparse_topk", "block_size": "sparse_block",
             "kernel_size": "sparse_kernel", "kernel_stride": "sparse_stride",
             "init_blocks": "sparse_init_blocks",
             "window_size": "sparse_window", "dense_len": "sparse_dense_len"}
    return {names[k]: int(v) for k, v in dict(sparse_config).items()
            if k in names}


def attention(data, name, window, hidden, heads, kv_heads, head_dim,
              v_head_dim, rotary_dim, theta, value_scale, sink,
              key_multiplier=1.0, sparse=None, gate=False, qk_norm_eps=0.0,
              layer=None, qk_norm_whole=False):
    q = sym.FullyConnected(data, num_hidden=heads * head_dim, no_bias=True,
                           flatten=False, name=name + "_q")
    k = sym.FullyConnected(data, num_hidden=kv_heads * head_dim,
                           no_bias=True, flatten=False, name=name + "_k")
    if qk_norm_eps and qk_norm_whole:
        # over the whole projection, before the heads are cut
        q, k = (sym.RMSNorm(x, eps=qk_norm_eps,
                            name="%s_%s_norm" % (name, part))
                for x, part in ((q, "q"), (k, "k")))
    elif qk_norm_eps:
        q, k = (sym.Reshape(sym.RMSNorm(
            sym.Reshape(x, shape=(0, 0, -1, head_dim)), eps=qk_norm_eps,
            name="%s_%s_norm" % (name, part)), shape=(0, 0, -1))
            for x, part in ((q, "q"), (k, "k")))
    v = sym.FullyConnected(data, num_hidden=kv_heads * v_head_dim,
                           no_bias=True, flatten=False, name=name + "_v")
    # keys x key_multiplier: the same logits as a scale of multiplier /
    # sqrt(head_dim), and the cached keys keep their own range
    scaled = {} if float(key_multiplier) == 1.0 else {
        "scale": float(key_multiplier) / float(head_dim) ** 0.5}
    scaled.update(sparse_attrs(sparse) if sparse else {})
    with AttrScope(**(layer or (_WINDOW if window else _SPARSE if sparse
                                else {}))):
        att = sym.dot_product_attention(
            q, k, v, num_heads=heads, num_kv_heads=kv_heads, causal=True,
            window=window, sink=sink, rotary_dim=rotary_dim,
            rope_theta=theta, value_scale=value_scale, name=name + "_att",
            **scaled)
        if gate:
            att = att * sym.Activation(sym.FullyConnected(
                data, num_hidden=heads * v_head_dim, no_bias=True,
                flatten=False, name=name + "_gate"), act_type="sigmoid")
    return sym.FullyConnected(att, num_hidden=hidden, no_bias=True,
                              flatten=False, name=name + "_attout")


def latent_rope_attrs(rope_parameters, rope_theta):
    """A published ``rope_parameters`` as the latent-attention node's
    attributes: the base, YaRN's keys where ``rope_type`` says yarn, and the
    query temperature's beta."""
    rp = dict(rope_parameters or {})
    out = {"rope_theta": float(rp.get("rope_theta", rope_theta))}
    span = int(rp.get("original_max_position_embeddings", 0) or 0)
    if rp.get("rope_type", rp.get("type")) == "yarn":
        out.update(rope_type="yarn", rope_factor=float(rp["factor"]),
                   beta_fast=float(rp.get("beta_fast", 32)),
                   beta_slow=float(rp.get("beta_slow", 1)),
                   mscale=float(rp.get("mscale", 1)),
                   mscale_all_dim=float(rp.get("mscale_all_dim", 0)))
    if float(rp.get("llama_4_scaling_beta", 0) or 0):
        out["query_scaling_beta"] = float(rp["llama_4_scaling_beta"])
    if span:
        out["original_max_position_embeddings"] = span
    return out


def latent_attention(data, name, hidden, heads, q_rank, kv_rank, nope, rope,
                     v_head_dim, eps, interleave, rope_attrs, layer=None):
    """Multi-head latent attention: ``hidden`` -> the low-rank query and the
    latent with its rotary key part -> ``ops.attention.LATENT_OP`` ->
    ``hidden``."""
    fc = lambda x, width, part: sym.FullyConnected(
        x, num_hidden=width, no_bias=True, flatten=False, name=name + part)
    if not q_rank or not interleave:
        raise ValueError(
            "latent attention with q_lora_rank %r, rope_interleave %r: a "
            "query of one matrix and half-split rotary pairs are not built "
            "(no configuration here brings them)" % (q_rank, interleave))
    q = fc(sym.RMSNorm(fc(data, q_rank, "_q_a"), eps=eps,
                       name=name + "_q_a_norm"),
           heads * (nope + rope), "_q_b")
    kv = fc(data, kv_rank + rope, "_kv_a")
    with AttrScope(**(layer or _LATENT)):
        latent = sym.slice_axis(kv, axis=2, begin=0, end=kv_rank)
        key_rope = sym.slice_axis(kv, axis=2, begin=kv_rank,
                                  end=kv_rank + rope)
    latent = sym.RMSNorm(latent, eps=eps, name=name + "_kv_a_norm")
    with AttrScope(**(layer or _LATENT)):
        att = sym.LatentAttention(
            q, latent, key_rope, num_heads=heads, qk_nope_head_dim=nope,
            qk_rope_head_dim=rope, v_head_dim=v_head_dim,
            kv_lora_rank=kv_rank, name=name + "_latt", **rope_attrs)
    return fc(att, hidden, "_attout")


def lightning_mixer(data, name, hidden, heads, head_dim, theta, eps,
                    slope_scale, rotary=True, qk_norm=True, output_norm=True,
                    output_gate=True):
    """Lightning linear attention: ``hidden`` -> q, k, v and the gate ->
    ``ops.linattn`` -> ``hidden``."""
    width = heads * head_dim
    streams = [sym.FullyConnected(data, num_hidden=width, no_bias=True,
                                  flatten=False, name=name + "_lin_" + part)
               for part in ("q", "k", "v") + (("gate",) if output_gate
                                              else ())]
    mixed = sym.LightningAttention(
        *streams, num_heads=heads, head_dim=head_dim, rotary=bool(rotary),
        rope_theta=float(theta), qk_norm=bool(qk_norm),
        output_norm=bool(output_norm), output_gate=bool(output_gate),
        slope_scale=float(slope_scale), eps=float(eps), name=name + "_lin")
    return sym.FullyConnected(mixed, num_hidden=hidden, no_bias=True,
                              flatten=False, name=name + "_lin_out")


def kda_mixer(data, name, hidden, heads, head_dim, conv, eps, neg_eigval,
              full_proj=False):
    """Kimi delta attention: ``hidden`` -> q, k, v, the decay's and the
    gate's low-rank pairs and beta -> ``ops.kda`` -> ``hidden``."""
    if full_proj:
        raise ValueError(
            "kda_use_full_proj true: the decay's and the gate's paths are "
            "built as low-rank pairs of rank head_dim (no configuration "
            "here brings full ones)")
    if not neg_eigval:
        raise ValueError(
            "kda_allow_neg_eigval false: ops.kda takes beta = 2 sigmoid(.), "
            "in (0, 2) (no configuration here keeps it in (0, 1))")
    width = heads * head_dim
    fc = lambda x, n, part: sym.FullyConnected(
        x, num_hidden=n, no_bias=True, flatten=False,
        name="%s_kda_%s" % (name, part))
    pair = lambda part: fc(fc(data, head_dim, part + "_a"), width,
                           part + "_b")
    mixed = sym.KimiDeltaAttention(
        fc(data, width, "q"), fc(data, width, "k"), fc(data, width, "v"),
        pair("f"), fc(data, heads, "beta"), pair("g"), num_heads=heads,
        head_dim=head_dim, conv_kernel=int(conv), eps=float(eps),
        name=name + "_kda")
    return fc(mixed, hidden, "out")


def gdn_mixer(data, name, hidden, heads, value_heads, key_dim, value_dim,
              conv, eps, neg_eigval):
    """Gated DeltaNet: ``hidden`` -> q, k, v, the decay and beta a head and
    the gate -> ``ops.gdn`` -> ``hidden``."""
    if int(value_heads or heads) != heads:
        raise ValueError(
            "linear_num_value_heads %r != linear_num_key_heads %d: ops.gdn "
            "gives every key head one value head (no configuration here "
            "groups them)" % (value_heads, heads))
    if not neg_eigval:
        raise ValueError(
            "linear_allow_neg_eigval false: ops.gdn takes beta = 2 "
            "sigmoid(.), in (0, 2) (no configuration here keeps it in (0, "
            "1))")
    fc = lambda x, n, part: sym.FullyConnected(
        x, num_hidden=n, no_bias=True, flatten=False,
        name="%s_gdn_%s" % (name, part))
    mixed = sym.GatedDeltaNet(
        fc(data, heads * key_dim, "q"), fc(data, heads * key_dim, "k"),
        fc(data, heads * value_dim, "v"), fc(data, heads, "a"),
        fc(data, heads, "b"), fc(data, heads * value_dim, "g"),
        num_heads=heads, key_head_dim=key_dim, value_head_dim=value_dim,
        conv_kernel=int(conv), eps=float(eps), name=name + "_gdn")
    return fc(mixed, hidden, "out")


def gated_mlp(data, name, hidden, width, multipliers=(1.0, 1.0)):
    gate = sym.FullyConnected(data, num_hidden=width, no_bias=True,
                              flatten=False, name=name + "_ffn_gate")
    up = sym.FullyConnected(data, num_hidden=width, no_bias=True,
                            flatten=False, name=name + "_ffn_up")
    with AttrScope(**_MLP):
        h = sym.Activation(times(gate, multipliers[0]),
                           act_type="silu") * up
    return times(sym.FullyConnected(h, num_hidden=hidden, no_bias=True,
                                    flatten=False, name=name + "_ffn_down"),
                 multipliers[1])


def relu2_mlp(data, name, hidden, width):
    """``relu(x W_u)^2 W_d``: two matrices, no gate."""
    up = sym.FullyConnected(data, num_hidden=width, no_bias=True,
                            flatten=False, name=name + "_ffn_up")
    with AttrScope(**_MLP):
        h = sym.square(sym.Activation(up, act_type="relu"))
    return sym.FullyConnected(h, num_hidden=hidden, no_bias=True,
                              flatten=False, name=name + "_ffn_down")


def ssm_mixer(data, name, hidden, heads, head_dim, state, groups, conv,
              chunk, eps, proj_bias, state_dtype, multipliers):
    """The state-space mixer: ``hidden`` -> [z | x | B | C | dt] ->
    ``ops.ssm`` -> ``hidden``; ``multipliers`` scale the five segments."""
    d_ssm, bc = heads * head_dim, groups * state
    widths = (d_ssm, d_ssm, bc, bc, heads)
    proj = sym.FullyConnected(data, num_hidden=sum(widths),
                              no_bias=not proj_bias, flatten=False,
                              name=name + "_ssm_in")
    if any(float(m) != 1.0 for m in multipliers):
        parts, at = [], 0
        for width, m in zip(widths, multipliers):
            parts.append(times(sym.slice_axis(proj, axis=2, begin=at,
                                              end=at + width), m))
            at += width
        proj = sym.Concat(*parts, dim=2)
    mixed = sym.SelectiveSSM(
        proj, num_heads=heads, head_dim=head_dim, state_size=state,
        n_groups=groups, conv_kernel=conv, chunk_size=chunk, eps=eps,
        state_dtype=state_dtype,
        name=name + "_ssm")
    return sym.FullyConnected(mixed, num_hidden=hidden,
                              no_bias=not proj_bias, flatten=False,
                              name=name + "_ssm_out")


@_obs.phased("build.symbol")
def get_symbol(vocab_size, hidden_size, num_layers, num_attention_heads,
               head_dim, hybrid_layer_pattern=None, moe_layer_freq=None,
               intermediate_size=0, num_key_value_heads=0, v_head_dim=0,
               swa_num_key_value_heads=0, sliding_window=0,
               partial_rotary_factor=1.0, rope_theta=10000.0,
               swa_rope_theta=0.0, attention_value_scale=1.0,
               add_full_attention_sink_bias=False,
               add_swa_attention_sink_bias=False, layernorm_epsilon=1e-5,
               moe_intermediate_size=0, n_routed_experts=0,
               num_experts_per_tok=1, scoring_func="softmax",
               norm_topk_prob=True, topk_method="greedy", num_held=0,
               first_held=0, mamba_d_ssm=0, mamba_n_heads=0, mamba_d_head=0,
               mamba_d_state=0, mamba_n_groups=1, mamba_d_conv=4,
               mamba_chunk_size=128, mamba_proj_bias=False,
               mamba_conv_bias=True, ssm_state_dtype="float32",
               embedding_multiplier=1.0, lm_head_multiplier=1.0,
               attention_in_multiplier=1.0, attention_out_multiplier=1.0,
               key_multiplier=1.0, ssm_in_multiplier=1.0,
               ssm_out_multiplier=1.0, ssm_multipliers=(1.0,) * 5,
               mlp_multipliers=(1.0, 1.0), mixer_types=None, first_layer=0,
               total_layers=0, sparse_config=None, attn_use_rope=True,
               attn_use_output_gate=False, lightning_nh=0,
               lightning_head_dim=0, lightning_use_rope=True, qk_norm=True,
               use_output_norm=True, use_output_gate=True, scale_depth=0.0,
               dim_model_base=0, layer_types=None, mlp_layer_types=None,
               rope_parameters=None, attn_qk_norm=False,
               full_attn_use_rope=True, n_shared_experts=0,
               routed_scaling_factor=1.0, num_nextn_predict_layers=0,
               q_lora_rank=0, kv_lora_rank=0, qk_nope_head_dim=0,
               qk_rope_head_dim=0, rope_interleave=True,
               first_k_dense_replace=None, linear_attn_config=None,
               gqa_layers=None, use_gqa_gate=False,
               kda_allow_neg_eigval=False, kda_use_full_proj=False,
               linear_num_key_heads=0, linear_num_value_heads=0,
               linear_key_head_dim=0, linear_value_head_dim=0,
               linear_conv_kernel_dim=4, linear_allow_neg_eigval=False,
               norm_after=False, hybrid_override_pattern=None,
               mlp_hidden_act="silu", moe_shared_expert_intermediate_size=0,
               **kwargs):
    """data (B, T) int tokens -> softmax over the vocabulary at every
    position (``softmax_label`` (B, T) next tokens, pad = -1 ignored)."""
    heads = int(num_attention_heads)
    v_head_dim = int(v_head_dim) or int(head_dim)
    rotary = rotary_dims(head_dim, partial_rotary_factor)
    first = int(first_layer)
    total = int(total_layers) or int(num_layers)
    zeros = (0,) * (first + int(num_layers))
    if layer_types:
        hybrid_layer_pattern = tuple(
            int(kind == "sliding_attention") for kind in layer_types)
    if mlp_layer_types:
        moe_layer_freq = tuple(int(kind == "sparse")
                               for kind in mlp_layer_types)
    if first_k_dense_replace is not None and int(n_routed_experts):
        moe_layer_freq = tuple(int(i >= int(first_k_dense_replace))
                               for i in range(len(zeros)))
    if rope_parameters:
        theta = dict(rope_parameters).get("rope_theta", rope_theta)
        if theta is None:
            full_attn_use_rope = False      # no base: no angle
        else:
            rope_theta = theta
    if int(num_nextn_predict_layers) not in (0, 1):
        raise ValueError("num_nextn_predict_layers %r: one multi-token-"
                         "prediction block is built, or none"
                         % (num_nextn_predict_layers,))
    hybrid_layer_pattern = hybrid_layer_pattern or zeros
    moe_layer_freq = moe_layer_freq or zeros
    # the experts' op attributes that only some configurations state: left
    # off the node where they say nothing, so that the others' graphs stand
    moe_more = {}
    if int(n_shared_experts or 0):
        moe_more["n_shared_experts"] = int(n_shared_experts)
    if float(routed_scaling_factor or 1.0) != 1.0:
        moe_more["routed_scaling_factor"] = float(routed_scaling_factor)
    if mlp_hidden_act not in ("silu", "relu2"):
        raise ValueError("mlp_hidden_act %r: the feed-forward parts are "
                         "built gated (silu) or as relu(x W_u)^2 W_d (relu2)"
                         % (mlp_hidden_act,))
    if mlp_hidden_act == "relu2":
        moe_more["expert_act"] = "relu2"
    if int(moe_shared_expert_intermediate_size or 0):
        moe_more["shared_hidden_size"] = int(
            moe_shared_expert_intermediate_size)

    def experts(normed, name):
        return sym.MoEFFN(
            normed, num_experts=int(n_routed_experts),
            hidden_size=int(moe_intermediate_size), gated=True,
            num_experts_per_tok=int(num_experts_per_tok),
            score_func=scoring_func, score_bias=topk_method == "noaux_tc",
            norm_topk=bool(norm_topk_prob), num_held=int(num_held),
            first_held=int(first_held), name=name + "_moe", **moe_more)

    def attend(normed, name, windowed, selects=False, layer=None):
        if int(kv_lora_rank or 0):
            return latent_attention(
                normed, name, hidden_size, heads, int(q_lora_rank or 0),
                int(kv_lora_rank), int(qk_nope_head_dim),
                int(qk_rope_head_dim), v_head_dim, layernorm_epsilon,
                rope_interleave,
                latent_rope_attrs(rope_parameters, rope_theta), layer=layer)
        return attention(
            normed, name, window=int(sliding_window) if windowed else 0,
            hidden=hidden_size, heads=heads,
            kv_heads=int((swa_num_key_value_heads if windowed
                          else num_key_value_heads) or heads),
            head_dim=int(head_dim), v_head_dim=v_head_dim,
            rotary_dim=rotary if (attn_use_rope or not selects) and (
                windowed or full_attn_use_rope) else 0,
            theta=float((swa_rope_theta if windowed else 0.0)
                        or rope_theta),
            value_scale=float(attention_value_scale),
            sink=bool(add_swa_attention_sink_bias if windowed
                      else add_full_attention_sink_bias),
            key_multiplier=key_multiplier,
            sparse=sparse_config if selects else None,
            gate=bool(attn_use_output_gate if selects else use_gqa_gate),
            qk_norm_eps=float(layernorm_epsilon) if attn_qk_norm else 0.0,
            layer=layer, qk_norm_whole=attn_qk_norm == "projection")

    def dense(normed, name):
        if mlp_hidden_act == "relu2":
            return relu2_mlp(normed, name, hidden_size,
                             int(intermediate_size))
        return gated_mlp(normed, name, hidden_size, int(intermediate_size),
                         mlp_multipliers)

    def state_space(normed, name):
        return ssm_mixer(
            normed, name, hidden_size, int(mamba_n_heads), int(mamba_d_head),
            int(mamba_d_state), int(mamba_n_groups), int(mamba_d_conv),
            int(mamba_chunk_size), layernorm_epsilon, bool(mamba_proj_bias),
            ssm_state_dtype, ssm_multipliers)

    # Nemotron-H's letters: the one sublayer a layer is
    alone = {"M": state_space, "E": experts, "-": dense,
             "*": lambda normed, name: attend(normed, name, windowed=False)}
    pattern = str(hybrid_override_pattern or "")
    if pattern and (set(pattern) - set(alone) or mamba_d_ssm or norm_after
                    or len(pattern) < len(zeros)):
        raise ValueError(
            "hybrid_override_pattern %r: %d letters of M, E, * and - are "
            "read, one sublayer a layer under its own norm before it (not "
            "beside mamba_d_ssm's parallel block or norm_after)"
            % (pattern, len(zeros)))
    mixer_types = mixer_types or ("",) * len(zeros)
    if int(linear_key_head_dim or 0):
        mixer_types = tuple(
            "gdn" if kind == "linear_attention" else mixer
            for kind, mixer in zip(layer_types or (), mixer_types))
    if linear_attn_config:
        delta = dict(linear_attn_config)
        attends = tuple(gqa_layers or ())
        mixer_types = tuple(kind if i in attends else "kda"
                            for i, kind in enumerate(mixer_types))
    # MiniCPM's muP: every residual branch, and the logits
    depth = float(scale_depth) / total ** 0.5 if float(scale_depth) else 1.0
    if int(dim_model_base):
        lm_head_multiplier = float(lm_head_multiplier) \
            * int(dim_model_base) / int(hidden_size)
    if mamba_d_ssm and int(mamba_d_ssm) != int(mamba_n_heads) \
            * int(mamba_d_head):
        raise ValueError("mamba_d_ssm %d != mamba_n_heads %d x mamba_d_head "
                         "%d" % (mamba_d_ssm, mamba_n_heads, mamba_d_head))
    if (mamba_d_ssm or "M" in pattern) and not mamba_conv_bias:
        raise ValueError("mamba_conv_bias false: the mixer's convolution is "
                         "built with its bias (ops.ssm)")
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    # with a prediction block the embedding is read twice: one variable,
    # under the name it would have had
    embed_w = {"weight": sym.Variable("embed_weight")} \
        if int(num_nextn_predict_layers) else {}
    net = times(sym.Embedding(data, input_dim=vocab_size,
                              output_dim=hidden_size, name="embed",
                              **embed_w), embedding_multiplier)
    if norm_after and mamba_d_ssm:
        raise ValueError("norm_after beside the parallel block (mamba_d_ssm "
                         "> 0): one norm cannot follow two summed mixers "
                         "(no configuration here brings both)")
    norm = lambda x, name: sym.RMSNorm(x, eps=layernorm_epsilon, name=name)
    # where a sublayer's norm stands: before it (pre-norm), or after it and
    # before the residual sum (OLMo 2)
    before = (lambda x, name: x) if norm_after else norm
    after = norm if norm_after else (lambda x, name: x)
    for i in range(first, first + int(num_layers)):
        name = "layer%d" % i
        if pattern:
            net = net + alone[pattern[i]](norm(net, name + "_norm"), name)
            continue
        windowed = bool(hybrid_layer_pattern[i])
        normed = before(net, name + "_att_norm")
        selects = mixer_types[i] == "minicpm4"
        if mixer_types[i] == "lightning-attn":
            mixed = times(lightning_mixer(
                normed, name, hidden_size, int(lightning_nh),
                int(lightning_head_dim), rope_theta, layernorm_epsilon,
                1.0 - i / max(total - 1, 1) + 1e-5,
                rotary=lightning_use_rope, qk_norm=qk_norm,
                output_norm=use_output_norm, output_gate=use_output_gate),
                depth)
        elif mixer_types[i] == "kda":
            mixed = times(kda_mixer(
                normed, name, hidden_size, int(delta["num_heads"]),
                int(delta["head_dim"]),
                int(delta.get("short_conv_kernel_size", 4)),
                layernorm_epsilon, kda_allow_neg_eigval, kda_use_full_proj),
                depth)
        elif mixer_types[i] == "gdn":
            mixed = times(gdn_mixer(
                normed, name, hidden_size, int(linear_num_key_heads),
                int(linear_num_value_heads), int(linear_key_head_dim),
                int(linear_value_head_dim), int(linear_conv_kernel_dim),
                layernorm_epsilon, linear_allow_neg_eigval), depth)
        else:
            if mamba_d_ssm:
                # the parallel block: both mixers read the one normed input
                net = net + times(state_space(
                    times(normed, ssm_in_multiplier), name),
                    ssm_out_multiplier)
            mixed = times(attend(
                times(normed, attention_in_multiplier), name, windowed,
                selects), attention_out_multiplier * depth)
        net = net + after(mixed, name + "_att_norm")
        normed = before(net, name + "_ffn_norm")
        ffn = (experts if moe_layer_freq[i] else dense)(normed, name)
        net = net + after(times(ffn, depth), name + "_ffn_norm")
    if not int(num_nextn_predict_layers):
        net = sym.RMSNorm(net, eps=layernorm_epsilon, name="final_norm")
        with AttrScope(**_HEAD):
            logits = times(sym.FullyConnected(
                sym.Reshape(net, shape=(-1, hidden_size)),
                num_hidden=vocab_size, no_bias=True, name="head"),
                lm_head_multiplier)
            flat_label = sym.Reshape(label, shape=(-1,))
            return sym.SoftmaxOutput(logits, flat_label, use_ignore=True,
                                     ignore_label=-1, name="softmax")
    # the head's matrix too is read twice: one variable
    head_w = sym.Variable("head_weight")

    def head(x, lab, scope, name):
        with AttrScope(**scope):
            logits = times(sym.FullyConnected(
                sym.Reshape(x, shape=(-1, hidden_size)), weight=head_w,
                num_hidden=vocab_size, no_bias=True, name=name + "head"),
                lm_head_multiplier)
            return sym.SoftmaxOutput(
                logits, sym.Reshape(lab, shape=(-1,)), use_ignore=True,
                ignore_label=-1, name=name + "softmax")

    main = head(sym.RMSNorm(net, eps=layernorm_epsilon, name="final_norm"),
                label, _HEAD, "")
    with AttrScope(**_MTP):
        nxt = times(sym.Embedding(
            sym.Variable("mtp_data"), input_dim=vocab_size,
            output_dim=hidden_size, name="mtp_embed", **embed_w),
            embedding_multiplier)
        u = sym.FullyConnected(sym.Concat(
            sym.RMSNorm(nxt, eps=layernorm_epsilon, name="mtp_enorm"),
            sym.RMSNorm(net, eps=layernorm_epsilon, name="mtp_hnorm"),
            dim=2), num_hidden=hidden_size, no_bias=True, flatten=False,
            name="mtp_proj")
        u = u + times(attend(
            sym.RMSNorm(u, eps=layernorm_epsilon, name="mtp_att_norm"),
            "mtp", windowed=False, layer=_MTP), depth)
        u = u + times(experts(
            sym.RMSNorm(u, eps=layernorm_epsilon, name="mtp_ffn_norm"),
            "mtp"), depth)
        u = sym.RMSNorm(u, eps=layernorm_epsilon, name="mtp_final_norm")
    return sym.Group([main, head(u, sym.Variable("mtp_label"), _MTP,
                                 "mtp_")])
