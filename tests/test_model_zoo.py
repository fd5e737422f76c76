"""Model-zoo sweep: every architecture family composes, infers shapes, and
runs one training forward/backward (reference: the symbols under
example/image-classification/symbols/ + example/rnn)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models
from mxnet_tpu import ndarray as nd
from mxnet_tpu.io import DataBatch


def _one_step(net, data_shape, label_shape, label_vals=None):
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", data_shape)],
             label_shapes=[("softmax_label", label_shape)])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd")
    rng = np.random.RandomState(0)
    x = rng.normal(size=data_shape).astype(np.float32)
    y = label_vals if label_vals is not None else \
        rng.randint(0, 3, size=label_shape).astype(np.float32)
    batch = DataBatch([nd.array(x)], [nd.array(y)])
    mod.forward_backward(batch)
    mod.update()
    return mod.get_outputs()[0].asnumpy()


# small input variants so the sweep stays fast; channel math is identical
CNN_ZOO = {
    "lenet": (models.get_lenet, {"num_classes": 4}, (2, 1, 28, 28)),
    "mlp": (models.get_mlp, {"num_classes": 4}, (2, 32)),
    "alexnet": (models.get_alexnet, {"num_classes": 4}, (2, 3, 224, 224)),
    "vgg": (models.get_vgg, {"num_classes": 4, "num_layers": 11},
            (2, 3, 64, 64)),
    "inception_bn": (models.get_inception_bn, {"num_classes": 4},
                     (2, 3, 224, 224)),
    "googlenet": (models.get_googlenet, {"num_classes": 4},
                  (2, 3, 224, 224)),
    "inception_v3": (models.get_inception_v3, {"num_classes": 4},
                     (2, 3, 299, 299)),
    "resnet18": (models.get_resnet,
                 {"num_classes": 4, "num_layers": 18,
                  "image_shape": (3, 32, 32)}, (2, 3, 32, 32)),
    "resnext50": (models.get_resnext,
                  {"num_classes": 4, "num_layers": 50,
                   "image_shape": (3, 32, 32)}, (2, 3, 32, 32)),
}


@pytest.mark.parametrize("name", sorted(CNN_ZOO))
def test_cnn_family_shapes(name):
    build, kwargs, shape = CNN_ZOO[name]
    net = build(**kwargs)
    arg_shapes, out_shapes, _ = net.infer_shape(
        data=shape, softmax_label=(shape[0],))
    assert out_shapes[0] == (shape[0], kwargs["num_classes"])


@pytest.mark.parametrize("name", ["lenet", "mlp", "resnet18", "googlenet",
                                  "resnext50"])
def test_cnn_family_train_step(name):
    build, kwargs, shape = CNN_ZOO[name]
    net = build(**kwargs)
    out = _one_step(net, shape, (shape[0],))
    assert out.shape == (shape[0], kwargs["num_classes"])
    np.testing.assert_allclose(out.sum(-1), 1.0, rtol=1e-4)


def test_attention_lm_trains():
    """The leapfrog LM family learns a deterministic chain; MoE variant
    compiles and steps."""
    b, t, vocab = 8, 16, 17
    net = models.get_attention_lm(vocab_size=vocab, seq_len=t,
                                  num_layers=2, embed=32, heads=4,
                                  ffn_hidden=64)
    rng = np.random.RandomState(0)
    x = np.zeros((160, t), np.float32)
    x[:, 0] = rng.randint(1, vocab, size=160)
    for i in range(1, t):
        x[:, i] = (x[:, i - 1] * 3 + 1) % vocab
    y = np.roll(x, -1, axis=1)
    y[:, -1] = (x[:, -1] * 3 + 1) % vocab

    mod = mx.mod.Module(net, context=mx.cpu())
    it = mx.io.NDArrayIter(x, y, batch_size=b)
    mod.fit(it, optimizer="adam", optimizer_params={"learning_rate": 3e-3},
            initializer=mx.initializer.Xavier(),
            eval_metric=mx.metric.Perplexity(ignore_label=-1), num_epoch=6)
    it.reset()
    score = dict(mod.score(it, mx.metric.Perplexity(ignore_label=-1)))
    assert score["Perplexity"] < 4.0, score


# the LM's graph variants, at one tiny size: vocab 17, T 8, one layer of
# width 16, 4 heads, ffn 32
LM_VARIANTS = {"mha": {}, "gqa": {"num_kv_heads": 2}, "moe": {"moe_experts": 2}}
_LN_OPS = {"mean", "broadcast_sub", "square", "_plus_scalar", "rsqrt",
           "broadcast_mul", "broadcast_add"}
_LM_OPS = _LN_OPS | {"Embedding", "FullyConnected", "dot_product_attention",
                     "_plus", "Reshape", "SoftmaxOutput"}
LM_OPS = {"mha": _LM_OPS | {"Activation"}, "gqa": _LM_OPS | {"Activation"},
          "moe": _LM_OPS | {"MoEFFN"}}


def _lm_variant(variant):
    return models.get_attention_lm(vocab_size=17, seq_len=8, num_layers=1,
                                   embed=16, heads=4, ffn_hidden=32,
                                   **LM_VARIANTS[variant])


def _lm_params(kv, ffn):
    """The arguments of ``_lm_variant`` in ``list_arguments()`` order, as
    the graph gave them before the block returned to plain ops (PR 32): the
    names and layouts a checkpoint and the benchmark's weights are keyed
    by."""
    return ([("data", (2, 8)), ("embed_weight", (17, 16)),
             ("pos_embed_weight", (1, 8, 16)),
             ("layer0_att_ln_gamma", (1, 1, 16)),
             ("layer0_att_ln_beta", (1, 1, 16)),
             ("layer0_q_weight", (16, 16)), ("layer0_q_bias", (16,)),
             ("layer0_k_weight", (kv, 16)), ("layer0_k_bias", (kv,)),
             ("layer0_v_weight", (kv, 16)), ("layer0_v_bias", (kv,)),
             ("layer0_attout_weight", (16, 16)),
             ("layer0_attout_bias", (16,)),
             ("layer0_ffn_ln_gamma", (1, 1, 16)),
             ("layer0_ffn_ln_beta", (1, 1, 16))]
            + ffn
            + [("final_ln_gamma", (1, 1, 16)), ("final_ln_beta", (1, 1, 16)),
               ("head_weight", (17, 16)), ("head_bias", (17,)),
               ("softmax_label", (2, 8))])


_DENSE_FFN = [("layer0_ffn1_weight", (32, 16)), ("layer0_ffn1_bias", (32,)),
              ("layer0_ffn2_weight", (16, 32)), ("layer0_ffn2_bias", (16,))]
LM_PARAMS = {
    "mha": _lm_params(16, _DENSE_FFN),
    "gqa": _lm_params(8, _DENSE_FFN),
    "moe": _lm_params(16, [("layer0_moe_gate_weight", (16, 2)),
                           ("layer0_moe_expert1_weight", (2, 16, 32)),
                           ("layer0_moe_expert1_bias", (2, 32)),
                           ("layer0_moe_expert2_weight", (2, 32, 16)),
                           ("layer0_moe_expert2_bias", (2, 16))]),
}


@pytest.mark.parametrize("variant", sorted(LM_VARIANTS))
def test_attention_lm_block_is_plain_ops(variant):
    """The decoder block is expressed in the registry's ordinary ops: no
    node of the LM belongs to an op that exists for this model alone."""
    from mxnet_tpu.registry import list_ops

    net = _lm_variant(variant)
    ops = {nd_.op.name for nd_ in net._topo() if nd_.op is not None}
    assert ops == LM_OPS[variant], sorted(ops ^ LM_OPS[variant])
    # nor does the registry hold an LN->linear op for a block to reach for
    assert not [name for name in list_ops() if "LNLinear" in name]


@pytest.mark.parametrize("variant", sorted(LM_VARIANTS))
def test_attention_lm_params_unchanged(variant):
    """Argument names, order and inferred shapes are the literal table: a
    checkpoint, and ``chipbench/weights.py``'s draw by sorted name, meet
    the same parameters whatever ops the block is built from."""
    net = _lm_variant(variant)
    arg_shapes, _, _ = net.infer_shape(data=(2, 8), softmax_label=(2, 8))
    got = list(zip(net.list_arguments(), [tuple(s) for s in arg_shapes]))
    assert got == LM_PARAMS[variant]


@pytest.mark.parametrize("variant", ["mha", "gqa"])
def test_attention_lm_tp_plan(variant):
    """``plan_tensor_parallel`` over the real model: Megatron pairs through
    the attention op and through the FFN's ``Activation`` — column q/k/v/
    ffn1 with sharded biases, row attout/ffn2 with replicated biases, and
    LayerNorm's per-feature gamma/beta left out (replicated)."""
    from mxnet_tpu.parallel.tp_rules import plan_tensor_parallel

    plan = plan_tensor_parallel(_lm_variant(variant))
    for name in ("q", "k", "v", "ffn1"):
        assert plan["layer0_%s_weight" % name] == ("model", None), name
        assert plan["layer0_%s_bias" % name] == ("model",), name
    for name in ("attout", "ffn2"):
        assert plan["layer0_%s_weight" % name] == (None, "model"), name
        assert "layer0_%s_bias" % name not in plan, name
    assert not [n for n in plan if n.endswith(("ln_gamma", "ln_beta"))], plan


def test_fully_connected_row_merge_follows_producer():
    """``FullyConnected(flatten=False)`` contracts a (B, T, K) operand as
    one (B*T, K) matmul, except straight after attention, where the operand
    is taken as it comes: the placement the LM cells' rates depend on
    (PERF.md section 6, PR 32)."""
    import jax

    from mxnet_tpu.registry import OpContext, get_op

    fc = get_op("FullyConnected")
    attrs = {"num_hidden": 4, "flatten": False}
    args = (np.zeros((2, 3, 5), np.float32), np.zeros((4, 5), np.float32),
            np.zeros((4,), np.float32))

    def lhs_ranks(producers):
        octx = OpContext(producers=producers)
        jaxpr = jax.make_jaxpr(
            lambda *a: fc.fcompute(attrs, list(a), [], octx)[0][0])(*args)
        assert jaxpr.out_avals[0].shape == (2, 3, 4)
        return [e.invars[0].aval.ndim for e in jaxpr.eqns
                if e.primitive.name == "dot_general"]

    assert lhs_ranks(("broadcast_add", None, None)) == [2]
    assert lhs_ranks(()) == [2]
    assert lhs_ranks(("dot_product_attention", None, None)) == [3]


def test_attention_lm_moe_variant_steps():
    b, t, vocab = 4, 8, 11
    net = models.get_attention_lm(vocab_size=vocab, seq_len=t,
                                  num_layers=1, embed=16, heads=2,
                                  ffn_hidden=32, moe_experts=2)
    rng = np.random.RandomState(1)
    x = rng.randint(0, vocab, size=(b, t)).astype(np.float32)
    y = np.roll(x, -1, axis=1)
    out = _one_step(net, (b, t), (b, t), label_vals=y)
    assert out.shape == (b * t, vocab)
