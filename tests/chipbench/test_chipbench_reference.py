"""The plain references agree with the system at a tiny size on the CPU:
outputs, loss and, for training, gradients.  (On the chip every run makes
the comparison at the published widths; ``chipbench/correct.py``.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from chipbench import correct, harness, weights
from chipbench.reference import opt as ref_opt, resnet50 as ref_rn

import tiny

from chipbench import manifest

REAL_RN = manifest.load_json(manifest.ROOT,
                             "chipbench/configs/resnet50.json")
REAL_LM = manifest.load_json(manifest.ROOT,
                             "chipbench/configs/opt-1.3b.json")
INIT_RN, INIT_LM = REAL_RN["init"], REAL_LM["init"]


def _bound(cfg, data_shape, label_shape, seed, **overrides):
    """The system's executor on seeded weights: ``(exec, params)``."""
    sym = harness.build_symbol(cfg, **overrides)
    ex = sym.simple_bind(mx.cpu(), grad_req="write", data=data_shape,
                         softmax_label=label_shape)
    arg_shapes, _, aux_shapes = sym.infer_shape(data=data_shape,
                                                softmax_label=label_shape)
    shapes = {n: s for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in ("data", "softmax_label")}
    shapes.update(zip(sym.list_auxiliary_states(), aux_shapes))
    params = weights.make_params(shapes, cfg, seed, "float32")
    for n, v in params.items():
        (ex.arg_dict if n in ex.arg_dict else ex.aux_dict)[n]._set_data(v)
    return ex, params


@pytest.fixture(scope="module")
def lm():
    cfg = dict(tiny.TINY_LM, init=INIT_LM)
    ex, params = _bound(cfg, (2, 64), (2, 64), 11)
    rng = np.random.RandomState(0)
    toks = rng.randint(0, cfg["vocab_size"], (2, 64))
    labels = rng.randint(0, cfg["vocab_size"], (2, 64))
    ex.arg_dict["data"]._set_data(jnp.asarray(toks, jnp.float32))
    ex.arg_dict["softmax_label"]._set_data(jnp.asarray(labels, jnp.float32))
    ex.forward(is_train=True)
    ex.backward()
    return cfg, ex, params, toks, labels


def test_opt_reference_forward_matches_the_system(lm):
    cfg, ex, params, toks, _ = lm
    probs = ex.outputs[0].data
    logits = ref_opt.forward(params, cfg, toks).reshape(-1, cfg["vocab_size"])
    out = correct.compare_logp(probs, logits, 1e-4)
    assert out["ok"], out
    assert out["positions"] == 128


def test_opt_reference_tied_head(lm):
    _, _, params, _, _ = lm
    assert np.array_equal(params["head_weight"], params["embed_weight"])
    assert not np.any(np.asarray(params["head_bias"]))


def test_opt_reference_is_causal(lm):
    cfg, _, params, toks, _ = lm
    full = ref_opt.forward(params, cfg, toks)
    head = ref_opt.forward(params, cfg, toks[:, :20])
    assert np.allclose(full[:, :20], head, atol=1e-5)


def test_opt_reference_gradients_match_the_system(lm):
    cfg, ex, params, toks, labels = lm
    # SoftmaxOutput's gradient is that of the summed cross-entropy
    grads = jax.grad(lambda p: ref_opt.loss(p, cfg, toks, labels)
                     * labels.size)(params)
    for name in ("layer0_q_weight", "layer1_ffn2_weight", "final_ln_gamma",
                 "pos_embed_weight", "layer0_att_ln_beta", "head_bias"):
        got = np.asarray(ex.grad_dict[name].data)
        want = np.asarray(grads[name])
        assert np.allclose(got, want, rtol=2e-3,
                           atol=2e-4 * np.abs(want).max()), name


def test_opt_reference_depth_argument(lm):
    cfg, _, params, toks, _ = lm
    one = ref_opt.forward(params, cfg, toks, layers=1)
    two = ref_opt.forward(params, cfg, toks)
    assert not np.allclose(one, two)


@pytest.fixture(scope="module")
def rn():
    cfg = dict(tiny.TINY_RESNET, init=INIT_RN)
    shape = (4,) + tuple(cfg["image_shape"])
    ex, params = _bound(cfg, shape, (4,), 12)
    rng = np.random.RandomState(1)
    x = rng.uniform(-1, 1, shape).astype(np.float32)
    y = rng.randint(0, cfg["num_classes"], (4,))
    ex.arg_dict["data"]._set_data(jnp.asarray(x))
    ex.arg_dict["softmax_label"]._set_data(jnp.asarray(y, jnp.float32))
    ex.forward(is_train=True)
    ex.backward()
    return cfg, ex, params, x, y


def test_resnet_reference_forward_and_loss_match_the_system(rn):
    cfg, ex, params, x, y = rn
    probs = ex.outputs[0].data
    logits = ref_rn.forward(params, cfg, x, training=True)
    assert correct.compare_logp(probs, logits, 1e-3)["ok"]
    out = correct.compare_loss(probs, logits, y,
                               correct.limit(REAL_RN, "train_fit",
                                             "loss_rtol"))
    assert out["ok"] and out["loss"] == pytest.approx(
        float(ref_rn.loss(params, cfg, x, y, training=True)), rel=1e-4)


def test_resnet_reference_gradients_match_the_system(rn):
    cfg, ex, params, x, y = rn
    grads = jax.grad(lambda p: ref_rn.loss(p, cfg, x, y, training=True)
                     * y.size)(params)
    for name in ("conv0_weight", "stage1_unit1_sc_weight",
                 "stage3_unit2_conv2_weight", "stage4_unit3_bn3_gamma",
                 "fc1_weight", "bn1_beta"):
        got = np.asarray(ex.grad_dict[name].data)
        want = np.asarray(grads[name])
        # batch statistics over 4 images of 2x2 positions in the last stage
        # are ill-conditioned, so two float32 orders of summation differ in
        # the third digit after 50 layers; a wrong gradient is off by O(1)
        err = np.linalg.norm(got - want) / np.linalg.norm(want)
        assert err < 0.03, (name, err)


def test_resnet_reference_eval_mode_uses_the_stored_statistics(rn):
    cfg, _, params, x, _ = rn
    a = ref_rn.forward(params, cfg, x, training=False)
    moved = dict(params, bn0_moving_mean=params["bn0_moving_mean"] + 1.0)
    b = ref_rn.forward(moved, cfg, x, training=False)
    c = ref_rn.forward(moved, cfg, x, training=True)
    assert not np.allclose(a, b)
    assert np.allclose(c, ref_rn.forward(params, cfg, x, training=True))


@pytest.mark.parametrize("fault", ["mask", "layer", "precision"])
def test_the_tolerance_refuses_a_wrong_model(lm, fault):
    """What the on-chip limits must catch moves log-probabilities by far
    more than they allow."""
    cfg, ex, params, toks, _ = lm
    probs = ex.outputs[0].data
    if fault == "mask":          # attends to the future: position 0 differs
        logits = ref_opt.forward(params, cfg, toks[:, ::-1])[:, ::-1]
    elif fault == "layer":
        logits = ref_opt.forward(params, cfg, toks, layers=1)
    else:                        # weights kept to 3 bits of mantissa
        coarse = {k: jnp.round(v * 8) / 8 for k, v in params.items()}
        logits = ref_opt.forward(coarse, cfg, toks)
    out = correct.compare_logp(probs, logits.reshape(-1, cfg["vocab_size"]),
                               correct.limit(REAL_LM, "train_fit",
                                             "logp_atol"))
    assert not out["ok"], out


@pytest.mark.parametrize("cell", ["tiny_lm", "tiny_serve"])
def test_the_control_reads_far_above_a_sound_run(cell, tmp_path_factory):
    """``chipbench/control.py``: the reference with its matrices in the
    precision below the configuration's (float32 here, so bfloat16), over
    the rows the cell's own comparison reads, moves log-probabilities by
    more than three times what the system's float32 differs from the
    reference by at this size (under 1e-4, the tests above).  On the chip
    the same reading, at the cell's size, has to stay above the cell's
    limit (PERF.md keeps both)."""
    from chipbench import control

    root = tiny.make_root(tmp_path_factory.mktemp("control"))
    loaded = manifest.load_cell(cell, root=root)
    got = [control.reading(loaded, seed) for seed in (3, 2 ** 31 + 9)]
    assert all(np.isfinite(g) and g > 3e-4 for g in got), got
    # the same seed reads the same; 8-bit matrices read higher still
    assert control.reading(loaded, 3) == got[0]
    if cell == "tiny_lm":
        assert control.reading(loaded, 3, below="float8_e4m3fn") > got[0]
