"""Do two trees lower to the same programs?  Four parts, a hash a line.

The serving programs (decode and chunk; exaone's verify and two self-drafting
ones) of every serving configuration at its test's toy size: caches of 1024
in pages of 16, so that the walk engages; Pallas interpreted, so that a
kernel lowers as plain StableHLO where a toy's shapes tile and the routed
product's chooser is asked on a backend that runs one; printed with the paths
each program's attention took (the form each program's gated expert layers
took goes to stderr).

The fused train steps of a toy ResNet and a toy OPT through ``Module``: the
programs of ``rn50_train_*`` and ``opt_train_*`` at a size the CPU lowers in
seconds.

The decode row of each serving cell's first node that takes the kernel
through ``paged_attend`` AT THE CELL'S OWN SHAPES, on a backend that is told
it runs Pallas: a hash of the jaxpr (the ``pallas_call``'s body in it is what
Mosaic lowers; no source locations), with the ``Tiles`` the rule gave.

The latent node of ``mistral4_serve_longdoc`` AT THE CELL'S OWN SHAPES, the
same way (PR 62): its decode row (20 slots, one row) and its prefill chunk
(one slot, 2048 rows) through ``latent_attend``, with the form each took.
The toy above cannot show them: its widths tile for neither kernel and its
chunk is 64 rows, the absorbed walk.

Run it over the parent's tree and over the change's, BOTH UNPACKED AT ONE
PATH in turn (a Mosaic kernel's body carries its checkout's path), and
compare; a parent without this file is handed the change's copy:

    T=/root/scratch/tree
    rm -rf $T; mkdir -p $T; git archive HEAD | tar -x -C $T
    cp benchmarks/runs/hashes.py /root/scratch/
    (cd $T && TREE=$T python /root/scratch/hashes.py) > a
    rm -rf $T; mkdir -p $T; git archive $(git write-tree) | tar -x -C $T
    (cd $T && TREE=$T python benchmarks/runs/hashes.py) > b; diff a b

CPU only; nothing here is run by a test or by the benchmark."""
import hashlib
import importlib
import os
import sys

TREE = os.environ["TREE"]
sys.path[:0] = [TREE, os.path.join(TREE, "tests"),
                os.path.join(TREE, "benchmarks"),
                os.path.join(TREE, "tests", "chipbench")]
import jax                                                  # noqa: E402
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp                                     # noqa: E402
import numpy as np                                          # noqa: E402
import mxnet_tpu as mx                                      # noqa: E402
assert mx.__file__.startswith(TREE), mx.__file__
import bench_decode_kernel as probe                         # noqa: E402
from chipbench import harness, manifest, weights            # noqa: E402
from chipbench.drivers import (serve_ticks, serve_ticks_by_leaf,  # noqa: E402
                               serve_ticks_mtp)
from mxnet_tpu import config                                # noqa: E402
from mxnet_tpu.decode import DecodePredictor                # noqa: E402
from mxnet_tpu.models import resnet                         # noqa: E402
from mxnet_tpu.ops import attention as attn                 # noqa: E402
from mxnet_tpu.programs import spec as pspec                # noqa: E402


def digest(text):
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def programs(pred, slots, chunk, spec_k=0):
    """A hash and a length for each serving program ``pred`` has, and the
    paths its attention took at each width."""
    avals = pred.serving_avals(slots, chunk_w=chunk, spec_k=spec_k)
    fns = {"decode": pred._paged_decode_impl, "chunk": pred._chunk_impl,
           "verify": getattr(pred, "_paged_verify_impl", None),
           "mtp_step": getattr(pred, "_paged_decode_mtp_impl", None),
           "mtp_chunk": getattr(pred, "_mtp_chunk_impl", None)}
    out = {}
    for kind, fn in fns.items():
        if fn is None or kind not in avals:
            continue
        try:
            with pspec.probing(pred):
                text = jax.jit(fn).lower(*avals[kind]).as_text()
        except Exception as e:
            out[kind] = "ERR " + type(e).__name__ + str(e)[:80]
            continue
        out[kind] = digest(text) + " %d" % len(text)
    print("  moe forms", getattr(pred, "_moe_forms", None), file=sys.stderr)
    return out, {k: sorted(v) for k, v in pred._decode_paths.items()}


def pred_of(sym, params, **kw):
    args = dict(cache_len=1024, ctx=mx.cpu(), temperature=0.0, paged=True,
                page_tokens=16, prefill_chunk=64, kv_dtype="int8")
    args.update(kw)
    return DecodePredictor(sym, {n: mx.nd.NDArray(v, mx.cpu())
                                 for n, v in params.items()}, **args)


def opt_toy(**over):
    return dict(manifest.load_json(manifest.ROOT,
                                   "chipbench/configs/opt-1.3b.json"),
                vocab_size=96, ffn_dim=128, num_attention_heads=4,
                num_hidden_layers=2, **over)


def of_a_test_module(mod, over, sized=lambda m: {}):
    """A configuration whose test keeps ``toy_config`` and ``build``;
    ``sized(module)`` gives what its predictor takes of the module's own."""
    m = importlib.import_module(mod)
    sym, params = m.build(m.toy_config(**over))
    kw = sized(m)
    return pred_of(sym, params, **kw), kw.get("prefill_chunk", 64)


def of_a_cell(cell, mod, driver=None, **kw):
    """A configuration of the benchmark, cut down by its test's
    ``tiny_config``, under the weights its cell's driver draws (by leaf,
    over ``serve_ticks``' shapes, unless the driver has its own)."""
    cfg = importlib.import_module(mod).tiny_config(
        manifest.load_cell(cell)["config"], max_position_embeddings=1024)
    sym = harness.build_symbol(cfg)
    shapes = (driver or serve_ticks).weight_shapes(sym, cfg)
    params = (driver or serve_ticks_by_leaf).make_params(
        shapes, cfg, 7, "float32")
    return pred_of(sym, params, **kw)


def opt_pred():
    cfg = opt_toy(hidden_size=256, word_embed_proj_dim=256,
                  max_position_embeddings=1024)
    sym = harness.build_symbol(cfg)
    arg_shapes, _, _ = sym.infer_shape(data=(1, 1024),
                                       softmax_label=(1, 1024))
    shapes = {n: s for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in ("data", "softmax_label")}
    return pred_of(sym, weights.make_params(shapes, cfg, 7, "float32"))


def train_step(sym, data, label):
    """The fused step of ``sym`` over one batch: its StableHLO's hash."""
    with mx.NameManager():
        it = mx.io.NDArrayIter(data, label, batch_size=data.shape[0],
                               label_name="softmax_label")
        mod = mx.mod.Module(sym, context=mx.cpu())
        mod.bind(it.provide_data, it.provide_label)
        mod.init_params(mx.initializer.Xavier())
        mod.init_optimizer(optimizer="sgd", optimizer_params=dict(
            learning_rate=0.1, momentum=0.9, wd=1e-4))
        mod.forward_backward(next(iter(it)))
        mod.update()
    text = mod._fused_step.artifact().stablehlo_text
    return digest(text) + " %d" % len(text)


def decode_row(cell):
    """The decode row of ``cell``'s first node that takes the kernel."""
    node = next((n for n in probe.serving_nodes(cell)
                 if probe.decode_path(n) == "decode-kernel"), None)
    if node is None:
        return "no node of its decode step takes the kernel",
    b, m = node["slots"], node["cap"] // node["pt"]
    kp, vp = probe.abstract_pools(node)
    args = (jax.ShapeDtypeStruct((b, 1, node["e"]), jnp.bfloat16), kp, vp,
            jax.ShapeDtypeStruct((b, m), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.int32))
    text = str(jax.make_jaxpr(
        lambda q, kp, vp, table, total: attn.paged_attend(
            q, kp, vp, table, total, num_heads=node["heads"],
            num_kv_heads=node["kv_heads"],
            value_scale=node["value_scale"]))(*args))
    assert "pallas_call" in text
    t, _ = attn.decode_kernel_selected(args[0].shape, kp, vp, (b, m),
                                       node["heads"], node["kv_heads"])
    body = getattr(t, "body", "whole")
    return ("decode row at the cell's shapes", digest(text), len(text),
            "body", body, "rows a product",
            t.prows if body == "grouped" else t.pieces * t.rows)


MIMO = dict(max_position_embeddings=1024, head_dim=64, v_head_dim=64,
            swa_head_dim=64, swa_v_head_dim=64, num_key_value_heads=4)
# name -> () -> (predictor, chunk width[, draft length])
SERVING = {
    "opt-like(decoder_lm heads of 64)": lambda: (
        importlib.import_module("test_pallas_decode")._predictor(), 64),
    "opt-1.3b": lambda: (opt_pred(), 64),
    "mimo-v2.5": lambda: of_a_test_module("test_decoder_lm", MIMO),
    "falcon-h1-34b": lambda: of_a_test_module(
        "test_hybrid_ssm_lm", dict(max_position_embeddings=1024)),
    "minicpm-sala": lambda: of_a_test_module(
        "test_sparse_attention", {}, lambda m: dict(
            cache_len=m.CACHE, page_tokens=m.PAGE, prefill_chunk=m.CHUNK,
            kv_dtype="")),
    "k-exaone-236b": lambda: (of_a_cell(
        "exaone_serve_reason", "test_k_exaone", serve_ticks_mtp), 64, 1),
    "mistral-small-4-119b": lambda: (of_a_cell(
        "mistral4_serve_longdoc", "test_mistral_small_4", kv_dtype=""), 64),
    "solar-open2-250b": lambda: (of_a_cell(
        "solar2_serve_agent", "test_solar_open2"), 64),
    "olmo-hybrid-7b": lambda: (of_a_cell(
        "olmoh_serve_rollouts", "test_olmo_hybrid"), 64),
    "nemotron-3-nano": lambda: (of_a_cell(
        "nemotron3_serve_agent", "test_nemotron_3_nano"), 64),
}

with config.overrides(MXNET_PALLAS_INTERPRET="1"):
    for name, build in SERVING.items():
        pred, *widths = build()
        print(name, *programs(pred, 2, *widths))

rng = np.random.RandomState(0)
with mx.NameManager():
    net = resnet.get_symbol(num_classes=10, num_layers=18,
                            image_shape=(3, 32, 32))
print("resnet train step", train_step(
    net, rng.randn(4, 3, 32, 32).astype(np.float32),
    rng.randint(0, 10, (4,)).astype(np.float32)))
with mx.NameManager():
    net = harness.build_symbol(opt_toy(
        hidden_size=64, word_embed_proj_dim=64, max_position_embeddings=32))
print("opt-1.3b train step", train_step(
    net, rng.randint(0, 96, (4, 32)).astype(np.float32),
    rng.randint(0, 96, (4, 32)).astype(np.float32)))

attn._kernel_backend = lambda: (True, False)
for cell in probe.SERVING_CELLS:
    print(cell, *decode_row(cell))


def latent_rows(cell):
    """The latent node of ``cell`` at the cell's shapes: ``(rows a slot,
    form, hash, length)`` of its decode row and of its prefill chunk."""
    import probe_latent_decode
    from mxnet_tpu.ops import pallas_decode as pd

    spec, b, m, pt = probe_latent_decode.cell_shapes()
    plane = jax.ShapeDtypeStruct(
        pd.latent_plane_shape(b * m + 1, pt, spec.rank + spec.rope),
        jnp.bfloat16)
    w = jax.ShapeDtypeStruct(
        (spec.heads * (spec.nope + spec.v), spec.rank), jnp.bfloat16)
    chunk = manifest.load_cell(cell)["traffic"]["prefill_chunk"]
    for slots, rows in ((b, 1), (1, chunk)):
        q = lambda d: jax.ShapeDtypeStruct((slots, rows, spec.heads, d),
                                           jnp.bfloat16)
        text = str(jax.make_jaxpr(
            lambda qn, qr, c, table, total, w: attn.latent_attend(
                qn, qr, c, table, total, w, spec))(
            q(spec.nope), q(spec.rope), plane,
            jax.ShapeDtypeStruct((slots, m), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.int32), w))
        yield rows, attn.DECODE_PATH["last"], digest(text), len(text)


for row in latent_rows("mistral4_serve_longdoc"):
    print("mistral4_serve_longdoc latent node, rows a slot", *row)
