"""PR 60: which programs the decode row's grouped body moved.  Two parts.

``pr59_hashes.py``'s twenty-one serving programs of nine configurations at
toy sizes (Pallas interpreted, so the kernel lowers as plain StableHLO), and
nemotron-3-nano's two at its test's toy size: a toy whose decode row takes
the kernel with several query heads a KV head changes, every other line has
to be the parent's.

Then the decode row of each serving cell's first node that takes the kernel
through ``paged_attend`` AT THE CELL'S OWN SHAPES, on a backend that is told
it runs Pallas: a hash of the jaxpr (the ``pallas_call``'s body in it is what
Mosaic lowers; no source locations), with the ``Tiles`` the rule gave.  H =
H_kv (``opt_serve_backlog``, ``olmoh_serve_rollouts``) has to hash to the
parent's; ``falconh1_serve_chat``, ``mimo_serve_longshort`` and
``solar2_serve_agent`` change.  Run it over the parent's tree and over the
change's, both unpacked at ONE path in turn, and compare:

    T=/root/scratch/tree
    rm -rf $T; mkdir -p $T; git archive HEAD | tar -x -C $T
    cp benchmarks/runs/pr60_hashes.py benchmarks/bench_decode_kernel.py \\
        /root/scratch/                                  # the parent has neither
    (cd $T && TREE=$T PROBE=/root/scratch python /root/scratch/pr60_hashes.py) > a
    rm -rf $T; mkdir -p $T; git archive $(git write-tree) | tar -x -C $T
    (cd $T && TREE=$T python benchmarks/runs/pr60_hashes.py) > b; diff a b

CPU only; nothing here is run by a test or by the benchmark."""
import hashlib
import importlib.util
import os
import runpy

TREE = os.environ["TREE"]
g = runpy.run_path(os.path.join(TREE, "benchmarks", "runs",
                                "pr59_hashes.py"))["g"]
import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402
from chipbench import harness, manifest                     # noqa: E402
from chipbench.drivers import serve_ticks, serve_ticks_by_leaf  # noqa: E402
import test_nemotron_3_nano as tnn                          # noqa: E402
from mxnet_tpu.ops import attention as attn                 # noqa: E402

assert attn.__file__.startswith(TREE), attn.__file__
with g["config"].overrides(MXNET_PALLAS_INTERPRET="1"):
    cfg = tnn.tiny_config(manifest.load_cell("nemotron3_serve_agent")
                          ["config"], max_position_embeddings=1024)
    sym = harness.build_symbol(cfg)
    params = serve_ticks_by_leaf.make_params(
        serve_ticks.weight_shapes(sym, cfg), cfg, 7, "float32")
    print("nemotron-3-nano", *g["programs"](g["pred_of"](sym, params), 2, 64))

# the probe that reads a cell's nodes: this PR's, on either tree
spec = importlib.util.spec_from_file_location(
    "bench_decode_kernel", os.path.join(
        os.environ.get("PROBE", os.path.join(TREE, "benchmarks")),
        "bench_decode_kernel.py"))
probe = importlib.util.module_from_spec(spec)
spec.loader.exec_module(probe)
attn._kernel_backend = lambda: (True, False)
for cell in probe.SERVING_CELLS:
    node = next((n for n in probe.serving_nodes(cell)
                 if probe.decode_path(n) == "decode-kernel"), None)
    if node is None:
        print(cell, "no node of its decode step takes the kernel")
        continue
    b, m = node["slots"], node["cap"] // node["pt"]
    kp, vp = probe.abstract_pools(node)
    args = (jax.ShapeDtypeStruct((b, 1, node["e"]), jnp.bfloat16), kp, vp,
            jax.ShapeDtypeStruct((b, m), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.int32))
    text = str(jax.make_jaxpr(lambda q, kp, vp, table, total: attn.paged_attend(
        q, kp, vp, table, total, num_heads=node["heads"],
        num_kv_heads=node["kv_heads"],
        value_scale=node["value_scale"]))(*args))
    assert "pallas_call" in text
    t, _ = attn.decode_kernel_selected(args[0].shape, kp, vp, (b, m),
                                       node["heads"], node["kv_heads"])
    print(cell, "decode row at the cell's shapes",
          hashlib.sha256(text.encode()).hexdigest()[:16], len(text),
          "body", getattr(t, "body", "whole"), "rows a product",
          t.prows if getattr(t, "body", "whole") == "grouped"
          else t.pieces * t.rows)
