"""PR 59: the decode and chunk programs (and exaone's verify and draft
programs) of the EIGHT other serving configurations lower to the parent's
StableHLO: ``pr57_hashes.py``'s nineteen programs of seven configurations,
and olmo-hybrid-7b's two (Gated DeltaNet beside attention over padded int8
scale rows) at its test's toy size.  ``ops/moe.py``'s share form now takes
its experts' body and its shared MLP's width from the node: the gated body of
five of these configurations has to trace to what it was.  Run it over the
parent's tree and over the change's, BOTH UNPACKED AT ONE PATH in turn (a
Mosaic kernel's body carries its checkout's path), and compare:

    T=/root/scratch/tree
    rm -rf $T; mkdir -p $T; git archive HEAD | tar -x -C $T
    cp benchmarks/runs/pr59_hashes.py /root/scratch/   # the parent has none
    (cd $T && TREE=$T python /root/scratch/pr59_hashes.py) > a
    rm -rf $T; mkdir -p $T; git archive $(git write-tree) | tar -x -C $T
    (cd $T && TREE=$T python benchmarks/runs/pr59_hashes.py) > b; diff a b

CPU only; nothing here is run by a test or by the benchmark."""
import os
import runpy

g = runpy.run_path(os.path.join(os.environ["TREE"], "benchmarks", "runs",
                                "pr57_hashes.py"))["g"]
from chipbench import harness, manifest                    # noqa: E402
from chipbench.drivers import serve_ticks, serve_ticks_by_leaf  # noqa: E402
import mxnet_tpu                                            # noqa: E402
import test_olmo_hybrid as toh                              # noqa: E402

assert mxnet_tpu.__file__.startswith(os.environ["TREE"]), mxnet_tpu.__file__
with g["config"].overrides(MXNET_PALLAS_INTERPRET="1"):
    cfg = toh.tiny_config(manifest.load_cell("olmoh_serve_rollouts")["config"],
                          max_position_embeddings=1024)
    sym = harness.build_symbol(cfg)
    params = serve_ticks_by_leaf.make_params(
        serve_ticks.weight_shapes(sym, cfg), cfg, 7, "float32")
    print("olmo-hybrid-7b", *g["programs"](g["pred_of"](sym, params), 2, 64))
