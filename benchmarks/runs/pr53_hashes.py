"""PR 53: the decode and chunk programs (and exaone's verify and draft
programs) of the six OTHER serving configurations lower to the parent's
StableHLO: ``pr52_hashes.py``'s fifteen programs, and mistral4's two (latent
attention, softmax routing beside a shared expert) at its test's toy size.
Run it over the parent's tree and over the change's and compare:

    TREE=$PWD/scratch/parent python benchmarks/runs/pr53_hashes.py > a
    TREE=$PWD python benchmarks/runs/pr53_hashes.py > b; diff a b

CPU only; nothing here is run by a test or by the benchmark."""
import os
import runpy

g = runpy.run_path(os.path.join(os.environ["TREE"], "benchmarks", "runs",
                                "pr52_hashes.py"))
from chipbench import harness, manifest                    # noqa: E402
from chipbench.drivers import serve_ticks, serve_ticks_by_leaf  # noqa: E402
import test_mistral_small_4 as tms                          # noqa: E402

with g["config"].overrides(MXNET_PALLAS_INTERPRET="1"):
    cfg = tms.tiny_config(manifest.load_cell("mistral4_serve_longdoc")
                          ["config"], max_position_embeddings=1024)
    sym = harness.build_symbol(cfg)
    params = serve_ticks_by_leaf.make_params(
        serve_ticks.weight_shapes(sym, cfg), cfg, 7, "float32")
    print("mistral-small-4-119b", *g["programs"](
        g["pred_of"](sym, params, kv_dtype=""), 2, 64))
