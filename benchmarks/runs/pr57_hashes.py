"""PR 57: the decode and chunk programs (and exaone's verify and draft
programs) of the seven OTHER serving configurations lower to the parent's
StableHLO: ``pr53_hashes.py``'s seventeen programs, and solar2's two (Kimi
delta attention beside gated attention over int8 pages) at its test's toy
size.  Run it over the parent's tree and over the change's and compare:

    TREE=$PWD/scratch/parent python benchmarks/runs/pr57_hashes.py > a
    TREE=$PWD python benchmarks/runs/pr57_hashes.py > b; diff a b

CPU only; nothing here is run by a test or by the benchmark."""
import os
import runpy

g = runpy.run_path(os.path.join(os.environ["TREE"], "benchmarks", "runs",
                                "pr53_hashes.py"))["g"]
from chipbench import harness, manifest                    # noqa: E402
from chipbench.drivers import serve_ticks, serve_ticks_by_leaf  # noqa: E402
import test_solar_open2 as tso                              # noqa: E402

with g["config"].overrides(MXNET_PALLAS_INTERPRET="1"):
    cfg = tso.tiny_config(manifest.load_cell("solar2_serve_agent")["config"],
                          max_position_embeddings=1024)
    sym = harness.build_symbol(cfg)
    params = serve_ticks_by_leaf.make_params(
        serve_ticks.weight_shapes(sym, cfg), cfg, 7, "float32")
    print("solar-open2-250b", *g["programs"](g["pred_of"](sym, params), 2,
                                             64))
