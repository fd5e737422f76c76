"""Configuration ``mimo-v2.5`` and its cell ``mimo_serve_longshort``: the
published numbers pinned, the cut's arithmetic, the byte counts, and the
cell's own loop driver at a tiny size on the CPU (the reference against the
system through ``serve_ticks``' comparison, both cache groups, the ring
wrapping inside the compared prompt)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import harness, manifest, run, work, work_moe

import tiny

CELL, CONFIG = "mimo_serve_longshort", "mimo-v2.5"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

# config.json of XiaomiMiMo/MiMo-V2.5 as the catalog holds it: what the
# equations read
PUBLISHED = {
    "hidden_size": 4096, "num_attention_heads": 64, "head_dim": 192,
    "v_head_dim": 128, "swa_head_dim": 192, "swa_v_head_dim": 128,
    "num_key_value_heads": 4, "swa_num_key_value_heads": 8,
    "swa_num_attention_heads": 64, "num_hidden_layers": 48,
    "intermediate_size": 16384, "moe_intermediate_size": 2048,
    "n_routed_experts": 256, "num_experts_per_tok": 8, "n_group": 1,
    "topk_group": 1, "n_shared_experts": None, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "routed_scaling_factor": None, "sliding_window": 128,
    "sliding_window_size": 128, "partial_rotary_factor": 0.334,
    "rope_theta": 10000000, "swa_rope_theta": 10000,
    "attention_value_scale": 0.707, "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False, "attention_bias": False,
    "layernorm_epsilon": 1e-05, "vocab_size": 152576,
    "max_position_embeddings": 1048576, "tie_word_embeddings": False,
    "hidden_act": "silu", "model_type": "mimo_v2",
}


@pytest.fixture(scope="module")
def loaded():
    return manifest.load_cell(CELL)


def test_published_numbers(loaded):
    cfg = loaded["config"]
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    assert cfg["hybrid_layer_pattern"] == ([0, 1, 1, 1, 1] + [0, 1, 1, 1, 1, 1]
                                           * 7 + [0])
    assert cfg["moe_layer_freq"] == [0] + [1] * 47
    assert len(cfg["hybrid_layer_pattern"]) == 48
    # the cut: both reduced keys beside their published values
    assert cfg["serve_num_hidden_layers"] == 7
    assert cfg["held_n_routed_experts"] == 16
    assert cfg["first_held_expert"] == 0
    assert cfg["hybrid_layer_pattern"][:7] == [0, 1, 1, 1, 1, 0, 1]
    assert cfg["moe_layer_freq"][:7] == [0, 1, 1, 1, 1, 1, 1]
    for key in ("rotary_pairing", "rotary_dims", "window_edge",
                "correction_bias", "attention_chunk_size",
                "attention_projection_layout", "left_out", "share"):
        assert cfg["assumed"][key]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_catalog_key_at_its_published_value(loaded):
    with open(CATALOG) as f:
        entry = [e for e in map(json.loads, f) if e["name"] == "MiMo-V2.5"][0]
    man = manifest.load_manifest()
    assert manifest.find(man["configs"], CONFIG, "config")["source"] \
        == entry["source_url"]
    for key, value in entry["config"].items():
        assert loaded["config"][key] == value, key


def test_manifest_entries(loaded):
    man = manifest.load_manifest()
    assert manifest.validate(man) == []
    entry = manifest.find(man["configs"], CONFIG, "config")
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    assert len(entry["why"]) <= 200 and entry["why"].isascii()
    cell = loaded["cell"]
    assert (cell["config"], cell["chips"]) == (CONFIG, 1)
    assert cell["traffic"] == "backlog_p1024-8192_o256-1024_s64"
    assert len(cell["why"]) <= 200 and cell["why"].isascii()
    traffic = loaded["traffic"]
    want = dict(loop="backlog", slots=64, cache_len=9216, page_tokens=16,
                prefill_chunk=512, max_prefill=8192, kv_dtype="int8",
                prompt_min=1024, prompt_max=8192, output_min=256,
                output_max=1024, requests=1024, block=64, order_seed=0,
                warmup_ticks=8, trace_seconds=30, trace_ticks=96,
                check_prompt=1100, check_decode=8)
    assert {k: traffic[k] for k in want} == want
    # the cell reports what the accepted serving cell reports, and its own
    names = {m["name"] for m in loaded["per_layer"]}
    theirs = {m["name"] for m in manifest.load_cell("opt_serve_backlog")[
        "per_layer"]}
    assert names - theirs == {"moe_device_pct.serve",
                              "attn_window_device_pct.serve",
                              "moe_experts_hbm_util_pct",
                              "moe_held_rows_per_tick"}
    assert theirs <= names
    assert {m["name"] for m in loaded["end_to_end"]} == {
        "serve_out_tokens_per_s", "serve_gap_p95_ms", "setup_s"}
    # the two drivers' limits are one limit
    lim = loaded["config"]["limits"]
    assert lim["serve_ticks"]["logp_atol.int8"]["value"] \
        == lim["serve_ticks_by_leaf"]["logp_atol.int8"]["value"]


def test_parameter_counts_and_memory(loaded):
    cfg = loaded["config"]
    sym = harness.build_symbol(cfg)
    arg_shapes, _, _ = sym.infer_shape(data=(1, 8), softmax_label=(1, 8))
    shapes = {n: s for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in ("data", "softmax_label")}
    size = lambda pred: sum(int(np.prod(s)) for n, s in shapes.items()
                            if pred(n))
    mega = lambda n: round(n / 1e6, 2)
    attn = lambda l: size(lambda n: n.startswith("layer%d_" % l) and any(
        x in n for x in ("_q_", "_k_", "_v_", "_attout_")))
    assert mega(attn(0)) == 89.13 and mega(attn(5)) == 89.13      # full
    assert mega(attn(1)) == 94.37                                  # window
    assert shapes["layer1_att_sink"] == (64,)
    assert mega(size(lambda n: n == "layer1_moe_gate_weight")) == 1.05
    assert shapes["layer1_moe_gate_bias"] == (256,)
    assert mega(size(lambda n: n.startswith("layer1_moe_expert"))) \
        == round(16 * 25.165824, 2)
    assert mega(size(lambda n: n.startswith("layer0_ffn_") and "norm"
                     not in n)) == 201.33
    assert mega(size(lambda n: n in ("embed_weight", "head_weight"))) \
        == 1249.9
    total = size(lambda n: True)
    assert round(total / 1e6, 1) == 4523.6
    assert round(2 * total / 1e9, 2) == 9.05            # bfloat16
    assert work_moe.expert_bytes(cfg) == 3 * 4096 * 2048 * 2


def test_counts(loaded):
    cfg, traffic = loaded["config"], loaded["traffic"]
    assert round(work_moe.experts_touched(cfg, 64), 1) == 13.9
    assert work_moe.experts_touched(cfg, 10 ** 6) == pytest.approx(16)
    experts = work_moe.moe_expert_bytes_per_tick(cfg, traffic)
    assert experts == pytest.approx(6 * 13.903 * 50.331648e6, rel=1e-4)
    # a full layer's position: 4 x (192 + 128) int8 + 2 x 4 scales; a
    # window layer's: 8 x (192 + 128) + 2 x 8 scales
    assert work_moe.kv_bytes_per_token(cfg, False, 1) == 1312
    assert work_moe.kv_bytes_per_token(cfg, True, 1) == 2624
    live = 64 * 4000
    total = work.decode_step_bytes(cfg, traffic, live)
    dense = 2 * (2 * 89.128960e6 + 5 * 94.371840e6 + 201.326592e6
                 + 6 * 1.048576e6 + 624.951296e6)
    cache = 2 * live * 1312 + 5 * 64 * 128 * 2624
    assert total == pytest.approx(dense + experts + cache, rel=1e-6)
    # short contexts: the window layers hold what is live, not the window
    assert work.decode_step_bytes(cfg, traffic, 64 * 100) \
        == pytest.approx(dense + experts + 64 * 100 * (2 * 1312 + 5 * 2624),
                         rel=1e-6)


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 77])
def test_the_init_routes_near_what_the_counts_expect(loaded, seed):
    """``work_moe.experts_touched`` prices a decode tick under uniform
    routing (13.9 of the 16 held experts a layer at 64 slots).  A router
    and a correction bias drawn by the configuration's own ``init`` rules
    route 64 normalised tokens near that: the token's scores decide, not
    the bias (at the std 0.1 first tried, 6 to 7 of 16 were touched and the
    count read 2.2 times what the run had to read), and the bias still
    changes what is chosen."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import moe

    from chipbench.drivers import serve_ticks_by_leaf as driver

    cfg = loaded["config"]
    d, e = cfg["hidden_size"], cfg["n_routed_experts"]
    k, held = cfg["num_experts_per_tok"], cfg["held_n_routed_experts"]
    touched, rows, changed = [], [], []
    shapes = {}
    for layer in range(6):
        shapes["l%d_moe_gate_weight" % layer] = (d, e)
        shapes["l%d_moe_gate_bias" % layer] = (e,)
    p = driver.make_params(shapes, cfg, seed, "float32")
    for layer in range(6):
        wr, b = (p["l%d_moe_gate_%s" % (layer, n)] for n in ("weight",
                                                             "bias"))
        assert 0.005 < float(jnp.std(b)) < 0.02
        x = np.random.default_rng(layer).normal(size=(64, d))
        x = jnp.asarray(x / np.sqrt((x ** 2).mean(-1, keepdims=True)),
                        jnp.float32)                   # what RMSNorm hands on
        choice, _ = moe._scores(x, wr, b, k, "sigmoid", True)
        plain, _ = moe._scores(x, wr, None, k, "sigmoid", True)
        here = np.asarray(choice) < held
        touched.append(len(np.unique(np.asarray(choice)[here])))
        rows.append(int(here.sum()))
        changed.append(float((np.sort(choice, -1) != np.sort(plain, -1))
                             .any(-1).mean()))
    want = work_moe.experts_touched(cfg, 64)
    assert abs(np.mean(touched) - want) < 1.0, touched
    assert 0.6 * 64 * k * held / e < np.mean(rows) < 1.4 * 64 * k * held / e
    assert np.mean(changed) > 0.3, changed


def test_expert_roofline_share_reads_what_the_run_routed(loaded, monkeypatch):
    """The share's bytes are the window's own visits a tick times an
    expert's bytes; where the program notes none the metric is left out."""
    from chipbench import scopes, trace

    read = manifest.load_reader("moe_experts_hbm_util_pct")
    mod = "jit__paged_decode_impl"
    parsed = {"devices": {0: {
        trace.MODULES_LINE: [(mod + "(1)", 100, 1000), (mod + "(1)", 2000,
                                                        1000)],
        trace.OPS_LINE: [("fusion.7", 200, 300), ("fusion.9", 600, 300),
                         ("fusion.7", 2100, 300)]}}}
    monkeypatch.setattr(trace, "window_of", lambda p: (0, 4000))
    facts = {"trace": parsed, "config": loaded["config"],
             "traffic": loaded["traffic"],
             "scope_maps": {trace.module_stem(mod + "(1)"): {
                 "fusion.7": "moe/experts", "fusion.9": "attn"}},
             "peaks": {"hbm_bytes_per_s": 819e9},
             "_aligned_serve": {"spans": [("serve.readback", 0, 1, {})]}}
    assert read(facts) is None          # a program that notes nothing
    facts["_aligned_serve"]["spans"] = [
        ("serve.readback", 0, 1, {"moe_expert_visits": 40}),
        ("serve.deliver", 1, 2, {"moe_expert_visits": 999}),
        ("serve.readback", 2, 3, {"moe_expert_visits": 56})]
    # 48 experts a tick over 300 ns a run under moe/experts
    want = 100.0 * 48 * work_moe.expert_bytes(loaded["config"]) \
        / 300e-9 / 819e9
    assert read(facts) == pytest.approx(want)


TINY_MIMO = dict(
    vocab_size=96, hidden_size=64, num_attention_heads=4, head_dim=24,
    v_head_dim=16, swa_head_dim=24, swa_v_head_dim=16,
    num_key_value_heads=1, swa_num_key_value_heads=2, sliding_window=8,
    intermediate_size=128, moe_intermediate_size=32, n_routed_experts=16,
    num_experts_per_tok=4, held_n_routed_experts=4, first_held_expert=4,
    max_position_embeddings=64, serve_dtype="float32")
TINY_TRAFFIC = dict(tiny.TINY_TRAFFIC["tiny_backlog"],
                    driver="serve_ticks_by_leaf", check_prompt=21)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory, loaded):
    """``tiny.make_root`` plus the configuration at a toy size, a tiny mix
    for this cell's driver, and the cell that pairs them."""
    root = tiny.make_root(tmp_path_factory.mktemp("bench_mimo"))
    with open(os.path.join(root, "chipbench/configs/tiny-mimo.json"),
              "w") as f:
        json.dump(dict(loaded["config"], **TINY_MIMO), f)
    with open(os.path.join(root, manifest.traffic_path("tiny_backlog_leaf")),
              "w") as f:
        json.dump(TINY_TRAFFIC, f)
    man = manifest.load_manifest(root)
    man["configs"].append({
        "name": "tiny-mimo", "source": "test", "reduced": [],
        "file": "chipbench/configs/tiny-mimo.json", "why": "CPU test size"})
    man["workloads"].append({
        "name": "tiny_mimo_serve", "config": "tiny-mimo",
        "traffic": "tiny_backlog_leaf", "chips": 1, "why": "CPU test size"})
    for m in man["end_to_end"] + man["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny_mimo_serve")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    return root


def test_the_cells_driver_at_a_tiny_size(tiny_root):
    """``serve_ticks_by_leaf`` end to end on the CPU: a backlog through
    ``DecodeServer`` over both cache groups, then ``serve_ticks``' own
    comparison against the plain reference (a 21-token prompt in chunks of
    8 through a ring of 16, then 4 decoded positions), and the control."""
    import mxnet_tpu as mx
    from chipbench import control

    assert manifest.validate(manifest.load_manifest(tiny_root),
                             tiny_root) == []
    cell = manifest.load_cell("tiny_mimo_serve", root=tiny_root)
    counters = harness.CompileCounters().install()
    res = run.run_cell(cell, 2 ** 31 + 11, 1.0, False, [mx.cpu()], counters,
                       harness.Phases(), harness.MemoryPeak(1))
    assert all(c["ok"] for c in res["checks"]), res["checks"]
    assert res["checks"][0]["positions"] == 5
    # int8 keys and values against float32: far inside the cell's limit
    assert res["checks"][0]["max_abs_dlogp"] < 0.05
    assert res["failed"] == 0 and res["side"]["queue_left"] > 0
    assert res["side"]["requests_completed"] > 5
    assert counters.in_window == 0
    # what the cell's own metrics read: the decode program's counts a tick
    # in the arguments of the tick's serve.readback span, and their sums in
    # the process's counters
    from chipbench import spans

    notes = [a for name, _, _, a in spans.spans_of(spans.program_events())
             if name == "serve.readback" and "moe_rows_held" in a]
    counted = harness.program_counters()
    assert len(notes) == counted["mx_moe_calls_total{program=decode}"]
    assert sum(a["moe_rows_held"] for a in notes) \
        == counted["mx_moe_rows_total{program=decode,where=held}"]
    assert sum(a["moe_expert_visits"] for a in notes) \
        == counted["mx_moe_expert_visits_total{program=decode}"]
    read = manifest.load_reader("moe_held_rows_per_tick", tiny_root)
    window = {"_aligned_serve": {"spans": [
        ("serve.readback", 0, 1, a) for a in notes[-20:]]}}
    assert 0 < read(window) < 6 * 4 * cell["traffic"]["slots"]
    assert read({"_aligned_serve": None}) is None
    # other seeds draw other weights; the same seed the same
    from chipbench.drivers import serve_ticks_by_leaf as driver

    shapes = {"a_weight": (3, 4), "b_weight": (3, 4), "l_att_sink": (4,)}
    one = driver.make_params(shapes, cell["config"], 5, "float32")
    two = driver.make_params(shapes, cell["config"], 5, "float32")
    other = driver.make_params(shapes, cell["config"], 6, "float32")
    assert np.array_equal(one["a_weight"], two["a_weight"])
    assert not np.array_equal(one["a_weight"], one["b_weight"])
    assert not np.array_equal(one["a_weight"], other["a_weight"])
    assert float(np.abs(one["l_att_sink"]).max()) > 0.1     # std 1.0
    # the control runs on this driver's host-side weights: the reference
    # with its matrices rounded to bfloat16 reads off, and not by much at
    # this size
    assert 0 < control.reading(cell, 3) < 1


def test_readers_return_nothing_where_the_program_has_nothing(loaded):
    """On a program without the scopes and counters this PR adds (the
    parent's), the new readers leave their metric out and do not raise."""
    facts = {"trace": None, "config": loaded["config"],
             "traffic": loaded["traffic"], "peaks": {"hbm_bytes_per_s": 1}}
    assert manifest.load_reader("moe_experts_hbm_util_pct")(facts) is None
    assert manifest.load_reader("moe_device_pct.serve")(dict(facts)) is None
    assert manifest.load_reader("attn_window_device_pct.serve")(
        dict(facts)) is None


def test_existing_cells_import_nothing_of_this_configuration():
    """Importing the program and loading an accepted cell loads none of
    the modules only this configuration names, and compiles nothing."""
    code = """
import sys, jax
jax.config.update("jax_platforms", "cpu")
compiles = []
jax.monitoring.register_event_duration_secs_listener(
    lambda e, s, **_: compiles.append(e) if "backend_compile" in e else None)
import mxnet_tpu
from chipbench import run, manifest, harness
import chipbench.drivers.serve_ticks, chipbench.drivers.train_fit
for cell in ("opt_serve_backlog", "opt_train_t256", "rn50_train_bs256"):
    loaded = manifest.load_cell(cell)
    harness.build_symbol(loaded["config"])
late = [m for m in ("mxnet_tpu.models.decoder_lm",
                    "chipbench.reference.mimo_v2", "chipbench.work_moe",
                    "chipbench.drivers.serve_ticks_by_leaf")
        if m in sys.modules]
print("LATE", late, "COMPILES", len(compiles))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=manifest.ROOT)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "LATE [] COMPILES 0" in out.stdout, out.stdout
