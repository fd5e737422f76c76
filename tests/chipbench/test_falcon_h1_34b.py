"""Configuration ``falcon-h1-34b`` and its cell ``falconh1_serve_chat``: the
published numbers pinned, the cut's arithmetic, the counts module, what the
init rules give at the first block, the four readers, and the cell's own
loop driver at a tiny size on the CPU (the reference against the system
through ``serve_ticks``' comparison: a prompt that is a multiple neither of
the chunk nor of the scan's block, a state group beside pages)."""
import json
import math
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from chipbench import harness, manifest, run, work, work_ssm

import tiny

CELL, CONFIG = "falconh1_serve_chat", "falcon-h1-34b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SOURCE = "https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/" \
    "config.json"

# config.json of tiiuae/Falcon-H1-34B-Instruct as the catalog holds it
PUBLISHED = {
    "attention_bias": False, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804,
    "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 128,
    "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_expand": 2,
    "mamba_n_groups": 2, "mamba_n_heads": 32,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "model_type": "falcon_h1", "num_attention_heads": 20,
    "num_hidden_layers": 72, "num_key_value_heads": 4,
    "num_logits_to_keep": 1, "projectors_bias": False,
    "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845,
    "tie_word_embeddings": False, "vocab_size": 261120,
}


@pytest.fixture(scope="module")
def loaded():
    return manifest.load_cell(CELL)


def test_published_numbers(loaded):
    cfg = loaded["config"]
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    assert cfg["serve_num_hidden_layers"] == 6          # the cut
    assert cfg["serve_dtype"] == "bfloat16"
    assert cfg["ssm_state_dtype"] == "float32"
    assert cfg["builder"] == "mxnet_tpu.models.decoder_lm:get_symbol"
    assert cfg["reference"] == "chipbench.reference.falcon_h1"
    assert cfg["counts"] == {"decode_step_bytes":
                             "chipbench.work_ssm:hybrid_lm_decode_step_bytes"}
    assert "12-stage pipeline" in cfg["deployment"]
    for key in ("segment_order", "gated_norm", "dt", "rotary_pairing",
                "key_multiplier", "state_types", "init", "left_out"):
        assert cfg["assumed"][key]
    rules = {r["match"]: r for r in cfg["init"]}
    assert rules["_ssm_A_log$"] == {"match": "_ssm_A_log$", "dist": "uniform",
                                    "low": 0.0,
                                    "high": round(math.log(16), 6)}
    assert (rules["_ssm_dt_bias$"]["low"],
            rules["_ssm_dt_bias$"]["high"]) == (-6.9, -2.25)
    assert rules["_ssm_D$"] == {"match": "_ssm_D$", "dist": "const",
                                "value": 1.0}


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_catalog_key_at_its_published_value(loaded):
    with open(CATALOG) as f:
        entry = [e for e in map(json.loads, f)
                 if e["name"] == "Falcon-H1-34B-Instruct"][0]
    man = manifest.load_manifest()
    assert manifest.find(man["configs"], CONFIG, "config")["source"] \
        == entry["source_url"] == SOURCE
    assert entry["config"] == PUBLISHED
    for key, value in entry["config"].items():
        assert loaded["config"][key] == value, key


def test_manifest_entries(loaded):
    man = manifest.load_manifest()
    assert manifest.validate(man) == []
    entry = manifest.find(man["configs"], CONFIG, "config")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == SOURCE
    assert 1 <= len(entry["why"]) <= 200 and entry["why"].isascii() \
        and entry["why"].isprintable()
    cell = loaded["cell"]
    assert (cell["config"], cell["chips"]) == (CONFIG, 1)
    assert cell["traffic"] == "backlog_p128-1024_o128-512_s96"
    assert 1 <= len(cell["why"]) <= 200 and cell["why"].isascii()
    want = dict(driver="serve_ticks_by_leaf", loop="backlog", slots=96,
                cache_len=2048, page_tokens=16, prefill_chunk=256,
                max_prefill=1024, kv_dtype="int8", prompt_min=128,
                prompt_max=1024, output_min=128, output_max=512,
                requests=4096, block=64, order_seed=0, warmup_ticks=8,
                trace_seconds=30, trace_ticks=96, check_prompt=700,
                check_decode=8)
    assert {k: loaded["traffic"][k] for k in want} == want
    # the comparison's prompt: three chunks, the last 188 real tokens of 256
    assert 700 % 256 == 188 and 700 % 128
    # the cell reports what the accepted serving cell reports, and its own
    names = {m["name"] for m in loaded["per_layer"]}
    theirs = {m["name"] for m in manifest.load_cell("opt_serve_backlog")[
        "per_layer"]}
    assert names - theirs == {"ssm_device_pct.serve", "ssm_rows_per_tick",
                              "ssm_state_hbm_util_pct",
                              "ssm_scan_roofline_pct"}
    assert theirs <= names
    for m in loaded["per_layer"]:
        if m["name"] in names - theirs:
            assert m["workloads"] == [CELL]
    assert {m["name"] for m in loaded["end_to_end"]} == {
        "serve_out_tokens_per_s", "serve_gap_p95_ms", "setup_s"}
    lim = loaded["config"]["limits"]
    assert lim["serve_ticks"]["logp_atol.int8"]["value"] \
        == lim["serve_ticks_by_leaf"]["logp_atol.int8"]["value"]


def test_parameter_counts_and_memory(loaded):
    cfg, traffic = loaded["config"], loaded["traffic"]
    sym = harness.build_symbol(cfg)
    arg_shapes, _, _ = sym.infer_shape(data=(1, 8), softmax_label=(1, 8))
    shapes = {n: s for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in ("data", "softmax_label")}
    size = lambda pred: sum(int(np.prod(s)) for n, s in shapes.items()
                            if pred(n))
    layer0 = lambda *parts: size(lambda n: n.startswith("layer0_") and any(
        x in n for x in parts))
    assert shapes["layer0_ssm_in_weight"] == (9248, 5120)
    assert shapes["layer0_ssm_conv_weight"] == (5120, 4)
    assert shapes["layer0_q_weight"] == (2560, 5120)         # q width != d
    assert shapes["layer0_k_weight"] == (512, 5120)
    assert not [n for n in shapes if n.endswith("_bias")
                and "conv" not in n and "dt" not in n]
    assert layer0("_q_", "_k_", "_v_", "_attout_") == 31457280 \
        == work_ssm.attention_params(cfg)
    assert layer0("_ssm_") == 68351072 == work_ssm.mixer_params(cfg)
    assert layer0("_ssm_in_") == 47349760 and layer0("_ssm_out_") == 20971520
    assert layer0("_ffn_gate", "_ffn_up", "_ffn_down") == 330301440 \
        == work_ssm.mlp_params(cfg)
    assert layer0("_norm_gamma") - 4096 == 10240     # the block's two norms
    assert size(lambda n: n.startswith("layer0_")) == 430120032 \
        == work_ssm.block_params(cfg)
    assert size(lambda n: n in ("embed_weight", "head_weight")) \
        == 2 * 261120 * 5120
    total = size(lambda n: True)
    assert total == work_ssm.model_params(cfg)
    assert round(total / 1e6, 1) == 5254.6
    assert round(2 * total / 1e9, 2) == 10.51           # bfloat16
    # what a slot holds: 6 x (32 x 128 x 256 float32 + 3 x 5120 bfloat16)
    assert work_ssm.state_row_bytes(cfg) == (4194304, 30720)
    assert round(6 * sum(work_ssm.state_row_bytes(cfg)) / 1e6, 2) == 25.35
    assert work_ssm.kv_bytes_per_token(cfg, 1) == 1056
    assert 4194304 // 1056 == 3971      # tokens of keys one state row costs
    slots, cache = traffic["slots"], traffic["cache_len"]
    state_gb = slots * 6 * sum(work_ssm.state_row_bytes(cfg)) / 1e9
    kv_gb = 6 * (slots * cache + traffic["page_tokens"]) * 1056 / 1e9
    assert round(state_gb, 2) == 2.43 and round(kv_gb, 2) == 1.25
    assert round(2 * total / 1e9 + state_gb + kv_gb, 2) == 14.19


def test_counts(loaded):
    cfg, traffic = loaded["config"], loaded["traffic"]
    live = 96 * 800
    total = work.decode_step_bytes(cfg, traffic, live)
    weights = 2 * (6 * 430120032 + 261120 * 5120 + 5120)
    state = 6 * 96 * 2 * (4194304 + 30720)
    keys = 6 * live * 1056
    assert total == weights + state + keys
    assert [round(x / 1e9, 2) for x in (weights, state, keys)] \
        == [7.84, 4.87, 0.49]
    assert work_ssm.state_step_bytes(cfg) == 2 * 4194304 + 2 * 30720
    # a chunk of 256: two whole blocks of 128; of 188: one and 60 tokens
    h, p, n, g = 32, 128, 256, 2
    for tokens, pairs in ((256, 2 * 128 * 129 // 2),
                          (188, 128 * 129 // 2 + 60 * 61 // 2)):
        flops, moved = work_ssm.chunk_scan_work(cfg, tokens)
        assert flops == 2 * 4 * 5120 * tokens + 2 * pairs * (g * n + h * p) \
            + 4 * tokens * h * p * n
        assert moved == 2 * (4194304 + 30720) + tokens * (5120 + 32 + 4096) * 2
    flops, moved = work_ssm.chunk_scan_work(cfg, 256)
    # bound by its bytes: 16 us a layer against 6 us of products
    assert moved / 819e9 > flops / 197e12


TINY_FALCON = dict(
    vocab_size=96, hidden_size=64, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, intermediate_size=128,
    mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16, mamba_d_state=8,
    mamba_n_groups=2, mamba_chunk_size=8, max_position_embeddings=64,
    serve_num_hidden_layers=3, num_hidden_layers=3, serve_dtype="float32")
TINY_TRAFFIC = dict(tiny.TINY_TRAFFIC["tiny_backlog"],
                    driver="serve_ticks_by_leaf", check_prompt=21)


def tiny_config(cfg):
    """The configuration at the toy's widths, each matrix's standard
    deviation carried over by its fan-in (``std x sqrt(fan_in)`` is what
    the published multipliers were balanced against)."""
    big = harness.build_symbol(cfg)
    shapes, _, _ = big.infer_shape(data=(1, 8), softmax_label=(1, 8))
    fan_in = dict(zip(big.list_arguments(), (s[-1] for s in shapes)))
    out = dict(cfg, **TINY_FALCON)
    small = harness.build_symbol(out)
    shapes, _, _ = small.infer_shape(data=(1, 8), softmax_label=(1, 8))
    toy_fan = dict(zip(small.list_arguments(), (s[-1] for s in shapes)))
    import re

    # one rule a matrix, ahead of the file's: the published rule's std
    # times sqrt(published fan-in / the toy's)
    init = []
    for name in toy_fan:
        if not name.endswith("_weight") or "conv" in name \
                or name == "embed_weight":       # rows looked up: no fan-in
            continue
        rule = next(r for r in cfg["init"] if re.search(r["match"], name))
        assert rule["dist"] == "normal", name
        init.append(dict(rule, match="^%s$" % name, std=rule["std"]
                         * math.sqrt(fan_in[name] / toy_fan[name])))
    init += cfg["init"]
    return dict(out, init=init)


def test_the_init_keeps_every_branch_and_the_logits_in_range(loaded):
    """With the published multipliers, the init rules leave the mixer's,
    attention's and the MLP's outputs each within a factor of three of the
    residual stream at the first block, and the logits' standard deviation
    between 1 and 3 (He-normal matrices would leave the branches at a
    thousandth and the log-probabilities flat).  At the toy's widths, the
    stds carried over by fan-in; the file's ``assumed.init`` has the
    readings at the published widths."""
    import jax
    import jax.numpy as jnp

    from chipbench import weights
    from chipbench.reference import falcon_h1 as ref

    cfg = tiny_config(loaded["config"])
    sym = harness.build_symbol(cfg)
    arg_shapes, _, _ = sym.infer_shape(data=(1, 8), softmax_label=(1, 8))
    shapes = {n: s for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in ("data", "softmax_label")}
    p = weights.make_params(shapes, cfg, 11, "float32")
    assert 0.0 <= float(p["layer0_ssm_A_log"].min()) \
        and float(p["layer0_ssm_A_log"].max()) <= math.log(16)
    assert -6.9 <= float(p["layer0_ssm_dt_bias"].min()) \
        and float(p["layer0_ssm_dt_bias"].max()) <= -2.25
    assert np.array_equal(p["layer0_ssm_D"], np.ones(4, np.float32))
    toks = np.random.default_rng(0).integers(0, 96, size=(4, 48))
    rms = lambda x: float(jnp.sqrt(jnp.mean(x * x)))
    n = "layer0_"
    with jax.default_matmul_precision("highest"):
        h = jnp.take(p["embed_weight"], toks, axis=0) \
            * cfg["embedding_multiplier"]
        u = ref._rms(h, p[n + "att_norm_gamma"], cfg["rms_norm_eps"])
        m = ref._mixer(p, n, cfg, u * cfg["ssm_in_multiplier"]) \
            * cfg["ssm_out_multiplier"]
        a = ref._attention(p, n, cfg, u * cfg["attention_in_multiplier"]) \
            * cfg["attention_out_multiplier"]
        logits = ref.forward(p, cfg, toks)
        one = ref.forward(p, cfg, toks, layers=1)
    stream = rms(h)
    assert 0.7 < stream < 1.4
    for branch in (m, a):
        assert stream / 3 < rms(branch) < 3 * stream, (rms(m), rms(a))
    # the MLP's part of the first block: what one layer adds past m and a
    assert 1.0 < float(jnp.std(logits)) < 3.0, float(jnp.std(logits))
    assert 1.0 < float(jnp.std(one)) < 3.0
    logp = jax.nn.log_softmax(logits, -1)
    assert float(jnp.max(logp) - jnp.min(logp)) > 5.0       # not flat


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory, loaded):
    """``tiny.make_root`` plus the configuration at a toy size, a tiny mix
    for this cell's driver, and the cell that pairs them."""
    root = tiny.make_root(tmp_path_factory.mktemp("bench_falcon"))
    with open(os.path.join(root, "chipbench/configs/tiny-falcon.json"),
              "w") as f:
        json.dump(tiny_config(loaded["config"]), f)
    with open(os.path.join(root, manifest.traffic_path("tiny_backlog_leaf")),
              "w") as f:
        json.dump(TINY_TRAFFIC, f)
    man = manifest.load_manifest(root)
    man["configs"].append({
        "name": "tiny-falcon", "source": "test", "reduced": [],
        "file": "chipbench/configs/tiny-falcon.json", "why": "CPU test size"})
    man["workloads"].append({
        "name": "tiny_falcon_serve", "config": "tiny-falcon",
        "traffic": "tiny_backlog_leaf", "chips": 1, "why": "CPU test size"})
    for m in man["end_to_end"] + man["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny_falcon_serve")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    return root


def test_the_cells_driver_at_a_tiny_size(tiny_root):
    """``serve_ticks_by_leaf`` end to end on the CPU: a backlog through
    ``DecodeServer`` over a page group and a state group, then
    ``serve_ticks``' own comparison against the plain reference (a 21-token
    prompt in chunks of 8, the scan in blocks of 8, the last chunk 5 real
    tokens; then 4 decoded positions; the other three slots one token
    each), and the control."""
    import mxnet_tpu as mx
    from chipbench import control, spans

    assert manifest.validate(manifest.load_manifest(tiny_root),
                             tiny_root) == []
    cell = manifest.load_cell("tiny_falcon_serve", root=tiny_root)
    counters = harness.CompileCounters().install()
    began = time.perf_counter_ns()
    res = run.run_cell(cell, 2 ** 31 + 13, 1.0, False, [mx.cpu()], counters,
                       harness.Phases(), harness.MemoryPeak(1))
    assert all(c["ok"] for c in res["checks"]), res["checks"]
    assert res["checks"][0]["positions"] == 5
    # int8 keys and values against float32 (0.07 to 0.12 over seeds at
    # this size, heads of 16; the float pool reads 1e-5): inside the limit
    assert res["checks"][0]["max_abs_dlogp"] < 0.25
    assert res["failed"] == 0 and res["side"]["queue_left"] > 0
    assert res["side"]["requests_completed"] > 5
    assert counters.in_window == 0
    # what the cell's own metrics read: the rows a decode tick advanced, in
    # the arguments of the tick's serve.readback span, and the real tokens
    # of each chunk in its serve.prefill span
    # (this run's spans: the ring is the process's, other tests' are in it)
    every = [sp for sp in spans.spans_of(spans.program_events())
             if sp[1] >= began]
    notes = [a for name, _, _, a in every
             if name == "serve.readback" and "ssm_rows" in a]
    slots = cell["traffic"]["slots"]
    assert notes and all(0 < a["ssm_rows"] <= 3 * slots for a in notes)
    assert max(a["ssm_rows"] for a in notes) == 3 * slots
    read = manifest.load_reader("ssm_rows_per_tick", tiny_root)
    window = {"_aligned_serve": {"spans": [
        ("serve.readback", 0, 1, a) for a in notes[-20:]]}}
    assert 0 < read(window) <= 3 * slots
    assert read({"_aligned_serve": None}) is None
    chunks = [a["tokens"] for name, _, _, a in every
              if name == "serve.prefill"]
    assert chunks and max(chunks) == 8 and min(chunks) < 8
    from mxnet_tpu import obs

    snap = obs.registry.snapshot()
    assert snap["mx_ssm_rows_total"]["series"][0]["value"] \
        >= sum(a["ssm_rows"] for a in notes)
    assert snap["mx_ssm_chunk_tokens_total"]["series"][0]["value"] > 0
    assert snap["mx_ssm_state_bytes"]["series"][0]["value"] \
        == slots * 3 * (4 * 16 * 8 * 4 + 3 * 96 * 4)
    # the control runs on this driver's host-side weights: the reference
    # with its matrices rounded to bfloat16 reads off
    assert 0 < control.reading(cell, 3) < 1


def _facts(loaded, spans_, ops, maps):
    from chipbench import trace

    dec, chk = "jit__paged_decode_impl", "jit__chunk_impl"
    parsed = {"devices": {0: {
        trace.MODULES_LINE: [(dec + "(1)", 100, 1000), (chk + "(2)", 1500,
                                                        400),
                             (dec + "(1)", 2000, 1000)],
        trace.OPS_LINE: ops}}}
    return {"trace": parsed, "config": loaded["config"],
            "traffic": loaded["traffic"],
            "scope_maps": {trace.module_stem(dec + "(1)"): maps[0],
                           trace.module_stem(chk + "(2)"): maps[1]},
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
            "_aligned_serve": {"spans": spans_}}


def test_the_two_rooflines_read_what_the_window_shows(loaded, monkeypatch):
    """``ssm_state_hbm_util_pct``: the window's rows a tick times one row's
    bytes over the decode program's time under ``ssm/step`` a run;
    ``ssm_scan_roofline_pct``: the chunks' own floor over the chunk
    program's time under ``ssm/scan`` and ``ssm/conv``."""
    from chipbench import trace

    monkeypatch.setattr(trace, "window_of", lambda p: (0, 4000))
    cfg = loaded["config"]
    ops = [("fusion.7", 200, 300), ("fusion.9", 600, 300),
           ("fusion.3", 1550, 100), ("fusion.4", 1700, 60),
           ("fusion.5", 1800, 90), ("fusion.7", 2100, 300)]
    maps = ({"fusion.7": "ssm/step", "fusion.9": "attn"},
            {"fusion.3": "ssm/scan", "fusion.4": "ssm/conv",
             "fusion.5": "linear"})
    spans_ = [("serve.readback", 0, 1, {"ssm_rows": 500}),
              ("serve.prefill", 1, 2, {"tokens": 188}),
              ("serve.readback", 2, 3, {"ssm_rows": 576})]
    facts = _facts(loaded, spans_, ops, maps)
    state = manifest.load_reader("ssm_state_hbm_util_pct")
    scan = manifest.load_reader("ssm_scan_roofline_pct")
    assert state(dict(facts)) == pytest.approx(
        100.0 * 538 * (2 * 4194304 + 2 * 30720) / 300e-9 / 819e9)
    flops, moved = work_ssm.chunk_scan_work(cfg, 188)
    assert scan(dict(facts)) == pytest.approx(
        100.0 * 6 * max(flops / 197e12, moved / 819e9) / 160e-9)
    assert work_ssm.scope_seconds(facts, r"paged_decode", {"ssm/step"}) \
        == (600e-9, 2)


def test_readers_return_nothing_where_the_program_has_nothing(loaded,
                                                              monkeypatch):
    """On a program without the scopes and counters this PR adds (the
    parent's), the four readers leave their metric out and do not raise."""
    from chipbench import trace

    names = ("ssm_device_pct.serve", "ssm_rows_per_tick",
             "ssm_state_hbm_util_pct", "ssm_scan_roofline_pct")
    bare = {"trace": None, "config": loaded["config"],
            "traffic": loaded["traffic"],
            "peaks": {"hbm_bytes_per_s": 1, "bf16_flops_per_s": 1},
            "_aligned_serve": None, "scope_maps": None}
    for name in names:
        assert manifest.load_reader(name)(dict(bare)) is None, name
    # a traced parent: programs with maps, none of them with an ssm scope,
    # spans without the new arguments
    monkeypatch.setattr(trace, "window_of", lambda p: (0, 4000))
    ops = [("fusion.7", 200, 300), ("fusion.3", 1550, 100)]
    maps = ({"fusion.7": "attn"}, {"fusion.3": "linear"})
    spans_ = [("serve.readback", 0, 1, {"attn_blocks_live": 4}),
              ("serve.prefill", 1, 2, {"tokens": 188})]
    for name in names:
        facts = _facts(loaded, spans_, ops, maps)
        assert manifest.load_reader(name)(facts) is None, name


def test_existing_cells_import_nothing_of_this_configuration():
    """Importing the program and loading an accepted cell loads none of
    the modules only this configuration (or MiMo's) names, and compiles
    nothing."""
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
                    "chipbench.reference.falcon_h1", "chipbench.work_ssm",
                    "chipbench.drivers.serve_ticks_by_leaf")
        if m in sys.modules]
print("LATE", late, "COMPILES", len(compiles))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=manifest.ROOT)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "LATE [] COMPILES 0" in out.stdout, out.stdout
