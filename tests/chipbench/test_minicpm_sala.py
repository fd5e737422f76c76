"""Configuration ``minicpm-sala`` and its cell ``sala_serve_longctx``: the
published numbers pinned, the cut's arithmetic, the counts module, what the
init rules give at the first built layers, the seven readers, and the cell's
own loop driver at a tiny size on the CPU (the reference against the system
through ``serve_ticks``' comparison: both layer kinds, a ``dense_len`` small
enough to be crossed inside the comparison's prompt, the index and the state
rows beside the pages)."""
import json
import math
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

from chipbench import harness, manifest, run, work, work_sala

import tiny

CELL, CONFIG = "sala_serve_longctx", "minicpm-sala"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SOURCE = "https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json"
MIXERS = ["minicpm4"] + ["lightning-attn"] * 8 + ["minicpm4"] \
    + ["lightning-attn"] * 6 + ["minicpm4"] * 2 + ["lightning-attn"] * 4 \
    + ["minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"] * 3

# config.json of openbmb/MiniCPM-SALA as the catalog holds it
PUBLISHED = {
    "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 16384,
    "lightning_head_dim": 128, "lightning_nh": 32, "lightning_nkv": 32,
    "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
    "max_position_embeddings": 524288, "model_type": "minicpm_sala",
    "mixer_types": MIXERS, "num_attention_heads": 32,
    "num_hidden_layers": 32, "num_key_value_heads": 2, "qk_norm": True,
    "rand_init": False, "rms_norm_eps": 1e-06, "vocab_size": 73448,
    "rope_theta": 10000, "scale_emb": 12, "scale_depth": 1.4,
    "mup_denominator": 32, "dim_model_base": 256,
    "tie_word_embeddings": False, "use_output_gate": True,
    "use_output_norm": True, "attn_use_output_gate": True,
}
SPARSE = {"kernel_size": 32, "kernel_stride": 16, "init_blocks": 1,
          "block_size": 64, "window_size": 2048, "topk": 64,
          "dense_len": 8192}


@pytest.fixture(scope="module")
def loaded():
    return manifest.load_cell(CELL)


def test_published_numbers(loaded):
    cfg = loaded["config"]
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    assert len(MIXERS) == 32 and MIXERS.count("minicpm4") == 8
    assert [i for i, m in enumerate(MIXERS) if m == "minicpm4"] \
        == [0, 9, 16, 17, 22, 29, 30, 31]
    assert cfg["sparse_config"] == SPARSE
    # the cut: layers 9-20 at their published indices, 3 sparse to 9
    assert (cfg["serve_first_layer"], cfg["serve_num_hidden_layers"]) \
        == (9, 12)
    assert work_sala.layers_run(cfg) == (3, 9)
    run_ = MIXERS[9:21]
    assert [i + 9 for i, m in enumerate(run_) if m == "minicpm4"] \
        == [9, 16, 17]
    assert cfg["serve_dtype"] == "bfloat16"
    assert cfg["builder"] == "mxnet_tpu.models.decoder_lm:get_symbol"
    assert cfg["reference"] == "chipbench.reference.minicpm_sala"
    assert cfg["counts"] == {"decode_step_bytes":
                             "chipbench.work_sala:decode_step_bytes"}
    assert "12 consecutive" in cfg["deployment"] \
        and "32/12" in cfg["deployment"]
    for key in ("sparse_config", "qk_norm", "output_norm", "decay",
                "block_score", "always_taken", "index", "rotary_pairing",
                "mup_denominator", "lightning_nkv", "lightning_scale", "init",
                "left_out", "serve_num_hidden_layers"):
        assert cfg["assumed"][key], key


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_catalog_key_at_its_published_value(loaded):
    with open(CATALOG) as f:
        entry = [e for e in map(json.loads, f)
                 if e["name"] == "MiniCPM-SALA"][0]
    man = manifest.load_manifest()
    assert manifest.find(man["configs"], CONFIG, "config")["source"] \
        == entry["source_url"] == SOURCE
    assert entry["config"] == PUBLISHED
    for key, value in entry["config"].items():
        assert loaded["config"][key] == value, key


def test_manifest_entries(loaded):
    man = manifest.load_manifest()
    assert manifest.validate(man) == []
    assert len(man["workloads"]) == 9 \
        and sum(w["chips"] == 4 for w in man["workloads"]) == 1
    entry = manifest.find(man["configs"], CONFIG, "config")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == SOURCE
    assert 1 <= len(entry["why"]) <= 200 and entry["why"].isascii() \
        and entry["why"].isprintable()
    cell = loaded["cell"]
    assert (cell["config"], cell["chips"]) == (CONFIG, 1)
    assert cell["traffic"] == "backlog_p16384-65536_o256-1024_s24"
    assert 1 <= len(cell["why"]) <= 200 and cell["why"].isascii()
    assert "weights bound" in cell["why"]
    want = dict(driver="serve_ticks_by_leaf", loop="backlog", slots=24,
                cache_len=66560, page_tokens=16, prefill_chunk=2048,
                max_prefill=65536, kv_dtype="int8", prompt_min=16384,
                prompt_max=65536, output_min=256, output_max=1024,
                requests=192, block=64, order_seed=0, warmup_ticks=8,
                trace_seconds=30, trace_ticks=96, check_prompt=12288,
                check_decode=8)
    assert {k: loaded["traffic"][k] for k in want} == want
    # the page is the compression's stride; the longest request fits a slot;
    # the comparison's prompt lies 4096 past dense_len, whole chunks
    assert want["page_tokens"] == SPARSE["kernel_stride"]
    assert want["prompt_max"] + want["output_max"] == want["cache_len"]
    assert want["check_prompt"] == SPARSE["dense_len"] + 4096 \
        and want["check_prompt"] % want["prefill_chunk"] == 0
    # the cell reports what the accepted serving cells report, and its own
    names = {m["name"] for m in loaded["per_layer"]}
    theirs = {m["name"] for m in manifest.load_cell("opt_serve_backlog")[
        "per_layer"]}
    mine = {"linattn_device_pct.serve", "attn_sparse_device_pct.serve",
            "linattn_step_device_pct.serve", "linattn_chunk_roofline_pct",
            "attn_sparse_hbm_util_pct", "sparse_chosen_share_pct",
            "linattn_rows_per_tick"}
    assert names - theirs == mine and theirs <= names
    for m in loaded["per_layer"]:
        if m["name"] in mine:
            assert m["workloads"] == [CELL]
            assert m["layer"] == ("serving loop" if m["name"]
                                  == "linattn_rows_per_tick"
                                  else "kernels, serving")
    # no share of a bandwidth is claimed for the state's step: its bytes
    # move beside other layers' work (PERF.md section 3)
    assert not any("linattn_state" in n for n in names)
    assert {m["name"] for m in loaded["end_to_end"]} == {
        "serve_out_tokens_per_s", "serve_gap_p95_ms", "setup_s"}
    lim = loaded["config"]["limits"]
    assert lim["serve_ticks"]["logp_atol.int8"]["value"] \
        == lim["serve_ticks_by_leaf"]["logp_atol.int8"]["value"]
    # the other configurations' metrics stay where they were
    for name in ("ssm_rows_per_tick", "moe_device_pct.serve",
                 "attn_window_device_pct.serve"):
        assert CELL not in manifest.find(man["per_layer"], name,
                                         "metric")["workloads"]


def test_parameter_counts_and_memory(loaded):
    cfg, traffic = loaded["config"], loaded["traffic"]
    sym = harness.build_symbol(cfg)
    arg_shapes, _, _ = sym.infer_shape(data=(1, 8), softmax_label=(1, 8))
    shapes = {n: s for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in ("data", "softmax_label")}
    size = lambda pred: sum(int(np.prod(s)) for n, s in shapes.items()
                            if pred(n))
    layers = sorted({int(re.match(r"layer(\d+)_", n).group(1))
                     for n in shapes if n.startswith("layer")})
    assert layers == list(range(9, 21))
    assert shapes["layer9_q_weight"] == (4096, 4096)
    assert shapes["layer9_k_weight"] == shapes["layer9_v_weight"] \
        == (256, 4096)
    assert shapes["layer9_gate_weight"] == (4096, 4096)
    assert shapes["layer10_lin_q_norm_gamma"] == (128,)
    assert shapes["layer10_lin_out_norm_gamma"] == (4096,)
    assert not [n for n in shapes if n.endswith("_bias")]
    layer = lambda l: size(lambda n: n.startswith("layer%d_" % l))
    mlp = 3 * 4096 * 16384
    assert layer(10) == 5 * 4096 ** 2 + mlp + 2 * 128 + 4096 + 2 * 4096 \
        == work_sala.lightning_params(cfg) + work_sala.mlp_params(cfg) + 8192
    assert layer(9) == 3 * 4096 ** 2 + 2 * 4096 * 256 + mlp + 2 * 4096 \
        == work_sala.sparse_params(cfg) + work_sala.mlp_params(cfg) + 8192
    assert round(layer(10) / 1e6, 1) == 285.2
    assert round(layer(9) / 1e6, 1) == 253.8
    assert {l for l in layers if layer(l) == layer(9)} == {9, 16, 17}
    assert size(lambda n: n in ("embed_weight", "head_weight")) \
        == 2 * 73448 * 4096
    total = size(lambda n: True)
    assert total == work_sala.model_params(cfg)
    assert round(total / 1e9, 2) == 3.93
    assert round(2 * total / 1e9, 2) == 7.86            # bfloat16
    # what a slot holds: 9 x 32 x 128 x 128 float32; a cached token
    assert work_sala.state_row_bytes(cfg) == 32 * 128 * 128 * 4 == 2097152
    assert round(9 * work_sala.state_row_bytes(cfg) / 1e6, 1) == 18.9
    per_token = 2 * work_sala.kv_position_bytes(cfg, 1)
    assert per_token == 512 + 16
    assert 2 * work_sala.index_row_bytes(cfg) // 16 == 32
    slots, cache = traffic["slots"], traffic["cache_len"]
    pages = slots * cache // 16 + 1
    pools_gb = 3 * pages * 16 * per_token / 1e9
    index_gb = 3 * pages * 2 * work_sala.index_row_bytes(cfg) / 1e9
    state_gb = slots * 9 * work_sala.state_row_bytes(cfg) / 1e9
    assert [round(x, 2) for x in (pools_gb, index_gb, state_gb)] \
        == [2.53, 0.15, 0.45]
    assert round(2 * total / 1e9 + pools_gb + index_gb + state_gb, 1) == 11.0
    # twelve full-attention layers at this traffic would hold four times
    # the pages
    assert round(12 * pages * 16 * per_token / 1e9, 1) == 10.1


def test_counts(loaded):
    cfg, traffic = loaded["config"], loaded["traffic"]
    live = 24 * 40000
    total = work.decode_step_bytes(cfg, traffic, live)
    weights = 2 * (work_sala.model_params(cfg) - 73448 * 4096)
    state = 9 * 24 * 2 * 2097152
    rows = (40000 - 32) // 16 + 1
    selected = 3 * 24 * 2 * (64 * 64 * 264 + rows * 256)
    assert total == weights + state + selected
    assert [round(x / 1e9, 2) for x in (weights, state, selected)] \
        == [7.26, 0.91, 0.25]
    # under dense_len: every position, no index
    assert work_sala.attended(cfg, 8192) == (8192, 0)
    assert work_sala.attended(cfg, 8193) == (4096, 511)
    assert work.decode_step_bytes(cfg, traffic, 24 * 8000) \
        == weights + state + 3 * 24 * 2 * 8000 * 264
    assert work_sala.lightning_step_work(cfg) == (5 * 32 * 128 * 128,
                                                  2 * 2097152)
    # a chunk of 2048: eight whole blocks of 256; of 300: one and 44 tokens
    for tokens, pairs in ((2048, 8 * 256 * 257 // 2),
                          (300, 256 * 257 // 2 + 44 * 45 // 2)):
        flops, moved = work_sala.lightning_chunk_work(cfg, tokens)
        assert flops == 2 * pairs * 2 * 128 * 32 \
            + 4 * tokens * 32 * 128 * 128
        assert moved == 2 * 2097152 + 5 * tokens * 4096 * 2
    flops, moved = work_sala.lightning_chunk_work(cfg, 2048)
    # bound by its bytes: 107 us a layer against 50 us of products
    assert moved / 819e9 > flops / 197e12
    assert work_sala.index_score_work(cfg, 40000) == (
        2 * 32 * 128 * rows, 2 * rows * 256)
    assert work_sala.chosen_attend_work(cfg, 40000) == (
        4 * 32 * 128 * 4096, 2 * 4096 * 264)
    assert work_sala.selected_bytes(cfg, 10, 100) \
        == 10 * 64 * 264 + 100 * 4 * 256


TINY_SALA = dict(
    vocab_size=96, hidden_size=64, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, intermediate_size=128,
    lightning_nh=4, lightning_head_dim=16, dim_model_base=4,
    max_position_embeddings=128,
    sparse_config=dict(kernel_size=8, kernel_stride=4, init_blocks=1,
                       block_size=8, window_size=16, topk=4, dense_len=24),
    serve_num_hidden_layers=3, serve_dtype="float32")  # layers 9, 10, 11
TINY_TRAFFIC = dict(tiny.TINY_TRAFFIC["tiny_backlog"],
                    driver="serve_ticks_by_leaf", page_tokens=4,
                    cache_len=64, check_prompt=37, check_decode=6,
                    prompt_min=20, prompt_max=40, max_prefill=40)


def tiny_config(cfg):
    """The configuration at the toy's widths, each matrix's standard
    deviation carried over by its fan-in (``std x sqrt(fan_in)`` is what
    the init rules were balanced at)."""
    big = harness.build_symbol(cfg)
    shapes, _, _ = big.infer_shape(data=(1, 8), softmax_label=(1, 8))
    fan_in = {re.sub(r"^layer\d+", "layer", n): s[-1]
              for n, s in zip(big.list_arguments(), shapes)}
    out = dict(cfg, **TINY_SALA)
    small = harness.build_symbol(out)
    shapes, _, _ = small.infer_shape(data=(1, 8), softmax_label=(1, 8))
    init = []
    for name, shape in zip(small.list_arguments(), shapes):
        if not name.endswith("_weight") or name == "embed_weight":
            continue                             # rows looked up: no fan-in
        rule = next(r for r in cfg["init"] if re.search(r["match"], name))
        assert rule["dist"] == "normal", name
        init.append(dict(rule, match="^%s$" % name, std=rule["std"]
                         * math.sqrt(fan_in[re.sub(r"^layer\d+", "layer",
                                                   name)] / shape[-1])))
    return dict(out, init=init + cfg["init"])


def test_the_init_keeps_every_branch_and_the_logits_in_range(loaded):
    """The init rules leave, at the first built sparse layer and the first
    lightning layer, the lightning mixer's and the MLP's parts each within a
    factor of two of a stream of order 1, and the logits' standard deviation
    between 1 and 3.  The sparse layer's part is held SMALL on purpose, a
    twentieth to one times the stream over 48 positions (the file's
    ``assumed.init``: 0.63 over the first 10 positions, 0.18 over the last
    50 of 600, 0.08 over the 4096 positions of 64 chosen blocks): which
    blocks a row chooses is discrete, a choice that flips under bfloat16 or
    int8 rounding changes that row's output by a fifth, and the lightning
    layers carry it to every later position, so at an output matrix eight
    times larger the comparison read 2.0 to 2.8 where it now reads 0.5
    (PERF.md section 6, PR 43).  At the toy's widths, the stds carried over
    by fan-in, at the published depth (1.4 / sqrt(32) a branch)."""
    import jax
    import jax.numpy as jnp

    from chipbench import weights
    from chipbench.reference import minicpm_sala as ref

    cfg = tiny_config(loaded["config"])
    sym = harness.build_symbol(cfg)
    arg_shapes, _, _ = sym.infer_shape(data=(1, 8), softmax_label=(1, 8))
    shapes = {n: s for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in ("data", "softmax_label")}
    p = weights.make_params(shapes, cfg, 11, "float32")
    toks = np.random.default_rng(0).integers(0, 96, size=(4, 48))
    rms = lambda x: float(jnp.sqrt(jnp.mean(x * x)))
    c = cfg["scale_depth"] / cfg["num_hidden_layers"] ** 0.5
    with jax.default_matmul_precision("highest"):
        h = jnp.take(p["embed_weight"], toks, axis=0) * cfg["scale_emb"]
        stream = rms(h)
        assert 0.7 < stream < 1.4
        u = ref._rms(h, p["layer9_att_norm_gamma"], cfg["rms_norm_eps"])
        lin = c * ref._lightning(p, "layer10_", cfg, u, 10)
        att = c * ref._sparse_attention(p, "layer9_", cfg, u)
        mlp = c * ((jax.nn.silu(u @ p["layer9_ffn_gate_weight"].T)
                    * (u @ p["layer9_ffn_up_weight"].T))
                   @ p["layer9_ffn_down_weight"].T)
        logits = ref.forward(p, cfg, toks)
    assert c == pytest.approx(1.4 / 32 ** 0.5)
    for branch in (lin, mlp):
        assert stream / 2 < rms(branch) < 2 * stream, (rms(lin), rms(mlp))
    assert stream / 20 < rms(att) < stream, rms(att)
    assert 1.0 < float(jnp.std(logits)) < 3.0, float(jnp.std(logits))
    logp = jax.nn.log_softmax(logits, -1)
    assert float(jnp.max(logp) - jnp.min(logp)) > 5.0       # not flat


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory, loaded):
    """``tiny.make_root`` plus the configuration at a toy size, a tiny mix
    for this cell's driver, and the cell that pairs them."""
    root = tiny.make_root(tmp_path_factory.mktemp("bench_sala"))
    with open(os.path.join(root, "chipbench/configs/tiny-sala.json"),
              "w") as f:
        json.dump(tiny_config(loaded["config"]), f)
    with open(os.path.join(root, manifest.traffic_path("tiny_backlog_sala")),
              "w") as f:
        json.dump(TINY_TRAFFIC, f)
    man = manifest.load_manifest(root)
    man["configs"].append({
        "name": "tiny-sala", "source": "test", "reduced": [],
        "file": "chipbench/configs/tiny-sala.json", "why": "CPU test size"})
    man["workloads"].append({
        "name": "tiny_sala_serve", "config": "tiny-sala",
        "traffic": "tiny_backlog_sala", "chips": 1, "why": "CPU test size"})
    for m in man["end_to_end"] + man["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny_sala_serve")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    return root


def test_the_cells_driver_at_a_tiny_size(tiny_root):
    """``serve_ticks_by_leaf`` end to end on the CPU: a backlog through
    ``DecodeServer`` over a page group with an index and a state group, then
    ``serve_ticks``' own comparison against the plain reference (a 37-token
    prompt in chunks of 8, ``dense_len`` 24 crossed in the fourth, the last
    chunk 5 real tokens; then 6 decoded positions that each choose 4 of
    their 5 or 6 blocks), and the control."""
    import mxnet_tpu as mx
    from chipbench import control, spans

    assert manifest.validate(manifest.load_manifest(tiny_root),
                             tiny_root) == []
    cell = manifest.load_cell("tiny_sala_serve", root=tiny_root)
    counters = harness.CompileCounters().install()
    began = time.perf_counter_ns()
    res = run.run_cell(cell, 2 ** 31 + 13, 1.0, False, [mx.cpu()], counters,
                       harness.Phases(), harness.MemoryPeak(1))
    assert all(c["ok"] for c in res["checks"]), res["checks"]
    assert res["checks"][0]["positions"] == 7
    assert res["failed"] == 0 and res["side"]["queue_left"] > 0
    assert res["side"]["requests_completed"] > 3
    assert counters.in_window == 0
    # what the cell's own metrics read, in the arguments of each tick's
    # serve.readback span (this run's spans: the ring is the process's)
    every = [sp for sp in spans.spans_of(spans.program_events())
             if sp[1] >= began]
    notes = [a for name, _, _, a in every
             if name == "serve.readback" and "linattn_rows" in a]
    slots = cell["traffic"]["slots"]
    assert notes and all(0 < a["linattn_rows"] <= 2 * slots for a in notes)
    assert max(a["linattn_rows"] for a in notes) == 2 * slots
    assert all(0 < a["sparse_blocks_chosen"] <= a["sparse_blocks_live"]
               for a in notes)
    # some tick had a slot past dense_len: it attended fewer than it holds
    assert any(a["sparse_blocks_chosen"] < a["sparse_blocks_live"]
               for a in notes)
    window = {"_aligned_serve": {"spans": [
        ("serve.readback", 0, 1, a) for a in notes[-20:]]}}
    share = manifest.load_reader("sparse_chosen_share_pct", tiny_root)
    assert 0 < share(window) <= 100
    assert share({"_aligned_serve": None}) is None
    from mxnet_tpu import obs

    snap = obs.registry.snapshot()
    assert snap["mx_linattn_rows_total"]["series"][0]["value"] \
        >= sum(a["linattn_rows"] for a in notes)
    assert snap["mx_linattn_state_bytes"]["series"][0]["value"] \
        == slots * 2 * 4 * 16 * 16 * 4
    # the control runs on this driver's host-side weights: the reference
    # with its matrices rounded to bfloat16 reads off
    assert 0 < control.reading(cell, 3) < 2


def test_the_probe_of_the_selection_at_a_tiny_size(loaded):
    """``benchmarks/probe_sala_selection.py`` (what ``correct`` reads when
    the selection is at fault; ``PERF.md`` section 6, PR 43) on the CPU:
    nine layers (sparse at 9, 16, 17), the sparse layers' output matrix
    three times the file's so that a block that flips shows.  With every
    sparse layer's choice forced to the reference's the programs read what
    rounding alone leaves, whatever they read choosing for themselves; a
    reference that never selects, or takes the lowest blocks, reads far
    over both."""
    import importlib.util

    import mxnet_tpu as mx

    spec = importlib.util.spec_from_file_location(
        "probe_sala_selection", os.path.join(
            manifest.ROOT, "benchmarks", "probe_sala_selection.py"))
    probe = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(probe)
    cfg = dict(tiny_config(loaded["config"]), serve_num_hidden_layers=9)
    cfg["init"] = [dict(r, std=3 * r["std"]) if "attout" in r["match"]
                   else r for r in cfg["init"]]
    traffic = dict(TINY_TRAFFIC, slots=2, prefill_chunk=8)
    out = probe.probe(cfg, traffic, 5, mx.cpu(),
                      probe.reference_fns(cfg, traffic),
                      ["forced", "fault_dense", "fault_lowest"])
    assert out["counts"] == out["forced_counts"] \
        and out["counts"]["sparse_blocks_chosen"] \
        < out["counts"]["sparse_blocks_live"]
    assert out["forced"] < 0.2 and out["forced"] <= out["sound"] + 1e-3
    assert len(out["forced_rows"]) == 1 + TINY_TRAFFIC["check_decode"]
    assert out["fault_dense"] > 1.0 and out["fault_lowest"] > 1.0


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


NAMES = ("linattn_device_pct.serve", "attn_sparse_device_pct.serve",
         "linattn_step_device_pct.serve", "linattn_chunk_roofline_pct",
         "attn_sparse_hbm_util_pct", "sparse_chosen_share_pct",
         "linattn_rows_per_tick")


def test_the_readers_read_what_the_window_shows(loaded, monkeypatch):
    """The two shares of a roofline against a hand-made window: the blocks
    the ticks counted times what each must move, over the decode program's
    time under the layer's scopes a run; the chunks' own floor over the
    chunk program's time under ``linattn/chunk``.  The state's step: the
    rows a tick, and its own events' share of the busy time."""
    from chipbench import trace

    monkeypatch.setattr(trace, "window_of", lambda p: (0, 4000))
    cfg = loaded["config"]
    ops = [("fusion.7", 200, 300), ("fusion.8", 520, 60),
           ("fusion.9", 600, 300), ("fusion.3", 1550, 100),
           ("fusion.4", 1700, 60), ("fusion.5", 1800, 90),
           ("fusion.7", 2100, 300), ("fusion.8", 2420, 60),
           ("fusion.9", 2500, 100)]
    maps = ({"fusion.7": "linattn/step", "fusion.8": "attn_sparse/select",
             "fusion.9": "attn_sparse/kv_gather"},
            {"fusion.3": "linattn/chunk", "fusion.4": "attn_sparse/scores",
             "fusion.5": "linear"})
    spans_ = [("serve.readback", 0, 1, {"linattn_rows": 200,
                                        "sparse_blocks_chosen": 9000,
                                        "sparse_blocks_live": 60000}),
              ("serve.prefill", 1, 2, {"tokens": 2048}),
              ("serve.readback", 2, 3, {"linattn_rows": 216,
                                        "sparse_blocks_chosen": 9216,
                                        "sparse_blocks_live": 92160})]
    read = {n: manifest.load_reader(n) for n in NAMES}
    facts = lambda: _facts(loaded, spans_, ops, maps)
    # the step's share counts the step's events AND the events of the
    # compiler's moves of its state, each for what it takes itself (NOT the
    # time a move is in flight): 2 x 300 + 2 x (5 + 10 + 5 + 10) of the
    # 1370 + 50 ns busy (copy-done.2 runs inside fusion.9, whose own time
    # it shortens); the move of a weight (copy-start.3) is not the step's
    moved = ops + [("slice-start.1", 120, 5), ("slice-done.1", 190, 10),
                   ("copy-start.2", 500, 5), ("copy-done.2", 640, 10),
                   ("slice-start.1", 2010, 5), ("slice-done.1", 2090, 10),
                   ("copy-start.2", 2400, 5), ("copy-done.2", 2540, 10),
                   ("copy-start.3", 1000, 5), ("copy-done.3", 1200, 5)]
    step = {"scope": "linattn/step", "moves": False}
    to_step = {"scope": "unscoped", "moves": True,
               "src": "state.caches[0][0]", "feeds": "linattn/step"}
    from_step = {"scope": "unscoped", "moves": True, "src": "linattn/step",
                 "feeds": "output"}
    imap = {"fusion.7": step, "slice-start.1": to_step,
            "slice-done.1": to_step, "copy-start.2": from_step,
            "copy-done.2": from_step,
            "copy-start.3": {"scope": "unscoped", "moves": True,
                             "src": "env['w']", "feeds": "linear"},
            "copy-done.3": {"scope": "unscoped", "moves": True,
                            "src": "env['w']", "feeds": "linear"}}
    with_moves = _facts(loaded, spans_, sorted(moved, key=lambda e: e[1]),
                        maps)
    with_moves["instruction_maps"] = {"jit__paged_decode_impl": {
        "source": "dispatched", "conflicts": 0, "instructions": imap}}
    assert read["linattn_step_device_pct.serve"](with_moves) \
        == pytest.approx(100.0 * 660 / 1420)
    assert read["linattn_rows_per_tick"](facts()) == pytest.approx(208.0)
    flops, moved = work_sala.lightning_chunk_work(cfg, 2048)
    assert read["linattn_chunk_roofline_pct"](facts()) == pytest.approx(
        100.0 * 9 * max(flops / 197e12, moved / 819e9) / 100e-9)
    need = work_sala.selected_bytes(cfg, 9108, 76080)
    assert read["attn_sparse_hbm_util_pct"](facts()) == pytest.approx(
        100.0 * need / ((360 + 160) / 2 * 1e-9) / 819e9)
    assert read["sparse_chosen_share_pct"](facts()) == pytest.approx(
        (15.0 + 10.0) / 2)
    # the two scope shares: of the 1370 ns busy in the window
    assert read["linattn_device_pct.serve"](facts()) == pytest.approx(
        100.0 * 700 / 1370)
    assert read["attn_sparse_device_pct.serve"](facts()) == pytest.approx(
        100.0 * 580 / 1370)


def test_readers_return_nothing_where_the_program_has_nothing(loaded,
                                                              monkeypatch):
    """On a program without the scopes and counters this PR adds (the
    parent's), the seven readers leave their metric out and do not raise."""
    from chipbench import trace

    bare = {"trace": None, "config": loaded["config"],
            "traffic": loaded["traffic"],
            "peaks": {"hbm_bytes_per_s": 1, "bf16_flops_per_s": 1},
            "_aligned_serve": None, "scope_maps": None}
    for name in NAMES:
        assert manifest.load_reader(name)(dict(bare)) is None, name
    # a traced parent: programs with maps, none of them with the new
    # scopes, spans without the new arguments
    monkeypatch.setattr(trace, "window_of", lambda p: (0, 4000))
    ops = [("fusion.7", 200, 300), ("fusion.3", 1550, 100)]
    maps = ({"fusion.7": "attn"}, {"fusion.3": "linear"})
    spans_ = [("serve.readback", 0, 1, {"attn_blocks_live": 4}),
              ("serve.prefill", 1, 2, {"tokens": 188})]
    for name in NAMES:
        facts = _facts(loaded, spans_, ops, maps)
        assert manifest.load_reader(name)(facts) is None, name


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
                    "chipbench.reference.minicpm_sala", "chipbench.work_sala",
                    "chipbench.drivers.serve_ticks_by_leaf")
        if m in sys.modules]
print("LATE", late, "COMPILES", len(compiles))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=manifest.ROOT)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "LATE [] COMPILES 0" in out.stdout, out.stdout
