"""Configuration ``nemotron-3-nano-30b`` and its cell ``nemotron3_serve_agent``:
the published numbers pinned against the catalog and the cut beside them, the
parameter count and the cut's byte table by hand, ``work_nemotron_h``'s counts
by hand, each new reader on synthetic facts, the traffic against ISSUE 59's
table, the two shares of one expert layer adding up to the uncut reference's
layer, prefill in chunks then decode over int8 pages against the reference's
one pass, the cell's own loop driver end to end at a tiny size on the CPU,
and every fault the chip's probe plants failing the comparison there."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, manifest, run, work, work_nemotron_h as wn
from chipbench.reference import nemotron_h as ref

import tiny

CELL, CONFIG = "nemotron3_serve_agent", "nemotron-3-nano-30b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("ssm_solo_device_pct.serve", "ssm_solo_step_hbm_util_pct",
       "ssm_solo_chunk_roofline_pct", "moe_relu2_device_pct.serve",
       "moe_relu2_experts_hbm_util_pct")
# ISSUE 59, hazard 4: each would misread this model or moves a metric the
# cell does not report
# (and ``ssm_rows_per_tick``, which ISSUE 59 would have joined: an accepted
# test, ``test_falcon_h1_34b.py::test_manifest_entries``, holds that list to
# its one cell, and no accepted test may change)
NOT_JOINED = ("ssm_rows_per_tick", "moe_experts_hbm_util_pct",
              "ssm_scan_roofline_pct", "ssm_state_hbm_util_pct",
              "ssm_device_pct.serve", "moe_device_pct.serve",
              "moe_shared_device_pct.serve",
              "decode_tick_device_ms", "decode_hbm_util_pct")
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@pytest.fixture(scope="module")
def loaded():
    return manifest.load_cell(CELL)


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_catalog_key_at_its_published_value(loaded):
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    entry = manifest.find(manifest.load_manifest()["configs"], CONFIG,
                          "config")
    cfg = loaded["config"]
    assert entry["source"] == cfg["source"] == row["source_url"]
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    for key, value in row["config"].items():
        assert cfg[key] == value, key
    assert cfg["model_type"] == "nemotron_h"
    assert cfg["hybrid_override_pattern"] == PATTERN and len(PATTERN) == 52
    assert [PATTERN.count(c) for c in "ME*-"] == [23, 23, 6, 0]
    # the cut beside the published counts, and the layers it keeps
    assert (cfg["num_hidden_layers"], cfg["serve_num_hidden_layers"]) \
        == (52, 16)
    assert (cfg["n_routed_experts"], cfg["held_n_routed_experts"],
            cfg["first_held_expert"]) == (128, 64, 0)
    assert wn.letters(cfg) == "MEMEM*EMEMEM*EME"
    assert [wn.letters(cfg).count(c) for c in "ME*"] == [7, 7, 2]
    assert "EMEMEM*" in wn.letters(cfg)[6:13]       # the unit, whole
    assert cfg["serve_dtype"] == "bfloat16"
    assert "2 chips share each layer" in cfg["deployment"]
    assert "3.25 x" in cfg["deployment"] and "eight chips" \
        in cfg["deployment"]
    assert set(cfg["assumed"]) >= {
        "rotation", "dt_clamp", "segment_order", "gated_norm", "routing",
        "expert_layout", "serve_num_hidden_layers", "init", "left_out"}
    # this file's own keys for the builder: no rotation, DeepSeek-V3's router
    assert cfg["attn_use_rope"] is False and cfg["rope_theta"] == 10000
    assert (cfg["scoring_func"], cfg["topk_method"]) == ("sigmoid",
                                                         "noaux_tc")
    assert set(cfg["limits"]) == {"serve_ticks_rows", "serve_ticks"}
    limit = cfg["limits"]["serve_ticks_rows"]["logp_atol.int8"]
    assert cfg["limits"]["serve_ticks"]["logp_atol.int8"]["value"] \
        == limit["value"] == 0.42
    # the largest sound reading and the smallest control it lies between
    assert "0.283" in limit["why"] and "0.614" in limit["why"]
    assert cfg["counts"] == {
        "decode_step_bytes": "chipbench.work_nemotron_h:decode_step_bytes"}


def test_manifest_entries(loaded):
    """What this cell and its five readers state, and nothing of any other
    cell or list: an appended cell or metric trips nothing here."""
    man = manifest.load_manifest()
    assert manifest.validate(man) == []
    cell = loaded["cell"]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert "16 of 52" in cell["why"] and "64 of 128" in cell["why"] \
        and "half load" in cell["why"] and "3.25x" in cell["why"]
    assert cell["traffic"] == "backlog_p1024-8192_o1024-4096_s64"
    reported = {m["name"] for m in loaded["end_to_end"]}
    assert reported == {"serve_out_tokens_per_s", "setup_s"}
    mine = {m["name"]: m for m in loaded["per_layer"]}
    assert set(mine) >= set(NEW) | {
        "prefill_chunk_device_ms", "moe_held_rows_per_tick",
        "tick_host_ms", "slot_occupancy_pct",
        "peak_hbm_gb.serve", "compiles_in_window.serve",
        "device_idle_pct.serve", "unscoped_named_pct.serve",
        "scope_join_found_pct.serve"}
    assert not set(mine) & set(NOT_JOINED)
    for m in mine.values():
        assert m["moves"] in reported, m["name"]
    for name in NEW:
        assert mine[name]["workloads"] == [CELL]
        assert mine[name]["moves"] == "serve_out_tokens_per_s"
        assert mine[name]["layer"] == "kernels, serving"
        assert os.path.exists(os.path.join(
            manifest.ROOT, manifest.reader_path(name)))
    # appended: the entries are the last of their lists
    assert man["configs"][-1]["name"] == CONFIG
    assert man["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in man["per_layer"][-5:]] == list(NEW)


def test_the_traffic_file_is_the_issues_table(loaded):
    traffic = loaded["traffic"]
    want = dict(driver="serve_ticks_rows", loop="backlog", slots=64,
                cache_len=12288, page_tokens=16, prefill_chunk=2048,
                max_prefill=8192, kv_dtype="int8", prompt_min=1024,
                prompt_max=8192, output_min=1024, output_max=4096,
                requests=256, block=64, order_seed=0, warmup_ticks=1024,
                trace_seconds=30, trace_ticks=96, check_prompt=6144,
                check_decode=8)
    assert {k: traffic[k] for k in want} == want
    assert set(traffic) == set(want) | {"note"}
    from chipbench import traffic as traffic_mod
    whole = traffic_mod.backlog(traffic, 8, 0)
    assert max(len(p) + o for p, o in whole) <= traffic["cache_len"]
    assert max(len(p) for p, _ in whole) <= traffic["max_prefill"]
    # ISSUE 59's means: prompts 3.4 k (2.2 chunks), outputs 2.2 k
    prompts = [len(p) for p, _ in whole]
    assert 3300 < np.mean(prompts) < 3600
    assert 2.0 < np.mean([-(-n // 2048) for n in prompts]) < 2.4
    assert 2150 < np.mean([o for _, o in whole]) < 2300
    assert traffic["check_prompt"] == 3 * traffic["prefill_chunk"]
    assert traffic["prefill_chunk"] == 16 * loaded["config"]["chunk_size"]
    # warmup_ticks, worked out from the file: the slots fill in 137 chunks
    # and the first of them turns over 943 ticks after that; a window that
    # opened before then would hold no chunk in a traced run's 96 ticks
    chunks = np.cumsum([-(-len(p) // 2048) for p, _ in whole])
    fill = chunks[traffic["slots"] - 1]
    first = min(c + o for c, (_, o) in zip(chunks, whole))
    assert fill == 137 and 900 < first - fill < traffic["warmup_ticks"]
    assert traffic["warmup_ticks"] >= traffic["output_min"]


def test_the_files_init_is_what_assumed_says(loaded):
    """The init's rules by the leaves they draw: dt_bias the inverse softplus
    of [time_step_min, time_step_max], A between -1 and -16, D 1, a router
    bias of 0.01, a routed expert's way back at half the other matrices (the
    flips' reason, ``assumed.init``), and NO rule for a leaf this graph does
    not have: the graph another builder makes of these keys is refused at
    its first such leaf, seconds into set-up."""
    import math

    from chipbench import weights

    cfg = loaded["config"]
    rule = lambda name: weights._rule_for(name, cfg["init"])
    dt = rule("layer0_ssm_dt_bias")
    inv = lambda y: math.log(math.expm1(y))
    assert abs(dt["low"] - inv(cfg["time_step_min"])) < 0.01
    assert abs(dt["high"] - inv(cfg["time_step_max"])) < 0.01
    assert inv(cfg["time_step_floor"]) < dt["low"]
    a = rule("layer0_ssm_A_log")
    assert (a["low"], round(math.exp(a["high"]))) == (0.0, 16)
    assert rule("layer0_ssm_D") == {"match": "_ssm_D$", "dist": "const",
                                    "value": 1.0}
    assert rule("layer1_moe_gate_bias")["std"] == 0.01
    std = lambda name: rule(name)["std"]
    assert std("layer1_moe_expert_down_weight") == 0.01
    assert std("layer1_moe_expert_up_weight") \
        == std("layer1_moe_shared_down_weight") \
        == std("layer1_moe_gate_weight") == std("layer5_v_weight") == 0.02
    assert std("layer5_q_weight") == std("layer0_ssm_in_weight") \
        == std("head_weight") == 0.04
    assert std("embed_weight") == 1.0
    for name in _full_shapes(cfg):
        rule(name)
    for foreign in ("layer0_ffn_gate_weight", "layer0_att_sink",
                    "layer1_moe_expert_gate_weight"):
        with pytest.raises(KeyError, match="no init rule"):
            rule(foreign)
    assert "0.12 to 0.23" in cfg["assumed"]["init"]


def _full_shapes(cfg):
    from chipbench.drivers import serve_ticks

    return serve_ticks.weight_shapes(harness.build_symbol(cfg), cfg)


def test_the_parameter_count_and_the_cuts_byte_table(loaded):
    """31.6 B in all and 3.2 B a token from the equations, and ISSUE 59's
    table of the cut from the shapes the builder infers (nothing is
    allocated): parameters in millions, GB at 2 bytes."""
    cfg, traffic = loaded["config"], loaded["traffic"]
    d, v = 2688, 131072
    mixer = d * 10304 + 4096 * d + 6144 * 5 + 3 * 64 + 4096
    att = d * 128 * (32 + 2 + 2 + 32)
    expert, shared, router = 2 * d * 1856, 2 * d * 3712, d * 128 + 128
    e_layer = router + shared + 128 * expert
    whole = 23 * (mixer + d) + 23 * (e_layer + d) + 6 * (att + d) \
        + 2 * v * d + d
    active = 23 * (mixer + d) + 23 * (router + shared + 6 * expert + d) \
        + 6 * (att + d) + v * d + d
    assert wn.model_params(cfg, 52, 128) == whole
    assert wn.active_params_per_token(cfg) == active
    assert abs(whole / 1e9 - 31.58) < 0.01 and abs(active / 1e9 - 3.23) < 0.01
    assert abs(mixer / 1e6 - 38.74) < 0.01 and abs(att / 1e6 - 23.40) < 0.01
    assert abs(expert / 1e6 - 9.978) < 0.001
    assert abs((e_layer + d) / 1e6 - 1297.5) < 0.05
    shapes = _full_shapes(cfg)
    size = lambda n: int(np.prod(shapes[n]))
    close = lambda got, millions: abs(got / 1e6 - millions) < 0.01
    layer = lambda l: sum(size(n) for n in shapes
                          if n.startswith("layer%d_" % l))
    for l, c in enumerate("MEMEM*EMEMEM*EME"):
        assert layer(l) == wn.layer_params(cfg, c, 64), l
    assert close(layer(0), 38.74) and close(layer(5), 23.40)
    assert close(layer(1), 638.58 + 19.96 + 0.34 + 0.0027)
    assert shapes["layer0_ssm_in_weight"] == (10304, d)
    assert shapes["layer0_ssm_conv_weight"] == (6144, 4)
    assert shapes["layer5_k_weight"] == (256, d)
    # two matrices an expert, a hidden unit a row: no gate, no padding
    assert shapes["layer1_moe_expert_up_weight"] == (64, 1856, d)
    assert shapes["layer1_moe_expert_down_weight"] == (64, 1856, d)
    assert shapes["layer1_moe_shared_up_weight"] == (3712, d)
    assert shapes["layer1_moe_gate_weight"] == (d, 128)
    assert not [n for n in shapes if "_gate_weight" in n and "moe_gate" not
                in n]
    ends = size("embed_weight") + size("head_weight")
    assert close(ends, 704.64)
    total = sum(size(n) for n in shapes)
    assert total == wn.model_params(cfg) == wn.model_params(cfg, 16, 64)
    assert abs(total / 1e9 - 5.635) < 0.001 \
        and abs(2 * total / 1e9 - 11.27) < 0.005
    # the state group: 64 slots x 7 mixer layers x (the float32 state and 3
    # positions of the 6144 x, B, C channels in bfloat16)
    state, tail = wn.state_row_bytes(cfg)
    assert (state, tail) == (64 * 64 * 128 * 4, 3 * 6144 * 2)
    assert abs(7 * (state + tail) / 1e6 - 14.94) < 0.005
    # the two attention layers' int8 pages: 512 B a position a layer and 16
    # B of scales
    per = wn.kv_bytes_per_token(cfg, 1)
    assert per == 2 * 2 * 128 + 2 * 2 * 4 and abs(2 * per / 1e3 - 1.06) < 0.01
    res = wn.resident_bytes(cfg, traffic)
    assert abs(res["weights"] / 1e9 - 11.27) < 0.005
    assert abs(res["state"] / 1e9 - 0.96) < 0.005
    assert abs(res["pages"] / 1e9 - 0.83) < 0.005
    assert abs(sum(res.values()) / 1e9 - 13.06) < 0.01


def test_counts_by_hand(loaded):
    cfg, traffic = loaded["config"], loaded["traffic"]
    # 64 rows touch 61.0 of the 64 held experts a layer (95 %)
    touched = wn.experts_touched(cfg, 64)
    assert touched == pytest.approx(64 * (1 - (1 - 6 / 128) ** 64))
    assert 0.95 < touched / 64 < 0.96
    assert wn.expert_bytes(cfg) == 2 * 2688 * 1856 * 2
    live = 64 * 5000
    need = work.decode_step_bytes(cfg, traffic, live)
    d = 2688
    mixer = d * 10304 + 4096 * d + 6144 * 5 + 3 * 64 + 4096
    att = d * 128 * 68
    by_hand = 2 * (131072 * d + d + 7 * (mixer + d) + 2 * (att + d)
                   + 7 * (d + d * 128 + 128 + 2 * d * 3712)) \
        + 7 * touched * wn.expert_bytes(cfg) \
        + 7 * 64 * 2 * (2097152 + 36864) + live * 1056
    assert need == pytest.approx(by_hand, rel=1e-12)
    # ISSUE 59's count: experts 8.5 GB, state rows 1.9, the mixers' matrices
    # 0.54, the head 0.70, shared experts and routers 0.28: 12.4 GB a tick
    assert abs(7 * touched * wn.expert_bytes(cfg) / 1e9 - 8.53) < 0.01
    assert abs(7 * 64 * wn.state_step_bytes(cfg) / 1e9 - 1.91) < 0.01
    assert abs(2 * 7 * mixer / 1e9 - 0.54) < 0.005
    assert 12.3e9 < need < 12.5e9
    assert work.decode_step_bytes(cfg, traffic, 2 * live) - need \
        == pytest.approx(live * 1056)
    # a chunk of 200 tokens of one mixer layer: one block of 128 and one of
    # 72; a head's pairs up to the diagonal 8256 + 2628
    flops, moved = wn.chunk_scan_work(cfg, 200)
    pairs = 128 * 129 // 2 + 72 * 73 // 2
    assert flops == 2 * 4 * 6144 * 200 + 2 * pairs * (1024 + 4096) \
        + 4 * 200 * 64 * 64 * 128
    assert moved == 2 * (2097152 + 36864) + 200 * (6144 + 64 + 4096) * 2
    # a full chunk: 5.7 GFLOP a layer, 0.03 ms at the bf16 peak; 46 MB, 0.06
    # ms at the HBM peak: the streams' bytes bound it
    flops, moved = wn.chunk_scan_work(cfg, 2048)
    assert 5.6e9 < flops < 5.9e9 and 4.5e7 < moved < 4.8e7
    with pytest.raises(ValueError, match="letter"):
        wn.layer_params(cfg, "X")


def _facts(cfg, rows, visits, chunks):
    """A hand-made window: two runs of the decode program, one of the
    chunk's."""
    from chipbench import trace

    dec, chk = "jit__paged_decode_impl", "jit__chunk_impl"
    ops = [("fusion.1", 200, 300), ("fusion.2", 520, 100),
           ("fusion.3", 640, 200), ("fusion.9", 860, 100),
           ("fusion.4", 1550, 100), ("fusion.5", 1660, 40),
           ("fusion.6", 1710, 60), ("fusion.7", 1800, 50),
           ("fusion.1", 2200, 300), ("fusion.2", 2520, 100),
           ("fusion.3", 2640, 200), ("fusion.9", 2860, 100)]
    scoped = ({"fusion.1": "ssm/step", "fusion.2": "ssm/conv",
               "fusion.3": "moe/experts", "fusion.9": "moe/shared"},
              {"fusion.4": "ssm/scan", "fusion.5": "moe/route",
               "fusion.6": "linear", "fusion.7": "ssm/conv"})
    spans = [("serve.readback", 0, 1, {"ssm_rows": r, "moe_expert_visits": v})
             for r, v in zip(rows, visits)] \
        + [("serve.prefill", 0, 1, {"pos": 0, "tokens": t}) for t in chunks]
    return {
        "trace": {"devices": {0: {
            trace.MODULES_LINE: [(dec + "(1)", 100, 1000),
                                 (chk + "(2)", 1500, 400),
                                 (dec + "(1)", 2100, 1000)],
            trace.OPS_LINE: ops}}},
        "scope_maps": {dec: scoped[0], chk: scoped[1]},
        "_aligned_serve": {"spans": spans}, "config": cfg,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}


def test_each_new_reader_on_synthetic_facts(loaded, monkeypatch):
    from chipbench import trace

    monkeypatch.setattr(trace, "window_of", lambda p: (0, 4000))
    cfg = loaded["config"]
    read = {n: manifest.load_reader(n) for n in NEW}
    rows, visits, chunks = [448, 441], [420, 430], [2048, 1000]
    facts = _facts(cfg, rows, visits, chunks)
    # busy: 2 x 700 in the decode runs and 250 in the chunk's
    assert read["ssm_solo_device_pct.serve"](facts) == pytest.approx(
        100.0 * (2 * 400 + 150) / (2 * 700 + 250))
    assert read["moe_relu2_device_pct.serve"](facts) == pytest.approx(
        100.0 * (2 * 300 + 40) / (2 * 700 + 250))
    assert read["ssm_solo_step_hbm_util_pct"](facts) == pytest.approx(
        100 * np.mean(rows) * wn.state_step_bytes(cfg) / 300e-9 / 819e9)
    assert read["moe_relu2_experts_hbm_util_pct"](facts) == pytest.approx(
        100 * np.mean(visits) * wn.expert_bytes(cfg) / 200e-9 / 819e9)
    floor = np.mean([max(f / 197e12, b / 819e9) for f, b in (
        wn.chunk_scan_work(cfg, t) for t in chunks)]) * 7
    assert read["ssm_solo_chunk_roofline_pct"](facts) == pytest.approx(
        100 * floor / 150e-9)
    # a window whose programs have no such scope leaves the metrics out
    facts = _facts(cfg, rows, visits, chunks)
    facts["scope_maps"] = {m: {k: "linear" for k in names}
                           for m, names in facts["scope_maps"].items()}
    for name in NEW:
        assert read[name](facts) is None, name
    # and a configuration of another family is not read by this model's
    # counts, whatever its programs' scopes
    other = manifest.load_cell("falconh1_serve_chat")["config"]
    facts = dict(_facts(cfg, rows, visits, chunks), config=other)
    for name in (NEW[1], NEW[2], NEW[4]):
        assert read[name](facts) is None, name


def test_readers_return_nothing_where_the_program_has_nothing(loaded):
    """On a program without the scopes and the counters (the parent's has
    them for no graph of this kind), the new readers leave their metric out
    and do not raise."""
    facts = {"trace": None, "config": loaded["config"],
             "traffic": loaded["traffic"],
             "peaks": {"hbm_bytes_per_s": 1, "bf16_flops_per_s": 1},
             "_aligned_serve": {"spans": [
                 ("serve.readback", 0, 1, {"moe_rows_held": 3}),
                 ("serve.prefill", 0, 1, {"pos": 0, "tokens": 8})]}}
    for name in NEW:
        assert manifest.load_reader(name)(dict(facts)) is None, name
    facts["_aligned_serve"] = None
    for name in NEW:
        assert manifest.load_reader(name)(dict(facts)) is None, name


# ---------------------------------------------------------------------------
# the share, the serving path, the cell's driver and the probe's faults, at a
# tiny size
# ---------------------------------------------------------------------------
TINY = dict(vocab_size=96, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, intermediate_size=48,
            moe_intermediate_size=48, moe_shared_expert_intermediate_size=80,
            n_routed_experts=8, num_experts_per_tok=3,
            held_n_routed_experts=4, first_held_expert=4,
            mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=16,
            n_groups=2, chunk_size=8, hybrid_override_pattern="MEM*EM",
            num_hidden_layers=6, serve_num_hidden_layers=6,
            max_position_embeddings=64, serve_dtype="float32")
TINY_TRAFFIC = dict(tiny.TINY_TRAFFIC["tiny_backlog"],
                    driver="serve_ticks_rows", kv_dtype="bfloat16",
                    cache_len=512, page_tokens=16, prefill_chunk=96,
                    max_prefill=320, slots=3, prompt_min=40, prompt_max=300,
                    output_min=4, output_max=12, check_prompt=288,
                    check_decode=4)


def tiny_config(cfg, **over):
    """The configuration at the toy's widths: the unit's letters (a mixer
    alone, experts alone, attention alone, stateless layers between stateful
    ones), the expert's width 48 (no whole number of sublane tiles of 32:
    nothing of the toy leans on an alignment), matrices wider than the
    cell's so that every mechanism moves the output."""
    wider = {"_(q|k)_weight$": 0.16, "_ssm_in_weight$": 0.16}
    init = [dict(r, std=wider.get(r["match"], 0.08))
            if r["match"].endswith("_weight$") and r["dist"] == "normal"
            and "conv" not in r["match"] and "embed" not in r["match"]
            else r for r in cfg["init"]]
    limits = {"serve_ticks_rows": {"logp_atol.bfloat16": {
        "value": 1e-4, "why": "float32 against float32"}}}
    return dict(cfg, init=init, limits=limits, **dict(TINY, **over))


def test_the_two_shares_of_one_expert_layer_add_up(loaded):
    """Two chips with four of the 8 experts each, sigmoid scores over all 8,
    the 3 largest of score + bias renormalised and times 2.5: their shares
    of one layer, the shared expert (80 wide, not 1 x 48) counted in one of
    them, are the uncut reference's layer."""
    import mxnet_tpu as mx

    cfg = tiny_config(loaded["config"])
    n = "layer1_"
    rng = np.random.default_rng(3)
    d, m, hs, e = 64, 48, 80, 8
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape) * 0.3,
                                      jnp.float32)
    whole = {n + "moe_gate_weight": draw(d, e),
             n + "moe_gate_bias": draw(e),
             n + "moe_expert_up_weight": draw(e, m, d),
             n + "moe_expert_down_weight": draw(e, m, d),
             n + "moe_shared_up_weight": draw(hs, d),
             n + "moe_shared_down_weight": draw(hs, d)}
    x = draw(2, 5, d) / 0.3
    uncut = dict(cfg, held_n_routed_experts=e, first_held_expert=0)
    want = ref._experts(whole, n, uncut, x)
    total = 0.0
    for chip, first in enumerate((0, 4)):
        shared = {"n_shared_experts": 1, "shared_hidden_size": hs} \
            if chip == 0 else {}
        sym = mx.sym.MoEFFN(
            mx.sym.Variable("data"), num_experts=e, hidden_size=m,
            gated=True, expert_act="relu2", num_experts_per_tok=3,
            score_func="sigmoid", score_bias=True, norm_topk=True,
            routed_scaling_factor=2.5, num_held=4, first_held=first,
            name="moe", **shared)
        ex = sym.simple_bind(mx.cpu(), grad_req="null", data=x.shape)
        ex.arg_dict["data"]._set_data(x)
        for arg in sym.list_arguments():
            if arg == "data":
                continue
            value = whole[n + arg]
            if "_expert_" in arg:
                value = value[first:first + 4]
            ex.arg_dict[arg]._set_data(value)
        ex.forward(is_train=False)
        total = total + ex.outputs[0].data
    assert float(jnp.max(jnp.abs(total - want))) < 1e-4
    # and not without the shared expert; one share is not the layer; the
    # factor 2.5 is in it
    alone = ref._experts(whole, n, dict(uncut, n_shared_experts=0), x)
    assert float(jnp.max(jnp.abs(alone - want))) > 1e-2
    part = ref._experts(whole, n, cfg, x)
    assert float(jnp.max(jnp.abs(part - want))) > 1e-3
    plain = ref._experts(whole, n, dict(uncut, routed_scaling_factor=1.0), x)
    assert float(jnp.max(jnp.abs(plain - want))) > 1e-2


@pytest.fixture(scope="module")
def tiny_case(loaded):
    from chipbench.drivers import serve_ticks, serve_ticks_by_leaf

    cfg = tiny_config(loaded["config"])
    sym = harness.build_symbol(cfg)
    shapes = serve_ticks.weight_shapes(sym, cfg)
    return cfg, sym, serve_ticks_by_leaf.make_params(shapes, cfg, 11,
                                                     "float32")


@pytest.mark.parametrize("kv_dtype,atol", [("", 2e-5), ("int8", 5e-3)])
def test_prefill_in_chunks_then_decode_against_the_references_one_pass(
        tiny_case, kv_dtype, atol):
    """40 prompt tokens in chunks of 8 (each one block of the chunked scan:
    the state and the convolution tail carried four times), then 6 decode
    steps over paged keys and values, float and int8: the logits, not the
    tokens, against ONE pass of the reference over the sequence that was
    decoded."""
    import mxnet_tpu as mx
    from mxnet_tpu.decode import DecodePredictor

    cfg, sym, params = tiny_case
    nd = {n: mx.nd.NDArray(v, mx.cpu()) for n, v in params.items()}
    pred = DecodePredictor(sym, nd, cache_len=64, ctx=mx.cpu(), paged=True,
                           page_tokens=4, kv_dtype=kv_dtype, prefill_chunk=8)
    assert [l.kind for l in pred.cache_layouts()] \
        == ["state", "state", "full", "state"]
    rng = np.random.default_rng(5)
    toks = np.zeros((2, 40), np.float32)
    toks[0] = rng.integers(0, 96, size=40)
    toks[1, :5] = rng.integers(0, 96, size=5)
    state, probs = pred.prefill(toks, np.array([40, 5]))
    got, fed = [probs[0]], [int(np.asarray(state.tok)[0, 0])]
    for _ in range(6):
        state, probs = pred.step(state)
        got.append(probs[0])
        fed.append(int(np.asarray(state.tok)[0, 0]))
    seq = np.concatenate([toks[0], fed[:-1]])[None]
    want = jax.nn.log_softmax(ref.forward(params, cfg, seq)[0, 39:], -1)
    worst = float(jnp.max(jnp.abs(jnp.log(jnp.stack(got)) - want)))
    assert worst < atol, worst


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory, loaded):
    root = tiny.make_root(tmp_path_factory.mktemp("bench_nemotron3"))
    with open(os.path.join(root, "chipbench/configs/tiny-nemotron3.json"),
              "w") as f:
        json.dump(tiny_config(loaded["config"]), f)
    with open(os.path.join(root, manifest.traffic_path("tiny_backlog_nh")),
              "w") as f:
        json.dump(TINY_TRAFFIC, f)
    man = manifest.load_manifest(root)
    man["configs"].append({
        "name": "tiny-nemotron3", "source": "test", "reduced": [],
        "file": "chipbench/configs/tiny-nemotron3.json",
        "why": "CPU test size"})
    man["workloads"].append({
        "name": "tiny_nemotron3_serve", "config": "tiny-nemotron3",
        "traffic": "tiny_backlog_nh", "chips": 1, "why": "CPU test size"})
    for met in man["end_to_end"] + man["per_layer"]:
        if CELL in met.get("workloads", ()):
            met["workloads"].append("tiny_nemotron3_serve")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    return root


def test_the_cells_driver_at_a_tiny_size(tiny_root):
    """``serve_ticks_rows`` end to end on the CPU: a backlog through
    ``DecodeServer`` over a state group whose layers lie between stateless
    ones, paged attention and held two-matrix experts, every finished request
    at exactly its length, then the comparison with the reference (three
    chunks of 96, the chunked scan from a carried state and tail; 4 decode
    rows).  The tick's counters are there for the readers."""
    import mxnet_tpu as mx
    from chipbench import spans
    from mxnet_tpu import obs

    assert manifest.validate(manifest.load_manifest(tiny_root),
                             tiny_root) == []
    cell = manifest.load_cell("tiny_nemotron3_serve", root=tiny_root)
    counters = harness.CompileCounters().install()
    before = harness.program_counters()
    res = run.run_cell(cell, 2 ** 31 + 13, 1.0, False, [mx.cpu()], counters,
                       harness.Phases(), harness.MemoryPeak(1))
    assert all(c["ok"] for c in res["checks"]), res["checks"]
    first = res["checks"][0]
    assert first["statistic"] == "row_rms_median"
    assert first["row_rms_median"] < first["max_abs_dlogp"] < 1e-4
    assert first["positions"] == 5
    assert res["failed"] == 0 and res["side"]["queue_left"] > 0
    assert res["side"]["requests_completed"] >= 1
    assert counters.in_window == 0
    # what the readers read: a tick's rows in the arguments of its
    # serve.readback span, (slot, mixer layer) pairs and routed pairs
    notes = [a for name, _, _, a in spans.spans_of(spans.program_events())
             if name == "serve.readback" and "ssm_rows" in a]
    assert notes and all(a["ssm_rows"] % 3 == 0 for a in notes)
    assert max(a["ssm_rows"] for a in notes) == 3 * 3
    assert all({"moe_rows_held", "moe_expert_visits"} <= set(a)
               for a in notes)
    # two E layers, four held experts each: at most 8 visits a tick
    assert 0 < max(a["moe_expert_visits"] for a in notes) <= 8
    window = {"_aligned_serve": {"spans": [
        ("serve.readback", 0, 1, a) for a in notes[-20:]]}}
    assert 3 <= manifest.load_reader("ssm_rows_per_tick", tiny_root)(
        window) <= 9
    assert manifest.load_reader("moe_held_rows_per_tick", tiny_root)(
        window) > 0
    # the two-matrix form under a label of its own, dense on the CPU
    forms = {k: v for k, v in harness.program_counters(before).items()
             if k.startswith("mx_moe_dispatch_total")}
    assert set(forms) == {"mx_moe_dispatch_total{form=held_dense_relu2}"}
    snap = obs.registry.snapshot()
    assert snap["mx_ssm_rows_total"]["series"][0]["value"] > 0


@pytest.fixture(scope="module")
def probe():
    sys.path.insert(0, os.path.join(manifest.ROOT, "benchmarks"))
    try:
        import probe_nemotron3_faults
    finally:
        sys.path.pop(0)
    return probe_nemotron3_faults


@pytest.mark.parametrize("which", [
    "sound", "relu_not_squared", "no_scaling_factor", "no_shared_expert",
    "weights_not_normalised", "rotation_applied", "norm_over_whole",
    "no_d_skip", "chunk_from_zero_state", "tail_not_carried"])
def test_the_probes_faults_at_a_tiny_size(probe, tiny_case, which):
    """The comparison the chip's probe makes, on the CPU in float32: sound
    programs agree with the reference to rounding, every planted fault of
    what this architecture adds moves the median row by a hundred times that
    and more."""
    import mxnet_tpu as mx
    from mxnet_tpu.ops import moe, ssm

    cfg, _, params = tiny_case
    assert which == "sound" or which in probe.FAULTS
    got = probe.reading(cfg, TINY_TRAFFIC, dict(params), 11, which, mx.cpu(),
                        1e-4)
    assert got["statistic"] == "row_rms_median" and got["positions"] == 5
    if which == "sound":
        assert got["ok"] and got["max_abs_dlogp"] < 1e-5
    else:
        assert not got["ok"] and got["row_rms_median"] > 1e-3, got
    # the probe leaves the ops as it found them
    assert ssm.mix.__module__ == ssm._gate_norm.__module__ == ssm.__name__
    assert moe.BODIES["relu2"][1] is moe._relu2


def test_existing_cells_import_nothing_of_this_configuration():
    """Importing the program and setting an accepted cell up loads none of
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
late = [m for m in ("chipbench.work_nemotron_h",
                    "chipbench.reference.nemotron_h",
                    "chipbench.drivers.serve_ticks_by_leaf",
                    "chipbench.drivers.serve_ticks_rows",
                    "chipbench.drivers.serve_ticks_mtp",
                    "mxnet_tpu.models.decoder_lm")
        if m in sys.modules]
print("LATE", late, "COMPILES", len(compiles))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=manifest.ROOT)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "LATE [] COMPILES 0" in out.stdout, out.stdout
