"""Configuration ``solar-open2-250b`` and its cell ``solar2_serve_agent``: the
published numbers pinned against the catalog, the parameter count and the
cut's byte table by hand, ``work_kda``'s counts by hand, each new reader on
synthetic facts, the traffic against ISSUE 53's table, the 16 shares of one
expert layer adding up to the uncut reference's layer, the cell's own loop
driver end to end at a tiny size on the CPU, and every fault the chip's probe
plants failing the comparison there."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, manifest, run, work, work_kda
from chipbench.reference import solar_open2 as ref

import tiny

CELL, CONFIG = "solar2_serve_agent", "solar-open2-250b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("kda_device_pct.serve", "kda_step_hbm_util_pct",
       "kda_chunk_roofline_pct", "kda_rows_per_tick")


@pytest.fixture(scope="module")
def loaded():
    return manifest.load_cell(CELL)


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_catalog_key_at_its_published_value(loaded):
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Solar-Open2-250B")
    entry = manifest.find(manifest.load_manifest()["configs"], CONFIG,
                          "config")
    cfg = loaded["config"]
    assert entry["source"] == cfg["source"] == row["source_url"]
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    for key, value in row["config"].items():
        assert cfg[key] == value, key
    assert cfg["model_type"] == "solar_open2"
    assert cfg["gqa_layers"] == list(range(0, 48, 4))
    assert cfg["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    assert (cfg["serve_num_hidden_layers"], cfg["held_n_routed_experts"],
            cfg["first_held_expert"]) == (4, 20, 0)
    assert cfg["serve_dtype"] == "bfloat16"
    assert "16 chips share each layer" in cfg["deployment"]
    assert "12 x" in cfg["deployment"] and "1/16" in cfg["deployment"]
    assert set(cfg["assumed"]) >= {
        "routing", "gate_rank", "convolution", "l2_norm", "beta",
        "output_norm", "gqa", "init", "left_out"}
    # this file's own keys for the builder, the first of the assumed
    assert (cfg["scoring_func"], cfg["topk_method"]) == ("sigmoid",
                                                         "noaux_tc")
    assert list(cfg["limits"]) == ["serve_ticks_rows"]
    limit = cfg["limits"]["serve_ticks_rows"]["logp_atol.int8"]
    assert limit["value"] == 0.07 and "0.0286" in limit["why"] \
        and "0.1559" in limit["why"]
    assert cfg["counts"] == {
        "decode_step_bytes": "chipbench.work_kda:decode_step_bytes"}


def test_manifest_entries(loaded):
    """What this cell and its four readers state, and nothing of any other
    cell or list: an appended cell or metric trips nothing here."""
    assert manifest.validate(manifest.load_manifest()) == []
    cell = loaded["cell"]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert "4 of 48" in cell["why"] and "1/16" in cell["why"] \
        and "12x" in cell["why"]
    assert cell["traffic"] == "backlog_p2048-16384_o1024-4096_s96"
    reported = {m["name"] for m in loaded["end_to_end"]}
    assert reported >= {"serve_out_tokens_per_s", "setup_s"}
    mine = {m["name"]: m for m in loaded["per_layer"]}
    assert set(mine) >= set(NEW) | {
        "prefill_chunk_device_ms", "moe_held_rows_per_tick", "tick_host_ms",
        "peak_hbm_gb.serve"}
    for m in mine.values():
        assert m["moves"] in reported, m["name"]
    for name in NEW:
        assert mine[name]["workloads"] == [CELL]
        assert mine[name]["moves"] == "serve_out_tokens_per_s"
        assert os.path.exists(os.path.join(
            manifest.ROOT, manifest.reader_path(name)))


def test_the_traffic_file_is_the_issues_table(loaded):
    traffic = loaded["traffic"]
    want = dict(driver="serve_ticks_rows", loop="backlog", slots=96,
                cache_len=20480, page_tokens=16, prefill_chunk=2048,
                max_prefill=16384, kv_dtype="int8", prompt_min=2048,
                prompt_max=16384, output_min=1024, output_max=4096,
                requests=256, block=64, order_seed=0, warmup_ticks=768,
                trace_seconds=30, trace_ticks=96, check_prompt=6144,
                check_decode=8)
    assert {k: traffic[k] for k in want} == want
    assert set(traffic) == set(want) | {"note"}
    from chipbench import traffic as traffic_mod
    queue = traffic_mod.backlog(dict(traffic, requests=traffic["block"]),
                                8, 0)
    assert max(len(p) + o for p, o in queue) + 1 <= traffic["cache_len"]
    assert max(len(p) for p, _ in queue) <= traffic["max_prefill"]
    # ISSUE 53's means: prompts 6.9 k (3.9 chunks), outputs 2.2 k
    prompts = [len(p) for p, _ in queue]
    assert 6700 < np.mean(prompts) < 7100
    assert 3.7 < np.mean([-(-n // 2048) for n in prompts]) < 4.1
    assert 2150 < np.mean([o for _, o in queue]) < 2300
    assert traffic["check_prompt"] == 3 * traffic["prefill_chunk"]
    # ISSUE 53's table but for warmup_ticks (768 for its 8): every answer is
    # at least 1024 ticks long and the slots fill in some 370, so the first
    # slot turns over 710 ticks after the last one filled; a window that
    # opened before then would hold no chunk in a traced run's 96 ticks
    whole = traffic_mod.backlog(traffic, 8, 0)
    chunks = np.cumsum([-(-len(p) // 2048) for p, _ in whole])
    fill = chunks[traffic["slots"] - 1]
    first = min(c + o for c, (_, o) in zip(chunks, whole))
    assert fill == 371 and 700 < first - fill < traffic["warmup_ticks"]


def _full_shapes(cfg):
    from chipbench.drivers import serve_ticks

    return serve_ticks.weight_shapes(harness.build_symbol(cfg), cfg)


def test_the_parameter_count_and_the_cuts_byte_table(loaded):
    """250.3 B in all and 14.7 B a token from the equations, and ISSUE 53's
    table of the cut from the shapes the builder infers (nothing is
    allocated): parameters in millions, GB at 2 bytes."""
    cfg, traffic = loaded["config"], loaded["traffic"]
    d, m, v = 4096, 1280, 196608
    gqa = d * 128 * (64 + 8 + 8 + 64 + 64)
    kda = 4 * d * 8192 + 2 * (d * 128 + 128 * 8192) + d * 64 \
        + 3 * 8192 * 4 + 64 + 8192 + 128
    expert = 3 * d * m
    rest = d * 320 + 320 + 2 * d + expert          # router, norms, shared
    whole = 12 * gqa + 36 * kda + 48 * (rest + 320 * expert) + 2 * v * d + d
    active = 12 * gqa + 36 * kda + 48 * (rest + 8 * expert) + 2 * v * d + d
    assert work_kda.model_params(cfg) == whole
    assert work_kda.model_params(cfg, experts=8) == active
    assert abs(whole / 1e9 - 250.3) < 0.05 and abs(active / 1e9 - 14.7) < 0.05
    assert work_kda.delta_layers(cfg, 48) == 36
    assert work_kda.delta_layers(cfg) == 3
    shapes = _full_shapes(cfg)
    size = lambda n: int(np.prod(shapes[n]))
    close = lambda got, millions: abs(got / 1e6 - millions) < 0.01
    mixer = lambda l, part: sum(size(n) for n in shapes if n.startswith(
        "layer%d_%s" % (l, part)))
    att = sum(size("layer0_%s_weight" % p)
              for p in ("q", "k", "v", "gate", "attout"))
    assert close(att, 109.05) and att == work_kda.gqa_mixer_params(cfg) == gqa
    for l in (1, 2, 3):
        assert close(mixer(l, "kda_"), 137.73)
        assert mixer(l, "kda_") == work_kda.delta_mixer_params(cfg) == kda
    assert shapes["layer1_kda_conv_weight"] == (3 * 8192, 4)
    assert shapes["layer1_kda_f_a_weight"] == (128, 4096)
    assert shapes["layer1_kda_beta_weight"] == (64, 4096)
    shared = sum(size("layer0_moe_shared_%s_weight" % p)
                 for p in ("gate", "up", "down"))
    assert close(shared, 15.73) and close(size("layer0_moe_gate_weight"),
                                          1.31)
    assert shapes["layer0_moe_expert_gate_weight"][0] == 20
    held = sum(size("layer%d_moe_expert_%s_weight" % (l, p))
               for l in range(4) for p in ("gate", "up", "down"))
    assert close(held, 1258.29)
    ends = size("embed_weight") + size("head_weight")
    assert close(ends, 1610.61)
    total = sum(size(n) for n in shapes)
    assert total == work_kda.model_params(cfg, 4, 20)
    assert close(total - held - ends, 590.45)
    assert abs(total / 1e6 - 3459.3) < 0.1 and abs(2 * total / 1e9 - 6.92) \
        < 0.005
    # the state group: 96 slots x 3 delta layers x (the float32 matrices and
    # 3 positions of the 24576 q, k, v channels in bfloat16)
    state, tail = work_kda.state_row_bytes(cfg)
    assert (state, tail) == (64 * 128 * 128 * 4, 3 * 24576 * 2)
    row = 3 * (state + tail)
    assert abs(row / 1e6 - 13.03) < 0.005
    assert abs(traffic["slots"] * row / 1e9 - 1.25) < 0.005
    # the attention layer's int8 pages: 2048 B a position and 64 B of scales
    per = work_kda.kv_bytes_per_token(cfg, 1)
    assert per == 2 * 8 * 128 + 2 * 8 * 4
    pool = traffic["slots"] * traffic["cache_len"] * per
    assert abs(pool / 1e9 - 4.15) < 0.01
    assert 12.2e9 < 2 * total + traffic["slots"] * row + pool < 12.4e9


def test_counts_by_hand(loaded):
    cfg, traffic = loaded["config"], loaded["traffic"]
    # 96 rows touch 18.2 of the 20 held experts a layer (91 %)
    touched = work_kda.experts_touched(cfg, 96)
    assert touched == pytest.approx(20 * (1 - (1 - 8 / 320) ** 96))
    assert 0.90 < touched / 20 < 0.92
    live = 96 * 8192
    need = work.decode_step_bytes(cfg, traffic, live)
    d, expert = 4096, 3 * 4096 * 1280
    outside = work_kda.gqa_mixer_params(cfg) \
        + 3 * work_kda.delta_mixer_params(cfg) \
        + 4 * (d * 320 + 320 + 2 * d + expert)
    by_hand = 2 * (outside + 4 * touched * expert + d * 196608 + d
                   + 96 * d) \
        + live * 2112 + 3 * 96 * 2 * (4194304 + 147456)
    assert need == pytest.approx(by_hand, rel=1e-12)
    # ISSUE 53's table: state rows 2.4 GB, experts 2.3, pages 1.6 (1.7 with
    # the scales), head 1.6, the rest 1.2: 9.1 GB a tick
    assert abs(3 * 96 * work_kda.state_step_bytes(cfg) / 1e9 - 2.50) < 0.01
    assert abs(2 * 4 * touched * expert / 1e9 - 2.29) < 0.01
    assert abs(live * 2112 / 1e9 - 1.66) < 0.01
    assert abs(2 * outside / 1e9 - 1.18) < 0.01
    assert 9.1e9 < need < 9.4e9
    assert work.decode_step_bytes(cfg, traffic, 2 * live) - need \
        == pytest.approx(live * 2112)
    # a chunk of 100 tokens of one delta layer: one block of 64 (the
    # program's own, a constant there too) and one of 36; a head's pairs
    # below the diagonal 2016 + 630, up to it 100 more
    from mxnet_tpu.ops import kda
    assert work_kda.BLOCK == kda.BLOCK == 64
    flops, moved = work_kda.chunk_work(cfg, 100)
    below = 64 * 63 // 2 + 36 * 35 // 2
    assert flops == 2 * 4 * 24576 * 100 + 64 * (
        2 * below * 256 + 2 * (below + 100) * 256 + 6 * 100 * 128 * 128)
    assert moved == 2 * (4194304 + 147456) + 100 * (6 * 8192 + 64) * 2
    # a full chunk: 17.6 GFLOP a layer, 0.09 ms at the bf16 peak; 210 MB,
    # 0.26 ms at the HBM peak: the streams' bytes bound it
    flops, moved = work_kda.chunk_work(cfg, 2048)
    assert 1.7e10 < flops < 1.8e10 and 2.0e8 < moved < 2.2e8


def _facts(cfg, rows, chunks):
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
    scoped = ({"fusion.1": "kda/step", "fusion.2": "kda/conv",
               "fusion.3": "moe/experts", "fusion.9": "kda/gate_norm"},
              {"fusion.4": "kda/chunk", "fusion.5": "kda/solve",
               "fusion.6": "linear", "fusion.7": "kda/conv"})
    spans = [("serve.readback", 0, 1, {"kda_rows": r}) for r in rows] \
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
    rows, chunks = [288, 285], [2048, 1000]
    facts = _facts(cfg, rows, chunks)
    assert read["kda_rows_per_tick"](facts) == np.mean(rows)
    # 2 x (300 + 100 + 100) in the decode runs and 100 + 40 + 50 in the
    # chunk's, of 2 x 700 + 250 busy
    assert read["kda_device_pct.serve"](facts) == pytest.approx(
        100.0 * (2 * 500 + 190) / (2 * 700 + 250))
    assert read["kda_step_hbm_util_pct"](facts) == pytest.approx(
        100 * np.mean(rows) * work_kda.state_step_bytes(cfg) / 300e-9
        / 819e9)
    floor = np.mean([max(f / 197e12, b / 819e9) for f, b in (
        work_kda.chunk_work(cfg, t) for t in chunks)]) * 3
    assert read["kda_chunk_roofline_pct"](facts) == pytest.approx(
        100 * floor / 140e-9)
    # a window whose programs have no delta scope leaves the metrics out
    facts = _facts(cfg, rows, chunks)
    facts["scope_maps"] = {m: {k: "linear" for k in names}
                           for m, names in facts["scope_maps"].items()}
    for name in NEW[:3]:
        assert read[name](facts) is None, name


def test_readers_return_nothing_where_the_program_has_nothing(loaded):
    """On a program without the scope and the counter this PR adds (the
    parent's), the new readers leave their metric out and do not raise."""
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
# the share, the cell's driver and the probe's faults, at a tiny size
# ---------------------------------------------------------------------------
TINY = dict(vocab_size=96, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16,
            linear_attn_config=dict(short_conv_kernel_size=4, head_dim=16,
                                    num_heads=4, num_kv_heads=None),
            intermediate_size=128, moe_intermediate_size=32,
            n_routed_experts=32, num_experts_per_tok=4,
            held_n_routed_experts=8, first_held_expert=8,
            serve_num_hidden_layers=4, max_position_embeddings=64,
            serve_dtype="float32")
TINY_TRAFFIC = dict(tiny.TINY_TRAFFIC["tiny_backlog"],
                    driver="serve_ticks_rows", kv_dtype="bfloat16",
                    cache_len=512, page_tokens=16, prefill_chunk=96,
                    max_prefill=320, slots=3, prompt_min=40, prompt_max=300,
                    output_min=4, output_max=12, check_prompt=288,
                    check_decode=4)


def tiny_config(cfg, **over):
    """The configuration at the toy's widths: one period (attention, then
    three delta layers), matrices wider than the cell's 0.02 so that every
    mechanism moves the output, decays of 0.5 to 0.98 a step so that 300
    positions forget."""
    wider = {"_weight$": dict(std=0.08),
             "_kda_dt_bias$": dict(low=-3.0, high=0.0)}
    init = [dict(r, **wider.get(r["match"], {})) for r in cfg["init"]]
    limits = {"serve_ticks_rows": {"logp_atol.bfloat16": {
        "value": 1e-4, "why": "float32 against float32"}}}
    return dict(cfg, init=init, limits=limits, **dict(TINY, **over))


def test_the_16_shares_of_one_expert_layer_add_up(loaded):
    """Sixteen chips with two of the 32 experts each, sigmoid scores over all
    32, the 4 largest of score + bias renormalised: their shares of one
    layer, the shared expert counted in one of them, are the uncut
    reference's layer."""
    import mxnet_tpu as mx

    cfg = tiny_config(loaded["config"])
    n = "layer0_"
    rng = np.random.default_rng(3)
    d, m, e = cfg["hidden_size"], cfg["moe_intermediate_size"], 32
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape) * 0.3,
                                      jnp.float32)
    whole = {n + "moe_gate_weight": draw(d, e),
             n + "moe_gate_bias": draw(e),
             n + "moe_expert_gate_weight": draw(e, d, m),
             n + "moe_expert_up_weight": draw(e, d, m),
             n + "moe_expert_down_weight": draw(e, m, d),
             n + "moe_shared_gate_weight": draw(d, m),
             n + "moe_shared_up_weight": draw(d, m),
             n + "moe_shared_down_weight": draw(m, d)}
    x = draw(2, 5, d) / 0.3
    uncut = dict(cfg, held_n_routed_experts=e, first_held_expert=0)
    want = ref._experts(whole, n, uncut, x)
    total = 0.0
    for chip, first in enumerate(range(0, e, 2)):
        sym = mx.sym.MoEFFN(
            mx.sym.Variable("data"), num_experts=e, hidden_size=m,
            gated=True, num_experts_per_tok=4, score_func="sigmoid",
            score_bias=True, norm_topk=True, num_held=2, first_held=first,
            name="moe", **({"n_shared_experts": 1} if chip == 0 else {}))
        ex = sym.simple_bind(mx.cpu(), grad_req="null", data=x.shape)
        ex.arg_dict["data"]._set_data(x)
        for arg in sym.list_arguments():
            if arg == "data":
                continue
            value = whole[n + arg]
            if "_expert_" in arg:
                value = value[first:first + 2]
            ex.arg_dict[arg]._set_data(value)
        ex.forward(is_train=False)
        total = total + ex.outputs[0].data
    assert chip == 15
    assert float(jnp.max(jnp.abs(total - want))) < 1e-4
    # and not without the shared expert; one share is not the layer
    alone = ref._experts(whole, n, dict(uncut, n_shared_experts=0), x)
    assert float(jnp.max(jnp.abs(alone - want))) > 1e-2
    part = ref._experts(whole, n, cfg, x)
    assert float(jnp.max(jnp.abs(part - want))) > 1e-3


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory, loaded):
    root = tiny.make_root(tmp_path_factory.mktemp("bench_solar2"))
    with open(os.path.join(root, "chipbench/configs/tiny-solar2.json"),
              "w") as f:
        json.dump(tiny_config(loaded["config"]), f)
    with open(os.path.join(root, manifest.traffic_path("tiny_backlog_kda")),
              "w") as f:
        json.dump(TINY_TRAFFIC, f)
    man = manifest.load_manifest(root)
    man["configs"].append({
        "name": "tiny-solar2", "source": "test", "reduced": [],
        "file": "chipbench/configs/tiny-solar2.json",
        "why": "CPU test size"})
    man["workloads"].append({
        "name": "tiny_solar2_serve", "config": "tiny-solar2",
        "traffic": "tiny_backlog_kda", "chips": 1, "why": "CPU test size"})
    for met in man["end_to_end"] + man["per_layer"]:
        if CELL in met.get("workloads", ()):
            met["workloads"].append("tiny_solar2_serve")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    return root


def test_the_cells_driver_at_a_tiny_size(tiny_root):
    """``serve_ticks_rows`` end to end on the CPU: a backlog through
    ``DecodeServer`` over a state group of two-leaf rows beside paged
    attention and held experts, every finished request at exactly its
    length, then the comparison with the reference (three chunks of 96, the
    delta rule's chunk form from a carried state and tail; 4 decode rows)."""
    import mxnet_tpu as mx
    from chipbench import spans

    assert manifest.validate(manifest.load_manifest(tiny_root),
                             tiny_root) == []
    cell = manifest.load_cell("tiny_solar2_serve", root=tiny_root)
    counters = harness.CompileCounters().install()
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
    # what the new counter's reader reads: a tick's rows in the arguments
    # of its serve.readback span, (slot, delta layer) pairs
    notes = [a for name, _, _, a in spans.spans_of(spans.program_events())
             if name == "serve.readback" and "kda_rows" in a]
    assert notes and all(a["kda_rows"] % 3 == 0 for a in notes)
    assert max(a["kda_rows"] for a in notes) == 3 * 3
    window = {"_aligned_serve": {"spans": [
        ("serve.readback", 0, 1, a) for a in notes[-20:]]}}
    per_tick = manifest.load_reader("kda_rows_per_tick", tiny_root)(window)
    assert 3 <= per_tick <= 9
    from mxnet_tpu import obs
    snap = obs.registry.snapshot()
    assert snap["mx_kda_rows_total"]["series"][0]["value"] > 0
    row = 3 * ((4 - 1) * 3 * 64 * 4 + 4 * 16 * 16 * 4)   # float32 streams
    assert snap["mx_kda_state_bytes"]["series"][0]["value"] == 3 * row


@pytest.fixture(scope="module")
def probe():
    sys.path.insert(0, os.path.join(manifest.ROOT, "benchmarks"))
    try:
        import probe_solar2_faults
    finally:
        sys.path.pop(0)
    return probe_solar2_faults


@pytest.fixture(scope="module")
def tiny_case(loaded):
    from chipbench.drivers import serve_ticks, serve_ticks_by_leaf

    cfg = tiny_config(loaded["config"])
    shapes = serve_ticks.weight_shapes(harness.build_symbol(cfg), cfg)
    return cfg, serve_ticks_by_leaf.make_params(shapes, cfg, 11, "float32")


@pytest.mark.parametrize("which", [
    "sound", "beta_not_doubled", "no_decay", "corrected_before_decay",
    "tail_not_carried", "no_l2_norm", "no_gqa_gate"])
def test_the_probes_faults_at_a_tiny_size(probe, tiny_case, which):
    """The comparison the chip's probe makes, on the CPU in float32: sound
    programs agree with the reference to rounding, every planted fault of
    the mechanism moves the median row by a thousand times that (a tail not
    carried by a hundred times: it spoils three positions of each chunk, 192
    and 96 positions before the rows compared)."""
    import mxnet_tpu as mx

    cfg, params = tiny_case
    assert which == "sound" or which in probe.FAULTS
    got = probe.reading(cfg, TINY_TRAFFIC, dict(params), 11, which, mx.cpu(),
                        1e-4)
    assert got["statistic"] == "row_rms_median" and got["positions"] == 5
    if which == "sound":
        assert got["ok"] and got["max_abs_dlogp"] < 1e-5
    else:
        least = 1e-4 if which == "tail_not_carried" else 1e-3
        assert not got["ok"] and got["row_rms_median"] > least, got
    from mxnet_tpu.ops import kda
    assert kda.mix.__module__ == kda._step.__module__ == kda.__name__


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
late = [m for m in ("chipbench.work_kda", "chipbench.reference.solar_open2",
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
