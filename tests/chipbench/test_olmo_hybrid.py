"""Configuration ``olmo-hybrid-7b`` and its cell ``olmoh_serve_rollouts``: the
published numbers pinned against the catalog, the parameter count and the
cut's byte table by hand, ``work_gdn``'s counts by hand, each new reader on
synthetic facts and on a recorded run's spans, the traffic against ISSUE 57's
table, the system against the plain reference through chunked prefill and
decode over the paged int8 pools of a KV-head count whose scale row is
padded, the cell's own loop driver end to end at a tiny size on the CPU, and
every fault the chip's probe plants failing the comparison there.  It asserts
what its own cell and readers state, and no count of all cells or metrics."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, manifest, run, work, work_gdn

import tiny

CELL, CONFIG = "olmoh_serve_rollouts", "olmo-hybrid-7b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("gdn_device_pct.serve", "gdn_step_hbm_util_pct",
       "gdn_chunk_roofline_pct", "gdn_rows_per_tick")


@pytest.fixture(scope="module")
def loaded():
    return manifest.load_cell(CELL)


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_catalog_key_at_its_published_value(loaded):
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Olmo-Hybrid-7B")
    entry = manifest.find(manifest.load_manifest()["configs"], CONFIG,
                          "config")
    cfg = loaded["config"]
    assert entry["source"] == cfg["source"] == row["source_url"]
    assert len(entry["source"]) <= 200 and 1 <= len(entry["why"]) <= 200
    assert entry["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        assert cfg[key] == value, key
    assert cfg["model_type"] == "olmo_hybrid"
    assert cfg["layer_types"] == (["linear_attention"] * 3
                                  + ["full_attention"]) * 8
    assert cfg["rope_parameters"] == {"rope_theta": None}
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["vocab_size"]) == (3840, 11008, 100352)
    assert (cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"]) \
        == (30, 96, 192, 4)
    assert (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"]) == (30, 30, 128)
    assert (cfg["num_hidden_layers"], cfg["serve_num_hidden_layers"]) \
        == (32, 8)
    assert cfg["serve_dtype"] == "bfloat16"
    assert "four pipeline stages" in cfg["deployment"] \
        and "4 x" in cfg["deployment"]
    assert set(cfg["assumed"]) >= {
        "norm_place", "qk_norm", "rotation", "activations", "convolution",
        "decay", "types", "init", "left_out"}
    assert "it wins" in cfg["assumed"]["sources"]
    # this file's own keys for the builder, each among the assumed
    assert (cfg["norm_after"], cfg["attn_qk_norm"]) == (True, "projection")
    assert set(cfg["limits"]) == {"serve_ticks", "serve_ticks_by_leaf"}
    one, two = (cfg["limits"][d]["logp_atol.int8"]["value"]
                for d in ("serve_ticks", "serve_ticks_by_leaf"))
    assert one == two
    assert cfg["counts"] == {
        "decode_step_bytes": "chipbench.work_gdn:decode_step_bytes"}


def test_manifest_entries(loaded):
    """What this cell and its four readers state, and nothing of any other
    cell or list: an appended cell or metric trips nothing here."""
    assert manifest.validate(manifest.load_manifest()) == []
    cell = loaded["cell"]
    assert cell["chips"] == 1 and 1 <= len(cell["why"]) <= 200
    assert "8 of 32" in cell["why"] and "96 slots" in cell["why"]
    assert cell["traffic"] == "backlog_p256-1024_o1024-3072_s96"
    reported = {m["name"] for m in loaded["end_to_end"]}
    assert reported >= {"serve_out_tokens_per_s", "setup_s"}
    assert "serve_gap_p95_ms" not in reported
    mine = {m["name"]: m for m in loaded["per_layer"]}
    assert set(mine) >= set(NEW) | {
        "prefill_chunk_device_ms", "tick_host_ms", "slot_occupancy_pct",
        "peak_hbm_gb.serve", "scope_join_found_pct.serve", "compile_s"}
    for m in mine.values():
        assert m["moves"] in reported, m["name"]
    for name in NEW:
        assert mine[name]["workloads"] == [CELL]
        assert mine[name]["moves"] == "serve_out_tokens_per_s"
        assert os.path.exists(os.path.join(
            manifest.ROOT, manifest.reader_path(name)))


def test_the_traffic_file_is_the_issues_table(loaded):
    traffic = loaded["traffic"]
    want = dict(driver="serve_ticks_by_leaf", loop="backlog", slots=96,
                cache_len=4096, page_tokens=16, prefill_chunk=512,
                max_prefill=1024, kv_dtype="int8", prompt_min=256,
                prompt_max=1024, output_min=1024, output_max=3072,
                requests=512, block=64, order_seed=0, warmup_ticks=1024,
                trace_seconds=30, trace_ticks=96, check_prompt=1536,
                check_decode=8)
    assert {k: traffic[k] for k in want} == want
    assert set(traffic) == set(want) | {"note"}
    from chipbench import traffic as traffic_mod
    whole = traffic_mod.backlog(traffic, 8, 0)
    assert max(len(p) + o for p, o in whole) + 1 <= traffic["cache_len"]
    assert max(len(p) for p, _ in whole) <= traffic["max_prefill"]
    prompts = [len(p) for p, _ in whole]
    assert 540 < np.mean(prompts) < 570
    assert 1800 < np.mean([o for _, o in whole]) < 1900
    assert traffic["check_prompt"] == 3 * traffic["prefill_chunk"]
    # the file's note: the slots fill in 144 ticks, the first turns over 926
    # ticks later, so a window opened after output_min ticks holds chunks
    chunks = np.cumsum([-(-n // traffic["prefill_chunk"]) for n in prompts])
    fill = chunks[traffic["slots"] - 1]
    first = min(c + o for c, (_, o) in zip(chunks, whole))
    assert fill == 144 and first - fill == 926
    assert first - fill < traffic["warmup_ticks"] >= traffic["output_min"]


def _full_shapes(cfg):
    from chipbench.drivers import serve_ticks

    return serve_ticks.weight_shapes(harness.build_symbol(cfg), cfg)


def test_the_parameter_count_and_the_cuts_byte_table(loaded):
    """ISSUE 57's numbers from the equations and from the shapes the builder
    infers (nothing is allocated): 215.6 M a Gated DeltaNet layer, 185.8 M an
    attention layer, 7.43 G whole, 2.44 G as run, 13.69 MB of state a slot,
    12.4 GB of weights, state rows and pages."""
    cfg, traffic = loaded["config"], loaded["traffic"]
    d, f, v = 3840, 11008, 100352
    mlp = 3 * d * f
    gdn = d * (2 * 2880 + 3 * 5760 + 2 * 30) + 11520 * 4 + 2 * 30 + 192
    att = 4 * d * d + 2 * 3840
    assert work_gdn.delta_mixer_params(cfg) == gdn
    assert work_gdn.attention_mixer_params(cfg) == att
    assert abs(gdn / 1e6 - 88.75) < 0.005 and abs(mlp / 1e6 - 126.81) < 0.005
    assert abs(work_gdn.layer_params(cfg, True) / 1e6 - 215.6) < 0.05
    assert abs(work_gdn.layer_params(cfg, False) / 1e6 - 185.8) < 0.05
    whole = 24 * (gdn + mlp + 2 * d) + 8 * (att + mlp + 2 * d) \
        + 2 * v * d + d
    assert work_gdn.model_params(cfg) == whole
    assert abs(whole / 1e9 - 7.43) < 0.005
    assert work_gdn.delta_layers(cfg, 32) == 24
    assert work_gdn.delta_layers(cfg) == 6
    shapes = _full_shapes(cfg)
    size = lambda n: int(np.prod(shapes[n]))
    mixer = lambda l, part: sum(size(n) for n in shapes if n.startswith(
        "layer%d_%s" % (l, part)))
    for l in (0, 1, 2, 4, 5, 6):
        assert mixer(l, "gdn_") == gdn
    for l in (3, 7):
        assert sum(size("layer%d_%s" % (l, p)) for p in (
            "q_weight", "k_weight", "v_weight", "attout_weight",
            "q_norm_gamma", "k_norm_gamma")) == att
        assert shapes["layer%d_q_norm_gamma" % l] == (3840,)
    assert shapes["layer0_gdn_conv_weight"] == (11520, 4)
    assert shapes["layer0_gdn_a_weight"] == (30, 3840)
    assert shapes["layer0_gdn_g_weight"] == (5760, 3840)
    assert shapes["layer0_gdn_out_norm_gamma"] == (192,)
    assert shapes["layer0_att_norm_gamma"] == (3840,)
    total = sum(size(n) for n in shapes)
    assert total == work_gdn.model_params(cfg, 8)
    assert abs(total / 1e9 - 2.44) < 0.005
    assert abs(2 * total / 1e9 - 4.87) < 0.005
    # the state group: 96 slots x 6 delta layers x (the float32 matrices and
    # 3 positions of the 11520 q, k, v channels in bfloat16)
    state, tail = work_gdn.state_row_bytes(cfg)
    assert (state, tail) == (30 * 96 * 192 * 4, 3 * 11520 * 2)
    row = 6 * (state + tail)
    assert abs(row / 1e6 - 13.69) < 0.005
    assert abs(traffic["slots"] * row / 1e9 - 1.31) < 0.005
    # an attention layer's int8 pages: 7680 B a position and the scales (60
    # floats that count; the pool's row holds 64)
    per = work_gdn.kv_bytes_per_token(cfg, 1)
    assert per == 2 * 30 * 128 + 2 * 30 * 4
    from mxnet_tpu.ops.attention import scale_group
    pool = 2 * traffic["slots"] * traffic["cache_len"] \
        * (2 * 30 * 128 + 4 * scale_group(30))
    assert abs(pool / 1e9 - 6.24) < 0.005
    assert abs((2 * total + traffic["slots"] * row + pool) / 1e9 - 12.4) \
        < 0.05


def test_counts_by_hand(loaded):
    cfg, traffic = loaded["config"], loaded["traffic"]
    live = 96 * 1600
    need = work.decode_step_bytes(cfg, traffic, live)
    d = 3840
    layers = 6 * work_gdn.layer_params(cfg, True) \
        + 2 * work_gdn.layer_params(cfg, False)
    by_hand = 2 * (layers + d * 100352 + d + 96 * d) \
        + 2 * live * 7920 + 6 * 96 * 2 * (2211840 + 69120)
    assert need == pytest.approx(by_hand, rel=1e-12)
    # ISSUE 57's arithmetic: 4.1 GB of matrices, 2.63 GB of state rows, 2.4
    # GB of pages at a mean 1.6 k live positions a slot
    assert abs(2 * (layers + d * 100352) / 1e9 - 4.1) < 0.05
    assert abs(6 * 96 * work_gdn.state_step_bytes(cfg) / 1e9 - 2.63) < 0.005
    assert abs(2 * live * 7920 / 1e9 - 2.43) < 0.01
    assert work.decode_step_bytes(cfg, traffic, 2 * live) - need \
        == pytest.approx(2 * live * 7920)
    # a chunk of 100 tokens of one delta layer: one block of 64 (the
    # program's own, a constant there too) and one of 36; a head's pairs
    # below the diagonal 2016 + 630, up to it 100 more
    from mxnet_tpu.ops import gdn
    assert work_gdn.BLOCK == gdn.BLOCK == 64
    flops, moved = work_gdn.chunk_work(cfg, 100)
    below = 64 * 63 // 2 + 36 * 35 // 2
    assert flops == 2 * 4 * 11520 * 100 + 30 * (
        2 * (2 * below + 100) * (96 + 192) + 6 * 100 * 96 * 192)
    assert moved == 2 * (2211840 + 69120) \
        + 100 * (2 * 2880 + 3 * 5760 + 60) * 2
    # a full chunk: 2.3 GFLOP a layer, 0.012 ms at the bf16 peak; 28 MB,
    # 0.034 ms at the HBM peak: the streams' bytes bound it
    flops, moved = work_gdn.chunk_work(cfg, 512)
    assert 2.2e9 < flops < 2.5e9 and 2.7e7 < moved < 2.9e7


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
    scoped = ({"fusion.1": "gdn/step", "fusion.2": "gdn/conv",
               "fusion.3": "linear", "fusion.9": "gdn/gate_norm"},
              {"fusion.4": "gdn/chunk", "fusion.5": "gdn/solve",
               "fusion.6": "linear", "fusion.7": "gdn/conv"})
    spans = [("serve.readback", 0, 1, {"gdn_rows": r}) for r in rows] \
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
    rows, chunks = [576, 570], [512, 300]
    facts = _facts(cfg, rows, chunks)
    assert read["gdn_rows_per_tick"](facts) == np.mean(rows)
    # 2 x (300 + 100 + 100) in the decode runs and 100 + 40 + 50 in the
    # chunk's, of 2 x 700 + 250 busy
    assert read["gdn_device_pct.serve"](facts) == pytest.approx(
        100.0 * (2 * 500 + 190) / (2 * 700 + 250))
    assert read["gdn_step_hbm_util_pct"](facts) == pytest.approx(
        100 * np.mean(rows) * work_gdn.state_step_bytes(cfg) / 300e-9
        / 819e9)
    floor = np.mean([max(f / 197e12, b / 819e9) for f, b in (
        work_gdn.chunk_work(cfg, t) for t in chunks)]) * 6
    assert read["gdn_chunk_roofline_pct"](facts) == pytest.approx(
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
                 ("serve.readback", 0, 1, {"kda_rows": 3}),
                 ("serve.prefill", 0, 1, {"pos": 0, "tokens": 8})]}}
    for name in NEW:
        assert manifest.load_reader(name)(dict(facts)) is None, name
    facts["_aligned_serve"] = None
    for name in NEW:
        assert manifest.load_reader(name)(dict(facts)) is None, name


# ---------------------------------------------------------------------------
# the system against the reference, the cell's driver and the probe's faults,
# at a tiny size
# ---------------------------------------------------------------------------
TINY = dict(vocab_size=96, hidden_size=64, num_attention_heads=3,
            num_key_value_heads=3, head_dim=16, linear_num_key_heads=3,
            linear_num_value_heads=3, linear_key_head_dim=8,
            linear_value_head_dim=16, intermediate_size=128,
            serve_num_hidden_layers=4, max_position_embeddings=64,
            serve_dtype="float32")
TINY_TRAFFIC = dict(tiny.TINY_TRAFFIC["tiny_backlog"],
                    driver="serve_ticks_by_leaf", kv_dtype="bfloat16",
                    cache_len=512, page_tokens=16, prefill_chunk=96,
                    max_prefill=320, slots=3, prompt_min=40, prompt_max=300,
                    output_min=4, output_max=12, check_prompt=288,
                    check_decode=4)


def tiny_config(cfg, **over):
    """The configuration at the toy's widths: one period (three delta
    layers, then attention; three heads, so that an int8 pool's scale row is
    padded as the cell's is), matrices wider than the cell's 0.02 so that
    every mechanism moves the output, decays of 0.5 to 0.98 a step so that
    300 positions forget."""
    wider = {"_weight$": dict(std=0.08),
             "_gdn_a_weight$": dict(std=0.05),
             "_gdn_b_weight$": dict(std=0.2),
             "_gdn_dt_bias$": dict(low=-3.0, high=0.0)}
    init = [dict(r, **wider.get(r["match"], {})) for r in cfg["init"]]
    atol = {"value": 1e-4, "why": "float32 against float32"}
    limits = {d: {"logp_atol.bfloat16": atol, "logp_atol.int8": dict(
        atol, value=3e-2)} for d in ("serve_ticks", "serve_ticks_by_leaf")}
    return dict(cfg, init=init, limits=limits, **dict(TINY, **over))


@pytest.fixture(scope="module")
def tiny_case(loaded):
    from chipbench.drivers import serve_ticks, serve_ticks_by_leaf

    cfg = tiny_config(loaded["config"])
    shapes = serve_ticks.weight_shapes(harness.build_symbol(cfg), cfg)
    return cfg, serve_ticks_by_leaf.make_params(shapes, cfg, 11, "float32")


def test_prefill_and_steps_over_int8_pages_match_the_reference(tiny_case):
    """``prefill`` in three chunks and four ``step``s through the state rows
    and the paged int8 pools of three KV heads (a token's scales padded from
    6 floats to 8) against the reference's one pass: logits, not tokens.
    The int8 pool's rounding is all that separates them (3e-2, as
    ``tests/test_decoder_lm.py`` holds its int8 pools to; the same programs
    over bfloat16 pages of float32 values agree to 1e-4, the driver's test
    below)."""
    import mxnet_tpu as mx
    from chipbench.drivers import serve_ticks
    from mxnet_tpu.ops.attention import QuantKV, scale_group

    cfg, params = tiny_case
    traffic = dict(TINY_TRAFFIC, kv_dtype="int8")
    nd = {n: mx.nd.NDArray(v, mx.cpu()) for n, v in params.items()}
    pred = serve_ticks.build_server(harness.build_symbol(cfg), traffic, nd,
                                    mx.cpu())[0]
    got = serve_ticks.check_against_reference(pred, cfg, traffic, params, 11,
                                              3e-2)[0]
    assert got["ok"] and got["positions"] == 5, got
    assert 1e-6 < got["max_abs_dlogp"] < 3e-2
    kinds = [l.kind for l in pred.cache_layouts()]
    assert kinds == ["state"] * 3 + ["full"]
    # the state row's two leaves: widths that differ, read off the probe
    layout = pred.cache_layouts()[0]
    assert (layout.key_width, layout.value_width) == (2 * 24 + 48, 16)
    assert pred.state_row_bytes("gdn_rows") == 3 * (
        3 * 96 * 4 + 3 * 8 * 16 * 4)
    assert pred.state_nodes("gdn_rows") == 3
    assert scale_group(3) == 8
    pools = [p for p in pred._probe_cache_shapes()[3]
             if isinstance(p, QuantKV)]
    assert pools and pools[0].scale.shape[-1] == 3


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory, loaded):
    root = tiny.make_root(tmp_path_factory.mktemp("bench_olmoh"))
    with open(os.path.join(root, "chipbench/configs/tiny-olmoh.json"),
              "w") as f:
        json.dump(tiny_config(loaded["config"]), f)
    with open(os.path.join(root, manifest.traffic_path("tiny_backlog_gdn")),
              "w") as f:
        json.dump(TINY_TRAFFIC, f)
    man = manifest.load_manifest(root)
    man["configs"].append({
        "name": "tiny-olmoh", "source": "test", "reduced": [],
        "file": "chipbench/configs/tiny-olmoh.json",
        "why": "CPU test size"})
    man["workloads"].append({
        "name": "tiny_olmoh_serve", "config": "tiny-olmoh",
        "traffic": "tiny_backlog_gdn", "chips": 1, "why": "CPU test size"})
    for met in man["end_to_end"] + man["per_layer"]:
        if CELL in met.get("workloads", ()):
            met["workloads"].append("tiny_olmoh_serve")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    return root


def test_the_cells_driver_at_a_tiny_size(tiny_root):
    """``serve_ticks_by_leaf`` end to end on the CPU: a backlog through
    ``DecodeServer`` over a state group of two-leaf rows beside paged
    attention, every finished request at exactly its length, then the
    comparison with the reference (three chunks of 96, the matrix chunk form
    from a carried state and tail; 4 decode rows); and the rows counter's
    reader on the spans the run recorded."""
    import mxnet_tpu as mx
    from chipbench import spans

    assert manifest.validate(manifest.load_manifest(tiny_root),
                             tiny_root) == []
    cell = manifest.load_cell("tiny_olmoh_serve", root=tiny_root)
    counters = harness.CompileCounters().install()
    res = run.run_cell(cell, 2 ** 31 + 13, 1.0, False, [mx.cpu()], counters,
                       harness.Phases(), harness.MemoryPeak(1))
    assert all(c["ok"] for c in res["checks"]), res["checks"]
    first = res["checks"][0]
    assert first["max_abs_dlogp"] < 1e-4 and first["positions"] == 5
    assert res["failed"] == 0 and res["side"]["queue_left"] > 0
    assert res["side"]["requests_completed"] >= 1
    assert counters.in_window == 0
    # what the new counter's reader reads: a tick's rows in the arguments
    # of its serve.readback span, (slot, delta layer) pairs
    notes = [a for name, _, _, a in spans.spans_of(spans.program_events())
             if name == "serve.readback" and "gdn_rows" in a]
    assert notes and all(a["gdn_rows"] % 3 == 0 for a in notes)
    assert max(a["gdn_rows"] for a in notes) == 3 * 3
    window = {"_aligned_serve": {"spans": [
        ("serve.readback", 0, 1, a) for a in notes[-20:]]}}
    per_tick = manifest.load_reader("gdn_rows_per_tick", tiny_root)(window)
    assert 3 <= per_tick <= 9
    from mxnet_tpu import obs
    snap = obs.registry.snapshot()
    assert snap["mx_gdn_rows_total"]["series"][0]["value"] > 0
    row = 3 * ((4 - 1) * 96 * 4 + 3 * 8 * 16 * 4)     # float32 streams
    assert snap["mx_gdn_state_bytes"]["series"][0]["value"] == 3 * row
    assert "GatedDeltaNet" in snap["mx_gdn_rows_total"]["help"]


@pytest.fixture(scope="module")
def probe():
    sys.path.insert(0, os.path.join(manifest.ROOT, "benchmarks"))
    try:
        import probe_olmoh_faults
    finally:
        sys.path.pop(0)
    return probe_olmoh_faults


@pytest.mark.parametrize("which", [
    "sound", "beta_not_doubled", "no_decay", "no_l2_norm", "gate_sigmoid",
    "norm_before", "qk_norm_by_head", "tail_not_carried",
    "corrected_before_decay", "chunk_default_precision"])
def test_the_probes_faults_at_a_tiny_size(probe, tiny_case, which):
    """The comparison the chip's probe makes, on the CPU in float32: sound
    programs agree with the reference to rounding, every planted fault of
    the mechanism moves the log-probabilities by a thousand times that (a
    tail not carried by a hundred times: it spoils three positions of each
    chunk, 192 and 96 positions before the rows compared).  The chunk form's
    products at the default precision are the same float32 products on the
    CPU: that fault is the chip's to show, here it only has to build."""
    import mxnet_tpu as mx

    cfg, params = tiny_case
    assert which == "sound" or which in probe.FAULTS
    got = probe.reading(cfg, TINY_TRAFFIC, dict(params), 11, which, mx.cpu(),
                        1e-4)
    assert got["positions"] == 5
    if which in ("sound", "chunk_default_precision"):
        assert got["ok"] and got["max_abs_dlogp"] < 1e-5
    else:
        # (unnormed keys let the state overflow: NaN is no agreement either)
        least = 1e-4 if which == "tail_not_carried" else 1e-3
        assert not got["ok"] and not got["max_abs_dlogp"] <= least, got
    from mxnet_tpu.ops import gdn
    assert gdn.mix.__module__ == gdn.__name__ and gdn.BETA_SCALE == 2.0 \
        and gdn.PRECISION == "highest"


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
late = [m for m in ("chipbench.work_gdn", "chipbench.reference.olmo_hybrid",
                    "chipbench.drivers.serve_ticks_by_leaf",
                    "mxnet_tpu.models.decoder_lm")
        if m in sys.modules]
print("LATE", late, "COMPILES", len(compiles))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=manifest.ROOT)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "LATE [] COMPILES 0" in out.stdout, out.stdout
