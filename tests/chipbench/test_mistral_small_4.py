"""Configuration ``mistral-small-4-119b`` and its cell
``mistral4_serve_longdoc``: the published numbers pinned, the cut's byte
table from the shapes the builder infers, ``work_mla``'s counts by hand, each
new reader on synthetic facts, the traffic against its cache, the shares of
one expert layer under softmax routing adding up to the uncut reference's
layer, and the cell's own loop driver (``serve_ticks_rows``: the comparison
by the median row) end to end at a tiny size on the CPU, a planted fault
failing it."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import correct, harness, manifest, run, work, work_mla
from chipbench.reference import mistral4 as ref

import tiny

CELL, CONFIG = "mistral4_serve_longdoc", "mistral-small-4-119b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("attn_latent_device_pct.serve", "attn_latent_hbm_util_pct",
       "attn_latent_chunk_roofline_pct", "attn_latent_rows_per_tick")


@pytest.fixture(scope="module")
def loaded():
    return manifest.load_cell(CELL)


def test_published_numbers(loaded):
    cfg = loaded["config"]
    assert cfg["model_type"] == "mistral4"
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_hidden_layers"]) == (4096, 32, 36)
    assert (cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["qk_head_dim"], cfg["v_head_dim"]) \
        == (1024, 256, 64, 64, 128, 128)
    assert cfg["rope_interleave"] is True and cfg["sliding_window"] is None
    assert cfg["rope_parameters"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 128,
        "llama_4_scaling_beta": 0.1, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 8192, "rope_theta": 10000,
        "rope_type": "yarn", "type": "yarn"}
    assert (cfg["n_routed_experts"], cfg["num_experts_per_tok"],
            cfg["n_shared_experts"], cfg["moe_intermediate_size"]) \
        == (128, 4, 1, 2048)
    assert cfg["first_k_dense_replace"] == 0 and cfg["norm_topk_prob"]
    assert cfg["routed_scaling_factor"] == 1
    assert (cfg["n_group"], cfg["topk_group"]) == (1, 1)
    assert cfg["vocab_size"] == 131072 and not cfg["tie_word_embeddings"]
    assert cfg["rms_norm_eps"] == 1e-6
    assert cfg["max_position_embeddings"] == 1048576


def test_every_catalog_key_at_its_published_value(loaded):
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Mistral-Small-4-119B-2603")
    entry = manifest.find(manifest.load_manifest()["configs"], CONFIG,
                          "config")
    cfg = loaded["config"]
    assert entry["source"] == cfg["source"] == row["source_url"]
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    for key, value in row["config"].items():
        assert cfg[key] == value, key
    assert (cfg["serve_num_hidden_layers"], cfg["held_n_routed_experts"],
            cfg["first_held_expert"]) == (6, 16, 0)
    assert cfg["serve_dtype"] == "bfloat16"
    assert "8 chips share each layer" in cfg["deployment"]
    assert "no head axis" in cfg["deployment"]
    assert set(cfg["assumed"]) >= {"routing", "softmax_scale",
                                   "query_temperature"}
    # this file's own keys for the builder, the first of the three assumed
    assert (cfg["scoring_func"], cfg["topk_method"]) == ("softmax", "greedy")


def test_manifest_entries(loaded):
    man = manifest.load_manifest()
    assert manifest.validate(man) == []
    assert len(man["workloads"]) == 11
    assert sum(w["chips"] == 4 for w in man["workloads"]) == 1
    cell = loaded["cell"]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert "6 of 36" in cell["why"] and "1/8" in cell["why"]
    assert cell["traffic"] == "backlog_p16384-65536_o256-1024_s20_bf16"
    mine = {m["name"] for m in loaded["per_layer"]}
    theirs = {m["name"] for m in manifest.load_cell(
        "exaone_serve_reason")["per_layer"]}
    assert mine - theirs == set(NEW) | {"prefill_chunk_device_ms"}
    # its attention is under mx.attn_latent, it has no window layers and no
    # prediction block: those readers would find nothing
    # and the cell does not report serve_gap_p95_ms (5 % of a window's 559
    # ticks are the 28 last chunks of five long prompts, 0.6 % apart: the
    # percentile is one of them and hops with a tick more or less, PERF.md
    # section 7), so no metric that moves it may list the cell
    gap = {"decode_tick_device_ms", "decode_hbm_util_pct",
           "moe_device_pct.serve", "moe_experts_hbm_util_pct",
           "moe_shared_device_pct.serve"}
    assert theirs - mine == gap | {
        "attn_device_pct.serve", "attn_window_device_pct.serve",
        "mtp_accept_pct", "mtp_tokens_per_slot_tick", "mtp_device_pct.serve"}
    assert {m["name"] for m in loaded["end_to_end"]} == {
        "serve_out_tokens_per_s", "setup_s"}
    for m in loaded["per_layer"]:
        assert m["moves"] in ("serve_out_tokens_per_s", "setup_s")
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            assert os.path.exists(os.path.join(
                manifest.ROOT, manifest.reader_path(m["name"])))


def test_the_traffic_file_is_the_issues_table(loaded):
    traffic = loaded["traffic"]
    want = dict(driver="serve_ticks_rows", loop="backlog", slots=20,
                cache_len=66560, page_tokens=16, prefill_chunk=2048,
                max_prefill=65536, kv_dtype="bfloat16", prompt_min=16384,
                prompt_max=65536, output_min=256, output_max=1024,
                requests=128, block=64, order_seed=0, warmup_ticks=8,
                trace_seconds=30, trace_ticks=96, check_prompt=12288,
                check_decode=8)
    assert {k: traffic[k] for k in want} == want
    assert set(traffic) == set(want) | {"note"}
    from chipbench import traffic as traffic_mod
    queue = traffic_mod.backlog(dict(traffic, requests=traffic["block"]),
                                8, 0)
    assert max(len(p) + o for p, o in queue) + 1 <= traffic["cache_len"]
    assert max(len(p) for p, _ in queue) <= traffic["max_prefill"]
    mean = np.mean([len(p) for p, _ in queue])
    assert 35000 < mean < 36000
    assert traffic["check_prompt"] \
        > loaded["config"]["rope_parameters"][
            "original_max_position_embeddings"]
    # the comparison's limit is stated once, under the cell's driver's name:
    # where serve_ticks' loop asks for its own entry it is handed that one
    from chipbench.drivers import serve_ticks, serve_ticks_rows

    assert list(loaded["config"]["limits"]) == ["serve_ticks_rows"]
    with serve_ticks_rows._by_rows():
        assert serve_ticks.correct.limit(
            loaded["config"], "serve_ticks", "logp_atol.bfloat16") == 0.02
        # every other name of chipbench.correct is the module's own
        assert serve_ticks.correct.reference_of is correct.reference_of
        assert serve_ticks.correct.logp_of_probs is correct.logp_of_probs
    assert serve_ticks.correct is correct


def _full_shapes(cfg):
    from chipbench.drivers import serve_ticks

    return serve_ticks.weight_shapes(harness.build_symbol(cfg), cfg)


def test_the_cuts_byte_table(loaded):
    """ISSUE 50's table, from the shapes the builder infers (nothing is
    allocated): parameters in millions and GB at 2 bytes."""
    cfg, traffic = loaded["config"], loaded["traffic"]
    shapes = _full_shapes(cfg)
    size = lambda n: int(np.prod(shapes[n]))
    close = lambda got, millions: abs(got / 1e6 - millions) < 0.01
    assert close(size("layer0_q_a_weight"), 4.19)
    assert close(size("layer0_q_b_weight"), 4.19)
    assert close(size("layer0_kv_a_weight"), 1.31)
    assert close(size("layer0_latt_kv_b_weight"), 1.57)
    assert close(size("layer0_attout_weight"), 16.78)
    att = sum(size("layer0_" + n) for n in (
        "q_a_weight", "q_a_norm_gamma", "q_b_weight", "kv_a_weight",
        "kv_a_norm_gamma", "latt_kv_b_weight", "attout_weight"))
    assert close(att, 28.05)
    assert att == work_mla.attention_params(cfg)
    shared = sum(size("layer0_moe_shared_%s_weight" % p)
                 for p in ("gate", "up", "down"))
    assert close(shared, 25.17) and close(size("layer0_moe_gate_weight"),
                                          0.52)
    held = sum(size("layer0_moe_expert_%s_weight" % p)
               for p in ("gate", "up", "down"))
    assert shapes["layer0_moe_expert_gate_weight"][0] == 16
    assert close(held, 402.65)
    layer = sum(size(n) for n in shapes if n.startswith("layer0_"))
    assert close(layer, 456.40)
    ends = size("embed_weight") + size("head_weight")
    assert close(ends, 1073.74)
    total = sum(size(n) for n in shapes)
    assert total == 6 * layer + ends + size("final_norm_gamma")
    assert abs(2 * total / 1e9 - 7.62) < 0.005
    # the latent pool: 20 slots x 66,560 positions x 6 layers x 640 B (and
    # the scratch page)
    pages = traffic["slots"] * traffic["cache_len"] // traffic["page_tokens"]
    pool = 6 * (pages + 1) * traffic["page_tokens"] \
        * work_mla.row_values(cfg) * 2
    assert work_mla.row_values(cfg) * 2 == 640
    assert abs(pool / 1e9 - 5.11) < 0.005
    assert 12.7e9 < 2 * total + pool < 12.8e9


def test_counts_by_hand(loaded):
    cfg, traffic = loaded["config"], loaded["traffic"]
    # 20 rows touch 7.52 of the 16 held experts a layer
    assert work_mla.experts_touched(cfg, 20) == pytest.approx(
        16 * (1 - (1 - 4 / 128) ** 20))
    assert 7.5 < work_mla.experts_touched(cfg, 20) < 7.55
    live = 20 * 36000
    need = work.decode_step_bytes(cfg, traffic, live)
    d, m = 4096, 2048
    att = 4096 * 1024 + 1024 + 1024 * 32 * 128 + 4096 * 320 + 256 \
        + 32 * 192 * 256 + 32 * 128 * 4096
    layer = att + 2 * d + d * 128 + (1 + work_mla.experts_touched(cfg, 20)) \
        * 3 * d * m
    by_hand = 2 * (6 * layer + d * 131072 + 20 * d + d) + 6 * live * 640
    assert need == pytest.approx(by_hand, rel=1e-12)
    assert 6.7e9 < need < 6.9e9         # ISSUE 50: 4.0 GB + 2.8 GB
    assert work.decode_step_bytes(cfg, traffic, 2 * live) - need \
        == pytest.approx(6 * live * 640)
    # the absorbed form's floor: the rows once, W_kvb a layer
    assert work_mla.absorbed_step_bytes(cfg, 6 * live) == pytest.approx(
        6 * live * 640 + 6 * 32 * 192 * 256 * 2)
    # the expanded form's: a chunk of 2048 rows at positions 4096 ..: 6144
    # positions expanded, row i sees 4097 + i
    pairs = 2048 * 4096 + 2048 * 2049 // 2
    assert work_mla.expanded_chunk_flops(cfg, 4096, 2048) == pytest.approx(
        6 * (2 * 6144 * 32 * 192 * 256 + 2 * pairs * 32 * (128 + 128)))
    # ISSUE 50's 4.0 TFLOP of expanded attention at a mean context of 20k,
    # and 0.4 more to expand the 21k live positions once
    assert 4.3e12 < work_mla.expanded_chunk_flops(cfg, 19000, 2048) < 4.5e12


def test_each_new_reader_on_synthetic_facts(loaded, monkeypatch):
    """A hand-made window: two runs of the decode program and one of the
    chunk's.  Latent attention's time is what its scopes hold AND the
    compiler's moves between two of them (the gathered pages' re-layout,
    ``reshape.1`` and ``reshape.4``: no scope of their own, made by
    ``attn_latent/kv_gather``, fed to ``attn_latent/scores``); a move that
    brings a weight (``copy.2``) and one that leaves the layer
    (``reshape.6``) stay out."""
    from chipbench import trace

    monkeypatch.setattr(trace, "window_of", lambda p: (0, 4000))
    cfg = loaded["config"]
    read = {n: manifest.load_reader(n) for n in NEW}
    dec, chk = "jit__paged_decode_impl", "jit__chunk_impl"
    ops = [("fusion.7", 200, 300), ("reshape.1", 520, 60),
           ("fusion.9", 600, 200), ("copy.2", 820, 40),
           ("fusion.2", 900, 100), ("reshape.6", 1010, 30),
           ("fusion.3", 1550, 100), ("reshape.4", 1660, 20),
           ("fusion.4", 1700, 60), ("fusion.5", 1800, 90),
           ("fusion.7", 2100, 300), ("reshape.1", 2420, 60),
           ("fusion.9", 2500, 200), ("copy.2", 2720, 40),
           ("fusion.2", 2800, 100), ("reshape.6", 2910, 30)]
    scoped = ({"fusion.7": "attn_latent/kv_gather",
               "fusion.9": "attn_latent/scores", "fusion.2": "moe/experts",
               "reshape.1": "unscoped", "copy.2": "unscoped",
               "reshape.6": "unscoped"},
              {"fusion.3": "attn_latent/expand",
               "fusion.4": "attn_latent/scores", "fusion.5": "linear",
               "reshape.4": "unscoped"})
    ins = lambda scope, src=None, feeds=None: {
        "scope": scope, "moves": scope == "unscoped", "src": src,
        "feeds": feeds}
    between = ins("unscoped", "attn_latent/kv_gather", "attn_latent/scores")
    what = ({k: ins(v) for k, v in scoped[0].items()},
            {k: ins(v) for k, v in scoped[1].items()})
    what[0].update({
        "reshape.1": between,
        "copy.2": ins("unscoped", "env['layer0_moe_w']", "moe/experts"),
        "reshape.6": ins("unscoped", "attn_latent", "linear")})
    what[1]["reshape.4"] = between
    rows = [6 * 20 * 30000, 6 * 20 * 30020]
    spans = [("serve.readback", 0, 1, {"latent_rows": r}) for r in rows] \
        + [("serve.prefill", 0, 1, {"pos": 4096, "tokens": 2048,
                                    "slot": 3, "rid": 9, "head": False}),
           ("serve.prefill", 2, 3, {"pos": 6144, "tokens": 1000,
                                    "slot": 3, "rid": 9, "head": True})]
    facts = {
        "trace": {"devices": {0: {
            trace.MODULES_LINE: [(dec + "(1)", 100, 1000),
                                 (chk + "(2)", 1500, 400),
                                 (dec + "(1)", 2000, 1000)],
            trace.OPS_LINE: ops}}},
        "scope_maps": {dec: scoped[0], chk: scoped[1]},
        "instruction_maps": {
            m: {"source": "dispatched", "conflicts": 0, "instructions": w}
            for m, w in ((dec, what[0]), (chk, what[1]))},
        "_aligned_serve": {"spans": spans}, "config": cfg,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}
    assert read["attn_latent_rows_per_tick"](facts) == np.mean(rows)
    # 2 x (300 + 60 + 200) in the decode runs and 100 + 20 + 60 in the
    # chunk's, of 2 x 730 + 270 busy
    assert read["attn_latent_device_pct.serve"](facts) == pytest.approx(
        100.0 * (2 * 560 + 180) / (2 * 730 + 270))
    assert read["attn_latent_hbm_util_pct"](facts) == pytest.approx(
        100 * work_mla.absorbed_step_bytes(cfg, np.mean(rows))
        / 560e-9 / 819e9)
    flops = (work_mla.expanded_chunk_flops(cfg, 4096, 2048)
             + work_mla.expanded_chunk_flops(cfg, 6144, 1000)) / 2
    assert read["attn_latent_chunk_roofline_pct"](facts) == pytest.approx(
        100 * flops / 197e12 / 180e-9)
    # a program without instruction maps: the scopes alone
    del facts["instruction_maps"]
    monkeypatch.setattr("chipbench.moves.program_maps", lambda: (None, None))
    assert read["attn_latent_device_pct.serve"](facts) == pytest.approx(
        100.0 * (2 * 500 + 160) / (2 * 730 + 270))
    # and one whose maps have no latent scope leaves the metric out
    facts["scope_maps"] = {dec: {"fusion.2": "moe/experts"}}
    assert read["attn_latent_device_pct.serve"](facts) is None


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
# the share, and the cell's driver, at a tiny size
# ---------------------------------------------------------------------------
# (a vocabulary of 2048 and not the other toys' 96: the latent group shares
# prefixes, a prompt whose FIRST token is one a resident prompt began with
# maps that page and forks it, and the fork's program would compile inside
# the tiny window)
TINY = dict(vocab_size=2048, hidden_size=64, num_attention_heads=4, head_dim=16,
            v_head_dim=16, q_lora_rank=24, kv_lora_rank=16,
            qk_nope_head_dim=8, qk_rope_head_dim=8, qk_head_dim=16,
            intermediate_size=128, moe_intermediate_size=32,
            n_routed_experts=16, num_experts_per_tok=4,
            held_n_routed_experts=4, first_held_expert=4,
            serve_num_hidden_layers=2, max_position_embeddings=64,
            serve_dtype="float32")
TINY_TRAFFIC = dict(tiny.TINY_TRAFFIC["tiny_backlog"],
                    driver="serve_ticks_rows", kv_dtype="bfloat16",
                    cache_len=512, page_tokens=16, prefill_chunk=96,
                    max_prefill=320, slots=3, prompt_min=40, prompt_max=300,
                    output_min=4, output_max=12, check_prompt=300,
                    check_decode=4)


def tiny_config(cfg, **over):
    """The configuration at the toy's widths: YaRN over a window of 24 at
    factor 8 (the check's 300 positions cross it twelve times), matrices
    wider than the cell's 0.02 so that every mechanism moves the output."""
    init = [dict(r, std=0.08) if r["match"] == "_weight$" else r
            for r in cfg["init"]]
    rp = dict(cfg["rope_parameters"], original_max_position_embeddings=24,
              factor=8.0)
    return dict(cfg, init=init, rope_parameters=rp, **dict(TINY, **over))


def test_the_shares_of_one_expert_layer_add_up(loaded):
    """Four chips with four of the 16 experts each, softmax over all 16, the
    4 largest renormalised, no bias: their shares of one layer, the shared
    expert counted in one of them, are the uncut reference's layer."""
    import mxnet_tpu as mx

    cfg = tiny_config(loaded["config"])
    n = "layer0_"
    rng = np.random.default_rng(3)
    d, m = cfg["hidden_size"], cfg["moe_intermediate_size"]
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape) * 0.3,
                                      jnp.float32)
    whole = {n + "moe_gate_weight": draw(d, 16),
             n + "moe_expert_gate_weight": draw(16, d, m),
             n + "moe_expert_up_weight": draw(16, d, m),
             n + "moe_expert_down_weight": draw(16, m, d),
             n + "moe_shared_gate_weight": draw(d, m),
             n + "moe_shared_up_weight": draw(d, m),
             n + "moe_shared_down_weight": draw(m, d)}
    x = draw(2, 5, d) / 0.3
    uncut = dict(cfg, held_n_routed_experts=16, first_held_expert=0)
    want = ref._experts(whole, n, uncut, x)
    total = 0.0
    for chip, first in enumerate(range(0, 16, 4)):
        sym = mx.sym.MoEFFN(
            mx.sym.Variable("data"), num_experts=16, hidden_size=m,
            gated=True, num_experts_per_tok=4, score_func="softmax",
            score_bias=False, norm_topk=True, num_held=4, first_held=first,
            name="moe", **({"n_shared_experts": 1} if chip == 0 else {}))
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
    # and not without the shared expert, nor under sigmoid scores
    alone = ref._experts(whole, n, dict(uncut, n_shared_experts=0), x)
    assert float(jnp.max(jnp.abs(alone - want))) > 1e-2
    # one share is what the reference says of it
    part = ref._experts(whole, n, dict(cfg, held_n_routed_experts=4,
                                       first_held_expert=4), x)
    assert float(jnp.max(jnp.abs(part - want))) > 1e-3


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory, loaded):
    root = tiny.make_root(tmp_path_factory.mktemp("bench_mistral4"))
    cfg = tiny_config(loaded["config"])
    for limits in cfg["limits"].values():
        for lim in limits.values():
            lim["value"] = 1e-4         # float32 against float32
    with open(os.path.join(root, "chipbench/configs/tiny-mistral4.json"),
              "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root, manifest.traffic_path("tiny_backlog_mla")),
              "w") as f:
        json.dump(TINY_TRAFFIC, f)
    man = manifest.load_manifest(root)
    man["configs"].append({
        "name": "tiny-mistral4", "source": "test", "reduced": [],
        "file": "chipbench/configs/tiny-mistral4.json",
        "why": "CPU test size"})
    man["workloads"].append({
        "name": "tiny_mistral4_serve", "config": "tiny-mistral4",
        "traffic": "tiny_backlog_mla", "chips": 1, "why": "CPU test size"})
    for met in man["end_to_end"] + man["per_layer"]:
        if CELL in met.get("workloads", ()):
            met["workloads"].append("tiny_mistral4_serve")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    return root


def test_the_cells_driver_at_a_tiny_size(tiny_root):
    """``serve_ticks_rows`` end to end on the CPU: a backlog through
    ``DecodeServer`` over a latent pool, every finished request at exactly
    its length, then the comparison with the reference (chunks of 96, the
    expanded form, over a walked pool; 4 absorbed decode rows)."""
    import mxnet_tpu as mx
    from chipbench import spans

    assert manifest.validate(manifest.load_manifest(tiny_root),
                             tiny_root) == []
    cell = manifest.load_cell("tiny_mistral4_serve", root=tiny_root)
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
    took = harness.program_counters(since=before)
    assert took["mx_attn_latent_rows_total{form=absorbed}"] > 0
    assert took["mx_attn_latent_rows_total{form=expanded}"] > 0
    # what the new counter's reader reads: a tick's rows in the arguments
    # of its serve.readback span, (slot, position, layer) triples
    notes = [a for name, _, _, a in spans.spans_of(spans.program_events())
             if name == "serve.readback" and "latent_rows" in a]
    assert notes and all(a["latent_rows"] % 2 == 0 for a in notes)
    window = {"_aligned_serve": {"spans": [
        ("serve.readback", 0, 1, a) for a in notes[-20:]]}}
    per_tick = manifest.load_reader("attn_latent_rows_per_tick",
                                    tiny_root)(window)
    assert 2 * 3 <= per_tick <= 2 * 3 * 512


def test_the_drivers_comparison_sees_a_fault_the_maximum_would_pass():
    """One row of nine moved by a whole expert's part (what a flipped expert
    does) passes the median and would fail a maximum; every row moved a
    little (what a mechanism at fault does) fails the median."""
    from chipbench.drivers import serve_ticks, serve_ticks_rows

    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.standard_normal((9, 512)), jnp.float32)
    flipped = logits.at[4].add(jnp.asarray(
        rng.standard_normal(512) * 0.3, jnp.float32))
    shifted = logits + jnp.asarray(rng.standard_normal((9, 512)) * 0.05,
                                   jnp.float32)
    import jax
    probs = lambda x: jax.nn.softmax(x, -1)
    with serve_ticks_rows._by_rows():
        compare = serve_ticks.correct.compare_logp
        one_row = compare(probs(flipped), logits, 0.02)
        every_row = compare(probs(shifted), logits, 0.02)
    assert serve_ticks.correct.compare_logp.__module__ == "chipbench.correct"
    assert one_row["ok"] and one_row["max_abs_dlogp"] > 0.5
    assert one_row["row_rms_median"] < 1e-6 < one_row["row_rms_max"]
    assert not every_row["ok"] and every_row["max_abs_dlogp"] < 0.5
    assert 0.03 < every_row["row_rms_median"] < 0.07


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
late = [m for m in ("chipbench.work_mla", "chipbench.reference.mistral4",
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
