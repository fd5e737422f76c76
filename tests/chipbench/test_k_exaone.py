"""Configuration ``k-exaone-236b`` and its cell ``exaone_serve_reason``: the
published numbers pinned, the cut's byte table, the counts, the plain
reference against the system at a tiny size on the CPU (the full forward,
then chunked prefill and self-drafting ticks through the paged int8 and
float pools, both distributions, accepted and rejected ticks), the shares of
one expert layer adding up to the uncut layer, and the cell's own loop driver
end to end."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import correct, harness, manifest, run, work
from chipbench.reference import k_exaone as ref

import tiny

CELL, CONFIG = "exaone_serve_reason", "k-exaone-236b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
LLLG = ["sliding_attention"] * 3 + ["full_attention"]


@pytest.fixture(scope="module")
def loaded():
    return manifest.load_cell(CELL)


def test_published_numbers(loaded):
    cfg = loaded["config"]
    assert cfg["num_hidden_layers"] == 48
    assert cfg["layer_types"] == LLLG * 12
    assert cfg["sliding_windows"] == [128, 128, 128, 0] * 12
    assert cfg["sliding_window_pattern"] == "LLLG"
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 47
    assert cfg["first_k_dense_replace"] == 1
    assert (cfg["num_experts"], cfg["num_experts_per_tok"],
            cfg["num_shared_experts"]) == (128, 8, 1)
    assert cfg["routed_scaling_factor"] == 2.5
    assert cfg["scoring_func"] == "sigmoid" and cfg["norm_topk_prob"]
    assert (cfg["n_group"], cfg["topk_group"]) == (1, 1)
    assert cfg["sliding_window"] == 128
    assert cfg["vocab_size"] == 153600 and not cfg["tie_word_embeddings"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"]) == (6144, 64, 8, 128)
    assert (cfg["intermediate_size"], cfg["moe_intermediate_size"]) \
        == (18432, 2048)
    assert cfg["num_nextn_predict_layers"] == 1
    assert cfg["mtp_layer_types"] == ["full_attention"]
    assert cfg["rope_parameters"]["rope_theta"] == 1000000
    assert cfg["rms_norm_eps"] == 1e-5
    assert cfg["model_type"] == "exaone_moe"


def test_every_catalog_key_at_its_published_value(loaded):
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "K-EXAONE-236B-A23B")
    entry = manifest.find(manifest.load_manifest()["configs"], CONFIG,
                          "config")
    assert entry["source"] == loaded["config"]["source"] == row["source_url"]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts"]
    for key, value in row["config"].items():
        assert loaded["config"][key] == value, key
    assert loaded["config"]["serve_num_hidden_layers"] == 5
    assert loaded["config"]["held_num_experts"] == 16
    assert loaded["config"]["first_held_expert"] == 0
    assert set(loaded["config"]["assumed"]) >= {
        "pre_norm", "qk_norm", "rope_on_window_layers_only",
        "selection_bias", "mtp_block", "window_edge", "rotary_pairing",
        "init", "share", "serve_num_hidden_layers"}


def test_manifest_entries(loaded):
    man = manifest.load_manifest()
    assert manifest.validate(man) == []
    cell = loaded["cell"]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert "5 of 48" in cell["why"] and "1/8" in cell["why"]
    assert cell["traffic"] == "backlog_p128-1024_o2048-6144_s48_mtp1"
    mine = {m["name"] for m in loaded["per_layer"]}
    theirs = {m["name"] for m in manifest.load_cell(
        "mimo_serve_longshort")["per_layer"]}
    # a traced window is the 96 ticks after every slot began its answer and
    # no answer is shorter than 2048 tokens: no chunk runs in it, so the
    # chunk's reader finds nothing and the cell is off that metric's list
    assert theirs - mine == {"prefill_chunk_device_ms"}
    assert mine - theirs == {"mtp_accept_pct", "mtp_tokens_per_slot_tick",
                             "mtp_device_pct.serve",
                             "moe_shared_device_pct.serve"}
    assert {m["name"] for m in loaded["end_to_end"]} == {
        "serve_out_tokens_per_s", "serve_gap_p95_ms", "setup_s"}
    traffic = loaded["traffic"]
    assert (traffic["slots"], traffic["cache_len"], traffic["spec_k"],
            traffic["temperature"]) == (48, 7168, 1, 1.0)
    # no request of the backlog wraps its pages under a verify step's rows
    from chipbench import traffic as traffic_mod
    longest = max(len(p) + o for p, o in traffic_mod.backlog(
        dict(traffic, requests=traffic["block"]), 8, 0))
    assert longest + 2 <= traffic["cache_len"]


def _full_shapes(cfg):
    from chipbench.drivers import serve_ticks_mtp

    return serve_ticks_mtp.weight_shapes(harness.build_symbol(cfg), cfg)


def test_the_cuts_byte_table(loaded):
    """ISSUE 46's table, from the shapes the builder infers (nothing is
    allocated): parameters in millions and GB at 2 bytes, to three digits."""
    shapes = _full_shapes(loaded["config"])
    count = lambda pred: sum(int(np.prod(s)) for n, s in shapes.items()
                             if pred(n))
    att = count(lambda n: n.startswith("layer1_") and (
        "_q_" in n or "_k_" in n or "_v_" in n or "attout" in n))
    layer0 = count(lambda n: n.startswith("layer0_"))
    expert_layer = count(lambda n: n.startswith("layer1_"))
    ends = count(lambda n: n in ("embed_weight", "head_weight"))
    block = count(lambda n: n.startswith("mtp_"))
    total = count(lambda n: True)
    close = lambda got, millions: abs(got / 1e6 - millions) < 0.1
    assert close(att, 113.2), att
    assert close(layer0, 453.0), layer0
    assert close(expert_layer, 755.7), expert_layer
    assert close(ends, 1887.4), ends
    assert close(block, 831.2), block
    assert total == layer0 + 4 * expert_layer + ends + block \
        + int(np.prod(shapes["final_norm_gamma"]))
    assert abs(2 * total / 1e9 - 12.39) < 0.005, 2 * total / 1e9
    assert abs(total / 1e9 - 6.19) < 0.005


def test_counts(loaded):
    cfg, traffic = loaded["config"], loaded["traffic"]
    from chipbench import work_exaone as we

    live = 48 * 2000
    need = work.decode_step_bytes(cfg, traffic, live)
    # 96 rows touch 15.97 of the 16 held experts a layer: all but all the
    # weights, the head a second time, and the live keys and values
    assert 15.9 < we.experts_touched(cfg, 96) <= 16
    shapes = _full_shapes(cfg)
    weights = 2 * sum(int(np.prod(s)) for s in shapes.values())
    head = 2 * int(np.prod(shapes["head_weight"]))
    embed = 2 * int(np.prod(shapes["embed_weight"]))
    # every matrix once (the embedding by rows, the head twice; all but
    # 0.03 of the 16 held experts a layer), two full nodes at 2000
    # positions a slot and four rings of 128, keys and values with scales
    per_token = 2 * 8 * 128 + 2 * 8 * 4
    cached = 48 * (2 * 2000 + 4 * 128) * per_token
    assert abs(need - (weights - embed + head + cached)) < 0.003 * need
    assert 12.0e9 < need < 13.5e9
    assert work.decode_step_bytes(cfg, traffic, 2 * live) > need


# ---------------------------------------------------------------------------
# the reference against the system, at a tiny size
# ---------------------------------------------------------------------------
TINY = dict(vocab_size=96, hidden_size=64, num_attention_heads=4, head_dim=16,
            num_key_value_heads=2, intermediate_size=128,
            moe_intermediate_size=32, num_experts=16, num_experts_per_tok=4,
            held_num_experts=4, first_held_expert=4, sliding_window=8,
            max_position_embeddings=64, serve_num_hidden_layers=5,
            serve_dtype="float32")
TINY_TRAFFIC = dict(tiny.TINY_TRAFFIC["tiny_backlog"],
                    driver="serve_ticks_mtp", page_tokens=4, temperature=1.0,
                    spec_k=1, check_prompt=21, check_decode=8,
                    output_min=4, output_max=12)


def tiny_config(cfg, **over):
    """The configuration at the toy's widths; matrices wider than the cell's
    0.02 (0.08: towards 1 / sqrt(hidden 64)) so that every mechanism moves
    the output, the head flat enough (0.03) for most drafts to be taken."""
    init = [dict(r, std=0.08) if r["match"] == "_weight$"
            else dict(r, std=0.03) if "head" in r["match"] else r
            for r in cfg["init"]]
    return dict(cfg, init=init, **dict(TINY, **over))


@pytest.fixture(scope="module")
def toy(loaded):
    from chipbench.drivers import serve_ticks_mtp as driver

    cfg = tiny_config(loaded["config"])
    sym = harness.build_symbol(cfg)
    params = driver.make_params(driver.weight_shapes(sym, cfg), cfg, 7,
                                "float32")
    return cfg, sym, params


def test_the_full_forward_is_the_references(toy):
    """Both outputs of the graph through the executor, the block fed each
    position's next token, against ``forward`` and ``forward_mtp``."""
    import mxnet_tpu as mx

    cfg, sym, params = toy
    toks = np.random.default_rng(0).integers(0, cfg["vocab_size"],
                                             size=(2, 40))
    nxt = np.concatenate([toks[:, 1:], np.zeros((2, 1), toks.dtype)], 1)
    shapes = {n: toks.shape for n in ("data", "softmax_label", "mtp_data",
                                      "mtp_label")}
    ex = sym.simple_bind(mx.cpu(), grad_req="null", **shapes)
    for n, v in params.items():
        ex.arg_dict[n]._set_data(v)
    ex.arg_dict["data"]._set_data(jnp.asarray(toks, jnp.float32))
    ex.arg_dict["mtp_data"]._set_data(jnp.asarray(nxt, jnp.float32))
    ex.forward(is_train=False)
    main, block = (o.data.reshape(2, 40, -1) for o in ex.outputs)
    got = correct.compare_logp(main.reshape(80, -1), ref.forward(
        params, cfg, toks).reshape(80, -1), 1e-4)
    assert got["ok"], got
    got = correct.compare_logp(
        block[:, :-1].reshape(78, -1),
        ref.forward_mtp(params, cfg, toks).reshape(78, -1), 1e-4)
    assert got["ok"], got
    # each mechanism the configuration adds moves the reference's output
    base = ref.forward_mtp(params, cfg, toks)
    for change in (dict(routed_scaling_factor=1.0),
                   dict(num_shared_experts=0)):
        moved = float(jnp.max(jnp.abs(
            ref.forward_mtp(params, dict(cfg, **change), toks) - base)))
        assert moved > 1e-3, (change, moved)


@pytest.mark.parametrize("kv_dtype,atol", [("", 1e-5), ("int8", 1e-2)])
def test_chunks_and_self_drafting_ticks_are_the_references(toy, kv_dtype,
                                                           atol):
    """The driver's own comparison at the toy's size: 21 prompt tokens in
    chunks of 8 through rings of 16 (the rings wrap inside the prompt), then
    8 ticks, the fourth with a draft that is rejected for sure; the stack's
    distribution at every committed position and the block's at every
    drafted one, on accepted and on rejected ticks; the limit is on the
    median row's RMS difference in log-probability
    (``serve_ticks_mtp.compare_rows``)."""
    import mxnet_tpu as mx
    from chipbench.drivers import serve_ticks_mtp as driver

    cfg, sym, params = toy
    traffic = dict(TINY_TRAFFIC, slots=2, kv_dtype=kv_dtype)
    name = "draft_logp_atol." + kv_dtype
    cfg = dict(cfg, limits={driver.NAME: {name: {"value": atol,
                                                 "why": "toy"}}})
    pred, _ = driver.build_server(
        sym, traffic, {n: mx.nd.NDArray(v, mx.cpu())
                       for n, v in params.items()}, mx.cpu())
    assert [(g.kind, g.capacity, g.nodes) for g in pred._groups] == [
        ("full", 64, (3, 5)), ("window", 16, (0, 1, 2, 4))]

    def force(tick, state):
        # a draft the stack gives about one chance in 96, stated as certain
        if tick != 3:
            return state
        d = (state.draft + 1) % cfg["vocab_size"]
        return state._replace(draft=d, draft_probs=jax.nn.one_hot(
            d[:, 0], cfg["vocab_size"], dtype=jnp.float32))

    stack, block = driver.check_against_reference(
        pred, cfg, traffic, params, 11, atol, drafts=force)
    assert stack["ok"] and block["ok"], (stack, block)
    assert stack["ticks_accepted"] >= 1 and stack["ticks_rejected"] >= 1
    assert stack["positions"] == 1 + 8 + stack["ticks_accepted"]
    assert block["positions"] == 1 + 8
    # a fault in one mechanism is seen: the reference without the factor
    wrong = dict(cfg, routed_scaling_factor=1.0)
    stack, block = driver.check_against_reference(
        pred, wrong, traffic, params, 11, atol, drafts=force)
    assert not stack["ok"] and not block["ok"]


def test_the_shares_of_one_expert_layer_add_up(toy):
    """Four chips with four of the 16 experts each: their shares of one
    layer, the shared expert counted in one of them, are the uncut
    reference's layer."""
    import mxnet_tpu as mx

    cfg, _, params = toy
    n = "layer1_"
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((2, 5, cfg["hidden_size"])),
                    jnp.float32)
    stack = lambda part: jnp.asarray(rng.standard_normal(
        (16,) + tuple(params[n + "moe_expert_%s_weight" % part].shape[1:])
    ) * 0.08, jnp.float32)
    whole = dict(params, **{n + "moe_expert_%s_weight" % part: stack(part)
                            for part in ("gate", "up", "down")})
    want = ref._experts(whole, n, dict(cfg, held_num_experts=16,
                                       first_held_expert=0), x)
    total = 0.0
    for chip, first in enumerate(range(0, 16, 4)):
        shared = chip == 0
        sym = mx.sym.MoEFFN(
            mx.sym.Variable("data"), num_experts=16,
            hidden_size=cfg["moe_intermediate_size"], gated=True,
            num_experts_per_tok=4, score_func="sigmoid", score_bias=True,
            norm_topk=True, num_held=4, first_held=first,
            routed_scaling_factor=2.5, name="moe",
            **({"n_shared_experts": 1} if shared else {}))
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
    # and not without the shared expert, or with it in every share
    alone = ref._experts(whole, n, dict(cfg, held_num_experts=16,
                                        first_held_expert=0,
                                        num_shared_experts=0), x)
    assert float(jnp.max(jnp.abs(alone - want))) > 1e-2


# ---------------------------------------------------------------------------
# the cell's driver end to end
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory, loaded):
    root = tiny.make_root(tmp_path_factory.mktemp("bench_exaone"))
    cfg = tiny_config(loaded["config"])
    for limits in cfg["limits"].values():
        for lim in limits.values():
            lim["value"] = 0.02     # int8 keys at heads of 16
    with open(os.path.join(root, "chipbench/configs/tiny-exaone.json"),
              "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root, manifest.traffic_path("tiny_backlog_mtp")),
              "w") as f:
        json.dump(TINY_TRAFFIC, f)
    man = manifest.load_manifest(root)
    man["configs"].append({
        "name": "tiny-exaone", "source": "test", "reduced": [],
        "file": "chipbench/configs/tiny-exaone.json", "why": "CPU test size"})
    man["workloads"].append({
        "name": "tiny_exaone_serve", "config": "tiny-exaone",
        "traffic": "tiny_backlog_mtp", "chips": 1, "why": "CPU test size"})
    for m in man["end_to_end"] + man["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny_exaone_serve")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    return root


def test_the_cells_driver_at_a_tiny_size(tiny_root):
    """``serve_ticks_mtp`` end to end on the CPU: a backlog through a
    self-drafting ``DecodeServer``, every finished request at exactly its
    length, then the comparison with the reference, and the control."""
    import mxnet_tpu as mx
    from chipbench import control, spans

    assert manifest.validate(manifest.load_manifest(tiny_root),
                             tiny_root) == []
    cell = manifest.load_cell("tiny_exaone_serve", root=tiny_root)
    counters = harness.CompileCounters().install()
    res = run.run_cell(cell, 2 ** 31 + 11, 1.0, False, [mx.cpu()], counters,
                       harness.Phases(), harness.MemoryPeak(1))
    assert all(c["ok"] for c in res["checks"]), res["checks"]
    assert [c.get("what") for c in res["checks"][:2]] == ["stack", "block"]
    for check in res["checks"][:2]:
        assert check["statistic"] == "row_rms_median"
        assert check["row_rms_median"] < 0.02 < 0.15 > check["max_abs_dlogp"]
    assert res["failed"] == 0 and res["side"]["queue_left"] > 0
    assert res["side"]["requests_completed"] > 5
    assert counters.in_window == 0
    counted = res["side"]["spec_process"]
    assert 0 < counted["mx_spec_accepted"] < counted["mx_spec_proposed"]
    # what the new metrics read: a tick's drafts in the arguments of its
    # serve.readback span
    notes = [a for name, _, _, a in spans.spans_of(spans.program_events())
             if name == "serve.readback" and "spec_proposed" in a]
    assert notes and all("moe_expert_visits" in a for a in notes)
    window = {"_aligned_serve": {"spans": [
        ("serve.readback", 0, 1, a) for a in notes[-20:]]}}
    accept = manifest.load_reader("mtp_accept_pct", tiny_root)(window)
    per_slot = manifest.load_reader("mtp_tokens_per_slot_tick",
                                    tiny_root)(window)
    assert 0 < accept < 100
    assert per_slot == pytest.approx(1 + accept / 100)
    # the control reads the reference against itself with coarser matrices
    assert control.reading(cell, 5, below="bfloat16") > 0


def test_readers_return_nothing_where_the_program_has_nothing(loaded):
    """On a program without the scopes and counters this PR adds (the
    parent's), the new readers leave their metric out and do not raise."""
    facts = {"trace": None, "config": loaded["config"],
             "traffic": loaded["traffic"], "peaks": {"hbm_bytes_per_s": 1},
             "_aligned_serve": {"spans": [
                 ("serve.readback", 0, 1, {"moe_rows_held": 3})]}}
    for name in ("mtp_accept_pct", "mtp_tokens_per_slot_tick",
                 "mtp_device_pct.serve", "moe_shared_device_pct.serve"):
        assert manifest.load_reader(name)(dict(facts)) is None, name
    table = {"layers": {"moe": 40.0}, "scopes": {"moe/experts": 30.0}}
    assert manifest.load_reader("mtp_device_pct.serve")(
        {"_layer_table": table}) is None
    assert manifest.load_reader("moe_shared_device_pct.serve")(
        {"_layer_table": dict(table, scopes={"moe/shared": 2.5})}) == 2.5


def test_a_program_without_the_block_fails_at_once(loaded):
    """The parent's builder takes the new arguments for unknown ones and
    builds a graph without the block: the driver says so before a weight is
    drawn."""
    from chipbench.drivers import serve_ticks_mtp as driver

    cfg = tiny_config(loaded["config"], num_nextn_predict_layers=0)
    with pytest.raises(RuntimeError, match="no multi-token-prediction"):
        driver.weight_shapes(harness.build_symbol(cfg), cfg)


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
late = [m for m in ("chipbench.work_exaone", "chipbench.reference.k_exaone",
                    "chipbench.drivers.serve_ticks_mtp",
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
