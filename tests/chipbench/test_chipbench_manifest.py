"""``BENCHMARK.json`` and every data file it names are sound, and a cell, a
configuration, a traffic mix and a per-layer metric can each be added as new
files plus one new entry."""
import copy
import importlib
import json
import os
import shutil

import pytest

from chipbench import correct, manifest, work

import tiny

MAN = manifest.load_manifest()
CELLS = [w["name"] for w in MAN["workloads"]]
METRICS = MAN["end_to_end"] + MAN["per_layer"]
DRIVERS = ("train_fit", "serve_ticks")


def test_manifest_validates():
    assert manifest.validate(MAN) == []


# what PR 23's benchmark was accepted with: later PRs add to it, none may
# take a cell or a configuration away
ACCEPTED_CELLS = ["rn50_train_bs256", "opt_train_t2048",
                  "opt_serve_backlog", "rn50_train_dp4"]
ACCEPTED_CONFIGS = ["resnet50", "opt-1.3b"]


def test_manifest_is_small_and_names_the_issues_cells():
    assert os.path.getsize(os.path.join(manifest.ROOT,
                                        "BENCHMARK.json")) < 64 * 1024
    # the accepted cells and configurations are there, first and in their
    # order; whatever a later PR brought follows them
    assert CELLS[:4] == ACCEPTED_CELLS
    assert [c["name"] for c in MAN["configs"]][:2] == ACCEPTED_CONFIGS
    assert len(CELLS) <= 24 and len(MAN["configs"]) <= 24
    assert sum(w["chips"] == 4 for w in MAN["workloads"]) <= \
        max(1, len(CELLS) // 4)
    assert 1 <= MAN["run_seconds"] <= 51
    assert MAN["command"] == ["python3", "-m", "chipbench.run"]
    for p in MAN["paths"]:
        assert os.path.isdir(os.path.join(manifest.ROOT, p))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads_with_its_files(cell):
    loaded = manifest.load_cell(cell)
    assert loaded["traffic"]["driver"] in DRIVERS
    importlib.import_module("chipbench.drivers."
                            + loaded["traffic"]["driver"])
    cfg = loaded["config"]
    assert callable(manifest.load_named(cfg["builder"]))
    assert all(callable(manifest.load_named(v))
               for v in cfg["counts"].values())
    ref = importlib.import_module(cfg["reference"])
    assert callable(ref.forward) and callable(ref.loss)
    names = [m["name"] for m in loaded["end_to_end"]]
    assert "setup_s" in names and len(names) >= 2
    assert loaded["per_layer"]


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert manifest.NAME.match(metric["name"])
    assert manifest.UNIT.match(metric["unit"]) and len(metric["unit"]) <= 16
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in manifest.SOURCES
    if "layer" in metric:
        assert "\n" not in metric["layer"] and 1 <= len(metric["layer"]) <= 200
        assert callable(manifest.load_reader(metric["name"]))
        target = manifest.find(MAN["end_to_end"], metric["moves"], "metric")
        for cell in metric.get("workloads", CELLS):
            assert cell in target.get("workloads", CELLS), \
                "%s moves %s, which %s does not report" % (
                    metric["name"], metric["moves"], cell)
    else:
        assert 0 < metric["bound"] <= 0.1


# A published number pinned for a configuration chosen by name.  One with no
# case here is held to the generic rules alone; the PR that brings it brings
# its pins in a test file of its own.
PUBLISHED = {
    # facebook/opt-1.3b config.json
    "opt-1.3b": {"hidden_size": 2048, "ffn_dim": 8192, "vocab_size": 50272,
                 "num_attention_heads": 32, "num_hidden_layers": 24,
                 "max_position_embeddings": 2048},
    # symbols/resnet.py at --num-layers 50
    "resnet50": {"num_layers": 50, "num_classes": 1000,
                 "image_shape": [3, 224, 224], "units": [3, 4, 6, 3]},
}


@pytest.mark.parametrize("config", MAN["configs"], ids=lambda c: c["name"])
def test_config_file_keeps_published_widths(config):
    cfg = manifest.load_json(manifest.ROOT, config["file"])
    drivers = {manifest.load_json(manifest.ROOT, manifest.traffic_path(
        w["traffic"]))["driver"] for w in MAN["workloads"]
        if w["config"] == config["name"]}
    # any configuration: no width is reduced, each reduced key stands at its
    # published value beside the cut, counts and limits are stated
    assert manifest.config_problems(config, cfg, drivers) == []
    for key in config["reduced"]:
        assert manifest.REDUCIBLE.match(key), key
        cuts = [k for k in cfg if k.endswith("_" + key)]
        assert cuts and all(cfg[k] <= cfg[key] for k in cuts)
    for key, value in PUBLISHED.get(config["name"], {}).items():
        assert cfg[key] == value, key


@pytest.mark.parametrize("key", [
    "hidden_size", "ffn_dim", "intermediate_size", "moe_intermediate_size",
    "head_dim", "kv_lora_rank", "q_lora_rank", "qk_rope_head_dim",
    "num_experts_per_tok", "expansion_factor", "word_embed_proj_dim",
    "ssm_state_size", "latent_width", "num_attention_heads",
    "num_key_value_heads", "d_model", "n_embd", "d_ff", "hidden",
    "vocab_size", "num_experts_per_token", "moe_layer_freq"])
def test_reduced_may_never_name_a_width(key):
    """``reduced`` is held to a short list of what it may name, so a width
    is refused whatever it is called."""
    assert not manifest.REDUCIBLE.match(key)
    for ok in ("num_hidden_layers", "num_layers", "n_layer", "num_experts",
               "n_routed_experts", "num_local_experts",
               "mtp_num_hidden_layers"):
        assert manifest.REDUCIBLE.match(ok)
    cfg = manifest.load_json(manifest.ROOT, MAN["configs"][1]["file"])
    entry = dict(MAN["configs"][1], reduced=[key])
    assert any("reduces " + key in p
               for p in manifest.config_problems(entry, cfg, ()))


def _break(name):
    man = copy.deepcopy(MAN)
    if name == "unit with a space":
        man["end_to_end"][0]["unit"] = "samples per s"
    elif name == "moves a metric the cell does not report":
        manifest.find(man["per_layer"], "step_device_ms",
                      "m")["moves"] = "serve_out_tokens_per_s"
    elif name == "two four-chip cells":
        man["workloads"][0]["chips"] = 4
    elif name == "bound too wide":
        man["end_to_end"][0]["bound"] = 0.5
    elif name == "extra key on a metric":
        man["per_layer"][0]["why"] = "no"
    elif name == "duplicate cell":
        man["workloads"].append(dict(man["workloads"][0]))
    elif name == "unknown traffic":
        man["workloads"][0]["traffic"] = "nope"
    elif name == "no setup_s":
        man["end_to_end"] = [m for m in man["end_to_end"]
                             if m["name"] != "setup_s"]
    elif name == "metric without a reader":
        man["per_layer"].append(dict(man["per_layer"][0], name="ghost_ms"))
    elif name == "twenty-five cells":
        for i in range(25 - len(man["workloads"])):
            man["workloads"].append(dict(man["workloads"][0],
                                         name="more_%d" % i))
    elif name == "a width among the reduced keys":
        manifest.find(man["configs"], "opt-1.3b",
                      "c")["reduced"].append("ffn_dim")
    elif name == "a reduced key with no cut beside it":
        manifest.find(man["configs"], "resnet50",
                      "c")["reduced"].append("num_layers")
    return man


@pytest.mark.parametrize("fault", [
    "unit with a space", "moves a metric the cell does not report",
    "two four-chip cells", "bound too wide", "extra key on a metric",
    "duplicate cell", "unknown traffic", "no setup_s",
    "metric without a reader", "twenty-five cells",
    "a width among the reduced keys",
    "a reduced key with no cut beside it"])
def test_validate_catches(fault):
    assert manifest.validate(_break(fault)) != []


def test_a_cell_config_traffic_and_metric_are_added_as_new_files(tmp_path):
    root = tiny.make_root(tmp_path)
    # a per-layer metric: one new reader file, one new entry
    with open(os.path.join(root, manifest.reader_path("ticks_per_s.new")),
              "w") as f:
        f.write("def read(facts):\n"
                "    return facts['ticks'] / facts['window_s']\n")
    man = manifest.load_manifest(root)
    man["per_layer"].append({
        "name": "ticks_per_s.new", "unit": "1/s", "better": "higher",
        "source": "host_clock", "layer": "serving loop",
        "moves": "serve_out_tokens_per_s", "workloads": ["tiny_serve"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    assert manifest.validate(manifest.load_manifest(root), root) == []
    loaded = manifest.load_cell("tiny_serve", root=root)
    assert loaded["config"]["hidden_size"] == 32
    assert loaded["traffic"]["driver"] == "serve_ticks"
    new = [m for m in loaded["per_layer"] if m["name"] == "ticks_per_s.new"]
    got = manifest.read_layer_metrics(new, {"ticks": 30, "window_s": 2.0},
                                      root)
    assert got == {"ticks_per_s.new": {"value": 15.0, "unit": "1/s"}}
    # and no file that was there changed
    for rel in ("chipbench/configs/opt-1.3b.json",
                manifest.traffic_path("closed_b256")):
        with open(os.path.join(root, rel)) as a, \
                open(os.path.join(manifest.ROOT, rel)) as b:
            assert a.read() == b.read()


def _tree(root):
    """``{relative path: bytes}`` of the benchmark's own files under
    ``root``, less what a run or an import leaves behind."""
    out = {}
    top = os.path.join(root, manifest.HERE)
    for d, dirs, files in os.walk(top):
        dirs[:] = [x for x in dirs
                   if x not in ("out", "__pycache__", "testdata")]
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                out[os.path.relpath(os.path.join(d, f), root)] = fh.read()
    return out


NEW_FAMILY = """
from chipbench.reference.opt import forward, loss   # the plain reference


def train_flops(cfg, traffic):
    # sparse experts: only the experts a token is routed to are work
    return 3 * traffic["seq_len"] * cfg["active_flops_per_token"]


def decode_bytes(cfg, traffic, live_tokens):
    return cfg["expert_bytes_read"] + 7 * live_tokens
"""


def test_a_new_family_brings_its_counts_limit_and_reference_as_new_files(
        tmp_path, monkeypatch):
    """What a ``model_config`` PR does: a configuration of a family the
    benchmark has never seen, its counts, its limit and its reference in
    files of its own, a cell on it; no file that was there is rewritten,
    and the utilisation readers read the new family's counts."""
    root = tiny.make_root(tmp_path)
    before = _tree(root)
    with open(os.path.join(root, "sparse_family.py"), "w") as f:
        f.write(NEW_FAMILY)
    monkeypatch.syspath_prepend(root)
    cfg = dict(tiny.TINY_LM, family="sparse_lm", reference="sparse_family",
               active_flops_per_token=1000, expert_bytes_read=4096,
               init=manifest.load_json(
                   manifest.ROOT, "chipbench/configs/opt-1.3b.json")["init"],
               counts={"train_flops_per_sample": "sparse_family:train_flops",
                       "decode_step_bytes": "sparse_family:decode_bytes"},
               limits={"train_fit": {"logp_atol": {
                   "value": 0.02, "why": "float32 on both sides"}}})
    rel = "chipbench/configs/tiny-sparse.json"
    with open(os.path.join(root, rel), "w") as f:
        json.dump(cfg, f)
    man = manifest.load_manifest(root)
    man["configs"].append({"name": "tiny-sparse", "source": "test",
                           "file": rel, "reduced": [], "why": "new family"})
    man["workloads"].append({"name": "tiny_sparse", "config": "tiny-sparse",
                             "traffic": "tiny_closed_lm", "chips": 1,
                             "why": "new family"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "tiny_lm" in m.get("workloads", ()):
            m["workloads"].append("tiny_sparse")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    assert manifest.validate(manifest.load_manifest(root), root) == []
    loaded = manifest.load_cell("tiny_sparse", root=root)
    assert loaded["config"]["family"] == "sparse_lm"
    assert correct.limit(loaded["config"], "train_fit", "logp_atol") == 0.02
    assert callable(correct.reference_of(loaded["config"]).forward)
    assert work.train_flops_per_sample(loaded["config"],
                                       loaded["traffic"]) == 3 * 64 * 1000
    assert work.decode_step_bytes(loaded["config"], {}, 10) == 4096 + 70
    facts = {"rate": 5.0, "batch": 2, "chips": 1, "config": loaded["config"],
             "traffic": loaded["traffic"],
             "peaks": {"bf16_flops_per_s": 1e6}}
    util = [m for m in loaded["per_layer"]
            if m["name"] == "model_flops_util_pct.train"]
    got = manifest.read_layer_metrics(util, facts, root)
    assert got["model_flops_util_pct.train"]["value"] == pytest.approx(
        100.0 * 3 * 64 * 1000 * 5.0 / 1e6)
    # every file that was there is there still, byte for byte
    after = _tree(root)
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [rel]


def _faulty(fault):
    cfg = manifest.load_json(manifest.ROOT, "chipbench/configs/opt-1.3b.json")
    if fault == "no limits at all":
        del cfg["limits"]
    elif fault == "no limit for the serving driver":
        del cfg["limits"]["serve_ticks"]
    elif fault == "a limit without its reason":
        del cfg["limits"]["train_fit"]["logp_atol"]["why"]
    elif fault == "a limit that is no number":
        cfg["limits"]["train_fit"]["logp_atol"]["value"] = "0.1"
    elif fault == "no counts":
        del cfg["counts"]
    elif fault == "a count that names no function":
        cfg["counts"]["train_flops_per_sample"] = "chipbench.work"
    elif fault == "the cut above the published value":
        cfg["train_num_hidden_layers"] = 48
    return cfg


@pytest.mark.parametrize("fault", [
    "no limits at all", "no limit for the serving driver",
    "a limit without its reason", "a limit that is no number", "no counts",
    "a count that names no function", "the cut above the published value"])
def test_validate_catches_a_configuration_files_fault(fault, tmp_path):
    """A configuration without a tolerance is an error, not a default."""
    rel = "chipbench/configs/opt-1.3b.json"
    os.makedirs(os.path.join(tmp_path, "chipbench", "configs"))
    for sub in ("traffic", "drivers", "layer_metrics"):
        os.symlink(os.path.join(manifest.ROOT, "chipbench", sub),
                   os.path.join(tmp_path, "chipbench", sub))
    shutil.copy(os.path.join(manifest.ROOT,
                             "chipbench/configs/resnet50.json"),
                os.path.join(tmp_path, "chipbench/configs"))
    with open(os.path.join(tmp_path, rel), "w") as f:
        json.dump(_faulty("none"), f)
    assert manifest.validate(MAN, str(tmp_path)) == []
    with open(os.path.join(tmp_path, rel), "w") as f:
        json.dump(_faulty(fault), f)
    bad = manifest.validate(MAN, str(tmp_path))
    assert bad and all("opt-1.3b" in b for b in bad), bad
    if "limit" in fault:
        with pytest.raises(KeyError, match="states no limit"):
            correct.limit({"limits": {}}, "train_fit", "logp_atol")


def test_a_reader_with_nothing_to_read_leaves_its_metric_out():
    m = [manifest.find(MAN["per_layer"], "slot_occupancy_pct", "m"),
         manifest.find(MAN["per_layer"], "host_syncs_per_step", "m")]
    got = manifest.read_layer_metrics(
        m, {"step_stats": {"host_syncs_per_step": 0.0}})
    assert list(got) == ["host_syncs_per_step"]


def test_peaks_are_keyed_by_the_exact_device_kind():
    peaks = manifest.load_json(manifest.ROOT, "chipbench/peaks.json")
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["ici_bits_per_s"] == 1600e9
    assert "TPU v5e" not in peaks and "cpu" not in peaks
