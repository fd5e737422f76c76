"""``BENCHMARK.json`` and every data file it names are sound, and a cell, a
configuration, a traffic mix and a per-layer metric can each be added as new
files plus one new entry."""
import copy
import importlib
import json
import os

import pytest

from chipbench import manifest

import tiny

MAN = manifest.load_manifest()
CELLS = [w["name"] for w in MAN["workloads"]]
METRICS = MAN["end_to_end"] + MAN["per_layer"]
DRIVERS = ("train_fit", "serve_ticks")


def test_manifest_validates():
    assert manifest.validate(MAN) == []


def test_manifest_is_small_and_names_the_issues_cells():
    assert os.path.getsize(os.path.join(manifest.ROOT,
                                        "BENCHMARK.json")) < 64 * 1024
    assert CELLS == ["rn50_train_bs256", "opt_train_t2048",
                     "opt_serve_backlog", "rn50_train_dp4"]
    assert [c["name"] for c in MAN["configs"]] == ["resnet50", "opt-1.3b"]
    assert sum(w["chips"] == 4 for w in MAN["workloads"]) <= 1
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
    mod, fn = cfg["builder"].split(":")
    assert hasattr(importlib.import_module(mod), fn)
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


@pytest.mark.parametrize("config", MAN["configs"], ids=lambda c: c["name"])
def test_config_file_keeps_published_widths(config):
    cfg = manifest.load_json(manifest.ROOT, config["file"])
    for key in config["reduced"]:
        assert not key.endswith(("_dim", "_rank", "_size")), key
    if config["name"] == "opt-1.3b":
        # facebook/opt-1.3b config.json
        assert (cfg["hidden_size"], cfg["ffn_dim"], cfg["vocab_size"],
                cfg["num_attention_heads"], cfg["num_hidden_layers"],
                cfg["max_position_embeddings"]) == \
            (2048, 8192, 50272, 32, 24, 2048)
        assert cfg["train_num_hidden_layers"] <= cfg["num_hidden_layers"]
    else:
        assert (cfg["num_layers"], cfg["num_classes"], cfg["image_shape"],
                cfg["units"]) == (50, 1000, [3, 224, 224], [3, 4, 6, 3])


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
    return man


@pytest.mark.parametrize("fault", [
    "unit with a space", "moves a metric the cell does not report",
    "two four-chip cells", "bound too wide", "extra key on a metric",
    "duplicate cell", "unknown traffic", "no setup_s",
    "metric without a reader"])
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
