"""The command's refusals, and both loop drivers at a tiny size on the CPU
through the function ``main`` itself calls."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import harness, manifest, run, work

import tiny

ARGS = ["--workload", "rn50_train_bs256", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def _run(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "-m", "chipbench.run"] + ARGS,
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_without_a_tpu_exits_nonzero_with_empty_stdout():
    out = _run(manifest.ROOT, {"JAX_PLATFORMS": "cpu", "BENCH_RUN": "7"})
    assert out.returncode != 0
    assert out.stdout == ""
    assert "needs 1 TPU chip" in out.stderr


def test_without_the_program_exits_nonzero_with_empty_stdout(tmp_path):
    # a directory that holds only BENCHMARK.json and the files under paths
    man = manifest.load_manifest()
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), tmp_path)
    for p in man["paths"]:
        shutil.copytree(os.path.join(manifest.ROOT, p),
                        os.path.join(tmp_path, p),
                        ignore=shutil.ignore_patterns("out", "__pycache__"))
    out = _run(str(tmp_path), {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout == ""


def test_unknown_workload_names_the_known_ones():
    with pytest.raises(KeyError, match="rn50_train_bs256"):
        manifest.load_cell("no_such_cell")


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture(scope="module")
def results(tiny_root):
    """Each tiny cell once, through ``run.run_cell`` on ``mx.cpu()``."""
    import mxnet_tpu as mx
    from mxnet_tpu import obs

    counters = harness.CompileCounters().install()
    out = {}
    for cell, _, _ in tiny.TINY_CELLS:
        # the program's counters are the process's: whatever ran before (an
        # earlier test, here two dispatches made up) is not this run's
        obs.registry.counter("mx_attn_dispatch_total", labels=("path",)
                             ).labels(path="einsum").inc(2)
        loaded = manifest.load_cell(cell, root=tiny_root)
        out[cell] = (loaded, run.run_cell(
            loaded, 2 ** 31 + 5, 1.0, False, [mx.cpu()], counters,
            harness.Phases(), harness.MemoryPeak(1)), counters.in_window)
    return out


@pytest.mark.parametrize("cell", ["tiny_rn", "tiny_lm"])
def test_train_fit_driver(results, cell):
    loaded, res, in_window = results[cell]
    side, facts = res["side"], res["facts"]
    batch = loaded["traffic"]["batch"]
    assert all(c["ok"] for c in res["checks"]), res["checks"]
    assert res["failed"] == 0 and res["attempted"] == side["steps"] > 0
    # whole steps over the measured interval, not over --seconds
    assert res["end_to_end"]["train_samples_per_s"] == pytest.approx(
        batch * side["steps"] / side["window_s"])
    assert side["window_s"] >= 1.0
    assert facts["step_stats"]["steps"] == side["steps"]
    assert facts["step_stats"]["host_syncs_per_step"] == 0
    assert in_window == 0
    assert side["gc_collections_in_window"][2] == 0
    assert res["setup_s"] > 0 and res["trace"] is None
    assert 0 < side["longest_step_s"] < side["window_s"]
    # which attention path the step's nodes were traced on, from the
    # program's own counter, counted from the run's start: the LM has one
    # such node, ResNet none, whatever the process traced before
    paths = {k: v for k, v in side["program_counters_before_window"].items()
             if k.startswith("mx_attn_dispatch_total{")}
    assert paths == ({"mx_attn_dispatch_total{path=einsum}": 1.0}
                     if cell == "tiny_lm" else {})


def test_serve_ticks_driver(results):
    loaded, res, _ = results["tiny_serve"]
    side, facts = res["side"], res["facts"]
    assert all(c["ok"] for c in res["checks"]), res["checks"]
    assert res["failed"] == 0
    assert res["end_to_end"]["serve_out_tokens_per_s"] == pytest.approx(
        side["tokens"] / side["window_s"])
    assert side["requests_completed"] > 10 and side["queue_left"] > 0
    assert side["gap_samples"] > side["ticks"] > 0
    assert 0 < side["gap_p50_ms"] <= side["gap_p95_ms"]
    assert res["end_to_end"]["serve_gap_p95_ms"] == side["gap_p95_ms"]
    assert 0 < facts["mean_active"] <= loaded["traffic"]["slots"]
    assert side["gc_collections_in_window"][2] == 0
    # an untraced run takes no notice of the traffic file's `trace_ticks`
    assert side["ticks"] > loaded["traffic"]["trace_ticks"]
    assert side["window_s"] >= 1.0


class StubTracer:
    """A tracer that is on and records nothing: the CPU has no device
    plane to read, and the drivers ask only for these."""

    def __init__(self, on, name):
        self.on, self.parsed, self.trace_bytes = True, None, None
        self.stopped = 0

    def start(self):
        pass

    def span(self, what):
        import contextlib
        return contextlib.nullcontext()

    def stop(self, phases):
        self.stopped += 1


def test_a_traced_serving_window_closes_after_trace_ticks(tiny_root,
                                                          monkeypatch):
    """The traced window is a fixed number of ticks, so what a traced run
    costs does not follow the tick's length; `trace_seconds` still caps
    it."""
    import mxnet_tpu as mx

    monkeypatch.setattr(harness, "Tracer", StubTracer)
    loaded = manifest.load_cell("tiny_serve", root=tiny_root)
    assert loaded["traffic"]["trace_ticks"] == 5
    phases = harness.Phases()
    res = run.run_cell(loaded, 7, 30.0, True, [mx.cpu()],
                       harness.CompileCounters(), phases,
                       harness.MemoryPeak(1))
    assert res["side"]["ticks"] == 5
    assert res["side"]["window_s"] < loaded["traffic"]["trace_seconds"]
    assert all(c["ok"] for c in res["checks"]), res["checks"]
    # where the run's seconds went, in the order they were spent
    names = list(phases.seconds)
    assert names[names.index("trace_start"):] == [
        "trace_start", "window", "after_window", "check"]


def test_a_traffic_files_mesh_reaches_module_as_mesh_config(tiny_root,
                                                            monkeypatch):
    """`mesh` in a traffic file is the axis sizes of the cell's
    `parallel.MeshConfig`; a file without the key passes nothing."""
    import mxnet_tpu as mx

    seen = []
    real = mx.mod.Module

    def recording(*args, **kwargs):
        seen.append(kwargs.get("mesh_config", "absent"))
        return real(*args, **kwargs)

    monkeypatch.setattr(mx.mod, "Module", recording)
    loaded = manifest.load_cell("tiny_lm", root=tiny_root)
    meshed = dict(loaded, traffic=dict(loaded["traffic"],
                                       mesh={"data": 2}))
    for cell, contexts in ((meshed, [mx.cpu(0), mx.cpu(1)]),
                           (loaded, [mx.cpu()])):
        res = run.run_cell(cell, 11, 0.2, False, contexts,
                           harness.CompileCounters(), harness.Phases(),
                           harness.MemoryPeak(1))
        assert all(c["ok"] for c in res["checks"]), res["checks"]
    assert seen[0] == mx.parallel.MeshConfig(data=2)
    assert seen[1] == "absent"


def test_side_file_is_written(results, tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    _, res, _ = results["tiny_lm"]
    path = harness.write_side_file("tiny_lm", 2 ** 31 + 5, res["side"])
    assert os.path.basename(path) == "tiny_lm-%d-%d.json" % (2 ** 31 + 5,
                                                             os.getpid())
    with open(path) as f:
        side = json.load(f)
    assert side["segment_rates"] == res["side"]["segment_rates"]


def test_untraced_metrics_are_the_cells_end_to_end(results, tiny_root):
    for cell, (loaded, res, _) in results.items():
        want = {m["name"] for m in loaded["end_to_end"]} - {"setup_s"}
        assert want == set(res["end_to_end"]), cell


def test_per_layer_readers_on_the_tiny_facts(results, tiny_root):
    loaded, res, _ = results["tiny_lm"]
    facts = dict(res["facts"], config=loaded["config"],
                 traffic=loaded["traffic"], chips=1, compile_s=1.5,
                 compiles_in_window=0, memory_peak_bytes=2e9,
                 peaks={"bf16_flops_per_s": 197e12})
    names = ["host_syncs_per_step", "input_stall_pct",
             "model_flops_util_pct.train", "compile_s",
             "compiles_in_window.train", "peak_hbm_gb.train"]
    metrics = [m for m in loaded["per_layer"] if m["name"] in names]
    got = manifest.read_layer_metrics(metrics, facts, tiny_root)
    assert set(got) == set(names)
    assert got["peak_hbm_gb.train"]["value"] == 2.0
    assert got["model_flops_util_pct.train"]["value"] == pytest.approx(
        100 * work.train_flops_per_sample(loaded["config"],
                                          loaded["traffic"])
        * res["facts"]["rate"] / 197e12)


def test_work_counts():
    rn = manifest.load_json(manifest.ROOT, "chipbench/configs/resnet50.json")
    opt = manifest.load_json(manifest.ROOT, "chipbench/configs/opt-1.3b.json")
    # ResNet-50: 4.09 G multiply-adds an image (He et al. report 3.8 G for
    # the stride-on-1x1 variant; stride on the 3x3 adds the rest)
    assert work.resnet_fwd_flops(rn) == pytest.approx(8.18e9, rel=0.01)
    # one OPT-1.3B layer: 12 d^2 parameters in its six matrices
    per_layer = 4 * 2048 * 2048 + 2 * 2048 * 8192
    assert work.lm_weight_bytes(opt, 24, 2) == \
        2 * (24 * per_layer + 2048 * 50272)
    assert work.kv_bytes_per_token(opt, 24, 1) == \
        2 * 24 * 2048 + 2 * 24 * 32 * 4
    assert work.kv_bytes_per_token(opt, 24, 2) == 2 * 24 * 2048 * 2
    t = {"seq_len": 2048, "layers_key": "train_num_hidden_layers"}
    fwd = work.lm_fwd_flops_per_token(opt, 4, 2048)
    assert work.train_flops_per_sample(opt, t) == 3 * 2048 * fwd
    assert fwd == pytest.approx(
        4 * (2 * per_layer + 4 * 2048 * 2049 / 2) + 2 * 2048 * 50272)


def test_process_age_and_phases():
    assert 0 < harness.process_age_s() < 3600
    ph = harness.Phases()
    ph.mark("a")
    ph.mark("b")
    assert list(ph.seconds) == ["a", "b"] and ph.seconds["a"] > 0
