"""The Pallas autotuner subsystem (``ops/tuning.py``).

ISSUE-16's tentpole piece 2: every kernel module registers its tunable
block-shape space, a ``MXNET_PALLAS_TUNE``-armed sweep probes the live
device layout_probe-style, and the winner persists in a
content-addressed tuning cache next to the AOT program cache — so a
COLD process resolves by deserializing the decision, not by re-probing.
What tier-1 pins:

* round-trip: an armed 2-candidate toy sweep runs (probe counter moves),
  persists its winner, and a memo-reset re-resolve is a pure disk hit
  (zero probes, same params);
* zero-probe cold start: a SUBPROCESS sharing only the cache directory
  resolves every swept space with ``PROBE_COUNT == 0`` — the fleet
  cold-start contract of PR 14, extended to tuning decisions;
* corrupt/stale entries read as a miss (defaults, visible warning,
  never a crash);
* interpret-mode sweeps are deterministic in WHAT they produce
  (winner key set = the space's params; every candidate either timed
  or skipped via SpaceError);
* unarmed resolution never probes and returns the registered defaults.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from mxnet_tpu import config
from mxnet_tpu.ops import tuning

pytestmark = pytest.mark.usefixtures("tmp_path")


@pytest.fixture
def tune_cache(tmp_path, monkeypatch):
    """A fresh cache dir + clean memo for every test."""
    cache = str(tmp_path / "programs")
    with config.overrides(MXNET_PROGRAM_CACHE=cache):
        tuning.reset_memo()
        yield cache
    tuning.reset_memo()


def _register_toy_space(calls):
    """A 2-candidate toy space whose probes count invocations; the
    block=16 candidate's probe is made measurably slower so the sweep
    deterministically picks block=8."""
    import time as _time

    def runner(params, shape_class, dtype, interpret):
        calls.append(dict(params))
        delay = 0.0 if params["block"] == 8 else 0.003

        def probe():
            if delay:
                _time.sleep(delay)
        return probe

    tuning.register_space(
        "toy_kernel", version=1, defaults={"block": 8},
        constants=("TOY_BLOCK",),
        candidates=lambda shape_class, interpret: [
            {"block": 8}, {"block": 16}],
        runner=runner)
    return calls


def test_unarmed_resolve_returns_defaults_without_probing(tune_cache):
    _register_toy_space([])
    before = tuning.PROBE_COUNT["n"]
    params = tuning.resolve("toy_kernel", "n64", "float32")
    assert params == {"block": 8}
    assert tuning.PROBE_COUNT["n"] == before


def test_sweep_roundtrip_persists_and_reloads(tune_cache):
    calls = _register_toy_space([])
    with config.overrides(MXNET_PALLAS_TUNE=True,
                          MXNET_PALLAS_INTERPRET=True):
        before = tuning.PROBE_COUNT["n"]
        params = tuning.resolve("toy_kernel", "n64", "float32")
        probes = tuning.PROBE_COUNT["n"] - before
    assert params == {"block": 8}          # the faster candidate won
    assert probes > 0                       # the sweep really probed
    assert {c["block"] for c in calls} == {8, 16}   # both candidates ran

    # the decision persisted: a memo-less re-resolve (armed OR not) is a
    # disk hit with ZERO probes
    tuning.reset_memo()
    before = tuning.PROBE_COUNT["n"]
    again = tuning.resolve("toy_kernel", "n64", "float32")
    assert again == params
    assert tuning.PROBE_COUNT["n"] == before

    # and the sidecar is honest about what it swept
    files = [f for f in os.listdir(tune_cache) if f.startswith("tune_")]
    assert len(files) == 1
    entry = json.load(open(os.path.join(tune_cache, files[0])))
    assert entry["op"] == "toy_kernel"
    assert entry["params"] == {"block": 8}
    assert len(entry["swept"]) == 2


def test_sweep_skips_space_error_candidates(tune_cache):
    def runner(params, shape_class, dtype, interpret):
        if params["block"] == 16:
            raise tuning.SpaceError("block does not tile")
        return lambda: None

    tuning.register_space(
        "toy_gated", version=1, defaults={"block": 8},
        constants=(),
        candidates=lambda shape_class, interpret: [{"block": 8},
                                                   {"block": 16}],
        runner=runner)
    with config.overrides(MXNET_PALLAS_TUNE=True,
                          MXNET_PALLAS_INTERPRET=True):
        params = tuning.resolve("toy_gated", "n64", "float32")
    assert params == {"block": 8}


def test_corrupt_entry_reads_as_defaults(tune_cache):
    calls = _register_toy_space([])
    with config.overrides(MXNET_PALLAS_TUNE=True,
                          MXNET_PALLAS_INTERPRET=True):
        tuning.resolve("toy_kernel", "n64", "float32")
    files = [f for f in os.listdir(tune_cache) if f.startswith("tune_")]
    path = os.path.join(tune_cache, files[0])
    with open(path, "w") as f:
        f.write("{not json")
    tuning.reset_memo()
    params = tuning.resolve("toy_kernel", "n64", "float32")
    assert params == {"block": 8}   # defaults, no crash


def test_stale_version_reads_as_miss(tune_cache):
    _register_toy_space([])
    with config.overrides(MXNET_PALLAS_TUNE=True,
                          MXNET_PALLAS_INTERPRET=True):
        tuning.resolve("toy_kernel", "n64", "float32")
    files = [f for f in os.listdir(tune_cache) if f.startswith("tune_")]
    path = os.path.join(tune_cache, files[0])
    entry = json.load(open(path))
    entry["version"] = 99   # a rewritten kernel bumped the space version
    with open(path, "w") as f:
        json.dump(entry, f)
    tuning.reset_memo()
    params = tuning.resolve("toy_kernel", "n64", "float32")
    assert params == {"block": 8}


def test_tampered_params_cannot_inject_unknown_keys(tune_cache):
    _register_toy_space([])
    key = tuning.put("toy_kernel", "n64", "float32",
                     {"block": 16, "evil_extra": 1}, version=1)
    assert key
    params = tuning.resolve("toy_kernel", "n64", "float32")
    assert params == {"block": 16}   # known key kept, unknown dropped


def test_shape_class_roundtrip():
    sc = tuning.shape_class_for(m=1000, k=64, n=256)
    assert sc == "k64,m1024,n256"
    assert tuning.parse_shape_class(sc) == {"k": 64, "m": 1024, "n": 256}


def test_all_kernel_spaces_registered():
    """The Pallas kernel modules with block constants all registered spaces
    (the same surface the mxlint tuner-coverage pass audits; the decode
    row's kernel, ``ops/pallas_decode.py``, takes its tile sizes from the
    call's shapes and has none)."""
    spaces = tuning.spaces()
    assert "pallas_decode" not in spaces
    for op in ("pallas_attention", "pallas_update"):
        assert op in spaces, sorted(spaces)
        sp = spaces[op]
        assert sp.defaults and sp.constants


_CHILD = textwrap.dedent("""
    import json, sys
    from mxnet_tpu import config
    from mxnet_tpu.ops import tuning

    cache, payload = sys.argv[1], json.loads(sys.argv[2])
    tuning.spaces()     # import the kernel modules' registrations
    with config.overrides(MXNET_PROGRAM_CACHE=cache):
        before = tuning.PROBE_COUNT["n"]
        out = {}
        for op, sc, dtype in payload:
            out[op] = tuning.resolve(op, sc, dtype)
        print(json.dumps({"probes": tuning.PROBE_COUNT["n"] - before,
                          "params": out}))
""")


@pytest.mark.slow
def test_cold_process_zero_probe_cache_hit(tune_cache):
    """The acceptance proof: sweep every REAL kernel space in this
    process, then a cold subprocess sharing only the cache directory
    resolves all of them with PROBE_COUNT == 0."""
    cases = [("pallas_attention",
              tuning.shape_class_for(t=128, d=64), "float32"),
             ("pallas_update", tuning.shape_class_for(n=4096), "any")]
    with config.overrides(MXNET_PALLAS_TUNE=True,
                          MXNET_PALLAS_INTERPRET=True):
        before = tuning.PROBE_COUNT["n"]
        warm = {op: tuning.resolve(op, sc, dt) for op, sc, dt in cases}
        assert tuning.PROBE_COUNT["n"] > before

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD, tune_cache, json.dumps(cases)],
        capture_output=True, text=True, timeout=240, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["probes"] == 0, result
    assert result["params"] == warm, (result, warm)
