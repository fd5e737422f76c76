"""The static-analysis pass framework: finding/suppression machinery and —
the acceptance teeth — deliberately broken programs caught by the matching
pass:

* a dropped donation (donated buffer XLA cannot alias) -> DonationPass;
* a perturbed sharding spec inserting an all-gather the budget never had
  -> CollectiveBudgetPass;
* a dtype-drift retrace (f32 call then f64 call of "the same" program)
  -> RetracePass, with the signature diff naming the drifted leaf;
* a host callback left inside a jitted program -> HostSyncPass;
* f32 dots inside a bf16 program / unmodeled dot-like ops ->
  FlopDtypePass.

The five canonical programs' zero-finding run is exercised end-to-end by
``tools/mxlint.py --smoke`` (tests/test_bench_contract.py).
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import analysis
from mxnet_tpu.analysis import (Finding, ProgramArtifact, RetraceAuditor,
                                artifact_from_jit, run_passes)
from mxnet_tpu.analysis.passes import (CollectiveBudgetPass, DonationPass,
                                       FlopDtypePass, HostSyncPass,
                                       RetracePass)


# ---------------------------------------------------------------------------
# framework: findings, suppressions, missing surfaces
# ---------------------------------------------------------------------------
def _stub(name="prog", **kw):
    kw.setdefault("jaxpr_text", "")
    kw.setdefault("stablehlo_text", "")
    kw.setdefault("compiled_text", "HloModule stub\n")
    return ProgramArtifact(name=name, **kw)


def test_finding_severity_validated():
    with pytest.raises(ValueError):
        Finding(pass_name="p", program="x", severity="fatal", message="m")


def test_run_passes_suppression_patterns():
    art = _stub(donated_leaves=3)  # stub compiled text has no aliases
    report = run_passes([art], passes=[DonationPass()])
    assert len(report.errors) == 1
    # exact, program-scoped, and wildcard suppressions all match
    for spec in ("donation", "donation:prog", "donation:*:dropped-donation",
                 "*:prog"):
        rep = run_passes([art], passes=[DonationPass()], suppressions=spec)
        assert rep.errors == [] and len(rep.suppressed) == 1, spec
    # non-matching pattern suppresses nothing
    rep = run_passes([art], passes=[DonationPass()],
                     suppressions="donation:otherprog")
    assert len(rep.errors) == 1


def test_run_passes_env_suppression(monkeypatch):
    from mxnet_tpu import config as _config

    art = _stub(donated_leaves=1)
    monkeypatch.setenv("MXNET_ANALYSIS_SUPPRESS", "donation")
    _config.refresh("MXNET_ANALYSIS_SUPPRESS")
    try:
        rep = run_passes([art], passes=[DonationPass()])
        assert rep.errors == [] and len(rep.suppressed) == 1
    finally:
        monkeypatch.delenv("MXNET_ANALYSIS_SUPPRESS")
        _config.refresh("MXNET_ANALYSIS_SUPPRESS")


def test_run_passes_budget_file_suppressions():
    art = _stub(donated_leaves=1)
    rep = run_passes([art], passes=[DonationPass()],
                     budgets={"suppressions": ["donation:prog"]})
    assert rep.errors == [] and len(rep.suppressed) == 1


def test_missing_surface_degrades_visibly():
    art = ProgramArtifact(name="partial")  # no texts at all
    rep = run_passes([art], passes=[DonationPass(), HostSyncPass()])
    codes = {f.code for f in rep.findings}
    assert codes == {"missing-surface"}
    assert all(f.severity == "info" for f in rep.findings)


def test_report_json_and_text_roundtrip():
    art = _stub(donated_leaves=2)
    rep = run_passes([art], passes=[DonationPass()])
    import json

    blob = json.loads(rep.to_json())
    assert blob["summary"]["errors"] == 1
    assert blob["findings"][0]["pass"] == "donation"
    assert "dropped-donation" in rep.format_text()


# ---------------------------------------------------------------------------
# broken program 1: dropped donation
# ---------------------------------------------------------------------------
def test_donation_pass_catches_dropped_donation():
    import jax
    import jax.numpy as jnp

    # the donated f32 input's only output is bf16 — half the bytes, so
    # XLA cannot reuse the buffer and the donation is silently dropped
    fn = jax.jit(lambda x: x.astype(jnp.bfloat16), donate_argnums=(0,))
    art = artifact_from_jit(
        fn, (jax.ShapeDtypeStruct((16, 16), jnp.float32),),
        name="bad_donation", donated_leaves=1)
    rep = run_passes([art], passes=[DonationPass()])
    assert len(rep.errors) == 1
    err = rep.errors[0]
    assert err.code == "dropped-donation"
    assert err.detail["donated"] == 1 and err.detail["aliased"] == 0


def test_donation_pass_passes_real_donation():
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda x, y: (x + y, x * y), donate_argnums=(0, 1))
    art = artifact_from_jit(
        fn, (jax.ShapeDtypeStruct((8, 8), jnp.float32),
             jax.ShapeDtypeStruct((8, 8), jnp.float32)),
        name="good_donation", donated_leaves=2)
    rep = run_passes([art], passes=[DonationPass()])
    assert rep.errors == []


# ---------------------------------------------------------------------------
# broken program 2: sharding-spec regression inserts an all-gather
# ---------------------------------------------------------------------------
@pytest.mark.skipif("len(__import__('jax').devices()) < 8")
def test_budget_pass_catches_gspmd_inserted_all_gather():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = np.array(jax.devices()[:8]).reshape(4, 2)
    mesh = Mesh(devs, ("data", "model"))
    # the "regressed" spec: input sharded on model, output demanded
    # replicated — GSPMD must insert an all-gather to satisfy it
    fn = jax.jit(lambda x: x * 2.0,
                 in_shardings=NamedSharding(mesh, P("model")),
                 out_shardings=NamedSharding(mesh, P()))
    art = artifact_from_jit(
        fn, (jax.ShapeDtypeStruct((16, 8), jnp.float32),),
        name="sharded_mul")
    from mxnet_tpu.analysis.hlo_parse import collective_stats

    measured = collective_stats(art.compiled_text)
    assert measured["all-gather"]["count"] >= 1  # the regression is real
    # the committed budget says this program has NO collectives
    budgets = {"programs": {"sharded_mul": {
        "collectives": {"total": {"count": 0, "bytes": 0}}}}}
    rep = run_passes([art], passes=[CollectiveBudgetPass()], budgets=budgets)
    codes = {f.code for f in rep.errors}
    assert "unbudgeted-op" in codes          # brand-new all-gather
    assert "over-budget" in codes            # total count 0 exceeded


def test_budget_pass_over_budget_and_within():
    hlo = ("HloModule m\n  %ar = f32[256]{0} all-reduce(f32[256]{0} %x), "
           "replica_groups={}\n")
    art = _stub("p", compiled_text=hlo)
    over = {"programs": {"p": {"collectives": {
        "total": {"count": 1, "bytes": 512},
        "all-reduce": {"count": 1, "bytes": 512}}}}}
    rep = run_passes([art], passes=[CollectiveBudgetPass()], budgets=over)
    assert any(f.code == "over-budget" and f.detail["kind"] == "bytes"
               for f in rep.errors)
    ok = {"programs": {"p": {"collectives": {
        "total": {"count": 1, "bytes": 1024},
        "all-reduce": {"count": 1, "bytes": 1024}}}}}
    rep = run_passes([art], passes=[CollectiveBudgetPass()], budgets=ok)
    assert rep.errors == []


def test_budget_pass_stale_headroom_is_visible():
    # a budgeted op that vanished from the program entirely must surface
    # (its ceiling is silent headroom a regression could refill)
    art = _stub("p", compiled_text="HloModule m\n")
    budgets = {"programs": {"p": {"collectives": {
        "total": {"count": 54, "bytes": 18112},
        "all-reduce": {"count": 54, "bytes": 18112}}}}}
    rep = run_passes([art], passes=[CollectiveBudgetPass()], budgets=budgets)
    assert rep.errors == []
    stale = [f for f in rep.findings if f.code == "stale-budget"]
    assert len(stale) == 1 and stale[0].detail["op"] == "all-reduce"


def test_budget_pass_missing_budget_is_visible():
    hlo = ("HloModule m\n  %ar = f32[64]{0} all-reduce(f32[64]{0} %x), "
           "replica_groups={}\n")
    rep = run_passes([_stub("p", compiled_text=hlo)],
                     passes=[CollectiveBudgetPass()])
    assert any(f.code == "no-budget" and f.severity == "warning"
               for f in rep.findings)


# ---------------------------------------------------------------------------
# broken program 3: dtype-drift retrace
# ---------------------------------------------------------------------------
def test_retrace_pass_catches_dtype_drift():
    import jax

    auditor = RetraceAuditor(lambda x: x * 2, name="drifty")
    fn = jax.jit(auditor.wrapped)
    x32 = np.arange(8, dtype=np.float32)
    auditor.observe(x32)
    fn(x32)
    auditor.observe(x32)
    fn(x32)                       # same signature: cache hit
    assert auditor.traces == 1
    x64 = np.arange(8, dtype=np.float64)  # the drift (x64 is enabled)
    auditor.observe(x64)
    fn(x64)
    assert auditor.traces == 2
    rec = auditor.record(expected_traces=1)
    assert rec["unique_signatures"] == 2
    assert any("float32 -> float64" in d for diff in rec["diffs"]
               for d in diff)
    art = ProgramArtifact(name="drifty", trace_count=auditor.traces,
                          expected_traces=1, meta={"retrace": rec})
    rep = run_passes([art], passes=[RetracePass()])
    assert len(rep.errors) == 1
    assert "float32 -> float64" in rep.errors[0].message


def test_retrace_pass_ok_and_uninstrumented():
    art = ProgramArtifact(name="ok", trace_count=1, expected_traces=1)
    rep = run_passes([art], passes=[RetracePass()])
    assert rep.errors == [] and rep.findings[0].code == "no-retrace"
    bare = ProgramArtifact(name="bare")
    rep = run_passes([bare], passes=[RetracePass()])
    assert rep.findings[0].code == "no-instrumentation"


def test_decode_predictor_trace_counters():
    # the DecodeServer "zero retraces" claim as a checked invariant:
    # repeated prefills at one shape and many decode steps = one trace each
    import jax

    from mxnet_tpu.decode import DecodePredictor
    from mxnet_tpu.models import attention_lm

    sym = attention_lm.get_symbol(vocab_size=16, seq_len=8, num_layers=1,
                                  embed=8, heads=2, ffn_hidden=16)
    rng = np.random.RandomState(0)
    arg_shapes, _, aux_shapes = sym.infer_shape(data=(2, 8),
                                                softmax_label=(2, 8))
    params = {n: rng.normal(0, 0.02, s).astype(np.float32)
              for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in ("data", "softmax_label")}
    pred = DecodePredictor(sym, params, cache_len=8, temperature=0.0)
    prompts = rng.randint(0, 16, (2, 8)).astype(np.float32)
    key = jax.random.PRNGKey(0)
    state, _ = pred.prefill(prompts, 4, key)
    state, _ = pred.prefill(prompts, 4, key)
    for _ in range(3):
        state, _ = pred.step(state, key)
    art = pred.decode_artifact(state)
    assert pred.trace_counts == {"prefill": 1, "decode": 1, "verify": 0,
                                 "chunk": 0, "fork": 0, "commit": 0,
                                 "extract": 0, "install": 0}
    assert art.trace_count == 1 and art.donated_leaves == \
        len(jax.tree_util.tree_leaves(state))
    rep = run_passes([art, pred.prefill_artifact(2, 8)],
                     passes=[RetracePass(), DonationPass()])
    assert rep.errors == []


# ---------------------------------------------------------------------------
# host-sync lint
# ---------------------------------------------------------------------------
def test_host_sync_pass_catches_callback():
    import jax
    import jax.numpy as jnp

    def leaky(x):
        jax.debug.print("x={x}", x=x.sum())
        return x * 2

    art = artifact_from_jit(jax.jit(leaky),
                            (jax.ShapeDtypeStruct((4,), jnp.float32),),
                            name="leaky", compile_program=False)
    rep = run_passes([art], passes=[HostSyncPass()])
    assert len(rep.errors) == 1
    assert rep.errors[0].code == "debug_print"


def test_host_sync_pass_catches_pure_callback():
    import jax
    import jax.numpy as jnp

    def impure(x):
        return jax.pure_callback(
            lambda v: np.asarray(v) * 2,
            jax.ShapeDtypeStruct((4,), jnp.float32), x)

    art = artifact_from_jit(jax.jit(impure),
                            (jax.ShapeDtypeStruct((4,), jnp.float32),),
                            name="impure", compile_program=False)
    rep = run_passes([art], passes=[HostSyncPass()])
    assert any(f.code == "pure_callback" for f in rep.errors)


def test_host_sync_pass_sanctioned_allowlist():
    """An artifact may declare intentional host transfers
    (meta['host_sync_allow'] — the elastic fence-d2h mechanism): matching
    findings downgrade to visible info rows instead of errors, while
    unlisted codes still fail."""
    import jax
    import jax.numpy as jnp

    def leaky(x):
        jax.debug.print("x={x}", x=x.sum())
        return x * 2

    art = artifact_from_jit(jax.jit(leaky),
                            (jax.ShapeDtypeStruct((4,), jnp.float32),),
                            name="fence", compile_program=False,
                            host_sync_allow=["debug_print"])
    rep = run_passes([art], passes=[HostSyncPass()])
    assert rep.errors == []
    sanc = [f for f in rep.findings
            if f.code == "sanctioned:debug_print"]
    assert len(sanc) == 1 and sanc[0].severity == "info", rep.findings
    # the waiver is code-specific: a different leak is still an error
    art2 = artifact_from_jit(jax.jit(leaky),
                             (jax.ShapeDtypeStruct((4,), jnp.float32),),
                             name="fence2", compile_program=False,
                             host_sync_allow=["hlo-outfeed"])
    rep2 = run_passes([art2], passes=[HostSyncPass()])
    assert len(rep2.errors) == 1
    assert rep2.errors[0].code == "debug_print"


def test_host_sync_pass_clean_program():
    import jax
    import jax.numpy as jnp

    art = artifact_from_jit(jax.jit(lambda x: x * 2),
                            (jax.ShapeDtypeStruct((4,), jnp.float32),),
                            name="clean")
    rep = run_passes([art], passes=[HostSyncPass()])
    assert rep.errors == []


# ---------------------------------------------------------------------------
# FLOP/dtype lint
# ---------------------------------------------------------------------------
def test_flop_pass_errors_on_uncounted_ops():
    # a label-less convolution whose output-feature dim matches NO
    # conventional kernel layout (result features 5, kernel dims
    # [4,3,3,3]) defeats the shape-inference fallback and must stay a
    # visible uncounted error
    sh = ("%4 = stablehlo.convolution(%1, %2) : (tensor<1x3x8x8xf32>, "
          "tensor<4x3x3x3xf32>) -> tensor<1x5x6x6xf32>")
    art = _stub("convnet", stablehlo_text=sh, compiled_text=None)
    rep = run_passes([art], passes=[FlopDtypePass()])
    assert any(f.code == "uncounted:stablehlo.convolution"
               for f in rep.errors)
    # the resolvable layout (features 4 == kernel dim 0) is now COUNTED
    # by shape inference, not an error (see test_hlo_stats)
    ok = _stub("convnet", stablehlo_text=sh.replace("1x5x6x6", "1x4x6x6"),
               compiled_text=None)
    rep = run_passes([ok], passes=[FlopDtypePass()])
    assert not any(f.code.startswith("uncounted") for f in rep.errors)


def test_flop_pass_flags_f32_dot_in_bf16_program():
    sh = ("%3 = stablehlo.dot_general %1, %2, contracting_dims = [1] x [0]"
          " : (tensor<8x16xf32>, tensor<16x4xf32>) -> tensor<8x4xf32>\n"
          "%5 = stablehlo.dot_general %3, %4, contracting_dims = [1] x [0]"
          " : (tensor<8x4xbf16>, tensor<4x2xbf16>) -> tensor<8x2xbf16>\n")
    art = _stub("mixed", stablehlo_text=sh, compiled_text=None,
                compute_dtype="bfloat16")
    rep = run_passes([art], passes=[FlopDtypePass()])
    warn = [f for f in rep.findings if f.code == "f32-dot"]
    assert len(warn) == 1 and warn[0].severity == "warning"
    assert warn[0].detail["count"] == 1 and warn[0].detail["total_dots"] == 2
    # the same program declared f32 is clean
    art32 = _stub("plain", stablehlo_text=sh, compiled_text=None)
    rep = run_passes([art32], passes=[FlopDtypePass()])
    assert all(f.code != "f32-dot" for f in rep.findings)


def test_flop_pass_warns_unknown_dtype_in_compiled():
    art = _stub("weird", stablehlo_text="", compiled_text=(
        "HloModule m\n  %x = f6e3m2[32]{0} parameter(0)\n"))
    rep = run_passes([art], passes=[FlopDtypePass()])
    assert any(f.code == "unknown-dtype" and f.detail["dtypes"] == ["f6e3m2"]
               for f in rep.findings)


# ---------------------------------------------------------------------------
# module surface + runtime transfer guard
# ---------------------------------------------------------------------------
def _tiny_fit(num_epoch=1):
    from mxnet_tpu.io import NDArrayIter

    rng = np.random.RandomState(0)
    X = rng.uniform(-1, 1, (64, 16)).astype(np.float32)
    y = rng.randint(0, 4, (64,)).astype(np.float32)
    it = NDArrayIter(X, y, batch_size=16)
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, eval_metric="acc", num_epoch=num_epoch, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            initializer=mx.initializer.Xavier())
    return mod


def test_module_program_artifacts_clean_under_all_passes():
    mod = _tiny_fit()
    arts = mod.program_artifacts()
    assert "train_step" in arts
    art = arts["train_step"]
    assert art.donated_leaves > 0 and art.trace_count is not None
    rep = run_passes(list(arts.values()))
    assert rep.errors == [], rep.format_text()


def test_fit_under_transfer_guard_disallow(monkeypatch):
    # the async loop's zero-per-step-host-syncs invariant survives the
    # armed runtime guard (device metrics keep accumulation on device;
    # CPU same-device reads are free, so this checks arming + the loop
    # plumbing — the rig is where 'disallow' has real teeth)
    from mxnet_tpu import config as _config

    monkeypatch.setenv("MXNET_TRANSFER_GUARD", "disallow")
    _config.refresh("MXNET_TRANSFER_GUARD")
    try:
        mod = _tiny_fit()
        assert mod._fused_step is not None
    finally:
        monkeypatch.delenv("MXNET_TRANSFER_GUARD")
        _config.refresh("MXNET_TRANSFER_GUARD")


def test_ruff_clean_on_lint_scope():
    """`ruff check` over the configured scope (pyproject.toml: the
    analysis package + tools/) must be clean.  Skips where ruff is not
    installed — the container bakes no linters and installing is out of
    scope; the pinned config keeps CI and laptops that do have it in
    agreement."""
    import os
    import shutil
    import subprocess

    if shutil.which("ruff") is None:
        pytest.skip("ruff not installed in this environment")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        ["ruff", "check", "mxnet_tpu/analysis", "tools"],
        capture_output=True, text=True, cwd=root, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_load_budgets_default_and_missing(tmp_path):
    budgets = analysis.load_budgets()
    assert "programs" in budgets          # the committed file
    assert set(budgets["programs"]) >= {"train_step", "eval_step",
                                        "prefill", "decode_step",
                                        "decode_step_q", "draft_step",
                                        "verify_step", "ring_tp_step"}
    assert analysis.load_budgets(str(tmp_path / "nope.json")) == {}


# ---------------------------------------------------------------------------
# cache-bytes pass (PR 6): byte ceilings + quantized-config dtype check
# ---------------------------------------------------------------------------
def _cache_budgets(name, ceiling):
    return {"programs": {name: {"cache_bytes": ceiling}}}


def test_cache_bytes_pass_skips_programs_without_cache_meta():
    from mxnet_tpu.analysis.passes import CacheBytesPass

    rep = run_passes([_stub("train_step")], passes=[CacheBytesPass()])
    assert [f.code for f in rep.findings] == ["no-cache"]
    assert not rep.unsuppressed


def test_cache_bytes_pass_flags_over_budget():
    from mxnet_tpu.analysis.passes import CacheBytesPass

    art = _stub("decode_step", meta={"cache_bytes": 4096,
                                     "kv_dtype": None,
                                     "cache_data_dtypes": ["float32"]})
    rep = run_passes([art], passes=[CacheBytesPass()],
                     budgets=_cache_budgets("decode_step", 2048))
    assert len(rep.errors) == 1 and rep.errors[0].code == "over-budget"
    # inclusive ceiling: measured == budget passes
    rep = run_passes([art], passes=[CacheBytesPass()],
                     budgets=_cache_budgets("decode_step", 4096))
    assert not rep.errors
    assert any(f.code == "within-budget" for f in rep.findings)


def test_cache_bytes_pass_flags_f32_cache_in_quantized_config():
    """The dtype regression the pass exists for: MXNET_KV_DTYPE promises
    narrow reads but the data planes silently store f32."""
    from mxnet_tpu.analysis.passes import CacheBytesPass

    art = _stub("decode_step_q",
                meta={"cache_bytes": 4096, "kv_dtype": "int8",
                      "cache_data_dtypes": ["float32"]})
    rep = run_passes([art], passes=[CacheBytesPass()],
                     budgets=_cache_budgets("decode_step_q", 8192))
    assert any(f.code == "f32-cache" and f.severity == "error"
               for f in rep.errors)
    # properly-narrow data is clean
    ok = _stub("decode_step_q",
               meta={"cache_bytes": 2048, "kv_dtype": "int8",
                     "cache_data_dtypes": ["int8"]})
    rep = run_passes([ok], passes=[CacheBytesPass()],
                     budgets=_cache_budgets("decode_step_q", 8192))
    assert not rep.errors


def test_cache_bytes_pass_warns_without_committed_budget():
    from mxnet_tpu.analysis.passes import CacheBytesPass

    art = _stub("mystery", meta={"cache_bytes": 1024, "kv_dtype": None,
                                 "cache_data_dtypes": ["float32"]})
    rep = run_passes([art], passes=[CacheBytesPass()])
    assert any(f.code == "no-budget" and f.severity == "warning"
               for f in rep.findings)


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-v"]))


# ---------------------------------------------------------------------------
# sort/scatter intermediate pricing (stablehlo_sort_scatter_stats)
# ---------------------------------------------------------------------------
def test_sort_scatter_stats_canned_snippets():
    """Canned lowered-StableHLO forms: a region-bearing multi-result
    sort (argsort's (keys, payload) pair), a region-bearing scatter,
    an inline one-line sort — and select_and_scatter (pooling backward)
    must NOT count."""
    from mxnet_tpu.analysis.hlo_parse import stablehlo_sort_scatter_stats

    text = "\n".join([
        'module @jit_f {',
        '  %0:2 = "stablehlo.sort"(%arg0, %arg1) ({',
        '  ^bb0(%a: tensor<i32>, %b: tensor<i32>, %c: tensor<i32>,'
        ' %d: tensor<i32>):',
        '    %c0 = stablehlo.compare  LT, %a, %b : (tensor<i32>,'
        ' tensor<i32>) -> tensor<i1>',
        '    stablehlo.return %c0 : tensor<i1>',
        '  }) : (tensor<64xi32>, tensor<64xi32>)'
        ' -> (tensor<64xi32>, tensor<64xi32>)',
        '  %1 = "stablehlo.scatter"(%arg2, %idx, %upd) ({',
        '  ^bb0(%e: tensor<f32>, %f: tensor<f32>):',
        '    stablehlo.return %f : tensor<f32>',
        '  }) : (tensor<16xf32>, tensor<4x1xi32>, tensor<4xf32>)'
        ' -> tensor<16xf32>',
        '  %2 = "stablehlo.select_and_scatter"(%x, %y, %z) ({',
        '  ^bb0(%g: tensor<f32>, %h: tensor<f32>):',
        '    stablehlo.return %g : tensor<i1>',
        '  }) : (tensor<8x8xf32>, tensor<4x4xf32>, tensor<f32>)'
        ' -> tensor<8x8xf32>',
        '  %3 = "stablehlo.sort"(%arg3) : (tensor<32xbf16>)'
        ' -> tensor<32xbf16>',
        '}',
    ])
    stats = stablehlo_sort_scatter_stats(text)
    # region sort: 2x (64*4 + 64*4); inline sort: 2x 32*2
    assert stats["sort"] == {"count": 2, "bytes": 2 * 512 + 2 * 64}
    # scatter: 2x the 16-f32 result; select_and_scatter NOT counted
    assert stats["scatter"] == {"count": 1, "bytes": 2 * 64}
    assert stats["total"] == {"count": 3,
                              "bytes": 2 * 512 + 2 * 64 + 2 * 64}


def test_sort_scatter_stats_empty_and_real_lowering():
    """No sort/scatter -> zero totals; and a REAL jax argsort+scatter
    lowering is priced > 0 through program_cost (the sort_scatter_bytes
    term folds into bytes)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.analysis.cost import program_cost
    from mxnet_tpu.analysis.hlo_parse import stablehlo_sort_scatter_stats

    assert stablehlo_sort_scatter_stats("module @empty {}")["total"] == \
        {"count": 0, "bytes": 0}

    def f(x):
        order = jnp.argsort(x)
        return jnp.zeros_like(x).at[order].set(x)

    spec = jax.ShapeDtypeStruct((128,), jnp.float32)
    cost = program_cost(jax.jit(f), (spec,))
    assert cost["sort_scatter_bytes"] > 0
    # the term folds into the total bytes floor
    assert cost["bytes"] >= 2 * 128 * 4 + cost["sort_scatter_bytes"]


# ---------------------------------------------------------------------------
# schedule pass (PR: async-overlap analysis) — canned TPU HLO corpus
# ---------------------------------------------------------------------------
def _corpus(name):
    import pathlib

    return (pathlib.Path(__file__).parent / "data" / "hlo"
            / name).read_text()


def _overlap_budget(prog, **kw):
    ceiling = {"min_pairs": 6, "min_shadow_flops": 1_000_000_000,
               "max_serialized": 0}
    ceiling.update(kw)
    return {"programs": {prog: {"overlap":
                                {"collective-permute": ceiling}}}}


def test_parse_schedule_double_buffered_ring():
    """The acceptance numbers for the canned n=4 ring: 2*(n-1)=6 matched
    collective-permute pairs, zero unpaired, and every overlap window
    shadows the chunk matmul (nonzero FLOPs) plus the half-chunk wire
    payload."""
    from mxnet_tpu.analysis.schedule import parse_schedule

    model = parse_schedule(
        _corpus("ring_collective_permute_overlapped.hlo"))
    assert len(model.pairs) == 6
    assert model.unpaired_starts == [] and model.unpaired_dones == []
    assert model.serialized_pairs() == []
    for p in model.pairs:
        assert p.op == "collective-permute"
        assert p.shadow_flops > 0 and p.shadow_ops > 0
        assert p.bytes == 2 * 2048 * 2048  # bf16[2048,2048] chunk
    # each window hides the bf16[2048,2048] x [2048,4096] chunk matmul
    assert model.pairs[0].shadow_flops == 2 * 2048 * 2048 * 4096


def test_schedule_pass_ring_meets_overlap_budget():
    art = _stub("ring_tpu", compiled_text=_corpus(
        "ring_collective_permute_overlapped.hlo"))
    from mxnet_tpu.analysis.schedule import SchedulePass

    rep = run_passes([art], passes=[SchedulePass()],
                     budgets=_overlap_budget("ring_tpu"))
    assert rep.errors == [], [f.message for f in rep.errors]
    info = next(f for f in rep.findings if f.code == "overlapped")
    assert info.detail["pairs"] == 6


def test_schedule_pass_serialized_ring_fails_overlap_budget():
    """The same ring with every -done retiring its -start immediately:
    the async split hides nothing, and the overlap budget (which says
    this program PAYS for latency hiding) must flag all six pairs."""
    art = _stub("ring_tpu", compiled_text=_corpus(
        "ring_collective_permute_serialized.hlo"))
    from mxnet_tpu.analysis.schedule import SchedulePass

    rep = run_passes([art], passes=[SchedulePass()],
                     budgets=_overlap_budget("ring_tpu"))
    ser = [f for f in rep.errors if f.code == "serialized-pair"]
    assert ser and ser[0].detail["measured"] == 6
    # without a budget the same schedule is a visible info, not an error
    rep = run_passes([art], passes=[SchedulePass()])
    assert rep.errors == []
    assert any(f.code == "serialized-pair" and f.severity == "info"
               for f in rep.findings)


def test_schedule_pass_unpaired_start_always_error():
    art = _stub("broken", compiled_text=_corpus(
        "unpaired_collective_permute_start.hlo"))
    from mxnet_tpu.analysis.schedule import SchedulePass

    rep = run_passes([art], passes=[SchedulePass()])  # no budget at all
    assert len(rep.errors) == 1
    assert rep.errors[0].code == "unpaired-start"
    assert "cp-start.1" in rep.errors[0].message


def test_schedule_pass_mixed_async_families_and_sync_backend():
    from mxnet_tpu.analysis.schedule import SchedulePass, parse_schedule

    model = parse_schedule(_corpus("async_mixed_overlap.hlo"))
    assert sorted(p.op for p in model.pairs) == \
        ["all-gather", "all-reduce", "copy"]
    assert all(not p.serialized for p in model.pairs)
    # XLA:CPU keeps sync collectives: no pairs -> info row, never errors
    rep = run_passes([_stub("cpu_prog")], passes=[SchedulePass()])
    assert rep.errors == []
    assert [f.code for f in rep.findings] == ["sync-backend"]


def test_schedule_pass_missing_pairs_floor():
    """A budget promising more pairs than the schedule carries means the
    latency-hiding structure was lost (sync legalization)."""
    art = _stub("ring_tpu", compiled_text=_corpus(
        "ring_collective_permute_overlapped.hlo"))
    from mxnet_tpu.analysis.schedule import SchedulePass

    rep = run_passes([art], passes=[SchedulePass()],
                     budgets=_overlap_budget("ring_tpu", min_pairs=8))
    assert any(f.code == "missing-pairs" for f in rep.errors)


# ---------------------------------------------------------------------------
# sharding-coverage pass (PR: partition-rule coverage audit)
# ---------------------------------------------------------------------------
def _cov_art(name="tp_prog", leaves=None, mesh=None, degrades=None):
    meta = {}
    if leaves is not None:
        meta["sharding_coverage"] = {
            "mesh": mesh or {"data": 2, "model": 2},
            "leaves": leaves}
    if degrades is not None:
        meta["replicated_degrades"] = degrades
    return _stub(name, meta=meta)


def test_sharding_coverage_degrade_is_error_naming_param():
    from mxnet_tpu.analysis.passes import ShardingCoveragePass

    art = _cov_art(leaves={
        "layer0_ffn_w1": {"shape": [16, 48], "source": "rule",
                          "degrade": "indivisible"},
        "layer0_attn_q": {"shape": [16, 16], "source": "rule",
                          "spec": [None, "model"]}})
    rep = run_passes([art], passes=[ShardingCoveragePass()])
    assert len(rep.errors) == 1
    err = rep.errors[0]
    assert err.code == "replicated-degrade"
    assert "layer0_ffn_w1" in err.message and "indivisible" in err.message


def test_sharding_coverage_unmatched_param_strict_vs_info():
    from mxnet_tpu.analysis.passes import ShardingCoveragePass

    art = _cov_art(leaves={
        "pos_embed_weight": {"shape": [1, 16, 16], "source": "default"}})
    rep = run_passes([art], passes=[ShardingCoveragePass()])
    assert rep.errors == []
    info = next(f for f in rep.findings if f.code == "unmatched-param")
    assert info.severity == "info" and "pos_embed_weight" in info.message
    # the budget opts the program into strict coverage -> error
    rep = run_passes(
        [art], passes=[ShardingCoveragePass()],
        budgets={"programs": {"tp_prog": {"sharding": {"strict": True}}}})
    assert len(rep.errors) == 1
    assert rep.errors[0].code == "unmatched-param"


def test_sharding_coverage_vectors_and_scalars_are_intentional():
    """Effective rank < 2 (scalars, [16] biases, [1,1,16] LN gains)
    always counts as an intentional replicate — even under strict."""
    from mxnet_tpu.analysis.passes import ShardingCoveragePass

    art = _cov_art(leaves={
        "step": {"shape": [], "source": "scalar"},
        "layer0_ln_bias": {"shape": [16], "source": "default"},
        "layer0_ln_gain": {"shape": [1, 1, 16], "source": "default"},
        "layer0_attn_q": {"shape": [16, 16], "source": "plan",
                          "spec": [None, "model"]}})
    rep = run_passes(
        [art], passes=[ShardingCoveragePass()],
        budgets={"programs": {"tp_prog": {"sharding": {"strict": True}}}})
    assert rep.errors == []
    cov = next(f for f in rep.findings if f.code == "covered")
    assert cov.detail["sharded"] == 1 and cov.detail["replicated"] == 3


def test_sharding_coverage_kv_degrade_visible_info():
    from mxnet_tpu.analysis.passes import ShardingCoveragePass

    art = _cov_art(degrades=[
        {"site": "kv-cache", "reason": "num_kv_heads=2 % model=4 != 0"}])
    rep = run_passes([art], passes=[ShardingCoveragePass()])
    assert rep.errors == []
    row = next(f for f in rep.findings
               if f.code == "kv-replicated-degrade")
    assert row.severity == "info" and "kv-cache" in row.message


def test_sharding_coverage_unmeshed_program_skips():
    from mxnet_tpu.analysis.passes import ShardingCoveragePass

    rep = run_passes([_stub("decode_step")],
                     passes=[ShardingCoveragePass()])
    assert [f.code for f in rep.findings] == ["no-mesh"]
    assert rep.errors == []


# ---------------------------------------------------------------------------
# drift pass (PR: mxlint --record / --check differential gate)
# ---------------------------------------------------------------------------
def _drift_art(name="ring_tpu"):
    # a stub with real collective bytes + cache meta so the priced
    # quantities are nonzero (the corpus ring carries 6 cp transfers)
    return _stub(name, compiled_text=_corpus(
        "ring_collective_permute_overlapped.hlo"),
        meta={"cache_bytes": 4096})


def test_drift_record_check_roundtrip_green():
    from mxnet_tpu.analysis import record_snapshot, snapshot_hash
    from mxnet_tpu.analysis.passes import DriftPass

    art = _drift_art()
    snap = record_snapshot([art])
    assert snap["content_hash"] == snapshot_hash(snap)
    row = snap["programs"]["ring_tpu"]
    assert row["collective_bytes"] > 0 and row["cache_bytes"] == 4096
    rep = run_passes([art], passes=[DriftPass()], snapshot=snap)
    assert rep.errors == []
    assert [f.code for f in rep.findings] == ["within-tolerance"]


def test_drift_regression_fails_naming_program_and_quantity():
    """The acceptance case: +10% collective bytes vs the recorded
    baseline is an error naming the program and the quantity."""
    from mxnet_tpu.analysis import record_snapshot
    from mxnet_tpu.analysis.passes import DriftPass

    art = _drift_art()
    snap = record_snapshot([art])
    row = snap["programs"]["ring_tpu"]
    # rewind the baseline so this run's measurement reads +10%; counts
    # must agree or the EXACT comparison fires first
    row["collective_bytes"] = int(row["collective_bytes"] / 1.1)
    rep = run_passes([art], passes=[DriftPass()], snapshot=snap)
    assert len(rep.errors) == 1
    err = rep.errors[0]
    assert err.code == "drift:collective_bytes"
    assert err.program == "ring_tpu"
    assert "collective_bytes" in err.message and "%" in err.message


def test_drift_improvement_and_exact_quantities():
    from mxnet_tpu.analysis import record_snapshot
    from mxnet_tpu.analysis.passes import DriftPass

    art = _drift_art()
    snap = record_snapshot([art])
    # a SHRUNK priced quantity is an improvement to bank, not an error
    snap["programs"]["ring_tpu"]["cache_bytes"] = 8192
    rep = run_passes([art], passes=[DriftPass()], snapshot=snap)
    assert rep.errors == []
    assert any(f.code == "improved:cache_bytes" for f in rep.findings)
    # structural integers have no tolerance band at all
    snap = record_snapshot([art])
    snap["programs"]["ring_tpu"]["collective_count"] += 1
    rep = run_passes([art], passes=[DriftPass()], snapshot=snap)
    assert any(f.code == "drift:collective_count" for f in rep.errors)


def test_drift_new_program_warns_and_no_snapshot_is_info():
    from mxnet_tpu.analysis import record_snapshot
    from mxnet_tpu.analysis.passes import DriftPass

    art = _drift_art()
    snap = record_snapshot([_drift_art("other_prog")])
    rep = run_passes([art], passes=[DriftPass()], snapshot=snap)
    assert rep.errors == []
    assert any(f.code == "new-program" and f.severity == "warning"
               for f in rep.findings)
    rep = run_passes([art], passes=[DriftPass()])  # no snapshot loaded
    assert [f.code for f in rep.findings] == ["no-snapshot"]


def test_load_snapshot_refuses_hand_edited_baseline(tmp_path):
    import json as _json

    from mxnet_tpu.analysis import record_snapshot

    snap = record_snapshot([_drift_art()])
    path = tmp_path / "snap.json"
    path.write_text(_json.dumps(snap))
    assert analysis.load_snapshot(str(path))["version"] == 1
    # a hand edit (no re-record) breaks the content address
    snap["programs"]["ring_tpu"]["collective_bytes"] = 1
    path.write_text(_json.dumps(snap))
    with pytest.raises(ValueError, match="content hash mismatch"):
        analysis.load_snapshot(str(path))


# ---------------------------------------------------------------------------
# stale suppressions (PR satellite: suppression-interaction lint)
# ---------------------------------------------------------------------------
def test_stale_budget_suppression_becomes_info():
    art = _stub(donated_leaves=1)
    # matches the live dropped-donation finding: no stale row
    rep = run_passes([art], passes=[DonationPass()],
                     budgets={"suppressions": ["donation:prog"]})
    assert not any(f.code == "stale-suppression" for f in rep.findings)
    # the waived issue stopped firing: the dead waiver surfaces
    rep = run_passes([art], passes=[DonationPass()],
                     budgets={"suppressions": ["donation:otherprog"]})
    stale = next(f for f in rep.findings if f.code == "stale-suppression")
    assert stale.severity == "info" and stale.pass_name == "suppressions"
    assert "donation:otherprog" in stale.message
    assert rep.errors and rep.errors[0].code == "dropped-donation"
    # session-local (argument/env) suppressions are exempt
    rep = run_passes([art], passes=[DonationPass()],
                     suppressions="donation:otherprog")
    assert not any(f.code == "stale-suppression" for f in rep.findings)


# ---------------------------------------------------------------------------
# mxlint CLI contract: github annotations + exit codes
# ---------------------------------------------------------------------------
def _mxlint():
    import importlib.util
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "_mxlint_under_test", os.path.join(root, "tools", "mxlint.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_mxlint_format_github_annotations():
    mxlint = _mxlint()
    art = _stub(donated_leaves=2)
    rep = run_passes([art], passes=[DonationPass()])
    lines = mxlint.format_github(rep)
    assert len(lines) == 1
    line = lines[0]
    assert line.startswith("::error file=benchmarks/budgets.json,line=1,")
    assert "title=donation(prog):dropped-donation" in line
    # workflow-command escaping: no raw newlines or percents in the data
    rep.findings[0].message = "50% lost\nsecond line"
    assert "::50%25 lost%0Asecond line" in mxlint.format_github(rep)[0]
    # suppressed findings stay off the PR
    rep = run_passes([art], passes=[DonationPass()],
                     suppressions="donation")
    assert mxlint.format_github(rep) == []


def test_mxlint_exit_code_contract():
    """0 clean/info-only, 1 unsuppressed errors; 2 (usage/bad --check
    input) is pinned by test_bench_contract's subprocess runs."""
    mxlint = _mxlint()
    art = _stub(donated_leaves=1)
    assert mxlint._exit_code(run_passes([art],
                                        passes=[DonationPass()])) == 1
    assert mxlint._exit_code(run_passes([art], passes=[DonationPass()],
                                        suppressions="donation")) == 0
    clean = _stub()
    assert mxlint._exit_code(run_passes([clean],
                                        passes=[DonationPass()])) == 0
