#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls, in
ONE process on the TPU, at the full width of the models the repo benchmarks
(depth cut, weights random from a seed):

* ``train``     ResNet-50 bs 256 bf16 through ``Module.fit`` — the async
                loop, ``DevicePrefetchIter`` and device-side metrics on the
                path;
* ``serve``     a 2-layer width-1024 ``attention_lm`` behind ``DecodeServer``
                over a paged int8 KV pool, checked against the dense
                f32-cache predictor on the same chip;
* ``kernels``   each of the two Pallas families compiled by Mosaic once at a
                shape the phases above use, against its XLA reference;
* ``multichip`` (>= 4 chips) data-parallel ResNet-50 and one ring-attention
                LM step over a 4-way 'seq' mesh.

Every phase is a plain function of ``(ctx, sizes)``; only :func:`main` holds
the platform refusal and the full sizes, so the same functions run on the CPU
at a tiny size table before any chip time is spent (tests/
test_bench_contract.py does exactly that).  No phase catches an exception and
no child process is started: a chip belongs to one process.

Each phase prints one JSON line naming the device it ran on; the last line of
stdout is ``{"ok": true, "device": {"platform", "kind", "count"}}``.  Without
a TPU the script prints what it found and exits non-zero before building a
model.  Rates printed here are information for the reader, not claims.
"""
import json
import sys
import time

import numpy as np

FULL = {
    "train": dict(layers=50, classes=1000, image=(3, 224, 224), batch=256,
                  batches=4, epochs=2),
    # benchmarks/bench_decode.py's on-chip dims at head dim 128
    "serve": dict(vocab=8192, seq_len=2048, layers=2, embed=1024, heads=8,
                  ffn=4096, cache_len=2048, page_tokens=16, prefill_chunk=64,
                  slots=4, max_prefill=512, requests=8, prompt_lo=64,
                  prompt_hi=512, new_tokens=32),
    # flash: the LM's attention at T 2048.  The decode kernel takes the
    # serve phase's pool, the optimizer kernel the train phase's parameter
    # tree.
    "kernels": dict(interpret=False,
                    flash=dict(batch=4, t=2048, heads=8, head_dim=128)),
    # benchmarks/bench_long_context.py's on-chip dims, depth 1
    "multichip": dict(train=dict(batches=3, epochs=1),
                      lm=dict(vocab=8192, t=8192, layers=1, embed=2048,
                              heads=16, ffn=8192, batch=2, seq=4)),
}

# first-token / first-decode-step log-prob agreement, paged int8 pool vs
# dense f32 cache on the same device: int8 K/V carry per-(token, head)
# scales, so each stored value is off by at most 1/254 of its head's max
SERVE_LOGPROB_ATOL = 5e-3


def device_facts():
    """The device as jax reports it — carried by every printed result."""
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices())}


def _as_list(ctx):
    return list(ctx) if isinstance(ctx, (list, tuple)) else [ctx]


def _devices_of(tree):
    import jax

    out = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        out |= set(leaf.devices())
    return out


def _sig(x):
    """Three significant digits, for printing."""
    return float("%.3g" % x)


def _rel_err(got, ref):
    """max|got - ref| over max|ref|, in f32."""
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def _fit_resnet(ctx, s):
    """ResNet through ``Module.fit`` on a synthetic ``NDArrayIter``; returns
    ``(module, facts)`` after asserting the loop stayed on the device."""
    import mxnet_tpu as mx
    from mxnet_tpu import profiler
    from mxnet_tpu.io import DevicePrefetchIter
    from mxnet_tpu.models import resnet

    ctxs = _as_list(ctx)
    want = {c.jax_device for c in ctxs}
    batch, image = s["batch"], tuple(s["image"])
    rng = np.random.RandomState(0)
    n = batch * s["batches"]
    X = rng.uniform(-1, 1, (n,) + image).astype(np.float32)
    y = rng.randint(0, s["classes"], (n,)).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=batch)

    net = resnet.get_symbol(num_classes=s["classes"], num_layers=s["layers"],
                            image_shape=image)
    mod = mx.mod.Module(net, context=ctxs, compute_dtype="bfloat16")
    # bound and initialized here (fit keeps an existing binding) so the
    # starting weights can be compared with the trained ones
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mx.random.seed(0)
    mod.init_params(mx.initializer.Xavier(rnd_type="gaussian",
                                          factor_type="in", magnitude=2))
    before = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}

    seen = {"first_step_s": None, "epoch_end": [], "in_loop_d2h": 0,
            "boundary_d2h": 0, "unplaced": 0}
    tic = time.perf_counter()

    def on_batch(param):
        if seen["first_step_s"] is None:
            seen["first_step_s"] = time.perf_counter() - tic
        # metric device->host reads since the last epoch boundary: the hot
        # loop must add none (the epoch-end drain is the only sanctioned one)
        seen["in_loop_d2h"] += \
            profiler.step_stats()["metric_d2h"] - seen["boundary_d2h"]
        feed = param.locals["train_data"]
        assert isinstance(feed, DevicePrefetchIter), type(feed)
        seen["unplaced"] = feed.fallback_batches
        placed = _devices_of([a.data for a in param.locals["batch"].data])
        assert placed == want, "batch on %s, module on %s" % (placed, want)

    def on_epoch(epoch, symbol, arg_params, aux_params):
        seen["epoch_end"].append(time.perf_counter() - tic)
        seen["boundary_d2h"] = profiler.step_stats()["metric_d2h"]

    profiler.reset_step_stats()
    mod.fit(it, eval_metric="acc", num_epoch=s["epochs"], optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                              "wd": 1e-4},
            batch_end_callback=on_batch, epoch_end_callback=on_epoch)
    stats = profiler.step_stats()

    step = mod._fused_step
    assert step is not None, "fused train step not active"
    assert step._metric_acc is not None, \
        "device metric was demoted to the per-step host path"
    steps = s["batches"] * s["epochs"]
    assert stats["steps"] == steps and step.num_steps == steps, \
        (stats["steps"], step.num_steps, steps)
    assert seen["in_loop_d2h"] == 0 and seen["unplaced"] == 0, seen
    assert stats["metric_syncs"] == s["epochs"], stats
    assert _devices_of(step.params) == want and \
        _devices_of(step.slots) == want, \
        "master weights on %s, asked for %s" % (_devices_of(step.params),
                                                want)

    probs = mod.get_outputs()[0].asnumpy()
    assert probs.shape == (batch, s["classes"]), probs.shape
    last = y[-batch:].astype(np.int64)
    loss = float(-np.mean(np.log(np.maximum(
        probs[np.arange(batch), last].astype(np.float64), 1e-30))))
    assert np.isfinite(probs).all() and np.isfinite(loss), loss
    after = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    assert all(np.isfinite(v).all() for v in after.values())
    changed = sum(not np.array_equal(before[k], after[k]) for k in before)
    assert changed > 0, "no weight changed"

    ends = seen["epoch_end"]
    facts = {
        "steps": steps, "loss": round(loss, 4),
        "weights_changed": "%d/%d" % (changed, len(before)),
        "weights_on": sorted(str(d) for d in want),
        "host_syncs_per_step": seen["in_loop_d2h"] / steps,
        "first_step_s": round(seen["first_step_s"], 2),
        # a compile-free epoch's wall, epoch-end parameter sync included
        "last_epoch_steps_per_s": round(
            s["batches"] / (ends[-1] - ends[-2]), 3)
        if len(ends) > 1 else None,
    }
    return mod, facts


def train(ctx, sizes):
    """ResNet training through ``Module.fit`` on one device."""
    _, facts = _fit_resnet(ctx, sizes["train"])
    return facts


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def _lm(s, seq_len, rng):
    """An ``attention_lm`` symbol and random parameters from ``rng``."""
    from mxnet_tpu.models import attention_lm

    sym = attention_lm.get_symbol(
        vocab_size=s["vocab"], seq_len=seq_len, num_layers=s["layers"],
        embed=s["embed"], heads=s["heads"], ffn_hidden=s["ffn"])
    arg_shapes, _, aux_shapes = sym.infer_shape(
        data=(1, seq_len), softmax_label=(1, seq_len))
    params = {}
    for name, shape in zip(sym.list_arguments(), arg_shapes):
        if name not in ("data", "softmax_label"):
            params[name] = rng.normal(0, 0.02, shape).astype(np.float32)
    for name, shape in zip(sym.list_auxiliary_states(), aux_shapes):
        params["aux:" + name] = np.zeros(shape, np.float32)
    return sym, params


def serve(ctx, sizes):
    """Mixed-length requests through ``DecodeServer`` over a paged int8 pool;
    logits checked against the dense f32-cache predictor on the same
    device, tokens against batched ``generate`` of the same prompts."""
    from mxnet_tpu.decode import DecodePredictor, DecodeServer

    s = sizes["serve"]
    dev = ctx.jax_device
    rng = np.random.RandomState(0)
    sym, params = _lm(s, s["seq_len"], rng)
    pred = DecodePredictor(
        sym, params, cache_len=s["cache_len"], ctx=ctx, temperature=0.0,
        paged=True, page_tokens=s["page_tokens"], kv_dtype="int8",
        prefill_chunk=s["prefill_chunk"])
    server = DecodeServer(pred, max_prefill=s["max_prefill"],
                          slots=s["slots"], spec_k=0)
    prompts = [rng.randint(0, s["vocab"], size=(
        rng.randint(s["prompt_lo"], s["prompt_hi"] + 1),))
        for _ in range(s["requests"])]

    tic = time.perf_counter()
    rids = [server.submit(p, max_new_tokens=s["new_tokens"])
            for p in prompts]
    results = server.run()
    wall = time.perf_counter() - tic
    served = [np.asarray(results[r]) for r in rids]
    assert len(results) == s["requests"] and \
        all(t.size == s["new_tokens"] for t in served), \
        [t.size for t in served]
    assert _devices_of(pred._env) == {dev}, _devices_of(pred._env)
    pools = _devices_of(server._ps["state"].caches)
    assert pools == {dev}, "KV pools on %s, asked for %s" % (pools, dev)

    # the same programs at the same shapes: slots-wide prompt batches
    # through the dense f32-cache predictor and the paged int8 one
    dense = DecodePredictor(sym, params, cache_len=s["cache_len"], ctx=ctx,
                            temperature=0.0, kv_dtype="", paged=False)
    width, b = s["max_prefill"], s["slots"]
    identical, worst = 0, 0.0
    for lo in range(0, len(prompts) - b + 1, b):
        group = prompts[lo:lo + b]
        toks = np.zeros((b, width), np.float32)
        lens = np.array([p.size for p in group])
        for row, p in enumerate(group):
            toks[row, :p.size] = p
        if lo == 0:
            sd, pd = dense.prefill(toks, lens)
            sp, pp = pred.prefill(toks, lens)
            for got, ref in ((pp, pd), (pred.step(sp)[1],
                                        dense.step(sd)[1])):
                worst = max(worst, float(np.max(np.abs(
                    np.log(np.asarray(got, np.float64))
                    - np.log(np.asarray(ref, np.float64))))))
        gen = np.asarray(pred.generate(toks, prompt_len=lens,
                                       max_new_tokens=s["new_tokens"]))
        identical += sum(np.array_equal(gen[row], served[lo + row])
                         for row in range(b))
    assert worst <= SERVE_LOGPROB_ATOL, \
        "paged int8 vs dense f32 log-probs differ by %g" % worst
    # greedy rows are independent and both sides run the same compiled
    # programs: exact on the CPU, and on the v5e in every run so far
    compared = len(prompts) // b * b
    assert identical == compared, \
        "%d/%d served streams equal generate's" % (identical, compared)

    tc = pred.trace_counts
    assert tc["chunk"] == 1 and tc["decode"] == 1 and \
        tc["commit"] <= 1 and tc["fork"] <= 1, tc
    return {
        "requests": s["requests"], "tokens": s["requests"] * s["new_tokens"],
        "retired_at_cap": len(served), "trace_counts": {
            k: tc[k] for k in ("chunk", "decode", "commit", "fork")},
        "params_on": str(dev), "pool_bytes": pred.pool_bytes(),
        "logprob_max_abs_diff_vs_dense_f32": _sig(worst),
        "logprob_atol": SERVE_LOGPROB_ATOL,
        "token_identical_to_generate": "%d/%d" % (identical, compared),
        "first_drain_tokens_per_s_incl_compile": round(
            s["requests"] * s["new_tokens"] / wall, 2),
    }


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def kernel_flash(ctx, sizes):
    """Flash attention forward + backward vs the einsum ``sdpa``."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import attention, pallas_attention

    k = sizes["kernels"]
    f = k["flash"]
    heads, e = f["heads"], f["heads"] * f["head_dim"]
    shape = (f["batch"], f["t"], e)
    if not pallas_attention.supported(shape, shape, True, heads):
        return {"outcome": "gated", "shape": shape}
    rng = np.random.RandomState(1)
    q, kk, v, w = [jax.device_put(
        jnp.asarray(rng.normal(0, 0.5, shape), jnp.bfloat16), ctx.jax_device)
        for _ in range(4)]

    def loss(fn):
        return lambda q_, k_, v_: jnp.sum(
            (fn(q_, k_, v_) * w).astype(jnp.float32))

    def flash(q_, k_, v_):
        return pallas_attention.sdpa_flash(q_, k_, v_, heads, True, None,
                                           interpret=k["interpret"])

    def einsum(q_, k_, v_):
        return attention.sdpa(q_, k_, v_, num_heads=heads, causal=True)

    errs = {"fwd": _rel_err(jax.jit(flash)(q, kk, v),
                            jax.jit(einsum)(q, kk, v))}
    got = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, kk, v)
    ref = jax.jit(jax.grad(loss(einsum), argnums=(0, 1, 2)))(q, kk, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, ref):
        errs[name] = _rel_err(a, b)
    # bf16 in and out (2^-8 per rounding), f32 softmax on both sides
    assert max(errs.values()) <= 3e-2, errs
    return {"outcome": "compiled", "shape": shape,
            "rel_err": {n: _sig(x) for n, x in errs.items()}}


def kernel_decode(ctx, sizes):
    """The decode row's kernel over the serve phase's int8 pools (each live
    block's pages copied and multiplied inside one ``pallas_call``) vs the
    walk that gathers them, both through ``paged_attend``: the kernel is
    what its rule chooses from the shapes, the walk what it chooses on a
    backend it is shown no Pallas on."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import config
    from mxnet_tpu.ops import attention
    from mxnet_tpu.serve import PagedKVManager

    s, interp = sizes["serve"], sizes["kernels"]["interpret"]
    heads, e, pt, b = s["heads"], s["embed"], s["page_tokens"], s["slots"]
    per_slot = s["cache_len"] // pt
    pages = PagedKVManager.pool_sizing(b, s["cache_len"], pt)
    rng = np.random.RandomState(2)
    k_pool, v_pool = jax.device_put(attention.quantize_pools(
        *(jnp.asarray(rng.normal(0, 1, (pages, pt, e)), jnp.float32)
          for _ in range(2)), jnp.int8, heads), ctx.jax_device)
    q = jax.device_put(jnp.asarray(rng.normal(0, 1, (b, 1, e)), jnp.float32),
                       ctx.jax_device)
    # slot i owns pages [1 + i*per_slot, 1 + (i+1)*per_slot); page 0 is the
    # scratch page.  Lengths cover an empty-ish, a partial and a full view.
    table = jnp.asarray(1 + np.arange(b * per_slot).reshape(b, per_slot),
                        jnp.int32)
    lens = jnp.asarray(np.linspace(pt + 1, s["cache_len"], b), jnp.int32)

    def attend(kernel):
        # a fresh function per call: the path is chosen while tracing
        def fn(q_, kp, vp, tb, ln):
            return attention.paged_attend(q_, kp, vp, tb, ln,
                                          num_heads=heads)

        backend = attention._kernel_backend
        try:
            if not kernel:
                attention._kernel_backend = lambda: (False, False)
            with config.overrides(
                    MXNET_PALLAS_INTERPRET="1" if interp else "0"):
                out = jax.jit(fn)(q, k_pool, v_pool, table, lens)
        finally:
            attention._kernel_backend = backend
        return out, attention.DECODE_PATH["last"]

    got, path = attend(True)
    ref, ref_path = attend(False)
    assert ref_path == "walk", ref_path
    shape = {"q": q.shape, "pool": k_pool.data.shape, "table": table.shape}
    if path == "walk":
        return {"outcome": "gated", "shape": shape}
    assert path == "decode-kernel", path
    # the same sums in another order, float32 on both sides
    err = _rel_err(got, ref)
    assert err <= 1e-4, err
    return {"outcome": "compiled", "shape": shape, "rel_err": _sig(err)}


KERNELS = {"flash_attention": kernel_flash, "paged_decode": kernel_decode}


def kernels(ctx, sizes):
    """Each Pallas family once: compiled and within tolerance of its XLA
    reference, or visibly refused by its own ``supported()`` gate."""
    return {name: fn(ctx, sizes) for name, fn in KERNELS.items()}


# ---------------------------------------------------------------------------
# multichip
# ---------------------------------------------------------------------------

def _ring_lm_step(ctxs, s):
    """One fused ``attention_lm`` train step with the time axis sharded over
    a ``seq`` mesh axis: the in-program ring attention."""
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu.io import DataBatch, DataDesc
    from mxnet_tpu.ops.attention import PATH_TAKEN
    from mxnet_tpu.parallel import MeshConfig
    from mxnet_tpu.parallel.ring import RING_PATH

    b, t = s["batch"], s["t"]
    rng = np.random.RandomState(5)
    sym, _ = _lm(s, t, rng)
    mod = mx.mod.Module(sym, context=ctxs, compute_dtype="bfloat16",
                        mesh_config=MeshConfig(data=len(ctxs) // s["seq"],
                                               seq=s["seq"]))
    descs = [DataDesc("data", (b, t), layout="NT"),
             DataDesc("softmax_label", (b, t), layout="NT")]
    mod.bind(data_shapes=descs[:1], label_shapes=descs[1:])
    mx.random.seed(0)
    mod.init_params(mx.initializer.Xavier(rnd_type="gaussian"))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.01,
                                         "momentum": 0.9})
    assert mod._fused_step is not None, "fused train step not active"
    x = rng.randint(0, s["vocab"], size=(b, t)).astype(np.float32)
    y = np.concatenate([x[:, 1:], -np.ones((b, 1), np.float32)], axis=1)
    PATH_TAKEN["last"] = RING_PATH["last"] = None
    mod.forward_backward(DataBatch([mx.nd.array(x)], [mx.nd.array(y)],
                                   provide_data=descs[:1],
                                   provide_label=descs[1:]))
    mod.update()
    assert PATH_TAKEN["last"] == "ring", PATH_TAKEN
    probs = mod.get_outputs()[0].data              # (b*t, vocab), on device
    label = jnp.asarray(np.maximum(y, 0).reshape(-1, 1), jnp.int32)
    nll = -jnp.log(jnp.take_along_axis(probs.astype(jnp.float32), label, 1))
    loss = float(jnp.sum(nll[:, 0] * (y.reshape(-1) >= 0)) / (y >= 0).sum())
    assert np.isfinite(loss), loss
    hlo = mod._fused_step.compiled_hlo(mod._exec_group)
    assert "collective-permute" in hlo, "no collective-permute in the step"
    return {"mesh": dict(mod._exec_group._mesh.shape), "t": t,
            "attention_path": PATH_TAKEN["last"],
            "ring_hop": RING_PATH["last"], "loss": round(loss, 4),
            "collective_permutes": hlo.count(" collective-permute")}


def multichip(ctx, sizes):
    """Data-parallel ResNet over every chip, then one ring-attention LM
    step over a 'seq' mesh: the multi-chip halves of the two paths."""
    s = sizes["multichip"]
    ctxs = _as_list(ctx)
    mod, facts = _fit_resnet(ctxs, dict(sizes["train"], **s["train"]))
    group = mod._exec_group
    devices = list(group._mesh.devices.flat)
    assert len(set(devices)) == len(ctxs) == len(devices), devices
    spec = tuple(group.exec_.arg_dict["data"].data.sharding.spec)
    assert spec[0] == "data", spec
    hlo = mod._fused_step.compiled_hlo(group)
    assert "all-reduce" in hlo, "no all-reduce in the data-parallel step"
    facts.update(mesh_devices=len(set(devices)), batch_spec=list(spec),
                 all_reduces=hlo.count(" all-reduce"))
    return {"data_parallel": facts, "ring": _ring_lm_step(ctxs, s["lm"])}


# ---------------------------------------------------------------------------

def main():
    from mxnet_tpu.cache_dirs import arm_compile_cache

    cache_dir = arm_compile_cache()
    import jax
    import jaxlib

    facts = device_facts()
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    start = json.dumps(dict(
        facts, phase="start", jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=libtpu_version, compile_cache=cache_dir))
    if facts["platform"] != "tpu":
        # nothing on stdout: a run without a chip has no result
        print("%s\nchip_smoke.py needs a TPU; jax found platform=%r (%s)"
              % (start, facts["platform"], facts["device_kind"]),
              file=sys.stderr)
        return 1
    print(start, flush=True)

    import mxnet_tpu as mx

    # seconds jax spent in backend compiles (cache hits included, as the
    # time to fetch them) and how often the persistent cache answered
    spent = {"compile_s": 0.0, "cache_hits": 0}

    def on_duration(event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            spent["compile_s"] += seconds

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            spent["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    def run(name, fn, ctx):
        before, tic = dict(spent), time.perf_counter()
        result = fn(ctx, FULL)
        print(json.dumps(dict(
            facts, phase=name, **result,
            wall_s=round(time.perf_counter() - tic, 2),
            compile_s=round(spent["compile_s"] - before["compile_s"], 2),
            cache_hits=spent["cache_hits"] - before["cache_hits"]),
            default=str), flush=True)

    chip = mx.tpu()
    run("train", train, chip)
    run("serve", serve, chip)
    run("kernels", kernels, chip)
    need = FULL["multichip"]["lm"]["seq"]
    if facts["device_count"] >= need:
        run("multichip", multichip, [mx.tpu(i) for i in range(need)])
    else:
        print(json.dumps(dict(
            facts, phase="multichip",
            skipped="multichip: skipped (%d chip)" % facts["device_count"])),
            flush=True)
    print(json.dumps(dict(facts, phase="total",
                          compile_s=round(spent["compile_s"], 2),
                          cache_hits=spent["cache_hits"])), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": facts["platform"], "kind": facts["device_kind"],
        "count": facts["device_count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
