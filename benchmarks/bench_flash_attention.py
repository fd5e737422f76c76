"""Flash-attention kernel vs einsum attention on the real chip.

The einsum path materializes (B*H, T, T) fp32 logits in HBM; the Pallas
kernel streams them through VMEM.  Both directions are measured — the
backward kernels (custom_vjp) make training take the flash path too,
the analog of the reference's fused-RNN-kernel-that-trains precedent
(src/operator/cudnn_rnn-inl.h implements forward *and* backward).

Timing uses a one-element host readback as the sync point (on this
machine ``block_until_ready`` fences just as well — CHANGES.md, PR 19).

    python benchmarks/bench_flash_attention.py            # sweep
    python benchmarks/bench_flash_attention.py --train8k  # LM step, T=8192
    python benchmarks/bench_flash_attention.py --crossover  # the dispatch's table

``--crossover`` is the measurement behind ``ops.attention.FLASH_MIN_T`` and
``pallas_attention``'s block constants: at 8192 tokens a step, for 32 heads
of 64 and 16 heads of 128, einsum against flash (forward + backward through
``jax.grad``), the flash kernels' forward and backward apart, and their block
sizes swept at (4, 2048), (8, 1024) and (16, 512); it ends on the table that
``pallas_attention``'s comment and ``PERF.md`` quote; rows go to
``chiprun_out/attn_crossover.json``.
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def _bench(fn, *args, n=20, trials=3):
    """min-of-trials ms/call with host-readback sync.  The scalars are on
    the device before the clock starts: made inside the loop they put a
    floor of 0.9 ms under every call (PR 31)."""
    import jax
    import jax.numpy as jnp

    cs = [jnp.float32(i) for i in range(n)]
    np.asarray(jax.tree.leaves(fn(jnp.float32(1.0), *args))[0][(0,) * 2])
    times = []
    for _ in range(trials):
        t0 = time.perf_counter()
        for c in cs:
            out = fn(c, *args)
        np.asarray(jax.tree.leaves(out)[0][(0,) * 2])
        times.append((time.perf_counter() - t0) / n * 1e3)
    return min(times)


def sweep():
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_attention as pa
    from mxnet_tpu.ops.attention import sdpa

    on_tpu = jax.default_backend() == "tpu"
    print("backend:", jax.default_backend())
    b, heads, d = 4, 8, 128
    e = heads * d
    interp = not on_tpu

    for t in (1024, 2048, 4096, 8192):
        rng = np.random.RandomState(0)
        q, k, v = [jnp.asarray(rng.normal(size=(b, t, e)), jnp.bfloat16)
                   for _ in range(3)]

        def eloss(c, q_, k_, v_):
            o = sdpa(q_ * c, k_, v_, num_heads=heads, causal=True)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        def floss(c, q_, k_, v_):
            o = pa.sdpa_flash(q_ * c, k_, v_, num_heads=heads, causal=True,
                              scale=None, interpret=interp)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        ein_f = jax.jit(lambda c, q_, k_, v_: sdpa(
            q_ * c, k_, v_, num_heads=heads, causal=True))
        fla_f = jax.jit(lambda c, q_, k_, v_: pa.sdpa_flash(
            q_ * c, k_, v_, num_heads=heads, causal=True, scale=None,
            interpret=interp))
        ein_g = jax.jit(jax.grad(eloss, argnums=(1, 2, 3)))
        fla_g = jax.jit(jax.grad(floss, argnums=(1, 2, 3)))

        row = {"T": t}
        try:
            row["ein_fwd"] = _bench(ein_f, q, k, v)
            row["ein_fb"] = _bench(ein_g, q, k, v)
        except Exception as exc:       # einsum logits OOM HBM at long T
            row["oom"] = "OOM" if "memory" in str(exc).lower() else "ERROR"
        row["fla_fwd"] = _bench(fla_f, q, k, v)
        row["fla_fb"] = _bench(fla_g, q, k, v)

        if "oom" in row:
            ok = bool(jnp.isfinite(
                fla_f(jnp.float32(1), q, k, v).astype(jnp.float32)).all())
            ein_fwd = ("%7.2f ms" % row["ein_fwd"]
                       if "ein_fwd" in row else "    %s" % row["oom"])
            print("T=%5d | einsum fwd %s fwd+bwd %7s | flash fwd %7.2f ms "
                  "fwd+bwd %7.2f ms (finite=%s) | flash runs where O(T^2) "
                  "logits exceed HBM" % (t, ein_fwd, row["oom"],
                                         row["fla_fwd"], row["fla_fb"], ok),
                  flush=True)
        else:
            err = float(jnp.max(jnp.abs(
                ein_f(jnp.float32(1), q, k, v).astype(jnp.float32)
                - fla_f(jnp.float32(1), q, k, v).astype(jnp.float32))))
            print("T=%5d | fwd: einsum %7.2f flash %7.2f (%4.2fx) | "
                  "fwd+bwd: einsum %7.2f flash %7.2f (%4.2fx) | "
                  "max|diff| %.3g"
                  % (t, row["ein_fwd"], row["fla_fwd"],
                     row["ein_fwd"] / row["fla_fwd"],
                     row["ein_fb"], row["fla_fb"],
                     row["ein_fb"] / row["fla_fb"], err), flush=True)


def train8k():
    """One real LM train step at T=8192 through the framework op — the
    configuration whose (B*H, T, T) einsum logits are HBM-infeasible at
    full batch trains on the flash path."""
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import symbol as sym
    from mxnet_tpu.ops.attention import PATH_TAKEN

    b, t, e, heads = 4, 8192, 1024, 8
    data = sym.Variable("data")
    qp = sym.FullyConnected(data, num_hidden=e, flatten=False, name="q")
    kp = sym.FullyConnected(data, num_hidden=e, flatten=False, name="k")
    vp = sym.FullyConnected(data, num_hidden=e, flatten=False, name="v")
    att = sym.dot_product_attention(qp, kp, vp, num_heads=heads,
                                    causal=True)
    out = sym.FullyConnected(att, num_hidden=e, flatten=False, name="o")
    loss = sym.mean(sym.square(out))

    ctx = mx.tpu() if jax.default_backend() == "tpu" else mx.cpu()
    ex = loss.simple_bind(ctx, data=(b, t, e), grad_req="write")
    rng = np.random.RandomState(0)
    ex.arg_dict["data"]._set_data(
        rng.normal(size=(b, t, e)).astype(np.float32) * 0.02)
    for name, arr in ex.arg_dict.items():
        if name.endswith("weight"):
            arr._set_data(rng.normal(
                size=arr.shape).astype(np.float32) * (1.0 / np.sqrt(e)))

    t0 = time.perf_counter()
    ex.forward(is_train=True)
    ex.backward()
    g = ex.grad_dict["q_weight"].asnumpy()
    dt = time.perf_counter() - t0
    assert PATH_TAKEN["last"] == "flash", PATH_TAKEN
    assert np.isfinite(g).all() and np.abs(g).max() > 0
    print("LM train step @ T=8192 (b=%d, e=%d, %d heads): fwd+bwd ran on "
          "the flash path, first step (incl. compile) %.1f s, grads "
          "finite" % (b, e, heads, dt))

    t0 = time.perf_counter()
    ex.forward(is_train=True)
    ex.backward()
    ex.grad_dict["q_weight"].asnumpy()
    print("steady-state step: %.1f ms" % ((time.perf_counter() - t0) * 1e3))


def crossover():
    """``sdpa`` against ``sdpa_flash`` forward + backward (``jax.grad`` of a
    sum, jitted, bf16, causal) at 8192 tokens a step, the kernels' forward
    and backward apart, and the block sweep; the table the dispatch rule's
    thresholds and the kernels' block constants are read from."""
    import json

    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_attention as pa
    from mxnet_tpu.ops.attention import sdpa

    on_tpu = jax.default_backend() == "tpu"
    interp = not on_tpu
    dev = jax.devices()[0]
    shapes = [(32, 256), (16, 512), (8, 1024), (4, 2048)]
    widths = [(32, 64), (16, 128)]
    fwd_blocks = [(512, 512), (512, 1024), (1024, 512), (1024, 1024),
                  (256, 1024)]
    bwd_blocks = [(256, 512), (512, 512), (512, 1024), (1024, 512),
                  (256, 1024)]
    if interp:                          # CPU rehearsal: control flow only
        shapes, widths = [(2, 128)], [(2, 64)]
        fwd_blocks = bwd_blocks = [(128, 128)]
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "table": [], "sweep_fwd": [], "sweep_bwd": []}

    def qkv(b, t, e):
        rng = np.random.RandomState(0)
        return [jnp.asarray(rng.normal(size=(b, t, e)), jnp.bfloat16)
                for _ in range(3)]

    for heads, hd in widths:
        scale = 1.0 / float(np.sqrt(hd))
        for b, t in shapes:
            q, k, v = qkv(b, t, heads * hd)

            def grad_of(attend):
                def loss(c, q_, k_, v_):
                    return jnp.sum(attend(q_ * c, k_, v_)
                                   .astype(jnp.float32))
                return jax.jit(jax.grad(loss, argnums=(1, 2, 3)))

            g_ein = grad_of(lambda q_, k_, v_: sdpa(
                q_, k_, v_, num_heads=heads, causal=True))
            g_fla = grad_of(lambda q_, k_, v_: pa.sdpa_flash(
                q_, k_, v_, num_heads=heads, causal=True, scale=None,
                interpret=interp))
            row = {"heads": heads, "head_dim": hd, "b": b, "t": t,
                   "einsum_ms": _bench(g_ein, q, k, v),
                   "flash_ms": _bench(g_fla, q, k, v)}
            row["flash_speedup"] = row["einsum_ms"] / row["flash_ms"]
            ge = g_ein(jnp.float32(1), q, k, v)
            gf = g_fla(jnp.float32(1), q, k, v)
            row["grad_rel_err"] = max(
                float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                      - b_.astype(jnp.float32)))
                      / jnp.max(jnp.abs(a.astype(jnp.float32))))
                for a, b_ in zip(ge, gf))

            # the kernels alone, on head-folded operands: forward (with the
            # (BH, T) logsumexp residual, as the vjp runs it) and backward apart,
            # at the blocks the module resolves, then at the swept ones
            qf, kf, vf = [x.reshape(b, t, heads, hd).transpose(0, 2, 1, 3)
                          .reshape(b * heads, t, hd) for x in (q, k, v)]

            def fwd_at(bq=None, bk=None):
                return jax.jit(lambda c, q_, k_, v_: pa._fwd_rows(
                    q_, k_, v_, scale, True, interp, with_lse=True,
                    block_q=bq, block_k=bk))

            o, lse = fwd_at()(None, qf, kf, vf)

            def bwd_at(bq=None, bk=None):
                return jax.jit(lambda c, q_, k_, v_: pa._bwd_call(
                    q_, k_, v_, o, lse, o, scale, True, interp,
                    block_q=bq, block_k=bk))

            row["flash_fwd_ms"] = _bench(fwd_at(), qf, kf, vf)
            row["flash_bwd_ms"] = _bench(bwd_at(), qf, kf, vf)
            out["table"].append(row)
            print(json.dumps(row), flush=True)
            if t < 512 and not interp:
                continue
            for key, at, blocks in (("sweep_fwd", fwd_at, fwd_blocks),
                                    ("sweep_bwd", bwd_at, bwd_blocks)):
                for bq, bk in blocks:
                    if bq > t or bk > t:
                        continue
                    srow = {"head_dim": hd, "b": b, "t": t, "block_q": bq,
                            "block_k": bk}
                    try:
                        srow["ms"] = _bench(at(bq, bk), qf, kf, vf)
                    except Exception as exc:  # VMEM overflow and the like
                        srow["error"] = str(exc).splitlines()[0][:200]
                    out[key].append(srow)
                    print(key, json.dumps(srow), flush=True)

    print("kernels alone, forward / backward ms at (B, T), blocks as "
          "resolved (fwd %s, bwd %s):" % (
              (pa.BLOCK_Q, pa.BLOCK_K), (pa.BLOCK_Q_BWD, pa.BLOCK_K_BWD)))
    for heads, hd in widths:
        print("  %2d heads of %3d: %s" % (heads, hd, "   ".join(
            "(%d, %d) %.3f / %.3f" % (r["b"], r["t"], r["flash_fwd_ms"],
                                      r["flash_bwd_ms"])
            for r in out["table"] if r["head_dim"] == hd)))
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/attn_crossover.json", "w") as f:
        json.dump(out, f, indent=1)


def ring_row():
    """Ring attention per-hop compute: flash kernel vs jnp streaming.

    The multi-hop ring schedule runs IDENTICAL ppermutes under both
    paths; what differs is each hop's block compute.  A seq-mesh of size
    1 on the real chip isolates exactly that (one hop, T_local = T,
    causal diagonal case — the fullest per-hop compute), timed fwd+bwd
    through the actual `ring_attention` dispatch including the flash
    path's custom-vjp backward ring.
    """
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel.compat import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from mxnet_tpu.parallel.ring import ring_attention

    on_tpu = jax.default_backend() == "tpu"
    print("backend:", jax.default_backend(),
          "(per-hop compute at T_local; multi-hop adds identical "
          "ppermutes to both paths)")
    b, heads, hd = 4, 8, 128
    e = heads * hd
    mesh = Mesh(np.array(jax.devices()[:1]), ("seq",))

    for t_local in (2048, 4096, 8192):
        rng = np.random.RandomState(0)
        q, k, v = [jnp.asarray(rng.normal(size=(b, t_local, e)),
                               jnp.bfloat16) for _ in range(3)]

        def make(use_flash):
            ring = shard_map(
                lambda q_, k_, v_: ring_attention(
                    q_, k_, v_, axis_name="seq", num_heads=heads,
                    causal=True, use_flash=use_flash,
                    interpret=not on_tpu),
                mesh=mesh, in_specs=(P(None, "seq", None),) * 3,
                out_specs=P(None, "seq", None), check_vma=False)

            def loss(c, q_, k_, v_):
                o = ring(q_ * c, k_, v_)
                return jnp.sum(o.astype(jnp.float32) ** 2)

            return (jax.jit(lambda c, q_, k_, v_: ring(q_ * c, k_, v_)),
                    jax.jit(jax.grad(loss, argnums=(1, 2, 3))))

        st_f, st_g = make(False)
        fl_f, fl_g = make(True)
        err = float(jnp.max(jnp.abs(
            st_f(jnp.float32(1), q, k, v).astype(jnp.float32)
            - fl_f(jnp.float32(1), q, k, v).astype(jnp.float32))))
        st_fwd = _bench(st_f, q, k, v, n=5)
        fl_fwd = _bench(fl_f, q, k, v, n=5)
        try:
            st_fb = _bench(st_g, q, k, v, n=5)
        except Exception as exc:
            # the streaming backward rematerializes the full (Tl, Tl) f32
            # block logits through autodiff — HBM-infeasible at long
            # blocks; the flash backwardkernels stream them
            st_fb = None
            oom = "OOM" if "memory" in str(exc).lower() else "ERROR"
        fl_fb = _bench(fl_g, q, k, v, n=5)
        if st_fb is None:
            print("T_local=%5d | fwd: streaming %7.2f flash %7.2f (%4.2fx)"
                  " | fwd+bwd: streaming %s flash %7.2f — the kernel is "
                  "the only trainable ring path at this block size | "
                  "max|diff| %.3g"
                  % (t_local, st_fwd, fl_fwd, st_fwd / fl_fwd, oom, fl_fb,
                     err), flush=True)
        else:
            print("T_local=%5d | fwd: streaming %7.2f flash %7.2f (%4.2fx)"
                  " | fwd+bwd: streaming %7.2f flash %7.2f (%4.2fx) | "
                  "max|diff| %.3g"
                  % (t_local, st_fwd, fl_fwd, st_fwd / fl_fwd,
                     st_fb, fl_fb, st_fb / fl_fb, err), flush=True)


if __name__ == "__main__":
    from mxnet_tpu.cache_dirs import arm_compile_cache

    arm_compile_cache()
    if "--train8k" in sys.argv:
        train8k()
    elif "--ring" in sys.argv:
        ring_row()
    elif "--crossover" in sys.argv:
        crossover()
    else:
        sweep()
