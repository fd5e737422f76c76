"""Per-shape conv forward/dgrad/wgrad throughput on the bench chip.

ROOFLINE.md names conv backward (~29 TFLOP/s on early-stage shapes) as the
floor-blocker for ResNet-50 training; round 4 never measured WHICH conv
shapes are slow or what lever moves them.  This benchmark times every
distinct ResNet-50 convolution — forward, input-gradient (dgrad), and
weight-gradient (wgrad) separately — and sweeps the cheap levers per
shape:

  * layout: NCHW vs NHWC
  * f32 accumulation vs bf16 inputs (the default)
  * channel-padded stage-1 (cin 3 -> 8) for conv0

Each op is timed inside ONE jit program that runs it K times in a
fori_loop with an iteration-dependent input perturbation (no CSE, no
per-call dispatch overhead).

Usage: python bench_conv_bwd.py [--quick]
"""
import argparse
import os
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

N = int(os.environ.get("N", "256"))

# (name, cin, cout, k, stride, hin)  — every distinct RN50 conv at bs=256
SHAPES = [
    ("conv0_7x7s2", 3, 64, 7, 2, 224),
    ("s0_1x1_64_64", 64, 64, 1, 1, 56),
    ("s0_3x3_64_64", 64, 64, 3, 1, 56),
    ("s0_1x1_64_256", 64, 256, 1, 1, 56),
    ("s0_1x1_256_64", 256, 64, 1, 1, 56),
    ("s1_3x3s2_128", 128, 128, 3, 2, 56),
    ("s1_3x3_128", 128, 128, 3, 1, 28),
    ("s1_1x1_128_512", 128, 512, 1, 1, 28),
    ("s1_1x1_512_128", 512, 128, 1, 1, 28),
    ("s1_sc_256_512s2", 256, 512, 1, 2, 56),
    ("s2_3x3s2_256", 256, 256, 3, 2, 28),
    ("s2_3x3_256", 256, 256, 3, 1, 14),
    ("s2_1x1_256_1024", 256, 1024, 1, 1, 14),
    ("s2_1x1_1024_256", 1024, 256, 1, 1, 14),
    ("s3_3x3s2_512", 512, 512, 3, 2, 14),
    ("s3_3x3_512", 512, 512, 3, 1, 7),
    ("s3_1x1_512_2048", 512, 2048, 1, 1, 7),
    ("s3_1x1_2048_512", 2048, 512, 1, 1, 7),
]


def conv_fn(layout, stride, pad):
    dn = ("NCHW", "OIHW", "NCHW") if layout == "NCHW" \
        else ("NHWC", "HWIO", "NHWC")

    def f(x, w):
        return lax.conv_general_dilated(
            x, w, (stride, stride), [(pad, pad)] * 2,
            dimension_numbers=dn)
    return f


MIN_ROTATE_BYTES = 256 << 20     # defeat VMEM residency (v5e VMEM 128MB)


def timed_loop(op, args, iters=96, base_iters=16, reps=5):
    """Per-op time of `op` inside one jit, measured DIFFERENTIALLY.

    Methodology (each piece is load-bearing on this rig):
      * The operands rotate through R copies sized past VMEM, indexed
        i % R with a dynamic slice that fuses into the consumer's read —
        otherwise XLA's memory-space assignment pins a single operand in
        VMEM for the whole loop and reports VMEM-fed throughput the real
        model never sees.
      * The first operand also gets an additive per-iteration shift: a
        scalar MULTIPLY would commute through the linear conv and hoist
        it out of the loop entirely (measured: 10000+ "TF/s").
      * The reported time is (T(iters) - T(base_iters)) / (iters - base),
        which cancels the per-call dispatch and readback constant; a
        plain T/iters is noise at these op sizes.
      * float() readback is the sync.
    """
    total = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in args)
    # no small cap: r_copies * total must EXCEED VMEM or small shapes get
    # pinned resident and report VMEM-fed throughput
    r_copies = max(2, int(np.ceil(MIN_ROTATE_BYTES / max(total, 1))))
    r_copies = min(r_copies, 64)
    big = [jnp.stack([a + jnp.asarray(k * 1e-6, a.dtype)
                      for k in range(r_copies)]) for a in args]

    def make(n_iters):
        def body(*ops):
            def step(i, acc):
                idx = lax.rem(i, r_copies)
                sel = [lax.dynamic_index_in_dim(o, idx, 0, keepdims=False)
                       for o in ops]
                x0 = sel[0] + (1e-6 * i.astype(jnp.float32)) \
                    .astype(sel[0].dtype)
                out = op(x0, *sel[1:])
                return acc + out.astype(jnp.float32).sum()
            return lax.fori_loop(0, n_iters, step, jnp.float32(0.0))
        return jax.jit(body)

    f_hi, f_lo = make(iters), make(base_iters)
    float(f_hi(*big))
    float(f_lo(*big))
    # MEDIAN of the differentials: host jitter makes a single difference
    # occasionally negative; min-of-n biases toward those outliers, the
    # median doesn't
    diffs = []
    for _ in range(reps):
        t0 = time.perf_counter()
        float(f_lo(*big))
        t_lo = time.perf_counter() - t0
        t0 = time.perf_counter()
        float(f_hi(*big))
        t_hi = time.perf_counter() - t0
        diffs.append((t_hi - t_lo) / (iters - base_iters))
    return max(float(np.median(diffs)), 1e-9)


def flops_of(cin, cout, k, stride, hin):
    hout = (hin + 2 * (k // 2) - k) // stride + 1
    return 2.0 * N * cout * cin * k * k * hout * hout


def bench_shape(name, cin, cout, k, stride, hin, layout="NCHW",
                dtype=jnp.bfloat16):
    rng = np.random.RandomState(0)
    pad = k // 2
    hout = (hin + 2 * pad - k) // stride + 1
    if layout == "NCHW":
        x = jnp.asarray(rng.rand(N, cin, hin, hin), dtype)
        w = jnp.asarray(rng.rand(cout, cin, k, k), dtype)
        dy_shape = (N, cout, hout, hout)
    else:
        x = jnp.asarray(rng.rand(N, hin, hin, cin), dtype)
        w = jnp.asarray(rng.rand(k, k, cin, cout), dtype)
        dy_shape = (N, hout, hout, cout)
    dy = jnp.asarray(rng.rand(*dy_shape), dtype)
    f = conv_fn(layout, stride, pad)
    fl = flops_of(cin, cout, k, stride, hin)

    t_fwd = timed_loop(lambda x_, w_: f(x_, w_), (x, w))

    def dgrad(dy_, x_, w_):
        _, vjp = jax.vjp(lambda xx: f(xx, w_), x_)
        return vjp(dy_)[0]

    def wgrad(dy_, x_, w_):
        _, vjp = jax.vjp(lambda ww: f(x_, ww), w_)
        return vjp(dy_)[0]

    t_dg = timed_loop(dgrad, (dy, x, w))
    t_wg = timed_loop(wgrad, (dy, x, w))
    return fl, t_fwd, t_dg, t_wg


# the measured floor-blockers (NCHW table, round 5): early-stage shapes
# whose small channel counts underfill the 128x128 MXU
WORST = ["conv0_7x7s2", "s0_1x1_64_64", "s0_3x3_64_64", "s0_1x1_64_256",
         "s0_1x1_256_64", "s1_sc_256_512s2"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="only the 4 heaviest shapes")
    ap.add_argument("--worst", action="store_true",
                    help="only the measured floor-blocker shapes")
    ap.add_argument("--pad-conv0", action="store_true",
                    help="also bench conv0 with cin padded 3 -> 8 "
                         "(TF/s reported on the PADDED flops; compare "
                         "the ms columns against conv0_7x7s2)")
    ap.add_argument("--layout", default="NCHW", choices=["NCHW", "NHWC"])
    args = ap.parse_args()

    shapes = SHAPES
    if args.quick:
        shapes = [s for s in SHAPES if s[0] in
                  ("conv0_7x7s2", "s0_3x3_64_64", "s0_1x1_256_64",
                   "s1_3x3_128")]
    if args.worst:
        shapes = [s for s in SHAPES if s[0] in WORST]
    if args.pad_conv0:
        shapes = list(shapes) + [("conv0_pad8", 8, 64, 7, 2, 224),
                                 ("conv0_pad4", 4, 64, 7, 2, 224)]

    print("%-18s %7s | %7s %6s | %7s %6s | %7s %6s   (%s, bf16)"
          % ("shape", "GFLOP", "fwd ms", "TF/s", "dgrad", "TF/s",
             "wgrad", "TF/s", args.layout), flush=True)
    tot = {"fwd": 0.0, "dg": 0.0, "wg": 0.0}
    for name, cin, cout, k, s, hin in shapes:
        fl, tf, td, tw = bench_shape(name, cin, cout, k, s, hin,
                                     layout=args.layout)
        print("%-18s %7.1f | %7.3f %6.1f | %7.3f %6.1f | %7.3f %6.1f"
              % (name, fl / 1e9, tf * 1e3, fl / tf / 1e12,
                 td * 1e3, fl / td / 1e12, tw * 1e3, fl / tw / 1e12),
              flush=True)
        tot["fwd"] += tf
        tot["dg"] += td
        tot["wg"] += tw
    print("unique-shape totals (x1 each): fwd %.2f ms, dgrad %.2f ms, "
          "wgrad %.2f ms" % (tot["fwd"] * 1e3, tot["dg"] * 1e3,
                             tot["wg"] * 1e3), flush=True)


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from mxnet_tpu.cache_dirs import arm_compile_cache

    arm_compile_cache()
    main()
