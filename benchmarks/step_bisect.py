"""Bisect the fused ResNet-50 train step: where does the time go?

Times (a) forward-only, (b) forward+backward, (c) full fused step, and dumps
XLA cost_analysis flops for each to compare against the analytic 4.1 GFLOP
fwd / 12.3 GFLOP step per image.
"""
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import jax
import jax.numpy as jnp
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import ndarray as nd
from mxnet_tpu.io import DataBatch
from mxnet_tpu.models import resnet

BATCH = 256


def fence(tree):
    leaf = jax.tree_util.tree_leaves(tree)[0]
    return float(jnp.sum(leaf.astype(jnp.float32)))


def timeit(fn, *args, iters=10):
    out = fn(*args)
    fence(out)
    tic = time.time()
    for _ in range(iters):
        out = fn(*args)
    fence(out)
    return (time.time() - tic) / iters


def main():
    ctx = mx.tpu()
    net = resnet.get_symbol(1000, 50, (3, 224, 224))
    mod = mx.mod.Module(net, context=ctx, compute_dtype="bfloat16")
    mod.bind(data_shapes=[("data", (BATCH, 3, 224, 224))],
             label_shapes=[("softmax_label", (BATCH,))])
    mod.init_params(mx.initializer.Xavier(rnd_type="gaussian",
                                          factor_type="in", magnitude=2))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                                         "wd": 1e-4})
    step = mod._fused_step
    rng = np.random.RandomState(0)
    x = nd.array(rng.uniform(-1, 1, (BATCH, 3, 224, 224)).astype(np.float32),
                 ctx=ctx)
    y = nd.array(rng.randint(0, 1000, (BATCH,)).astype(np.float32), ctx=ctx)
    batch = DataBatch([x], [y])

    # --- full fused step ---
    dt = timeit(lambda: (step.run(batch), step.params)[1])
    print("full step      : %7.2f ms  %7.1f img/s" % (dt * 1e3, BATCH / dt))

    # --- pieces, built from the executor's pure functions ---
    exe = step._exec
    cdtype = jnp.bfloat16
    params = {n: (v.astype(cdtype)
                  if jnp.issubdtype(v.dtype, jnp.floating) else v)
              for n, v in step.params.items()}
    aux = dict(step.aux)
    data = {"data": x.data.astype(cdtype), "softmax_label": y.data}
    key = jax.random.PRNGKey(0)

    grad_names = step._grad_names

    def fwd_only(params, data, aux):
        env = dict(params)
        env.update(data)
        outs, new_aux = exe._run_graph(env, aux, key, True)
        return outs

    f = jax.jit(fwd_only)
    dt = timeit(f, params, data, aux)
    print("forward only   : %7.2f ms  %7.1f img/s" % (dt * 1e3, BATCH / dt))
    ca = f.lower(params, data, aux).compile().cost_analysis()
    print("  fwd flops: %.2f G (expect ~%.0f G)"
          % (ca["flops"] / 1e9, 4.1 * BATCH))

    def fwd_bwd(params, data, aux):
        def loss(gvals):
            env = dict(params)
            env.update(zip(grad_names, gvals))
            env.update(data)
            outs, new_aux = exe._run_graph(env, aux, key, True)
            return outs, [new_aux[n] for n in step._aux_names]

        gvals = [params[n] for n in grad_names]
        outs, vjp_fn, new_aux = jax.vjp(loss, gvals, has_aux=True)
        cts = [jnp.ones_like(o) for o in outs]
        (grads,) = vjp_fn(cts)
        return grads

    g = jax.jit(fwd_bwd)
    dt = timeit(g, params, data, aux)
    print("fwd+bwd        : %7.2f ms  %7.1f img/s" % (dt * 1e3, BATCH / dt))
    ca = g.lower(params, data, aux).compile().cost_analysis()
    print("  step flops: %.2f G (expect ~%.0f G)"
          % (ca["flops"] / 1e9, 12.3 * BATCH))

    cstep = step._fn.lower(step.params, step.slots, step.aux, data,
                           np.zeros(len(grad_names), np.float32),
                           np.zeros(len(grad_names), np.float32),
                           np.float32(1), np.float32(-1), key) \
        .compile().cost_analysis()
    print("full-step flops: %.2f G  bytes accessed: %s GB"
          % (cstep["flops"] / 1e9,
             round(cstep.get("bytes accessed", 0) / 1e9, 2)))


if __name__ == "__main__":
    from mxnet_tpu.cache_dirs import arm_compile_cache

    arm_compile_cache()
    main()
