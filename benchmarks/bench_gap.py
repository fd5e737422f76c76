"""Locate the framework-vs-raw step gap: bench variants on the real chip."""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def run(tag, wd=1e-4, skip_bn_data=False, batch=256, iters=12):
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu.io import DataBatch
    from mxnet_tpu import ndarray as nd
    from mxnet_tpu.models import resnet as resnet_mod
    from mxnet_tpu import symbol as sym

    if skip_bn_data:
        # rebuild without the input BatchNorm
        orig = sym.BatchNorm

        def fake_bn(data, **kw):
            if kw.get("name") == "bn_data":
                return data
            return orig(data, **kw)

        sym.BatchNorm = fake_bn  # resnet_mod.sym IS this module
    try:
        net = resnet_mod.get_symbol(num_classes=1000, num_layers=50,
                                    image_shape=(3, 224, 224))
    finally:
        if skip_bn_data:
            sym.BatchNorm = orig

    mod = mx.mod.Module(net, context=mx.tpu(), compute_dtype="bfloat16")
    mod.bind(data_shapes=[("data", (batch, 3, 224, 224))],
             label_shapes=[("softmax_label", (batch,))])
    mod.init_params(mx.initializer.Xavier(rnd_type="gaussian",
                                          factor_type="in", magnitude=2))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9, "wd": wd})
    ctx = mx.tpu()
    rng = np.random.RandomState(0)
    x = nd.array(rng.uniform(-1, 1, (batch, 3, 224, 224)).astype(np.float32),
                 ctx=ctx)
    y = nd.array(rng.randint(0, 1000, (batch,)).astype(np.float32), ctx=ctx)
    b = DataBatch([x], [y])

    def sync():
        src = next(iter(mod._fused_step.params.values()))
        return float(jnp.sum(src.astype(jnp.float32)))

    for _ in range(4):
        mod.forward_backward(b)
        mod.update()
    sync()
    t0 = time.time()
    for _ in range(iters):
        mod.forward_backward(b)
        mod.update()
    sync()
    dt = time.time() - t0
    print("%s: %.1f ms/step, %.0f img/s"
          % (tag, dt / iters * 1e3, batch * iters / dt), flush=True)


if __name__ == "__main__":
    from mxnet_tpu.cache_dirs import arm_compile_cache

    arm_compile_cache()
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    if which in ("all", "base"):
        run("baseline (wd=1e-4, bn_data)")
    if which in ("all", "nowd"):
        run("wd=0", wd=0.0)
    if which in ("all", "nobn"):
        run("no bn_data", skip_bn_data=True)
    if which in ("all", "neither"):
        run("wd=0 + no bn_data", wd=0.0, skip_bn_data=True)
