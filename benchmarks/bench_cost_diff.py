"""XLA cost analysis of the framework's fused train step.

(The raw-JAX side of the comparison is `COST=1 rn50_raw.py`.)"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def framework_cost():
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.io import DataBatch
    from mxnet_tpu import ndarray as nd
    from mxnet_tpu.models import resnet

    net = resnet.get_symbol(num_classes=1000, num_layers=50,
                            image_shape=(3, 224, 224))
    mod = mx.mod.Module(net, context=mx.tpu(), compute_dtype="bfloat16")
    mod.bind(data_shapes=[("data", (256, 3, 224, 224))],
             label_shapes=[("softmax_label", (256,))])
    mod.init_params(mx.initializer.Xavier(rnd_type="gaussian",
                                          factor_type="in", magnitude=2))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9, "wd": 1e-4})
    ctx = mx.tpu()
    rng = np.random.RandomState(0)
    x = nd.array(rng.uniform(-1, 1, (256, 3, 224, 224)).astype(np.float32),
                 ctx=ctx)
    y = nd.array(rng.randint(0, 1000, (256,)).astype(np.float32), ctx=ctx)
    mod.forward_backward(DataBatch([x], [y]))
    mod.update()
    step = mod._fused_step
    fn = step._fn
    # reconstruct avals for lowering
    def aval(v):
        return jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=v.sharding)
    params = {n: aval(v) for n, v in step.params.items()}
    slots = {n: tuple(aval(s) for s in v) for n, v in step.slots.items()}
    aux = {n: aval(v) for n, v in step.aux.items()}
    data = {"data": aval(x.data), "softmax_label": aval(y.data)}
    hyper = step._hyper_cache[5]
    lrs, wds, rescale, clip, extra = hyper
    from mxnet_tpu import random as _rnd
    rngk = _rnd.split_key()
    lowered = fn.lower(params, slots, aux, data, aval(lrs), aval(wds),
                       rescale, clip, aval(extra), aval(rngk))
    return lowered.compile().cost_analysis()


def show(tag, ca):
    if isinstance(ca, list):
        ca = ca[0]
    keys = ["flops", "bytes accessed", "transcendentals",
            "bytes accessed output", "optimal_seconds"]
    print(tag, {k: ca.get(k) for k in keys if k in ca}, flush=True)
    # biggest categories
    big = sorted((kv for kv in ca.items() if isinstance(kv[1], float)),
                 key=lambda kv: -kv[1])[:8]
    for k, v in big:
        print("   %-28s %.3e" % (k, v), flush=True)


if __name__ == "__main__":
    from mxnet_tpu.cache_dirs import arm_compile_cache

    arm_compile_cache()
    show("framework", framework_cost())
