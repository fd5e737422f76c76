"""Count the forward graph's convolutions at the StableHLO level.

Sanity tool: ResNet-50 must lower to exactly 53 convolutions + 1 dot.
Run on CPU (structure only): JAX_PLATFORMS=cpu python benchmarks/hlo_convs.py
"""
import re
import sys
from collections import Counter

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.models import resnet

BATCH = 8


def main():
    ctx = mx.tpu() if jax.devices()[0].platform != "cpu" else mx.cpu()
    net = resnet.get_symbol(1000, 50, (3, 224, 224))
    mod = mx.mod.Module(net, context=ctx, compute_dtype="bfloat16")
    mod.bind(data_shapes=[("data", (BATCH, 3, 224, 224))],
             label_shapes=[("softmax_label", (BATCH,))])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd", optimizer_params={"learning_rate": 0.1})
    step = mod._fused_step
    exe = step._exec
    params = {n: (v.astype(jnp.bfloat16)
                  if jnp.issubdtype(v.dtype, jnp.floating) else v)
              for n, v in step.params.items()}
    aux = dict(step.aux)
    data = {"data": jnp.zeros((BATCH, 3, 224, 224), jnp.bfloat16),
            "softmax_label": jnp.zeros((BATCH,), jnp.float32)}
    key = jax.random.PRNGKey(0)

    def fwd_only(params, data, aux):
        env = dict(params)
        env.update(data)
        outs, _ = exe._run_graph(env, aux, key, True)
        return outs

    txt = jax.jit(fwd_only).lower(params, data, aux).as_text()
    convs = re.findall(r"stablehlo\.convolution.*", txt)
    dots = re.findall(r"stablehlo\.dot_general.*", txt)
    print("convolutions: %d  dot_generals: %d" % (len(convs), len(dots)))
    shapes = Counter()
    for line in convs:
        m = re.search(r"->\s*tensor<([^>]+)>", line)
        shapes[m.group(1) if m else "?"] += 1
    for shape, count in sorted(shapes.items()):
        print("%3d x %s" % (count, shape))


if __name__ == "__main__":
    from mxnet_tpu.cache_dirs import arm_compile_cache

    arm_compile_cache()
    main()
