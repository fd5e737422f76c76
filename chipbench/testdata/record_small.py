"""Record the small traces the tests read.  Run on the chip:

    python chipbench/testdata/record_small.py chiprun_out/small_<n>chip.xplane.pb

A few steps of one tiny jitted program (a matmul chain and, across chips, a
``psum``) inside a ``chipbench:window`` span with a ``chipbench:fit_step``
span per step — the same marks the harness writes, at a size git can carry.
"""
import shutil
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

sys.path.insert(0, ".")
from chipbench import trace  # noqa: E402


def main(out):
    devs = jax.devices()
    assert devs[0].platform == "tpu", devs
    mesh = Mesh(np.array(devs), ("data",))
    sharded = NamedSharding(mesh, P("data"))
    x = jax.device_put(jnp.ones((8 * len(devs), 1024), jnp.bfloat16), sharded)
    w = jax.device_put(jnp.ones((1024, 1024), jnp.bfloat16),
                       NamedSharding(mesh, P()))

    @jax.jit
    def step(x, w):
        for _ in range(4):
            x = jnp.tanh(x @ w)
        g = jnp.einsum("bi,bj->ij", x, x)       # all-reduced across chips
        return x, (w + 1e-3 * g).astype(w.dtype)

    x, w = step(x, w)
    jax.block_until_ready(w)
    logdir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("chipbench:window"):
        for _ in range(5):
            with jax.profiler.TraceAnnotation("chipbench:fit_step"):
                x, w = step(x, w)
                jax.block_until_ready(w)
    jax.profiler.stop_trace()
    shutil.copy(trace.find_xplane(logdir), out)
    parsed = trace.load(out)
    print("devices", sorted(parsed["devices"]), "modules",
          trace.module_names(parsed), "busy,window", trace.busy(parsed),
          "exposed", trace.exposed_collective_pct(parsed),
          "top", trace.top_ops(parsed)[:4], "gaps", trace.idle_gaps(parsed))


if __name__ == "__main__":
    main(sys.argv[1])
