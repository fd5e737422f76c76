"""PR 58: compile (never run) the decode programs of the two cells whose
delta layers take ``ops.pallas_delta``'s kernel, at the cells' sizes for a
DESCRIBED v5e chip, over abstract weights, here on the CPU.  Prints, a cell:
the form each delta layer's step took, how many ``delta_step`` custom calls
the optimized HLO holds, what else reads a matrix-state leaf, how often one
is copied, the leaf's layout as the compiler stores it, and the program's
argument, aliased and scratch bytes.

    JAX_PLATFORMS=cpu python benchmarks/runs/pr58_compile.py [cell ...]

Nothing here is run by a test or by the benchmark."""
import os, re, sys, time
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.getcwd())
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
import mxnet_tpu as mx
from mxnet_tpu.decode import DecodePredictor
from mxnet_tpu.programs import spec as pspec
from chipbench import harness, manifest
from chipbench.drivers import serve_ticks
from mxnet_tpu.ops import attention as _attn

# the rule that chooses the Pallas kernels asks the backend: answer for the
# chip the programs are compiled for
_attn._kernel_backend = lambda: (True, False)
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
chip = SingleDeviceSharding(topo.devices[0])
put = jax.device_put
jax.device_put = lambda x, *a, **k: x if isinstance(
    x, jax.ShapeDtypeStruct) else put(x, *a, **k)
for cell in sys.argv[1:] or ["olmoh_serve_rollouts", "solar2_serve_agent"]:
    loaded = manifest.load_cell(cell)
    cfg, traffic = loaded["config"], dict(loaded["traffic"])
    slots, chunk = int(traffic["slots"]), int(traffic["prefill_chunk"])
    sym = harness.build_symbol(cfg)
    shapes = serve_ticks.weight_shapes(sym, cfg)
    abstract = {n: mx.nd.NDArray(jax.ShapeDtypeStruct(tuple(s), jnp.bfloat16),
                                mx.cpu()) for n, s in shapes.items()}
    pred = DecodePredictor(
        sym, abstract, cache_len=int(traffic["cache_len"]), ctx=mx.cpu(),
        temperature=0.0, paged=True, page_tokens=int(traffic["page_tokens"]),
        kv_dtype=traffic["kv_dtype"], prefill_chunk=chunk)
    avals = pred.serving_avals(slots, chunk_w=chunk)
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)
    began = time.time()
    with pspec.probing(pred):
        compiled = jax.jit(pred._paged_decode_impl, donate_argnums=(1,)).lower(
            *on_chip(avals["decode"])).compile()
    mem, text = compiled.memory_analysis(), compiled.as_text()
    dims = {a.shape for a in jax.tree_util.tree_leaves(avals["decode"][1])
            if a.ndim == 4 and a.dtype == jnp.float32}
    leaf = "|".join(r"f32\[%s\]" % ",".join(map(str, d)) for d in dims)
    leaf = "(?:%s)" % leaf
    layouts = sorted(set(re.findall(r"(%s\{[^}]*\}) parameter\(" % leaf, text)))
    calls = [l for l in text.splitlines() if "custom_call_target=\"tpu_custom_call\"" in l
             and "delta_step" in l]
    readers = [l.split(" = ")[0].strip() for l in text.splitlines()
               if " = " in l and re.search(leaf, l.split(" = ", 1)[1].split("(", 1)[-1])
               and "delta_step" not in l and " tuple(" not in l]
    print("%s decode: %.0f s; arguments %.2f GB, aliased %.2f GB, scratch "
          "%.2f GB" % (cell, time.time() - began,
                       mem.argument_size_in_bytes / 1e9,
                       mem.alias_size_in_bytes / 1e9,
                       mem.temp_size_in_bytes / 1e9))
    print("   delta steps %s; attention paths %s" % (
        pred._delta_steps.get(1), sorted(pred._decode_paths.get(1, ()))))
    print("   delta_step custom calls %d; copies of a matrix state %d; other "
          "instructions that read one %s" % (
              len(calls), len(re.findall(r"= %s[^=]* copy\(" % leaf, text)),
              readers[:6]))
    print("   a matrix-state leaf as stored: %s" % layouts, flush=True)
    os.makedirs("/root/scratch", exist_ok=True)
    with open("/root/scratch/pr58_%s_decode.hlo" % cell, "w") as f:
        f.write(text)
