"""PR 59: compile (never run) nemotron3_serve_agent's decode and chunk programs,
and the reference's pass over the comparison's sequence, at the cell's size
for a DESCRIBED v5e chip, over abstract weights, here on the CPU: what the
chip's compiler says of memory before a chip-minute is spent.  Prints each
program's argument, output and scratch bytes, the form each program's routed
product took, and how often the optimized HLO copies a state leaf, a pool or
an expert stack.

    JAX_PLATFORMS=cpu python benchmarks/runs/pr59_compile.py [slots]

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
from mxnet_tpu.ops import attention as _attn, moe as _moe

# the rule that chooses the Pallas kernels asks the backend: answer for the
# chip the programs are compiled for
_attn._kernel_backend = lambda: (True, False)

loaded = manifest.load_cell("nemotron3_serve_agent")
cfg, traffic = loaded["config"], dict(loaded["traffic"])
if len(sys.argv) > 1:
    traffic["slots"] = int(sys.argv[1])
slots, chunk = int(traffic["slots"]), int(traffic["prefill_chunk"])
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
chip = SingleDeviceSharding(topo.devices[0])
sym = harness.build_symbol(cfg)
shapes = serve_ticks.weight_shapes(sym, cfg)
print("parameters %.1f M, %.2f GB" % (
    sum(int(jnp.prod(jnp.array(s))) for s in shapes.values()) / 1e6,
    2 * sum(int(jnp.prod(jnp.array(s))) for s in shapes.values()) / 1e9))
abstract = {n: mx.nd.NDArray(jax.ShapeDtypeStruct(tuple(s), jnp.bfloat16),
                            mx.cpu()) for n, s in shapes.items()}
put = jax.device_put
jax.device_put = lambda x, *a, **k: x if isinstance(
    x, jax.ShapeDtypeStruct) else put(x, *a, **k)
pred = DecodePredictor(
    sym, abstract, cache_len=int(traffic["cache_len"]), ctx=mx.cpu(),
    temperature=0.0, paged=True, page_tokens=int(traffic["page_tokens"]),
    kv_dtype=traffic["kv_dtype"], prefill_chunk=chunk)
avals = pred.serving_avals(slots, chunk_w=chunk)
on_chip = lambda tree: jax.tree_util.tree_map(
    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)
for kind, fn, donate in (("decode", pred._paged_decode_impl, (1,)),
                         ("chunk", pred._chunk_impl, (1,))):
    began = time.time()
    with pspec.probing(pred):
        compiled = jax.jit(fn, donate_argnums=donate).lower(
            *on_chip(avals[kind])).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    copies = len(re.findall(r"= f32\[%d,64,64,128\][^=]* copy\(" % slots,
                            text))
    print("%s: %.0f s; arguments %.2f GB, outputs %.2f GB, aliased %.2f GB, "
          "scratch %.2f GB; copies of a state %d; of an expert stack %d; "
          "routed product %s" % (
              kind, time.time() - began, mem.argument_size_in_bytes / 1e9,
              mem.output_size_in_bytes / 1e9, mem.alias_size_in_bytes / 1e9,
              mem.temp_size_in_bytes / 1e9, copies,
              len(re.findall(r"= bf16\[64,1856,2688\][^=]* copy\(", text)),
              _moe.MOE_PATH["last"]), flush=True)
    print("   attention paths %s; tpu_custom_call %d; copies of a pool %d"
          % (sorted(pred._decode_paths.get(1 if kind == "decode" else chunk,
                                           ())),
             text.count("tpu_custom_call"),
             len(re.findall(r"= s8\[\d+,16,256\][^=]* copy\(", text))),
          flush=True)
    with open("/root/scratch/pr59_%s.hlo" % kind, "w") as f:
        f.write(text)
began = time.time()
n = int(traffic["check_prompt"]) + int(traffic["check_decode"])
ref = serve_ticks.reference_rows(cfg, traffic)
params = {k: jax.ShapeDtypeStruct(tuple(s), jnp.bfloat16, sharding=chip)
          for k, s in shapes.items()}
compiled = ref.lower(params, jax.ShapeDtypeStruct(
    (1, n), jnp.int32, sharding=chip)).compile()
mem = compiled.memory_analysis()
print("reference over %d tokens: %.0f s; arguments %.2f GB, scratch %.2f GB"
      % (n, time.time() - began, mem.argument_size_in_bytes / 1e9,
         mem.temp_size_in_bytes / 1e9))
