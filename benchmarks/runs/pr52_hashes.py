"""PR 52: the decode and chunk programs (and exaone's verify and draft
programs) of the five OTHER serving configurations lower to the parent's
StableHLO: a hash a program at toy sizes (caches of 1024 in pages of 16, so
that the walk engages; Pallas interpreted, so that a kernel lowers where a
toy's shapes tile and the routed product's chooser is asked on a backend that
runs one), printed with the paths each program's attention took; the form
each program's gated expert layers took goes to stderr (the parent keeps no
such record).  Run it over the parent's tree and over the change's and compare
the two outputs (15 programs, every line the same; CHANGES.md, PR 52):

    TREE=$PWD/scratch/parent python benchmarks/runs/pr52_hashes.py > a
    TREE=$PWD python benchmarks/runs/pr52_hashes.py > b; diff a b

CPU only; nothing here is run by a test or by the benchmark."""
import os, sys, hashlib, importlib
TREE = os.environ["TREE"]
sys.path[:0] = [TREE, os.path.join(TREE, "tests"), os.path.join(TREE, "benchmarks"), os.path.join(TREE, "tests", "chipbench")]
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import mxnet_tpu as mx
assert mx.__file__.startswith(TREE), mx.__file__
from mxnet_tpu import config
from mxnet_tpu.decode import DecodePredictor
from mxnet_tpu.programs import spec as pspec
from chipbench import harness, manifest, weights

def programs(pred, slots, chunk, spec_k=0):
    avals = pred.serving_avals(slots, chunk_w=chunk, spec_k=spec_k)
    fns = {"decode": pred._paged_decode_impl, "chunk": pred._chunk_impl,
           "verify": getattr(pred, "_paged_verify_impl", None),
           "mtp_step": getattr(pred, "_paged_decode_mtp_impl", None),
           "mtp_chunk": getattr(pred, "_mtp_chunk_impl", None)}
    out = {}
    for kind, fn in fns.items():
        if fn is None or kind not in avals:
            continue
        try:
            with pspec.probing(pred):
                text = jax.jit(fn).lower(*avals[kind]).as_text()
        except Exception as e:
            out[kind] = "ERR " + type(e).__name__ + str(e)[:80]
            continue
        out[kind] = hashlib.sha256(text.encode()).hexdigest()[:16] + " %d" % len(text)
    print("  moe forms", getattr(pred, "_moe_forms", None), file=sys.stderr)
    return out, {k: sorted(v) for k, v in pred._decode_paths.items()}

def pred_of(sym, params, **kw):
    args = dict(cache_len=1024, ctx=mx.cpu(), temperature=0.0, paged=True, page_tokens=16, prefill_chunk=64, kv_dtype="int8")
    args.update(kw)
    return DecodePredictor(sym, {n: mx.nd.NDArray(v, mx.cpu()) for n, v in params.items()}, **args)

with config.overrides(MXNET_PALLAS_INTERPRET="1"):
    import test_pallas_decode as tpd
    print("opt-like(decoder_lm heads of 64)", *programs(tpd._predictor(), 2, 64))
    cfg = manifest.load_json(manifest.ROOT, "chipbench/configs/opt-1.3b.json")
    cfg = dict(cfg, vocab_size=96, hidden_size=256, word_embed_proj_dim=256, ffn_dim=128, num_attention_heads=4, num_hidden_layers=2, max_position_embeddings=1024)
    sym = harness.build_symbol(cfg)
    arg_shapes, _, _ = sym.infer_shape(data=(1, 1024), softmax_label=(1, 1024))
    shapes = {n: s for n, s in zip(sym.list_arguments(), arg_shapes) if n not in ("data", "softmax_label")}
    print("opt-1.3b", *programs(pred_of(sym, weights.make_params(shapes, cfg, 7, "float32")), 2, 64))
    for name, mod, over in (("mimo-v2.5", "test_decoder_lm", dict(max_position_embeddings=1024, head_dim=64, v_head_dim=64, swa_head_dim=64, swa_v_head_dim=64, num_key_value_heads=4)),
                            ("falcon-h1-34b", "test_hybrid_ssm_lm", dict(max_position_embeddings=1024)),
                            ("minicpm-sala", "test_sparse_attention", {})):
        m = importlib.import_module(mod)
        cfg = m.toy_config(**over)
        sym, params = m.build(cfg)
        kw = {} if name != "minicpm-sala" else dict(cache_len=m.CACHE, page_tokens=m.PAGE, prefill_chunk=m.CHUNK, kv_dtype="")
        print(name, *programs(pred_of(sym, params, **kw), 2, kw.get("prefill_chunk", 64)))
    import test_k_exaone as tke
    from chipbench.drivers import serve_ticks_mtp as driver
    cfg = tke.tiny_config(manifest.load_cell("exaone_serve_reason")["config"], max_position_embeddings=1024)
    sym = harness.build_symbol(cfg)
    params = driver.make_params(driver.weight_shapes(sym, cfg), cfg, 7, "float32")
    print("k-exaone-236b", *programs(pred_of(sym, params), 2, 64, spec_k=1))
