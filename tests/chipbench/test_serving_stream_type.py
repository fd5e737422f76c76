"""The type a serving program's residual stream runs in, over int8 pages.

An attention of more than one row a slot over a quantized pool hands its
output back in the type of the queries it was given
(``ops.attention._out_dtype``), so a chunk program of a configuration whose
file states bfloat16 activations multiplies bfloat16 activations all the way
down: the chunk programs of each int8 family (and K-EXAONE's drafting tick,
two rows a slot), traced abstractly at a toy size with bfloat16 weights, hold
no ``dot_general`` with a float32 operand outside the scopes that state
float32 (attention's own products and running sums, the recurrent ops'
accumulations, the routed experts' weighted sum), and a carried state comes
back in the type it was allocated in.  A decode step of one row a slot keeps
the float32 output it had, and its program is the one it was.
"""
import importlib

import pytest

import jax

import mxnet_tpu as mx
from chipbench import harness, manifest, weights
from chipbench.drivers import serve_ticks

# the scopes whose sums are float32 by statement (docs/inference.md): the
# attention kinds, the lightning, delta-rule and SSM recurrences, and the
# weighted sum of the routed experts' float32 outputs
FLOAT32_SCOPES = ("mx.attn", "mx.linattn/", "mx.kda/", "mx.ssm/",
                  "mx.moe/combine", "mx.mtp/combine")
# test module that holds the family's toy, the cell it is cut from
FAMILIES = {
    "minicpm-sala": ("test_minicpm_sala", "sala_serve_longctx"),
    "falcon-h1": ("test_falcon_h1_34b", "falconh1_serve_chat"),
    "mimo-v2.5": ("test_mimo_v2_5", "mimo_serve_longshort"),
    "k-exaone": ("test_k_exaone", "exaone_serve_reason"),
    "solar-open2": ("test_solar_open2", "solar2_serve_agent"),
}


def _dots(jaxpr, found, outer=""):
    """Every ``dot_general`` and convolution of ``jaxpr`` and the programs
    inside it: (name stack, operand types)."""
    for eqn in jaxpr.eqns:
        stack = outer + "/" + str(eqn.source_info.name_stack)
        if eqn.primitive.name in ("dot_general", "conv_general_dilated"):
            found.append((stack, tuple(str(v.aval.dtype)
                                       for v in eqn.invars)))
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                inner = getattr(sub, "jaxpr", sub)
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    _dots(inner, found, stack)


def _predictor(family):
    module, cell = FAMILIES[family]
    toy = importlib.import_module(module)
    cfg = manifest.load_cell(cell)["config"]
    cfg = toy.tiny_config(cfg) if hasattr(toy, "tiny_config") \
        else dict(cfg, **toy.TINY_MIMO)
    cfg = dict(cfg, serve_dtype="bfloat16")
    traffic = dict(toy.TINY_TRAFFIC, kv_dtype="int8")
    driver = importlib.import_module(
        "chipbench.drivers." + traffic["driver"])
    if not hasattr(driver, "build_server"):
        driver = serve_ticks
    sym = harness.build_symbol(cfg)
    params = weights.make_params(driver.weight_shapes(sym, cfg), cfg, 3,
                                 "bfloat16")
    ctx = mx.cpu()
    pred, _ = driver.build_server(
        sym, traffic, {n: mx.nd.NDArray(v, ctx) for n, v in params.items()},
        ctx)
    return pred, traffic


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_no_float32_product_outside_the_scopes_that_state_it(family):
    pred, traffic = _predictor(family)
    avals = pred.serving_avals(int(traffic["slots"]),
                               chunk_w=int(traffic["prefill_chunk"]))
    programs = {"chunk": pred._chunk_impl}
    if "mtp_step" in avals:     # a graph that drafts for itself
        programs = {"mtp_chunk": pred._mtp_chunk_impl,
                    "mtp_step": pred._paged_decode_mtp_impl}
    for name, impl in programs.items():
        closed = jax.make_jaxpr(impl)(*avals[name])
        found = []
        _dots(closed.jaxpr, found)
        assert len(found) > 8, name
        wide = [(stack, types) for stack, types in found
                if "float32" in types
                and not any(scope in stack for scope in FLOAT32_SCOPES)]
        assert not wide, (name, wide[:6])
        # every matrix of the stack multiplies bfloat16 by bfloat16
        linear = [types for stack, types in found if "mx.linear/" in stack]
        assert linear and set(linear) == {("bfloat16", "bfloat16")}, name
        # a pool, an index, a convolution tail and a recurrent state come
        # back in the type they were allocated in: the program that reads
        # them a second time is the one that was compiled
        args = avals[name]
        before = args[1] if "chunk" in name else args[1].caches
        out = jax.eval_shape(impl, *args)[0]
        after = out if "chunk" in name else out.caches
        assert [str(a.dtype) for a in jax.tree_util.tree_leaves(before)] \
            == [str(a.dtype) for a in jax.tree_util.tree_leaves(after)], name
