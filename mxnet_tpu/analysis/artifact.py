"""ProgramArtifact — one canonical program's static surfaces, bundled.

An artifact carries every text form the passes inspect:

* ``jaxpr_text`` — the traced jaxpr (host-callback lint);
* ``stablehlo_text`` — the lowered, pre-optimization StableHLO (FLOP and
  dtype accounting: reflects what the program *asked for*, before backend
  legalization e.g. rewrites bf16 dots to f32 on CPU);
* ``compiled_text`` — the optimized HLO of the compiled executable
  (collective budgets, donation aliasing: what actually runs);

plus the metadata the passes check against: how many donated buffers the
program was traced with, the intended compute dtype, the mesh shape, and
the retrace instrumentation counters.

:func:`artifact_from_jit` builds all three surfaces from a jitted callable
in one ``trace -> lower -> compile`` chain — the uniform exposure used by
``CompiledTrainStep.artifact`` / ``CompiledEvalStep.artifact`` /
``DecodePredictor.*_artifact`` / ``Predictor.artifact``.
"""
from __future__ import annotations

from dataclasses import dataclass, field

__all__ = ["ProgramArtifact", "artifact_from_jit", "aval_of"]


def aval_of(x):
    """``jax.ShapeDtypeStruct`` mirror of an array, sharding preserved
    when it is committed to one — the one helper behind every artifact
    probe, so the committed-vs-uncommitted handling stays in a single
    place.  An uncommitted array (a fresh ``jnp.asarray``) mirrors with
    no sharding, as the dispatch itself resolves it: lowering the
    mirrors then meets the very lowering the call made, and
    ``.compile()`` hands back the executable already loaded
    (``obs.programs.scope_map`` reads its text; nothing compiles)."""
    import jax

    sharding = getattr(x, "sharding", None) \
        if getattr(x, "committed", True) else None
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


@dataclass
class ProgramArtifact:
    """Static views + metadata of one canonical compiled program."""

    name: str
    jaxpr_text: str = None
    stablehlo_text: str = None
    compiled_text: str = None
    # donation contract: number of donated array buffers the program was
    # traced with (0 = nothing donated, the donation pass skips it)
    donated_leaves: int = 0
    # intended compute dtype of the program's hot math ("bfloat16" arms
    # the f32-upcast lint; None/"float32" disables it)
    compute_dtype: str = None
    # mesh axis sizes the program was built under, e.g. {"data": 2, ...}
    mesh_shape: dict = None
    # retrace contract: observed python-level trace count vs how many
    # distinct traces this program legitimately needs (shape variants)
    trace_count: int = None
    expected_traces: int = 1
    # content address of the compiled program when known
    # (ProgramSpec.fingerprint — the AOT cache key; equal fingerprints
    # prove two hosts run byte-identical programs)
    fingerprint: str = None
    meta: dict = field(default_factory=dict)

    def describe(self):
        return {
            "name": self.name,
            "has_jaxpr": self.jaxpr_text is not None,
            "has_stablehlo": self.stablehlo_text is not None,
            "has_compiled": self.compiled_text is not None,
            "donated_leaves": self.donated_leaves,
            "compute_dtype": self.compute_dtype,
            "mesh_shape": self.mesh_shape,
            "trace_count": self.trace_count,
            "expected_traces": self.expected_traces,
            "fingerprint": self.fingerprint,
        }


def artifact_from_jit(fn, args, name, donated_leaves=0, compute_dtype=None,
                      mesh_shape=None, trace_count=None, expected_traces=1,
                      compile_program=True, fingerprint=None, **meta):
    """Build a :class:`ProgramArtifact` from a ``jax.jit``-wrapped callable
    and the (abstract or concrete) arguments that select its trace.

    One ``fn.trace(*args)`` yields the jaxpr; its lowering yields the
    StableHLO; compiling the lowering yields the optimized HLO.  Tracing
    against ``jax.ShapeDtypeStruct`` avals keeps live buffers off the hook;
    the compile produces a throwaway executable (jit caches key on concrete
    arrays, not avals), so this is a probe, not a free read.
    """
    traced = fn.trace(*args)
    jaxpr_text = str(traced.jaxpr)
    lowered = traced.lower()
    stablehlo_text = lowered.as_text()
    compiled_text = lowered.compile().as_text() if compile_program else None
    return ProgramArtifact(
        name=name, jaxpr_text=jaxpr_text, stablehlo_text=stablehlo_text,
        compiled_text=compiled_text, donated_leaves=donated_leaves,
        compute_dtype=compute_dtype, mesh_shape=mesh_shape,
        trace_count=trace_count, expected_traces=expected_traces,
        fingerprint=fingerprint, meta=meta)
