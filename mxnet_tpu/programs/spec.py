"""ProgramSpec — one compiled program's registration record.

Every compiled program the framework runs (the fused train step, the
eval step, the decode/verify/chunk serving programs, the page
migration pair) used to hand-thread the same plumbing three separate
times: an aval snapshot for probes, a ``_probing`` guard so probe
traces don't count as retraces, a donated-leaf count for the donation
pass and mesh/dtype metadata for the artifact.  A :class:`ProgramSpec`
is that plumbing written ONCE: the call site registers (name, jitted fn,
abstract args, donation map, partition rules, trace counters) and gets

* :meth:`artifact`  — the :class:`~mxnet_tpu.analysis.artifact.
  ProgramArtifact` probe (jaxpr + StableHLO + compiled HLO + metadata),
  donated leaves COMPUTED from ``donate_argnums`` over the actual args
  instead of hand-counted;
* :meth:`lowered` / :meth:`compiled` — the raw AOT pipeline stages;
* :meth:`fingerprint` — the content address of the compiled program:
  a digest over (name, abstract args, donation map, jax version,
  backend, mesh shape, caller extras) that keys the on-disk AOT cache
  (``mxnet_tpu.programs.aot``) and lets two hosts PROVE they run
  byte-identical programs by comparing keys.

The probing helpers at module level (:func:`probing`,
:func:`probe_artifact`, :func:`probe_lowered_text`) are the ONE copy of
the ``owner._probing`` guard dance that
``CompiledTrainStep``/``CompiledEvalStep``/``DecodePredictor`` each
used to hand-roll around every artifact/HLO probe.
"""
from __future__ import annotations

import contextlib
import hashlib
import json
import weakref

__all__ = ["ProgramSpec", "probing", "probe_artifact",
           "probe_lowered_text"]


@contextlib.contextmanager
def probing(owner):
    """Flag ``owner`` as mid-probe so its python-level trace counters
    skip the probe's (re)trace — the retrace auditors stay honest.
    ``owner=None`` is a no-op scope (free functions, registry-only
    specs)."""
    if owner is None:
        yield
        return
    owner._probing = True
    try:
        yield
    finally:
        owner._probing = False


def probe_artifact(owner, fn, args, name, refine=None, **kw):
    """Build a :class:`~mxnet_tpu.analysis.artifact.ProgramArtifact`
    from a jitted fn under the probing guard — the registry helper the
    five per-class probing blocks collapsed into.  ``refine`` is an
    optional post-hook on the artifact (decode's pallas-promise
    withdrawal)."""
    from ..analysis.artifact import artifact_from_jit

    with probing(owner):
        art = artifact_from_jit(fn, args, name=name, **kw)
    return refine(art) if refine is not None else art


def probe_lowered_text(owner, fn, args):
    """Lowered (pre-optimization) StableHLO text under the probing
    guard — the FLOP-assertion probe body."""
    with probing(owner):
        return fn.lower(*args).as_text()


def _resolve(v):
    return v() if callable(v) else v


def _leaf_sig(leaf):
    """(shape, dtype, sharding) signature of one abstract-arg leaf."""
    sharding = getattr(leaf, "sharding", None)
    return [list(getattr(leaf, "shape", ()) or ()),
            str(getattr(leaf, "dtype", None)),
            str(sharding.spec) if hasattr(sharding, "spec")
            else (str(sharding) if sharding is not None else None)]


class ProgramSpec:
    """One registered compiled program.

    Parameters
    ----------
    name : str
        The program's registry/telemetry name (``train_step``,
        ``decode_step``, ...).
    fn : jitted callable
        The ``jax.jit``-wrapped program (an
        :class:`~mxnet_tpu.programs.aot.AotDispatch` facade works too —
        probes use its ``.trace``/``.lower`` delegation).
    owner : object, optional
        The live object whose ``_probing`` flag guards probe traces;
        held weakly so a spec never pins a model's parameter store.
    abstract_args : tuple or callable, optional
        The aval pytree selecting the program's trace (a callable is
        resolved lazily — shapes often exist only after the first run —
        and may return None for "not ready yet").
    donate_argnums : tuple of int
        The jit donation map; donated-leaf counts for the donation pass
        are computed from it over the actual args.
    mesh_shape, compute_dtype, expected_traces, trace_count, meta
        Artifact metadata; values or callables.
    partition_rules : list, optional
        The regex partition rules the program's named param tree was
        placed by (``programs.partition``) — recorded for docs/probes
        and folded into the fingerprint.
    fingerprint_extra : dict or callable, optional
        Caller-identity payload for the AOT cache key (e.g. the symbol
        graph digest + decode knobs) — everything that changes the
        traced program but not the aval signature.
    """

    def __init__(self, name, fn, *, owner=None, abstract_args=None,
                 donate_argnums=(), mesh_shape=None, compute_dtype=None,
                 expected_traces=1, trace_count=None, meta=None,
                 partition_rules=None, fingerprint_extra=None):
        self.name = name
        self.fn = fn
        self._owner = weakref.ref(owner) if owner is not None else None
        self._abstract_args = abstract_args
        self.donate_argnums = tuple(donate_argnums or ())
        self._mesh_shape = mesh_shape
        self._compute_dtype = compute_dtype
        self._expected_traces = expected_traces
        self._trace_count = trace_count
        self._meta = meta
        self.partition_rules = partition_rules
        self._fingerprint_extra = fingerprint_extra

    # ------------------------------------------------------------------
    def owner(self):
        return self._owner() if self._owner is not None else None

    def avals(self, args=None):
        """The aval pytree selecting this program's trace (None when the
        spec's lazy supplier says the program is not runnable yet)."""
        return args if args is not None else _resolve(self._abstract_args)

    def donated_leaves(self, args):
        """Donated array-buffer count, computed from the donation map
        over the actual args — the hand-counted ``ndon``/``donated``
        arithmetic the per-class probes used to carry."""
        import jax.tree_util as jtu

        return sum(len(jtu.tree_leaves(args[i]))
                   for i in self.donate_argnums if i < len(args))

    # ------------------------------------------------------------------
    # probes (the uniform exposure the passes consume)
    # ------------------------------------------------------------------
    def artifact(self, args=None, name=None, refine=None, **extra_meta):
        """:class:`~mxnet_tpu.analysis.artifact.ProgramArtifact` of the
        program at ``args`` (or the spec's abstract args); None before
        the program is runnable."""
        args = self.avals(args)
        if args is None:
            return None
        meta = dict(_resolve(self._meta) or {})
        meta.update(extra_meta)
        return probe_artifact(
            self.owner(), self.fn, args, name or self.name,
            refine=refine, donated_leaves=self.donated_leaves(args),
            compute_dtype=_resolve(self._compute_dtype),
            mesh_shape=_resolve(self._mesh_shape),
            trace_count=_resolve(self._trace_count),
            expected_traces=_resolve(self._expected_traces) or 1, **meta)

    # ------------------------------------------------------------------
    # the AOT pipeline stages
    # ------------------------------------------------------------------
    def lowered(self, args=None):
        """``fn.lower(*args)`` under the probing guard."""
        args = self.avals(args)
        if args is None:
            return None
        with probing(self.owner()):
            return self.fn.lower(*args)

    def compiled(self, args=None):
        """``fn.lower(*args).compile()`` under the probing guard — the
        executable the AOT cache serializes."""
        low = self.lowered(args)
        return low.compile() if low is not None else None

    def fingerprint(self, args=None, backend=None):
        """Content address of the compiled program: digest over the
        abstract args (shapes/dtypes/shardings + tree structure), the
        donation map, the jax version, the backend, the mesh shape, the
        partition rules and the caller's identity extras.  Two specs
        with equal fingerprints compile to byte-identical programs —
        the checkable "every fleet host runs the canonical program"
        invariant, and the AOT cache key."""
        import jax
        import jax.tree_util as jtu

        args = self.avals(args)
        if args is None:
            return None
        if backend is None:
            backend = jax.default_backend()
        leaves, treedef = jtu.tree_flatten(args)
        payload = {
            "name": self.name,
            "jax": jax.__version__,
            "backend": str(backend),
            "mesh_shape": _resolve(self._mesh_shape),
            "donate": list(self.donate_argnums),
            "tree": str(treedef),
            "leaves": [_leaf_sig(x) for x in leaves],
            "rules": [[p, [str(a) for a in s]]
                      for p, s in (self.partition_rules or [])],
            "extra": _resolve(self._fingerprint_extra),
        }
        blob = json.dumps(payload, sort_keys=True, default=str)
        return hashlib.blake2b(blob.encode(), digest_size=16).hexdigest()
