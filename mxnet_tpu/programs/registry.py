"""The program registry — live specs + the canonical lint catalog.

Two namespaces, one registry object:

* **Live programs** — every :class:`~mxnet_tpu.programs.spec.
  ProgramSpec` a call site registers (``registry.register(spec)``,
  latest wins per name, weakly owned).  ``registry.trace_report()``
  folds their retrace counters into one accounting view; artifacts
  come off the specs themselves.
* **Canonical programs** — the programs ``tools/mxlint.py`` audits.
  ``analysis/programs.py`` REGISTERS builder groups here (a builder
  drives a real workload and returns ``[(name, artifact), ...]``);
  mxlint enumerates ``canonical_names()`` and calls
  ``build_canonical()`` instead of importing a hand-maintained tuple —
  adding the 13th canonical program is one ``register_canonical``
  call, not edits across three files.
"""
from __future__ import annotations

import weakref

from ..base import MXNetError

__all__ = ["ProgramRegistry", "REGISTRY", "register", "get", "names",
           "register_canonical", "canonical_names", "build_canonical",
           "trace_report"]


class ProgramRegistry:
    """Name -> :class:`ProgramSpec` (live), plus the canonical builder
    catalog the lint enumerates."""

    def __init__(self):
        self._specs = {}        # name -> weakref to ProgramSpec
        self._canonical = []    # ordered canonical names
        self._groups = {}       # name -> (group_key, builder, availability)

    # ------------------------------------------------------------------
    # live programs
    # ------------------------------------------------------------------
    def register(self, spec):
        """Register (or refresh) a live program spec; latest wins.  Held
        WEAKLY: the registering call site owns the spec (the spec in
        turn owns a jitted fn closing over real model state, which a
        process-global table must never pin); a collected owner's entry
        simply evaporates."""
        self._specs[spec.name] = weakref.ref(spec)
        return spec

    def get(self, name):
        ref = self._specs.get(name)
        spec = ref() if ref is not None else None
        if ref is not None and spec is None:
            del self._specs[name]
        return spec

    def names(self):
        return sorted(n for n in list(self._specs)
                      if self.get(n) is not None)

    def trace_report(self):
        """``{name: {"trace_count", "expected_traces"}}`` over every
        live spec whose owner is still alive — the registry-native
        retrace accounting."""
        from .spec import _resolve

        out = {}
        for name in self.names():
            spec = self.get(name)
            if spec is None or (spec._owner is not None
                                and spec.owner() is None):
                continue
            out[name] = {
                "trace_count": _resolve(spec._trace_count),
                "expected_traces": _resolve(spec._expected_traces),
            }
        return out

    # ------------------------------------------------------------------
    # canonical catalog (the mxlint surface)
    # ------------------------------------------------------------------
    def register_canonical(self, names, builder, availability=None):
        """Register a builder group producing the canonical programs
        ``names`` (in catalog order).  ``builder(want)`` receives the
        subset of its names requested and returns ``[(name, artifact),
        ...]``; ``availability()`` returns None when buildable on this
        host, else a human-readable reason (surfaced as a skip note).
        """
        key = tuple(names)
        for name in names:
            if name in self._groups:
                raise MXNetError("canonical program %r registered twice"
                                 % name)
            self._canonical.append(name)
            self._groups[name] = (key, builder, availability)

    def canonical_names(self):
        return tuple(self._canonical)

    def build_canonical(self, names=None):
        """Build the requested canonical artifacts (default: all).

        Returns ``(artifacts, notes)`` — ``notes`` maps unbuildable
        programs to the reason, so the caller surfaces the gap instead
        of silently auditing a smaller set."""
        want = list(names) if names else list(self._canonical)
        unknown = [n for n in want if n not in self._groups]
        if unknown:
            raise MXNetError("unknown canonical program(s) %s; known: %s"
                             % (unknown, list(self._canonical)))
        artifacts, notes, done = [], {}, set()
        for name in want:
            key, builder, availability = self._groups[name]
            if key in done:
                continue
            done.add(key)
            group_want = [n for n in key if n in want]
            if availability is not None:
                reason = availability()
                if reason is not None:
                    for n in group_want:
                        notes[n] = reason
                    continue
            built = dict(builder(group_want))
            missing = [n for n in group_want if n not in built]
            if missing:
                raise MXNetError("canonical builder for %s did not "
                                 "produce %s" % (list(key), missing))
            for n in group_want:
                art = built[n]
                art.name = n
                artifacts.append(art)
        order = {n: i for i, n in enumerate(self._canonical)}
        artifacts.sort(key=lambda a: order.get(a.name, len(order)))
        return artifacts, notes


REGISTRY = ProgramRegistry()

# module-level conveniences bound to the process-wide registry
register = REGISTRY.register
get = REGISTRY.get
names = REGISTRY.names
register_canonical = REGISTRY.register_canonical
canonical_names = REGISTRY.canonical_names
build_canonical = REGISTRY.build_canonical
trace_report = REGISTRY.trace_report
