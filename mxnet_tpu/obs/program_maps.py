"""The programs that ran: a lazy reader of each one's optimized HLO.

The dispatch wrappers (``CompiledTrainStep`` / ``DecodePredictor``)
register, once a program, a weakly bound reader of the text of the
executable they dispatch.  :class:`ProgramMaps` resolves a reader when a
map is asked for, off every hot path: which layer each instruction
belongs to (:meth:`~ProgramMaps.scope_maps`), what each instruction is
(:meth:`~ProgramMaps.instruction_maps`), whether the text came off the
loaded executable or a fresh compile, and on how many instruction names
two live programs of one module name disagree.  The benchmark joins a
device trace's instruction names with these maps (docs/observability.md).
"""
from __future__ import annotations

import logging
import threading

__all__ = ["ProgramMaps"]


def _backend_compiles():
    """Backend compiles this process has made (a persistent-cache read
    counts: it loads a program too), from the process's one listener."""
    from .startup import backend_compiles

    return backend_compiles()


def _what(row):
    return row["scope"], row["opcode"], row["shape"]


class _Registered:
    """One registered program: its lazy reader, then what was read."""

    __slots__ = ("name", "thunk", "seq", "owner", "module", "instructions",
                 "scopes", "source")

    def __init__(self, name, thunk, seq, owner):
        self.name, self.thunk, self.seq, self.owner = name, thunk, seq, owner
        self.module = self.instructions = self.scopes = self.source = None

    def alive(self):
        return self.owner is None or self.owner() is not None


class ProgramMaps:
    """Lazy HLO readers and what was read, per registered program."""

    def __init__(self):
        self._lock = threading.Lock()
        # (name, id(owner)) -> _Registered: one record a registered
        # program, in the order registered (a count, not a clock)
        self._hlo = {}
        self._registered = 0
        self._conflicts_logged = set()   # module stems already warned of

    def register_hlo(self, name, thunk, owner=None):
        """Attach a lazy reader of program ``name``'s optimized HLO text
        (``() -> text | None``, bound weakly to what it reads).
        ``owner`` is the object that dispatches the program: one record
        is kept for each ``(name, owner)``, so two predictors in a
        process each keep their own map, and a record whose owner has
        been collected no longer counts (held weakly; None = the
        process's).  Registering the same pair again replaces its record.
        Read only by the map readers below, off every hot path."""
        import weakref

        with self._lock:
            self._registered += 1
            self._hlo[(name, id(owner) if owner is not None else None)] = \
                _Registered(name, thunk, self._registered,
                            weakref.ref(owner) if owner is not None
                            else None)

    def reset(self, clear_static=False):
        """Forget every registered program when ``clear_static`` (a
        test's clean slate); a measurement window's reset leaves the
        registrations be."""
        if clear_static:
            with self._lock:
                self._hlo.clear()
                self._conflicts_logged.clear()

    def _resolve(self, rec):
        """Read (once) ``rec``'s program: its module's name, its
        instruction map, and whether the text came off an executable that
        was already loaded (``"dispatched"``) or a backend compile ran
        while it was read (``"relowered"``: another compile's text, whose
        ``fusion.N`` need not be the running program's).  False while the
        program cannot be read (never dispatched, owner collected)."""
        if rec.instructions is not None:
            return True
        thunk = rec.thunk
        if thunk is None:
            return False
        from .scopes import instruction_map

        before = _backend_compiles()
        try:
            text = thunk()
            module, rows = instruction_map(text) if text else (None, None)
        except Exception as exc:    # a reader of telemetry never raises
            logging.getLogger(__name__).warning(
                "no scope map for program %r: %s", rec.name, exc)
            rows = None
        if rows is None:
            return False
        with self._lock:
            rec.module, rec.instructions = module, rows
            rec.scopes = {k: v["scope"] for k, v in rows.items()}
            rec.source = "relowered" if _backend_compiles() > before \
                else "dispatched"
            rec.thunk = None            # resolved: unpin the owner
        return True

    def _readable(self, name=None):
        """The resolved records of live owners, oldest registration
        first; only program ``name``'s, if given."""
        with self._lock:
            for key in [k for k, r in self._hlo.items() if not r.alive()]:
                del self._hlo[key]
            recs = sorted((r for r in self._hlo.values()
                           if name is None or r.name == name),
                          key=lambda r: r.seq)
        return [r for r in recs if self._resolve(r)]

    def scope_map(self, name):
        """``{instruction name: "<layer>[/<sub>]"}`` for the compiled
        program ``name`` (``train_step``, ``paged_decode_step``, ...):
        the join between a device trace's instruction names and the
        ``mx.<layer>`` scopes (:mod:`~mxnet_tpu.obs.scopes`), read off the
        executable its owner dispatches (:meth:`instruction_maps` says
        whether it was).  Of several owners' programs of that name, the
        newest registered whose owner lives.  None before the program's
        first dispatch.  Computed when asked."""
        recs = self._readable(name)
        return recs[-1].scopes if recs else None

    def _by_module(self):
        """``{HLO module name: (newest live record, conflicts)}``.  Within
        a module's name instruction names are unique only for one
        executable: where two live records share it and disagree on an
        instruction (scope, opcode or shape), the newest registered is
        taken whole, and the instructions they disagree on are counted and
        logged once, not overwritten one by the other."""
        out = {}
        for rec in self._readable():
            if not rec.module:
                continue
            older = out.get(rec.module)
            if older is None:
                out[rec.module] = (rec, 0)
                continue
            differ = sum(
                1 for k, v in rec.instructions.items()
                if k in older[0].instructions and _what(v) != _what(
                    older[0].instructions[k]))
            out[rec.module] = (rec, max(older[1], differ))
            if differ and rec.module not in self._conflicts_logged:
                self._conflicts_logged.add(rec.module)
                logging.getLogger(__name__).warning(
                    "programs %r and %r are both module %r and disagree "
                    "on %d instruction name(s): device time under that "
                    "name is joined with the newer one's map",
                    older[0].name, rec.name, rec.module, differ)
        return out

    def scope_maps(self):
        """Every readable program's scope map, keyed by its HLO module's
        own name (``jit_step``, ``jit__paged_decode_impl``) — the stem a
        device trace prints on its ``XLA Modules`` line, within which
        instruction names are unique."""
        return {module: rec.scopes
                for module, (rec, _) in self._by_module().items()}

    def instruction_maps(self):
        """``{HLO module name: {"source", "conflicts", "instructions"}}``:
        for each module :meth:`scope_maps` lists, what every instruction is
        (:func:`~mxnet_tpu.obs.scopes.instruction_map`), whether the text
        was the dispatched executable's (``"dispatched"``) or another
        compile's (``"relowered"``), and on how many instruction names
        another live program of the same module name disagrees."""
        return {module: {"source": rec.source, "conflicts": conflicts,
                         "instructions": rec.instructions}
                for module, (rec, conflicts) in self._by_module().items()}
