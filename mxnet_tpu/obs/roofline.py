"""Per-program roofline accounting — measured wall time vs static cost.

The telemetry subsystem's third layer (docs/observability.md): the
compiled-step dispatch wrappers (``CompiledTrainStep`` /
``CompiledEvalStep`` / ``DecodePredictor``) report host-observed wall
seconds per named program into one :class:`ProgramAccounting`, and each
program registers a LAZY static-cost prober
(:func:`mxnet_tpu.analysis.cost.program_cost`: dot FLOPs from the
lowered StableHLO, traffic bytes from arg+output avals through the
analysis width table).  :meth:`ProgramAccounting.table` joins the two
into the per-program MFU / achieved-bytes/s table ``bench.py`` publishes
in its JSON contract and ``tools/mxstat.py`` renders — the ROADMAP's
"track the roofline gap per kernel, not in aggregate".

Wall-time semantics: a program's ``wall_s`` is the host time spent
INSIDE its dispatch calls.  jax dispatch is asynchronous, so on a
backend with deep async queues this under-measures device time for a
single call — but ``fit()`` bounds in-flight steps on a fence
(``MXNET_MAX_STEPS_IN_FLIGHT``) and the decode loop reads each step's
tokens, so in the steady state the host is throttled by the device and
the accumulated dispatch wall converges to device wall.  The
interpretation caveats (and the ``host_wait`` cross-check) live in
docs/observability.md.  The probers trace+lower only (never compile,
never execute) and run at TABLE time, off every hot path.
"""
from __future__ import annotations

import logging
import threading

__all__ = ["ProgramAccounting", "PEAK_FLOPS", "peak_flops_for",
           "require_peak_flops", "auto_peak", "render_mfu_table"]

# peak bf16 FLOP/s per chip by TPU generation (public spec sheets) —
# moved here from bench.py so the bench and the MFU table share one map
PEAK_FLOPS = {
    "TPU v2": 45e12 / 2,      # per-chip: 2 cores, 22.5T each
    "TPU v3": 123e12 / 2,
    "TPU v4": 275e12,
    "TPU v5e": 197e12,
    "TPU v5 lite": 197e12,
    "TPU v5p": 459e12,
    "TPU v5": 459e12,
    "TPU v6e": 918e12,
    "TPU v6 lite": 918e12,
    "TPU7x": 2307e12,
}


def peak_flops_for(device):
    """``(peak_flops_or_None, device_kind)`` for a jax device.  The kind
    must match a table row exactly: a prefix match would hand an unknown
    "TPU v5..." string some other generation's peak."""
    kind = getattr(device, "device_kind", "")
    return PEAK_FLOPS.get(kind), kind


def require_peak_flops(device):
    """:func:`peak_flops_for` for the measuring paths (``bench.py``,
    ``chip_smoke.py``): a device the table does not name is an error,
    not an MFU column quietly left null."""
    peak, kind = peak_flops_for(device)
    if peak is None:
        raise KeyError("device_kind %r is not in obs.roofline.PEAK_FLOPS "
                       "(platform %r); add its spec-sheet peak before "
                       "measuring on it" % (kind, device.platform))
    return peak, kind


def auto_peak():
    """The MFU denominator: ``MXNET_PEAK_FLOPS`` when set, else the spec
    peak of the first jax device, else ``None`` (CPU harness — the table
    still carries flops/bytes/wall, mfu reads null)."""
    from .. import config as _config

    override = float(_config.get("MXNET_PEAK_FLOPS"))
    if override > 0:
        return override
    try:
        import jax

        peak, _ = peak_flops_for(jax.devices()[0])
        return peak
    except Exception:
        return None


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


class ProgramAccounting:
    """Measured wall seconds + lazy static costs, per program name."""

    def __init__(self):
        self._lock = threading.Lock()
        self._timing = {}   # name -> [calls, wall_s]
        self._probers = {}  # name -> () -> {"flops", "bytes"} | None
        self._static = {}   # name -> resolved {"flops", "bytes"} | error row
        # (name, id(owner)) -> _Registered: one record a registered
        # program, in the order registered (a count, not a clock)
        self._hlo = {}
        self._registered = 0
        self._conflicts_logged = set()   # module stems already warned of

    # ------------------------------------------------------------------
    def note(self, name, seconds):
        """One dispatch of ``name`` took ``seconds`` of host wall."""
        with self._lock:
            t = self._timing.get(name)
            if t is None:
                t = self._timing[name] = [0, 0.0]
            t[0] += 1
            t[1] += seconds

    def register_static(self, name, prober):
        """Attach a lazy static-cost prober (idempotent; the newest
        registration wins so a rebuilt program refreshes its cost).
        Producers register weakly-bound probers — a prober may return
        None (owner gone, or program not yet runnable) and the row then
        simply carries no static columns."""
        with self._lock:
            self._probers[name] = prober
            self._static.pop(name, None)

    def register_hlo(self, name, thunk, owner=None):
        """Attach a lazy reader of program ``name``'s optimized HLO text
        (``() -> text | None``, weakly bound like the static probers).
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

    def set_static(self, name, flops, bytes):
        """Directly record a program's static cost (mxstat --smoke, or a
        caller that already holds an artifact)."""
        with self._lock:
            self._static[name] = {"flops": int(flops), "bytes": int(bytes)}
            self._probers.pop(name, None)

    def reset(self, clear_static=False):
        """Zero the timings (a bench's measurement window starts here);
        static registrations survive unless ``clear_static``."""
        with self._lock:
            self._timing.clear()
            if clear_static:
                self._probers.clear()
                self._static.clear()
                self._hlo.clear()
                self._conflicts_logged.clear()

    # ------------------------------------------------------------------
    def _resolve_static(self, name):
        """Run (once) and cache ``name``'s prober.  A prober returning
        None (program not yet runnable) is retried next time; a raising
        prober is cached as an error so a broken lowering cannot re-pay
        its cost on every table."""
        with self._lock:
            hit = self._static.get(name)
            prober = self._probers.get(name)
        if hit is not None:
            return hit
        if prober is None:
            return None
        try:
            cost = prober()
        except Exception as exc:  # surfaced in the row, not raised
            cost = {"flops": None, "bytes": None, "error": str(exc)[:200]}
        if cost is None:
            return None
        with self._lock:
            self._static[name] = cost
            # resolved: drop the prober so it cannot pin its program's
            # owner (a model's whole parameter store) for process life
            self._probers.pop(name, None)
        return cost

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

    def table(self, peak_flops=None):
        """The joined per-program rows, sorted by wall share (largest
        first): ``{"program", "calls", "wall_s", "flops", "bytes",
        "achieved_tflops", "achieved_gbps", "mfu"}`` — flops/bytes are
        PER CALL; mfu is achieved FLOP/s over ``peak_flops`` (null
        without a peak)."""
        with self._lock:
            names = set(self._timing) | set(self._probers) \
                | set(self._static)
            timing = {n: tuple(v) for n, v in self._timing.items()}
        rows = []
        for name in names:
            calls, wall = timing.get(name, (0, 0.0))
            cost = self._resolve_static(name) or {}
            flops = cost.get("flops")
            nbytes = cost.get("bytes")
            row = {"program": name, "calls": calls,
                   "wall_s": round(wall, 6),
                   "flops": flops, "bytes": nbytes,
                   "achieved_tflops": None, "achieved_gbps": None,
                   "mfu": None}
            if cost.get("collective_bytes"):
                # programs with explicit exchanges (MoE all-to-all, ring
                # ppermute) break their wire traffic out of the floor
                row["collective_bytes"] = cost["collective_bytes"]
            if cost.get("gather_bytes"):
                # programs with materialized gather intermediates (the
                # einsum decode path's paged_gather view of the KV pool)
                # break them out too — the column the fused Pallas
                # flash-decoding kernel zeroes
                row["gather_bytes"] = cost["gather_bytes"]
            if cost.get("sort_scatter_bytes"):
                # programs with materialized sort/scatter intermediates
                # (the MoE sort-based dispatch's key sort + slot
                # scatter) — the column that prices the two
                # MXNET_MOE_DISPATCH algorithms against each other
                row["sort_scatter_bytes"] = cost["sort_scatter_bytes"]
            if cost.get("aot"):
                # programs dispatching an AOT-deserialized (or AOT-
                # compiled) executable carry their provenance — the
                # cold-start story made visible per program
                row["aot"] = cost["aot"]
            if "error" in cost:
                row["error"] = cost["error"]
            if wall > 0 and calls > 0:
                if flops:
                    rate = flops * calls / wall
                    row["achieved_tflops"] = round(rate / 1e12, 6)
                    if peak_flops:
                        row["mfu"] = round(rate / peak_flops, 6)
                if nbytes:
                    row["achieved_gbps"] = round(nbytes * calls / wall / 1e9,
                                                 6)
            rows.append(row)
        rows.sort(key=lambda r: -r["wall_s"])
        return rows


def _fmt(v, unit=""):
    if v is None:
        return "-"
    if isinstance(v, float) and unit == "":
        return "%.4g" % v
    return "%s%s" % (v, unit)


def render_mfu_table(rows):
    """Fixed-width text rendering of :meth:`ProgramAccounting.table`
    rows (the ``tools/mxstat.py`` output).  The ``collective_bytes``
    column appears only when some program carries explicit exchanges
    (MoE all-to-all, ring ppermute)."""
    cols = ("program", "calls", "wall_s", "flops", "bytes",
            "achieved_tflops", "achieved_gbps", "mfu")
    if any(r.get("collective_bytes") for r in rows):
        cols = cols + ("collective_bytes",)
    if any(r.get("gather_bytes") for r in rows):
        cols = cols + ("gather_bytes",)
    if any(r.get("sort_scatter_bytes") for r in rows):
        cols = cols + ("sort_scatter_bytes",)
    if any(r.get("aot") for r in rows):
        cols = cols + ("aot",)
    table = [[str(c) for c in cols]]
    for r in rows:
        table.append([_fmt(r.get(c)) for c in cols])
    widths = [max(len(row[i]) for row in table) for i in range(len(cols))]
    lines = []
    for i, row in enumerate(table):
        lines.append("  ".join(c.rjust(w) for c, w in zip(row, widths)))
        if i == 0:
            lines.append("  ".join("-" * w for w in widths))
    return "\n".join(lines)
