"""Pallas block-shape autotuner + persistent tuning cache.

The kernel modules ship block constants "swept on the bench chip" —
``pallas_attention.BLOCK_Q = 1024``, ``pallas_update.BLOCK_ROWS = 16``
and friends — which are exactly wrong the day the fleet moves to the
next device generation.  This module closes the shape problem the way
AutoTVM closed it (Chen et al., 2018): each kernel module registers its
**tunable space** (the parameters, their hardcoded defaults, a
candidate enumerator and a probe runner), and the first armed process
sweeps the candidates ``benchmarks/layout_probe.py``-style — the SAME
jitted probe runs per candidate, only the block shape changes, so the
delta IS the shape — and persists the winner in a content-addressed
**tuning cache** riding the program-registry cache directory
(:func:`mxnet_tpu.programs.aot.cache_dir`).

Cache entries are small JSON sidecars keyed by
``(device generation, op, shape-class, dtype, space version)`` —
``tune_<sha256[:20]>.json`` — so a cold process resolves every
registered kernel's block shapes by reading files, with ZERO probe
executions (:data:`PROBE_COUNT` is the proof, asserted by the tier-1
subprocess round-trip in tests/test_tuning.py).  A corrupt or stale
entry warns visibly and reads as a miss; without ``MXNET_PALLAS_TUNE``
a miss resolves to the module's hardcoded defaults, which thereby
demote to mere interpret/CPU-mode fallbacks.

Shape classes bucket each dimension to its power-of-two ceiling
(:func:`shape_class_for`): block-shape winners depend on operand
magnitude, not exact row counts, and the bucketing keeps one sweep's
winner live for every batch size in its octave.
"""
from __future__ import annotations

import hashlib
import json
import logging
import os
import tempfile
import time

log = logging.getLogger(__name__)

__all__ = ["register_space", "spaces", "resolve", "shape_class_for",
           "parse_shape_class", "sweep_mode", "cache_key", "put", "get",
           "reset_memo", "PROBE_COUNT", "SpaceError"]

# schema version of the cache entries themselves (bump to invalidate the
# whole cache format); per-space staleness rides the space's own version
_FORMAT = 1

# op -> _Space; populated by the kernel modules at import
_SPACES = {}

# (op, shape_class, dtype, device) -> params resolved this process
_MEMO = {}

# timed candidate executions this process — the zero-probes-on-cache-hit
# proof counter.  A dict (not an int) so tests can reset in place.
PROBE_COUNT = {"n": 0}


class SpaceError(ValueError):
    """A runner rejecting a candidate it cannot execute (bad shape for
    the probe, VMEM overflow...).  Sweeps skip the candidate; every
    other exception propagates."""


class _Space:
    __slots__ = ("op", "version", "defaults", "constants", "candidates",
                 "runner")

    def __init__(self, op, version, defaults, constants, candidates,
                 runner):
        self.op = op
        self.version = int(version)
        self.defaults = dict(defaults)
        self.constants = tuple(constants)
        self.candidates = candidates
        self.runner = runner


def register_space(op, version, defaults, constants, candidates, runner):
    """Register a kernel module's tunable space.

    ``op``          — the cache namespace (module name, e.g.
                      ``"pallas_attention"``);
    ``version``     — bump when the space's meaning changes (param
                      renames, kernel rewrites): older cache entries
                      then read as stale;
    ``defaults``    — ``{param: value}``, the module's hardcoded
                      constants (the interpret/CPU fallback);
    ``constants``   — the module-level constant NAMES the space governs
                      (``("BLOCK_Q", ...)``), audited by the mxlint
                      tuner-coverage pass;
    ``candidates``  — ``f(shape_class, interpret) -> [ {param: value},
                      ... ]`` partial overrides of ``defaults``;
    ``runner``      — ``f(params, shape_class, dtype, interpret) ->
                      g()`` where ``g`` executes ONE timed probe of the
                      kernel under ``params`` (build/jit outside ``g``
                      so the timing sees steady-state dispatch); raise
                      :class:`SpaceError` for candidates the kernel
                      cannot run.
    """
    _SPACES[op] = _Space(op, version, defaults, constants, candidates,
                         runner)
    return _SPACES[op]


def spaces():
    """{op: space} of every registered tunable space (imports the
    kernel modules so their registrations ran)."""
    from . import pallas_attention, pallas_update  # noqa

    return dict(_SPACES)


def reset_memo():
    """Forget in-process resolutions (tests; cache files stay)."""
    _MEMO.clear()


# ---------------------------------------------------------------------------
# keys
# ---------------------------------------------------------------------------

def _pow2_ceil(v):
    v = int(v)
    if v <= 1:
        return 1
    return 1 << (v - 1).bit_length()


def shape_class_for(**dims):
    """Canonical shape-class string: each dim bucketed to its pow-2
    ceiling, sorted by name — ``shape_class_for(m=1000, k=64, n=256)``
    -> ``"k64,m1024,n256"``."""
    return ",".join("%s%d" % (k, _pow2_ceil(v))
                    for k, v in sorted(dims.items()))


def parse_shape_class(shape_class):
    """Back-parse a shape-class string into ``{dim: bucket}`` — sweep
    runners probe at the bucket sizes themselves (every shape in the
    octave shares the winner, so the ceiling is the representative)."""
    out = {}
    for part in shape_class.split(","):
        name = part.rstrip("0123456789")
        out[name] = int(part[len(name):])
    return out


def device_generation():
    """The cache's device axis: ``jax.devices()[0].device_kind``
    normalized, or ``"unknown"`` before/without a backend."""
    try:
        import jax

        return str(jax.devices()[0].device_kind).strip().replace(" ", "_")
    except Exception:
        return "unknown"


def cache_key(op, shape_class, dtype, version, device=None):
    """Content address of one tuning decision."""
    ident = json.dumps({
        "format": _FORMAT,
        "device": device or device_generation(),
        "op": op,
        "shape_class": shape_class,
        "dtype": str(dtype),
        "version": int(version),
    }, sort_keys=True)
    return "tune_" + hashlib.sha256(ident.encode()).hexdigest()[:20]


def _cache_path(key):
    from ..programs import aot

    return os.path.join(aot.cache_dir(), key + ".json")


# ---------------------------------------------------------------------------
# persistence
# ---------------------------------------------------------------------------

def put(op, shape_class, dtype, params, version=0, device=None,
        extra=None):
    """Persist one tuning decision (atomic tmp+rename, AOT-cache idiom).
    Returns the cache key; failures warn and are swallowed — the cache
    is an accelerator, never a correctness dependency."""
    from ..programs import aot

    device = device or device_generation()
    key = cache_key(op, shape_class, dtype, version, device=device)
    entry = {"format": _FORMAT, "op": op, "shape_class": shape_class,
             "dtype": str(dtype), "version": int(version),
             "device": device, "params": dict(params)}
    if extra:
        entry.update(extra)
    try:
        d = aot.cache_dir(create=True)
        fd, tmp = tempfile.mkstemp(dir=d, prefix=".tune_tmp_")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(entry, f, indent=2, sort_keys=True)
                f.write("\n")
            os.replace(tmp, os.path.join(d, key + ".json"))
        except BaseException:
            os.unlink(tmp)
            raise
    except Exception as exc:
        log.warning("tuning cache save failed for %s/%s (%s); the "
                    "winner stays in-process only", op, shape_class, exc)
    return key


def get(op, shape_class, dtype, version=0, device=None):
    """The persisted params for one key, or None on miss.  Corrupt or
    stale entries (unreadable JSON, wrong op/version, params that are
    not a dict) warn VISIBLY and read as a miss."""
    key = cache_key(op, shape_class, dtype, version, device=device)
    path = _cache_path(key)
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            entry = json.load(f)
        if not isinstance(entry, dict):
            raise ValueError("entry is not an object")
        if entry.get("op") != op or entry.get("version") != int(version) \
                or entry.get("format") != _FORMAT:
            raise ValueError("key fields do not match (stale entry)")
        params = entry.get("params")
        if not isinstance(params, dict):
            raise ValueError("params missing")
        return params
    except Exception as exc:
        log.warning("tuning cache entry %s for %s/%s is corrupt or stale "
                    "(%s); falling back to defaults", key, op,
                    shape_class, exc)
        return None


# ---------------------------------------------------------------------------
# sweep + resolve
# ---------------------------------------------------------------------------

def sweep_mode():
    """``(armed, interpret)``: sweeps run when ``MXNET_PALLAS_TUNE`` is
    set AND the backend can execute probes (TPU natively, anything else
    under ``MXNET_PALLAS_INTERPRET``) — the same gate rule as the
    kernel knobs themselves."""
    from .. import config as _config

    if not _config.get("MXNET_PALLAS_TUNE"):
        return False, False
    import jax

    if jax.default_backend() == "tpu":
        return True, False
    if _config.get("MXNET_PALLAS_INTERPRET"):
        return True, True
    return False, False


def _sweep(space, shape_class, dtype, interpret, iters=3):
    """Time every candidate; return (winner_params, results list).
    Each timed execution bumps :data:`PROBE_COUNT`."""
    results = []
    for cand in space.candidates(shape_class, interpret):
        params = dict(space.defaults)
        params.update(cand)
        try:
            probe = space.runner(params, shape_class, dtype, interpret)
            probe()                      # warmup: compile outside timing
            PROBE_COUNT["n"] += 1
            tic = time.perf_counter()
            for _ in range(iters):
                probe()
                PROBE_COUNT["n"] += 1
            dt = (time.perf_counter() - tic) / iters
        except SpaceError as exc:
            log.info("tuning %s/%s: candidate %s unsupported (%s)",
                     space.op, shape_class, cand, exc)
            continue
        results.append((dt, params))
    if not results:
        return dict(space.defaults), []
    results.sort(key=lambda r: r[0])
    return dict(results[0][1]), results


def resolve(op, shape_class, dtype):
    """The tuned parameters for ``(op, shape_class, dtype)`` on this
    device generation — the ONE lookup the kernel modules call at
    trace time.

    Resolution order: in-process memo -> persisted cache entry ->
    sweep (when :func:`sweep_mode` arms, persisting the winner) ->
    the space's registered defaults.  Always returns a full params
    dict; unknown params in a cache entry are dropped so a tampered
    entry cannot inject keys the kernels never declared."""
    space = _SPACES.get(op)
    if space is None:
        raise KeyError("no tunable space registered for %r" % op)
    dtype = str(dtype)
    device = device_generation()
    memo_key = (op, shape_class, dtype, device)
    hit = _MEMO.get(memo_key)
    if hit is not None:
        return dict(hit)

    entry = get(op, shape_class, dtype, version=space.version,
                device=device)
    if entry is not None:
        params = dict(space.defaults)
        params.update({k: v for k, v in entry.items()
                       if k in space.defaults})
        _MEMO[memo_key] = params
        return dict(params)

    armed, interpret = sweep_mode()
    if armed:
        params, results = _sweep(space, shape_class, dtype, interpret)
        put(op, shape_class, dtype, params, version=space.version,
            device=device,
            extra={"swept": [{"ms": round(dt * 1e3, 4), "params": p}
                             for dt, p in results]})
        _MEMO[memo_key] = params
        return dict(params)

    _MEMO[memo_key] = dict(space.defaults)
    return dict(space.defaults)
