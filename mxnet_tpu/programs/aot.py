"""AOT-serialized executables — the fleet cold-start diet.

Every fleet host used to pay a full trace -> lower -> compile for each
of its serving programs (chunk prefill, decode step, verify, page
extract/install, commit, fork) before it could serve a single token —
the dominant cold-start cost.  This module turns program preparation
into a deserialize:

* :func:`save` serializes a compiled executable
  (``jax.experimental.serialize_executable``) into a CONTENT-ADDRESSED
  on-disk cache: ``<MXNET_PROGRAM_CACHE>/<fingerprint>.aotx`` (pickled
  ``(payload, in_tree, out_tree)``) plus a ``.json`` sidecar describing
  what the key hashes.  The fingerprint
  (:meth:`~mxnet_tpu.programs.spec.ProgramSpec.fingerprint`) covers the
  abstract args, donation map, partition rules, jax version, backend,
  mesh shape and the caller's identity extras — so a jax upgrade, a
  dtype/page-size change or a different model graph is a key MISS, not
  a wrong program.
* :func:`load` deserializes a cached executable; corrupt or
  incompatible entries log a VISIBLE warning and fall back to the JIT
  path (a cold start is slower, never wrong).
* :func:`load_or_compile` is the pipeline a call site drives per
  program: cache hit -> deserialize (milliseconds); miss -> trace +
  lower + compile now and save the result back, so the NEXT host's cold
  start is a deserialize.

:class:`AotDispatch` is the callable facade a program owner installs in
place of its raw ``jax.jit`` handle: dispatches to the armed executable
(donation and numerics identical — it IS the same program), falls back
to the JIT path on an aval mismatch (counted, warned once), and
delegates ``.lower``/``.trace`` to the jit fn so every artifact/FLOP
probe keeps working unchanged.

Arming: ``MXNET_AOT=1`` (off by default — nothing changes for existing
paths), cache directory from ``MXNET_PROGRAM_CACHE`` (default
``<checkout>/.mxnet_programs``, see :mod:`mxnet_tpu.cache_dirs`).  ``AOT_STATS`` carries the process
counters the bench contract publishes (hits / misses / saves / errors /
fallbacks).
"""
from __future__ import annotations

import json
import logging
import os
import pickle
import tempfile

__all__ = ["AOT_STATS", "AotDispatch", "enabled", "cache_dir",
           "load", "save", "load_or_compile", "reset_stats"]

log = logging.getLogger(__name__)

# process-wide accounting (mirrored into the obs registry lazily so a
# scrape sees them; the python ints stay the bench's source of truth)
AOT_STATS = {"hits": 0, "misses": 0, "saves": 0, "errors": 0,
             "fallbacks": 0}


def reset_stats():
    for k in AOT_STATS:
        AOT_STATS[k] = 0


def _note(kind, n=1):
    AOT_STATS[kind] += n
    try:
        from .. import obs as _obs

        _obs.registry.counter(
            "mx_aot_" + kind,
            "AOT program cache %s" % kind).inc(n)
    except Exception:
        pass


def enabled():
    """Whether the AOT pipeline is armed (``MXNET_AOT``)."""
    from .. import config as _config

    return bool(_config.get("MXNET_AOT"))


def cache_dir(create=False):
    """The program-cache directory (``MXNET_PROGRAM_CACHE``, default
    ``<checkout>/.mxnet_programs``), created on demand."""
    from .. import config as _config
    from ..cache_dirs import PROGRAM_CACHE

    path = os.path.expanduser(_config.get("MXNET_PROGRAM_CACHE")
                              or PROGRAM_CACHE)
    if create:
        os.makedirs(path, exist_ok=True)
    return path


def _paths(key):
    d = cache_dir()
    return os.path.join(d, key + ".aotx"), os.path.join(d, key + ".json")


def save(key, compiled, meta=None):
    """Serialize ``compiled`` under content address ``key`` (atomic
    write: tmp + rename).  Returns True on success; serialization
    failures are warned and swallowed — the cache is an accelerator,
    never a correctness dependency."""
    from jax.experimental import serialize_executable as _se

    blob_path, meta_path = _paths(key)
    try:
        payload, in_tree, out_tree = _se.serialize(compiled)
        blob = pickle.dumps((payload, in_tree, out_tree))
        cache_dir(create=True)
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(blob_path),
                                   prefix=".aot_tmp_")
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(blob)
            os.replace(tmp, blob_path)
        except BaseException:
            os.unlink(tmp)
            raise
        with open(meta_path, "w") as f:
            json.dump(dict(meta or {}, key=key, bytes=len(blob)), f,
                      indent=2, sort_keys=True)
            f.write("\n")
        _note("saves")
        return True
    except Exception as exc:
        _note("errors")
        log.warning("AOT cache save failed for %s (%s); the program "
                    "stays JIT-compiled in this process", key, exc)
        return False


def load(key, name="program"):
    """Deserialize the executable under ``key``; None on a miss.  A
    corrupt/incompatible entry warns VISIBLY and reads as a miss (the
    caller falls back to trace+compile)."""
    from jax.experimental import serialize_executable as _se

    blob_path, _ = _paths(key)
    if not os.path.exists(blob_path):
        return None
    try:
        with open(blob_path, "rb") as f:
            payload, in_tree, out_tree = pickle.loads(f.read())
        return _se.deserialize_and_load(payload, in_tree, out_tree)
    except Exception as exc:
        _note("errors")
        log.warning("AOT cache entry %s for %r failed to load (%s); "
                    "falling back to trace+compile", key, name, exc)
        return None


def load_or_compile(spec, args, save_ok=True, warn_miss=True):
    """The per-program AOT pipeline: fingerprint -> cache hit
    (deserialize) or miss (``spec.compiled(args)`` now, saved back when
    ``save_ok``).  Returns ``(executable, source, key)`` with source in
    {"cache", "compile"}; ``(None, "jit", key)`` when compilation
    itself fails (the caller keeps the plain JIT path)."""
    key = spec.fingerprint(args)
    exe = load(key, spec.name)
    if exe is not None:
        _note("hits")
        return exe, "cache", key
    _note("misses")
    if warn_miss and os.path.isdir(cache_dir()):
        log.warning("AOT cache miss for program %r (key %s): tracing + "
                    "compiling now; the executable will be cached for "
                    "the next cold start", spec.name, key)
    try:
        compiled = spec.compiled(args)
    except Exception as exc:
        _note("errors")
        log.warning("AOT compile of %r failed (%s); keeping the JIT "
                    "dispatch path", spec.name, exc)
        return None, "jit", key
    if save_ok:
        save(key, compiled, meta={"name": spec.name})
    return compiled, "compile", key


def _trace_clean():
    """True when no jax trace is in progress — an armed executable must
    only see CONCRETE arguments; under tracing (eval_shape probes, an
    enclosing jit) the dispatch routes straight to the jit fn."""
    try:
        from jax.core import trace_state_clean
    except ImportError:
        return True
    return trace_state_clean()


class AotDispatch:
    """Callable facade over one jitted program.

    Starts as a transparent pass-through to the ``jax.jit`` fn.
    :meth:`arm` installs an AOT executable (deserialized or freshly
    compiled); calls then dispatch to it — same program, same donation,
    same numerics, zero traces.  An argument signature the armed
    executable was not compiled for falls back to the JIT path
    (counted in ``AOT_STATS['fallbacks']``, warned once per dispatch) —
    slower, never wrong.  Probe surfaces (``.lower``/``.trace``/
    ``.eval_shape``) always delegate to the jit fn so artifacts and
    FLOP text keep working unchanged.
    """

    _MAX_ARMED = 4

    def __init__(self, name, fn):
        self.name = name
        self.fn = fn
        self._armed = []        # [(executable, key)] most-recent-hit first
        self.source = "jit"     # "cache" | "compile" | "jit"
        self.key = None         # fingerprint of the primary executable
        self._warned = False

    def arm(self, executable, source, key=None):
        """Install an executable (newest first; bounded)."""
        self._armed.insert(0, (executable, key))
        del self._armed[self._MAX_ARMED:]
        self.source = source
        self.key = key

    def disarm(self):
        self._armed = []
        self.source = "jit"
        self.key = None

    @property
    def armed(self):
        return bool(self._armed)

    def executable(self):
        """The armed executable that answered last (the first armed
        before any call), or None when disarmed: what a dispatch calls,
        so ``obs.programs`` reads the program's text off it."""
        return self._armed[0][0] if self._armed else None

    def __call__(self, *args):
        if self._armed and not _trace_clean():
            return self.fn(*args)
        for i, (exe, key) in enumerate(self._armed):
            try:
                out = exe(*args)
            except TypeError:
                # aval mismatch — try the next armed signature, then JIT
                continue
            if i:
                self._armed.insert(0, self._armed.pop(i))
            return out
        if self._armed:
            _note("fallbacks")
            if not self._warned:
                self._warned = True
                log.warning(
                    "AOT-loaded program %r saw an argument signature it "
                    "was not compiled for; dispatching through JIT "
                    "(slower, traced) for such calls", self.name)
        return self.fn(*args)

    # probe delegation — artifacts/FLOP text never notice
    def lower(self, *args, **kw):
        return self.fn.lower(*args, **kw)

    def trace(self, *args, **kw):
        return self.fn.trace(*args, **kw)

    def eval_shape(self, *args, **kw):
        return self.fn.eval_shape(*args, **kw)
