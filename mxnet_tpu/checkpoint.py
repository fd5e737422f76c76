"""Sharded checkpointing — the multi-host-scale upgrade.

The reference's checkpoint is a host-gathered binary blob
(`src/ndarray/ndarray.cc:605-700`; kept here as `model.save_checkpoint`
for format parity).  That requires every parameter on one host — fine for
one chip, impossible for pod-scale models.  This module adds the TPU-era
path on orbax: each host writes only ITS shards of the mesh-sharded
params/aux/optimizer state, and restore re-shards onto the live mesh.

    from mxnet_tpu import checkpoint
    checkpoint.save_sharded(prefix_dir, step, module)
    checkpoint.load_sharded(prefix_dir, step, module)

Works on any module bound over a mesh (or a single device — then it is
simply an async, atomic checkpoint directory).

Commit protocol: a step directory counts as a checkpoint only once it is
*committed* — orbax's atomic rename has landed AND the commit marker
(:data:`COMMIT_MARKER`, written last) is present.  ``latest_step`` skips
uncommitted/torn directories — including post-rename crash debris that
carries orbax's own metadata but never reached the marker — so a crash
mid-save can never poison resume by becoming the "latest" checkpoint.
Adopt a checkpoint written by external orbax tooling with
:func:`commit_step`.  The elastic subsystem
(``mxnet_tpu.elastic``) builds its fence checkpoints on these exact
primitives and adds a sidecar with loop state (RNG chain, metric sums,
iterator cursor) for deterministic resume.
"""
from __future__ import annotations

import os

from .base import MXNetError

__all__ = ["save_sharded", "load_sharded", "latest_step", "save_state_tree",
           "commit_step", "is_committed", "COMMIT_MARKER"]

# written LAST, inside the finalized step directory; mirrors the name orbax
# itself uses on non-atomic filesystems (GCS) so external tooling recognizes it
COMMIT_MARKER = "commit_success.txt"


def _state_of(module):
    """The module's live state as a pytree of (possibly sharded) jax
    arrays: params + aux from the executor buffers (flushed if a fused
    step holds newer state), optimizer slots when present.  Requires a
    bound ``Module``."""
    module._flush_fused()   # fused master state -> executor buffers
    exe = module._exec_group.exec_
    state = {
        "params": {n: exe.arg_dict[n].data
                   for n in module._exec_group.param_names},
        "aux": {n: exe.aux_dict[n].data
                for n in module._exec_group.aux_names},
    }
    step = getattr(module, "_fused_step", None)
    if step is not None and step.slots:
        state["slots"] = {n: list(s) for n, s in step.slots.items()}
    return state


def save_state_tree(directory, step, state):
    """Write an arbitrary pytree of jax arrays as the step's orbax
    checkpoint and commit it (marker written after the atomic rename).
    The building block ``save_sharded`` and the elastic fence writer
    share; safe to call from a background writer thread."""
    import orbax.checkpoint as ocp

    path = os.path.join(os.path.abspath(directory), str(step))
    with ocp.Checkpointer(ocp.StandardCheckpointHandler()) as ckptr:
        ckptr.save(path, state, force=True)
    return path


def commit_step(path):
    """Drop the commit marker into a finalized step directory — the LAST
    write of a checkpoint.  ``latest_step`` only ever returns committed
    steps, so a crash anywhere before this leaves the previous checkpoint
    as the resume point instead of a torn directory."""
    with open(os.path.join(path, COMMIT_MARKER), "w") as f:
        f.write("committed\n")
    return path


def is_committed(directory, step):
    """Whether ``directory/step`` is a complete, committed checkpoint —
    the marker file is the ONLY accepted evidence.  Orbax writes its own
    ``_CHECKPOINT_METADATA`` inside the renamed directory, so accepting
    it would count the debris of a crash *between* the rename and the
    sidecar/marker writes as committed; checkpoints produced by external
    orbax tooling must be adopted explicitly with :func:`commit_step`."""
    path = os.path.join(os.path.abspath(directory), str(step))
    return os.path.isdir(path) and \
        os.path.exists(os.path.join(path, COMMIT_MARKER))


def save_sharded(directory, step, module):
    """Write an orbax checkpoint of the module's params/aux (+fused
    optimizer slots) at ``directory/step`` — every host writes its own
    shards; the directory commit is atomic and marker-finalized."""
    assert module.binded and module.params_initialized
    path = save_state_tree(directory, step, _state_of(module))
    return commit_step(path)


def load_sharded(directory, step, module):
    """Restore params/aux (+slots when both sides have them) in place,
    re-sharded to the module's live mesh placement.  Structure differences
    are tolerated: a training checkpoint (with optimizer slots) restores
    into an inference module, and vice versa — a slot-less checkpoint
    loaded into a training module synthesizes FRESH optimizer slots (zero
    moments) rather than keeping moments from whatever the module trained
    on before."""
    import jax
    import logging

    import orbax.checkpoint as ocp

    assert module.binded and module.params_initialized
    if step is None:
        raise MXNetError("no checkpoint step to load from %s (is the "
                         "directory empty?)" % directory)
    path = os.path.join(os.path.abspath(directory), str(step))
    if not os.path.isdir(path):
        raise MXNetError("no sharded checkpoint at %s" % path)

    template = _state_of(module)
    abstract = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        template)

    with ocp.Checkpointer(ocp.StandardCheckpointHandler()) as ckptr:
        # orbax requires the restore target to match the SAVED structure;
        # synthesize plain abstract leaves for on-disk sections the module
        # does not carry (e.g. slots into an inference module), and drop
        # module sections absent on disk (restored state leaves them as-is)
        disk_tree = ckptr.metadata(path).item_metadata.tree
        target = {}
        for key, sub in disk_tree.items():
            if key in abstract:
                target[key] = abstract[key]
            else:
                target[key] = jax.tree_util.tree_map(
                    lambda m: jax.ShapeDtypeStruct(tuple(m.shape), m.dtype),
                    sub)
        for key in abstract:
            if key not in disk_tree:
                logging.info("sharded checkpoint %s has no %r section; "
                             "leaving the module's live state", path, key)
        state = ckptr.restore(path, args=ocp.args.StandardRestore(target))

    exe = module._exec_group.exec_
    for name, val in state["params"].items():
        exe.arg_dict[name]._set_data(val)
    for name, val in state.get("aux", {}).items():
        exe.aux_dict[name]._set_data(val)
    fused = getattr(module, "_fused_step", None)
    if fused is not None:
        # master store must adopt the restored executor buffers
        fused.load_from_executor()
        module._step_stale = False
        if "slots" in state and fused.slots:
            fused.slots = {n: tuple(s) for n, s in state["slots"].items()}
            # restored slots are now the live optimizer state — a later
            # fused step must not re-import stale eager updater moments
            module._opt_owner = "fused"
        elif fused.slots:
            # slot-less (inference) checkpoint into a training module: the
            # restored params deserve FRESH moments, not the moments of the
            # weights they just replaced; owning them as "fused" keeps a
            # stale eager updater from re-importing the old ones either
            fused.reset_slots()
            module._opt_owner = "fused"
    module._params_dirty = True
    return module


def latest_step(directory):
    """Highest COMMITTED step number checkpointed under ``directory`` (or
    None).  Torn directories — a crash mid-save, an in-flight async write,
    an orbax tmp dir — are skipped, never returned as the resume point."""
    if not os.path.isdir(directory):
        return None
    steps = [int(d) for d in os.listdir(directory)
             if d.isdigit() and is_committed(directory, d)]
    return max(steps) if steps else None
