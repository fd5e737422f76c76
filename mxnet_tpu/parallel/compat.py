"""The two jax spellings the explicit-collective paths (ring attention,
pipeline schedules, MoE all-to-all) share, under the installed jax 0.9:
``jax.shard_map`` with its ``check_vma`` knob, and ``lax.pcast(...,
to="varying")``.  Kept as one module so every shard_map region in the tree
imports from one place.
"""
from __future__ import annotations

__all__ = ["shard_map", "pvary"]


def shard_map(f, mesh, in_specs, out_specs, check_vma=True):
    """``jax.shard_map``.  ``check_vma=False`` relaxes the varying-axes
    typing that e.g. pallas interpreter mode cannot satisfy."""
    import jax

    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def pvary(x, axis_names):
    """Mark ``x`` as device-varying over mesh axes (vma typing)."""
    from jax import lax

    return lax.pcast(x, tuple(axis_names), to="varying")
