"""Share of the first chip's busy time in the window that is latent
attention's, between its projections and in both forms (the rotation and the
query's temperature, the row's append, the decode row's absorbed walk with its
two folded products, the chunk's expansion of each live block and its
products): the instructions under the ``mx.attn_latent`` scopes of the
serving programs, and the compiler's moves between two of them
(``work_mla.scope_maps``: the gathered pages' re-layout to positions carries
no scope of its own).  The projections are ``FullyConnected`` nodes and stay
under ``mx.linear``.  ``XLA Ops`` events joined to the programs' maps.
"""

from chipbench import work_mla


def read(facts):
    # 0 would say "attention that took no time": where no program has the
    # scope (the parent of the PR that added it) the metric is left out
    return work_mla.device_pct(facts)
