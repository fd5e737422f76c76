"""Operator library — registers every op into the global registry on import.

Families mirror the reference inventory (SURVEY §2.3): elemwise (unary /
binary / broadcast / scalar / logic), tensor (reduce / matrix / indexing /
init / ordering / control / softmax), nn layer ops, sampling, fused
optimizer updates.  Contrib (detection / CTC / fft) and RNN register from
their own modules as they land.
"""
from . import (elemwise, tensor, nn, sample, optimizer_ops, rnn_op, spatial,
               contrib_ops, attention, moe, ssm, linattn, kda, gdn)

_registered = False


def register_all():
    global _registered
    if _registered:
        return
    _registered = True
    elemwise.register_all()
    tensor.register_all()
    nn.register_all()
    sample.register_all()
    optimizer_ops.register_all()
    rnn_op.register_all()
    spatial.register_all()
    contrib_ops.register_all()
    attention.register_all()
    moe.register_all()
    ssm.register_all()
    linattn.register_all()
    kda.register_all()
    gdn.register_all()


register_all()
