"""Plain references: each configuration's forward pass and loss in
straightforward ``jax.numpy``, float32, ``highest`` matmul precision, written
from the published description and sharing no code with ``mxnet_tpu``.

A reference module offers ``forward(params, cfg, inputs, layers=None)`` and
``loss(params, cfg, inputs, labels, layers=None)``; ``params`` is the
checkpoint as the system names it (the names are data, not code).
"""
