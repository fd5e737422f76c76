#!/usr/bin/env python
"""Model-parallel LSTM language model.

Capability parity with the reference's example/model-parallel-lstm
(`lstm.py:48-112`): each LSTM layer is pinned to its own device through
``AttrScope(ctx_group=...)`` + ``bind(group2ctx=...)``, so a deep recurrent
net whose layers don't fit one accelerator spreads across several, and the
async dispatch overlaps the per-layer stages.

Run on the virtual CPU mesh for a quick check:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/model_parallel_lstm.py --num-layers 4
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import argparse
import logging

import numpy as np

import mxnet_tpu as mx


def build_lstm(seq_len, vocab, num_embed, num_hidden, num_layers, devices):
    """Unrolled multi-layer LSTM LM; layer i carries ctx_group 'layer<i>'
    plus an embed/decode group, each mappable to a device."""
    data = mx.sym.Variable("data")
    label = mx.sym.Variable("softmax_label")
    with mx.AttrScope(ctx_group="embed"):
        hidden = mx.sym.Embedding(data, input_dim=vocab,
                                  output_dim=num_embed, name="embed")
    for layer in range(num_layers):
        with mx.AttrScope(ctx_group="layer%d" % layer):
            cell = mx.rnn.LSTMCell(num_hidden, prefix="lstm_l%d_" % layer)
            hidden, _ = cell.unroll(seq_len, inputs=hidden,
                                    layout="NTC", merge_outputs=True)
    with mx.AttrScope(ctx_group="decode"):
        pred = mx.sym.Reshape(hidden, shape=(-1, num_hidden))
        pred = mx.sym.FullyConnected(pred, num_hidden=vocab, name="decode")
        flat_label = mx.sym.Reshape(label, shape=(-1,))
        net = mx.sym.SoftmaxOutput(pred, flat_label, name="softmax")

    group2ctx = {"embed": devices[0], "decode": devices[-1]}
    for layer in range(num_layers):
        group2ctx["layer%d" % layer] = devices[layer % len(devices)]
    return net, group2ctx


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq-len", type=int, default=12)
    ap.add_argument("--vocab", type=int, default=200)
    ap.add_argument("--num-embed", type=int, default=32)
    ap.add_argument("--num-hidden", type=int, default=64)
    ap.add_argument("--num-layers", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--num-batches", type=int, default=30)
    ap.add_argument("--lr", type=float, default=0.5)
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)

    import jax

    n_dev = max(1, len(jax.devices()))
    # mx.cpu on the CPU platform; mx.tpu otherwise (mx.tpu(i) raises when
    # there is no chip i — it never falls back to the host)
    make_ctx = mx.cpu if jax.devices()[0].platform == "cpu" else mx.tpu
    devices = [make_ctx(i) for i in range(min(n_dev, args.num_layers + 2))]
    logging.info("placing %d LSTM layers over %d device(s)",
                 args.num_layers, len(devices))

    net, group2ctx = build_lstm(args.seq_len, args.vocab, args.num_embed,
                                args.num_hidden, args.num_layers, devices)

    shapes = {"data": (args.batch_size, args.seq_len),
              "softmax_label": (args.batch_size, args.seq_len)}
    exe = net.simple_bind(devices[0], grad_req="write",
                          group2ctx=group2ctx, **shapes)

    init = mx.initializer.Xavier()
    for name, arr in exe.arg_dict.items():
        if name not in shapes:
            init(name, arr)
    opt = mx.optimizer.SGD(learning_rate=args.lr, rescale_grad=1.0 /
                           (args.batch_size * args.seq_len))
    updater = mx.optimizer.get_updater(opt)

    rng = np.random.RandomState(0)

    def markov_batch():
        """Deterministic token chains (learnable next-token structure)."""
        x = np.empty(shapes["data"], np.float32)
        x[:, 0] = rng.randint(1, args.vocab, args.batch_size)
        for t in range(1, args.seq_len):
            x[:, t] = (x[:, t - 1] * 31 + 7) % args.vocab
            x[:, t][x[:, t] == 0] = 1
        return x

    losses = []
    for step in range(args.num_batches):
        x = markov_batch()
        y = np.roll(x, -1, axis=1)
        exe.arg_dict["data"][:] = x
        exe.arg_dict["softmax_label"][:] = y
        exe.forward(is_train=True)
        exe.backward()
        for i, name in enumerate(net.list_arguments()):
            if name in shapes:
                continue
            updater(i, exe.grad_dict[name], exe.arg_dict[name])
        prob = exe.outputs[0].asnumpy()
        nll = -np.log(np.maximum(
            prob[np.arange(prob.shape[0]), y.reshape(-1).astype(int)],
            1e-10)).mean()
        losses.append(nll)
        if step % 10 == 0:
            logging.info("batch %3d  nll %.4f", step, nll)
    logging.info("nll first->last: %.4f -> %.4f", losses[0], losses[-1])
    assert losses[-1] < losses[0], "model-parallel LSTM failed to learn"
    print("model-parallel LSTM OK: nll %.4f -> %.4f"
          % (losses[0], losses[-1]))


if __name__ == "__main__":
    main()
