"""PTB-shaped bucketed LSTM throughput: fused shared-store vs eager path.

Measures tokens/sec through BucketingModule.fit on a synthetic corpus with
PTB-like bucket structure (buckets 10/20/30/40, vocab 10k, 2-layer LSTM 200
hidden — the reference example/rnn/lstm_bucketing.py configuration scaled to
bench quickly).  Run:  python benchmarks/bench_bucketing.py [--eager]
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--eager", action="store_true",
                    help="disable the fused train step")
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--sentences", type=int, default=2000)
    args = ap.parse_args()
    if args.eager:
        os.environ["MXNET_FUSED_TRAIN_STEP"] = "0"

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import symbol as sym
    from mxnet_tpu import rnn as rnn_mod

    vocab, embed_dim, hidden, batch = 10000, 200, 200, 32
    buckets = [10, 20, 30, 40]

    rng = np.random.RandomState(0)
    sentences = []
    for _ in range(args.sentences):
        length = rng.randint(5, 41)
        sentences.append(rng.randint(1, vocab, size=length).tolist())
    it = rnn_mod.BucketSentenceIter(sentences, batch_size=batch,
                                    buckets=buckets, seed=0)

    def sym_gen(seq_len):
        data = sym.Variable("data")
        label = sym.Variable("softmax_label")
        net = sym.Embedding(data, input_dim=vocab, output_dim=embed_dim,
                            name="embed")
        for i in range(2):
            cell = mx.rnn.LSTMCell(hidden, prefix="l%d_" % i)
            net, _ = cell.unroll(seq_len, inputs=net, merge_outputs=True)
        pred = sym.FullyConnected(sym.Reshape(net, shape=(-1, hidden)),
                                  num_hidden=vocab, name="fc")
        flat = sym.Reshape(label, shape=(-1,))
        out = sym.SoftmaxOutput(pred, flat, use_ignore=True, ignore_label=-1,
                                name="softmax")
        return out, ("data",), ("softmax_label",)

    mod = mx.mod.BucketingModule(sym_gen,
                                 default_bucket_key=it.default_bucket_key)

    tokens_per_epoch = sum(min(len(s), buckets[-1]) for s in sentences)
    epoch_times = []

    t_wall = time.perf_counter()

    def batch_cb(param):
        pass

    class EpochTimer:
        def __init__(self):
            self.t0 = time.perf_counter()

        def __call__(self, epoch, *a):
            now = time.perf_counter()
            epoch_times.append(now - self.t0)
            self.t0 = now

    mod.fit(it, optimizer="adam", optimizer_params={"learning_rate": 0.001},
            initializer=mx.initializer.Xavier(), num_epoch=args.epochs,
            eval_metric=mx.metric.Perplexity(ignore_label=-1),
            epoch_end_callback=EpochTimer())
    wall = time.perf_counter() - t_wall

    # first epoch pays compilation; steady state = later epochs
    steady = epoch_times[1:] or epoch_times
    tok_s = tokens_per_epoch / (sum(steady) / len(steady))
    mode = "eager" if args.eager else "fused"
    print({"mode": mode, "tokens_per_sec": round(tok_s, 1),
           "epoch_times_s": [round(t, 2) for t in epoch_times],
           "wall_s": round(wall, 1)})


if __name__ == "__main__":
    from mxnet_tpu.cache_dirs import arm_compile_cache

    arm_compile_cache()
    main()
