"""PR 58: what the change must NOT move.  The decode and chunk programs (and
exaone's verify and draft programs) of the six serving configurations without
a delta layer lower to the parent's StableHLO (``pr53_hashes.py``'s seventeen
programs), and so do the fused train steps of a toy ResNet and a toy OPT
(``models.resnet``, and ``chipbench.harness``'s graph of ``opt-1.3b`` cut
down, through ``Module``: the programs of ``rn50_train_*`` and
``opt_train_*`` at a size the CPU lowers in seconds).
Run it over the parent's tree and over the change's, BOTH UNPACKED AT ONE
PATH in turn (a Mosaic kernel's body carries its checkout's path), and
compare:

    T=/root/scratch/tree
    rm -rf $T; mkdir -p $T; git archive HEAD | tar -x -C $T
    TREE=$T python $T/benchmarks/runs/pr53_hashes.py > a   # the parent has no pr58_hashes.py
    cp benchmarks/runs/pr58_hashes.py /root/scratch/; (cd $T && TREE=$T python /root/scratch/pr58_hashes.py train) >> a
    rm -rf $T; mkdir -p $T; git archive $(git write-tree) | tar -x -C $T
    (cd $T && TREE=$T python benchmarks/runs/pr58_hashes.py) > b; diff a b

CPU only; nothing here is run by a test or by the benchmark."""
import hashlib
import os
import runpy
import sys

TREE = os.environ["TREE"]
if sys.argv[1:] != ["train"]:
    runpy.run_path(os.path.join(TREE, "benchmarks", "runs", "pr53_hashes.py"))
sys.path.insert(0, TREE)
import jax                                                  # noqa: E402
jax.config.update("jax_platforms", "cpu")
import numpy as np                                          # noqa: E402
import mxnet_tpu as mx                                      # noqa: E402
assert mx.__file__.startswith(TREE), mx.__file__
from chipbench import harness, manifest                    # noqa: E402
from mxnet_tpu.models import resnet                        # noqa: E402


def train_step(sym, data, label):
    """The fused step of ``sym`` over one batch: its StableHLO's hash."""
    with mx.NameManager():
        it = mx.io.NDArrayIter(data, label, batch_size=data.shape[0],
                               label_name="softmax_label")
        mod = mx.mod.Module(sym, context=mx.cpu())
        mod.bind(it.provide_data, it.provide_label)
        mod.init_params(mx.initializer.Xavier())
        mod.init_optimizer(optimizer="sgd", optimizer_params=dict(
            learning_rate=0.1, momentum=0.9, wd=1e-4))
        mod.forward_backward(next(iter(it)))
        mod.update()
    text = mod._fused_step.artifact().stablehlo_text
    return hashlib.sha256(text.encode()).hexdigest()[:16] + " %d" % len(text)


rng = np.random.RandomState(0)
with mx.NameManager():
    net = resnet.get_symbol(num_classes=10, num_layers=18,
                            image_shape=(3, 32, 32))
print("resnet train step", train_step(
    net, rng.randn(4, 3, 32, 32).astype(np.float32),
    rng.randint(0, 10, (4,)).astype(np.float32)))
cfg = dict(manifest.load_json(manifest.ROOT, "chipbench/configs/opt-1.3b.json"),
           vocab_size=96, hidden_size=64, word_embed_proj_dim=64, ffn_dim=128,
           num_attention_heads=4, num_hidden_layers=2,
           max_position_embeddings=32)
with mx.NameManager():
    net = harness.build_symbol(cfg)
print("opt-1.3b train step", train_step(
    net, rng.randint(0, 96, (4, 32)).astype(np.float32),
    rng.randint(0, 96, (4, 32)).astype(np.float32)))
