"""Worker program for the multi-process dist-kvstore test.

Launched by tests/test_dist_kvstore.py as N real OS processes (the
reference's nightly pattern: tests/nightly/dist_sync_kvstore.py spawned by
tools/launch.py — no mocked transports).  Asserts exact deterministic sums
through the dist_sync KVStore, then trains one synchronized step.

Usage: python dist_worker.py <rank> <nprocs> <coordinator>
"""
import sys

rank, nprocs, coordinator = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]

import jax

# pinned in code, like tests/conftest.py, so the worker never needs the
# launcher's environment to say it
jax.config.update("jax_platforms", "cpu")

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import ndarray as nd
from mxnet_tpu.parallel import launch

launch.init(coordinator_address=coordinator, num_processes=nprocs,
            process_id=rank)
assert jax.process_count() == nprocs, jax.process_count()

kv = mx.kvstore.create("dist_sync")
assert kv.rank == rank
assert kv.num_workers == nprocs

# -- exact-sum push/pull over several keys/shapes (dist_sync_kvstore.py) ----
shapes = {3: (4, 5), "big": (30, 10), 9: (2,)}
for key, shape in shapes.items():
    kv.init(key, nd.zeros(shape))
for step in range(3):
    for key, shape in shapes.items():
        # worker r pushes (r+1) * (step+1); global sum is deterministic
        kv.push(key, nd.full(shape, float(rank + 1) * (step + 1)))
        out = nd.zeros(shape)
        kv.pull(key, out=out)
        want = sum(r + 1 for r in range(nprocs)) * (step + 1)
        np.testing.assert_allclose(out.asnumpy(), want)
kv.barrier()

# -- updater path: optimizer applies the globally summed gradient ----------
kv2 = mx.kvstore.create("dist_sync")
kv2.set_optimizer(mx.optimizer.SGD(learning_rate=0.5, rescale_grad=1.0))
kv2.init(0, nd.full((3, 3), 10.0))
kv2.push(0, nd.full((3, 3), float(rank + 1)))   # global grad = sum = 3
out = nd.zeros((3, 3))
kv2.pull(0, out=out)
want = 10.0 - 0.5 * sum(r + 1 for r in range(nprocs))
np.testing.assert_allclose(out.asnumpy(), want, rtol=1e-6)
kv2.barrier()

# -- distributed TRAINING to convergence (dist_lenet.py analog) ------------
# each worker holds a disjoint shard; Module.fit(kvstore=dist_sync) must
# reach the same accuracy single-process training would
shard_rng = np.random.RandomState(100 + rank)
n_shard = 128
w_true = np.random.RandomState(7).normal(size=(6,)).astype(np.float32)
xs = shard_rng.normal(size=(n_shard, 6)).astype(np.float32)
ys = (xs @ w_true > 0).astype(np.float32)

net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=16,
                            name="fc1")
net = mx.sym.Activation(net, act_type="relu")
net = mx.sym.FullyConnected(net, num_hidden=2, name="fc2")
net = mx.sym.SoftmaxOutput(net, name="softmax")

mod = mx.mod.Module(net, context=mx.cpu())
mx.random.seed(5)   # identical init on every worker
it = mx.io.NDArrayIter(xs, ys, batch_size=16)
mod.fit(it, optimizer="sgd",
        optimizer_params={"learning_rate": 0.2},
        initializer=mx.initializer.Xavier(rnd_type="gaussian"),
        kvstore="dist_sync", num_epoch=8)
it.reset()
acc = dict(mod.score(it, "acc"))["accuracy"]
assert acc >= 0.9, "rank %d accuracy %.3f" % (rank, acc)
# synchronized workers end with IDENTICAL weights: compare a checksum
w = mod.get_params()[0]["fc1_weight"].asnumpy()
from mxnet_tpu.parallel import collectives

gathered = np.asarray(collectives.global_sum(w / nprocs))
np.testing.assert_allclose(w, gathered, rtol=1e-5, atol=1e-6)

# -- failure detection: every worker's heartbeat is fresh ------------------
import os as _os

if _os.environ.get("MXNET_HEARTBEAT_DIR"):
    import time as _time

    kv.barrier()                 # all workers have created their stamps
    _time.sleep(0.1)
    assert kv.num_dead_node() == 0, \
        "live workers misreported dead: %d" % kv.num_dead_node()
    # a rank beyond the group has no stamp -> detected
    from mxnet_tpu.parallel import health

    dead = health.dead_nodes(_os.environ["MXNET_HEARTBEAT_DIR"],
                             nprocs + 1)
    assert dead == [nprocs], dead
    kv.barrier()

print("WORKER_%d_OK" % rank, flush=True)
