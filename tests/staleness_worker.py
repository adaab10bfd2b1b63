"""Worker for the dist_async staleness sweep (VERDICT r4 item 8).

Usage: staleness_worker.py <coordinator> <nprocs> <rank> <outdir>
                           <mode> <K> <epochs> [momentum]

``mode`` = 'sync' (kvstore dist_tpu_sync) or 'async' (dist_async with
``MXNET_ASYNC_SYNC_PERIOD=K`` — a parameter-averaging round every K
local updates on top of the epoch-boundary rounds).

Both ranks train a small CIFAR-shaped convnet on equal-size shards of
the same synthetic task (per-rank disjoint data, identical init), then
save final params + held-out accuracy.  With momentum=0 and K=1 the
async run is MATHEMATICALLY the sync run: averaging parameters after
one local SGD step equals applying the gradient average.
"""
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("XLA_FLAGS", None)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def make_data(n, rs):
    """CIFAR-shaped (3, 16, 16) images, 4 classes by quadrant blob."""
    import numpy as np

    imgs = 0.3 * rs.randn(n, 3, 16, 16).astype("float32")
    labels = rs.randint(0, 4, n).astype("float32")
    for i in range(n):
        q = int(labels[i])
        cy, cx = 4 + 8 * (q // 2), 4 + 8 * (q % 2)
        imgs[i, :, cy - 3:cy + 3, cx - 3:cx + 3] += 1.2
    return imgs, labels


def get_symbol():
    import mxnet_tpu as mx

    data = mx.sym.Variable("data")
    c = mx.sym.Convolution(data, num_filter=8, kernel=(3, 3),
                           pad=(1, 1), name="conv1")
    c = mx.sym.Activation(mx.sym.BatchNorm(c, fix_gamma=False,
                                           name="bn1"), act_type="relu")
    c = mx.sym.Pooling(c, kernel=(2, 2), stride=(2, 2), pool_type="max")
    c = mx.sym.Convolution(c, num_filter=16, kernel=(3, 3), pad=(1, 1),
                           name="conv2")
    c = mx.sym.Activation(mx.sym.BatchNorm(c, fix_gamma=False,
                                           name="bn2"), act_type="relu")
    c = mx.sym.Pooling(c, global_pool=True, kernel=(2, 2),
                       pool_type="avg")
    fc = mx.sym.FullyConnected(mx.sym.Flatten(c), num_hidden=4,
                               name="fc")
    return mx.sym.SoftmaxOutput(fc, name="softmax")


def main():
    coordinator, nprocs, rank, outdir, mode, period, epochs = \
        sys.argv[1:8]
    momentum = float(sys.argv[8]) if len(sys.argv) > 8 else 0.0
    nprocs, rank = int(nprocs), int(rank)
    epochs = int(epochs)
    if mode == "async" and int(period) > 0:
        os.environ["MXNET_ASYNC_SYNC_PERIOD"] = period

    import jax

    jax.config.update("jax_platforms", "cpu")
    # jax CPU clients reject cross-process programs unless a
    # collectives implementation is chosen before backend creation
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=nprocs, process_id=rank)
    import numpy as np

    import mxnet_tpu as mx

    # equal shard sizes (a periodic averaging round is a collective);
    # per-rank disjoint data, shared held-out set
    rs = np.random.RandomState(1000 + rank)
    X, y = make_data(256, rs)
    val_rs = np.random.RandomState(99)
    Xv, yv = make_data(256, val_rs)
    bs = int(os.environ.get("STALE_BATCH", "32"))
    it = mx.io.NDArrayIter(X, y, batch_size=bs)
    val_it = mx.io.NDArrayIter(Xv, yv, batch_size=32)

    # identical init across ranks AND modes (the K=1==sync anchor
    # compares two separate runs)
    mx.random.seed(7)
    np.random.seed(7)
    kv = "dist_tpu_sync" if mode == "sync" else "dist_async"
    if os.environ.get("STALE_SAVE_INIT"):
        m0 = mx.mod.Module(get_symbol(), context=mx.cpu())
        m0.bind(data_shapes=it.provide_data,
                label_shapes=it.provide_label)
        m0.init_params(mx.initializer.Xavier(rnd_type="gaussian",
                                             magnitude=2.0))
        ip, _ = m0.get_params()
        np.savez(os.path.join(outdir, "init_%s_rank%d.npz"
                 % (mode, rank)),
                 **{k: v.asnumpy() for k, v in ip.items()})

    mod = mx.mod.Module(get_symbol(), context=mx.cpu())
    mod.fit(it, num_epoch=epochs, kvstore=kv, optimizer="sgd",
            optimizer_params={"learning_rate": 0.3,
                              "momentum": momentum},
            initializer=mx.initializer.Xavier(rnd_type="gaussian",
                                              magnitude=2.0))
    acc = dict(mod.score(val_it, mx.metric.Accuracy()))["accuracy"]
    params, _ = mod.get_params()
    tag = "%s_K%s_rank%d" % (mode, period, rank)
    np.savez(os.path.join(outdir, "staleness_%s.npz" % tag),
             **{k: v.asnumpy() for k, v in params.items()})
    with open(os.path.join(outdir, "staleness_%s.json" % tag), "w") as f:
        json.dump({"accuracy": float(acc)}, f)
    print("WORKER DONE", tag, acc)


if __name__ == "__main__":
    main()
