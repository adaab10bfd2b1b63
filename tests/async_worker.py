"""Worker for the two-process dist_async test.

Usage: async_worker.py <coordinator> <num_procs> <rank> <outdir>

Each rank trains on a DIFFERENT-SIZED shard of a separable toy task
through ``Module.fit(kvstore='dist_async')`` — per-host local updates
with zero per-step DCN traffic, meeting only at the epoch-boundary
parameter-averaging rounds (the TPU-native bounded-staleness answer to
the reference's serverside immediate-apply,
``src/kvstore/kvstore_dist_server.h:226``).  The ranks therefore run
DIFFERENT numbers of optimizer updates (asserted by the runner) yet end
with identical, converged parameters.
"""
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("XLA_FLAGS", None)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    coordinator, num_procs, rank, outdir = sys.argv[1:5]
    mode = sys.argv[5] if len(sys.argv) > 5 else "module"
    num_procs, rank = int(num_procs), int(rank)

    import jax

    jax.config.update("jax_platforms", "cpu")
    # jax CPU clients reject cross-process programs unless a
    # collectives implementation is chosen before backend creation
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_procs,
                               process_id=rank)
    import numpy as np

    import mxnet_tpu as mx

    # different shard sizes -> different local step counts per epoch
    shard = 48 if rank == 0 else 80
    rs = np.random.RandomState(100 + rank)   # different data AND seed
    w_true = np.random.RandomState(7).randn(8, 3).astype("float32")
    X = rs.randn(shard, 8).astype("float32")
    y = (X @ w_true).argmax(axis=1).astype("float32")
    it = mx.io.NDArrayIter(X, y, batch_size=8)

    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=3, name="fc2")
    net = mx.sym.SoftmaxOutput(fc2, name="softmax")

    if mode == "gluon":
        return gluon_main(X, y, rank, outdir)
    mod = mx.mod.Module(net, context=mx.cpu())
    metric = mx.metric.Accuracy()
    mod.fit(it, num_epoch=8, kvstore="dist_async", optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            initializer=mx.initializer.Xavier(
                rnd_type="gaussian", magnitude=2.0),
            eval_metric=metric)
    acc = dict(mod.score(it, mx.metric.Accuracy()))["accuracy"]

    params, _ = mod.get_params()
    np.savez(os.path.join(outdir, "async_params_rank%d.npz" % rank),
             **{k: v.asnumpy() for k, v in params.items()})
    with open(os.path.join(outdir,
                           "async_result_rank%d.json" % rank), "w") as f:
        json.dump({"num_update": mod._optimizer.num_update,
                   "accuracy": float(acc)}, f)
    print("ASYNC WORKER %d DONE updates=%d acc=%.3f"
          % (rank, mod._optimizer.num_update, acc))




def gluon_main(X, y, rank, outdir):
    """Gluon face of dist_async: Trainer local steps + explicit
    sync_params() rounds at epoch boundaries."""
    import json

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon

    net = gluon.nn.Sequential()
    net.add(gluon.nn.Dense(16, activation="relu"))
    net.add(gluon.nn.Dense(3))
    net.initialize(mx.init.Xavier(rnd_type="gaussian", magnitude=2.0))
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9},
                            kvstore="dist_async")
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    dataset = gluon.data.ArrayDataset(X, y)
    loader = gluon.data.DataLoader(dataset, batch_size=8, shuffle=True)
    n_updates = 0
    net(mx.nd.array(X[:1]))        # materialize deferred shapes
    trainer.sync_params()          # also triggers kv init + the
                                   # automatic common-start round
    for _ in range(8):
        for data, label in loader:
            with autograd.record():
                loss = loss_fn(net(data), label)
            loss.backward()
            trainer.step(data.shape[0])
            n_updates += 1
        trainer.sync_params()      # epoch-boundary averaging round
    correct = n = 0
    for data, label in loader:
        out = net(data)
        correct += int((out.asnumpy().argmax(axis=1)
                        == label.asnumpy()).sum())
        n += data.shape[0]
    params = {k: v.data().asnumpy()
              for k, v in net.collect_params().items()}
    np.savez(os.path.join(outdir, "async_params_rank%d.npz" % rank),
             **params)
    with open(os.path.join(outdir,
                           "async_result_rank%d.json" % rank), "w") as f:
        json.dump({"num_update": n_updates,
                   "accuracy": correct / n}, f)
    print("ASYNC GLUON WORKER %d DONE updates=%d acc=%.3f"
          % (rank, n_updates, correct / n))


if __name__ == "__main__":
    main()
