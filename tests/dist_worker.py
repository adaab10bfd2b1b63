"""Worker for the two-process DCN test (the launcher-less analogue of the
reference's ``tests/nightly/dist_sync_kvstore.py`` run with
``tools/launch.py -n 2 --launcher local``).

Usage: dist_worker.py <coordinator> <num_procs> <rank> <outdir>
   or: dist_worker.py --from-env <outdir>   (tools/launch.py contract:
       coordinator/size/rank read from MXNET_COORDINATOR /
       MXNET_NUM_WORKERS / MXNET_WORKER_ID)

Runs three conformance checks against the multi-process (DCN) branch of
``parallel.collectives.allreduce_nd`` and the KVStore rank/num_workers
surface, then trains a deterministic MLP through
``Module.fit(kvstore='dist_tpu_sync')`` on this rank's shard of the data
and saves the final params for the runner to compare.
"""
import json
import os
import sys
import time

# one CPU device per process; the split Module path is the multi-process
# contract under test (grads ride kvstore push/pull over DCN)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["MXNET_FUSED_STEP"] = "0"
os.environ.pop("XLA_FLAGS", None)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    import worker_guard

    # a wedged rendezvous/collective must kill the worker (exit 70), not
    # pin the whole test session on the runner's outer timeout
    worker_guard.install(float(os.environ.get("TEST_WORKER_TIMEOUT_S",
                                              "180")))
    if sys.argv[1] == "--from-env":
        outdir = sys.argv[2]
        coordinator = os.environ["MXNET_COORDINATOR"]
        num_procs = int(os.environ["MXNET_NUM_WORKERS"])
        rank = int(os.environ["MXNET_WORKER_ID"])
    else:
        coordinator, num_procs, rank, outdir = sys.argv[1:5]
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

    assert jax.process_count() == num_procs

    results = {}

    # rank heartbeats ride the dist kvstore when the directory is set
    hb_dir = os.path.join(outdir, "heartbeats")
    os.environ["MXNET_HEARTBEAT_DIR"] = hb_dir

    # 1) dense push/pull across processes
    kv = mx.kv.create("dist_tpu_sync")
    assert kv.rank == rank and kv.num_workers == num_procs

    # 1b) heartbeat liveness + dead-peer naming: every live rank's
    # beacon appears; a phantom rank is NAMED as never having written
    from mxnet_tpu import health

    assert kv._heartbeat is not None and kv._heartbeat.alive
    assert os.path.exists(health.RankHeartbeat.path_for(hb_dir, rank))
    deadline = time.time() + 60
    while any(not os.path.exists(health.RankHeartbeat.path_for(hb_dir, r))
              for r in range(num_procs)):
        assert time.time() < deadline, "peer heartbeat never appeared"
        time.sleep(0.05)
    assert health.stale_peers(hb_dir, num_procs, stale_s=1e9,
                              self_rank=rank) == []
    ghost = health.stale_peers(hb_dir, num_procs + 1, stale_s=1e9,
                               self_rank=rank)
    assert [g for g, _ in ghost] == [num_procs], ghost
    assert "never wrote a heartbeat" in ghost[0][1]
    report = health.peer_report(num_procs, self_rank=rank)
    assert "all current" in report, report
    results["heartbeat"] = "ok"
    kv.init("w", mx.nd.zeros((4, 3)))
    grad = mx.nd.array(np.full((4, 3), float(rank + 1), "float32"))
    kv.push("w", grad)
    out = mx.nd.zeros((4, 3))
    kv.pull("w", out=out)
    expect = sum(r + 1 for r in range(num_procs))
    np.testing.assert_allclose(out.asnumpy(), expect)
    results["dense_push_pull"] = "ok"

    # 2) row_sparse push across processes (densify -> DCN sum -> sparse)
    from mxnet_tpu.ndarray import sparse as sp

    kv.init("emb", mx.nd.zeros((6, 2)))
    rows = np.array([rank, rank + 2], "int32")
    vals = np.full((2, 2), float(rank + 1), "float32")
    rsp = sp.row_sparse_array((vals, rows), shape=(6, 2))
    kv.push("emb", rsp)
    # the merged value stayed SPARSE across the DCN reduce (no densify —
    # the bandwidth property row_sparse exists for)
    assert isinstance(kv._merged["emb"], sp.RowSparseNDArray), \
        type(kv._merged["emb"])
    assert kv._merged["emb"].indices.shape[0] <= 4  # true nnz <= sum
    dense = mx.nd.zeros((6, 2))
    kv.pull("emb", out=dense)
    expect_emb = np.zeros((6, 2), "float32")
    for r in range(num_procs):
        expect_emb[r] += r + 1
        expect_emb[r + 2] += r + 1
    np.testing.assert_allclose(dense.asnumpy(), expect_emb)
    results["row_sparse_push"] = "ok"

    # 3) row_sparse_pull of selected rows
    pulled = mx.nd.zeros((2, 2))
    kv.row_sparse_pull("emb", out=pulled,
                       row_ids=mx.nd.array([1.0, 3.0]))
    np.testing.assert_allclose(pulled.asnumpy(), expect_emb[[1, 3]])
    results["row_sparse_pull"] = "ok"

    # 4) Module.fit on this rank's shard == single-process full batch
    np.random.seed(7)  # identical init on every rank
    rs = np.random.RandomState(0)
    X = rs.randn(64, 8).astype("float32")
    w_true = rs.randn(8, 3).astype("float32")
    y = (X @ w_true).argmax(axis=1).astype("float32")
    # interleaved shard: the union of every rank's k-th batch equals the
    # single-process k-th full batch, so trajectories match exactly
    Xs = X[rank::num_procs]
    ys = y[rank::num_procs]
    it = mx.io.NDArrayIter(Xs, ys, batch_size=16)

    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=3, name="fc2")
    net = mx.sym.SoftmaxOutput(fc2, name="softmax")

    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=3, kvstore="dist_tpu_sync", optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            initializer=mx.init.Xavier())
    params, _ = mod.get_params()
    np.savez(os.path.join(outdir, "params_rank%d.npz" % rank),
             **{k: v.asnumpy() for k, v in params.items()})
    results["fit"] = "ok"

    with open(os.path.join(outdir, "result_rank%d.json" % rank), "w") as f:
        json.dump(results, f)
    print("WORKER %d DONE" % rank)


if __name__ == "__main__":
    main()
