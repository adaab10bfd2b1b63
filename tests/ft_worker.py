"""Worker for the kill-and-resume fault-tolerance tests.

Usage: ft_worker.py <mode> <workdir> [coordinator num_procs rank]

Modes (all train the same deterministic MLP for 2 epochs):

* ``full``   — uninterrupted run; saves ``params_full_rank<r>.npz``.
* ``train``  — run with a CheckpointManager.  Touches ``started_rank<r>``
  after the first batch and sleeps a little per batch so the parent can
  land a SIGTERM mid-epoch (or, when ``FT_KILL_AT_BATCH=N`` is set, the
  worker SIGTERMs itself at batch N — the deterministic variant the
  multi-process test needs so every rank stops at the same boundary).
  On ``TrainingPreempted`` prints ``PREEMPTED <epoch> <nbatch>`` and
  exits 0.
* ``resume`` — ``fit(resume_from=...)`` from the checkpoint directory;
  saves ``params_resume_rank<r>.npz``.
* ``restore`` — elastic-restore probe: ``fit(resume_from=...)`` with
  ``num_epoch`` equal to the checkpointed epoch count, so ZERO batches
  run and ``params_restore_rank<r>.npz`` is exactly what the checkpoint
  reassembled onto THIS topology (the cross-process-count bit-exactness
  check).
* ``asyncsave`` — trains 1 epoch (synchronous checkpoint), then starts
  an async ``save()`` for epoch 2 with the ``shard_write`` fault site
  armed to delay mid-write, touches ``asyncsave_inflight_rank<r>``, and
  blocks in ``flush()`` — the parent SIGTERMs it there, modeling
  preemption DURING a background checkpoint write; epoch 1 must stay
  loadable.

With the optional distributed triple the worker joins a
``jax.distributed`` pod and trains through ``kvstore='dist_tpu_sync'``
on its interleaved shard (the ``dist_worker.py`` pattern).
"""
import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("XLA_FLAGS", None)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    import worker_guard

    worker_guard.install(float(os.environ.get("TEST_WORKER_TIMEOUT_S",
                                              "180")))
    mode, workdir = sys.argv[1], sys.argv[2]
    dist = len(sys.argv) > 3
    rank = 0
    kvstore = "local"

    import jax

    jax.config.update("jax_platforms", "cpu")
    if dist:
        coordinator, num_procs, rank = \
            sys.argv[3], int(sys.argv[4]), int(sys.argv[5])
        # the split path is the multi-process contract under test
        os.environ["MXNET_FUSED_STEP"] = "0"
        # jax CPU clients reject cross-process programs unless a
        # collectives implementation is chosen before backend creation
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=num_procs,
                                   process_id=rank)
        kvstore = "dist_tpu_sync"

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import checkpoint as ckpt

    rs = np.random.RandomState(0)
    X = rs.randn(64, 8).astype("float32")
    w_true = rs.randn(8, 3).astype("float32")
    y = (X @ w_true).argmax(axis=1).astype("float32")
    if dist:
        X, y = X[rank::num_procs], y[rank::num_procs]

    def make_iter():
        return mx.io.NDArrayIter(X, y, batch_size=8, shuffle=True, seed=42)

    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=3, name="fc2")
    net = mx.sym.SoftmaxOutput(fc2, name="softmax")

    def make_module():
        np.random.seed(7)  # identical init draws on every run and rank
        mx.random.seed(7)
        return mx.mod.Module(net, context=mx.cpu())

    fit_kwargs = dict(
        num_epoch=2, kvstore=kvstore, optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
        initializer=mx.init.Xavier())

    ckpt_dir = os.path.join(workdir, "ckpt")
    mgr = ckpt.CheckpointManager(ckpt_dir, prefix="ft")

    def save_params(mod, tag):
        params, _ = mod.get_params()
        np.savez(os.path.join(workdir, "params_%s_rank%d.npz" % (tag, rank)),
                 **{k: v.asnumpy() for k, v in params.items()})

    if mode == "full":
        mod = make_module()
        mod.fit(make_iter(), **fit_kwargs)
        save_params(mod, "full")
        print("WORKER %d DONE full" % rank)
        return

    if mode == "train":
        kill_at = int(os.environ.get("FT_KILL_AT_BATCH", "0"))
        sentinel = os.path.join(workdir, "started_rank%d" % rank)
        seen = [0]

        def batch_cb(param):
            seen[0] += 1
            if seen[0] == 1:
                with open(sentinel, "w") as f:
                    f.write("up\n")
            if kill_at and seen[0] == kill_at:
                import signal

                os.kill(os.getpid(), signal.SIGTERM)
            elif not kill_at:
                time.sleep(0.1)  # give the parent's SIGTERM time to land

        mod = make_module()
        try:
            mod.fit(make_iter(), checkpoint=mgr, batch_end_callback=batch_cb,
                    **fit_kwargs)
            # clean completion: record the final params so an elastic
            # restore on a different process count can diff against them
            save_params(mod, "train")
            print("WORKER %d DONE train (no preemption)" % rank)
        except mx.TrainingPreempted as e:
            with open(os.path.join(workdir,
                                   "preempt_rank%d.json" % rank), "w") as f:
                json.dump({"epoch": e.epoch, "nbatch": e.nbatch,
                           "signum": e.signum}, f)
            print("PREEMPTED %d %d" % (e.epoch, e.nbatch))
        return

    if mode == "resume":
        mod = make_module()
        mod.fit(make_iter(), resume_from=mgr, **fit_kwargs)
        save_params(mod, "resume")
        print("WORKER %d DONE resume" % rank)
        return

    if mode == "restore":
        # resume with num_epoch == the checkpoint's completed epochs:
        # fit binds, restores params/optimizer, trains zero batches —
        # the saved params round-trip through the elastic load path
        # unmodified onto whatever topology THIS process runs
        n_epochs = int(os.environ.get("FT_RESTORE_EPOCHS", "2"))
        mod = make_module()
        mod.fit(make_iter(), resume_from=mgr,
                **dict(fit_kwargs, num_epoch=n_epochs))
        save_params(mod, "restore")
        print("WORKER %d DONE restore" % rank)
        return

    if mode == "asyncsave":
        from mxnet_tpu.testing import faults

        mod = make_module()
        mod.fit(make_iter(), checkpoint=mgr,
                **dict(fit_kwargs, num_epoch=1))
        amgr = ckpt.CheckpointManager(ckpt_dir, prefix="ft",
                                      async_writes=True)
        os.environ["MXNET_FAULT_INJECT"] = \
            "shard_write:delay:seconds=%s" % os.environ.get(
                "FT_ASYNC_DELAY_S", "30")
        faults.reset()
        amgr.save(mod, epoch=2)  # background writer enters the delay
        with open(os.path.join(workdir,
                               "asyncsave_inflight_rank%d" % rank),
                  "w") as f:
            f.write("writing\n")
        amgr.flush()  # parent SIGTERMs us while blocked here
        print("WORKER %d DONE asyncsave (no kill landed)" % rank)
        return

    raise SystemExit("unknown mode %r" % mode)


if __name__ == "__main__":
    main()
