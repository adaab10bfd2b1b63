"""Worker for the plan-elastic multi-process round-trip test.

Usage: plan_worker.py <mode> <workdir> [coordinator num_procs rank]

Every mode builds the same deterministic MLP ``TrainStep`` under the
COMPOSED plan ``data=2,model=2,zero=3`` over 4 CPU devices — either
2 processes x 2 forced host devices (the distributed triple given) or
1 process x 4 forced host devices — so the update math, the
group-local shard-major tiling, and therefore the Adam moments are
IDENTICAL across topologies and only the checkpoint plumbing differs.

* ``train`` — 3 fixed Adam steps (power-of-two lr), then
  ``CheckpointManager.save(zero_states=..., zero_params=...)`` through
  the v2 piece windows: each rank writes only the flat tile windows it
  owns, and asserts it never materializes a full TP-sharded parameter.
  Single-process runs also dump the canonical (unsharded) moments and
  params as the cross-topology oracles.
* ``dump`` — load the checkpoint on THIS topology and write the
  reassembled canonical optimizer state + params to
  ``loaded*_rank<r>.npz``, bit-comparable against the oracles.

The fused step is driven directly (not through ``Module.fit``): the
round-trip under test is the composed plan's tile interchange, which
lives entirely in the in-jit program + checkpoint manifest.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
DIST = len(sys.argv) > 3
# 2 procs x 2 local devices or 1 proc x 4: same 4-device global mesh
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d" \
    % (2 if DIST else 4)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

STEPS = 3
BATCH = 16
FEAT = 8


def _sym():
    import mxnet_tpu as mx

    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax",
                                normalization="batch")


def _step():
    from mxnet_tpu.fused import TrainStep
    from mxnet_tpu.parallel import ParallelPlan

    return TrainStep(_sym(), optimizer="adam",
                     optimizer_params={"learning_rate": 0.125,
                                       "rescale_grad": 1.0 / BATCH},
                     plan=ParallelPlan(data=2, model=2, zero="3"))


def _flatten_states(states):
    """{name: tree} -> {"name/j": leaf} host arrays, ordered like
    ``parallel.zero.state_leaves`` (the checkpoint's leaf order)."""
    import numpy as np

    from mxnet_tpu.parallel import zero

    out = {}
    for name, st in states.items():
        for j, leaf in enumerate(zero.state_leaves(st)):
            out["%s/%d" % (name, j)] = np.asarray(leaf)
    return out


def main():
    import worker_guard

    worker_guard.install(float(os.environ.get("TEST_WORKER_TIMEOUT_S",
                                              "180")))
    mode, workdir = sys.argv[1], sys.argv[2]
    rank = 0

    import jax

    jax.config.update("jax_platforms", "cpu")
    if DIST:
        coordinator, num_procs, rank = \
            sys.argv[3], int(sys.argv[4]), int(sys.argv[5])
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=num_procs,
                                   process_id=rank)
        os.environ["MXNET_NUM_WORKERS"] = str(num_procs)

    import numpy as np

    from mxnet_tpu import checkpoint as ckpt
    from mxnet_tpu.parallel import zero

    os.environ["MXNET_ZERO_MIN_PARAM_BYTES"] = "0"
    os.environ["MXNET_ZERO_GATHER_BUCKET_MB"] = "0.0001"
    ckpt_dir = os.path.join(workdir, "ckpt")
    mgr = ckpt.CheckpointManager(ckpt_dir, prefix="p")

    if mode == "train":
        step = _step()
        assert step.zero_axis == "data", step.zero_axis
        assert step.zero3 and step._plan_tp
        shapes = {"data": (BATCH, FEAT), "softmax_label": (BATCH,)}
        params, aux, states = step.init_state(shapes)
        rs = np.random.RandomState(42)
        rng = jax.random.PRNGKey(7)
        for _ in range(STEPS):
            bd = {"data": rs.randn(BATCH, FEAT).astype("float32"),
                  "softmax_label": rs.randint(0, 4, (BATCH,))
                  .astype("float32")}
            params, aux, states, _ = step(params, aux, states, bd, rng)
        lay = step.zero_layout(params)
        if DIST:
            # no rank ever materializes a full sharded param: this
            # process addresses only its devices' flat tile windows
            for name, ent in lay.items():
                if not ent.sharded:
                    continue
                # distinct windows only: a non-TP tile is replicated
                # across model groups on purpose (tiles WITHIN a group)
                uniq = {tuple((sl.start, sl.stop) for sl in s.index):
                        int(np.prod(s.data.shape))
                        for s in params[name].addressable_shards}
                local = sum(uniq.values())
                assert local < ent.padded, \
                    "rank %d holds %d/%d of %s" % (rank, local,
                                                   ent.padded, name)
        mgr.save(epoch=1, nbatch=STEPS, symbol=step.symbol,
                 arg_params={},
                 zero_states=zero.export_states(states, lay),
                 zero_params=zero.export_params(params, lay),
                 num_update=STEPS)
        if not DIST:
            canon = {n: zero.unshard_state(st, lay[n])
                     for n, st in states.items()}
            np.savez(os.path.join(workdir, "canonical_rank0.npz"),
                     num_update=np.int64(STEPS),
                     **_flatten_states(canon))
            np.savez(os.path.join(workdir, "canonical3_rank0.npz"),
                     **{n: np.asarray(a)
                        for n, a in step.unpack_params(params).items()})
        print("WORKER %d DONE %s" % (rank, mode))
        return

    if mode == "dump":
        state = mgr.load()
        assert state.opt_states is not None, \
            "checkpoint carried no ZeRO optimizer state"
        assert state.states_path is None, \
            "legacy states blob must not shadow the sharded state"
        assert state.manifest.get("zero_params"), \
            "manifest carried no at-rest param tiles"
        np.savez(os.path.join(workdir, "loaded_rank%d.npz" % rank),
                 num_update=np.int64(state.num_update),
                 **_flatten_states(state.opt_states))
        np.savez(os.path.join(workdir, "loaded3_rank%d.npz" % rank),
                 **{n: np.asarray(a.asnumpy())
                    for n, a in state.arg_params.items()})
        print("WORKER %d DONE %s" % (rank, mode))
        return

    raise SystemExit("unknown mode %r" % mode)


if __name__ == "__main__":
    main()
