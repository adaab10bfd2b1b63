"""Worker for the elastic ZeRO optimizer-state round-trip tests.

Usage: zero_worker.py <mode> <workdir> [coordinator num_procs rank]

Every mode builds the same deterministic MLP ``TrainStep`` with
``zero='on'`` over a 2-way data mesh — either 2 processes x 1 CPU
device (the distributed triple given) or 1 process x 2 forced host
devices — so the update math, the 1/N tiling, and therefore the Adam
moments are IDENTICAL across topologies and only the checkpoint
plumbing differs.

* ``train`` — 3 fixed Adam steps (power-of-two lr, so the sharded
  update is bit-exact vs any layout), then
  ``CheckpointManager.save(zero_states=..., num_update=3)`` through the
  v2 piece-window format: each rank writes the 1/N state windows it
  owns.  Single-process runs also dump the canonical (unsharded)
  moments to ``canonical_rank0.npz`` as the cross-topology oracle.
* ``dump`` — load the checkpoint on THIS topology (single process or
  every rank of a pod) and write the reassembled canonical optimizer
  state + ``num_update`` to ``loaded_rank<r>.npz``: what any resume
  would seed from, bit-comparable against the oracle.
* ``train3`` / ``dump3`` — the same protocol under ``zero='3'``: the
  save carries the at-rest flat 1/N parameter tiles through
  ``zero_params=`` (each rank writes only the windows it owns — no
  rank ever materializes the full params), and the load reassembles
  them back to canonical shapes.  The single-process ``train3`` also
  dumps the canonical params oracle (``canonical3_rank0.npz``), which
  any topology's ``dump3`` must match bit for bit.

The fused step is driven directly (not through ``Module.fit``): the
module path hands multi-process sync training to the kvstore's split
pipeline, while the sharded update under test is the in-jit
reduce-scatter/all-gather program spanning the pod's global mesh.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
DIST = len(sys.argv) > 3
if DIST:
    os.environ.pop("XLA_FLAGS", None)
else:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

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


def _step(mesh, zero="on"):
    from mxnet_tpu.fused import TrainStep

    return TrainStep(_sym(), optimizer="adam",
                     optimizer_params={"learning_rate": 0.125,
                                       "rescale_grad": 1.0 / BATCH},
                     mesh=mesh, batch_sharding_axis="data", zero=zero)


def _flatten_states(states):
    """{name: tree} -> {"name/j": leaf} host arrays, orderd like
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
        # CheckpointManager rank/barrier via the jax pod
        os.environ["MXNET_NUM_WORKERS"] = str(num_procs)

    import numpy as np

    from mxnet_tpu import checkpoint as ckpt
    from mxnet_tpu.parallel import create_mesh, zero

    ckpt_dir = os.path.join(workdir, "ckpt")
    mgr = ckpt.CheckpointManager(ckpt_dir, prefix="z")

    if mode in ("train", "train3"):
        z3 = mode == "train3"
        os.environ["MXNET_ZERO_MIN_PARAM_BYTES"] = "0"
        if z3:
            os.environ["MXNET_ZERO_GATHER_BUCKET_MB"] = "0.0001"
        mesh = create_mesh({"data": 2})
        step = _step(mesh, zero="3" if z3 else "on")
        assert step.zero_axis == "data", step.zero_axis
        assert step.zero3 == z3
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
        # every rank owns a genuine window of each sharded state leaf
        for name, ent in lay.items():
            if ent.sharded:
                leaf = zero.state_leaves(states[name])[0]
                owned = [s for s in leaf.addressable_shards
                         if s.replica_id == 0]
                assert owned, "rank %d owns no window of %s" % (rank,
                                                                name)
        if z3:
            # ZeRO-3: no rank holds the full params — each writes only
            # its at-rest 1/N tile windows through zero_params
            mgr.save(epoch=1, nbatch=STEPS, symbol=step.symbol,
                     arg_params={},
                     zero_states=zero.export_states(states, lay),
                     zero_params=zero.export_params(params, lay),
                     num_update=STEPS)
        else:
            mgr.save(epoch=1, nbatch=STEPS, symbol=step.symbol,
                     arg_params={n: np.asarray(
                         p.addressable_data(0))
                         for n, p in params.items()},
                     zero_states=zero.export_states(states, lay),
                     num_update=STEPS)
        if not DIST:
            canon = {n: zero.unshard_state(st, lay[n])
                     for n, st in states.items()}
            np.savez(os.path.join(workdir, "canonical_rank0.npz"),
                     num_update=np.int64(STEPS), **_flatten_states(canon))
            if z3:
                np.savez(os.path.join(workdir, "canonical3_rank0.npz"),
                         **zero.unpack_params(params, lay))
        print("WORKER %d DONE %s" % (rank, mode))
        return

    if mode in ("dump", "dump3"):
        state = mgr.load()
        assert state.opt_states is not None, \
            "checkpoint carried no ZeRO optimizer state"
        assert state.states_path is None, \
            "legacy states blob must not shadow the sharded state"
        np.savez(os.path.join(workdir, "loaded_rank%d.npz" % rank),
                 num_update=np.int64(state.num_update),
                 **_flatten_states(state.opt_states))
        if mode == "dump3":
            assert state.manifest.get("zero_params"), \
                "manifest carried no ZeRO-3 at-rest param tiles"
            np.savez(os.path.join(workdir, "loaded3_rank%d.npz" % rank),
                     **{n: np.asarray(a.asnumpy())
                        for n, a in state.arg_params.items()})
        print("WORKER %d DONE %s" % (rank, mode))
        return

    raise SystemExit("unknown mode %r" % mode)


if __name__ == "__main__":
    main()
