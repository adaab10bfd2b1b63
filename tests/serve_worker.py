"""Worker for the sharded-save -> single-process-serve test.

Usage: serve_worker.py <mode> <workdir> [coordinator num_procs rank]

Modes (both build the same deterministic tiny transformer LM):

* ``save``  — joins a ``jax.distributed`` pod, lays the embedding and
  LM-head weights out over a process-spanning mesh (so every rank owns
  a genuine index window of the global arrays), and writes a v2
  elastic checkpoint through ``CheckpointManager.save`` — per-rank
  windowed shards, rank-0 manifest last, commit barrier through the
  jax global-device sync (``MXNET_NUM_WORKERS`` mode).
* ``serve`` — single process: restores the checkpoint through
  ``InferenceSession.from_checkpoint`` (the shard windows reassemble
  onto this 1-process topology), checks every parameter is bit-equal
  to the generating ``init_params`` draw, then runs a bucketed prefill
  plus paged decode steps and asserts each step's logits row matches
  the ``reference_last_logits`` full-context oracle (another
  executable: tests/closeness.py).
  Writes ``serve_ok.json`` on success.
"""
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("XLA_FLAGS", None)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

SEED = 11
PAGE = 8


def _model_config():
    from mxnet_tpu.serve import ModelConfig

    return ModelConfig(vocab_size=64, num_layers=2, d_model=32,
                       num_heads=2, max_len=64)


def main():
    import worker_guard

    worker_guard.install(float(os.environ.get("TEST_WORKER_TIMEOUT_S",
                                              "180")))
    mode, workdir = sys.argv[1], sys.argv[2]
    ckpt_dir = os.path.join(workdir, "ckpt")

    import jax

    jax.config.update("jax_platforms", "cpu")

    if mode == "save":
        coordinator, num_procs, rank = \
            sys.argv[3], int(sys.argv[4]), int(sys.argv[5])
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=num_procs,
                                   process_id=rank)
        # CheckpointManager's coordinator-env mode: rank/barrier via jax
        os.environ["MXNET_NUM_WORKERS"] = str(num_procs)

        import numpy as np

        from mxnet_tpu import checkpoint as ckpt
        from mxnet_tpu.parallel.mesh import create_mesh, mesh_scope
        from mxnet_tpu.parallel.sharding import named_sharding
        from mxnet_tpu.serve import init_params

        cfg = _model_config()
        params = dict(init_params(cfg, seed=SEED))  # same draw per rank

        # Lay the two vocab-sized matrices out over the pod so each
        # process owns a genuine window — the layout a real trained
        # model serves from, and the case the restore must reassemble.
        mesh = create_mesh({"data": num_procs})
        for name in ("tok_embed_weight", "lm_head_weight"):
            host = np.asarray(params[name])
            sharding = named_sharding(mesh, "data", None)
            params[name] = jax.make_array_from_callback(
                host.shape, sharding, lambda idx, h=host: h[idx])

        with mesh_scope(mesh):
            mgr = ckpt.CheckpointManager(ckpt_dir, prefix="lm",
                                         save_optimizer_states=False)
            mgr.save(epoch=1, arg_params=params)
        print("WORKER %d DONE save" % rank)
        return

    if mode == "serve":
        import numpy as np

        from mxnet_tpu.serve import InferenceSession, ServeConfig, \
            init_params, reference_last_logits

        from closeness import assert_close_across_executables

        cfg = _model_config()
        sess = InferenceSession.from_checkpoint(
            ckpt_dir, prefix="lm", num_heads=cfg.num_heads,
            config=ServeConfig(slots=2, page_size=PAGE, buckets=(8, 16),
                               max_new=8, exact=True))

        # every restored parameter bit-equals the generating draw
        expected = init_params(cfg, seed=SEED)
        assert sorted(sess.params) == sorted(expected), \
            "restored param set mismatch: %r" % sorted(sess.params)
        for name, ref in expected.items():
            np.testing.assert_array_equal(
                np.asarray(sess.params[name]), np.asarray(ref),
                err_msg="param %r changed across save/restore" % name)

        # paged decode off the restored params stays on the
        # full-context reference forward
        prompt = [int(t) for t in
                  np.random.RandomState(5).randint(1, 63, size=9)]
        slot = sess.try_alloc(len(prompt), 6)
        assert slot is not None
        first, last_logits = sess.prefill(slot, prompt)
        last_logits = np.asarray(last_logits)
        assert_close_across_executables(
            last_logits,
            np.asarray(reference_last_logits(sess.params, prompt,
                                             sess.model, PAGE, exact=True)))
        seq = list(prompt) + [first]
        for _ in range(5):
            toks, logits = sess.step()
            logits = np.asarray(logits)
            assert_close_across_executables(
                logits[slot],
                np.asarray(reference_last_logits(sess.params, seq,
                                                 sess.model, PAGE,
                                                 exact=True)))
            seq.append(toks[slot])
        sess.release(slot)

        with open(os.path.join(workdir, "serve_ok.json"), "w") as f:
            json.dump({"ok": True, "params": len(expected),
                       "decode_steps": 5, "tokens": seq[len(prompt):]}, f)
        print("WORKER DONE serve")
        return

    raise SystemExit("unknown mode %r" % mode)


if __name__ == "__main__":
    main()
