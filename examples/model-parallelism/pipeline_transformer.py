#!/usr/bin/env python
"""Pipeline-parallel transformer LM training through the public Module
API (first-class pipeline parallelism, round 4): the Symbol is cut into
heterogeneous stages (embed -> blocks -> head) by
``parallel.pipeline.split_symbol``, per-stage parameters/optimizer
states shard over the mesh's 'pipe' axis (each device holds ONLY its
stage), and the 1F1B schedule runs a bounded activation ring with
per-stage remat backward — O(S) activation memory, no gradient
collectives at all.

Runs on a virtual CPU mesh when real chips are scarce (the same code
drives a pod slice):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python examples/model-parallelism/pipeline_transformer.py

Reference analogue: the manual layer-per-GPU staging of
``example/model-parallel-lstm`` — here the cut, schedule, and sharding
are automatic.
"""
import argparse
import logging
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

def main(args):
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.models import transformer
    from mxnet_tpu.parallel import create_mesh, mesh_scope
    from mxnet_tpu.parallel.pipeline import PipelineTrainStep

    n_dev = min(args.stages, len(jax.devices()))
    if n_dev < 2:
        print("need >= 2 devices for a pipeline; run with "
              "JAX_PLATFORMS=cpu XLA_FLAGS="
              "--xla_force_host_platform_device_count=%d" % args.stages)
        return 1

    sym = transformer.get_symbol(
        vocab_size=args.vocab, num_layers=args.layers, d_model=args.dim,
        num_heads=4, seq_len=args.seq_len,
        moe_experts=args.moe_experts, moe_top_k=2,
        moe_capacity_factor=float(max(args.moe_experts, 1)))

    rs = np.random.RandomState(0)
    toks = rs.randint(0, args.vocab,
                      (args.num_examples, args.seq_len)).astype("float32")
    labels = (3 * toks + 1) % args.vocab
    it = mx.io.NDArrayIter(toks, labels, batch_size=args.batch_size)

    mesh = create_mesh({"pipe": n_dev}, devices=jax.devices()[:n_dev])
    with mesh_scope(mesh):
        mod = mx.mod.Module(sym, context=mx.current_context(),
                            pipeline_stages=n_dev,
                            pipeline_microbatches=args.microbatches,
                            pipeline_schedule=args.schedule)
        mod.fit(it, num_epoch=args.num_epochs, optimizer="adam",
                kvstore="dist_tpu_sync",
                optimizer_params={"learning_rate": args.lr},
                initializer=mx.init.Xavier(),
                eval_metric=mx.metric.Perplexity(ignore_label=None))
        assert isinstance(mod._fused, PipelineTrainStep)
        ppl = dict(mod.score(
            it, mx.metric.Perplexity(ignore_label=None)))["perplexity"]
    print("final perplexity: %.4f (%d stages, %s schedule%s)"
          % (ppl, n_dev, args.schedule,
             ", MoE E%d" % args.moe_experts if args.moe_experts else ""))
    if ppl < 3.0:
        print("PIPELINE TRAINS OK")
        return 0
    print("PIPELINE DID NOT LEARN")
    return 1


if __name__ == "__main__":
    logging.basicConfig(level=logging.INFO)
    p = argparse.ArgumentParser(description="pipeline-parallel LM")
    p.add_argument("--stages", type=int, default=4)
    p.add_argument("--microbatches", type=int, default=4)
    p.add_argument("--schedule", choices=("1f1b", "gpipe"),
                   default="1f1b")
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--dim", type=int, default=32)
    p.add_argument("--vocab", type=int, default=16)
    p.add_argument("--seq-len", type=int, default=8)
    p.add_argument("--moe-experts", type=int, default=0)
    p.add_argument("--batch-size", type=int, default=16)
    p.add_argument("--num-epochs", type=int, default=12)
    p.add_argument("--lr", type=float, default=0.02)
    p.add_argument("--num-examples", type=int, default=64)
    sys.exit(main(p.parse_args()))
