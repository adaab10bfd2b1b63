"""Shared training harness for the image-classification examples
(reference ``example/image-classification/common/fit.py:108-205``): one
``fit(args, network, data_loader)`` that wires kvstore, optimizer,
LR schedule, checkpointing, and monitoring around ``Module.fit``.
"""
from __future__ import annotations

import argparse
import logging
import os

import mxnet_tpu as mx


def add_fit_args(parser):
    train = parser.add_argument_group("Training")
    train.add_argument("--network", type=str, default="mlp")
    train.add_argument("--num-layers", type=int, default=None)
    train.add_argument("--gpus", type=str, default=None,
                       help="ignored on TPU; kept for script parity")
    train.add_argument("--kv-store", type=str, default="local")
    train.add_argument("--num-epochs", type=int, default=10)
    train.add_argument("--lr", type=float, default=0.1)
    train.add_argument("--lr-factor", type=float, default=0.1)
    train.add_argument("--lr-step-epochs", type=str, default=None)
    train.add_argument("--optimizer", type=str, default="sgd")
    train.add_argument("--mom", type=float, default=0.9)
    train.add_argument("--wd", type=float, default=1e-4)
    train.add_argument("--batch-size", type=int, default=128)
    train.add_argument("--disp-batches", type=int, default=20)
    train.add_argument("--model-prefix", type=str, default=None)
    train.add_argument("--load-epoch", type=int, default=None)
    train.add_argument("--monitor", type=int, default=0)
    train.add_argument("--param-sharding", type=str, default=None,
                       choices=(None, "fsdp", "tp"),
                       help="TPU-native: shard parameters over the mesh")
    return train


def _lr_scheduler(args, epoch_size):
    if not args.lr_step_epochs:
        return args.lr, None
    begin = args.load_epoch or 0
    steps = [int(e) for e in args.lr_step_epochs.split(",")]
    lr = args.lr
    for s in steps:
        if begin >= s:
            lr *= args.lr_factor
    remaining = [epoch_size * (s - begin) for s in steps if s > begin]
    if not remaining:
        return lr, None
    return lr, mx.lr_scheduler.MultiFactorScheduler(
        step=remaining, factor=args.lr_factor)


def fit(args, network, data_loader):
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)-15s %(message)s")
    kv = mx.kv.create(args.kv_store)
    train, val = data_loader(args, kv)

    epoch_size = getattr(args, "num_examples", 50000) // args.batch_size
    lr, sched = _lr_scheduler(args, epoch_size)

    checkpoint = None
    arg_params = aux_params = None
    if args.model_prefix:
        checkpoint = mx.callback.do_checkpoint(args.model_prefix)
        if args.load_epoch is not None:
            network, arg_params, aux_params = mx.model.load_checkpoint(
                args.model_prefix, args.load_epoch)

    mod = mx.mod.Module(network, context=mx.current_context())
    optimizer_params = {"learning_rate": lr, "wd": args.wd}
    if args.optimizer in ("sgd", "nag"):
        optimizer_params["momentum"] = args.mom
    if sched is not None:
        optimizer_params["lr_scheduler"] = sched

    monitor = mx.Monitor(args.disp_batches, pattern=".*") \
        if args.monitor > 0 else None

    mod.fit(train,
            param_sharding=args.param_sharding,
            compute_dtype=getattr(args, "compute_dtype", None),
            eval_data=val,
            eval_metric=["accuracy"],
            kvstore=kv,
            optimizer=args.optimizer,
            optimizer_params=optimizer_params,
            initializer=mx.init.Xavier(rnd_type="gaussian",
                                       factor_type="in", magnitude=2),
            arg_params=arg_params,
            aux_params=aux_params,
            begin_epoch=args.load_epoch or 0,
            num_epoch=args.num_epochs,
            batch_end_callback=mx.callback.Speedometer(
                args.batch_size, args.disp_batches),
            epoch_end_callback=checkpoint,
            monitor=monitor)
    return mod
