#!/usr/bin/env python
"""bandwidth.py — measure allreduce/collective bandwidth over the mesh.

Reference: ``tools/bandwidth/measure.py`` (kvstore push/pull bandwidth —
the tool BASELINE.md points at for the unpublished comm numbers).  Here
the measured primitive is the XLA collective itself: psum over the
'data' axis of the active mesh, swept over sizes, reporting algorithmic
bus bandwidth (2(n-1)/n factor for ring allreduce).

Usage: python tools/bandwidth.py [--sizes-mb 1,4,16,64] [--iters 20]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sizes-mb", default="1,4,16,64")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--cpu-mesh", type=int, default=0,
                    help="run on a virtual N-device CPU mesh (validates "
                         "the collective path without N chips; numbers "
                         "are host-memory, not ICI)")
    ap.add_argument("--dcn", type=int, default=0,
                    help="measure the multi-PROCESS (DCN-branch) "
                         "allreduce with N local jax.distributed "
                         "workers (localhost transport)")
    ap.add_argument("--dcn-worker", default="",
                    help=argparse.SUPPRESS)  # internal: coord,nproc,rank
    args = ap.parse_args()

    if args.dcn and not args.dcn_worker:
        return _dcn_launch(args)
    if args.dcn_worker:
        return _dcn_worker(args)

    if args.cpu_mesh:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            " --xla_force_host_platform_device_count=%d" % args.cpu_mesh)

    import jax

    if args.cpu_mesh:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.parallel import create_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P

    devices = jax.devices()
    n = len(devices)
    mesh = create_mesh({"data": n}, devices=devices)
    print("devices: %d x %s" % (n, getattr(devices[0], "device_kind",
                                           "?")))

    for mb in [float(x) for x in args.sizes_mb.split(",")]:
        elems = int(mb * (1 << 20) / 4)
        per_dev = -(-elems // n)
        x = jax.device_put(
            np.ones((n * per_dev,), "float32"),
            NamedSharding(mesh, P("data")))

        fn = jax.jit(jax.shard_map(
            lambda v: jax.lax.psum(v, "data"), mesh=mesh,
            in_specs=P("data"), out_specs=P("data")))
        out = fn(x)
        # a host fetch of one element waits for the whole result
        float(np.asarray(out.addressable_shards[0].data[0]))
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = fn(out)
        float(np.asarray(out.addressable_shards[0].data[0]))
        dt = (time.perf_counter() - t0) / args.iters
        nbytes = elems * 4
        busbw = 2 * (n - 1) / n * nbytes / dt
        print("size %8.1f MB  time %8.3f ms  busbw %8.2f GB/s"
              % (mb, dt * 1e3, busbw / 1e9))


def _dcn_launch(args):
    import socket
    import subprocess

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    coord = "127.0.0.1:%d" % s.getsockname()[1]
    s.close()
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    procs = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__),
         "--sizes-mb", args.sizes_mb, "--iters", str(args.iters),
         "--dcn-worker", "%s,%d,%d" % (coord, args.dcn, r)],
        env=env) for r in range(args.dcn)]
    rc = 0
    for p in procs:
        rc |= p.wait()
    return rc


def _dcn_worker(args):
    coord, nproc, rank = args.dcn_worker.split(",")
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")
    # jax CPU clients reject cross-process programs unless a
    # collectives implementation is chosen before backend creation
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=int(nproc),
                               process_id=int(rank))
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.parallel.collectives import allreduce_nd

    n = jax.process_count()
    for mb in [float(x) for x in args.sizes_mb.split(",")]:
        elems = int(mb * (1 << 20) / 4)
        arr = mx.nd.array(np.ones((elems,), "float32"))
        allreduce_nd(arr)  # warm the path
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = allreduce_nd(arr)
        out.asnumpy()
        dt = (time.perf_counter() - t0) / args.iters
        nbytes = elems * 4
        # allgather-based: each process receives (n-1) remote shards
        busbw = (n - 1) * nbytes / dt
        if int(rank) == 0:
            print("DCN %dproc size %8.1f MB  time %8.3f ms  "
                  "busbw %8.2f GB/s" % (n, mb, dt * 1e3, busbw / 1e9))
    return 0


if __name__ == "__main__":
    sys.exit(main() or 0)
