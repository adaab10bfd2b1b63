"""Device mesh management.

The mesh is this framework's "cluster object": where the reference wires
dp via kvstore types and mp via ``group2ctx`` device placement, here both
are axes of one ``jax.sharding.Mesh`` ("data", "model", "pipe", "seq",
"expert") and XLA lays collectives onto ICI neighbors (SURVEY.md §7
item 7; scaling-book recipe: pick a mesh, annotate shardings, let XLA
insert collectives).
"""
from __future__ import annotations

import threading
from contextlib import contextmanager

import numpy as np

from ..base import MXNetError

__all__ = ["create_mesh", "current_mesh", "set_mesh", "mesh_scope",
           "data_axis_size", "axis_size"]

_state = threading.local()

# canonical axis order: batch-like axes first (fastest-varying ICI ring
# gets the highest-traffic collective)
AXIS_ORDER = ("data", "fsdp", "seq", "pipe", "model", "expert")


def create_mesh(axes=None, devices=None):
    """Build a ``jax.sharding.Mesh``.

    ``axes``: dict axis-name -> size (e.g. ``{"data": 4, "model": 2}``);
    -1 for one axis means "all remaining devices".  Defaults to pure data
    parallelism over every visible device.
    """
    import jax
    from jax.sharding import Mesh

    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if axes is None:
        axes = {"data": n}
    axes = dict(axes)
    # resolve -1
    known = 1
    wild = None
    for k, v in axes.items():
        if v == -1:
            if wild is not None:
                raise MXNetError("only one mesh axis may be -1")
            wild = k
        else:
            known *= v
    if wild is not None:
        if n % known:
            raise MXNetError("cannot infer axis %r: %d devices not divisible "
                             "by %d" % (wild, n, known))
        axes[wild] = n // known
        known *= axes[wild]
    if known != n:
        raise MXNetError("mesh axes %r use %d devices but %d are available"
                         % (axes, known, n))
    names = sorted(axes, key=lambda a: AXIS_ORDER.index(a)
                   if a in AXIS_ORDER else len(AXIS_ORDER))
    shape = tuple(axes[a] for a in names)
    dev_array = np.array(devices).reshape(shape)
    return Mesh(dev_array, names)


def set_mesh(mesh):
    """Set the process-wide active mesh (imperative ops and KVStore
    consult it)."""
    _state.mesh = mesh


def current_mesh():
    return getattr(_state, "mesh", None)


@contextmanager
def mesh_scope(mesh):
    prev = current_mesh()
    set_mesh(mesh)
    try:
        yield mesh
    finally:
        set_mesh(prev)


def axis_size(name):
    mesh = current_mesh()
    if mesh is None or name not in mesh.shape:
        return 1
    return mesh.shape[name]


def data_axis_size():
    return axis_size("data")


def init_distributed(coordinator=None, num_processes=None,
                     process_id=None):
    """Join a multi-process job (the worker-side counterpart of
    ``tools/launch.py``; the reference's ps-lite rendezvous role is
    played by ``jax.distributed``'s coordination service).

    Arguments default from the launcher env: ``MXNET_COORDINATOR``,
    ``MXNET_NUM_WORKERS``, ``MXNET_WORKER_ID``.  No-op when those are
    absent (single-process run).
    """
    import os

    import jax

    coordinator = coordinator or os.environ.get("MXNET_COORDINATOR")
    if coordinator is None:
        return False
    # already joined (e.g. the worker called jax.distributed.initialize
    # itself before any mxnet_tpu entry): a second initialize would
    # raise "must be called before any JAX calls", not "already"
    if jax.distributed.is_initialized():
        return True
    if num_processes is None:
        num_processes = int(os.environ.get("MXNET_NUM_WORKERS", "1"))
    if process_id is None:
        process_id = int(os.environ.get("MXNET_WORKER_ID", "0"))
    try:
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=num_processes,
                                   process_id=process_id)
    except RuntimeError as e:
        # idempotent: callers (kvstore.create for dist types, user
        # scripts, the CI dist worker) may race to initialize
        if "already" not in str(e).lower():
            raise
    return True
