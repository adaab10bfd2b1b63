"""Device context.

Replaces the reference's ``python/mxnet/context.py`` (``Context``,
``mx.cpu()``/``mx.gpu()``, thread-local default).  The TPU build adds
``mx.tpu()`` as the accelerator context — the north-star API from
BASELINE.json — and maps a context to a concrete ``jax.Device``.

Unlike the reference (where a context selects a CUDA device and a worker
thread pool, ``src/engine/threaded_engine_perdevice.cc``), here a context
selects a JAX device for ``jax.device_put`` / compilation targets; XLA owns
streams and async dispatch.
"""
from __future__ import annotations

import threading

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context"]


class Context:
    """Device context, API-compatible with the reference ``Context``
    (``python/mxnet/context.py:23``): ``devtype2mask``-style device types,
    equality, ``with ctx:`` default scoping."""

    devtype2str = {1: "cpu", 2: "tpu", 3: "cpu_pinned", 4: "gpu"}
    devstr2type = {"cpu": 1, "tpu": 2, "cpu_pinned": 3, "gpu": 4}
    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            self.device_typeid = Context.devstr2type[device_type]
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self):
        return Context.devtype2str[self.device_typeid]

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __str__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    __repr__ = __str__

    def __enter__(self):
        self._old_ctx = getattr(Context._default_ctx, "value", None)
        Context._default_ctx.value = self
        return self

    def __exit__(self, ptype, value, trace):
        Context._default_ctx.value = self._old_ctx

    # -- JAX mapping ---------------------------------------------------
    @property
    def jax_device(self):
        """The concrete ``jax.Device`` this context denotes.

        Always a process-LOCAL (addressable) device: under multi-process
        ``jax.distributed``, ``jax.devices()`` is the global list and
        ``device_put`` onto another process's device would silently
        create a non-addressable global array (reference semantics: a
        Context names a device of THIS worker)."""
        import jax

        # first device lookup doubles as the lazy hook for the
        # persistent compilation cache: anything about to jit resolves a
        # device first, so the cache config lands before the first trace
        from .compile_cache import ensure_initialized

        ensure_initialized()
        kind = self.device_type
        if kind in ("cpu", "cpu_pinned"):
            # every cpu(i) is the same host memory in the reference, so
            # the id wraps over whatever host devices exist
            devs = jax.local_devices(backend="cpu") if _has_platform("cpu") \
                else jax.local_devices()
            return devs[self.device_id % len(devs)]
        # tpu (and the gpu alias): the accelerator or nothing — a run
        # that names the chip must not pass on another device
        devs = [d for d in jax.local_devices() if d.platform == "tpu"]
        if not 0 <= self.device_id < len(devs):
            raise MXNetError(
                "%s names TPU device %d but this process has %d TPU "
                "device(s); jax.devices() holds %s"
                % (self, self.device_id, len(devs), describe_devices()))
        return devs[self.device_id]


def describe_devices():
    """What ``jax.devices()`` holds, as error messages and the chip
    scripts name it: e.g. ``8 x cpu (cpu)`` or ``1 x tpu (TPU v5 lite)``."""
    import collections

    import jax

    kinds = collections.Counter(
        (d.platform, d.device_kind) for d in jax.devices())
    return ", ".join("%d x %s (%s)" % (n, platform, kind)
                     for (platform, kind), n in sorted(kinds.items()))


def _has_platform(name):
    import jax

    try:
        return bool(jax.devices(name))
    except RuntimeError:
        return False


def cpu(device_id=0):
    """A CPU context (reference ``mx.cpu()``)."""
    return Context("cpu", device_id)


def tpu(device_id=0):
    """A TPU context — the accelerator context of this framework
    (the ``mx.tpu()`` from the north star in BASELINE.json).  Resolving
    it (``.jax_device``) raises a typed ``MXNetError`` when device
    ``device_id`` is not a TPU of this process: it never lands on a CPU
    and never wraps onto another chip.  Code that means "the default
    device" says ``mx.current_context()``."""
    return Context("tpu", device_id)


def gpu(device_id=0):
    """Compatibility alias: reference scripts that say ``mx.gpu(i)`` get the
    accelerator (TPU) so `--gpus` scripts run unmodified on a TPU host.
    Like ``mx.tpu(i)`` it raises when resolved on a host with no TPU."""
    return Context("tpu", device_id)


def current_context():
    """The thread-local default context (reference ``current_context()``):
    ``tpu(0)`` when the default backend is a TPU, ``cpu(0)`` on a CPU
    host (the documented CPU mode for tests and rehearsals)."""
    ctx = getattr(Context._default_ctx, "value", None)
    if ctx is None:
        ctx = Context("tpu", 0) if _accelerator_present() else Context("cpu", 0)
        Context._default_ctx.value = ctx
    return ctx


def _accelerator_present():
    import jax

    return jax.default_backend() == "tpu"
