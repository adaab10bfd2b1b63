"""NDArray — the imperative value type, backed by XLA.

TPU-native replacement for the reference's ``NDArray``
(``include/mxnet/ndarray.h:93``, ``src/ndarray/ndarray.cc``, Python
``python/mxnet/ndarray/ndarray.py``).

Design mapping (SURVEY.md §7 items 1-3):

* the reference's ``Chunk`` (storage handle + engine variable) becomes an
  immutable ``jax.Array`` reference that is **rebound** on mutation — a
  version chain instead of in-place writes.  JAX/XLA's async dispatch *is*
  the dependency engine: ops on the same buffer are ordered by data flow,
  and ``wait_to_read`` maps to ``jax.block_until_ready`` (reference
  ``WaitToRead``, ``ndarray.h:336``).
* every operator call goes through :func:`imperative_invoke` — the analogue
  of ``MXImperativeInvoke`` (``src/c_api/c_api_ndarray.cc:548``): gather
  input buffers, run the op's cached jitted executable, wrap outputs, write
  back functionally-threaded state (``mutable_inputs``), and record on the
  autograd tape when recording is active.
* ``context`` moves data with ``jax.device_put`` (reference ``CopyFromTo``
  with kCopyFromGPU/kCopyToGPU FnProperty, ``src/ndarray/ndarray.cc:499``).
"""
from __future__ import annotations

import numpy as _np

from ..base import MXNetError
from ..context import Context, current_context, cpu
from .. import random as _random
from ..ops import registry as _registry

__all__ = ["NDArray", "imperative_invoke", "array", "empty", "zeros", "ones",
           "full", "arange", "moveaxis", "concat", "save", "load", "waitall",
           "onehot_encode"]


def _as_jax(value, dtype=None, ctx=None):
    import jax

    dev = (ctx or current_context()).jax_device
    arr = _np.asarray(value, dtype=dtype if dtype else None)
    if arr.dtype == _np.float64 and dtype is None:
        arr = arr.astype(_np.float32)
    return jax.device_put(arr, dev)


class NDArray:
    """A multidimensional array on a device context.

    Mirrors the reference Python ``NDArray`` API surface: shape/dtype/size,
    ``asnumpy``/``asscalar``, arithmetic operators, indexing/assignment,
    ``copyto``/``as_in_context``, ``wait_to_read``, ``astype``, ``reshape``,
    ``T`` …  The backing buffer is an immutable ``jax.Array``; "mutation"
    rebinds ``_data`` and bumps ``_version`` (engine write-ordering made
    explicit).
    """

    __slots__ = ("_data", "_ctx", "_version", "_grad", "_grad_req",
                 "_tape_marked", "__weakref__")

    def __init__(self, data, ctx=None):
        self._data = data
        self._ctx = ctx or current_context()
        self._version = 0
        self._grad = None
        self._grad_req = None
        self._tape_marked = False

    # -- basic properties ---------------------------------------------------
    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return _np.dtype(self._data.dtype)

    @property
    def size(self):
        s = 1
        for d in self.shape:
            s *= d
        return s

    @property
    def ndim(self):
        return len(self.shape)

    @property
    def context(self):
        return self._ctx

    ctx = context

    @property
    def grad(self):
        """Gradient buffer attached by ``autograd.mark_variables`` /
        ``Parameter`` (reference: ``args_grad``)."""
        return self._grad

    @property
    def T(self):
        return transpose_nd(self)

    @property
    def handle(self):
        """The raw jax.Array (stands in for the C-ABI NDArrayHandle)."""
        return self._data

    stype = "default"

    def tostype(self, stype):
        """Convert storage type (reference ``NDArray.tostype``)."""
        if stype == "default":
            return self
        from .sparse import cast_storage

        return cast_storage(self, stype)

    def todense(self):
        return self

    # -- sync & host transfer ----------------------------------------------
    def wait_to_read(self):
        import jax

        jax.block_until_ready(self._data)

    wait_to_write = wait_to_read

    def asnumpy(self):
        self.wait_to_read()
        return _np.asarray(self._data)

    def asscalar(self):
        if self.size != 1:
            raise MXNetError("The current array is not a scalar")
        return self.asnumpy().reshape(())[()]

    def astype(self, dtype):
        return imperative_invoke("Cast", [self], {"dtype": _np.dtype(dtype).name})[0]

    def copy(self):
        return imperative_invoke("_copy", [self], {})[0]

    def copyto(self, other):
        import jax

        if isinstance(other, NDArray):
            other._set_data(jax.device_put(self._data, other._ctx.jax_device))
            return other
        if isinstance(other, Context):
            return NDArray(jax.device_put(self._data, other.jax_device), other)
        raise MXNetError("copyto target must be NDArray or Context")

    def as_in_context(self, ctx):
        if ctx == self._ctx:
            return self
        return self.copyto(ctx)

    def detach(self):
        from .. import autograd

        if autograd.is_recording():
            # route through BlockGrad so the tape records a stop_gradient —
            # sharing the raw buffer would let replay differentiate through
            # the "detached" value
            return imperative_invoke("BlockGrad", [self], {})[0]
        return NDArray(self._data, self._ctx)

    def clip(self, a_min, a_max):
        return imperative_invoke("clip", [self],
                                 {"a_min": a_min, "a_max": a_max})[0]

    # -- mutation (engine write semantics) ----------------------------------
    def _set_data(self, data):
        self._data = data
        self._version += 1

    def __setitem__(self, key, value):
        import jax.numpy as jnp

        if isinstance(value, NDArray):
            value = value._data
        if key is Ellipsis or key == slice(None):
            import jax

            # materialize on THIS array's context device: jnp.* default to
            # the default platform, which silently migrates a cpu-context
            # param to the accelerator on multi-platform hosts
            dev = self._ctx.jax_device
            if _np.isscalar(value):
                self._set_data(jax.device_put(
                    jnp.full(self.shape, value, self.dtype), dev))
            else:
                arr = _as_jax(value, self.dtype, self._ctx) \
                    if not hasattr(value, "dtype") or isinstance(value, _np.ndarray) else value
                self._set_data(jax.device_put(
                    jnp.broadcast_to(arr, self.shape).astype(self.dtype), dev))
            return
        if isinstance(value, _np.ndarray):
            value = _as_jax(value, self.dtype, self._ctx)
        self._set_data(self._data.at[key].set(value))

    def __getitem__(self, key):
        from .. import autograd

        if autograd.is_recording():
            # route the common cases through registered ops so indexing is
            # on the tape (raw buffer indexing would silently cut gradients)
            if isinstance(key, int):
                k = key % self.shape[0] if self.shape else key
                out = imperative_invoke(
                    "slice_axis", [self],
                    {"axis": 0, "begin": k, "end": k + 1})[0]
                return imperative_invoke(
                    "Reshape", [out], {"shape": self.shape[1:] or (1,)})[0]
            if isinstance(key, slice) and key.step in (None, 1):
                b = 0 if key.start is None else key.start
                e = self.shape[0] if key.stop is None else key.stop
                return imperative_invoke(
                    "slice_axis", [self],
                    {"axis": 0, "begin": b, "end": e})[0]
        out = self._data[key]
        return NDArray(out, self._ctx)

    def reshape(self, shape, **kwargs):
        if isinstance(shape, int):
            shape = (shape,)
        return imperative_invoke("Reshape", [self],
                                 {"shape": tuple(shape), **kwargs})[0]

    def expand_dims(self, axis):
        return imperative_invoke("expand_dims", [self], {"axis": axis})[0]

    def flatten(self):
        return imperative_invoke("Flatten", [self], {})[0]

    def attach_grad(self, grad_req="write"):
        """Allocate gradient buffer and mark for autograd (Gluon-style;
        reference ``python/mxnet/ndarray/ndarray.py`` + autograd)."""
        from .. import autograd

        grad = zeros(self.shape, self._ctx, dtype=self.dtype)
        autograd.mark_variables([self], [grad], grad_req)

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        from .. import autograd

        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph=retain_graph, train_mode=train_mode)

    def __getattr__(self, name):
        # any registered op is available as a method with self as first
        # input (the reference generates these on the NDArray class from
        # the registry, python/mxnet/ndarray/op.py)
        if name.startswith("_") or not _registry.exists(name):
            raise AttributeError(
                "'NDArray' object has no attribute %r" % name)

        def method(*args, **kwargs):
            bad = [a for a in args if not isinstance(a, NDArray)]
            if bad:
                raise TypeError(
                    "NDArray.%s: pass scalar attributes as keywords "
                    "(got positional %r)" % (name, bad[0]))
            inputs = [self] + list(args)
            res = imperative_invoke(name, inputs, kwargs)
            return res[0] if len(res) == 1 else res

        method.__name__ = name
        return method

    # -- python protocol ----------------------------------------------------
    def __len__(self):
        return self.shape[0] if self.shape else 0

    def __repr__(self):
        return "%s\n<NDArray %s @%s>" % (
            str(self.asnumpy()), "x".join(str(s) for s in self.shape), self._ctx)

    def __bool__(self):
        if self.size != 1:
            raise MXNetError("ambiguous truth value of multi-element NDArray")
        return bool(self.asscalar())

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    # arithmetic — routed through the registry so autograd records them
    def _binary(self, other, op, scalar_op, rop=False):
        if isinstance(other, NDArray):
            a, b = (other, self) if rop else (self, other)
            return imperative_invoke(op, [a, b], {})[0]
        if rop and scalar_op.startswith("_r"):
            return imperative_invoke(scalar_op, [self], {"scalar": float(other)})[0]
        return imperative_invoke(scalar_op, [self], {"scalar": float(other)})[0]

    def __add__(self, o): return self._binary(o, "elemwise_add", "_plus_scalar")
    def __radd__(self, o): return self._binary(o, "elemwise_add", "_plus_scalar")
    def __sub__(self, o): return self._binary(o, "elemwise_sub", "_minus_scalar")
    def __rsub__(self, o): return self._binary(o, "elemwise_sub", "_rminus_scalar", rop=True)
    def __mul__(self, o): return self._binary(o, "elemwise_mul", "_mul_scalar")
    def __rmul__(self, o): return self._binary(o, "elemwise_mul", "_mul_scalar")
    def __truediv__(self, o): return self._binary(o, "elemwise_div", "_div_scalar")
    def __rtruediv__(self, o): return self._binary(o, "elemwise_div", "_rdiv_scalar", rop=True)
    def __mod__(self, o): return self._binary(o, "elemwise_mod", "_mod_scalar")
    def __rmod__(self, o): return self._binary(o, "elemwise_mod", "_rmod_scalar", rop=True)
    def __pow__(self, o): return self._binary(o, "elemwise_power", "_power_scalar")
    def __rpow__(self, o): return self._binary(o, "elemwise_power", "_rpower_scalar", rop=True)
    def __neg__(self): return imperative_invoke("negative", [self], {})[0]
    def __abs__(self): return imperative_invoke("abs", [self], {})[0]
    def __eq__(self, o): return self._binary(o, "elemwise_equal", "_equal_scalar")
    def __ne__(self, o): return self._binary(o, "elemwise_not_equal", "_not_equal_scalar")
    def __gt__(self, o): return self._binary(o, "elemwise_greater", "_greater_scalar")
    def __ge__(self, o): return self._binary(o, "elemwise_greater_equal", "_greater_equal_scalar")
    def __lt__(self, o): return self._binary(o, "elemwise_lesser", "_lesser_scalar")
    def __le__(self, o): return self._binary(o, "elemwise_lesser_equal", "_lesser_equal_scalar")
    __hash__ = object.__hash__

    def __iadd__(self, o):
        out = self.__add__(o)
        self._set_data(out._data)
        return self

    def __isub__(self, o):
        out = self.__sub__(o)
        self._set_data(out._data)
        return self

    def __imul__(self, o):
        out = self.__mul__(o)
        self._set_data(out._data)
        return self

    def __itruediv__(self, o):
        out = self.__truediv__(o)
        self._set_data(out._data)
        return self


def transpose_nd(arr):
    return imperative_invoke("transpose", [arr], {})[0]


# ---------------------------------------------------------------------------
# the imperative invoke path (≈ MXImperativeInvoke / ImperativeInvokeImpl)
# ---------------------------------------------------------------------------

def imperative_invoke(op_name, inputs, attrs, out=None):
    """Run one registered op imperatively.

    Returns a list of output NDArrays.  Handles: rng key injection, train
    mode, functional write-back of ``mutable_inputs``, ``out=`` targets, and
    autograd tape recording (reference
    ``AutogradRuntime::RecordImperativeFCompute``, ``src/ndarray/autograd.cc:104``).
    """
    from .. import autograd

    op = _registry.get(op_name)
    attrs = dict(attrs)
    op.validate_attrs(attrs)

    if op.uses_train_mode and "__is_train__" not in attrs:
        attrs["__is_train__"] = autograd.is_training()

    in_arrays = [x._data if isinstance(x, NDArray) else _as_jax(x)
                 for x in inputs]
    if op.spans_mesh is not None and op.spans_mesh(attrs):
        # the compute holds a shard_map over the active mesh: inputs must
        # live replicated on ALL mesh devices, not committed to one
        from ..parallel import current_mesh

        mesh = current_mesh()
        if mesh is not None:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec

            repl = NamedSharding(mesh, PartitionSpec())
            in_arrays = [jax.device_put(a, repl) for a in in_arrays]
    rng_key = None
    if op.needs_rng:
        rng_key = _random.next_key()
        in_arrays = [rng_key] + in_arrays

    frozen = _registry.FrozenAttrs(attrs)
    results = _registry.invoke(op, in_arrays, frozen)

    n_out = op.count_outputs(frozen)
    outputs = results[:n_out]
    updates = results[n_out:]

    ctx = inputs[0]._ctx if inputs and isinstance(inputs[0], NDArray) \
        else current_context()

    # functional state write-back (≈ FMutateInputs)
    for idx, new_val in zip(op.mutable_inputs, updates):
        tgt = inputs[idx]
        if isinstance(tgt, NDArray):
            tgt._set_data(new_val)

    if out is not None:
        out_list = out if isinstance(out, (list, tuple)) else [out]
        for tgt, val in zip(out_list, outputs):
            tgt._set_data(val)
        out_nd = list(out_list)
    else:
        out_nd = [NDArray(o, ctx) for o in outputs]

    if autograd.is_recording():
        autograd._record(op, frozen, inputs, in_arrays, out_nd, outputs,
                         rng_key)
    return out_nd


# ---------------------------------------------------------------------------
# creation / io helpers (reference ndarray.py module functions)
# ---------------------------------------------------------------------------

def array(source_array, ctx=None, dtype=None):
    if isinstance(source_array, NDArray):
        source_array = source_array.asnumpy()
    return NDArray(_as_jax(source_array, dtype, ctx), ctx or current_context())


def empty(shape, ctx=None, dtype="float32"):
    return zeros(shape, ctx, dtype)


def zeros(shape, ctx=None, dtype="float32", **kwargs):
    import jax

    if isinstance(shape, int):
        shape = (shape,)
    ctx = ctx or current_context()
    return NDArray(jax.device_put(
        _np.zeros(shape, dtype or "float32"), ctx.jax_device), ctx)


def ones(shape, ctx=None, dtype="float32", **kwargs):
    import jax

    if isinstance(shape, int):
        shape = (shape,)
    ctx = ctx or current_context()
    return NDArray(jax.device_put(
        _np.ones(shape, dtype or "float32"), ctx.jax_device), ctx)


def full(shape, val, ctx=None, dtype="float32"):
    import jax

    if isinstance(shape, int):
        shape = (shape,)
    ctx = ctx or current_context()
    return NDArray(jax.device_put(
        _np.full(shape, val, dtype or "float32"), ctx.jax_device), ctx)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype="float32"):
    out = _np.arange(start, stop, step).astype(dtype or "float32")
    if repeat > 1:
        out = _np.repeat(out, repeat)
    return array(out, ctx, dtype)


def moveaxis(tensor, source, destination):
    axes = list(range(tensor.ndim))
    axes.remove(source % tensor.ndim)
    axes.insert(destination % tensor.ndim, source % tensor.ndim)
    return imperative_invoke("transpose", [tensor], {"axes": tuple(axes)})[0]


def concat(*data, **kwargs):
    dim = kwargs.get("dim", 1)
    if len(data) == 1 and isinstance(data[0], (list, tuple)):
        data = data[0]
    return imperative_invoke("Concat", list(data), {"dim": dim})[0]


def onehot_encode(indices, out):
    depth = out.shape[1]
    res = imperative_invoke("one_hot", [indices], {"depth": depth})[0]
    out._set_data(res._data)
    return out


def waitall():
    """Block until all pending computation completes (reference
    ``MXNDArrayWaitAll``).  XLA dispatch is async exactly like the
    engine; this is where deferred execution errors surface, so
    exceptions propagate to the caller (the reference engine's fatal
    handler contract, ``threaded_engine.h:347``)."""
    import jax

    # effects_barrier waits for effectful computations only; an ordinary
    # async-dispatched step is awaited through the arrays it produced
    jax.effects_barrier()
    for a in jax.live_arrays():
        if not a.is_deleted():
            a.block_until_ready()


# -- save/load: the reference's binary NDArray dict format is replaced by
#    the portable .npz container (documented divergence; the *API* —
#    nd.save/nd.load round-tripping dicts or lists — is identical to
#    python/mxnet/ndarray/utils.py save/load).

def save(fname, data):
    if isinstance(data, NDArray):
        data = [data]
    if isinstance(data, dict):
        _np.savez(fname, **{k: v.asnumpy() for k, v in data.items()})
    else:
        _np.savez(fname, **{"__list_%d" % i: v.asnumpy()
                            for i, v in enumerate(data)})


def load(fname):
    with _np.load(fname if fname.endswith(".npz") else fname + ".npz"
                  if not _is_file(fname) else fname, allow_pickle=False) as f:
        keys = list(f.keys())
        if keys and all(k.startswith("__list_") for k in keys):
            return [array(f[k]) for k in sorted(
                keys, key=lambda s: int(s.split("_")[-1]))]
        return {k: array(f[k]) for k in keys}


def _is_file(fname):
    import os

    return os.path.exists(fname)
