"""Custom operators defined in Python.

Reference: ``python/mxnet/operator.py`` + ``src/operator/custom/custom.cc``
(SURVEY.md §2.1 "Custom-op bridge"): user subclasses ``CustomOp`` (compute)
and ``CustomOpProp`` (metadata), registers the prop under a name, and
invokes ``mx.nd.Custom(..., op_type=name)`` / ``mx.sym.Custom(...)``.  In
the reference the callbacks run on the engine's ``kAsync`` path.

TPU-native form: the ``Custom`` registry op lowers to
``jax.pure_callback`` — XLA calls back onto the host mid-graph — wrapped
in ``jax.custom_vjp`` so the user's ``backward`` supplies the gradient.
It works imperatively, inside Symbol graphs, under the split Module path,
AND inside the fused train step (the callback compiles into the XLA
program; each step still pays one host round-trip per custom op, so keep
them off the hot path for peak throughput).

Divergences (documented):
* one ``CustomOp`` instance is created per callback invocation, so ops
  must be stateless between calls (the reference creates one per bound
  executor);
* auxiliary states are not supported;
* ``ctx`` passed to ``create_operator`` is the host CPU context.
"""
from __future__ import annotations

import functools

import numpy as np

from .base import MXNetError

__all__ = ["CustomOp", "CustomOpProp", "register", "get_prop_class"]

_CUSTOM_PROPS = {}


class CustomOp:
    """Base class for the compute part (reference ``CustomOp``)."""

    def forward(self, is_train, req, in_data, out_data, aux):
        raise NotImplementedError

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        raise NotImplementedError

    def assign(self, dst, req, src):
        """Write ``src`` into ``dst`` honoring the grad request."""
        if req == "null":
            return
        if req in ("write", "inplace"):
            dst[:] = src
        elif req == "add":
            dst[:] = dst + src
        else:
            raise MXNetError("unknown req %r" % req)


class CustomOpProp:
    """Base class for the metadata part (reference ``CustomOpProp``)."""

    def __init__(self, need_top_grad=True):
        self.need_top_grad_ = need_top_grad

    def infer_shape(self, in_shape):
        return in_shape, (in_shape[0],) * len(self.list_outputs()), ()

    def infer_type(self, in_type):
        return in_type, (in_type[0],) * len(self.list_outputs()), ()

    def list_arguments(self):
        return ["data"]

    def list_outputs(self):
        return ["output"]

    def list_auxiliary_states(self):
        return []

    def need_top_grad(self):
        return self.need_top_grad_

    def declare_backward_dependency(self, out_grad, in_data, out_data):
        deps = []
        if self.need_top_grad():
            deps.extend(out_grad)
        deps.extend(in_data)
        deps.extend(out_data)
        return deps

    def create_operator(self, ctx, in_shapes, in_dtypes):
        raise NotImplementedError


def register(reg_name):
    """Register a CustomOpProp subclass under ``op_type`` (reference
    ``mx.operator.register``)."""
    def _do(prop_cls):
        if not issubclass(prop_cls, CustomOpProp):
            raise MXNetError("register expects a CustomOpProp subclass")
        _CUSTOM_PROPS[reg_name] = prop_cls
        return prop_cls

    return _do


def get_prop_class(op_type):
    try:
        return _CUSTOM_PROPS[op_type]
    except KeyError:
        raise MXNetError(
            "custom op %r is not registered (known: %s)"
            % (op_type, sorted(_CUSTOM_PROPS))) from None


def _make_prop(attrs):
    """Instantiate the prop with the user's string kwargs (the reference
    passes all attrs as strings to the prop constructor)."""
    kwargs = {k: v for k, v in attrs.items()
              if k not in ("op_type",) and not k.startswith("__")}
    return get_prop_class(attrs["op_type"])(**kwargs)


def _custom_num_outputs(attrs):
    return len(_make_prop(attrs).list_outputs())


def _custom_compute(attrs, *inputs):
    """The Custom registry op (reference ``custom.cc:36``), two tiers:

    1. **Device path (default)**: the user's ``forward``/``backward``
       are CALLED DURING TRACING with NDArray shims over the traced
       values — custom ops written with ``mx.nd`` operations (which ARE
       jax computations) compile straight into the surrounding XLA
       program and run on the accelerator, no host round-trip.  This is
       the TPU-native answer to the reference's ``FnProperty::kAsync``
       callback scheduling.
    2. **Host-callback fallback**: ops that materialize numpy
       (``.asnumpy()``) cannot trace; they raise a concretization error
       and fall back to ``jax.pure_callback`` + ``custom_vjp`` — every
       call then leaves the device for the host and returns, a round
       trip per op per step.  ``MXNET_CUSTOM_OP_CALLBACK=1`` forces
       this tier.
    """
    import jax

    if "op_type" not in attrs:
        raise MXNetError("Custom needs an op_type attr")
    prop = _make_prop(attrs)
    if prop.list_auxiliary_states():
        raise MXNetError("Custom ops with auxiliary states are not "
                         "supported on the TPU build")
    is_train = bool(attrs.get("__is_train__", False))
    n_in = len(inputs)
    in_shapes = [tuple(x.shape) for x in inputs]
    in_dtypes = [np.dtype(x.dtype).name for x in inputs]
    shape_res = prop.infer_shape([list(s) for s in in_shapes])
    out_shapes = [tuple(s) for s in shape_res[1]]
    type_res = prop.infer_type(list(in_dtypes))
    out_dtypes = list(type_res[1])
    out_avals = [jax.ShapeDtypeStruct(s, np.dtype(d))
                 for s, d in zip(out_shapes, out_dtypes)]
    in_avals = [jax.ShapeDtypeStruct(s, np.dtype(d))
                for s, d in zip(in_shapes, in_dtypes)]

    from .ndarray import array, zeros

    def _new_op():
        from .context import cpu

        return prop.create_operator(cpu(), [list(s) for s in in_shapes],
                                    list(in_dtypes))

    # ---- tier 1: trace the user code into the XLA program -------------
    from .base import get_env

    if not get_env("MXNET_CUSTOM_OP_CALLBACK", 0, int):
        import jax.numpy as jnp

        from .ndarray import NDArray

        from . import autograd as _ag

        def traced_forward(*xs):
            op = _new_op()
            in_data = [NDArray(jnp.asarray(x)) for x in xs]
            out_data = [NDArray(jnp.zeros(s, np.dtype(d)))
                        for s, d in zip(out_shapes, out_dtypes)]
            # the op's internals run INSIDE this trace; recording them
            # on the imperative tape would leak tracers (the Custom
            # node itself is what the tape sees)
            with _ag.pause():
                op.forward(is_train=is_train,
                           req=["write"] * len(out_data),
                           in_data=in_data, out_data=out_data, aux=[])
            return tuple(o._data.astype(a.dtype)
                         for o, a in zip(out_data, out_avals))

        def traced_backward(cts, xs, outs):
            op = _new_op()
            in_grad = [NDArray(jnp.zeros(s, np.dtype(d)))
                       for s, d in zip(in_shapes, in_dtypes)]
            with _ag.pause():
                op.backward(
                    req=["write"] * n_in,
                    out_grad=[NDArray(jnp.asarray(g)) for g in cts],
                    in_data=[NDArray(jnp.asarray(x)) for x in xs],
                    out_data=[NDArray(jnp.asarray(o)) for o in outs],
                    in_grad=in_grad, aux=[])
            return tuple(g._data.astype(a.dtype)
                         for g, a in zip(in_grad, in_avals))

        device_ok = True
        try:
            # probe abstractly FIRST: a half-traced user forward must
            # not leak partial effects into the real trace
            jax.eval_shape(traced_forward, *in_avals)
        except Exception:  # noqa: BLE001 — any probe failure: host tier
            device_ok = False
        if device_ok:
            try:
                jax.eval_shape(traced_backward, out_avals, in_avals,
                               out_avals)
            except jax.errors.ConcretizationTypeError:
                # host-bound backward (.asnumpy() etc. — covers the
                # TracerArrayConversion subclass): the whole op takes
                # the callback tier so gradients stay available
                device_ok = False
            except Exception:  # noqa: BLE001
                # user error (e.g. forward-only op: backward raises
                # NotImplementedError) — keep the device tier; the
                # error surfaces if/when gradients are requested,
                # matching the reference contract
                pass
        if device_ok:
            @jax.custom_vjp
            def run_traced(*xs):
                return traced_forward(*xs)

            def traced_fwd_rule(*xs):
                outs = traced_forward(*xs)
                return outs, (xs, outs)

            def traced_bwd_rule(res, cts):
                xs, outs = res
                return traced_backward(tuple(cts), xs, outs)

            run_traced.defvjp(traced_fwd_rule, traced_bwd_rule)
            return run_traced(*inputs)

    def host_forward(*np_in):
        op = _new_op()
        in_data = [array(np.asarray(x)) for x in np_in]
        out_data = [zeros(s) for s in out_shapes]
        op.forward(is_train=is_train, req=["write"] * len(out_data),
                   in_data=in_data, out_data=out_data, aux=[])
        return tuple(np.asarray(o.asnumpy(), dtype=a.dtype)
                     for o, a in zip(out_data, out_avals))

    def host_backward(*np_args):
        ograds = [np.asarray(x) for x in np_args[:len(out_shapes)]]
        ins = [np.asarray(x) for x in
               np_args[len(out_shapes):len(out_shapes) + n_in]]
        outs = [np.asarray(x) for x in np_args[len(out_shapes) + n_in:]]
        op = _new_op()
        in_grad = [zeros(s) for s in in_shapes]
        op.backward(req=["write"] * n_in,
                    out_grad=[array(g) for g in ograds],
                    in_data=[array(x) for x in ins],
                    out_data=[array(x) for x in outs],
                    in_grad=in_grad, aux=[])
        return tuple(np.asarray(g.asnumpy(), dtype=a.dtype)
                     for g, a in zip(in_grad, in_avals))

    @jax.custom_vjp
    def run(*xs):
        return jax.pure_callback(host_forward, tuple(out_avals), *xs,
                                 vmap_method="sequential")

    def run_fwd(*xs):
        outs = run(*xs)
        return outs, (xs, outs)

    def run_bwd(res, cts):
        xs, outs = res
        grads = jax.pure_callback(host_backward, tuple(in_avals),
                                  *(tuple(cts) + tuple(xs) + tuple(outs)),
                                  vmap_method="sequential")
        return tuple(grads)

    run.defvjp(run_fwd, run_bwd)
    return run(*inputs)


def _register_custom_op():
    from .ops.registry import register as reg_op

    reg_op("Custom", num_outputs=_custom_num_outputs,
           uses_train_mode=True)(_custom_compute)


_register_custom_op()
