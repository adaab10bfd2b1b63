"""Runtime custom kernels.

Reference: ``MXRtc`` (``include/mxnet/mxrtc.h:44``, Python
``mxnet/rtc.py``) — compile CUDA source strings at runtime into callable
kernels.  The TPU-native counterpart compiles PALLAS kernels: the user
writes a Python kernel body against ``pl``/``pltpu`` refs and gets a
callable over NDArrays.  ``ops/pallas_bn.py`` is the in-tree example of
the same facility used for a framework op.

Differences from the reference, by design: kernels are Python (traced,
compiled by Mosaic/XLA), not source strings; grid/block specs follow
Pallas conventions (see /opt/skills/guides/pallas_guide.md).
"""
from __future__ import annotations

from .base import MXNetError

__all__ = ["PallasKernel"]


class PallasKernel:
    """A compiled custom kernel (the ``mx.rtc.Rtc`` analogue).

    ``kernel(*refs)``: a Pallas kernel body taking input refs then
    output refs.  ``out_shapes``: list of (shape, dtype) for outputs.
    Optional ``grid``/``in_specs``/``out_specs`` pass through to
    ``pl.pallas_call``; by default whole arrays land in VMEM.
    ``interpret=True`` runs the body in the Pallas interpreter — a
    test mode the caller asks for by name; the default compiles with
    Mosaic, which needs a TPU.

    Example::

        def body(x_ref, o_ref):
            o_ref[...] = x_ref[...] * 2.0

        k = PallasKernel(body, [((128, 128), "float32")])
        (y,) = k(x)
    """

    def __init__(self, kernel, out_shapes, grid=None, in_specs=None,
                 out_specs=None, interpret=False):
        self._kernel = kernel
        self._out_shapes = [(tuple(s), d) for (s, d) in out_shapes]
        self._grid = grid
        self._in_specs = in_specs
        self._out_specs = out_specs
        if not isinstance(interpret, bool):
            raise MXNetError(
                "PallasKernel(interpret=%r): pass True (Pallas "
                "interpreter, for tests off-TPU) or False (Mosaic)"
                % (interpret,))
        self._interpret = interpret
        self._compiled = None

    def _build(self):
        import jax
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        out_shape = [jax.ShapeDtypeStruct(s, d)
                     for (s, d) in self._out_shapes]
        kwargs = {}
        if self._grid is not None:
            kwargs["grid"] = self._grid
        if self._in_specs is not None:
            kwargs["in_specs"] = self._in_specs
        if self._out_specs is not None:
            kwargs["out_specs"] = self._out_specs
        call = pl.pallas_call(self._kernel, out_shape=out_shape,
                              interpret=self._interpret, **kwargs)
        self._compiled = jax.jit(call)

    def __call__(self, *inputs):
        """Run on NDArrays (or raw arrays); returns a tuple of
        NDArrays."""
        from .ndarray import NDArray, array

        if self._compiled is None:
            self._build()
        raw = [x._data if isinstance(x, NDArray) else array(x)._data
               for x in inputs]
        out = self._compiled(*raw)
        if not isinstance(out, (tuple, list)):
            out = (out,)
        return tuple(NDArray(o) for o in out)

    def push(self, inputs, outputs=None, grid_dims=None, block_dims=None):
        """Reference ``Rtc.push`` signature adapter: runs the kernel and
        copies into ``outputs`` when given."""
        results = self(*inputs)
        if outputs:
            for res, dst in zip(results, outputs):
                res.copyto(dst)
            return outputs
        return list(results)
