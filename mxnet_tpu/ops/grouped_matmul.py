"""The routed-expert layer as one grouped-matmul kernel (Pallas, TPU).

``serve/latent_moe.py:_routed_experts`` sorts the tokens x k assignments
by expert and pads each expert's group to whole tiles of ``tile`` rows;
this kernel computes ``down_e(silu(gate_e x) * up_e x)`` for every tile
in use, ``e`` the tile's expert.  Its grid walks the padded tiles; the
tiles' experts and the number of tiles in use are scalar-prefetched, so
the three weight blocks' index maps follow the tile's expert and the
pipeline's double buffering fetches tile ``t + 1``'s expert while tile
``t`` computes.  Consecutive tiles of one expert have the same block
index and are not fetched again: **an expert is read once a call**,
whatever the number of its tiles (they are consecutive by construction).
A tile at or past ``in_use`` fetches nothing (its index maps stay on the
last tile in use) and computes nothing: its rows are zero.

The arithmetic is XLA's at default precision on this chip, what the
serving configurations state ("float32 weights, default matmul
precision"): the stacks stay float32 in HBM in their ``(E, out, in)``
layout, each of the three matmuls contracts the last axis of both
operands (``_mm``), rounds its operands to bfloat16 where they are read,
in VMEM, and accumulates in float32; ``silu`` and the product are
float32.

**VMEM.**  A block is a whole expert: three matrices of ``moe_d_ff x d``
values, 18.9 MB in float32 at kanana-2's widths (768 x 2048) and 23.6 MB
at Ling-3.0-flash's (768 x 2560), twice for the double buffer; their
bfloat16 roundings (half of one copy); the ``x`` and ``y`` tiles twice
each; the ``(tile, moe_d_ff)`` intermediates; 8 MiB of slack.  56-62 MB
at kanana-2's tiles of 8-128 rows and 68 MB at Ling-3.0-flash's of 8-16,
of a v5e core's 128 MiB; the default scoped limit is 16 MiB, so
:func:`_vmem_bytes` states the sum as ``vmem_limit_bytes``.  ``d_ff``
is not split: a split would change the weights' block index at every
grid step and re-read an expert once a tile.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["grouped_swiglu", "grouped_swiglu_eligible", "kernel_name"]

_LANES = 128        # a matrix dimension the MXU takes whole
_SUBLANES = 8       # rows of a float32 tile
_VMEM_SLACK = 8 << 20   # Mosaic's own scratch and what the sum leaves out
_NT = (((1,), (1,)), ((), ()))   # contract the last axis of both operands


def grouped_swiglu_eligible(x, gate, up, down, tile, exact, dequantized):
    """Whether ``_routed_experts`` sends this call to the kernel: a
    decision from what the call shows at trace time, never from whether a
    trial call raised.  The backend is TPU; ``exact`` (the M-invariant
    reduce form) is not asked; the stacks arrive as float32 or bfloat16
    arrays, not out of a weight-only-quantized tree dequantized inside
    the trace (``dequantized``: a kernel's operand would make XLA
    materialise every dequantized stack, every call); ``d`` and
    ``moe_d_ff`` are multiples of 128 and the tile a multiple of 8."""
    if jax.default_backend() != "tpu" or exact or dequantized:
        return False
    if any(w.dtype not in (jnp.float32, jnp.bfloat16) for w in (gate, up,
                                                                 down)):
        return False
    d, f = x.shape[-1], gate.shape[1]
    return d % _LANES == 0 and f % _LANES == 0 and tile % _SUBLANES == 0


def kernel_name(tile):
    """The ``pallas_call``'s name, which carries its tile: what a trace's
    device operations show of this layer (``moe_grouped_swiglu_t8``)."""
    return "moe_grouped_swiglu_t%d" % tile


def _vmem_bytes(tile, d, f, x_dtype, w_dtype):
    """The kernel's VMEM need (the module docstring's reckoning)."""
    expert = 3 * f * d
    weights = 2 * expert * jnp.dtype(w_dtype).itemsize
    rounded = expert * 2 if w_dtype != jnp.bfloat16 else 0
    tiles = 2 * 2 * tile * d * jnp.dtype(x_dtype).itemsize
    between = 4 * tile * f * 4 + tile * d * 4
    return weights + rounded + tiles + between + _VMEM_SLACK


def _swiglu_tile(expert_ref, in_use_ref, x_ref, gate_ref, up_ref, down_ref,
                 y_ref):
    from jax.experimental import pallas as pl

    del expert_ref   # read by the index maps
    bf16, f32 = jnp.bfloat16, jnp.float32

    @pl.when(pl.program_id(0) < in_use_ref[0])
    def _():
        x = x_ref[...].astype(bf16)
        g = lax.dot_general(x, gate_ref[0].astype(bf16), _NT,
                            preferred_element_type=f32)
        u = lax.dot_general(x, up_ref[0].astype(bf16), _NT,
                            preferred_element_type=f32)
        h = (jax.nn.silu(g) * u).astype(bf16)
        y_ref[...] = lax.dot_general(
            h, down_ref[0].astype(bf16), _NT,
            preferred_element_type=f32).astype(y_ref.dtype)

    @pl.when(pl.program_id(0) >= in_use_ref[0])
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)


def grouped_swiglu(x, expert_of_tile, in_use, gate, up, down, *, tile):
    """x (max_tiles * tile, d) padded rows; expert_of_tile (max_tiles,)
    int32; in_use () int32, the tiles that hold rows; gate, up (E, f, d)
    and down (E, d, f).  -> y like x: tile ``t < in_use`` is
    ``down_e(silu(gate_e x_t) * up_e x_t)`` with ``e =
    expert_of_tile[t]``, every other row zero."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, d = x.shape
    f = gate.shape[1]
    max_tiles = rows // tile

    def last(t, in_use_ref):   # a tile past the last stays on the last
        return jnp.maximum(jnp.minimum(t, in_use_ref[0] - 1), 0)

    def rows_of(t, expert_ref, in_use_ref):
        return last(t, in_use_ref), 0

    def expert_of(t, expert_ref, in_use_ref):
        return expert_ref[last(t, in_use_ref)], 0, 0

    return pl.pallas_call(
        _swiglu_tile,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(max_tiles,),
            in_specs=[pl.BlockSpec((tile, d), rows_of),
                      pl.BlockSpec((1, f, d), expert_of),
                      pl.BlockSpec((1, f, d), expert_of),
                      pl.BlockSpec((1, d, f), expert_of)],
            out_specs=pl.BlockSpec((tile, d), lambda t, *_: (t, 0))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_bytes(tile, d, f, x.dtype, gate.dtype)),
        name=kernel_name(tile),
    )(expert_of_tile.astype(jnp.int32), in_use.reshape(1).astype(jnp.int32),
      x, gate, up, down)
