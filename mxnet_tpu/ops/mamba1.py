"""The Mamba-1 selective scan (arXiv:2312.00752), as pure functions of
arrays, for the serving runtime (``serve/phi4flash.py``).

A selective state-space layer with a decay of its own for every (channel,
state) pair: per channel ``c`` of ``d_inner`` a state of ``N`` values that
every token decays by ``exp(dt_t[c] A[c, n])`` and feeds with
``dt_t[c] B_t[n] x_t[c]``, read out by ``C_t``; ``B`` and ``C`` are shared
by all channels.

    h_t[c, n] = exp(dt_t[c] A[c, n]) h_{t-1}[c, n] + dt_t[c] B_t[n] x_t[c]
    y_t[c]    = sum_n C_t[n] h_t[c, n] + D[c] x_t[c]

``ops/mamba2.py`` has one decay a head, which is what lets its chunked
form be matmuls.  Here ``d_inner x N`` recurrences run side by side and
there is no matmul form: the prefill form walks the rows with the state
resident, and never holds ``rows x d_inner x N`` of anything.

**The state lies (N, d_inner)**, the channels on the last axis, here and
in the cache (``serve/phi4flash.py:state_shapes``): a TPU keeps an array's
last axis in lanes of 128, so (d_inner, 16) at rest would be padded to
eight times its bytes, and the kernel below wants a row of ``dt`` or ``x``
(channels on the lanes) to broadcast down the state's sublanes.  ``A`` is
taken as the parameter lies, (d_inner, N), and turned once a call (80 K
values).

* :func:`selective_scan`: a prefill chunk's rows from a carried state.
  On a TPU, where :func:`scan_kernel_eligible` accepts the call, ONE
  Pallas kernel: the grid runs over tiles of channels (independent) and
  blocks of rows (in order), ``h`` of a tile stays in VMEM across the row
  blocks, and inside a block the rows are walked eight at a time, one
  aligned (8, tile) load of ``dt`` and of ``x`` and one aligned store of
  ``y`` a group.  ``B_t`` and ``C_t`` must lie down the sublanes, one
  value a state row, the same in every lane: they are handed over
  broadcast to one lane tile, (rows, N, 128), 8 KB a row each, and the
  kernel repeats the tile across the channels (16 MB a call of 2 048
  rows, read once a channel tile; the state's own traffic, which a scan
  that materialised it would pay, is 671 MB an operand).  Everywhere
  else a ``lax.scan`` over the rows in plain XLA: the same recurrence,
  the same order, the kernel's fallback and its oracle.
* :func:`selective_step`: the recurrence for one token a slot (decode),
  elementwise.

Everything is float32 and elementwise: no product here goes to the MXU,
so no precision setting changes a result.  A row with ``dt = 0`` is an
identity of the recurrence (decay 1, input 0): that is how
:func:`selective_scan` pads a bucket, so the state it returns is the
state after the last REAL row.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError

__all__ = ["selective_scan", "selective_step", "scan_kernel_eligible",
           "SCAN_KERNEL_NAME"]

_LANES = 128
_SUBLANES = 8
# channels a grid step holds: h of a tile is (16, 512) float32, 8 vector
# registers, and the walk's temporaries a few times that
_CHANNEL_TILE = 512
# rows a grid step holds: B and C of a block, broadcast to a lane tile,
# are 1 MB each at 16 states
_ROW_BLOCK = 128
# the ``pallas_call``'s name: what a trace's device operations show of it
SCAN_KERNEL_NAME = "mamba1_selective_scan"


def _check(x, dt, a, b, c, d, state):
    di, n = a.shape
    if x.shape != dt.shape or x.shape[-1] != di or d.shape != (di,) \
            or b.shape != c.shape or b.shape[-1] != n \
            or b.shape[:-1] != x.shape[:-1] \
            or state.shape[-2:] != (n, di):
        raise MXNetError(
            "mamba1: x %r, dt %r, A %r, B %r, C %r, D %r, state %r"
            % (x.shape, dt.shape, a.shape, b.shape, c.shape, d.shape,
               state.shape))


def scan_kernel_eligible(x, a):
    """Whether :func:`selective_scan` sends this call to the kernel: a
    decision from what the call shows at trace time.  The backend is a
    TPU; the channels are whole tiles of :data:`_CHANNEL_TILE` (or one
    tile of whole lane tiles); the rows are whole sublane tiles."""
    t, di = x.shape
    tile = min(_CHANNEL_TILE, di)
    return (jax.default_backend() == "tpu" and di % tile == 0
            and tile % _LANES == 0 and t % _SUBLANES == 0
            and x.dtype == jnp.float32)


def _prepared(x, dt, a, b, c, d, state0, length):
    """The scan's operands as both of its forms take them: float32, ``dt``
    zero on the rows past ``length`` (bucket padding: identities of the
    recurrence), ``A`` turned to (N, d_inner)."""
    _check(x, dt, a, b, c, d, state0)
    x, dt, a, b, c, d, state0 = (v.astype(jnp.float32)
                                 for v in (x, dt, a, b, c, d, state0))
    dt = jnp.where(jnp.arange(x.shape[0])[:, None] < length, dt, 0.0)
    return x, dt, a.T, b, c, d, state0


def selective_scan(x, dt, a, b, c, d, state0, length):
    """Rows ``0 .. T - 1`` of one sequence through the recurrence, from
    ``state0``; the first ``length`` rows are real.

    x: (T, d_inner), the convolved, activated input; dt: (T, d_inner),
    already positive (softplus applied); a: (d_inner, N) negative; b, c:
    (T, N); d: (d_inner,); state0: (N, d_inner) float32.
    -> (y (T, d_inner) float32 with the skip ``D x`` in it, state
    (N, d_inner) float32 after row ``length - 1``).  Rows past ``length``
    leave the state as it is; their ``y`` is junk.
    """
    x, dt, a_t, b, c, d, state0 = _prepared(x, dt, a, b, c, d, state0, length)
    if scan_kernel_eligible(x, a):
        # called from a traced step, long after both packages are loaded
        from ..serve.model import note_traced

        note_traced("sscan_kernel_layers", 1)
        return _scan_kernel(x, dt, a_t, b, c, d, state0)

    def row(h, xs):
        x_t, dt_t, b_t, c_t = xs
        h = jnp.exp(dt_t * a_t) * h + b_t[:, None] * (dt_t * x_t)
        return h, jnp.sum(h * c_t[:, None], axis=0)

    state, y = lax.scan(row, state0, (x, dt, b, c))
    return y + d * x, state


def selective_step(x, dt, a, b, c, d, state):
    """One token a slot.  x, dt: (S, d_inner); a: (d_inner, N); b, c:
    (S, N); d: (d_inner,); state: (S, N, d_inner) float32.
    -> (y (S, d_inner) with the skip in it, state)."""
    _check(x, dt, a, b, c, d, state)
    f32 = jnp.float32
    x, dt = x.astype(f32), dt.astype(f32)
    state = jnp.exp(dt[:, None, :] * a.astype(f32).T) * state \
        + b.astype(f32)[:, :, None] * (dt * x)[:, None, :]
    y = jnp.sum(state * c.astype(f32)[:, :, None], axis=1)
    return y + d.astype(f32) * x, state


# ---------------------------------------------------------------------------
# the Pallas kernel (TPU)
# ---------------------------------------------------------------------------

def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, h0_ref, y_ref,
            state_ref, h_ref):
    from jax.experimental import pallas as pl

    rows, tile = x_ref.shape
    repeat = tile // _LANES
    block = pl.program_id(1)

    @pl.when(block == 0)
    def _():
        h_ref[...] = h0_ref[...]

    a = a_ref[...]                                          # (N, tile)
    skip = d_ref[...]                                       # (1, tile)

    def across(v):
        """(N, 128), every lane alike -> (N, tile)."""
        return v if repeat == 1 else jnp.concatenate([v] * repeat, axis=1)

    def group(g, h):
        at = pl.multiple_of(g * _SUBLANES, _SUBLANES)
        x8 = x_ref[pl.ds(at, _SUBLANES), :]
        dt8 = dt_ref[pl.ds(at, _SUBLANES), :]
        ys = []
        for j in range(_SUBLANES):
            x_t, dt_t = x8[j:j + 1], dt8[j:j + 1]           # (1, tile)
            h = jnp.exp(dt_t * a) * h + across(b_ref[at + j]) * (dt_t * x_t)
            ys.append(jnp.sum(h * across(c_ref[at + j]), axis=0,
                              keepdims=True) + skip * x_t)
        y_ref[pl.ds(at, _SUBLANES), :] = jnp.concatenate(ys, axis=0)
        return h

    h = lax.fori_loop(0, rows // _SUBLANES, group, h_ref[...])
    h_ref[...] = h
    state_ref[...] = h


def _scan_kernel(x, dt, a_t, b, c, d, state0):
    """:func:`selective_scan`'s kernel: all float32, ``dt`` already zero
    on the padded rows, ``a_t`` (N, d_inner)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, di = x.shape
    n = a_t.shape[0]
    tile = min(_CHANNEL_TILE, di)
    rows = _ROW_BLOCK if t % _ROW_BLOCK == 0 else t
    # one value a state row, the same in every lane of one lane tile
    b3, c3 = (jnp.broadcast_to(v[:, :, None], (t, n, _LANES))
              for v in (b, c))
    by_rows = pl.BlockSpec((rows, tile), lambda ch, r: (r, ch))
    by_state = pl.BlockSpec((rows, n, _LANES), lambda ch, r: (r, 0, 0))
    whole = pl.BlockSpec((n, tile), lambda ch, r: (0, ch))
    y, state = pl.pallas_call(
        _kernel,
        out_shape=(jax.ShapeDtypeStruct((t, di), jnp.float32),
                   jax.ShapeDtypeStruct((n, di), jnp.float32)),
        grid=(di // tile, t // rows),
        in_specs=[by_rows, by_rows, whole, by_state, by_state,
                  pl.BlockSpec((1, tile), lambda ch, r: (0, ch)), whole],
        out_specs=(by_rows, whole),
        scratch_shapes=[pltpu.VMEM((n, tile), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name=SCAN_KERNEL_NAME,
    )(x, dt, a_t, b3, c3, d[None, :], state0)
    return y, state


def scan_kernel_interpreted(x, dt, a, b, c, d, state0, length):
    """:func:`selective_scan` through the kernel whatever the backend,
    for the tests (under ``pltpu.force_tpu_interpret_mode()``)."""
    return _scan_kernel(*_prepared(x, dt, a, b, c, d, state0, length))
