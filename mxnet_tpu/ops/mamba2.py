"""The Mamba-2 mixer's three pieces (arXiv:2405.21060), as pure functions
of arrays, for the serving runtime (``serve/granite_hybrid.py``).

A selective state-space layer: per head ``h`` a state of ``(P, N)``
(head width x state size) that every token decays by its own
``exp(dt_t A_h)`` and feeds with ``dt_t x_t (outer) B_t``, read out by
``C_t``; ``B`` and ``C`` are shared by the heads of a group.

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t
    y_t = h_t C_t                       (the caller adds ``D x_t``)

* :func:`ssd_chunked_scan`: a whole chunk of rows in the matmul-shaped
  ("SSD") form.  Within a chunk of ``Q`` rows the recurrence unrolls to
  ``y = (C B^T * L) (dt x)`` with ``L[t, s] = exp(sum_{s < r <= t} dt_r
  A)`` for ``s <= t``; a chunk's contribution to the state and the
  incoming state's contribution to its rows are matmuls too, and only
  the pass from chunk to chunk is sequential.  No loop over tokens.
* :func:`ssd_step`: the recurrence itself for one token a slot (decode).
* :func:`causal_conv` / :func:`conv_step`: the depthwise causal
  convolution in front of the scan, with its left context carried.

The decays, their cumulative sums and the state are float32 whatever the
inputs are.  A row with ``dt = 0`` is an identity of the recurrence
(decay 1, input 0): that is how a caller pads, and how
:func:`ssd_chunked_scan` pads to whole chunks.  The chunked form's
matmuls run at the default precision; :func:`ssd_step` is elementwise.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from ..base import MXNetError

__all__ = ["ssd_chunked_scan", "ssd_step", "causal_conv", "conv_step"]


def _grouped(x, dt, a, b):
    """Heads split as (groups, heads a group); -> (g, hg)."""
    h, g = x.shape[-2], b.shape[-2]
    if h % g or dt.shape[-1] != h or a.shape != (h,):
        raise MXNetError("mamba2: %d heads (dt %r, A %r) over %d groups"
                         % (h, dt.shape, a.shape, g))
    return g, h // g


def ssd_chunked_scan(x, dt, a, b, c, state0, chunk=256):
    """Rows ``0..T-1`` of one sequence through the recurrence, from
    ``state0``.

    x: (T, H, P); dt: (T, H), already positive (softplus applied), 0 for
    a row that must not touch the state; a: (H,) negative; b, c:
    (T, G, N); state0: (H, P, N) float32.  -> (y (T, H, P) float32,
    state (H, P, N) float32 after row T - 1).
    """
    t, h, p = x.shape
    n = b.shape[-1]
    g, hg = _grouped(x, dt, a, b)
    q = max(min(int(chunk), t), 1)
    pad = -t % q
    f32 = jnp.float32
    x, dt, b, c = (v.astype(f32) for v in (x, dt, b, c))
    if pad:   # whole chunks: the rows added are identities (dt = 0)
        x, dt, b, c = (jnp.concatenate(
            [v, jnp.zeros((pad,) + v.shape[1:], f32)]) for v in (x, dt, b, c))
    nc = (t + pad) // q
    x = x.reshape(nc, q, g, hg, p)
    dt = dt.reshape(nc, q, g, hg)
    b, c = b.reshape(nc, q, g, n), c.reshape(nc, q, g, n)
    # log-decays, cumulative within the chunk: (nc, g, hg, q), <= 0
    cum = jnp.cumsum(jnp.moveaxis(dt * a.astype(f32).reshape(g, hg), 1, -1),
                     axis=-1)
    total = cum[..., -1]                                   # (nc, g, hg)
    dtx = x * dt[..., None]                                # (nc, q, g, hg, p)
    # within a chunk: row t reads rows s <= t through C_t . B_s, decayed
    causal = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(causal, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                   # (nc, g, hg, t, s)
    cb = jnp.einsum("ctgn,csgn->cgts", c, b)
    y = jnp.einsum("cghts,csghp->ctghp", cb[:, :, None] * decay, dtx)
    # what each chunk adds to the state by its end
    to_end = jnp.moveaxis(jnp.exp(total[..., None] - cum), -1, 1)
    added = jnp.einsum("csghp,csgn->cghpn", dtx * to_end[..., None], b)

    # the pass between chunks: the state ENTERING each
    def enter(state, xs):
        keep, add = xs
        return state * keep[..., None, None] + add, state

    state, entering = lax.scan(enter, state0.astype(f32).reshape(g, hg, p, n),
                               (jnp.exp(total), added))
    y = y + jnp.einsum("ctgn,cghpn->ctghp", c, entering) \
        * jnp.moveaxis(jnp.exp(cum), -1, 1)[..., None]
    return y.reshape(nc * q, h, p)[:t], state.reshape(h, p, n)


def ssd_step(x, dt, a, b, c, state):
    """One token a slot.  x: (S, H, P); dt: (S, H); a: (H,); b, c:
    (S, G, N); state: (S, H, P, N) float32.  -> (y (S, H, P), state)."""
    s, h, p = x.shape
    n = b.shape[-1]
    g, hg = _grouped(x, dt, a, b)
    f32 = jnp.float32
    dt = dt.astype(f32)
    keep = jnp.exp(dt * a.astype(f32)).reshape(s, g, hg, 1, 1)
    dtx = (x.astype(f32) * dt[..., None]).reshape(s, g, hg, p, 1)
    state = state.reshape(s, g, hg, p, n) * keep \
        + dtx * b.astype(f32)[:, :, None, None, :]
    y = jnp.sum(state * c.astype(f32)[:, :, None, None, :], axis=-1)
    return y.reshape(s, h, p), state.reshape(s, h, p, n)


def causal_conv(rows, context, weight, bias, length):
    """Depthwise causal convolution over one sequence's rows.

    rows: (T, C), of which the first ``length`` are real; context:
    (K - 1, C), the rows before row 0 (zeros at a sequence's start);
    weight: (C, K), tap ``K - 1`` on the current row; bias: (C,).
    -> (out (T, C), context (K - 1, C) after row ``length - 1``: the
    last K - 1 REAL rows, reaching back into the old context where the
    chunk is shorter than that; never the bucket's padded tail).
    """
    t = rows.shape[0]
    k = weight.shape[1]
    if context.shape != (k - 1, rows.shape[1]):
        raise MXNetError("causal_conv: context %r for %d taps over %r"
                         % (context.shape, k, rows.shape))
    padded = jnp.concatenate([context.astype(rows.dtype), rows])
    out = bias + sum(padded[j:j + t] * weight[:, j] for j in range(k))
    return out, lax.dynamic_slice_in_dim(padded, length, k - 1, axis=0)


def conv_step(row, context, weight, bias):
    """:func:`causal_conv` for one row a slot.  row: (S, C); context:
    (S, K - 1, C).  -> (out (S, C), context (S, K - 1, C))."""
    window = jnp.concatenate([context.astype(row.dtype), row[:, None]],
                             axis=1)
    out = bias + sum(window[:, j] * weight[:, j]
                     for j in range(weight.shape[1]))
    return out, window[:, 1:]
