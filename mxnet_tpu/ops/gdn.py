"""Gated DeltaNet's recurrence (arXiv:2412.06464, as Qwen3-Next configures
it): the gated delta rule with ONE decay a head, as pure functions of
arrays, for the serving runtime (``serve/qwen3_next.py``).

``ops/kda.py``'s rule with ``alpha_t`` a number a head and not a vector of
K channels: per head a matrix state ``S`` of ``(K, V)`` that every token
scales by its ``alpha_t = exp(g_t)`` in ``(0, 1]``, then corrects by the
delta rule at the rate ``beta_t``:

    S_t = alpha_t S_{t-1} + k_t (beta_t (v_t - (alpha_t S_{t-1})^T k_t))^T
    o_t = S_t^T q_t

* :func:`gdn_step`: the recurrence for one token a slot (decode):
  :func:`~mxnet_tpu.ops.kda.kda_step` with the decay broadcast over a
  head's channels, which is the same arithmetic and reads the state once.
* :func:`gdn_chunked`: a whole sequence of rows in the matmul-shaped (WY)
  form, ``ops/kda.py``'s, at chunks of 64 rows.  With ``G_t`` the
  log-decay summed from a chunk's start through row ``t`` and
  ``Gamma[t, s] = exp(G_t - G_s)`` for ``s <= t``, a chunk of ``C`` rows
  entered with ``S_0`` is

      (I + tril(Diag(beta) (K K^T * Gamma), -1)) [W | U]
          = Diag(beta) [K e^G | V]
      U' = U - W S_0
      O = (Q e^G) S_0 + tril(Q K^T * Gamma) U'
      S_C = e^(G_C) S_0 + (K e^(G_C - G))^T U'

  A decay that is one number a head needs no factoring: ``Gamma`` is a
  ``(C, C)`` matrix a head, formed directly from differences that are
  never positive (``ops/kda.py`` factors ``e^(G_t - G_s)`` a channel
  around a chunk's middle row and so bounds its chunk by the gate's lower
  bound).  **No exponent is positive**, so a log-decay has no lower bound
  here and the published chunk of 64 holds in float32 for any weights: a
  token that forgets everything (``g = -40``) gives zeros, not infinities.

The decays, their sums, ``Gamma``, the solve and the state are float32
whatever the inputs are; the chunked form's matmuls run at the default
precision.  A row with ``g = 0`` and ``beta = 0`` is an identity of the
recurrence: that is how a caller pads, and how :func:`gdn_chunked` pads to
whole chunks.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

from .kda import chunk_pass, kda_step

__all__ = ["gdn_step", "gdn_chunked"]


def gdn_step(q, k, v, g, beta, state):
    """One token a slot.  q, k: (S, H, K); v: (S, H, V); g, beta: (S, H);
    state: (S, H, K, V) float32.  -> (o (S, H, V) float32, state)."""
    return kda_step(q, k, v, g[..., None], beta, state)


def gdn_chunked(q, k, v, g, beta, state0, chunk=64):
    """Rows ``0..T-1`` of one sequence through the recurrence, from
    ``state0``.

    q, k: (T, H, K); v: (T, H, V); g: (T, H) log-decays ``<= 0``, as low
    as they like; beta: (T, H); a row that must not touch the state has
    ``g = 0`` and ``beta = 0``; state0: (H, K, V) float32.
    -> (o (T, H, V) float32, state (H, K, V) float32 after row T - 1).
    """
    t, h, kw = k.shape
    c = max(min(int(chunk), t), 1)
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    pad = -t % c
    if pad:   # whole chunks: the rows added are identities
        q, k, v, g, beta = (jnp.concatenate(
            [a, jnp.zeros((pad,) + a.shape[1:], f32)])
            for a in (q, k, v, g, beta))
    nc = (t + pad) // c
    # (chunks, heads, rows, width); the decay and the rate (chunks, heads,
    # rows, 1)
    q, k, v = (a.reshape(nc, c, h, -1).transpose(0, 2, 1, 3)
               for a in (q, k, v))
    cum = jnp.cumsum(g.reshape(nc, c, h).transpose(0, 2, 1), axis=2)
    beta = beta.reshape(nc, c, h).transpose(0, 2, 1)[..., None]
    total = cum[:, :, -1:]                      # (nc, h, 1)
    rows = jnp.arange(c)
    upto = rows[:, None] >= rows[None, :]
    # Gamma[t, s] = e^(G_t - G_s) for s <= t: every exponent <= 0
    gamma = jnp.exp(jnp.where(upto, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))
    a = jnp.where(rows[:, None] > rows[None, :],
                  jnp.einsum("nhtk,nhsk->nhts", k, k) * gamma, 0.0)
    b = jnp.einsum("nhtk,nhsk->nhts", q, k) * gamma
    from_start = jnp.exp(cum)[..., None]
    solved = solve_triangular(
        jnp.eye(c, dtype=f32) + beta * a,
        jnp.concatenate([beta * k * from_start, beta * v], axis=-1),
        lower=True, unit_diagonal=True)
    state, o = chunk_pass(
        solved[..., :kw], solved[..., kw:], q * from_start, b,
        k * jnp.exp(total - cum)[..., None], jnp.exp(total), state0)
    return o.transpose(0, 2, 1, 3).reshape(nc * c, h, -1)[:t], state
