"""Kimi Delta Attention's recurrence (arXiv:2510.26692): the gated delta
rule with a decay a channel, as pure functions of arrays, for the serving
runtime (``serve/bailing_hybrid.py``).

A linear-attention layer: per head a matrix state ``S`` of ``(K, V)`` (key
width x value width) that every token decays channel by channel with its
own ``alpha_t = exp(g_t)`` in ``(0, 1]^K``, then corrects by the delta
rule: what the decayed state predicts for ``k_t`` is taken out and
``v_t`` put in, at the rate ``beta_t``.

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

* :func:`kda_step`: the recurrence itself for one token a slot (decode),
  elementwise in float32.
* :func:`kda_chunked`: a whole sequence of rows in the matmul-shaped (WY)
  form.  With ``G_t`` the log-decay summed from a chunk's start through
  row ``t`` and ``u_t = beta_t (v_t - (alpha_t k_t)^T S_{t-1})`` the
  rule reads ``S_t = Diag(alpha_t) S_{t-1} + k_t u_t^T``, which unrolls,
  inside a chunk of ``C`` rows entered with ``S_0``, to

      (I + Diag(beta) A) U = Diag(beta) (V - (K e^G) S_0),
      A[t, s] = sum_c k_tc k_sc e^(G_tc - G_sc)  for s < t
      O = (Q e^G) S_0 + B U,    B[t, s] = the same with q_t, for s <= t
      S_C = Diag(e^G_C) S_0 + (K e^(G_C - G))^T U

  so a chunk is a unit-triangular solve and matmuls, and only the pass
  from chunk to chunk is sequential.  ``e^(G_t - G_s)`` is formed as
  ``e^(G_t - R) e^(R - G_s)`` around the chunk's middle row ``R``: with
  ``g >= lower_bound`` a row either factor stays within
  ``e^(|lower_bound| C / 2)``, which :func:`kda_chunked` holds inside
  float32's range by refusing a longer chunk.
* the short causal convolution in front of it is ``ops/mamba2.py``'s
  :func:`~mxnet_tpu.ops.mamba2.causal_conv` / ``conv_step`` with no bias.

The decays, their cumulative sums, the solve and the state are float32
whatever the inputs are; the chunked form's matmuls run at the default
precision.  A row with ``g = 0`` and ``beta = 0`` is an identity of the
recurrence: that is how a caller pads, and how :func:`kda_chunked` pads to
whole chunks.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax
from jax.scipy.linalg import solve_triangular

from ..base import MXNetError

__all__ = ["kda_step", "kda_chunked", "chunk_pass", "MAX_EXPONENT"]

# the largest |exponent| a factor of the chunked form may reach: e^80 is
# 5.5e34, inside float32 (and bfloat16, which has its exponent range)
MAX_EXPONENT = 80.0


def kda_step(q, k, v, g, beta, state):
    """One token a slot.  q, k, g: (S, H, K); v: (S, H, V); beta: (S, H);
    state: (S, H, K, V) float32.  -> (o (S, H, V) float32, state)."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    decayed = state.astype(f32) * jnp.exp(g)[..., None]
    predicted = jnp.sum(decayed * k[..., None], axis=-2)
    read = jnp.sum(decayed * q[..., None], axis=-2)
    delta = beta[..., None] * (v - predicted)
    # S_t^T q = (Diag(alpha) S)^T q + (q . k) delta: both sums over the
    # state are of the decayed one, so it is read for them once
    o = read + jnp.sum(q * k, axis=-1, keepdims=True) * delta
    return o, decayed + k[..., None] * delta[..., None, :]


def kda_chunked(q, k, v, g, beta, state0, chunk=32, lower_bound=-5.0):
    """Rows ``0..T-1`` of one sequence through the recurrence, from
    ``state0``.

    q, k: (T, H, K); v: (T, H, V); g: (T, H, K) log-decays in
    ``[lower_bound, 0]``; beta: (T, H); a row that must not touch the
    state has ``g = 0`` and ``beta = 0``; state0: (H, K, V) float32.
    -> (o (T, H, V) float32, state (H, K, V) float32 after row T - 1).
    """
    t, h, kw = k.shape
    c = max(min(int(chunk), t), 1)
    if abs(lower_bound) * (c // 2) > MAX_EXPONENT:
        raise MXNetError(
            "kda_chunked: a chunk of %d rows at log-decays down to %g "
            "leaves float32's range (|lower_bound| * (chunk / 2) <= %g)"
            % (c, lower_bound, MAX_EXPONENT))
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    pad = -t % c
    if pad:   # whole chunks: the rows added are identities
        q, k, v, g, beta = (jnp.concatenate(
            [a, jnp.zeros((pad,) + a.shape[1:], f32)])
            for a in (q, k, v, g, beta))
    nc = (t + pad) // c
    # (chunks, heads, rows, width)
    q, k, v, g = (a.reshape((nc, c) + a.shape[1:]).transpose(0, 2, 1, 3)
                  for a in (q, k, v, g))
    beta = beta.reshape(nc, c, h).transpose(0, 2, 1)[..., None]
    cum = jnp.cumsum(g, axis=2)                 # G_t, <= 0
    total = cum[:, :, -1]                       # (nc, h, K)
    mid = cum[:, :, c // 2][:, :, None]
    rise, fall = jnp.exp(cum - mid), jnp.exp(mid - cum)
    k_fall = k * fall
    rows = jnp.arange(c)
    below = rows[:, None] > rows[None, :]
    # masked entries (s > t) may overflow: selected away, never multiplied
    a = jnp.where(below, jnp.einsum("nhtk,nhsk->nhts", k * rise, k_fall),
                  0.0)
    b = jnp.where(below | (rows[:, None] == rows[None, :]),
                  jnp.einsum("nhtk,nhsk->nhts", q * rise, k_fall), 0.0)
    from_start = jnp.exp(cum)
    solved = solve_triangular(
        jnp.eye(c, dtype=f32) + beta * a,
        jnp.concatenate([beta * k * from_start, beta * v], axis=-1),
        lower=True, unit_diagonal=True)
    w, u_free = solved[..., :kw], solved[..., kw:]
    q_start = q * from_start
    k_end = k * jnp.exp(total[:, :, None] - cum)

    state, o = chunk_pass(w, u_free, q_start, b, k_end, jnp.exp(total),
                          state0)
    return o.transpose(0, 2, 1, 3).reshape(nc * c, h, -1)[:t], state


def chunk_pass(w, u_free, q_start, b, k_end, keep, state0):
    """The pass between chunks, from the state ENTERING each: the one
    sequential part of a chunked delta rule (this file's, and
    ``ops/gdn.py``'s).  Per chunk and head: w, q_start, k_end (C, K);
    u_free (C, V); b (C, C); keep (K,) or (1,), what the chunk's decays
    leave of a state row.  -> (state after the last chunk, o (chunks, H,
    C, V))."""
    def one_chunk(state, xs):
        w_c, u_c, q_c, b_c, k_c, keep_c = xs
        u = u_c - jnp.einsum("htk,hkv->htv", w_c, state)
        o = jnp.einsum("htk,hkv->htv", q_c, state) \
            + jnp.einsum("hts,hsv->htv", b_c, u)
        state = state * keep_c[..., None] \
            + jnp.einsum("htk,htv->hkv", k_c, u)
        return state, o

    return lax.scan(one_chunk, state0.astype(jnp.float32),
                    (w, u_free, q_start, b, k_end, keep))
