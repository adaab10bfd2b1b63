"""What more than one block computes the same way, under public names: the
norm of the blocks that state their architecture, and window attention
over a slot's ring (``kv_cache``'s ``kw_pool`` / ``vw_pool``, read through
``kv_cache.read_ring``): the readers of ``laguna.py`` and ``phi4flash.py``.
A reader that takes a ring where it lies (``PERF.md`` Open questions) goes
here, for both.
"""
from __future__ import annotations

from ..ops.attention import attend_block, finalize_attention
from .kv_cache import ring_positions

__all__ = ["rms_norm", "attend_once", "band", "window_prefill",
           "window_decode"]


def rms_norm(x, gamma, eps):
    import jax.numpy as jnp
    from jax import lax

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * gamma


def attend_once(q, k, v, seen, exact):
    """Softmax attention over one block of keys.  q (..., Q, D) float32,
    scaled; k, v (..., K, D); ``seen`` broadcastable to (..., Q, K)."""
    import jax.numpy as jnp

    acc = jnp.zeros(q.shape[:-1] + (v.shape[-1],), jnp.float32)
    m = jnp.full(q.shape[:-1] + (1,), -jnp.inf, jnp.float32)
    acc, _, l = attend_block(q, k, v, acc, m, jnp.zeros_like(m),
                             kv_valid=seen, mi=exact)
    return finalize_attention(acc, l)


def band(q_pos, k_pos, window):
    """(Q,), (K,) positions -> (Q, K) bool: a key that was written (its
    position is not negative) inside the query's band."""
    behind = q_pos[:, None] - k_pos[None, :]
    return (k_pos[None, :] >= 0) & (behind >= 0) & (behind < window)


def window_prefill(q, k, v, ring_k, ring_v, ring_pos, abs_pos, window,
                   exact, scale=None):
    """A chunk's window attention.  q (T, KV, G, D), k and v (T, KV, D) the
    chunk's own rows at positions ``abs_pos`` (T,); ``ring_k``, ``ring_v``
    (R, KV, D) the slot's ring as the chunks before left it, its rows at
    positions ``ring_pos`` (R,).  A block of R queries at a time where the
    chunk is whole blocks: the first sees [ring | its own rows], a later
    one the block before it and its own rows (every key further back is
    outside its band, R >= window).  ``scale`` multiplies the scores
    (1 / sqrt(D) by default).  -> (T, KV, G * D)."""
    import jax.numpy as jnp

    t, kv, g, d = q.shape
    ring = ring_k.shape[0]
    block = ring if t > ring and t % ring == 0 else t
    q32 = q.astype(jnp.float32) * (d ** -0.5 if scale is None else scale)
    outs = []
    for start in range(0, t, block):
        if start == 0:
            keys = jnp.concatenate([ring_k.astype(k.dtype), k[:block]])
            values = jnp.concatenate([ring_v.astype(v.dtype), v[:block]])
            k_pos = jnp.concatenate([ring_pos, abs_pos[:block]])
        else:
            keys, values = k[start - ring:start + block], \
                v[start - ring:start + block]
            k_pos = abs_pos[start - ring:start + block]
        q_pos = jnp.repeat(abs_pos[start:start + block], g)
        # a key/value head's query heads are its rows: row t * G + g sees
        # what row t sees
        out = attend_once(
            q32[start:start + block].transpose(1, 0, 2, 3).reshape(
                kv, block * g, d),
            keys.transpose(1, 0, 2), values.transpose(1, 0, 2),
            band(q_pos, k_pos, window)[None], exact)
        outs.append(out.reshape(kv, block, g * d).transpose(1, 0, 2))
    return jnp.concatenate(outs).astype(q.dtype)


def window_decode(q, ring_k, ring_v, lengths, window, exact, scale=None):
    """One token a slot over its ring.  q (S, KV, G, D); rings
    (S, R, KV, D), this token's row appended; ``lengths`` (S,) its
    position; ``scale`` multiplies the scores (1 / sqrt(D) by default).
    -> (att (S, KV, G * D), ring rows inside the band (S,))."""
    import jax.numpy as jnp

    s, kv, g, d = q.shape
    k_pos = ring_positions(ring_k.shape[1], lengths)            # (S, R)
    behind = lengths[:, None] - k_pos
    seen = (k_pos >= 0) & (behind < window)
    att = attend_once(q.astype(jnp.float32)
                       * (d ** -0.5 if scale is None else scale),
                       ring_k.transpose(0, 2, 1, 3),
                       ring_v.transpose(0, 2, 1, 3),
                       seen[:, None, None, :], exact)
    return att.reshape(s, kv, g * d).astype(q.dtype), seen.sum(axis=1)
