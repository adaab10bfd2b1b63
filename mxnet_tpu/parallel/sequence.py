"""Sequence/context parallelism — ring attention over the 'seq' mesh axis.

The reference has nothing to port here (2017: bucketing + truncated BPTT
were its long-sequence story, SURVEY.md §5 "Long-context"); this is the
fresh TPU-first design the blueprint calls for: shard the SEQUENCE axis
of Q/K/V over the mesh's 'seq' axis, and rotate K/V blocks around the
ring with ``lax.ppermute`` while each device accumulates its queries'
attention in flash-attention style (running max + running sum), so the
full T×T score matrix never materializes and each hop's communication
is scheduled so it CAN overlap the current block's compute (Liu et al.,
Ring Attention, 2023 — public technique; the overlap itself is a
pending-real-ICI measurement, see ``ring_attention``'s docstring).

Two entry points:

* :func:`ring_attention` — inside ``shard_map``/``pjit`` code: takes the
  LOCAL (per-device) Q/K/V chunks and an axis name.
* :func:`sequence_parallel_attention` — whole-array convenience: shards
  (B, H, T, D) tensors over the active mesh's 'seq' axis via shard_map
  and runs :func:`ring_attention`.

Causal masking is supported: block positions are recovered from the ring
hop index, so masking stays exact under rotation.
"""
from __future__ import annotations

import functools

from ..base import MXNetError
from ..compile_cache import track_lru
from .mesh import current_mesh

__all__ = ["ring_attention", "sequence_parallel_attention"]


def _online_softmax_merge(acc, m, l, scores, v):
    """Back-compat alias: the flash accumulation step now lives in
    ``ops/attention.py`` (shared with the single-chip blockwise
    kernel)."""
    from ..ops.attention import online_block_merge

    return online_block_merge(acc, m, l, scores, v)


def ring_attention(q, k, v, axis_name, causal=False, scale=None):
    """Ring attention on LOCAL chunks inside shard_map.

    q/k/v: (..., T_local, D) — leading dims (batch, heads) are free; the
    sequence axis is sharded over ``axis_name``.  Each of the
    ``axis_size`` hops computes one (T_local x T_local) score block and
    rotates K/V to the next neighbor over ICI (``ppermute``), so peak
    memory is O(T_local^2 / ring) per device.  Design intent (pending
    real-ICI measurement — this environment has one chip): the hop
    structure gives XLA's scheduler independent send/compute chains so
    the transfer of hop i+1 CAN overlap the matmul of hop i; the
    measurement to run on a pod is a profiler trace of one layer at
    T_local >= 1024 checking ppermute slots hide under the score
    matmuls (docs/distributed.md "pending hardware" list).
    """
    import jax.numpy as jnp
    from jax import lax

    from ..ops.attention import attend_block, finalize_attention

    n = lax.axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    t_local = q.shape[-2]
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    perm = [(i, (i + 1) % n) for i in range(n)]

    acc0 = jnp.zeros(q.shape[:-1] + (v.shape[-1],), jnp.float32)
    m0 = jnp.full(q.shape[:-1] + (1,), -jnp.inf, jnp.float32)
    l0 = jnp.zeros(q.shape[:-1] + (1,), jnp.float32)

    q32 = q.astype(jnp.float32) * scale
    # global positions of this device's queries (causal masking)
    q_pos = rank * t_local + jnp.arange(t_local)

    def hop(i, state):
        acc, m, l, kk, vv = state
        # the K/V block now resident came from rank - i (ring rotation):
        # one visit of the shared blockwise kernel per hop
        src = (rank - i) % n
        k_pos = src * t_local + jnp.arange(t_local)
        acc, m, l = attend_block(q32, kk, vv, acc, m, l, q_pos=q_pos,
                                 k_pos=k_pos, causal=causal)
        kk = lax.ppermute(kk, axis_name, perm)
        vv = lax.ppermute(vv, axis_name, perm)
        return acc, m, l, kk, vv

    state = (acc0, m0, l0, k, v)
    for i in range(n):  # static unroll: n is a mesh constant
        state = hop(i, state)
    acc, m, l, _, _ = state
    return finalize_attention(acc, l).astype(q.dtype)


def sequence_parallel_attention(q, k, v, causal=False, mesh=None,
                                axis="seq"):
    """Whole-array sequence-parallel attention.

    q/k/v: (B, H, T, D) with T divisible by the mesh's ``axis`` size.
    Shards T over the mesh and runs :func:`ring_attention` under
    ``shard_map``.  The batch and heads dims COMPOSE with the other plan
    axes: B additionally shards over the data (and fsdp) axes and H over
    the 'model' axis whenever the sizes divide — attention is
    independent across batch and heads, so the ring stays the only
    cross-device exchange and each (data, model) group runs its own.
    """
    mesh = mesh or current_mesh()
    if mesh is None or axis not in mesh.shape:
        raise MXNetError(
            "sequence_parallel_attention needs a mesh with a %r axis "
            "(create one with parallel.create_mesh)" % axis)
    t = q.shape[-2]
    n = mesh.shape[axis]
    if t % n != 0:
        raise MXNetError("sequence length %d not divisible by %s=%d"
                         % (t, axis, n))
    shape = dict(mesh.shape)
    batch_axes = tuple(ax for ax in ("data", "fsdp")
                       if int(shape.get(ax, 1)) > 1
                       and int(q.shape[0]) % int(shape[ax]) == 0)
    heads_axis = ("model" if int(shape.get("model", 1)) > 1
                  and int(q.shape[1]) % int(shape["model"]) == 0
                  else None)
    return _sp_attention_fn(mesh, axis, causal, batch_axes,
                            heads_axis)(q, k, v)


@track_lru("parallel._sp_attention_fn")
@functools.lru_cache(maxsize=32)
def _sp_attention_fn(mesh, axis, causal, batch_axes=(), heads_axis=None):
    """Cached jitted shard_map program per (mesh, axis, causal,
    batch/heads placement): jit caches by function identity, so
    rebuilding per call would re-trace and recompile every step."""
    import jax
    from jax.sharding import PartitionSpec as P

    spec = P(batch_axes or None, heads_axis, axis, None)
    body = functools.partial(ring_attention, axis_name=axis,
                             causal=causal)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return jax.jit(fn)
