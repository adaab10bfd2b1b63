"""Blockwise (flash-style) dot-product attention.

The reference (0.11, pre-transformer) has nothing to port here; this
module is the TPU-first kernel behind ``MultiHeadAttention`` and the
per-hop inner kernel of ring attention (``parallel/sequence.py``).

Why it exists: the materialized-scores path builds an ``(n, h, T, T)``
fp32 tensor that XLA's fusion heuristics will not cross ("Operator
Fusion in XLA", arXiv 2301.13062) — at the bench shape (8L-d2048-T1024)
it is the single largest live buffer in the train step and caps both
sequence length and MFU.  The flash path tiles the key/value sequence
into blocks and keeps online-softmax statistics (running max ``m`` and
denominator ``l``) in fp32, so peak attention memory is O(T·block)
instead of O(T²), with a ``jax.custom_vjp`` backward that *recomputes*
each block's probabilities from the saved logsumexp instead of storing
them (Dao et al., FlashAttention, 2022 — public technique).

Three implementations, selected by ``MXNET_ATTN_IMPL``:

* ``reference`` — the original materialized path (exact softmax over
  the full score matrix).  Ground truth for tests.
* ``flash`` — the pure-``lax`` blockwise kernel below.  Runs on every
  backend, so the CPU tier-1 rig exercises the same code path that
  ships on TPU.
* ``auto`` (default) — on TPU, try the Pallas fused flash kernel
  (``jax.experimental.pallas.ops.tpu.flash_attention``) and fall back
  to the ``lax`` blockwise kernel when the shape/backend does not
  qualify; elsewhere, the ``lax`` blockwise kernel.

The per-block accumulation (:func:`attend_block` /
:func:`online_block_merge`) is shared with ring attention: each ring
hop is exactly one K/V-block visit with positions recovered from the
hop index, so sequence parallelism and the single-chip kernel stay one
implementation.

Gradient contract: the custom VJP is linear in the incoming cotangent
(``d(q,k,v)`` scale with ``g``), so the dynamic loss scale riding the
loss-head cotangent (PR 3) flows through unchanged — same semantics the
materialized path gets from autodiff.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError, get_env
from .paged_attention import (paged_attention, paged_attention_eligible,
                              paged_prefill, paged_prefill_eligible,
                              prefill_tiling)

__all__ = ["attention_impl", "attention_block_size", "dot_product_attention",
           "flash_attention", "reference_attention", "attend_block",
           "online_block_merge", "finalize_attention", "decode_attention",
           "paged_decode_attention", "paged_prefill_attention",
           "pallas_eligible"]

_IMPLS = ("auto", "flash", "reference")


def attention_impl():
    """Resolve ``MXNET_ATTN_IMPL`` (``auto`` | ``flash`` | ``reference``).

    Read at trace time: jitted programs bake in whichever implementation
    was active when they were traced (the registry's imperative-invoke
    cache keys on attrs/shapes, not env) — tests that need to force a
    path per-call should pass the ``attn_impl`` op attr instead.
    """
    impl = get_env("MXNET_ATTN_IMPL", "auto").strip().lower()
    if impl not in _IMPLS:
        raise MXNetError("MXNET_ATTN_IMPL=%r not in %s" % (impl, _IMPLS))
    return impl


def attention_block_size():
    """K/V block length for the blockwise kernel (``MXNET_ATTN_BLOCK``)."""
    block = get_env("MXNET_ATTN_BLOCK", 128)
    if block < 1:
        raise MXNetError("MXNET_ATTN_BLOCK must be >= 1, got %d" % block)
    return block


# ---------------------------------------------------------------------------
# shared online-softmax inner kernel (also the ring-attention hop kernel)
# ---------------------------------------------------------------------------

def _qk_scores(q32, kb32, mi=False):
    """(..., Tq, D) x (..., Tk, D) -> (..., Tq, Tk) score matmul.

    ``mi=True`` selects the M-invariant broadcast-multiply-reduce form:
    each output element reduces over D in an order independent of Tq, so
    a single-query decode step produces bit-identical scores to the
    matching row of a full-context forward (the serving bit-exactness
    contract — XLA's gemm packs/accumulates differently per M, which is
    ~1 ulp of drift the einsum form cannot avoid).  Costs extra bandwidth
    (the product tensor materializes), so it is opt-in.
    """
    if mi:
        return jnp.sum(q32[..., :, None, :] * kb32[..., None, :, :],
                       axis=-1)
    return jnp.einsum("...qd,...kd->...qk", q32, kb32)


def _pv_accum(p, vb32, mi=False):
    """(..., Tq, Tk) x (..., Tk, D) -> (..., Tq, D) probability-value
    matmul; ``mi`` as in :func:`_qk_scores`."""
    if mi:
        return jnp.sum(p[..., :, :, None] * vb32[..., None, :, :],
                       axis=-2)
    return jnp.einsum("...qk,...kd->...qd", p, vb32)


def online_block_merge(acc, m, l, scores, v, mi=False):
    """One flash-attention accumulation step.

    acc: (..., Tq, D) weighted-value accumulator; m: (..., Tq, 1) running
    max; l: (..., Tq, 1) running denominator; scores: (..., Tq, Tk) this
    block's logits (fp32, masked entries at ``-inf``); v: (..., Tk, D).
    Returns updated (acc, m, l).
    """
    block_max = jnp.max(scores, axis=-1, keepdims=True)
    new_m = jnp.maximum(m, block_max)
    # guard against all--inf rows (fully masked block): exp(-inf - -inf)
    new_m_safe = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
    correction = jnp.exp(m - new_m_safe)
    correction = jnp.where(jnp.isfinite(m), correction, 0.0)
    p = jnp.exp(scores - new_m_safe)
    p = jnp.where(jnp.isfinite(scores), p, 0.0)
    new_l = l * correction + jnp.sum(p, axis=-1, keepdims=True)
    new_acc = acc * correction + _pv_accum(p, v, mi=mi)
    return new_acc, new_m, new_l


def attend_block(q32, kb, vb, acc, m, l, q_pos=None, k_pos=None,
                 causal=False, kv_valid=None, mi=False, window=0):
    """Visit one K/V block: score, mask, merge into the running stats.

    ``q32`` is the full (pre-scaled, fp32) query; ``kb``/``vb`` one key/
    value block.  ``q_pos``/``k_pos`` are absolute positions (1-D int
    arrays) used for causal masking — ring attention recovers ``k_pos``
    from the hop index, the blockwise kernel from the block start.
    ``kv_valid`` masks padded keys in the (ragged) last block; any
    broadcastable mask shape works (the paged decode kernel passes a
    per-batch-element (..., 1, Tk) validity mask).  ``mi`` selects the
    M-invariant matmuls (see :func:`_qk_scores`).  ``window > 0`` adds a
    sliding-window lower bound: a key is visible only when
    ``q_pos - k_pos < window`` — a causal horizon that also *starts*
    late.  Fully windowed-out blocks are exact no-ops in the merge, so
    windowing preserves the M-invariant accumulation contract.
    """
    scores = _qk_scores(q32, kb.astype(jnp.float32), mi=mi)
    mask = None
    if causal:
        mask = q_pos[:, None] >= k_pos[None, :]
    if window:
        wmask = k_pos[None, :] > q_pos[:, None] - window
        mask = wmask if mask is None else mask & wmask
    if kv_valid is not None:
        mask = kv_valid if mask is None else mask & kv_valid
    if mask is not None:
        scores = jnp.where(mask, scores, -jnp.inf)
    return online_block_merge(acc, m, l, scores,
                              vb.astype(jnp.float32), mi=mi)


def finalize_attention(acc, l):
    """Normalize the accumulator by the running denominator."""
    return acc / jnp.maximum(l, 1e-20)


# ---------------------------------------------------------------------------
# reference (materialized) path
# ---------------------------------------------------------------------------

def reference_attention(q, k, v, causal=True, scale=None, window=0):
    """Exact softmax attention over the full (..., Tq, Tk) score matrix.

    The pre-flash ``_multi_head_attention`` body, kept verbatim as the
    numeric ground truth: scores in fp32, O(T²) peak memory.
    ``window > 0`` restricts row ``i`` to keys ``j`` with
    ``i - j < window`` (sliding-window attention).
    """
    t, d = q.shape[-2], q.shape[-1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    scores = jnp.einsum("...qd,...kd->...qk", q, k).astype(jnp.float32)
    scores = scores * scale
    mask = None
    if causal:
        mask = jnp.tril(jnp.ones((t, k.shape[-2]), bool))
    if window:
        row = jnp.arange(t)[:, None]
        col = jnp.arange(k.shape[-2])[None, :]
        wmask = col > row - window
        mask = wmask if mask is None else mask & wmask
    if mask is not None:
        scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("...qk,...kd->...qd", probs, v)


# ---------------------------------------------------------------------------
# blockwise flash kernel (pure lax, custom VJP)
# ---------------------------------------------------------------------------

def _kv_blocks(x, t_pad, block):
    """(..., T, D) -> (nblk, ..., block, D) scan-ready block stack."""
    pad = [(0, 0)] * (x.ndim - 2) + [(0, t_pad - x.shape[-2]), (0, 0)]
    x = jnp.pad(x, pad)
    x = x.reshape(x.shape[:-2] + (t_pad // block, block, x.shape[-1]))
    return jnp.moveaxis(x, -3, 0)


def _flash_forward(q, k, v, causal, scale, block, mi=False, window=0):
    """Tiled forward: scan over K/V blocks carrying (acc, m, l) in fp32.

    Returns ``(out, lse)`` where ``lse = m + log l`` is the per-query
    logsumexp the backward recomputes probabilities from.  Peak live
    memory is O(T·block) — the (T, T) score matrix never exists.
    """
    t, d = q.shape[-2], q.shape[-1]
    nblk = -(-t // block)
    t_pad = nblk * block
    kb = _kv_blocks(k, t_pad, block)
    vb = _kv_blocks(v, t_pad, block)
    starts = jnp.arange(nblk) * block
    q32 = q.astype(jnp.float32) * scale
    q_pos = jnp.arange(t)

    acc0 = jnp.zeros(q.shape[:-1] + (v.shape[-1],), jnp.float32)
    m0 = jnp.full(q.shape[:-1] + (1,), -jnp.inf, jnp.float32)
    l0 = jnp.zeros(q.shape[:-1] + (1,), jnp.float32)

    def body(carry, xs):
        acc, m, l = carry
        kblk, vblk, start = xs
        k_pos = start + jnp.arange(block)
        kv_valid = k_pos < t if t_pad != t else None
        acc, m, l = attend_block(q32, kblk, vblk, acc, m, l,
                                 q_pos=q_pos, k_pos=k_pos, causal=causal,
                                 kv_valid=kv_valid, mi=mi, window=window)
        return (acc, m, l), None

    (acc, m, l), _ = lax.scan(body, (acc0, m0, l0), (kb, vb, starts))
    out = finalize_attention(acc, l).astype(q.dtype)
    # l > 0 always (row q attends to at least key 0 under causal; all
    # keys when not), so the log is finite
    lse = jnp.where(jnp.isfinite(m), m, 0.0) + jnp.log(jnp.maximum(l, 1e-38))
    return out, lse


def _flash_backward(q, k, v, out, lse, g, causal, scale, block, window=0):
    """Recompute-based backward: one more scan over K/V blocks.

    Each block's probabilities are rebuilt from ``lse`` (never stored),
    then ``ds = p * (dp - delta)`` with ``delta = Σ dO·O`` gives the
    score gradient.  dq accumulates across blocks (carry); dk/dv are
    per-block (stacked ys).  Linear in ``g`` by construction.
    """
    t = q.shape[-2]
    nblk = -(-t // block)
    t_pad = nblk * block
    kb = _kv_blocks(k, t_pad, block)
    vb = _kv_blocks(v, t_pad, block)
    starts = jnp.arange(nblk) * block
    q32 = q.astype(jnp.float32) * scale
    q_pos = jnp.arange(t)
    do = g.astype(jnp.float32)
    delta = jnp.sum(do * out.astype(jnp.float32), axis=-1, keepdims=True)

    def body(dq, xs):
        kblk, vblk, start = xs
        kb32 = kblk.astype(jnp.float32)
        vb32 = vblk.astype(jnp.float32)
        scores = jnp.einsum("...qd,...kd->...qk", q32, kb32)
        k_pos = start + jnp.arange(block)
        mask = q_pos[:, None] >= k_pos[None, :] if causal else None
        if window:
            wmask = k_pos[None, :] > q_pos[:, None] - window
            mask = wmask if mask is None else mask & wmask
        if t_pad != t:
            valid = k_pos < t
            mask = valid if mask is None else mask & valid
        if mask is not None:
            scores = jnp.where(mask, scores, -jnp.inf)
        p = jnp.exp(scores - lse)  # masked -> exp(-inf) == 0 exactly
        dv_blk = jnp.einsum("...qk,...qd->...kd", p, do)
        dp = jnp.einsum("...qd,...kd->...qk", do, vb32)
        ds = p * (dp - delta)
        dq = dq + jnp.einsum("...qk,...kd->...qd", ds, kb32)
        dk_blk = jnp.einsum("...qk,...qd->...kd", ds, q32)
        return dq, (dk_blk, dv_blk)

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dq, (dk_blk, dv_blk) = lax.scan(body, dq0, (kb, vb, starts))

    def unblocks(blk, like):
        x = jnp.moveaxis(blk, 0, -3)
        x = x.reshape(x.shape[:-3] + (t_pad, x.shape[-1]))
        return x[..., :t, :].astype(like.dtype)

    # scores = (q*scale)·k: d/dq carries the scale factor explicitly,
    # d/dk already has it through q32
    dq = (dq * scale).astype(q.dtype)
    return dq, unblocks(dk_blk, k), unblocks(dv_blk, v)


@functools.lru_cache(maxsize=64)
def _flash_fn(causal, scale, block, mi=False, window=0):
    """Per-(causal, scale, block, mi, window) custom-VJP closure.

    ``custom_vjp`` needs the static config out of the traced signature;
    the cache keeps function identity stable so jit does not re-trace
    per call.  ``mi`` only changes the forward matmul form (serving
    bit-exactness); the recompute backward keeps the einsum form —
    gradients carry no M-invariance contract.  ``window`` masks
    identically in forward and backward (a windowed-out key gets exactly
    zero probability and zero gradient).
    """

    @jax.custom_vjp
    def attn(q, k, v):
        out, _ = _flash_forward(q, k, v, causal, scale, block, mi=mi,
                                window=window)
        return out

    def fwd(q, k, v):
        out, lse = _flash_forward(q, k, v, causal, scale, block, mi=mi,
                                  window=window)
        return out, (q, k, v, out, lse)

    def bwd(res, g):
        q, k, v, out, lse = res
        return _flash_backward(q, k, v, out, lse, g, causal, scale, block,
                               window=window)

    attn.defvjp(fwd, bwd)
    return attn


def flash_attention(q, k, v, causal=True, scale=None, block=None,
                    mi=False, window=0):
    """Blockwise online-softmax attention, O(T·block) peak memory.

    q/k/v: (..., T, D) with identical leading dims (batch, heads are
    free).  Ragged T is handled by padding the last K/V block and
    masking the padded keys to ``-inf``.  Differentiable via a
    recompute-based ``custom_vjp`` (no stored probabilities).  ``mi``
    selects M-invariant forward matmuls so per-row outputs do not depend
    on how many query rows share the call (see :func:`_qk_scores`).
    ``window > 0`` limits each query to the most recent ``window`` keys
    (sliding-window attention; see :func:`attend_block`).
    """
    d = q.shape[-1]
    t = k.shape[-2]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if block is None:
        # default only: clamp to T so short sequences do not pay padding.
        # An explicit block is honored verbatim — serving bit-exactness
        # needs the accumulation width fixed across different T.
        block = min(attention_block_size(), max(t, 1))
    return _flash_fn(bool(causal), float(scale), int(block),
                     bool(mi), int(window))(q, k, v)


# ---------------------------------------------------------------------------
# single-query paged decode kernel (serving)
# ---------------------------------------------------------------------------

def decode_attention(q, k_ctx, v_ctx, lengths, scale=None, block=None,
                     mi=False, k_scale=None, v_scale=None, window=0,
                     k_positions=None):
    """One autoregressive decode step of attention over a paged KV
    context: the O(1)-per-token serving counterpart of
    :func:`flash_attention`, built from the same :func:`attend_block`
    online-softmax primitive so the two paths cannot drift numerically.

    q: (S, H, Q, D) — ``Q`` queries per batch slot (1 for the decode
    step, ``K+1`` for the speculative verify step); k_ctx/v_ctx:
    (S, H, Tcap, D) — the slot's gathered KV pages, where ``Tcap`` is the
    fixed page capacity and rows at positions >= the valid length are
    stale/garbage; lengths: (S,) int — valid context length per slot
    (INCLUDING the current token, whose KV the caller appends before
    attending) — or (S, Q) int for a per-query-row valid length (the
    verify step: row ``j`` at absolute position ``L + j`` sees exactly
    ``L + j + 1`` keys, which is the causal mask expressed as raggedness).
    ``Tcap`` must be a multiple of ``block`` (the page size, for the
    paged cache).  Fully-masked blocks are exact no-ops in the online
    merge (correction 1, p 0), so visiting the blocks with the validity
    mask reproduces the reference forward's merge sequence bit-for-bit
    when ``mi=True``, and the loop stops after the last block any query
    row of the call can see: ``ceil(max(lengths) / block)`` visits of
    the ``Tcap / block``, a traced trip count (one executable whatever
    the lengths; not differentiable, as serving never is), which gives
    every row, padding rows included, what the whole walk gives, bit for
    bit.  A prefill chunk at ``offset`` of ``t_b`` rows passes horizons
    up to ``offset + t_b`` and pays for that many keys, not for the
    slot's whole page table.

    ``k_scale``/``v_scale``: optional (S, Tcap) float32 per-position
    scales of a quantized KV context (``quantize.kv_quantize_rows``
    rows).  Dequantization happens HERE, per block inside the scan —
    an elementwise convert + multiply feeding the score/value matmuls
    directly, so XLA fuses it into the attention kernel and the f32
    context never materializes at (S, H, Tcap, D).

    ``window > 0`` adds the sliding-window lower bound: a context row is
    visible only when its position ``p`` satisfies
    ``valid_len - 1 - window < p <= valid_len - 1``.  ``k_positions``
    (optional, (S, Tcap) int32) gives each context row an explicit
    absolute position — the windowed-layer ring gather rotates a slot's
    ring pages into ascending-position order and labels each row, so
    rows that wrapped (or were never written) carry positions outside
    the window (or < 0) and mask out exactly.  Because the gathered
    blocks are page-aligned at the same absolute boundaries the
    reference forward uses, the online merge visits visible blocks in
    the same order with the same masks — windowed decode stays
    bit-exact against the windowed reference under ``mi=True``.  With
    ``k_positions`` a row's index says nothing of its horizon, so such
    a call walks every block.
    """
    d = q.shape[-1]
    t_cap = k_ctx.shape[-2]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if block is None:
        block = attention_block_size()
    block = min(block, max(t_cap, 1))
    if t_cap % block:
        raise MXNetError(
            "decode_attention: context capacity %d not a multiple of "
            "block %d" % (t_cap, block))
    nblk = t_cap // block
    q32 = q.astype(jnp.float32) * scale
    acc0 = jnp.zeros(q.shape[:-1] + (v_ctx.shape[-1],), jnp.float32)
    m0 = jnp.full(q.shape[:-1] + (1,), -jnp.inf, jnp.float32)
    l0 = jnp.zeros(q.shape[:-1] + (1,), jnp.float32)
    if lengths.ndim == 2:
        if lengths.shape != (q.shape[0], q.shape[-2]):
            raise MXNetError(
                "decode_attention: per-row lengths %r do not match query "
                "rows %r" % (lengths.shape, (q.shape[0], q.shape[-2])))
        # (S, 1, Q, 1) so each query row carries its own validity horizon
        valid_len = lengths[:, None, :, None]
    else:
        # (S, 1, 1, 1) so the mask broadcasts against (S, H, Q, block)
        valid_len = lengths.reshape(lengths.shape + (1,) * (q.ndim - 1))
    if k_positions is not None or nblk == 1:
        # explicit positions: a row's index says nothing of its horizon;
        # one block: nothing to skip, and a constant bound of 1 is no loop
        live_blocks = nblk
    else:
        # rows at index >= max(lengths) are masked for every query row
        live_blocks = jnp.clip((jnp.max(lengths) + block - 1) // block,
                               0, nblk)

    def rows(x, it, axis):
        return lax.dynamic_slice_in_dim(x, it * block, block, axis=axis)

    def body(it, carry):
        kblk, vblk = rows(k_ctx, it, -2), rows(v_ctx, it, -2)
        if k_scale is not None:  # in-kernel dequant of quantized pages
            # (S, block) -> (S, 1, block, 1) against (S, H, block, D)
            kblk = kblk.astype(jnp.float32) \
                * rows(k_scale, it, 1)[:, None, :, None]
            vblk = vblk.astype(jnp.float32) \
                * rows(v_scale, it, 1)[:, None, :, None]
        if k_positions is not None:
            # explicit per-row absolute positions (ring gather): rows
            # that wrapped or were never written carry positions outside
            # [0, valid_len) and mask out exactly
            kp = rows(k_positions, it, 1)[:, None, None, :]
            pos = valid_len - 1  # query row's absolute position
            kv_valid = (kp >= 0) & (kp <= pos)
            if window:
                kv_valid = kv_valid & (kp > pos - window)
        else:
            k_pos = it * block + jnp.arange(block)
            kv_valid = k_pos < valid_len
            if window:
                kv_valid = kv_valid & (k_pos >= valid_len - window)
        return attend_block(q32, kblk, vblk, *carry, kv_valid=kv_valid,
                            mi=mi)

    acc, _, l = lax.fori_loop(0, live_blocks, body, (acc0, m0, l0))
    return finalize_attention(acc, l).astype(q.dtype)


# keys one iteration of the paged reader's loop takes.  On a v5e, at 16
# slots x 16 heads x 128, pages of 16: 8 pages an iteration served 5.6 %
# more tokens/s than 1 (4 pages 4.9 %), 16 nothing more and short
# contexts pay for the rounding up (PERF.md, PR 27)
_PAGED_KEYS_PER_ITERATION = 128


def paged_decode_attention(q, k_pool, v_pool, layer, tables, lengths,
                           page_size, mi=False, k_scale=None, v_scale=None,
                           scale=None):
    """The decode step's attention, reading KV pages where they lie.

    :func:`decode_attention` over ``kv_cache.read_context``'s gather without
    that ``(S, Tcap, H, D)`` copy of every slot's whole page table, and without
    the blocks past the longest live context: each iteration gathers the
    next few pages of every slot from the pool and merges them, page by
    page in ascending order, with the same :func:`attend_block` and the
    same validity mask.  A block that every slot masks is an exact no-op
    of the online merge (correction 1, p 0), so stopping after the last
    block any slot can see gives :func:`decode_attention`'s result bit for
    bit, ``mi`` or not.  Its cost follows the longest live context, not
    the table's capacity.

    That is the ``fori_loop``, on every backend and for every call.  On a
    TPU a call that :func:`~.paged_attention.paged_attention_eligible`
    accepts (not ``mi``, no scales, float32 pools in either layout the
    cache gives them: heads of 128 on an axis of their own, or heads that
    divide a lane tile or are whole lane tiles folded into a last axis of
    whole lane tiles, under a table of at least 2 048 keys) is
    instead ONE Pallas kernel (``ops/paged_attention.py``) that walks each
    slot's own pages and stops at that slot's length, at the loop's
    precision; the loop is its fallback (``mi``, quantized or bfloat16
    pages, heads of 256 on an axis of their own, folded pools under short
    tables, every other backend) and its oracle.  Which ran
    is noted in the trace under way (``paged_kernel_layers``, which
    ``InferenceSession.decode_report()`` hands on).

    q: (S, H, R, D): one query row a slot and head (R = 1), or the R
    query heads that share key/value head H as its rows (grouped-query
    attention; they share the slot's length too); k_pool/v_pool: as
    ``serve/kv_cache.py`` lays them out at rest (its ``kv_pool_shape``),
    read through its ``read_pages``, which hands back the gathered pages
    as (..., page_size, H, D) whatever that layout is;
    ``layer`` the pool's layer to read; tables: (S, max_pages) int32 (rows
    past a slot's reservation name the trash page, which is in bounds);
    lengths: (S,) int, valid rows INCLUDING the current token, which the
    caller has appended; ``k_scale``/``v_scale``: (L, pages + 1, page_size)
    float32 scale pools of quantized pages, dequantized per page inside
    the loop; ``scale`` multiplies the scores (1 / sqrt(D) by default).
    Traced bound, so not differentiable: decode never is.
    """
    # called from a traced step, long after both packages are loaded
    # (``serve`` imports this module while it is itself imported)
    from ..serve.kv_cache import pool_heads, read_pages
    from ..serve.model import note_traced

    s, max_pages = tables.shape
    d = q.shape[-1]
    heads = pool_heads(k_pool, d)
    if q.shape[1] != heads or lengths.ndim != 1:
        raise MXNetError(
            "paged_decode_attention takes the pool's %d heads and one "
            "length a slot, got q %r, lengths %r"
            % (heads, q.shape, lengths.shape))
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if paged_attention_eligible(q, k_pool, v_pool, mi, k_scale, v_scale,
                                max_pages * page_size):
        note_traced("paged_kernel_layers", 1)
        return paged_attention(q, k_pool, v_pool, layer, tables, lengths,
                               page_size, scale)
    group = max(1, min(_PAGED_KEYS_PER_ITERATION // page_size, max_pages))
    # columns that complete the last group lie past every horizon
    # (position >= max_pages * page_size >= lengths): any page in bounds
    pad = -max_pages % group
    if pad:
        tables = jnp.concatenate(
            [tables, jnp.broadcast_to(tables[:, -1:], (s, pad))], axis=1)
    q32 = q.astype(jnp.float32) * scale
    valid_len = lengths.reshape(lengths.shape + (1,) * (q.ndim - 1))
    live_pages = jnp.clip((jnp.max(lengths) + page_size - 1) // page_size,
                          0, max_pages)

    def body(it, carry):
        cols = lax.dynamic_slice_in_dim(tables, it * group, group, axis=1)
        k_grp = read_pages(k_pool, layer, cols, d)  # (S, group, page, H, D)
        v_grp = read_pages(v_pool, layer, cols, d)
        if k_scale is not None:
            ks_grp, vs_grp = k_scale[layer, cols], v_scale[layer, cols]
        for g in range(group):
            # (S, page, H, D) -> (S, H, page, D), this page alone
            kblk = k_grp[:, g].transpose(0, 2, 1, 3)
            vblk = v_grp[:, g].transpose(0, 2, 1, 3)
            if k_scale is not None:  # in-kernel dequant of quantized pages
                kblk = kblk.astype(jnp.float32) \
                    * ks_grp[:, g][:, None, :, None]
                vblk = vblk.astype(jnp.float32) \
                    * vs_grp[:, g][:, None, :, None]
            k_pos = (it * group + g) * page_size + jnp.arange(page_size)
            carry = attend_block(q32, kblk, vblk, *carry,
                                 kv_valid=k_pos < valid_len, mi=mi)
        return carry

    acc0 = jnp.zeros(q.shape, jnp.float32)
    m0 = jnp.full(q.shape[:-1] + (1,), -jnp.inf, jnp.float32)
    l0 = jnp.zeros(q.shape[:-1] + (1,), jnp.float32)
    acc, _, l = lax.fori_loop(0, (live_pages + group - 1) // group, body,
                              (acc0, m0, l0))
    return finalize_attention(acc, l).astype(q.dtype)


def paged_prefill_attention(q, k_pool, v_pool, layer, table_row, positions,
                            page_size, block, mi=False, k_scale=None,
                            v_scale=None, scale=None, horizons=None):
    """A prefill chunk's attention over the slot's pages, the chunk's own
    rows among them (the caller has appended them).

    On every backend, and for every call the kernel refuses, that is
    ``kv_cache.read_context``'s gather of the slot's whole table and
    :func:`decode_attention` over it in key blocks of ``block`` with a
    horizon a query row: the bounded scan, which ends at the chunk's
    furthest horizon.  On a TPU a call that
    :func:`~.paged_attention.paged_prefill_eligible` accepts (the rules of
    :func:`paged_decode_attention`'s kernel: not ``mi``, no scales,
    float32 pools in either layout the cache gives them, a folded pool
    under a table of at least 2 048 keys) is instead ONE Pallas kernel
    (``ops/paged_attention.py:paged_prefill``) that reads the pages where
    they lie, each tile of query rows up to its own furthest horizon, and
    writes no score to HBM, at the scan's precision; the scan is its
    fallback and its oracle.  Which ran is noted in the trace under way
    (``prefill_kernel_layers``, with the kernel's tile in tokens and its
    key block summed over those layers beside it, from which
    ``InferenceSession.prefill_report()`` counts the rows visited).

    q: (T, H, G, D), the chunk's T tokens' query heads, the G that share
    key/value head H side by side; k_pool / v_pool, ``layer``,
    ``k_scale`` / ``v_scale`` (scale pools of quantized pages) and
    ``scale`` as :func:`paged_decode_attention` takes them; table_row
    (max_pages,) int32, the slot's pages; positions (T,) int, each
    token's row in the slot: a token's query heads see the rows up to
    and including its own, ``positions + 1`` keys, unless ``horizons``
    (T,) gives each token the keys it sees (a diffusion block's rows see
    each other).  A horizon is data: nothing assumes causality.
    -> (T, H, G x D): each token's results, a key/value head's query
    heads side by side.  Traced bound, so not differentiable."""
    # called from a traced step, long after both packages are loaded
    from ..serve.kv_cache import read_context
    from ..serve.model import note_traced

    t, heads, group, d = q.shape
    max_pages = table_row.shape[0]
    if paged_prefill_eligible(q, k_pool, v_pool, mi, k_scale, v_scale,
                              max_pages * page_size):
        if scale is None:
            scale = 1.0 / (d ** 0.5)
        if horizons is None:
            horizons = positions + 1
        tile, pages = prefill_tiling(t * group, d, k_pool.ndim == 4, heads,
                                     page_size, max_pages)
        note_traced("prefill_kernel_layers", 1)
        # summed over those layers: what ``prefill_report()`` counts from
        note_traced("prefill_kernel_tile_rows", tile)
        note_traced("prefill_kernel_query_heads", group)
        note_traced("prefill_kernel_block_keys", pages * page_size)
        att = paged_prefill(
            q.transpose(1, 0, 2, 3).reshape(heads, t * group, d), k_pool,
            v_pool, layer, table_row, jnp.repeat(horizons, group), page_size,
            scale, tile, pages)
        return att.reshape(heads, t, group * d).transpose(1, 0, 2)
    # the gathers first, then the query's rows and the horizons: the order
    # the blocks wrote them in, which the lowered text keeps
    ctx_k = read_context(k_pool, layer, table_row, d)
    ctx_v = read_context(v_pool, layer, table_row, d)
    if k_scale is not None:
        k_scale = k_scale[layer, table_row].reshape(1, max_pages * page_size)
        v_scale = v_scale[layer, table_row].reshape(1, max_pages * page_size)
    # a key/value head's query heads are its rows: row t * group + g sees
    # the keys token t sees
    att = decode_attention(
        q.transpose(1, 0, 2, 3).reshape(1, heads, t * group, d), ctx_k,
        ctx_v, jnp.repeat(positions + 1 if horizons is None else horizons,
                          group)[None],
        scale=scale, block=block, mi=mi, k_scale=k_scale, v_scale=v_scale)
    return att.reshape(heads, t, group * d).transpose(1, 0, 2)


# ---------------------------------------------------------------------------
# Pallas fused kernel (TPU) + dispatcher
# ---------------------------------------------------------------------------

# the library kernel tiles the sequence in multiples of 128 and asserts
# that a block divides its axis; Mosaic tiles the head dim in lanes of 128
# and accepts a half lane
_PALLAS_T_BLOCK = 128
_PALLAS_D_MULTIPLE = 64
# block lengths, from a sweep of the three kernels alone on a v5e chip
# (PERF.md, Findings PR 31): an outer block of 1024 rows and inner steps
# of 512 are within a few per cent of the best at every shape read; the
# library's 128 on every axis pays the grid's per-step overhead 4-5 times
# over.  The dq kernel's ``di`` operand is broadcast in HBM to its outer k
# block's width, so that one stays at 512.
_PALLAS_MAJOR_ROWS = 1024
_PALLAS_MINOR_ROWS = 512
# a row of a tile is d * itemsize bytes as an operand and d * 4 in the
# kernels' float32 accumulators; 2 MiB of rows is what the widest shape
# compiled for the chip holds (float32, d 256: 1024 rows;
# tests/test_tpu_compile.py), and wider heads get shorter blocks
_PALLAS_TILE_BYTES = 2 << 20


def pallas_eligible(q, k, v, window=0):
    """Whether ``auto`` sends this call to the library Pallas flash
    kernel on TPU — a decision from what is visible at trace time
    (rank, shapes, dtype, ``window``), never from whether a trial call
    raised.  The kernel takes ``(n, h, T, d)`` floating inputs of one
    shape family, ``T`` a multiple of its 128 block on both sides,
    ``d`` a multiple of 64, and has no sliding-window mask."""
    if window or q.ndim != 4 or k.shape != v.shape:
        return False
    if q.shape[:2] != k.shape[:2] or q.shape[-1] != k.shape[-1]:
        return False
    if not jnp.issubdtype(q.dtype, jnp.floating):
        return False
    return (q.shape[-2] % _PALLAS_T_BLOCK == 0
            and k.shape[-2] % _PALLAS_T_BLOCK == 0
            and q.shape[-1] % _PALLAS_D_MULTIPLE == 0)


def _largest_block(axis, limit):
    """The largest multiple of 128 that divides ``axis`` and is at most
    ``limit`` (itself at least 128, which divides every axis
    :func:`pallas_eligible` admits)."""
    return max(b for b in range(_PALLAS_T_BLOCK, limit + 1, _PALLAS_T_BLOCK)
               if axis % b == 0)


def pallas_block_sizes(q, k):
    """The library kernel's ``BlockSizes`` for an eligible call, forward
    and both backward kernels, from what is visible at trace time: the
    two sequence lengths, the head dim and the dtype's itemsize.  Every
    block divides its axis (1152 gets 384, 128 gets 128), an inner block
    divides its outer block, and a wide head shortens the blocks so that
    the tiles fit VMEM."""
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes

    q_len, kv_len, d = q.shape[-2], k.shape[-2], q.shape[-1]
    rows = max(_PALLAS_T_BLOCK, _PALLAS_TILE_BYTES
               // (d * (jnp.dtype(q.dtype).itemsize + 4)))
    major, minor = min(_PALLAS_MAJOR_ROWS, rows), min(_PALLAS_MINOR_ROWS, rows)
    q_major = _largest_block(q_len, major)
    k_major = _largest_block(kv_len, major)
    q_minor = _largest_block(q_major, minor)
    k_minor = _largest_block(k_major, minor)
    k_dq = _largest_block(kv_len, minor)
    return BlockSizes(
        block_q=q_major, block_k_major=k_major, block_k=k_minor, block_b=1,
        block_q_major_dkv=q_major, block_q_dkv=q_minor,
        block_k_major_dkv=k_major, block_k_dkv=k_minor,
        block_q_dq=q_major, block_k_major_dq=k_dq, block_k_dq=k_dq)


def _pallas_attention(q, k, v, causal, scale):
    """TPU fused flash kernel (Mosaic), tiled by
    :func:`pallas_block_sizes`.  Callers check :func:`pallas_eligible`
    first; whatever the kernel or its compiler then raises is an error of
    the step, not a reason to change path."""
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        flash_attention as pl_flash)

    return pl_flash(q, k, v, causal=causal, sm_scale=scale,
                    block_sizes=pallas_block_sizes(q, k))


def dot_product_attention(q, k, v, causal=True, scale=None, impl=None,
                          block=None, window=0):
    """Dispatch attention to the implementation ``MXNET_ATTN_IMPL`` (or
    the explicit ``impl`` argument) selects.

    ``auto`` takes the Pallas fused kernel when tracing for TPU and
    :func:`pallas_eligible` says the call fits it, and the portable
    ``lax`` blockwise kernel otherwise — which is also what ``flash``
    forces, so the CPU tier-1 rig and ineligible TPU calls run identical
    code.  The choice is made before the kernel is called; an error
    from either kernel propagates.
    """
    impl = (impl or attention_impl()).strip().lower()
    if impl not in _IMPLS:
        raise MXNetError("attention impl %r not in %s" % (impl, _IMPLS))
    if impl == "reference":
        return reference_attention(q, k, v, causal=causal, scale=scale,
                                   window=window)
    if impl == "auto" and jax.default_backend() == "tpu" \
            and pallas_eligible(q, k, v, window):
        if scale is None:
            scale = 1.0 / (q.shape[-1] ** 0.5)
        return _pallas_attention(q, k, v, causal, scale)
    return flash_attention(q, k, v, causal=causal, scale=scale,
                           block=block, window=window)
