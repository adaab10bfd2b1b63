"""The decode step's paged attention as one kernel (Pallas, TPU).

``ops/attention.py:paged_decode_attention`` as a ``fori_loop`` gathers the
next few pages of EVERY slot up to the longest live context and masks what
a shorter slot cannot see.  This kernel walks, for each slot, **that
slot's** pages ``0 .. ceil(lengths[s] / page_size) - 1`` and no others:

* the pools stay where they lie, whole, in HBM (``memory_space=ANY``):
  the caller's ``(layers, pages, page_size, H, D)`` is handed over as
  ``(layers * pages, page_size x H, D)`` (the layers merge into the
  pages, and a page's rows into its heads, which are whole sublane tiles:
  a bitcast) and the page numbers as ``tables + layer * pages``.  Never
  ``pool[layer]``: XLA materialises such a slice in front of a custom
  call, a whole layer of the pool a call;
* ``lengths`` and the tables are scalar-prefetched.  The grid is the
  slots; inside a slot the kernel loops over blocks of ``pages_per_block``
  pages.  A page, ``(page_size x H, D)`` contiguous, K and V each, is
  one DMA HBM -> VMEM (64 KB at 16 x 8 x 128 float32); a block's copies
  are all in flight at once and the NEXT block's (the slot's next, or the
  next slot's first) are started before the current block is computed,
  into the other half of a double buffer.  A page past the slot's own
  length is neither copied nor computed: its copy is not started, and its
  rows are masked;
* in VMEM one key/value head's rows are a strided load of the block seen
  as ``(rows x H, D)``; scores and the value product go to the MXU with
  the ``R`` query rows that share the head as the left operand (padded to
  a sublane tile of 8), operands rounded to bfloat16 where they are read
  and accumulated in float32: what ``jnp.einsum`` at default precision
  does with the loop's float32 operands on this chip, **the same
  precision, not a lower one**.  Where the process asks for full-precision
  matmuls (``jax_default_matmul_precision`` ``highest`` / ``float32``, as
  the tests do) the operands stay float32, as the loop's einsum's do.  The
  online softmax (running maximum, sum, correction) is
  ``online_block_merge``'s in float32, with a large finite negative in
  place of ``-inf`` so that no ``isfinite`` guard is needed; the mask is
  ``k_pos < lengths[s]``.  A slot of length 0 reads nothing and gives 0,
  as the loop does.

**VMEM** (:func:`_vmem_bytes`): the double buffer, ``2 x 2 x
pages_per_block x page`` (4 MB at 8 pages of 128 KB, the dense cell's),
the query and result blocks twice each, the running statistics, and slack
for Mosaic's own scratch; stated as ``vmem_limit_bytes``.

Stale rows (a page of the block that was not copied this time) hold what
an earlier copy left: finite by the pool's own contract (the loop's
``0 x garbage`` needs the same).  The value buffer is zeroed once, at the
first slot, because memory never written may hold anything.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["paged_attention", "paged_attention_eligible", "kernel_name",
           "pages_per_block"]

_LANES = 128        # a head's width the MXU takes whole
_SUBLANES = 8       # rows of a float32 tile
_VMEM_SLACK = 8 << 20   # Mosaic's own scratch and what the sum leaves out
_NT = (((1,), (1,)), ((), ()))   # contract the last axis of both operands
# in place of -inf: exp(_NEG - m) is 0 for any real m and _NEG - _NEG is 0
_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)
# keys a block holds: 8 pages of 16 rows.  PERF.md (PR 44) has the sweep
_KEYS_PER_BLOCK = 128


def paged_attention_eligible(q, k_pool, v_pool, mi, k_scale, v_scale):
    """Whether ``paged_decode_attention`` sends this call to the kernel: a
    decision from what the call shows at trace time, never from whether a
    trial call raised.  The backend is TPU; ``mi`` (the M-invariant reduce
    form) is not asked; the pages are not quantized (the loop dequantises
    a page at a time); the pools are float32 and keep their heads on an
    axis of their own (a folded pool keeps the loop), a multiple of 8 of
    them (whole sublane tiles), each of 128 values: Mosaic's strided load,
    which takes one head's rows out of a page, wants a last axis of one
    lane tile (the installed library kernel notes the same), so a head of
    256 keeps the loop as well."""
    if jax.default_backend() != "tpu" or mi:
        return False
    if k_scale is not None or v_scale is not None:
        return False
    if k_pool.ndim != 5 or k_pool.shape != v_pool.shape:
        return False
    if k_pool.dtype != jnp.float32 or v_pool.dtype != jnp.float32:
        return False
    if q.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    heads, d = k_pool.shape[3:]
    return d == _LANES and heads % _SUBLANES == 0


def pages_per_block(page_size, max_pages):
    """Pages one block of the kernel holds: ``_KEYS_PER_BLOCK`` keys'
    worth, at least one and at most the table."""
    return max(1, min(_KEYS_PER_BLOCK // page_size, max_pages))


def kernel_name(pages):
    """The ``pallas_call``'s name, which carries its block: what a trace's
    device operations show of this reader (``paged_decode_attention_p8``:
    8 pages a block)."""
    return "paged_decode_attention_p%d" % pages


def _full_precision():
    """Whether the process asks matmuls for float32 operands: then the
    kernel keeps them, as the loop's einsum does."""
    return jax.config.jax_default_matmul_precision in ("highest", "float32")


def _vmem_bytes(pages, page_size, heads, rows, d):
    """The kernel's VMEM need (the module docstring's reckoning), all of
    it float32."""
    buffers = 2 * 2 * pages * page_size * heads * d * 4
    blocks = 2 * 2 * heads * rows * d * 4
    stats = heads * rows * (d + 2 * _LANES) * 4
    return buffers + blocks + stats + _VMEM_SLACK


def _decode_kernel(lengths_ref, tables_ref, q_ref, k_hbm, v_hbm, o_ref,
                   k_buf, v_buf, sems, m_ref, l_ref, acc_ref, buf_ref, *,
                   page_size, width, pages, full_precision):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slot = pl.program_id(0)
    slots = pl.num_programs(0)
    heads, rows, d = acc_ref.shape
    keys = pages * page_size
    f32 = jnp.float32
    operand = f32 if full_precision else jnp.bfloat16
    precision = lax.Precision.HIGHEST if full_precision else None

    def live_pages(s):   # the lengths come clamped to the table
        return pl.cdiv(lengths_ref[s], page_size)

    def blocks_of(s):   # a slot of length 0 still takes its turn: one
        return jnp.maximum(pl.cdiv(live_pages(s), pages), 1)  # masked block

    def copies(buf, i, page):
        at = pl.ds(i * page_size * heads, page_size * heads)
        return (pltpu.make_async_copy(k_hbm.at[page], k_buf.at[buf, at],
                                      sems.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[page], v_buf.at[buf, at],
                                      sems.at[1, buf]))

    def live_copies(s, blk, buf, start):
        """Start, or wait for, the copies of block ``blk`` of slot ``s``:
        those of its pages that the slot's length reaches."""
        live = live_pages(s) - blk * pages
        for i in range(pages):
            @pl.when(i < live)
            def _():
                # a wait needs the copy's shape and semaphore, not its page
                page = tables_ref[s * width + blk * pages + i] \
                    if start else 0
                for copy in copies(buf, i, page):
                    copy.start() if start else copy.wait()

    @pl.when(slot == 0)
    def _():
        buf_ref[0] = 0
        v_buf[...] = jnp.zeros_like(v_buf)
        live_copies(0, 0, 0, start=True)

    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    length = lengths_ref[slot]
    n_blocks = blocks_of(slot)

    def block(blk, buf):
        last = blk + 1 >= n_blocks
        next_slot = jnp.where(last, slot + 1, slot)
        next_blk = jnp.where(last, 0, blk + 1)

        @pl.when(next_slot < slots)
        def _():
            live_copies(next_slot, next_blk, 1 - buf, start=True)

        live_copies(slot, blk, buf, start=False)
        k_pos = blk * keys + lax.broadcasted_iota(jnp.int32, (rows, keys), 1)
        seen = k_pos < length
        for h in range(heads):
            k = k_buf[buf, pl.ds(h, keys, stride=heads), :].astype(operand)
            v = v_buf[buf, pl.ds(h, keys, stride=heads), :].astype(operand)
            scores = lax.dot_general(
                q_ref[0, h].astype(operand), k, _NT, precision=precision,
                preferred_element_type=f32)
            scores = jnp.where(seen, scores, _NEG)
            m = m_ref[h]
            new_m = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))
            correction = jnp.exp(m - new_m)
            p = jnp.where(seen, jnp.exp(scores - new_m[:, :1]), 0.0)
            l_ref[h] = l_ref[h] * correction \
                + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * correction[:, :1] + lax.dot_general(
                p.astype(operand), v, (((1,), (0,)), ((), ())),
                precision=precision, preferred_element_type=f32)
            m_ref[h] = new_m
        return 1 - buf

    buf_ref[0] = lax.fori_loop(0, n_blocks, block, buf_ref[0])
    for h in range(heads):
        o_ref[0, h] = (acc_ref[h] / jnp.maximum(l_ref[h][:, :1], 1e-20)
                       ).astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, layer, tables, lengths, page_size,
                    scale, pages=None):
    """q (S, H, R, D); k_pool, v_pool (layers, pages, page_size, H, D)
    float32; tables (S, max_pages) int32; lengths (S,) int, the valid
    rows a slot; ``scale`` multiplies the scores.  -> (S, H, R, D) like q:
    softmax attention of each slot's R rows a head over that slot's first
    ``lengths[s]`` rows of layer ``layer``.  ``pages``: pages a block
    (:func:`pages_per_block` unless given).

    The layer's number is handed to the jitted body as data, so a step's
    layers share ONE trace and one lowering of the kernel: lowering it
    anew for each of the dense model's 24 layers added 23 s to a
    session's start with every executable already in the compile cache
    (PERF.md, PR 44)."""
    if pages is None:
        pages = pages_per_block(page_size, tables.shape[1])
    return _paged_attention(
        q, k_pool, v_pool, jnp.asarray(layer, jnp.int32), tables, lengths,
        page_size=page_size, scale=float(scale), pages=pages,
        full_precision=_full_precision())


@functools.partial(jax.jit, static_argnames=("page_size", "scale", "pages",
                                             "full_precision"))
def _paged_attention(q, k_pool, v_pool, layer, tables, lengths, *, page_size,
                     scale, pages, full_precision):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, heads, r, d = q.shape
    layers, pool_pages = k_pool.shape[:2]
    max_pages = tables.shape[1]
    rows = -(-r // _SUBLANES) * _SUBLANES
    # the table's columns past the last whole block are never a block's
    width = -(-max_pages // pages) * pages
    tables = jnp.pad(tables.astype(jnp.int32) + layer * pool_pages,
                     ((0, 0), (0, width - max_pages)))
    q32 = jnp.pad(q.astype(jnp.float32) * scale,
                  ((0, 0), (0, 0), (0, rows - r), (0, 0)))
    # a page as (rows x H, D): only leading axes merge, H whole sublane tiles
    flat = (layers * pool_pages, page_size * heads, d)
    kernel = functools.partial(
        _decode_kernel, page_size=page_size, width=width, pages=pages,
        full_precision=full_precision)
    block = pl.BlockSpec((1, heads, rows, d), lambda i, *_: (i, 0, 0, 0))
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(q32.shape, jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(s,),
            in_specs=[block, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=block,
            scratch_shapes=[
                pltpu.VMEM((2, pages * flat[1], d), k_pool.dtype),
                pltpu.VMEM((2, pages * flat[1], d), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((heads, rows, _LANES), jnp.float32),
                pltpu.VMEM((heads, rows, _LANES), jnp.float32),
                pltpu.VMEM((heads, rows, d), jnp.float32),
                pltpu.SMEM((1,), jnp.int32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_bytes(pages, page_size, heads, rows, d)),
        name=kernel_name(pages),
    )(jnp.minimum(lengths.astype(jnp.int32), max_pages * page_size),
      tables.reshape(-1), q32, k_pool.reshape(flat), v_pool.reshape(flat))
    return out[:, :, :r].astype(q.dtype)
