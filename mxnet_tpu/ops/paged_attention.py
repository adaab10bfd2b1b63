"""Paged attention as one kernel (Pallas, TPU): the decode step's, and (at
the end of the file) a prefill chunk's.

**The decode step's.**

``ops/attention.py:paged_decode_attention`` as a ``fori_loop`` gathers the
next few pages of EVERY slot up to the longest live context and masks what
a shorter slot cannot see.  This kernel walks, for each slot, **that
slot's** pages ``0 .. ceil(lengths[s] / page_size) - 1`` and no others,
over float32 pools in either layout ``serve/kv_cache.py:kv_pool_shape``
gives them at rest (:func:`paged_attention_eligible`: heads of 128 on an
axis of their own, or heads that divide a lane tile, or are whole lane
tiles, folded into the last axis).  One body; what differs between the layouts is how a page lies in
the buffer and how a head's rows are taken out of it, both read off the
buffers' shapes:

* the pools stay where they lie, whole, in HBM (``memory_space=ANY``):
  the caller's ``(layers, pages, page_size, H, D)`` is handed over as
  ``(layers * pages, page_size x H, D)`` (the layers merge into the
  pages, and a page's rows into its heads, which are whole sublane tiles:
  a bitcast), a folded ``(layers, pages, page_size, H x D)`` as
  ``(layers * pages, page_size, H x D)``, and the page numbers as
  ``tables + layer * pages``.  Never ``pool[layer]``: XLA materialises
  such a slice in front of a custom call, a whole layer of the pool a
  call;
* ``lengths`` and the tables are scalar-prefetched.  The grid is the
  slots; inside a slot the kernel loops over blocks of ``pages_per_block``
  pages.  A page, contiguous, K and V each, is one DMA HBM -> VMEM (64 KB
  at 16 x 8 x 128 float32, 32 KB at 16 x 512 folded); a block's copies
  are all in flight at once and the NEXT block's (the slot's next, or the
  next slot's first) are started before the current block is computed,
  into the other half of a double buffer.  A page past the slot's own
  length is neither copied nor computed: its copy is not started, and its
  rows are masked;
* **heads on their own axis**: in VMEM one key/value head's rows are a
  strided load of the block seen as ``(rows x H, D)``; scores and the
  value product go to the MXU with the ``R`` query rows that share the
  head as the left operand (padded to a sublane tile of 8);
* **folded heads**: the block is ``(keys, H x D)`` and a head of 64 is
  half a lane tile.  The kernel never slices narrower than a tile: for
  lane tile ``t`` (heads ``2t``, ``2t + 1``) the left operand holds both
  heads' ``R`` query rows one under the other, **block-diagonally inside
  the tile** (head ``2t``'s rows zero in lanes 64-127, head ``2t + 1``'s
  zero in lanes 0-63; built once, outside the kernel, from ``q``), so
  ``Q_t (2R, 128) x K[:, tile t]^T`` is each row's own head's scores and
  ``P (2R, keys) x V[:, tile t]`` is each row's result in its own head's
  64 lanes; its neighbour's 64 lanes are dropped outside the kernel.  Two
  MXU products a tile a block, the same products a byte as the other
  layout.  A lane slice of 64 a head instead costs twice the products
  and a lane shift: 346 GB/s on the live rows where this form reads 535
  at the same 8 pages a block (a v5e at LFM2-24B-A2B's pools; PERF.md,
  PR 48).  To the kernel a folded pool's "heads" are its lane tiles, of
  128 lanes each, and a tile's query rows are just rows: any head width
  that divides a tile takes the same form;
* **folded heads of whole lane tiles** (Qwen3-Next's 2 heads of 256 in 512
  lanes: two heads are no sublane tile, so the cache folds them): a head
  is a group of its own whose lanes are its two tiles, ``(keys, 256)`` of
  the block; the score product contracts over both tiles at once and the
  value product fills both, nothing is block-diagonal, and a key/value
  head's 8 query heads are exactly one sublane tile of rows
  (``paged_decode_attention_f256_p32``);
* operands are rounded to bfloat16 where they are read and accumulated in
  float32: what ``jnp.einsum`` at default precision does with the loop's
  float32 operands on this chip, **the same precision, not a lower one**
  (the block-diagonal zeros are exact).  Where the process asks for
  full-precision matmuls (``jax_default_matmul_precision`` ``highest`` /
  ``float32``, as the tests do) the operands stay float32, as the loop's
  einsum's do.  The online softmax (running maximum, sum, correction) is
  ``online_block_merge``'s in float32, with a large finite negative in
  place of ``-inf`` so that no ``isfinite`` guard is needed; the mask is
  ``k_pos < lengths[s]``.  A slot of length 0 reads nothing and gives 0,
  as the loop does.

What keeps the loop: ``mi`` (``exact=True``), ``kv_quant`` pages and
their scales, bfloat16 pools, heads of 256 on an axis of their own, a
folded last axis that is not whole lane tiles, a folded pool under a table of fewer than 2 048 keys
(granite-4.0-h-micro's 768: ``_FOLDED_MIN_TABLE_KEYS`` has why), every
backend but a TPU.

**VMEM** (:func:`_vmem_bytes`): the double buffer, ``2 x 2 x
pages_per_block x page`` (4 MB at 8 pages of 128 KB, the dense cell's,
and at 32 folded pages of 32 KB), the query and result blocks twice each,
the running statistics, and slack for Mosaic's own scratch; stated as
``vmem_limit_bytes``.

Stale rows (a page of the block that was not copied this time) hold what
an earlier copy left: finite by the pool's own contract (the loop's
``0 x garbage`` needs the same).  The value buffer is zeroed once, at the
first slot, because memory never written may hold anything.

**A prefill chunk's** (:func:`paged_prefill`; what
``ops/attention.py:paged_prefill_attention`` runs in the place of
``kv_cache.read_context``'s gather of the slot's whole table and
``decode_attention``'s bounded scan over it, for the calls
:func:`paged_prefill_eligible` accepts: the rules above).  One slot, many
query rows (a key/value head's query heads are its rows), and a horizon a
row, which is data: ``k_pos < horizons[row]``, so nothing assumes
causality.  The same pools whole in HBM, the same pages copied as they
lie into the same double buffer, the same two layouts and the same
block-diagonal left operand over a folded pool, the same precision.  What
differs: the grid runs over **tiles of query rows**, and inside a tile the
loop runs over key blocks up to **that tile's furthest horizon** (a scalar
a tile, prefetched), so a chunk at offset 0 pays its triangle in whole
blocks and a chunk at offset 6 144 pays 6 144 keys and its own triangle,
where the scan walked every row to the chunk's last horizon; the blocks
wholly under a tile's nearest horizon skip the mask; a (rows, keys) block
of scores lives and dies in VMEM, where the scan wrote every block's to
HBM and read it back.  Every column of the table names a page in bounds
(rows past a slot's reservation name the trash page), so a block's pages
are all copied, whatever the horizon: nothing stale is ever read.  And
the loops over a block's pages and over the heads stay loops in the
kernel (a page's and a head's number are data there): a head's work on a
block of 1 024 keys is thousands of vector operations, and written out a
head, a form (masked or not) and a page they made megabytes of code a
layer and seconds of lowering a bucket, which a session pays at every
start, compile cache or not.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["paged_attention", "paged_attention_eligible", "kernel_name",
           "pages_per_block", "paged_prefill", "paged_prefill_eligible",
           "prefill_kernel_name", "prefill_tiling"]

_LANES = 128        # a head's width the MXU takes whole
_SUBLANES = 8       # rows of a float32 tile
_VMEM_SLACK = 8 << 20   # Mosaic's own scratch and what the sum leaves out
_NT = (((1,), (1,)), ((), ()))   # contract the last axis of both operands
# in place of -inf: exp(_NEG - m) is 0 for any real m and _NEG - _NEG is 0
_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)
# keys a block holds: 8 pages of 16 rows where a page is 64-128 KB (heads of
# 128 on their own axis; PERF.md, PR 44, has the sweep), 32 pages where it
# is 32 KB (8 heads of 64 folded; PERF.md, PR 48): 0.5-1 MB of keys a block
_KEYS_PER_BLOCK = 128
_FOLDED_KEYS_PER_BLOCK = 512
# a folded pool's table narrower than this keeps the loop: four blocks.  At
# granite-4.0-h-micro's 768 keys the loop is 1.09 ms of a 23.5 ms decode
# event and the kernel took 0.45 ms off the event (+1.4 % tokens/s), while a
# process that had no Pallas kernel took 3.6-4.3 s longer to build its
# session (1.2 s of it the Pallas import), a tenth of that cell's set-up; at
# LFM2-24B-A2B's 9 216 keys the loop is 35 ms of a 42 ms event (PERF.md,
# PR 48)
_FOLDED_MIN_TABLE_KEYS = 2048


def paged_attention_eligible(q, k_pool, v_pool, mi, k_scale, v_scale,
                             table_keys):
    """Whether ``paged_decode_attention`` sends this call to the kernel: a
    decision from what the call shows at trace time, never from whether a
    trial call raised.  The backend is TPU; ``mi`` (the M-invariant reduce
    form) is not asked; the pages are not quantized (the loop dequantises
    a page at a time); the pools are float32 and lie in one of the two
    layouts ``serve/kv_cache.py:kv_pool_shape`` gives them.  **Heads on an
    axis of their own**: a multiple of 8 of them (whole sublane tiles),
    each of 128 values: Mosaic's strided load, which takes one head's rows
    out of a page, wants a last axis of one lane tile (the installed
    library kernel notes the same), so a head of 256 keeps the loop.
    **Heads folded into the last axis**: that axis is whole lane tiles and
    a head divides a tile (64: two heads a tile) or is whole tiles (256:
    two tiles a head), so no head shares a tile with part of another; and
    the table (``table_keys``: its columns x the page size, the
    longest context a slot can hold) is at least
    ``_FOLDED_MIN_TABLE_KEYS`` wide: over a shorter one the loop is too
    small a part of a step to be worth a kernel's start-up."""
    if jax.default_backend() != "tpu" or mi:
        return False
    if k_scale is not None or v_scale is not None:
        return False
    if k_pool.ndim not in (4, 5) or k_pool.shape != v_pool.shape:
        return False
    if k_pool.dtype != jnp.float32 or v_pool.dtype != jnp.float32:
        return False
    if q.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    if k_pool.ndim == 4:
        d = q.shape[-1]
        return (k_pool.shape[3] % _LANES == 0
                and (_LANES % d == 0 or d % _LANES == 0)
                and table_keys >= _FOLDED_MIN_TABLE_KEYS)
    heads, d = k_pool.shape[3:]
    return d == _LANES and heads % _SUBLANES == 0


def pages_per_block(page_size, max_pages, folded):
    """Pages one block of the kernel holds: ``_KEYS_PER_BLOCK`` keys'
    worth (``_FOLDED_KEYS_PER_BLOCK`` over a folded pool, whose pages are
    smaller), at least one and at most the table."""
    keys = _FOLDED_KEYS_PER_BLOCK if folded else _KEYS_PER_BLOCK
    return max(1, min(keys // page_size, max_pages))


def kernel_name(pages, folded_head):
    """The ``pallas_call``'s name, which carries its block and, over a
    folded pool, the width of the heads a lane tile holds (``folded_head``;
    0 where the heads keep their own axis): what a trace's
    device operations show of this reader (``paged_decode_attention_p8``:
    8 pages a block, heads on their own axis;
    ``paged_decode_attention_f64_p32``: 32 pages, heads of 64 folded)."""
    layout = "f%d_" % folded_head if folded_head else ""
    return "paged_decode_attention_%sp%d" % (layout, pages)


def _full_precision():
    """Whether the process asks matmuls for float32 operands: then the
    kernel keeps them, as the loop's einsum does."""
    return jax.config.jax_default_matmul_precision in ("highest", "float32")


def _vmem_bytes(pages, page_size, heads, rows, d):
    """The kernel's VMEM need (the module docstring's reckoning), all of
    it float32; a folded pool's ``heads`` are its lane tiles, of ``d`` =
    128."""
    buffers = 2 * 2 * pages * page_size * heads * d * 4
    blocks = 2 * 2 * heads * rows * d * 4
    stats = heads * rows * (d + 2 * _LANES) * 4
    return buffers + blocks + stats + _VMEM_SLACK


def _page_copies(k_hbm, v_hbm, k_buf, v_buf, sems, page_rows, buf, i, page):
    """The two copies, K's and V's, of pool page ``page`` into row ``i`` of
    half ``buf`` of the double buffer."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    at = pl.ds(i * page_rows, page_rows)
    return (pltpu.make_async_copy(k_hbm.at[page], k_buf.at[buf, at],
                                  sems.at[0, buf]),
            pltpu.make_async_copy(v_hbm.at[page], v_buf.at[buf, at],
                                  sems.at[1, buf]))


def _merge_block(q_of, k_buf, v_buf, buf, seen, m_ref, l_ref, acc_ref, keys,
                 full_precision, rolled):
    """Merge the key block in half ``buf`` of the double buffer into every
    head's running maximum, sum and accumulator (``online_block_merge``'s
    arithmetic, ``_NEG`` in place of ``-inf``).  ``q_of(h)``: head ``h``'s
    query rows (a folded pool's ``h`` is a lane tile); ``seen`` (rows,
    keys) bool, or None where every row sees the whole block.  ``rolled``:
    the heads are a loop of the kernel's and ``h`` is data (the prefill
    kernel, whose one head's work on a block is thousands of vector
    operations: written out sixteen times in two forms they were most of
    3 MB of code a layer and of the seconds a lowering took, PERF.md,
    PR 51), or the loop is written out (decode's few rows a head)."""
    from jax.experimental import pallas as pl

    heads, _, d = acc_ref.shape
    folded = k_buf.shape[2] != d
    f32 = jnp.float32
    operand = f32 if full_precision else jnp.bfloat16
    precision = lax.Precision.HIGHEST if full_precision else None

    def head(h, _):
        # a head's rows of the block, or a lane tile's columns of it
        at = (slice(None), pl.ds(pl.multiple_of(h * d, d) if rolled
                                 else h * d, d)) if folded \
            else (pl.ds(h, keys, stride=heads), slice(None))
        k = k_buf[(buf,) + at].astype(operand)
        v = v_buf[(buf,) + at].astype(operand)
        scores = lax.dot_general(
            q_of(h).astype(operand), k, _NT, precision=precision,
            preferred_element_type=f32)
        if seen is not None:
            scores = jnp.where(seen, scores, _NEG)
        m = m_ref[h]
        new_m = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))
        correction = jnp.exp(m - new_m)
        p = jnp.exp(scores - new_m[:, :1])
        if seen is not None:
            p = jnp.where(seen, p, 0.0)
        l_ref[h] = l_ref[h] * correction \
            + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[h] = acc_ref[h] * correction[:, :1] + lax.dot_general(
            p.astype(operand), v, (((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=f32)
        m_ref[h] = new_m
        return _

    if rolled:
        lax.fori_loop(0, heads, head, 0)
    else:
        for h in range(heads):
            head(h, 0)


def _decode_kernel(lengths_ref, tables_ref, q_ref, k_hbm, v_hbm, o_ref,
                   k_buf, v_buf, sems, m_ref, l_ref, acc_ref, buf_ref, *,
                   page_size, width, pages, full_precision):
    from jax.experimental import pallas as pl

    slot = pl.program_id(0)
    slots = pl.num_programs(0)
    # heads, or a folded pool's lane tiles: the buffers' shape says which
    heads, rows, _ = acc_ref.shape
    keys = pages * page_size
    copies = functools.partial(_page_copies, k_hbm, v_hbm, k_buf, v_buf, sems,
                               k_buf.shape[1] // pages)

    def live_pages(s):   # the lengths come clamped to the table
        return pl.cdiv(lengths_ref[s], page_size)

    def blocks_of(s):   # a slot of length 0 still takes its turn: one
        return jnp.maximum(pl.cdiv(live_pages(s), pages), 1)  # masked block

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
        _merge_block(lambda h: q_ref[0, h], k_buf, v_buf, buf,
                     k_pos < length, m_ref, l_ref, acc_ref, keys,
                     full_precision, rolled=False)
        return 1 - buf

    buf_ref[0] = lax.fori_loop(0, n_blocks, block, buf_ref[0])
    for h in range(heads):
        o_ref[0, h] = (acc_ref[h] / jnp.maximum(l_ref[h][:, :1], 1e-20)
                       ).astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, layer, tables, lengths, page_size,
                    scale, pages=None):
    """q (S, H, R, D); k_pool, v_pool (layers, pages, page_size, H, D) or
    folded, (layers, pages, page_size, H x D), float32; tables (S,
    max_pages) int32; lengths (S,) int, the valid rows a slot; ``scale``
    multiplies the scores.  -> (S, H, R, D) like q:
    softmax attention of each slot's R rows a head over that slot's first
    ``lengths[s]`` rows of layer ``layer``.  ``pages``: pages a block
    (:func:`pages_per_block` unless given).

    The layer's number is handed to the jitted body as data, so a step's
    layers share ONE trace and one lowering of the kernel: lowering it
    anew for each of the dense model's 24 layers added 23 s to a
    session's start with every executable already in the compile cache
    (PERF.md, PR 44)."""
    if pages is None:
        pages = pages_per_block(page_size, tables.shape[1],
                                k_pool.ndim == 4)
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
    folded = k_pool.ndim == 4
    # the table's columns past the last whole block are never a block's
    width = -(-max_pages // pages) * pages
    tables = jnp.pad(tables.astype(jnp.int32) + layer * pool_pages,
                     ((0, 0), (0, width - max_pages)))
    q32 = q.astype(jnp.float32) * scale
    if folded:
        # a lane tile's heads one under the other, each head's rows zero
        # outside its own lanes of the tile: (S, tiles, per x R, 128); a
        # head of whole tiles is a group of its own, (S, H, R, D)
        wide = max(_LANES, d)
        per = wide // d
        q32 = q32.reshape(s, heads // per, per, r, d)
        q32 = jnp.concatenate([
            jnp.pad(q32[:, :, j],
                    ((0, 0),) * 3 + ((j * d, wide - (j + 1) * d),))
            for j in range(per)], axis=2)
        # a page as it lies, (rows, H x D): only leading axes merge
        flat = (layers * pool_pages,) + k_pool.shape[2:]
    else:
        # a page as (rows x H, D): only leading axes merge, H whole
        # sublane tiles
        flat = (layers * pool_pages, page_size * heads, d)
    groups, live, lanes = q32.shape[1:]
    rows = -(-live // _SUBLANES) * _SUBLANES
    q32 = jnp.pad(q32, ((0, 0), (0, 0), (0, rows - live), (0, 0)))
    kernel = functools.partial(
        _decode_kernel, page_size=page_size, width=width, pages=pages,
        full_precision=full_precision)
    block = pl.BlockSpec((1, groups, rows, lanes), lambda i, *_: (i, 0, 0, 0))
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
                pltpu.VMEM((2, pages * flat[1], flat[2]), k_pool.dtype),
                pltpu.VMEM((2, pages * flat[1], flat[2]), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((groups, rows, _LANES), jnp.float32),
                pltpu.VMEM((groups, rows, _LANES), jnp.float32),
                pltpu.VMEM((groups, rows, lanes), jnp.float32),
                pltpu.SMEM((1,), jnp.int32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_bytes(pages, page_size, groups, rows,
                                         lanes)),
        name=kernel_name(pages, d if folded else 0),
    )(jnp.minimum(lengths.astype(jnp.int32), max_pages * page_size),
      tables.reshape(-1), q32, k_pool.reshape(flat), v_pool.reshape(flat))
    if folded:   # each head's rows, out of its own lanes of its tile
        out = jnp.stack([out[:, :, j * r:(j + 1) * r, j * d:(j + 1) * d]
                         for j in range(per)], axis=2).reshape(q.shape)
    return out[:, :, :r].astype(q.dtype)


# ---------------------------------------------------------------------------
# a prefill chunk's attention over the slot's pages
# ---------------------------------------------------------------------------

# What one step of the prefill kernel's grid takes and what one iteration
# of its loop takes, at most: the left operand's rows (a key/value head's
# query rows; over a folded pool the rows of a lane tile's heads one under
# the other) and the keys a block holds.  Every row of a tile pays for
# its running maximum, sum and accumulator once a key block, so few large
# blocks win (a v5e, one LFM2-24B-A2B layer at the deepest offset: 4.04 ms
# at 1 024 rows x 512 keys, 2.43 at 1 024 x 1 024 (2.52 since its loops are
# rolled), 3.24 at 1 024 x 2 048, where whole blocks past a tile's horizon
# are computed and masked; the scan 13.1; PERF.md, PR 51, has the sweep).
_PREFILL_TILE_ROWS = 1024
_PREFILL_KEYS_PER_BLOCK = 1024
# and what bounds both under more heads: rows of a lane tile over all the
# kernel's groups (heads of 128, or a folded pool's lane tiles).  The
# query, result and statistics blocks hold 7 x groups x tile rows, the K
# and V double buffer 4 x groups x keys: 14.7 MB and up to 16.8 MB at
# these, beside ~25 MB of one group's scores.  Laguna's 8 heads at
# 1 024 x 1 024 asked 78 MB and XLA had no room for it beside what it
# keeps in VMEM itself; at 512 x 1 024 (52 MB) it runs (PR 51).
_PREFILL_TILE_ROWS_ALL_GROUPS = 4096
_PREFILL_KEYS_ALL_GROUPS = 8192


def paged_prefill_eligible(q, k_pool, v_pool, mi, k_scale, v_scale,
                           table_keys):
    """Whether ``ops/attention.py:paged_prefill_attention`` sends a chunk
    to :func:`paged_prefill`: :func:`paged_attention_eligible`'s rules,
    read off the same facts of the call (``q`` any array of the chunk's
    query heads: its type and head width are asked)."""
    return paged_attention_eligible(q, k_pool, v_pool, mi, k_scale, v_scale,
                                    table_keys)


def prefill_tiling(rows, head_dim, folded, heads, page_size, max_pages):
    """-> (query rows of one key/value head a grid step takes, pages a key
    block holds) for a chunk of ``rows`` query rows a head, ``heads``
    key/value heads of ``head_dim`` and a table of ``max_pages`` pages.
    The kernel's left operand is the step's rows of every head a lane tile
    holds (one where the heads keep their own axis): at most
    ``_PREFILL_TILE_ROWS``, fewer under more than four groups (the VMEM
    the blocks of all groups take), and the chunk is shared out evenly
    over the fewest steps in whole sublane tiles; a key block likewise
    ``_PREFILL_KEYS_PER_BLOCK`` keys at most, and the table's."""
    per = max(_LANES // head_dim, 1) if folded else 1
    groups = heads * head_dim // _LANES if folded else heads    # lane tiles
    operand = min(_PREFILL_TILE_ROWS, _PREFILL_TILE_ROWS_ALL_GROUPS // groups)
    steps = -(-rows * per // operand)
    tile = -(-rows // (steps * _SUBLANES)) * _SUBLANES
    keys = min(_PREFILL_KEYS_PER_BLOCK, _PREFILL_KEYS_ALL_GROUPS // groups)
    return tile, max(1, min(keys // page_size, max_pages))


def prefill_kernel_name(tile, pages, folded_head):
    """The prefill ``pallas_call``'s name: its layout as
    :func:`kernel_name` writes it, a head's query rows a grid step and
    the pages a key block (``paged_prefill_attention_f64_r512_p32``)."""
    layout = "f%d_" % folded_head if folded_head else ""
    return "paged_prefill_attention_%sr%d_p%d" % (layout, tile, pages)


def _prefill_vmem_bytes(pages, page_row_bytes, groups, rows, keys):
    """The prefill kernel's VMEM need: the double buffer of K and V
    blocks, the query and result blocks twice each, the running maximum,
    sum and accumulator, a few (rows, keys) float32 temporaries of one
    head's scores, and slack."""
    buffers = 2 * 2 * pages * page_row_bytes
    blocks = (2 * 2 + 3) * groups * rows * _LANES * 4
    scores = 6 * rows * keys * 4
    return buffers + blocks + scores + _VMEM_SLACK


def _prefill_kernel(most_ref, least_ref, table_ref, q_ref, seen_ref, k_hbm,
                    v_hbm, o_ref, k_buf, v_buf, sems, m_ref, l_ref, acc_ref,
                    buf_ref, *, page_size, pages, full_precision):
    from jax.experimental import pallas as pl

    tile = pl.program_id(0)
    tiles = pl.num_programs(0)
    # heads, or a folded pool's lane tiles, each with the rows of the
    # heads it holds one under the other
    groups, rows, _ = acc_ref.shape
    keys = pages * page_size
    copies = functools.partial(_page_copies, k_hbm, v_hbm, k_buf, v_buf, sems,
                               k_buf.shape[1] // pages)

    def block_copies(blk, buf, start):
        """Start, or wait for, the copies of key block ``blk``: every
        page of it (the table names a page in bounds in every column).
        A loop the kernel keeps rolled: written out, the five places that
        call this were 640 copies of a block of 64 pages, most of the
        kernel's text, and lowering it took a session's start 3 s a
        bucket with every executable in the compile cache (PERF.md,
        PR 51)."""
        def page_copies(i, _):
            # a wait needs the copy's shape and semaphore, not its page
            page = table_ref[blk * pages + i] if start else 0
            for copy in copies(buf, i, page):
                copy.start() if start else copy.wait()
            return _

        lax.fori_loop(0, pages, page_copies, 0)

    @pl.when(tile == 0)
    def _():
        buf_ref[0] = 0
        block_copies(0, 0, start=True)

    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    horizon = seen_ref[...]                                  # (rows, 1)
    # a tile walks the key blocks up to its furthest horizon (one at
    # least: it takes its turn in the double buffer), and those wholly
    # under its nearest one need no mask
    n_blocks = jnp.maximum(pl.cdiv(most_ref[tile], keys), 1)
    n_plain = jnp.minimum(least_ref[tile] // keys, n_blocks)

    def block(blk, buf, masked):
        last = blk + 1 >= n_blocks

        @pl.when(jnp.logical_or(jnp.logical_not(last), tile + 1 < tiles))
        def _():   # this tile's next block, or the next tile's first
            block_copies(jnp.where(last, 0, blk + 1), 1 - buf, start=True)

        block_copies(blk, buf, start=False)
        seen = None
        if masked:
            k_pos = blk * keys + lax.broadcasted_iota(jnp.int32,
                                                      (rows, keys), 1)
            seen = k_pos < horizon
        _merge_block(lambda g: q_ref[g], k_buf, v_buf, buf, seen, m_ref,
                     l_ref, acc_ref, keys, full_precision, rolled=True)
        return 1 - buf

    buf = lax.fori_loop(0, n_plain,
                        functools.partial(block, masked=False), buf_ref[0])
    buf_ref[0] = lax.fori_loop(n_plain, n_blocks,
                               functools.partial(block, masked=True), buf)
    o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...][:, :, :1], 1e-20)
                  ).astype(o_ref.dtype)


def paged_prefill(q, k_pool, v_pool, layer, table_row, horizons, page_size,
                  scale, tile, pages):
    """q (H, rows, D), a key/value head's query rows (a chunk's tokens x
    the query heads that share the head); k_pool, v_pool as
    :func:`paged_attention` takes them; table_row (max_pages,) int32, the
    slot's pages (every column a page in bounds); horizons (rows,) int,
    the key rows each query row sees: its own token's and every earlier
    one's, whatever the caller's rule (no causality is assumed).  ->
    (H, rows, D) like q: softmax attention of each row over the slot's
    first ``horizons[row]`` rows of layer ``layer``, clipped to the table.
    ``tile`` and ``pages``: a head's query rows a grid step and the pages
    a key block (:func:`prefill_tiling`'s).

    The layer's number is data to the jitted body, as in
    :func:`paged_attention`: a chunk's layers share one trace and one
    lowering of the kernel."""
    return _paged_prefill(
        q, k_pool, v_pool, jnp.asarray(layer, jnp.int32), table_row,
        horizons, page_size=page_size, scale=float(scale), tile=tile,
        pages=pages, full_precision=_full_precision())


@functools.partial(jax.jit, static_argnames=("page_size", "scale", "tile",
                                             "pages", "full_precision"))
def _paged_prefill(q, k_pool, v_pool, layer, table_row, horizons, *,
                   page_size, scale, tile, pages, full_precision):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    heads, live, d = q.shape
    layers, pool_pages = k_pool.shape[:2]
    max_pages = table_row.shape[0]
    folded = k_pool.ndim == 4
    # a group's lanes: a tile, or a folded head of whole tiles
    lanes = max(_LANES, d)
    per = lanes // d if folded else 1
    steps = -(-live // tile)
    rows = steps * tile
    # the columns that complete the last block lie past every horizon:
    # the layer's trash page, which is in bounds and finite
    width = -(-max_pages // pages) * pages
    table = jnp.pad(table_row.astype(jnp.int32), (0, width - max_pages),
                    constant_values=pool_pages - 1) + layer * pool_pages
    # rows that complete the last tile see what the last row sees
    seen = jnp.pad(jnp.clip(horizons.astype(jnp.int32), 0,
                            max_pages * page_size),
                   (0, rows - live), mode="edge").reshape(steps, tile)
    q32 = jnp.pad(q.astype(jnp.float32) * scale,
                  ((0, 0), (0, rows - live), (0, 0)))
    if folded:
        # a step's left operand for a lane tile: its heads' rows of the
        # step one under the other, each head's zero outside its own
        # lanes of the tile: (tiles, steps x per x tile, 128)
        q32 = q32.reshape(heads // per, per, steps, tile, d)
        q32 = jnp.stack([
            jnp.pad(q32[:, j],
                    ((0, 0),) * 3 + ((j * d, lanes - (j + 1) * d),))
            for j in range(per)], axis=2)
        q32 = q32.reshape(heads // per, rows * per, lanes)
        # a page as it lies, (rows, H x D): only leading axes merge
        flat = (layers * pool_pages,) + k_pool.shape[2:]
    else:
        # a page as (rows x H, D): only leading axes merge, H whole
        # sublane tiles
        flat = (layers * pool_pages, page_size * heads, d)
    groups = q32.shape[0]
    operand = per * tile
    kernel = functools.partial(
        _prefill_kernel, page_size=page_size, pages=pages,
        full_precision=full_precision)
    block = pl.BlockSpec((groups, operand, lanes), lambda i, *_: (0, i, 0))
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(q32.shape, jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(steps,),
            in_specs=[block,
                      pl.BlockSpec((operand, 1), lambda i, *_: (i, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=block,
            scratch_shapes=[
                pltpu.VMEM((2, pages * flat[1], flat[2]), k_pool.dtype),
                pltpu.VMEM((2, pages * flat[1], flat[2]), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((groups, operand, _LANES), jnp.float32),
                pltpu.VMEM((groups, operand, _LANES), jnp.float32),
                pltpu.VMEM((groups, operand, lanes), jnp.float32),
                pltpu.SMEM((1,), jnp.int32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_prefill_vmem_bytes(
                pages, flat[1] * flat[2] * 4, groups * lanes // _LANES,
                operand, pages * page_size)),
        name=prefill_kernel_name(tile, pages, d if folded else 0),
    )(jnp.max(seen, axis=1), jnp.min(seen, axis=1), table, q32,
      jnp.tile(seen[:, None], (1, per, 1)).reshape(-1, 1),
      k_pool.reshape(flat), v_pool.reshape(flat))
    if folded:   # each head's rows, out of its own lanes of its tile
        out = out.reshape(heads // per, steps, per, tile, lanes)
        out = jnp.stack([out[:, :, j, :, j * d:(j + 1) * d]
                         for j in range(per)], axis=1).reshape(heads, rows, d)
    return out[:, :live].astype(q.dtype)
