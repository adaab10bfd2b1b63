"""Paged KV cache: fixed-capacity device pools + host-side page tables.

The decode-side memory design (PAPERS.md "Compiler-First State Space
Duality and Portable O(1) Autoregressive Caching"): all KV state lives
in two fixed-shape device pools

    k_pool, v_pool : (num_layers, num_pages + 1, page_size, H, D)
                     when D is a multiple of 128, and otherwise
                     (num_layers, num_pages + 1, page_size, H * D)

so every prefill/decode executable sees one unchanging buffer shape —
no per-request allocation, no growing tensors, no recompiles.  Requests
own *pages* (rows of the pool), recorded in a per-slot page table the
executables consume as a plain (slots, max_pages) int32 array.

**The K/V pools' layout at rest** (:func:`kv_pool_shape`; THIS file is
the only place that knows it).  A TPU keeps an array's last two axes in
tiles of (8, 128): 8 sublanes by 128 lanes.  With heads of 128 the
(H, D) axes fill whole tiles and a 16-row append is an in-place update
of 16 rows.  With heads of 64 (granite-4.0-h-micro: 8 x 64) the lane
axis is half a tile, the pool at rest is padded to twice its bytes, and
the compiler unpacks and repacks the WHOLE pool around each append: for
a donated float32 pool of 100.8 MB, 117.5 MB of argument and 201.6 MB of
temporaries, four whole-pool copies a layer for K and V, 6-7 ms of a
32 ms decode step for under 1 % of its bytes (PERF.md, PRs 30 and 38).
So a head narrower than a lane tile is not given the lane axis to
itself: the heads fold into it, (..., H * D), 512 lanes with no padding,
and the same append compiles to the in-place update alone.  Heads of 128
that are no whole sublane tile fold likewise (SDAR-30B-A3B's 4 x 128): at
rest the compiler keeps (4, 128) in tiles of 4, and around a step it
turns the WHOLE pool to tiles of (page rows, 128) and back, 3 GB a pool
each way beside 16 GB that are full (read in the text compiled for a
described v5e: PERF.md, PR 50); folded they are 512 lanes like the
others'.  Axes 0-2
(layer, page, row in the page) are the same in both layouts, so pages,
tables, the trash page, copy-on-write and the scale pools of
``kv_quant`` do not know the difference.  The step functions never
index the trailing axes: they write rows of (..., H, D) through
:func:`append_rows` and read pages back as (..., H, D) through
:func:`read_pages` / :func:`read_context`, which gather from the pool
where it lies (``pool[layer, pages]``, one gather over both leading axes:
indexing the layer first and the pages second materialises the layer, a
copy of it written in front of every prefill's gather, 1.2 GB read and
written again for 19 MB of pages at LFM2's pools: PERF.md, PR 49) and
reshape what they have *gathered* (a few pages a slot, or a slot's
table), never the pool.  One reader takes
folded pages in place, without gathering them: the decode step's
paged-attention kernel (``ops/paged_attention.py``), which copies a slot's
live pages as they lie, ``(page_size, H * D)``, and works on whole lane
tiles of them.
:attr:`PagedKVCache.kv_lanes` says which layout a cache took.

A latent-attention model (``latent_dim > 0``, ``serve/latent_moe.py``)
keeps one row of ``latent_dim`` values a token a layer instead of
per-head keys and values: ONE pool and no other

    latent_pool : (full layers, num_pages + 1, page_size, lanes)
                  lanes = latent_dim rounded up to a multiple of 128

**The latent pool's layout at rest** (:func:`latent_pool_shape`; THIS
file is the only place that knows it).  A row of 512 + 64 = 576 values
(kanana-2, Ling-3.0-flash) is four and a half lane tiles.  Given a pool
of (..., 16, 576) the compiler does not pad the rows to 640 (11 %): it
puts the PAGE axis on the lanes (2305 -> 2432 pages pad 5.5 %), and a
pool that lies page-minor cannot take a row: every executable that
touches it transposes the WHOLE pool to rows in front of its first
append and back behind its last, 1.75 GB moved for 16 rows of 2 304
bytes, ~2.9 ms of every kanana-2 call and ~2.2 ms of every Ling call
(read in the compiled text and in the traces' ``copy``: PERF.md, PR 41).
So the row is laid in whole lane tiles here, 640 lanes, the lanes past
``latent_dim`` zero: the same executables then take the pool row-major,
update 16 rows in place and hold no whole-pool copy; the pool is 11 %
larger at rest (+24 MB at kanana-2's size) and a call's temporaries
0.5 GB smaller.  A width that already fills lane tiles keeps its shape
letter for letter.  The step functions never index the pool's trailing
axis: they write rows of ``latent_dim`` values through
:func:`append_latent_rows` (which writes the pad lanes zero, so they are
zero wherever a row was ever written, and copy-on-write copies them as
they are) and read a slot's or every slot's table through
:func:`read_latent_context`, which gathers the table's pages from the
pool where it lies (``pool[layer, tables]``: the layer indexed first and
the tables second materialises the layer, 85 MB read and written a layer
a call)
and hands the rows back as wide as the pool keeps them.  The absorbed
attention gives its query as many zero lanes, which add exactly 0 to a
score; the materialised one slices what it *gathered*.
:attr:`PagedKVCache.latent_lanes` says how wide the rows lie.

Pages, tables, reference counts, the prefix index, oversubscription and
copy-on-write index axes 0-2 and do not know the difference.  Its layer
axis counts the layers of kind ``"full"`` only, as the K/V pools' does: a
stack whose
other layers keep slot-private state (``serve/bailing_hybrid.py``: one
latent-attention layer in six, linear-attention state in the rest) has
the latent pool for the few and the ``state`` pools below for the many,
in one cache.  ``kv_quant`` and windowed layers' rings beside a latent
pool are still refused.

**Slot-private recurrent state** (``state``): what a layer keeps a slot
besides pages, as the block's ``state_shapes(cfg)`` names it: name ->
(layers, one slot's shape a layer, dtype), built here as

    <name> : (layers, slots) + shape

(the Mamba-2 block's ``ssm_state`` of (heads, head width, state size) and
``conv_state`` of (taps - 1, channels); the KDA block's ``kda_state`` of
(heads, key width, value width) and ``conv_state``).  No page indexes it:
a slot's rows are zeroed at :meth:`alloc`, a request's chunks carry them from one prefill dispatch
to the next, and a cache that has any is ``hybrid``: the prefix index is
off and prefill takes the slot.  Only ``"full"`` layers own pages, so the
page pools' layer axis is their count and their head axis the key/value
head count.

**A windowed layer's ring** (``kw_pool`` / ``vw_pool``; THIS file is the
only place that knows its layout):

    kw_pool, vw_pool : (window layers, slots, ring rows, H, D), or
                       (window layers, slots, ring rows, H * D) where
                       the page pools fold: one rule,
                       :func:`kv_pool_shape`'s, with a slot for a page

No page indexes it: every slot owns ``ring_pages * page_size`` rows a
windowed layer for the session's life, and position ``p`` of a slot's
request lies in row ``p % rows``.  Who sizes it: the block, whose
``ring_pages(model, config)`` the session asks.  The GPT-2 block's rule
is the model's window plus the largest write span, because its
dispatches write a whole bucket into the ring before they read;
``serve/laguna.py``'s is the model's window alone, rounded up to whole
pages, whatever the buckets are: its prefill reads the rows from before a
chunk, attends over them and the chunk's own rows, and only then folds
the chunk's last rows in.  The step functions never compute a row's index
or a row's position themselves: one token a slot is written through
:func:`append_rows` at ``lengths % rows``, a chunk's last rows through
:func:`fold_into_ring` (a scatter of the slot's ring rows that the chunk
reaches: bucket padding writes nothing), a layer's rings are read through
:func:`read_ring`, and what a row holds is read off
:func:`ring_positions`, the latest position at most ``newest`` that maps
to the row, negative where the request has not written it.  A row that is
stale (the request before, an idle slot's junk token in row 0) therefore
labels outside every band, and ``alloc`` scrubs nothing.

**One owner.**  Every device array the cache holds lives in
:attr:`PagedKVCache.pools`, one mapping from name to array that is built
once in ``__init__`` and holds only what this cache has.  The serve
executables take the mapping as one pytree argument and return it as
one (a dict flattens in sorted key order); the session stores what came
back.  THIS file is where the names are written; the step functions read
them by name, and nothing else enumerates them.  A new kind of state is
one more entry here (plus its name in ``paged`` if pages index it).

Two admission modes (vs the original reservation-only pager):

* **Reservation admission** (default) — a request is admitted only when
  pages for its whole worst case (prompt + max_new tokens) are free, so
  an admitted request can never stall mid-decode waiting for a page and
  no preemption machinery is needed.  The cost is lower pool
  utilization when requests finish early.
* **Oversubscription** (``alloc(..., oversub=True)``, driven by
  ``MXNET_SERVE_OVERSUB``) — admit by *current* need (the prompt pages
  only) and grow on demand at decode boundaries via
  :meth:`append_pages`.  The scheduler watches
  :attr:`reclaimable_pages` against a watermark and preempts requests
  when the pool runs dry; preempted requests re-prefill
  deterministically on resume, so oversubscription changes capacity,
  never content.

**Prefix cache** (``prefix_pages != 0``): a page-aligned token-hash
index over the pool.  :meth:`alloc` matches the prompt's full pages
against a chain hash (page ``i``'s key folds page ``i-1``'s key, so a
hit certifies the whole transcript prefix, not just one page's tokens)
and maps hits read-only into the new slot's table with a reference
count; prefill then runs only on the uncached suffix.
:meth:`register_prefix` publishes a slot's full prompt pages after
prefill so later requests (and preempted-then-resumed ones) hit them.
Pages whose refcount drops to zero are *retained* in LRU order (up to
``prefix_pages`` when positive) and reclaimed lazily — the free heap is
always preferred, so retention never costs an admission.  Shared or
published pages are never written in place: :meth:`ensure_writable` is
the copy-on-write guard every write path crosses.

**The trash page** — pool row ``num_pages`` is a write-only dump.
Unreserved page-table entries and inactive slots point at it, so the
fixed-shape executables can always scatter (padded prefill positions,
idle slots) without conditionals; nothing ever reads it through a
validity mask.

Page-table/length bookkeeping is host-side numpy (the scheduler mutates
it between steps); :meth:`device_tables` re-uploads only after a
mutation.  The pools themselves live on device and flow through the
donated executable arguments.  Free slots and pages are min-heaps
popped lowest-id-first, so allocation order stays deterministic no
matter the order requests finished in (the old implementation re-sorted
a list on every release; the heap keeps the same reuse contract at
O(log n) per op).
"""
from __future__ import annotations

import functools
import hashlib
import heapq
import math
from collections import OrderedDict

from ..base import MXNetError

__all__ = ["PagedKVCache", "kv_pool_shape", "append_rows", "pool_heads",
           "read_pages", "read_context", "latent_pool_shape",
           "append_latent_rows", "read_latent_context", "ring_positions",
           "fold_into_ring", "read_ring"]

# a TPU tile's lane count: the last axis of an array at rest is padded to
# a multiple of it
_LANES = 128
# and its sublane count: heads on an axis of their own are its rows
_SUBLANES = 8


def kv_pool_shape(layers, rows, page_size, num_heads, head_dim):
    """Shape at rest of a paged K or V pool of ``rows`` pages (the trash
    page included), or of a ring pool of ``rows`` slots of ``page_size``
    ring rows: heads of whole lane tiles, a whole number of sublane
    tiles of them, keep their own axis; narrower or fewer heads fold into
    the last one (the module docstring has why; ten heads of 128 on an
    axis of their own in a ring lay at rest in tiles a step cannot write
    a row into, and every decode step turned both ring pools WHOLE into
    another layout and back, twelve copies of 503 MB each way at
    Phi-4-mini-flash's six window layers: read in the text compiled for a
    described v5e, PERF.md, PR 54)."""
    lead = (int(layers), int(rows), int(page_size))
    if head_dim % _LANES == 0 and num_heads % _SUBLANES == 0:
        return lead + (int(num_heads), int(head_dim))
    return lead + (int(num_heads) * int(head_dim),)


def read_ring(pool, layer, head_dim, slot=None):
    """Layer ``layer`` of a ring pool, every slot's or ``slot``'s, as
    (..., ring rows, H, D) whatever the pool's layout at rest.  One slot's
    ring is GATHERED (``pool[layer, [slot]]``), as pages are: sliced out,
    the layout its reader wants (the keys transposed for the scores)
    travels back through the slice to the pool, and the compiler turns the
    WHOLE pool into it in front of the chunk and back behind it, 503 MB
    each way a pool at Phi-4-mini-flash's rings (read in the text compiled
    for a described v5e: PERF.md, PR 54)."""
    import jax.numpy as jnp

    if slot is None:
        ring = pool[layer]
        return ring.reshape(ring.shape[:2] + (-1, head_dim))
    ring = pool[layer, jnp.reshape(slot, (1,))]
    return ring.reshape(ring.shape[1], -1, head_dim)


def append_rows(pools, which, layer, major, minor, rows, kv_quant=""):
    """Scatter a batch of KV rows into layer ``layer`` of ``pools[which +
    "_pool"]``, in place in the mapping.  ``which`` is ``"k"`` / ``"v"``
    (the page pools: ``major`` the pages, ``minor`` the offsets in them)
    or ``"kw"`` / ``"vw"`` (a windowed layer's per-slot ring, (Lw, S, R,
    H, D): ``major`` the slots, ``minor`` the ring rows; broadcastable).

    ``rows`` is (..., H, D), one row per token, whatever the pool's
    layout at rest: they take the shape of the pool's axes past the
    third.  With ``kv_quant`` each row quantizes independently (codes
    into the storage pool, one float32 scale per row into the parallel
    ``which + "_scale"`` pool), so a page's or ring's bytes are a pure
    function of the tokens written to it — the property that keeps
    prefill scatter, serial decode append, batched verify append,
    prefix-hit replay, COW and preempt/re-prefill byte-identical.
    """
    name = which + "_pool"
    pool = pools[name]
    if kv_quant:
        from .. import quantize as _q

        rows, scales = _q.kv_quantize_rows(rows, kv_quant)
        pools[which + "_scale"] = \
            pools[which + "_scale"].at[layer, major, minor].set(scales)
    rows = rows.reshape(rows.shape[:-2] + pool.shape[3:])
    pools[name] = pool.at[layer, major, minor].set(rows.astype(pool.dtype))


def pool_heads(pool, head_dim):
    """Heads of ``head_dim`` a row of a K or V pool holds."""
    return math.prod(pool.shape[3:]) // head_dim


def read_pages(pool, layer, pages, head_dim):
    """Pages ``pages`` (an int array of any shape) of layer ``layer`` of a
    K or V pool, read where they lie -> pages.shape + (page_size, H, D)."""
    return pool[layer, pages].reshape(
        pages.shape + (pool.shape[2], -1, head_dim))


def read_context(pool, layer, tables, head_dim):
    """A slot's whole page table, or every slot's, of layer ``layer`` of
    a K or V pool as one context for ``ops.attention.decode_attention``,
    gathered from the pool where it lies: ``tables`` (max_pages,) or
    (S, max_pages) -> (1 or S, H, max_pages * page_size, D)."""
    n = 1 if tables.ndim == 1 else tables.shape[0]
    return pool[layer, tables].reshape(
        n, tables.shape[-1] * pool.shape[2], -1, head_dim
    ).transpose(0, 2, 1, 3)


def latent_pool_shape(layers, rows, page_size, latent_dim):
    """Shape at rest of a paged latent pool of ``rows`` pages (the trash
    page included): a row of ``latent_dim`` values in whole lane tiles,
    the lanes past ``latent_dim`` zero (the module docstring has why)."""
    lanes = -(-int(latent_dim) // _LANES) * _LANES
    return (int(layers), int(rows), int(page_size), lanes)


def append_latent_rows(pools, layer, pages, offsets, rows):
    """Scatter a batch of latent rows (N, latent_dim), one a token, into
    layer ``layer`` of ``pools["latent_pool"]`` at (``pages``,
    ``offsets``), in place in the mapping.  The pool's lanes past the
    row's width are written zero."""
    import jax.numpy as jnp

    pool = pools["latent_pool"]
    rows = jnp.pad(rows, ((0, 0), (0, pool.shape[-1] - rows.shape[-1])))
    pools["latent_pool"] = pool.at[layer, pages, offsets].set(
        rows.astype(pool.dtype))


def read_latent_context(pool, layer, tables):
    """A slot's whole page table, or every slot's, of layer ``layer`` of
    a latent pool as rows in position order, gathered from the pool where
    it lies: ``tables`` (max_pages,) or (S, max_pages) -> (max_pages *
    page_size, lanes) or (S, max_pages * page_size, lanes).  The rows are
    as wide as the pool's (the lanes past ``latent_dim`` zero, which add
    exactly 0 to a score against a query given as many zero lanes)."""
    return pool[layer, tables].reshape(
        tables.shape[:-1] + (tables.shape[-1] * pool.shape[2],
                             pool.shape[3]))


def ring_positions(rows, newest):
    """The absolute position each of a ring's ``rows`` rows holds once the
    slot's request has written every position up to ``newest`` (an int
    array of any shape; -1 for nothing yet) -> newest.shape + (rows,):
    row ``r`` holds the latest ``p <= newest`` with ``p % rows == r``,
    which is negative where the request has not written the row."""
    import jax.numpy as jnp

    newest = jnp.asarray(newest)[..., None]
    return newest - (newest - jnp.arange(rows, dtype=newest.dtype)) % rows


def fold_into_ring(pools, which, layer, slot, rows, first, count):
    """Write a prefill chunk into ``slot``'s ring of windowed layer
    ``layer`` of ``pools[which + "_pool"]`` (``"kw"`` / ``"vw"``), in
    place in the mapping: ``rows`` (T, H, D) are the chunk's rows at
    positions ``first`` on, of which the first ``count`` are real.  The
    ring then holds the last positions up to ``first + count - 1``: a
    row whose position lies in the chunk takes the chunk's row, every
    other row keeps what it held, and bucket padding is not written."""
    import jax.numpy as jnp

    name = which + "_pool"
    pool = pools[name]
    held = ring_positions(pool.shape[2], first + count - 1)
    from_chunk = held >= first
    taken = rows[jnp.clip(held - first, 0, rows.shape[0] - 1)]
    # the rows are SCATTERED, a row that keeps what it held to an index
    # past the ring, which drops it.  Rewritten densely (a select between
    # the chunk's row and the ring's), the layout the chunk's keys were
    # made in for their scores (transposed) travels through the select to
    # the update of a folded pool, and the compiler turns the whole pool
    # into it and back (PERF.md, PR 54)
    where = jnp.where(from_chunk, jnp.arange(pool.shape[2]), pool.shape[2])
    pools[name] = pool.at[layer, slot, where].set(
        taken.reshape((taken.shape[0],) + pool.shape[3:]).astype(pool.dtype),
        mode="drop")


def _chain_key(prev_key, page_tokens):
    """Chain hash over one page of prompt tokens: folds the previous
    page's key so equal keys certify equal *transcripts*, not just
    equal final pages.  Content-addressed and deterministic."""
    import numpy as np

    h = hashlib.sha256(prev_key)
    h.update(np.asarray(page_tokens, np.int64).tobytes())
    return h.digest()


@functools.lru_cache(maxsize=None)
def _zero_slot():
    """-> jitted f(pools, slot) = pools with axis 1's row ``slot`` of
    every pool zeroed; ``pools`` is donated."""
    import jax

    return jax.jit(
        lambda pools, slot: {name: pool.at[:, slot].set(0)
                             for name, pool in pools.items()},
        donate_argnums=0)


class PagedKVCache:
    """Fixed-pool paged KV cache for ``slots`` concurrent requests."""

    def __init__(self, num_layers, num_heads, head_dim, page_size,
                 num_pages, slots, max_pages_per_slot, dtype=None,
                 table_pad=0, prefix_pages=0, kv_quant="",
                 layer_kinds=(), window=0, ring_pages=0, latent_dim=0,
                 state=None):
        import jax.numpy as jnp
        import numpy as np

        from .. import quantize as _quantize

        if min(num_layers, num_heads, head_dim, page_size, num_pages,
               slots, max_pages_per_slot) < 1:
            raise MXNetError("PagedKVCache: all dimensions must be >= 1")
        if table_pad < 0:
            raise MXNetError("PagedKVCache: table_pad must be >= 0")
        if prefix_pages < -1:
            raise MXNetError("PagedKVCache: prefix_pages must be >= -1 "
                             "(-1 = unbounded retention, 0 = off)")
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.page_size = int(page_size)
        self.num_pages = int(num_pages)
        self.slots = int(slots)
        self.max_pages_per_slot = int(max_pages_per_slot)
        # -- hybrid-stack layout ------------------------------------------
        # layer_kinds: per-layer "full" | "window" | "ssm" | "shared" (empty
        # = all full-attention).  Only FULL layers occupy the paged pools —
        # the pool's layer axis is the full-layer count, so a hybrid
        # stack's page costs proportionally less and a fixed pool budget
        # admits proportionally more slots.  Windowed layers get a fixed
        # ring of ``ring_pages`` pages per slot (``kw_pool``/``vw_pool``,
        # slot-indexed: ring append overwrites the oldest page's rows in
        # place, and the attention mask saturates visibility at the
        # window).  SSM layers own neither: what they keep a slot is in
        # ``state`` (below), the per-layer state pools beside the KV
        # pools.  SHARED layers own nothing at all: they read what
        # another layer of the stack owns (its pages, or a value of the
        # same step), so no pool has an entry for them and
        # ``pool_bytes()`` counts nothing.
        self.layer_kinds = tuple(layer_kinds) or ("full",) * self.num_layers
        if len(self.layer_kinds) != self.num_layers:
            raise MXNetError(
                "PagedKVCache: layer_kinds %r does not cover %d layers"
                % (self.layer_kinds, self.num_layers))
        bad = set(self.layer_kinds) - {"full", "window", "ssm", "shared"}
        if bad:
            raise MXNetError("PagedKVCache: unknown layer kinds %r"
                             % sorted(bad))
        self.n_full = self.layer_kinds.count("full")
        self.n_window = self.layer_kinds.count("window")
        self.n_ssm = self.layer_kinds.count("ssm")
        self.n_shared = self.layer_kinds.count("shared")
        self.window = int(window)
        self.ring_pages = int(ring_pages)
        if self.n_window and (self.window < 1 or self.ring_pages < 1):
            raise MXNetError(
                "PagedKVCache: windowed layers need window >= 1 and "
                "ring_pages >= 1 (got window=%d, ring_pages=%d)"
                % (self.window, self.ring_pages))
        self.ring_tokens = self.ring_pages * self.page_size
        self.state = tuple(state or ())   # names of the state pools
        # extra always-trash table columns past the reservable range, so
        # executables that clip a past-the-reservation write position
        # (the speculative verify's overflow rows) land on the trash
        # page instead of aliasing the slot's last real page
        self.table_pad = int(table_pad)
        # prefix-cache retention cap: 0 disables the token-hash index
        # entirely, -1 retains refcount-0 pages without bound (the pool
        # size is the real bound), > 0 caps retained pages LRU-first
        self.prefix_pages = int(prefix_pages)
        self.trash_page = self.num_pages  # reserved last pool row
        # quantized pages: pools store 1-byte int8/e4m3 codes and a
        # parallel (L, pages + 1, page_size) float32 scale pool holds
        # one scale per (layer, token) row — indexed by the SAME
        # (page, offset) the codes are, so the page tables, COW, and
        # preempt/resume machinery never know quantization exists
        self.kv_quant = _quantize.quant_mode(kv_quant)
        self.latent_dim = int(latent_dim)
        if self.latent_dim and (self.kv_quant or self.n_window):
            raise MXNetError(
                "PagedKVCache: a latent pool has no kv_quant (no per-head "
                "row to scale) and no windowed layers' rings yet; "
                "slot-private state pools beside it are served")
        if self.kv_quant:
            dtype = jnp.dtype(_quantize.quant_dtype(self.kv_quant))
        else:
            dtype = dtype or jnp.float32
        pool_shape = kv_pool_shape(max(self.n_full, 1), self.num_pages + 1,
                                   self.page_size, self.num_heads,
                                   self.head_dim)
        # name -> device array: ALL the cache's device state, and the one
        # pytree every serve executable takes and returns
        self.pools = {}
        if self.latent_dim:
            self.pools["latent_pool"] = jnp.zeros(latent_pool_shape(
                *pool_shape[:3], self.latent_dim), dtype)
        else:
            self.pools["k_pool"] = jnp.zeros(pool_shape, dtype)
            self.pools["v_pool"] = jnp.zeros(pool_shape, dtype)
        if self.kv_quant:
            self.pools["k_scale"] = jnp.ones(pool_shape[:3], jnp.float32)
            self.pools["v_scale"] = jnp.ones(pool_shape[:3], jnp.float32)
        # the pools whose axis 1 is the page: what copy-on-write copies
        self.paged = tuple(self.pools)
        # windowed-layer rings: slot-indexed, no page table — every slot
        # owns exactly ring_pages pages for each windowed layer, for the
        # session's whole lifetime (that is the O(1)-per-slot story)
        if self.n_window:
            ring_shape = kv_pool_shape(
                self.n_window, self.slots, self.ring_tokens, self.num_heads,
                self.head_dim)
            self.pools["kw_pool"] = jnp.zeros(ring_shape, dtype)
            self.pools["vw_pool"] = jnp.zeros(ring_shape, dtype)
            if self.kv_quant:
                self.pools["kw_scale"] = jnp.ones(ring_shape[:3],
                                                  jnp.float32)
                self.pools["vw_scale"] = jnp.ones(ring_shape[:3],
                                                  jnp.float32)
        # slot-private recurrent state, as the block names it: built
        # zero, a slot's rows zeroed again at every alloc
        for name, (layers, shape, state_dtype) in (state or {}).items():
            self.pools[name] = jnp.zeros(
                (int(layers), self.slots) + tuple(shape), state_dtype)
        # min-heaps: heappop yields the lowest free id, preserving the
        # deterministic lowest-first reuse contract (a sorted range is
        # already a valid heap)
        self._free_pages = list(range(self.num_pages))
        self._free_slots = list(range(self.slots))
        self._tables = np.full((self.slots, self.table_width),
                               self.trash_page, np.int32)
        self._pages_of = {}    # slot -> [page, ...] (prefix hits first)
        self._cached_len = {}  # slot -> tokens covered by mapped hits
        self.lengths = np.zeros((self.slots,), np.int32)
        self._tables_dev = None  # upload cache, invalidated on mutation
        # -- prefix-cache state ------------------------------------------
        self._refcount = {}  # page -> count of slots currently mapping it
        self._index = {}     # chain key -> page (published prefix pages)
        self._key_of = {}    # page -> chain key (reverse of _index)
        self._retained = OrderedDict()  # refcount-0 published pages, LRU
        self.prefix_stats = {"lookups": 0, "hits": 0, "hit_pages": 0,
                             "hit_tokens": 0, "published_pages": 0,
                             "evicted_pages": 0, "cow_copies": 0}

    @property
    def table_width(self):
        """Page-table columns: reservable pages + the all-trash pad."""
        return self.max_pages_per_slot + self.table_pad

    @property
    def kv_lanes(self):
        """Width of the K/V pools' last axis at rest: ``head_dim`` where a
        head fills whole lane tiles, ``num_heads * head_dim`` where the
        heads fold into it (:func:`kv_pool_shape`); ``None`` for a cache
        with no K/V pool (a latent one)."""
        pool = self.pools.get("k_pool")
        return None if pool is None else int(pool.shape[-1])

    @property
    def latent_lanes(self):
        """Width of the latent pool's last axis at rest: ``latent_dim``
        rounded up to whole lane tiles (:func:`latent_pool_shape`);
        ``None`` for a cache with no latent pool."""
        pool = self.pools.get("latent_pool")
        return None if pool is None else int(pool.shape[-1])

    # -- capacity ---------------------------------------------------------
    @property
    def free_pages(self):
        return len(self._free_pages)

    @property
    def free_slots(self):
        return len(self._free_slots)

    @property
    def retained_pages(self):
        """Published prefix pages no live request maps (reclaimable)."""
        return len(self._retained)

    @property
    def reclaimable_pages(self):
        """Pages an allocation could obtain right now: the free heap
        plus retained prefix pages it may lazily evict.  This is the
        quantity the scheduler's oversubscription watermark watches."""
        return len(self._free_pages) + len(self._retained)

    @property
    def hybrid(self):
        """True when a slot owns more than pages: a windowed layer's
        ring, or recurrent state of any kind."""
        return bool(self.n_window or self.state)

    def pages_needed(self, prompt_len, max_new):
        """Worst-case page reservation for one request.  Pool pages hold
        FULL-attention layers only — a stack with none needs no pages at
        all (ring and state buffers are per-slot and pre-reserved), so
        admission is bounded by slots alone."""
        if not self.n_full:
            return 0
        total = int(prompt_len) + int(max_new)
        return -(-total // self.page_size)

    def can_admit(self, prompt_len, max_new, tokens=None, oversub=False):
        need = self.pages_needed(prompt_len, max_new)
        if need > self.max_pages_per_slot:
            raise MXNetError(
                "request needs %d pages (prompt %d + max_new %d at page "
                "size %d) but slots hold at most %d — raise the session's "
                "max context" % (need, prompt_len, max_new,
                                 self.page_size, self.max_pages_per_slot))
        if not self._free_slots:
            return False
        hit = self._usable_hit(tokens, prompt_len)
        fresh = self._fresh_needed(prompt_len, max_new, hit, oversub)
        return self._available_for(hit) >= fresh

    def _usable_hit(self, tokens, prompt_len):
        """Longest mapped-page chain the prompt may reuse: full pages
        whose chain key is published, capped so at least one prompt
        token is always left for prefill (the suffix computes the
        request's first logits, and suffix offsets stay page-aligned).

        Hybrid stacks: a usable hit must restore EVERY layer kind's
        state at the resume boundary.  Published pool pages restore the
        full-attention layers, but window rings and SSM states are
        slot-private — the only window-aligned boundary at which they
        are reconstructible without recomputation is offset 0, so hits
        cap at zero pages and hybrid prompts always prefill cold (see
        :meth:`register_prefix`)."""
        if tokens is None or not self.prefix_pages or self.hybrid:
            return []
        hit = self.match_prefix(tokens)
        cap = (int(prompt_len) - 1) // self.page_size
        return hit[:cap]

    def _fresh_needed(self, prompt_len, max_new, hit, oversub):
        if oversub:
            now = -(-int(prompt_len) // self.page_size)
        else:
            now = self.pages_needed(prompt_len, max_new)
        return max(now - len(hit), 0)

    def _available_for(self, hit):
        """Pages obtainable without touching the hit set (hit pages may
        themselves sit in the retained LRU; they are about to be
        re-activated, not evicted)."""
        hits = set(hit)
        avail = len(self._free_pages)
        avail += sum(1 for p in self._retained if p not in hits)
        return avail

    # -- prefix index -----------------------------------------------------
    def match_prefix(self, tokens):
        """Pages of the longest published chain prefix of ``tokens``
        (full pages only; stops at the first unpublished page)."""
        if not self.prefix_pages:
            return []
        pages = []
        key = b""
        n_full = len(tokens) // self.page_size
        for i in range(n_full):
            key = _chain_key(
                key, tokens[i * self.page_size:(i + 1) * self.page_size])
            page = self._index.get(key)
            if page is None:
                break
            pages.append(page)
        return pages

    def register_prefix(self, slot, tokens):
        """Publish the slot's full prompt pages into the token-hash
        index (called after prefill, when their KV is final — positions
        below the committed length are never rewritten).  Pages already
        published under the same chain (the slot's own hits) are left
        alone; a chain another slot published concurrently wins and this
        slot's duplicate page stays private.  Returns pages published.

        Hybrid stacks publish nothing: :meth:`_usable_hit` can never map
        the pages (window rings / SSM states cannot ride along), so
        publishing would only pin pool pages in the retained LRU."""
        if not self.prefix_pages or self.hybrid:
            return 0
        pages = self._pages_of.get(slot)
        if pages is None:
            raise MXNetError("register_prefix of unallocated slot %r"
                             % (slot,))
        key = b""
        published = 0
        n_full = min(len(tokens) // self.page_size, len(pages))
        for i in range(n_full):
            key = _chain_key(
                key, tokens[i * self.page_size:(i + 1) * self.page_size])
            page = pages[i]
            if key in self._index or page in self._key_of:
                continue
            self._index[key] = page
            self._key_of[page] = key
            published += 1
        self.prefix_stats["published_pages"] += published
        return published

    def cached_len(self, slot):
        """Prompt tokens covered by mapped prefix hits at admission —
        the position prefill starts from."""
        return self._cached_len.get(slot, 0)

    def _take_page(self):
        """Lowest free page, or — free heap empty — the least-recently
        retained prefix page, unpublished and recycled."""
        if self._free_pages:
            return heapq.heappop(self._free_pages)
        if not self._retained:
            raise MXNetError("page pool exhausted (no free or retained "
                             "pages) — preempt or release a request first")
        page, key = self._retained.popitem(last=False)
        del self._index[key]
        del self._key_of[page]
        self.prefix_stats["evicted_pages"] += 1
        return page

    def _drop_ref(self, page):
        """Release one slot's hold on ``page``; a published page whose
        count hits zero is retained (evictable), others go back to the
        free heap."""
        rc = self._refcount.get(page, 0) - 1
        if rc > 0:
            self._refcount[page] = rc
            return
        self._refcount.pop(page, None)
        key = self._key_of.get(page)
        if key is not None and self.prefix_pages:
            self._retained[page] = key
        else:
            heapq.heappush(self._free_pages, page)

    def _enforce_retention_cap(self):
        if self.prefix_pages <= 0:
            return
        while len(self._retained) > self.prefix_pages:
            page, key = self._retained.popitem(last=False)
            del self._index[key]
            del self._key_of[page]
            self.prefix_stats["evicted_pages"] += 1
            heapq.heappush(self._free_pages, page)

    # -- slot lifecycle ---------------------------------------------------
    def alloc(self, prompt_len, max_new, tokens=None, oversub=False):
        """Admit a request: reserve a slot plus its pages — the worst
        case by default, the *current* need (prompt pages only) under
        ``oversub`` — mapping published prefix pages first when
        ``tokens`` is given and the index hits.  Returns the slot id or
        ``None`` when either resource is exhausted (the scheduler keeps
        the request queued); :meth:`cached_len` reports how many prompt
        tokens the mapped hits already cover."""
        if not self.can_admit(prompt_len, max_new, tokens=tokens,
                              oversub=oversub):
            return None
        hit = self._usable_hit(tokens, prompt_len)
        fresh = self._fresh_needed(prompt_len, max_new, hit, oversub)
        slot = heapq.heappop(self._free_slots)
        for page in hit:
            self._retained.pop(page, None)  # re-activated, not evictable
            self._refcount[page] = self._refcount.get(page, 0) + 1
        pages = list(hit)
        for _ in range(fresh):
            page = self._take_page()
            self._refcount[page] = 1
            pages.append(page)
        self._pages_of[slot] = pages
        self._tables[slot, :] = self.trash_page
        self._tables[slot, :len(pages)] = pages
        self._cached_len[slot] = len(hit) * self.page_size
        # lengths starts AT the cached prefix, not 0: fixed-shape
        # executables write junk rows for every slot at its current
        # length, and those must land in the slot's private fresh pages
        # (suffix prefill overwrites them), never inside a shared hit
        # page
        self.lengths[slot] = self._cached_len[slot]
        self._tables_dev = None
        # a recurrence starts from zero state at offset 0; ring rows
        # need no scrub — the position labels the windowed gather
        # computes for a fresh request exclude every row the request has
        # not itself written (stale rows label as position < 0)
        if self.state:
            self._scrub_state(slot)
        if tokens is not None and self.prefix_pages:
            self.prefix_stats["lookups"] += 1
            if hit:
                self.prefix_stats["hits"] += 1
                self.prefix_stats["hit_pages"] += len(hit)
                self.prefix_stats["hit_tokens"] += \
                    len(hit) * self.page_size
        return slot

    def _scrub_state(self, slot):
        """Zero ``slot``'s rows of every state pool, in place: the state
        pools go to one jitted call donated and the slot as an argument,
        so it compiles once a set of shapes and writes one slot's rows,
        not a copy of the pools."""
        import numpy as np

        self.pools.update(_zero_slot()(
            {name: self.pools[name] for name in self.state},
            np.int32(slot)))

    def append_pages(self, slot, new_len):
        """Grow the slot's mapped pages to cover ``new_len`` token
        positions (capped at the reservable range — speculative rows
        past it land on the trash pad by design).  On-demand growth for
        oversubscribed admission; a no-op when the slot already covers
        the range (always, under reservation).  Returns pages appended;
        raises when the pool cannot supply — the scheduler's watermark
        preemption runs first precisely so this never fires."""
        pages = self._pages_of.get(slot)
        if pages is None:
            raise MXNetError("append_pages of unallocated slot %r"
                             % (slot,))
        need = min(-(-int(new_len) // self.page_size),
                   self.max_pages_per_slot)
        added = 0
        while len(pages) < need:
            page = self._take_page()
            self._refcount[page] = 1
            self._tables[slot, len(pages)] = page
            pages.append(page)
            added += 1
        if added:
            self._tables_dev = None
        return added

    def pages_short(self, slot, new_len):
        """Pages :meth:`append_pages` would have to obtain to cover
        ``new_len`` positions — the scheduler's per-step need probe."""
        pages = self._pages_of.get(slot)
        if pages is None:
            raise MXNetError("pages_short of unallocated slot %r"
                             % (slot,))
        need = min(-(-int(new_len) // self.page_size),
                   self.max_pages_per_slot)
        return max(need - len(pages), 0)

    def ensure_writable(self, slot, start_pos, n_rows=1):
        """Copy-on-write guard: before a dispatch writes KV rows
        [``start_pos``, ``start_pos + n_rows``) for ``slot``, make every
        mapped page in that range private.  A page other slots also map
        (refcount > 1) is copied device-side into a fresh page and the
        table repointed, so readers of the shared page never observe the
        write; a page only *published* (refcount 1 but in the index) is
        cheaper — it is unpublished in place, since no one else reads
        it yet.  The natural write paths (suffix prefill, decode,
        verify) only ever touch positions past the shared prefix, so
        this is a no-op there; it exists so that no future write path
        can corrupt a shared page by construction.  Returns pages
        copied."""
        pages = self._pages_of.get(slot)
        if pages is None:
            raise MXNetError("ensure_writable of unallocated slot %r"
                             % (slot,))
        if n_rows < 1:
            return 0
        first = max(int(start_pos), 0) // self.page_size
        last = (int(start_pos) + int(n_rows) - 1) // self.page_size
        copied = 0
        for idx in range(first, min(last + 1, len(pages))):
            page = pages[idx]
            shared = self._refcount.get(page, 0) > 1
            published = page in self._key_of
            if not shared and not published:
                continue
            if not shared:
                # sole holder: unpublish and write in place (chains
                # beyond this page become unreachable and age out of
                # the retained LRU like any cold entry)
                key = self._key_of.pop(page)
                self._index.pop(key, None)
                self._retained.pop(page, None)
                continue
            new = self._take_page()
            # device-side page copy across all layers, one op a paged
            # pool (scale rows travel with their codes); pure copy, so
            # the private page is bit-identical to the shared one and
            # the stream stays exact
            for name in self.paged:
                pool = self.pools[name]
                self.pools[name] = pool.at[:, new].set(pool[:, page])
            self._refcount[new] = 1
            pages[idx] = new
            self._tables[slot, idx] = new
            self._drop_ref(page)
            copied += 1
        if copied:
            self._tables_dev = None
            self.prefix_stats["cow_copies"] += copied
        return copied

    def release(self, slot):
        """Return the slot's resources (request finished, evicted, or
        failed).  Refcount-aware: shared prefix pages survive for their
        other holders, and published pages this slot held alone are
        retained for future hits instead of freed."""
        pages = self._pages_of.pop(slot, None)
        if pages is None:
            raise MXNetError("release of unallocated slot %r" % (slot,))
        for page in pages:
            self._drop_ref(page)
        self._enforce_retention_cap()
        heapq.heappush(self._free_slots, slot)
        self._tables[slot, :] = self.trash_page
        self.lengths[slot] = 0
        self._cached_len.pop(slot, None)
        self._tables_dev = None

    def truncate(self, slot, n_tokens):
        """Roll back the slot's last ``n_tokens`` KV rows (speculative-
        decode rejection).  Host-side O(1): only ``lengths`` shrinks —
        the slot's page mapping is untouched (vacated pages are reused
        when the length catches up again) and the vacated rows are
        invalidated deterministically by the length mask every
        executable applies: positions >= the new length are never read,
        and the next append overwrites them.  The device page-table
        upload cache is deliberately NOT touched (the invalidate-only-
        on-table-mutation contract holds): tables do not change here,
        and lengths re-upload every step anyway.

        Window rings stay O(1) too: the position -> ring row map is
        deterministic, so the rejected rows' ring slots are exactly the
        ones the re-issued positions overwrite next step, and the
        windowed mask (driven by the rolled-back length) never reads
        them in between — rolling back ``lengths`` IS rolling back the
        ring position.  Slot-private state has no row to roll back: the
        blocks that keep any refuse ``spec_k``."""
        if slot not in self._pages_of:
            raise MXNetError("truncate of unallocated slot %r" % (slot,))
        n = int(n_tokens)
        if n < 0:
            raise MXNetError("truncate(%r, %d): negative rollback"
                             % (slot, n))
        if n > int(self.lengths[slot]):
            raise MXNetError(
                "truncate(%r, %d): slot only holds %d tokens"
                % (slot, n, int(self.lengths[slot])))
        self.lengths[slot] -= n

    def active_slots(self):
        return sorted(self._pages_of)

    def drop_prefix_index(self):
        """Forget every published prefix chain (replica cold rejoin:
        a restarted replica's pool holds no reusable KV, so its index
        must not advertise any).  Retained refcount-0 pages go back to
        the free heap; pages live slots still map merely lose their
        published key — their holders keep decoding untouched and the
        pages free normally on release.  Returns pages unpublished."""
        dropped = len(self._key_of)
        for page in self._retained:
            heapq.heappush(self._free_pages, page)
        self._retained.clear()
        self._index.clear()
        self._key_of.clear()
        return dropped

    # -- executable-facing views -----------------------------------------
    # Each hands the device a private COPY of the host array.  The host
    # arrays are mutated in place (``lengths[slot] += 1``, table rows at
    # alloc/release) while a dispatched executable may not have run yet,
    # and ``jnp.asarray`` of a numpy array may alias its memory (the CPU
    # backend of jax 0.9.0 does whenever the buffer is 64-byte aligned)
    # or read it after the call returns: without the copy the draft's
    # prompt ingest read lengths its own loop had already advanced.
    def device_tables(self):
        """The (slots, max_pages) int32 page-table array, uploaded only
        when the host copy changed since the last call."""
        import jax.numpy as jnp

        if self._tables_dev is None:
            self._tables_dev = jnp.asarray(self._tables.copy())
        return self._tables_dev

    def lengths_arg(self):
        """The (slots,) int32 lengths as an executable takes them: a host
        copy (the live array moves on while a launch may still read its
        argument), which the launch itself uploads."""
        return self.lengths.copy()

    def table_row(self, slot):
        """One slot's page-table row, likewise."""
        return self._tables[slot].copy()

    # -- accounting -------------------------------------------------------
    def pool_bytes(self):
        """Total device bytes held by the pools (scale pools included
        for quantized caches) — constant for the session's lifetime,
        which IS the O(1) decode-memory story."""
        return sum(int(pool.nbytes) for pool in self.pools.values())

    @classmethod
    def page_bytes(cls, num_layers, num_heads, head_dim, page_size,
                   kv_quant=""):
        """Device bytes ONE page costs (k + v codes, plus scale rows
        for quantized caches) — what the capacity-at-fixed-bytes A/B in
        bench_serve.py divides a pool budget by."""
        import numpy as np

        from .. import quantize as _quantize

        mode = _quantize.quant_mode(kv_quant)
        itemsize = (np.dtype(_quantize.quant_dtype(mode)).itemsize
                    if mode else 4)
        per_row = num_heads * head_dim * itemsize + (4 if mode else 0)
        return 2 * num_layers * page_size * per_row

    def utilization(self):
        used = self.num_pages - len(self._free_pages)
        return used / float(self.num_pages)
