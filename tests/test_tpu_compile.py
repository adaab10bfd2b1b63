"""The main path's kernels compile for the chip — without the chip.

The TPU compiler is installed next to the CPU backend and compiles for a
topology that is described, not attached (``v5e:2x2``, device kind "TPU
v5 lite").  Interpret-mode tests cannot see what Mosaic refuses (tiling,
VMEM budget); these can, at the real shapes: the two BatchNorm reduction
kernels at ResNet-50 shapes and the library flash-attention kernel that
``MXNET_ATTN_IMPL=auto`` dispatches to on TPU, forward and backward, with
the blocks ``pallas_block_sizes`` gives each shape (the benchmark's, the
transformer bench's, the rule's edges); and the metrics' device
reductions over the Cerebras-GPT head's (8192, 50257) bfloat16 softmax,
which must read the prediction in place; and the KDA layer's two forms
at Ling-3.0-flash's widths (plain XLA: the solve's lowering, the
temporaries, the state updated in place); and the routed-expert layer's
grouped-matmul kernel over kanana-2's and Ling-3.0-flash's stacks, a
whole float32 expert a block; and a grouped-query attention layer's
append to and read of the paged K/V pools at granite-4.0-h-micro's pool
size (the loop over folded pools under a short table), at LFM2-24B-A2B's
(the paged-attention kernel's form for folded pools) and at the dense
cell's and Laguna-S-2.1's (its form for pools that keep their heads'
axis, the Mosaic module letter for letter what it was before there were
two), which must leave the pools where they lie; and such a layer's
prefill half at the four cells' pools and both buckets of each (the
chunk's rows appended, ``read_context``'s gather of the slot's table, the
bounded scan), which must hold no copy of a pool's layer, and through the
reader the blocks call (``paged_prefill_attention``: the prefill kernel
where the call is eligible, which holds no gathered context either, the
gather and the scan letter for letter where it is not); and the dense
block's whole decode step at Cerebras-GPT-1.3B's widths with the kernel
in; and a latent-attention
layer's append to and read of the latent pool at kanana-2's and
Ling-3.0-flash's pool sizes, likewise; and the window / full
grouped-query block's whole decode step and both its prefill chunks at
Laguna-S-2.1's published widths and the cell's cache, pages and rings
updated in place, and LFM2-24B-A2B's three executables likewise.  Nothing runs, so
nothing here is a result or a time — a compile that passes is not a
chip run.

All of it lives in this one file, and the topology is described inside a
module-scoped fixture: only the xdist worker that is handed this file
loads the TPU library, and only once a test of it has started.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

BN_SHAPES = [(64, 256, 56, 56), (512, 64, 112, 112)]
LM_HEAD_SHAPE = (8192, 50257)  # batch 4 x 2048 tokens, Cerebras-GPT vocabulary


@pytest.fixture(scope="module")
def one_chip():
    """A sharding on one described v5e chip, with the persistent
    compilation cache off around the module: an executable compiled for
    a described chip can be written to it but not read back here."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # mxlint: disable=MX008 — whatever the describe call raises means "cannot be described here"
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _compile(fn, *avals):
    # conftest asks for "highest" matmul precision so CPU results match
    # numpy; the programs the chip runs are traced at the default, and
    # Mosaic refuses an fp32-precision matmul over bf16 operands ("Bad
    # lhs type") — so trace these as production does
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(fn).lower(*avals).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("shape", BN_SHAPES, ids=str)
def test_bn_stats_compiles_for_v5e(one_chip, shape):
    from mxnet_tpu.ops.pallas_bn import bn_stats

    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    _compile(bn_stats, x)


def test_bn_grad_sums_compiles_for_v5e(one_chip):
    from mxnet_tpu.ops.pallas_bn import bn_grad_sums

    shape = BN_SHAPES[0]
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    stat = jax.ShapeDtypeStruct((shape[1],), jnp.float32,
                                sharding=one_chip)
    _compile(bn_grad_sums, x, x, stat, stat)


# (B, H, T, D), dtype: the transformer bench shape, the benchmark's
# training shape, a float32 half-lane head, and the edges of
# ``pallas_block_sizes``: one block an axis, an axis only 128 and 384
# divide, the single-step forward kernel (T 512), the widest tiles the
# rule lets stand (float32 d 256) and a head wide enough to shorten them
FLASH_CASES = [
    ((8, 16, 1024, 128), "bfloat16"),
    ((4, 16, 2048, 128), "bfloat16"),
    ((8, 16, 1024, 64), "float32"),
    ((2, 4, 128, 128), "bfloat16"),
    ((2, 4, 1152, 128), "bfloat16"),
    ((2, 4, 512, 128), "bfloat16"),
    ((1, 2, 2048, 256), "float32"),
    ((1, 2, 2048, 512), "float32"),
]


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "bwd"])
@pytest.mark.parametrize("shape,dtype", FLASH_CASES, ids=[
    "bench", "cell", "d64-f32", "T128", "T1152", "T512", "d256-f32",
    "d512-f32"])
def test_flash_attention_compiles_for_v5e(one_chip, shape, dtype, backward):
    """Through ``_pallas_attention``, so with the blocks
    ``pallas_block_sizes`` gives the call: Mosaic takes every tile (VMEM,
    tiling), and the backward kernels are the ones named for them."""
    from mxnet_tpu.ops import attention

    q = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    # the shape the dispatcher must judge fit for the kernel
    assert attention.pallas_eligible(q, q, q)
    scale = shape[-1] ** -0.5

    def fwd(q, k, v):
        return attention._pallas_attention(q, k, v, True, scale)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if backward else fwd
    text = _compile(fn, q, q, q).as_text()
    # forward alone; with the backward: forward, dkv, dq
    assert text.count("custom_call_target=\"tpu_custom_call\"") == (
        3 if backward else 1)
    if backward:
        sizes = attention.pallas_block_sizes(q, q)
        assert ("flash_mha_bwd_dkv_block_q_major=%d_block_q=%d_"
                "block_k_major=%d_block_k=%d/" % (
                    sizes.block_q_major_dkv, sizes.block_q_dkv,
                    sizes.block_k_major_dkv, sizes.block_k_dkv)) in text
        assert ("flash_mha_bwd_dq_block_q_major=%d_block_k_major=%d_"
                "block_k=%d/" % (sizes.block_q_dq, sizes.block_k_major_dq,
                                 sizes.block_k_dq)) in text


@pytest.mark.parametrize("name, static", [
    ("metric_cross_entropy", {"eps": 1e-12}),
    ("metric_accuracy", {"axis": 1}),
    ("metric_top_k_accuracy", {"top_k": 5}),
    ("metric_perplexity", {"ignore_label": 0}),
    ("metric_loss", {}),
], ids=lambda v: v if isinstance(v, str) else "")
def test_metric_reduction_reads_the_lm_head_in_place(one_chip, name, static):
    """The 0.82 GB softmax is an argument and nothing like it is a
    temporary: no cast or re-laid-out copy of it beside the training
    step's own buffers (a float32 copy would be 1.65 GB)."""
    from mxnet_tpu import metric

    pred = jax.ShapeDtypeStruct(LM_HEAD_SHAPE, jnp.bfloat16,
                                sharding=one_chip)
    label = jax.ShapeDtypeStruct(LM_HEAD_SHAPE[:1], jnp.float32,
                                 sharding=one_chip)
    args = (pred,) if name == "metric_loss" else (label, pred)
    compiled = getattr(metric, name).lower(*args, **static).compile()
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes >= 2 * 8192 * 50257
    assert memory.temp_size_in_bytes < 1 << 20


# granite-4.0-h-micro's Mamba-2 layer: heads, head width, state size,
# groups; 16 slots, the largest prefill bucket, the published chunk
MAMBA = dict(heads=64, width=64, state=128, groups=1)


def _mamba_avals(rows, one_chip, carried):
    def aval(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    h, p, n, g = (MAMBA[k] for k in ("heads", "width", "state", "groups"))
    return (aval(rows, h, p), aval(rows, h), aval(h), aval(rows, g, n),
            aval(rows, g, n), aval(*carried, h, p, n))


def test_mamba2_chunked_scan_compiles_for_v5e_without_a_token_loop(one_chip):
    """A bucket of 512 rows at chunk 256: the compiled program's only
    loop is the pass between its two chunks."""
    from mxnet_tpu.ops.mamba2 import ssd_chunked_scan

    with jax.default_matmul_precision("default"):
        compiled = jax.jit(ssd_chunked_scan).lower(
            *_mamba_avals(512, one_chip, ())).compile()
    text = compiled.as_text()
    assert text.count(" while(") <= 1
    assert "trip_count\":{\"n\":\"512\"" not in text
    # the decay matrices of one chunk pair are what it holds at most
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20


def test_mamba2_decode_step_updates_the_donated_state_in_place(one_chip):
    """16 slots' state, 134 MB a layer, is an argument that the result
    aliases: no second copy of it beside the 14 GB the cell holds."""
    from mxnet_tpu.ops.mamba2 import ssd_step

    compiled = jax.jit(ssd_step, donate_argnums=5).lower(
        *_mamba_avals(16, one_chip, (16,))).compile()
    memory = compiled.memory_analysis()
    state_bytes = 16 * 64 * 64 * 128 * 4
    assert memory.alias_size_in_bytes >= state_bytes
    assert memory.temp_size_in_bytes < state_bytes // 8


# Ling-3.0-flash's KDA layer at its published widths: 32 heads of 128 x
# 128 float32 state; a prefill bucket of 1024 rows in chunks of 32, a
# decode step over 64 slots.  Plain XLA, no Mosaic kernel: what could be
# refused is the triangular solve's lowering and the temporaries' size.
def _kda_avals(lead, one_chip):
    sds = lambda *shape: jax.ShapeDtypeStruct(lead + shape, jnp.float32,
                                              sharding=one_chip)
    return (sds(32, 128), sds(32, 128), sds(32, 128), sds(32, 128), sds(32),
            sds(32, 128, 128))


def test_kda_chunked_form_compiles_for_v5e(one_chip):
    from mxnet_tpu.ops.kda import kda_chunked

    q, k, v, g, beta, _ = _kda_avals((1024,), one_chip)
    state = jax.ShapeDtypeStruct((32, 128, 128), jnp.float32,
                                 sharding=one_chip)
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(lambda *a: kda_chunked(*a, chunk=32)).lower(
            q, k, v, g, beta, state).compile()
    # the chunk pass is the one loop over rows' chunks; temporaries stay
    # far under what the cell leaves free beside 12.7 GB (~3 GB)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_kda_decode_step_updates_the_state_in_place_on_v5e(one_chip):
    from mxnet_tpu.ops.kda import kda_step

    avals = _kda_avals((64,), one_chip)
    compiled = jax.jit(kda_step, donate_argnums=5).lower(*avals).compile()
    mem = compiled.memory_analysis()
    # 64 slots' state is 134 MB a layer: donated, it is updated in place
    assert mem.alias_size_in_bytes >= 64 * 32 * 128 * 128 * 4
    assert mem.temp_size_in_bytes < 64 * 32 * 128 * 128 * 4


# The routed-expert layer of the two expert cells at their real shapes
# (rows of the call, d, routed experts, experts a token, experts held, the
# tile, the tiles the layout holds, the tiles every assignment of the call
# could fill here): kanana-2's decode step over 16 slots (108 tiles of 8
# rows) and its bucket of 2048 (224 tiles of 128) over (128, 768, 2048)
# stacks; Ling-3.0-flash's decode step over 64 slots (128 tiles of 8) and
# its bucket of 1024 over the (64, 768, 2560) stacks of the share this
# chip holds: since PR 57 the 193 tiles of 16 that twice its balanced
# share of the 8 192 assignments (2 048) can fill on 64 experts, and one
# that stays zero, where all 8 192 could fill 576.
EXPERT_CASES = {
    "kanana-decode": (16, 2048, 128, 6, (), 8, 108, 108),
    "kanana-bucket-2048": (2048, 2048, 128, 6, (), 128, 224, 224),
    "ling-decode": (64, 2560, 512, 8, (0, 64), 8, 128, 128),
    "ling-bucket-1024": (1024, 2560, 512, 8, (0, 64), 16, 193, 576),
}


@pytest.mark.parametrize("name", sorted(EXPERT_CASES))
def test_grouped_swiglu_compiles_for_v5e(one_chip, name, monkeypatch):
    """Through ``_routed_experts``, so with the tiles it builds and the
    predicate's own answer for a TPU: Mosaic takes a whole expert a block
    (``vmem_limit_bytes``), the kernel carries its tile in its name, and
    no stack is copied or converted on its way in: the kernel reads the
    float32 matrices where the parameters lie.  The kernel's rows are the
    layout's: every assignment's worst case where the block holds all its
    experts or the call is a decode step, the bounded height in a share's
    prefill chunk, whose text holds no array of the worst case's rows."""
    from mxnet_tpu.ops.grouped_matmul import kernel_name
    from mxnet_tpu.serve import latent_moe
    from serve_util import expert_layer_config

    rows, d, experts, top_k, held, tile, tiles, worst = EXPERT_CASES[name]
    cfg = expert_layer_config(d, 768, experts, top_k, held)
    e = latent_moe.held_range(cfg)[1]
    assert latent_moe._tile_rows(rows * top_k, experts) == tile
    assert rows * top_k // tile + min(e, rows * top_k) == worst
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def layer(u, taken, w, gate, up, down):
        params = {"blk1_experts_gate_weight": gate,
                  "blk1_experts_up_weight": up,
                  "blk1_experts_down_weight": down}
        return latent_moe._routed_experts(u, taken, w, params, "blk1_", cfg,
                                          False)

    text = _compile(layer, sds((rows, d)), sds((rows, top_k), jnp.int32),
                    sds((rows, top_k)), sds((e, 768, d)), sds((e, 768, d)),
                    sds((e, d, 768))).as_text()
    assert len(_kernel_calls(text, kernel_name(tile))) == 1
    assert re.search(r"%%%s[.\d]* = f32\[%d,%d\]"
                     % (kernel_name(tile), tiles * tile, d), text)
    if tiles < worst:
        tall = re.findall(r"f32\[(%d|%d),%d\]"
                          % (worst * tile, worst * tile + 1, d), text)
        assert not tall, tall
    stack = r"(f32|bf16)\[%d,(768,%d|%d,768)\]" % (e, d, d)
    moved = [line.strip()[:160] for line in text.splitlines()
             if re.search(r"= %s\S* (copy|convert|fusion|transpose)\("
                          % stack, line)]
    assert not moved, moved


# An attention layer's decode half at granite-4.0-h-micro's pool size (4
# attention layers, 16 slots x 48 pages + the trash page, pages of 16, 8
# key/value heads of 64, 32 query heads), at LFM2-24B-A2B's (3 attention
# layers, 64 slots x 576 pages + 1, the same heads), at the dense cell's
# heads of 128 over as many pages as granite's, and at Laguna-S-2.1's two
# full layers (16 slots x 832 pages + 1, 8 key/value heads of 128, 48 query
# heads): a row a slot appended to the donated K and V pools, then the
# paged read, with the TPU's branches taken: the ``fori_loop`` over
# granite's folded pools (a table of 768 keys: too short for the kernel's
# folded form to be worth its start-up), the paged-attention kernel
# (``ops/paged_attention.py``) over the other three, in its form for the
# pool's layout.  What is compiled is what the blocks' ``decode_step``
# runs a layer, without its weights.
KV_CASES = {
    # name: (key/value heads, head width, query heads a key/value head,
    #        layers, pages a slot, the kernel's pages a block or 0: loop,
    #        slots)
    "granite_heads_of_64": (8, 64, 4, 4, 48, 0, 16),
    "lfm2_heads_of_64": (8, 64, 4, 3, 576, 32, 64),
    "dense_heads_of_128": (16, 128, 1, 4, 48, 8, 16),
    "laguna_heads_of_128": (8, 128, 6, 2, 832, 8, 16),
}


def _kv_layer_program(one_chip, monkeypatch, pool_shape, heads, head_dim,
                      group, slots=16):
    """-> the compiled append + paged read over two donated float32 pools
    of ``pool_shape`` as a TPU traces them, and one pool's logical
    bytes."""
    from mxnet_tpu.ops.attention import paged_decode_attention
    from mxnet_tpu.serve.kv_cache import append_rows

    page = 16
    max_pages = (pool_shape[1] - 1) // slots
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def layer(pools, q, k, v, tables, lengths):
        pools = dict(pools)
        page_slot = jnp.clip(lengths // page, 0, max_pages - 1)
        at = jnp.take_along_axis(tables, page_slot[:, None], axis=1)[:, 0]
        append_rows(pools, "k", 1, at, lengths % page, k)
        append_rows(pools, "v", 1, at, lengths % page, v)
        return pools, paged_decode_attention(
            q, pools["k_pool"], pools["v_pool"], 1, tables, lengths + 1,
            page)

    pool = sds(pool_shape)
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(layer, donate_argnums=0).lower(
            {"k_pool": pool, "v_pool": pool},
            sds((slots, heads, group, head_dim)),
            sds((slots, heads, head_dim)), sds((slots, heads, head_dim)),
            sds((slots, max_pages), jnp.int32),
            sds((slots,), jnp.int32)).compile()
    return compiled, 4 * math.prod(pool_shape)


def _whole_pool_copies(text, pool_shape):
    """-> (copies, prefetches): the lines of a compiled text whose result
    is a whole pool made by a ``copy`` (or by a fusion the compiler named
    for one: ``copy_fusion``, ``*.remat_compressed``), and those that
    only move a pool as it lies into the chip's fast memory
    (``copy-done`` into ``S(1)``, the same layout on both sides)."""
    dims = ",".join(str(n) for n in pool_shape)
    whole = r"\(?f32\[%s\]" % dims
    copies, prefetches = [], []
    for line in text.splitlines():
        if re.search(r"= %s\S* copy\(" % whole, line) or re.search(
                r"%%\S*(copy|remat_\w*compressed)\S* = %s\S* fusion\("
                % whole, line):
            copies.append(line.strip()[:140])
        elif re.search(r"= %s\S*S\(1\)\} copy-done\(" % whole, line):
            prefetches.append(line.strip()[:140])
    return copies, prefetches


def _layer_slices(text, pool_shape):
    """The lines of a compiled text whose result holds as many values as
    one layer of a pool, in whatever type and shape (what ``pool[layer]``
    in front of a custom call or of a gather materialises: the compiler
    writes the slice of a float32 pool as ``bf16[...]`` where a matmul will
    round the gathered rows, and layer 0's, which starts at the pool's
    first byte, as a plain ``slice`` of the pool laid out as rows,
    ``f32[589840,512]`` of ``f32[1769520,512]`` at LFM2's pools)."""
    size = math.prod(pool_shape[1:])
    found = []
    for line in text.splitlines():
        m = re.search(r"= [a-z]\w*\[([\d,]+)\]", line)
        if m and math.prod(int(n) for n in m.group(1).split(",")) == size:
            found.append(line.strip()[:140])
    return found


def _worst_case_rows(text, rows, top_k, experts, held, d):
    """What a compiled text keeps of a share's expert layout as tall as if
    every assignment of a chunk of ``rows`` fell on the ``held`` experts
    here: the expert kernel's results of that height, and the arrays of
    that height and the appended zero row.  A share's prefill chunk held
    both a layer until PR 57 and holds neither since."""
    from mxnet_tpu.ops.grouped_matmul import kernel_name
    from mxnet_tpu.serve import latent_moe

    a = rows * top_k
    tile = latent_moe._tile_rows(a, experts)
    worst = (a // tile + min(held, a)) * tile
    return re.findall(r"%%%s[.\d]* = f32\[%d,%d\]|f32\[%d,%d\]"
                      % (kernel_name(tile), worst, d, worst + 1, d), text)


def _kernel_calls(text, name):
    """The ``pallas_call``s named ``name`` in a compiled text."""
    return re.findall(r"%%%s[.\d]* = " % name, text)


@pytest.mark.parametrize("name", sorted(KV_CASES))
def test_kv_append_and_paged_read_leave_the_pools_where_they_lie(
        one_chip, monkeypatch, name):
    """The pools' layout at rest is the cache's rule (``kv_pool_shape``):
    under it the two pools are arguments of their logical size, the result
    aliases them and no operation of the compiled text copies a whole
    pool into another layout.  With nothing else in memory the compiler
    may still park one 100.8 MB pool, as it lies, in the 128 MiB of fast
    memory for the read loop (granite's folded V pool here: a
    ``copy-start`` / ``copy-done`` into ``S(1)``, 101.6 MB of
    temporaries); the cell's executables, with 12.8 GB of weights to
    stream, have no such move (read in their text at PR 38: PERF.md), so
    one is allowed and no more.  **Which reader**: the paged-attention
    kernel wherever the call is eligible, named for the layout and for
    its block (``paged_decode_attention_p8`` over pools that keep their
    heads' axis, ``paged_decode_attention_f64_p32`` over LFM2's heads of
    64 folded into 512 lanes), which takes the pools whole (no slice of a
    layer in front of it, no temporaries) and leaves no loop; Mosaic takes
    its double buffer within the ``vmem_limit_bytes`` it states (the
    compile is the check), well under the chip's 128 MiB.  Granite's
    folded pools under a table of 768 keys keep the loop."""
    from mxnet_tpu.ops import paged_attention
    from mxnet_tpu.serve.kv_cache import kv_pool_shape

    heads, head_dim, group, layers, max_pages, pages, slots = KV_CASES[name]
    shape = kv_pool_shape(layers, slots * max_pages + 1, 16, heads, head_dim)
    compiled, logical = _kv_layer_program(one_chip, monkeypatch, shape,
                                          heads, head_dim, group, slots)
    memory = compiled.memory_analysis()
    # q, k, v, tables, lengths and their padding: 1 MB at 16 slots
    small = slots << 16
    assert 2 * logical <= memory.argument_size_in_bytes < 2 * logical + small
    assert memory.alias_size_in_bytes >= 2 * logical
    text = compiled.as_text()
    copies, prefetches = _whole_pool_copies(text, shape)
    assert not copies, copies
    loops = len(re.findall(r" while\(", text))
    folded = len(shape) == 4
    assert folded == (head_dim < 128)
    if not pages:
        assert folded and loops == 1
        assert 16 * max_pages < paged_attention._FOLDED_MIN_TABLE_KEYS
        assert "tpu_custom_call" not in text
        assert len(prefetches) <= 1, prefetches
        assert memory.temp_size_in_bytes < len(prefetches) * logical + small
        return
    assert paged_attention.pages_per_block(16, max_pages, folded) == pages
    kernel = paged_attention.kernel_name(pages, head_dim if folded else 0)
    assert kernel == ("paged_decode_attention_f64_p32" if folded
                      else "paged_decode_attention_p8")
    assert len(_kernel_calls(text, kernel)) == 1
    assert text.count("tpu_custom_call") == 1
    assert not loops and not prefetches
    assert not _layer_slices(text, shape), _layer_slices(text, shape)
    assert memory.temp_size_in_bytes < small
    # a folded pool's kernel works on lane tiles: 4 of 128, a tile's two
    # heads' query rows one under the other
    per = 128 // head_dim
    assert paged_attention._vmem_bytes(
        pages, 16, heads // per, -(-per * group // 8) * 8, 128) < 16 << 20


# sha256 of the Mosaic module, printed without source locations, that the
# kernel lowered to at PR 47's commit over the dense cell's pools and over
# laguna's: before there was a form for folded pools
UNFOLDED_MODULES = {
    "dense_heads_of_128": (
        (24, 16 * 48 + 1, 16, 16, 128), (16, 16, 1, 128),
        "d4411f054160d4d96f4685e03bdeda1591a3bf36349e7fe811ab845f9fd9fdff"),
    "laguna_heads_of_128": (
        (2, 16 * 832 + 1, 16, 8, 128), (16, 8, 6, 128),
        "2e47e7837f8d930adb28940c3f98e901f52633ca1dd0fd83adcbe6ecdd0df423"),
}


def _mosaic_modules(lowered_text):
    """The Mosaic modules a lowered text carries (a ``tpu_custom_call``'s
    ``body``, serialized), each printed without its source locations: the
    payload itself names this repo's files and lines, which a docstring's
    edit moves."""
    import base64

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    found = []
    for body in re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22',
                           lowered_text):
        context = jax_mlir.make_ir_context()
        context.allow_unregistered_dialects = True   # ``stable_mosaic``
        with context:
            module = ir.Module.parse(base64.b64decode(body))
            found.append(module.operation.get_asm(enable_debug_info=False))
    return found


@pytest.mark.parametrize("name", sorted(UNFOLDED_MODULES))
def test_the_kernel_over_pools_that_keep_their_heads_axis_is_what_it_was(
        one_chip, name):
    """The form for folded pools shares the kernel's body with the form
    ``cgpt1.3b-chat`` and ``laguna-s2.1-l5-code`` run, and must not have
    moved it: at their shapes the body lowers to the Mosaic module it
    lowered to before (``paged_decode_attention_p8``, 8 pages a block),
    letter for letter once the source locations are left out.  A change
    that means to alter that kernel states the new digest here, and
    measures both cells."""
    import hashlib

    from mxnet_tpu.ops import paged_attention

    pool, q, digest = UNFOLDED_MODULES[name]
    slots, table = q[0], (pool[1] - 1) // q[0]

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with jax.default_matmul_precision("default"):    # as the cells trace it
        text = paged_attention._paged_attention.lower(
            sds(q), sds(pool), sds(pool), sds((), jnp.int32),
            sds((slots, table), jnp.int32), sds((slots,), jnp.int32),
            page_size=16, scale=q[-1] ** -0.5,
            pages=paged_attention.pages_per_block(16, table, False),
            full_precision=False).as_text()
    (module,) = _mosaic_modules(text)
    assert "module @paged_decode_attention_p8 " in module
    assert hashlib.sha256(module.encode()).hexdigest() == digest


def test_unfolded_heads_of_64_cost_the_whole_pool(one_chip, monkeypatch):
    """The control of the test above, and why the rule exists: the same
    program over pools that keep heads of 64 on an axis of their own
    (the layout before PR 38) pads the pools at rest (117.5 MB for 100.8)
    and copies them whole around the 16-row append."""
    heads, head_dim, group = KV_CASES["granite_heads_of_64"][:3]
    shape = (4, 16 * 48 + 1, 16, heads, head_dim)
    compiled, logical = _kv_layer_program(one_chip, monkeypatch, shape,
                                          heads, head_dim, group)
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes > 2 * logical + (16 << 20)
    assert memory.temp_size_in_bytes > 2 * logical
    assert len(_whole_pool_copies(compiled.as_text(), shape)[0]) >= 4


def test_a_layer_sliced_in_front_of_the_kernel_is_found(one_chip,
                                                        monkeypatch):
    """The control of ``_layer_slices``: the same append and read with
    the kernel handed ``pool[layer]`` (as a pool of one layer) instead of
    the pool and the layer's number holds a slice of each pool's layer,
    the kernel's operand, and a layer's bytes of temporaries."""
    from mxnet_tpu.ops import attention
    from mxnet_tpu.serve.kv_cache import kv_pool_shape

    heads, head_dim, group, layers, max_pages = KV_CASES[
        "dense_heads_of_128"][:5]
    shape = kv_pool_shape(layers, 16 * max_pages + 1, 16, heads, head_dim)
    whole = attention.paged_attention

    def sliced(q, k_pool, v_pool, layer, *rest):
        return whole(q, k_pool[layer][None], v_pool[layer][None], 0, *rest)

    monkeypatch.setattr(attention, "paged_attention", sliced)
    compiled, logical = _kv_layer_program(one_chip, monkeypatch, shape,
                                          heads, head_dim, group)
    assert len(_layer_slices(compiled.as_text(), shape)) >= 2
    assert compiled.memory_analysis().temp_size_in_bytes \
        >= logical // layers


# A latent-attention layer's decode half at the two latent cells' pool
# sizes (kanana-2: 5 latent layers, 16 slots x 144 pages + the trash page;
# Ling-3.0-flash: 1 latent layer, 64 slots x 128 pages + 1; pages of 16,
# rows of 512 + 64 values, 32 heads): 16 or 64 rows appended to the donated
# pool, every slot's table gathered, the absorbed attention over it.  What
# is compiled is what ``latent_moe.decode_step`` runs a layer, without its
# weights.
LATENT_CASES = {
    # name: (latent layers, slots, pages a slot, the layer compiled)
    "kanana_5_layers_2305_pages": (5, 16, 144, 3),
    "ling_1_layer_8193_pages": (1, 64, 128, 0),
}
LATENT_ROW, LATENT_RANK, LATENT_HEADS = 576, 512, 32


def _latent_layer_program(one_chip, pool_shape, slots, max_pages, layer):
    """-> the compiled append + whole-table read + absorbed attention over
    one donated float32 latent pool of ``pool_shape``, through the cache's
    own two functions, and the pool's logical bytes."""
    from mxnet_tpu.ops.attention import decode_attention
    from mxnet_tpu.serve.kv_cache import (append_latent_rows,
                                          read_latent_context)

    page = pool_shape[2]

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(pools, q, rows, tables, lengths):
        pools = dict(pools)
        page_slot = jnp.clip(lengths // page, 0, max_pages - 1)
        at = jnp.take_along_axis(tables, page_slot[:, None], axis=1)[:, 0]
        append_latent_rows(pools, layer, at, lengths % page, rows)
        ctx = read_latent_context(pools["latent_pool"], layer, tables)
        q = jnp.pad(q, ((0, 0), (0, 0), (0, ctx.shape[-1] - q.shape[-1])))
        return pools, decode_attention(
            q[:, None], ctx[:, None], ctx[:, None, :, :LATENT_RANK],
            lengths + 1, scale=0.07, block=max_pages * page)

    with jax.default_matmul_precision("default"):
        compiled = jax.jit(step, donate_argnums=0).lower(
            {"latent_pool": sds(pool_shape)},
            sds((slots, LATENT_HEADS, LATENT_ROW)), sds((slots, LATENT_ROW)),
            sds((slots, max_pages), jnp.int32),
            sds((slots,), jnp.int32)).compile()
    return compiled, 4 * math.prod(pool_shape)


def _pool_parameter_layout(text, pool_shape):
    """-> the minor-to-major order of the entry parameter that is the
    pool, as the compiled text writes it (``3,2,1,0`` = row-major)."""
    dims = ",".join(str(n) for n in pool_shape)
    found = re.search(
        r"%%pools\S* = f32\[%s\]\{([0-9,]+)[:}]\S* parameter\(" % dims, text)
    assert found, "no entry parameter of f32[%s]" % dims
    return found.group(1)


@pytest.mark.parametrize("name", sorted(LATENT_CASES))
def test_latent_append_and_read_leave_the_pool_where_it_lies(one_chip, name):
    """The latent pool's layout at rest is the cache's rule
    (``latent_pool_shape``: rows of 576 values in five whole lane tiles):
    under it the pool is a row-major argument of its logical size, the
    result aliases it, no operation of the compiled text copies the whole
    pool into another layout or slices a layer out of it in front of the
    gather, and the temporaries stay under two gathered contexts."""
    from mxnet_tpu.serve.kv_cache import latent_pool_shape

    layers, slots, max_pages, layer = LATENT_CASES[name]
    shape = latent_pool_shape(layers, slots * max_pages + 1, 16, LATENT_ROW)
    assert shape[-1] == 640
    compiled, logical = _latent_layer_program(one_chip, shape, slots,
                                              max_pages, layer)
    text, memory = compiled.as_text(), compiled.memory_analysis()
    # the queries in whole lane tiles; rows, tables, lengths, padding
    others = 4 * slots * LATENT_HEADS * shape[-1] + (1 << 20)
    assert _pool_parameter_layout(text, shape) == "3,2,1,0"
    assert logical <= memory.argument_size_in_bytes < logical + others
    assert memory.alias_size_in_bytes >= logical
    copies, prefetches = _whole_pool_copies(text, shape)
    assert not copies and not prefetches, copies + prefetches
    sliced = [line.strip()[:140] for line in text.splitlines() if re.search(
        r"%%\S*slice\S* = f32\[%d,%d,%d\]\S* fusion\(" % shape[1:], line)]
    assert not sliced, sliced
    context = 4 * slots * max_pages * 16 * shape[-1]
    assert memory.temp_size_in_bytes < 2 * context


@pytest.mark.parametrize("name", sorted(LATENT_CASES))
def test_latent_rows_of_576_cost_the_whole_pool(one_chip, name):
    """The control of the test above, and why the rule exists: the same
    program, through the same two functions, over a pool that keeps rows
    of 576 values (the layout before PR 41).  The compiler then puts the
    PAGE axis on the lanes at rest (2305 -> 2432 pads less than 576 ->
    640), and transposes the whole pool to rows in front of the append
    and back behind it: two whole-pool copies a call."""
    layers, slots, max_pages, layer = LATENT_CASES[name]
    shape = (layers, slots * max_pages + 1, 16, LATENT_ROW)
    compiled, logical = _latent_layer_program(one_chip, shape, slots,
                                              max_pages, layer)
    text, memory = compiled.as_text(), compiled.memory_analysis()
    assert _pool_parameter_layout(text, shape) == "1,3,2,0"
    assert len(_whole_pool_copies(text, shape)[0]) >= 2
    assert memory.temp_size_in_bytes > logical      # a pool of temporaries


# The window / full grouped-query block's own executables at
# Laguna-S-2.1's published widths, the share the cell holds and the cell's
# cache: 5 layers (full | window x 3 | full, 48 | 72 | 48 query heads over
# 8 key/value heads of 128, window 512), experts 0-31 of 256 held, 1/8 of
# the vocabulary; 16 slots x 832 pages of 16 + the trash page in the two
# full layers' K/V pools (1.745 GB each), rings of 512 rows a slot in the
# three window layers (0.101 GB each).  What is compiled is
# ``laguna.decode_step`` / ``laguna.prefill_forward`` with the TPU's
# branches taken (the expert layers' grouped-matmul kernel).
LAGUNA_SLOTS, LAGUNA_TABLE = 16, (12288 + 1024) // 16


def _laguna_program(one_chip, monkeypatch, bucket):
    """-> the compiled decode step (``bucket`` 0) or prefill chunk of
    ``bucket`` rows, the cache's pool shapes, and the notes of the trace."""
    from mxnet_tpu import serve
    from mxnet_tpu.serve import kv_cache, laguna
    from mxnet_tpu.serve import model as serve_model

    cfg = serve.ModelConfig(
        block="laguna", vocab_size=12544, num_layers=5, d_model=3072,
        num_heads=48, num_key_value_heads=8, max_len=1048576,
        attn_head_dim=128, num_attention_heads_per_layer=(48, 72, 72, 72, 48),
        layer_types=("full_attention",) + ("sliding_attention",) * 3
        + ("full_attention",), sliding_window=512,
        rope_parameters={
            "full_attention": dict(
                rope_theta=500000, rope_type="yarn", factor=128,
                original_max_position_embeddings=8192, beta_slow=1,
                beta_fast=32, attention_factor=1.4852030263919618,
                partial_rotary_factor=0.5),
            "sliding_attention": dict(rope_type="default", rope_theta=10000,
                                      partial_rotary_factor=1)},
        mlp_only_layers=(0,), d_ff=12288, moe_d_ff=1024,
        n_routed_experts=256, num_experts_per_tok=10,
        shared_expert_intermediate_size=1024, routed_scaling_factor=2.5,
        scoring_func="softmax", experts_held=(0, 32)).validate()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    i32 = jnp.int32
    params = {k: sds(v) for k, v in laguna.param_shapes(cfg).items()}
    assert abs(sum(math.prod(v.shape) for v in params.values())
               - 1717e6) < 1e6
    page = 16
    rings = laguna.ring_pages(cfg, serve.ServeConfig(
        page_size=page, buckets=(512, 2048))) * page
    shapes = {
        "k_pool": kv_cache.kv_pool_shape(
            2, LAGUNA_SLOTS * LAGUNA_TABLE + 1, page, 8, 128),
        "kw_pool": (3, LAGUNA_SLOTS, rings, 8, 128)}
    shapes.update(v_pool=shapes["k_pool"], vw_pool=shapes["kw_pool"])
    pools = {name: sds(shape) for name, shape in shapes.items()}
    counters = {"moe_stats": sds((2, len(laguna.MOE_COLUMNS)), i32),
                "attn_stats": sds((2, len(laguna.ATTN_COLUMNS)), i32)}
    static = dict(cfg=cfg, page_size=page, exact=False, kv_quant="")
    if bucket:
        def step(params, tokens, length, offset, table_row, pools, counters,
                 slot):
            return laguna.prefill_forward(
                params, tokens, length, offset, table_row, pools, counters,
                slot=slot, **static)

        avals = (params, sds((1, bucket), i32), sds((), i32), sds((), i32),
                 sds((LAGUNA_TABLE,), i32), pools, counters, sds((), i32))
        donate = (5, 6)
    else:
        def step(params, tokens, lengths, tables, pools, counters):
            return laguna.decode_step(params, tokens, lengths, tables, pools,
                                      counters, **static)

        avals = (params, sds((LAGUNA_SLOTS,), i32), sds((LAGUNA_SLOTS,), i32),
                 sds((LAGUNA_SLOTS, LAGUNA_TABLE), i32), pools, counters)
        donate = (4, 5)
    with jax.default_matmul_precision("default"), \
            serve_model.trace_notes() as notes:
        lowered = jax.jit(step, donate_argnums=donate).lower(*avals)
    return lowered.compile(
        compiler_options=laguna.compiler_options("tpu")), shapes, notes


# ``lfm2-24b-l13-docqa``'s executables at LFM2-24B-A2B's published widths
# over the cell's cache: 13 layers (conv | attention conv conv conv x 3; 32
# query heads over 8 key/value heads of 64; 3 taps), experts 0-7 of 64
# held, 1/8 of the vocabulary, a tied head; 64 slots x 576 pages of 16 +
# the trash page in the three attention layers' K/V pools, 8 heads of 64
# folded into 512 lanes (3.62 GB each), and two convolution rows a slot in
# ten layers (10.5 MB).  What is compiled is ``lfm2_moe.decode_step`` /
# ``lfm2_moe.prefill_forward`` with the TPU's branches taken.
LFM2_SLOTS, LFM2_TABLE = 64, (8192 + 1024) // 16


def _lfm2_program(one_chip, monkeypatch, bucket):
    """-> the compiled decode step (``bucket`` 0) or prefill chunk of
    ``bucket`` rows, the cache's pool shapes, and the notes of the trace."""
    from mxnet_tpu import serve
    from mxnet_tpu.serve import kv_cache, lfm2_moe
    from mxnet_tpu.serve import model as serve_model

    cfg = serve.ModelConfig(
        block="lfm2_moe", vocab_size=8192, num_layers=13, d_model=2048,
        num_heads=32, num_key_value_heads=8, max_len=128000,
        attn_head_dim=64, rope_theta=1e6, rms_norm_eps=1e-5,
        layer_types=("conv",) + ("full_attention", "conv", "conv", "conv")
        * 3, conv_L_cache=3, d_ff=11776, first_k_dense=1, moe_d_ff=1536,
        n_routed_experts=64, num_experts_per_tok=4,
        tie_word_embeddings=True, experts_held=(0, 8)).validate()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    i32 = jnp.int32
    params = {k: sds(v) for k, v in lfm2_moe.param_shapes(cfg).items()}
    assert abs(sum(math.prod(v.shape) for v in params.values())
               - 1196.0e6) < 1e6
    page = 16
    shapes = {"k_pool": kv_cache.kv_pool_shape(
        3, LFM2_SLOTS * LFM2_TABLE + 1, page, 8, 64)}
    shapes["v_pool"] = shapes["k_pool"]
    shapes["conv_state"] = (10, LFM2_SLOTS, 2, 2048)
    assert shapes["k_pool"][-1] == 512 and len(shapes["k_pool"]) == 4
    pools = {name: sds(shape) for name, shape in shapes.items()}
    counters = {name: sds(leaf.shape, i32) for name, leaf
                in lfm2_moe.init_counters(cfg).items()}
    static = dict(cfg=cfg, page_size=page, exact=False, kv_quant="")
    if bucket:
        def step(params, tokens, length, offset, table_row, pools, counters,
                 slot):
            return lfm2_moe.prefill_forward(
                params, tokens, length, offset, table_row, pools, counters,
                slot=slot, **static)

        avals = (params, sds((1, bucket), i32), sds((), i32), sds((), i32),
                 sds((LFM2_TABLE,), i32), pools, counters, sds((), i32))
        donate = (5, 6)
    else:
        def step(params, tokens, lengths, tables, pools, counters):
            return lfm2_moe.decode_step(params, tokens, lengths, tables,
                                        pools, counters, **static)

        avals = (params, sds((LFM2_SLOTS,), i32), sds((LFM2_SLOTS,), i32),
                 sds((LFM2_SLOTS, LFM2_TABLE), i32), pools, counters)
        donate = (4, 5)
    with jax.default_matmul_precision("default"), \
            serve_model.trace_notes() as notes:
        lowered = jax.jit(step, donate_argnums=donate).lower(*avals)
    return lowered.compile(
        compiler_options=lfm2_moe.compiler_options("tpu")), shapes, notes


@pytest.mark.parametrize("bucket, tile, temporaries", [
    (0, 8, 64 << 20), (512, 32, 7 << 23), (2048, 128, 3 << 26)],
    ids=["decode", "prefill-512", "prefill-2048"])
def test_lfm2_executables_compile_for_v5e_at_the_published_widths(
        one_chip, monkeypatch, bucket, tile, temporaries):
    """The whole step fits the chip beside its arguments (12.04 GB: 4.78
    of weights, 7.25 of pages, 0.01 of convolution rows), Mosaic takes a
    whole float32 expert of 1536 x 2048 a block in all twelve expert
    layers, and the donated pools and the state are updated where they
    lie: the result aliases all three, and no operation copies a whole
    K/V pool into another layout.  The decode step's three attention
    layers read their folded pools through the paged-attention kernel's
    folded form (one lowering, three calls): no loop under
    ``gqa_decode``, no slice of a pool's layer.  Nor does a prefill chunk
    hold one (until PR 49 six float32 copies of a 1.21 GB layer a chunk in
    front of ``read_context``'s gathers, layer 0's two at bucket 2048 as
    plain slices of the pool laid out as rows, and 1.27-1.32 GB of
    temporaries where there were then 0.06 and 0.25), nor since PR 51 the
    gathers or the scan: its three attention layers read their pages
    through the prefill kernel (one lowering, three calls).  Since PR 57
    a chunk's expert layers lay out rows for what they hold (3 200 where
    every assignment could fill 9 216 at bucket 2048, 800 for 2 304 at
    512): 50.0 and 175.9 MB of temporaries where there were 65.3 and
    228.8, and no array of the worst case's rows."""
    from mxnet_tpu.ops.grouped_matmul import kernel_name

    compiled, shapes, notes = _lfm2_program(one_chip, monkeypatch, bucket)
    assert notes == dict({"expert_kernel_layers": 12}, **(
        _prefill_notes(3, 512, 4) if bucket
        else {"paged_kernel_layers": 3}))
    text = compiled.as_text()
    assert len(_kernel_calls(text, kernel_name(tile))) == 12
    memory = compiled.memory_analysis()
    held = 4 * sum(math.prod(shape) for shape in shapes.values())
    assert memory.alias_size_in_bytes >= held
    assert 12.0e9 < memory.argument_size_in_bytes < 12.1e9
    assert memory.temp_size_in_bytes < temporaries
    if bucket:
        assert not _worst_case_rows(text, bucket, 4, 64, 8, 2048)
    copies, _ = _whole_pool_copies(text, shapes["k_pool"])
    assert not copies, copies
    # the state (10.5 MB) is at rest a slot's two rows at a time; a decode
    # step turns it slot-minor and back (three copies of 10.5 MB, under
    # 0.1 ms of an ~11 ms step: ling's ``conv_state`` likewise, ROADMAP
    # D15), a prefill chunk touches one slot's rows and copies nothing
    copies, _ = _whole_pool_copies(text, shapes["conv_state"])
    assert len(copies) <= (0 if bucket else 3), copies
    if not bucket:
        _decode_reads_by_kernel(text, shapes["k_pool"], "gqa_decode", 3,
                                folded_head=64)
    else:
        # two heads of 64 a lane tile, 512 rows each: a left operand of
        # 1 024 rows, 64 pages a key block
        _prefill_reads_by_kernel(text, shapes["k_pool"], "gqa_prefill", 3,
                                 (1, 8, LFM2_TABLE * 16, 64), 512,
                                 folded_head=64)


# ``sdar-30b-l12-chat``'s executables at SDAR-30B-A3B-Chat's published
# widths over the cell's cache: 12 layers (32 query heads over 4 key/value
# heads of 128, a norm a head), experts 0-15 of 128 held, 1/8 of the
# vocabulary, an untied head; 32 slots x 256 pages of 16 + the trash page
# in twelve layers' K/V pools, 4 heads of 128 folded into 512 lanes (3.22
# GB each).  What is compiled is ``sdar_moe.block_pass`` /
# ``sdar_moe.prefill_forward`` with the TPU's branches taken.
SDAR_SLOTS, SDAR_TABLE, SDAR_BLOCK = 32, (3072 + 1024) // 16, 4
# ``_sdar_program``'s "bucket" for the block pass as the session's
# executable takes its tokens since PR 55 (0: ``sdar_moe.block_pass`` alone)
AHEAD = -1


def _sdar_program(one_chip, monkeypatch, bucket):
    """-> the compiled block pass (``bucket`` 0) or prefill chunk of
    ``bucket`` rows, the cache's pool shapes, and the notes of the trace."""
    from mxnet_tpu import serve
    from mxnet_tpu.serve import kv_cache, sdar_moe
    from mxnet_tpu.serve import model as serve_model

    cfg = serve.ModelConfig(
        block="sdar_moe", vocab_size=18992, num_layers=12, d_model=2048,
        num_heads=32, num_key_value_heads=4, max_len=32768,
        attn_head_dim=128, rope_theta=1e6, rms_norm_eps=1e-6, moe_d_ff=768,
        n_routed_experts=128, num_experts_per_tok=8, scoring_func="softmax",
        experts_held=(0, 16), block_length=SDAR_BLOCK, mask_token_id=18991,
        denoising_steps=4, confidence_threshold=0.9).validate()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    i32 = jnp.int32
    params = {k: sds(v) for k, v in sdar_moe.param_shapes(cfg).items()}
    assert abs(sum(math.prod(v.shape) for v in params.values())
               - 1213.5e6) < 1e6
    page = 16
    shapes = {"k_pool": kv_cache.kv_pool_shape(
        12, SDAR_SLOTS * SDAR_TABLE + 1, page, 4, 128)}
    shapes["v_pool"] = shapes["k_pool"]
    assert shapes["k_pool"][-1] == 512 and len(shapes["k_pool"]) == 4
    pools = {name: sds(shape) for name, shape in shapes.items()}
    counters = {name: sds(leaf.shape, i32) for name, leaf
                in sdar_moe.init_counters(cfg).items()}
    static = dict(cfg=cfg, page_size=page, exact=False, kv_quant="")
    if bucket > 0:
        def step(params, tokens, length, offset, table_row, pools, counters):
            return sdar_moe.prefill_forward(
                params, tokens, length, offset, table_row, pools, counters,
                **static)

        avals = (params, sds((1, bucket), i32), sds((), i32), sds((), i32),
                 sds((SDAR_TABLE,), i32), pools, counters)
        donate = (5, 6)
    elif bucket == AHEAD:
        # the executable as the session builds it (``_compile_all``): a
        # slot's rows are the host's or the unread pass's, selected inside
        def step(params, tokens, before, from_host, quota, fresh, lengths,
                 tables, pools, counters):
            tokens = jnp.where(from_host[:, None], tokens, before)
            return sdar_moe.block_pass(params, tokens, quota, fresh, lengths,
                                       tables, pools, counters, **static)

        rows = sds((SDAR_SLOTS, SDAR_BLOCK), i32)
        avals = (params, rows, rows, sds((SDAR_SLOTS,), jnp.bool_),
                 sds((SDAR_SLOTS,), i32), sds((SDAR_SLOTS,), i32),
                 sds((SDAR_SLOTS,), i32), sds((SDAR_SLOTS, SDAR_TABLE), i32),
                 pools, counters)
        donate = (8, 9)
    else:
        def step(params, tokens, quota, fresh, lengths, tables, pools,
                 counters):
            return sdar_moe.block_pass(params, tokens, quota, fresh, lengths,
                                       tables, pools, counters, **static)

        avals = (params, sds((SDAR_SLOTS, SDAR_BLOCK), i32),
                 sds((SDAR_SLOTS,), i32), sds((SDAR_SLOTS,), i32),
                 sds((SDAR_SLOTS,), i32), sds((SDAR_SLOTS, SDAR_TABLE), i32),
                 pools, counters)
        donate = (6, 7)
    with jax.default_matmul_precision("default"), \
            serve_model.trace_notes() as notes:
        lowered = jax.jit(step, donate_argnums=donate).lower(*avals)
    return lowered.compile(
        compiler_options=sdar_moe.compiler_options("tpu")), shapes, notes


@pytest.mark.parametrize("bucket, tile, temporaries", [
    (0, 8, 512 << 20), (AHEAD, 8, 512 << 20), (2048, 128, 5 << 26)],
    ids=["block_pass", "block_pass-ahead", "prefill-2048"])
def test_sdar_executables_compile_for_v5e_at_the_published_widths(
        one_chip, monkeypatch, bucket, tile, temporaries):
    """The whole step fits the chip beside its arguments (11.3 GB: 4.85 of
    weights, 6.44 of pages), Mosaic takes a whole float32 expert of 768 x
    2048 a block in all twelve layers (a pass's 128 rows x 8 experts over
    128 is a tile of 8, a chunk of 2048 rows one of 128), and the donated
    pools are updated where they lie: the result aliases both, and no
    operation copies a whole K/V pool into another layout (four heads of
    128 on an axis of their own lie at rest in tiles of 4 rows, and the
    step then turns both pools whole to tiles of page rows and back: 6 GB
    of temporaries, more than the chip has left: PERF.md, PR 50).  Folded,
    the pools are the paged-attention kernel's to read, one head a lane
    tile with the block's 4 rows x 8 query heads as its 32 rows: twelve
    kernels in a pass and no loop under ``bdiff_pass``.  The pass as the
    session's executable takes its tokens (``block_pass-ahead``: the
    host's rows, the unread pass's, a mask) is all of that too, and its
    temporaries are the plain pass's to within the selected rows.  Since
    PR 57 a chunk's expert layers lay out 6 272 rows where every
    assignment could fill 18 432: 268.9 MB of temporaries where there were
    385.0."""
    from mxnet_tpu.ops import paged_attention
    from mxnet_tpu.ops.grouped_matmul import kernel_name

    compiled, shapes, notes = _sdar_program(one_chip, monkeypatch, bucket)
    if bucket == AHEAD:
        # the select in front of the pass costs the selected rows a buffer
        # of their own (two tiles' worth: 30 687 744 bytes of temporaries
        # against 30 655 488 without it) and nothing of a pool's size
        plain, _, _ = _sdar_program(one_chip, monkeypatch, 0)
        assert compiled.memory_analysis().temp_size_in_bytes \
            <= plain.memory_analysis().temp_size_in_bytes + (64 << 10)
        bucket = 0      # from here on it is held to all a block pass is
    assert notes == dict({"expert_kernel_layers": 12}, **(
        _prefill_notes(12, 1024, 8) if bucket
        else {"paged_kernel_layers": 12}))
    text = compiled.as_text()
    # a prefill yields no token: what the last layer's attention and
    # experts would add to x nobody reads, and the compiler drops both
    assert len(_kernel_calls(text, kernel_name(tile))) == (11 if bucket
                                                           else 12)
    memory = compiled.memory_analysis()
    held = 4 * sum(math.prod(shape) for shape in shapes.values())
    assert memory.alias_size_in_bytes >= held
    # (the head, the final norm and the last layer's q and o are no
    # arguments of a prefill: 0.17 GB.  Until PR 57 its experts were none
    # either, 0.30 GB: the loop over the layout's rounds stays for what it
    # counts, the compiler drops the kernel and the combine out of its
    # body, and the stacks stay in the signature of a loop that no longer
    # reads them.  They lie on the device as the session's parameters
    # whether an executable names them or not.)
    assert (11.1e9 if bucket else 11.2e9) < memory.argument_size_in_bytes \
        < (11.2e9 if bucket else 11.4e9)
    assert memory.temp_size_in_bytes < temporaries
    if bucket:
        assert not _worst_case_rows(text, bucket, 8, 128, 16, 2048)
    copies, _ = _whole_pool_copies(text, shapes["k_pool"])
    assert not copies, copies
    if not bucket:
        # ONE trace of the kernel for the twelve layers
        assert len(_kernel_calls(
            text, paged_attention.kernel_name(32, 128))) >= 1
        assert not re.findall(r" while\([^\n]*op_name=\"[^\"]*bdiff_pass",
                              text)
    else:
        # a chunk's layers read their pages through the prefill kernel,
        # one head of 128 a lane tile with 1 024 of its 16 384 rows a step
        # (the last layer's too: its router's counts read what it gives)
        _prefill_reads_by_kernel(text, shapes["k_pool"], "bdiff_prefill",
                                 12, (1, 4, SDAR_TABLE * 16, 128), 1024,
                                 folded_head=128)


def _decode_reads_by_kernel(text, pool_shape, scope, layers, folded_head=0):
    """A decode executable's compiled text holds ``layers``
    paged-attention kernels in the form for its pools' layout (heads on
    their own axis, 8 pages a block; or heads of ``folded_head`` folded,
    32) and none in the other, no ``while`` that the trace put under
    ``scope`` (the attention's; the expert layers' ``searchsorted`` loops
    are elsewhere) and no slice of a pool's layer."""
    from mxnet_tpu.ops import paged_attention

    name = paged_attention.kernel_name(32 if folded_head else 8, folded_head)
    assert len(_kernel_calls(text, name)) == layers
    assert len(re.findall(r"%paged_decode_attention_\w+[.\d]* = ", text)) \
        == layers
    assert not re.findall(r" while\([^\n]*op_name=\"[^\"]*%s[^\"]*\""
                          % scope, text)
    assert not _layer_slices(text, pool_shape), _layer_slices(text,
                                                              pool_shape)


def _prefill_notes(layers, tile, group, pages=64):
    """What ``paged_prefill_attention`` notes in a trace whose ``layers``
    full-attention layers all took the kernel at ``tile`` query rows of a
    head a step, ``group`` query heads a key/value head and ``pages``
    pages of 16 keys a block: the sums ``prefill_report()`` counts
    from."""
    return {"prefill_kernel_layers": layers,
            "prefill_kernel_tile_rows": layers * tile,
            "prefill_kernel_query_heads": layers * group,
            "prefill_kernel_block_keys": layers * pages * 16}


def _prefill_reads_by_kernel(text, pool_shape, scope, layers, context, tile,
                             folded_head=0, pages=64):
    """A prefill executable's compiled text holds ``layers`` prefill
    kernels in the form for its pools' layout at ``tile`` query rows a
    step and ``pages`` pages a key block, no ``while`` that the trace put under
    ``scope`` (the bounded scan's), no slice of a pool's layer and no
    gathered context (``context``: the slot's whole table as
    ``read_context`` hands it to the scan, (1, H, rows, D), or as its
    gather and reshape leave it on the way there)."""
    from mxnet_tpu.ops import paged_attention

    name = paged_attention.prefill_kernel_name(tile, pages, folded_head)
    assert len(_kernel_calls(text, name)) == layers
    assert len(re.findall(r"%paged_prefill_attention_\w+[.\d]* = ", text)) \
        == layers
    assert not re.findall(r" while\([^\n]*op_name=\"[^\"]*%s[^\"]*\""
                          % scope, text)
    assert not _layer_slices(text, pool_shape), _layer_slices(text,
                                                              pool_shape)
    _, heads, rows, d = context
    shapes = ("1,%d,%d,%d" % (heads, rows, d), "1,%d,%d,%d" % (rows, heads, d),
              "%d,16,%d,%d" % (rows // 16, heads, d),
              "%d,16,%d" % (rows // 16, heads * d))
    found = [line.strip()[:140] for line in text.splitlines()
             if re.search(r"= f32\[(%s)\]" % "|".join(shapes), line)]
    assert not found, found


def test_dense_decode_step_compiles_for_v5e_with_the_kernel_in(one_chip,
                                                               monkeypatch):
    """``cgpt1.3b-chat``'s decode executable at Cerebras-GPT-1.3B's
    published widths (24 layers, d 2048, 16 heads of 128, ffn 8192,
    vocabulary 50257) over the cell's cache (16 slots x 48 pages of 16 +
    the trash page: two pools of 2.42 GB): all 24 layers' attention is the
    paged-attention kernel, the program holds no loop at all, no slice of
    a pool's layer and no whole-pool copy, and the donated pools are
    updated where they lie."""
    from mxnet_tpu import serve
    from mxnet_tpu.serve import kv_cache
    from mxnet_tpu.serve import model as serve_model

    cfg = serve.ModelConfig(vocab_size=50257, num_layers=24, d_model=2048,
                            num_heads=16, max_len=2048)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    slots, table, page, i32 = 16, 48, 16, jnp.int32
    params = {k: sds(v.shape) for k, v in jax.eval_shape(
        lambda: serve_model.init_params(cfg)).items()}
    shape = kv_cache.kv_pool_shape(24, slots * table + 1, page, 16, 128)
    pools = {"k_pool": sds(shape), "v_pool": sds(shape)}

    def step(params, tokens, lengths, tables, pools):
        return serve_model.decode_step(params, tokens, lengths, tables,
                                       pools, {}, cfg, page, exact=False)

    with jax.default_matmul_precision("default"), \
            serve_model.trace_notes() as notes:
        lowered = jax.jit(step, donate_argnums=4).lower(
            params, sds((slots,), i32), sds((slots,), i32),
            sds((slots, table), i32), pools)
    assert notes == {"paged_kernel_layers": 24}
    compiled = lowered.compile()
    text, memory = compiled.as_text(), compiled.memory_analysis()
    _decode_reads_by_kernel(text, shape, "", 24)
    assert not _whole_pool_copies(text, shape)[0]
    assert memory.alias_size_in_bytes >= 2 * 4 * math.prod(shape)
    assert memory.temp_size_in_bytes < 64 << 20


def _loop_conditions(text, scope):
    """The condition computations of the ``while`` operations that the
    trace put under ``scope``, as the compiled text writes them."""
    found = []
    for name in re.findall(
            r" while\(\S+ condition=%%(\S+), body=\S+ "
            r"metadata=\{op_name=\"[^\"]*/%s/while\"" % scope, text):
        body = re.search(r"^%%%s \(.*?^\}" % re.escape(name), text,
                         re.M | re.S)
        assert body, "no computation %s" % name
        found.append(body.group(0))
    return found


@pytest.mark.parametrize("bucket, tile, temporaries", [
    (0, 8, 64 << 20), (512, 32, 7 << 25), (2048, 128, 20 << 25)],
    ids=["decode", "prefill-512", "prefill-2048"])
def test_laguna_executables_compile_for_v5e_at_the_published_widths(
        one_chip, monkeypatch, bucket, tile, temporaries):
    """The whole step fits the chip beside its arguments (10.56 GB: 6.87
    of weights, 3.49 of pages, 0.20 of rings), Mosaic takes a whole
    float32 expert of 1024 x 3072 a block in all four expert layers, and
    the donated pools and rings are updated where they lie: the result
    aliases all four, and no operation copies a whole pool or a whole
    ring into another layout, nor (since PR 49) one layer of a pool in
    a prefill chunk: the temporaries stay under one (0.87 GB).  Since
    PR 57 a chunk's expert layers lay out 9 344 rows of 3 072 where every
    assignment could fill 24 576 (2 336 for 6 144 at bucket 512): 608.2 MB
    of temporaries at bucket 2048 where there were 798.0 (190.6 for 196.2
    at 512, where the window layers' attention sets the peak)."""
    from mxnet_tpu.ops.grouped_matmul import kernel_name

    compiled, shapes, notes = _laguna_program(one_chip, monkeypatch, bucket)
    assert notes == dict({"expert_kernel_layers": 4}, **(
        _prefill_notes(2, 512, 6) if bucket
        else {"paged_kernel_layers": 2}))
    text = compiled.as_text()
    assert len(_kernel_calls(text, kernel_name(tile))) == 4
    memory = compiled.memory_analysis()
    held = 4 * sum(math.prod(shape) for shape in shapes.values())
    assert shapes["kw_pool"][2] == 512
    assert memory.alias_size_in_bytes >= held
    assert 10.5e9 < memory.argument_size_in_bytes < 10.6e9
    assert memory.temp_size_in_bytes < temporaries
    if bucket:
        assert not _worst_case_rows(text, bucket, 10, 256, 32, 3072)
    for shape in (shapes["k_pool"], shapes["kw_pool"]):
        copies, _ = _whole_pool_copies(text, shape)
        assert not copies, copies
    if not bucket:
        # the two full layers' reads are the paged-attention kernel over
        # the whole pools: no loop under their scope, no layer sliced out
        _decode_reads_by_kernel(text, shapes["k_pool"], "gqa_decode", 2)
    if bucket:
        # the two full layers read their pages through the prefill kernel,
        # 512 of a head's 12 288 (or 3 072) rows a step: no loop under
        # their scope (until PR 51 one a layer over the gathered table's
        # 26 blocks of 512 keys), no gathered table
        _prefill_reads_by_kernel(text, shapes["k_pool"], "gqa_prefill", 2,
                                 (1, 8, LAGUNA_TABLE * 16, 128), 512)


# ``phi4-flash-l24-reason``'s executables at Phi-4-mini-flash-reasoning's
# published widths over the cell's cache: 24 layers by the rule (7 Mamba-1,
# 6 window and 1 full differential attention, 5 gated memory units, 5
# cross-attention), the whole vocabulary, a tied head; 24 slots x 256 pages
# of 16 (the larger bucket, 2048, and ``max_new`` 2048) + the trash page
# in ONE layer's K/V pools, 10 key/value pairs of 128 folded into 1280
# lanes (0.50 GB each); six rings of 512 rows, folded
# likewise (0.38 GB each); seven layers' state as (16, 5120) a slot.  What
# is compiled is ``phi4flash.decode_step`` / ``phi4flash.prefill_forward``
# with the TPU's branches taken.
PHI4_SLOTS, PHI4_TABLE = 24, (2048 + 2048) // 16


def _phi4flash_program(one_chip, monkeypatch, bucket, slots=PHI4_SLOTS):
    """-> the compiled decode step (``bucket`` 0) or prefill chunk of
    ``bucket`` rows, the cache's pool shapes, and the notes of the trace."""
    from mxnet_tpu import serve
    from mxnet_tpu.serve import kv_cache, phi4flash
    from mxnet_tpu.serve import model as serve_model

    cfg = serve.ModelConfig(
        block="phi4flash", vocab_size=200064, num_layers=24, d_model=2560,
        num_heads=20, num_key_value_heads=10, max_len=262144, d_ff=10240,
        layer_types=phi4flash.layer_rule(24), sliding_window=512,
        mamba_d_state=16, mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=160,
        rms_norm_eps=1e-5, layer_norm_eps=1e-5,
        tie_word_embeddings=True).validate()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    i32 = jnp.int32
    params = {k: sds(v) for k, v in phi4flash.param_shapes(cfg).items()}
    assert abs(sum(math.prod(v.shape) for v in params.values())
               - 3022.86e6) < 1e4          # 12.09 GB in float32
    page = 16
    shapes = {"k_pool": kv_cache.kv_pool_shape(
        1, slots * PHI4_TABLE + 1, page, 10, 128)}
    shapes["v_pool"] = shapes["k_pool"]
    shapes["kw_pool"] = shapes["vw_pool"] = kv_cache.kv_pool_shape(
        6, slots, 512, 10, 128)
    assert shapes["k_pool"][-1] == shapes["kw_pool"][-1] == 1280
    for name, (layers, shape, _) in phi4flash.state_shapes(cfg).items():
        shapes[name] = (layers, slots) + tuple(shape)
    pools = {name: sds(shape) for name, shape in shapes.items()}
    counters = {name: sds(leaf.shape, i32) for name, leaf
                in phi4flash.init_counters(cfg).items()}
    static = dict(cfg=cfg, page_size=page, exact=False, kv_quant="")
    if bucket:
        def step(params, tokens, length, offset, table_row, pools, counters,
                 slot):
            return phi4flash.prefill_forward(
                params, tokens, length, offset, table_row, pools, counters,
                slot=slot, **static)

        avals = (params, sds((1, bucket), i32), sds((), i32), sds((), i32),
                 sds((PHI4_TABLE,), i32), pools, counters, sds((), i32))
        donate = (5, 6)
    else:
        def step(params, tokens, lengths, tables, pools, counters):
            return phi4flash.decode_step(params, tokens, lengths, tables,
                                         pools, counters, **static)

        avals = (params, sds((slots,), i32), sds((slots,), i32),
                 sds((slots, PHI4_TABLE), i32), pools, counters)
        donate = (4, 5)
    with jax.default_matmul_precision("default"), \
            serve_model.trace_notes() as notes:
        lowered = jax.jit(step, donate_argnums=donate).lower(*avals)
    return lowered.compile(
        compiler_options=phi4flash.compiler_options("tpu")), shapes, notes


@pytest.mark.parametrize("bucket, temporaries", [
    (0, 1 << 28), (512, 1 << 28), (2048, 2 << 28)],
    ids=["decode", "prefill-512", "prefill-2048"])
def test_phi4flash_executables_compile_for_v5e_at_the_published_widths(
        one_chip, monkeypatch, bucket, temporaries):
    """The whole step fits the chip beside its arguments (13.92 GB: 12.09 of
    weights, 1.01 of one layer's pages, 0.75 of rings, 0.07 of state):
    under 0.27 GB of temporaries in a decode step and 0.54 GB in a chunk,
    and the compiler rematerializes nothing (at 32 slots it does: the
    test below).  The owner and the five cross-attention layers
    read the ONE layer of pages through the paged-attention kernel (six
    calls: in a chunk for its last row only), a chunk's seven scans are
    the selective-scan kernel, and the donated pools, rings and states are
    updated where they lie: the result aliases all six, and no operation
    copies a whole page pool or a whole ring pool into another layout (ten
    heads of 128 on an axis of their own in a ring did: twelve copies of
    503 MB each way a decode step and 1.35 GB of temporaries, over the
    chip: PERF.md, PR 54)."""
    from mxnet_tpu.ops import mamba1, paged_attention

    compiled, shapes, notes = _phi4flash_program(one_chip, monkeypatch,
                                                 bucket)
    assert notes == dict({"paged_kernel_layers": 6}, **(
        {"sscan_kernel_layers": 7} if bucket else {}))
    text = compiled.as_text()
    assert len(_kernel_calls(text, mamba1.SCAN_KERNEL_NAME)) == (
        7 if bucket else 0)
    assert len(_kernel_calls(
        text, paged_attention.kernel_name(32, 128))) == 6
    memory = compiled.memory_analysis()
    held = 4 * sum(math.prod(shape) for shape in shapes.values())
    assert 1.82e9 < held < 1.84e9
    assert memory.alias_size_in_bytes >= held
    assert 13.91e9 < memory.argument_size_in_bytes < 13.93e9
    assert memory.temp_size_in_bytes < temporaries
    assert not _rematerialized(text)
    for shape in (shapes["k_pool"], shapes["kw_pool"]):
        copies, _ = _whole_pool_copies(text, shape)
        assert not copies, copies


def _rematerialized(text):
    """Shapes of the operations the compiler computes a second time to
    stay inside its memory limit (``<name>.remat``)."""
    return re.findall(r"%\S+\.remat\S* = (\w+\[[\d,]*\])", text)


def test_at_32_slots_the_state_updates_are_rematerialized(one_chip,
                                                          monkeypatch):
    """Why the cell has 24 slots (ISSUE 54's fallback).  At 32 slots and
    tables of 256 pages the arguments are 14.53 GB and the compiler stays
    inside its limit by computing three in-place updates of donated pools
    twice in every decode step: a ring's append, which written twice is
    written once, and the Mamba-1 state's recurrence and the convolution
    rows' shift of one layer, which are not (which of them goes wrong was
    not isolated on the chip).  What such executables served
    failed the cell's comparison on the chip on 8 seeds of 8 (mean gap
    3.3e-3 to 4.6e-3 where 3e-4 is sound, beside an int8 control that
    holds 9 GB less and read what it always reads); at tables of 192 pages
    (buckets 512 and 1024) nothing was rematerialized and 9 seeds of 9
    were sound (PERF.md, PR 54)."""
    compiled, shapes, _ = _phi4flash_program(one_chip, monkeypatch, 0,
                                             slots=32)
    assert 14.52e9 < compiled.memory_analysis().argument_size_in_bytes \
        < 14.54e9
    twice = _rematerialized(compiled.as_text())
    for name in ("ssm_state", "conv_state", "vw_pool"):
        assert "f32[%s]" % ",".join(map(str, shapes[name])) in twice, twice


def test_the_selective_scan_kernel_compiles_for_v5e(one_chip):
    """A chunk of 2 048 rows at d_inner 5120 and 16 states: Mosaic takes
    the kernel's aligned (8, 512) loads, the (16, 128) B and C tiles
    repeated across a channel tile and the state carried in VMEM across
    the row blocks."""
    from mxnet_tpu.ops import mamba1

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    t, di, n = 2048, 5120, 16
    compiled = _compile(mamba1._scan_kernel, sds(t, di), sds(t, di),
                        sds(n, di), sds(t, n), sds(t, n), sds(di),
                        sds(n, di))
    assert len(_kernel_calls(compiled.as_text(),
                             mamba1.SCAN_KERNEL_NAME)) == 1


# A grouped-query (or dense) attention layer's prefill half at the four
# cells' pool shapes and both buckets of each: the chunk's rows appended to
# the donated K and V pools at the slot's pages, ``kv_cache.read_context``
# of the slot's whole table, ``decode_attention`` over it in the block's
# key blocks with a horizon a row.  What is compiled is what the blocks'
# ``prefill_forward`` ran a layer under ``gqa_prefill`` until PR 51, and
# what their reader still runs where its kernel does not (``serve/model.py``
# with one query head a key/value head), without its weights, at a layer
# past the first (layer 0 starts at the pool's first byte: in a program
# this small its slice is a bitcast, whatever the reader).
PREFILL_CASES = {
    # name: (key/value heads, head width, query heads a key/value head,
    #        layers, pages a slot, slots, the cell's buckets)
    "dense": (16, 128, 1, 24, 48, 16, (128, 512)),
    "granite": (8, 64, 4, 4, 48, 16, (128, 512)),
    "laguna": (8, 128, 6, 2, LAGUNA_TABLE, LAGUNA_SLOTS, (512, 2048)),
    "lfm2": (8, 64, 4, 3, LFM2_TABLE, LFM2_SLOTS, (512, 2048)),
}


def _layer_first(pool, layer, tables, head_dim):
    """``kv_cache.read_context`` as it was until PR 49: the layer indexed
    first, the table second."""
    return pool[layer][tables].reshape(
        1, tables.shape[-1] * pool.shape[2], -1, head_dim
    ).transpose(0, 2, 1, 3)


# The same layer through the reader the blocks call since PR 51
# (``ops/attention.py:paged_prefill_attention``) as a TPU traces it: the
# prefill kernel where the call is eligible, the gather and the scan above
# where it is not.
READER_CASES = dict(PREFILL_CASES,
                    sdar=(4, 128, 8, 12, SDAR_TABLE, SDAR_SLOTS, (512, 2048)))


def _prefill_reader_lowered(one_chip, monkeypatch, name, bucket, by_reader,
                            exact=False, kv_quant=False, read=None):
    """-> the lowered append + read of one prefill chunk of ``bucket``
    rows at the cell's pools: through the reader as a TPU traces it
    (``by_reader``), or as the blocks wrote the gather and the scan out
    until PR 51, the gather through ``read`` (the cache's own
    ``read_context`` unless given); the pools' shape; the notes of the
    trace."""
    from mxnet_tpu.ops.attention import (decode_attention,
                                         paged_prefill_attention)
    from mxnet_tpu.serve import kv_cache, latent_moe
    from mxnet_tpu.serve import model as serve_model

    heads, head_dim, group, layers, max_pages, slots, _ = READER_CASES[name]
    page, layer = 16, 1
    block = (serve_model if name == "dense" else latent_moe).prefill_block(
        max_pages, page, exact)
    shape = kv_cache.kv_pool_shape(layers, slots * max_pages + 1, page,
                                   heads, head_dim)
    read = read or kv_cache.read_context
    if by_reader:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def chunk(pools, q, k, v, table_row, offset):
        pools = dict(pools)
        abs_pos = offset + jnp.arange(bucket, dtype=jnp.int32)
        pages, offsets = table_row[abs_pos // page], abs_pos % page
        quant = "int8" if kv_quant else ""
        kv_cache.append_rows(pools, "k", layer, pages, offsets, k, quant)
        kv_cache.append_rows(pools, "v", layer, pages, offsets, v, quant)
        with jax.named_scope("gqa_prefill"):
            return pools, attend(pools, q, table_row, abs_pos)

    def attend(pools, q, table_row, abs_pos):
        ks, vs = pools.get("k_scale"), pools.get("v_scale")
        if by_reader:
            return paged_prefill_attention(
                q, pools["k_pool"], pools["v_pool"], layer, table_row,
                abs_pos, page, block, mi=exact, k_scale=ks, v_scale=vs)
        ctx_k = read(pools["k_pool"], layer, table_row, head_dim)
        ctx_v = read(pools["v_pool"], layer, table_row, head_dim)
        if kv_quant:
            ks = ks[layer, table_row].reshape(1, max_pages * page)
            vs = vs[layer, table_row].reshape(1, max_pages * page)
        att = decode_attention(
            q.transpose(1, 0, 2, 3).reshape(1, heads, bucket * group,
                                            head_dim),
            ctx_k, ctx_v, jnp.repeat(abs_pos + 1, group)[None], block=block,
            mi=exact, k_scale=ks, v_scale=vs)
        return att.reshape(heads, bucket, group * head_dim).transpose(1, 0, 2)

    pool = sds(shape, jnp.int8 if kv_quant else jnp.float32)
    pools = {"k_pool": pool, "v_pool": pool}
    if kv_quant:
        pools.update(k_scale=sds(shape[:3]), v_scale=sds(shape[:3]))
    rows = sds((bucket, heads, head_dim))
    with jax.default_matmul_precision("default"), \
            serve_model.trace_notes() as notes:
        lowered = jax.jit(chunk, donate_argnums=0).lower(
            pools, sds((bucket, heads, group, head_dim)), rows, rows,
            sds((max_pages,), jnp.int32), sds((), jnp.int32))
    return lowered, shape, notes


def _prefill_layer_program(one_chip, name, bucket, read=None):
    """-> the compiled append + whole-table read + scan of one prefill
    chunk of ``bucket`` rows over two donated float32 pools at the cell's
    shape, through ``read``, and the pools' shape."""
    lowered, shape, _ = _prefill_reader_lowered(one_chip, None, name, bucket,
                                                False, read=read)
    return lowered.compile(), shape


@pytest.mark.parametrize("large", [False, True], ids=["small", "large"])
@pytest.mark.parametrize("name", sorted(PREFILL_CASES))
def test_a_prefill_chunk_gathers_its_table_from_the_pool_where_it_lies(
        one_chip, name, large):
    """``read_context`` gathers the slot's pages from the pool itself
    (``pool[layer, tables]``), so a prefill chunk's compiled text holds no
    result the size of a pool's layer in any type, no whole-pool copy, and
    less than a layer of temporaries (counted at the two bytes a value the
    compiler gave the slice); the donated pools are updated where they
    lie."""
    bucket = PREFILL_CASES[name][-1][large]
    compiled, shape = _prefill_layer_program(one_chip, name, bucket)
    text, memory = compiled.as_text(), compiled.memory_analysis()
    assert not _layer_slices(text, shape), _layer_slices(text, shape)
    assert not _whole_pool_copies(text, shape)[0]
    assert memory.alias_size_in_bytes >= 2 * 4 * math.prod(shape)
    assert memory.temp_size_in_bytes < 2 * math.prod(shape[1:])


@pytest.mark.parametrize("name", sorted(PREFILL_CASES))
def test_a_layer_sliced_in_front_of_a_prefills_gather_is_found(one_chip,
                                                               name):
    """The control of the test above, and what every prefill paid until
    PR 49: the same chunk with the layer indexed first and the table
    second holds a copy of each pool's layer in front of its gather, which
    the compiler writes in bfloat16 (the scan's matmuls round their
    operands so).  A copy the chip's 128 MiB of fast memory cannot hold
    (laguna's 436 MB, LFM2's 604 MB; the dense cell's and granite's 50 MB
    go there, ``S(1)``, with nothing else in this program to stream) is
    a copy's bytes of temporaries: K's is dead once its pages are
    gathered."""
    bucket = PREFILL_CASES[name][-1][1]
    compiled, shape = _prefill_layer_program(one_chip, name, bucket,
                                             read=_layer_first)
    found = _layer_slices(compiled.as_text(), shape)
    assert len(found) >= 2, found
    assert any("= bf16[" in line for line in found), found
    copy = 2 * math.prod(shape[1:])
    if copy > 128 << 20:
        assert compiled.memory_analysis().temp_size_in_bytes >= copy


@pytest.mark.parametrize("name, large, tile, pages", [
    ("dense", False, 128, 32), ("dense", True, 256, 32),
    ("sdar", False, 1024, 64), ("laguna", False, 512, 64),
    ("lfm2", True, 512, 64)],
    ids=["dense-128", "dense-512", "sdar-512", "laguna-512", "lfm2-2048"])
def test_a_prefill_chunk_reads_its_pages_through_the_kernel(
        one_chip, monkeypatch, name, large, tile, pages):
    """Where the call is eligible a chunk's attention is ONE kernel, named
    for the pools' layout, its tile of query rows and its key block, over
    the pools whole: the compiled text holds no loop, no slice of a pool's
    layer, no gathered context and next to no temporaries, and Mosaic
    takes the kernel's blocks within the ``vmem_limit_bytes`` it states
    (the compile is the check), under the chip's 128 MiB.  The whole
    prefill executables of laguna, LFM2 and SDAR above hold the other
    buckets of those cells."""
    from mxnet_tpu.ops import paged_attention

    heads, head_dim, group, _, max_pages, _, buckets = READER_CASES[name]
    bucket = buckets[large]
    lowered, shape, notes = _prefill_reader_lowered(
        one_chip, monkeypatch, name, bucket, by_reader=True)
    assert notes == _prefill_notes(1, tile, group, pages)
    folded = len(shape) == 4
    assert (tile, pages) == paged_attention.prefill_tiling(
        bucket * group, head_dim, folded, heads, 16, max_pages)
    (module,) = _mosaic_modules(lowered.as_text())
    kernel = paged_attention.prefill_kernel_name(
        tile, pages, head_dim if folded else 0)
    assert "module @%s " % kernel in module
    # the kernel keeps its loops over a block's pages and over the heads
    # rolled: one head's work on a block in two forms (masked or not), a
    # copy's start in three places and its wait in two, whatever the
    # pages a block and the heads (written out, a block of 64 pages was
    # 640 copies and sixteen heads 64 matmuls: seconds of a session's
    # start a bucket and megabytes of code a layer, PERF.md, PR 51)
    assert module.count("tpu.matmul") == 4
    assert module.count("tpu.enqueue_dma") == 6
    assert module.count("tpu.wait_dma") == 4
    compiled = lowered.compile()
    text, memory = compiled.as_text(), compiled.memory_analysis()
    assert memory.generated_code_size_in_bytes < 3 << 20
    assert text.count("tpu_custom_call") == 1
    assert not re.findall(r" while\(", text)
    _prefill_reads_by_kernel(text, shape, "", 1,
                             (1, heads, max_pages * 16, head_dim), tile,
                             folded_head=head_dim if folded else 0,
                             pages=pages)
    assert not _whole_pool_copies(text, shape)[0]
    assert memory.alias_size_in_bytes >= 2 * 4 * math.prod(shape)
    # the query's and the result's copies in the kernel's layout (a folded
    # pool's take a lane tile a head): a few times the query's size
    query = 4 * bucket * heads * group * 128
    assert memory.temp_size_in_bytes < 6 * query + (1 << 20)
    per = 128 // head_dim if folded else 1
    assert paged_attention._prefill_vmem_bytes(
        pages, math.prod(shape[2:]) * 4, heads // per, per * tile,
        pages * 16) < 64 << 20


@pytest.mark.parametrize("name, exact, kv_quant", [
    ("granite", False, False), ("lfm2", True, False), ("dense", True, False),
    ("dense", False, True), ("laguna", True, False)],
    ids=["granite", "lfm2-exact", "dense-exact", "dense-kv_int8",
         "laguna-exact"])
def test_a_refused_prefill_lowers_to_the_gather_and_the_scan_letter_for_letter(
        one_chip, monkeypatch, name, exact, kv_quant):
    """What keeps the scan on a TPU: granite-4.0-h-micro's folded pools
    under a table of 768 keys, and every call under ``exact`` or
    ``kv_quant``.  Through the reader such a chunk lowers to the text it
    lowered to when the blocks wrote the gather and the scan out
    themselves, letter for letter, so the executable is the one the
    compile cache holds; it notes no kernel, and the scan's trip count is
    still data (PR 43)."""
    bucket = READER_CASES[name][-1][1]
    lowered, _, notes = _prefill_reader_lowered(
        one_chip, monkeypatch, name, bucket, True, exact, kv_quant)
    was, _, _ = _prefill_reader_lowered(
        one_chip, monkeypatch, name, bucket, False, exact, kv_quant)
    assert notes == {}
    text = lowered.as_text()
    assert text == was.as_text()
    assert "tpu_custom_call" not in text and "stablehlo.while" in text
    if name == "granite":
        compiled = lowered.compile().as_text()
        (cond,) = _loop_conditions(compiled, "gqa_prefill")
        assert "constant(" not in cond, cond


def test_the_latent_blocks_prefill_keeps_calling_the_scan():
    """kanana-2's and Ling-3.0-flash's prefill read a latent context, not
    K/V pages: ``latent_moe.py`` calls ``decode_attention`` itself and
    knows nothing of the reader, so its executables cannot have moved."""
    import inspect

    from mxnet_tpu.serve import bailing_hybrid, latent_moe

    assert "decode_attention(" in inspect.getsource(latent_moe)
    for module in (latent_moe, bailing_hybrid):
        assert "paged_prefill_attention" not in inspect.getsource(module)


# ``qwen3next-l8-longdoc``'s executables at Qwen3-Next-80B-A3B-Instruct's
# published widths over the cell's cache: 8 layers (published 0-7: three
# Gated DeltaNet layers, one gated attention layer, twice), experts 0-63
# of 512 held, 1/8 of the vocabulary, an untied head; 32 slots x 1088 pages
# of 16 + the trash page in two layers' K/V pools, 2 heads of 256 folded
# into 512 lanes (2.28 GB each); six layers' state as (32, 128, 128) and
# (3, 8192) a slot.  What is compiled is ``qwen3_next.decode_step`` /
# ``qwen3_next.prefill_forward`` with the TPU's branches taken.
Q3N_SLOTS, Q3N_TABLE = 32, (16384 + 1024) // 16


def _qwen3next_program(one_chip, monkeypatch, bucket):
    """-> the compiled decode step (``bucket`` 0) or prefill chunk of
    ``bucket`` rows, the cache's pool shapes, and the notes of the trace."""
    from mxnet_tpu import serve
    from mxnet_tpu.serve import kv_cache, qwen3_next
    from mxnet_tpu.serve import model as serve_model

    cfg = serve.ModelConfig(
        block="qwen3_next", vocab_size=18992, num_layers=8, d_model=2048,
        num_heads=16, num_key_value_heads=2, max_len=262144,
        attn_head_dim=256, partial_rotary_factor=0.25, rope_theta=1e7,
        rms_norm_eps=1e-6,
        layer_types=(("linear_attention",) * 3 + ("full_attention",)) * 2,
        linear_num_key_heads=16, linear_num_value_heads=32,
        linear_key_head_dim=128, linear_value_head_dim=128,
        linear_conv_kernel_dim=4, gdn_chunk_size=64, moe_d_ff=512,
        n_routed_experts=512, num_experts_per_tok=10, n_shared_experts=1,
        shared_expert_gate=True, scoring_func="softmax",
        experts_held=(0, 64)).validate()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    i32 = jnp.int32
    params = {k: sds(v) for k, v in qwen3_next.param_shapes(cfg).items()}
    assert abs(sum(math.prod(v.shape) for v in params.values())
               - 1978.8e6) < 1e5           # 7.92 GB in float32
    page = 16
    shapes = {"k_pool": kv_cache.kv_pool_shape(
        2, Q3N_SLOTS * Q3N_TABLE + 1, page, 2, 256)}
    shapes["v_pool"] = shapes["k_pool"]
    assert shapes["k_pool"][-1] == 512 and len(shapes["k_pool"]) == 4
    for name, (layers, shape, _) in qwen3_next.state_shapes(cfg).items():
        shapes[name] = (layers, Q3N_SLOTS) + tuple(shape)
    pools = {name: sds(shape) for name, shape in shapes.items()}
    counters = {name: sds(leaf.shape, i32) for name, leaf
                in qwen3_next.init_counters(cfg).items()}
    static = dict(cfg=cfg, page_size=page, exact=False, kv_quant="")
    if bucket:
        def step(params, tokens, length, offset, table_row, pools, counters,
                 slot):
            return qwen3_next.prefill_forward(
                params, tokens, length, offset, table_row, pools, counters,
                slot=slot, **static)

        avals = (params, sds((1, bucket), i32), sds((), i32), sds((), i32),
                 sds((Q3N_TABLE,), i32), pools, counters, sds((), i32))
        donate = (5, 6)
    else:
        def step(params, tokens, lengths, tables, pools, counters):
            return qwen3_next.decode_step(params, tokens, lengths, tables,
                                          pools, counters, **static)

        avals = (params, sds((Q3N_SLOTS,), i32), sds((Q3N_SLOTS,), i32),
                 sds((Q3N_SLOTS, Q3N_TABLE), i32), pools, counters)
        donate = (4, 5)
    with jax.default_matmul_precision("default"), \
            serve_model.trace_notes() as notes:
        lowered = jax.jit(step, donate_argnums=donate).lower(*avals)
    return lowered.compile(
        compiler_options=qwen3_next.compiler_options("tpu")), shapes, notes


@pytest.mark.parametrize("bucket, tile, temporaries", [
    (0, 8, 1 << 28), (512, 16, 5 << 25), (2048, 64, 21 << 25)],
    ids=["decode", "prefill-512", "prefill-2048"])
def test_qwen3next_executables_compile_for_v5e_at_the_published_widths(
        one_chip, monkeypatch, bucket, tile, temporaries):
    """The whole step fits the chip beside its arguments (12.9 GB: 7.92 of
    weights, 4.56 of pages, 0.42 of state, under the 13.6 GB past which
    PR 54 found state updates computed twice), Mosaic takes a whole float32
    expert of 512 x 2048 a block in all eight expert layers, and the
    donated pools and the states are updated where they lie: the result
    aliases all four, no operation copies a whole K/V pool into another
    layout, and a DeltaNet state's update is applied once and never
    rematerialized (ROADMAP M4 (f)).  The two attention layers read their
    folded pools of 2 heads of 256 through the paged kernels' form for a head of two lane
    tiles (one lowering, two calls): no loop under ``gattn_decode`` or
    ``gattn_prefill``, no slice of a pool's layer, no gathered context.
    Since PR 57 a chunk's expert layers lay out 9 280 rows where every
    assignment could fill 24 576 (2 320 for 6 144 at bucket 512): 667.1
    and 137.5 MB of temporaries where there were 723.8 and 206.4 (the
    DeltaNet scan sets the peak of a 2 048-row chunk), and no array of the
    worst case's rows is left in a chunk's text."""
    from mxnet_tpu.ops.grouped_matmul import kernel_name

    compiled, shapes, notes = _qwen3next_program(one_chip, monkeypatch,
                                                 bucket)
    assert notes == dict({"expert_kernel_layers": 8}, **(
        _prefill_notes(2, min(1024, bucket * 8), 8) if bucket
        else {"paged_kernel_layers": 2}))
    text = compiled.as_text()
    assert len(_kernel_calls(text, kernel_name(tile))) == 8
    memory = compiled.memory_analysis()
    held = 4 * sum(math.prod(shape) for shape in shapes.values())
    assert 4.97e9 < held < 4.99e9
    assert memory.alias_size_in_bytes >= held
    assert 12.89e9 < memory.argument_size_in_bytes < 12.92e9
    assert memory.temp_size_in_bytes < temporaries
    if bucket:
        assert not _worst_case_rows(text, bucket, 10, 512, 64, 2048)
    # what the compiler computes a second time (a chunk's activations;
    # layer 0's two appends of a decode step are clones whose originals are
    # gone) is no update of a state pool, and each is applied once
    state = "f32[%s]" % ",".join(map(str, shapes["gdn_state"]))
    assert state not in _rematerialized(text)
    assert len(re.findall(
        r"= %s\S* dynamic-update-slice\(" % re.escape(state), text)) == 6
    copies, _ = _whole_pool_copies(text, shapes["k_pool"])
    assert not copies, copies
    if not bucket:
        pool = "f32[%s]" % ",".join(map(str, shapes["k_pool"]))
        assert len(re.findall(
            r"\n\s*%%\S+ = %s\S* fusion\(" % re.escape(pool),
            text)) == 4          # K's and V's append, in two layers
        _decode_reads_by_kernel(text, shapes["k_pool"], "gattn_decode", 2,
                                folded_head=256)
    else:
        _prefill_reads_by_kernel(text, shapes["k_pool"], "gattn_prefill", 2,
                                 (1, 2, Q3N_TABLE * 16, 256),
                                 min(1024, bucket * 8), folded_head=256)
