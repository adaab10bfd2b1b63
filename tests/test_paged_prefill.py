"""A prefill chunk's attention reads the slot's K/V pages where they lie
(``ops/attention.py:paged_prefill_attention``).  On every backend but a
TPU, and for every call the kernel refuses, that is what the blocks'
``prefill_forward`` did before there was a reader: ``read_context``'s
gather of the slot's whole table and ``decode_attention``'s bounded scan
over it, letter for letter.  On a TPU the eligible calls are one Pallas
kernel (``ops/paged_attention.py:paged_prefill``) in which each tile of
query rows walks the key blocks up to its own furthest horizon: here it
runs in Pallas's TPU interpreter against the scan, which stays as the
fallback and the oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.experimental.pallas import tpu as pltpu

from mxnet_tpu.ops import attention, paged_attention
from mxnet_tpu.ops.attention import decode_attention, paged_prefill_attention
from mxnet_tpu.serve import kv_cache
from mxnet_tpu.serve import model as serve_model

from closeness import assert_close_across_executables

PAGE, MAX_PAGES, LAYERS, LAYER, TOKENS = 4, 10, 2, 1, 8
CAP = PAGE * MAX_PAGES            # 40 rows: five chunks of 8 tokens
POOL_PAGES = 2 * MAX_PAGES + 1    # another slot's pages, and the trash page
KD = 128

# the two layouts the cache gives pools at rest -> (key/value heads, head
# width): heads of 128 on an axis of their own (dense chat, laguna), heads
# of 64 folded two a lane tile (LFM2), heads of 128 folded one a tile
# (SDAR), heads of 256 folded two tiles a head (Qwen3-Next)
LAYOUTS = {"heads": (2, KD), "folded": (4, 64), "folded128": (2, KD),
           "folded256": (2, 2 * KD)}
# (layout, query heads that share a key/value head): one (dense chat),
# four (LFM2), six (laguna), eight (SDAR)
FORMS = [("heads", 1), ("heads", 6), ("folded", 4), ("folded", 1),
         ("folded128", 8), ("folded256", 8)]
FORM_IDS = ["%s-G%d" % form for form in FORMS]


def _causal(offset, tokens=TOKENS):
    return offset + np.arange(tokens) + 1


def _block_causal(offset, tokens=TOKENS, block=4):
    """A diffusion block's rows see each other (``serve/sdar_moe.py``)."""
    return ((offset + np.arange(tokens)) // block + 1) * block


# name -> the key rows each of the chunk's tokens sees
HORIZONS = {
    "offset_0": _causal(0),
    "mid_table": _causal(16),
    "mid_page": _causal(18),            # not what a session sends; data
    "last_chunk": _causal(CAP - TOKENS),
    "clipped_at_the_table": _causal(CAP - 3),     # 38 .. 45 of 40 rows
    "block_causal": _block_causal(16),
    "block_causal_offset_0": _block_causal(0),
    # no causality is assumed: any horizon a row, in any order
    "any_order": np.array([9, 1, 33, 4, 17, 17, 2, 40]),
    "nothing_for_one_token": np.array([5, 0, 7, 8, 9, 10, 11, 12]),
}


def _case(rs, layout, group, horizons, live=None):
    """Pools in ``layout`` whose every row is finite garbage where no
    query of the chunk can see it (the table's rows past the furthest
    horizon, or past ``live`` if given; the other slot's pages, the trash
    page, the other layer): 1e3, so that one such row reaching a result
    moves it by far more than any tolerance.  -> q (T, H, G, D), the two
    pools, the slot's table."""
    heads, d = LAYOUTS[layout]
    shape = (LAYERS, POOL_PAGES, PAGE, heads, d)
    k, v = (rs.randn(*shape).astype(np.float32) for _ in "kv")
    table = rs.permutation(POOL_PAGES - 1)[:MAX_PAGES].astype(np.int32)
    seen = np.zeros(shape[:3], bool)
    top = min(int(np.max(horizons)) if live is None else live, CAP)
    for pos in range(top):
        seen[LAYER, table[pos // PAGE], pos % PAGE] = True
    k[~seen], v[~seen] = 1e3, -1e3
    q = jnp.asarray(rs.randn(len(horizons), heads, group, d)
                    .astype(np.float32))
    # two heads of 128 are no whole sublane tile and the cache would fold
    # them; the kernel's form for heads on their own axis takes any count
    at_rest = shape if layout == "heads" else kv_cache.kv_pool_shape(*shape)
    assert len(at_rest) == (5 if layout == "heads" else 4)
    return q, jnp.asarray(k.reshape(at_rest)), jnp.asarray(
        v.reshape(at_rest)), jnp.asarray(table)


def _rows(q):
    """q (T, H, G, D) -> (H, T x G, D): a key/value head's query heads as
    its rows, row ``t x G + g``."""
    t, heads, group, d = q.shape
    return q.transpose(1, 0, 2, 3).reshape(heads, t * group, d)


def _by_scan(q, k, v, table, horizons, block=PAGE, **kw):
    """What every block's ``prefill_forward`` did: gather the slot's whole
    table, scan it to the chunk's furthest horizon."""
    d, group = q.shape[-1], q.shape[2]
    ctx_k = kv_cache.read_context(k, LAYER, table, d)
    ctx_v = kv_cache.read_context(v, LAYER, table, d)
    return decode_attention(
        _rows(q)[None], ctx_k, ctx_v,
        jnp.repeat(jnp.asarray(horizons, jnp.int32), group)[None],
        block=block, **kw)[0]


def _by_kernel(q, k, v, table, horizons, tile=8, pages=2, **more):
    """The kernel as the chip would run it but for the interpreter, whose
    memory starts as NaN: what was never copied must not be read."""
    with pltpu.force_tpu_interpret_mode():
        return paged_attention.paged_prefill(
            _rows(q), k, v, LAYER, table,
            jnp.repeat(jnp.asarray(horizons, jnp.int32), q.shape[2]), PAGE,
            more.pop("scale", q.shape[-1] ** -0.5), tile=tile, pages=pages)


@pytest.mark.parametrize("layout, group", FORMS, ids=FORM_IDS)
@pytest.mark.parametrize("case", sorted(HORIZONS))
def test_kernel_equals_the_scan(case, layout, group):
    """Tiles of 8 query rows and key blocks of two pages (so a chunk is
    one to eight tiles, a tile walks one to five blocks and the next
    tile's first block is fetched behind the last): the scan's result to
    the tolerance of two executables of one computation, for a chunk at
    offset 0, in the middle of the table and at its end, causal horizons
    and a diffusion block's, horizons past the table's last row, in any
    order, and 0 (nothing to see: 0, as the scan gives); one query head
    a key/value head, or a group's four, six or eight as its rows; pools
    that keep their heads' axis and folded ones (a head of 64's rows are
    its half of a lane tile: its neighbour's keys, values and queries
    reach no result).  Nothing past a row's horizon reaches it: such
    rows hold 1e3."""
    rs = np.random.RandomState(21)
    horizons = HORIZONS[case]
    q, k, v, table = _case(rs, layout, group, horizons)
    want = _by_scan(q, k, v, table, horizons)
    got = _by_kernel(q, k, v, table, horizons)
    assert got.shape == want.shape == _rows(q).shape
    assert got.dtype == q.dtype
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(want)).max() < 10     # no garbage in the oracle
    assert_close_across_executables(got, want)


@pytest.mark.parametrize("layout, group", [("heads", 6), ("folded", 4)],
                         ids=["heads-G6", "folded-G4"])
def test_a_buckets_padding_rows_are_finite_and_move_no_real_row(layout,
                                                                group):
    """A bucket of 8 tokens that holds 5: the padding tokens' rows carry
    the horizons their positions give them and see whatever the chunk left
    at those rows (here 1e3), and what they give nobody reads; the five
    real tokens' results are the scan's and hold none of it."""
    rs = np.random.RandomState(22)
    horizons = _causal(16)
    q, k, v, table = _case(rs, layout, group, horizons, live=16 + 5)
    want = np.asarray(_by_scan(q, k, v, table, horizons))
    got = np.asarray(_by_kernel(q, k, v, table, horizons))
    assert np.isfinite(got).all()
    real = 5 * group
    assert np.abs(want[:, :real]).max() < 10 < np.abs(want[:, real:]).max()
    assert_close_across_executables(got[:, :real], want[:, :real])


@pytest.mark.parametrize("layout, group", [("heads", 6), ("folded", 4)],
                         ids=["heads-G6", "folded-G4"])
@pytest.mark.parametrize("tile, pages", [
    (8, 1), (16, 3), (24, 2), (48, MAX_PAGES), (1024, 64)],
    ids=["r8-p1", "r16-p3", "r24-p2", "r48-whole_table", "beyond_both"])
def test_tile_and_block_sizes_do_not_change_the_result(tile, pages, layout,
                                                       group):
    """One page a key block, three (the table's ten columns completed with
    two that name the trash page), the whole table in one or more than it
    holds; tiles of 8, 16 or 24 query rows (24 does not divide folded-G4's
    32: the last tile is completed with rows that see what the last row
    sees), the whole chunk, or more than it has."""
    rs = np.random.RandomState(23)
    horizons = HORIZONS["mid_page"]
    q, k, v, table = _case(rs, layout, group, horizons)
    want = _by_scan(q, k, v, table, horizons)
    rows = TOKENS * group
    tile = min(tile, -(-rows // 8) * 8)
    pages = min(pages, MAX_PAGES)
    assert_close_across_executables(
        _by_kernel(q, k, v, table, horizons, tile=tile, pages=pages), want)


def test_the_tiling_shares_a_chunk_out_in_whole_sublane_tiles():
    """``prefill_tiling``: the left operand holds at most 1 024 rows of
    the heads of one lane tile and a key block 1 024 keys, both fewer
    under more than four groups (heads of 128, or a folded pool's lane
    tiles: the VMEM their blocks take together); the chunk's rows a head
    are shared out evenly over the fewest grid steps."""
    tiling = paged_attention.prefill_tiling
    assert paged_attention._PREFILL_TILE_ROWS == 1024
    assert paged_attention._PREFILL_KEYS_PER_BLOCK == 1024
    # LFM2: 2048 tokens x 4 query heads, 8 heads of 64 in four lane tiles
    assert tiling(8192, 64, True, 8, 16, 576) == (512, 64)
    # SDAR: 2048 x 8, 4 heads of 128 in four lane tiles
    assert tiling(16384, 128, True, 4, 16, 256) == (1024, 64)
    # Qwen3-Next: 2048 x 8, 2 heads of 256, each two of the four lane tiles
    assert tiling(16384, 256, True, 2, 16, 1088) == (1024, 64)
    assert paged_attention.prefill_kernel_name(1024, 64, 256) \
        == "paged_prefill_attention_f256_r1024_p64"
    # laguna: 2048 x 6, 8 heads of 128 on their own axis: half the rows
    assert tiling(12288, 128, False, 8, 16, 832) == (512, 64)
    # dense chat: 512 and 128 rows a head, 16 heads, a table of 48 pages:
    # a quarter of the rows and half the keys
    assert tiling(512, 128, False, 16, 16, 48) == (256, 32)
    assert tiling(128, 128, False, 16, 16, 48) == (128, 32)
    # the table's pages where it has fewer than a block
    assert tiling(512, 128, False, 8, 16, 48) == (512, 48)
    # rows no multiple of a tile are shared out evenly: 3 x 1000 for 3000
    assert tiling(3000, 128, False, 4, 16, 832) == (1000, 64)
    assert tiling(20, 128, False, 4, 16, 8) == (24, 8)
    assert paged_attention.prefill_kernel_name(512, 64, 64) \
        == "paged_prefill_attention_f64_r512_p64"
    assert paged_attention.prefill_kernel_name(512, 64, 0) \
        == "paged_prefill_attention_r512_p64"


@pytest.mark.parametrize("layout, group", [("heads", 6), ("folded", 4)],
                         ids=["heads-G6", "folded-G4"])
def test_kernel_takes_a_scale_and_a_bfloat16_query(layout, group):
    """``scale`` multiplies the scores; a bfloat16 query gets a bfloat16
    answer."""
    rs = np.random.RandomState(24)
    horizons = HORIZONS["mid_table"]
    q, k, v, table = _case(rs, layout, group, horizons)
    assert_close_across_executables(
        _by_kernel(q, k, v, table, horizons, scale=0.2),
        _by_scan(q, k, v, table, horizons, scale=0.2))
    half = _by_kernel(q.astype(jnp.bfloat16), k, v, table, horizons)
    assert half.dtype == jnp.bfloat16
    assert_close_across_executables(
        half, _by_scan(q.astype(jnp.bfloat16), k, v, table, horizons),
        limit=2, dtype="bfloat16")


@pytest.mark.parametrize("layout, group", [("heads", 6), ("folded", 4)],
                         ids=["heads-G6", "folded-G4"])
@pytest.mark.parametrize("pool", ["k", "v"])
def test_kernel_sees_a_planted_fault_in_a_row_inside_a_horizon(pool, layout,
                                                               group):
    """The control: two rows of one pool's page swapped, at positions 22
    and 23 under horizons 17 .. 24, move the last two tokens' results
    (token 6 sees what row 23 held; token 7 sees both rows, each now
    beside the other's value or key) and no other's."""
    rs = np.random.RandomState(25)
    horizons = HORIZONS["mid_table"]
    q, k, v, table = _case(rs, layout, group, horizons)
    sound = np.asarray(_by_kernel(q, k, v, table, horizons))
    arr = np.array(k if pool == "k" else v)
    page = int(table[22 // PAGE])
    arr[LAYER, page, [2, 3]] = arr[LAYER, page, [3, 2]]
    kk, vv = (jnp.asarray(arr), v) if pool == "k" else (k, jnp.asarray(arr))
    faulty = np.asarray(_by_kernel(q, kk, vv, table, horizons))
    moved = np.abs(faulty - sound).reshape(sound.shape[0], TOKENS, -1).max(
        axis=(0, 2))
    assert moved[6] > 1e-3 and moved[7] > 1e-3
    np.testing.assert_array_equal(moved[:6], 0)


@pytest.mark.parametrize("layout, group", [("heads", 6), ("folded", 4)],
                         ids=["heads-G6", "folded-G4"])
def test_kernel_rounds_its_operands_as_the_chip_does_at_default_precision(
        layout, group):
    """What runs on the chip: at the default matmul precision the
    operands are rounded to bfloat16 where they are read and the sums are
    float32, as XLA's einsum does there with the scan's.  The CPU's scan
    multiplies in float32, so the two differ by the roundings, and by no
    more: the limit is the decode kernel's, 4 eps of bfloat16 (1.6 % of
    the result's largest magnitude)."""
    rs = np.random.RandomState(26)
    horizons = HORIZONS["mid_page"]
    q, k, v, table = _case(rs, layout, group, horizons)
    want = np.asarray(_by_scan(q, k, v, table, horizons))
    with jax.default_matmul_precision("default"):
        got = np.asarray(_by_kernel(q, k, v, table, horizons))
    gap = np.abs(got - want).max() / np.abs(want).max()
    assert 1e-5 < gap < 4 * 2.0 ** -8, gap


# ---------------------------------------------------------------------------
# the reader: which calls take the kernel, and what the others lower to
# ---------------------------------------------------------------------------

def _parents(q, k, v, table, positions, block, mi=False, ks=None, vs=None,
             scale=None, horizons=None):
    """A full-attention layer's prefill read as the four grouped-query
    blocks wrote it out before there was a reader, in their order: the two
    gathers, the quantized pages' scales, the query heads as rows, the
    horizons repeated over them, the scan, the heads side by side."""
    t, heads, group, d = q.shape
    ctx_k = kv_cache.read_context(k, LAYER, table, d)
    ctx_v = kv_cache.read_context(v, LAYER, table, d)
    if ks is not None:
        ks = ks[LAYER, table].reshape(1, MAX_PAGES * PAGE)
        vs = vs[LAYER, table].reshape(1, MAX_PAGES * PAGE)
    att = decode_attention(
        q.transpose(1, 0, 2, 3).reshape(1, heads, t * group, d),
        ctx_k, ctx_v,
        jnp.repeat(positions + 1 if horizons is None else horizons,
                   group)[None],
        scale=scale, block=block, mi=mi, k_scale=ks, v_scale=vs)
    return att.reshape(heads, t, group * d).transpose(1, 0, 2)


def _reader(q, k, v, table, positions, block, mi=False, ks=None, vs=None,
            scale=None, horizons=None):
    return paged_prefill_attention(
        q, k, v, LAYER, table, positions, PAGE, block, mi=mi, k_scale=ks,
        v_scale=vs, scale=scale, horizons=horizons)


def _text(fn, *args, **static):
    # a function of its own each time: a trace is cached by the function
    return jax.jit(lambda *a: fn(*a, **static)).lower(*args).as_text()


def test_only_an_eligible_call_on_a_tpu_takes_the_kernel(monkeypatch):
    """``paged_prefill_eligible`` is read off the call while it is traced,
    by the decode kernel's rules: the backend is a TPU, ``mi`` is not
    asked, the pages carry no scales, the pools are float32 and either
    keep their heads' axis in whole sublane tiles of whole lane tiles, or
    fold heads that divide a lane tile into a last axis of whole lane
    tiles under a table of at least 2 048 keys.  Every other call lowers
    to ``read_context`` + ``decode_attention`` as the blocks wrote them
    out, letter for letter, on the CPU and on a TPU."""
    rs = np.random.RandomState(27)
    shape = (LAYERS, POOL_PAGES, PAGE, 8, KD)
    k = v = jnp.asarray(rs.randn(*shape).astype(np.float32))
    q = jnp.asarray(rs.randn(TOKENS, 8, 6, KD).astype(np.float32))
    scales = jnp.ones(shape[:3], jnp.float32)
    positions = jnp.asarray(16 + np.arange(TOKENS), jnp.int32)
    seen = jnp.asarray(_block_causal(16), jnp.int32)
    toy = jnp.asarray(rs.permutation(POOL_PAGES - 1)[:MAX_PAGES]
                      .astype(np.int32))
    # the same pages under a table as wide as the rule for folded pools
    # asks (512 pages of 4 rows), and under one a page short of it
    wide = jnp.concatenate(
        [toy, jnp.full((512 - MAX_PAGES,), POOL_PAGES - 1, jnp.int32)])
    assert wide.shape[0] * PAGE == paged_attention._FOLDED_MIN_TABLE_KEYS

    def folded(pool, heads, d):
        pool = pool[:, :, :, :heads, :d].reshape(shape[:3] + (heads * d,))
        return q[:, :heads, :, :d], pool, pool

    refused = {
        # name: (q, K pool, V pool, table, mi, K scales, V scales)
        "mi": (q, k, v, toy, True, None, None),
        "scales": (q, k.astype(jnp.int8), v.astype(jnp.int8), toy, False,
                   scales, scales),
        "folded_under_a_short_table": folded(k, 8, 64) + (
            wide[:-1], False, None, None),
        "folded_into_part_of_a_tile": folded(k, 3, 64) + (
            wide, False, None, None),
        "folded_bfloat16_pools": folded(k.astype(jnp.bfloat16), 8, 64) + (
            wide, False, None, None),
        "heads_of_part_of_a_tile": (q[:, :2], k[:, :, :, :2], v[:, :, :, :2],
                                    toy, False, None, None),
        "heads_of_two_lane_tiles": (jnp.tile(q, 2), jnp.tile(k, 2),
                                    jnp.tile(v, 2), toy, False, None, None),
    }
    accepted = {
        # name: (q, K pool, V pool, table, the kernel's name: 48 rows a
        #        head in one step, the toy table's ten pages under a
        #        block; the wide one's 512 in two blocks of 256 pages)
        "heads": (q, k, v, toy, "paged_prefill_attention_r48_p10"),
        "bfloat16_query": (q.astype(jnp.bfloat16), k, v, toy,
                           "paged_prefill_attention_r48_p10"),
        "folded_heads_of_64": folded(k, 8, 64) + (
            wide, "paged_prefill_attention_f64_r48_p256"),
        "folded_heads_of_32": folded(k, 8, 32) + (
            wide, "paged_prefill_attention_f32_r48_p256"),
    }

    def args(case, horizons):
        q_, k_, v_, table, mi, ks, vs = case
        return (q_, k_, v_, table, positions), dict(
            block=PAGE, mi=mi, ks=ks, vs=vs, horizons=horizons,
            scale=None if horizons is None else 0.2)

    texts = {}
    for backend in ("cpu", "tpu"):
        if backend == "tpu":
            monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        for name, case in refused.items():
            for horizons in (None, seen):
                arrays, static = args(case, horizons)
                got = _text(_reader, *arrays, **static)
                assert got == _text(_parents, *arrays, **static), name
                assert "stablehlo.while" in got, name
                assert "tpu_custom_call" not in got, name
                texts.setdefault((name, horizons is None), got)
                assert got == texts[name, horizons is None]
    eligible = paged_attention.paged_prefill_eligible
    for name, (q_, k_, v_, table, mi, ks, vs) in refused.items():
        assert not eligible(q_, k_, v_, mi, ks, vs, table.shape[0] * PAGE)
    # the eligible calls: traced only, this backend cannot lower the kernel
    for name, (q_, k_, v_, table, kernel) in accepted.items():
        assert eligible(q_, k_, v_, False, None, None,
                        table.shape[0] * PAGE), name
        with serve_model.trace_notes() as notes:
            traced = jax.make_jaxpr(
                lambda *a: _reader(*a, block=PAGE, horizons=seen))(
                    q_, k_, v_, table, positions)
        rows, pages = 48, min(1024 // PAGE, table.shape[0])
        assert notes == {"prefill_kernel_layers": 1,
                         "prefill_kernel_tile_rows": rows,
                         "prefill_kernel_query_heads": 6,
                         "prefill_kernel_block_keys": pages * PAGE}, name
        assert (rows, pages) == paged_attention.prefill_tiling(
            TOKENS * 6, q_.shape[-1], k_.ndim == 4, 8, PAGE, table.shape[0])
        assert kernel == paged_attention.prefill_kernel_name(
            rows, pages, q_.shape[-1] if k_.ndim == 4 else 0)
        assert "name=%s\n" % kernel in str(traced), name
        # the kernel in a jitted body of its own: no gathered context and
        # no scan beside it
        steps = [eqn.primitive.name for eqn in traced.jaxpr.eqns]
        assert str(traced).count("pallas_call") == 1, name
        assert "while" not in steps and "gather" not in steps, name
    monkeypatch.undo()
    for q_, k_, v_, table, _ in accepted.values():           # the CPU
        assert not eligible(q_, k_, v_, False, None, None,
                            table.shape[0] * PAGE)


@pytest.mark.parametrize("case", ["mid_table", "block_causal",
                                  "clipped_at_the_table"])
def test_the_reader_hands_the_kernel_what_the_scan_gets(monkeypatch, case):
    """Through the reader as a TPU traces it (eight key/value heads of 128
    on their own axis: an eligible call), in the interpreter: each token's
    results a key/value head with its query heads side by side, as the
    scan's path of the same reader gives them on the CPU; positions alone
    mean causal horizons, ``horizons`` anything else."""
    rs = np.random.RandomState(28)
    shape = (LAYERS, POOL_PAGES, PAGE, 8, KD)
    k, v = (jnp.asarray(rs.randn(*shape).astype(np.float32)) for _ in "kv")
    q = jnp.asarray(rs.randn(TOKENS, 8, 2, KD).astype(np.float32))
    table = jnp.asarray(rs.permutation(POOL_PAGES - 1)[:MAX_PAGES]
                        .astype(np.int32))
    horizons = jnp.asarray(HORIZONS[case], jnp.int32)
    positions = horizons - 1
    given = None if case == "mid_table" else horizons
    want = _reader(q, k, v, table, positions, PAGE, horizons=given)
    assert want.shape == (TOKENS, 8, 2 * KD)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pltpu.force_tpu_interpret_mode(), \
            serve_model.trace_notes() as notes:
        got = _reader(q, k, v, table, positions, PAGE, horizons=given)
    assert notes["prefill_kernel_layers"] == 1
    assert_close_across_executables(got, want)


def test_a_chunks_layers_share_one_trace_of_the_kernel(monkeypatch):
    """The layer's number is data to the kernel's jitted body, so two
    layers of one chunk are two calls of ONE traced body (lowered once),
    as the decode kernel's are."""
    rs = np.random.RandomState(29)
    shape = (LAYERS, POOL_PAGES, PAGE, 8, KD)
    k = v = jnp.asarray(rs.randn(*shape).astype(np.float32))
    q = jnp.asarray(rs.randn(TOKENS, 8, 1, KD).astype(np.float32))
    table = jnp.arange(MAX_PAGES, dtype=jnp.int32)
    positions = jnp.arange(TOKENS, dtype=jnp.int32)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def two_layers(q, k, v, table, positions):
        return sum(paged_prefill_attention(q, k, v, layer, table, positions,
                                           PAGE, PAGE)
                   for layer in range(LAYERS))

    with serve_model.trace_notes() as notes:
        traced = jax.make_jaxpr(two_layers)(q, k, v, table, positions)
    assert notes["prefill_kernel_layers"] == 2
    bodies = [eqn.params["jaxpr"] for eqn in traced.jaxpr.eqns
              if eqn.params.get("name") == "_paged_prefill"]
    assert len(bodies) == LAYERS == 2 and bodies[0] is bodies[1]
    assert str(bodies[0]).count("pallas_call") == 1


def test_the_reader_refuses_nothing_the_scan_took():
    """On the CPU the reader is the scan whatever the call: ``mi``,
    quantized pages with their scale pools, a block of several pages."""
    from mxnet_tpu import quantize

    rs = np.random.RandomState(30)
    horizons = HORIZONS["mid_page"]
    q, k, v, table = _case(rs, "folded", 4, horizons)
    positions = jnp.asarray(horizons - 1, jnp.int32)
    for mi in (True, False):
        np.testing.assert_array_equal(
            np.asarray(_reader(q, k, v, table, positions, 2 * PAGE, mi=mi)),
            np.asarray(_parents(q, k, v, table, positions, 2 * PAGE, mi=mi)))
    heads, d = LAYOUTS["folded"]
    k8, ks = quantize.kv_quantize_rows(
        k.reshape(k.shape[:3] + (heads, d)), "int8")
    v8, vs = quantize.kv_quantize_rows(
        v.reshape(v.shape[:3] + (heads, d)), "int8")
    k8, v8 = (x.reshape(k.shape) for x in (k8, v8))
    np.testing.assert_array_equal(
        np.asarray(_reader(q, k8, v8, table, positions, PAGE, ks=ks, vs=vs)),
        np.asarray(_parents(q, k8, v8, table, positions, PAGE, ks=ks,
                            vs=vs)))
    assert attention.paged_prefill_attention is paged_prefill_attention
