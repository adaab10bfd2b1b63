"""The dense decode step's attention reads KV pages in place
(``ops/attention.py:paged_decode_attention``): bit for bit
``decode_attention`` over the gathered context, with no gathered context
in the program, stopping at the longest live context; and
``InferenceSession.decode_report()`` says how far that was.  On a TPU
the eligible calls are one Pallas kernel (``ops/paged_attention.py``)
that stops at each slot's own length: here it runs in Pallas's TPU
interpreter against the loop, which stays as the fallback and the
oracle."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.experimental.pallas import tpu as pltpu

from mxnet_tpu import quantize, serve
from mxnet_tpu.ops import attention, paged_attention
from mxnet_tpu.ops.attention import decode_attention, paged_decode_attention
from mxnet_tpu.serve import kv_cache
from mxnet_tpu.serve import model as serve_model

from closeness import assert_close_across_executables
from serve_util import lend

S, H, D, PAGE, MAX_PAGES, LAYERS, LAYER = 3, 2, 8, 4, 5, 2, 1
CAP = PAGE * MAX_PAGES
TRASH = S * MAX_PAGES

# valid rows a slot (the current token included) -> what the case is for
LENGTHS = {
    "mid_page": (6, 3, 10),
    "page_boundary": (8, 4, 12),
    "one_row": (1, 1, 1),
    "at_capacity": (CAP, CAP, CAP),
    "idle_beside_full": (1, CAP, 7),   # an idle slot attends length 0 + 1
    "one_long": (CAP - 3, 2, 5),
    "two_iterations": (2 * PAGE + 1, 3, PAGE),
}


def _pools(rs, kv_quant="", layout="heads"):
    """K and V pools of LAYERS layers whose trash page is NaN-free
    garbage, page tables that map only the pages a length needs (the
    rest name the trash page, as the cache does), and one query row.
    ``layout``: the heads on an axis of their own, or ``"folded"`` into
    the last one, as the cache lays out heads of 8 at rest."""
    shape = (LAYERS, TRASH + 1, PAGE, H, D)
    k = jnp.asarray(rs.randn(*shape).astype(np.float32))
    v = jnp.asarray(rs.randn(*shape).astype(np.float32))
    q = jnp.asarray(rs.randn(S, H, 1, D).astype(np.float32))
    ks = vs = None
    if kv_quant:
        k, ks = quantize.kv_quantize_rows(k, kv_quant)
        v, vs = quantize.kv_quantize_rows(v, kv_quant)
    if layout == "folded":
        at_rest = kv_cache.kv_pool_shape(*shape)
        assert at_rest == shape[:3] + (H * D,)
        k, v = k.reshape(at_rest), v.reshape(at_rest)
    return q, k, v, ks, vs


def _tables(rs, lengths):
    perm = rs.permutation(TRASH)
    tables = np.full((S, MAX_PAGES), TRASH, np.int32)
    for s, n in enumerate(lengths):
        pages = -(-n // PAGE)
        tables[s, :pages] = perm[s * MAX_PAGES:s * MAX_PAGES + pages]
    return jnp.asarray(tables)


def _gathered(q, k, v, ks, vs, tables, lengths, mi):
    """What ``decode_step`` did before: gather every slot's whole table,
    then ``decode_attention`` over all ``MAX_PAGES`` blocks."""
    ctx_k = k[LAYER][tables].reshape(S, CAP, H, D).transpose(0, 2, 1, 3)
    ctx_v = v[LAYER][tables].reshape(S, CAP, H, D).transpose(0, 2, 1, 3)
    if ks is not None:
        ks = ks[LAYER][tables].reshape(S, CAP)
        vs = vs[LAYER][tables].reshape(S, CAP)
    return decode_attention(q, ctx_k, ctx_v, lengths, block=PAGE, mi=mi,
                            k_scale=ks, v_scale=vs)


def _paged(q, k, v, ks, vs, tables, lengths, mi):
    return paged_decode_attention(q, k, v, LAYER, tables, lengths, PAGE,
                                  mi=mi, k_scale=ks, v_scale=vs)


@pytest.mark.parametrize("layout", ["heads", "folded"])
@pytest.mark.parametrize("kv_quant", ["", "int8"])
@pytest.mark.parametrize("mi", [True, False])
@pytest.mark.parametrize("case", sorted(LENGTHS))
def test_paged_reader_equals_gathered_reader_bit_for_bit(case, mi, kv_quant,
                                                         layout):
    """Skipping the blocks every slot masks, and reading the rest from
    the pool, changes no bit: lengths that end mid-page, on a page
    boundary, at 1, at the table's capacity, an idle slot beside a full
    one, float32 pages and quantized ones with their scales, the heads on
    their own axis or folded into the last."""
    rs = np.random.RandomState(7)
    q, k, v, ks, vs = _pools(rs, kv_quant, layout)
    lengths = jnp.asarray(LENGTHS[case], jnp.int32)
    tables = _tables(rs, LENGTHS[case])
    want = jax.jit(_gathered, static_argnames="mi")(
        q, k, v, ks, vs, tables, lengths, mi=mi)
    got = jax.jit(_paged, static_argnames="mi")(
        q, k, v, ks, vs, tables, lengths, mi=mi)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.fixture(autouse=True)
def _two_pages_an_iteration(monkeypatch):
    """At these toy tables the reader's own group would swallow the whole
    table in one iteration: two pages an iteration (five columns, so the
    last iteration is completed with a masked one) unless a test says
    otherwise."""
    monkeypatch.setattr(attention, "_PAGED_KEYS_PER_ITERATION", 2 * PAGE)


@pytest.mark.parametrize("kv_quant", ["", "int8"])
@pytest.mark.parametrize("keys", [PAGE, 3 * PAGE, 64 * PAGE])
def test_pages_an_iteration_do_not_change_the_result(monkeypatch, keys,
                                                     kv_quant):
    """One page an iteration, three (the table's five columns completed
    with a masked sixth), or the whole table in one: the same bits."""
    rs = np.random.RandomState(8)
    q, k, v, ks, vs = _pools(rs, kv_quant)
    lengths = jnp.asarray(LENGTHS["one_long"], jnp.int32)
    tables = _tables(rs, LENGTHS["one_long"])
    want = _gathered(q, k, v, ks, vs, tables, lengths, True)
    monkeypatch.setattr(attention, "_PAGED_KEYS_PER_ITERATION", keys)
    got = _paged(q, k, v, ks, vs, tables, lengths, True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("pool", ["k", "v"])
def test_planted_fault_in_a_live_page_is_seen(pool):
    """The control of the comparison above: two rows of one live page
    swapped in the pool change the paged reader's result and leave the
    other slots' alone; the same swap in a page no table maps changes
    nothing."""
    rs = np.random.RandomState(9)
    q, k, v, ks, vs = _pools(rs)
    lengths = jnp.asarray(LENGTHS["mid_page"], jnp.int32)
    tables = _tables(rs, LENGTHS["mid_page"])
    sound = np.asarray(_paged(q, k, v, ks, vs, tables, lengths, True))

    def swapped(page):
        arr = np.array(k if pool == "k" else v)
        arr[LAYER, page, [0, 1]] = arr[LAYER, page, [1, 0]]
        kk, vv = (arr, v) if pool == "k" else (k, arr)
        return np.asarray(_paged(q, jnp.asarray(kk), jnp.asarray(vv), ks,
                                 vs, tables, lengths, True))

    live = int(tables[2, 1])     # slot 2 holds 10 rows: its second page
    faulty = swapped(live)
    assert np.abs(faulty[2] - sound[2]).max() > 1e-3
    np.testing.assert_array_equal(faulty[:2], sound[:2])
    unmapped = sorted(set(range(TRASH)) - set(np.asarray(tables).ravel()))
    np.testing.assert_array_equal(swapped(unmapped[0]), sound)


def test_reader_takes_a_groups_query_heads_as_rows_and_no_other_head_count():
    """Grouped-query attention: the query heads that share a key/value
    head are that head's rows, each answered as it is alone; a head count
    that is not the pool's is refused; ``scale`` multiplies the scores."""
    rs = np.random.RandomState(10)
    q, k, v, ks, vs = _pools(rs)
    lengths = jnp.asarray(LENGTHS["mid_page"])
    tables = _tables(rs, LENGTHS["mid_page"])
    from mxnet_tpu.base import MXNetError

    alone = [paged_decode_attention(row, k, v, LAYER, tables, lengths, PAGE)
             for row in (q, 2.0 * q)]
    both = paged_decode_attention(jnp.concatenate([q, 2.0 * q], axis=2), k,
                                  v, LAYER, tables, lengths, PAGE)
    assert_close_across_executables(both[:, :, :1], alone[0])
    assert_close_across_executables(both[:, :, 1:], alone[1])
    scaled = paged_decode_attention(q, k, v, LAYER, tables, lengths, PAGE,
                                    scale=2.0 / q.shape[-1] ** 0.5)
    assert_close_across_executables(scaled, alone[1])
    with pytest.raises(MXNetError, match="the pool's %d heads" % k.shape[-2]):
        paged_decode_attention(jnp.concatenate([q, q], axis=1), k, v, LAYER,
                               tables, lengths, PAGE)


# ---------------------------------------------------------------------------
# the kernel that reads each slot's own pages (Pallas, TPU interpreter)
# ---------------------------------------------------------------------------

KD = 128    # a head of whole lane tiles: the pools keep the heads' axis

# the two layouts the cache gives pools at rest -> (key/value heads, head
# width): two heads of 128 on an axis of their own, or eight heads of 64
# folded into 512 lanes, two a lane tile (granite-4.0-h-micro, LFM2)
LAYOUTS = {"heads": (2, KD), "folded": (8, 64)}
# (layout, query rows that share a key/value head)
FORMS = [("heads", 1), ("heads", 6), ("folded", 1), ("folded", 4),
         ("folded", 5)]
FORM_IDS = ["%s-R%d" % form for form in FORMS]


def _kernel_case(rs, lengths, rows, layout="heads", head=None):
    """Pools in ``layout`` whose every row is finite garbage where no
    slot can see it (the pages past a length, the rows of a last page
    past it, the trash page, the other layer): 1e3, so that one such row
    reaching a result moves it by far more than any tolerance."""
    heads, d = LAYOUTS[layout]
    d = head or d
    shape = (LAYERS, TRASH + 1, PAGE, heads, d)
    k, v = (rs.randn(*shape).astype(np.float32) for _ in "kv")
    tables = np.asarray(_tables(rs, lengths))
    seen = np.zeros(shape[:3], bool)
    for s, n in enumerate(lengths):
        for pos in range(n):
            seen[LAYER, tables[s, pos // PAGE], pos % PAGE] = True
    k[~seen], v[~seen] = 1e3, -1e3
    q = jnp.asarray(rs.randn(S, heads, rows, d).astype(np.float32))
    if layout == "folded":
        k, v = (x.reshape(shape[:3] + (heads * d,)) for x in (k, v))
    return q, jnp.asarray(k), jnp.asarray(v), jnp.asarray(tables)


def _by_kernel(q, k, v, tables, lengths, pages=2, **more):
    """The kernel as the chip would run it but for the interpreter, whose
    memory starts as NaN: what was never copied must not be read."""
    with pltpu.force_tpu_interpret_mode():
        return paged_attention.paged_attention(
            q, k, v, LAYER, tables, lengths, PAGE,
            more.pop("scale", 1.0 / q.shape[-1] ** 0.5), pages=pages, **more)


@pytest.mark.parametrize("layout, rows", FORMS, ids=FORM_IDS)
@pytest.mark.parametrize("case", sorted(LENGTHS))
def test_kernel_equals_the_loop(case, layout, rows):
    """Each slot's own pages and no others, two pages a block (so a slot
    is one to three blocks and the next slot's first block is fetched
    behind the last): the loop's result to the tolerance of two
    executables of one computation, one query row a head or a group's
    four, five or six, for every case of ``LENGTHS``, over pools that keep
    their heads' axis and over folded ones (where a head's rows are its 64
    lanes of a lane tile it shares with its neighbour: the neighbour's
    keys, values and queries reach no result); nothing a slot cannot see
    reaches its result, and nothing is NaN."""
    rs = np.random.RandomState(12)
    lengths = jnp.asarray(LENGTHS[case], jnp.int32)
    q, k, v, tables = _kernel_case(rs, LENGTHS[case], rows, layout)
    want = _paged(q, k, v, None, None, tables, lengths, False)
    got = _by_kernel(q, k, v, tables, lengths)
    assert got.shape == q.shape and got.dtype == q.dtype
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(want)).max() < 10     # no garbage in the oracle
    assert_close_across_executables(got, want)


@pytest.mark.parametrize("head, rows", [(32, 3), (KD, 3), (KD, 32),
                                        (2 * KD, 8), (2 * KD, 3)])
def test_folded_kernel_takes_any_head_that_divides_a_lane_tile(head, rows):
    """Four heads of 32 a lane tile, or one of 128: the same form, since
    the kernel sees lane tiles and the query rows laid out over them; 32
    rows a head are a diffusion block's 4 rows x 8 query heads
    (``serve/sdar_moe.py``).  And a head of 256, two lane tiles whole: a
    group of its own, whose 8 query heads are one sublane tile of rows
    (``serve/qwen3_next.py``)."""
    rs = np.random.RandomState(19)
    lengths = jnp.asarray(LENGTHS["idle_beside_full"], jnp.int32)
    q, k, v, tables = _kernel_case(rs, LENGTHS["idle_beside_full"], rows,
                                   "folded", head)
    assert k.shape[-1] == 8 * head
    want = _paged(q, k, v, None, None, tables, lengths, False)
    assert_close_across_executables(
        _by_kernel(q, k, v, tables, lengths), want)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("pages", [1, 3, 8])
def test_kernel_pages_a_block_do_not_change_the_result(pages, layout):
    """One page a block, three (the table's five columns completed with a
    sixth that no block reads) or the whole table in one."""
    rs = np.random.RandomState(13)
    lengths = jnp.asarray(LENGTHS["one_long"], jnp.int32)
    q, k, v, tables = _kernel_case(rs, LENGTHS["one_long"], 6, layout)
    want = _paged(q, k, v, None, None, tables, lengths, False)
    assert_close_across_executables(
        _by_kernel(q, k, v, tables, lengths, pages=pages), want)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_kernel_gives_a_slot_of_length_zero_zero_and_takes_a_scale(layout):
    """No row to see: 0, as the loop gives; ``scale`` multiplies the
    scores; a bfloat16 query gets a bfloat16 answer."""
    rs = np.random.RandomState(14)
    lengths = jnp.asarray((0, CAP, 7), jnp.int32)
    q, k, v, tables = _kernel_case(rs, (0, CAP, 7), 1, layout)
    got = _by_kernel(q, k, v, tables, lengths, scale=0.2)
    want = paged_decode_attention(q, k, v, LAYER, tables, lengths, PAGE,
                                  scale=0.2)
    assert not np.asarray(got[0]).any()
    assert_close_across_executables(got, want)
    half = _by_kernel(q.astype(jnp.bfloat16), k, v, tables, lengths,
                      scale=0.2)
    assert half.dtype == jnp.bfloat16
    assert_close_across_executables(
        half, paged_decode_attention(q.astype(jnp.bfloat16), k, v, LAYER,
                                     tables, lengths, PAGE, scale=0.2),
        limit=2, dtype="bfloat16")


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("pool", ["k", "v"])
def test_kernel_sees_a_planted_fault_in_a_live_page(pool, layout):
    """The control: two rows of one live page swapped move that slot's
    result and no other's."""
    rs = np.random.RandomState(15)
    lengths = jnp.asarray(LENGTHS["mid_page"], jnp.int32)
    q, k, v, tables = _kernel_case(rs, LENGTHS["mid_page"], 6, layout)
    sound = np.asarray(_by_kernel(q, k, v, tables, lengths))
    arr = np.array(k if pool == "k" else v)
    live = int(tables[2, 1])     # slot 2 holds 10 rows: its second page
    arr[LAYER, live, [0, 1]] = arr[LAYER, live, [1, 0]]
    kk, vv = (jnp.asarray(arr), v) if pool == "k" else (k, jnp.asarray(arr))
    faulty = np.asarray(_by_kernel(q, kk, vv, tables, lengths))
    assert np.abs(faulty[2] - sound[2]).max() > 1e-3
    np.testing.assert_array_equal(faulty[:2], sound[:2])


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_kernel_rounds_its_operands_as_the_chip_does_at_default_precision(
        layout):
    """What runs on the chip: at the default matmul precision the
    operands are rounded to bfloat16 where they are read and the sums are
    float32, as XLA's einsum does there with the loop's.  The CPU's loop
    multiplies in float32, so the two differ by the roundings: the
    largest read over six seeds was 0.36 % of the result's largest
    magnitude (heads of 128; folded heads of 64, whose neighbour's lanes
    are exact zeros in the left operand, over seeds 16 and 1-5: 0.36 % as
    well); the limit is 4 eps of bfloat16, 1.6 %."""
    rs = np.random.RandomState(16)
    lengths = jnp.asarray(LENGTHS["one_long"], jnp.int32)
    q, k, v, tables = _kernel_case(rs, LENGTHS["one_long"], 6, layout)
    want = np.asarray(_paged(q, k, v, None, None, tables, lengths, False))
    with jax.default_matmul_precision("default"):
        got = np.asarray(_by_kernel(q, k, v, tables, lengths))
    gap = np.abs(got - want).max() / np.abs(want).max()
    assert 1e-5 < gap < 4 * 2.0 ** -8, gap


def _loop_text(q, k, v, ks, vs, tables, lengths, mi):
    # a function of its own each time: a trace is cached by the function
    return jax.jit(lambda *args: _paged(*args, mi)).lower(
        q, k, v, ks, vs, tables, lengths).as_text()


def test_only_an_eligible_call_on_a_tpu_takes_the_kernel(monkeypatch):
    """``paged_attention_eligible`` is read off the call while it is
    traced: the backend is a TPU, ``mi`` is not asked, the pages carry no
    scales, the pools are float32 and either keep their heads' axis in
    whole sublane tiles of whole lane tiles, or fold heads that divide a
    lane tile into a last axis of whole lane tiles under a table of at
    least 2 048 keys.  Every other call lowers to the loop's text, letter
    for letter what it lowered to with no kernel to ask."""
    rs = np.random.RandomState(17)
    shape = (LAYERS, TRASH + 1, PAGE, 8, KD)
    k = v = jnp.asarray(rs.randn(*shape).astype(np.float32))
    q = jnp.asarray(rs.randn(S, 8, 6, KD).astype(np.float32))
    scales = jnp.ones(shape[:3], jnp.float32)
    lengths = jnp.asarray(LENGTHS["mid_page"], jnp.int32)
    toy = _tables(rs, LENGTHS["mid_page"])
    # the same pages under a table as wide as the rule for folded pools
    # asks (512 pages of 4 rows), and under one a page short of it
    wide = jnp.concatenate(
        [toy, jnp.full((S, 512 - MAX_PAGES), TRASH, jnp.int32)], axis=1)
    assert wide.shape[1] * PAGE == paged_attention._FOLDED_MIN_TABLE_KEYS
    eligible = paged_attention.paged_attention_eligible
    two_heads = k[:, :, :, :2]

    def folded(pool, heads, d):
        """``heads`` heads of ``d`` out of ``pool``, folded as the cache
        folds them, and a query of as many heads."""
        pool = pool[:, :, :, :heads, :d].reshape(shape[:3] + (heads * d,))
        return q[:, :heads, :, :d], pool, pool

    refused = {
        # name: (q, K pool, V pool, mi, K scales, V scales, tables)
        "mi": (q, k, v, True, None, None, toy),
        "scales": (q, k.astype(jnp.int8), v.astype(jnp.int8), False, scales,
                   scales, toy),
        # three heads of 64: 192 lanes, a tile and a half
        "folded_into_part_of_a_tile": folded(k, 3, 64) + (False, None, None,
                                                          wide),
        # four heads of 96 fill three lane tiles, and two of them straddle
        "folded_heads_across_tiles": folded(k, 4, 96) + (False, None, None,
                                                         wide),
        "folded_bfloat16_pools": folded(k.astype(jnp.bfloat16), 8, 64)
        + (False, None, None, wide),
        "folded_scales": folded(k.astype(jnp.int8), 8, 64) + (False, scales,
                                                              scales, wide),
        "folded_under_a_short_table": folded(k, 8, 64) + (False, None, None,
                                                          wide[:, :-1]),
        "heads_of_part_of_a_tile": (q[:, :2], two_heads, two_heads, False,
                                    None, None, toy),
        "bfloat16_pools": (q, k.astype(jnp.bfloat16), v.astype(jnp.bfloat16),
                           False, None, None, toy),
        "heads_of_two_lane_tiles": (
            jnp.tile(q, 2), jnp.tile(k, 2), jnp.tile(v, 2), False, None,
            None, toy),
    }
    accepted = {
        # name: (q, K pool, V pool, tables, the kernel's name: the toy
        #        table's five pages are under a block, the wide one's 512
        #        are four blocks of 128 pages of 4 rows)
        "heads": (q, k, v, toy, "paged_decode_attention_p5"),
        "bfloat16_query": (q.astype(jnp.bfloat16), k, v, toy,
                           "paged_decode_attention_p5"),
        "folded_heads_of_64": folded(k, 8, 64) + (
            wide, "paged_decode_attention_f64_p128"),
        "folded_heads_of_32": folded(k, 8, 32) + (
            wide, "paged_decode_attention_f32_p128"),
        # two heads of 256 in 512 lanes: a head is two lane tiles whole
        "folded_heads_of_256": (
            jnp.tile(q[:, :2], 2),
            jnp.tile(two_heads, 2).reshape(shape[:3] + (4 * KD,)),
            jnp.tile(two_heads, 2).reshape(shape[:3] + (4 * KD,)), wide,
            "paged_decode_attention_f256_p128"),
    }
    for q_, k_, v_, tables, _ in accepted.values():        # the CPU
        assert not eligible(q_, k_, v_, False, None, None,
                            tables.shape[1] * PAGE)
    texts = {name: _loop_text(q_, k_, v_, ks, vs, tables, lengths, mi)
             for name, (q_, k_, v_, mi, ks, vs, tables) in refused.items()}
    texts["the_cpu"] = _loop_text(q, k, v, None, None, toy, lengths, False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for name, (q_, k_, v_, mi, ks, vs, tables) in refused.items():
        assert not eligible(q_, k_, v_, mi, ks, vs,
                            tables.shape[1] * PAGE), name
        text = _loop_text(q_, k_, v_, ks, vs, tables, lengths, mi)
        assert text == texts[name] and "while" in text, name
        assert "tpu_custom_call" not in text, name
    # the eligible calls: traced only, this backend cannot lower the kernel
    for name, (q_, k_, v_, tables, kernel) in accepted.items():
        assert eligible(q_, k_, v_, False, None, None,
                        tables.shape[1] * PAGE), name
        with serve_model.trace_notes() as notes:
            traced = jax.make_jaxpr(lambda *args: _paged(*args, False))(
                q_, k_, v_, None, None, tables, lengths)
        assert notes == {"paged_kernel_layers": 1}, name
        assert kernel == paged_attention.kernel_name(
            paged_attention.pages_per_block(PAGE, tables.shape[1],
                                            k_.ndim == 4),
            q_.shape[-1] if k_.ndim == 4 else 0)
        assert "name=%s\n" % kernel in str(traced), name
        # the kernel in a jitted body of its own, and no loop beside it
        steps = [eqn.primitive.name for eqn in traced.jaxpr.eqns]
        assert str(traced).count("pallas_call") == 1, name
        assert "while" not in steps, name


def test_a_steps_layers_share_one_trace_of_the_kernel(monkeypatch):
    """The layer's number is data to the kernel's jitted body, so two
    layers of one step are two calls of ONE traced body (lowered once):
    the dense model's 24 lowerings added 23 s to a session's start."""
    rs = np.random.RandomState(18)
    shape = (LAYERS, TRASH + 1, PAGE, 8, KD)
    k = v = jnp.asarray(rs.randn(*shape).astype(np.float32))
    q = jnp.asarray(rs.randn(S, 8, 1, KD).astype(np.float32))
    lengths = jnp.asarray(LENGTHS["mid_page"], jnp.int32)
    tables = _tables(rs, LENGTHS["mid_page"])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def two_layers(q, k, v, tables, lengths):
        return sum(paged_decode_attention(q, k, v, layer, tables, lengths,
                                          PAGE) for layer in range(LAYERS))

    traced = jax.make_jaxpr(two_layers)(q, k, v, tables, lengths)
    bodies = [eqn.params["jaxpr"] for eqn in traced.jaxpr.eqns
              if eqn.params.get("name") == "_paged_attention"]
    assert len(bodies) == LAYERS == 2 and bodies[0] is bodies[1]
    assert str(bodies[0]).count("pallas_call") == 1


# ---------------------------------------------------------------------------
# the pools' layout at rest: the cache's, and no result knows it
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("heads, head_dim, folds", [
    (8, 32, True), (8, 64, True), (8, 128, False), (8, 256, False),
    (16, 128, False), (4, 128, True), (12, 128, True), (4, 256, True)])
def test_heads_narrower_than_a_lane_tile_fold_into_the_last_axis(
        heads, head_dim, folds):
    """And heads of whole lane tiles that are no whole sublane tile of
    them (SDAR-30B-A3B's 4 x 128): at rest those would lie in tiles of 4
    rows, which a step turns the whole pool out of and back into."""
    shape = kv_cache.kv_pool_shape(4, 769, 16, heads, head_dim)
    assert shape == ((4, 769, 16, heads * head_dim) if folds
                     else (4, 769, 16, heads, head_dim))
    cache = kv_cache.PagedKVCache(4, heads, head_dim, 16, 6, 2, 3)
    assert cache.pools["k_pool"].shape == cache.pools["v_pool"].shape \
        == kv_cache.kv_pool_shape(4, 7, 16, heads, head_dim)
    assert cache.kv_lanes == (heads * head_dim if folds else head_dim)
    assert kv_cache.pool_heads(cache.pools["k_pool"], head_dim) == heads


def test_a_cache_without_a_kv_pool_names_no_lane_width():
    cache = kv_cache.PagedKVCache(4, 8, 64, 16, 6, 2, 3, latent_dim=576)
    assert cache.kv_lanes is None and "k_pool" not in cache.pools
    assert cache.latent_lanes == 640


@pytest.mark.parametrize("head_dim", [64, 128])
def test_a_cache_without_a_latent_pool_names_no_latent_width(head_dim):
    cache = kv_cache.PagedKVCache(4, 8, head_dim, 16, 6, 2, 3)
    assert cache.latent_lanes is None and "latent_pool" not in cache.pools


@pytest.mark.parametrize("kv_quant", ["", "int8"])
@pytest.mark.parametrize("rows", [1, 4], ids=["ungrouped", "grouped"])
@pytest.mark.parametrize("head_dim", [32, 64])
def test_append_and_read_over_a_folded_pool_equal_the_heads_layout(
        head_dim, rows, kv_quant):
    """The decode step's two halves, ``append_rows`` at (page, offset)
    and ``paged_decode_attention``, over the cache's folded pools and
    over the same pools with the heads on their own axis: the same pools
    afterwards and the same attention, bit for bit; one query row a head
    or a group's four, float32 pages or quantized ones with their
    scales."""
    heads = 2
    rs = np.random.RandomState(11)
    shape = (LAYERS, TRASH + 1, PAGE, heads, head_dim)
    folded = kv_cache.kv_pool_shape(*shape)
    assert folded == shape[:3] + (heads * head_dim,)
    lengths = np.asarray(LENGTHS["mid_page"], np.int32)
    tables = _tables(rs, LENGTHS["mid_page"])
    new_k, new_v = (jnp.asarray(rs.randn(S, heads, head_dim), jnp.float32)
                    for _ in "kv")
    q = jnp.asarray(rs.randn(S, heads, rows, head_dim), jnp.float32)
    filled = {}
    for which in "kv":
        pool = jnp.asarray(rs.randn(*shape).astype(np.float32))
        if kv_quant:
            pool, filled[which + "_scale"] = quantize.kv_quantize_rows(
                pool, kv_quant)
        filled[which + "_pool"] = pool
    # the row each slot appends: its last valid one
    page = jnp.take_along_axis(
        tables, jnp.asarray((lengths - 1) // PAGE)[:, None], axis=1)[:, 0]
    offset = jnp.asarray((lengths - 1) % PAGE)

    @jax.jit
    def step(pools):
        pools = dict(pools)
        kv_cache.append_rows(pools, "k", LAYER, page, offset, new_k,
                             kv_quant)
        kv_cache.append_rows(pools, "v", LAYER, page, offset, new_v,
                             kv_quant)
        return pools, paged_decode_attention(
            q, pools["k_pool"], pools["v_pool"], LAYER, tables,
            jnp.asarray(lengths), PAGE, mi=True,
            k_scale=pools.get("k_scale"), v_scale=pools.get("v_scale"))

    want_pools, want = step(filled)
    got_pools, got = step({
        name: pool.reshape(folded) if name.endswith("_pool") else pool
        for name, pool in filled.items()})
    assert got_pools["k_pool"].shape == folded
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for name, pool in want_pools.items():
        np.testing.assert_array_equal(
            np.asarray(got_pools[name]).reshape(pool.shape),
            np.asarray(pool))
    if not kv_quant:   # and the appended rows are the rows handed in
        np.testing.assert_array_equal(
            np.asarray(got_pools["k_pool"][LAYER, page, offset]).reshape(
                new_k.shape), np.asarray(new_k))


@pytest.mark.parametrize("rank", [1, 2], ids=["one_slot", "every_slot"])
@pytest.mark.parametrize("layout", ["heads", "folded"])
def test_read_context_gathers_what_the_layers_slice_held(layout, rank):
    """``read_context`` gathers a table's pages from the pool where it
    lies: over both layouts ``kv_pool_shape`` gives, for one slot's table
    and for every slot's, bit for bit the rows that a slice of the layer
    gathered by the table holds, laid out (slots, heads, rows, head)."""
    rs = np.random.RandomState(7)
    _, k, _, _, _ = _pools(rs, layout=layout)
    assert k.ndim == (4 if layout == "folded" else 5)
    tables = _tables(rs, LENGTHS["mid_page"])
    if rank == 1:
        tables = tables[2]
    n = 1 if rank == 1 else S
    want = np.asarray(k)[LAYER][np.asarray(tables)].reshape(
        n, CAP, H, D).transpose(0, 2, 1, 3)
    got = kv_cache.read_context(k, LAYER, tables, D)
    assert got.shape == (n, H, CAP, D)
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(kv_cache.read_context, static_argnums=3)(
            k, jnp.int32(LAYER), tables, D)), want)


# ---------------------------------------------------------------------------
# the decode program and the session's counter
# ---------------------------------------------------------------------------

CFG = serve.ModelConfig(vocab_size=61, num_layers=2, d_model=32,
                        num_heads=2, max_len=64)
SLOTS, SPAGE = 3, 8


@pytest.fixture(scope="module")
def _session():
    sconf = serve.ServeConfig(slots=SLOTS, page_size=SPAGE, buckets=(8, 16),
                              max_new=8, exact=True)
    return serve.InferenceSession(serve_model.init_params(CFG, seed=3),
                                  num_heads=CFG.num_heads, config=sconf)


@pytest.fixture
def session(_session):
    yield from lend(_session)


@pytest.mark.parametrize("kv_quant", ["", "int8"])
def test_decode_program_holds_no_copy_of_a_slots_whole_table(kv_quant):
    """The gathered context cannot come back unnoticed: no array of the
    jitted decode program, before or after XLA's passes, has the shape
    (S, max_pages * page, H, D), its transpose, or the gather's own
    (S, max_pages, page, H, D).  The same search finds the copy in the
    program that gathers (the control)."""
    s, page, max_pages = 3, 4, 5
    h, d = CFG.num_heads, CFG.head_dim
    params = serve_model.init_params(CFG, seed=3)
    store = jnp.int8 if kv_quant else jnp.float32
    pool = jnp.zeros((CFG.num_layers, s * max_pages + 1, page, h, d), store)
    scale = jnp.ones(pool.shape[:3], jnp.float32) if kv_quant else None
    tables = jnp.zeros((s, max_pages), jnp.int32)
    ints = jnp.zeros((s,), jnp.int32)

    def shapes_of(fn, *args):
        lowered = jax.jit(fn).lower(*args)
        text = lowered.as_text() + lowered.compile().as_text()
        dims = set(re.findall(r"(?:tensor<|[a-z]\d+\[)([\dx,]+)", text))
        return {tuple(int(n) for n in re.split("[x,]", dim) if n)
                for dim in dims}

    def decode(params, tokens, lengths, tables, pools):
        return serve_model.decode_step(
            params, tokens, lengths, tables, pools, {}, CFG, page,
            exact=False, kv_quant=kv_quant)

    def gathers(k_pool, tables):
        return k_pool[0][tables].reshape(
            s, max_pages * page, h, d).transpose(0, 2, 1, 3) * 2

    cap = max_pages * page
    banned = {(s, cap, h, d), (s, h, cap, d), (s, max_pages, page, h, d)}
    assert banned & shapes_of(gathers, pool.astype(jnp.float32), tables)
    pools = {"k_pool": pool, "v_pool": pool}
    if kv_quant:
        pools.update(k_scale=scale, v_scale=scale)
    found = shapes_of(decode, params, ints, ints, tables, pools)
    assert (s, page, h, d) in found     # the search reads this program
    assert not banned & found


def test_decode_report_counts_blocks_to_the_longest_context(session):
    """``blocks_visited`` grows each step by the page blocks of the
    longest live context, its new row included, and ``blocks_capacity``
    by the table's width; idle slots count as one block."""
    width = session.cache.table_width
    assert width == (16 + 8) // SPAGE
    before = session.decode_report()
    rs = np.random.RandomState(5)
    for n in (3, 13):
        slot = session.try_alloc(n, 8)
        session.prefill(slot, rs.randint(1, CFG.vocab_size, size=n).tolist())
    want = 0
    for step in range(5):
        # the longest context holds 13 + step rows and appends one
        want += -(-(13 + step + 1) // SPAGE)
        session.step()
    rep = session.decode_report()
    assert rep["steps"] - before["steps"] == 5
    assert rep["blocks_visited"] - before["blocks_visited"] == want == 12
    assert rep["blocks_capacity"] - before["blocks_capacity"] == 5 * width
    assert rep["visited_share"] == rep["blocks_visited"] / rep[
        "blocks_capacity"]
    session.reset_cold()
    session.step()          # no live slot: every row attends its one row
    rep2 = session.decode_report()
    assert rep2["blocks_visited"] - rep["blocks_visited"] == 1


@pytest.mark.parametrize("lengths, page, width, per_slot, per_loop", [
    # 16-row pages: the contexts' own pages | the longest's for every slot
    ((0, 0, 0), 16, 8, 3, 3),                 # idle slots attend one row
    ((15, 16, 31), 16, 8, 1 + 2 + 2, 6),      # the new row opens a page
    ((100, 0, 7, 7), 8, 13, 13 + 1 + 1 + 1, 52),
    ((300, 5), 16, 4, 4 + 1, 8),              # no further than the table
])
def test_pages_a_step_visits_under_both_readers(lengths, page, width,
                                                per_slot, per_loop):
    """The host arithmetic alone: the kernel visits every slot's own
    ``ceil((length + 1) / page)`` pages, the loop the longest context's
    for every slot; neither past the table's width."""
    visits = serve_model.decode_pages_visited
    assert visits(np.asarray(lengths), page, width, True) == per_slot
    assert visits(np.asarray(lengths), page, width, False) == per_loop
    assert type(visits(lengths, page, width, True)) is int


@pytest.mark.parametrize("kernel_layers", [0, 2], ids=["loop", "kernel"])
def test_decode_report_counts_for_the_reader_that_was_traced(
        session, monkeypatch, kernel_layers):
    """``paged_kernel_layers`` is what the decode executable's trace
    noted (0 on the CPU); with it the session counts each slot's own
    pages and ``blocks_visited`` is their mean a slot, a float; without
    it the longest context's, an int.  The counts are host arithmetic:
    the note alone switches them, the executable is the CPU's loop."""
    rep = session.decode_report()
    assert rep["paged_kernel_layers"] == 0
    monkeypatch.setattr(session._exes["decode"], "traced",
                        {"paged_kernel_layers": kernel_layers}
                        if kernel_layers else {})
    rs = np.random.RandomState(6)
    for n in (3, 13):
        slot = session.try_alloc(n, 8)
        session.prefill(slot, rs.randint(1, CFG.vocab_size, size=n).tolist())
    before = session.decode_report()
    pages = 0
    for step in range(4):
        # contexts of 3 + step and 13 + step rows and an idle slot, each
        # appending one row
        own = [-(-(3 + step + 1) // SPAGE), -(-(13 + step + 1) // SPAGE), 1]
        pages += sum(own) if kernel_layers else max(own) * SLOTS
        session.step()
    rep = session.decode_report()
    assert rep["paged_kernel_layers"] == kernel_layers
    assert rep["pages_visited"] - before["pages_visited"] == pages \
        == (17 if kernel_layers else 27)
    visited = rep["blocks_visited"] - before["blocks_visited"]
    assert visited == pytest.approx(pages / SLOTS, rel=1e-12)
    assert type(rep["blocks_visited"]) is (float if kernel_layers else int)
    assert rep["visited_share"] == rep["blocks_visited"] / rep[
        "blocks_capacity"]


@pytest.mark.parametrize("head, width, layers_by_kernel", [
    (16, 256, 0), (64, 255, 0), (64, 256, 2)],
    ids=["quarter_tile", "short_table", "whole_tile_wide_table"])
def test_a_folded_cache_notes_the_kernel_where_the_call_is_eligible(
        monkeypatch, head, width, layers_by_kernel):
    """What switches the counts above, read off a real trace: the dense
    decode step over a cache that folds two heads of 64 into one lane tile
    notes the kernel once a layer when a TPU traces it under a table of
    2 048 keys (256 pages of 8: the folded reader); under a table a page
    narrower, and over this file's toy cache (two heads of 16: 32 lanes,
    a quarter of a tile), it keeps the loop and notes nothing."""
    cfg = serve.ModelConfig(vocab_size=61, num_layers=2, d_model=2 * head,
                            num_heads=2, max_len=64)
    params = jax.eval_shape(lambda: serve_model.init_params(cfg, seed=3))
    cache = kv_cache.PagedKVCache(2, 2, head, SPAGE, 9, SLOTS, 3)
    assert cache.kv_lanes == 2 * head and cache.pools["k_pool"].ndim == 4
    ints = jnp.zeros((SLOTS,), jnp.int32)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with serve_model.trace_notes() as notes:
        traced = jax.make_jaxpr(
            lambda params, pools: serve_model.decode_step(
                params, ints, ints, jnp.zeros((SLOTS, width), jnp.int32),
                pools, {}, cfg, SPAGE, exact=False))(params, cache.pools)
    assert notes.get("paged_kernel_layers", 0) == layers_by_kernel
    name = paged_attention.kernel_name(64, 64)      # 512 keys a block
    assert name == "paged_decode_attention_f64_p64"
    # one jitted body for both layers, so the name prints once
    assert str(traced).count("name=%s\n" % name) == bool(layers_by_kernel)
    steps = [eqn.primitive.name for eqn in traced.jaxpr.eqns]
    assert steps.count("while") == 2 - layers_by_kernel


def test_decode_report_of_a_fresh_session_is_zero():
    sconf = serve.ServeConfig(slots=2, page_size=SPAGE, buckets=(8,),
                              max_new=8, exact=True)
    sess = serve.InferenceSession(serve_model.init_params(CFG, seed=3),
                                  num_heads=CFG.num_heads, config=sconf)
    assert sess.decode_report() == {
        "steps": 0, "steps_ahead": 0, "blocks_visited": 0,
        "pages_visited": 0,
        "blocks_capacity": 0, "visited_share": 0.0,
        "paged_kernel_layers": 0,
        # two heads of 16 fold into the pools' last axis
        "kv_lanes": CFG.num_heads * CFG.head_dim}
    assert sess.cache.kv_lanes == 32
    assert sess.cache.pools["k_pool"].ndim == 4
