"""The dense decode step's attention reads KV pages in place
(``ops/attention.py:paged_decode_attention``): bit for bit
``decode_attention`` over the gathered context, with no gathered context
in the program, stopping at the longest live context; and
``InferenceSession.decode_report()`` says how far that was."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import quantize, serve
from mxnet_tpu.ops import attention
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
# the pools' layout at rest: the cache's, and no result knows it
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("head_dim, folds", [(32, True), (64, True),
                                             (128, False), (256, False)])
def test_heads_narrower_than_a_lane_tile_fold_into_the_last_axis(head_dim,
                                                                 folds):
    shape = kv_cache.kv_pool_shape(4, 769, 16, 8, head_dim)
    assert shape == ((4, 769, 16, 8 * head_dim) if folds
                     else (4, 769, 16, 8, head_dim))
    cache = kv_cache.PagedKVCache(4, 8, head_dim, 16, 6, 2, 3)
    assert cache.pools["k_pool"].shape == cache.pools["v_pool"].shape \
        == kv_cache.kv_pool_shape(4, 7, 16, 8, head_dim)
    assert cache.kv_lanes == (8 * head_dim if folds else head_dim)
    assert kv_cache.pool_heads(cache.pools["k_pool"], head_dim) == 8


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


def test_decode_report_of_a_fresh_session_is_zero():
    sconf = serve.ServeConfig(slots=2, page_size=SPAGE, buckets=(8,),
                              max_new=8, exact=True)
    sess = serve.InferenceSession(serve_model.init_params(CFG, seed=3),
                                  num_heads=CFG.num_heads, config=sconf)
    assert sess.decode_report() == {
        "steps": 0, "blocks_visited": 0, "blocks_capacity": 0,
        "visited_share": 0.0,
        # two heads of 16 fold into the pools' last axis
        "kv_lanes": CFG.num_heads * CFG.head_dim}
    assert sess.cache.kv_lanes == 32
    assert sess.cache.pools["k_pool"].ndim == 4
