"""``ops/attention.py:decode_attention`` stops after the last key block
any query row of the call can see (``ceil(max(lengths) / block)`` visits,
a traced trip count): bit for bit what the walk of every block gave, and
what the same call gives on a context cut off at the bound; a call that
labels its rows with ``k_positions`` still walks every block."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from mxnet_tpu import quantize
from mxnet_tpu.ops.attention import (_kv_blocks, attend_block,
                                     decode_attention, finalize_attention)

S, H, Q, D, BLOCK, NBLK = 2, 2, 6, 8, 4, 5
CAP = BLOCK * NBLK

# the furthest horizon of the call -> the blocks the loop has to visit
FURTHEST = {"first_block": 3, "block_boundary": 2 * BLOCK,
            "middle_block": 2 * BLOCK + 1, "last_block": CAP - 1,
            "capacity": CAP}


def _whole_walk(q, k_ctx, v_ctx, lengths, block, mi, k_scale=None,
                v_scale=None):
    """The function as it was before the bound: a ``lax.scan`` over the
    stack of all ``Tcap / block`` blocks, every one of them visited."""
    t_cap = k_ctx.shape[-2]
    nblk = t_cap // block
    kb, vb = _kv_blocks(k_ctx, t_cap, block), _kv_blocks(v_ctx, t_cap, block)
    xs = [kb, vb, jnp.arange(nblk) * block]
    if k_scale is not None:
        xs += [jnp.moveaxis(s.reshape(s.shape[0], nblk, block), 1, 0)[
            :, :, None, :, None] for s in (k_scale, v_scale)]
    q32 = q.astype(jnp.float32) * q.shape[-1] ** -0.5
    valid_len = lengths[:, None, :, None] if lengths.ndim == 2 \
        else lengths.reshape(lengths.shape + (1, 1, 1))

    def body(carry, x):
        kblk, vblk, start = x[:3]
        if k_scale is not None:
            kblk = kblk.astype(jnp.float32) * x[3]
            vblk = vblk.astype(jnp.float32) * x[4]
        return attend_block(
            q32, kblk, vblk, *carry, mi=mi,
            kv_valid=start + jnp.arange(block) < valid_len), None

    carry = (jnp.zeros(q.shape, jnp.float32),
             jnp.full(q.shape[:-1] + (1,), -jnp.inf, jnp.float32),
             jnp.zeros(q.shape[:-1] + (1,), jnp.float32))
    (acc, _, l), _ = lax.scan(body, carry, tuple(xs))
    return finalize_attention(acc, l).astype(q.dtype)


def _inputs(furthest, ndim, kv_quant, seed=11):
    """A context whose rows past every horizon are garbage (finite: the
    whole walk multiplies them by a probability of 0), and lengths whose
    maximum is ``furthest``: per row (a prefill chunk's horizons, the
    last rows padding that sees furthest) or one a slot (decode; slot 1
    idle beside it)."""
    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.randn(S, H, Q, D).astype(np.float32))
    k = jnp.asarray(rs.randn(S, H, CAP, D).astype(np.float32))
    v = jnp.asarray(rs.randn(S, H, CAP, D).astype(np.float32))
    ks = vs = None
    if kv_quant:
        # (S, Tcap, H, D) rows -> codes and one scale a position
        k, ks = quantize.kv_quantize_rows(k.transpose(0, 2, 1, 3), kv_quant)
        v, vs = quantize.kv_quantize_rows(v.transpose(0, 2, 1, 3), kv_quant)
        k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
        assert ks.shape == (S, CAP)
    if ndim == 2:
        lengths = np.clip(furthest - Q + 1 + np.arange(Q), 0, CAP)
        lengths = np.stack([lengths, lengths // 2])
    else:
        lengths = np.asarray([furthest, 0])
    return q, k, v, ks, vs, jnp.asarray(lengths, jnp.int32)


@pytest.mark.parametrize("kv_quant", ["", "int8"])
@pytest.mark.parametrize("mi", [True, False])
@pytest.mark.parametrize("ndim", [1, 2])
@pytest.mark.parametrize("case", sorted(FURTHEST))
def test_bounded_walk_changes_no_bit(case, ndim, mi, kv_quant):
    """The furthest horizon in the first block, on a block's boundary,
    in a middle block, in the last and at capacity; horizons per query
    row and per slot; float32 rows and quantized ones with their scales:
    the whole walk's bits, and those of the same call on a context that
    ends at the bound."""
    furthest = FURTHEST[case]
    q, k, v, ks, vs, lengths = _inputs(furthest, ndim, kv_quant)
    run = jax.jit(decode_attention, static_argnames=("block", "mi"))
    got = np.asarray(run(q, k, v, lengths, block=BLOCK, mi=mi, k_scale=ks,
                         v_scale=vs))
    assert np.isfinite(got).all()
    whole = jax.jit(_whole_walk, static_argnames=("block", "mi"))(
        q, k, v, lengths, block=BLOCK, mi=mi, k_scale=ks, v_scale=vs)
    np.testing.assert_array_equal(got, np.asarray(whole))
    cut = -(-furthest // BLOCK) * BLOCK
    short = run(q, k[:, :, :cut], v[:, :, :cut], lengths, block=BLOCK, mi=mi,
                k_scale=None if ks is None else ks[:, :cut],
                v_scale=None if vs is None else vs[:, :cut])
    np.testing.assert_array_equal(got, np.asarray(short))


@pytest.mark.parametrize("ndim", [1, 2])
@pytest.mark.parametrize("case", sorted(FURTHEST))
def test_blocks_past_the_bound_are_not_read(case, ndim):
    """NaN in every row past the bound's last block reaches no output
    (a visited block would multiply it by 0, which is NaN); NaN in the
    last row inside the bound does where that row is past the horizon:
    the bound is a whole number of blocks and no tighter."""
    furthest = FURTHEST[case]
    q, k, v, _, _, lengths = _inputs(furthest, ndim, "")
    cut = -(-furthest // BLOCK) * BLOCK
    got = decode_attention(q, k, v.at[:, :, cut:].set(jnp.nan), lengths,
                           block=BLOCK)
    assert np.isfinite(np.asarray(got)).all()
    inside = decode_attention(q, k, v.at[:, :, cut - 1].set(jnp.nan),
                              lengths, block=BLOCK)
    assert not np.isfinite(np.asarray(inside)[0]).all()


def test_no_live_row_is_no_visit_and_zeros():
    """Every length 0 (a decode step with every slot idle): no block is
    visited, and the result is the whole walk's zeros."""
    q, k, v, _, _, _ = _inputs(1, 1, "")
    lengths = jnp.zeros((S,), jnp.int32)
    got = decode_attention(q, k, jnp.full_like(v, jnp.nan), lengths,
                           block=BLOCK)
    np.testing.assert_array_equal(np.asarray(got), 0.0)
    np.testing.assert_array_equal(
        np.asarray(_whole_walk(q, k, v, lengths, BLOCK, False)), 0.0)


def _loops(fn, *args):
    """[(primitive, static trip count or None)] of the loops in ``fn``'s
    jaxpr: a ``fori_loop`` with constant bounds is a ``scan`` of that
    length, one with a traced bound a ``while``."""
    return [(e.primitive.name, e.params.get("length"))
            for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns
            if e.primitive.name in ("scan", "while")]


def test_trip_count_is_data_and_positions_keep_the_whole_walk():
    """One loop either way.  Without ``k_positions`` its trip count is
    traced (no shape or constant of the program depends on the lengths:
    one executable a bucket); with them (the ring gather: a row's index
    says nothing of its horizon) it is the constant ``Tcap / block``,
    and blocks past ``max(lengths)`` that hold visible positions count."""
    q, k, v, _, _, lengths = _inputs(BLOCK, 2, "")

    def plain(q, k, v, lengths):
        return decode_attention(q, k, v, lengths, block=BLOCK)

    assert _loops(plain, q, k, v, lengths) == [("while", None)]
    # a ring in rotated order: the newest rows lie in the LAST block
    pos = jnp.broadcast_to((jnp.arange(CAP) + BLOCK) % CAP, (S, CAP))

    def labelled(q, k, v, lengths, pos):
        return decode_attention(q, k, v, lengths, block=BLOCK,
                                k_positions=pos)

    assert _loops(labelled, q, k, v, lengths, pos) == [("scan", NBLK)]
    got = labelled(q, k, v, lengths, pos)
    order = np.argsort(np.asarray(pos[0]))
    want = plain(q, k[:, :, order], v[:, :, order], lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6,
                               atol=1e-6)
    poisoned = labelled(q, k, v.at[:, :, -1].set(jnp.nan), lengths, pos)
    assert not np.isfinite(np.asarray(poisoned)).all()
