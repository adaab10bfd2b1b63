"""The GraniteMoeHybrid block in the serving runtime
(``serve/granite_hybrid.py``: Mamba-2 layers whose state and convolution
context the cache keeps a slot, grouped-query attention layers over K/V
pages, no positions), held to the plain reference the benchmark keeps,
``benchmark/references/granite_hybrid_lm.py``, loaded from its path: one
reference in the repo, and it runs the recurrence token by token.  Toy
widths, seeded weights, logits compared.

Tolerances, each with its reason:

* ``LIMIT_SPACINGS`` (tests/closeness.py, 32 float32 spacings at the
  row's largest logit) wherever two programs compute the same sums in
  another order: the session's executables against the reference, the
  chunked scan against the recurrence (``(C B^T * L) (dt x)`` against
  ``h_t C_t``, decays as ``exp`` of a difference of cumulative sums
  against a running product), chunked against one-piece prefill, the
  convolution by a carried context against zero rows in front.
  tests/conftest.py sets full-precision matmuls, so what is left is
  float32 rounding: the largest reading over the cases below and 12
  seeds was 2.0; a state left from the request before, a convolution
  context taken from a bucket's padded tail, a position off by one read
  in the thousands and more (``test_the_comparison_can_fail``).
* The ops-level comparisons (``ops/mamba2.py`` against a loop written
  here) hold outputs and states to 2e-5 of their largest magnitude: the
  same reorderings, over values of order one.
* Scheduler runs return tokens only, and an argmax over random weights
  may turn on a last bit: a served token's logit has to lie within 1e-5
  of the row's spread below the reference's best (the benchmark's
  ``served_token_gap``), which an equal logit meets and a wrong row
  misses by four orders.
* Weight-only int8 is another model: it has to serve, and to land
  beyond the float32 limit and short of a wrong model.
"""
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import serve
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops import mamba2
from mxnet_tpu.serve import granite_hybrid
from mxnet_tpu.serve import model as serve_model
from mxnet_tpu.serve.kv_cache import kv_pool_shape
from mxnet_tpu.serve.scheduler import Request, Scheduler

from closeness import (LIMIT_SPACINGS, assert_close_across_executables,
                       spacings_apart)
from serve_util import lend

_REF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark", "references", "granite_hybrid_lm.py")
_spec = importlib.util.spec_from_file_location("granite_hybrid_lm_reference",
                                               _REF)
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

PAGE, CHUNK = 8, 8
# the reference's configuration: the published config.json's keys
HF = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
          shared_intermediate_size=96, vocab_size=97, num_hidden_layers=4,
          layer_types=("mamba", "attention", "mamba", "mamba"),
          mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
          mamba_n_groups=2, mamba_d_conv=4, mamba_chunk_size=CHUNK,
          embedding_multiplier=12.0, attention_multiplier=0.0625,
          residual_multiplier=0.22, logits_scaling=8.0, rms_norm_eps=1e-5,
          max_position_embeddings=128, tie_word_embeddings=True)
MAMBA_LAYERS = HF["layer_types"].count("mamba")
CONV_DIM = 8 * 16 + 2 * 2 * 16


def model_config(hf):
    return serve.ModelConfig(
        block="granitemoehybrid", vocab_size=hf["vocab_size"],
        num_layers=hf["num_hidden_layers"], d_model=hf["hidden_size"],
        num_heads=hf["num_attention_heads"],
        num_key_value_heads=hf["num_key_value_heads"],
        max_len=hf["max_position_embeddings"],
        rms_norm_eps=hf["rms_norm_eps"], d_ff=hf["shared_intermediate_size"],
        layer_types=tuple(hf["layer_types"]),
        mamba_n_heads=hf["mamba_n_heads"], mamba_d_head=hf["mamba_d_head"],
        mamba_d_state=hf["mamba_d_state"],
        mamba_n_groups=hf["mamba_n_groups"], mamba_d_conv=hf["mamba_d_conv"],
        mamba_chunk_size=hf["mamba_chunk_size"],
        embedding_multiplier=hf["embedding_multiplier"],
        attention_multiplier=hf["attention_multiplier"],
        residual_multiplier=hf["residual_multiplier"],
        logits_scaling=hf["logits_scaling"],
        tie_word_embeddings=hf["tie_word_embeddings"])


CFG = model_config(HF)


@functools.lru_cache(maxsize=None)
def _jitted_reference(hf_items):
    hf = dict(hf_items)
    return jax.jit(lambda params, seq: reference.logits(params, seq, hf))


def ref_logits(params, seq, hf=HF):
    """The reference's (len(seq), vocab) logits.  One compilation a
    configuration: the sequence is padded to 64 tokens, which a causal
    model's earlier rows cannot see."""
    padded = jnp.asarray(list(seq) + [0] * (64 - len(seq)), jnp.int32)
    return np.asarray(_jitted_reference(tuple(sorted(hf.items())))(
        params, padded))[:len(seq)]


def tokens(seed, n):
    return np.random.default_rng(seed).integers(
        0, HF["vocab_size"], n).tolist()


@pytest.fixture(scope="module")
def params():
    return serve_model.init_params(CFG, seed=3)


def session(params, **over):
    conf = dict(slots=3, page_size=PAGE, buckets=(16, 32), max_new=16,
                exact=False)
    conf.update(over)
    return serve.InferenceSession(params, model=CFG,
                                  config=serve.ServeConfig(**conf))


@pytest.fixture(scope="module")
def _plain(params):
    return session(params)


@pytest.fixture
def plain(_plain):
    yield from lend(_plain)


# -- the layer's three pieces (ops/mamba2.py) ------------------------------

def _layer_inputs(seed, t, heads=4, width=8, state=16, groups=2):
    rs = np.random.RandomState(seed)
    f = lambda *shape: jnp.asarray(rs.randn(*shape).astype(np.float32))
    dt = jax.nn.softplus(f(t, heads) - 2.0)
    a = -jnp.exp(jnp.asarray(rs.uniform(0.0, 2.5, heads).astype(np.float32)))
    return (f(t, heads, width), dt, a, f(t, groups, state),
            f(t, groups, state), f(heads, width, state))


def _token_by_token(x, dt, a, b, c, state):
    """The recurrence as ``ssd_step`` runs it, one row at a time."""
    ys = []
    for i in range(x.shape[0]):
        y, state = mamba2.ssd_step(x[i:i + 1], dt[i:i + 1], a, b[i:i + 1],
                                   c[i:i + 1], state[None])
        ys.append(y[0])
        state = state[0]
    return jnp.stack(ys), state


def _near(got, want, what):
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= 2e-5 * scale, what


@pytest.mark.parametrize("rows, chunk, real", [
    (24, 8, 24),     # three whole chunks: two boundaries crossed
    (24, 8, 13),     # bucket padding from the middle of the second chunk
    (21, 8, 21),     # not whole chunks: the scan pads with identities
    (16, 256, 9)])   # one chunk wider than the bucket (chunk 256, bucket 128)
def test_chunked_scan_is_the_recurrence(rows, chunk, real):
    """Across chunk boundaries, from a non-zero carried state, with
    padded rows: outputs of the real rows and the state after the last
    real row are the token-by-token recurrence's."""
    x, dt, a, b, c, state0 = _layer_inputs(rows + chunk, rows)
    dt = jnp.where(jnp.arange(rows)[:, None] < real, dt, 0.0)
    y, state = mamba2.ssd_chunked_scan(x, dt, a, b, c, state0, chunk)
    want_y, want_state = _token_by_token(x[:real], dt[:real], a, b[:real],
                                         c[:real], state0)
    _near(y[:real], want_y, "outputs")
    _near(state, want_state, "the state after the last real row")
    # the carried state matters: from zero the same rows read otherwise
    cold, _ = mamba2.ssd_chunked_scan(x, dt, a, b, c, 0 * state0, chunk)
    assert float(jnp.max(jnp.abs(cold[:real] - want_y))) > 1e-2


def test_chunked_scan_loops_over_chunks_not_tokens():
    """Prefill's form is matmul-shaped: the one sequential pass is over
    the chunks (``lax.scan`` of length rows / chunk), nothing over rows."""
    args = _layer_inputs(0, 64)

    def loops(jaxpr):
        found = []
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in ("scan", "while"):
                found.append(eqn.params.get("length"))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found += loops(sub)
        return found

    jaxpr = jax.make_jaxpr(
        lambda *a: mamba2.ssd_chunked_scan(*a, chunk=16))(*args)
    assert loops(jaxpr.jaxpr) == [4]


@pytest.mark.parametrize("real", [16, 11, 2, 0])
def test_conv_context_is_the_last_real_rows(real):
    """A chunk's convolution reads the carried context in front of it,
    and what it carries on is the last ``taps - 1`` REAL pre-activation
    rows (reaching into the old context where the chunk is shorter), not
    the bucket's tail."""
    rs = np.random.RandomState(real)
    rows, context = (jnp.asarray(rs.randn(*s).astype(np.float32))
                     for s in ((16, 6), (3, 6)))
    weight, bias = (jnp.asarray(rs.randn(*s).astype(np.float32))
                    for s in ((6, 4), (6,)))
    out, carried = mamba2.causal_conv(rows, context, weight, bias, real)
    whole = np.concatenate([np.asarray(context), np.asarray(rows)])
    want = np.asarray(bias) + sum(
        whole[j:j + 16] * np.asarray(weight)[:, j] for j in range(4))
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(carried), whole[real:real + 3])
    # one row a slot, as decode runs it: the same filter, the window moved
    step, moved = mamba2.conv_step(rows[:1], context[None], weight, bias)
    np.testing.assert_allclose(np.asarray(step), want[:1], rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(moved[0]), whole[1:4])


# -- the block against the reference ---------------------------------------

def test_params_are_the_references_spec(params):
    want = {k: tuple(v) for k, v in reference.spec(HF).items()}
    assert {k: tuple(v.shape) for k, v in params.items()} == want
    assert granite_hybrid.param_shapes(CFG) == want
    # decays a token from ~0.2 to ~0.999: a state that is neither
    # forgotten at once nor frozen
    a = -np.exp(np.asarray(params["blk0_A_log"]))
    dt = np.log1p(np.exp(np.asarray(params["blk0_dt_bias"])))
    decay = np.exp(dt * a)
    assert 0.15 < decay.min() < 0.5 and 0.99 < decay.max() < 1.0


@pytest.mark.parametrize("exact", [False, True])
@pytest.mark.parametrize("seed", [0, 1])
def test_full_forward_matches_reference(params, exact, seed):
    seq = tokens(seed, 40)          # five chunks of 8
    got = np.asarray(serve_model.full_forward(
        params, jnp.asarray([seq], jnp.int32), CFG, exact=exact))[0]
    assert_close_across_executables(got, ref_logits(params, seq))


@pytest.mark.parametrize("exact", [False, True])
def test_prefill_then_decode_through_the_cache(params, exact):
    """Three prompts of different lengths share the decode batch; every
    logits row the session returns, at every served position, is the
    reference's full forward's row."""
    sess = session(params, exact=exact)
    assert sorted(sess.executables) == ["decode", "prefill_16", "prefill_32"]
    seqs, slots = [], []
    for i, n in enumerate((5, 16, 27)):
        p = tokens(10 + i, n)
        slot = sess.try_alloc(n, 8, tokens=p)
        first, logits = sess.prefill(slot, p)
        assert_close_across_executables(np.asarray(logits),
                                        ref_logits(params, p)[-1])
        seqs.append(p + [first])
        slots.append(slot)
    for _ in range(6):
        toks, logits = sess.step()
        logits = np.asarray(logits)
        for slot, seq in zip(slots, seqs):
            assert_close_across_executables(
                logits[slot], ref_logits(params, seq)[-1])
            seq.append(toks[slot])
    assert sess.fallback_count() == 0


def test_a_prompt_longer_than_the_largest_bucket_carries_state(params,
                                                               plain):
    """A transcript of 45 tokens runs as chunks of 32 and 13: the second
    takes up the state and the convolution context the first wrote.  The
    same tokens in one piece (a session with a bucket that holds them)
    and the reference give the same last row, and the decode steps that
    follow go on from the carried state."""
    seq = tokens(21, 45)
    slot = plain.try_alloc(len(seq), 3, tokens=seq, resume=True)
    before = plain.block_report()
    first, chunked = plain.prefill(slot, seq)
    after = plain.block_report()
    assert after["prefill_chunks"] - before["prefill_chunks"] == 2
    assert after["prefills_from_zero"] - before["prefills_from_zero"] == 1
    assert after["prefills_carried"] - before["prefills_carried"] == 1
    whole = session(params, buckets=(48,), max_new=16)
    wslot = whole.try_alloc(len(seq), 3, tokens=seq)
    _, one_piece = whole.prefill(wslot, seq)
    assert_close_across_executables(np.asarray(chunked),
                                    np.asarray(one_piece))
    assert_close_across_executables(np.asarray(chunked),
                                    ref_logits(params, seq)[-1])
    seq = seq + [first]
    for _ in range(2):
        toks, logits = plain.step()
        assert_close_across_executables(np.asarray(logits)[slot],
                                        ref_logits(params, seq)[-1])
        seq.append(toks[slot])


def _serve_one(sess, prompt, steps):
    """Prefill ``prompt`` into the lowest free slot and decode ``steps``
    steps; -> (slot, the logits rows returned, the sequence)."""
    slot = sess.try_alloc(len(prompt), 8, tokens=prompt)
    first, logits = sess.prefill(slot, prompt)
    rows, seq = [np.asarray(logits)], list(prompt) + [first]
    for _ in range(steps):
        toks, logits = sess.step()
        rows.append(np.asarray(logits)[slot])
        seq.append(toks[slot])
    return slot, rows, seq


def test_a_slot_admitted_again_starts_from_zero_state(params, plain):
    """A slot that served one request and is admitted again gives the
    second request the rows a session that never served gives it:
    ``alloc`` zeroes the state and the convolution context."""
    slot, _, _ = _serve_one(plain, tokens(50, 30), 5)
    assert float(jnp.abs(plain.cache.pools["ssm_state"][:, slot]).max()) > 0
    plain.release(slot)
    again, rows, seq = _serve_one(plain, tokens(51, 19), 4)
    assert again == slot
    fresh_slot, fresh_rows, fresh_seq = _serve_one(session(params),
                                                   tokens(51, 19), 4)
    assert fresh_slot == slot and fresh_seq == seq
    for got, want in zip(rows, fresh_rows):
        assert_close_across_executables(got, want)
    assert_close_across_executables(rows[-1], ref_logits(params, seq[:-1])[-1])


def served_gap(params, prompt, served):
    """How far a served token's logit lies below the reference's best, as
    a share of the row's spread; the widest over the stream."""
    rows = ref_logits(params, prompt + served[:-1])[len(prompt) - 1:]
    picked = rows[np.arange(len(served)), served]
    return float(((rows.max(-1) - picked)
                  / (rows.max(-1) - rows.min(-1))).max())


def test_preempt_and_reprefill_rebuilds_both_states(params):
    """A 5-page pool under three growing requests has to preempt; every
    resumed request is admitted to a zeroed slot and re-prefills its
    transcript, which rebuilds its state and its convolution context
    cold, and its stream is the uninterrupted one: the reference's."""
    sess = session(params, buckets=(8, 16), max_new=8, num_pages=5,
                   oversub=True)
    reqs = [Request(rid=i, prompt=tokens(30 + i, 8), max_new=6,
                    arrival_s=0.0) for i in range(3)]
    sched = Scheduler(sess, policy="continuous")
    done, _ = sched.run(reqs)
    assert sched.stats["preemptions"] > 0
    assert sched.stats["resumes"] == sched.stats["preemptions"]
    for r in done:
        assert not r.failed, r.error
        assert len(r.tokens) == r.max_new
        assert served_gap(params, list(r.prompt), list(r.tokens)) <= 1e-5
    assert sess.cache.free_slots == sess.config.slots


def test_sixteen_slots_turn_over_under_the_scheduler(params):
    """Forty requests of mixed lengths through sixteen slots: every slot
    is admitted to several times, every stream is the reference's, and
    the block's device counters equal the counts made here."""
    sess = session(params, slots=16, max_new=12)
    rng = np.random.default_rng(7)
    reqs = [Request(rid=i, prompt=tokens(100 + i, int(rng.integers(3, 33))),
                    max_new=int(rng.integers(2, 13)), arrival_s=0.0)
            for i in range(40)]
    steps = []
    inner = sess.step
    # every step launched is read by one call, a step ahead or not
    sess.step = lambda **how: steps.append(1) or inner(**how)
    done, _ = Scheduler(sess, policy="continuous").run(reqs)
    assert len(done) == 40
    for r in done:
        assert not r.failed, r.error
        assert len(r.tokens) == r.max_new
        assert served_gap(params, list(r.prompt), list(r.tokens)) <= 1e-5
    report = sess.block_report()
    buckets = [16 if len(r.prompt) <= 16 else 32 for r in reqs]
    assert report == {
        "decode_steps": len(steps), "prefill_chunks": 40,
        "rows_valid": sum(len(r.prompt) for r in reqs),
        "rows_padded": sum(buckets) - sum(len(r.prompt) for r in reqs),
        "prefills_from_zero": 40, "prefills_carried": 0,
        "mamba_layers": MAMBA_LAYERS, "attention_layers": 1,
        "state_bytes_per_slot": MAMBA_LAYERS * 4 * (8 * 16 * 16
                                                    + 3 * CONV_DIM)}
    assert sess.moe_report() == report       # the name the routers had
    assert sess.fallback_count() == 0
    assert sess.cache.free_slots == 16


# -- the cache, the surface, the refusals -----------------------------------

def test_pools_are_pages_for_attention_and_state_for_mamba(plain):
    cache, conf = plain.cache, plain.config
    pages = conf.slots * conf.max_pages_per_slot
    # key/value heads of 16 are narrower than a lane tile: the cache
    # folds them into the pools' last axis
    kv = kv_pool_shape(1, pages + 1, PAGE, HF["num_key_value_heads"], 16)
    assert kv == (1, pages + 1, PAGE, HF["num_key_value_heads"] * 16)
    assert cache.kv_lanes == plain.decode_report()["kv_lanes"] == kv[-1]
    assert {n: tuple(p.shape) for n, p in cache.pools.items()} == {
        "k_pool": kv, "v_pool": kv,
        "ssm_state": (MAMBA_LAYERS, conf.slots, 8, 16, 16),
        "conv_state": (MAMBA_LAYERS, conf.slots, 3, CONV_DIM)}
    assert cache.state == ("ssm_state", "conv_state")
    assert cache.paged == ("k_pool", "v_pool") and cache.hybrid
    assert cache.pool_bytes() == plain.state_report()["pool_bytes"] \
        == sum(p.nbytes for p in cache.pools.values())
    assert list(plain.counters) == ["ssm_stats"]
    assert plain.decode_report()["steps"] == plain.block_report()[
        "decode_steps"]
    # a cache with recurrent state keeps no prefix index
    assert cache.register_prefix is not None and not cache._index


@pytest.mark.parametrize("conf", [dict(spec_k=2), dict(kv_quant="int8")])
def test_unsupported_combinations_are_refused(params, conf):
    with pytest.raises(MXNetError, match="does not support"):
        session(params, **conf)


@pytest.mark.parametrize("wrong, says", [
    (dict(mamba_d_state=8), "architecture says"),
    (dict(num_key_value_heads=4), "architecture says"),
    (dict(layer_types=("mamba",) * 3), "layer_types"),
    (dict(attention_multiplier=0.0), "attention_multiplier"),
    (dict(tie_word_embeddings=False), "untied head")])
def test_a_wrong_architecture_is_refused(params, wrong, says):
    with pytest.raises(MXNetError, match=says):
        serve.InferenceSession(
            params, model=model_config(dict(HF, **wrong)),
            config=serve.ServeConfig(page_size=PAGE, buckets=(16,)))


def test_weight_only_int8_serves_the_block(params):
    """The cell's control: the quantized session runs, and lands where a
    lower precision lands, off the float32 reference by more than the
    float32 limit and by less than a wrong model."""
    sess = session(params, quant="int8")
    seq = tokens(42, 20)
    slot = sess.try_alloc(len(seq), 4, tokens=seq)
    first, logits = sess.prefill(slot, seq)
    gaps = [spacings_apart(np.asarray(logits), ref_logits(params, seq)[-1])]
    _, logits = sess.step()
    gaps.append(spacings_apart(np.asarray(logits)[slot],
                               ref_logits(params, seq + [first])[-1]))
    assert all(30 * LIMIT_SPACINGS < gap < 1e6 for gap in gaps), gaps


def test_the_comparison_can_fail(plain, params, monkeypatch):
    """The planted faults the limit has to catch: a slot admitted over
    the state the request before it left, a convolution context taken
    from the bucket's padded tail, a decode step at the wrong position
    (which only the attention layers can see: nothing else knows one)."""
    slot, _, _ = _serve_one(plain, tokens(60, 30), 3)
    plain.release(slot)
    monkeypatch.setattr(plain.cache, "_scrub_state", lambda slot: None)
    short = tokens(61, 5)     # read 47 929; after 19 tokens still 678
    again = plain.try_alloc(len(short), 8, tokens=short)
    assert again == slot
    _, logits = plain.prefill(again, short)
    assert spacings_apart(np.asarray(logits),
                          ref_logits(params, short)[-1]) > 1e3
    monkeypatch.undo()
    plain.release(again)
    seq = tokens(61, 19)

    # the bucket's tail in place of the last real rows
    monkeypatch.setattr(
        granite_hybrid, "causal_conv",
        lambda rows, context, weight, bias, length: mamba2.causal_conv(
            rows, context, weight, bias, rows.shape[0]))
    tail = session(params)
    slot = tail.try_alloc(len(seq), 8, tokens=seq)
    first, _ = tail.prefill(slot, seq)        # 19 real rows of a bucket of 32
    _, logits = tail.step()
    want = ref_logits(params, seq + [first])[-1]
    assert spacings_apart(np.asarray(logits)[slot], want) > 1e3
    monkeypatch.undo()

    slot = plain.try_alloc(len(seq), 8, tokens=seq)
    first, _ = plain.prefill(slot, seq)
    plain.cache.lengths[slot] -= 1
    _, logits = plain.step()
    assert spacings_apart(np.asarray(logits)[slot], want) > 1e3
