"""The Phi-4-flash (SambaY) block in the serving runtime
(``serve/phi4flash.py``: Mamba-1 layers whose state the cache keeps a slot,
window differential attention on rings, one full-attention layer whose
pages every later cross-attention layer reads, gated memory units on one
Mamba layer's scan output; ``ops/mamba1.py``), held to the plain reference
the benchmark keeps, ``benchmark/references/phi4flash_lm.py``, loaded from
its path: one reference in the repo, and it runs the recurrence token by
token, every row through every layer and a pair's two softmaxes as two.
Toy widths, seeded weights, logits compared.

Tolerances, each with its reason:

* ``LIMIT_SPACINGS`` (tests/closeness.py, 32 float32 spacings at the
  row's largest logit) wherever two programs compute the same sums in
  another order: the session's executables against the reference (a
  chunk's blocks and a ring against one T x T softmax, the 128-wide
  identity's zero products against none, the prefill's one row through
  the second half against every row), chunked against one-piece prefill.
  tests/conftest.py sets full-precision matmuls, so what is left is
  float32 rounding: the largest reading over the cases below and 8 seeds
  was 9; every planted fault of ``test_the_comparison_can_fail`` reads in
  the hundreds and more.
* The ops-level comparisons (``ops/mamba1.py`` against a loop written
  here, the kernel in the interpreter against the scan) hold outputs and
  states to 2e-5 of their largest magnitude: elementwise float32, the same
  order of operations but for ``exp``'s own rounding.
* Scheduler runs return tokens only, and an argmax over random weights
  may turn on a last bit: a served token's logit has to lie within 1e-5
  of the row's spread below the reference's best (the benchmark's
  ``served_token_gap``).
* Weight-only int8 is another model: it has to serve, and to land beyond
  the float32 limit and short of a wrong model.
"""
import dataclasses
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import serve
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops import mamba1
from mxnet_tpu.serve import kv_cache, phi4flash
from mxnet_tpu.serve import model as serve_model
from mxnet_tpu.serve.scheduler import Request, Scheduler

from closeness import (LIMIT_SPACINGS, assert_close_across_executables,
                       spacings_apart)
from serve_util import lend

_REF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark", "references", "phi4flash_lm.py")
_spec = importlib.util.spec_from_file_location("phi4flash_lm_reference",
                                               _REF)
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

PAGE, LAYERS, WINDOW = 8, 8, 8
# the reference's configuration: the published config.json's keys and the
# Mamba-1 sizes beside them; 8 published heads of 8 are 4 differential
# heads of 16 over 2 key/value pairs
HF = dict(hidden_size=64, num_attention_heads=8, num_key_value_heads=4,
          intermediate_size=96, vocab_size=97, num_hidden_layers=LAYERS,
          layer_types=phi4flash.layer_rule(LAYERS), sliding_window=WINDOW,
          layer_norm_eps=1e-5, mamba_d_state=4, mamba_d_conv=4,
          mamba_expand=2, mamba_dt_rank=4, max_position_embeddings=128)
D_INNER = 128


def model_config(hf):
    return serve.ModelConfig(
        block="phi4flash", vocab_size=hf["vocab_size"],
        num_layers=hf["num_hidden_layers"], d_model=hf["hidden_size"],
        num_heads=hf["num_attention_heads"] // 2,
        num_key_value_heads=hf["num_key_value_heads"] // 2,
        max_len=hf["max_position_embeddings"],
        d_ff=hf["intermediate_size"], layer_types=tuple(hf["layer_types"]),
        sliding_window=hf["sliding_window"],
        mamba_d_state=hf["mamba_d_state"], mamba_d_conv=hf["mamba_d_conv"],
        mamba_expand=hf["mamba_expand"], mamba_dt_rank=hf["mamba_dt_rank"],
        layer_norm_eps=hf["layer_norm_eps"],
        rms_norm_eps=hf["layer_norm_eps"], tie_word_embeddings=True)


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


# -- the scan (ops/mamba1.py) ----------------------------------------------

def _scan_inputs(seed, t, di=32, n=4):
    rs = np.random.RandomState(seed)
    f = lambda *shape: jnp.asarray(rs.randn(*shape).astype(np.float32))
    return (f(t, di), jax.nn.softplus(f(t, di) - 2.0),
            -jnp.exp(jnp.asarray(rs.uniform(0.0, 2.5, (di, n)
                                            ).astype(np.float32))),
            f(t, n), f(t, n), f(di), f(n, di))


def _token_by_token(x, dt, a, b, c, d, state, real):
    """The recurrence one row at a time, in numpy, the state (d_inner,
    N) as the equations write it."""
    x, dt, a, b, c, d = (np.asarray(v, np.float64)
                         for v in (x, dt, a, b, c, d))
    h = np.asarray(state, np.float64).T.copy()
    ys = []
    for t in range(real):
        h = np.exp(dt[t][:, None] * a) * h \
            + (dt[t] * x[t])[:, None] * b[t][None, :]
        ys.append((h * c[t][None, :]).sum(-1) + d * x[t])
    return np.array(ys), h.T


def _near(got, want, what):
    scale = float(np.max(np.abs(want)))
    assert float(np.max(np.abs(np.asarray(got) - want))) <= 2e-5 * scale, \
        what


@pytest.mark.parametrize("rows, real, carried", [
    (24, 24, True),      # a whole bucket from a carried state
    (24, 13, True),      # bucket padding: identities of the recurrence
    (24, 13, False),     # from zero
    (8, 0, True)])       # nothing real: the state comes back as it went
def test_selective_scan_is_the_recurrence(rows, real, carried):
    x, dt, a, b, c, d, state0 = _scan_inputs(rows + real, rows)
    if not carried:
        state0 = 0 * state0
    y, state = mamba1.selective_scan(x, dt, a, b, c, d, state0, real)
    want_y, want_state = _token_by_token(x, dt, a, b, c, d, state0, real)
    if real:
        _near(y[:real], want_y, "outputs")
    _near(state, want_state, "the state after the last real row")
    if carried and real:
        # the carried state matters: from zero the same rows read otherwise
        cold, _ = mamba1.selective_scan(x, dt, a, b, c, d, 0 * state0, real)
        assert float(jnp.max(jnp.abs(cold[:real] - want_y))) > 1e-2
    # one token a slot, as decode runs it: the same recurrence
    step_y, step_state = mamba1.selective_step(
        x[:1], dt[:1], a, b[:1], c[:1], d, state0[None])
    one_y, one_state = _token_by_token(x, dt, a, b, c, d, state0, 1)
    _near(step_y, one_y, "a decode step's output")
    _near(step_state[0], one_state, "a decode step's state")


@pytest.mark.parametrize("rows, di, n, real", [
    (256, 1024, 16, 200),    # two row blocks, two channel tiles
    (32, 256, 4, 32),        # one block, one tile of two lane tiles
    (24, 128, 8, 9)])        # rows that are no whole row block
def test_the_scan_kernel_in_the_interpreter_is_the_scan(rows, di, n, real):
    """The Pallas form (what a TPU runs) against the plain XLA form, from
    a carried state and with bucket padding."""
    from jax.experimental.pallas import tpu as pltpu

    x, dt, a, b, c, d, state0 = _scan_inputs(rows, rows, di, n)
    want_y, want_state = mamba1.selective_scan(x, dt, a, b, c, d, state0,
                                               real)
    with pltpu.force_tpu_interpret_mode():
        y, state = mamba1.scan_kernel_interpreted(x, dt, a, b, c, d, state0,
                                                  real)
    _near(y[:real], np.asarray(want_y[:real]), "outputs")
    _near(state, np.asarray(want_state), "the state")
    assert not mamba1.scan_kernel_eligible(x, a)    # no TPU here: the scan


def test_the_scan_walks_rows_and_holds_no_rows_by_state_array():
    """The XLA form is one loop over the rows whose carry is the state:
    nothing of rows x d_inner x N is ever built."""
    args = _scan_inputs(0, 64)
    jaxpr = jax.make_jaxpr(lambda *v: mamba1.selective_scan(*v, 64))(*args)

    def loops(j):
        found = []
        for eqn in j.eqns:
            if eqn.primitive.name == "scan":
                found.append(eqn.params["length"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found += loops(sub)
        return found

    assert loops(jaxpr.jaxpr) == [64]
    assert max(int(np.prod(v.aval.shape)) for eqn in jaxpr.jaxpr.eqns
               for v in eqn.outvars) <= 64 * 32


# -- differential attention as grouped-query attention ----------------------

def test_the_wide_identity_is_the_four_softmax_definition():
    """Two key/value pairs of two query pairs each: every pair's two
    softmaxes written out (four a key/value pair) against the rows
    ``[q_1 | 0]`` and ``[0 | q_2]`` over ``K = [k_1 | k_2]`` with the scale
    stated."""
    rs = np.random.RandomState(5)
    t, hd = 12, 16
    cfg = dataclasses.replace(CFG, d_model=4 * hd)
    q, k, v = (rs.randn(t, n, hd).astype(np.float64) for n in (4, 2, 2))
    seen = np.tril(np.ones((t, t), bool))
    half = hd // 2

    def softmax(scores):
        scores = np.where(seen, scores, -np.inf)
        e = np.exp(scores - scores.max(-1, keepdims=True))
        return e / e.sum(-1, keepdims=True)

    want = np.zeros((t, 4, 2, hd))
    for pair in range(4):
        kp, vp = k[:, pair // 2], v[:, pair // 2]
        for s, cols in enumerate((slice(0, half), slice(half, hd))):
            want[:, pair, s] = softmax(
                q[:, pair, cols] @ kp[:, cols].T / np.sqrt(half)) @ vp
    rows = np.asarray(phi4flash._query_rows(
        jnp.asarray(q.reshape(t, -1), jnp.float32), cfg))
    assert rows.shape == (t, 2, 4, hd)
    got = np.zeros((t, 2, 4, hd))
    for kv in range(2):
        for r in range(4):
            got[:, kv, r] = softmax(rows[:, kv, r].astype(np.float64)
                                    @ k[:, kv].T
                                    * phi4flash._score_scale(cfg)) @ v[:, kv]
    np.testing.assert_allclose(got.reshape(t, 4, 2, hd), want, rtol=1e-6,
                               atol=1e-6)


# -- the block against the reference ---------------------------------------

def test_params_are_the_references_spec(params):
    want = {k: tuple(v) for k, v in reference.spec(HF).items()}
    assert {k: tuple(v.shape) for k, v in params.items()} == want
    assert phi4flash.param_shapes(CFG) == want
    # decays a token from ~0.7 to ~0.999: a state that is neither
    # forgotten at once nor frozen
    a = -np.exp(np.asarray(params["blk0_A_log"]))
    dt = np.log1p(np.exp(np.asarray(params["blk0_dt_bias"])))
    decay = np.exp(dt[:, None] * a)
    assert 0.5 < decay.min() < 0.8 and 0.998 < decay.max() < 1.0
    # a bias that is not zero, and a lambda that is not lambda_init
    assert float(jnp.abs(params["blk1_qkv_b"]).max()) > 0.01
    assert abs(float(phi4flash._lambda(params, "blk1_", 1))
               - phi4flash.lambda_init(1)) > 1e-3


def test_the_layer_rule_is_the_published_one():
    assert phi4flash.layer_rule(8) == (
        "mamba", "sliding_attention", "mamba", "sliding_attention", "mamba",
        "full_attention", "gmu", "cross_attention")
    rule = phi4flash.layer_rule(32)
    assert [rule.count(k) for k in phi4flash.KINDS] == [9, 8, 1, 7, 7]
    assert rule[16:18] == ("mamba", "full_attention")
    cfg = dataclasses.replace(CFG, num_layers=32, layer_types=rule)
    assert (phi4flash.memory_layer(cfg), phi4flash.owner_layer(cfg)) \
        == (16, 17)
    assert cfg.kinds.count("shared") == 14 and cfg.kinds.count("full") == 1
    with pytest.raises(MXNetError, match="multiple of 4"):
        phi4flash.layer_rule(10)


@pytest.mark.parametrize("exact", [False, True])
@pytest.mark.parametrize("seed", [0, 1])
def test_full_forward_matches_reference(params, exact, seed):
    seq = tokens(seed, 40)          # five windows of 8
    got = np.asarray(serve_model.full_forward(
        params, jnp.asarray([seq], jnp.int32), CFG, exact=exact))[0]
    assert_close_across_executables(got, ref_logits(params, seq))


@pytest.mark.parametrize("exact", [False, True])
def test_prefill_then_decode_through_the_cache(params, exact):
    """Three prompts of different lengths share the decode batch; every
    logits row the session returns, at every served position, is the
    reference's full forward's row (which ran every row through every
    layer, where the prefill ran the second half for the last row)."""
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
    report = sess.block_report()
    # one row a prompt ran the second half, of 48 that were prefilled
    assert (report["cross_rows"], report["rows_valid"]) == (3, 48)


def test_a_prompt_longer_than_the_largest_bucket_carries_everything(params,
                                                                    plain):
    """A transcript of 45 tokens runs as chunks of 32 and 13: the second
    takes up the state and the convolution context, the rings (five
    windows back) and the pages the first wrote.  The same tokens in one
    piece and the reference give the same last row, and the decode steps
    that follow go on from all three."""
    seq = tokens(21, 45)
    slot = plain.try_alloc(len(seq), 3, tokens=seq, resume=True)
    before = plain.block_report()
    first, chunked = plain.prefill(slot, seq)
    after = plain.block_report()
    assert after["prefill_chunks"] - before["prefill_chunks"] == 2
    assert after["prefills_from_zero"] - before["prefills_from_zero"] == 1
    assert after["prefills_carried"] - before["prefills_carried"] == 1
    # the second half ran for one row a CHUNK: a chunk is not told
    # whether it ends its prompt
    assert after["cross_rows"] - before["cross_rows"] == 2
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
    slot, _, _ = _serve_one(plain, tokens(50, 30), 5)
    assert float(jnp.abs(plain.cache.pools["ssm_state"][:, slot]).max()) > 0
    plain.release(slot)
    again, rows, seq = _serve_one(plain, tokens(51, 19), 4)
    assert again == slot
    assert_close_across_executables(rows[-1],
                                    ref_logits(params, seq[:-1])[-1])


def served_gap(params, prompt, served):
    """How far a served token's logit lies below the reference's best, as
    a share of the row's spread; the widest over the stream."""
    rows = ref_logits(params, prompt + served[:-1])[len(prompt) - 1:]
    picked = rows[np.arange(len(served)), served]
    return float(((rows.max(-1) - picked)
                  / (rows.max(-1) - rows.min(-1))).max())


def test_slots_turn_over_under_the_scheduler(params):
    """Thirty requests of mixed lengths through eight slots, a step ahead:
    every stream is the reference's, and the block's device counters equal
    the counts made here."""
    sess = session(params, slots=8, max_new=12)
    rng = np.random.default_rng(7)
    reqs = [Request(rid=i, prompt=tokens(100 + i, int(rng.integers(3, 33))),
                    max_new=int(rng.integers(2, 13)), arrival_s=0.0)
            for i in range(30)]
    steps = []
    inner = sess.step
    sess.step = lambda **how: steps.append(1) or inner(**how)
    done, _ = Scheduler(sess, policy="continuous").run(reqs)
    assert len(done) == 30
    for r in done:
        assert not r.failed, r.error
        assert len(r.tokens) == r.max_new
        assert served_gap(params, list(r.prompt), list(r.tokens)) <= 1e-5
    report = sess.block_report()
    buckets = [16 if len(r.prompt) <= 16 else 32 for r in reqs]
    counted = {k: report.pop(k) for k in ("window_rows_in_band",
                                          "shared_rows_read")}
    assert report == {
        "decode_steps": len(steps), "prefill_chunks": 30,
        "rows_valid": sum(len(r.prompt) for r in reqs),
        "rows_padded": sum(buckets) - sum(len(r.prompt) for r in reqs),
        "cross_rows": 30, "prefills_from_zero": 30, "prefills_carried": 0,
        "mamba_layers": 3, "window_layers": 2, "full_layers": 1,
        "gmu_layers": 1, "cross_layers": 1, "shared_readers": 2,
        "sliding_window": WINDOW, "ring_rows": 8,
        "state_bytes_per_slot": 3 * 4 * (4 * D_INNER + 3 * D_INNER)}
    # every live row once for each of the two layers that read the pages;
    # at most a window of them a window layer
    assert counted["shared_rows_read"] % 2 == 0
    assert 0 < counted["window_rows_in_band"] <= counted["shared_rows_read"]
    assert sess.decode_report()["steps_ahead"] > 0
    assert sess.fallback_count() == 0
    assert sess.cache.free_slots == 8


# -- the cache, the surface, the refusals -----------------------------------

def test_the_pools_hold_one_full_layer_and_nothing_for_a_shared_one(plain):
    cache, conf = plain.cache, plain.config
    pages = conf.slots * conf.max_pages_per_slot
    assert CFG.kinds == ("ssm", "window", "ssm", "window", "ssm", "full",
                         "shared", "shared")
    assert (cache.n_full, cache.n_window, cache.n_ssm, cache.n_shared) \
        == (1, 2, 3, 2)
    # two key/value pairs of 16 are narrower than a lane tile: folded
    kv = (1, pages + 1, PAGE, 2 * 16)
    assert {n: tuple(p.shape) for n, p in cache.pools.items()} == {
        "k_pool": kv, "v_pool": kv,
        "kw_pool": (2, conf.slots, 8, 2 * 16),
        "vw_pool": (2, conf.slots, 8, 2 * 16),
        "ssm_state": (3, conf.slots, 4, D_INNER),
        "conv_state": (3, conf.slots, 3, D_INNER)}
    assert cache.state == ("ssm_state", "conv_state")
    assert cache.paged == ("k_pool", "v_pool") and cache.hybrid
    # ONE layer of pages, whatever reads them: the bytes by hand
    assert cache.pool_bytes() == plain.state_report()["pool_bytes"] == 4 * (
        2 * (pages + 1) * PAGE * 32 + 2 * 2 * conf.slots * 8 * 32
        + 3 * conf.slots * 7 * D_INNER)
    assert list(plain.counters) == ["yoco_stats"]
    assert not cache._index     # a cache with state keeps no prefix index


def test_a_ring_folds_where_the_pages_fold():
    """One rule for both, ``kv_pool_shape``'s: ten heads of 128 fold into
    a ring's last axis, eight keep their own, and a chunk folds into
    either layout the same rows through the one writer."""
    assert kv_cache.kv_pool_shape(6, 32, 512, 10, 128) == (6, 32, 512, 1280)
    assert kv_cache.kv_pool_shape(3, 16, 512, 8, 128) \
        == (3, 16, 512, 8, 128)
    assert kv_cache.kv_pool_shape(2, 3, 8, 2, 16) == (2, 3, 8, 32)
    assert kv_cache.PagedKVCache(
        num_layers=1, num_heads=10, head_dim=128, page_size=16, num_pages=4,
        slots=2, max_pages_per_slot=2, layer_kinds=("window",), window=16,
        ring_pages=1).pools["kw_pool"].shape == (1, 2, 16, 1280)
    rs = np.random.RandomState(0)
    rows = jnp.asarray(rs.randn(24, 10, 128).astype(np.float32))
    rings = {}
    for shape in ((2, 3, 16, 1280), (2, 3, 16, 10, 128)):
        pools = {"kw_pool": jnp.asarray(rs.randn(*shape[:3], 1280).astype(
            np.float32) * 0 + 7.0).reshape(shape)}
        kv_cache.fold_into_ring(pools, "kw", 1, 2, rows, 8, 21)
        kv_cache.append_rows(pools, "kw", 0, jnp.arange(3),
                             jnp.asarray([1, 5, 9]), rows[:3], "")
        rings[len(shape)] = pools["kw_pool"]
        assert kv_cache.read_ring(pools["kw_pool"], 1, 128, 2).shape \
            == (16, 10, 128)
        assert kv_cache.read_ring(pools["kw_pool"], 0, 128).shape \
            == (3, 16, 10, 128)
    np.testing.assert_array_equal(np.asarray(rings[4]).reshape(rings[5].shape),
                                  np.asarray(rings[5]))
    # positions 13 .. 28 of the chunk at 8: row r holds position p % 16
    held = np.asarray(kv_cache.read_ring(rings[4], 1, 128, 2))
    np.testing.assert_array_equal(held[28 % 16], np.asarray(rows[28 - 8]))
    np.testing.assert_array_equal(held[13 % 16], np.asarray(rows[13 - 8]))


@pytest.mark.parametrize("conf, name", [(dict(spec_k=2), "spec_k"),
                                        (dict(kv_quant="int8"), "kv_quant")])
def test_unsupported_features_are_refused_by_name(params, conf, name):
    with pytest.raises(MXNetError, match="does not support.*%s" % name):
        session(params, **conf)


def _types(**at):
    types = list(HF["layer_types"])
    for i, kind in at.items():
        types[int(i[1:])] = kind
    return tuple(types)


@pytest.mark.parametrize("wrong, says", [
    (dict(mamba_d_state=8), "architecture says"),
    (dict(num_key_value_heads=2), "architecture says"),
    (dict(layer_types=HF["layer_types"][:7]), "layer_types"),
    # cross layers and no owner; two owners; a window layer behind the owner
    (dict(layer_types=_types(l5="sliding_attention")), "full_attention"),
    (dict(layer_types=_types(l7="full_attention")), "ONE full_attention"),
    (dict(layer_types=_types(l7="sliding_attention")), "after it"),
    (dict(layer_types=_types(l0="attention")), "layer_types"),
    (dict(sliding_window=0), "sliding_window")])
def test_a_wrong_architecture_is_refused(params, wrong, says):
    with pytest.raises(MXNetError, match=says):
        serve.InferenceSession(
            params, model=model_config(dict(HF, **wrong)),
            config=serve.ServeConfig(page_size=PAGE, buckets=(16,)))


def test_an_untied_head_is_refused():
    with pytest.raises(MXNetError, match="untied head"):
        dataclasses.replace(CFG, tie_word_embeddings=False).validate()


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


# -- the planted faults the limit has to catch ------------------------------

def _memory_after_the_gate(monkeypatch):
    inner = phi4flash._mamba_rows

    def gated(params, pre, u, state, context, length, cfg, exact):
        out, y, state, context = inner(params, pre, u, state, context,
                                       length, cfg, exact)
        z = jnp.split(phi4flash._mm(u, params[pre + "in_weight"], exact), 2,
                      axis=-1)[1]
        return out, y * jax.nn.silu(z), state, context

    monkeypatch.setattr(phi4flash, "_mamba_rows", gated)


def _memory_without_the_skip(monkeypatch):
    inner = phi4flash._mamba_rows

    def bare(params, pre, u, state, context, length, cfg, exact):
        out, y, state, context = inner(params, pre, u, state, context,
                                       length, cfg, exact)
        x = jax.nn.silu(phi4flash.causal_conv(
            jnp.split(phi4flash._mm(u, params[pre + "in_weight"], exact), 2,
                      axis=-1)[0], jnp.zeros((3, D_INNER)),
            params[pre + "conv_weight"], params[pre + "conv_bias"],
            length)[0])
        return out, y - params[pre + "D"] * x, state, context

    monkeypatch.setattr(phi4flash, "_mamba_rows", bare)


def _output_bias_left_out(monkeypatch):
    inner = phi4flash._differential

    def unbiased(params, pre, i, att, cfg, exact):
        return inner(dict(params, **{pre + "o_b": 0 * params[pre + "o_b"]}),
                     pre, i, att, cfg, exact)

    monkeypatch.setattr(phi4flash, "_differential", unbiased)


FORWARD_FAULTS = {
    "the memory taken after the gate": _memory_after_the_gate,
    "the memory without D x": _memory_without_the_skip,
    "lambda at lambda_init": lambda mp: mp.setattr(
        phi4flash, "_lambda", lambda params, pre, i: jnp.float32(
            phi4flash.lambda_init(i))),
    "lambda_init of another layer": lambda mp: mp.setattr(
        phi4flash, "lambda_init", lambda i: 0.8 - 0.6 * np.exp(-0.3 * (
            i + 8))),
    "the sub-layer norm left out": lambda mp: mp.setattr(
        phi4flash, "rms_norm", lambda x, gamma, eps: x * gamma),
    "an attention bias left out": _output_bias_left_out,
    "the score scale of the wide head": lambda mp: mp.setattr(
        phi4flash, "_score_scale", lambda cfg: cfg.head_dim ** -0.5),
}


@pytest.mark.parametrize("fault", sorted(FORWARD_FAULTS))
def test_the_comparison_can_fail(params, monkeypatch, fault):
    """Each fault planted in the block, the forward over forty tokens
    against the reference: far over the limit."""
    seq = tokens(1, 40)
    FORWARD_FAULTS[fault](monkeypatch)
    got = np.asarray(serve_model.full_forward(
        params, jnp.asarray([seq], jnp.int32), CFG, exact=False))[0]
    assert spacings_apart(got, ref_logits(params, seq)) > 10 * LIMIT_SPACINGS


@pytest.mark.parametrize("window", [WINDOW - 1, WINDOW + 1])
def test_a_window_one_key_off_fails(params, window):
    seq = tokens(1, 40)
    got = np.asarray(serve_model.full_forward(
        params, jnp.asarray([seq], jnp.int32),
        dataclasses.replace(CFG, sliding_window=window), exact=False))[0]
    assert spacings_apart(got, ref_logits(params, seq)) > 10 * LIMIT_SPACINGS


def test_a_cross_layer_reading_stale_pages_fails(params, monkeypatch):
    """The owner's decode append lost: the cross-attention layer (and the
    owner) then read pages that end at the prompt, and the rows served
    after it are another model's."""
    inner = phi4flash.append_rows

    def lost(pools, which, layer, major, minor, rows, kv_quant=""):
        if which in ("kw", "vw") or rows.shape[0] != 3:   # 3: the slots
            inner(pools, which, layer, major, minor, rows, kv_quant)

    monkeypatch.setattr(phi4flash, "append_rows", lost)
    stale = session(params)
    monkeypatch.undo()
    slot, rows, seq = _serve_one(stale, tokens(60, 20), 3)
    assert_close_across_executables(rows[0],
                                    ref_logits(params, seq[:20])[-1])
    assert spacings_apart(rows[-1], ref_logits(params, seq[:-1])[-1]) \
        > 10 * LIMIT_SPACINGS


def test_a_state_left_from_the_request_before_fails(plain, params,
                                                    monkeypatch):
    slot, _, _ = _serve_one(plain, tokens(60, 30), 3)
    plain.release(slot)
    monkeypatch.setattr(plain.cache, "_scrub_state", lambda slot: None)
    short = tokens(61, 5)
    again = plain.try_alloc(len(short), 8, tokens=short)
    assert again == slot
    _, logits = plain.prefill(again, short)
    assert spacings_apart(np.asarray(logits),
                          ref_logits(params, short)[-1]) > 10 * LIMIT_SPACINGS
