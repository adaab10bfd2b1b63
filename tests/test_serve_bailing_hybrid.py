"""The BailingHybrid block in the serving runtime
(``serve/bailing_hybrid.py``: KDA linear-attention layers whose matrix
state and convolution context the cache keeps a slot, a gated
latent-attention layer over the latent pool's pages, group-routed experts
of which a share is held), held to the plain reference the benchmark
keeps, ``benchmark/references/bailing_hybrid_lm.py``, loaded from its
path: one reference in the repo, and it runs the recurrence token by
token.  Toy widths, seeded weights, logits compared.

Tolerances, each with its reason:

* ``LIMIT_SPACINGS`` (tests/closeness.py, 32 float32 spacings at the
  row's largest logit) wherever two programs compute the same sums in
  another order: the session's executables against the reference, chunked
  against one-piece prefill.  tests/conftest.py sets full-precision
  matmuls, so what is left is float32 rounding; a state left from the
  request before, a held expert skipped, a group limit ignored read in the
  thousands and more (``test_the_comparison_can_fail``).
* The ops-level comparisons (``ops/kda.py`` against the reference's
  token-by-token scan) hold outputs and states to 2e-5 of their largest
  magnitude: the chunked form's triangular solve and its decays as
  ``exp`` of differences of cumulative sums against a running product,
  over values of order one.  At the gate's lower bound in every channel
  the factors reach e^80 and 2e-3 is what float32 leaves.
* The share test adds four partial results in another order than the
  uncut layer's loop over its experts: 1e-5 of the layer's largest value.
* Scheduler runs return tokens only: a served token's logit has to lie
  within 1e-5 of the row's spread below the reference's best.
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
from mxnet_tpu.ops import kda
from mxnet_tpu.serve import bailing_hybrid, latent_moe
from mxnet_tpu.serve import model as serve_model
from mxnet_tpu.serve.kv_cache import PagedKVCache, latent_pool_shape
from mxnet_tpu.serve.scheduler import Request, Scheduler

from closeness import (LIMIT_SPACINGS, assert_close_across_executables,
                       spacings_apart)
from serve_util import assert_the_cpu_runs_the_expert_loop, lend

_REF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark", "references", "bailing_hybrid_lm.py")
_spec = importlib.util.spec_from_file_location("bailing_hybrid_lm_reference",
                                               _REF)
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

PAGE, CHUNK = 8, 8
# the reference's configuration: the published config.json's keys.  The
# published stack here has a period of 3 (kda kda mla); kept are its
# layers 0 and 2-4: kda | mla kda kda, one dense layer in front
HF = dict(hidden_size=64, num_attention_heads=4, head_dim=16,
          qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
          kv_lora_rank=24, intermediate_size=96, moe_intermediate_size=32,
          moe_shared_expert_intermediate_size=32, num_shared_experts=1,
          router_experts=16, num_experts=4, experts_first=4, n_group=4,
          topk_group=2, num_experts_per_tok=4, routed_scaling_factor=2.5,
          norm_topk_prob=True, vocab_size=97, num_hidden_layers=4,
          layer_group_size=3, layers_kept=(0, 2, 3, 4),
          first_k_dense_replace=1, short_conv_kernel_size=4,
          kda_lower_bound=-5.0, kda_chunk_size=CHUNK, rms_norm_eps=1e-6,
          rope_theta=6e6, max_position_embeddings=128)
UNCUT = dict(HF, num_experts=16, experts_first=0)


def model_config(hf):
    first, count, routed = reference.held(hf)
    return serve.ModelConfig(
        block="bailing_hybrid", vocab_size=hf["vocab_size"],
        num_layers=hf["num_hidden_layers"], d_model=hf["hidden_size"],
        num_heads=hf["num_attention_heads"],
        max_len=hf["max_position_embeddings"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"], kv_lora_rank=hf["kv_lora_rank"],
        rope_theta=hf["rope_theta"], rms_norm_eps=hf["rms_norm_eps"],
        d_ff=hf["intermediate_size"],
        first_k_dense=hf["first_k_dense_replace"],
        moe_d_ff=hf["moe_intermediate_size"], n_routed_experts=routed,
        num_experts_per_tok=hf["num_experts_per_tok"],
        n_shared_experts=hf["num_shared_experts"],
        routed_scaling_factor=hf["routed_scaling_factor"],
        norm_topk_prob=hf["norm_topk_prob"], n_group=hf["n_group"],
        topk_group=hf["topk_group"],
        experts_held=(first, count) if count < routed else (),
        layer_types=tuple(reference.layer_types(hf)),
        kda_head_dim=hf["head_dim"],
        kda_d_conv=hf["short_conv_kernel_size"],
        kda_lower_bound=hf["kda_lower_bound"],
        kda_chunk_size=hf["kda_chunk_size"])


CFG = model_config(HF)
KDA_LAYERS = CFG.layer_types.count("kda")
ROW = bailing_hybrid.latent_dim(CFG)     # a latent row: rank + rope
EXPERT_LAYERS = HF["num_hidden_layers"] - HF["first_k_dense_replace"]


def test_the_layer_order_follows_the_published_period():
    assert CFG.layer_types == ("kda", "mla", "kda", "kda")
    assert CFG.kinds == ("ssm", "full", "ssm", "ssm") and CFG.hybrid
    whole = dict(HF, num_hidden_layers=7, layers_kept=None,
                 layer_group_size=6)
    assert reference.layer_types(whole) == ["kda"] * 5 + ["mla", "kda"]
    # Ling-3.0-flash's cut: published layers 0 and 2-7
    assert reference.layer_types(dict(whole, layers_kept=[0, 2, 3, 4, 5, 6,
                                                          7])) \
        == ["kda"] * 4 + ["mla", "kda", "kda"]


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


# -- the recurrence's two forms (ops/kda.py) --------------------------------

def _layer_inputs(seed, t, heads=3, width=16, floor=False):
    rs = np.random.RandomState(seed)
    f = lambda *shape: jnp.asarray(rs.randn(*shape).astype(np.float32))
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    g = -5.0 * jax.nn.sigmoid(3.0 * f(t, heads, width))
    if floor:       # every channel at the gate's lower bound
        g = jnp.full_like(g, -4.999)
    return (unit(f(t, heads, width)) * width ** -0.5,
            unit(f(t, heads, width)), f(t, heads, width), g,
            jax.nn.sigmoid(f(t, heads)), f(heads, width, width))


def _near(got, want, what, rel=2e-5):
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= rel * scale, what


@pytest.mark.parametrize("rows, chunk, real", [
    (24, 8, 24),     # three whole chunks: two boundaries crossed
    (24, 8, 13),     # bucket padding from the middle of the second chunk
    (21, 8, 21),     # not whole chunks: the form pads with identities
    (64, 32, 40),    # the served chunk, padding from its second chunk on
    (16, 32, 9)])    # one chunk wider than the bucket
def test_chunked_form_is_the_recurrence(rows, chunk, real):
    """Across chunk boundaries, from a non-zero carried state, with
    padded rows: outputs of the real rows and the state after the last
    real row are the token-by-token definition's (the reference's scan)."""
    q, k, v, g, beta, state0 = _layer_inputs(rows + chunk, rows)
    pad = jnp.arange(rows) < real
    g, beta = (jnp.where(pad[:, None, None], g, 0.0),
               jnp.where(pad[:, None], beta, 0.0))
    o, state = kda.kda_chunked(q, k, v, g, beta, state0, chunk)
    want_o, want_state = reference.kda_recurrence(
        q[:real], k[:real], v[:real], jnp.exp(g[:real]), beta[:real], state0)
    _near(o[:real], want_o, "outputs")
    _near(state, want_state, "the state after the last real row")
    # the carried state matters: from zero the same rows read otherwise
    cold, _ = kda.kda_chunked(q, k, v, g, beta, 0 * state0, chunk)
    assert float(jnp.max(jnp.abs(cold[:real] - want_o))) > 1e-2


def test_one_step_is_the_definition():
    q, k, v, g, beta, state0 = _layer_inputs(5, 6)
    want_o, want_state = reference.kda_recurrence(
        q, k, v, jnp.exp(g), beta, state0)
    state, outs = state0, []
    for i in range(6):
        o, state = kda.kda_step(q[i:i + 1], k[i:i + 1], v[i:i + 1],
                                g[i:i + 1], beta[i:i + 1], state[None])
        outs.append(o[0])
        state = state[0]
    _near(jnp.stack(outs), want_o, "outputs")
    _near(state, want_state, "state")


def test_chunked_form_loops_over_chunks_not_tokens():
    """Prefill's form is matmul-shaped: the one sequential pass is over
    the chunks (``lax.scan`` of length rows / chunk); what else loops is
    the triangular solve's own blocks, never the rows."""
    args = _layer_inputs(0, 64)

    def loops(jaxpr):
        found = []
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scan":
                found.append(eqn.params.get("length"))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found += loops(sub)
        return found

    jaxpr = jax.make_jaxpr(lambda *a: kda.kda_chunked(*a, chunk=16))(*args)
    assert loops(jaxpr.jaxpr) == [4]


def test_the_gates_lower_bound_stays_inside_float32():
    """Every channel decaying by e^-5 a token: the chunked form's factors
    reach e^80 at the served chunk of 32 and the result is still the
    definition's; a chunk that would leave float32 is refused."""
    q, k, v, g, beta, state0 = _layer_inputs(9, 64, floor=True)
    o, state = kda.kda_chunked(q, k, v, g, beta, state0, 32)
    want_o, want_state = reference.kda_recurrence(q, k, v, jnp.exp(g), beta,
                                                  state0)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(state).all())
    _near(o, want_o, "outputs", rel=2e-3)
    _near(state, want_state, "state", rel=2e-3)
    with pytest.raises(MXNetError, match="float32's range"):
        kda.kda_chunked(q, k, v, g, beta, state0, 64)
    with pytest.raises(MXNetError, match="float32's range"):
        dataclasses.replace(CFG, kda_chunk_size=64).validate()


# -- the router and the share (serve/latent_moe.py) -------------------------

def _ffn_layer(seed, hf):
    """One expert layer's parameters at the reference's shapes."""
    rs = np.random.RandomState(seed)
    spec = {k: v for k, v in reference.spec(hf).items()
            if k.startswith("blk1_") and ("router" in k or "expert" in k
                                          or "shared" in k)}
    return {k: jnp.asarray((0.5 * rs.randn(*shape)).astype(np.float32))
            for k, shape in sorted(spec.items())}


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_group_limited_routing_is_the_references_choice(seed):
    """16 experts in 4 groups, 2 groups kept by the sum of their two best,
    4 experts a token, a selection bias that moves the choice and not the
    weights."""
    p = _ffn_layer(seed, UNCUT)
    u = jnp.asarray(np.random.RandomState(seed + 10).randn(40, 64)
                    .astype(np.float32))
    taken, w = latent_moe._route(u, p, "blk1_", model_config(UNCUT))
    want = np.asarray(reference.route(u, p, "blk1_", UNCUT))
    got = np.zeros_like(want)
    np.put_along_axis(got, np.asarray(taken), np.asarray(w), axis=1)
    assert ((got > 0) == (want > 0)).all()
    np.testing.assert_allclose(got, want, rtol=1e-5)
    groups = np.asarray(taken) // 4
    assert all(len(set(row)) <= 2 for row in groups)
    # without the limit the choice is another one
    free, _ = latent_moe._route(u, p, "blk1_", dataclasses.replace(
        model_config(UNCUT), n_group=1, topk_group=1))
    assert (np.sort(np.asarray(free)) != np.sort(np.asarray(taken))).any()
    assert any(len(set(row)) > 2 for row in np.asarray(free) // 4)


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts that all four shares compute, plus the shared
    expert counted once, are the uncut reference's layer; each share is
    the reference's own share; an assignment is computed by exactly one."""
    p = _ffn_layer(7, UNCUT)
    u = jnp.asarray(np.random.RandomState(17).randn(40, 64)
                    .astype(np.float32))
    want = np.asarray(reference.routed(u, p, "blk1_", UNCUT)
                      + reference.shared(u, p, "blk1_"))
    total = np.asarray(reference.shared(u, p, "blk1_"))
    computed = np.zeros((40, 4), int)
    for first in (0, 4, 8, 12):
        hf = dict(UNCUT, num_experts=4, experts_first=first)
        cfg = model_config(hf)
        assert cfg.experts_held == (first, 4)
        mine = {k: (v[first:first + 4] if "experts_" in k else v)
                for k, v in p.items()}
        taken, w = latent_moe._route(u, mine, "blk1_", cfg)
        out, done, _ = latent_moe._routed_experts(u, taken, w, mine, "blk1_",
                                               cfg, False)
        here = np.asarray(latent_moe.held(taken, cfg))
        assert (np.asarray(done) == here).all()      # none dropped
        _near(out, reference.routed(u, mine, "blk1_", hf), "a share",
              rel=1e-5)
        total = total + np.asarray(out)
        computed += np.asarray(done)
    assert (computed == 1).all()
    _near(jnp.asarray(total), jnp.asarray(want), "the shares' sum",
          rel=1e-5)


def test_an_uncut_layer_is_its_own_whole_share():
    """``experts_held`` empty: every expert is here, as for kanana."""
    cfg = model_config(UNCUT)
    assert cfg.experts_held == () and latent_moe.held_range(cfg) == (0, 16)
    p = _ffn_layer(3, UNCUT)
    u = jnp.asarray(np.random.RandomState(4).randn(24, 64)
                    .astype(np.float32))
    taken, w = latent_moe._route(u, p, "blk1_", cfg)
    out, done, _ = latent_moe._routed_experts(u, taken, w, p, "blk1_", cfg,
                                           False)
    assert bool(done.all())
    _near(out, reference.routed(u, p, "blk1_", UNCUT), "the whole layer",
          rel=1e-5)


@pytest.mark.parametrize("bad", [
    dict(n_group=3), dict(topk_group=5), dict(experts_held=(14, 4)),
    dict(n_group=16, topk_group=16), dict(topk_group=1, n_group=8)])
def test_routing_that_does_not_fit_is_refused(bad):
    with pytest.raises(MXNetError):
        dataclasses.replace(CFG, **bad).validate()


# -- the block against the reference ---------------------------------------

def test_params_are_the_references_spec(params):
    want = {k: tuple(v) for k, v in reference.spec(HF).items()}
    assert {k: tuple(v.shape) for k, v in params.items()} == want
    assert bailing_hybrid.param_shapes(CFG) == want
    assert want["blk1_router_weight"] == (16, 64)
    assert want["blk1_experts_gate_weight"] == (4, 32, 64)
    # decays a token from 0.2 to 0.999 at W_f u = 0: a state that is
    # neither forgotten at once nor frozen
    sharp = np.exp(np.asarray(params["blk0_kda_A_log"]))[:, None]
    bias = np.asarray(params["blk0_kda_dt_bias"]).reshape(4, 16)
    decay = np.exp(-5.0 / (1.0 + np.exp(-sharp * bias)))
    assert abs(decay.min() - 0.2) < 1e-3 and abs(decay.max() - 0.999) < 1e-4
    assert (sharp.min(), sharp.max()) == pytest.approx((0.5, 2.0))


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
    takes up the state and the convolution context the first wrote and
    attends to the latent rows it left."""
    seq = tokens(21, 45)
    slot = plain.try_alloc(len(seq), 3, tokens=seq, resume=True)
    before = plain.block_report()
    first, chunked = plain.prefill(slot, seq)
    after = plain.block_report()
    assert after["prefill_chunks"] - before["prefill_chunks"] == 2
    assert after["state_slot_layers"] - before["state_slot_layers"] \
        == 2 * KDA_LAYERS
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
    second request the rows the reference gives it: ``alloc`` zeroes the
    matrix state and the convolution context, beside a latent pool."""
    slot, _, _ = _serve_one(plain, tokens(50, 30), 5)
    assert float(jnp.abs(plain.cache.pools["kda_state"][:, slot]).max()) > 0
    plain.release(slot)
    again, rows, seq = _serve_one(plain, tokens(51, 12), 4)
    assert again == slot
    want = ref_logits(params, seq)
    for i, row in enumerate(rows):
        assert_close_across_executables(row, want[11 + i])


def test_the_comparison_can_fail(params, plain, monkeypatch):
    """The control: a slot admitted over the state the request before it
    left reads thousands of spacings from the reference."""
    slot, _, _ = _serve_one(plain, tokens(60, 30), 5)
    plain.release(slot)
    monkeypatch.setattr(PagedKVCache, "_scrub_state",
                        lambda self, slot: None)
    again, rows, seq = _serve_one(plain, tokens(61, 12), 4)
    assert again == slot
    want = ref_logits(params, seq)
    assert max(spacings_apart(row, want[11 + i])
               for i, row in enumerate(rows)) > 30 * LIMIT_SPACINGS


def test_a_held_expert_skipped_is_seen(params, monkeypatch):
    """The other control: the expert loop stops one tile short (the
    block's counters cannot see that: they count the tiles the loop was
    given, so the comparison has to)."""
    from jax import lax

    loop = lax.fori_loop
    monkeypatch.setattr(lax, "fori_loop", lambda lo, hi, body, init:
                        loop(lo, jnp.maximum(hi - 1, 0), body, init))
    sess = session(params)
    _, rows, seq = _serve_one(sess, tokens(62, 14), 3)
    want = ref_logits(params, seq)
    assert max(spacings_apart(row, want[13 + i])
               for i, row in enumerate(rows)) > 30 * LIMIT_SPACINGS


def test_scheduler_serves_and_the_block_counts(params):
    sess = session(params)
    prompts = [tokens(70 + i, 6 + 5 * i) for i in range(5)]
    done, _ = Scheduler(sess, policy="continuous").run(
        [Request(rid=i, prompt=p, max_new=6, arrival_s=0.0)
         for i, p in enumerate(prompts)])
    assert not any(r.failed for r in done), [r.error for r in done]
    for r in done:
        seq = list(r.prompt) + list(r.tokens)
        rows = ref_logits(params, seq[:-1])[len(r.prompt) - 1:]
        served = np.asarray(r.tokens)
        gap = (rows.max(-1) - rows[np.arange(len(served)), served]) \
            / (rows.max(-1) - rows.min(-1))
        assert gap.max() <= 1e-5
    rep = sess.block_report()
    prompt_rows = sum(len(p) for p in prompts)
    assert rep["prefill_chunks"] == 5 and rep["decode_steps"] > 0
    # three expert layers; a decode step routes every slot's row
    assert rep["assignments_asked"] == 3 * 4 * (
        prompt_rows + 3 * rep["decode_steps"])
    assert 0 < rep["assignments_held"] < rep["assignments_asked"]
    assert rep["assignments_computed"] == rep["assignments_held"]
    assert 0 < rep["distinct_held_experts"] <= 4 * 3 * rep["decode_steps"]
    assert 0 < rep["rows_without_held_expert"]
    assert rep["state_slot_layers"] == KDA_LAYERS * (
        5 + 3 * rep["decode_steps"])
    assert (rep["kda_layers"], rep["mla_layers"], rep["expert_layers"],
            rep["experts_held"]) == (3, 1, 3, 4)
    assert rep["state_bytes_per_slot"] == 3 * 4 * (4 * 16 * 16 + 3 * 192)
    assert sess.decode_report() is None and sess.fallback_count() == 0


@pytest.mark.parametrize("upto", ["prefill", "decode", "release"])
def test_the_latent_pools_pad_lanes_stay_zero(plain, upto):
    """The one latent layer's rows are ROW values in a lane tile of 128:
    what lies past them is zero after a prefill, after decode steps (all
    slots' rows, idle ones too) and after the slot's release, and the
    block reports the width the pool took."""
    slot = plain.try_alloc(19, 8, tokens=tokens(90, 19))
    plain.prefill(slot, tokens(90, 19))
    if upto != "prefill":
        for _ in range(3):
            plain.step()
    if upto == "release":
        plain.release(slot)
    pool = np.asarray(plain.cache.pools["latent_pool"])
    assert pool.shape[-1] == plain.block_report()["latent_lanes"] \
        == plain.cache.latent_lanes == 128 > ROW
    assert float(np.abs(pool[..., ROW:]).max()) == 0.0
    assert float(np.abs(pool[..., :ROW]).max()) > 0.0


def test_on_the_cpu_the_expert_layers_run_the_loop(plain, params,
                                                   monkeypatch):
    """The predicate beside the kernel says "loop" here (the backend, and
    these widths): both traced programs hold the ``while`` and no
    ``pallas_call``, and the report says so with an integer.  Asked to say
    "kernel", it is answered by a ``pallas_call`` an expert layer, which
    the executables note while they are traced; and what it is told of a
    weight-only-quantized tree is that its stacks were made inside the
    trace."""
    assert_the_cpu_runs_the_expert_loop(
        plain, session(params, quant="int8"), EXPERT_LAYERS, monkeypatch)


def test_what_the_block_refuses(params):
    assert bailing_hybrid.REFUSES == ("spec_k", "kv_quant")
    for over in (dict(spec_k=2, draft="layers:1"), dict(kv_quant="int8")):
        with pytest.raises(MXNetError, match="does not support"):
            session(params, **over)
    with pytest.raises(MXNetError, match="layer_types"):
        dataclasses.replace(CFG, layer_types=("kda", "mamba", "kda",
                                              "kda")).validate()


def test_int8_weights_serve_another_model(params):
    """Weight-only int8 is another model: it serves, and lands beyond
    the float32 limit."""
    sess = session(params, quant="int8")
    _, rows, seq = _serve_one(sess, tokens(80, 20), 3)
    want = ref_logits(params, seq)
    assert all(np.isfinite(row).all() for row in rows)
    assert max(spacings_apart(row, want[19 + i])
               for i, row in enumerate(rows)) > LIMIT_SPACINGS


# -- the cache: a latent pool and state pools together -----------------------

def _cache(**over):
    conf = dict(num_layers=4, num_heads=4, head_dim=16, page_size=8,
                num_pages=12, slots=3, max_pages_per_slot=4,
                layer_kinds=CFG.kinds,
                latent_dim=bailing_hybrid.latent_dim(CFG),
                state=bailing_hybrid.state_shapes(CFG))
    conf.update(over)
    return PagedKVCache(**conf)


def test_a_latent_pool_beside_state_pools():
    cache = _cache()
    assert {n: tuple(p.shape) for n, p in cache.pools.items()} == {
        # the one "full" layer; a row of 32 values in one lane tile
        "latent_pool": latent_pool_shape(1, 13, 8, 32),
        "kda_state": (3, 3, 4, 16, 16), "conv_state": (3, 3, 3, 192)}
    assert cache.pools["latent_pool"].shape[-1] == cache.latent_lanes == 128
    assert cache.kv_lanes is None
    assert cache.paged == ("latent_pool",)
    assert cache.state == ("kda_state", "conv_state") and cache.hybrid
    assert cache.pages_needed(20, 8) == 4
    # alloc zeroes a slot's state and nobody else's; pages serve the
    # latent layer; the prefix index is off for a cache with state
    cache.pools = {n: p + 1.0 for n, p in cache.pools.items()}
    slot = cache.alloc(20, 8, tokens=list(range(20)))
    other = [s for s in range(3) if s != slot]
    for name in cache.state:
        assert float(jnp.abs(cache.pools[name][:, slot]).max()) == 0.0
        assert float(jnp.abs(cache.pools[name][:, other] - 1.0).max()) == 0.0
    assert float(cache.pools["latent_pool"].min()) == 1.0
    assert len(cache._pages_of[slot]) == 4 and cache.free_pages == 8
    cache.register_prefix(slot, list(range(20)))
    assert cache.match_prefix(list(range(20))) == []
    cache.release(slot)
    assert cache.free_pages == 12 and cache.free_slots == 3
    assert cache.alloc(5, 3) == slot        # lowest id first, zeroed again


def test_what_a_latent_pool_still_refuses():
    for over in (dict(kv_quant="int8"),
                 dict(layer_kinds=("ssm", "full", "window", "ssm"), window=8,
                      ring_pages=2)):
        with pytest.raises(MXNetError, match="latent pool"):
            _cache(**over)
