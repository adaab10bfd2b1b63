"""The Qwen3-Next block in the serving runtime (``serve/qwen3_next.py``:
Gated DeltaNet layers whose matrix state and convolution rows the cache
keeps a slot, a gated grouped-query attention layer over K/V pages,
softmax-routed experts of which a share is held beside a gated shared
expert), held to the plain reference the benchmark keeps,
``benchmark/references/qwen3_next_lm.py``, loaded from its path: one
reference in the repo, and it runs the recurrence token by token.  Toy
widths, seeded weights (the zero-centred norms' ``w`` drawn away from their
zero start, so that ``1 + w`` is tested), logits compared.

Tolerances, each with its reason:

* ``LIMIT_SPACINGS`` (tests/closeness.py, 32 float32 spacings at the
  row's largest logit) wherever two programs compute the same sums in
  another order: the session's executables against the reference, a
  prompt in one, two or five chunks.  tests/conftest.py sets full-precision
  matmuls, so what is left is float32 rounding; a state held in bfloat16, a
  convolution row not carried, a state update applied twice, a state left
  from the request before read in the hundreds and more (the controls
  below).
* The share test adds eight partial results in another order than the
  uncut layer's loop over its experts: 1e-5 of the layer's largest value.
* Scheduler runs return tokens only: a served token's logit has to lie
  within 1e-5 of the row's spread below the reference's best.

The recurrence's two forms are held to the definition in
``tests/test_gdn.py``.
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
from mxnet_tpu.serve import latent_moe, qwen3_next
from mxnet_tpu.serve import model as serve_model
from mxnet_tpu.serve.kv_cache import PagedKVCache
from mxnet_tpu.serve.scheduler import Request, Scheduler

from closeness import (LIMIT_SPACINGS, assert_close_across_executables,
                       spacings_apart)
from serve_util import assert_the_cpu_runs_the_expert_loop, lend

_REF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark", "references", "qwen3_next_lm.py")
_spec = importlib.util.spec_from_file_location("qwen3_next_lm_reference",
                                               _REF)
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

PAGE, CHUNK = 8, 8
# the reference's configuration: the published config.json's keys.  The
# published stack here has a period of 3 (gdn gdn attn); kept are its
# layers 0-3: gdn gdn attn gdn
HF = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
          head_dim=16, linear_num_key_heads=2, linear_num_value_heads=4,
          linear_key_head_dim=16, linear_value_head_dim=16,
          linear_conv_kernel_dim=4, moe_intermediate_size=32,
          shared_expert_intermediate_size=32, router_experts=16,
          num_experts=2, experts_first=4, num_experts_per_tok=4,
          norm_topk_prob=True, vocab_size=97, num_hidden_layers=4,
          full_attention_interval=3, layers_kept=(0, 1, 2, 3),
          partial_rotary_factor=0.25, rope_theta=1e7, rms_norm_eps=1e-6,
          max_position_embeddings=128)
UNCUT = dict(HF, num_experts=16, experts_first=0)


def model_config(hf):
    first, count, routed = reference.held(hf)
    return serve.ModelConfig(
        block="qwen3_next", vocab_size=hf["vocab_size"],
        num_layers=hf["num_hidden_layers"], d_model=hf["hidden_size"],
        num_heads=hf["num_attention_heads"],
        num_key_value_heads=hf["num_key_value_heads"],
        attn_head_dim=hf["head_dim"],
        max_len=hf["max_position_embeddings"],
        partial_rotary_factor=hf["partial_rotary_factor"],
        rope_theta=hf["rope_theta"], rms_norm_eps=hf["rms_norm_eps"],
        moe_d_ff=hf["moe_intermediate_size"], n_routed_experts=routed,
        num_experts_per_tok=hf["num_experts_per_tok"], n_shared_experts=1,
        shared_expert_gate=True, scoring_func="softmax",
        norm_topk_prob=hf["norm_topk_prob"],
        experts_held=(first, count) if count < routed else (),
        layer_types=tuple(reference.layer_types(hf)),
        linear_num_key_heads=hf["linear_num_key_heads"],
        linear_num_value_heads=hf["linear_num_value_heads"],
        linear_key_head_dim=hf["linear_key_head_dim"],
        linear_value_head_dim=hf["linear_value_head_dim"],
        linear_conv_kernel_dim=hf["linear_conv_kernel_dim"],
        gdn_chunk_size=CHUNK).validate()


CFG = model_config(HF)
GDN_LAYERS = CFG.layer_types.count("linear_attention")


def test_the_layer_order_follows_the_published_period():
    assert CFG.layer_types == ("linear_attention", "linear_attention",
                               "full_attention", "linear_attention")
    assert CFG.kinds == ("ssm", "ssm", "full", "ssm") and CFG.hybrid
    # Qwen3-Next's cut: published layers 0-7 of a period of four
    whole = dict(HF, num_hidden_layers=8, layers_kept=None,
                 full_attention_interval=4)
    assert reference.layer_types(whole) == (
        ["linear_attention"] * 3 + ["full_attention"]) * 2


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
    """Seeded weights, with every zero-centred norm's ``w`` drawn at 0.1:
    at its zero start ``1 + w`` and ``1`` could not be told apart."""
    made = serve_model.init_params(CFG, seed=3)
    rs = np.random.RandomState(5)
    return {name: (jnp.asarray(0.1 * rs.randn(*leaf.shape), jnp.float32)
                   if name.endswith("_norm_weight") else leaf)
            for name, leaf in sorted(made.items())}


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


def _near(got, want, what, rel=2e-5):
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= rel * scale, what


# -- the router, the shared expert's gate and the share ----------------------

def _ffn_layer(seed, hf):
    """One expert layer's parameters at the reference's shapes."""
    rs = np.random.RandomState(seed)
    spec = {k: v for k, v in reference.spec(hf).items()
            if k.startswith("blk1_") and ("router" in k or "expert" in k
                                          or "shared" in k)}
    return {k: jnp.asarray((0.5 * rs.randn(*shape)).astype(np.float32))
            for k, shape in sorted(spec.items())}


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_softmax_routing_is_the_references_choice(seed):
    """16 experts, the 4 largest softmax scores taken and renormalised."""
    p = _ffn_layer(seed, UNCUT)
    u = jnp.asarray(np.random.RandomState(seed + 10).randn(40, 64)
                    .astype(np.float32))
    taken, w = latent_moe._route(u, p, "blk1_", model_config(UNCUT))
    want = np.asarray(reference.route(u, p, "blk1_", UNCUT))
    got = np.zeros_like(want)
    np.put_along_axis(got, np.asarray(taken), np.asarray(w), axis=1)
    assert ((got > 0) == (want > 0)).all()
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(got.sum(-1), 1.0, rtol=1e-5)


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts that all eight shares compute, plus the shared
    expert behind its gate counted once, are the uncut reference's layer;
    each share is the reference's own share; an assignment is computed by
    exactly one."""
    p = _ffn_layer(7, UNCUT)
    u = jnp.asarray(np.random.RandomState(17).randn(40, 64)
                    .astype(np.float32))
    want = np.asarray(reference.routed(u, p, "blk1_", UNCUT)
                      + reference.shared(u, p, "blk1_"))
    total = np.asarray(reference.shared(u, p, "blk1_"))
    computed = np.zeros((40, 4), int)
    for first in range(0, 16, 2):
        hf = dict(UNCUT, num_experts=2, experts_first=first)
        cfg = model_config(hf)
        assert cfg.experts_held == (first, 2)
        mine = {k: (v[first:first + 2] if "experts_" in k else v)
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


def test_the_shared_expert_is_gated_a_row():
    """``latent_moe._ffn_out`` with ``shared_expert_gate``: the shared
    expert's result times one sigmoid a row; without the option (every
    other block) the same call adds it whole."""
    p = _ffn_layer(9, HF)
    p["blk1_ffn_norm_gamma"] = jnp.ones((64,), jnp.float32)
    x = jnp.asarray(np.random.RandomState(19).randn(24, 64)
                    .astype(np.float32))
    u = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6)
    want = reference.routed(u, p, "blk1_", HF) + reference.shared(u, p,
                                                                  "blk1_")
    got = latent_moe._ffn_out(p, 1, x, CFG, False, False)[0]
    _near(got, want, "the gated layer", rel=1e-5)
    whole = latent_moe._ffn_out(
        p, 1, x, dataclasses.replace(CFG, shared_expert_gate=False), False,
        False)[0]
    gate = jax.nn.sigmoid(u @ p["blk1_shared_expert_gate_weight"].T)
    assert float(gate.min()) < 0.1 and float(gate.max()) > 0.9
    _near(whole - got, (1 - gate) * reference.shared(u, p, "blk1_") / gate,
          "what the gate took", rel=1e-4)


@pytest.mark.parametrize("bad", [
    dict(scoring_func="sigmoid"), dict(shared_expert_gate=False),
    dict(n_shared_experts=0), dict(first_k_dense=1),
    dict(experts_held=(15, 2)), dict(linear_num_value_heads=3),
    dict(partial_rotary_factor=0.3), dict(tie_word_embeddings=True),
    dict(layer_types=("linear_attention", "kda", "full_attention",
                      "linear_attention"))])
def test_a_configuration_that_does_not_fit_is_refused(bad):
    with pytest.raises(MXNetError):
        dataclasses.replace(CFG, **bad).validate()


# -- the block against the reference ---------------------------------------

def test_params_are_the_references_spec(params):
    want = {k: tuple(v) for k, v in reference.spec(HF).items()}
    assert {k: tuple(v.shape) for k, v in params.items()} == want
    assert qwen3_next.param_shapes(CFG) == want
    assert want["blk1_router_weight"] == (16, 64)
    assert want["blk1_experts_gate_weight"] == (2, 32, 64)
    assert want["blk0_gdn_qkvz_weight"] == (2 * 32 + 2 * 64, 64)
    assert want["blk2_q_weight"] == (4 * 2 * 16, 64)
    # decays a token from 0.999 down to 0.2 at a = 0, dt_bias the
    # published 1: a state that is neither forgotten at once nor frozen
    fresh = serve_model.init_params(CFG, seed=3)
    decay = np.exp(-np.exp(np.asarray(fresh["blk0_gdn_A_log"]))
                   * np.log1p(np.e))
    assert np.asarray(fresh["blk0_gdn_dt_bias"]).tolist() == [1.0] * 4
    assert abs(decay[0] - 0.999) < 1e-5 and abs(decay[-1] - 0.2) < 1e-5
    assert (np.diff(decay) < 0).all()
    assert float(jnp.abs(fresh["blk0_attn_norm_weight"]).max()) == 0.0
    assert float(fresh["blk0_gdn_o_norm_gamma"].min()) == 1.0


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


def _prefill_in_chunks(sess, seq, steps=2):
    """``seq`` as a resumed transcript (chunks of the largest bucket) then
    ``steps`` decode steps; -> (the logits rows returned, the sequence)."""
    slot = sess.try_alloc(len(seq), steps + 1, tokens=seq, resume=True)
    first, logits = sess.prefill(slot, seq)
    rows, seq = [np.asarray(logits)], list(seq) + [first]
    for _ in range(steps):
        toks, logits = sess.step()
        rows.append(np.asarray(logits)[slot])
        seq.append(toks[slot])
    sess.release(slot)
    return rows, seq


@pytest.mark.parametrize("bucket, chunks", [(64, 1), (32, 2), (16, 3),
                                            (8, 5)])
def test_a_prompt_in_chunks_carries_state_and_rows(params, bucket, chunks):
    """A transcript of 39 tokens runs as one chunk, as 32 + 7, as 16 + 16 +
    7 and as five of 8 (7 in the last): every later chunk takes up the
    state and the convolution rows the one before it wrote and attends to
    the K/V rows it left, and then decode goes on from them."""
    sess = session(params, buckets=(bucket,), slots=1, max_prompt=64)
    seq = tokens(21, 39)
    rows, served = _prefill_in_chunks(sess, seq)
    rep = sess.block_report()
    assert rep["prefill_chunks"] == chunks
    assert (rep["prefills_from_zero"], rep["prefills_carried"]) \
        == (1, chunks - 1)
    assert rep["state_slot_layers"] == GDN_LAYERS * (chunks + 2)
    want = ref_logits(params, served)
    for i, row in enumerate(rows):
        assert_close_across_executables(row, want[38 + i])


def _controlled(params, monkeypatch, patch):
    """The largest distance from the reference, in spacings, of a prompt of
    39 tokens in chunks of 16 and two decode steps, under ``patch``."""
    patch(monkeypatch)
    rows, served = _prefill_in_chunks(
        session(params, buckets=(16,), slots=1, max_prompt=64),
        tokens(21, 39))
    want = ref_logits(params, served)
    return max(spacings_apart(row, want[38 + i])
               for i, row in enumerate(rows))


def _bfloat16_state(monkeypatch):
    chunked = qwen3_next.gdn_chunked

    def rounded(*args):
        o, state = chunked(*args)
        return o, state.astype(jnp.bfloat16).astype(jnp.float32)

    monkeypatch.setattr(qwen3_next, "gdn_chunked", rounded)


def _no_carried_rows(monkeypatch):
    conv = qwen3_next.causal_conv
    monkeypatch.setattr(
        qwen3_next, "causal_conv",
        lambda rows, context, w, bias, length:
        conv(rows, 0 * context, w, bias, length))


def _update_applied_twice(monkeypatch):
    step = qwen3_next.gdn_step

    def twice(q, k, v, g, beta, state):
        _, state = step(q, k, v, g, beta, state)
        return step(q, k, v, g, beta, state)

    monkeypatch.setattr(qwen3_next, "gdn_step", twice)


@pytest.mark.parametrize("patch", [_bfloat16_state, _no_carried_rows,
                                   _update_applied_twice],
                         ids=lambda f: f.__name__.strip("_"))
def test_the_comparison_sees_a_wrong_state(params, monkeypatch, patch):
    """The controls: a state handed from chunk to chunk in bfloat16, a
    chunk that starts from zero convolution rows, a decode step that
    applies its update twice (what a rematerialized update of a donated
    pool does: ROADMAP M4 (f)): each reads far past the limit."""
    assert _controlled(params, monkeypatch, patch) > 10 * LIMIT_SPACINGS


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
    matrix state and the convolution rows."""
    slot, _, _ = _serve_one(plain, tokens(50, 30), 5)
    assert float(jnp.abs(plain.cache.pools["gdn_state"][:, slot]).max()) > 0
    plain.release(slot)
    again, rows, seq = _serve_one(plain, tokens(51, 12), 4)
    assert again == slot
    want = ref_logits(params, seq)
    for i, row in enumerate(rows):
        assert_close_across_executables(row, want[11 + i])


def test_a_state_left_from_the_request_before_is_seen(params, plain,
                                                      monkeypatch):
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


def test_scheduler_serves_and_the_block_counts(params):
    sess = session(params, max_prompt=64)
    prompts = [tokens(70 + i, 6 + 11 * i) for i in range(5)]   # 6 .. 50
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
    # the prompts of 39 and 50 tokens are two chunks each
    assert rep["prefill_chunks"] == 7 and rep["decode_steps"] > 0
    assert (rep["prefills_from_zero"], rep["prefills_carried"]) == (5, 2)
    # four expert layers; a decode step routes every slot's row
    assert rep["assignments_asked"] == 4 * 4 * (
        prompt_rows + 3 * rep["decode_steps"])
    assert 0 < rep["assignments_held"] < rep["assignments_asked"]
    assert rep["assignments_computed"] == rep["assignments_held"]
    assert 0 < rep["distinct_held_experts"] <= 2 * 4 * rep["decode_steps"]
    assert 0 < rep["rows_without_held_expert"]
    assert rep["state_slot_layers"] == GDN_LAYERS * (
        7 + 3 * rep["decode_steps"])
    assert 0 < rep["full_rows_live"]
    assert (rep["gdn_layers"], rep["full_layers"], rep["window_layers"],
            rep["expert_layers"], rep["experts_held"]) == (3, 1, 0, 4, 2)
    assert rep["window_rows_visited"] == rep["window_rows_in_band"] == 0
    assert rep["state_bytes_per_slot"] == 3 * 4 * (4 * 16 * 16 + 3 * 128)
    assert rep["kv_lanes"] == 32            # two heads of 16, folded
    dec = sess.decode_report()
    assert dec["steps"] == rep["decode_steps"] and dec["blocks_visited"] > 0
    assert dec["paged_kernel_layers"] == 0 and sess.fallback_count() == 0


def test_on_the_cpu_the_expert_layers_run_the_loop(plain, params,
                                                   monkeypatch):
    """The predicate beside the kernel says "loop" here (the backend, and
    these widths), and is answered by a ``pallas_call`` an expert layer
    when asked to say "kernel"."""
    assert_the_cpu_runs_the_expert_loop(
        plain, session(params, quant="int8"), HF["num_hidden_layers"],
        monkeypatch)


def test_what_the_block_refuses(params):
    assert qwen3_next.REFUSES == ("spec_k", "kv_quant")
    for over in (dict(spec_k=2, draft="layers:1"), dict(kv_quant="int8")):
        with pytest.raises(MXNetError, match="does not support"):
            session(params, **over)


def test_int8_weights_serve_another_model(params):
    """Weight-only int8 is another model: it serves, and lands beyond
    the float32 limit."""
    sess = session(params, quant="int8")
    _, rows, seq = _serve_one(sess, tokens(80, 20), 3)
    want = ref_logits(params, seq)
    assert all(np.isfinite(row).all() for row in rows)
    assert max(spacings_apart(row, want[19 + i])
               for i, row in enumerate(rows)) > LIMIT_SPACINGS
