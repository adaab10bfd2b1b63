"""The DeepSeek-V3 block in the serving runtime (``serve/latent_moe.py``:
latent attention over one latent page pool, dropless routed experts with
shared experts), held to the plain reference the benchmark keeps,
``benchmark/references/deepseek_v3_lm.py``, loaded from its path: one
reference in the repo.  Toy widths, seeded weights, logits compared.

Tolerances, each with its reason:

* ``LIMIT_SPACINGS`` (tests/closeness.py, 32 float32 spacings at the
  row's largest logit) wherever two programs compute the same sums in
  another order: the session's executables against the reference, chunked
  against one-piece prefill, the absorbed decode against the materialised
  prefill (which associate ``q_nope . (W_k c)`` as ``(W_k^T q_nope) . c``).
  tests/conftest.py sets full-precision matmuls, so what is left is
  float32 rounding: the largest reading over the cases below and 12 seeds
  was 6.0; a position off by one, a stale page or an expert left out
  reads in the thousands (``test_the_comparison_can_fail``).
* The router works in float32 at highest precision on both sides, so a
  token's experts do not flip between programs at these sizes; a flipped
  expert would read in the thousands as well.
* Scheduler runs return tokens only, and an argmax over random weights
  may turn on a last bit: a served token's logit has to lie within 1e-5
  of the row's spread below the reference's best (the benchmark's
  ``served_token_gap``), which an equal logit meets and a wrong row
  misses by four orders.
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
from mxnet_tpu.compile_cache import signature_of
from mxnet_tpu.serve import kv_cache, latent_moe
from mxnet_tpu.serve import model as serve_model
from mxnet_tpu.serve.scheduler import Request, Scheduler

from closeness import assert_close_across_executables, spacings_apart
from serve_util import assert_the_cpu_runs_the_expert_loop, lend

_REF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark", "references", "deepseek_v3_lm.py")
_spec = importlib.util.spec_from_file_location("deepseek_v3_lm_reference",
                                               _REF)
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

PAGE = 8
# the reference's configuration: the published config.json's keys
HF = dict(hidden_size=64, num_attention_heads=4, qk_nope_head_dim=16,
          qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32, vocab_size=97,
          intermediate_size=96, moe_intermediate_size=24,
          n_routed_experts=8, n_shared_experts=2, num_experts_per_tok=2,
          num_hidden_layers=3, first_k_dense_replace=1, rope_theta=1e6,
          rms_norm_eps=1e-6, routed_scaling_factor=2.448,
          norm_topk_prob=True, max_position_embeddings=128)


def model_config(hf):
    return serve.ModelConfig(
        block="deepseek_v3", vocab_size=hf["vocab_size"],
        num_layers=hf["num_hidden_layers"], d_model=hf["hidden_size"],
        num_heads=hf["num_attention_heads"],
        max_len=hf["max_position_embeddings"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"], kv_lora_rank=hf["kv_lora_rank"],
        rope_theta=hf["rope_theta"], rms_norm_eps=hf["rms_norm_eps"],
        d_ff=hf["intermediate_size"],
        first_k_dense=hf["first_k_dense_replace"],
        moe_d_ff=hf["moe_intermediate_size"],
        n_routed_experts=hf["n_routed_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"],
        n_shared_experts=hf["n_shared_experts"],
        routed_scaling_factor=hf["routed_scaling_factor"],
        norm_topk_prob=hf["norm_topk_prob"])


CFG = model_config(HF)
EXPERT_LAYERS = HF["num_hidden_layers"] - HF["first_k_dense_replace"]
ROW = HF["kv_lora_rank"] + HF["qk_rope_head_dim"]
LANES = 128     # what a row of ROW values occupies in the pool at rest


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


@pytest.fixture(scope="module")
def _prefix(params):
    return session(params, prefix_pages=-1)


@pytest.fixture
def prefix(_prefix):
    yield from lend(_prefix)


def test_params_are_the_references_spec(params):
    assert {k: tuple(v.shape) for k, v in params.items()} \
        == {k: tuple(v) for k, v in reference.spec(HF).items()}
    assert latent_moe.param_shapes(CFG) == {
        k: tuple(v) for k, v in reference.spec(HF).items()}


@pytest.mark.parametrize("exact", [False, True])
@pytest.mark.parametrize("seed", [0, 1])
def test_full_forward_matches_reference(params, exact, seed):
    seq = tokens(seed, 40)
    got = np.asarray(serve_model.full_forward(
        params, jnp.asarray([seq], jnp.int32), CFG, exact=exact))[0]
    assert_close_across_executables(got, ref_logits(params, seq))


@pytest.mark.parametrize("exact", [False, True])
def test_prefill_then_decode_through_the_latent_cache(params, exact):
    """Three prompts of different lengths share the decode batch; every
    logits row the session returns is the reference's row."""
    sess = session(params, exact=exact)
    assert sorted(sess.executables) == ["decode", "prefill_16", "prefill_32"]
    seqs, slots = [], []
    for i, n in enumerate((5, 16, 27)):
        p = tokens(10 + i, n)
        slot = sess.try_alloc(n, 8, tokens=p)
        first, logits = sess.prefill(slot, p)
        logits = np.asarray(logits)
        assert_close_across_executables(logits, ref_logits(params, p)[-1])
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


def test_chunked_prefill_matches_one_piece(params, plain):
    """A resumed transcript longer than the largest bucket runs as
    max-bucket chunks at page-aligned offsets; the same tokens in one
    piece (a session with a bucket that holds them) and the reference give
    the same last row."""
    seq = tokens(21, 45)                         # 32 + 13: two chunks
    slot = plain.try_alloc(len(seq), 3, tokens=seq, resume=True)
    _, chunked = plain.prefill(slot, seq)
    chunked = np.asarray(chunked)
    whole = session(params, buckets=(48,), max_new=16)
    wslot = whole.try_alloc(len(seq), 3, tokens=seq)
    _, one_piece = whole.prefill(wslot, seq)
    one_piece = np.asarray(one_piece)
    assert_close_across_executables(chunked, one_piece)
    assert_close_across_executables(chunked, ref_logits(params, seq)[-1])
    assert plain.moe_report()["prefill_chunks"] \
        - whole.moe_report()["prefill_chunks"] >= 1


def test_absorbed_decode_matches_materialised_prefill(plain, params):
    """Position n's logits two ways: prefill of n + 1 tokens (K and V
    built from the latent rows) and prefill of n tokens followed by one
    decode step fed token n (absorbed projections, no K or V)."""
    seq = tokens(22, 20)
    a = plain.try_alloc(len(seq), 4, tokens=seq)
    _, materialised = plain.prefill(a, seq)
    materialised = np.asarray(materialised)
    b = plain.try_alloc(len(seq) - 1, 4, tokens=seq[:-1])
    plain.prefill(b, seq[:-1])
    plain._slot_tokens[b] = seq[-1]
    _, logits = plain.step()
    logits = np.asarray(logits)
    assert_close_across_executables(logits[b], materialised)


def test_prefix_hit_on_latent_pages_gives_cold_logits(prefix, params):
    """The second request maps the first one's published latent pages
    read-only and prefills only its suffix; its rows are a cold run's."""
    shared = tokens(23, 2 * PAGE)
    pa, pb = shared + tokens(24, 3), shared + tokens(25, 5)
    sa = prefix.try_alloc(len(pa), 6, tokens=pa)
    first_a, _ = prefix.prefill(sa, pa)
    sb = prefix.try_alloc(len(pb), 6, tokens=pb)
    assert prefix.cache.cached_len(sb) == 2 * PAGE
    assert int(prefix.cache._tables[sb, 0]) == int(prefix.cache._tables[sa, 0])
    first_b, logits_b = prefix.prefill(sb, pb)
    logits_b = np.asarray(logits_b)
    assert_close_across_executables(logits_b, ref_logits(params, pb)[-1])
    seqs = {sa: pa + [first_a], sb: pb + [first_b]}
    for _ in range(3):
        toks, logits = prefix.step()
        logits = np.asarray(logits)
        for slot, seq in seqs.items():
            assert_close_across_executables(
                logits[slot], ref_logits(params, seq)[-1])
            seq.append(toks[slot])
    assert prefix.cache.prefix_stats["hit_pages"] >= 2


def served_gap(params, prompt, served):
    """How far a served token's logit lies below the reference's best, as
    a share of the row's spread; the widest over the stream."""
    rows = ref_logits(params, prompt + served[:-1])[len(prompt) - 1:]
    picked = rows[np.arange(len(served)), served]
    return float(((rows.max(-1) - picked)
                  / (rows.max(-1) - rows.min(-1))).max())


def test_preempt_and_reprefill_on_latent_pages(params):
    """A 5-page pool under three growing requests has to preempt; every
    resumed request re-prefills its transcript (chunked where it outgrew
    the bucket) and its stream is still the reference's."""
    sess = session(params, buckets=(8, 16), max_new=8, num_pages=5,
                   oversub=True, prefix_pages=-1)
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
    report = sess.moe_report()
    assert report["assignments_asked"] == report["assignments_computed"] > 0
    assert sess.cache.free_slots == sess.config.slots


@pytest.mark.parametrize("top_k, hot", [(1, (3,)), (6, (0, 2, 3, 5, 6, 7))])
def test_skewed_routing_is_dropless(top_k, hot):
    """A crafted router (a selection bias no score can outweigh) sends
    every token to the same expert, then to the same six: the tiles of one
    group outnumber every other's, nothing is dropped, the load is where
    the bias put it, and the logits are the reference's."""
    hf = dict(HF, num_experts_per_tok=top_k)
    cfg = model_config(hf)
    params = dict(serve_model.init_params(cfg, seed=5))
    bias = np.zeros((hf["n_routed_experts"],), np.float32)
    bias[list(hot)] = 10.0
    for i in range(hf["first_k_dense_replace"], hf["num_hidden_layers"]):
        params["blk%d_router_bias" % i] = jnp.asarray(bias)
    seq = tokens(40, 30)
    got = np.asarray(serve_model.full_forward(
        params, jnp.asarray([seq], jnp.int32), cfg, exact=False))[0]
    assert_close_across_executables(got, ref_logits(params, seq, hf))
    sess = serve.InferenceSession(
        params, model=cfg, config=serve.ServeConfig(
            slots=2, page_size=PAGE, buckets=(32,), max_new=8, exact=False))
    slot = sess.try_alloc(len(seq), 4, tokens=seq)
    first, logits = sess.prefill(slot, seq)
    logits = np.asarray(logits)
    assert_close_across_executables(logits, ref_logits(params, seq, hf)[-1])
    _, logits = sess.step()
    logits = np.asarray(logits)
    assert_close_across_executables(
        logits[slot], ref_logits(params, seq + [first], hf)[-1])
    report = sess.moe_report()
    moe_layers = hf["num_hidden_layers"] - hf["first_k_dense_replace"]
    # 30 prompt tokens, then one decode step over both slots' rows
    asked = (len(seq) + 2) * top_k * moe_layers
    assert report["assignments_asked"] == report["assignments_computed"] \
        == asked
    assert report["decode_steps"] == 1 and report["prefill_chunks"] == 1
    assert report["distinct_experts"] == top_k * moe_layers
    load = report["expert_load"]
    assert load.shape == (moe_layers, hf["n_routed_experts"])
    assert load[:, list(hot)].sum() == asked and load.sum() == asked


# A share's expert layer alone, at toy widths: 16 experts of which 4..7
# are held, 4 a token, 64 rows: 256 assignments in tiles of 16.  One round
# of the layout for what is held takes 2 x 256 x 4 / 16 = 128 of them, in
# 128 / 16 + 4 tiles and the one that stays zero: 208 rows, where all 256
# could fill 16 + 4 tiles, 320 rows.
SHARE = dict(d=32, f=16, experts=16, top_k=4, held=(4, 4), rows=64)
SHARE_ROUND_ROWS, SHARE_ALL_ROWS = 208, 320
# name: (the experts the crafted router takes from, rounds)
SHARE_ROUTINGS = {"balanced": (range(16), 1), "every-one-held": (range(4, 8), 2),
                  "three-in-four-held": ((3, 4, 5, 6), 2),
                  "none-held": ((0, 1, 2, 3, 8, 9, 12, 15), 0)}


def _share_layer(routing, seed=0):
    """-> (cfg, the layer's arguments, the plain reference's result and
    the held assignments): every row takes ``top_k`` distinct experts of
    ``routing``'s."""
    from serve_util import expert_layer_config

    c = SHARE
    cfg = expert_layer_config(c["d"], c["f"], c["experts"], c["top_k"],
                              c["held"])
    rng = np.random.default_rng(seed)
    first, e = c["held"]
    params = {"blk1_experts_%s_weight" % m: jnp.asarray(
        rng.standard_normal(shape) / 4, jnp.float32) for m, shape in (
            ("gate", (e, c["f"], c["d"])), ("up", (e, c["f"], c["d"])),
            ("down", (e, c["d"], c["f"])))}
    u = rng.standard_normal((c["rows"], c["d"])).astype(np.float32)
    pool = np.asarray(list(SHARE_ROUTINGS[routing][0]))
    taken = np.stack([rng.permutation(pool)[:c["top_k"]]
                      for _ in range(c["rows"])]).astype(np.int32)
    w = rng.uniform(0.1, 1.0, taken.shape).astype(np.float32)
    here = (taken >= first) & (taken < first + e)
    want = np.zeros_like(u, dtype=np.float64)
    gate, up, down = (np.asarray(params["blk1_experts_%s_weight" % m],
                                 np.float64) for m in ("gate", "up", "down"))
    for t, j in zip(*np.nonzero(here)):
        g = taken[t, j] - first
        a = gate[g] @ u[t]
        want[t] += w[t, j] * (down[g] @ (a / (1 + np.exp(-a))
                                         * (up[g] @ u[t])))
    return cfg, (jnp.asarray(u), jnp.asarray(taken), jnp.asarray(w),
                 params), want, here


def _share_layouts(monkeypatch, cfg, args):
    """-> the layer's three results under the layout for what is held,
    then under the one for every assignment (today's at these sizes)."""
    out = []
    for worth in (0, latent_moe._WORTH_BYTES):
        monkeypatch.setattr(latent_moe, "_WORTH_BYTES", worth)
        out.append(jax.jit(lambda *a: latent_moe._routed_experts(
            *a, "blk1_", cfg, False))(*args))
    return out


@pytest.mark.parametrize("routing", sorted(SHARE_ROUTINGS))
def test_a_share_lays_out_rows_for_what_it_holds(routing, monkeypatch):
    """Under balanced routing one round of the bounded layout; with every
    assignment (or three in four) on the held experts the bound overflows
    and a second round computes the rest; with none held no round runs.
    Each time the result is the one-pass layout's and the plain
    reference's, ``computed`` is true for exactly the held assignments,
    and the rows laid out are the count from the shapes."""
    cfg, args, want, here = _share_layer(routing)
    (out, computed, rows), (old, old_computed, old_rows) = _share_layouts(
        monkeypatch, cfg, args)
    assert_close_across_executables(np.asarray(out), np.asarray(old))
    assert_close_across_executables(np.asarray(out), want.astype(np.float32))
    assert (np.asarray(computed) == here).all()
    assert (np.asarray(old_computed) == here).all()
    rounds = SHARE_ROUTINGS[routing][1]
    assert -(-here.sum() // 128) == rounds
    assert int(rows) == rounds * SHARE_ROUND_ROWS
    assert int(old_rows) == SHARE_ALL_ROWS


def test_a_bound_that_truncates_is_caught(monkeypatch):
    """The planted fault: the loop over rounds runs its body once, so what
    lies past the bound is silently left out.  The comparison reads it in
    the thousands of spacings and ``computed`` says which assignments no
    tile reached (the benchmark's ``moe_assignments_dropped``)."""
    from jax import lax

    cfg, args, want, here = _share_layer("every-one-held")
    monkeypatch.setattr(lax, "while_loop",
                        lambda cond, body, init: body(init))
    (out, computed, rows), (old, old_computed, _) = _share_layouts(
        monkeypatch, cfg, args)
    assert int(rows) == SHARE_ROUND_ROWS
    assert spacings_apart(np.asarray(out), want.astype(np.float32)) > 1e3
    assert np.asarray(computed).sum() == 128 < here.sum() == 256
    # the one-pass layout has no such loop
    assert_close_across_executables(np.asarray(old), want.astype(np.float32))
    assert bool(np.asarray(old_computed).all())


# (rows, d, f, experts, top_k, held): a call whose layout has to be the
# one-pass text, primitive for primitive as ``jax.make_jaxpr`` shows it
# (recorded at the parent of PR 57: the count of primitives, nested
# computations included, and the first 12 digits of the SHA-1 of their
# names joined by spaces), and the share's prefill chunks, which lay out
# rows for what they hold.
ONE_PASS = {
    "qwen3next-decode": ((32, 2048, 512, 512, 10, (0, 64)),
                         (184, "d9477e8e2cdb")),
    "sdar-block-pass": ((128, 2048, 768, 128, 8, (0, 16)),
                        (184, "d9477e8e2cdb")),
    "lfm2-decode": ((64, 2048, 1536, 64, 4, (0, 8)), (184, "d9477e8e2cdb")),
    "kanana-bucket-2048": ((2048, 2048, 768, 128, 6, ()),
                           (166, "be2f39dfbc88")),
}
# name: (sizes, rows a round lays out, rows every assignment could fill)
IN_ROUNDS = {
    "qwen3next-bucket-2048": ((2048, 2048, 512, 512, 10, (0, 64)),
                              145 * 64, 384 * 64),
    "qwen3next-bucket-512": ((512, 2048, 512, 512, 10, (0, 64)),
                             145 * 16, 384 * 16),
    "laguna-bucket-2048": ((2048, 3072, 512, 256, 10, (0, 32)),
                           73 * 128, 192 * 128),
    "lfm2-bucket-512": ((512, 2048, 1536, 64, 4, (0, 8)), 25 * 32, 72 * 32),
    "sdar-bucket-2048": ((2048, 2048, 768, 128, 8, (0, 16)),
                         49 * 128, 144 * 128),
    "ling-bucket-1024": ((1024, 2560, 768, 512, 8, (0, 64)),
                         193 * 16, 576 * 16),
}


def _traced_layer(sizes):
    """-> (the names of the primitives of ``_routed_experts`` traced at
    ``sizes`` on abstract values, nested computations included; the
    leading sizes of every array it makes)."""
    from serve_util import expert_layer_config

    n, d, f, experts, top_k, held = sizes
    cfg = expert_layer_config(d, f, experts, top_k, held)
    e = latent_moe.held_range(cfg)[1]
    sds = jax.ShapeDtypeStruct
    params = {"blk1_experts_gate_weight": sds((e, f, d), jnp.float32),
              "blk1_experts_up_weight": sds((e, f, d), jnp.float32),
              "blk1_experts_down_weight": sds((e, d, f), jnp.float32)}
    jaxpr = jax.make_jaxpr(lambda u, t, w, p: latent_moe._routed_experts(
        u, t, w, p, "blk1_", cfg, False)[:2])(
            sds((n, d), jnp.float32), sds((n, top_k), jnp.int32),
            sds((n, top_k), jnp.float32), params)
    names, heights = [], set()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            names.append(eqn.primitive.name)
            heights.update(v.aval.shape[0] for v in eqn.outvars
                           if getattr(v.aval, "shape", ()))
            for value in eqn.params.values():
                for sub in (value if isinstance(value, (list, tuple))
                            else [value]):
                    if hasattr(sub, "jaxpr"):
                        walk(sub.jaxpr)
                    elif hasattr(sub, "eqns"):
                        walk(sub)

    walk(jaxpr.jaxpr)
    return names, heights


@pytest.mark.parametrize("name", sorted(ONE_PASS))
def test_a_small_call_and_a_whole_block_keep_the_one_pass_text(name):
    """A share's decode step (the worst case is a few megabytes) and a
    block that holds every expert (its worst case is what it computes)
    trace what they traced before there was a second layout."""
    import hashlib

    sizes, (count, digest) = ONE_PASS[name]
    names, _ = _traced_layer(sizes)
    assert (len(names), hashlib.sha1(" ".join(names).encode())
            .hexdigest()[:12]) == (count, digest)


@pytest.mark.parametrize("name", sorted(IN_ROUNDS))
def test_a_shares_prefill_chunk_holds_no_worst_case_array(name):
    """At the served blocks' widths a share's prefill chunk lays out the
    bounded rows: no array of the height all ``n x k`` assignments could
    fill is left in its text, nor one of that height and the zero row."""
    sizes, round_rows, all_rows = IN_ROUNDS[name]
    names, heights = _traced_layer(sizes)
    assert round_rows in heights
    assert not {all_rows, all_rows + 1} & heights, sorted(heights)
    assert names.count("while") == 2    # the rounds, and the tiles' loop


def test_moe_report_counts_every_router(plain):
    seq = tokens(41, 11)
    slot = plain.try_alloc(len(seq), 4, tokens=seq)
    before = plain.moe_report()
    plain.prefill(slot, seq)
    plain.step()
    after = plain.moe_report()
    k, layers = HF["num_experts_per_tok"], 2
    # a decode step routes every slot's row, the idle slots' too
    asked = (len(seq) + plain.config.slots) * k * layers
    assert after["assignments_asked"] - before["assignments_asked"] == asked
    assert after["assignments_computed"] - before["assignments_computed"] \
        == asked
    assert after["decode_steps"] - before["decode_steps"] == 1
    assert (after["expert_load"] - before["expert_load"]).sum() == asked
    assert after["expert_layers"] == layers


def test_counters_carry_past_thirty_bits():
    stats = jnp.asarray([[(1 << 30) - 3, 5], [2, 0]], jnp.int32)
    folded = np.asarray(latent_moe._fold(stats, jnp.asarray([7, 1])))
    assert [int(lo) + (int(hi) << 30) for lo, hi in zip(*folded)] \
        == [(3 << 30) + 4, 6]


def test_pool_is_one_latent_pool(plain):
    cache, conf = plain.cache, plain.config
    pages = conf.slots * conf.max_pages_per_slot
    assert list(cache.pools) == ["latent_pool"]
    # a row of 40 values lies in one whole lane tile (kv_cache.py)
    assert cache.pools["latent_pool"].shape == kv_cache.latent_pool_shape(
        HF["num_hidden_layers"], pages + 1, PAGE, ROW) == (
        HF["num_hidden_layers"], pages + 1, PAGE, LANES)
    assert cache.latent_lanes == plain.block_report()["latent_lanes"] \
        == LANES
    assert cache.kv_lanes is None and plain.decode_report() is None
    assert cache.pool_bytes() == plain.state_report()["pool_bytes"] \
        == HF["num_hidden_layers"] * (pages + 1) * PAGE * LANES * 4
    assert plain.moe_report()["expert_load"].shape == (2, 8)


@pytest.mark.parametrize("width, lanes", [
    (576, 640), (40, 128), (1, 128), (128, 128), (512, 512), (640, 640)])
def test_a_latent_row_lies_in_whole_lane_tiles(width, lanes):
    """The rule reads the row's width alone: rounded up to whole tiles of
    128 lanes (kanana-2 and Ling-3.0-flash: 512 + 64 -> 640), and a width
    that already fills them keeps its shape letter for letter."""
    assert kv_cache.latent_pool_shape(5, 2305, 16, width) \
        == (5, 2305, 16, lanes)
    cache = kv_cache.PagedKVCache(2, 4, 16, 8, 6, 2, 3, latent_dim=width)
    assert cache.pools["latent_pool"].shape == (2, 7, 8, lanes)
    assert cache.latent_lanes == lanes and cache.kv_lanes is None


def _pad_lanes(sess):
    """(the largest magnitude in the pool's lanes past the row's width,
    that among the lanes a row fills)."""
    pool = np.asarray(sess.cache.pools["latent_pool"])
    return float(np.abs(pool[..., ROW:]).max()), \
        float(np.abs(pool[..., :ROW]).max())


@pytest.mark.parametrize("upto", ["prefill", "decode", "copy_on_write",
                                  "release"])
def test_the_pad_lanes_stay_zero(prefix, upto):
    """What lies past a row's width is written zero by every append and
    copied as zero: after a prefill (a bucket's padding rows on the trash
    page too), after decode steps (idle slots' rows too), after a
    copy-on-write of a shared page and after the slot's release."""
    assert _pad_lanes(prefix)[0] == 0.0
    prompt = tokens(61, 2 * PAGE + 3)
    slot = prefix.try_alloc(len(prompt), 6, tokens=prompt)
    prefix.prefill(slot, prompt)
    if upto != "prefill":
        for _ in range(3):
            prefix.step()
    if upto in ("copy_on_write", "release"):
        cache = prefix.cache
        page = cache._pages_of[slot][0]
        cache._refcount[page] += 1
        assert cache.ensure_writable(slot, 0, 1) == 1
        new = cache._pages_of[slot][0]
        pool = np.asarray(cache.pools["latent_pool"])
        np.testing.assert_array_equal(pool[:, new], pool[:, page])
        cache._drop_ref(page)
    if upto == "release":
        prefix.release(slot)
    pad, row = _pad_lanes(prefix)
    assert pad == 0.0 and row > 0.0


# rank + rope: the suite's 32 + 8 = 40 (a third of a lane tile) and a row
# that fills one whole tile, which keeps its shape
WIDTHS = {"a_third_of_a_tile": (32, 8), "one_whole_tile": (96, 32)}


@pytest.mark.parametrize("exact", [False, True])
@pytest.mark.parametrize("name", sorted(WIDTHS))
def test_paged_prefill_and_decode_equal_full_forward_at_both_widths(name,
                                                                    exact):
    """The paged paths over a pool whose rows are padded (40 -> 128
    lanes) and over one whose rows are not (128): a prefill's last row
    and four decode steps' rows are ``full_forward``'s rows of the same
    tokens."""
    rank, rope = WIDTHS[name]
    cfg = model_config(dict(HF, kv_lora_rank=rank, qk_rope_head_dim=rope))
    params = serve_model.init_params(cfg, seed=4)
    sess = serve.InferenceSession(params, model=cfg, config=serve.ServeConfig(
        slots=3, page_size=PAGE, buckets=(16, 32), max_new=16, exact=exact))
    assert sess.cache.latent_lanes == 128
    assert (rank + rope) % 128 == (0 if name == "one_whole_tile" else 40)

    forward = jax.jit(lambda seq: serve_model.full_forward(
        params, seq, cfg, exact=exact))

    def full(seq):      # one compilation: a causal model's earlier rows
        padded = jnp.asarray([seq + [0] * (32 - len(seq))], jnp.int32)
        return np.asarray(forward(padded))[0, len(seq) - 1]

    seqs = {}
    for i, n in enumerate((7, 21)):
        p = tokens(70 + i, n)
        slot = sess.try_alloc(n, 8, tokens=p)
        first, logits = sess.prefill(slot, p)
        assert_close_across_executables(np.asarray(logits), full(p))
        seqs[slot] = p + [first]
    for _ in range(4):
        toks, logits = sess.step()
        logits = np.asarray(logits)
        for slot, seq in seqs.items():
            assert_close_across_executables(logits[slot], full(seq))
            seq.append(toks[slot])
    assert sess.fallback_count() == 0


@pytest.mark.parametrize("conf", [dict(spec_k=2), dict(kv_quant="int8")])
def test_unsupported_combinations_are_refused(params, conf):
    with pytest.raises(MXNetError, match="does not support"):
        session(params, **conf)


def test_a_wrong_or_missing_architecture_is_refused(params):
    with pytest.raises(MXNetError, match="num_heads= .* or model="):
        serve.InferenceSession(params, config=serve.ServeConfig())
    with pytest.raises(MXNetError, match="architecture says"):
        serve.InferenceSession(
            params, model=model_config(dict(HF, kv_lora_rank=16)),
            config=serve.ServeConfig(page_size=PAGE, buckets=(16,)))
    with pytest.raises(MXNetError, match="unknown block"):
        serve.ModelConfig(vocab_size=8, num_layers=1, d_model=8,
                          num_heads=1, max_len=8, block="other").validate()


def test_weight_only_int8_serves_the_block(params):
    """The cell's control: the quantized session runs, and lands where a
    lower precision lands, off the float32 reference by more than
    rounding and by less than a wrong model."""
    sess = session(params, quant="int8")
    seq = tokens(42, 20)
    slot = sess.try_alloc(len(seq), 4, tokens=seq)
    _, logits = sess.prefill(slot, seq)
    logits = np.asarray(logits)
    gap = spacings_apart(logits, ref_logits(params, seq)[-1])
    assert 1e3 < gap < 1e6


def test_the_comparison_can_fail(plain, params):
    """The planted faults the limit has to catch: a position off by one,
    and an expert's contribution left out."""
    seq = tokens(43, 20)
    slot = plain.try_alloc(len(seq), 4, tokens=seq)
    first, _ = plain.prefill(slot, seq)
    plain.cache.lengths[slot] -= 1            # decode at the wrong position
    _, logits = plain.step()
    logits = np.asarray(logits)
    want = ref_logits(params, seq + [first])[-1]
    assert spacings_apart(logits[slot], want) > 1e3
    starved = dict(params)
    starved["blk1_experts_down_weight"] = \
        params["blk1_experts_down_weight"].at[2].set(0.0)
    got = np.asarray(serve_model.full_forward(
        starved, jnp.asarray([seq], jnp.int32), CFG, exact=False))[0]
    assert spacings_apart(got, ref_logits(params, seq)) > 1e3


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


def _gpt2_session():
    cfg = serve.ModelConfig(vocab_size=61, num_layers=2, d_model=32,
                            num_heads=2, max_len=64)
    assert cfg.block == "gpt2"
    sess = serve.InferenceSession(
        serve_model.init_params(cfg, seed=3), num_heads=2,
        config=serve.ServeConfig(slots=3, page_size=PAGE, buckets=(8, 16),
                                 max_new=8))
    assert sess.moe_report() is None and sess.counters == {}
    assert sess.cache.pool_bytes() == 2 * 2 * 10 * PAGE * 2 * 16 * 4
    # two heads of 16 fold into the pools' last axis (kv_cache.py)
    pool = jax.ShapeDtypeStruct((2, 3 * 3 + 1, PAGE, 2 * 16), jnp.float32)
    return sess, 3, ["decode", "prefill_16", "prefill_8"], (pool, pool)


def _latent_session(params):
    sess = session(params)
    f32, i32 = jnp.float32, jnp.int32
    return sess, 6, ["decode", "prefill_16", "prefill_32"], (
        jax.ShapeDtypeStruct(
            kv_cache.latent_pool_shape(3, 3 * 6 + 1, PAGE, ROW), f32),
        jax.ShapeDtypeStruct((2, 5 + 2 * 8), i32))


@pytest.mark.parametrize("block", ["gpt2", "deepseek_v3"])
def test_flattened_executable_inputs_are_pinned(block, params):
    """What an executable is compiled over, leaf by leaf in the order the
    device sees them: the parameters, the step's tokens (a decode step's
    three: the host's, the launch before's, the mask between them),
    lengths and tables, then the cache's pools and the block's counters, and nothing
    else.  The GPT-2 call infers as before (two pools, no counters); the
    latent call has its one pool and its routers' counts.  How the
    session groups them into arguments is free to change; this is not."""
    sess, width, names, state = (
        _gpt2_session() if block == "gpt2" else _latent_session(params))
    assert sorted(sess.executables) == names
    sds, i32 = jax.ShapeDtypeStruct, jnp.int32
    param_avals = jax.tree.map(lambda v: sds(v.shape, v.dtype), sess.params)

    def leaves(sig):
        return [leaf for _, leaf in sig]

    assert leaves(sess._exes["decode"].aval_sig) == leaves(signature_of(
        (param_avals, sds((3,), i32), sds((3,), i32), sds((3,), jnp.bool_),
         sds((3,), i32), sds((3, width), i32)) + state))
    assert leaves(sess._exes["prefill_16"].aval_sig) == leaves(signature_of(
        (param_avals, sds((1, 16), i32), sds((), i32), sds((), i32),
         sds((width,), i32)) + state))
