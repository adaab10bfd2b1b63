"""The host side of a serve call (``serve/session.py``: ``_dispatch``,
``step``, ``prefill``) does work that does not grow with the model.

* The parameters are an executable's first argument, some hundreds of
  leaves assigned once: their part of the call signature is described
  when the executable is built, and a call describes only the arguments
  it makes anew.  What the recompile guard observes is still
  ``signature_of`` of the whole argument tuple, so drift of anything a
  caller or the cache can get wrong still goes through the lazy ``jit``.
* ``step`` / ``prefill`` hand back the executable's logits as the device
  array they are; who wants numbers converts, and pays then.

Every case runs over both blocks of ``model.BLOCKS`` and over weight-only
int8 (``{"q", "s"}`` records are leaves of the same constant part).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import serve
from mxnet_tpu.compile_cache import signature_of
from mxnet_tpu.serve.scheduler import Request, Scheduler

from serve_util import lend

GPT2 = serve.ModelConfig(vocab_size=61, num_layers=3, d_model=32,
                         num_heads=2, max_len=64)
LATENT = serve.ModelConfig(
    block="deepseek_v3", vocab_size=61, num_layers=2, d_model=32,
    num_heads=2, max_len=64, qk_nope_head_dim=8, qk_rope_head_dim=4,
    v_head_dim=8, kv_lora_rank=12, d_ff=48, first_k_dense=1, moe_d_ff=16,
    n_routed_experts=4, num_experts_per_tok=2, n_shared_experts=1)
CONF = dict(slots=3, page_size=8, buckets=(8, 16), max_new=24)
VARIANTS = {"gpt2": (GPT2, {}), "deepseek_v3": (LATENT, {}),
            "gpt2-int8": (GPT2, {"quant": "int8"}),
            "deepseek_v3-int8": (LATENT, {"quant": "int8"})}
# tokens, lengths, tables and a few scalars, the pools, the counters:
# what a call makes anew, whatever the depth
A_DOZEN = 12


@pytest.fixture(scope="module", params=sorted(VARIANTS))
def _session(request):
    cfg, over = VARIANTS[request.param]
    return serve.InferenceSession(
        serve.init_params(cfg, seed=3), model=cfg,
        config=serve.ServeConfig(**dict(CONF, **over)))


@pytest.fixture
def sess(_session):
    yield from lend(_session)


def prompt(seed, n):
    return np.random.default_rng(seed).integers(0, 61, n).tolist()


def spy(sess, monkeypatch):
    """Record, for every dispatch, ``signature_of`` of the whole argument
    tuple (computed here) beside what the guard was shown."""
    calls = []
    dispatch = sess._dispatch

    def observed(name, args):
        rec = sess._exes[name]
        seen = []
        observe = rec.guard.observe
        monkeypatch.setattr(rec.guard, "observe",
                            lambda sig, **kw: seen.append(sig) or
                            observe(sig, **kw))
        before = rec.leaves_described
        whole = signature_of(args)
        try:
            return dispatch(name, args)
        finally:
            monkeypatch.setattr(rec.guard, "observe", observe)
            calls.append((name, whole, seen,
                          rec.leaves_described - before))

    monkeypatch.setattr(sess, "_dispatch", observed)
    return calls


def serve_some(sess, prefills=3, steps=20):
    slots = {}
    for i in range(prefills):
        p = prompt(20 + i, 5 + 4 * i)            # buckets 8, 16, 16
        slot = sess.try_alloc(len(p), 22, tokens=p)
        slots[slot] = p + [sess.prefill(slot, p)[0]]
    for _ in range(steps):
        for slot, tok in sess.step()[0].items():
            slots[slot].append(tok)
    return slots


def test_a_call_describes_only_what_it_makes_anew(sess, monkeypatch):
    n_params = len(jax.tree.leaves(sess.params))
    built = {name: rep["leaves_described"]
             for name, rep in sess.guard_report().items()}
    calls, fallbacks = spy(sess, monkeypatch), sess.fallback_count()
    serve_some(sess, prefills=3, steps=20)
    assert [name for name, _, _, _ in calls] == \
        ["prefill_8", "prefill_16", "prefill_16"] + ["decode"] * 20
    for name, whole, seen, described in calls:
        # the guard is shown the whole tuple's signature, as it was
        assert seen == [whole]
        assert whole == sess._exes[name].aval_sig
        # and only what follows the parameters was described to make it
        assert described == len(whole) - n_params
        assert 0 < described <= A_DOZEN
    assert sess.fallback_count() == fallbacks
    grown = {name: rep["leaves_described"] - built[name]
             for name, rep in sess.guard_report().items()}
    rest = {name: len(rec.aval_sig) - n_params
            for name, rec in sess._exes.items()}
    assert grown == {"decode": 20 * rest["decode"],
                     "prefill_8": rest["prefill_8"],
                     "prefill_16": 2 * rest["prefill_16"]}


def test_the_parameters_are_described_at_the_build_and_not_again():
    """Twice the depth is twice the parameters' leaves at the build, and
    not one leaf more a call."""
    reports = []
    for layers in (2, 4):
        cfg = serve.ModelConfig(vocab_size=61, num_layers=layers,
                                d_model=32, num_heads=2, max_len=64)
        sess = serve.InferenceSession(
            serve.init_params(cfg, seed=3), model=cfg,
            config=serve.ServeConfig(**CONF))
        n_params = len(jax.tree.leaves(sess.params))
        at_build = sess.guard_report()["decode"]["leaves_described"]
        serve_some(sess, prefills=1, steps=5)
        reports.append((n_params, at_build, sess.guard_report()
                        ["decode"]["leaves_described"] - at_build))
    (p2, b2, c2), (p4, b4, c4) = reports
    assert p4 - p2 == 2 * 12 and b4 - b2 == p4 - p2
    assert c2 == c4 == 5 * (b2 - p2)


@pytest.mark.parametrize("drift", ["dtype", "shape"])
def test_a_drifted_argument_still_goes_through_the_lazy_jit(sess, drift):
    """What a caller can get wrong is what a call still describes: the
    decode step's tokens in another dtype, a prefill's tokens at another
    bucket's length."""
    slots = serve_some(sess, prefills=1, steps=2)
    slot = next(iter(slots))
    before = sess.fallback_count()
    signatures = sess.guard_report()
    if drift == "dtype":
        name = "decode"
        args = (sess.params, jnp.zeros((CONF["slots"],), jnp.int16),
                jnp.zeros((CONF["slots"],), jnp.int32),
                jnp.ones((CONF["slots"],), jnp.bool_),
                sess.cache.lengths_arg(), sess.cache.device_tables(),
                sess.cache.pools, sess.counters)
    else:
        name = "prefill_8"
        args = (sess.params, jnp.zeros((1, 16), jnp.int32),
                jnp.asarray(3, jnp.int32), jnp.asarray(0, jnp.int32),
                sess.cache.table_row(slot), sess.cache.pools,
                sess.counters, None)
    _, logits, sess.cache.pools, sess.counters = sess._dispatch(name, args)
    assert logits.shape[-1] == 61
    assert sess.fallback_count() == before + 1
    assert sess.guard_report()[name]["signatures"] == \
        signatures[name]["signatures"] + 1
    sess.step()                                  # and the next call is sound
    assert sess.fallback_count() == before + 1


def test_parameters_assigned_anew_are_described_there_once(sess):
    serve_some(sess, prefills=1, steps=2)
    n_params = len(jax.tree.leaves(sess.params))
    rec = sess._exes["decode"]
    rest = len(rec.aval_sig) - n_params
    kept, fallbacks = sess.params, sess.fallback_count()
    try:
        sess.params = dict(kept)                 # equal leaves, another tree
        before = rec.leaves_described
        sess.step()
        assert rec.leaves_described - before == n_params + rest
        sess.step()
        assert rec.leaves_described - before == n_params + 2 * rest
        assert sess.fallback_count() == fallbacks
        # a parameter of another dtype is seen where it is assigned
        name = sorted(k for k, v in kept.items() if not isinstance(v, dict)
                      and v.dtype == jnp.float32)[0]
        sess.params = dict(kept, **{name: kept[name].astype(jnp.bfloat16)})
        sess.step()
        assert sess.fallback_count() == fallbacks + 1
    finally:
        sess.params = kept
    sess.step()
    assert sess.fallback_count() == fallbacks + 1


def test_every_executable_returns_its_logits_and_donates_the_cache(sess):
    """The executables are the parent's: tokens, logits, pools, counters
    out; the pools and the counters donated, and nothing else."""
    n_params = len(jax.tree.leaves(sess.params))
    n_state = len(jax.tree.leaves((sess.cache.pools, sess.counters)))
    assert sorted(sess.executables) == ["decode", "prefill_16", "prefill_8"]
    for name, compiled in sess.executables.items():
        toks, logits, pools, counters = compiled.out_info
        if name == "decode":
            assert (toks.shape, logits.shape) == ((3,), (3, 61))
        else:
            assert (toks.shape, logits.shape) == ((), (61,))
        assert logits.dtype == jnp.float32
        assert jax.tree.structure(pools) == \
            jax.tree.structure(sess.cache.pools)
        assert jax.tree.structure(counters) == \
            jax.tree.structure(sess.counters)
        donated = [leaf.donated for leaf in
                   jax.tree.leaves(compiled.args_info)]
        assert not any(donated[:-n_state]) and all(donated[-n_state:])
        # a decode step's tokens are three: the host's, the launch
        # before's, and the mask between them
        assert len(donated) - n_params - n_state == \
            (5 if name == "decode" else 4)


def test_logits_stay_on_the_device_until_read(sess):
    """The second value of ``step`` / ``prefill`` is the executable's
    output array; ``np.asarray`` of it is what the session used to hand
    back: the same executable over the same inputs gives the same bits,
    and the emitted token is its row's argmax."""
    def run():
        rows, slots = [], {}
        for i in range(2):
            p = prompt(30 + i, 6 + 5 * i)
            slot = sess.try_alloc(len(p), 8, tokens=p)
            first, logits = sess.prefill(slot, p)
            assert isinstance(logits, jax.Array) and logits.shape == (61,)
            assert first == int(np.argmax(np.asarray(logits)))
            rows.append(np.asarray(logits))
            slots[slot] = first
        for _ in range(4):
            toks, logits = sess.step()
            assert isinstance(logits, jax.Array)
            assert logits.shape == (3, 61) and logits.dtype == jnp.float32
            host = np.asarray(logits)
            for slot in slots:
                assert toks[slot] == int(np.argmax(host[slot]))
            rows.append(host[sorted(slots)])
        return rows

    first_time = run()
    sess.reset_cold()
    for a, b in zip(first_time, run()):
        np.testing.assert_array_equal(a, b)


def test_scheduler_emits_the_tokens_of_a_hand_driven_session(sess):
    """Eight requests through ``Scheduler.run``, which reads no logits,
    are the tokens of the same requests served one at a time by hand,
    each the argmax of the logits a caller converts."""
    prompts = [prompt(40 + i, 4 + (5 * i) % 12) for i in range(8)]
    want, fallbacks = [], sess.fallback_count()
    for p in prompts:
        slot = sess.try_alloc(len(p), 6, tokens=p)
        first, logits = sess.prefill(slot, p)
        out = [int(np.argmax(np.asarray(logits)))]
        assert out[0] == first
        for _ in range(5):
            toks, logits = sess.step()
            out.append(int(np.argmax(np.asarray(logits)[slot])))
            assert out[-1] == toks[slot]
        sess.release(slot)
        want.append(out)
    sess.reset_cold()
    done, _ = Scheduler(sess, policy="continuous").run(
        [Request(rid=i, prompt=p, max_new=6, arrival_s=0.0)
         for i, p in enumerate(prompts)])
    assert not any(r.failed for r in done), [r.error for r in done]
    assert {r.rid: list(r.tokens) for r in done} == dict(enumerate(want))
    assert sess.fallback_count() == fallbacks


def test_speculative_executables_describe_their_parameters_once():
    """``verify`` takes the target's parameters and ``draft`` the draft's:
    each is described at its build, and a speculative step describes the
    rest."""
    sess = serve.InferenceSession(
        serve.init_params(GPT2, seed=3), num_heads=2,
        config=serve.ServeConfig(spec_k=2, draft="layers:1", **CONF))
    n_target = len(jax.tree.leaves(sess.params))
    n_draft = len(jax.tree.leaves(sess.draft_params))
    assert n_draft < n_target
    p = prompt(50, 7)
    slot = sess.try_alloc(len(p), 12, tokens=p)
    sess.prefill(slot, p)
    before = sess.guard_report()
    for _ in range(3):
        sess.spec_step()
    after = sess.guard_report()
    for name, n_params in (("verify", n_target), ("draft", n_draft)):
        rest = len(sess._exes[name].aval_sig) - n_params
        assert 0 < rest <= A_DOZEN
        assert after[name]["calls"] - before[name]["calls"] == 3
        assert after[name]["leaves_described"] \
            - before[name]["leaves_described"] == 3 * rest
    assert sess.fallback_count() == 0
