"""What the serve-side test files share: the per-test lending of the
module-scoped sessions, and the traced text of a block's executables.

Compiling an ``InferenceSession`` per test is what would make these
files slow, so the sessions stay module-scoped; what a test may not do
is leave one in the state it failed in (a test that died holding two of
three slots once starved every test after it).  Each file wraps its
module-scoped session(s) in a function-scoped fixture whose body is
``yield from lend(...)``."""
import jax
import jax.numpy as jnp
import numpy as np

from mxnet_tpu import serve
from mxnet_tpu.serve import latent_moe
from mxnet_tpu.serve import model as serve_model

from closeness import spacings_apart


def lend(*sessions):
    """Lend module-scoped sessions to one test.  Whether the test
    passed or not, every session goes back through ``reset_cold()``
    (all slots released, prefix index dropped), and its occupancy must
    then equal what the test was handed — so a leak ``reset_cold``
    cannot repair is reported on the test that caused it, not on the
    one after.  Yields the session, or the list of them."""
    before = [s.state_report() for s in sessions]
    yield sessions[0] if len(sessions) == 1 else list(sessions)
    for sess in sessions:
        sess.reset_cold()
    after = [s.state_report() for s in sessions]
    assert after == before, (
        "session not back at its baseline after reset_cold(): %s -> %s"
        % (before, after))


def traced_programs(sess, bucket):
    """-> ({"decode": text, "prefill": text}, notes): the jaxprs of the
    block's ``decode_step`` and ``prefill_forward`` traced over the
    session's own parameters, pools and counters (nothing is lowered or
    run), and what the block noted while they were traced
    (``serve_model.trace_notes``), a dict an executable."""
    block, conf, cache = sess.block, sess.config, sess.cache
    i32 = jnp.int32
    static = dict(cfg=sess.model, page_size=conf.page_size,
                  exact=bool(conf.exact))
    state = (cache.pools, sess.counters)
    calls = {
        "decode": (block.decode_step, (
            sess.params, jnp.zeros((conf.slots,), i32),
            jnp.zeros((conf.slots,), i32),
            jnp.zeros((conf.slots, cache.table_width), i32)) + state, {}),
        "prefill": (block.prefill_forward, (
            sess.params, jnp.zeros((1, bucket), i32), i32(bucket - 1),
            i32(0), jnp.zeros((cache.table_width,), i32)) + state,
            {"slot": i32(0) if cache.hybrid else None}),
    }
    texts, notes = {}, {}
    for name, (fn, args, more) in calls.items():
        with serve_model.trace_notes() as notes[name]:
            texts[name] = str(jax.make_jaxpr(
                lambda *a: fn(*a, **static, **more))(*args))
    return texts, notes


def expert_layer_config(d, f, experts, top_k, held=()):
    """A ``ModelConfig`` whose routed-expert layer has these sizes and
    whose other sizes are toys: for the tests that call
    ``latent_moe._routed_experts`` alone."""
    return serve.ModelConfig(
        block="deepseek_v3", vocab_size=64, num_layers=2, d_model=d,
        num_heads=2, max_len=64, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, kv_lora_rank=32, d_ff=64, first_k_dense=1,
        moe_d_ff=f, n_routed_experts=experts, num_experts_per_tok=top_k,
        n_shared_experts=0, experts_held=held)


def assert_the_cpu_runs_the_expert_loop(plain, quantized, expert_layers,
                                        monkeypatch):
    """What both expert blocks' files assert of their toy sessions
    (``plain``, and ``quantized`` built with ``quant="int8"``): see
    ``test_on_the_cpu_the_expert_layers_run_the_loop`` there."""
    texts, notes = traced_programs(plain, 16)
    for name, text in texts.items():
        assert "while" in text and "pallas_call" not in text, name
        assert notes[name] == {"expert_kernel_layers": 0}
    for report in (plain.block_report(), plain.moe_report()):
        assert report["expert_kernel_layers"] == 0
        assert type(report["expert_kernel_layers"]) is int
    told = []
    monkeypatch.setattr(
        latent_moe, "grouped_swiglu_eligible",
        lambda x, gate, up, down, tile, exact, dequantized:
        told.append((exact, dequantized)) or True)
    texts, notes = traced_programs(plain, 16)
    for name, text in texts.items():
        assert text.count("pallas_call") == expert_layers, name
        assert notes[name] == {"expert_kernel_layers": expert_layers}
    assert set(told) == {(False, False)}
    del told[:]
    traced_programs(quantized, 16)
    assert set(told) == {(False, True)}


def reference_row(sess, seq, params=None):
    """Last-row logits of the jitted full-context reference forward over
    ``seq`` — at the session's own KV precision, over the session's own
    (possibly quantized) weights unless ``params`` says otherwise."""
    return np.asarray(serve_model.reference_last_logits(
        sess.params if params is None else params, seq, sess.model,
        sess.config.page_size, exact=True,
        kv_quant=sess.config.kv_quant))


def worst_gap_vs_reference(sess, prompts, steps, max_new=8, plant=None,
                           ref_params=None):
    """Prefill every prompt into a slot of its own, decode ``steps``
    steps co-batched, and return the largest gap (tests/closeness.py's
    spacings) between any logits row the session returned and the
    reference forward over the same tokens.  ``plant(sess, slots)``,
    when given, corrupts the session once before the first decode step:
    the control that shows the comparison can fail."""
    slots, seqs, worst = [], [], 0.0
    for p in prompts:
        slot = sess.try_alloc(len(p), max_new, tokens=p)
        assert slot is not None
        first, logits = sess.prefill(slot, p)
        logits = np.asarray(logits)
        worst = max(worst, spacings_apart(
            logits, reference_row(sess, p, ref_params)))
        slots.append(slot)
        seqs.append(list(p) + [first])
    if plant is not None:
        plant(sess, slots)
    for _ in range(steps):
        toks, logits = sess.step()
        logits = np.asarray(logits)
        for slot, seq in zip(slots, seqs):
            worst = max(worst, spacings_apart(
                logits[slot], reference_row(sess, seq, ref_params)))
            seq.append(toks[slot])
    return worst
