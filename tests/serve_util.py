"""What the serve-side test files share: the per-test lending of the
module-scoped sessions.

Compiling an ``InferenceSession`` per test is what would make these
files slow, so the sessions stay module-scoped; what a test may not do
is leave one in the state it failed in (a test that died holding two of
three slots once starved every test after it).  Each file wraps its
module-scoped session(s) in a function-scoped fixture whose body is
``yield from lend(...)``."""
import numpy as np

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
