"""The two comparisons the serving and ZeRO tests make, and why there
are two (ROADMAP.md D2).

*Same executable, same inputs -> same bits.*  Failover replay, an
idempotent retry, park/resume re-prefill, a checkpoint round trip of
codes and tiles, "the decode pool's bytes at step 1 == at step N": one
compiled program run twice on equal inputs.  That holds on every
backend, and those tests say ``np.testing.assert_array_equal``.

*Different executables (or shapes) -> a stated gap.*  A decode row
against ``reference_last_logits``, prefill against decode, a verify row
against a decode row, ZeRO against replicated: two programs that
compute the same sums.  XLA is free to vectorise each program's
reductions differently, so their float32 results differ in the last
bits (jax 0.9.0 on the CPU: 1-2 spacings where earlier versions gave
0), and nothing the program does can promise otherwise.  Those tests
call :func:`assert_close_across_executables`.
"""
import numpy as np

# Limit, in spacings of the compute dtype at the row's largest
# magnitude.  Largest sound readings (jax 0.9.0, XLA:CPU, 12 seeds a
# family; CHANGES.md PR 24 has the table): 5 for logits rows, 4 for
# ZeRO's parameters under adam, so the limit is six times that.  The
# smallest planted fault (a page-table entry one page off, a position
# off by one, a stale ring row, a scale of the wrong row) reads 1 475 or
# more: each family's control case shows it.
LIMIT_SPACINGS = 32.0


def spacings_apart(got, want, dtype="float32"):
    """Largest ``|got - want|`` in units of ``dtype``'s spacing at the
    largest magnitude of ``want``: the unit in which two orders of one
    float sum differ."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    assert got.shape == want.shape, (got.shape, want.shape)
    if not want.size:
        return 0.0
    top = float(np.max(np.abs(want)))
    if dtype == "bfloat16":
        import ml_dtypes

        eps = float(ml_dtypes.finfo(ml_dtypes.bfloat16).eps)
    else:
        eps = float(np.finfo(dtype).eps)
    # spacing at top: eps * 2**floor(log2(top)); a zero row compares in
    # units of the smallest normal's spacing
    unit = eps * 2.0 ** np.floor(np.log2(max(top, float(
        np.finfo(np.float32).tiny))))
    return float(np.max(np.abs(got - want)) / unit)


def assert_close_across_executables(got, want, limit=LIMIT_SPACINGS,
                                    dtype="float32", err_msg=""):
    """``got`` and ``want`` come from two different compiled programs
    that compute the same thing: they agree within ``limit`` spacings."""
    gap = spacings_apart(got, want, dtype)
    assert gap <= limit, (
        "%s%.1f %s spacings apart (limit %.0f): two executables "
        "disagree by more than a reordered sum explains"
        % (err_msg and err_msg + ": ", gap, dtype, limit))
