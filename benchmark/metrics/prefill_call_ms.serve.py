"""Median wall time of ``session.prefill`` in the window: one request's
bucketed prefill, ending in a host read of its first token."""
import statistics

LAYER = "step program"
UNIT = "ms"
MOVES = "serve_ttft_p95_ms"


def read(run):
    w0, w1 = run["window"]
    calls = run["spans"].durations("prefill", since=w0, until=w1)
    return statistics.median(calls) * 1e3 if calls else None
