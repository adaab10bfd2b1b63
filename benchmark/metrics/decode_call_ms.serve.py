"""Median wall time of ``session.step`` in the window: one decode step
for every live slot, ending in a host read of the new tokens."""
import statistics

LAYER = "step program"
UNIT = "ms"
MOVES = "serve_gap_p95_ms"


def read(run):
    w0, w1 = run["window"]
    calls = run["spans"].durations("step", since=w0, until=w1)
    return statistics.median(calls) * 1e3 if calls else None
