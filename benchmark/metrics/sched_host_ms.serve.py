"""What the scheduler's own Python adds to a decode boundary: the median
wall time of one ``Scheduler.tick`` less the ``session.prefill``,
``session.step`` and ``session.release`` calls inside it (the
benchmark's wrappers)."""
import statistics

LAYER = "entry"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
INNER = ("prefill", "step", "release")


def read(run):
    w0, w1 = run["window"]
    spans = sorted((s for s in run["spans"].spans if w0 <= s[1] < w1),
                   key=lambda s: s[1])
    ticks = [s for s in spans if s[0] == "tick"]
    inner = [s for s in spans if s[0] in INNER]
    if not ticks:
        return None
    own, i = [], 0
    for _, t0, t1 in ticks:
        inside = 0.0
        while i < len(inner) and inner[i][1] < t0:
            i += 1
        j = i
        while j < len(inner) and inner[j][2] <= t1:
            inside += inner[j][2] - inner[j][1]
            j += 1
        own.append(t1 - t0 - inside)
    return statistics.median(own) * 1e3
