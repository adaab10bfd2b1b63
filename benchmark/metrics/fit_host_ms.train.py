"""What Module.fit's own loop adds to a step: the median wall time
between consecutive batch-end callbacks, less the device time of one
step from the trace."""
import statistics

import trace_reduce

LAYER = "entry"
UNIT = "ms"
MOVES = "train_items_per_s"


def read(run):
    found = trace_reduce.step_module(run["trace"])
    steps = run["facts"].get("step_times_s")
    if found is None or not steps:
        return None
    _, count, total = found
    return (statistics.median(steps) - total / count) * 1e3
