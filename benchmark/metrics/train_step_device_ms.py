"""Device time of one train step: the step program's module events on
the TPU plane of the trace (``trace_reduce.step_module``: the module with
most device time in a training window), seconds over count."""
import trace_reduce

LAYER = "step program"
UNIT = "ms"
MOVES = "train_items_per_s"


def read(run):
    found = trace_reduce.step_module(run["trace"])
    if found is None:
        return None
    _, count, total = found
    return total / count * 1e3
