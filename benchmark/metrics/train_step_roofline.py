"""Roofline share of the whole train step (until kernels have names):
the least time a chip could take for one step, the larger of its
operations over the bf16 peak and its bytes over the HBM peak
(``flops.py``), over the step's device time from the trace.  A step over
several chips is counted as each chip's even share of the operations and
of the bytes (a lower bound: replicated weights are read whole)."""
import flops
import trace_reduce

LAYER = "kernels"
UNIT = "%"
MOVES = "train_items_per_s"


def read(run):
    facts = run["facts"]
    found = trace_reduce.step_module(run["trace"])
    if "train_flops_per_item" not in facts or found is None:
        return None
    _, count, total = found
    least, bound = flops.roofline_seconds(
        facts["train_flops_per_item"] * facts["items_per_step"]
        / run["chips"],
        flops.train_step_bytes(facts["n_params"], facts["batch_bytes"],
                               facts["output_bytes"]) / run["chips"],
        run["peaks"])
    print("train_step_roofline: bound by %s, least %.4f s a step" %
          (bound, least), flush=True)
    return 100.0 * least / (total / count)
