"""Model FLOP/s utilization of the traced run: operations the forward
and backward passes need per item (``flops.py``; recomputation not
counted, attention counted unmasked) times items per second, over chips
times the bf16 peak."""
LAYER = "step program"
UNIT = "%"
MOVES = "train_items_per_s"


def read(run):
    facts = run["facts"]
    if "train_flops_per_item" not in facts:
        return None
    rate = facts["steps"] * facts["items_per_step"] / facts["window_s"]
    peak = run["chips"] * run["peaks"]["bf16_flops_per_s"]
    return 100.0 * rate * facts["train_flops_per_item"] / peak
