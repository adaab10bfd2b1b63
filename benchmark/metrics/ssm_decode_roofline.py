"""Roofline share of the Mamba-2 / grouped-query decode step: the least
bytes one step must move (the family's ``decode_least_bytes``: every
matmul weight and the tied head once, each live slot's state and
convolution rows read and written in every Mamba-2 layer, the live K/V
rows of every slot's context in the attention layers; live slots and rows
from the benchmark's own stamps) over the HBM peak, over the device time
of one decode module event from the trace.  Means over the window's
steps.  A decode step at 16 slots is bound by bytes."""
import manifest

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_gap_p95_ms"


def read(run):
    facts, trace = run["facts"], run["trace"]
    steps = facts.get("step_live")
    if not trace or not steps or "mamba_layers" not in facts.get("block", {}):
        return None
    events = [(count, total) for name, (count, total)
              in trace["modules"].items() if facts["decode_module"] in name]
    if not events:
        return None
    count = sum(c for c, _ in events)
    device_s = sum(t for _, t in events)
    family = manifest.load_module("families", facts["family"],
                                  facts["bench_root"])
    least = family.decode_least_bytes(
        facts["config"], sum(n for n, _ in steps) / len(steps),
        sum(live for _, live in steps) / len(steps)) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (device_s / count)
