"""Roofline share of the decode step: the bytes one step must read (every
matmul weight once and the keys and values of each live slot's context,
``flops.lm_decode_bytes``, from shapes) over the HBM peak, summed over
the window's steps, over the device time of as many decode module events
from the trace.  A decode step is bound by bytes at 16 slots."""
import flops

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_gap_p95_ms"


def read(run):
    facts, trace = run["facts"], run["trace"]
    if not trace or "step_live" not in facts or not facts["step_live"]:
        return None
    events = [(count, total) for name, (count, total)
              in trace["modules"].items() if facts["decode_module"] in name]
    if not events:
        return None
    count = sum(c for c, _ in events)
    device_s = sum(t for _, t in events)
    steps = facts["step_live"]
    mean_live = sum(live for _, live in steps) / len(steps)
    least = flops.lm_decode_bytes(facts["config"], [mean_live]) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (device_s / count)
