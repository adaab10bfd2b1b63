"""Roofline share of the Mamba-1 / differential-attention / shared-pages
decode step: the least bytes one step must move (the family's
``decode_least_bytes``: every matmul weight and the tied head once; each
live slot's state and convolution rows read and written in every Mamba
layer; the rows inside the band in every window layer; and the owner's
live rows once for EACH layer that reads its pages, the owner and every
cross-attention layer: nothing keeps a page on the chip from one layer to
the next) over the HBM peak, over the device time of one decode module
event from the trace.  Live slots and rows from the benchmark's own
stamps; the rows inside the band from the program's own count
(``window_rows_in_band`` of ``session.block_report()``: the window
layers' sum over the window's decode steps).  Means over the window's
steps.  A decode step at 32 slots is bound by bytes."""
import manifest

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_gap_p95_ms"


def read(run):
    facts, trace = run["facts"], run["trace"]
    steps, block = facts.get("step_live"), facts.get("block", {})
    if not trace or not steps or "shared_readers" not in block \
            or not block.get("decode_steps"):
        return None
    events = [(count, total) for name, (count, total)
              in trace["modules"].items() if facts["decode_module"] in name]
    if not events:
        return None
    count = sum(c for c, _ in events)
    device_s = sum(t for _, t in events)
    family = manifest.load_module("families", facts["family"],
                                  facts["bench_root"])
    band = block["window_rows_in_band"] / float(
        block["decode_steps"] * max(block["window_layers"], 1))
    least = family.decode_least_bytes(
        facts["config"], sum(s[0] for s in steps) / len(steps),
        sum(s[1] for s in steps) / len(steps), band) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (device_s / count)
