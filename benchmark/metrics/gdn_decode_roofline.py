"""Roofline share of the Gated DeltaNet / gated grouped-query / held-experts
decode step: the least bytes one step must move (the family's
``decode_least_bytes``: every matrix outside the routed experts and the
head's slice once, the held experts that at least one row reached, counted
on the device by the program's own routers and read through
``session.block_report()``, each live slot's DeltaNet state and convolution
rows read and written in every DeltaNet layer, the live K/V rows of every
slot's context in the attention layers; live slots and rows from the
benchmark's own stamps) over the HBM peak, over the device time of one
decode module event from the trace.  Means over the window's steps.  A
decode step at 32 slots is bound by bytes."""
import manifest

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_gap_p95_ms"


def read(run):
    facts, trace = run["facts"], run["trace"]
    steps, block = facts.get("step_live"), facts.get("block", {})
    if not trace or not steps or "gdn_layers" not in block \
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
    least = family.decode_least_bytes(
        facts["config"],
        block["distinct_held_experts"] / block["decode_steps"],
        sum(step[0] for step in steps) / len(steps),
        sum(step[1] for step in steps) / len(steps)) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (device_s / count)
