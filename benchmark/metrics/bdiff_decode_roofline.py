"""Roofline share of the block-diffusion / grouped-query / held-experts
block pass: the least bytes ONE pass must move (the family's
``decode_least_bytes``: every matrix outside the routed experts and the
head's slice once, the held experts that at least one row reached, counted
on the device by the program's own routers and read through
``session.block_report()``, the K/V rows inside every live slot's horizon
in every layer read and the open blocks' own rows written; live slots and
rows from the benchmark's own stamps) over the HBM peak, over the device
time of one block-pass module event from the trace.  Means over the
window's passes.  A pass at 32 slots x 4 rows is bound by bytes; a pass
yields no token by itself (``block_passes_per_token.serve`` says how many
make one)."""
import manifest

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_gap_p95_ms"


def read(run):
    facts, trace = run["facts"], run["trace"]
    steps, block = facts.get("step_live"), facts.get("block", {})
    if not trace or not steps or "slot_passes" not in block \
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
        sum(step[1] for step in steps) / len(steps),
        sum(step[0] for step in steps) / len(steps)) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (device_s / count)
