"""Roofline share of the latent-attention / routed-expert decode step: the
least bytes one step must read (the family's ``decode_least_bytes``: every
matmul weight outside the routed experts once, the routed experts that at
least one row reached, counted on the device by the program's own routers
and read through ``session.moe_report()``, and the live latent rows of
every slot's context from the benchmark's own stamps) over the HBM peak,
over the device time of one decode module event from the trace.  Means
over the window's steps.  A decode step at 16 slots is bound by bytes."""
import manifest

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_gap_p95_ms"


def read(run):
    facts, trace = run["facts"], run["trace"]
    moe = facts.get("moe")
    if not trace or not moe or not facts.get("step_live") \
            or not moe["decode_steps"]:
        return None
    events = [(count, total) for name, (count, total)
              in trace["modules"].items() if facts["decode_module"] in name]
    if not events:
        return None
    count = sum(c for c, _ in events)
    device_s = sum(t for _, t in events)
    family = manifest.load_module("families", facts["family"],
                                  facts["bench_root"])
    steps = facts["step_live"]
    least = family.decode_least_bytes(
        facts["config"], moe["distinct_experts"] / moe["decode_steps"],
        sum(live for _, live in steps) / len(steps)) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (device_s / count)
