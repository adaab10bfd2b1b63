"""Roofline share of the Mamba-2 / grouped-query prefill: the operations
the window's prefill calls need (the family's ``prefill_flops``: 2 per
matmul parameter per prompt token, the chunked scan's own products, causal
attention in the attention layers, the head for the last token; prompts
are unshared and fit one bucket, so a call is one chunk from position 0)
over the bf16 peak, over the device time of as many prefill module events
from the trace.  Means over the window's calls, every bucket under the one
module name; a bucket's padding is the program's cost and not counted."""
import manifest

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_ttft_p95_ms"


def read(run):
    facts, trace = run["facts"], run["trace"]
    fed = facts.get("prefill_tokens")
    if not trace or not fed or "mamba_layers" not in facts.get("block", {}):
        return None
    events = [(count, total) for name, (count, total)
              in trace["modules"].items() if facts["prefill_module"] in name]
    if not events:
        return None
    count = sum(c for c, _ in events)
    device_s = sum(t for _, t in events)
    family = manifest.load_module("families", facts["family"],
                                  facts["bench_root"])
    least = sum(family.prefill_flops(facts["config"], n) for n in fed) \
        / len(fed) / run["peaks"]["bf16_flops_per_s"]
    return 100.0 * least / (device_s / count)
