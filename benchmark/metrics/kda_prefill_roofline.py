"""Roofline share of the KDA / latent-attention / held-experts prefill:
the operations the window's prefill calls need (the family's
``prefill_flops``: 2 per active matmul parameter per prompt token, with the
held experts a token takes under balanced routing; the chunked form's own
products in every KDA layer; causal attention in the latent layers; the
head for the last token; prompts are unshared and fit one bucket, so a call
is one chunk from position 0) over the bf16 peak, over the device time of
as many prefill module events from the trace.  Means over the window's
calls, every bucket under the one module name; a bucket's padding is the
program's cost and not counted.  It moves the gap's tail, which holds a
prefill between two decode steps; this cell does not report a time to
first token (PERF.md, Findings PR 33: its 95th percentile is the 13th
largest of ~260 and jumps by its neighbours' distance)."""
import manifest

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_gap_p95_ms"


def read(run):
    facts, trace = run["facts"], run["trace"]
    fed = facts.get("prefill_tokens")
    if not trace or not fed or "kda_layers" not in facts.get("block", {}):
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
