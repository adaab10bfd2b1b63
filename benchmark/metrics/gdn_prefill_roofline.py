"""Roofline share of the Gated DeltaNet / gated grouped-query / held-experts
prefill: the operations the window's prompts need (the family's
``prefill_flops``: 2 per active matmul parameter per prompt token, with the
held experts a token takes under balanced routing; the chunked form's own
products in every DeltaNet layer; causal attention at 16 heads of 256 in
the attention layers; the head once a prompt) over the bf16 peak, over the
device time of the window's prefill module events from the trace.  A prompt
longer than the largest bucket is several events (one a chunk), so the
share is the prompts' operations over ALL the events' device time, not a
mean a call; every bucket lies under the one module name; a bucket's
padding and the keys a chunk's reader visits outside a row's horizon are
the program's cost and not counted."""
import manifest

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_ttft_p95_ms"


def read(run):
    facts, trace = run["facts"], run["trace"]
    fed = facts.get("prefill_tokens")
    if not trace or not fed or "gdn_layers" not in facts.get("block", {}):
        return None
    device_s = sum(total for name, (_, total) in trace["modules"].items()
                   if facts["prefill_module"] in name)
    if not device_s:
        return None
    family = manifest.load_module("families", facts["family"],
                                  facts["bench_root"])
    least = sum(family.prefill_flops(facts["config"], n) for n in fed) \
        / run["peaks"]["bf16_flops_per_s"]
    return 100.0 * least / device_s
