"""Roofline share of the block-diffusion / grouped-query / held-experts
prefill: the operations the window's prompts need (the family's
``prefill_flops``: a prompt's whole blocks alone, 2 per active matmul
parameter per row with the held experts a row takes under balanced
routing, attention under the block-causal mask in every layer, no head: a
prefill yields no token) over the bf16 peak, over the device time of the
window's prefill module events from the trace.  A prompt longer than the
largest bucket is two events (one a chunk) and a prompt shorter than a
block none, so the share is the prompts' operations over ALL the events'
device time, not a mean a call; every bucket lies under the one module
name; a bucket's padding and the keys a chunk's reader visits outside a
row's horizon are the program's cost and not counted."""
import manifest

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_ttft_p95_ms"


def read(run):
    facts, trace = run["facts"], run["trace"]
    fed = facts.get("prefill_tokens")
    if not trace or not fed \
            or "slot_passes" not in facts.get("block", {}):
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
