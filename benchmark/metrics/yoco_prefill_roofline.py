"""Roofline share of the half-depth prefill: the operations the window's
prefill calls need (the family's ``prefill_flops``: over every prompt
token 2 per matmul parameter of the layers up to the memory layer and of
the owner's K and V projection, the selective scan's elementwise
operations in every Mamba layer and window attention in the window layers;
over the prompt's LAST token the rest of the stack, each reader's
attention over the owner's pages and the head; the identity's zeros in the
score products are not counted; prompts are unshared and fit one bucket,
so a call is one chunk from position 0) over the bf16 peak, over the
device time of as many prefill module events from the trace.  Means over
the window's calls, every bucket under the one module name; a bucket's
padding is the program's cost and not counted.  The scan's operations are
elementwise and never reach the MXU: the share says how far the whole
chunk is from the one peak the chip publishes, the scan's part of it
included."""
import manifest

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_ttft_p95_ms"


def read(run):
    facts, trace = run["facts"], run["trace"]
    fed = facts.get("prefill_tokens")
    if not trace or not fed \
            or "shared_readers" not in facts.get("block", {}):
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
