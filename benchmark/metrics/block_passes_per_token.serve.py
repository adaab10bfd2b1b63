"""Block passes a slot made for each token it committed in the window,
from the program's own counts (``counters["diffusion_stats"]`` through
``session.block_report()``): ``slot_passes / tokens_committed``, a live
slot's share of one block-pass call over the generated tokens the requests
asked for.  1.0 is autoregression (a pass a token); a block of 4 that
takes 4 denoise passes and its commit reads 1.25 and a little more (a
first block opened by a prompt's last tokens and a last block's dropped
tail commit fewer than 4); a model whose rows clear the confidence
threshold takes fewer passes, and a commit fused with the next block's
first pass would take one off every block."""
LAYER = "step program"
UNIT = "ratio"
MOVES = "serve_tokens_per_s"


def read(run):
    block = run["facts"].get("block", {})
    if not block.get("tokens_committed") or "slot_passes" not in block:
        return None
    return block["slot_passes"] / block["tokens_committed"]
