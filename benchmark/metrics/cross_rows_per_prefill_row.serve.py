"""Rows that ran the second half of the stack in the window's prefill
chunks over the prompt rows those chunks held, from the program's own
counts (``cross_rows`` and ``rows_valid`` of ``session.block_report()``,
folded on the device by every prefill executable).  Nothing after the
full-attention layer's K/V projection writes any cache, so a prefill that
stops half-way runs the later layers for a chunk's last row alone: one
over the mean prompt, ~1/300 here.  A prefill that ran every row through
every layer would read 1.0."""
LAYER = "step program"
UNIT = "ratio"
MOVES = "serve_ttft_p95_ms"


def read(run):
    block = run["facts"].get("block", {})
    if "cross_rows" not in block or not block.get("rows_valid"):
        return None
    return block["cross_rows"] / float(block["rows_valid"])
