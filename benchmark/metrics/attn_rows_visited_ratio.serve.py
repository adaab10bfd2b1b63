"""K/V rows the window's decode steps visited over the rows inside their
queries' horizons, both kinds of attention layer, from the program's own
counts.  Visited: in the full layers the page blocks up to the longest
live context (``session.decode_report()``'s ``blocks_visited``, counted on
the host where the paged reader's loop ends) x the page's rows x every
slot, a layer; in the window layers every slot's whole ring a layer
(``window_rows_visited``).  Inside the horizons: the live slots' contexts
a full layer (``full_rows_live``) and their rows inside the band a window
layer (``window_rows_in_band``), counted on the device.  1.0 is a read
that touches nothing it masks; short slots beside a long one, idle slots
and a ring wider than a young context raise it."""
LAYER = "step program"
UNIT = "ratio"
MOVES = "serve_gap_p95_ms"


def read(run):
    facts = run["facts"]
    block, decode = facts.get("block", {}), facts.get("decode")
    sc = facts.get("serve_config")
    if not decode or not sc or "window_rows_visited" not in block:
        return None
    inside = block["full_rows_live"] + block["window_rows_in_band"]
    if not inside:
        return None
    visited = decode["blocks_visited"] * sc["page_size"] * sc["slots"] \
        * block["full_layers"] + block["window_rows_visited"]
    return visited / inside
