"""Padded rows the share's expert layers laid out over the assignments
they held, from the program's own counts (``session.block_report()``:
``dispatch_rows`` over ``dispatch_held``, counted on the device by every
executable, decode steps and prefill chunks alike).  An expert layer sorts
its assignments by expert and pads every expert's group to whole tiles;
the gathered input, the kernel's grid and its result are arrays of that
many rows, written and read once a layer.  1.0 is a layout with no
padding.  A layout sized for every assignment a chunk could send to the
experts held reads near the number of chips the experts are spread over;
one sized for what is held reads a few, and a call whose routing
overflowed that size (it then takes a second round) raises it.

Both counts run from the session's start to the window's close: the jobs
take the window's difference of the counts their family lists, which a
count of a later PR is not on, and the two are named so that they are
differenced together or not at all.  A program whose report has no such
counts (the parent of PR 57; a block that holds no share) gives
nothing."""
LAYER = "kernels"
UNIT = "ratio"
MOVES = "serve_ttft_p95_ms"


def read(run):
    block = run["facts"].get("block") or {}
    rows, held = block.get("dispatch_rows"), block.get("dispatch_held")
    if rows is None or not held:
        return None
    return rows / held
