"""Device time a decode step spends bringing the window layers' rings to
their reader: each layer's ring cut out of its pool (``slice``,
``slice-start``, ``slice-done``) and laid out again (``copy``,
``copy-start``, ``copy-done``: K turned for the scores, V to head-major)
for the plain einsums of ``serve/layers.py:window_decode``.  The trace's
operations of those names, summed over the traced stretch, over the decode
module's events: milliseconds a step.  It reads the trace's ten longest
operations (``trace_reduce.top_device_ops``), so once a reader takes a
ring where it lies and these fall below the tenth it says nothing, which
is the answer.  The few prefill chunks of a stretch put their copies in
the same sum (two chunks against 112 steps in this cell's 3 s): an upper
bound by that much.  Nothing else in this block's decode step slices or
copies a whole array: the pages are read where they lie by a kernel and
the state is updated in place."""

LAYER = "kernels"
UNIT = "ms"
MOVES = "serve_gap_p95_ms"
NAMES = ("slice", "slice-start", "slice-done", "copy", "copy-start",
         "copy-done")


def read(run):
    facts, trace = run["facts"], run["trace"]
    if not trace or "decode_module" not in facts:
        return None
    steps = sum(count for name, (count, _) in trace["modules"].items()
                if facts["decode_module"] in name)
    moved = [seconds for name, seconds in trace.get("device_ops", [])
             if name in NAMES]
    if not steps or not moved:
        return None
    return 1e3 * sum(moved) / steps
