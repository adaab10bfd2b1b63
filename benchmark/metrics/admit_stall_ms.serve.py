"""How long the live slots' next token waited for admissions: the 95th
percentile (the job's own, nearest rank), over the window's whole ``serve.tick`` spans
that began with ``live`` >= 1, of the summed ``serve.admit`` spans inside
the tick (0 for a tick that admitted nobody; an admission that found no
room, ``slot`` -1, is skipped).  From the program's own record
(``mxnet_tpu.profiler.spans``); a program without spans gives nothing."""
import manifest
import program_spans

LAYER = "entry"
UNIT = "ms"
MOVES = "serve_gap_p95_ms"


def value(records):
    stalled = {r.id: 0.0 for r in records
               if r.name == "serve.tick" and r.attrs.get("live", 0) >= 1}
    for r in records:
        if (r.name == "serve.admit" and r.parent in stalled
                and r.attrs.get("slot", -1) >= 0):
            stalled[r.parent] += r.end_s - r.start_s
    if not stalled:
        return None
    percentile = manifest.load_module("jobs", "serve_closed").percentile
    return percentile(stalled.values(), 95) * 1e3


def read(run):
    return value(program_spans.in_window(run))
