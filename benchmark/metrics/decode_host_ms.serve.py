"""What a decode call puts in series with the device: the median, over
the window's whole ``session.step`` spans of the program's own record
(``mxnet_tpu.profiler.spans``), of the span less its ``step.wait`` child
(the host read of the new tokens).  A step whose ``step.wait`` is missing
(the spans went on inside it) is skipped; a program without spans gives
nothing."""
import statistics

import program_spans

LAYER = "step program"
UNIT = "ms"
MOVES = "serve_tokens_per_s"


def value(records):
    waits = {r.parent: r.end_s - r.start_s for r in records
             if r.name == "step.wait"}
    host = [r.end_s - r.start_s - waits[r.id] for r in records
            if r.name == "session.step" and r.id in waits]
    return statistics.median(host) * 1e3 if host else None


def read(run):
    return value(program_spans.in_window(run))
