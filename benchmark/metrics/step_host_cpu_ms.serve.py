"""What a decode call's own thread computes outside the token read: the
mean, over the window's whole ``session.step`` spans that have a
``step.wait`` child, of the step's ``cpu_s`` less its ``step.wait``'s:
the Python and the runtime calls the call runs on its own thread (*ran*;
docs/performance.md, "Spans").  The rest of the same steps' host time
(``decode_host_ms.serve``) the thread *waited*, off the core.  A mean and
not a median, because the chip's host ticks the thread's CPU clock in
10 ms: one step reads 0 or 10 ms, and only the sum over a stretch's steps
says how long the thread ran (to 0.1-0.2 ms over the ~200 steps of a
traced stretch).  A program whose spans carry no CPU time gives
nothing."""
import statistics

import program_spans

LAYER = "step program"
UNIT = "ms"
MOVES = "serve_tokens_per_s"


def value(records):
    waits = {r.parent: r for r in records if r.name == "step.wait"}
    ran = [(getattr(r, "cpu_s", None), getattr(waits[r.id], "cpu_s", None))
           for r in records if r.name == "session.step" and r.id in waits]
    if not ran or any(step is None or wait is None for step, wait in ran):
        return None
    return statistics.fmean(step - wait for step, wait in ran) * 1e3


def read(run):
    return value(program_spans.in_window(run))
