"""The wall time of one chunk of the largest bucket, launch, device and
token read together: over the window's whole ``session.prefill`` spans all
of whose ``prefill.launch`` children carry the session's largest bucket
(``bucket`` equal to ``largest``), the median of (end of ``prefill.wait``
- start of the first ``prefill.launch``) / chunks.  The device is drained
where a prefill starts (the step before it ended in its token read), so
one bucket's chunk compares from run to run whatever the stretch's mix of
prompts, which ``prefill_call_ms.serve``'s median over whole calls of both
buckets does not.  A stretch in which no such prefill lies whole gives
nothing, and so does a program whose ``prefill.launch`` carries no
``bucket``."""
import collections
import statistics

import program_spans

LAYER = "step program"
UNIT = "ms"
MOVES = "serve_ttft_p95_ms"


def value(records):
    launches = collections.defaultdict(list)
    for r in records:
        if r.name == "prefill.launch":
            launches[r.parent].append(r)
    whole = {r.id for r in records if r.name == "session.prefill"}
    waits = {r.parent: r for r in records if r.name == "prefill.wait"}
    chunk = [(waits[parent].end_s - min(r.start_s for r in rs)) / len(rs)
             for parent, rs in launches.items()
             if parent in whole and parent in waits
             and all("bucket" in r.attrs
                     and r.attrs["bucket"] == r.attrs["largest"] for r in rs)]
    return statistics.median(chunk) * 1e3 if chunk else None


def read(run):
    return value(program_spans.in_window(run))
