"""How many of a diffusion block's pass calls hid the host behind the
device: the share, over the window's whole ``session.step`` spans of the
program's own record (``mxnet_tpu.profiler.spans``), of those whose
``ahead`` is 1: the call launched the next block pass before it read its
own pass's rows, so its launch, its commit, the tick's own work and the
next call's prepare ran under a running pass (docs/performance.md,
"Spans").  The other calls put all of it in series with the device, as
every pass call did before PR 55: the scheduler saw the commit of a
request's last block coming (its ``max_new`` spent, or its ``eos_id``
among the block's tokens), or an arrival it could admit.  It is
``decode_ahead_share.serve``'s quantity where a step is a block pass, and
is listed in the cells of a session that generates by diffusion.  A
program whose ``session.step`` spans carry no ``ahead`` (the parent of
PR 55, whose block pass never ran ahead) gives nothing."""
import program_spans

LAYER = "step program"
UNIT = "ratio"
MOVES = "serve_tokens_per_s"


def value(records):
    ahead = [r.attrs["ahead"] for r in records
             if r.name == "session.step" and "ahead" in r.attrs]
    return sum(1 for a in ahead if a) / len(ahead) if ahead else None


def read(run):
    return value(program_spans.in_window(run))
