"""How many of the decode calls hid the host behind the device: the share,
over the window's whole ``session.step`` spans of the program's own
record (``mxnet_tpu.profiler.spans``), of those whose ``ahead`` is 1: the
call launched the next step before it read its own tokens, so its launch,
its commit, the tick's own work and the next call's prepare ran under a
running step (docs/performance.md, "Spans").  The other calls put all of
it in series with the device, as every call did before PR 53: the
scheduler saw a request's last token coming, or an arrival it could
admit.  A program whose ``session.step`` spans carry no ``ahead`` (the
parent of PR 53; a diffusion block's pass, which never runs ahead) gives
nothing."""
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
