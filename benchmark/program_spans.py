"""The program's own host spans (``mxnet_tpu.profiler.spans``; the names
are in docs/performance.md, "Spans"), for the metrics that read them."""


def in_window(run):
    """The program's spans that lie whole inside the run's window.  None
    from a program that records none: a check lays these files over the
    parent of the PR that brought the spans, and its traced runs have to
    end all the same."""
    from mxnet_tpu import profiler

    spans = getattr(profiler, "spans", None)
    if spans is None:
        return []
    w0, w1 = run["window"]
    return spans(since=w0, until=w1)
