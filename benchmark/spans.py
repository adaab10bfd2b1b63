"""Host spans and counters, recorded by the benchmark's own wrappers.

The program has no spans of its own yet, so every span here is taken
around a call into it: a public method replaced on the instance by a
timed copy.  Spans live in memory until the run ends.  While the profiler
runs, each span is also written into its trace (``TraceAnnotation``) under
``bench:<name>``, so the device's idle gaps can be attributed to what the
host was doing on the trace's own clock.
"""
import contextlib
import time

import jax

PREFIX = "bench:"


class Recorder(object):
    def __init__(self):
        self.spans = []      # (name, start_s, end_s) on perf_counter
        self.tracing = False

    def wrap(self, obj, method, name=None, after=None):
        """Replace ``obj.method`` by a timed copy.  ``after(result,
        start_s, end_s, args)`` sees every call's result."""
        inner = getattr(obj, method)
        name = name or method
        spans = self.spans

        def timed(*args, **kwargs):
            with (jax.profiler.TraceAnnotation(PREFIX + name)
                  if self.tracing else contextlib.nullcontext()):
                t0 = time.perf_counter()
                out = inner(*args, **kwargs)
                t1 = time.perf_counter()
            spans.append((name, t0, t1))
            if after is not None:
                after(out, t0, t1, args)
            return out

        setattr(obj, method, timed)
        return timed

    def durations(self, name, since=None, until=None):
        return [t1 - t0 for n, t0, t1 in self.spans
                if n == name and (since is None or t0 >= since)
                and (until is None or t1 <= until)]
