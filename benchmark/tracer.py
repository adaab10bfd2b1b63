"""One traced stretch of the window: start, stop, reduce, clean up."""
import shutil
import tempfile
import time

import jax

import trace_reduce


class Tracer(object):
    def __init__(self, recorder, chips=None):
        self.recorder = recorder
        self.chips = chips
        self.running = False
        self._dir = None
        self._t0 = None

    def start(self):
        self._dir = tempfile.mkdtemp(prefix="bench_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(self._dir, profiler_options=options)
        self.recorder.tracing = True
        self.running = True
        self._t0 = time.perf_counter()

    def stop(self):
        """End the traced stretch (call where the window closes)."""
        self._window_s = time.perf_counter() - self._t0
        jax.profiler.stop_trace()
        self.recorder.tracing = False
        self.running = False

    def reduce(self):
        """-> the trace's summary (``trace_reduce.summarize``); the
        trace's files are removed."""
        try:
            trace = trace_reduce.load(trace_reduce.find_xplane(self._dir))
            summary = trace_reduce.summarize(trace, self._window_s,
                                             self.chips)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
        return summary
