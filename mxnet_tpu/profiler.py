"""Profiler — chrome-trace output via the XLA/JAX profiler.

Reference: ``python/mxnet/profiler.py`` over the engine profiler
(``src/engine/profiler.cc:152`` writes chrome://tracing JSON;
SURVEY.md §5 "Tracing/profiling").  Here the device timeline comes from
``jax.profiler`` (XLA's own op-level trace — strictly richer than the
reference's per-engine-op stat slabs) and ``dump()`` extracts the
chrome-trace JSON so the output opens in chrome://tracing / Perfetto
exactly like the reference's.

API surface: ``profiler_set_config(filename=...)``,
``profiler_set_state('run'|'stop')`` (aliases ``set_config``/
``set_state``), ``dump()``; env ``MXNET_PROFILER_AUTOSTART=1`` starts
tracing at import (reference ``env_var.md`` autostart contract).

Compile-time events are first-class here too: XLA compilation dominates
time-to-first-step on this platform, so every AOT/JIT compile the
framework performs is recorded via :func:`compile_event` (wall seconds,
FLOPs estimate, executable size) and retrievable with
:func:`compile_events` / summed with :func:`total_compile_s` — the
numbers ``TrainStep.compile_stats`` and the bench scripts' ``compile_s``
field surface (see docs/compilation.md).

Host spans (:func:`span`): the program's own record of what the host did
on its three hot paths (``Scheduler.tick``, a session call, ``fit``'s
batch), under the names ``docs/performance.md`` ("Spans") lists.  A span
is live while a profiler session runs, whoever started it, or after
``record_spans(True)``; it is then written into the running trace as
``mx:<name>`` (on the device trace's clock) and kept in memory on
``time.perf_counter()`` for :func:`spans`.  A :func:`cpu_span` keeps the
calling thread's CPU time over its stretch beside the wall time
(``cpu_s``: the thread *ran* that long and *waited*, off the core, for
the rest).  Otherwise a span site costs one ``is_enabled()`` check and
reads no clock.
"""
from __future__ import annotations

import collections
import glob
import gzip
import itertools
import os
import shutil
import tempfile
import threading
import time as _time

from jax.profiler import TraceAnnotation   # jax is loaded by now (base)

from .base import MXNetError, get_env

__all__ = ["profiler_set_config", "profiler_set_state", "set_config",
           "set_state", "dump", "dump_profile", "state",
           "compile_event", "compile_events", "total_compile_s",
           "span", "cpu_span", "spans", "clear_spans", "spans_dropped",
           "record_spans", "SpanRecord", "SPAN_PREFIX", "SPAN_CAPACITY"]

_config = {"filename": "profile.json", "profile_all": False}
_state = {"running": False, "tmpdir": None, "dumped": False}
_compile_events = []
_compile_lock = threading.Lock()


def profiler_set_config(mode="symbolic", filename="profile.json", **kwargs):
    """Configure output (reference ``profiler_set_config``; ``mode`` is
    accepted for API parity — the XLA trace always covers everything)."""
    _config["filename"] = filename
    _config["mode"] = mode
    _config.update(kwargs)


def profiler_set_state(state="stop"):
    """Start/stop tracing (reference ``profiler_set_state``)."""
    import jax

    if state == "run":
        if _state["running"]:
            return
        _state["tmpdir"] = tempfile.mkdtemp(prefix="mxtpu_profile_")
        _state["dumped"] = False
        jax.profiler.start_trace(_state["tmpdir"])
        _state["running"] = True
    elif state == "stop":
        if not _state["running"]:
            return
        jax.profiler.stop_trace()
        _state["running"] = False
    else:
        raise MXNetError("profiler state must be 'run' or 'stop', got %r"
                         % state)


set_config = profiler_set_config
set_state = profiler_set_state


def state():
    return "run" if _state["running"] else "stop"


def dump(finished=True):
    """Write the chrome-trace JSON to the configured filename (reference
    ``dump_profile`` → ``Profiler::DumpProfile``)."""
    if _state["running"] and finished:
        profiler_set_state("stop")
    tmpdir = _state["tmpdir"]
    if tmpdir is None:
        raise MXNetError("nothing profiled: call "
                         "profiler_set_state('run') first")
    traces = sorted(glob.glob(
        os.path.join(tmpdir, "**", "*.trace.json.gz"), recursive=True))
    if not traces:
        raise MXNetError("profiler produced no trace under %s" % tmpdir)
    with gzip.open(traces[-1], "rb") as src, \
            open(_config["filename"], "wb") as dst:
        shutil.copyfileobj(src, dst)
    _state["dumped"] = True
    return _config["filename"]


dump_profile = dump


# -- compile-time events ----------------------------------------------------

def compile_event(name, duration_s, flops=None, executable_bytes=None,
                  cache_hit=None, **extra):
    """Record one compilation: ``name`` identifies the callable (e.g.
    ``TrainStep(softmax)``), ``duration_s`` the end-to-end lower+compile
    wall time; ``flops`` (XLA cost analysis), ``executable_bytes``
    (generated code size), and ``cache_hit`` (persistent-cache) are
    best-effort.  Returns the recorded event dict."""
    event = {"name": name, "duration_s": float(duration_s),
             "time": _time.time()}
    if flops is not None:
        event["flops"] = float(flops)
    if executable_bytes is not None:
        event["executable_bytes"] = int(executable_bytes)
    if cache_hit is not None:
        event["cache_hit"] = bool(cache_hit)
    event.update(extra)
    with _compile_lock:
        _compile_events.append(event)
    return event


def compile_events():
    """All compile events recorded in this process (copies)."""
    with _compile_lock:
        return [dict(e) for e in _compile_events]


def total_compile_s():
    """Total wall seconds this process spent in recorded compilations."""
    with _compile_lock:
        return sum(e["duration_s"] for e in _compile_events)


# -- host spans -------------------------------------------------------------

SPAN_PREFIX = "mx:"
SPAN_CAPACITY = 1 << 18

SpanRecord = collections.namedtuple(
    "SpanRecord", "id parent name start_s end_s attrs cpu_s",
    defaults=(None,))

_perf_counter = _time.perf_counter   # the clock of Scheduler.now
# CPU seconds of the calling thread; None on a platform without the clock
_thread_time = getattr(_time, "thread_time", lambda: None)
_span_records = collections.deque(maxlen=SPAN_CAPACITY)
_span_lock = threading.Lock()
_span_ids = itertools.count(1)
_span_local = threading.local()
_span_state = {"record": False, "dropped": 0}


class _NoSpan(object):
    """What :func:`span` hands out while spans are off."""

    __slots__ = ()
    on = False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        pass


_NO_SPAN = _NoSpan()


class _Span(object):
    __slots__ = ("id", "parent", "name", "start_s", "attrs", "_annotation",
                 "_cpu", "_cpu0")
    on = True

    def __init__(self, name, attrs, annotation):
        self.name = name
        self.attrs = attrs
        self._annotation = annotation
        self._cpu = False     # cpu_span: read the thread's CPU clock too

    def __enter__(self):
        try:
            stack = _span_local.stack
        except AttributeError:
            stack = _span_local.stack = []
        self.id = next(_span_ids)
        self.parent = stack[-1] if stack else None
        stack.append(self.id)
        if self._annotation is not None:
            self._annotation.__enter__()
        self.start_s = _perf_counter()
        self._cpu0 = _thread_time() if self._cpu else None
        return self

    def __exit__(self, *exc):
        # the CPU clock inside the wall clock's stretch: cpu_s <= wall
        cpu_s = None if self._cpu0 is None else _thread_time() - self._cpu0
        end_s = _perf_counter()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        _span_local.stack.pop()
        record = (self.id, self.parent, self.name, self.start_s, end_s,
                  self.attrs, cpu_s)
        with _span_lock:
            if len(_span_records) == SPAN_CAPACITY:
                _span_state["dropped"] += 1
            _span_records.append(record)
        return False

    def set(self, **attrs):
        """Add what was not known at entry (small scalars)."""
        self.attrs.update(attrs)
        if self._annotation is not None:
            self._annotation.set_metadata(**attrs)


def span(name, **attrs):
    """A context manager around one stretch of host work.  On or off is
    decided here, at entry.  The ``with`` yields a handle: ``on``,
    ``start_s`` (when on) and ``set(**attrs)``."""
    if TraceAnnotation.is_enabled():
        return _Span(name, attrs, TraceAnnotation(SPAN_PREFIX + name, **attrs))
    if _span_state["record"]:
        return _Span(name, attrs, None)
    return _NO_SPAN


def cpu_span(name, **attrs):
    """:func:`span`, whose record also says how long the calling thread
    was on a core (``cpu_s``, by ``time.thread_time()``): for the spans a
    metric reads it of, since a read of that clock is a system call of
    6 us on some hosts (docs/performance.md, "What they cost")."""
    sp = span(name, **attrs)
    if sp.on:
        sp._cpu = True
    return sp


def record_spans(on=True):
    """Keep spans in memory with no profiler session running (about two
    microseconds a span); returns what the switch was."""
    was, _span_state["record"] = _span_state["record"], bool(on)
    return was


def spans(name=None, since=None, until=None):
    """The finished spans in the order they ended (a child before its
    parent), as :class:`SpanRecord` copies: those called ``name``, begun
    at or after ``since`` and ended at or before ``until``
    (``time.perf_counter()`` values)."""
    with _span_lock:
        records = list(_span_records)
    return [SpanRecord(i, parent, n, start_s, end_s, dict(attrs), cpu_s)
            for i, parent, n, start_s, end_s, attrs, cpu_s in records
            if (name is None or n == name)
            and (since is None or start_s >= since)
            and (until is None or end_s <= until)]


def clear_spans():
    with _span_lock:
        _span_records.clear()
        _span_state["dropped"] = 0


def spans_dropped():
    """Spans the bounded record (``SPAN_CAPACITY``) has let go, oldest
    first, since the last :func:`clear_spans`."""
    return _span_state["dropped"]


if get_env("MXNET_PROFILER_AUTOSTART", False, bool):
    profiler_set_state("run")
