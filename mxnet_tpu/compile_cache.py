"""Compile-time subsystem: persistent XLA cache, AOT stats, recompile
guardrails.

Three legs (docs/compilation.md):

1. **Persistent compilation cache** — a second process running the
   same model deserializes its executables instead of re-running XLA.
   Where the directory comes from, in order: ``JAX_COMPILATION_CACHE_DIR``
   (JAX reads it itself; this module then writes no directory into
   ``jax.config`` and never deletes a file there), else
   ``MXNET_COMPILE_CACHE_DIR`` (empty string opts out), else the fixed
   ``<checkout>/.cache/xla`` beside the package — the path is part of
   the cache key's usefulness, so it never depends on ``$HOME``, a
   temporary name, a pid or the time.  A directory this module chose
   is bounded by ``MXNET_COMPILE_CACHE_MAX_BYTES`` with an LRU eviction
   sweep, and :func:`cache_stats` reports directory / hits / misses /
   bytes / evictions for the current process.  Initialization is lazy:
   the first jit owner (``TrainStep``, ``Executor``, ``CachedOp``, a
   ``Context`` device lookup) calls :func:`ensure_initialized`.

2. **AOT compile accounting** — ``TrainStep.compile(shapes)`` /
   ``Module.prepare_compiled()`` lower-and-compile ahead of time and
   record wall time, FLOPs, and executable size through
   ``profiler.compile_event``; the per-callable stats land on
   ``TrainStep.compile_stats``.

3. **Recompile guardrails** — a process-wide :data:`registry` every jit
   owner registers with.  Each owner holds a :class:`RecompileGuard`
   and reports the signature of every dispatch; the guard counts
   distinct traced signatures, logs a structured warning (with the
   differing shape/dtype/weak-type leaves) past ``MXNET_RECOMPILE_WARN``
   retraces, and raises typed :class:`RecompileStorm` under
   ``MXNET_RECOMPILE_ERROR=1`` — turning silent shape-leak recompiles
   into diagnosable failures.  ``tools/compile_report.py`` pretty-prints
   the artifact written by :func:`write_artifact`.

This is the subsystem the reference framework carried as executor
caching (``simple_bind(shared_exec=...)``, the per-bucket executor cache
in BucketingModule): compilation cost is a first-order lever for a
compiled framework, so it gets measured, cached, and guarded instead of
being absorbed silently into "epoch 0".
"""
from __future__ import annotations

import atexit
import functools
import json
import os
import tempfile
import threading
import time

from .base import MXNetError, RecompileStorm, get_env, logger

__all__ = ["ensure_initialized", "cache_stats", "sweep_cache",
           "signature_of", "diff_signatures", "RecompileGuard",
           "RecompileRegistry", "RecompileStorm", "registry",
           "write_artifact", "track_lru"]

# one fixed place inside the checkout (git-ignored): derived from where
# the package lives, so every process of this checkout shares it
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".cache", "xla")
# cap chosen for a shared dev box: ~40 ResNet-class executables
DEFAULT_MAX_BYTES = 2 << 30

_lock = threading.Lock()
_state = {
    "initialized": False,
    "enabled": False,
    "dir": None,
    # False when the directory came from JAX_COMPILATION_CACHE_DIR: a
    # directory handed in from outside is never swept
    "owned": False,
    "max_bytes": None,
    "hits": 0,
    "requests": 0,
    "evictions": 0,
    "evicted_bytes": 0,
}


# ---------------------------------------------------------------------------
# leg 1: persistent compilation cache
# ---------------------------------------------------------------------------

def _on_monitoring_event(event, **kwargs):
    # registered with jax's internal monitoring bus; only the two cache
    # counters are interesting, everything else passes through untouched
    if event == "/jax/compilation_cache/cache_hits":
        _state["hits"] += 1
    elif event == "/jax/compilation_cache/compile_requests_use_cache":
        _state["requests"] += 1


def ensure_initialized():
    """Wire the JAX persistent compilation cache (idempotent, lazy).

    Called by every jit owner right before its first trace; the fast
    path is one boolean check.  Honors, in this order:

    * ``JAX_COMPILATION_CACHE_DIR`` — JAX's own variable.  When set,
      JAX's reading of it stands: no directory is written into
      ``jax.config`` here and the directory is never swept.
    * ``MXNET_COMPILE_CACHE_DIR`` — cache directory; empty string
      disables persistence.
    * neither: the fixed ``<checkout>/.cache/xla`` beside the package.
    * ``MXNET_COMPILE_CACHE_MAX_BYTES`` — LRU size cap for the sweep of
      a directory this module chose.
    * ``MXNET_COMPILE_CACHE_MIN_COMPILE_S`` — only executables whose
      XLA compile took at least this long are persisted (default 0.5;
      set 0 to persist everything, as the round-trip tests do).
    """
    if _state["initialized"]:
        return _state["enabled"]
    with _lock:
        if _state["initialized"]:
            return _state["enabled"]
        handed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        cache_dir = handed or get_env("MXNET_COMPILE_CACHE_DIR",
                                      DEFAULT_CACHE_DIR, str)
        _state["max_bytes"] = get_env("MXNET_COMPILE_CACHE_MAX_BYTES",
                                      DEFAULT_MAX_BYTES, int)
        if not cache_dir:
            _state["initialized"] = True
            _state["enabled"] = False
            return False
        try:
            import jax

            if handed:
                # the directory was placed from outside: report what
                # JAX itself read, set nothing, delete nothing
                cache_dir = jax.config.jax_compilation_cache_dir
            else:
                cache_dir = os.path.abspath(os.path.expanduser(cache_dir))
                os.makedirs(cache_dir, exist_ok=True)
                jax.config.update("jax_compilation_cache_dir", cache_dir)
            jax.config.update("jax_enable_compilation_cache", True)
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs",
                get_env("MXNET_COMPILE_CACHE_MIN_COMPILE_S", 0.5, float))
            # entry size gating would silently drop small-model
            # executables — the LRU sweep is the size policy here
            jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                              0)
            jax.monitoring.register_event_listener(_on_monitoring_event)
            _state["dir"] = cache_dir
            _state["owned"] = not handed
            _state["enabled"] = True
            if not handed:
                # bound the directory NOW (a previous run may have blown
                # the cap) and again at exit (this run's own entries)
                sweep_cache()
                atexit.register(sweep_cache)
        except Exception as e:  # cache is an optimization, never fatal
            logger.warning("persistent compilation cache unavailable "
                           "(%s); compiles will not be reused across "
                           "processes", e)
            _state["enabled"] = False
        _state["initialized"] = True
        return _state["enabled"]


def _cache_entries(cache_dir):
    """[(path, size, last-use timestamp)] for every cache file."""
    entries = []
    try:
        names = os.listdir(cache_dir)
    except OSError:
        return entries
    for name in names:
        path = os.path.join(cache_dir, name)
        try:
            st = os.stat(path)
        except OSError:
            continue
        if not os.path.isfile(path):
            continue
        # atime when the mount tracks it (a cache hit touches it),
        # else mtime — both give oldest-first eviction order
        entries.append((path, st.st_size, max(st.st_atime, st.st_mtime)))
    return entries


def sweep_cache(cache_dir=None, max_bytes=None):
    """LRU eviction sweep: delete least-recently-used cache entries
    until the directory fits ``max_bytes``.  Returns (entries, bytes)
    remaining.  Safe to call concurrently with running processes — an
    evicted entry just recompiles on its next use.  With no explicit
    ``cache_dir`` only a directory this module chose is swept."""
    if cache_dir is None and _state["owned"]:
        cache_dir = _state["dir"]
    if max_bytes is None:
        max_bytes = _state["max_bytes"]
        if max_bytes is None:
            max_bytes = get_env("MXNET_COMPILE_CACHE_MAX_BYTES",
                                DEFAULT_MAX_BYTES, int)
    if not cache_dir:
        return 0, 0
    entries = _cache_entries(cache_dir)
    total = sum(size for _, size, _ in entries)
    if total <= max_bytes:
        return len(entries), total
    entries.sort(key=lambda e: e[2])  # oldest last-use first
    removed = 0
    freed = 0
    for path, size, _ in entries:
        if total <= max_bytes:
            break
        try:
            os.remove(path)
        except OSError:
            continue
        total -= size
        removed += 1
        freed += size
        _state["evictions"] += 1
        _state["evicted_bytes"] += size
    if removed:
        logger.info("compile cache sweep: evicted %d entries (%d bytes "
                    "over the %d-byte cap) from %s", removed,
                    freed, max_bytes, cache_dir)
    return len(entries) - removed, total


def cache_stats():
    """Persistent-cache statistics for this process.

    ``hits``/``misses`` count XLA compile requests served from /
    missed by the persistent cache since initialization (misses include
    executables too cheap to persist); ``entries``/``bytes`` are the
    cache directory's current on-disk state; ``evictions`` counts
    entries this process's LRU sweeps removed."""
    entries, nbytes = 0, 0
    if _state["dir"]:
        found = _cache_entries(_state["dir"])
        entries = len(found)
        nbytes = sum(size for _, size, _ in found)
    return {
        "enabled": _state["enabled"],
        "dir": _state["dir"],
        "hits": _state["hits"],
        "misses": max(0, _state["requests"] - _state["hits"]),
        "requests": _state["requests"],
        "entries": entries,
        "bytes": nbytes,
        "max_bytes": _state["max_bytes"],
        "evictions": _state["evictions"],
        "evicted_bytes": _state["evicted_bytes"],
    }


# ---------------------------------------------------------------------------
# leg 3: recompile guardrails
# ---------------------------------------------------------------------------

def _describe_leaf(x):
    """(shape, dtype, weak_type) identity of one jit-signature leaf —
    exactly the triple jax keys its trace cache on.  Python scalars are
    the classic weak-type leak, so they get named as such."""
    if isinstance(x, bool):
        return ("py_bool", "weak")
    if isinstance(x, int):
        return ("py_int", "weak")
    if isinstance(x, float):
        return ("py_float", "weak")
    if isinstance(x, complex):
        return ("py_complex", "weak")
    shape = getattr(x, "shape", None)
    if shape is None:
        return (type(x).__name__,)
    return (tuple(shape), str(getattr(x, "dtype", "?")),
            bool(getattr(x, "weak_type", False)))


def signature_of(*trees):
    """Hashable (path, leaf-identity) signature of a jit call's inputs.

    Two calls with equal signatures hit the same traced program; a new
    signature is a retrace."""
    from jax.tree_util import tree_flatten_with_path, keystr

    sig = []
    for i, tree in enumerate(trees):
        leaves, _ = tree_flatten_with_path(tree)
        for path, leaf in leaves:
            sig.append(("%d%s" % (i, keystr(path)), _describe_leaf(leaf)))
    return tuple(sig)


def diff_signatures(old, new):
    """Leaf-level difference between two signatures: the argument paths
    whose shape/dtype/weak-type changed (or appeared/disappeared)."""
    old_map = dict(old)
    new_map = dict(new)
    lines = []
    for path in sorted(set(old_map) | set(new_map), key=str):
        a, b = old_map.get(path), new_map.get(path)
        if a == b:
            continue
        if a is None:
            lines.append("%s: (absent) -> %r" % (path, b))
        elif b is None:
            lines.append("%s: %r -> (absent)" % (path, a))
        else:
            lines.append("%s: %r -> %r" % (path, a, b))
    return lines


class RecompileGuard:
    """Per-callable retrace counter.

    The owner calls :meth:`observe` with the signature of each dispatch;
    the guard tracks distinct signatures (``signatures``), total traces
    including rebuilds of evicted entries (``traces``), and total calls
    (``calls``).  Past ``MXNET_RECOMPILE_WARN`` distinct signatures it
    logs one structured warning per further retrace — naming the leaves
    that differ from the previous trace — and raises
    :class:`RecompileStorm` when ``MXNET_RECOMPILE_ERROR=1``."""

    def __init__(self, name):
        self.name = name
        self.calls = 0
        self.traces = 0
        self._seen = {}          # signature -> first-seen trace index
        self._last_sig = None
        self._lock = threading.Lock()

    @property
    def signatures(self):
        return len(self._seen)

    def observe(self, sig, force=False):
        """Record one dispatch.  ``force=True`` counts a trace even for
        a previously seen signature (a rebuild after cache eviction).
        Returns True when this call traced."""
        with self._lock:
            self.calls += 1
            new = sig not in self._seen
            if new:
                self._seen[sig] = self.traces
            traced = new or force
            if traced:
                self.traces += 1
            prev, self._last_sig = self._last_sig, sig
            n = self.signatures
        if not new or n <= 1:
            return traced
        warn_after = get_env("MXNET_RECOMPILE_WARN", 3, int)
        if n > warn_after:
            diff = diff_signatures(prev, sig) if prev is not None else []
            msg = ("recompile guard: %r has been traced for %d distinct "
                   "input signatures (threshold %d) — every new "
                   "signature is a full XLA recompile. Changed vs the "
                   "previous trace:\n  %s\nCommon causes: uncommitted "
                   "arrays, python-scalar weak types, drifting batch "
                   "tails (see docs/compilation.md)."
                   % (self.name, n, warn_after,
                      "\n  ".join(diff) or "(no leaf-level difference — "
                      "tree structure changed)"))
            if get_env("MXNET_RECOMPILE_ERROR", False, bool):
                raise RecompileStorm(msg, name=self.name, signatures=n,
                                     diff=diff)
            logger.warning(msg)
        return traced

    def snapshot(self):
        return {"name": self.name, "calls": self.calls,
                "traces": self.traces, "signatures": self.signatures}


class RecompileRegistry:
    """Process-wide registry of :class:`RecompileGuard` s.

    ``guard(name)`` returns the existing guard for ``name`` (so a
    rebuilt owner — an ``Executor`` recreated by ``reshape`` on a
    drifting batch size — keeps accumulating into the same counter,
    which is exactly the storm the guard exists to catch)."""

    def __init__(self):
        self._guards = {}
        self._lock = threading.Lock()

    def guard(self, name):
        with self._lock:
            g = self._guards.get(name)
            if g is None:
                g = self._guards[name] = RecompileGuard(name)
            return g

    def report(self):
        """{name: {calls, traces, signatures}} for every registered
        guard, retrace-heaviest first."""
        with self._lock:
            guards = list(self._guards.values())
        return {g.name: g.snapshot() for g in
                sorted(guards, key=lambda g: -g.traces)}

    def reset(self):
        with self._lock:
            self._guards.clear()


registry = RecompileRegistry()


def track_lru(name):
    """Register an ``functools.lru_cache``-of-jits builder with the
    recompile registry: every cache miss (= a new jitted program) counts
    as a trace.  Stacks ABOVE the lru_cache decorator::

        @track_lru("parallel._moe_fn")
        @functools.lru_cache(maxsize=32)
        def _moe_fn(mesh, axis, top_k): ...
    """
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            misses = fn.cache_info().misses
            out = fn(*args, **kwargs)
            if fn.cache_info().misses > misses:
                sig = tuple(
                    (str(i), (str(a)[:120],))
                    for i, a in enumerate(args)
                ) + tuple(sorted(
                    (k, (str(v)[:120],)) for k, v in kwargs.items()))
                # force=True: an lru eviction rebuild is a real retrace
                registry.guard(name).observe(sig, force=True)
            return out

        wrapper.cache_info = fn.cache_info
        wrapper.cache_clear = fn.cache_clear
        return wrapper
    return deco


# ---------------------------------------------------------------------------
# artifact
# ---------------------------------------------------------------------------

ARTIFACT_KIND = "mxnet_tpu-compile-report"


def report():
    """The full compile-time picture of this process: persistent-cache
    stats, the recompile registry, every recorded compile event, and
    the autotune knob applications the build ran under."""
    from . import profiler

    try:
        from . import autotune as _autotune

        tuned = _autotune.provenance()
    except ImportError:
        tuned = []
    return {
        "kind": ARTIFACT_KIND,
        "pid": os.getpid(),
        "time": time.time(),
        "cache": cache_stats(),
        "recompiles": registry.report(),
        "compile_events": profiler.compile_events(),
        "autotune": tuned,
    }


def write_artifact(path=None):
    """Write the compile report as JSON (pretty-print it with
    ``tools/compile_report.py``).  Default location follows the health
    artifacts: ``$MXNET_HEALTH_DIR`` or the tmpdir."""
    if path is None:
        base_dir = get_env("MXNET_HEALTH_DIR", "", str) or \
            tempfile.gettempdir()
        path = os.path.join(
            base_dir, "compile-report-%d-%d.json"
            % (os.getpid(), int(time.time())))
    payload = report()
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, default=repr)
    return path


def _reset_for_tests():
    """Test hook: forget initialization and zero the counters (the jax
    config side is left as-is — re-init just re-applies it)."""
    with _lock:
        _state.update(initialized=False, enabled=False, dir=None,
                      max_bytes=None, hits=0, requests=0, evictions=0,
                      evicted_bytes=0)
    registry.reset()
