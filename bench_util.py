"""Shared bench-script plumbing: budget + watchdog + compile accounting.

Every bench script prints ONE final JSON line on stdout.  A harness
timeout (rc 124) that kills the process mid-phase leaves no line at all,
so two timers bound the run from the inside instead:

* ``arm_budget`` — ``MXNET_BENCH_BUDGET_S`` seconds after arming, the
  shared result dict (filled phase by phase by the script) is printed
  as the final stdout line (marked ``"partial": true``) and the process
  exits 0.  Opt-in: no budget env, no timer.
* ``arm_watchdog`` — the always-on wedge guard (default 420 s,
  ``MXNET_BENCH_WATCHDOG`` / ``--watchdog`` to change, 0 disables): if
  the run is still going when it fires — a hung backend init, a stale
  TPU lockfile, a device that stopped answering — the same partial line
  is emitted and the process exits ``WATCHDOG_EXIT_CODE`` (non-zero): a
  hang is a failure, and the line says how far the run got.  Both
  timers share one emitter that touches already-imported modules only
  (a timer thread that imports deadlocks on the interpreter's import
  lock when the main thread is stuck inside ``import jax``).

``compile_summary`` splits compile time out of the measured rates: the
scripts AOT-compile through ``TrainStep.compile``/``Module.fit`` warmup,
so every XLA compile lands in ``mxnet_tpu.profiler.compile_events`` and
the persistent-cache hit/miss counters (see docs/compilation.md).
"""
import json
import os
import sys
import threading


def budget_seconds():
    """The configured bench budget (0 = unbounded)."""
    for key in ("MXTPU_BENCH_BUDGET_S", "MXNET_BENCH_BUDGET_S"):
        raw = os.environ.get(key)
        if raw:
            try:
                return float(raw)
            except ValueError:
                pass
    return 0.0


def watchdog_seconds():
    """The wedge-guard timeout (default 420 s; 0 disables).  Sized to
    beat the harness's external timeout: an internally-bounded run
    emits partial JSON and exits 0, an externally-killed one is rc=124
    with nothing on stdout."""
    for key in ("MXTPU_BENCH_WATCHDOG", "MXNET_BENCH_WATCHDOG"):
        raw = os.environ.get(key)
        if raw:
            try:
                return float(raw)
            except ValueError:
                pass
    return 420.0


# exit code of a run the watchdog had to end (a hang is a failure)
WATCHDOG_EXIT_CODE = 3


def _emit_and_exit(result, extra, code=0):
    """Finalize ``result`` from a timer thread and hard-exit ``code``.

    MUST NOT import anything: the main thread may be stuck inside
    ``import jax`` holding the import lock, and a blocked emitter is
    exactly the round-5 no-artifact failure.  Compile stats are read
    only when their modules already finished importing."""
    result.update(extra)
    try:
        if "mxnet_tpu.profiler" in sys.modules and \
                "mxnet_tpu.compile_cache" in sys.modules:
            result.update(compile_summary())
    except Exception:
        pass
    print(json.dumps(result), flush=True)
    # stdout is line-buffered under pipes; make sure the line left
    sys.stdout.flush()
    os._exit(code)


# bf16 peak FLOP/s of one chip by ``device_kind`` prefix (Google Cloud
# TPU documentation, per-generation spec sheets).  A device that is not
# in the table is an error, not a default.
PEAK_BF16 = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def require_tpu(rehearsal=False):
    """The first device, which must be a TPU: a bench number is a fact
    about the chip, so a run without one fails instead of printing a
    CPU timing under a per-chip name.  ``rehearsal=True`` (a script's
    ``--small`` flag) lets a CPU run through to check control flow."""
    import jax

    from mxnet_tpu.context import describe_devices

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not rehearsal:
        raise SystemExit(
            "this benchmark measures a TPU and found none: jax.devices() "
            "holds %s" % describe_devices())
    return dev


def peak_flops(device):
    """bf16 peak FLOP/s of ``device`` from :data:`PEAK_BF16`; a device
    kind missing from the table raises."""
    kind = getattr(device, "device_kind", "")
    for k, v in PEAK_BF16.items():
        if kind.startswith(k):
            return v
    raise KeyError("no bf16 peak recorded for device kind %r (known: %s)"
                   % (kind, sorted(PEAK_BF16)))


def arm_budget(result, seconds=None):
    """Arm the wall-clock budget for this bench process.

    ``result`` is the script's shared phase-by-phase dict; on expiry it
    is finalized with ``partial``/``budget_s`` plus the compile summary,
    printed to stdout as the one JSON line, and the process exits 0 (a
    budgeted run IS a successful run — it reports what finished).
    Returns the armed Timer, or None when no budget is configured."""
    if seconds is None:
        seconds = budget_seconds()
    if seconds <= 0:
        return None
    # mxlint: disable=MX006 — the timer IS the teardown of last
    # resort (it hard-exits the process); joining it would defeat it
    t = threading.Timer(seconds, _emit_and_exit,
                        (result, {"partial": True, "budget_s": seconds}))
    t.daemon = True
    t.start()
    return t


def arm_watchdog(result, seconds=None):
    """Arm the always-on wedge guard (call BEFORE the first jax touch).

    Unlike the opt-in budget, this fires even with no budget configured:
    ``seconds`` (default :func:`watchdog_seconds`) after arming, the
    partial result line is printed and the process exits
    ``WATCHDOG_EXIT_CODE``.  Returns the Timer, or None when disabled
    (0)."""
    if seconds is None:
        seconds = watchdog_seconds()
    if seconds <= 0:
        return None
    # mxlint: disable=MX006 — deliberate daemon watchdog, never joined
    t = threading.Timer(
        seconds, _emit_and_exit,
        (result, {"partial": True, "watchdog_timeout_sec": seconds},
         WATCHDOG_EXIT_CODE))
    t.daemon = True
    t.start()
    return t


def compile_summary():
    """Process-wide compile accounting for the final result line:
    total ``compile_s``, persistent-cache counters, and any callable
    the recompile guard saw trace more than once."""
    out = {}
    try:
        from mxnet_tpu import compile_cache, profiler

        out["compile_s"] = round(profiler.total_compile_s(), 3)
        cs = compile_cache.cache_stats()
        out["compile_cache"] = {
            k: cs[k] for k in ("enabled", "hits", "misses", "entries",
                               "bytes")}
        retraced = {name: snap["traces"]
                    for name, snap in compile_cache.registry.report().items()
                    if snap["traces"] > 1}
        if retraced:
            out["recompiles"] = retraced
    except Exception as e:  # accounting must never sink the benchmark
        out["compile_stats_error"] = str(e)[:160]
    return out


def timed_compile(step, shapes, result=None, key="compile_s"):
    """AOT-compile ``step`` for ``shapes`` and return the compile wall
    seconds (also accumulated into ``result[key]`` when given).  Falls
    back to 0.0 when the step has no AOT form — the caller's first
    dispatch then absorbs the (lazy) compile as before."""
    try:
        stats = step.compile(shapes)
        dt = float(stats["duration_s"])
    except Exception:
        return 0.0
    if result is not None:
        result[key] = round(result.get(key, 0.0) + dt, 3)
    return dt
