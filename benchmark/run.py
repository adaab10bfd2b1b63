#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A run is a new process.  It needs the cell's chips as TPU devices and
fails, printing no result, without them.  It builds the model on the
device from ``--seed``, warms the cell's own shapes (set-up), measures for
``--seconds``, holds what the timed path produced against the plain
reference in ``references/`` and prints one JSON object as its last line.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` traces
a short stretch and reports its per-layer metrics.

``--rehearse`` runs the same code at toy sizes on whatever device is
there, for the builder's rehearsals on the CPU.  It prints no result line
and exits 1: a number from a CPU can never appear under a metric's name.
"""
import time

T_PROCESS = time.perf_counter()

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for path in (ROOT, HERE):
    if path not in sys.path:
        sys.path.insert(0, path)

import manifest


def log(msg, *args):
    print("[%7.2f] %s" % (time.perf_counter() - T_PROCESS,
                          msg % args if args else msg), flush=True)


def die(msg, *args):
    print("benchmark: " + (msg % args if args else msg), file=sys.stderr,
          flush=True)
    sys.exit(2)


def execute(argv=None, manifest_path=None, bench_root=None, control=False):
    """Run one cell; -> (the result line's object, its arguments).

    The keywords are for ``tests/`` and for control runs on the chip, not
    for the command: a manifest other than ``BENCHMARK.json`` with the
    directory its data files are found in (cells that are built but not
    admitted), and ``control``, which lays the traffic file's ``control``
    group over a serving cell's settings (the program's own next lower
    precision; a training cell's control is ``MXNET_FP8=on`` instead)."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on any device; prints no result, exits 1")
    args = ap.parse_args(argv)
    args.control = control

    try:
        cell = manifest.Cell(args.workload, manifest_path, bench_root)
    except manifest.ManifestError as e:
        die("%s", e)
    if args.trace:
        # a traced run measures a short stretch: traces are large
        args.seconds = min(args.seconds, manifest.sized(
            cell.traffic, args.rehearse)["trace_seconds"])
    if not os.path.isdir(os.path.join(ROOT, "mxnet_tpu")):
        die("no program here: %s holds no mxnet_tpu/", ROOT)

    # every program, small ones too, goes to the persistent cache, so that
    # only a checkout's first run of a cell compiles; where it lives is the
    # program's own rule (JAX_COMPILATION_CACHE_DIR, else <checkout>/.cache)
    os.environ.setdefault("MXNET_COMPILE_CACHE_MIN_COMPILE_S", "0")

    import jax

    devices = jax.devices()
    on_chip = devices[0].platform == "tpu" and len(devices) >= cell.chips
    found = "%d x %s (%s)" % (len(devices), devices[0].device_kind,
                              devices[0].platform)
    if not on_chip and not args.rehearse:
        die("cell %s needs %d TPU chip(s); jax.devices() holds %s",
            cell.name, cell.chips, found)
    if len(devices) < cell.chips:
        die("a rehearsal of cell %s needs %d devices (XLA_FLAGS=--xla_force_"
            "host_platform_device_count=%d); jax.devices() holds %s",
            cell.name, cell.chips, cell.chips, found)
    log("benchmark: cell %s (config %s, traffic %s), seed %d, %.0f s, "
        "trace %d, devices %s%s", cell.name, cell.config_name,
        cell.traffic_name, args.seed, args.seconds, args.trace, found,
        "  [REHEARSAL at toy sizes: no result will be printed]"
        if args.rehearse else "")

    from mxnet_tpu import compile_cache

    import spans
    import tracer as tracer_mod

    compile_cache.ensure_initialized()
    recorder = spans.Recorder()
    tracer = tracer_mod.Tracer(recorder, chips=cell.chips)
    try:
        run = cell.job().run(cell, args, recorder, tracer, T_PROCESS, log)
    except manifest.ManifestError as e:
        die("%s", e)

    cache = compile_cache.cache_stats()
    log("compile cache: dir=%s hits=%d misses=%d", cache["dir"],
        cache["hits"], cache["misses"])
    correct = True
    for name, value, limit in run["checks"]:
        ok = value <= limit                      # nan compares false
        correct = correct and bool(ok)
        log("check %-28s %-14.6g limit %-10.6g %s", name, value, limit,
            "ok" if ok else "FAILED")

    # a rehearsal does its arithmetic on the v5e's peaks: it prints none of it
    peaks = manifest.load_peaks(devices[0].device_kind if on_chip
                                else "TPU v5 lite")
    context = {"facts": run["facts"], "spans": recorder, "peaks": peaks,
               "chips": cell.chips, "window": run["window"], "trace": None,
               "peak_bytes": run["peak_bytes"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": run["peak_bytes"]}
    result = {"correct": correct, "attempted": run["attempted"],
              "failed": run["failed"], "metrics": {}, "device": device}
    if args.trace:
        context["trace"] = trace = tracer.reduce()
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
        for entry, reader in cell.per_layer:
            value = reader.read(context)
            if value is not None:
                result["metrics"][entry["name"]] = {"value": value,
                                                    "unit": entry["unit"]}
    else:
        values = dict(run["end_to_end"], setup_s=run["setup_s"])
        for entry in cell.end_to_end:
            result["metrics"][entry["name"]] = {
                "value": values[entry["name"]], "unit": entry["unit"]}
    return result, args


def main(argv=None):
    result, args = execute(argv)
    if args.rehearse:
        log("REHEARSAL finished (correct=%s); a rehearsal prints no result",
            result["correct"])
        sys.exit(1)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
