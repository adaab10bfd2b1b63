"""From a profiler trace (``.xplane.pb``) to numbers.

``load`` turns the file into plain lists with nothing but JAX
(``jax.profiler.ProfileData``); everything after that is arithmetic on
those lists, checked in ``tests/`` on a small recorded trace.

    {"planes": [{"name": str, "lines": [{"name": str,
                 "events": [[name, start_ns, duration_ns], ...]}]}]}

Device planes are the ones named ``/device:TPU:<n>``.  On such a plane the
line ``XLA Ops`` holds one event per executed operation and ``XLA
Modules`` one per executed program.  Host planes hold the benchmark's own
spans as events named ``bench:<span>`` (see ``spans.py``), on the same
clock.
"""
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"


def find_xplane(log_dir):
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % log_dir)
    return found[-1]


def load(path, keep_line=None):
    """The trace as plain lists.  ``keep_line(plane_name, line_name)``
    drops lines that no reduction reads (host planes are large)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            if keep_line is not None and not keep_line(plane.name, line.name):
                continue
            lines.append({"name": line.name, "events": [
                [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                for ev in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace):
    return [p for p in trace["planes"] if p["name"].startswith(DEVICE_PREFIX)]


def line_events(plane, line_name):
    out = []
    for line in plane["lines"]:
        if line["name"] == line_name:
            out.extend(line["events"])
    return out


def union(intervals):
    """Sorted, merged [start, end) intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def busy_intervals(plane):
    """Union of the intervals in which an operation ran on this device:
    the ``XLA Ops`` line, or the modules where a trace has no ops line."""
    events = line_events(plane, OPS_LINE) or line_events(plane, MODULES_LINE)
    return union((s, s + d) for _, s, d in events if d > 0)


def busy_seconds(trace, chips=None):
    """Seconds in which an operation ran, averaged over the device
    planes of the chips used (a host can show more than a cell uses)."""
    planes = device_planes(trace)[:chips]
    if not planes:
        return 0.0
    return sum(sum(e - s for s, e in busy_intervals(p))
               for p in planes) / len(planes) / 1e9


def module_times(trace):
    """{module name: (count, total seconds)} over the first device plane
    (a program that spans chips runs once on each)."""
    planes = device_planes(trace)
    out = {}
    if planes:
        for name, _, dur in line_events(planes[0], MODULES_LINE):
            count, total = out.get(name, (0, 0.0))
            out[name] = (count + 1, total + dur / 1e9)
    return out


def op_label(name):
    """A trace prints an operation as its whole HLO line,
    ``%fusion.849 = (bf16[...]) fusion(...)``: keep the name.  Kernels and
    other named calls repeat once a layer as ``<name>.<n>``: drop the
    counter, so that the layers' instances of one kernel add up (fusions
    keep theirs: ``fusion.849`` and ``fusion.850`` are different work)."""
    label = name.split(" = ", 1)[0].lstrip("%")
    head, _, tail = label.rpartition(".")
    if head and tail.isdigit() and not head.startswith("fusion"):
        label = head
    return label[:120]


def step_module(summary):
    """(name, count, total seconds) of the module that took most device
    time: in a training window, the train step.  ``None`` without a
    trace."""
    if not summary or not summary["modules"]:
        return None
    name, (count, total) = max(summary["modules"].items(),
                               key=lambda kv: kv[1][1])
    return name, count, total


def top_device_ops(trace, n=10):
    """[[name, seconds]] of the operations that took most device time,
    summed by ``op_label`` over the first device plane."""
    planes = device_planes(trace)
    totals = {}
    if planes:
        for name, _, dur in line_events(planes[0], OPS_LINE):
            label = op_label(name)
            totals[label] = totals.get(label, 0.0) + dur / 1e9
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, seconds] for name, seconds in ranked]


def host_spans(trace):
    """[(name, start_ns, end_ns)] of the benchmark's spans on any
    non-device plane."""
    out = []
    for plane in trace["planes"]:
        if plane["name"].startswith(DEVICE_PREFIX):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(SPAN_PREFIX):
                    out.append((name[len(SPAN_PREFIX):], start, start + dur))
    return out


def idle_gaps(trace, n=10):
    """[[what the host was doing, idle seconds]]: every gap between
    device operations on the first device plane, given to the innermost
    benchmark span that covers most of it (``host:other`` where none
    does), summed by span name, largest first."""
    planes = device_planes(trace)
    if not planes:
        return []
    busy = busy_intervals(planes[0])
    spans = sorted(host_spans(trace), key=lambda s: s[2] - s[1])
    totals = {}
    for (_, gap_start), (gap_end, _) in zip(busy, busy[1:]):
        length = gap_end - gap_start
        if length <= 0:
            continue
        owner, best = "host:other", 0
        for name, start, end in spans:           # shortest span first
            cover = min(end, gap_end) - max(start, gap_start)
            if cover * 2 >= length:              # innermost that covers half
                owner = name
                break
            if cover > best:
                owner, best = name, cover
        totals[owner] = totals.get(owner, 0.0) + length / 1e9
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, seconds] for name, seconds in ranked]


def summarize(trace, window_s, chips=None):
    """Everything the per-layer readers and the result line take from a
    trace."""
    return {
        "window_s": window_s,
        "busy_s": busy_seconds(trace, chips),
        "devices": len(device_planes(trace)),
        "modules": module_times(trace),
        "device_ops": top_device_ops(trace),
        "idle_gaps": idle_gaps(trace),
    }
