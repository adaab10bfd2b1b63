"""BENCHMARK.json and the files it names: one cell resolved to data.

Everything that belongs to one configuration, one traffic mix, one
per-layer metric, one model family or one kind of job is a file of its
own, found by name: ``configs/<configuration>.json``,
``traffic/<traffic>.json``, ``limits/<cell>.json``,
``metrics/<metric>.py``, ``families/<the configuration's family>.py``,
``jobs/<the traffic's kind>.py``.  A later PR adds files and entries and
edits nothing here.  A cell whose configuration, traffic, limits, job or
metric file is missing is refused before anything touches a device.
"""
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class ManifestError(Exception):
    """BENCHMARK.json or a file it names is missing or inconsistent."""


def _read_json(path, what):
    if not os.path.isfile(path):
        raise ManifestError("%s: no such file: %s" % (what, path))
    with open(path) as f:
        try:
            return json.load(f)
        except ValueError as e:
            raise ManifestError("%s: %s is not JSON: %s" % (what, path, e))


def load_peaks(device_kind, path=None):
    table = _read_json(path or os.path.join(HERE, "peaks.json"), "peaks")
    if device_kind not in table or device_kind.startswith("_"):
        raise ManifestError(
            "no peaks for device kind %r (peaks.json has %s)"
            % (device_kind, sorted(k for k in table if not k.startswith("_"))))
    return table[device_kind]


def sized(data, rehearse):
    """A configuration, traffic or limits file as run: its own values,
    or under ``--rehearse`` with its ``rehearse`` group laid over them."""
    out = {k: v for k, v in data.items() if k != "rehearse"}
    if rehearse:
        out.update(data.get("rehearse", {}))
    return out


# what a file of each directory has to define
PLUGINS = {
    "metrics": ("LAYER", "UNIT", "MOVES", "read"),
    "jobs": ("run",),
    "families": ("reference", "symbol", "batches", "items_per_row",
                 "grad_scale", "train_flops_per_item", "n_params",
                 "output_bytes_per_row"),
}


def module_path(directory, name, root=None):
    path = os.path.join(root or HERE, directory, name + ".py")
    if not os.path.isfile(path):
        raise ManifestError("%s %r: no file %s" % (directory, name, path))
    return path


def load_module(directory, name, root=None):
    """``<root>/<directory>/<name>.py`` as a module."""
    path = module_path(directory, name, root)
    spec = importlib.util.spec_from_file_location(
        "benchmark_%s_%s" % (directory, name.replace(".", "_")
                             .replace("-", "_")), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for attr in PLUGINS[directory]:
        if not hasattr(mod, attr):
            raise ManifestError("%s lacks %s" % (path, attr))
    return mod


class Cell(object):
    """One entry of ``workloads`` with everything it names loaded."""

    def __init__(self, name, manifest_path=None, bench_root=None):
        manifest_path = manifest_path or os.path.join(ROOT, "BENCHMARK.json")
        bench_root = bench_root or HERE
        self.manifest = m = _read_json(manifest_path, "manifest")
        cells = {w["name"]: w for w in m.get("workloads", [])}
        if name not in cells:
            raise ManifestError("no workload %r in %s (it has %s)"
                                % (name, manifest_path, sorted(cells)))
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        self.root = bench_root
        configs = {c["name"]: c for c in m.get("configs", [])}
        if self.entry["config"] not in configs:
            raise ManifestError("workload %r names configuration %r, which "
                                "BENCHMARK.json does not list"
                                % (name, self.entry["config"]))
        cfg_entry = configs[self.entry["config"]]
        # a configuration's file is given from the root of the repo
        self.config = _read_json(
            os.path.join(os.path.dirname(bench_root), cfg_entry["file"]),
            "configuration %r" % cfg_entry["name"])
        self.config_name = cfg_entry["name"]
        self.traffic_name = self.entry["traffic"]
        self.traffic = _read_json(
            os.path.join(bench_root, "traffic", self.traffic_name + ".json"),
            "traffic %r" % self.traffic_name)

        self.limits = _read_json(
            os.path.join(bench_root, "limits", name + ".json"),
            "limits of cell %r" % name)
        # the job and the family import JAX: found now, loaded when run
        self.kind = str(self.traffic.get("kind"))
        self.family_name = str(self.config.get("family"))
        module_path("jobs", self.kind, bench_root)
        module_path("families", self.family_name, bench_root)

        # an end-to-end metric without `workloads` is every cell's; a
        # per-layer metric without it belongs to every cell that reports
        # the end-to-end metric it moves
        self.end_to_end = [e for e in m["end_to_end"]
                           if name in e.get("workloads", [name])]
        e2e_names = {e["name"] for e in self.end_to_end}
        self.per_layer = []
        for p in m["per_layer"]:
            if "workloads" not in p and p["moves"] not in e2e_names:
                continue
            if name not in p.get("workloads", [name]):
                continue
            if p["moves"] not in e2e_names:
                raise ManifestError(
                    "per-layer metric %r moves %r, which cell %r does not "
                    "report" % (p["name"], p["moves"], name))
            reader = load_module("metrics", p["name"], bench_root)
            for key, attr in (("layer", "LAYER"), ("unit", "UNIT"),
                              ("moves", "MOVES")):
                if p[key] != getattr(reader, attr):
                    raise ManifestError(
                        "metric %r: BENCHMARK.json says %s=%r, its reader "
                        "says %r" % (p["name"], key, p[key],
                                     getattr(reader, attr)))
            self.per_layer.append((p, reader))

    def job(self):
        """``jobs/<kind>.py``: how traffic of this kind is driven."""
        return load_module("jobs", self.kind, self.root)

    def family(self):
        """``families/<family>.py``: what the configuration's ``family``
        stands for."""
        return load_module("families", self.family_name, self.root)
