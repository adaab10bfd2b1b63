"""The manifest loader finds each cell's files by name and refuses a
cell whose configuration, traffic, limits, job, family or metric file is
missing; a new cell, four-chip or of a new family or kind of job, is new
files and entries only."""
import json
import os
import shutil

import pytest

import manifest
import run

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


@pytest.fixture
def copy(tmp_path):
    """A copy of BENCHMARK.json and the data files, free to break."""
    root = tmp_path / "repo"
    bench = root / "benchmark"
    for sub in ("configs", "traffic", "metrics", "limits", "jobs", "families"):
        shutil.copytree(os.path.join(BENCH, sub), bench / sub)
    shutil.copy(os.path.join(BENCH, "peaks.json"), bench / "peaks.json")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    return root


def load(root, name):
    return manifest.Cell(name, str(root / "BENCHMARK.json"),
                         str(root / "benchmark"))


def cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("name", cells())
def test_every_cell_loads(copy, name):
    cell = load(copy, name)
    assert cell.chips in (1, 4)
    assert cell.kind in ("fit", "serve_closed")
    assert cell.per_layer, "every cell reports a per-layer metric"
    names = {e["name"] for e in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    for entry, reader in cell.per_layer:
        assert entry["moves"] in names


@pytest.mark.parametrize("victim", [
    "configs/cerebras-gpt-1.3b-l8.json", "traffic/fit-lm-b4-t2048.json",
    "limits/cgpt1.3b-fit.json", "metrics/train_mfu_pct.py", "jobs/fit.py",
    "families/transformer_lm.py"])
def test_missing_file_is_refused(copy, victim):
    os.remove(copy / "benchmark" / victim)
    with pytest.raises(manifest.ManifestError) as err:
        load(copy, "cgpt1.3b-fit")
    assert os.path.basename(victim) in str(err.value)


def test_unknown_cell_and_unknown_device_are_errors(copy):
    with pytest.raises(manifest.ManifestError):
        load(copy, "no-such-cell")
    assert manifest.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(manifest.ManifestError):
        manifest.load_peaks("TPU v9 imaginary")
    with pytest.raises(manifest.ManifestError):
        manifest.load_peaks("_source")


def test_reader_must_agree_with_the_manifest(copy):
    path = copy / "BENCHMARK.json"
    m = json.loads(path.read_text())
    for p in m["per_layer"]:
        if p["name"] == "train_mfu_pct":
            p["layer"] = "kernels"
    path.write_text(json.dumps(m))
    with pytest.raises(manifest.ManifestError):
        load(copy, "cgpt1.3b-fit")


def add_four_chip_cell(copy):
    """The README's worked example, done to a copy: a four-chip cell of a
    new family, on a new kind of job, as new files and new entries."""
    bench = copy / "benchmark"
    shutil.copy(bench / "families" / "transformer_lm.py",
                bench / "families" / "other_lm.py")
    shutil.copy(bench / "jobs" / "fit.py", bench / "jobs" / "other_fit.py")
    cfg = json.loads((bench / "configs" / "cerebras-gpt-1.3b-l8.json")
                     .read_text())
    cfg["family"] = "other_lm"
    (bench / "configs" / "other.json").write_text(json.dumps(cfg))
    job = json.loads((bench / "traffic" / "fit-lm-b4-t2048.json").read_text())
    job.update(kind="other_fit", batch_size=16, contexts=4,
               kvstore="dist_tpu_sync")
    (bench / "traffic" / "fit-lm-b16-t2048-x4.json").write_text(
        json.dumps(job))
    shutil.copy(bench / "limits" / "cgpt1.3b-fit.json",
                bench / "limits" / "cgpt1.3b-dist-sync-x4.json")
    path = copy / "BENCHMARK.json"
    m = json.loads(path.read_text())
    m["configs"].append({
        "name": "other", "source": "example", "reduced": ["num_layers"],
        "file": "benchmark/configs/other.json", "why": "example"})
    m["workloads"].append({
        "name": "cgpt1.3b-dist-sync-x4", "config": "other",
        "traffic": "fit-lm-b16-t2048-x4", "chips": 4, "why": "example"})
    for metric in m["end_to_end"] + m["per_layer"]:
        if "cgpt1.3b-fit" in metric.get("workloads", []):
            metric["workloads"].append("cgpt1.3b-dist-sync-x4")
    path.write_text(json.dumps(m))


def rehearse(copy, name, trace):
    return run.execute(["--workload", name, "--seed", "5", "--seconds", "1",
                        "--trace", str(trace), "--rehearse"],
                       manifest_path=str(copy / "BENCHMARK.json"),
                       bench_root=str(copy / "benchmark"))[0]


def test_a_new_cell_needs_only_new_files_and_entries(copy, monkeypatch):
    """Nothing that is there is edited, and the cell trains over four
    (virtual) devices through its own kvstore and comes out correct."""
    import mxnet_tpu as mx

    seen, fit = [], mx.mod.Module.fit

    def spy(self, *args, **keywords):
        seen.append((len(self._context), keywords.get("kvstore")))
        return fit(self, *args, **keywords)

    monkeypatch.setattr(mx.mod.Module, "fit", spy)
    add_four_chip_cell(copy)
    cell = load(copy, "cgpt1.3b-dist-sync-x4")
    assert cell.chips == 4 and len(cell.per_layer) == 5
    assert (cell.kind, cell.family_name) == ("other_fit", "other_lm")
    result = rehearse(copy, "cgpt1.3b-dist-sync-x4", trace=0)
    assert result["correct"] is True and result["attempted"] > 0
    assert set(result["metrics"]) == {"train_items_per_s", "setup_s"}
    assert seen == [(4, "dist_tpu_sync")]


def test_chips_and_contexts_have_to_agree(copy):
    """A four-chip entry over a one-chip job would train on one chip and
    report a quarter of its utilization: refused."""
    add_four_chip_cell(copy)
    path = copy / "BENCHMARK.json"
    m = json.loads(path.read_text())
    m["workloads"][-1]["traffic"] = "fit-lm-b4-t2048"
    path.write_text(json.dumps(m))
    with pytest.raises(SystemExit):
        rehearse(copy, "cgpt1.3b-dist-sync-x4", trace=0)
