"""Finds a cell's files by the names in ``BENCHMARK.json``: its
configuration (``configs/<config>.json``, as the manifest's ``file`` says),
its traffic mix (``traffic/<cell>.json``) and every per-layer metric
(``layer_metrics/*.json``) whose ``workloads`` holds the cell.  A later PR
adds a cell, a configuration or a metric by adding files and a manifest
entry; nothing here names one.  ``root`` is the repo's root, or a directory
laid out like it: ``tests/benchmarks/proposed`` holds a cell whose files are
written and whose program does not run yet."""

import glob
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _read(path):
    with open(path) as f:
        return json.load(f)


def load_manifest(root=ROOT):
    return _read(os.path.join(root, "BENCHMARK.json"))


def layer_metric_files(root=ROOT):
    return sorted(glob.glob(
        os.path.join(root, "benchmarks", "layer_metrics", "*.json")))


class Cell:
    """One entry of ``workloads`` with everything it names, read."""

    def __init__(self, name, root=ROOT, tiny=False):
        manifest = load_manifest(root)
        cells = {w["name"]: w for w in manifest["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                           f"{sorted(cells)}")
        self.name, self.root, self.tiny = name, root, tiny
        self.workload = cells[name]
        self.chips = self.workload["chips"]
        config_entry = next(c for c in manifest["configs"]
                            if c["name"] == self.workload["config"])
        self.config = _read(os.path.join(root, config_entry["file"]))
        self.traffic = _read(os.path.join(
            root, "benchmarks", "traffic", f"{name}.json"))
        if tiny:
            # The CPU rehearsal's sizes sit beside the real ones, so the
            # rehearsal walks the same files and the same code.
            self.config = {**self.config, **self.config["tiny"]}
            self.traffic = _merge(self.traffic, self.traffic["tiny"])
        self.end_to_end = [
            m for m in manifest["end_to_end"]
            if name in m.get("workloads", [name])]
        self.per_layer = [
            m for m in manifest["per_layer"]
            if name in m.get("workloads", [name])]
        self.layer_metrics = {}
        for path in layer_metric_files(root):
            spec = _read(path)
            if name in spec["workloads"]:
                self.layer_metrics[spec["name"]] = spec


def _merge(base, over):
    out = dict(base)
    for key, value in over.items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = _merge(out[key], value)
        else:
            out[key] = value
    return out
