"""A cell, found by its name in ``BENCHMARK.json``: its configuration's
file, its traffic's file, its family, its driver and its per-layer
metrics, each a file of its own that the harness finds by name."""
import glob
import importlib
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")


def _json(path):
    with open(path) as fh:
        return json.load(fh)


def benchmark_json():
    return _json(os.path.join(ROOT, "BENCHMARK.json"))


def layer_specs():
    return [_json(p) for p in sorted(glob.glob(
        os.path.join(BENCH, "layer_metrics", "*.json")))]


class Cell:
    def __init__(self, workload):
        bench = benchmark_json()
        entry = next((w for w in bench["workloads"]
                      if w["name"] == workload), None)
        if entry is None:
            raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                             f"({[w['name'] for w in bench['workloads']]})")
        self.name = workload
        self.bench = bench
        self.chips = int(entry["chips"])
        conf = next(c for c in bench["configs"]
                    if c["name"] == entry["config"])
        self.config = _json(os.path.join(ROOT, conf["file"]))
        self.traffic = _json(os.path.join(
            BENCH, "traffic", entry["traffic"] + ".json"))
        self.family = importlib.import_module(
            "benchmark.families." + self.config["family"])
        self.driver = importlib.import_module(
            "benchmark.drivers." + self.traffic["driver"])
        self.end_to_end = [m["name"] for m in bench["end_to_end"]
                           if workload in m.get("workloads", [workload])]
        self.per_layer = [s for s in layer_specs()
                          if workload in s["workloads"]]
