"""Everything the harness finds by name: the cell's workload file, its
configuration file, the metrics ``BENCHMARK.json`` gives the cell, and each
metric's reader ``bench/metrics/<metric>.py``."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path


class Spec:
    def __init__(self, root: Path):
        self.root = Path(root)
        self.bench = self.root / "bench"
        self.benchmark = json.loads((self.root / "BENCHMARK.json").read_text())

    def workload(self, name: str) -> dict:
        entry = next((w for w in self.benchmark["workloads"]
                      if w["name"] == name), None)
        if entry is None:
            raise KeyError(f"no cell {name!r} in BENCHMARK.json")
        wl = json.loads((self.bench / "workloads" / f"{name}.json"
                         ).read_text())
        if wl["config"] != entry["config"]:
            raise ValueError(f"{name}: workload file names config "
                             f"{wl['config']!r}, BENCHMARK.json "
                             f"{entry['config']!r}")
        return {**wl, "name": name, "chips": entry["chips"]}

    def config(self, name: str) -> dict:
        entry = next(c for c in self.benchmark["configs"]
                     if c["name"] == name)
        return json.loads((self.root / entry["file"]).read_text())

    def metrics(self, cell: str, *, trace: bool) -> list:
        """The cell's end-to-end metrics (``trace`` false) or per-layer
        metrics (``trace`` true), as ``BENCHMARK.json`` lists them."""
        e2e = [m for m in self.benchmark["end_to_end"]
               if cell in m.get("workloads", [cell])]
        if not trace:
            return e2e
        names = {m["name"] for m in e2e}
        return [m for m in self.benchmark["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in names)]

    def reader(self, metric: str):
        """``read(record) -> float | None`` of ``bench/metrics/<metric>.py``;
        for a metric split by cells (``compiles.ingest``) without a file of
        its own, that of the quantity it splits (``compiles.py``)."""
        path = self.bench / "metrics" / f"{metric}.py"
        if not path.exists() and "." in metric:
            path = path.with_name(metric.rsplit(".", 1)[0] + ".py")
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{metric.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
