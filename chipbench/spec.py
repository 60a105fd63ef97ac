"""Find a cell's configuration, traffic and metric readers by name."""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class Bench:
    """``BENCHMARK.json`` plus the files it names, under ``root``."""

    def __init__(self, root: str = ROOT, here: str = HERE):
        self.root, self.here = root, here
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.doc = json.load(f)
        self._readers: dict = {}

    def cell(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                with open(os.path.join(self.root, c["file"])) as f:
                    return json.load(f)
        raise SystemExit(f"chipbench: no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        with open(os.path.join(self.here, "traffic", name + ".json")) as f:
            return json.load(f)

    def metrics(self, cell: dict, traced: bool) -> list:
        """The metric entries a run of ``cell`` reports: its end-to-end
        metrics untraced, its per-layer metrics traced.  An entry without
        ``workloads`` belongs to every cell that reports what it moves."""
        e2e = [m for m in self.doc["end_to_end"] if self._in(m, cell)]
        if not traced:
            return e2e
        moved = {m["name"] for m in e2e}
        return [m for m in self.doc["per_layer"]
                if (cell["name"] in m["workloads"] if "workloads" in m
                    else m["moves"] in moved)]

    @staticmethod
    def _in(m: dict, cell: dict) -> bool:
        return "workloads" not in m or cell["name"] in m["workloads"]

    def reader(self, name: str):
        """``metrics/<name>.py``'s ``read``; a dotted variant such as
        ``serve.queue_ms.sat`` falls back to the reader of its stem."""
        if name not in self._readers:
            stem = name
            while True:
                path = os.path.join(self.here, "metrics", stem + ".py")
                if os.path.exists(path):
                    break
                if "." not in stem:
                    raise SystemExit(f"chipbench: no reader for {name!r}")
                stem = stem.rsplit(".", 1)[0]
            spec = importlib.util.spec_from_file_location(
                "chipbench_metric_" + stem.replace(".", "_"), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            self._readers[name] = mod.read
        return self._readers[name]
