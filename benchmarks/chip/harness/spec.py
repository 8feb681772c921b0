"""Find a cell and everything it is made of, by the names in BENCHMARK.json.

A cell ``<config>.<...>`` names a configuration (``configs/<config>.json``)
and a traffic mix (``traffic/<traffic>.json``); its serving-front settings,
offered rate and check limits are in ``cells/<cell>.json``. A per-layer
metric is read by ``metrics/<name>.py`` (or, where no file has the full
name, by the file of its name up to the first dot).
"""
from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))


def read_json(path: str):
    with open(path) as f:
        return json.load(f)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Cell:
    """One entry of ``workloads`` with its configuration, traffic and
    front settings loaded."""

    def __init__(self, name: str, bench_dir: str = HERE, root: str = ROOT):
        bench = read_json(os.path.join(root, "BENCHMARK.json"))
        entry = next((w for w in bench["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.name = name
        self.dir = bench_dir
        self.chips = int(entry["chips"])
        conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
        self.config = read_json(os.path.join(root, conf["file"]))
        self.traffic = read_json(
            os.path.join(bench_dir, "traffic", entry["traffic"] + ".json"))
        self.front = read_json(os.path.join(bench_dir, "cells",
                                            name + ".json"))
        self.end_to_end = [m for m in bench["end_to_end"] if applies(m, name)]
        self.per_layer = [m for m in bench["per_layer"] if applies(m, name)]

    def reader(self, metric: str):
        """The ``read(run)`` function of a per-layer metric."""
        for stem in (metric, metric.split(".")[0]):
            path = os.path.join(self.dir, "metrics", stem + ".py")
            if os.path.exists(path):
                return load_module(path).read
        raise FileNotFoundError(f"no reader for per-layer metric {metric!r}")


def load_module(path: str):
    name = "bench_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
