"""Finds everything by name, so a new cell, configuration, traffic mix or
metric is a new file and a new entry, never an edit:

  BENCHMARK.json               cells, metrics, run length
  bench/configs/<config>.json  a deployment, as it is run
  bench/traffic/<traffic>.json a traffic mix (benchlib.traffic reads it)
  bench/cells/<workload>.json  a cell's check: sample size, limits, trace
  bench/metrics/<metric>.py    a per-layer metric's reader, `read(ctx)`
  bench/work/<kernel>.py       a kernel's work count, `count(shape)`
  bench/peaks.json             chip peaks, keyed by JAX's device_kind
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, Dict

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _json(*parts: str, base: str = BENCH_DIR) -> dict:
    path = os.path.join(base, *parts)
    with open(path) as f:
        return json.load(f)


def _module(kind: str, name: str):
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


class Cell:
    """One workload of BENCHMARK.json with everything it names, loaded.

    `bench` and `base` stand in for BENCHMARK.json and bench/ where a test
    keeps cells of its own (bench/testdata/)."""

    def __init__(self, name: str, bench: dict = None, base: str = BENCH_DIR):
        bench = bench or benchmark()
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"unknown workload {name!r}; have {sorted(cells)}")
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        self.config = _json("configs", self.entry["config"] + ".json", base=base)
        self.traffic = _json("traffic", self.entry["traffic"] + ".json", base=base)
        self.check = _json("cells", name + ".json", base=base)
        self.end_to_end = [m for m in bench["end_to_end"] if self._has(m)]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [
            m for m in bench["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in reported)
        ]

    def _has(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def readers(self) -> Dict[str, Callable]:
        return {m["name"]: _module("metrics", m["name"]).read
                for m in self.per_layer}


def work(kernel: str):
    return _module("work", kernel)


def peaks() -> dict:
    return _json("peaks.json")


def rehearsal() -> dict:
    return _json("rehearsal.json")
