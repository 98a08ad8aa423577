"""Where the benchmark's data files are and how a cell is put together.

A cell of ``BENCHMARK.json`` names a configuration, a traffic mix and its
chips. Everything that belongs to one of them sits in a file of its own,
found by that name:

    configs/<config>.json    model family, published sizes, flags
    traffic/<traffic>.json   mode and sketch flags, round shape, mesh, lr
    metrics/<metric>.json    one per-layer metric: layer, unit, reader
    metrics/<metric>.py      (optional) the reader, when it is not declarative
    families/<family>.py     how to build model, loss, data, FLOPs, reference

so a later PR adds a cell, a configuration or a metric by adding files and
entries, never by editing one of these modules.
"""

from __future__ import annotations

import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH_DIR)
BENCHMARK_JSON = os.path.join(REPO, "BENCHMARK.json")


def _load(path):
    with open(path) as f:
        return json.load(f)


def load_benchmark(path=BENCHMARK_JSON):
    return _load(path)


def config_path(name):
    return os.path.join(BENCH_DIR, "configs", f"{name}.json")


def traffic_path(name):
    return os.path.join(BENCH_DIR, "traffic", f"{name}.json")


def metric_path(name, ext="json"):
    return os.path.join(BENCH_DIR, "metrics", f"{name}.{ext}")


class Cell:
    """One entry of ``workloads`` with its configuration and traffic."""

    def __init__(self, bench, name):
        found = [w for w in bench["workloads"] if w["name"] == name]
        if not found:
            raise KeyError(
                f"no workload {name!r} in BENCHMARK.json; it has "
                + ", ".join(w["name"] for w in bench["workloads"]))
        self.entry = found[0]
        self.name = name
        self.chips = int(self.entry["chips"])
        self.config = _load(config_path(self.entry["config"]))
        self.traffic = _load(traffic_path(self.entry["traffic"]))
        self.family = self.config["family"]
        # the driver's own flag list: configuration first, traffic after,
        # so a traffic mix can only add to a configuration's flags
        self.flags = list(self.config["flags"]) + list(self.traffic["flags"])

    def flag(self, name, default=None):
        """Value of ``--name`` in the cell's flag list (True for a bare
        switch, ``default`` when absent)."""
        flags = self.flags
        for i, f in enumerate(flags):
            if f == name:
                nxt = flags[i + 1] if i + 1 < len(flags) else None
                if nxt is None or nxt.startswith("--"):
                    return True
                return nxt
        return default

    def facts(self):
        """What a metric's ``applies`` condition may ask about."""
        return {"chips": self.chips, "family": self.family,
                "mode": self.flag("--mode", "sketch"),
                "config": self.entry["config"],
                "traffic": self.entry["traffic"]}


def applies(cond, facts):
    """``cond`` maps a fact to a value, a list of values, or
    ``{"min": n}``; all must hold. An empty condition always holds."""
    for key, want in (cond or {}).items():
        have = facts[key]
        if isinstance(want, dict):
            if "min" in want and have < want["min"]:
                return False
            if "max" in want and have > want["max"]:
                return False
        elif isinstance(want, list):
            if have not in want:
                return False
        elif have != want:
            return False
    return True


def cell_metrics(bench, cell):
    """(end_to_end names, per_layer metric files) this cell reports.

    ``BENCHMARK.json`` lists a metric's cells under ``workloads`` when it
    exists only in some; the metric's own file says *why* as a condition on
    the cell, and the lint holds the two together."""
    def listed(m):
        return "workloads" not in m or cell.name in m["workloads"]

    e2e = [m["name"] for m in bench["end_to_end"] if listed(m)]
    per_layer = []
    for m in bench["per_layer"]:
        if not listed(m):
            continue
        spec = _load(metric_path(m["name"]))
        if applies(spec.get("applies"), cell.facts()):
            per_layer.append(spec)
    return e2e, per_layer
