#!/usr/bin/env python3
"""Lint of ``BENCHMARK.json`` and the benchmark's data files.

    python3 perfbench/lint.py [--root DIR]

Checks the contract's own limits (names, units, lengths, key sets, the
quarter of cells that may take four chips, the room a full check needs) and
what holds the harness together (every cell's configuration, traffic and
family files found; every ``moves`` an end-to-end metric of the cells that
report the metric; a metric's ``workloads`` list equal to where its file's
``applies`` condition holds). Prints one line per problem and exits 1 if
there is any. Touches no accelerator and imports no JAX.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import re
import sys

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "projection",
               "head_dim", "head_size", "expansion", "experts_per_tok",
               "n_embd", "width")


def line_ok(s):
    return (isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s
            and "\t" not in s)


def lint(root):
    # the tree under ``root`` is checked with its own spec.py, wherever
    # this file was imported from
    mod_spec = importlib.util.spec_from_file_location(
        "_perfbench_lint_spec",
        os.path.join(root, "perfbench", "harness", "spec.py"))
    spec = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(spec)
    errs = []
    err = errs.append
    path = os.path.join(root, "BENCHMARK.json")
    if os.path.getsize(path) > 64 * 1024:
        err("BENCHMARK.json is over 64 KiB")
    with open(path) as f:
        b = json.load(f)
    if set(b) != TOP_KEYS:
        err(f"top-level keys {sorted(b)} != {sorted(TOP_KEYS)}")
        return errs

    # command and paths
    if not (1 <= len(b["command"]) <= 32 and all(map(line_ok, b["command"]))):
        err("command: 1..32 strings of 1..200 characters")
    if not 1 <= len(b["paths"]) <= 16:
        err("paths: 1..16 directories")
    for p in b["paths"]:
        if not PATH.match(p) or p.startswith("/") or ".." in p.split("/"):
            err(f"path {p!r} is not a plain relative path")
    for word in b["command"]:
        if word.startswith("/") or ".." in word.split("/"):
            err(f"command word {word!r} leaves the repo")
        if os.path.exists(os.path.join(root, word)) and not any(
                word == p or word.startswith(p + "/") for p in b["paths"]):
            err(f"command names {word!r}, a file outside paths")
    for p in b["paths"]:
        for dirpath, _dirs, files in os.walk(os.path.join(root, p)):
            if "__pycache__" in dirpath:
                continue
            for fn in files:
                rel = os.path.relpath(os.path.join(dirpath, fn), root)
                if not PATH.match(rel):
                    err(f"file name {rel!r} has characters outside a name")

    # run_seconds and the room a full check needs
    rs = b["run_seconds"]
    if not (isinstance(rs, int) and 1 <= rs <= 51):
        err("run_seconds: a whole number 1..51")
    else:
        cells = 24
        need = (2 + 14 * cells) * (rs + 60) + cells * 2 * 90 + 1200
        if need > 43200:
            err(f"run_seconds {rs}: a full check of 24 cells needs {need} s "
                "> 43200")

    # configs
    names = set()
    if not 1 <= len(b["configs"]) <= 24:
        err("configs: 1..24")
    files = set()
    for c in b["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            err(f"config {c.get('name')}: keys {sorted(c)}")
            continue
        if not NAME.match(c["name"]) or c["name"] in names:
            err(f"config name {c['name']!r} illegal or repeated")
        names.add(c["name"])
        for k in ("source", "why"):
            if not line_ok(c[k]):
                err(f"config {c['name']}: {k} must be 1..200 characters on "
                    "one line")
        if c["file"] in files or not any(
                c["file"].startswith(p + "/") for p in b["paths"]):
            err(f"config {c['name']}: file {c['file']!r} repeated or not "
                "under paths")
        files.add(c["file"])
        if len(c["reduced"]) > 16:
            err(f"config {c['name']}: more than 16 reduced keys")
        for k in c["reduced"]:
            if not NAME.match(k):
                err(f"config {c['name']}: reduced key {k!r} illegal")
            if k.endswith(("_dim", "_rank")) or any(
                    w in k for w in WIDTH_WORDS):
                err(f"config {c['name']}: reduced names a width: {k!r}")
        full = os.path.join(root, c["file"])
        if c["file"] != os.path.relpath(spec.config_path(c["name"]),
                                        spec.REPO):
            err(f"config {c['name']}: file is not perfbench/configs/"
                "<name>.json, where the harness looks")
        if not os.path.exists(full):
            err(f"config {c['name']}: {c['file']} not found")
            continue
        with open(full) as f:
            cf = json.load(f)
        for k in ("family", "source", "reduced", "assumed", "flags", "data"):
            if k not in cf:
                err(f"{c['file']}: no {k!r}")
        if cf.get("source") != c["source"] or cf.get(
                "reduced") != c["reduced"]:
            err(f"{c['file']}: source/reduced differ from BENCHMARK.json")
        fam = os.path.join(root, "perfbench", "families",
                           f"{cf.get('family')}.py")
        if not os.path.exists(fam) or not os.path.exists(
                fam[:-3] + "_reference.py"):
            err(f"{c['file']}: family {cf.get('family')!r} needs "
                "families/<family>.py and families/<family>_reference.py")

    # metrics
    metric_names = set()
    e2e = {}
    if not 1 <= len(b["end_to_end"]) <= 16:
        err("end_to_end: 1..16")
    if not 1 <= len(b["per_layer"]) <= 128:
        err("per_layer: 1..128")
    for m in b["end_to_end"]:
        allowed = {"name", "unit", "better", "bound", "source", "workloads"}
        if not set(m) <= allowed or not {"name", "unit", "better", "bound",
                                         "source"} <= set(m):
            err(f"end_to_end {m.get('name')}: keys {sorted(m)}")
            continue
        e2e[m["name"]] = m
        if m["source"] not in ("host_clock", "device_trace"):
            err(f"end_to_end {m['name']}: source {m['source']!r}")
        if not 0.01 <= m["bound"] <= 0.1:
            err(f"end_to_end {m['name']}: bound {m['bound']} outside "
                "1%..10%")
    if "setup_s" not in e2e:
        err("end_to_end has no setup_s")
    for m in b["per_layer"]:
        allowed = {"name", "unit", "better", "source", "layer", "moves",
                   "workloads"}
        if not set(m) <= allowed or not (allowed - {"workloads"}) <= set(m):
            err(f"per_layer {m.get('name')}: keys {sorted(m)}")
            continue
        if m["source"] not in SOURCES:
            err(f"per_layer {m['name']}: source {m['source']!r}")
        if not line_ok(m["layer"]):
            err(f"per_layer {m['name']}: layer")
        if m["moves"] not in e2e:
            err(f"per_layer {m['name']}: moves {m['moves']!r}, which is no "
                "end-to-end metric")
        if m["name"].endswith("_roofline") and m["unit"] != "%":
            err(f"per_layer {m['name']}: a roofline share has the unit %")
    for m in b["end_to_end"] + b["per_layer"]:
        n = m.get("name", "")
        if not NAME.match(n) or n in metric_names:
            err(f"metric name {n!r} illegal or repeated")
        metric_names.add(n)
        if not UNIT.match(m.get("unit", "")):
            err(f"metric {n}: unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            err(f"metric {n}: better")

    # workloads
    cells = b["workloads"]
    if not 2 <= len(cells) <= 24:
        err("workloads: 2..24")
    seen, pairs = set(), set()
    for w in cells:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            err(f"workload {w.get('name')}: keys {sorted(w)}")
            continue
        for k in ("name", "config", "traffic"):
            if not NAME.match(w[k]):
                err(f"workload {w['name']}: {k} {w[k]!r} illegal")
        if w["name"] in seen or (w["config"], w["traffic"]) in pairs:
            err(f"workload {w['name']}: name or (config, traffic) repeated")
        seen.add(w["name"])
        pairs.add((w["config"], w["traffic"]))
        if w["chips"] not in (1, 4):
            err(f"workload {w['name']}: chips {w['chips']}")
        if not line_ok(w["why"]):
            err(f"workload {w['name']}: why must be 1..200 characters on "
                f"one line (it has {len(w['why'])})")
        if w["config"] not in names:
            err(f"workload {w['name']}: config {w['config']!r} not listed")
        if not os.path.exists(spec.traffic_path(w["traffic"])):
            err(f"workload {w['name']}: no traffic file "
                f"perfbench/traffic/{w['traffic']}.json")
    four = sum(w.get("chips") == 4 for w in cells)
    if four > max(1, len(cells) // 4):
        err(f"{four} of {len(cells)} cells ask for four chips")
    for c in names - {w.get("config") for w in cells}:
        err(f"config {c} is used by no cell")

    if errs:
        return errs

    # what holds the harness together
    for w in cells:
        try:
            cell = spec.Cell(b, w["name"])
        except Exception as e:
            err(f"workload {w['name']}: {type(e).__name__}: {e}")
            continue
        for k in ("flags", "lr", "sync_every", "warmup_rounds",
                  "trace_rounds", "who"):
            if k not in cell.traffic:
                err(f"traffic {w['traffic']}: no {k!r}")
        want_chips = int(cell.flag("--mesh_shape", "1").split(",")[0])
        if want_chips != cell.chips:
            err(f"workload {w['name']}: chips {cell.chips} but the flags "
                f"build a mesh of {want_chips}")
        reported_e2e = [m for m in b["end_to_end"]
                        if "workloads" not in m or w["name"] in m[
                            "workloads"]]
        if not any(m["name"] == "setup_s" for m in reported_e2e) or len(
                reported_e2e) < 2:
            err(f"workload {w['name']}: needs setup_s and one more "
                "end-to-end metric")
        n_layer = 0
        for m in b["per_layer"]:
            mp = spec.metric_path(m["name"])
            if not os.path.exists(mp):
                err(f"per_layer {m['name']}: no metrics/{m['name']}.json")
                continue
            with open(mp) as f:
                mf = json.load(f)
            for k in ("layer", "unit", "better", "source", "moves"):
                if mf.get(k) != m[k]:
                    err(f"metrics/{m['name']}.json: {k} differs from "
                        "BENCHMARK.json")
            if mf.get("reader", {}).get("kind") == "module" and not \
                    os.path.exists(mp[:-5] + ".py"):
                err(f"metrics/{m['name']}.py not found")
            holds = spec.applies(mf.get("applies"), cell.facts())
            listed = "workloads" not in m or w["name"] in m["workloads"]
            if holds != listed:
                err(f"per_layer {m['name']} / cell {w['name']}: the "
                    f"metric's applies condition says {holds}, "
                    f"BENCHMARK.json's workloads list says {listed}")
            if listed:
                n_layer += 1
                if not any(e["name"] == m["moves"] for e in reported_e2e):
                    err(f"per_layer {m['name']} moves {m['moves']}, which "
                        f"cell {w['name']} does not report")
        if not n_layer:
            err(f"workload {w['name']}: reports no per-layer metric")
    return errs


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    args = ap.parse_args(argv)
    errs = lint(os.path.abspath(args.root))
    for e in errs:
        print("lint:", e)
    print(f"lint: {len(errs)} problem(s)")
    return 1 if errs else 0


if __name__ == "__main__":
    sys.exit(main())
