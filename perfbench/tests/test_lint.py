"""The lint passes on the benchmark as it is, catches what it is for, and
the README's worked example (a hypothetical ``rn50_sketch_32x128`` cell)
passes it as new files and new entries only."""

import json
import os
import shutil

from perfbench import lint
from perfbench.tests.conftest import ROOT


def scratch_copy(tmp_path):
    root = tmp_path / "repo"
    (root / "perfbench").mkdir(parents=True)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "perfbench"), root / "perfbench",
                    dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


def run_lint(root):
    return lint.lint(str(root))


def test_benchmark_passes():
    assert lint.lint(ROOT) == []


def test_readme_example_adds_files_and_entries_only(tmp_path):
    root = scratch_copy(tmp_path)
    before = {p: p.read_bytes() for p in (root / "perfbench").rglob("*")
              if p.is_file()}
    # 1. one new traffic file
    base = json.loads((root / "perfbench/traffic/sketch_8x64.json")
                      .read_text())
    flags = base["flags"]
    flags[flags.index("--num_workers") + 1] = "32"
    flags[flags.index("--local_batch_size") + 1] = "128"
    new = {**base, "name": "sketch_32x128", "flags": flags, "sync_every": 2,
           "trace_rounds": 2,
           "who": "a cross-silo round: 32 clients x 128 images"}
    (root / "perfbench/traffic/sketch_32x128.json").write_text(
        json.dumps(new))
    # 2. new entries in BENCHMARK.json: the cell, and its name in the
    #    workloads lists of the metrics whose condition it meets
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["workloads"].append({
        "name": "rn50_sketch_32x128", "config": "fixup_resnet50_imagenet",
        "traffic": "sketch_32x128", "chips": 1,
        "why": "32 clients x 128 images: the model-bound end of the round"})
    for m in b["per_layer"]:
        if m["name"] in ("circulant_sketch_encode_roofline",
                         "circulant_sketch_decode_roofline"):
            m["workloads"].append("rn50_sketch_32x128")
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    assert run_lint(root) == []
    # no file that was there has changed
    for p, data in before.items():
        assert p.read_bytes() == data, p


def test_lint_catches(tmp_path):
    root = scratch_copy(tmp_path)
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["workloads"][0]["chips"] = 4          # more than a quarter of the
    b["workloads"][1]["chips"] = 4          # cells on four chips
    by_name = {m["name"]: m for m in b["per_layer"]}
    by_name["input_wait_ms"]["moves"] = "tokens_per_s"
    by_name["dispatch_ms"]["unit"] = "ms per round"
    b["workloads"][1]["traffic"] = "no_such_mix"
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    errs = "\n".join(run_lint(root))
    assert "ask for four chips" in errs
    assert "no end-to-end metric" in errs
    assert "unit 'ms per round'" in errs
    assert "no traffic file" in errs


def test_a_metrics_cells_follow_from_its_condition(tmp_path):
    root = scratch_copy(tmp_path)
    b = json.loads((root / "BENCHMARK.json").read_text())
    by_name = {m["name"]: m for m in b["per_layer"]}
    by_name["circulant_sketch_decode_roofline"]["workloads"].append(
        "rn50_uncompressed_8x64")            # a cell without a sketch
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    errs = "\n".join(run_lint(root))
    assert "applies condition says False" in errs


def test_the_prepared_mesh_cell_needs_entries_only(tmp_path):
    """``rn50_sketch_8x64_mesh4`` has its files in the tree; what is left
    is what the README says."""
    root = scratch_copy(tmp_path)
    b = json.loads((root / "BENCHMARK.json").read_text())
    name = "rn50_sketch_8x64_mesh4"
    b["workloads"].append({
        "name": name, "config": "fixup_resnet50_imagenet",
        "traffic": "sketch_8x64_mesh4", "chips": 4,
        "why": "the sketch round as one program over a 4-chip mesh: "
               "collectives and the sharded server tail"})
    for m in b["per_layer"]:
        if m["name"] == "circulant_sketch_encode_roofline":
            m["workloads"].append(name)
    for metric in ("collective_ms", "collective_exposed_ms"):
        b["per_layer"].append({
            "name": metric, "unit": "ms/round", "better": "lower",
            "source": "device_trace", "layer": "collectives",
            "moves": "round_ms", "workloads": [name]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    assert run_lint(root) == []
