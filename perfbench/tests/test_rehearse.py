"""The command itself: a rehearsal walks every code path of the harness on
the CPU and can never print the result line; without a chip the command
refuses before it builds a model."""

import json
import os
import subprocess
import sys

from perfbench.tests.conftest import ROOT

RUN = [sys.executable, os.path.join(ROOT, "perfbench", "run.py")]


def _run(*args, env=None):
    e = {**os.environ, **(env or {})}
    return subprocess.run(RUN + list(args), cwd=ROOT, env=e,
                          capture_output=True, text=True, timeout=900)


def test_no_chip_exits_before_building_a_model():
    p = _run("--workload", "gpt2_sketch_8x8x2x256", "--seed", "1",
             "--seconds", "1", "--trace", "0",
             env={"JAX_PLATFORMS": "cpu"})
    assert p.returncode == 3, p.stderr[-2000:]
    assert "family" not in p.stdout
    assert not p.stdout.strip().endswith("}")


def test_rehearsal_walks_the_trace_run_and_prints_no_result():
    p = _run("--workload", "gpt2_sketch_8x8x2x256", "--seed", "1",
             "--seconds", "1", "--trace", "1", "--rehearse")
    assert p.returncode == 5, p.stderr[-2000:]
    last = p.stdout.strip().splitlines()[-1]
    assert last.startswith("[perfbench]")
    line = [ln for ln in p.stdout.splitlines()
            if "rehearsal result" in ln][0]
    result = json.loads(line.split("(NOT a measurement):", 1)[1])
    assert result["correct"] is True
    assert {"input_wait_ms", "dispatch_ms", "compile_s"} <= set(
        result["metrics"])
    assert result["breakdown"]["device_ops"]
