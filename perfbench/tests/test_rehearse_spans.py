"""The span metrics through the command: a rehearsal of a trace run reads
all seven from the program's ring (about five minutes on the CPU)."""

import json

from perfbench.tests.test_rehearse import _run

SPAN_METRICS = {"round_stage_ms", "round_launch_ms", "input_fetch_ms",
                "input_turnover_ms", "input_ready_pct", "runtime_init_s",
                "first_gather_s"}


def test_rehearsed_trace_run_prints_the_seven_span_metrics():
    p = _run("--workload", "rn50_sketch_8x64", "--seed", "1",
             "--seconds", "1", "--trace", "1", "--rehearse")
    assert p.returncode == 5, p.stderr[-2000:]
    line = [ln for ln in p.stdout.splitlines()
            if "rehearsal result" in ln][0]
    metrics = json.loads(line.split("(NOT a measurement):", 1)[1])["metrics"]
    assert SPAN_METRICS <= set(metrics), sorted(metrics)
    # what the program's spans split is what the benchmark's clock times
    # from outside: the two halves lie inside dispatch_ms
    inside = (metrics["round_stage_ms"]["value"]
              + metrics["round_launch_ms"]["value"])
    assert 0 < inside <= metrics["dispatch_ms"]["value"]
    assert 0 <= metrics["input_ready_pct"]["value"] <= 100
