"""The benchmark's reader of the program's span ring
(perfbench/harness/span_reader.py): which rounds of a trace run it picks,
what it refuses, the metrics' arithmetic on a ring of known durations, and
the attribution of device idle gaps to the innermost ``fed:`` span."""

import importlib.util
import os

import pytest

from perfbench.harness import span_reader

METRICS = os.path.join(os.path.dirname(span_reader.__file__), os.pardir,
                       "metrics")
# a trace run of 8 untraced rounds, then 4 under the profiler
CTX = {"host": {"rounds": 8}, "traced_rounds": 4}


def metric(name, ctx=CTX):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", os.path.join(METRICS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


class Ring:
    """Builds what ``tracing.current().snapshot()`` holds after a trace
    run of a cell with 2 rounds an epoch: times in ms, one loop thread
    (tid 0) and a prefetch thread (tid 1)."""

    def __init__(self):
        self.spans, self.t, self.ids = [], 0.0, 0

    def add(self, name, ms, rnd, tid=0, parent=None, **attrs):
        self.ids += 1
        self.spans.append({"id": self.ids, "parent": parent, "round": rnd,
                           "name": name, "ts": round(self.t * 1e-3, 6),
                           "dur_s": round(ms * 1e-3, 6), "tid": tid,
                           "depth": 0 if parent is None else 1, **attrs})
        self.t += ms
        return self.ids

    def round(self, g, runtime=0, stage=1.0, launch=4.0, fetch=3.0,
              wait=0.25, pipeline=True):
        first = pipeline and g % 2 == 1
        if first and g > 1:
            self.add("data_wait", 0.5, g)          # meets the sentinel
            self.add("pipeline_close", 2.0, g)
        if first:
            self.add("pipeline_open", 1.0, g)
        if pipeline:
            f = self.add("data_fetch", fetch, g, tid=1)
            if g == 1:      # the store's first batch compiles the gather
                self.add("data_gather_first", 0.0, g, tid=1, parent=f)
                self.spans[-1]["dur_s"] = 0.65
            self.add("data_wait", 3.5 if first else wait, g,
                     ready=not first)
        d = self.add("round_dispatch", 0.0, g, runtime=runtime)
        self.add("round_stage", stage, g, parent=d, runtime=runtime)
        self.add("round_launch", launch, g, parent=d, runtime=runtime)
        self.spans[d - 1]["dur_s"] = round((stage + launch + 0.1) * 1e-3, 6)

    def trace_run(self):
        self.add("runtime_init", 2000.0, None, runtime=0)
        self.add("init_state", 500.0, None, runtime=0)
        for g in range(1, 4):                       # warm-up, slow
            self.round(g, stage=50.0, launch=900.0, fetch=700.0)
        for g in range(4, 12):                      # the untraced stretch
            self.round(g)
        for g in range(12, 16):                     # under the profiler
            self.round(g, stage=9.0, launch=30.0, fetch=40.0)
        self.add("runtime_init", 100.0, 15, runtime=1)
        for _ in range(3):                          # round_algebra's runtime
            self.round(15, runtime=1, stage=70.0, launch=80.0,
                       pipeline=False)
        return self.spans


@pytest.fixture
def held(monkeypatch):
    spans = Ring().trace_run()
    box = {"held": (spans, 0)}
    monkeypatch.setattr(span_reader, "ring", lambda: box["held"])
    return box


def test_stretch_skips_warmup_the_traced_rounds_and_the_second_runtime(
        held):
    found = span_reader.stretch(CTX)
    assert found["rounds"] == list(range(4, 12))
    assert {s["round"] for s in found["spans"]} == set(range(4, 12))
    assert all(s.get("runtime", 0) == 0 for s in found["spans"])
    # per round: 1 + 4 ms of dispatch; 3 ms of fetch on the other thread
    assert metric("round_stage_ms") == pytest.approx(1.0)
    assert metric("round_launch_ms") == pytest.approx(4.0)
    assert metric("input_fetch_ms") == pytest.approx(3.0)
    # rounds 5, 7, 9, 11 open an epoch: (0.5 + 2 + 1 + 3.5) x 4 over 8
    assert metric("input_turnover_ms") == pytest.approx(3.5)
    assert metric("input_ready_pct") == pytest.approx(50.0)
    # set-up: the first runtime's, not the check's second one
    assert metric("runtime_init_s") == pytest.approx(2.5)
    assert metric("first_gather_s") == pytest.approx(0.65)


def test_a_shorter_untraced_stretch_takes_the_rounds_before_the_traced(held):
    ctx = {"host": {"rounds": 2}, "traced_rounds": 4}
    assert span_reader.stretch(ctx)["rounds"] == [10, 11]
    ctx = {"host": {"rounds": 2}, "traced_rounds": 0}
    assert span_reader.stretch(ctx)["rounds"] == [14, 15]


def test_none_on_a_ring_that_wrapped(held):
    spans = held["held"][0]
    # the ring dropped everything up to the middle of round 4: what is
    # left of the stretch is not whole
    cut = next(i for i, s in enumerate(spans)
               if s["name"] == "round_dispatch" and s["round"] == 4)
    held["held"] = (spans[cut:], cut)
    assert span_reader.stretch(CTX) is None
    assert metric("round_launch_ms") is None
    assert metric("input_ready_pct") is None
    assert metric("runtime_init_s") is None
    # it dropped the set-up and the warm-up only: the stretch is whole,
    # the set-up is gone
    cut = next(i for i, s in enumerate(spans) if s["round"] == 3)
    held["held"] = (spans[cut:], cut)
    assert span_reader.stretch(CTX)["rounds"] == list(range(4, 12))
    assert metric("first_gather_s") is None
    # too few rounds of the first runtime in the ring at all
    held["held"] = ([s for s in spans if s["round"] in (None, 14, 15)], 0)
    assert span_reader.stretch(CTX) is None


def test_none_without_a_ring(monkeypatch):
    # a checkout whose tracer records nothing (the NullTracer of old)
    from commefficient_tpu.telemetry import tracing

    class Null:
        pass

    monkeypatch.setattr(tracing, "_TRACER", Null())
    assert span_reader.ring() is None
    assert span_reader.stretch(CTX) is None
    assert all(metric(m) is None for m in (
        "round_stage_ms", "round_launch_ms", "input_fetch_ms",
        "input_turnover_ms", "input_ready_pct", "runtime_init_s",
        "first_gather_s"))


def test_none_where_no_pipeline_named_the_rounds(held):
    held["held"] = ([{**s, "round": None} for s in held["held"][0]], 0)
    assert span_reader.stretch(CTX) is None


def test_reads_the_programs_own_ring():
    """Unpatched: the reader reaches tracing.current() of this process."""
    from commefficient_tpu.telemetry import tracing

    with tracing.span("seen_by_the_reader"):
        pass
    spans, dropped = span_reader.ring()
    assert spans[-1]["name"] == "seen_by_the_reader"
    assert dropped == tracing.current().dropped_total


# ------------------------------------------------------------- idle gaps

MS = 1_000_000


def _dump():
    """One chip busy 0-10 and 30-40 ms, idle between; on the host a
    round_dispatch 8-34 with round_stage 9-12 and round_launch 12-33
    inside, a data_fetch on another thread 11-14, and the benchmark's own
    span around it all."""
    def ev(name, a, b):
        return [name, a * MS, (b - a) * MS, ""]

    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [ev("fusion.1", 0, 10),
                                           ev("fusion.2", 30, 40)]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [
                ev("bench:stretch", 0, 40), ev("bench:dispatch", 7, 35),
                ev("fed:round_dispatch", 8, 34), ev("fed:round_stage", 9, 12),
                ev("fed:round_launch", 12, 33)]},
            {"name": "round-prefetch", "events": [
                ev("fed:data_fetch", 11, 14)]}]}]}


def test_innermost_cuts_nested_spans_into_disjoint_pieces():
    pieces = span_reader.innermost([("outer", 0, 10), ("inner", 2, 5),
                                    ("innermost", 3, 4), ("next", 12, 13)])
    assert pieces == [("outer", 0, 2), ("inner", 2, 3), ("innermost", 3, 4),
                      ("inner", 4, 5), ("outer", 5, 10), ("next", 12, 13)]


def test_gap_goes_to_the_innermost_fed_span_that_covers_most_of_it():
    first, fed, bench = span_reader.gaps_by_span(_dump())
    assert first["gaps"] == [(10 * MS, 30 * MS)]
    # round_dispatch covers all 20 ms of the gap, but 18 of them are its
    # child's: the launch has the gap, not the parent, not the short fetch
    assert fed == [["fed:round_launch", pytest.approx(0.020)]]
    assert bench == [["bench:dispatch", pytest.approx(0.020)]]


def test_main_prints_both_attributions(tmp_path, capsys):
    import json
    path = tmp_path / "dump.json"
    path.write_text(json.dumps(_dump()))
    assert span_reader.main([str(path), "--rounds", "2"]) == 0
    out = capsys.readouterr().out
    assert "idle 10.000 ms/round" in out
    assert "fed:round_launch" in out and "bench:dispatch" in out
