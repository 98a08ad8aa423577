"""Span tracer + utilization accounting (telemetry/tracing.py,
telemetry/utilization.py): nesting/reentrancy/thread-safety of the
tracer, the zero-overhead null path, the MFU math against synthetic
cost dicts and a fake peak table, the schema round-trip of the new
``span``/``utilization`` events (incl. the v1 backward-compat read),
the driver wiring, and the structural validity of the perfetto
``trace.json`` that ``teleview timeline`` renders."""

import importlib.util
import json
import os
import threading
import time

import numpy as np
import pytest

from commefficient_tpu.config import FedConfig
from commefficient_tpu.telemetry import (RunTelemetry, SpanTracer, tracing,
                                         validate_file, validate_lines)
from commefficient_tpu.telemetry.schema import (SCHEMA_VERSION,
                                                TELEMETRY_BASENAME)
from commefficient_tpu.telemetry.utilization import (UtilizationTracker,
                                                     emit_from_totals,
                                                     peak_flops_for,
                                                     straggler_spread,
                                                     utilization_fields)
from tests.test_telemetry import (B, D_IN, D_OUT, W, StubDS, make_batch,
                                  make_runtime, read_events)


def load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(os.path.dirname(__file__), os.pardir,
                           "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------------ tracer


def test_span_nesting_and_drain():
    tr = SpanTracer()
    with tr.span("outer"):
        with tr.span("inner"):
            time.sleep(0.002)
        with tr.span("inner2"):
            pass
    spans = tr.drain()
    by_name = {s["name"]: s for s in spans}
    assert set(by_name) == {"outer", "inner", "inner2"}
    assert by_name["outer"]["depth"] == 0
    assert by_name["inner"]["depth"] == by_name["inner2"]["depth"] == 1
    # children close before the parent and start after it
    assert by_name["inner"]["ts"] >= by_name["outer"]["ts"]
    assert by_name["outer"]["dur_s"] >= by_name["inner"]["dur_s"] >= 0.002
    # drain cleared the buffer; re-entering after a drain works
    assert tr.drain() == []
    with tr.span("again"):
        pass
    assert [s["name"] for s in tr.drain()] == ["again"]


def test_span_records_on_exception():
    tr = SpanTracer()
    with pytest.raises(RuntimeError):
        with tr.span("dies"):
            raise RuntimeError("boom")
    spans = tr.drain()
    assert [s["name"] for s in spans] == ["dies"]
    # the depth counter unwound: a following span is top-level again
    with tr.span("next"):
        pass
    assert tr.drain()[0]["depth"] == 0


def test_span_thread_safety():
    tr = SpanTracer()
    # hold every thread at the gate until all are alive: a thread that
    # finishes before another starts can hand its (reused) OS ident to
    # the newcomer, merging their tids
    gate = threading.Barrier(4)

    def work():
        gate.wait()
        for _ in range(50):
            with tr.span("a"):
                with tr.span("b"):
                    pass

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = tr.drain()
    assert len(spans) == 4 * 50 * 2
    assert {s["tid"] for s in spans} == {0, 1, 2, 3}
    for s in spans:
        # per-thread nesting survived concurrency
        assert s["depth"] == (1 if s["name"] == "b" else 0)


def test_span_buffer_cap_counts_drops():
    tr = SpanTracer(max_spans=3)
    for i in range(5):
        with tr.span(f"s{i}"):
            pass
    assert len(tr.drain()) == 3
    # per-window semantics: pop returns the drops once, then resets —
    # each span event's n_dropped covers its own window only
    assert tr.pop_dropped() == 2
    assert tr.pop_dropped() == 0


def _last_id():
    """Id of the newest span the process's ring holds (0: none yet). Ids
    grow in opening order, so ``_since`` finds what a test added however
    many spans the ring has already turned over."""
    held = tracing.current().snapshot()
    return max((s["id"] for s in held), default=0)


def _since(before):
    return [s for s in tracing.current().snapshot() if s["id"] > before]


def _profiled_events(trace_dir):
    """[(name, duration in s)] of the ``fed:`` annotations in the newest
    profiler trace under ``trace_dir``, in starting order."""
    import glob

    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    events = [(ev.start_ns, ev.name, ev.duration_ns * 1e-9)
              for plane in ProfileData.from_file(path).planes
              for line in plane.lines for ev in line.events
              if ev.name.startswith("fed:")]
    return [(name, dur) for _start, name, dur in sorted(events)]


def test_spans_are_on_the_profilers_clock(tmp_path):
    """Every span enters ``jax.profiler.TraceAnnotation("fed:" + name)``
    itself, with no driver, flag or install(): a CPU trace around two
    rounds holds each ``fed:round_launch`` with the ring's duration."""
    import jax

    rt = make_runtime()
    batch, mask, ids = make_batch()
    state = rt.init_state()
    state, _ = rt.round(state, ids, batch, mask, 0.05)   # compiles
    jax.block_until_ready(state)
    before = _last_id()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for _ in range(2):
            state, _ = rt.round(state, ids, batch, mask, 0.05)
        jax.block_until_ready(state)
    finally:
        jax.profiler.stop_trace()
    ring = [s for s in _since(before) if s["name"] == "round_launch"]
    traced = [d for n, d in _profiled_events(str(tmp_path))
              if n == "fed:round_launch"]
    assert len(ring) == len(traced) == 2
    for s, d in zip(ring, traced):
        assert abs(s["dur_s"] - d) < 50e-6, (s, d)
    names = {n for n, _d in _profiled_events(str(tmp_path))}
    assert {"fed:round_dispatch", "fed:round_stage",
            "fed:round_launch"} <= names


def test_span_annotation_closes_on_an_exception(monkeypatch):
    """The annotation is entered in span order beside the span's own
    clock and left on an exception too; where jax is absent (the factory
    resolves to False) spans record all the same."""
    log = []

    class Recording:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))

        def __exit__(self, *exc):
            log.append(("exit", self.name, exc[0]))

    monkeypatch.setattr(tracing, "_ANNOTATION", Recording)
    tr = SpanTracer()
    with tr.span("round"):
        with tr.span("data_fetch"):
            pass
    with pytest.raises(KeyError):
        with tr.span("boom"):
            raise KeyError("x")
    assert log == [("enter", "fed:round"), ("enter", "fed:data_fetch"),
                   ("exit", "fed:data_fetch", None),
                   ("exit", "fed:round", None),
                   ("enter", "fed:boom"), ("exit", "fed:boom", KeyError)]
    assert [s["name"] for s in tr.drain()] == ["data_fetch", "round",
                                               "boom"]
    monkeypatch.setattr(tracing, "_ANNOTATION", False)
    with tr.span("quiet"):
        pass
    assert len(log) == 6 and len(tr.drain()) == 1


def test_default_ring_records_with_nothing_installed():
    """With no tracer installed (no driver, --no_telemetry) span() records
    into the process's bounded ring, and install/uninstall hand the sites
    to a driver's tracer and back to the same ring."""
    ring = tracing.current()
    assert isinstance(ring, SpanTracer)
    assert ring.max_spans == tracing.DEFAULT_RING
    with tracing.span("ringed"):
        pass
    assert ring.snapshot()[-1]["name"] == "ringed"
    tr = tracing.install()
    try:
        assert tracing.current() is tr
        with tracing.span("live"):
            pass
        assert [s["name"] for s in tr.drain()] == ["live"]
    finally:
        tracing.uninstall()
    assert tracing.current() is ring
    # the ring kept what it held and saw nothing of the driver's
    assert ring.snapshot()[-1]["name"] == "ringed"


def test_ring_is_bounded_and_drops_the_oldest():
    tr = SpanTracer(max_spans=4)
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    assert [s["name"] for s in tr.snapshot()] == ["s6", "s7", "s8", "s9"]
    assert tr.dropped_total == 6
    # a snapshot clears nothing; the per-window counter does not touch
    # the total
    assert len(tr.snapshot()) == 4 and tr.pop_dropped() == 6
    assert tr.pop_dropped() == 0 and tr.dropped_total == 6


def test_span_ids_parents_and_rounds():
    """A span's parent is the span that encloses it on its thread; its
    round is what set_round last said on that thread, None before; the
    site's attributes ride along."""
    tr = SpanTracer()
    seen = {}

    def other_thread():
        with tr.span("elsewhere"):
            pass
        tracing.set_round(9)
        with tr.span("elsewhere_9"):
            pass

    tracing.set_round(None)
    with tr.span("outer", runtime=3) as outer:
        tracing.set_round(7)
        with tr.span("inner"):
            pass
        outer.set(ready=True)
        t = threading.Thread(target=other_thread)
        t.start()
        t.join(timeout=10)
    tracing.set_round(None)
    with tr.span("after"):
        pass
    seen = {s["name"]: s for s in tr.drain()}
    assert len({s["id"] for s in seen.values()}) == 5
    assert seen["inner"]["parent"] == seen["outer"]["id"]
    assert seen["outer"]["parent"] is None
    # another thread has a stack and a round of its own
    assert seen["elsewhere"]["parent"] is None
    assert seen["elsewhere"]["round"] is None
    assert seen["elsewhere_9"]["round"] == 9
    assert seen["outer"]["round"] is None and seen["inner"]["round"] == 7
    assert seen["after"]["round"] is None
    assert seen["outer"]["runtime"] == 3 and seen["outer"]["ready"] is True
    assert "runtime" not in seen["inner"]


def test_summary_counts_totals_and_longest():
    tracing.install()
    try:
        tracing.set_round(4)
        with tracing.span("slow"):
            time.sleep(0.01)
            with tracing.span("quick"):
                pass
        tracing.set_round(None)
        for _ in range(tracing.LONGEST):
            with tracing.span("quick"):
                pass
        out = tracing.summary()
    finally:
        tracing.uninstall()
    assert out["spans"] == tracing.LONGEST + 2 and out["dropped"] == 0
    assert out["names"]["quick"]["count"] == tracing.LONGEST + 1
    assert out["names"]["slow"]["total_s"] >= 0.01
    assert out["names"]["slow"]["max_s"] == out["names"]["slow"]["total_s"]
    # the ten longest whole, longest first
    assert len(out["longest"]) == tracing.LONGEST
    slow = out["longest"][0]
    assert slow["name"] == "slow" and slow["round"] == 4
    inner = next(s for s in out["longest"] if s["parent"] is not None)
    assert inner["parent"] == slow["id"] and inner["round"] == 4
    # uninstalled: the process's ring again
    with tracing.span("in_the_ring"):
        pass
    assert "in_the_ring" in tracing.summary()["names"]


# ------------------------------------------- the sites, with no driver


def _drive_two_epochs(threaded):
    """FedRuntime.round over a real RoundPipeline and FedSampler, two
    epochs of two rounds, with no driver and nothing installed. Returns
    (new spans of the default ring, the two pipelines, the runtime)."""
    import jax

    from commefficient_tpu.core.pipeline import RoundPipeline
    from commefficient_tpu.data import FedSampler
    from commefficient_tpu.data.device_store import DeviceStore

    before = _last_id()
    rt = make_runtime()
    state = rt.init_state()
    rng = np.random.RandomState(0)
    store = DeviceStore({"x": rng.randn(8 * B, D_IN).astype(np.float32),
                         "y": rng.randn(8 * B, D_OUT).astype(np.float32)})
    g_round, pipes = 0, []

    def fetch(rnd, g):
        time.sleep(0.02)        # never ready when the thread just started
        return store.round_batch(rnd.idx, None)

    for epoch in range(2):
        sampler = FedSampler(np.full(8, B), W, B, seed=epoch)
        pipe = RoundPipeline(sampler, fetch, start_round=g_round,
                             enabled=threaded)
        pipes.append(pipe)
        for item in pipe:
            g_round = item.global_round
            state, _ = rt.round(state, item.rnd.client_ids, item.batch,
                                item.rnd.mask, 0.05)
            # let the prefetcher get ahead: the next batch is queued
            # before the loop asks for it
            jax.block_until_ready(state)
            time.sleep(0.15)
        pipe.close()
    assert g_round == 4
    return _since(before), pipes, rt


def _one(spans, name, **where):
    found = [s for s in spans if s["name"] == name
             and all(s.get(k) == v for k, v in where.items())]
    assert len(found) == 1, (name, where, found)
    return found[0]


def test_round_id_joins_the_two_threads_of_a_real_pipeline():
    spans, pipes, rt = _drive_two_epochs(threaded=True)
    assert all({"id", "parent", "round", "name", "ts", "dur_s",
                "tid"} <= set(s) for s in spans)
    for g in (1, 2, 3, 4):
        fetch = _one(spans, "data_fetch", round=g)
        wait = _one(spans, "data_wait", round=g, ready=g % 2 == 0)
        dispatch = _one(spans, "round_dispatch", round=g)
        # the worker's thread, the loop's thread
        assert fetch["tid"] != wait["tid"] == dispatch["tid"]
        gather = [s for s in spans if s["parent"] == fetch["id"]]
        assert [s["name"] for s in gather] == [
            "data_gather_first" if g == 1 else "data_gather"]
        assert gather[0]["round"] == g
        # staging and launch lie inside the dispatch, in that order
        stage = _one(spans, "round_stage", parent=dispatch["id"])
        launch = _one(spans, "round_launch", parent=dispatch["id"])
        assert dispatch["ts"] <= stage["ts"] <= launch["ts"]
        assert (launch["ts"] + launch["dur_s"]
                <= dispatch["ts"] + dispatch["dur_s"] + 1e-6)
        assert stage["dur_s"] + launch["dur_s"] <= dispatch["dur_s"] + 2e-6
        assert stage["round"] == launch["round"] == g
    # once an epoch: opened for the epoch's first round; closed when the
    # loop is asked for the round after its last, on the loop's thread
    assert sorted(s["round"] for s in spans
                  if s["name"] == "pipeline_open") == [1, 3]
    assert sorted(s["round"] for s in spans
                  if s["name"] == "pipeline_close") == [3, 5]
    # the call that meets the end-of-epoch sentinel waits too, and is no
    # round handed out: no ready mark
    sentinel = [s for s in spans if s["name"] == "data_wait"
                and "ready" not in s]
    assert sorted(s["round"] for s in sentinel) == [3, 5]
    # the two counts: rounds handed out, and of those the rounds marked
    # ready (every epoch's first finds the queue empty, its second was
    # fetched while the first ran)
    assert [p.rounds_out for p in pipes] == [2, 2]
    marks = sorted((s["round"], s["ready"]) for s in spans
                   if s["name"] == "data_wait" and "ready" in s)
    assert marks == [(1, False), (2, True), (3, False), (4, True)]


def test_a_span_after_the_pipeline_is_closed_is_in_no_round():
    """Exhausted or closed, a pipeline leaves its thread in no round:
    what the loop does between epochs and after the last one (validation,
    a second runtime's set-up) carries ``round`` None, not a stale one."""
    from commefficient_tpu.core.pipeline import RoundPipeline

    def round_of_a_span_now():
        before = _last_id()
        with tracing.span("now"):
            pass
        return _one(_since(before), "now")["round"]

    for threaded in (True, False):
        pipe = RoundPipeline([0, 1, 2], lambda rnd, g: rnd, start_round=4,
                             enabled=threaded)
        assert [item.global_round for item in pipe] == [5, 6, 7]
        assert round_of_a_span_now() is None          # exhausted
        # closed early, two rounds in
        pipe = RoundPipeline([0, 1, 2], lambda rnd, g: rnd, start_round=7,
                             enabled=threaded)
        next(pipe), next(pipe)
        assert round_of_a_span_now() == 9
        pipe.close()
        assert round_of_a_span_now() is None


def test_inline_pipeline_counts_none_ready():
    spans, pipes, rt = _drive_two_epochs(threaded=False)
    assert [p.rounds_out for p in pipes] == [2, 2]
    names = [s["name"] for s in spans]
    # no wait on a queue, so no span to mark ready
    assert "data_wait" not in names and "pipeline_close" not in names
    assert names.count("pipeline_open") == 2
    for g in (1, 2, 3, 4):
        fetch = _one(spans, "data_fetch", round=g)
        assert fetch["tid"] == _one(spans, "round_dispatch",
                                    round=g)["tid"]


def test_each_runtime_marks_its_spans_with_its_own_ordinal():
    """A second FedRuntime's rounds carry another ordinal; the set-up
    spans carry their runtime's; a compile is a child of the launch that
    met it."""
    from commefficient_tpu.telemetry.compilewatch import JitWatcher

    before = _last_id()
    first, second = make_runtime(), make_runtime()
    assert first.ordinal != second.ordinal
    second.set_compile_watcher(JitWatcher(CaptureTelemetry()))
    batch, mask, ids = make_batch()
    for rt in (first, second):
        state = rt.init_state()
        for _ in range(2):
            state, _ = rt.round(state, ids, batch, mask, 0.05)
    spans = _since(before)
    for rt in (first, second):
        mine = [s for s in spans if s.get("runtime") == rt.ordinal]
        assert sorted(s["name"] for s in mine) == sorted(
            ["runtime_init", "init_state"]
            + ["round_dispatch", "round_stage", "round_launch"] * 2)
    launches = [s for s in spans if s["name"] == "round_launch"
                and s["runtime"] == second.ordinal]
    compiles = [s for s in spans if s["name"] in ("compile_lower",
                                                  "compile_backend")]
    assert [s["name"] for s in compiles] == ["compile_lower",
                                             "compile_backend"]
    assert all(s["parent"] == launches[0]["id"] for s in compiles)
    assert sum(s["dur_s"] for s in compiles) <= launches[0]["dur_s"]


# ------------------------------------------------------------------ MFU math


def test_peak_flops_table_and_override():
    assert peak_flops_for("TPU v5 lite chip") == 197e12
    assert peak_flops_for("TPU v4 (whatever)") == 275e12
    assert peak_flops_for("cpu") is None          # unknown => null, not 0
    assert peak_flops_for("cpu", override=3e12) == 3e12
    assert peak_flops_for("TPU v4", override=1e12) == 1e12  # override wins


def test_utilization_fields_math():
    """Synthetic cost-analysis numbers through the pure math: the exact
    MFU/starvation identities, and nulls (never fake zeros) where the
    inputs are unknown."""
    f = utilization_fields(rounds=10, wall_s=2.0, host_s=0.5,
                           dispatch_s=0.3, device_s=1.0,
                           flops_per_round=1e11,
                           flops_source="cost_analysis",
                           device_kind="TPU v5e", peak_flops=197e12)
    assert f["achieved_flops"] == pytest.approx(10 * 1e11 / 2.0)
    assert f["mfu"] == pytest.approx(10 * 1e11 / 2.0 / 197e12, rel=1e-3)
    assert f["input_wait_frac"] == pytest.approx(0.25)
    assert f["dispatch_frac"] == pytest.approx(0.15)
    assert f["device_wait_frac"] == pytest.approx(0.5)
    assert f["flops_source"] == "cost_analysis"
    # no FLOPs count => null achieved/mfu/source
    f = utilization_fields(rounds=1, wall_s=1.0, host_s=0, dispatch_s=0,
                           device_s=0, flops_per_round=None,
                           flops_source="cost_analysis",
                           device_kind="TPU v5e", peak_flops=197e12)
    assert f["mfu"] is None and f["achieved_flops"] is None
    assert f["flops_source"] is None
    # no peak => achieved computes, mfu stays null
    f = utilization_fields(rounds=1, wall_s=1.0, host_s=0, dispatch_s=0,
                           device_s=0, flops_per_round=5e9,
                           flops_source="analytic", device_kind="cpu",
                           peak_flops=None)
    assert f["achieved_flops"] == pytest.approx(5e9)
    assert f["mfu"] is None


def test_straggler_spread():
    assert straggler_spread([]) is None
    assert straggler_spread([1.0]) is None          # one host can't straggle
    assert straggler_spread([1.0, 1.0]) == 0.0
    assert straggler_spread([1.0, 3.0]) == pytest.approx(1.0)  # (3-1)/2


class CaptureTelemetry:
    """RunTelemetry stand-in recording event() calls."""

    def __init__(self):
        self.events = []

    def event(self, kind, **fields):
        self.events.append({"event": kind, **fields})


def test_utilization_tracker_windows():
    tel = CaptureTelemetry()
    util = UtilizationTracker(tel, device_kind="TPU v5e", peak_flops=1e12)
    assert util.emit(0) is None and tel.events == []  # empty window no-ops
    util.set_flops_per_round(2e9, source="analytic")
    util.observe_round(host_s=0.01, dispatch_s=0.02, device_s=0.03)
    util.observe_round(host_s=0.01, dispatch_s=0.02)   # unsynced round
    f = util.emit(7)
    assert f is not None and tel.events[-1]["event"] == "utilization"
    assert tel.events[-1]["round"] == 7
    assert f["rounds"] == 2
    assert f["wall_s"] >= 0.03                 # window spans both rounds
    assert f["flops_per_round"] == 2e9 and f["flops_source"] == "analytic"
    assert f["mfu"] == pytest.approx(2 * 2e9 / (f["wall_s"] * 1e12),
                                     rel=1e-2)
    # the window reset: a second emit with no rounds observed is a no-op
    assert util.emit(8) is None


def test_utilization_tracker_reads_watcher_flops():
    class FakeWatcher:
        flops = {"round_step": 3e9}

    tel = CaptureTelemetry()
    util = UtilizationTracker(tel, device_kind="TPU v5e",
                              watcher=FakeWatcher())
    util.observe_round(host_s=0.0, dispatch_s=0.001, device_s=0.0)
    f = util.emit(1)
    assert f["flops_per_round"] == 3e9
    assert f["flops_source"] == "cost_analysis"


# ------------------------------------------------------------------- schema


def test_span_and_utilization_schema_roundtrip(tmp_path):
    tel = RunTelemetry(str(tmp_path), "test", cfg=None)
    tr = SpanTracer()
    with tr.span("data_fetch"):
        with tr.span("host_gather"):
            pass
    tel.span_event(tr)
    tel.span_event(tr)   # drained buffer => no empty event written
    emit_from_totals(tel, rnd=1, rounds=1, wall_s=0.5, host_s=0.1,
                     dispatch_s=0.2, device_s=0.1, flops_per_round=1e9,
                     flops_source="analytic", device_kind="TPU v5e",
                     per_host_device_s=[0.1, 0.3])
    tel.write_summary(aborted=False, n_rounds=1)
    tel.close()
    assert validate_file(tel.path) == []
    events = read_events(tel.path)
    kinds = [e["event"] for e in events]
    assert kinds.count("span") == 1
    assert kinds.count("utilization") == 1
    sp = next(e for e in events if e["event"] == "span")
    assert {s["name"] for s in sp["spans"]} == {"data_fetch", "host_gather"}
    assert sp["t0_wall"] > 0
    ut = next(e for e in events if e["event"] == "utilization")
    assert ut["straggler_spread"] == pytest.approx(1.0)
    man = events[0]
    assert man["schema"] == SCHEMA_VERSION == 12


def test_v1_streams_stay_readable():
    """Backward-compat read: a manifest written under schema 1 (pre
    span/utilization) must still validate."""
    man = {"event": "manifest", "t": 0.0, "seq": 0, "schema": 1,
           "run_type": "t", "jax_version": "x", "backend": "cpu",
           "device_kind": "cpu", "device_count": 1, "mesh_shape": [],
           "mesh_axes": [], "grad_size": 1, "sketch": None, "config": {}}
    assert validate_lines([json.dumps(man)]) == []
    # an unknown FUTURE version is still rejected
    man["schema"] = 99
    assert any("schema" in p for _, p in validate_lines([json.dumps(man)]))


def test_selftest_covers_new_event_types():
    mod = load_script("check_telemetry_schema")
    lines = mod.sample_stream()
    kinds = [json.loads(l)["event"] for l in lines]
    assert "span" in kinds and "utilization" in kinds
    assert "client_stats" in kinds and "alert" in kinds
    # the client_stats sample carries realistic ordered quantiles — the
    # selftest is the cheap CI proof the generator and validator agree
    cs = next(json.loads(l) for l in lines
              if json.loads(l)["event"] == "client_stats")
    q = cs["quantiles"]["loss"]
    assert q["p5"] <= q["p50"] <= q["p95"] <= q["max"]
    assert mod.main(["--selftest"]) == 0


# ------------------------------------------------------------ driver wiring


def run_driver(tmp_path, **cfg_kw):
    from commefficient_tpu import cv_train
    from commefficient_tpu.utils import TableLogger

    rt = make_runtime(dataset_name="SYNTH", telemetry_every=1,
                      peak_flops=1e12, **cfg_kw)
    tel = RunTelemetry(str(tmp_path), "cv_train", cfg=rt.cfg)
    tel.instrument(rt)
    cfg = rt.cfg.replace(num_epochs=1.0, pivot_epoch=0.5)
    state, summary = cv_train.train(cfg, rt, rt.init_state(), StubDS(),
                                    StubDS(), loggers=(TableLogger(),),
                                    telemetry=tel)
    tel.close()
    assert summary is not None
    return tel.path


def test_driver_emits_spans_and_utilization(tmp_path, capsys):
    path = run_driver(tmp_path)
    assert validate_file(path) == []
    events = read_events(path)
    kinds = [e["event"] for e in events]
    assert "span" in kinds and "utilization" in kinds
    names = {s["name"] for e in events if e["event"] == "span"
             for s in e["spans"]}
    # the full vertical slice: driver loop phases, runtime dispatch,
    # the validation sweep, the emission tail (the data-layer spans are
    # covered by test_data_layer_spans — StubDS is not a FedDataset)
    for expected in ("data_fetch", "round_dispatch", "device_wait",
                     "telemetry_emit", "validation", "val_dispatch"):
        assert expected in names, (expected, names)
    ut = [e for e in events if e["event"] == "utilization"]
    # cadence=1 emits per round, plus the epoch-boundary flush no-ops
    assert all(e["rounds"] >= 1 for e in ut)
    assert sum(e["rounds"] for e in ut) == 2     # StubDS: 2 rounds/epoch
    # the watcher's cost-analysis FLOPs reached the MFU join, and the
    # --peak_flops override made mfu computable on CPU
    assert all(e["flops_source"] == "cost_analysis" for e in ut)
    assert all(e["mfu"] is not None and e["mfu"] > 0 for e in ut)
    assert all(0 <= e["input_wait_frac"] <= 1 for e in ut)
    # the tracer was uninstalled on the way out: the sites are the
    # default ring's again
    assert tracing.current().max_spans == tracing.DEFAULT_RING


def test_data_layer_spans():
    """The loader waits are instrumented at the layer that owns them:
    FedDataset.gather (host pipeline) and DeviceStore.round_batch
    (device gather dispatch) each open their span."""
    from commefficient_tpu.data.device_store import DeviceStore
    from commefficient_tpu.data.fed_dataset import FedDataset

    ds = FedDataset.__new__(FedDataset)   # bypass the on-disk prepare
    ds.train, ds.do_iid, ds.transform = True, False, None
    ds.arrays = {"x": np.arange(12).reshape(6, 2)}
    store = DeviceStore({"x": np.zeros((6, 2), np.float32)})
    tr = tracing.install()
    try:
        out = ds.gather(np.array([1, 3]))
        assert out["x"].shape == (2, 2)
        for _ in range(3):
            batch = store.round_batch(np.array([0, 1]), None)
            assert batch["x"].shape == (2, 2)
    finally:
        tracing.uninstall()
    names = [s["name"] for s in tr.drain()]
    # a store's first batch holds the gather's compile: a name of its own
    assert names == ["host_gather", "data_gather_first", "data_gather",
                     "data_gather"]


def test_no_telemetry_records_into_the_default_ring(capsys):
    """--no_telemetry: train() installs no tracer of its own — the span
    sites record into the process's default ring, driver spans
    included."""
    from commefficient_tpu import cv_train

    ring = tracing.current()
    before = _last_id()
    rt = make_runtime(dataset_name="SYNTH", telemetry=False)
    cfg = rt.cfg.replace(num_epochs=1.0, pivot_epoch=0.5)
    state, summary = cv_train.train(cfg, rt, rt.init_state(), StubDS(),
                                    StubDS(), telemetry=None)
    assert summary is not None
    assert tracing.current() is ring
    names = {s["name"] for s in _since(before)}
    assert {"runtime_init", "init_state", "data_fetch", "round_dispatch",
            "round_stage", "round_launch", "validation"} <= names, names


def test_round_record_excludes_emission_from_phases(tmp_path):
    """The telemetry_emit span must sit OUTSIDE the recorded
    host/dispatch/device phases: the round record's phase sum never
    includes the JSONL flush that follows it."""
    path = run_driver(tmp_path)
    events = read_events(path)
    spans = [s for e in events if e["event"] == "span"
             for s in e["spans"]]
    emits = [s for s in spans if s["name"] == "telemetry_emit"]
    waits = [s for s in spans if s["name"] == "device_wait"]
    assert emits and waits
    # emission starts only after the device wait of the same round ended
    assert emits[0]["ts"] >= waits[0]["ts"] + waits[0]["dur_s"] - 1e-6


# ----------------------------------------------------------- teleview views


def test_teleview_timeline_perfetto_structure(tmp_path):
    path = run_driver(tmp_path / "run")
    mod = load_script("teleview")
    out = str(tmp_path / "trace.json")
    assert mod.main(["timeline", path, "-o", out]) == 0
    with open(out) as f:
        trace = json.load(f)          # valid JSON
    evs = trace["traceEvents"]
    assert evs, "empty trace"
    # complete ("X") / counter ("C") / metadata ("M") events only — no
    # B/E pairs to mismatch
    assert {e["ph"] for e in evs} <= {"X", "C", "M"}
    assert any(e["ph"] == "X" for e in evs)
    assert any(e["ph"] == "C" and e["name"] == "MFU" for e in evs)
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts), "timestamps not monotonic"
    assert all(t >= 0 for t in ts)
    for e in evs:
        if e["ph"] == "X":
            assert e["dur"] >= 0
            assert isinstance(e["name"], str) and "tid" in e


def test_teleview_summarize_has_utilization_line(tmp_path, capsys):
    path = run_driver(tmp_path)
    mod = load_script("teleview")
    assert mod.main(["summarize", path]) == 0
    out = capsys.readouterr().out
    assert "utilization" in out and "mfu" in out


def _stream_with_util(tmp_path, name, mfu, wait):
    d = tmp_path / name
    d.mkdir()
    lines = [
        {"event": "manifest", "t": 0.0, "seq": 0, "schema": SCHEMA_VERSION,
         "run_type": "t", "jax_version": "x", "backend": "cpu",
         "device_kind": "cpu", "device_count": 1, "mesh_shape": [],
         "mesh_axes": [], "grad_size": 1, "sketch": None, "config": {}},
        {"event": "utilization", "t": 1.0, "seq": 1, "round": 1,
         "rounds": 1, "wall_s": 1.0, "device_kind": "cpu",
         "peak_flops": 1e12, "flops_per_round": 1e9,
         "flops_source": "analytic", "achieved_flops": 1e9, "mfu": mfu,
         "input_wait_frac": wait, "dispatch_frac": 0.1,
         "device_wait_frac": 0.1, "straggler_spread": None},
    ]
    p = d / TELEMETRY_BASENAME
    p.write_text("\n".join(json.dumps(l) for l in lines) + "\n")
    return str(p)


def test_teleview_diff_flags_mfu_and_starvation(tmp_path, capsys):
    mod = load_script("teleview")
    base = _stream_with_util(tmp_path, "base", mfu=0.50, wait=0.10)
    slow = _stream_with_util(tmp_path, "slow", mfu=0.20, wait=0.10)
    starved = _stream_with_util(tmp_path, "starved", mfu=0.50, wait=0.40)
    same = _stream_with_util(tmp_path, "same", mfu=0.49, wait=0.12)
    assert mod.main(["diff", base, slow]) == 1
    assert "mfu" in capsys.readouterr().out
    assert mod.main(["diff", base, starved]) == 1
    assert "input_wait_frac" in capsys.readouterr().out
    # within thresholds: clean
    assert mod.main(["diff", base, same]) == 0


def test_bench_phase_split_and_utilization_event(tmp_path):
    """bench_common's phase split + the bench-side utilization event:
    one event per timed stage, schema-valid, MFU from the given FLOPs."""
    import bench_common

    rt = make_runtime()
    batch, mask, ids = make_batch()
    dt, metrics, phases = bench_common.timed_rounds(
        rt, (ids, batch, mask, 0.05), warmup=1, rounds=2, desc="t")
    tel = RunTelemetry(str(tmp_path), "bench", cfg=None)
    fields = emit_from_totals(
        tel, rnd=2, rounds=2, wall_s=dt, host_s=phases["host_s"],
        dispatch_s=phases["dispatch_s"], device_s=phases["device_wait_s"],
        flops_per_round=1e9, flops_source="cost_analysis",
        device_kind="TPU v5e")
    tel.write_summary(aborted=False, n_rounds=2)
    tel.close()
    assert validate_file(tel.path) == []
    assert fields["mfu"] == pytest.approx(2e9 / (dt * 197e12), rel=1e-2)
