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
from tests.test_telemetry import (StubDS, make_batch, make_runtime,
                                  read_events)


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


def test_spans_enter_the_annotation_factory_beside_their_clock():
    """``SpanTracer(annotate=...)``: every span enters the factory's
    context manager (the drivers pass jax.profiler.TraceAnnotation under
    --profile_rounds), nested in span order, closed on an exception too;
    without a factory nothing is entered."""
    log = []

    class Recording:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))

        def __exit__(self, *exc):
            log.append(("exit", self.name, exc[0]))

    tr = SpanTracer(annotate=lambda name: Recording("fed:" + name))
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
    plain = SpanTracer()
    with plain.span("quiet"):
        pass
    assert len(log) == 6 and len(plain.drain()) == 1


def test_null_tracer_is_free_and_default():
    """With no tracer installed (the --no_telemetry state), span() must
    return one shared no-op object — no allocation, no clock reads —
    and install/uninstall must restore that state."""
    assert isinstance(tracing.current(), tracing.NullTracer)
    assert tracing.span("x") is tracing.span("y") is tracing.NULL_SPAN
    tr = tracing.install()
    try:
        assert tracing.current() is tr
        with tracing.span("live"):
            pass
        assert [s["name"] for s in tr.drain()] == ["live"]
    finally:
        tracing.uninstall()
    assert isinstance(tracing.current(), tracing.NullTracer)
    assert tracing.current().drain() == []


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
    # the tracer was uninstalled on the way out
    assert isinstance(tracing.current(), tracing.NullTracer)


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
        batch = store.round_batch(np.array([0, 1]), None)
        assert batch["x"].shape == (2, 2)
    finally:
        tracing.uninstall()
    names = [s["name"] for s in tr.drain()]
    assert names == ["host_gather", "data_gather"]


def test_no_telemetry_leaves_null_tracer(capsys):
    """--no_telemetry: train() must never install a recording tracer —
    span sites stay the shared no-op (the zero-overhead contract)."""
    from commefficient_tpu import cv_train

    rt = make_runtime(dataset_name="SYNTH", telemetry=False)
    cfg = rt.cfg.replace(num_epochs=1.0, pivot_epoch=0.5)
    state, summary = cv_train.train(cfg, rt, rt.init_state(), StubDS(),
                                    StubDS(), telemetry=None)
    assert summary is not None
    assert isinstance(tracing.current(), tracing.NullTracer)
    assert tracing.span("anything") is tracing.NULL_SPAN


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
