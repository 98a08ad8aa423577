"""Online anomaly monitor + flight recorder (telemetry/health.py):
robust-z math, each rule firing exactly once with the right severity on
injected anomalies, the 200-round healthy-stream false-positive gate,
nonfinite-precursor semantics (null-after-numeric fires, always-null
stays silent), alert-event schema round-trips, action side effects, the
one-shot postmortem bundle, and the driver wiring (nan-abort emits a
final alert and the stream survives fsync'd)."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.checkpoint import load_state
from commefficient_tpu.core.state import FedState
from commefficient_tpu.telemetry import (AnomalyMonitor, FlightRecorder,
                                         RunTelemetry, robust_z,
                                         validate_event, validate_file)
from tests.test_telemetry import StubDS, make_runtime, read_events


def observe_rounds(mon, losses, start=1):
    fired = []
    for i, loss in enumerate(losses, start=start):
        fired += mon.observe("round", {"round": i, "loss": loss})
    return fired


# ------------------------------------------------------------- robust z


def test_robust_z_math():
    hist = [1.0, 1.1, 0.9, 1.05, 0.95, 1.0, 1.02, 0.98]
    z = robust_z(1.0, hist)
    assert abs(z["zscore"]) < 1.0
    assert z["median"] == pytest.approx(1.0, abs=0.02)
    spike = robust_z(10.0, hist)
    assert spike["zscore"] > 50
    # constant history: the MAD floor (2% of |median|) keeps z finite
    # and keeps a 1% wiggle from firing
    flat = robust_z(1.01, [1.0] * 20)
    assert abs(flat["zscore"]) < 1.0
    assert robust_z(2.0, [1.0] * 20)["zscore"] > 6


def test_robust_z_constant_zero_history_mad_floor():
    """The satellite regression: a metric whose rolling median is ZERO
    (staleness on a no-latency run, quarantine counts on a healthy
    fleet) has a zero relative MAD floor, and without an absolute
    epsilon the FIRST nonzero tick fired with an astronomical z. With
    the per-rule ``mad_floor_abs`` a single-unit tick stays far below
    the default threshold 6 while a multi-unit jump still breaches."""
    zeros = [0.0] * 20
    # the old behavior (no absolute floor): any tick is "infinitely"
    # surprising — this is the bug, kept visible as the default so
    # continuous metrics keep full sensitivity
    assert robust_z(1.0, zeros)["zscore"] > 1e6
    # the fix, applied by the monitor for count-like rules
    tick = robust_z(1.0, zeros, mad_floor_abs=0.5)
    assert abs(tick["zscore"]) < 2.0
    assert tick["mad"] == 0.5
    jump = robust_z(10.0, zeros, mad_floor_abs=0.5)
    assert jump["zscore"] > 6
    # the absolute floor composes with (never weakens) the relative one
    assert robust_z(2.0, [1.0] * 20,
                    mad_floor_abs=1e-9)["zscore"] > 6


def test_staleness_spike_quiet_on_first_tick_after_zero_history():
    """Monitor-level regression for the same satellite: a no-latency
    async run keeps staleness_max at 0; the first cohort that lands one
    commit late must NOT fire staleness_spike (it used to)."""
    mon = AnomalyMonitor(None, window=16, min_points=8)
    fired = []
    for i in range(1, 21):
        fired += mon.observe("async_round",
                             {"round": i, "staleness_max": 0.0,
                              "staleness_mean": 0.0, "error_norm": 1.0,
                              "loss": 2.0})
    fired += mon.observe("async_round",
                         {"round": 21, "staleness_max": 1.0,
                          "staleness_mean": 0.2, "error_norm": 1.0,
                          "loss": 2.0})
    assert fired == [], fired
    # a genuine staleness blowout still fires
    fired = mon.observe("async_round",
                        {"round": 22, "staleness_max": 25.0,
                         "staleness_mean": 9.0, "error_norm": 1.0,
                         "loss": 2.0})
    assert [f["rule"] for f in fired] == ["staleness_spike"]


def test_update_norm_outlier_rule():
    """PR-7 rule: the round's max per-client transmitted-update norm
    leaving the population envelope (the boosted-client signature)."""
    mon = AnomalyMonitor(None, window=16, min_points=8)
    rng = np.random.RandomState(3)
    fired = []
    for i in range(1, 21):
        q = {"tx_norm": {"max": 5.0 + 0.1 * rng.randn()},
             "loss": {"p5": 1.0, "p95": 1.2}}
        fired += mon.observe("client_stats", {"round": i, "quantiles": q})
    assert fired == []
    fired = mon.observe("client_stats", {
        "round": 21, "quantiles": {"tx_norm": {"max": 500.0},
                                   "loss": {"p5": 1.0, "p95": 1.2}}})
    assert [f["rule"] for f in fired] == ["update_norm_outlier"]
    assert fired[0]["metric"] == "client_stats.tx_norm_max"
    assert fired[0]["severity"] == "warn"


def test_quarantine_growth_rule_single_bench_quiet_jump_fires():
    """One benched client above an all-zero history is the system
    WORKING (absolute MAD floor keeps it quiet); a multi-client jump is
    the broken-fleet signature and fires."""
    mon = AnomalyMonitor(None, window=16, min_points=8)
    fired = []
    for i in range(1, 21):
        fired += mon.observe("defense", {"round": i, "quarantined": 0})
    fired += mon.observe("defense", {"round": 21, "quarantined": 1})
    assert fired == [], fired             # a single bench: quiet
    fired = mon.observe("defense", {"round": 22, "quarantined": 8})
    assert [f["rule"] for f in fired] == ["quarantine_growth"]


def test_new_rules_healthy_stream_false_positive_gate():
    """200 rounds of realistic healthy defense/client_stats streams must
    fire NEITHER new rule (mirrors the main healthy-stream gate)."""
    mon = AnomalyMonitor(None, window=32, min_points=8)
    rng = np.random.RandomState(11)
    for i in range(1, 201):
        fired = mon.observe("client_stats", {
            "round": i, "quantiles": {
                "tx_norm": {"max": 4.0 + 0.5 * abs(rng.randn())},
                "loss": {"p5": 1.5 + 0.05 * rng.randn(),
                         "p95": 2.5 + 0.05 * rng.randn()}}})
        # a healthy quarantine stream: count sits at 0 with the odd
        # transient bench that recovers
        q = 1 if i % 97 == 0 else 0
        fired += mon.observe("defense", {"round": i, "quarantined": q,
                                         "clip_frac": 0.0})
        assert fired == [], (i, fired)
    assert mon.n_observed == 400


# ------------------------------------------------------------ the rules


def test_loss_spike_fires_exactly_once_warn():
    mon = AnomalyMonitor(None, window=16, min_points=8)
    rng = np.random.RandomState(0)
    losses = list(2.0 + 0.05 * rng.randn(30)) + [40.0] + \
        list(2.0 + 0.05 * rng.randn(20))
    fired = observe_rounds(mon, losses)
    assert len(fired) == 1, fired
    assert fired[0]["rule"] == "loss_spike"
    assert fired[0]["severity"] == "warn"
    assert fired[0]["round"] == 31
    assert fired[0]["zscore"] > 6


def test_error_norm_blowup_fires_once_critical():
    """A sustained EF blowup (the round-5 subtract-EF class): the jump
    fires once; the plateau afterwards must NOT re-fire — the value
    enters the history and becomes the new normal, and the cooldown
    covers the transition."""
    mon = AnomalyMonitor(None, window=16, min_points=8)
    vals = [1.0 + 0.01 * (i % 5) for i in range(30)] + [1e6] * 30
    fired = []
    for i, v in enumerate(vals, start=1):
        fired += mon.observe("signals", {"round": i, "error_norm": v})
    assert [f["rule"] for f in fired] == ["error_norm_blowup"]
    assert fired[0]["severity"] == "critical"


def test_mfu_cliff_low_direction():
    mon = AnomalyMonitor(None, window=16, min_points=8)
    fired = []
    rng = np.random.RandomState(1)
    for i, m in enumerate(list(0.4 + 0.005 * rng.randn(20)) + [0.02],
                          start=1):
        fired += mon.observe("utilization",
                             {"round": i, "mfu": m,
                              "input_wait_frac": 0.05})
    assert [f["rule"] for f in fired] == ["mfu_cliff"]
    assert fired[0]["severity"] == "warn"
    assert fired[0]["zscore"] < -6


def test_client_loss_spread_rule():
    mon = AnomalyMonitor(None, window=16, min_points=8)
    fired = []
    rng = np.random.RandomState(2)
    spreads = list(1.0 + 0.02 * rng.randn(20)) + [50.0]
    for i, s in enumerate(spreads, start=1):
        q = {"loss": {"p5": 1.0, "p95": 1.0 + s}}
        fired += mon.observe("client_stats",
                             {"round": i, "quantiles": q})
    assert [f["rule"] for f in fired] == ["client_loss_spread"]
    assert fired[0]["metric"] == "client_stats.loss_spread"


def test_shared_metric_history_appends_once_per_event():
    """round.loss is watched by TWO rules (spike + nonfinite); one
    observed event must enter the shared history once, not per rule —
    double-appending would halve the effective rolling window."""
    mon = AnomalyMonitor(None, window=32, min_points=8)
    observe_rounds(mon, [2.0] * 10)
    assert len(mon._hist["round.loss"]) == 10


def test_tiny_alert_window_still_fires():
    """--alert_window below the default min_points must clamp
    min_points, not silently disarm every statistical rule (the deque
    could otherwise never hold enough history)."""
    mon = AnomalyMonitor(None, window=4)
    assert mon.min_points == 4
    fired = observe_rounds(mon, [2.0] * 6 + [50.0])
    assert [f["rule"] for f in fired] == ["loss_spike"]


def test_nonfinite_precursor_semantics():
    """null AFTER numeric history fires critical; a field that was
    always null (N/A for the mode) never fires."""
    mon = AnomalyMonitor(None, window=16, min_points=8)
    fired = observe_rounds(mon, [2.0] * 10 + [None])
    assert [f["rule"] for f in fired] == ["loss_nonfinite"]
    assert fired[0]["severity"] == "critical"
    assert mon.nonfinite_counts["round.loss"] == 1
    # always-null: e.g. sketch-mode topk_overlap without --signals_exact
    mon2 = AnomalyMonitor(None, window=16, min_points=8)
    for i in range(40):
        assert mon2.observe("signals",
                            {"round": i, "error_norm": 1.0,
                             "update_norm": None,
                             "topk_overlap": None}) == []


def test_healthy_stream_stays_silent_200_rounds():
    """The false-positive gate: 200 rounds of realistic noisy-but-
    healthy streams across every monitored kind must fire nothing."""
    mon = AnomalyMonitor(None, window=32, min_points=8)
    rng = np.random.RandomState(7)
    for i in range(1, 201):
        fired = mon.observe("round", {"round": i,
                                      "loss": 2.0 * np.exp(-i / 400)
                                      + 0.05 * rng.randn()})
        fired += mon.observe("signals", {
            "round": i, "grad_norm": 5.0 + 0.3 * rng.randn(),
            "error_norm": 3.0 + i / 100 + 0.1 * rng.randn(),
            "velocity_norm": 4.0 + 0.2 * rng.randn(),
            "update_norm": 1.0 + 0.05 * rng.randn(),
            "topk_overlap": min(1.0, 0.8 + 0.05 * rng.randn())})
        fired += mon.observe("utilization", {
            "round": i, "mfu": 0.42 + 0.01 * rng.randn(),
            "input_wait_frac": abs(0.05 + 0.01 * rng.randn())})
        fired += mon.observe("client_stats", {
            "round": i, "quantiles": {"loss": {
                "p5": 1.5 + 0.05 * rng.randn(),
                "p95": 2.5 + 0.05 * rng.randn()}}})
        assert fired == [], (i, fired)
    assert mon.n_observed == 800


# ------------------------------------------------ events, actions, bundle


def test_alert_events_written_and_schema_valid(tmp_path):
    tel = RunTelemetry(str(tmp_path), "test", cfg=None)
    mon = AnomalyMonitor(tel, window=16, min_points=8)
    tel.set_monitor(mon)
    assert mon.armed
    # feed THROUGH the stream (the driver wiring): monitored events
    # forwarded by event(), alert written back into the same stream
    for i, loss in enumerate([2.0] * 12 + [50.0], start=1):
        tel.event("round", round=i, epoch=1, lr=0.1, loss=loss, acc=0.5,
                  n_valid=4.0, moe=None, download_bytes=None,
                  upload_bytes=None, host_s=0.0, dispatch_s=0.0,
                  device_s=0.0)
    tel.write_summary(aborted=False, n_rounds=13)
    tel.close()
    assert validate_file(tel.path) == []
    events = read_events(tel.path)
    alerts = [e for e in events if e["event"] == "alert"]
    assert len(alerts) == 1 and alerts[0]["rule"] == "loss_spike"
    assert validate_event(alerts[0]) == []
    # the alert lands immediately after the round that fired it
    rounds = [e for e in events if e["event"] == "round"]
    assert alerts[0]["seq"] == rounds[-1]["seq"] + 1


def test_actions_warn_checkpoint_abort(capsys):
    warn = AnomalyMonitor(None, action="warn", window=16, min_points=8)
    observe_rounds(warn, [2.0] * 12 + [50.0])
    assert "ALERT [warn] loss_spike" in capsys.readouterr().err
    assert warn.pop_snapshot_request() is None
    assert not warn.abort_requested

    chk = AnomalyMonitor(None, action="checkpoint", window=16,
                         min_points=8)
    observe_rounds(chk, [2.0] * 12 + [50.0, 2.0] + [None])
    req = chk.pop_snapshot_request()
    assert req is not None and req["rule"] == "loss_spike"
    assert chk.pop_snapshot_request() is None   # one-shot
    assert not chk.abort_requested

    ab = AnomalyMonitor(None, action="abort", window=16, min_points=8)
    observe_rounds(ab, [2.0] * 12 + [50.0])
    assert ab.abort_requested


def _tiny_state():
    return FedState(ps_weights=jnp.arange(6, dtype=jnp.float32),
                    Vvelocity=jnp.zeros(6), Verror=jnp.zeros(6),
                    step=jnp.asarray(3, jnp.int32),
                    rng=jnp.zeros(2, jnp.uint32))


def test_flight_recorder_bundle(tmp_path):
    tel = RunTelemetry(str(tmp_path), "test", cfg=None)
    tel.event("round", round=1, epoch=1, lr=0.1, loss=2.0, acc=0.5,
              n_valid=4.0, download_bytes=None, upload_bytes=None,
              host_s=0.0, dispatch_s=0.0, device_s=0.0)
    rec = FlightRecorder(str(tmp_path), tel)
    out = rec.record(_tiny_state(), {"rule": "loss_spike", "round": 9})
    assert out == rec.path and rec.written
    for fn in ("state.npz", "state.meta.json", "events.jsonl",
               "alert.json"):
        assert os.path.exists(os.path.join(rec.path, fn)), fn
    # one-shot: a second alert must NOT overwrite the first bundle
    mtime = os.path.getmtime(os.path.join(rec.path, "state.npz"))
    assert rec.record(_tiny_state(), {"rule": "other"}) == out
    assert os.path.getmtime(
        os.path.join(rec.path, "state.npz")) == mtime
    # the bundle replays: state round-trips through the checkpoint
    # layer, events.jsonl holds the ring buffer, alert.json the context
    restored = load_state(os.path.join(rec.path, "state"))
    np.testing.assert_array_equal(np.asarray(restored.ps_weights),
                                  np.arange(6, dtype=np.float32))
    assert int(restored.step) == 3
    lines = open(os.path.join(rec.path, "events.jsonl")).read()
    assert '"event": "round"' in lines
    ctx = json.load(open(os.path.join(rec.path, "alert.json")))
    assert ctx["rule"] == "loss_spike"
    tel.close()


# --------------------------------------------------------- driver wiring


def test_driver_attaches_monitor_and_stream_valid(tmp_path):
    from commefficient_tpu import cv_train
    from commefficient_tpu.utils import TableLogger

    rt = make_runtime(dataset_name="SYNTH", telemetry_every=1,
                      alert_action="checkpoint")
    tel = RunTelemetry(str(tmp_path), "cv_train", cfg=rt.cfg)
    tel.instrument(rt)
    cfg = rt.cfg.replace(num_epochs=1.0, pivot_epoch=0.5)
    state, summary = cv_train.train(cfg, rt, rt.init_state(), StubDS(),
                                    StubDS(), loggers=(TableLogger(),),
                                    telemetry=tel)
    assert summary is not None
    assert tel._monitor is not None and tel._monitor.n_observed > 0
    tel.close()
    assert validate_file(tel.path) == []
    kinds = [e["event"] for e in read_events(tel.path)]
    assert "client_stats" in kinds
    # healthy 2-round smoke run: no alerts, no postmortem
    assert "alert" not in kinds
    assert not os.path.exists(os.path.join(str(tmp_path), "postmortem"))


def test_nan_abort_emits_final_alert_and_bundle(tmp_path):
    """The satellite contract: the divergence abort path writes a final
    critical alert BEFORE the nan_abort record, the flight recorder
    (armed via --alert_action checkpoint) captures the bundle, and the
    stream validates end to end (flushed+fsynced, never truncated)."""
    from commefficient_tpu import cv_train
    from commefficient_tpu.utils import TableLogger

    rt = make_runtime(dataset_name="SYNTH", telemetry_every=1,
                      alert_action="checkpoint")
    tel = RunTelemetry(str(tmp_path), "cv_train", cfg=rt.cfg)
    tel.instrument(rt)
    cfg = rt.cfg.replace(num_epochs=1.0, pivot_epoch=0.5, lr_scale=1e30)
    state, summary = cv_train.train(
        cfg, rt, rt.init_state(), StubDS(scale=1e25), StubDS(scale=1e25),
        loggers=(TableLogger(),), telemetry=tel)
    assert summary is None   # diverged
    tel.close()
    assert validate_file(tel.path) == []
    events = read_events(tel.path)
    kinds = [e["event"] for e in events]
    assert "nan_abort" in kinds
    alerts = [e for e in events if e["event"] == "alert"]
    assert any(a["rule"] == "nonfinite_abort"
               and a["severity"] == "critical" for a in alerts)
    abort_seq = next(e["seq"] for e in events
                     if e["event"] == "nan_abort")
    final = next(a for a in alerts if a["rule"] == "nonfinite_abort")
    assert final["seq"] < abort_seq
    assert events[-1]["event"] == "summary" and events[-1]["aborted"]
    # the flight recorder captured the poisoned run for replay
    bundle = os.path.join(str(tmp_path), "postmortem")
    assert os.path.exists(os.path.join(bundle, "state.npz"))
    assert os.path.exists(os.path.join(bundle, "alert.json"))
