"""Async buffered aggregation (core/async_agg.py): staleness-discount
math against numpy references, merge linearity (out-of-order == in-order),
the K=1/M=1 sync-equivalence bit-identity contract, the unsound-mode
fail-fast guard, buffer checkpoint/resume semantics (loud restart, never
a silent double-count; cross-vintage explanatory errors), the schema-v4
``async_round`` event + health rules, and the teleview staleness gates."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.checkpoint import CheckpointManager, load_state, \
    save_state
from commefficient_tpu.config import FedConfig
from commefficient_tpu.core import (AsyncAggregator, FedRuntime,
                                    staleness_weight, validate_async_combo)
from commefficient_tpu.core.async_agg import (commit_loss,
                                              reconcile_resumed_state)
from commefficient_tpu.data.fed_sampler import Round
from commefficient_tpu.data.scenarios import CohortFate
from tests.test_parallel import make_batch, quad_loss

W, B = 4, 4


def make_cfg(**kw):
    base = dict(mode="sketch", error_type="virtual", k=5, num_rows=3,
                num_cols=32, num_blocks=2, sketch_impl="hash",
                local_momentum=0.0, virtual_momentum=0.9,
                weight_decay=0.0, num_workers=W, local_batch_size=B,
                track_bytes=True, num_clients=16)
    base.update(kw)
    return FedConfig(**base)


def make_params(seed=0):
    return {"w": jnp.asarray(np.random.RandomState(seed).randn(6, 3),
                             jnp.float32)}


def make_round(seed):
    batch, mask, ids = make_batch(seed, W=W, B=B)
    return Round(np.asarray(ids, np.int64),
                 np.zeros((W, B), np.int64), np.asarray(mask)), batch


class FixedScenario:
    """Prescribed per-cohort fates, keyed by cohort index (test stub)."""

    def __init__(self, latencies=(), dropped=(), masks=None):
        self.latencies = dict(latencies)
        self.dropped = set(dropped)
        self.masks = masks or {}

    def fate(self, cohort_idx, mask, client_ids=None):
        return CohortFate(float(self.latencies.get(cohort_idx, 0.0)),
                          cohort_idx in self.dropped,
                          self.masks.get(cohort_idx, mask))


# ------------------------------------------------------------ staleness math


def test_staleness_weight_numpy_reference():
    for s in (0, 1, 2, 5, 17):
        assert staleness_weight("none", s) == 1.0
        for alpha in (0.25, 0.5, 2.0):
            np.testing.assert_allclose(
                staleness_weight("poly", s, alpha),
                (1.0 + s) ** (-alpha), rtol=1e-12)
            np.testing.assert_allclose(
                staleness_weight("exp", s, alpha),
                math.exp(-alpha * s), rtol=1e-12)


def test_staleness_weight_one_at_zero_and_monotone():
    """Weight EXACTLY 1.0 at s=0 (the sync-equivalence contract) and
    strictly decreasing in s for the discounting rules."""
    for rule in ("none", "poly", "exp"):
        assert staleness_weight(rule, 0) == 1.0
    for rule in ("poly", "exp"):
        ws = [staleness_weight(rule, s, 0.5) for s in range(8)]
        assert all(a > b for a, b in zip(ws, ws[1:]))
    with pytest.raises(ValueError):
        staleness_weight("linear", 1)
    with pytest.raises(ValueError):
        staleness_weight("poly", -1)


# -------------------------------------------------------------- merge algebra


def test_out_of_order_merge_equals_in_order_numpy():
    """Sketch linearity at the merge level: the buffer arithmetic
    (buffer + w*S, exactly what FedRuntime._merge_step computes) is
    order-independent for exactly-representable values — merging the
    same cohort sums in any arrival order commits the same aggregate."""
    rng = np.random.RandomState(0)
    sums = [rng.randint(-8, 8, (3, 32)).astype(np.float32)
            for _ in range(4)]
    weights = [1.0, 0.5, 0.25, 1.0]   # exact binary fractions

    def merge_all(order):
        buf = np.zeros((3, 32), np.float32)
        for i in order:
            buf = buf + np.float32(weights[i]) * sums[i]
        return buf

    ref = merge_all([0, 1, 2, 3])
    for order in ([3, 2, 1, 0], [1, 3, 0, 2], [2, 0, 3, 1]):
        np.testing.assert_array_equal(ref, merge_all(order))


def test_out_of_order_merge_matches_runtime():
    """End-to-end: the SAME three cohorts landing in different arrival
    orders (no commit between — M=3 — so staleness is 0 either way)
    commit the same weights up to float summation order."""
    params = make_params()

    def run(latencies):
        cfg = make_cfg(async_agg=True, max_inflight=3, buffer_goal=3,
                       staleness_discount="none")
        rt = FedRuntime(cfg, params, quad_loss, num_clients=16)
        agg = AsyncAggregator(rt, scenario=FixedScenario(latencies))
        state = rt.init_state()
        all_commits = []
        for g in range(1, 4):
            rnd, batch = make_round(g)
            state, _, commits = agg.step(state, rnd, g, batch, 0.1)
            all_commits.extend(commits)
        state, commits = agg.flush(state, 0.1)
        all_commits.extend(commits)
        assert len(all_commits) == 1 and all_commits[0]["n_cohorts"] == 3
        return np.asarray(rt.flat_weights(state)), all_commits[0]

    w_inorder, c_a = run({})                       # arrival 1, 2, 3
    w_reorder, c_b = run({1: 5.0, 2: 3.0})         # arrival 3, 2, 1
    assert c_a["cohorts"] == [1, 2, 3]
    assert c_b["cohorts"] == [3, 2, 1]
    np.testing.assert_allclose(w_inorder, w_reorder, rtol=2e-5, atol=1e-7)


# ------------------------------------------------------------ sync equivalence


@pytest.mark.parametrize("mode,extra", [
    ("sketch", {}),
    ("sketch", {"sketch_fused_encode": "off"}),
    ("sketch", {"sketch_dtype": "bfloat16"}),
    ("uncompressed", {"error_type": "none"}),
    ("true_topk", {"error_type": "virtual"}),
], ids=["sketch", "sketch-unfused", "sketch-bf16", "uncompressed",
        "true_topk"])
def test_sync_equivalence_bit_identical(mode, extra):
    """K=1, M=1, no scenario: every cohort lands and commits in its own
    tick with staleness 0 — losses and final weights must be BITWISE
    equal to the inline fused round (all discount rules give weight
    exactly 1.0 at s=0; the first-merge path adds no arithmetic)."""
    params = make_params()
    cfg = make_cfg(mode=mode, **extra)
    rt_sync = FedRuntime(cfg, params, quad_loss, num_clients=16)
    st_sync = rt_sync.init_state()
    sync_losses = []
    for g in range(1, 6):
        rnd, batch = make_round(g)
        st_sync, m = rt_sync.round(st_sync, rnd.client_ids, batch,
                                   rnd.mask, 0.1)
        sync_losses.append(np.asarray(m["results"][0]))

    rt_a = FedRuntime(cfg.replace(async_agg=True, max_inflight=1,
                                  buffer_goal=1),
                      params, quad_loss, num_clients=16)
    st_a = rt_a.init_state()
    agg = AsyncAggregator(rt_a)
    async_losses = []
    for g in range(1, 6):
        rnd, batch = make_round(g)
        st_a, m, commits = agg.step(st_a, rnd, g, batch, 0.1)
        async_losses.append(np.asarray(m["results"][0]))
        assert len(commits) == 1
        assert commits[0]["staleness_max"] == 0
        assert commits[0]["discount_min"] == 1.0
    st_a, leftover = agg.flush(st_a, 0.1)
    assert not leftover
    assert (np.stack(sync_losses) == np.stack(async_losses)).all()
    np.testing.assert_array_equal(
        np.asarray(rt_sync.flat_weights(st_sync)),
        np.asarray(rt_a.flat_weights(st_a)))


# ------------------------------------------------------- discounting dynamics


def test_staleness_discount_attenuates_stale_cohorts():
    """A cohort landing 2 commits stale under exp(-50*s) contributes
    ~nothing: its commit's update norm collapses vs discount none, and
    the denominator stays the RAW datum count (the discount must not
    cancel between numerator and denominator). Momentum-free
    uncompressed mode isolates the commit to THIS cohort's aggregate —
    with EF/momentum the server state legitimately carries residual
    mass across commits and the norm would not vanish."""
    params = make_params()

    def run(discount, alpha=50.0):
        cfg = make_cfg(mode="uncompressed", error_type="none",
                       virtual_momentum=0.0, async_agg=True,
                       max_inflight=2, buffer_goal=1,
                       staleness_discount=discount,
                       staleness_alpha=alpha)
        rt = FedRuntime(cfg, params, quad_loss, num_clients=16)
        # cohort 1 is slow (arrival tick 4); cohorts 2 and 3 land and
        # commit immediately, so cohort 1 merges 2 commits stale
        agg = AsyncAggregator(rt, scenario=FixedScenario({1: 3.0}))
        state = rt.init_state()
        all_commits = []
        for g in range(1, 4):
            rnd, batch = make_round(g)
            state, _, cms = agg.step(state, rnd, g, batch, 0.1)
            all_commits.extend(cms)
        state, cms = agg.flush(state, 0.1)
        all_commits.extend(cms)
        stale = [c for c in all_commits if c["staleness_max"] > 0]
        assert len(stale) == 1 and stale[0]["cohorts"] == [1]
        return float(np.asarray(stale[0]["update_norm"])), stale[0]

    norm_plain, rec_plain = run("none")
    norm_exp, rec_exp = run("exp")
    assert rec_plain["discount_min"] == 1.0
    assert rec_exp["discount_min"] == pytest.approx(math.exp(-100.0))
    assert norm_exp < norm_plain * 1e-3, (norm_exp, norm_plain)


def test_inflight_pool_bound_and_dropout():
    """The pool never exceeds K (dispatching past it forces the
    earliest arrival to land first), and a dropped cohort computes
    nothing: metrics is None, nothing merges, weights stay put."""
    params = make_params()
    cfg = make_cfg(async_agg=True, max_inflight=2, buffer_goal=4)
    rt = FedRuntime(cfg, params, quad_loss, num_clients=16)
    agg = AsyncAggregator(rt,
                          scenario=FixedScenario({g: 100.0
                                                  for g in range(1, 9)}))
    state = rt.init_state()
    for g in range(1, 7):
        rnd, batch = make_round(g)
        state, m, _ = agg.step(state, rnd, g, batch, 0.1)
        assert m is not None
        assert agg.inflight <= 2
    assert agg.merged == 4  # 6 dispatched, pool of 2 forced 4 landings

    cfg2 = make_cfg(async_agg=True, max_inflight=1, buffer_goal=1)
    rt2 = FedRuntime(cfg2, params, quad_loss, num_clients=16)
    agg2 = AsyncAggregator(rt2, scenario=FixedScenario(dropped={1, 2}))
    st = rt2.init_state()
    w0 = np.asarray(rt2.flat_weights(st))
    for g in (1, 2):
        rnd, batch = make_round(g)
        st, m, commits = agg2.step(st, rnd, g, batch, 0.1)
        assert m is None and commits == []
    assert agg2.dropped == 2 and agg2.dispatched == 0
    np.testing.assert_array_equal(w0, np.asarray(rt2.flat_weights(st)))


def test_dropped_cohort_never_evicts_pool_slot():
    """A dropped cohort needs no pool slot, so it must not force the
    earliest in-flight cohort to land early (which would skew the
    measured staleness/merge order) — the fate check runs BEFORE the
    pool-full wait."""
    params = make_params()
    cfg = make_cfg(async_agg=True, max_inflight=1, buffer_goal=8)
    rt = FedRuntime(cfg, params, quad_loss, num_clients=16)
    # cohort 1 is slow (arrival tick 11); cohort 2 is dropped; cohort 3
    # genuinely needs the slot and forces cohort 1 to land
    agg = AsyncAggregator(rt, scenario=FixedScenario({1: 10.0, 3: 10.0},
                                                     dropped={2}))
    state = rt.init_state()
    rnd, batch = make_round(1)
    state, _, _ = agg.step(state, rnd, 1, batch, 0.1)
    assert agg.inflight == 1
    rnd, batch = make_round(2)
    state, m, _ = agg.step(state, rnd, 2, batch, 0.1)
    assert m is None
    assert agg.inflight == 1 and agg.merged == 0  # slot NOT evicted
    rnd, batch = make_round(3)
    state, m, _ = agg.step(state, rnd, 3, batch, 0.1)
    assert m is not None
    assert agg.merged == 1      # now cohort 1 had to land...
    assert agg.inflight == 1    # ...making room for cohort 3


def test_signals_loudly_off_under_async(capsys):
    """--signals under --async_agg is not silently ignored: the runtime
    compiles the signal sites out AND says so on stderr (the async_round
    EF norms are the async health channel)."""
    cfg = make_cfg(async_agg=True, signals=True, telemetry=True)
    rt = FedRuntime(cfg, make_params(), quad_loss, num_clients=16)
    assert rt._signals is False
    assert "disables the per-round `signals`" in capsys.readouterr().err
    # sync runtime from the same flags keeps them on
    rt2 = FedRuntime(make_cfg(signals=True), make_params(), quad_loss,
                     num_clients=16)
    assert rt2._signals is True


def test_flush_commits_partial_buffer():
    params = make_params()
    cfg = make_cfg(async_agg=True, max_inflight=4, buffer_goal=3)
    rt = FedRuntime(cfg, params, quad_loss, num_clients=16)
    agg = AsyncAggregator(rt)
    state = rt.init_state()
    for g in (1, 2):
        rnd, batch = make_round(g)
        state, _, commits = agg.step(state, rnd, g, batch, 0.1)
        assert not commits  # below the goal
    state, commits = agg.flush(state, 0.1)
    assert len(commits) == 1
    assert commits[0]["partial"] is True
    assert commits[0]["n_cohorts"] == 2
    assert commit_loss(commits[0]) is not None
    # the buffer is empty after the flush — nothing left to double-count
    assert float(np.asarray(state.async_buffer_n)) == 0.0
    assert agg.pending == 0 and agg.inflight == 0


# ------------------------------------------------------------ fail-fast guard


def test_unsound_modes_fail_fast():
    for kw in (dict(mode="local_topk", error_type="local",
                    local_momentum=0.9),
               dict(mode="uncompressed", error_type="none",
                    local_momentum=0.9),
               dict(mode="true_topk", error_type="virtual",
                    do_topk_down=True)):
        with pytest.raises(ValueError, match="buffered merge is unsound"):
            validate_async_combo(make_cfg(async_agg=True, **kw))
    # sound combinations pass
    validate_async_combo(make_cfg(async_agg=True))
    validate_async_combo(make_cfg(async_agg=True, mode="local_topk",
                                  error_type="none"))
    # and the guard runs at runtime construction too
    with pytest.raises(ValueError, match="buffered merge is unsound"):
        FedRuntime(make_cfg(async_agg=True, mode="local_topk",
                            error_type="local", local_momentum=0.9),
                   make_params(), quad_loss, num_clients=16)


# -------------------------------------------------------- checkpoint / resume


def _mid_buffer_state(rt, agg, n_rounds=2):
    state = rt.init_state()
    for g in range(1, n_rounds + 1):
        rnd, batch = make_round(g)
        state, _, _ = agg.step(state, rnd, g, batch, 0.1)
    return state


def test_buffer_roundtrips_through_checkpoint(tmp_path):
    """A mid-buffer FedState (e.g. a flight-recorder postmortem) saves
    and loads the buffer losslessly — the state is never silently
    truncated on disk."""
    params = make_params()
    cfg = make_cfg(async_agg=True, max_inflight=4, buffer_goal=4)
    rt = FedRuntime(cfg, params, quad_loss, num_clients=16)
    state = _mid_buffer_state(rt, AsyncAggregator(rt))
    assert float(np.asarray(state.async_buffer_n)) > 0
    path = str(tmp_path / "ck")
    save_state(path, state)
    loaded = load_state(path)
    np.testing.assert_array_equal(np.asarray(state.async_buffer),
                                  np.asarray(loaded.async_buffer))
    np.testing.assert_array_equal(np.asarray(state.async_buffer_n),
                                  np.asarray(loaded.async_buffer_n))


def test_resume_mid_buffer_loudly_restarts():
    """reconcile_resumed_state: a restored NON-EMPTY buffer is zeroed
    with a message naming the double-count hazard — the epoch replays
    from its boundary, so its cohorts will be recomputed."""
    params = make_params()
    cfg = make_cfg(async_agg=True, max_inflight=4, buffer_goal=4)
    rt = FedRuntime(cfg, params, quad_loss, num_clients=16)
    state = _mid_buffer_state(rt, AsyncAggregator(rt))
    state2, msgs = reconcile_resumed_state(state, rt)
    assert len(msgs) == 1 and "double-count" in msgs[0]
    assert float(np.asarray(state2.async_buffer_n)) == 0.0
    assert not np.asarray(state2.async_buffer).any()
    # an EMPTY restored buffer reconciles silently
    state3, msgs3 = reconcile_resumed_state(state2, rt)
    assert msgs3 == []


def test_resume_cross_vintage_explanatory_error(tmp_path):
    """Pre-async checkpoint into an --async_agg run: the meta guard
    raises the explanatory error BEFORE any state is materialized
    (the PR-1 sketch_gen pattern); --resume_unverified opts into a
    fresh, empty buffer via reconcile_resumed_state."""
    sync_cfg = make_cfg()
    rt_sync = FedRuntime(sync_cfg, make_params(), quad_loss,
                         num_clients=16)
    mgr = CheckpointManager(str(tmp_path / "ck"))
    mgr.default_meta = {"sketch_gen": None}  # pre-async vintage: no marker
    mgr.save(rt_sync.init_state(), epoch=1)

    with pytest.raises(ValueError) as e:
        mgr.restore_latest(expect_async_gen="v1-poly-a0.5-M2-K4")
    assert "predates async buffered aggregation" in str(e.value)
    assert "--resume_unverified" in str(e.value)

    # the opt-in loads; the async runtime then starts with a fresh buffer
    restored, _ = mgr.restore_latest(expect_async_gen="v1-poly-a0.5-M2-K4",
                                     async_mismatch_ok=True)
    assert restored.async_buffer is None
    rt_async = FedRuntime(make_cfg(async_agg=True), make_params(),
                          quad_loss, num_clients=16)
    restored, msgs = reconcile_resumed_state(restored, rt_async)
    assert restored.async_buffer is not None
    assert float(np.asarray(restored.async_buffer_n)) == 0.0
    assert any("EMPTY" in m for m in msgs)

    # changed async parameters only warn (commits are atomic)
    mgr.default_meta = {"async_gen": "v1-none-a0.5-M1-K1"}
    mgr.save(rt_sync.init_state(), epoch=2)
    restored, _ = mgr.restore_latest(expect_async_gen="v1-exp-a2.0-M4-K8")
    assert restored is not None

    # a sync run resuming an async checkpoint drops the buffer fields
    rt_a = FedRuntime(make_cfg(async_agg=True, max_inflight=4,
                               buffer_goal=4), make_params(), quad_loss,
                      num_clients=16)
    st = _mid_buffer_state(rt_a, AsyncAggregator(rt_a))
    st2, msgs2 = reconcile_resumed_state(st, rt_sync)
    assert st2.async_buffer is None and st2.async_buffer_n is None
    assert any("resumed synchronously" in m for m in msgs2)


# ------------------------------------------------------- telemetry integration


def _fake_commit_rec(rnd=1, error_norm=1.0, staleness=0.0):
    return {"round": rnd, "n_cohorts": 2, "cohorts": [rnd, rnd + 1],
            "staleness_mean": staleness, "staleness_max": staleness,
            "discount_mean": 1.0, "discount_min": 1.0, "partial": False,
            "buffer_n": np.float32(8.0),
            "update_norm": np.float32(0.5),
            "error_norm": np.float32(error_norm),
            "velocity_norm": np.float32(0.25),
            "loss_refs": [(np.full((W,), 2.0, np.float32),
                           np.full((W,), float(B), np.float32))]}


def test_async_round_event_schema_roundtrip(tmp_path):
    from commefficient_tpu.telemetry import RunTelemetry
    from commefficient_tpu.telemetry.schema import validate_file
    tel = RunTelemetry(str(tmp_path), "test", cfg=make_cfg())
    tel.async_round_event(rec=_fake_commit_rec(), lr=0.1, loss=2.0,
                          with_device=True)
    # off the record cadence: device fields stay null, never fake zeros
    tel.async_round_event(rec=_fake_commit_rec(rnd=2), lr=0.1, loss=None,
                          with_device=False)
    tel.write_summary(aborted=False, n_rounds=2)
    tel.close()
    assert validate_file(tel.path) == []
    evs = [json.loads(l) for l in open(tel.path)]
    ars = [e for e in evs if e["event"] == "async_round"]
    assert len(ars) == 2
    assert ars[0]["error_norm"] == pytest.approx(1.0)
    assert ars[1]["error_norm"] is None and ars[1]["buffer_n"] is None


def test_commit_loss_weighted_mean_and_nonfinite():
    rec = _fake_commit_rec()
    assert commit_loss(rec) == pytest.approx(2.0)
    rec["loss_refs"] = [(np.full((W,), np.nan, np.float32),
                         np.full((W,), 1.0, np.float32))]
    assert commit_loss(rec) is None
    assert commit_loss({"loss_refs": []}) is None


def test_async_ef_blowup_rule_fires(tmp_path):
    """The staleness-EF-divergence monitor rule: a flat error_norm
    history followed by a blowup on the async_round stream fires
    async_ef_blowup (critical) exactly once."""
    from commefficient_tpu.telemetry import AnomalyMonitor, RunTelemetry
    tel = RunTelemetry(str(tmp_path), "test", cfg=make_cfg())
    mon = AnomalyMonitor(tel, action="log", window=16, min_points=8)
    tel.set_monitor(mon)
    rng = np.random.RandomState(0)
    for r in range(1, 20):
        blow = 500.0 if r == 16 else 1.0 + 0.01 * rng.rand()
        tel.async_round_event(rec=_fake_commit_rec(rnd=r, error_norm=blow),
                              lr=0.1, loss=2.0, with_device=True)
    tel.close()
    fired = [a for a in mon.alerts if a["rule"] == "async_ef_blowup"]
    assert len(fired) == 1
    assert fired[0]["severity"] == "critical"
    assert fired[0]["metric"] == "async_round.error_norm"


# ------------------------------------------------------------ teleview gates


def _load_teleview():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "teleview", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "scripts", "teleview.py"))
    tv = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tv)
    return tv


def test_teleview_async_keys_pinned_against_schema():
    """teleview must run jax-free, so its async_round field names are
    literals — pin them against the canonical schema vocabulary."""
    from commefficient_tpu.telemetry.schema import EVENT_FIELDS
    tv = _load_teleview()
    assert set(tv.ASYNC_ROUND_KEYS) <= set(EVENT_FIELDS["async_round"])


def _write_stream(path, staleness_mean, error_norm=1.0):
    events = [
        {"event": "manifest", "t": 0.0, "seq": 0, "schema": 4,
         "run_type": "cv_train", "jax_version": "0", "backend": "cpu",
         "device_kind": "cpu", "device_count": 1, "mesh_shape": [],
         "mesh_axes": [], "grad_size": 10, "sketch": None, "config": {}},
        {"event": "async_round", "t": 1.0, "seq": 1, "round": 1,
         "n_cohorts": 2, "cohorts": [1, 2],
         "staleness_mean": staleness_mean,
         "staleness_max": staleness_mean * 2, "discount_mean": 0.9,
         "discount_min": 0.8, "partial": False, "buffer_n": 8.0,
         "loss": 2.0, "update_norm": 0.5, "error_norm": error_norm,
         "velocity_norm": 0.2, "lr": 0.1},
        {"event": "summary", "t": 2.0, "seq": 2, "run_type": "cv_train",
         "aborted": False, "n_rounds": 1, "total_download_mib": None,
         "total_upload_mib": None, "wall_time_s": 1.0,
         "event_counts": {}, "final": None},
    ]
    with open(path, "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")
    return str(path)


def test_teleview_staleness_rise_gate_and_summarize(tmp_path, capsys):
    tv = _load_teleview()
    a = _write_stream(tmp_path / "a.jsonl", staleness_mean=0.5)
    b = _write_stream(tmp_path / "b.jsonl", staleness_mean=3.0)
    assert tv.main(["diff", a, a]) == 0
    assert tv.main(["diff", a, b]) == 1
    out = capsys.readouterr().out
    assert "staleness_mean" in out
    # the summarize staleness line
    tv.main(["summarize", a])
    out = capsys.readouterr().out
    assert "-- async: 1 commits" in out
    # the EF-divergence ratio gate on the async stream
    c = _write_stream(tmp_path / "c.jsonl", staleness_mean=0.5,
                      error_norm=50.0)
    assert tv.main(["diff", a, c]) == 1
    assert "error_norm" in capsys.readouterr().out


# --------------------------------------------------------- driver integration


def test_driver_end_to_end_async(tmp_path, monkeypatch):
    """One cv_train.train epoch over synthetic CIFAR with async
    aggregation + a straggler scenario: schema-valid stream with
    async_round events carrying measured staleness, ledger staleness
    tracked in client_stats, finite summary, empty buffer at the end."""
    from commefficient_tpu import cv_train, models
    from commefficient_tpu.data import FedCIFAR10, transforms_for
    from commefficient_tpu.losses import make_cv_loss
    from commefficient_tpu.telemetry import RunTelemetry
    from commefficient_tpu.telemetry.schema import validate_file

    ds = FedCIFAR10(str(tmp_path / "d"), synthetic=True,
                    synthetic_per_class=8,
                    transform=transforms_for("CIFAR10", True, seed=0))
    cfg = FedConfig(mode="sketch", error_type="virtual", k=10, num_rows=2,
                    num_cols=64, num_blocks=2, sketch_impl="hash",
                    local_momentum=0.0, virtual_momentum=0.9,
                    num_workers=4, local_batch_size=4,
                    num_clients=ds.num_clients, num_epochs=1.0,
                    track_bytes=True, compute_dtype="float32",
                    telemetry=True, telemetry_every=1,
                    async_agg=True, max_inflight=3, buffer_goal=2,
                    scenario="stragglers", scenario_latency=1.0,
                    scenario_straggler_frac=0.25,
                    scenario_straggler_mult=5.0, scenario_dropout=0.1)
    model = models.ResNet9(num_classes=10,
                           channels={"prep": 2, "layer1": 2,
                                     "layer2": 2, "layer3": 2})
    params = model.init(jax.random.PRNGKey(0), jnp.ones((1, 32, 32, 3)))
    rt = FedRuntime(cfg, params, make_cv_loss(model, "float32"),
                    num_clients=ds.num_clients)
    tel = RunTelemetry(str(tmp_path / "log"), "cv_train", cfg=rt.cfg)
    tel.instrument(rt)
    state, summary = cv_train.train(cfg, rt, rt.init_state(), ds, ds,
                                    telemetry=tel)
    tel.write_summary(aborted=False, n_rounds=1)
    tel.close()
    assert summary is not None and np.isfinite(summary["train_loss"])
    assert validate_file(tel.path) == []
    evs = [json.loads(l) for l in open(tel.path)]
    ars = [e for e in evs if e["event"] == "async_round"]
    assert ars, "no async_round events emitted"
    assert max(e["staleness_max"] for e in ars) > 0
    assert all(e["lr"] >= 0 for e in ars)
    cstats = [e for e in evs if e["event"] == "client_stats"]
    assert cstats and cstats[-1]["staleness_max"] is not None
    # the epoch-boundary flush left no open buffer behind
    assert float(np.asarray(state.async_buffer_n)) == 0.0
