"""Layer-wise compression attribution (telemetry/layer_signals.py +
ops/segments.py): group partition against a numpy reference on a small
pytree (conservation, range tiling, boundary and padding coordinates),
the in-round per-group signals across modes and topologies (null —
never fake-zero — contracts on the fused-encode and mesh paths), HLO
byte-identity with the groups off, the schema-v10 round-trip, the
group_starvation monitor rule, and the teleview layers/diff surface
(literal fallbacks pinned against the package)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.config import FedConfig
from commefficient_tpu.core import FedRuntime
from commefficient_tpu.telemetry import (LAYER_SIGNAL_KEYS, AnomalyMonitor,
                                         RunTelemetry,
                                         layer_signals_to_host,
                                         make_group_spec, signals_to_host,
                                         starved_groups, validate_event,
                                         validate_file)
from commefficient_tpu.telemetry.layer_signals import (STARVATION_MASS_SHARE,
                                                       STARVATION_WIN_SHARE,
                                                       STARVATION_WINDOW)

W, B, D_IN, D_OUT = 4, 4, 6, 3
D = D_IN * D_OUT + D_OUT            # w kernel + b bias


def loss_fn(params, batch, mask):
    pred = batch["x"] @ params["w"] + params["b"]
    m = mask.astype(jnp.float32)
    denom = jnp.maximum(m.sum(), 1.0)
    err = ((pred - batch["y"]) ** 2).sum(axis=1)
    loss = (err * m).sum() / denom
    return loss, (loss,)


def make_params(seed=0):
    return {"w": jnp.asarray(
        np.random.RandomState(seed).randn(D_IN, D_OUT), jnp.float32),
        "b": jnp.zeros((D_OUT,), jnp.float32)}


def make_runtime(**kw):
    cfg_kw = dict(mode="sketch", error_type="virtual", local_momentum=0.0,
                  virtual_momentum=0.9, weight_decay=0.0, num_workers=W,
                  local_batch_size=B, track_bytes=True, num_clients=8,
                  num_results_train=2, num_results_val=2,
                  k=5, num_rows=2, num_cols=32, exact_num_cols=True)
    cfg_kw.update(kw)
    return FedRuntime(FedConfig(**cfg_kw), make_params(), loss_fn,
                      num_clients=8)


def make_batch(seed=1):
    rng = np.random.RandomState(seed)
    batch = {"x": jnp.asarray(rng.randn(W, B, D_IN), jnp.float32),
             "y": jnp.asarray(rng.randn(W, B, D_OUT), jnp.float32)}
    return batch, jnp.ones((W, B), bool), jnp.arange(W, dtype=jnp.int32)


def fetch(metrics):
    return layer_signals_to_host(metrics["layer_signals"])


# --------------------------------------------------- partition vs numpy


def test_group_spec_tiles_ravel_order_exactly():
    """Ranges tile [0, d) with no gap/overlap, sizes sum to d, every
    boundary coordinate between adjacent leaf ranges lands in exactly
    one group, and the gid map agrees with a numpy re-derivation from
    the ravel layout."""
    params = make_params()
    spec = make_group_spec(params, "coarse")
    assert spec.d == D and sum(spec.sizes) == D
    covered = np.zeros(D, np.int32)
    for start, end, g in spec.ranges:
        assert 0 <= start < end <= D and 0 <= g < spec.n_groups
        covered[start:end] += 1
    assert (covered == 1).all()          # exactly-one-group tiling
    # ravel order is tree_leaves order: 'b' (3 coords) then 'w' (18)
    gid = spec.gid()
    names = [spec.names[g] for g in gid]
    assert names[:D_OUT] == ["b/norm-bias"] * D_OUT
    assert names[D_OUT:] == ["w"] * (D_IN * D_OUT)
    # the boundary pair straddles the b/w leaf edge: adjacent
    # coordinates, different (single) groups
    assert gid[D_OUT - 1] != gid[D_OUT]


def test_gid_padding_lands_in_no_group():
    """Mesh d_pad coordinates map to n_groups, which matches no group:
    padded mass never leaks into a real group."""
    from commefficient_tpu.ops.segments import group_sq_mass
    spec = make_group_spec(make_params(), "coarse")
    d_pad = D + 11
    gid = spec.gid(d_pad)
    assert (gid[D:] == spec.n_groups).all()
    x = jnp.ones((d_pad,), jnp.float32) * 2.0   # padding coords NONZERO
    masses = np.asarray(group_sq_mass(x, jnp.asarray(gid), spec.n_groups))
    np.testing.assert_allclose(masses.sum(), 4.0 * D, rtol=1e-6)
    np.testing.assert_allclose(masses, [4.0 * s for s in spec.sizes],
                               rtol=1e-6)


def test_segment_reductions_match_numpy_reference():
    rng = np.random.RandomState(3)
    d, G = 97, 5
    gid_np = rng.randint(0, G + 1, size=d).astype(np.int32)  # incl. drop
    x_np = rng.randn(d).astype(np.float32)
    from commefficient_tpu.ops.segments import (group_count, group_sq_mass,
                                                group_sum_at, group_sum_cols)
    gid, x = jnp.asarray(gid_np), jnp.asarray(x_np)
    ref_sq = np.zeros(G)
    ref_ct = np.zeros(G)
    for i in range(d):
        if gid_np[i] < G:
            ref_sq[gid_np[i]] += x_np[i] ** 2
            ref_ct[gid_np[i]] += float(x_np[i] != 0)
    np.testing.assert_allclose(np.asarray(group_sq_mass(x, gid, G)),
                               ref_sq, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(group_count(x != 0, gid, G)),
                               ref_ct, rtol=1e-6)
    cols = [x * x, (x != 0).astype(jnp.float32)]
    got = np.asarray(group_sum_cols(cols, gid, G))
    np.testing.assert_allclose(got[:, 0], ref_sq, rtol=1e-5)
    np.testing.assert_allclose(got[:, 1], ref_ct, rtol=1e-6)
    idx = jnp.asarray([0, 5, 5, 96], jnp.int32)
    ref_at = np.zeros(G)
    for j in idx:
        if gid_np[int(j)] < G:
            ref_at[gid_np[int(j)]] += 1.0
    np.testing.assert_allclose(
        np.asarray(group_sum_at(jnp.ones(4), idx, gid, G)), ref_at)


def test_gpt2_scanned_blocks_split_per_block():
    """The scan-stacked h/block leaves split along their leading block
    dim into per-block coarse groups (embed/attn/mlp/norm-bias per
    block + head), and the ranges still tile [0, d)."""
    from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads
    gcfg = GPT2Config.small(compute_dtype=jnp.float32)
    ids0 = jnp.zeros((1, 2, 16), jnp.int32)
    params = GPT2DoubleHeads(gcfg).init(
        jax.random.PRNGKey(0), ids0, jnp.zeros((1, 2), jnp.int32), ids0)
    spec = make_group_spec(params, "coarse")
    names = set(spec.names)
    assert "embed" in names and "head" in names
    for b in range(gcfg.n_layer):
        for sub in ("attn", "mlp", "norm-bias"):
            assert f"h{b}/{sub}" in names, (b, sub, sorted(names))
    covered = np.zeros(spec.d, np.int32)
    for start, end, g in spec.ranges:
        covered[start:end] += 1
    assert (covered == 1).all()
    assert sum(spec.sizes) == spec.d


def test_leaf_mode_one_group_per_leaf():
    spec = make_group_spec(make_params(), "leaf")
    assert spec.n_groups == 2 and set(spec.sizes) == {3, 18}


# --------------------------------------------------- in-round signals


def test_conservation_masses_and_counts():
    """Per-group masses sum to the whole-vector signal norms squared;
    support counts sum to exactly k (sketch top-k support)."""
    rt = make_runtime(signals_exact=True, sketch_fused_encode="off")
    batch, mask, ids = make_batch()
    state = rt.init_state()
    for _ in range(3):
        state, metrics = rt.round(state, ids, batch, mask, 0.05)
    sig = signals_to_host(metrics["signals"])
    ls = fetch(metrics)
    assert set(ls) == set(LAYER_SIGNAL_KEYS)
    assert sum(ls["update_mass"]) == pytest.approx(
        sig["update_norm"] ** 2, rel=1e-4)
    assert sum(ls["grad_mass"]) == pytest.approx(
        sig["grad_true_norm"] ** 2, rel=1e-4)
    assert sum(ls["error_mass"]) == pytest.approx(
        float(np.linalg.norm(np.asarray(state.sig_Verror))) ** 2, rel=1e-3)
    assert sum(ls["topk_count"]) == rt.cfg.k
    # lossless regime (c >= d): every group's winners recover (NaN =
    # the group owned no winner this round; serialized null)
    assert all(v == 1.0 or np.isnan(v) for v in ls["hh_overlap"])


def test_dense_mode_counts_are_group_sizes():
    rt = make_runtime(mode="uncompressed", error_type="none")
    batch, mask, ids = make_batch()
    _, metrics = rt.round(rt.init_state(), ids, batch, mask, 0.05)
    ls = fetch(metrics)
    assert ls["topk_count"] == [float(s) for s in rt.group_spec.sizes]
    assert ls["grad_mass"] is not None and ls["error_mass"] is not None


def test_fused_encode_reports_null_grad_mass_not_zero():
    """The PR-4 NaN contract applied to groups: the fused-encode round
    holds no dense aggregated gradient, so grad_mass/error_mass are
    NULL while the update-side fields stay live."""
    rt = make_runtime()                       # fused encode auto-engages
    assert rt._fused_encode and not rt._layer_grad_mass
    batch, mask, ids = make_batch()
    _, metrics = rt.round(rt.init_state(), ids, batch, mask, 0.05)
    ls = fetch(metrics)
    assert ls["grad_mass"] is None and ls["error_mass"] is None
    assert ls["hh_overlap"] is None
    assert sum(ls["topk_count"]) == rt.cfg.k
    assert sum(ls["update_mass"]) > 0


def test_mesh_sketch_reports_null_grad_mass_counts_live(devices):
    """Sharded (mesh) sketch round — the seq-sharded/fused-clients
    class: no dense aggregate ever materializes (per-shard encode), so
    grad_mass is null; support counts and update mass come from the
    update side and stay live, and conservation holds across shards."""
    from commefficient_tpu.parallel import make_mesh
    mesh = make_mesh((8,), ("clients",), devices=devices)
    params = make_params()
    cfg = FedConfig(mode="sketch", error_type="virtual",
                    local_momentum=0.0, virtual_momentum=0.9,
                    weight_decay=0.0, num_workers=8, local_batch_size=B,
                    track_bytes=True, num_clients=16,
                    num_results_train=2, num_results_val=2,
                    k=5, num_rows=2, num_cols=32, exact_num_cols=True)
    rt = FedRuntime(cfg, params, loss_fn, num_clients=16, mesh=mesh)
    rng = np.random.RandomState(1)
    batch = {"x": jnp.asarray(rng.randn(8, B, D_IN), jnp.float32),
             "y": jnp.asarray(rng.randn(8, B, D_OUT), jnp.float32)}
    mask = jnp.ones((8, B), bool)
    _, metrics = rt.round(rt.init_state(), jnp.arange(8, dtype=jnp.int32),
                          batch, mask, 0.05)
    sig = signals_to_host(metrics["signals"])
    ls = fetch(metrics)
    assert ls["grad_mass"] is None and ls["error_mass"] is None
    assert sum(ls["topk_count"]) == cfg.k
    assert sum(ls["update_mass"]) == pytest.approx(
        sig["update_norm"] ** 2, rel=1e-4)


@pytest.mark.slow
def test_seq_sharded_sketch_reports_null_grad_mass_counts_live():
    """The seq-sharded half of the null contract: a ("clients","seq")
    sketch round holds only per-shard partial gradients and a
    replicated table — grad_mass/error_mass null, update-side fields
    live and conserved."""
    from commefficient_tpu.gpt2_train import PERSONA_SEQ_SPEC
    from commefficient_tpu.losses import make_gpt2_train_loss
    from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads
    from commefficient_tpu.parallel import make_mesh
    Wg, Bg, C, S = 2, 2, 2, 32
    gcfg = GPT2Config.small(compute_dtype=jnp.float32, n_positions=128)
    ids0 = jnp.zeros((1, C, S), jnp.int32)
    params = GPT2DoubleHeads(gcfg).init(
        jax.random.PRNGKey(0), ids0, jnp.zeros((1, C), jnp.int32), ids0)
    mesh = make_mesh((2, 4), ("clients", "seq"))
    seq_model = GPT2DoubleHeads(gcfg, seq_axis="seq", seq_shards=4)
    cfg = FedConfig(mode="sketch", error_type="virtual",
                    local_momentum=0.0, virtual_momentum=0.9,
                    weight_decay=0.0, num_workers=Wg, local_batch_size=Bg,
                    num_clients=4, track_bytes=False, num_results_train=2,
                    k=8, num_rows=3, num_cols=256, num_blocks=2)
    rt = FedRuntime(cfg, params,
                    make_gpt2_train_loss(seq_model, seq_axis="seq",
                                         seq_shards=4),
                    num_clients=4, mesh=mesh, seq_spec=PERSONA_SEQ_SPEC)
    assert rt._layer_signals and not rt._layer_grad_mass
    rng = np.random.RandomState(0)
    batch = {
        "input_ids": jnp.asarray(rng.randint(0, 256, (Wg, Bg, C, S)),
                                 jnp.int32),
        "token_type_ids": jnp.asarray(rng.randint(0, 256, (Wg, Bg, C, S)),
                                      jnp.int32),
        "mc_token_ids": jnp.asarray(rng.randint(0, S, (Wg, Bg, C)),
                                    jnp.int32),
        "lm_labels": jnp.asarray(
            np.where(rng.rand(Wg, Bg, C, S) < 0.5,
                     rng.randint(0, 256, (Wg, Bg, C, S)), -100),
            jnp.int32),
        "mc_label": jnp.asarray(rng.randint(0, C, (Wg, Bg)), jnp.int32),
    }
    _, metrics = rt.round(rt.init_state(), jnp.arange(Wg, dtype=jnp.int32),
                          batch, jnp.ones((Wg, Bg), bool), 0.05)
    sig = signals_to_host(metrics["signals"])
    ls = fetch(metrics)
    assert ls["grad_mass"] is None and ls["error_mass"] is None
    assert sum(ls["topk_count"]) == cfg.k
    assert sum(ls["update_mass"]) == pytest.approx(
        sig["update_norm"] ** 2, rel=1e-4)
    # per-block groups exist for the scanned GPT-2 layout
    assert any(n.startswith("h0/") for n in rt.group_spec.names)


def test_groups_do_not_change_numerics():
    states = []
    for kw in ({"signal_groups": "coarse"}, {"signal_groups": "leaf"},
               {"signal_groups": "off"}):
        rt = make_runtime(**kw)
        batch, mask, ids = make_batch()
        s = rt.init_state()
        for _ in range(3):
            s, _ = rt.round(s, ids, batch, mask, 0.05)
        states.append(np.asarray(s.ps_weights))
    np.testing.assert_array_equal(states[0], states[2])
    np.testing.assert_array_equal(states[1], states[2])


def test_off_and_no_telemetry_hlo_byte_identity():
    """--signal_groups off compiles the group machinery out entirely:
    byte-identical HLO to a no-signals / no-telemetry round regardless
    of the groups setting, and the off round carries no gid argument."""
    batch, mask, ids = make_batch()

    def hlo(**kw):
        rt = make_runtime(**kw)
        return rt._round.lower(
            rt.init_state(), ids, batch, mask,
            jnp.asarray(0.05, jnp.float32), rt.cs, rt._gid).as_text()

    assert hlo(telemetry=False, signal_groups="coarse") == \
        hlo(telemetry=False, signal_groups="off")
    assert hlo(signals=False, signal_groups="coarse") == \
        hlo(signals=False, signal_groups="off")
    # sanity: with signals live the groups DO change the lowering
    assert hlo(signal_groups="coarse") != hlo(signal_groups="off")
    rt_off = make_runtime(signal_groups="off")
    assert rt_off._gid is None and rt_off.group_spec is None
    _, metrics = rt_off.round(rt_off.init_state(), ids, batch, mask, 0.05)
    assert metrics["layer_signals"] is None


# ------------------------------------------------- schema + emission


def test_layer_signals_event_roundtrip(tmp_path):
    rt = make_runtime(signals_exact=True, sketch_fused_encode="off")
    tel = RunTelemetry(str(tmp_path), "test", cfg=rt.cfg)
    batch, mask, ids = make_batch()
    _, metrics = rt.round(rt.init_state(), ids, batch, mask, 0.05)
    tel.layer_signals_event(rnd=1, mode=rt.cfg.mode,
                            signal_groups=rt.cfg.signal_groups,
                            groups=rt.group_spec.names,
                            sizes=rt.group_spec.sizes,
                            values=fetch(metrics))
    tel.write_summary(aborted=False, n_rounds=1)
    tel.close()
    assert validate_file(tel.path) == []
    ev = [json.loads(line) for line in open(tel.path)
          if '"event": "layer_signals"' in line][0]
    assert ev["groups"] == list(rt.group_spec.names)
    assert ev["sizes"] == list(rt.group_spec.sizes)
    assert len(ev["update_mass"]) == rt.group_spec.n_groups
    assert "NaN" not in open(tel.path).read()


def test_schema_rejects_malformed_layer_signals():
    assert validate_event({"event": "layer_signals", "t": 0.0, "seq": 0})
    ok = {"event": "layer_signals", "t": 0.0, "seq": 0, "round": 1,
          "mode": "sketch", "signal_groups": "coarse",
          "groups": ["w"], "sizes": [18], "grad_mass": None,
          "update_mass": [1.0], "topk_count": [5.0],
          "error_mass": None, "hh_overlap": None}
    assert validate_event(ok) == []
    assert validate_event(dict(ok, update_mass="nope"))


def test_driver_loop_emits_layer_signals_events(tmp_path):
    from commefficient_tpu import cv_train
    from test_telemetry import StubDS

    rt = make_runtime(dataset_name="SYNTH", telemetry_every=1,
                      sketch_fused_encode="off")
    tel = RunTelemetry(str(tmp_path), "cv_train", cfg=rt.cfg)
    tel.instrument(rt)
    cfg = rt.cfg.replace(num_epochs=1.0, pivot_epoch=0.5)
    _, summary = cv_train.train(cfg, rt, rt.init_state(),
                                StubDS(), StubDS(), telemetry=tel)
    tel.close()
    assert summary is not None
    assert validate_file(tel.path) == []
    events = [json.loads(line) for line in open(tel.path)]
    lsigs = [e for e in events if e["event"] == "layer_signals"]
    sigs = [e for e in events if e["event"] == "signals"]
    assert len(lsigs) == len(sigs) >= 1      # same cadence
    assert lsigs[0]["signal_groups"] == "coarse"
    assert sum(lsigs[0]["topk_count"]) == rt.cfg.k


# ------------------------------------------------------- starvation rule


def _ls_fields(groups, grad_mass, topk_count):
    return {"round": 1, "groups": list(groups),
            "grad_mass": list(grad_mass), "topk_count": list(topk_count)}


def test_starved_groups_predicate():
    # group 0 holds 30% of mass, wins 0 of k -> starved; group 1 fine
    out = starved_groups(["a", "b"], [3.0, 7.0], [0.0, 8.0])
    assert [g for g, _, _ in out] == ["a"]
    _, ms, ws = out[0]
    assert ms == pytest.approx(0.3) and ws == 0.0
    # null grad_mass: starvation is never guessed
    assert starved_groups(["a", "b"], None, [0.0, 8.0]) == []
    # below the mass floor: small groups losing k is EXPECTED
    assert starved_groups(["a", "b"], [0.1, 9.9], [0.0, 8.0]) == []


def test_group_starvation_rule_fires_after_window():
    mon = AnomalyMonitor(None)
    fields = _ls_fields(["conv", "bias"], [5.0, 5.0], [8.0, 0.0])
    fired = []
    for i in range(STARVATION_WINDOW - 1):
        fired += mon.observe("layer_signals", fields)
    assert fired == []                       # streak not ripe yet
    fired = mon.observe("layer_signals", fields)
    assert [f["rule"] for f in fired] == ["group_starvation"]
    a = fired[0]
    assert a["metric"] == "layer_signals.starvation[bias]"
    assert a["severity"] == "warn" and a["window"] == STARVATION_WINDOW
    # cooldown: the next ripe observation stays quiet
    assert mon.observe("layer_signals", fields) == []


def test_group_starvation_streak_breaks_on_recovery():
    mon = AnomalyMonitor(None)
    hungry = _ls_fields(["conv", "bias"], [5.0, 5.0], [8.0, 0.0])
    fed = _ls_fields(["conv", "bias"], [5.0, 5.0], [6.0, 2.0])
    for _ in range(STARVATION_WINDOW - 1):
        assert mon.observe("layer_signals", hungry) == []
    assert mon.observe("layer_signals", fed) == []     # streak broken
    for _ in range(STARVATION_WINDOW - 1):
        assert mon.observe("layer_signals", hungry) == []


def test_group_starvation_silent_on_null_grad_mass():
    mon = AnomalyMonitor(None)
    fields = {"round": 1, "groups": ["a", "b"], "grad_mass": None,
              "topk_count": [8.0, 0.0]}
    for _ in range(3 * STARVATION_WINDOW):
        assert mon.observe("layer_signals", fields) == []


def test_starvation_streak_survives_state_dict_roundtrip():
    mon = AnomalyMonitor(None)
    fields = _ls_fields(["conv", "bias"], [5.0, 5.0], [8.0, 0.0])
    for _ in range(STARVATION_WINDOW - 1):
        mon.observe("layer_signals", fields)
    mon2 = AnomalyMonitor(None)
    mon2.load_state_dict(mon.state_dict())
    fired = mon2.observe("layer_signals", fields)
    assert [f["rule"] for f in fired] == ["group_starvation"]


def test_committed_high_compression_arm_replays_starvation():
    """The evidence artifact's contract (runs/BREAKDOWN_layers.md):
    replaying the committed 10x hard-v2 attribution stream through the
    monitor fires group_starvation on the head group — the measured
    mechanism the adaptive-compression controller consumes. The 2.6x
    flagship arm flags too (later, once): starvation is present at the
    flagship compression and worsens with the ratio."""
    fired_by_arm = {}
    for arm in ("c26x", "c10x"):
        path = os.path.join(os.path.dirname(__file__), os.pardir, "runs",
                            "layer_attrib", arm, "telemetry.jsonl")
        mon = AnomalyMonitor(None)
        fired = []
        with open(path) as f:
            for line in f:
                try:
                    e = json.loads(line)
                except ValueError:
                    continue
                if e.get("event") == "layer_signals":
                    fired += mon.observe("layer_signals", e)
        fired_by_arm[arm] = [(a["metric"], a["round"]) for a in fired]
    assert any("head" in m for m, _ in fired_by_arm["c10x"]), fired_by_arm
    # dose response: the high arm fires no later and no less often
    assert len(fired_by_arm["c10x"]) >= len(fired_by_arm["c26x"]) >= 1, \
        fired_by_arm
    assert fired_by_arm["c10x"][0][1] <= fired_by_arm["c26x"][0][1], \
        fired_by_arm


# ---------------------------------------------------------------- teleview


def _teleview():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "teleview", os.path.join(os.path.dirname(__file__), os.pardir,
                                 "scripts", "teleview.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_teleview_fallback_constants_match_package():
    """teleview must run jax-free, so it carries literal twins of the
    layer-signal vocabulary and the starvation thresholds — pin them
    (and the fallback predicate's behavior) to the canonical values."""
    import re
    src = open(os.path.join(os.path.dirname(__file__), os.pardir,
                            "scripts", "teleview.py")).read()
    block = re.search(r"LAYER_SIGNAL_KEYS = \((.*?)\)", src, re.S).group(1)
    assert tuple(re.findall(r'"([a-z_0-9]+)"', block)) == LAYER_SIGNAL_KEYS
    m = re.search(r"STARVATION_MASS_SHARE = ([0-9.]+)", src)
    assert float(m.group(1)) == STARVATION_MASS_SHARE
    m = re.search(r"STARVATION_WIN_SHARE = ([0-9.]+)", src)
    assert float(m.group(1)) == STARVATION_WIN_SHARE
    # the literal fallback predicate agrees with the package's on a
    # starving sample (exercised by deleting the package import)
    tv = _teleview()
    sample = (["a", "b"], [3.0, 7.0], [0.0, 8.0])
    assert tv.starved_groups(*sample) == starved_groups(*sample)


def _write_stream(path, rounds=2, win_bias=0.0):
    tel = RunTelemetry(str(path), "test", cfg=None)
    for r in range(1, rounds + 1):
        tel.event("layer_signals", round=r, mode="sketch",
                  signal_groups="coarse",
                  groups=["conv", "bias"], sizes=[900, 100],
                  grad_mass=[6.0, 4.0], update_mass=[1.0, 0.1],
                  topk_count=[8.0 - win_bias, 0.0 + win_bias],
                  error_mass=[1.0, 9.0], hh_overlap=[1.0, None])
    tel.write_summary(aborted=False, n_rounds=rounds)
    tel.close()
    assert validate_file(tel.path) == []
    return tel.path


def test_teleview_layers_renders_table_and_flags_starved(tmp_path, capsys):
    tv = _teleview()
    p = _write_stream(tmp_path / "a")
    assert tv.main(["layers", p]) == 0
    out = capsys.readouterr().out
    assert "bias" in out and "STARVED" in out
    assert tv.main(["summarize", p]) == 0
    assert "STARVED" in capsys.readouterr().out


def test_teleview_diff_starvation_rise_gate(tmp_path, capsys):
    tv = _teleview()
    a = _write_stream(tmp_path / "a", win_bias=2.0)   # bias wins some k
    b = _write_stream(tmp_path / "b", win_bias=0.0)   # bias starves
    assert tv.main(["diff", a, b]) == 1
    assert "starvation gap" in capsys.readouterr().out
    assert tv.main(["diff", a, b, "--starvation_rise", "0.9"]) == 0
    # the input-wait gate keeps its own primary spelling
    assert tv.main(["diff", a, b, "--starvation_rise", "0.9",
                    "--input_wait_rise", "0.5"]) == 0
