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


def gid_reference(ranges, n_groups, d_pad):
    """The numpy reference the range reductions are held to: the d-long
    group-id map they replaced (PR 32). A coordinate in no range (mesh
    padding, a gap) carries ``n_groups``, which matches no group."""
    gid = np.full((d_pad,), n_groups, np.int32)
    for start, end, g in ranges:
        gid[start:end] = g
    return gid


def group_sums_reference(cols, gid, n_groups):
    """Per-group float64 sums of numpy columns, one coordinate at a
    time."""
    out = np.zeros((n_groups, len(cols)))
    for i, g in enumerate(gid):
        if g < n_groups:
            out[g] += [c[i] for c in cols]
    return out


def test_group_spec_tiles_ravel_order_exactly():
    """Ranges tile [0, d) with no gap/overlap, sizes sum to d, every
    boundary coordinate between adjacent leaf ranges lands in exactly
    one group, and the ranges agree with a numpy re-derivation from
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
    gid = gid_reference(spec.ranges, spec.n_groups, D)
    names = [spec.names[g] for g in gid]
    assert names[:D_OUT] == ["b/norm-bias"] * D_OUT
    assert names[D_OUT:] == ["w"] * (D_IN * D_OUT)
    # the boundary pair straddles the b/w leaf edge: adjacent
    # coordinates, different (single) groups
    assert gid[D_OUT - 1] != gid[D_OUT]


def test_padding_lands_in_no_group():
    """Mesh d_pad coordinates lie in no range, so they match no group:
    padded mass never leaks into a real group, through the dense
    reduction or through a winner index past d."""
    from commefficient_tpu.ops.segments import (group_sums_at,
                                                group_sums_dense, square)
    spec = make_group_spec(make_params(), "coarse")
    d_pad = D + 11
    x = jnp.ones((d_pad,), jnp.float32) * 2.0   # padding coords NONZERO
    masses = np.asarray(group_sums_dense([(x, (square,))], spec.ranges,
                                         spec.n_groups))[:, 0]
    np.testing.assert_allclose(masses.sum(), 4.0 * D, rtol=1e-6)
    np.testing.assert_allclose(masses, [4.0 * s for s in spec.sizes],
                               rtol=1e-6)
    at = np.asarray(group_sums_at(jnp.arange(d_pad), [square(x)],
                                  spec.ranges, spec.n_groups))[:, 0]
    np.testing.assert_allclose(at, masses, rtol=1e-6)


# ranges of the segment-reduction cases: (d, n_groups, ranges). Gaps
# between ranges and coordinates past the last one belong to no group.
RANGE_CASES = {
    # shorter than one 1,024-block: the tail path alone
    "tail_only": (97, 5, ((0, 10, 0), (10, 11, 3), (11, 60, 1),
                          (60, 61, 0), (70, 97, 4))),
    # one group owns interleaved ranges (norm leaves between kernels),
    # cuts on and off the 1,024 grid, neighbours of one group to merge
    "interleaved": (5000, 3, ((0, 1024, 0), (1024, 1030, 2),
                              (1030, 2048, 1), (2048, 2050, 2),
                              (2050, 3000, 0), (3000, 3500, 0),
                              (3500, 4999, 1), (4999, 5000, 2))),
    # ranges that start and end inside one 1,024-block, two of them in
    # the same block, and a block cut three times
    "inside_one_block": (4096, 4, ((0, 1100, 0), (1100, 1200, 1),
                                   (1200, 1210, 2), (1210, 1900, 3),
                                   (1900, 4096, 0))),
    # group 1 owns nothing; group 2 a single coordinate
    "empty_group": (3000, 3, ((0, 2047, 0), (2047, 2048, 2),
                              (2048, 3000, 0))),
    # whole blocks only (no tail), every cut on the grid
    "aligned": (4096, 2, ((0, 2048, 0), (2048, 4096, 1))),
}


@pytest.mark.parametrize("case", sorted(RANGE_CASES))
def test_segment_reductions_match_numpy_reference(case):
    """The dense range reduction and the k-sparse one against the
    coordinate-by-coordinate numpy reference, on the same operand."""
    from commefficient_tpu.ops.segments import (group_sums_at,
                                                group_sums_dense, nonzero,
                                                square)
    d, G, ranges = RANGE_CASES[case]
    rng = np.random.RandomState(3)
    x_np = rng.randn(d).astype(np.float32)
    x_np[rng.rand(d) < 0.3] = 0.0
    y_np = rng.randn(d).astype(np.float32)
    gid = gid_reference(ranges, G, d)
    ref = group_sums_reference(
        [x_np.astype(np.float64) ** 2, x_np != 0,
         y_np.astype(np.float64) ** 2], gid, G)
    x, y = jnp.asarray(x_np), jnp.asarray(y_np)
    got = np.asarray(group_sums_dense(
        [(x, (square, nonzero)), (y, (square,))], ranges, G))
    np.testing.assert_allclose(got[:, 0], ref[:, 0], rtol=1e-5)
    np.testing.assert_array_equal(got[:, 1], ref[:, 1])
    np.testing.assert_allclose(got[:, 2], ref[:, 2], rtol=1e-5)
    # the k-sparse path on the same update: its support, shuffled
    idx = rng.permutation(np.flatnonzero(x_np)).astype(np.int32)
    vals = jnp.asarray(x_np[idx])
    at = np.asarray(group_sums_at(jnp.asarray(idx),
                                  [square(vals), nonzero(vals)], ranges, G))
    np.testing.assert_allclose(at[:, 0], ref[:, 0], rtol=1e-5)
    np.testing.assert_array_equal(at[:, 1], ref[:, 1])
    # repeated indices each count (the heavy-hitter winner counts)
    rep = jnp.asarray([0, 5, 5, d - 1], jnp.int32)
    ref_at = group_sums_reference([np.ones(4)], gid[np.asarray(rep)], G)
    np.testing.assert_array_equal(
        np.asarray(group_sums_at(rep, [jnp.ones(4)], ranges, G)), ref_at)


@pytest.mark.parametrize("case", sorted(RANGE_CASES))
def test_dense_reduction_by_shards_matches_whole(case):
    """A mesh chip reduces its own coordinate shard from its own
    (traced) offset: shards of a length off the 1,024 grid, padding
    past d in the last, sum to the whole vector's group sums."""
    from commefficient_tpu.ops.segments import group_sums_dense, square
    d, G, ranges = RANGE_CASES[case]
    n = 3
    d_pad = -(-d // n) * n + n * 7
    x_np = np.random.RandomState(5).randn(d_pad).astype(np.float32)
    ref = group_sums_reference([x_np.astype(np.float64) ** 2],
                               gid_reference(ranges, G, d_pad), G)[:, 0]
    shard = d_pad // n
    by_shard = jax.jit(lambda xs, off: group_sums_dense(
        [(xs, (square,))], ranges, G, offset=off))
    got = sum(np.asarray(by_shard(jnp.asarray(
        x_np[i * shard:(i + 1) * shard]), jnp.asarray(i * shard)))[:, 0]
        for i in range(n))
    np.testing.assert_allclose(got, ref, rtol=1e-5)


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


@pytest.mark.parametrize("mode", ["uncompressed", "true_topk"])
def test_mesh_dense_operands_reduce_by_shard(devices, mode):
    """Dense operands on a mesh (the dense gradient and error of every
    dense mode, the uncompressed update): each chip reduces its own
    coordinate shard of the mesh-padded vector from its own offset and
    one psum recombines — the same groups the one-device round reports,
    padding (d = 21 over 8 chips) in none."""
    from commefficient_tpu.parallel import make_mesh
    mesh = make_mesh((8,), ("clients",), devices=devices)
    kw = dict(mode=mode, error_type="virtual" if mode == "true_topk"
              else "none", num_workers=8, num_clients=16)
    rng = np.random.RandomState(1)
    batch = {"x": jnp.asarray(rng.randn(8, B, D_IN), jnp.float32),
             "y": jnp.asarray(rng.randn(8, B, D_OUT), jnp.float32)}
    mask, ids = jnp.ones((8, B), bool), jnp.arange(8, dtype=jnp.int32)
    got = {}
    for name, m in (("one", None), ("mesh", mesh)):
        cfg = make_runtime(**kw).cfg
        rt = FedRuntime(cfg, make_params(), loss_fn, num_clients=16, mesh=m)
        state = rt.init_state()
        for _ in range(2):
            state, metrics = rt.round(state, ids, batch, mask, 0.05)
        got[name] = fetch(metrics), signals_to_host(metrics["signals"])
    assert rt.d_pad > D
    (one, _), (ls, sig) = got["one"], got["mesh"]
    for key in ("grad_mass", "update_mass", "error_mass"):
        np.testing.assert_allclose(ls[key], one[key], rtol=1e-4)
    assert ls["topk_count"] == one["topk_count"]
    assert sum(ls["topk_count"]) == (rt.cfg.k if mode == "true_topk" else D)
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
    of the groups setting."""
    batch, mask, ids = make_batch()

    def hlo(**kw):
        rt = make_runtime(**kw)
        return rt._round.lower(
            rt.init_state(), ids, batch, mask,
            jnp.asarray(0.05, jnp.float32), rt.cs).as_text()

    assert hlo(telemetry=False, signal_groups="coarse") == \
        hlo(telemetry=False, signal_groups="off")
    assert hlo(signals=False, signal_groups="coarse") == \
        hlo(signals=False, signal_groups="off")
    # sanity: with signals live the groups DO change the lowering
    assert hlo(signal_groups="coarse") != hlo(signal_groups="off")
    rt_off = make_runtime(signal_groups="off")
    assert rt_off.group_spec is None
    _, metrics = rt_off.round(rt_off.init_state(), ids, batch, mask, 0.05)
    assert metrics["layer_signals"] is None


@pytest.mark.parametrize("mode", ["sketch", "uncompressed"])
def test_round_takes_no_group_map(mode):
    """The groups add no argument to the round (PR 32: a (d_pad,) int32
    group-id map rode along, 4 B a parameter): the only d-long integer
    round_step takes is the byte ledger's coord_last_update, and the
    memory ledger's argument bytes are those of the groups-off round."""
    from commefficient_tpu.telemetry.memory_ledger import \
        round_memory_ledger
    batch, mask, ids = make_batch()
    kw = dict(mode=mode, error_type="virtual" if mode == "sketch"
              else "none")

    def lowered_args(rt):
        state = rt.init_state()
        avals = jax.tree.leaves(rt._round.lower(
            state, ids, batch, mask, jnp.asarray(0.05, jnp.float32),
            rt.cs).in_avals)
        ledger = round_memory_ledger(rt, state, ids, batch, mask, 0.05)
        return avals, ledger["argument_bytes"]

    rt = make_runtime(**kw)
    avals, arg_bytes = lowered_args(rt)
    d_long_ints = [a for a in avals if a.shape == (rt.d_pad,)
                   and jnp.issubdtype(a.dtype, jnp.integer)]
    assert len(d_long_ints) == 1, d_long_ints       # coord_last_update
    assert rt._layer_signals and rt.group_spec.n_groups == 2
    _, arg_bytes_off = lowered_args(make_runtime(signal_groups="off", **kw))
    assert arg_bytes == arg_bytes_off


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
