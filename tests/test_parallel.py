"""Sharded federated round on the virtual 8-device CPU mesh.

Validates the TPU mapping of the reference's distributed stack (SURVEY.md
§2.8): clients sharded over the mesh axis, XLA-inserted collectives for the
gradient sum, and exact equality with the single-device round — sharding
must never change numerics.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.config import FedConfig
from commefficient_tpu.core import FedRuntime
from commefficient_tpu.parallel import FedShardings, make_mesh
from commefficient_tpu.telemetry.clients import CLIENT_STAT_KEYS


def quad_loss(params, batch, mask):
    # simple convex loss: params is a dict pytree
    w = params["w"]
    x, y = batch["x"], batch["y"]
    pred = x @ w
    err = ((pred - y) ** 2).sum(axis=1)
    m = mask.astype(jnp.float32)
    denom = jnp.maximum(m.sum(), 1.0)
    loss = (err * m).sum() / denom
    return loss, (loss,)


def make_cfg(**kw):
    base = dict(mode="uncompressed", error_type="none", local_momentum=0.0,
                virtual_momentum=0.9, weight_decay=0.0, num_workers=8,
                local_batch_size=4, track_bytes=True, num_clients=16)
    base.update(kw)
    return FedConfig(**base)


def make_batch(seed, W=8, B=4, din=6, dout=3):
    rng = np.random.RandomState(seed)
    return (
        {"x": jnp.asarray(rng.randn(W, B, din), jnp.float32),
         "y": jnp.asarray(rng.randn(W, B, dout), jnp.float32)},
        jnp.asarray(rng.rand(W, B) > 0.2),
        jnp.arange(W, dtype=jnp.int32) * 2,
    )


@pytest.mark.parametrize("mode,extra", [
    ("uncompressed", {}),
    ("true_topk", {"error_type": "virtual", "k": 5}),
    ("sketch", {"error_type": "virtual", "k": 5, "num_rows": 3,
                "num_cols": 32, "num_blocks": 2, "sketch_impl": "hash"}),
    # rht: single-device (dense-preimage zeroing) and mesh (table-space
    # subtractive) rules only coincide in the lossless limit — assert the
    # exact-equality contract there (c >= padded d => exact round-trip)
    ("sketch", {"error_type": "virtual", "k": 5, "num_rows": 3,
                "num_cols": 32, "sketch_impl": "rht"}),
    ("local_topk", {"error_type": "local", "k": 5, "local_momentum": 0.9}),
    ("fedavg", {"error_type": "none", "local_batch_size": -1,
                "max_client_batch": 4, "fedavg_batch_size": 2,
                "num_fedavg_epochs": 2}),
])
def test_sharded_round_matches_single_device(mode, extra):
    cfg = make_cfg(mode=mode, **extra)
    params = {"w": jnp.asarray(
        np.random.RandomState(0).randn(6, 3), jnp.float32)}
    mesh = make_mesh((8,), ("clients",))

    rt_single = FedRuntime(cfg, params, quad_loss, num_clients=16)
    rt_shard = FedRuntime(cfg, params, quad_loss, num_clients=16, mesh=mesh)

    s1 = rt_single.init_state()
    s2 = rt_shard.init_state()
    batch, mask, client_ids = make_batch(1)
    lr = 0.1

    for step in range(3):
        s1, m1 = rt_single.round(s1, client_ids, batch, mask, lr)
        s2, m2 = rt_shard.round(s2, client_ids, batch, mask, lr)

    # mesh state is padded to d_pad (24 here for d=18 on 8 devices) so the
    # server runs sharded; the true coordinates must match the single-device
    # run up to fp32 reduction-order noise (reduce_scatter accumulates in
    # ring order where the single device sums in one pass)
    d = rt_single.cfg.grad_size
    assert rt_shard.d_pad == 24 and s2.ps_weights.shape == (24,)
    np.testing.assert_array_equal(np.asarray(s2.ps_weights[d:]), 0.0)
    np.testing.assert_allclose(np.asarray(s1.ps_weights),
                               np.asarray(s2.ps_weights[:d]),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(m1["results"][0]),
                               np.asarray(m2["results"][0]), rtol=1e-5)
    if cfg.track_bytes:
        np.testing.assert_allclose(np.asarray(m1["download_bytes"]),
                                   np.asarray(m2["download_bytes"]))


def test_sharded_state_layout():
    cfg = make_cfg(mode="local_topk", error_type="local", k=4,
                   local_momentum=0.9)
    params = {"w": jnp.zeros((6, 3), jnp.float32)}
    mesh = make_mesh((8,), ("clients",))
    rt = FedRuntime(cfg, params, quad_loss, num_clients=10, mesh=mesh)
    # client count padded to a multiple of the mesh axis
    assert rt.num_clients == 16
    state = rt.init_state()
    # dense client rows store COLUMN-sharded (home layout: every device
    # owns a d_row_pad/n slice of every row) so the round's gather/scatter
    # by client_ids is local and layout changes are W·d/n all_to_alls
    from jax.sharding import NamedSharding, PartitionSpec as P
    sh = state.client_errors.sharding
    assert sh.is_equivalent_to(
        NamedSharding(mesh, P(None, "clients")), state.client_errors.ndim)
    # dense server state shards over the weight axis even though the true
    # d (18) does not divide the mesh (padded to d_pad=24) — the VERDICT r1
    # replicated-fallback gap
    fs = FedShardings(mesh)
    assert rt.d_pad == 24
    for leaf in (state.ps_weights, state.Vvelocity, state.Verror,
                 state.coord_last_update):
        assert leaf.shape == (24,)
        assert leaf.sharding.is_equivalent_to(fs.dense_vec, leaf.ndim)
    # client rows live at d_row_pad so the column sharding divides evenly
    assert rt.d_row_pad == 24
    assert state.client_errors.shape == (16, 24)


def _collective_shapes(rt, state, batch, mask, client_ids):
    """(kind, n_elements) for every collective in the compiled round
    (tuple-typed combined collectives contribute one entry per element)."""
    from __graft_entry__ import _collective_report
    return _collective_report(rt, state, client_ids, batch, mask)


@pytest.mark.parametrize("mode,extra", [
    ("uncompressed", {}),
    ("true_topk", {"error_type": "virtual", "k": 5}),
    ("local_topk", {"error_type": "local", "k": 5, "local_momentum": 0.9}),
    ("fedavg", {"error_type": "none", "local_batch_size": -1,
                "max_client_batch": 4, "fedavg_batch_size": 2,
                "num_fedavg_epochs": 1}),
    ("sketch", {"error_type": "virtual", "k": 5, "num_rows": 3,
                "num_cols": 32, "num_blocks": 2}),
])
def test_collectives_are_shard_or_table_sized(mode, extra):
    """The round's gradient aggregation must never be a replicated full-d
    all-reduce: dense modes reduce_scatter the d_pad/n gradient shard,
    sketch reduce_scatters the (r, c) table over columns (the compressed
    payload, sharded — PR 11's server tail). The
    only full-length collective allowed is the one all-gather every client
    needs to read the weights (reference: every worker reads g_ps_weights,
    fed_worker.py:41)."""
    cfg = make_cfg(mode=mode, track_bytes=False, **extra)
    params = {"w": jnp.asarray(
        np.random.RandomState(0).randn(6, 3), jnp.float32)}
    mesh = make_mesh((8,), ("clients",))
    rt = FedRuntime(cfg, params, quad_loss, num_clients=16, mesh=mesh)
    state = rt.init_state()
    batch, mask, client_ids = make_batch(1)
    colls = _collective_shapes(rt, state, batch, mask, client_ids)
    assert colls, "expected collectives in the compiled round"
    d_pad = rt.d_pad
    table = cfg.num_rows * cfg.num_cols
    # HARD bound (mirrors __graft_entry__.dryrun_multichip): every
    # non-scalar collective result must be at most a dense shard, the
    # sketch table, or the per-device share of the round's client-state
    # rows (the all_to_all home-shard routing). Only the weight/top-k
    # all-gather may be full-length. The former W·d all-reduce pair for
    # velocity/error write-back (VERDICT r2 item 5) violates this bound.
    row_traffic = (8 * rt.d_row_pad // 8 if (cfg.needs_client_velocities
                                             or cfg.needs_client_errors)
                   else 0)
    # cfg.k covers the top-k select traffic (k ≪ a dense shard at real
    # configs; only this tiny test config has k > d_pad/n)
    bound = max(d_pad // 8, table if mode == "sketch" else 0, row_traffic,
                cfg.k)
    # all-gathers may be weight-sized, or TABLE-sized in sketch mode: the
    # signal diagnostics' row-norm estimates (l2estimate of the
    # column-sharded tables, telemetry/signals.py) gather the compressed
    # payload — bounded by the same table size as the aggregation psum
    gather_bound = max(d_pad, table if mode == "sketch" else 0)
    # ...or the client-stats summary: summarize_per_client replicates ONE
    # (K, W) matrix of per-client scalars, K <= len(CLIENT_STAT_KEYS).
    # That is O(K*W) whatever d is (56 floats against d = 6.5M on
    # ResNet-9); it exceeds d_pad only because this test's d is 18. XLA
    # under jax 0.9.0 gathers the stacked matrix in one launch (f32[5,8]
    # here: 5 stats x 8 clients), and with client_stats=False the gather
    # is gone (test_client_stats_gather_is_the_only_oversized_one).
    W = cfg.num_workers
    stats_gathers = [n for kind, n in colls if kind == "all-gather"
                     and n > gather_bound]
    assert len(stats_gathers) <= 1, colls
    for n in stats_gathers:
        assert n % W == 0 and n // W <= len(CLIENT_STAT_KEYS), colls
    for kind, n in colls:
        if kind == "all-gather":
            continue
        if n > 1:
            assert n <= bound, (kind, n)
        if kind == "reduce-scatter":
            if mode == "sketch":
                # the sharded server tail (PR 11): the table aggregation
                # reduce-scatters over COLUMNS — the result is the
                # (r, c/8) shard, never the replicated table
                assert n == table // 8, (kind, n)
            else:
                assert n == d_pad // 8, (kind, n)
    # every mode reduce-scatters its aggregate now: dense modes the
    # d_pad/n gradient shard, sketch the c/n table-column shard
    assert any(k == "reduce-scatter" for k, _ in colls), colls
    if cfg.needs_client_velocities or cfg.needs_client_errors:
        assert any(k == "all-to-all" for k, _ in colls), colls


def test_client_stats_gather_is_the_only_oversized_one():
    """Without the client-stats summary no all-gather exceeds the weight
    vector: the (K, W) stats matrix is the one exception the bound above
    admits, not a cover for a d-scaled gather."""
    cfg = make_cfg(mode="uncompressed", track_bytes=False,
                   client_stats=False)
    params = {"w": jnp.asarray(
        np.random.RandomState(0).randn(6, 3), jnp.float32)}
    mesh = make_mesh((8,), ("clients",))
    rt = FedRuntime(cfg, params, quad_loss, num_clients=16, mesh=mesh)
    batch, mask, client_ids = make_batch(1)
    colls = _collective_shapes(rt, rt.init_state(), batch, mask, client_ids)
    gathers = [n for kind, n in colls if kind == "all-gather"]
    assert gathers and max(gathers) <= rt.d_pad, colls


@pytest.mark.parametrize("mode,extra", [
    ("uncompressed", {}),
    ("true_topk", {"error_type": "virtual", "k": 5}),
    ("sketch", {"error_type": "virtual", "k": 5, "num_rows": 3,
                "num_cols": 32, "num_blocks": 2}),
    # microbatched: 2 microbatches per client — the fused scan must keep
    # per-client results/weighting exact across the client boundary
    ("uncompressed", {"microbatch_size": 2}),
    # bf16 wire: the fused branch's sum-rounding points must agree with
    # the vmap branch's (deferred encode in both)
    ("sketch", {"error_type": "virtual", "k": 5, "num_rows": 3,
                "num_cols": 32, "num_blocks": 2,
                "sketch_dtype": "bfloat16"}),
])
def test_fused_clients_matches_vmap(mode, extra):
    """The jointly-computed round gradient (make_fused_grad, default-on)
    must reproduce the per-client vmap path's trajectory and per-client
    metrics exactly up to summation order — single-device AND mesh."""
    cfg_f = make_cfg(mode=mode, local_momentum=0.0, weight_decay=5e-4,
                     **extra)
    cfg_v = cfg_f.replace(fused_clients=False)
    params = {"w": jnp.asarray(
        np.random.RandomState(0).randn(6, 3), jnp.float32)}
    mesh = make_mesh((8,), ("clients",))
    batch, mask, cids = make_batch(1)

    rt_f = FedRuntime(cfg_f, params, quad_loss, num_clients=16)
    rt_v = FedRuntime(cfg_v, params, quad_loss, num_clients=16)
    assert rt_f._fused and not rt_v._fused
    sf, sv = rt_f.init_state(), rt_v.init_state()
    for _ in range(3):
        sf, mf = rt_f.round(sf, cids, batch, mask, 0.1)
        sv, mv = rt_v.round(sv, cids, batch, mask, 0.1)
    np.testing.assert_allclose(np.asarray(sf.ps_weights),
                               np.asarray(sv.ps_weights),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(mf["results"][0]),
                               np.asarray(mv["results"][0]), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(mf["n_valid"]),
                                  np.asarray(mv["n_valid"]))

    rt_m = FedRuntime(cfg_f, params, quad_loss, num_clients=16, mesh=mesh)
    assert rt_m._fused
    sm = rt_m.init_state()
    for _ in range(3):
        sm, mm = rt_m.round(sm, cids, batch, mask, 0.1)
    d = rt_f.cfg.grad_size
    # a bf16 WIRE rounds the mesh psum's partial sums where one chip
    # rounds the full sum once — agreement there is only to bf16 epsilon
    wide = extra.get("sketch_dtype") == "bfloat16"
    np.testing.assert_allclose(np.asarray(sf.ps_weights),
                               np.asarray(sm.ps_weights[:d]),
                               rtol=0.02 if wide else 1e-4,
                               atol=1e-3 if wide else 1e-6)
    np.testing.assert_allclose(np.asarray(mf["results"][0]),
                               np.asarray(mm["results"][0]),
                               rtol=5e-3 if wide else 1e-5)


def test_bf16_sketch_tables():
    """--sketch_dtype bfloat16 (VERDICT r3 item 6): the table psum payload
    must compile as a bf16 all-reduce (half the ICI bytes of the
    reference's NCCL reduce, fed_worker.py:138), the round must stay
    close to the fp32-wire round (the only difference is ~2^-8 relative
    cell rounding), and single-device vs mesh must agree — the one-chip
    emulation applies the same wire quantization the psum would."""
    import re

    extra = dict(mode="sketch", error_type="virtual", k=5, num_rows=3,
                 num_cols=32, num_blocks=2, track_bytes=False)
    params = {"w": jnp.asarray(
        np.random.RandomState(0).randn(6, 3), jnp.float32)}
    mesh = make_mesh((8,), ("clients",))
    batch, mask, cids = make_batch(1)

    rt16 = FedRuntime(make_cfg(sketch_dtype="bfloat16", **extra), params,
                      quad_loss, num_clients=16, mesh=mesh)
    # payload dtype pinned in the UNOPTIMIZED lowering: the program hands
    # the collective a bf16 table. (The compiled text cannot be asserted
    # on the CPU backend — its FloatSupport pass legally promotes bf16
    # all-reduces to f32 because CPU lacks bf16 arithmetic; TPU keeps the
    # native bf16 wire.)
    txt = rt16._round.lower(
        rt16.init_state(), cids, batch, mask,
        jnp.asarray(0.1, jnp.float32), rt16.cs).as_text()
    # the sharded server tail (PR 11) reduce-SCATTERS the table over
    # columns, so the bf16 wire now pins the scattered collective: the
    # payload enters as the full bf16 table and leaves as the (r, c/8)
    # bf16 column shard
    assert re.search(
        r"stablehlo\.reduce_scatter.*?"
        r"\(tensor<3x32xbf16>\) -> tensor<3x4xbf16>", txt, re.S), \
        "expected a bf16 table reduce_scatter in the lowering"

    # numerics: bf16 wire stays near the fp32 wire...
    rt32 = FedRuntime(make_cfg(**extra), params, quad_loss,
                      num_clients=16, mesh=mesh)
    s16, s32 = rt16.init_state(), rt32.init_state()
    for _ in range(3):
        s16, _ = rt16.round(s16, cids, batch, mask, 0.1)
        s32, _ = rt32.round(s32, cids, batch, mask, 0.1)
    assert np.all(np.isfinite(np.asarray(s16.ps_weights)))
    np.testing.assert_allclose(np.asarray(s16.ps_weights),
                               np.asarray(s32.ps_weights),
                               rtol=0.05, atol=1e-3)
    # ...and the single-device emulation matches the mesh wire closely
    # (identical quantization points up to reduction order)
    rt1 = FedRuntime(make_cfg(sketch_dtype="bfloat16", **extra), params,
                     quad_loss, num_clients=16)
    s1 = rt1.init_state()
    for _ in range(3):
        s1, _ = rt1.round(s1, cids, batch, mask, 0.1)
    d = rt1.cfg.grad_size
    np.testing.assert_allclose(np.asarray(s1.ps_weights),
                               np.asarray(s16.ps_weights[:d]),
                               rtol=0.02, atol=1e-3)


def test_sharded_val_matches_dense():
    """Mesh-parallel validation (VERDICT r2 item 6): the val batch shards
    over all devices and the weighted recombination must equal the dense
    single-device evaluation — including a non-mesh-divisible item count
    (padded+masked) and an odd valid-mask."""
    cfg = make_cfg(mode="uncompressed")
    params = {"w": jnp.asarray(
        np.random.RandomState(0).randn(6, 3), jnp.float32)}
    mesh = make_mesh((8,), ("clients",))
    rt_single = FedRuntime(cfg, params, quad_loss, num_clients=16)
    rt_mesh = FedRuntime(cfg, params, quad_loss, num_clients=16, mesh=mesh)
    s1, s2 = rt_single.init_state(), rt_mesh.init_state()

    rng = np.random.RandomState(5)
    for N in (32, 13):  # mesh-divisible and not
        batch = {"x": jnp.asarray(rng.randn(N, 6), jnp.float32),
                 "y": jnp.asarray(rng.randn(N, 3), jnp.float32)}
        mask = jnp.asarray(rng.rand(N) > 0.3)
        r1, n1 = rt_single.val(s1, batch, mask)
        r2, n2 = rt_mesh.val(s2, batch, mask)
        assert float(n1) == float(n2)
        for a, b in zip(r1, r2):
            np.testing.assert_allclose(float(a), float(b), rtol=1e-5)


def test_make_mesh_defaults():
    assert make_mesh((), ("clients",),
                     devices=jax.devices()[:1]) is None
    m = make_mesh((), ("clients",))
    assert m is not None and m.shape["clients"] == 8
    with pytest.raises(ValueError):
        make_mesh((16,), ("clients",))


def test_fedavg_vector_lr_on_mesh():
    """A per-param LR vector (Fixup groups) must work in fedavg mode on a
    mesh with non-divisible d: the server sees it padded, the client step
    true-d."""
    cfg = make_cfg(mode="fedavg", error_type="none", local_momentum=0.0,
                   local_batch_size=-1, max_client_batch=4,
                   fedavg_batch_size=2, num_fedavg_epochs=1)
    params = {"w": jnp.asarray(
        np.random.RandomState(0).randn(6, 3), jnp.float32)}
    mesh = make_mesh((8,), ("clients",))
    rt = FedRuntime(cfg, params, quad_loss, num_clients=16, mesh=mesh)
    assert rt.d_pad != rt.cfg.grad_size
    state = rt.init_state()
    batch, mask, cids = make_batch(1)
    lr_vec = jnp.full((rt.cfg.grad_size,), 0.05, jnp.float32)
    s2, _ = rt.round(state, cids, batch, mask, lr_vec)
    s_ref, _ = rt.round(rt.init_state(), cids, batch, mask, 0.05)
    np.testing.assert_allclose(np.asarray(s2.ps_weights),
                               np.asarray(s_ref.ps_weights), rtol=1e-5)


def test_sketch_vector_lr_on_mesh():
    """Per-param LR vector in sketch mode on a non-divisible-d mesh: the
    padded vector must slice back to true d for the table-space server
    update."""
    cfg = make_cfg(mode="sketch", error_type="virtual", k=5, num_rows=3,
                   num_cols=32, num_blocks=2)
    params = {"w": jnp.asarray(
        np.random.RandomState(0).randn(6, 3), jnp.float32)}
    mesh = make_mesh((8,), ("clients",))
    rt = FedRuntime(cfg, params, quad_loss, num_clients=16, mesh=mesh)
    assert rt.d_pad != rt.cfg.grad_size
    batch, mask, cids = make_batch(1)
    lr_vec = jnp.full((rt.cfg.grad_size,), 0.05, jnp.float32)
    s2, _ = rt.round(rt.init_state(), cids, batch, mask, lr_vec)
    s_ref, _ = rt.round(rt.init_state(), cids, batch, mask, 0.05)
    np.testing.assert_allclose(np.asarray(s2.ps_weights),
                               np.asarray(s_ref.ps_weights), rtol=1e-5)
