"""Sharded sketch SERVER tail (core/server.sharded_sketch_server_update).

The round's server half — table momentum+EF, decode, top-k, error
feedback — runs reduce-scattered over the mesh: each device owns c/n
table columns and decodes only its d_pad/n coordinate range, and a tiny
(n, k) candidate all-gather + order-stable merge yields the global
top-k. Sharding must never change numerics: the round-level gates here
assert parity against the replicated tail (bitwise on this backend —
the merge is order-stable and the scattered reduce sums in device
order), and the op-level tests pin the range decode and the merge
against numpy references / the unsharded ``topk_with_idx``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.config import FedConfig
from commefficient_tpu.core import AsyncAggregator, FedRuntime
from commefficient_tpu.data.fed_sampler import Round
from commefficient_tpu.ops.circulant import make_circulant_sketch
from commefficient_tpu.ops.sketch import make_sketch
from commefficient_tpu.ops.topk import (local_topk_candidates,
                                        merge_topk_candidates,
                                        topk_with_idx)
from commefficient_tpu.parallel import make_mesh
from jax import shard_map


def _sketches(d, c=64, r=3):
    return [make_sketch(d, c, r, num_blocks=4),
            make_circulant_sketch(d, c, r)]


# ------------------------------------------------------- range decode


@pytest.mark.parametrize("impl", ["hash", "circ"])
def test_decode_range_matches_full_decode(impl):
    """decode_range(table, s, n) == decode(table)[s:s+n] — numpy-level
    parity for both estimator implementations, at several offsets
    including a non-block-aligned one."""
    d = 1000
    rng = np.random.RandomState(0)
    v = jnp.asarray(rng.randn(d), jnp.float32)
    cs = _sketches(d)[0 if impl == "hash" else 1]
    table = cs.encode(v)
    full = np.asarray(cs.decode(table))
    for start, length in ((0, d), (100, 300), (437, 129), (999, 1)):
        got = np.asarray(cs.decode_range(table, start, length))
        assert np.array_equal(got, full[start:start + length]), (
            impl, start, length)


@pytest.mark.parametrize("impl", ["hash", "circ"])
def test_decode_range_traced_start_under_jit(impl):
    """A traced start (the shard_map axis_index case) must produce the
    same estimates as the static-start call."""
    d = 777
    rng = np.random.RandomState(1)
    v = jnp.asarray(rng.randn(d), jnp.float32)
    cs = _sketches(d)[0 if impl == "hash" else 1]
    table = cs.encode(v)
    full = np.asarray(cs.decode(table))
    f = jax.jit(lambda t, s: cs.decode_range(t, s, 250))
    for start in (0, 13, 500):
        got = np.asarray(f(table, jnp.int32(start)))
        assert np.array_equal(got, full[start:start + 250]), (impl, start)


@pytest.mark.parametrize("impl", ["hash", "circ"])
def test_decode_range_zero_beyond_d(impl):
    """Coordinates >= d (mesh padding) decode to EXACTLY 0 — a padding
    coordinate must never win a top-k against real estimates."""
    d = 100
    rng = np.random.RandomState(2)
    v = jnp.asarray(rng.randn(d), jnp.float32)
    cs = _sketches(d, c=32)[0 if impl == "hash" else 1]
    table = cs.encode(v)
    full = np.asarray(cs.decode(table))
    got = np.asarray(cs.decode_range(table, d - 8, 40))
    assert np.array_equal(got[:8], full[-8:]), impl
    assert (got[8:] == 0).all(), impl


@pytest.mark.parametrize("impl", ["hash", "circ"])
def test_decode_range_inside_shard_map(impl):
    """The sharded tail's exact usage: each device decodes its
    axis_index-dependent slice of the padded range; the concatenated
    shards equal the full decode (plus zero padding)."""
    d = 1000
    n = 8
    d_pad = -(-d // n) * n
    blk = d_pad // n
    rng = np.random.RandomState(3)
    v = jnp.asarray(rng.randn(d), jnp.float32)
    cs = _sketches(d)[0 if impl == "hash" else 1]
    table = cs.encode(v)
    mesh = make_mesh((n,), ("clients",))
    from jax.sharding import PartitionSpec as P

    def block(t, cs):
        i = jax.lax.axis_index("clients")
        return cs.decode_range(t, i * blk, blk)

    out = shard_map(block, mesh=mesh,
                    in_specs=(P(), jax.tree.map(lambda _: P(), cs)),
                    out_specs=P("clients"), check_vma=False)(table, cs)
    full = np.asarray(cs.decode(table))
    got = np.asarray(out)
    assert got.shape == (d_pad,)
    assert np.array_equal(got[:d], full), impl
    assert (got[d:] == 0).all(), impl


@pytest.mark.parametrize("impl", ["hash", "circ"])
def test_decode_range_bf16_wire_table(impl):
    """Range decode of a table that went through the bf16 wire rounding
    (the --sketch_dtype bfloat16 collective payload) still matches the
    full decode of the SAME rounded table — the wire dtype changes what
    the server sees, never how the two decode paths see it."""
    d = 600
    rng = np.random.RandomState(4)
    v = jnp.asarray(rng.randn(d), jnp.float32)
    cs = _sketches(d)[0 if impl == "hash" else 1]
    table = cs.encode(v).astype(jnp.bfloat16).astype(jnp.float32)
    full = np.asarray(cs.decode(table))
    got = np.asarray(cs.decode_range(table, 64, 400))
    assert np.array_equal(got, full[64:464]), impl


def _kernel_range_decode(monkeypatch):
    """Steer ``CirculantSketch.decode_range`` onto the Pallas entry the
    TPU takes, in interpret mode: the CPU has no Mosaic."""
    import functools
    from commefficient_tpu.ops import circulant as circ
    from commefficient_tpu.ops import circulant_pallas as cp
    monkeypatch.setattr(circ.CirculantSketch, "_use_pallas_decode",
                        lambda self: True)
    monkeypatch.setattr(cp, "pallas_decode_range", functools.partial(
        cp.pallas_decode_range, interpret=True))


# d = 10,199 over c = 2,048 is m = 5 blocks; the shards' edges fall in
# mid-block, and the last shards' covers run past block 4
@pytest.mark.parametrize("n", [2, 4, 8])
def test_kernel_range_decode_inside_shard_map(monkeypatch, n):
    """The sharded tail's step 3 as the TPU runs it: every device
    decodes the whole blocks that cover its ``axis_index``-dependent
    range with the decode kernel (interpret mode) and slices its
    d_pad/n coordinates out; the concatenated shards are the full
    decode bit for bit, and the mesh padding reads exactly 0."""
    from commefficient_tpu.ops.circulant_pallas import range_cover_blocks
    from jax.sharding import PartitionSpec as P
    d, c = 10199, 2048
    d_pad = -(-d // n) * n
    blk = d_pad // n
    rng = np.random.RandomState(n)
    cs = make_circulant_sketch(d, c, 5, seed=n)
    table = cs.encode(jnp.asarray(rng.randn(d), jnp.float32))
    full = np.asarray(cs.decode(table))
    assert (n - 1) * blk // c + range_cover_blocks(c, blk) > cs.m
    _kernel_range_decode(monkeypatch)
    mesh = make_mesh((n,), ("clients",))

    def block(t, cs):
        i = jax.lax.axis_index("clients")
        return cs.decode_range(t, i * blk, blk)

    fn = jax.jit(shard_map(
        block, mesh=mesh, in_specs=(P(), jax.tree.map(lambda _: P(), cs)),
        out_specs=P("clients"), check_vma=False))
    assert "gather" not in fn.lower(table, cs).as_text()
    got = np.asarray(fn(table, cs))
    assert got.shape == (d_pad,)
    assert np.array_equal(got[:d], full)
    assert (got[d:] == 0).all()


# ------------------------------------------------------- top-k merge


def _sharded_select(x, k, n_shards):
    """Reference pipeline: per-shard candidates + merge over contiguous
    slices of ``x`` (len divisible by n_shards)."""
    blk = x.shape[0] // n_shards
    cv, ci = [], []
    for i in range(n_shards):
        lv, li = local_topk_candidates(x[i * blk:(i + 1) * blk], k, i * blk)
        cv.append(lv)
        ci.append(li)
    return merge_topk_candidates(jnp.stack(cv), jnp.stack(ci), k)


@pytest.mark.parametrize("k,n", [(7, 4), (8, 8), (13, 8), (1, 8)])
def test_merge_matches_unsharded_topk(k, n):
    """k not divisible by n, k == shards, k == 1: the merged selection
    (values AND index order) equals topk_with_idx on the full vector."""
    rng = np.random.RandomState(k * 31 + n)
    x = jnp.asarray(rng.randn(128), jnp.float32)
    ref_dense, ref_idx = topk_with_idx(x, k)
    mv, mi = _sharded_select(x, k, n)
    assert np.array_equal(np.asarray(mi), np.asarray(ref_idx)), (k, n)
    dense = np.zeros(128, np.float32)
    dense[np.asarray(mi)] = np.asarray(mv)
    assert np.array_equal(dense, np.asarray(ref_dense)), (k, n)


def test_merge_ties_straddling_shard_boundaries():
    """Equal magnitudes placed on both sides of shard boundaries (and
    a sign flip, which squares to the same key) must resolve exactly
    like the unsharded top-k: ascending index among equals."""
    n, k = 8, 6
    x = np.zeros(128, np.float32)
    x[15], x[16] = 2.0, 2.0          # straddles the 0|1 boundary
    x[31], x[32] = -2.0, 2.0         # sign flip straddling 1|2
    x[64], x[127] = 2.0, 2.0         # far shards
    x[40] = 5.0                      # one clear winner
    xv = jnp.asarray(x)
    ref_dense, ref_idx = topk_with_idx(xv, k)
    mv, mi = _sharded_select(xv, k, n)
    assert np.array_equal(np.asarray(mi), np.asarray(ref_idx))
    dense = np.zeros(128, np.float32)
    dense[np.asarray(mi)] = np.asarray(mv)
    assert np.array_equal(dense, np.asarray(ref_dense))


def test_merge_k_exceeds_shard_length():
    """k > per-shard candidate pool (k > d/n): every shard contributes
    its whole slice and the merge degenerates to the exact top-k."""
    n = 8
    d = 64                            # blk = 8 < k = 24
    k = 24
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(d), jnp.float32)
    ref_dense, ref_idx = topk_with_idx(x, k)
    mv, mi = _sharded_select(x, k, n)
    assert np.array_equal(np.asarray(mi), np.asarray(ref_idx))
    dense = np.zeros(d, np.float32)
    dense[np.asarray(mi)] = np.asarray(mv)
    assert np.array_equal(dense, np.asarray(ref_dense))


def test_merge_rejects_insufficient_candidates():
    """A candidate stack that cannot cover k is a caller bug, not a
    silent truncation."""
    with pytest.raises(AssertionError):
        merge_topk_candidates(jnp.zeros((2, 3)), jnp.zeros((2, 3),
                                                           jnp.int32), 8)


# ------------------------------------------------- round-level parity


def _params_and_loss(D=24, C=10):
    key = jax.random.PRNGKey(0xABCD)
    P_mat = jax.random.normal(jax.random.fold_in(key, 1), (D, C),
                              jnp.float32)

    def loss_fn(params, batch, mask):
        logits = batch["x"] @ params["w"]
        m = mask.astype(jnp.float32)
        denom = jnp.maximum(m.sum(), 1.0)
        lp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(lp, batch["target"][:, None],
                                   axis=1)[:, 0]
        loss = (nll * m).sum() / denom
        return loss, (loss,)

    def batch_for(W, B, g):
        k1 = jax.random.fold_in(key, 1000 + g)
        x = jax.random.normal(k1, (W, B, D), jnp.float32)
        t = jnp.argmax(x @ P_mat, axis=-1).astype(jnp.int32)
        return {"x": x, "target": t}

    return {"w": jnp.zeros((D, C), jnp.float32)}, loss_fn, batch_for


def _sketch_cfg(**kw):
    base = dict(mode="sketch", error_type="virtual", local_momentum=0.0,
                virtual_momentum=0.9, weight_decay=0.0, num_workers=8,
                local_batch_size=4, k=8, num_rows=3, num_cols=64,
                num_blocks=2, num_clients=16, track_bytes=True)
    base.update(kw)
    return FedConfig(**base)


# Sharded vs replicated weight tolerance after a few rounds — the dryrun
# gate's committed contract (__graft_entry__._sharded_server_gate). The
# two tails trace the same ops, but they are two XLA programs, and the
# CPU backend under jax 0.9.0 contracts ``agg + rho * Vvel`` into a fused
# multiply-add per loop, not per program: measured at this geometry, the
# sharded tail fuses all 192 table cells and the replicated tail fuses
# rows 0-1 and rounds the product first in row 2. No reduction reorders
# (round-1 tables and the rho=0 aggregates are bit-equal); from round 2
# the tables differ by <= 5 ulp in ~10 cells and the weights by <= 2 ulp
# (7.5e-9). A top-k selection flip, the failure this gate exists for,
# moves a weight by lr * |estimate| ~ 1e-2, four orders above atol.
SHARDED_W_RTOL, SHARDED_W_ATOL = 1e-4, 1e-6


def _run_rounds(cfg, n_rounds=4, lr=0.1, adapter=None, w_first=None,
                dims=(24, 10)):
    """``w_first``, when a list, receives the flat weights after round 1
    (momentum is still zero there, so nothing can contract)."""
    params, loss_fn, batch_for = _params_and_loss(*dims)
    mesh = make_mesh((8,), ("clients",))
    rt = FedRuntime(cfg, params, loss_fn, num_clients=cfg.num_clients,
                    mesh=mesh)
    obj = adapter(rt) if adapter is not None else rt
    st = obj.init_state() if adapter is not None else rt.init_state()
    ids = jnp.arange(8, dtype=jnp.int32)
    mask = jnp.ones((8, 4), bool)
    losses = []
    for g in range(1, n_rounds + 1):
        st, m = obj.round(st, ids, batch_for(8, 4, g), mask, lr)
        losses.append(np.asarray(m["results"][0]))
        if g == 1 and w_first is not None:
            w_first.append(np.asarray(rt.flat_weights(st)))
    return rt, np.stack(losses), np.asarray(rt.flat_weights(st))


@pytest.mark.parametrize("variant", [
    {},                                   # circ, zero-EF, f32
    {"sketch_impl": "hash"},
    {"sketch_ef": "subtract"},
    {"sketch_dtype": "bfloat16"},         # wire covers the scattered reduce
])
def test_sharded_round_matches_replicated(variant):
    """The tentpole parity gate at test granularity: a sharded-server
    sketch round must train like the replicated tail. The merge is
    order-stable and the scattered reduce sums in device order, so round
    1 (zero momentum) is BITWISE equal — selection, decode and reduce
    order all pinned; later rounds hold to SHARDED_W_*TOL (see there for
    what differs and why)."""
    w1_s, w1_r = [], []
    rt_s, losses_s, w_s = _run_rounds(_sketch_cfg(**variant), w_first=w1_s)
    assert rt_s._sharded_server, variant
    rt_r, losses_r, w_r = _run_rounds(
        _sketch_cfg(sketch_sharded_server="off", **variant), w_first=w1_r)
    assert not rt_r._sharded_server
    assert np.all(np.isfinite(losses_s)), variant
    assert (w1_s[0] == w1_r[0]).all(), variant
    # losses through round 2 are functions of the round-1 weights
    assert (losses_s[:2] == losses_r[:2]).all(), (variant, losses_s,
                                                  losses_r)
    np.testing.assert_allclose(losses_s, losses_r, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(w_s, w_r, rtol=SHARDED_W_RTOL,
                               atol=SHARDED_W_ATOL)


@pytest.mark.parametrize("variant", [
    {},                                   # zero-EF
    {"sketch_ef": "subtract"},
])
def test_sharded_round_kernel_decode_matches_gather_form(monkeypatch,
                                                         variant):
    """The sharded tail with the decode kernel under it (interpret
    mode; d = 2,400 over c = 1,024, eight shards of 300 in mid-block)
    trains like the same tail on the gather form, and like the
    replicated tail: the estimates are the same bits, so round 1 is
    bitwise and the later rounds hold to SHARDED_W_*TOL."""
    kw = dict(num_cols=1024, exact_num_cols=True, k=40, **variant)
    dims = (120, 20)
    runs = {}
    for name in ("gather", "replicated", "kernel"):
        if name == "kernel":
            _kernel_range_decode(monkeypatch)
        w1 = []
        cfg = _sketch_cfg(sketch_sharded_server="off"
                          if name == "replicated" else "auto", **kw)
        rt, losses, w = _run_rounds(cfg, w_first=w1, dims=dims)
        assert rt._sharded_server is (name != "replicated")
        assert rt.cs.m == 3 and rt.cs.c == 1024
        runs[name] = (w1[0], losses, w)
    for other in ("gather", "replicated"):
        assert (runs["kernel"][0] == runs[other][0]).all(), other
        assert (runs["kernel"][1][:2] == runs[other][1][:2]).all(), other
        np.testing.assert_allclose(runs["kernel"][2], runs[other][2],
                                   rtol=SHARDED_W_RTOL, atol=SHARDED_W_ATOL)
    assert np.abs(runs["kernel"][2]).max() > 0


@pytest.mark.parametrize("served", [False, True])
def test_runtime_says_which_decode_the_sharded_tail_takes(monkeypatch,
                                                          capsys, served):
    """Decided once at __init__, printed beside ``sketch kernel path``
    and kept as ``_server_tail_pallas``: the range decode on the Pallas
    kernel wherever the kernels serve the sketch, the gather form with
    its blocker elsewhere (here: the CPU)."""
    from commefficient_tpu.ops import circulant as circ
    if served:
        monkeypatch.setattr(circ.CirculantSketch, "pallas_blocker",
                            lambda self: None)
    params, loss_fn, _ = _params_and_loss(120, 20)
    cfg = _sketch_cfg(num_cols=1024, exact_num_cols=True)
    rt = FedRuntime(cfg, params, loss_fn, num_clients=cfg.num_clients,
                    mesh=make_mesh((8,), ("clients",)))
    out = capsys.readouterr().out
    assert rt._sharded_server and rt._server_tail_pallas is served
    assert not rt._server_tail_xla
    if served:
        # shards of 300 coordinates, c = 1,024: at most 2 blocks of 3
        assert ("sketch kernel path, server tail: pallas (range decode, "
                "2 of 3 blocks a chip)") in out
    else:
        assert ("sketch kernel path, server tail: xla (gather form: "
                "backend is 'cpu', not 'tpu')") in out


def test_sharded_round_per_param_lr_vector():
    """The per-parameter LR vector path (Fixup groups): the sharded tail
    multiplies d_pad-length shards, the replicated tail a true-d slice
    — same trained weights (round 1 bitwise, then SHARDED_W_*TOL)."""
    params, loss_fn, batch_for = _params_and_loss()
    mesh = make_mesh((8,), ("clients",))
    d = 24 * 10
    lr_vec = np.linspace(0.01, 0.2, d).astype(np.float32)
    outs = {}
    for ss in ("auto", "off"):
        cfg = _sketch_cfg(sketch_sharded_server=ss)
        rt = FedRuntime(cfg, params, loss_fn, num_clients=cfg.num_clients,
                        mesh=mesh)
        st = rt.init_state()
        ids = jnp.arange(8, dtype=jnp.int32)
        mask = jnp.ones((8, 4), bool)
        ws = []
        for g in range(1, 4):
            st, m = rt.round(st, ids, batch_for(8, 4, g), mask, lr_vec)
            ws.append(np.asarray(rt.flat_weights(st)))
        outs[ss] = ws
    assert (outs["auto"][0] == outs["off"][0]).all()
    np.testing.assert_allclose(outs["auto"][-1], outs["off"][-1],
                               rtol=SHARDED_W_RTOL, atol=SHARDED_W_ATOL)


class _CohortCommitRound:
    """``AsyncAggregator`` at K = 1, M = 1 behind ``FedRuntime.round``'s
    signature: every round is one cohort, one ``merge_first`` and one
    commit, the round's two halves as two executables."""

    def __init__(self, runtime):
        self.runtime = runtime
        self.agg = AsyncAggregator(runtime)
        self.tick = 0

    def init_state(self):
        return self.runtime.init_state()

    def round(self, state, client_ids, batch, mask, lr):
        self.tick += 1
        rnd = Round(np.asarray(client_ids, np.int64),
                    np.zeros(mask.shape, np.int64), np.asarray(mask))
        state, metrics, commits = self.agg.step(state, rnd, self.tick,
                                                batch, lr)
        assert len(commits) == 1 and commits[0]["staleness_max"] == 0
        return state, metrics


@pytest.mark.parametrize("wire", ["float32", "bfloat16", "int8"])
def test_cohort_commit_matches_round_on_the_mesh(wire):
    """The client half and the server half as separate executables
    (``--async_agg`` at K = 1, M = 1) reproduce the one-program round on
    the mesh, sharded server tail and wire format included: losses and
    weights BIT-identical. The cohort ends after the table's
    reduce-scatter (bf16: rounded on the wire; int8: the quantized
    all_to_all, its draws keyed by the server version both share)."""
    variant = {"float32": {},
               "bfloat16": {"sketch_dtype": "bfloat16"},
               "int8": {"wire_dtype": "int8", "wire_block": 8}}[wire]
    rt_mono, losses_mono, w_mono = _run_rounds(_sketch_cfg(**variant))
    rt, losses_split, w_split = _run_rounds(
        _sketch_cfg(async_agg=True, max_inflight=1, buffer_goal=1,
                    **variant),
        adapter=_CohortCommitRound)
    assert rt_mono._sharded_server and rt._sharded_server
    assert rt._int8_wire == (wire == "int8")
    assert (losses_split == losses_mono).all()
    assert (w_split == w_mono).all()


# ------------------------------------------- eligibility + ledger


def test_sharded_server_on_requires_mesh():
    params, loss_fn, _ = _params_and_loss()
    with pytest.raises(ValueError, match="no mesh"):
        FedRuntime(_sketch_cfg(sketch_sharded_server="on", num_workers=2,
                               num_clients=4),
                   params, loss_fn, num_clients=4)


def test_sharded_server_on_requires_divisible_cols():
    params, loss_fn, _ = _params_and_loss()
    mesh = make_mesh((8,), ("clients",))
    with pytest.raises(ValueError, match="num_cols"):
        FedRuntime(_sketch_cfg(sketch_sharded_server="on", num_cols=60,
                               exact_num_cols=True),
                   params, loss_fn, num_clients=16, mesh=mesh)


def test_sharded_server_on_requires_sketch_mode():
    with pytest.raises(ValueError, match="mode sketch"):
        FedConfig(mode="uncompressed", error_type="none",
                  sketch_sharded_server="on")


def test_ineligible_auto_falls_back_to_replicated_hlo():
    """auto with an ineligible geometry (c % n != 0) must trace the
    SAME program as the explicit off — the fallback IS the replicated
    round, byte for byte."""
    params, loss_fn, batch_for = _params_and_loss()
    mesh = make_mesh((8,), ("clients",))
    cfgs = [_sketch_cfg(num_cols=60, exact_num_cols=True,
                        sketch_sharded_server=ss) for ss in ("auto", "off")]
    texts = []
    for cfg in cfgs:
        rt = FedRuntime(cfg, params, loss_fn, num_clients=cfg.num_clients,
                        mesh=mesh)
        assert not rt._sharded_server
        st = rt.init_state()
        texts.append(rt._round.lower(
            st, jnp.arange(8, dtype=jnp.int32), batch_for(8, 4, 1),
            jnp.ones((8, 4), bool), jnp.asarray(0.1, jnp.float32),
            rt.cs).as_text())
    assert texts[0] == texts[1]


def test_teleview_perchip_drop_gate(tmp_path):
    """The scaling harness's regression gate: teleview diff exits 1
    when the candidate stream's last bench per_chip_items_per_s drops
    more than --perchip_drop relative to the baseline's, and 0 within
    the threshold (jax-free, like every teleview gate)."""
    import importlib.util
    import json
    import os

    spec = importlib.util.spec_from_file_location(
        "teleview",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "scripts", "teleview.py"))
    tv = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tv)

    def stream(path, per_chip):
        evs = [
            {"event": "manifest", "t": 0.0, "seq": 0},
            {"event": "bench", "t": 1.0, "seq": 1, "metric": "scaling",
             "result": {"items_per_s": per_chip * 8,
                        "per_chip_items_per_s": per_chip}},
        ]
        with open(path, "w") as f:
            for e in evs:
                f.write(json.dumps(e) + "\n")
        return str(path)

    base = stream(tmp_path / "a.jsonl", 100.0)
    bad = stream(tmp_path / "b.jsonl", 50.0)     # 50% drop
    ok = stream(tmp_path / "c.jsonl", 80.0)      # 20% drop
    assert tv.main(["diff", base, bad]) == 1
    assert tv.main(["diff", base, ok]) == 0
    # the threshold is the knob the virtual-device dryrun tunes
    assert tv.main(["diff", base, bad, "--perchip_drop", "0.6"]) == 0


def test_sharded_round_ledger_kinds():
    """The collective story the dryrun commits, at test granularity:
    the sharded sketch round's ledger holds a reduce-scatter (the table
    aggregation) and the ~n*k*8-byte candidate all-gathers, and NO
    table-sized (or larger) all-reduce — the replicated psum is gone."""
    from commefficient_tpu.telemetry.collectives import (round_ledger,
                                                         summarize_ledger)
    params, loss_fn, batch_for = _params_and_loss()
    mesh = make_mesh((8,), ("clients",))
    cfg = _sketch_cfg()
    rt = FedRuntime(cfg, params, loss_fn, num_clients=cfg.num_clients,
                    mesh=mesh)
    assert rt._sharded_server
    st = rt.init_state()
    led = round_ledger(rt, st, jnp.arange(8, dtype=jnp.int32),
                       batch_for(8, 4, 1), jnp.ones((8, 4), bool))
    counts = summarize_ledger(led)["counts"]
    assert counts.get("reduce-scatter", 0) >= 1, counts
    table = cfg.num_rows * cfg.num_cols
    big_ar = [e for e in led
              if e["kind"] == "all-reduce" and e["n_elements"] >= table]
    assert not big_ar, big_ar
    k_loc = min(cfg.k, rt.d_pad // 8)
    cand = [e for e in led if e["kind"] == "all-gather"
            and e["n_elements"] == 8 * k_loc]
    assert sum(e["bytes"] for e in cand) == 8 * k_loc * 8, cand
