"""Fused sketch encode (core/client.py + ops/sketch.py + ops/circulant.py):

- the streaming/accumulating encode entry points against dense-encode
  references (sketch linearity: ``table + encode(v)``, range offsets,
  scale folding, the loop-token contract);
- ``encode_grad_tree`` leaf coalescing/splitting against ``encode(ravel)``;
- StreamMLP's hand-written ``streaming_grad`` against ``jax.grad`` of the
  same loss (the manual-VJP contract of models/stream_mlp.py);
- fused-encode rounds == unfused rounds within fp tolerance on the
  fused-clients scan AND the vmap path, incl. masked/zero-datum clients
  and update-space adversary injection (which acts on the table);
- HLO byte-identity where the fused encode must be invisible (non-sketch
  modes; auto-with-blocker == explicit off);
- the --sketch_fused_encode on fail-fast guard;
- the byte ledger against numpy: the download count (one fused read of
  ``coord_last_update``) and, round by round through every mode and an
  async cohort + commit, the marks and the byte vectors.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from commefficient_tpu.config import FedConfig
from commefficient_tpu.core import FedRuntime
from commefficient_tpu.core.client import (encode_grad_tree,
                                           fused_encode_blockers)
from commefficient_tpu.models.stream_mlp import (init_stream_mlp,
                                                 make_stream_mlp_loss)
from commefficient_tpu.ops.sketch import (loop_token_zero, make_sketch_impl,
                                          sketch_encode_accum)
from commefficient_tpu.parallel import make_mesh
from tests.test_parallel import make_batch, quad_loss

W, B = 4, 4


def make_cfg(**kw):
    base = dict(mode="sketch", error_type="virtual", k=5, num_rows=3,
                num_cols=32, num_blocks=2, sketch_impl="hash",
                local_momentum=0.0, virtual_momentum=0.9,
                weight_decay=0.0, num_workers=W, local_batch_size=B,
                track_bytes=True, num_clients=16, microbatch_size=2)
    base.update(kw)
    return FedConfig(**base)


def make_params(seed=0):
    return {"w": jnp.asarray(np.random.RandomState(seed).randn(6, 3),
                             jnp.float32)}


def run_rounds(cfg, n=3, params=None, loss_fn=quad_loss, seed=0):
    rt = FedRuntime(cfg, params or make_params(), loss_fn, num_clients=16)
    state = rt.init_state()
    batch, mask, ids = make_batch(seed, W=W, B=B)
    losses = []
    for _ in range(n):
        state, m = rt.round(state, ids, batch, mask, 0.1)
        losses.append(np.asarray(m["results"][0]))
    return rt, np.stack(losses), state


# --------------------------------------------------------- streaming encodes


@pytest.mark.parametrize("impl", ["hash", "circ"])
def test_encode_accum_matches_dense_encode(impl):
    """``table + encode_accum(vals @ start)`` == ``table + encode(v)``
    for v zero outside the range — for interior ranges, the full vector,
    and with a scale folded in (sketch linearity)."""
    d = 1000
    cs = make_sketch_impl(impl, d=d, c=64, r=3, num_blocks=4)
    rng = np.random.RandomState(3)
    table0 = jnp.asarray(rng.randn(3, 64), jnp.float32)
    for start, n in ((0, d), (0, 17), (128, 300), (d - 33, 33)):
        vals = jnp.asarray(rng.randn(n), jnp.float32)
        dense = jnp.zeros(d).at[start:start + n].set(vals)
        ref = table0 + cs.encode(dense)
        got = cs.encode_accum(table0, vals, start)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        got_s = cs.encode_accum(table0, vals, start,
                                scale=jnp.asarray(2.5, jnp.float32),
                                token=jnp.asarray(1.7, jnp.float32))
        ref_s = table0 + 2.5 * cs.encode(dense)
        np.testing.assert_allclose(np.asarray(got_s), np.asarray(ref_s),
                                   rtol=1e-5, atol=1e-4)


def test_encode_accum_under_jit_and_scan():
    """The streaming encode composes with jit + lax.scan (the fused
    client path's actual shape: per-step encodes into a carried table)
    and the result equals the one-shot encode of the summed vector."""
    d = 257
    cs = make_sketch_impl("hash", d=d, c=32, r=3, num_blocks=2)
    rng = np.random.RandomState(0)
    vs = jnp.asarray(rng.randn(5, d), jnp.float32)

    @jax.jit
    def stream(vs):
        def body(tbl, v):
            return sketch_encode_accum(cs, tbl, v, 0, token=v[0]), None
        tbl, _ = jax.lax.scan(body, jnp.zeros((3, 32)), vs)
        return tbl

    ref = cs.encode(vs.sum(axis=0))
    np.testing.assert_allclose(np.asarray(stream(vs)), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_loop_token_zero_contract():
    """The opaque zero is EXACTLY zero for every token — finite, inf,
    nan (a diverging loss must never scramble bucket indices) — and
    None degrades to a plain zero."""
    for tok in (0.0, 3.7, -1e30, np.inf, -np.inf, np.nan):
        z = jax.jit(loop_token_zero)(jnp.asarray(tok, jnp.float32))
        assert int(z) == 0, (tok, z)
        assert z.dtype == jnp.uint32
    assert int(loop_token_zero(None)) == 0


@pytest.mark.parametrize("impl", ["hash", "circ"])
def test_encode_grad_tree_matches_ravel_encode(impl):
    """Leaf-range streaming over a mixed pytree (tiny bias leaves that
    coalesce, a large kernel that splits) equals the one-shot encode of
    the raveled tree; a scale folds in linearly."""
    rng = np.random.RandomState(1)
    gtree = {
        "a_bias": jnp.asarray(rng.randn(7), jnp.float32),
        "b_kernel": jnp.asarray(rng.randn(90, 30), jnp.float32),
        "c_bias": jnp.asarray(rng.randn(11), jnp.float32),
        "d_kernel": jnp.asarray(rng.randn(40, 10), jnp.float32),
    }
    flat, _ = ravel_pytree(gtree)
    d = flat.shape[0]
    cs = make_sketch_impl(impl, d=d, c=128, r=3, num_blocks=4)
    table0 = jnp.zeros((3, 128))
    ref = cs.encode(flat)
    # min/max chunk sizes chosen to force BOTH the coalesce path (7- and
    # 11-element biases) and the split path (the 2700-element kernel)
    got = encode_grad_tree(cs, table0, gtree, min_chunk=64, max_chunk=512)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)
    got_s = encode_grad_tree(cs, table0, gtree,
                             scale=jnp.asarray(0.5, jnp.float32),
                             token=jnp.asarray(2.0, jnp.float32),
                             min_chunk=64, max_chunk=512)
    np.testing.assert_allclose(np.asarray(got_s), 0.5 * np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


# (d, scale, last leaf): a ragged last block, with no scale, a weak-typed
# one and a traced one; d = m*c, where the zeros tail is empty; the zeros
# as a leaf of their own behind a large last leaf, and on a small one
@pytest.mark.parametrize("d_extra,scale,small_last", [
    (1877, None, False), (1877, 0.37, False), (1877, "traced", False),
    (0, "traced", False), (1877, "traced", True), (0, None, True),
], ids=["ragged_no_scale", "ragged_python_scale", "ragged_traced_scale",
        "whole_blocks_traced_scale", "ragged_zeros_on_last_leaf",
        "whole_blocks_small_last_leaf"])
def test_encode_grad_tree_pallas_route_equals_parents_table(
        monkeypatch, d_extra, scale, small_last):
    """Where the Pallas kernels serve the sketch, ``encode_grad_tree``
    ravels the tree once, to m*c with a zeros tail, and hands the scale
    to the kernel as a scalar (interpret mode here). The table is the
    parent's bit for bit: there XLA took ``ravel * scale``, padded it to
    m*c and appended each block's wrap in three d-long passes, and the
    kernel added the blocks in order."""
    import functools
    from commefficient_tpu.ops import circulant as circ
    from commefficient_tpu.ops import circulant_pallas as cp
    from tests.test_ops import TestCirculantSketch
    c, r = 2048, 3
    rng = np.random.RandomState(31)
    gtree = {
        "a_bias": jnp.asarray(rng.randn(7), jnp.float32),
        "b_kernel": jnp.asarray(rng.randn(90, 30), jnp.float32),
        "c_bias": jnp.asarray(rng.randn(11), jnp.float32),
        "d_kernel": jnp.asarray(rng.randn(3 * c - 2718 + d_extra
                                          - 13 * small_last), jnp.float32),
    }
    if small_last:
        gtree["e_bias"] = jnp.asarray(rng.randn(13), jnp.float32)
    flat, _ = ravel_pytree(gtree)
    d = flat.shape[0]
    last = jax.tree_util.tree_leaves(gtree)[-1]
    assert (64 * last.size <= d) == small_last
    cs = circ.make_circulant_sketch(d=d, c=c, r=r, seed=31)
    assert (d % c == 0) == (d_extra == 0) and cs.m in (3, 4)
    monkeypatch.setattr(circ.CirculantSketch, "_use_pallas_encode",
                        lambda self: True)
    monkeypatch.setattr(cp, "pallas_encode", functools.partial(
        cp.pallas_encode, interpret=True))
    table0 = jnp.asarray(rng.randn(r, c), jnp.float32)
    if scale == "traced":
        got = jax.jit(lambda t, g, s: encode_grad_tree(cs, t, g, scale=s))(
            table0, gtree, jnp.float32(2.5))
        factor = np.float32(2.5)
    else:
        got = encode_grad_tree(cs, table0, gtree, scale=scale)
        factor = np.float32(1.0 if scale is None else scale)
    want = np.asarray(table0) + TestCirculantSketch._encode_in_block_order(
        cs, factor * np.asarray(flat))
    np.testing.assert_array_equal(np.asarray(got), want)
    # the (d,) entry, the weight-decay encode of params_vec: one pad, the
    # same kernel, the same table
    got_d = cs.encode_accum(table0, flat, 0, scale=None if scale is None
                            else factor)
    np.testing.assert_array_equal(np.asarray(got_d), want)
    np.testing.assert_array_equal(
        np.asarray(cs.encode(flat)),
        TestCirculantSketch._encode_in_block_order(cs, np.asarray(flat)))


def test_streaming_grad_matches_jax_grad():
    """models/stream_mlp.py's manual VJP: the streamed table equals
    encode(jax.grad) of the same loss in ravel layout, the loss matches
    the pytree forward, and the client datum-count scale folds in."""
    params = init_stream_mlp(jax.random.PRNGKey(0), d_in=16, hidden=32,
                             n_layers=6, n_classes=5)
    loss_fn = make_stream_mlp_loss(params)
    pv, unravel = ravel_pytree(params)
    d = pv.shape[0]
    rng = np.random.RandomState(2)
    batch = {"x": jnp.asarray(rng.randn(8, 16), jnp.float32),
             "target": jnp.asarray(rng.randint(0, 5, (8,)), jnp.int32)}
    mask = jnp.asarray([1, 1, 1, 0, 1, 1, 0, 1], bool)

    def loss_vec(v):
        loss, _ = loss_fn(unravel(v), batch, mask)
        return loss

    g = jax.grad(loss_vec)(pv)
    for impl in ("hash", "circ"):
        cs = make_sketch_impl(impl, d=d, c=128, r=3, num_blocks=4)
        t, loss_s, (acc_s,) = loss_fn.streaming_grad(
            pv, batch, mask, cs, jnp.zeros((3, 128)))
        np.testing.assert_allclose(float(loss_s), float(loss_vec(pv)),
                                   rtol=1e-6)
        ref = np.asarray(cs.encode(g))
        np.testing.assert_allclose(np.asarray(t), ref, rtol=1e-4,
                                   atol=1e-5)
        t2, _, _ = loss_fn.streaming_grad(
            pv, batch, mask, cs, jnp.zeros((3, 128)),
            scale=jnp.asarray(3.0, jnp.float32))
        np.testing.assert_allclose(np.asarray(t2), 3.0 * ref, rtol=1e-4,
                                   atol=1e-4)


# ------------------------------------------------------- runtime equivalence


FUSED_LOSS_RTOL, FUSED_LOSS_ATOL = 1e-4, 1e-5


def test_fused_round_matches_unfused_fused_clients_path():
    rt_f, lf, sf = run_rounds(make_cfg(sketch_fused_encode="auto"))
    rt_u, lu, su = run_rounds(make_cfg(sketch_fused_encode="off"))
    assert rt_f._fused_encode and rt_f._fused
    assert not rt_u._fused_encode
    np.testing.assert_allclose(lf, lu, rtol=FUSED_LOSS_RTOL,
                               atol=FUSED_LOSS_ATOL)
    np.testing.assert_allclose(np.asarray(sf.ps_weights),
                               np.asarray(su.ps_weights),
                               rtol=1e-4, atol=1e-5)


def test_fused_round_matches_unfused_vmap_path():
    """The per-client table-carry scan (make_client_step): per-client
    grad stats are a blocker by design, so they are off here."""
    kw = dict(fused_clients=False, client_stats=False)
    rt_f, lf, sf = run_rounds(make_cfg(sketch_fused_encode="auto", **kw))
    rt_u, lu, su = run_rounds(make_cfg(sketch_fused_encode="off", **kw))
    assert rt_f._fused_encode and not rt_f._fused
    np.testing.assert_allclose(lf, lu, rtol=FUSED_LOSS_RTOL,
                               atol=FUSED_LOSS_ATOL)
    np.testing.assert_allclose(np.asarray(sf.ps_weights),
                               np.asarray(su.ps_weights),
                               rtol=1e-4, atol=1e-5)


def test_fused_round_zero_datum_client():
    """A fully-masked (zero-datum) client contributes NOTHING to the
    table in both paths — fused == unfused with a benched slot, and the
    benched slot's n_valid stays zero."""
    batch, mask, ids = make_batch(5, W=W, B=B)
    mask = jnp.asarray(np.asarray(mask)).at[1].set(False)

    def run(fe, fused_clients):
        cfg = make_cfg(sketch_fused_encode=fe, fused_clients=fused_clients,
                       client_stats=False)
        rt = FedRuntime(cfg, make_params(), quad_loss, num_clients=16)
        state, m = rt.round(rt.init_state(), ids, batch, mask, 0.1)
        return np.asarray(state.ps_weights), np.asarray(m["n_valid"])

    for fc in (True, False):
        wf, nf = run("auto", fc)
        wu, nu = run("off", fc)
        assert nf[1] == 0 and (nf == nu).all()
        np.testing.assert_allclose(wf, wu, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("kind", ["signflip", "scale"])
def test_fused_encode_with_adversary_injection(kind):
    """Update-space injection acts on the TABLE under the fused encode
    (the per-client transmitted quantity) — and because signflip/scale
    commute with the linear encode, the attacked fused round still
    matches the attacked unfused round within fp tolerance."""
    kw = dict(fused_clients=False, client_stats=False, adversary=kind,
              adversary_frac=0.6, adversary_scale=5.0)
    rt_f, lf, sf = run_rounds(make_cfg(sketch_fused_encode="auto", **kw))
    rt_u, lu, su = run_rounds(make_cfg(sketch_fused_encode="off", **kw))
    assert rt_f._fused_encode and rt_f._adv_inject
    np.testing.assert_allclose(lf, lu, rtol=FUSED_LOSS_RTOL,
                               atol=FUSED_LOSS_ATOL)
    np.testing.assert_allclose(np.asarray(sf.ps_weights),
                               np.asarray(su.ps_weights),
                               rtol=1e-4, atol=1e-5)


def test_fused_encode_table_frobenius_clip_stays_available():
    """--max_grad_norm WITHOUT --sketch_dense_clip is the per-client
    table-Frobenius clip — a per-table op the fused path keeps (the
    reference semantics, fed_worker.py:318)."""
    kw = dict(max_grad_norm=0.05, fused_clients=False, client_stats=False)
    rt_f, lf, _ = run_rounds(make_cfg(sketch_fused_encode="auto", **kw))
    rt_u, lu, _ = run_rounds(make_cfg(sketch_fused_encode="off", **kw))
    assert rt_f._fused_encode
    np.testing.assert_allclose(lf, lu, rtol=FUSED_LOSS_RTOL,
                               atol=FUSED_LOSS_ATOL)


# ----------------------------------------------------- soundness / fail-fast


def test_fused_encode_blockers_unit():
    assert fused_encode_blockers(make_cfg()) == []
    assert fused_encode_blockers(make_cfg(mode="uncompressed",
                                          error_type="none"))
    assert any("sketch_dense_clip" in p for p in fused_encode_blockers(
        make_cfg(sketch_dense_clip=True, max_grad_norm=1.0)))
    assert any("privacy" in p for p in fused_encode_blockers(
        make_cfg(do_dp=True, noise_multiplier=0.1)))
    # --signals_exact blocks only when the signal diagnostics are LIVE
    assert any("signals_exact" in p for p in fused_encode_blockers(
        make_cfg(signals_exact=True), signals=True))
    assert fused_encode_blockers(make_cfg(signals_exact=True),
                                 signals=False) == []


def test_fused_encode_on_fails_fast_with_explanation():
    for kw, needle in ((dict(sketch_dense_clip=True, max_grad_norm=1.0),
                        "sketch_dense_clip"),
                       (dict(do_dp=True, noise_multiplier=0.1),
                        "privacy"),
                       (dict(signals_exact=True), "signals_exact")):
        with pytest.raises(ValueError, match=needle):
            FedRuntime(make_cfg(sketch_fused_encode="on", **kw),
                       make_params(), quad_loss, num_clients=16)
    # ... and auto with the same blockers silently falls back (the
    # fallback IS the pre-fusion path)
    rt = FedRuntime(make_cfg(sketch_fused_encode="auto",
                             sketch_dense_clip=True, max_grad_norm=1.0),
                    make_params(), quad_loss, num_clients=16)
    assert not rt._fused_encode


def test_fused_encode_on_requires_sketch_mode():
    with pytest.raises(ValueError, match="mode sketch"):
        make_cfg(mode="uncompressed", error_type="none",
                 sketch_fused_encode="on")


def test_fused_encode_auto_with_blocker_hlo_identical_to_off():
    """auto's fallback must BE the old round: byte-identical HLO to the
    explicit off spelling (numerics never change silently), and the
    fused encode must be invisible to non-sketch modes entirely."""
    batch, mask, ids = make_batch(0, W=W, B=B)
    for kw in (dict(sketch_dense_clip=True, max_grad_norm=1.0),
               dict(mode="uncompressed", error_type="none")):
        rt_a = FedRuntime(make_cfg(sketch_fused_encode="auto", **kw),
                          make_params(), quad_loss, num_clients=16)
        rt_o = FedRuntime(make_cfg(sketch_fused_encode="off", **kw),
                          make_params(), quad_loss, num_clients=16)
        args = (rt_a.init_state(), ids, batch, mask,
                jnp.asarray(0.1, jnp.float32), rt_a.cs)
        assert (rt_a._round.lower(*args).as_text()
                == rt_o._round.lower(*args).as_text()), kw
    # sanity: where the fused encode ENGAGES, the lowering does change
    rt_on = FedRuntime(make_cfg(sketch_fused_encode="auto"),
                       make_params(), quad_loss, num_clients=16)
    rt_off = FedRuntime(make_cfg(sketch_fused_encode="off"),
                        make_params(), quad_loss, num_clients=16)
    args = (rt_on.init_state(), ids, batch, mask,
            jnp.asarray(0.1, jnp.float32), rt_on.cs)
    assert (rt_on._round.lower(*args).as_text()
            != rt_off._round.lower(*args).as_text())


# ----------------------------------------------------- byte-count accounting


@pytest.mark.parametrize("n_thresholds", [1, 4, 8])
@pytest.mark.parametrize("d", [100, 1553, 2048, 7 * 1024, 3 * 1024 + 5,
                               8 * 128 + 1])
def test_download_coord_counts_matches_numpy(d, n_thresholds):
    """The byte ledger's count (one fused read of the vector, PR 37)
    against the obvious numpy reference, under jit: lengths on and off
    the TPU's 1,024-wide tile, marks that include -1 (never updated, and
    what mesh padding holds), thresholds that include -1 (counts every
    coordinate), 0, the highest mark, one above it (counts none) and
    repeats."""
    rng = np.random.RandomState(d + n_thresholds)
    hi = 39
    marks = rng.randint(-1, hi + 1, (d,)).astype(np.int32)
    marks[rng.randint(0, d, 3)] = [-1, 0, hi]
    thr = np.asarray([hi, -1, 0, hi + 1, 3, hi, 3, 17][:n_thresholds],
                     np.int32)
    got = jax.jit(FedRuntime._download_coord_counts)(jnp.asarray(marks),
                                                     jnp.asarray(thr))
    assert got.dtype == jnp.int32 and got.shape == (n_thresholds,)
    ref = (marks[None, :] >= thr[:, None]).sum(axis=1)
    np.testing.assert_array_equal(np.asarray(got), ref)
    if n_thresholds >= 4:
        assert ref[1] == d and ref[3] == 0


class _LedgerModel:
    """The byte ledger in numpy: what a client downloads is 4 bytes a
    coordinate marked at or after its last round, a round marks the
    coordinates its update changed."""

    def __init__(self, rt):
        self.n, self.upload = rt.num_clients, rt.cfg.upload_wire_bytes()
        self.marks = np.full((rt.d_pad,), -1, np.int32)
        self.last_round = np.zeros((self.n,), np.int32)
        self.step = 0

    def dispatch(self, ids):
        counts = (self.marks[None, :]
                  >= self.last_round[ids][:, None]).sum(axis=1)
        self.down, self.up = (np.zeros((self.n,), np.float32)
                              for _ in range(2))
        self.down[ids], self.up[ids] = 4.0 * counts, self.upload
        self.last_round[ids] = self.step

    def commit(self, update):
        self.marks = np.where(update != 0, self.step,
                              self.marks).astype(np.int32)
        self.step += 1

    def check(self, state, out=None):
        np.testing.assert_array_equal(np.asarray(state.coord_last_update),
                                      self.marks)
        np.testing.assert_array_equal(np.asarray(state.client_last_round),
                                      self.last_round)
        if out is not None:
            np.testing.assert_array_equal(
                np.asarray(out["download_bytes"]), self.down)
            np.testing.assert_array_equal(
                np.asarray(out["upload_bytes"]), self.up)


def _spy_server_half(rt):
    """Record, round by round, the padded update the server half applied
    and its k-sparse form (None where the rule hands none over)."""
    seen, inner = [], rt._server_half

    def spy(*args, **kw):
        srv = inner(*args, **kw)
        jax.debug.callback(
            lambda padded, support: seen.append(
                (np.asarray(padded),
                 support and tuple(map(np.asarray, support)))),
            srv.applied, srv.support)
        return srv

    rt._server_half = spy
    return seen


LEDGER_CASES = {
    # name: (config, lr is a per-parameter vector, mesh devices)
    "sketch_scalar_lr": (dict(), False, 0),
    "sketch_lr_vector": (dict(), True, 0),
    "true_topk": (dict(mode="true_topk", k=20), False, 0),
    "uncompressed": (dict(mode="uncompressed", error_type="none"), False, 0),
    "sketch_scalar_lr_mesh": (dict(), False, 4),
    "true_topk_mesh": (dict(mode="true_topk", k=20), False, 4),
    "async_cohort_commit": (dict(async_agg=True, max_inflight=1,
                                 buffer_goal=1), False, 0),
}


@pytest.mark.parametrize("case", list(LEDGER_CASES))
def test_byte_ledger_trajectory_matches_numpy(case):
    """Four rounds through the runtime's own entry points: after every
    round ``coord_last_update``, ``client_last_round`` and the byte
    vectors equal the numpy model's, whose marks are ``where(update !=
    0, step, old)`` of the update the server half applied, be it dense
    or the scatter of its k winners. The third round's lr is 0 (an lr
    vector: a third of its entries always are), so winners whose value
    is exactly zero are met and not marked; a parameter the loss never
    reads gives true top-k zero winners in every round; mesh padding
    past d stays -1."""
    extra, lr_vector, n_mesh = LEDGER_CASES[case]
    cfg = make_cfg(**extra)
    params = {**make_params(), "unused": jnp.ones((3,), jnp.float32)}
    mesh = make_mesh((n_mesh,), ("clients",)) if n_mesh else None
    rt = FedRuntime(cfg, params, quad_loss, num_clients=16, mesh=mesh)
    d = rt.cfg.grad_size
    assert d == 21 and rt.d_pad == (24 if n_mesh else d)
    seen = _spy_server_half(rt)
    model = _LedgerModel(rt)
    state = rt.init_state()
    model.check(state)
    batch, mask, _ = make_batch(3, W=W, B=B)
    if mesh is not None:
        # the W thresholds and counts are whole on every chip: read off
        # or stacked into a vector sharded by client, each scalar was a
        # collective-permute of its own (24 in the ResNet-50 mesh round)
        hlo = rt._round.lower(state, jnp.arange(W, dtype=jnp.int32), batch,
                              mask, rt._prep_lr(0.1), rt.cs).compile()
        assert " collective-permute" not in hlo.as_text()
    for r, scale in enumerate([0.1, 0.1, 0.0, 0.1]):
        # clients 0-3, 3-6, 6-9, 9-12: one repeats from round to round
        ids = (np.arange(W) + 3 * r).astype(np.int32)
        lr = scale
        if lr_vector:
            lr = np.full((d,), 0.1, np.float32)
            lr[r % 3::3] = 0.0
        model.dispatch(ids)
        if cfg.async_agg:
            state, out = rt.cohort(state, ids, batch, mask, lr)
            model.check(state)          # dispatch-time half alone
            state = rt.merge_first(state, out["sum"], out["n_total"])
            state, _ = rt.commit(state, lr)
        else:
            state, out = rt.round(state, ids, batch, mask, lr)
        jax.effects_barrier()
        assert len(seen) == r + 1
        padded, support = seen[-1]
        assert padded.shape == (rt.d_pad,) and not padded[d:].any()
        # the k winners once more, where the rule selects them and one
        # scalar lr scales them (core/server.py:_support)
        assert (support is None) == (lr_vector
                                     or cfg.mode == "uncompressed")
        if support is not None:
            idx, vals = support
            dense = np.zeros_like(padded)
            dense[idx[idx < d]] = vals[idx < d]
            np.testing.assert_array_equal(dense, padded)
            if scale == 0.0 or cfg.mode == "true_topk":
                assert (vals == 0).any()
        model.commit(padded)
        model.check(state, out)
    assert (model.marks[:d] >= 0).any() and (model.marks[d:] == -1).all()
    assert int(state.step) == 4
