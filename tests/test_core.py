"""Core round-step tests: golden SGD trajectories and mode equivalences.

Method ported from the reference's (broken) unit_test.py (SURVEY.md §4):
compare against closed-form/numpy SGD trajectories, and exploit the lossless
limits — top-k with k=d and a huge sketch must reproduce uncompressed SGD
exactly (to float tolerance).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.config import FedConfig
from commefficient_tpu.core import FedRuntime

D_FEAT = 6
NUM_CLIENTS = 10
W = 4          # clients per round
B = 8          # local batch size


def loss_fn(params, batch, mask):
    """Masked linear-regression MSE with mean-abs-error metric."""
    x, y = batch["x"], batch["y"]
    pred = x @ params["w"] + params["b"]
    mask = mask.astype(jnp.float32)
    denom = jnp.maximum(mask.sum(), 1.0)
    err = pred - y
    loss = ((err ** 2) * mask).sum() / denom
    mae = (jnp.abs(err) * mask).sum() / denom
    return loss, (mae,)


def init_params(seed=0):
    rng = np.random.RandomState(seed)
    return {"w": jnp.asarray(rng.randn(D_FEAT).astype(np.float32)),
            "b": jnp.zeros(())}


def make_data(seed=1):
    rng = np.random.RandomState(seed)
    w_true = rng.randn(D_FEAT).astype(np.float32)
    xs = rng.randn(NUM_CLIENTS, B, D_FEAT).astype(np.float32)
    ys = xs @ w_true + 0.01 * rng.randn(NUM_CLIENTS, B).astype(np.float32)
    return xs, ys


def base_cfg(**kw):
    defaults = dict(mode="uncompressed", local_momentum=0.0,
                    virtual_momentum=0.0, weight_decay=0.0,
                    error_type="none", local_batch_size=B,
                    num_workers=W, num_clients=NUM_CLIENTS,
                    num_results_train=2, track_bytes=True)
    defaults.update(kw)
    return FedConfig(**defaults)


def run_rounds(cfg, n_rounds, lr=0.05, seed=3):
    params = init_params()
    xs, ys = make_data()
    rt = FedRuntime(cfg, params, loss_fn, num_clients=NUM_CLIENTS)
    state = rt.init_state()
    rng = np.random.RandomState(seed)
    traj, metrics_hist = [], []
    for _ in range(n_rounds):
        ids = rng.choice(NUM_CLIENTS, W, replace=False).astype(np.int32)
        batch = {"x": jnp.asarray(xs[ids]), "y": jnp.asarray(ys[ids])}
        mask = jnp.ones((W, B))
        state, m = rt.round(state, ids, batch, mask, lr)
        traj.append(np.asarray(state.ps_weights))
        metrics_hist.append(jax.tree.map(np.asarray, m))
    return rt, state, traj, metrics_hist


def numpy_sgd(n_rounds, lr=0.05, seed=3, rho=0.0):
    """Host-side replica of uncompressed federated SGD with virtual momentum
    (reference _server_helper_uncompressed, fed_aggregator.py:497-509)."""
    p = init_params()
    w = np.concatenate([np.asarray(p["b"]).reshape(1), np.asarray(p["w"])])
    # note: ravel_pytree orders dict keys alphabetically: b then w
    xs, ys = make_data()
    rng = np.random.RandomState(seed)
    vel = np.zeros_like(w)
    traj = []
    for _ in range(n_rounds):
        ids = rng.choice(NUM_CLIENTS, W, replace=False)
        x = xs[ids].reshape(-1, D_FEAT)
        y = ys[ids].reshape(-1)
        pred = x @ w[1:] + w[0]
        err = pred - y
        gw = 2 * (x * err[:, None]).mean(0)
        gb = 2 * err.mean()
        g = np.concatenate([[gb], gw])
        vel = g + rho * vel
        w = w - lr * vel
        traj.append(w.copy())
    return traj


class TestGoldenTrajectories:
    def test_uncompressed_matches_numpy(self):
        _, _, traj, _ = run_rounds(base_cfg(), 5)
        expected = numpy_sgd(5)
        for got, want in zip(traj, expected):
            np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-5)

    def test_virtual_momentum_matches_numpy(self):
        _, _, traj, _ = run_rounds(base_cfg(virtual_momentum=0.9), 5)
        expected = numpy_sgd(5, rho=0.9)
        for got, want in zip(traj, expected):
            np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-5)

    def test_true_topk_lossless_matches_uncompressed(self):
        d = D_FEAT + 1
        _, _, traj_t, _ = run_rounds(
            base_cfg(mode="true_topk", error_type="virtual", k=d), 5)
        _, _, traj_u, _ = run_rounds(base_cfg(), 5)
        for got, want in zip(traj_t, traj_u):
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)

    def test_local_topk_lossless_matches_uncompressed(self):
        d = D_FEAT + 1
        _, _, traj_t, _ = run_rounds(
            base_cfg(mode="local_topk", error_type="none", k=d), 5)
        _, _, traj_u, _ = run_rounds(base_cfg(), 5)
        for got, want in zip(traj_t, traj_u):
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)

    @pytest.mark.parametrize("impl,server_state", [
        ("hash", "table"), ("rht", "table"),
        ("hash", "dense"), ("circ", "dense")])
    def test_sketch_lossless_matches_true_topk(self, impl, server_state):
        """Huge table => estimates are near-exact => FetchSGD reduces to
        true top-k (SURVEY.md §4 golden strategy). For the rht impl the
        lossless limit is exact by construction (c == padded size), which
        certifies the dense-preimage support-zeroing rule coincides with
        the reference's cell-masking there (core/server.py); the
        sketch_server_state=dense cases certify the same for the circ/hash
        opt-in pre-image path."""
        d = D_FEAT + 1
        cfg_s = base_cfg(mode="sketch", error_type="virtual", k=d,
                         num_rows=7, num_cols=4096, num_blocks=1,
                         sketch_impl=impl, sketch_server_state=server_state)
        _, _, traj_s, _ = run_rounds(cfg_s, 5)
        _, _, traj_u, _ = run_rounds(base_cfg(), 5)
        for got, want in zip(traj_s, traj_u):
            np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-4)

    def test_fedavg_single_step_matches_sgd(self):
        """One local epoch, whole-client batch => fedavg transmit is exactly
        lr * mean-grad, so the server step equals plain SGD."""
        cfg = FedConfig(mode="fedavg", local_momentum=0.0,
                        virtual_momentum=0.0, weight_decay=0.0,
                        error_type="none", local_batch_size=-1,
                        max_client_batch=B, fedavg_batch_size=-1,
                        num_fedavg_epochs=1, num_workers=W,
                        num_clients=NUM_CLIENTS, num_results_train=2)
        _, _, traj_f, _ = run_rounds(cfg, 3)
        expected = numpy_sgd(3)
        for got, want in zip(traj_f, expected):
            np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-5)


def test_local_topk_matches_reference_sim():
    """scripts/local_topk_sim.py --check: our local_topk trajectory must be
    identical to a straight numpy transcription of the reference's
    fed_worker.py:184-230 + fed_aggregator.py:544-566 dynamics (VERDICT r4
    missing #2 — proves measured local_topk behavior is the algorithm's,
    not a port artifact)."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "local_topk_sim.py"),
         "--check"], capture_output=True, text=True, cwd=root, timeout=300)
    assert "OK: framework local_topk == reference dynamics" in out.stdout, \
        out.stdout + out.stderr


class TestAutoNumCols:
    """VERDICT r4 weak #1: default circulant geometry must hit the Pallas
    fast path; the rounding is pinned here."""

    def test_rounding_values(self):
        from commefficient_tpu.config import auto_num_cols
        assert auto_num_cols(500_000) == 500_736      # reference default
        assert auto_num_cols(524_288) == 524_288      # already aligned
        assert auto_num_cols(500_736) == 500_736
        # tiny test geometries must NOT be inflated (budget bound 5%)
        assert auto_num_cols(320) == 320
        assert auto_num_cols(256) == 256
        assert auto_num_cols(100_000) == 100_352      # +0.35%

    def test_runtime_applies_and_pins(self):
        params = init_params()
        cfg = base_cfg(mode="sketch", error_type="virtual", k=4,
                       num_rows=3, num_cols=100_000, num_blocks=1,
                       sketch_impl="circ")
        rt = FedRuntime(cfg, params, loss_fn, num_clients=NUM_CLIENTS)
        assert rt.cfg.num_cols == 100_352
        assert rt.cfg.num_cols % 1024 == 0
        # byte accounting must reflect the real table
        assert rt.cfg.upload_floats == 3 * 100_352
        rt2 = FedRuntime(cfg.replace(exact_num_cols=True), params, loss_fn,
                         num_clients=NUM_CLIENTS)
        assert rt2.cfg.num_cols == 100_000


class TestSketchEFVariants:
    """The TPU-native error-feedback extensions (config.py sketch_ef /
    error_decay) against the reference zero rule."""

    @pytest.mark.parametrize("impl", ["hash", "circ"])
    def test_subtract_ef_lossless_matches_zero(self, impl):
        """In the lossless limit (no cell collisions for circ; c >> d for
        hash) 'subtract the extracted estimates' and 'zero the occupied
        cells' are the same rule, so the trajectories must coincide."""
        d = D_FEAT + 1
        common = dict(mode="sketch", error_type="virtual", k=d,
                      num_rows=7, num_cols=4096, num_blocks=1,
                      sketch_impl=impl)
        _, _, traj_z, _ = run_rounds(base_cfg(**common), 5)
        _, _, traj_s, _ = run_rounds(
            base_cfg(**common, sketch_ef="subtract"), 5)
        tol = 0 if impl == "circ" else 1e-3
        for got, want in zip(traj_s, traj_z):
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=max(tol, 1e-6))

    def test_subtract_ef_preserves_colliding_error(self):
        """The point of the subtract rule: a coordinate whose cell collides
        with the update's keeps its accumulated error (the zero rule
        destroys it). Direct server_update check on a 1-block circulant
        sketch where collisions are by construction (c < d)."""
        from commefficient_tpu.core.server import server_update
        from commefficient_tpu.ops.circulant import make_circulant_sketch
        d, c, r, k = 64, 16, 3, 1
        cs = make_circulant_sketch(d=d, c=c, r=r, num_blocks=1, seed=3)
        rng = np.random.RandomState(0)
        g = jnp.asarray(0.01 * rng.randn(d).astype(np.float32))
        g = g.at[5].set(10.0)  # one dominant coordinate wins the top-1
        cfg_z = base_cfg(mode="sketch", error_type="virtual", k=k,
                         num_rows=r, num_cols=c, grad_size=d,
                         sketch_impl="circ")
        cfg_s = cfg_z.replace(sketch_ef="subtract")
        table = cs.encode(g)
        zeros = cs.empty_table()
        _, _, verr_z, _, _ = server_update(cfg_z, table, zeros, zeros,
                                        jnp.asarray(1.0), cs=cs)
        _, _, verr_s, _, _ = server_update(cfg_s, table, zeros, zeros,
                                        jnp.asarray(1.0), cs=cs)
        # zero rule wipes r cells entirely; subtract keeps the colliding
        # coordinates' mass: the surviving table mass must be strictly
        # larger under subtract
        assert float(jnp.abs(verr_s).sum()) > float(jnp.abs(verr_z).sum())
        # and the extracted coordinate's estimate is (near-)removed in both
        est_s = float(cs.decode_at(verr_s, jnp.asarray([5]))[0])
        assert abs(est_s) < 1.0  # was 10.0 before extraction

    def test_error_decay_scales_verror(self):
        from commefficient_tpu.core.server import server_update
        d, k = 16, 2
        cfg1 = base_cfg(mode="true_topk", error_type="virtual", k=k,
                        grad_size=d)
        cfg2 = cfg1.replace(error_decay=0.5)
        g = jnp.asarray(np.arange(1.0, d + 1, dtype=np.float32))
        zeros = jnp.zeros((d,), jnp.float32)
        lr = jnp.asarray(1.0)
        u1, v1, e1, _, _ = server_update(cfg1, g, zeros, zeros, lr)
        u2, v2, e2, _, _ = server_update(cfg2, g, zeros, zeros, lr)
        np.testing.assert_allclose(np.asarray(u1), np.asarray(u2))
        np.testing.assert_allclose(np.asarray(e2), 0.5 * np.asarray(e1))


class TestErrorFeedback:
    def test_true_topk_error_accumulates_and_masks(self):
        cfg = base_cfg(mode="true_topk", error_type="virtual", k=2)
        _, state, _, _ = run_rounds(cfg, 4)
        verr = np.asarray(state.Verror)
        # after any round, Verror must be zero on exactly the coords that
        # were just updated (k of them) and generally nonzero elsewhere
        assert (verr == 0).sum() >= 2
        assert (verr != 0).sum() > 0

    @pytest.mark.parametrize("impl", ["hash", "rht"])
    def test_loss_decreases(self, impl):
        cfg = base_cfg(mode="sketch", error_type="virtual", k=4,
                       num_rows=5, num_cols=256, num_blocks=1,
                       sketch_impl=impl)
        _, _, _, hist = run_rounds(cfg, 20, lr=0.05)
        first = hist[0]["results"][0].mean()
        last = hist[-1]["results"][0].mean()
        assert last < first * 0.5, (first, last)


class TestByteAccounting:
    def test_first_round_download_is_zero(self):
        _, _, _, hist = run_rounds(base_cfg(), 3)
        assert hist[0]["download_bytes"].sum() == 0

    def test_dense_update_downloads_full_model(self):
        d = D_FEAT + 1
        _, _, _, hist = run_rounds(base_cfg(), 3, seed=5)
        # by round 2+, participants that sat out exactly one dense update
        # download the whole model: 4 bytes * d
        later = hist[1]["download_bytes"]
        nz = later[later > 0]
        assert np.all(nz == 4 * d), nz

    def test_upload_matches_mode_table(self):
        # reference upload table fed_aggregator.py:291-299
        d = D_FEAT + 1
        _, _, _, hist = run_rounds(base_cfg(), 1)
        up = hist[0]["upload_bytes"]
        assert np.all(up[up > 0] == 4 * d)
        _, _, _, hist = run_rounds(
            base_cfg(mode="local_topk", error_type="none", k=3), 1)
        up = hist[0]["upload_bytes"]
        assert np.all(up[up > 0] == 4 * 3)
        _, _, _, hist = run_rounds(
            base_cfg(mode="sketch", error_type="virtual", k=3,
                     num_rows=3, num_cols=64, num_blocks=1), 1)
        up = hist[0]["upload_bytes"]
        assert np.all(up[up > 0] == 4 * 3 * 64)

    def test_sparse_update_downloads_only_changed(self):
        cfg = base_cfg(mode="true_topk", error_type="virtual", k=2)
        _, _, _, hist = run_rounds(cfg, 4, seed=7)
        later = hist[1]["download_bytes"]
        nz = later[later > 0]
        # a client stale by exactly one top-k(k=2) update downloads 8 bytes
        assert nz.size > 0 and np.all(nz <= 4 * 2 * 2), nz


class TestLocalState:
    def test_local_momentum_rows_update_only_for_participants(self):
        cfg = base_cfg(mode="local_topk", error_type="local", k=3,
                       local_momentum=0.9)
        params = init_params()
        xs, ys = make_data()
        rt = FedRuntime(cfg, params, loss_fn, num_clients=NUM_CLIENTS)
        state = rt.init_state()
        ids = np.array([1, 3, 5, 7], np.int32)
        batch = {"x": jnp.asarray(xs[ids]), "y": jnp.asarray(ys[ids])}
        state, _ = rt.round(state, ids, batch, jnp.ones((W, B)), 0.05)
        vel = np.asarray(state.client_velocities)
        err = np.asarray(state.client_errors)
        for c in range(NUM_CLIENTS):
            if c in ids:
                assert np.abs(vel[c]).sum() > 0
            else:
                assert np.abs(vel[c]).sum() == 0
                assert np.abs(err[c]).sum() == 0

    def test_microbatching_equivalence(self):
        """microbatch_size splitting scales the accumulated grad by
        num_iters (reference semantics, fed_worker.py:266-287): with lr
        scaled down by the same factor the trajectory must match."""
        _, _, traj_a, _ = run_rounds(base_cfg(microbatch_size=B), 3, lr=0.05)
        _, _, traj_b, _ = run_rounds(base_cfg(microbatch_size=B // 2), 3,
                                     lr=0.025)
        for got, want in zip(traj_b, traj_a):
            np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-5)


class TestPerParamLR:
    def test_vector_lr_scales_update_per_coordinate(self):
        """The reference's Fixup param groups yield a per-parameter LR
        vector from FedOptimizer.get_lr (fed_aggregator.py:411-427); our
        round accepts a (d,) lr and must scale each coordinate's update by
        its own rate — equivalent to running with scalar lr and rescaling."""
        cfg = base_cfg()
        params = init_params()
        xs, ys = make_data()
        rt = FedRuntime(cfg, params, loss_fn, num_clients=NUM_CLIENTS)
        d = rt.cfg.grad_size
        mult = np.ones(d, np.float32)
        mult[: d // 2] = 0.1
        ids = np.arange(W, dtype=np.int32)
        batch = {"x": jnp.asarray(xs[ids]), "y": jnp.asarray(ys[ids])}
        mask = jnp.ones((W, B))

        s_vec = rt.init_state()
        s_vec, _ = rt.round(s_vec, ids, batch, mask, 0.05 * mult)
        s_ref = rt.init_state()
        s_ref, _ = rt.round(s_ref, ids, batch, mask, 0.05)

        w0 = np.asarray(rt.init_state().ps_weights)
        upd_vec = w0 - np.asarray(s_vec.ps_weights)
        upd_ref = w0 - np.asarray(s_ref.ps_weights)
        np.testing.assert_allclose(upd_vec, upd_ref * mult,
                                   rtol=1e-5, atol=1e-7)


class TestNanFlag:
    """Device-side divergence flag (VERDICT r1 next #8): nan_round records
    the FIRST round whose loss/gradient/update went non-finite, without any
    per-round host fetch."""

    def test_records_first_bad_round(self):
        cfg = base_cfg()
        rt = FedRuntime(cfg, init_params(), loss_fn,
                        num_clients=NUM_CLIENTS)
        state = rt.init_state()
        xs, ys = make_data()
        ids = np.arange(W, dtype=np.int32)
        good = {"x": jnp.asarray(xs[ids]), "y": jnp.asarray(ys[ids])}
        bad = {"x": good["x"].at[0, 0, 0].set(jnp.nan), "y": good["y"]}
        mask = jnp.ones((W, B))

        state, _ = rt.round(state, ids, good, mask, 0.05)
        assert int(state.nan_round) == -1
        state, _ = rt.round(state, ids, bad, mask, 0.05)
        assert int(state.nan_round) == 1
        # weights are now poisoned; later rounds stay flagged at round 1
        state, _ = rt.round(state, ids, good, mask, 0.05)
        assert int(state.nan_round) == 1

    def test_train_loop_aborts_without_checkpoint(self, tmp_path):
        """The driver epoch loop reports the offending round and refuses to
        write a checkpoint of poisoned state."""
        from commefficient_tpu import models
        from commefficient_tpu.checkpoint import CheckpointManager
        from commefficient_tpu.cv_train import train
        from commefficient_tpu.data import FedCIFAR10, transforms_for
        from commefficient_tpu.losses import make_cv_loss

        ds = FedCIFAR10(str(tmp_path / "d"), synthetic=True,
                        synthetic_per_class=4,
                        transform=transforms_for("CIFAR10", False))
        cfg = FedConfig(mode="uncompressed", error_type="none",
                        local_momentum=0.0, virtual_momentum=0.0,
                        num_workers=2, local_batch_size=4,
                        num_clients=ds.num_clients, num_epochs=1.0,
                        track_bytes=False, compute_dtype="float32",
                        checkpoint_every=1)
        model = models.ResNet9(num_classes=10,
                               channels={"prep": 2, "layer1": 2,
                                         "layer2": 2, "layer3": 2})
        params = model.init(jax.random.PRNGKey(0),
                            jnp.ones((1, 32, 32, 3)))
        # poison the initial weights: every round's update is non-finite
        params = jax.tree.map(lambda t: t * jnp.nan, params)
        rt = FedRuntime(cfg, params, make_cv_loss(model, "float32"),
                        num_clients=ds.num_clients)
        mgr = CheckpointManager(str(tmp_path / "ck"))
        state, summary = train(cfg, rt, rt.init_state(), ds, ds,
                               ckpt_mgr=mgr)
        assert summary is None            # aborted
        assert int(state.nan_round) == 0  # flagged on the very first round
        assert mgr.epochs() == []         # nothing persisted


def test_subtract_ef_rejected_on_dense_preimage_paths():
    """--sketch_ef subtract is a TABLE-space rule; the dense-preimage
    server paths (sketch_server_state=dense, and rht's dense transform)
    would silently ignore it — they must refuse instead (ADVICE.md)."""
    from commefficient_tpu.core.server import validate_mode_combo
    common = dict(mode="sketch", error_type="virtual", local_momentum=0.0,
                  k=5, num_rows=2, num_cols=32)
    # the legal study configurations still validate
    validate_mode_combo(FedConfig(**common, sketch_ef="subtract"))
    validate_mode_combo(FedConfig(**common, sketch_ef="subtract",
                                  sketch_impl="hash"))
    validate_mode_combo(FedConfig(**common, sketch_server_state="dense"))
    with pytest.raises(ValueError, match="sketch_ef subtract"):
        validate_mode_combo(FedConfig(**common, sketch_ef="subtract",
                                      sketch_server_state="dense"))
    with pytest.raises(ValueError, match="sketch_ef subtract"):
        validate_mode_combo(FedConfig(**common, sketch_ef="subtract",
                                      sketch_impl="rht"))
    # and the runtime constructor (both drivers' entry point) enforces it
    params = {"w": jnp.zeros((4, 2), jnp.float32)}
    with pytest.raises(ValueError, match="sketch_ef subtract"):
        FedRuntime(FedConfig(**common, num_workers=2, local_batch_size=2,
                             sketch_ef="subtract",
                             sketch_server_state="dense"),
                   params, loss_fn, num_clients=4)
