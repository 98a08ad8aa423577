"""The plain references against the program at a tiny size on the CPU, each
with one deliberately wrong variant that must fail the tolerance."""

import numpy as np
import pytest


class Built:
    pass


def _cv_built(seed=0):
    import jax
    import jax.numpy as jnp
    from commefficient_tpu.losses import make_cv_loss
    from commefficient_tpu.models.fixup_resnet import FixupResNetImageNet
    from perfbench.harness.datasets import make_dataset
    b = Built()
    b.model = FixupResNetImageNet(layers=(1, 1, 1, 1), num_classes=10)
    b.params = jax.jit(b.model.init)(jax.random.PRNGKey(seed),
                                     jnp.zeros((1, 32, 32, 3)))
    b.loss_fn = make_cv_loss(b.model, "float32")
    b.dataset = make_dataset(seed, {
        "generator": "images", "num_clients": 4, "per_client": 4,
        "height": 32, "width": 32, "channels": 3, "num_classes": 10,
        "classes_per_client": 2})
    b.store_name = "ImageNet"
    b.model_name = "FixupResNet50"
    b.reference_kw = {"layers": (1, 1, 1, 1)}
    return b


def _gpt2_built(seed=0):
    from perfbench.families import gpt2_doubleheads as fam
    cfg = fam.parse(["--weight_decay", "0", "--lm_chunk", "8",
                     "--max_seq_len", "32", "--compute_dtype", "float32",
                     "--microbatch_size", "2", "--local_batch_size", "2",
                     "--remat"])
    config = {"vocab_size": 251, "n_positions": 64, "n_embd": 32,
              "n_layer": 2, "n_head": 2, "num_added_tokens": 5,
              "layer_norm_epsilon": 1e-5,
              "data": {"generator": "persona", "num_clients": 4,
                       "per_client": 2, "context_tokens": [8, 16],
                       "reply_tokens": [2, 6], "utterance_tokens": 4}}
    return fam, fam.build(cfg, config, seed), cfg


@pytest.mark.parametrize("variant,ok", [(None, True), ("bf16", False)])
def test_resnet_cv_reference(variant, ok):
    from perfbench.families import resnet_cv as fam
    from perfbench.harness import checks
    cfg = fam.parse(["--dataset_name", "ImageNet", "--model",
                     "FixupResNet50", "--compute_dtype", "float32"])
    out = checks.model_step(fam, _cv_built(), cfg, seed=3, variant=variant,
                            n=4)
    assert out["ok"] is ok, out
    if not ok:     # bf16 arithmetic is far outside the float32 tolerance
        assert out["grad_rel_l2"] > 5 * out["tol"]["grad_rel_l2"], out


@pytest.mark.parametrize("variant,ok", [(None, True), ("bf16", False)])
def test_gpt2_doubleheads_reference(variant, ok):
    from perfbench.harness import checks
    fam, built, cfg = _gpt2_built()
    out = checks.model_step(fam, built, cfg, seed=3, variant=variant, n=2)
    assert out["ok"] is ok, out
    if not ok:
        assert out["grad_rel_l2"] > 5 * out["tol"]["grad_rel_l2"], out


def _algebra_cfg(mode):
    from commefficient_tpu.config import parse_args
    flags = ["--mode", mode, "--virtual_momentum", "0.9",
             "--local_momentum", "0", "--num_workers", "4",
             "--local_batch_size", "4", "--compile_cache", ""]
    if mode == "sketch":
        flags += ["--error_type", "virtual", "--num_rows", "5",
                  "--num_cols", "16001", "--exact_num_cols", "--k", "50000",
                  "--approx_topk"]
    else:
        flags += ["--error_type", "none"]
    return parse_args(flags, default_lr=0.4)


@pytest.mark.parametrize("mode,drop,ok", [
    ("sketch", False, True), ("sketch", True, False),
    ("uncompressed", False, True)])
def test_round_algebra(mode, drop, ok):
    """Three rounds of the program's round against the plain server; with
    error feedback dropped from the reference they must part."""
    from perfbench.harness import checks
    out = checks.round_algebra(_algebra_cfg(mode), 200_000, seed=5,
                               drop_error_feedback=drop)
    assert out["ok"] is ok, out
    if mode == "sketch" and ok:
        assert out["agree_share"] == 1.0 and out["recall"] >= 0.9, out
    if drop:       # half of what the second round sends depends on it
        assert out["agree_share"] <= 0.6, out


def test_round_algebra_on_a_mesh():
    import jax
    from commefficient_tpu.parallel import make_mesh
    from perfbench.harness import checks
    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    cfg = _algebra_cfg("sketch").replace(mesh_shape=(4,))
    out = checks.round_algebra(cfg, 200_000, seed=5,
                               mesh=make_mesh(cfg.mesh_shape, cfg.mesh_axes))
    assert out["ok"], out


def test_closed_form_gradient_counts_microbatches():
    from perfbench.harness import checks
    coords, idx, val = checks._planted(1, 1000, 2, 4, 16, 4)
    assert checks.plan(10**6) == (64, 16, 32)
    g1 = checks.closed_form_gradient(coords, idx, val, -1)
    g2 = checks.closed_form_gradient(coords, idx, val, 2)
    np.testing.assert_allclose(g2, 2 * g1)


def test_upload_arithmetic():
    from perfbench.harness import arith
    assert arith.upload_bytes_per_client(
        "sketch", 10**7, (5, 500736)) == 5 * 500736 * 4
    assert arith.upload_bytes_per_client("uncompressed", 25504026) \
        == 4 * 25504026
    pct, bound = arith.roofline_pct(819e9, 1.0, 2.0, {
        "hbm_bytes_per_s": 819e9, "bf16_flops": 197e12})
    assert bound == "memory" and abs(pct - 50.0) < 1e-9
