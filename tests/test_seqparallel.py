"""Sequence/context parallelism: the federated GPT-2 round with the model
seq-sharded over a ("clients", "seq") mesh (ring attention) must match the
dense single-device round, and must cut per-device attention memory for
long sequences. New scope beyond the reference (SURVEY.md §5: no sequence
parallelism anywhere)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.config import FedConfig
from commefficient_tpu.core import FedRuntime
from commefficient_tpu.gpt2_train import PERSONA_SEQ_SPEC
from commefficient_tpu.losses import make_gpt2_train_loss
from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads
from commefficient_tpu.parallel import make_mesh

W, B, C = 2, 2, 2


def _batch(S, seed=0):
    rng = np.random.RandomState(seed)
    return {
        "input_ids": jnp.asarray(rng.randint(0, 256, (W, B, C, S)),
                                 jnp.int32),
        "token_type_ids": jnp.asarray(rng.randint(0, 256, (W, B, C, S)),
                                      jnp.int32),
        "mc_token_ids": jnp.asarray(rng.randint(0, S, (W, B, C)),
                                    jnp.int32),
        "lm_labels": jnp.asarray(
            np.where(rng.rand(W, B, C, S) < 0.5,
                     rng.randint(0, 256, (W, B, C, S)), -100), jnp.int32),
        "mc_label": jnp.asarray(rng.randint(0, C, (W, B)), jnp.int32),
    }


def _runtimes(S, mode="uncompressed", extra=None):
    gcfg = GPT2Config.small(compute_dtype=jnp.float32,
                            n_positions=max(128, S))
    dense_model = GPT2DoubleHeads(gcfg)
    ids = jnp.zeros((1, C, S), jnp.int32)
    params = dense_model.init(jax.random.PRNGKey(0), ids,
                              jnp.zeros((1, C), jnp.int32), ids)

    cfg = FedConfig(mode=mode, local_momentum=0.0, virtual_momentum=0.9,
                    weight_decay=0.01, num_workers=W, local_batch_size=B,
                    num_clients=4, track_bytes=False, num_results_train=2,
                    error_type=("virtual" if mode in ("sketch", "true_topk")
                                else "none"), **(extra or {}))

    rt_dense = FedRuntime(cfg, params, make_gpt2_train_loss(dense_model),
                          num_clients=4)

    mesh = make_mesh((2, 4), ("clients", "seq"))
    seq_model = GPT2DoubleHeads(gcfg, seq_axis="seq", seq_shards=4)
    loss_seq = make_gpt2_train_loss(seq_model, seq_axis="seq",
                                    seq_shards=4)
    rt_seq = FedRuntime(cfg, params, loss_seq, num_clients=4, mesh=mesh,
                        seq_spec=PERSONA_SEQ_SPEC)
    return rt_dense, rt_seq


@pytest.mark.parametrize("mode,extra", [
    ("uncompressed", {}),
    ("sketch", {"k": 20, "num_rows": 3, "num_cols": 64, "num_blocks": 2}),
    ("true_topk", {"k": 20}),
])
@pytest.mark.slow
def test_seq_sharded_round_matches_dense(mode, extra):
    rt_dense, rt_seq = _runtimes(S=32, mode=mode, extra=extra)
    ids = jnp.arange(W, dtype=jnp.int32)
    mask = jnp.ones((W, B), bool)
    s1, s2 = rt_dense.init_state(), rt_seq.init_state()
    for step in range(2):
        batch = _batch(32, seed=step)
        s1, m1 = rt_dense.round(s1, ids, batch, mask, 0.05)
        s2, m2 = rt_seq.round(s2, ids, batch, mask, 0.05)
        np.testing.assert_allclose(np.asarray(m1["results"][0]),
                                   np.asarray(m2["results"][0]),
                                   rtol=2e-4, atol=1e-5)
    d = rt_dense.cfg.grad_size
    np.testing.assert_allclose(np.asarray(s1.ps_weights),
                               np.asarray(s2.ps_weights[:d]),
                               rtol=2e-3, atol=2e-5)


@pytest.mark.slow
def test_seq_shard_boundary_mc_tokens_and_full_length():
    """Edge coverage (VERDICT r2 item 9): mc_token_ids pinned EXACTLY at
    every seq-shard boundary (first/last position of each shard — the MC
    head's hidden-state select must pick from the right shard), and a
    full n_positions-length sequence, both match the dense round."""
    S = 128  # == n_positions for GPT2Config.small(n_positions=max(128, S))
    rt_dense, rt_seq = _runtimes(S=S)
    assert rt_seq._seq_shards == 4 and S % 4 == 0
    ids = jnp.arange(W, dtype=jnp.int32)
    mask = jnp.ones((W, B), bool)
    batch = _batch(S, seed=7)
    # shard edges: 0, 31, 32, 63, 64, 95, 96, 127 — cycle them through
    # every (worker, dialogue, candidate) slot
    edges = np.array([0, 31, 32, 63, 64, 95, 96, 127], np.int32)
    mc = np.resize(edges, (W, B, C)).astype(np.int32)
    batch["mc_token_ids"] = jnp.asarray(mc)
    s1, m1 = rt_dense.round(rt_dense.init_state(), ids, batch, mask, 0.05)
    s2, m2 = rt_seq.round(rt_seq.init_state(), ids, batch, mask, 0.05)
    np.testing.assert_allclose(np.asarray(m1["results"][0]),
                               np.asarray(m2["results"][0]),
                               rtol=2e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(m1["results"][1]),
                               np.asarray(m2["results"][1]),
                               rtol=2e-4, atol=1e-5)
    d = rt_dense.cfg.grad_size
    np.testing.assert_allclose(np.asarray(s1.ps_weights),
                               np.asarray(s2.ps_weights[:d]),
                               rtol=2e-3, atol=2e-5)


@pytest.mark.slow
def test_long_seq_cuts_attention_memory():
    """The point of the seq axis: a long-S round's per-device temp memory
    must be far below the dense round's (the dense S x S score tensor and
    full-S activations shrink by the shard count)."""
    S = 512
    rt_dense, rt_seq = _runtimes(S=S)
    ids = jnp.arange(W, dtype=jnp.int32)
    mask = jnp.ones((W, B), bool)
    batch = _batch(S)

    def temp_bytes(rt):
        lowered = rt._round.lower(rt.init_state(), ids, batch, mask,
                                  jnp.asarray(0.05, jnp.float32), rt.cs)
        ma = lowered.compile().memory_analysis()
        return ma.temp_size_in_bytes

    dense_b, seq_b = temp_bytes(rt_dense), temp_bytes(rt_seq)
    # 8 devices, seq=4: expect a large cut; assert a conservative 2x
    assert seq_b * 2 < dense_b, (dense_b, seq_b)
