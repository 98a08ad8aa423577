"""models/joyai.py (latent attention at two head widths, a sigmoid router
with a selection bias over a routed expert layer that holds a share of its
experts, a multi-token-prediction module and its second loss) against its
plain reference, at tiny widths on the CPU; its loss, its counters, and one
federated sketch round through FedRuntime."""

import dataclasses
import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.losses import make_joyai_loss
from commefficient_tpu.models import gpt2 as attn
from commefficient_tpu.models.joyai import (ROUND_COUNTERS, JoyAIConfig,
                                            JoyAILM, interleaved_rope,
                                            joyai_model_flops)
from commefficient_tpu.models.laguna import RopeSpec, rope_tables
from commefficient_tpu.models.layers import MOE_COUNTERS, ExpertLayer
from perfbench.families import joyai_moe as fam
from perfbench.families import joyai_moe_reference as ref
from perfbench.harness import checks

CONFIG_FILE = "perfbench/configs/joyai_flash_share32.json"


def tiny(layers=5, held=(0, 4), experts=16, top_k=4):
    """A config.json in the published key set, every width tiny."""
    return {
        "vocab_size": 64, "hidden_size": 32, "intermediate_size": 64,
        "num_hidden_layers": layers, "num_attention_heads": 2,
        "num_key_value_heads": 2, "q_lora_rank": 24, "kv_lora_rank": 16,
        "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "qk_head_dim": 12,
        "v_head_dim": 8, "rms_norm_eps": 1e-6, "rope_theta": 32000000,
        "rope_interleave": True, "rope_scaling": None,
        "first_k_dense_replace": 1, "moe_layer_freq": 1,
        "n_routed_experts": held[1] - held[0],
        "n_routed_experts_published": experts, "experts_held": list(held),
        "n_shared_experts": 1, "num_experts_per_tok": top_k,
        "moe_intermediate_size": 16, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
        "topk_group": 1, "norm_topk_prob": True,
        "num_nextn_predict_layers": 1,
        "data": {"generator": "persona", "num_clients": 4, "per_client": 2,
                 "context_tokens": [8, 16], "reply_tokens": [2, 6],
                 "utterance_tokens": 4},
    }


def built(config, dtype, seed=3):
    cfg = fam.parse(["--weight_decay", "0", "--lm_chunk", "8",
                     "--num_candidates", "1", "--max_seq_len",
                     "32", "--compute_dtype", dtype, "--local_batch_size",
                     "1", "--microbatch_size", "1"])
    return cfg, fam.build(cfg, config, seed)


def _flat(tree):
    return jnp.concatenate([x.reshape(-1).astype(jnp.float32)
                            for x in jax.tree.leaves(tree)])


def _seed_biases(params, scale=0.02):
    """The selection biases start at zero; give them values that change
    which experts are chosen (of the size of the spread of the sigmoid
    scores at these weights, so that the choice still follows the
    token)."""
    def put(path, leaf):
        if path[-1].key != "e_score_correction_bias":
            return leaf
        key = jax.random.PRNGKey(abs(hash(jax.tree_util.keystr(path)))
                                 % 2**31)
        return scale * jax.random.normal(key, leaf.shape, leaf.dtype)
    return jax.tree_util.tree_map_with_path(put, params)


@functools.lru_cache(maxsize=None)
def _case(layers):
    """One seeded model with non-zero selection biases, one item, and the
    plain reference's loss and gradient on them (compiled once for both
    compute dtypes)."""
    cfg, b = built(tiny(layers), "float32")
    b.params = _seed_biases(b.params)
    batch, mask = fam.sample_batch(b, 1, 5), jnp.ones((1,), bool)
    loss, grad = jax.jit(jax.value_and_grad(fam.reference_loss(b, cfg)))(
        b.params, batch, mask)
    return cfg, b, batch, mask, float(loss), _flat(grad)


def _errors(loss_fn, params, batch, mask, ref_loss, ref_grad):
    loss, grad = jax.jit(jax.value_and_grad(
        lambda p, bb, m: loss_fn(p, bb, m)[0]))(params, batch, mask)
    return (float(jnp.linalg.norm(_flat(grad) - ref_grad)
                  / jnp.linalg.norm(ref_grad)),
            abs(float(loss) - ref_loss) / abs(ref_loss))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("layers", [7, 5], ids=["dense+6sparse", "cut5"])
def test_program_matches_the_plain_reference(layers, dtype):
    """Loss (both terms) and gradient on seeded weights, under the
    harness's own tolerances (perfbench/harness/checks.MODEL_TOL)."""
    cfg, b, batch, mask, ref_loss, ref_grad = _case(layers)
    assert b.lcfg.num_hidden_layers == layers
    model = JoyAILM(dataclasses.replace(b.lcfg,
                                        compute_dtype=jnp.dtype(dtype)))
    grad_err, loss_err = _errors(make_joyai_loss(model, b.pad_id, 8),
                                 b.params, batch, mask, ref_loss, ref_grad)
    tol = checks.MODEL_TOL[dtype]
    assert grad_err <= tol["grad_rel_l2"] and loss_err <= tol[
        "loss_rel"], (grad_err, loss_err)
    if dtype == "float32":      # same arithmetic, another order of sums
        assert grad_err < 2e-5, grad_err
    else:                       # and bf16 is not float32 in disguise
        assert grad_err > 1e-4, grad_err


@pytest.mark.parametrize("variant", ["bf16", "no_mtp", "bias_in_weights"])
def test_a_wrong_reference_fails_the_float32_tolerance(variant):
    """The harness's own comparison (perturbed weights, so the zero
    biases move), against the deliberately wrong references: bfloat16
    operands; the second loss left out; the bias in the weights."""
    cfg, b = _case(5)[:2]
    good = checks.model_step(fam, b, cfg, seed=5, n=1)
    assert good["ok"], good
    out = checks.model_step(fam, b, cfg, seed=5, variant=variant, n=1)
    assert not out["ok"], out
    assert (out["grad_rel_l2"] > 5 * out["tol"]["grad_rel_l2"]
            or out["loss_rel"] > 5 * out["tol"]["loss_rel"]), out


def _expert_layer(config, held, x, params, valid=None):
    """The program's ExpertLayer for the share ``held`` of ``params`` (the
    whole layer's: router and bias at full width, stacked experts)."""
    lcfg = JoyAIConfig.from_hf({**config, "experts_held": list(held)},
                               compute_dtype=jnp.float32)
    return ExpertLayer(lcfg).apply({"params": _share(params, held)}, x,
                                   valid)


def _share(params, held):
    lo, hi = held
    return {k: (v[lo:hi] if k.startswith("experts_") else v)
            for k, v in params.items()}


def _whole_layer_params(config, seed, scale=0.3):
    E, I = config["hidden_size"], config["moe_intermediate_size"]
    n = config["n_routed_experts_published"]
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return {"router": scale * jax.random.normal(ks[0], (E, n)),
            "e_score_correction_bias": scale * jax.random.normal(ks[4],
                                                                 (n,)),
            "experts_gate": scale * jax.random.normal(ks[1], (n, E, I)),
            "experts_up": scale * jax.random.normal(ks[2], (n, E, I)),
            "experts_down": scale * jax.random.normal(ks[3], (n, I, E))}


def _mm(a, b):
    with jax.default_matmul_precision("highest"):
        return a @ b


def test_the_32_shares_of_one_sparse_layer_add_up_to_the_whole():
    """Guide section 4, at the deployment's own division: the partial
    outputs of all 32 shares of a 256-expert layer (8 experts each, top
    8), and the shared expert counted once, are the uncut reference's
    output."""
    config = tiny(5, held=(0, 8), experts=256, top_k=8)
    p = _whole_layer_params(config, 1)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, 32))
    shared = {n: {"kernel": 0.3 * jax.random.normal(
        jax.random.PRNGKey(7 + i), s)} for i, (n, s) in enumerate(
            [("gate_proj", (32, 16)), ("up_proj", (32, 16)),
             ("down_proj", (16, 32))])}
    whole = dict(config, n_routed_experts=256)
    flat = x.reshape(-1, 32)
    want = (ref._experts(flat, p, whole, (0, 256), _mm)
            + ref._swiglu(flat, shared, _mm))
    got = ref._swiglu(flat, shared, _mm)
    shares = 0.0
    for lo in range(0, 256, 8):
        y, counts = _expert_layer(config, (lo, lo + 8), x, p)
        got = got + y.reshape(-1, 32)
        shares += float(counts["held_share"])
        assert float(counts["dropped"]) == 0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert abs(shares - 1.0) < 1e-6      # every routed slot has one home


def test_the_bias_changes_the_choice_and_never_the_weights():
    """With the bias, other experts are chosen than without; each chosen
    expert's weight is its sigmoid score over the chosen scores' sum, in
    which the bias has no part; and no gradient reaches the bias."""
    config = tiny(5, held=(0, 16), experts=16, top_k=4)
    whole = dict(config, n_routed_experts=16)
    p = _whole_layer_params(config, 3)
    x = jax.random.normal(jax.random.PRNGKey(4), (48, 32))
    score = jax.nn.sigmoid(_mm(x, p["router"]))
    with_b = np.asarray(jax.lax.top_k(
        score + p["e_score_correction_bias"], 4)[1])
    without = np.asarray(jax.lax.top_k(score, 4)[1])
    assert (np.sort(with_b, -1) != np.sort(without, -1)).any(-1).mean() > 0.3
    # the layer is the reference, whose weights leave the bias out, and
    # is neither the reference with the bias in the weights nor without
    # a bias at all
    y, counts = _expert_layer(config, (0, 16), x, p)
    want = ref._experts(x, p, whole, (0, 16), _mm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    for wrong in (
            ref._experts(x, p, whole, (0, 16), _mm, "bias_in_weights"),
            ref._experts(x, dict(p, e_score_correction_bias=jnp.zeros(16)),
                         whole, (0, 16), _mm)):
        assert np.abs(np.asarray(wrong - want)).max() > 1e-2
    # the tokens each expert is given are those of the biased choice
    np.testing.assert_array_equal(
        np.asarray(counts["tokens"]),
        (with_b[..., None] == np.arange(16)).any(1).sum(0))

    def layer_sum(q):
        return (_expert_layer(config, (0, 16), x, q)[0] ** 2).sum()

    grads = jax.grad(layer_sum)(p)
    assert float(jnp.abs(grads["e_score_correction_bias"]).max()) == 0.0
    assert float(jnp.abs(grads["router"]).max()) > 0


@pytest.mark.parametrize("case", ["all_to_one_held", "none_to_any_held"])
def test_a_skewed_router_drops_nothing(case):
    """Every token on one held expert (by the bias alone), and no token on
    any: both match the reference and the counters say so."""
    config = tiny(5, held=(4, 8), experts=32, top_k=2)
    p = _whole_layer_params(config, 4)
    x = jax.random.normal(jax.random.PRNGKey(5), (64, 32))
    col = jnp.arange(32)
    held = (col >= 4) & (col < 8)
    if case == "all_to_one_held":      # expert 5 first, the rest unheld
        bias = jnp.where(col == 5, 4.0, jnp.where(held, -4.0, 0.0))
    else:
        bias = jnp.where(held, -4.0, 0.0)
    p["e_score_correction_bias"] = bias
    y, counts = _expert_layer(config, (4, 8), x, p)
    want = ref._experts(x, _share(p, (4, 8)),
                        dict(config, n_routed_experts=32), (4, 8), _mm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    assert float(counts["dropped"]) == 0
    tokens = np.asarray(counts["tokens"])
    if case == "all_to_one_held":
        assert tokens.tolist() == [0, 64, 0, 0]
        assert np.abs(np.asarray(want)).max() > 0.1
    else:
        assert tokens.sum() == 0 and float(counts["held_share"]) == 0
        assert np.abs(np.asarray(y)).max() == 0


def test_padded_positions_go_to_no_expert():
    config = tiny(5, held=(4, 8), experts=32, top_k=2)
    p = _whole_layer_params(config, 9)
    x = jax.random.normal(jax.random.PRNGKey(10), (64, 32))
    x = x.at[40:].set(x[40])                       # 24 identical "pads"
    y, counts = _expert_layer(config, (4, 8), x, p, jnp.arange(64) < 40)
    y_tokens, counts_tokens = _expert_layer(config, (4, 8), x[:40], p)
    np.testing.assert_allclose(np.asarray(y[:40]), np.asarray(y_tokens),
                               rtol=2e-5, atol=2e-5)
    assert np.abs(np.asarray(y[40:])).max() == 0
    np.testing.assert_array_equal(np.asarray(counts["tokens"]),
                                  np.asarray(counts_tokens["tokens"]))


def test_two_head_widths_equal_a_dense_mask_reference():
    """q and k 192 wide, v 128 wide (here 12 and 8) through the
    generalised plain attention: scores over sqrt of q's width, the output
    v's width; the reference's block-of-queries attention is the same
    function; equal widths give what they gave."""
    S, H, D, Dv = 40, 4, 12, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (S, H, D))
    k = jax.random.normal(ks[1], (S, H, D))
    v = jax.random.normal(ks[2], (S, H, Dv))
    got = attn.dense_grouped_attention(q, k, v)
    assert got.shape == (S, H, Dv)
    mask = np.arange(S)[:, None] >= np.arange(S)[None, :]
    want = np.zeros((S, H, Dv), np.float32)
    for h in range(H):
        s = np.asarray(q[:, h]) @ np.asarray(k[:, h]).T / math.sqrt(D)
        s = np.where(mask, s, -np.inf)
        pr = np.exp(s - s.max(-1, keepdims=True))
        want[:, h] = (pr / pr.sum(-1, keepdims=True)) @ np.asarray(v[:, h])
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ref._attention(q, k, v)), want,
                               rtol=1e-5, atol=1e-5)
    # grouped, batched, and through ``auto`` (the plain path on the CPU)
    qb = jnp.stack([q, q[::-1]])[:, :, :, None].repeat(2, 3).reshape(
        2, S, 2 * H, D)
    out = attn.auto_grouped_attention(qb, jnp.stack([k, k[::-1]]),
                                      jnp.stack([v, v[::-1]]))
    assert out.shape == (2, S, 2 * H, Dv)
    np.testing.assert_allclose(np.asarray(out[0, :, ::2]), want, rtol=1e-5,
                               atol=1e-5)


def test_half_split_rotary_gives_the_interleaved_references_scores():
    """The program rotates the pairs (2i, 2i+1) and holds the result
    half-split; the reference rotates them in place. The two differ by a
    fixed permutation of the rotary dimensions, the same on q and k, so
    every score q . k is the same."""
    S, H, R = 24, 3, 8
    theta = 32000000
    q = jax.random.normal(jax.random.PRNGKey(0), (S, H, R))
    k = jax.random.normal(jax.random.PRNGKey(1), (S, 1, R))
    cos, sin = rope_tables(RopeSpec(rope_theta=theta), R, jnp.arange(S))
    qp, kp = interleaved_rope(q, cos, sin), interleaved_rope(k, cos, sin)
    qr, kr = ref._rotate_pairs(q, theta), ref._rotate_pairs(k, theta)
    perm = np.concatenate([np.arange(0, R, 2), np.arange(1, R, 2)])
    np.testing.assert_allclose(np.asarray(qp), np.asarray(qr)[..., perm],
                               rtol=1e-5, atol=1e-6)
    scores = lambda a, b: np.einsum("qhd,kd->hqk", np.asarray(a),
                                    np.asarray(b)[:, 0])
    np.testing.assert_allclose(scores(qp, kp), scores(qr, kr), rtol=1e-5,
                               atol=1e-5)
    # position 0 is not rotated; a later one is
    np.testing.assert_allclose(np.asarray(qr[0]), np.asarray(q[0]),
                               atol=1e-6)
    assert np.abs(np.asarray(qr[5] - q[5])).max() > 0.1
    # the angle of pair i at position p is p x theta^(-2i/R)
    p, i = 7, 1
    ang = p * theta ** (-2 * i / R)
    np.testing.assert_allclose(
        float(qr[p, 0, 2 * i]),
        float(q[p, 0, 2 * i]) * math.cos(ang)
        - float(q[p, 0, 2 * i + 1]) * math.sin(ang), rtol=1e-4, atol=1e-5)


def test_mtp_labels_are_two_ahead_and_the_last_two_positions_carry_none():
    """Position i of the module's stream is fed token i + 1 and trained on
    token i + 2: its term is the mean of -log p over the module's hidden
    states [0, S - 2) against tokens [2, S), the main term's over [0,
    S - 1) against [1, S); with the last two tokens padded both lose two
    labels."""
    b = _case(5)[1]
    S = 12
    ids = jnp.asarray(np.arange(1, S + 1)[None, None, :], jnp.int32)
    model = JoyAILM(dataclasses.replace(b.lcfg, compute_dtype=jnp.float32))
    loss_fn = make_joyai_loss(model, b.pad_id, lm_chunk=4)

    def terms(ids):
        _, aux = loss_fn(b.params, {"input_ids": ids}, jnp.ones((1,), bool))
        named = dict(zip(("acc",) + ROUND_COUNTERS, aux))
        return float(named["main_nll"]), float(named["mtp_nll"])

    hidden, hidden_mtp = ref.hidden_states(
        b.params, ids[0, 0], dict(b.config, n_routed_experts=16), (0, 4))
    head = b.params["params"]["lm_head"]

    def nll(h, labels):
        logp = jax.nn.log_softmax(_mm(h, head.T))
        return float(-jnp.take_along_axis(logp, labels[:, None], -1).mean())

    seq = ids[0, 0]
    main, mtp = terms(ids)
    np.testing.assert_allclose(main, nll(hidden[:-1], seq[1:]), rtol=1e-5)
    np.testing.assert_allclose(mtp, nll(hidden_mtp[:-2], seq[2:]), rtol=1e-5)
    assert abs(mtp - nll(hidden_mtp[:-1], seq[1:])) > 1e-3   # not one ahead
    # causal: what the first S - 2 positions compute does not depend on
    # the last two tokens, so padding those only takes labels away
    main_p, mtp_p = terms(ids.at[..., -2:].set(b.pad_id))
    np.testing.assert_allclose(main_p, nll(hidden[:-3], seq[1:-2]),
                               rtol=1e-5)
    np.testing.assert_allclose(mtp_p, nll(hidden_mtp[:-4], seq[2:-2]),
                               rtol=1e-5)


def test_loss_is_main_plus_three_tenths_mtp_and_validation_is_main():
    cfg, b, batch, mask = _case(5)[:4]
    model = JoyAILM(dataclasses.replace(b.lcfg, compute_dtype=jnp.float32))
    train = make_joyai_loss(model, b.pad_id, lm_chunk=8)
    assert train.num_results == 2 + len(ROUND_COUNTERS) == 9
    loss, aux = train(b.params, batch, mask)
    named = dict(zip(("acc",) + ROUND_COUNTERS, aux))
    np.testing.assert_allclose(
        float(loss), float(named["main_nll"]) + 0.3 * float(named["mtp_nll"]),
        rtol=1e-6)
    other = make_joyai_loss(model, b.pad_id, lm_chunk=8, mtp_coef=1.0)
    np.testing.assert_allclose(
        float(other(b.params, batch, mask)[0]),
        float(named["main_nll"]) + float(named["mtp_nll"]), rtol=1e-6)
    val = make_joyai_loss(model, b.pad_id, lm_chunk=8, counters=False)
    assert val.num_results == 2
    vloss, vaux = val(b.params, batch, mask)
    assert float(vloss) == float(named["main_nll"]) and len(vaux) == 1
    assert ROUND_COUNTERS[:2] == ("main_nll", "mtp_nll")
    assert ROUND_COUNTERS[2:] == MOE_COUNTERS
    assert float(named["dropped"]) == 0


def test_config_reads_the_published_keys_and_a_share():
    with open(CONFIG_FILE) as f:
        config = json.load(f)
    lcfg = JoyAIConfig.from_hf(config)
    assert (lcfg.num_experts, lcfg.experts_held, lcfg.n_held) == (
        256, (0, 8), 8)
    assert (lcfg.qk_head_dim, lcfg.v_head_dim, lcfg.q_lora_rank,
            lcfg.kv_lora_rank) == (192, 128, 1536, 512)
    assert lcfg.router_scoring == "sigmoid_bias"
    assert lcfg.moe_routed_scaling_factor == 2.5
    assert (lcfg.num_hidden_layers, lcfg.vocab_size) == (5, 16160)
    # every published key of the catalog row at its value but the three
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert (config["num_hidden_layers_published"],
            config["n_routed_experts_published"],
            config["vocab_size_published"]) == (40, 256, 129280)
    # a plain published file: every expert held
    plain = {k: v for k, v in config.items() if k not in (
        "experts_held", "n_routed_experts_published")}
    plain["n_routed_experts"] = 256
    assert JoyAIConfig.from_hf(plain).experts_held == (0, 256)
    # what the module does not build is refused, not guessed
    for key, value in (("n_group", 8), ("rope_interleave", False),
                       ("rope_scaling", {"type": "yarn"}),
                       ("num_nextn_predict_layers", 2)):
        with pytest.raises(ValueError, match=key):
            JoyAIConfig.from_hf({**config, key: value})
    # d of the cell, from shapes alone
    shapes = jax.eval_shape(JoyAILM(lcfg).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 1, 8), jnp.int32))
    d = sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))
    assert d == 491_697_408
    mtp = sum(math.prod(x.shape)
              for x in jax.tree.leaves(shapes["params"]["mtp"]))
    assert mtp == 77_738_240
    # and its operations: 8.5e13 a round of 32,768 positions
    assert 8.3e13 < joyai_model_flops(lcfg, 32768, 4096) < 8.8e13


def test_expert_leaves_are_layer_signal_groups_of_their_own():
    from commefficient_tpu.telemetry.layer_signals import make_group_spec
    b = _case(5)[1]
    spec = make_group_spec(b.params, "coarse")
    sizes = dict(zip(spec.names, spec.sizes))
    for group in [f"layers_{l}/experts" for l in range(1, 5)] + [
            "mtp/experts"]:
        assert sizes[group] == 4 * 3 * 32 * 16
    assert "layers_0/experts" not in sizes
    assert sum(spec.sizes) == sum(
        x.size for x in jax.tree.leaves(b.params))


def test_one_sketch_round_through_fedruntime_falls_in_loss():
    from commefficient_tpu.config import FedConfig
    from commefficient_tpu.core import FedRuntime
    b = _case(5)[1]
    cfg = FedConfig(mode="sketch", error_type="virtual",
                    virtual_momentum=0.9, local_momentum=0.0, num_rows=5,
                    num_cols=4096, k=2000, num_workers=4,
                    local_batch_size=1, microbatch_size=1, num_clients=4,
                    weight_decay=0.0, do_remat=True)
    rt = FedRuntime(cfg, b.params, b.loss_fn, num_clients=4)
    assert rt.cfg.num_results_train == 2 + len(ROUND_COUNTERS)
    state = rt.init_state()
    batch = {k: v[:4, None] for k, v in b.dataset.arrays.items()}
    ids, mask = np.arange(4), np.ones((4, 1), bool)
    losses = []
    for _ in range(4):
        state, m = rt.round(state, ids, batch, mask, 0.5)
        res = [np.asarray(r) for r in m["results"]]
        losses.append(float(res[0].mean()))
    assert np.isfinite(losses).all() and losses[-1] < losses[0], losses
    named = dict(zip(ROUND_COUNTERS, (float(r.mean()) for r in res[2:])))
    assert named["dropped"] == 0
    np.testing.assert_allclose(
        losses[-1], named["main_nll"] + 0.3 * named["mtp_nll"], rtol=1e-5)
    assert 0 <= named["tokens_per_expert_min"] < named[
        "tokens_per_expert_max"] <= 32
    assert 0.1 < named["held_share"] < 0.45, named


def test_the_two_terms_ride_the_round_event_and_pass_the_schema(tmp_path):
    from commefficient_tpu.telemetry.run import RunTelemetry
    from commefficient_tpu.telemetry.schema import (OPTIONAL_FIELDS,
                                                    validate_event,
                                                    validate_file)
    tel = RunTelemetry(str(tmp_path), "test", cfg=None)
    moe = dict(zip(MOE_COUNTERS, (96.0, 128.4, 171.0, 0.0313, 0.0)))
    common = dict(epoch=1, lr=0.04, acc=0.01, n_valid=8.0,
                  download_bytes=None, upload_bytes=None, host_s=0.0,
                  dispatch_s=0.0, device_s=0.0)
    tel.round_event(rnd=1, loss=12.6, moe=moe, main_nll=9.7, mtp_nll=9.8,
                    **common)
    tel.round_event(rnd=2, loss=9.2, moe=moe, **common)
    tel.write_summary(aborted=False, n_rounds=2, total_download_mib=0.0,
                      total_upload_mib=0.0, final=None)
    tel.close()
    path = str(tmp_path / "telemetry.jsonl")
    assert validate_file(path) == []
    rounds = [json.loads(l) for l in open(path)
              if json.loads(l)["event"] == "round"]
    assert (rounds[0]["main_nll"], rounds[0]["mtp_nll"]) == (9.7, 9.8)
    assert rounds[1]["main_nll"] is None and rounds[1]["mtp_nll"] is None
    bad = dict(rounds[0], mtp_nll="x")
    assert any("mtp_nll" in p for p in validate_event(bad))
    # a stream of this version from before the two fields lacks them
    assert OPTIONAL_FIELDS["round"] == ("main_nll", "mtp_nll")
    old = {k: v for k, v in rounds[0].items()
           if k not in ("main_nll", "mtp_nll")}
    assert validate_event(old) == []


def test_the_driver_trains_it_and_the_round_event_carries_both_terms(
        tmp_path, monkeypatch):
    """``gpt2_train --model joyai --model_checkpoint <config.json>``: the
    normal entry point, FedPERSONA, DeviceStore / FedSampler /
    RoundPipeline, FedRuntime.round, validation; at the configuration
    file's own rehearsal sizes, one round at ``--test``. Dense mode as in
    the Laguna test: ``--test`` forces a sketch of 10 columns, d / 10
    blocks, which the CPU takes an hour to compile; the sketch round is
    the test above and the cell's rehearsal (perfbench/tests)."""
    from commefficient_tpu import gpt2_train
    from commefficient_tpu.telemetry.schema import validate_file
    with open(CONFIG_FILE) as f:
        published = json.load(f)
    config = tmp_path / "config.json"
    config.write_text(json.dumps({**published, **published["rehearse"]}))
    monkeypatch.chdir(tmp_path)
    summary = gpt2_train.main([
        "--test", "--model", "joyai", "--model_checkpoint", str(config),
        "--mode", "uncompressed", "--error_type", "none",
        "--local_momentum", "0", "--num_workers", "4",
        "--local_batch_size", "1", "--microbatch_size", "1",
        "--num_candidates", "1", "--lm_chunk", "8", "--max_seq_len", "32",
        "--weight_decay", "0", "--compute_dtype", "float32",
        "--compile_cache", "", "--dataset_dir", str(tmp_path / "data"),
        "--logdir", str(tmp_path / "log")])
    assert summary is not None and np.isfinite(summary["train_loss"])
    stream = str(tmp_path / "log" / "telemetry.jsonl")
    assert validate_file(stream) == []
    events = [json.loads(l) for l in open(stream)]
    rounds = [e for e in events if e["event"] == "round"]
    assert rounds and set(rounds[0]["moe"]) == set(MOE_COUNTERS)
    assert rounds[0]["moe"]["dropped"] == 0
    assert rounds[0]["main_nll"] > 0 and rounds[0]["mtp_nll"] > 0
    np.testing.assert_allclose(
        rounds[0]["loss"],
        rounds[0]["main_nll"] + 0.3 * rounds[0]["mtp_nll"], rtol=1e-5)
    manifest = events[0]
    assert manifest["config"]["num_results_train"] == 2 + len(
        ROUND_COUNTERS)


def _loss(model, ids):
    def loss(p):
        hidden, hidden_mtp, head, _ = model.apply(p, ids)
        return ((hidden @ head.T).mean() + (hidden ** 2).mean()
                + (hidden_mtp ** 2).mean())
    return loss


def _grad(model, params, ids):
    return jax.jit(jax.value_and_grad(_loss(model, ids)))(params)


def test_remat_changes_nothing_on_the_plain_path():
    b = _case(5)[1]
    ids = fam.sample_batch(b, 1, 5)["input_ids"]
    out = [_grad(JoyAILM(dataclasses.replace(
        b.lcfg, compute_dtype=jnp.float32, remat=remat)), b.params, ids)
        for remat in (False, True)]
    assert float(out[0][0]) == float(out[1][0])
    for x, y in zip(jax.tree.leaves(out[0][1]), jax.tree.leaves(out[1][1]),
                    strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.fixture
def latent_kernels(monkeypatch):
    """``JoyAIBlock`` takes its TPU branch here, with the blocked kernel
    and the latent kernels around it (``ops/latent_pallas.py``) in
    interpret mode."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk)
    from commefficient_tpu.models import joyai
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(sk, "make_splash_mqa_single_device",
                        functools.partial(sk.make_splash_mqa_single_device,
                                          interpret=True))
    for name in ("qkv_to_heads", "heads_to_rows"):
        monkeypatch.setattr(joyai, name, functools.partial(
            getattr(joyai, name), interpret=True))


def _published_latent(heads=2, **kw):
    hf = tiny(1)
    hf.update(qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
              qk_head_dim=192, num_attention_heads=heads, **kw)
    return hf


def test_blocked_path_is_the_plain_path_with_the_blocked_kernel(
        latent_kernels):
    """``JoyAIBlock`` where the blocked kernel runs (interpret mode: q and
    k 192 wide, v 128 wide, one query head a KV head, S = 1,024, the main
    block and the prediction module's; the latent kernels around it)
    against the same model on the plain path, in float32: loss and
    gradients agree as two float32 softmaxes do, and the remat keeps the
    kernel's two residuals a block."""
    lcfg = JoyAIConfig.from_hf(_published_latent(), compute_dtype=jnp.float32,
                               remat=True)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 1024), 0, 64)
    params = jax.jit(JoyAILM(lcfg).init)(jax.random.PRNGKey(0), ids)
    blocked = _grad(JoyAILM(lcfg), params, ids)
    plain = _grad(JoyAILM(lcfg, attn_impl=attn.dense_grouped_attention),
                  params, ids)
    np.testing.assert_allclose(float(blocked[0]), float(plain[0]), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(blocked[1]), jax.tree.leaves(plain[1]),
                    strict=True):
        # leaves whose gradient is a small difference of large sums
        # (the embedding rows) carry float32 cancellation of the sums
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-3 * scale, rtol=0)


# lane 128 + j of a head as the plain path holds it (the rotary pairs
# half-split) is lane HALF_SPLIT[128 + j] of the fused path's (in place)
HALF_SPLIT = np.concatenate([np.arange(128), 128 + np.arange(0, 64, 2),
                             128 + np.arange(1, 64, 2)])


def _plain_to_heads(q, kv, kv_a, cos, sin, scale):
    """``JoyAIBlock``'s plain path from the projections' rows to the
    blocked kernel's operands: ``interleaved_rope``, the concatenations,
    ``splash_grouped_attention``'s scale and transposes. The shared key
    is spread over the heads in float32 (the same values), so that its
    transpose sums their cotangents in float32 as the kernel does."""
    B, S, width = kv.shape
    H = width // 256
    q, kv = q.reshape(B, S, H, 192), kv.reshape(B, S, H, 256)
    k_rope = interleaved_rope(kv_a[..., None, -64:], cos, sin)
    q = jnp.concatenate([q[..., :128], interleaved_rope(q[..., 128:], cos,
                                                        sin)], -1)
    k = jnp.concatenate([kv[..., :128], jnp.broadcast_to(
        k_rope.astype(jnp.float32), (B, S, H, 64)).astype(kv.dtype)], -1)
    q = (q * scale).astype(q.dtype)
    heads = lambda t: jnp.moveaxis(t, 2, 1)
    return heads(q)[:, :, None], heads(k), heads(kv[..., 128:])


def _within_ulps(got, want, bits):
    """|got - want| is at most the spacing of ``bits``-bit mantissas at
    the larger of the two (7: one bfloat16 ulp; 6: two), or, where a sum
    cancels, a float32 ulp of the largest value."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    top = np.maximum(np.abs(got), np.abs(want))
    ulp = np.exp2(np.floor(np.log2(np.maximum(top, 1e-30))) - bits)
    ulp = np.maximum(ulp, 2.0 ** -22 * top.max())
    worst = (np.abs(got - want) / ulp).max()
    assert worst <= 1 and top.max() > 0, worst


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_latent_kernels_equal_the_plain_path(dtype):
    """``ops/latent_pallas.py`` in interpret mode against the plain path
    around the blocked kernel at the published widths (32 heads, q and k
    128 + 64, v 128, the latent 512 + 64), two sequences of 128 positions
    (two passes of the kernels' inner loop), values and ``jax.vjp``: q, k
    and v as the kernel reads them, the pairs in place where the plain
    path holds them half-split (``HALF_SPLIT``), and the output back to
    rows. Where every product and sum is exact (whole numbers, tables in
    eighths) the two are equal to the bit in both dtypes: the same
    products, signs, scale and roundings. Under the real tables XLA's CPU
    backend contracts products into fused multiply-adds, and not the same
    ones in the two programs: float32 agrees to a float32 ulp of the
    largest value and bfloat16 to a bfloat16 ulp at each rounding."""
    from commefficient_tpu.ops import latent_pallas as lp
    dt = jnp.dtype(dtype)
    B, S, H = 2, 128, 32
    cos, sin = rope_tables(RopeSpec(rope_theta=32000000), 64, jnp.arange(S))
    keys = jax.random.split(jax.random.PRNGKey(0), 7)

    def draw(key, shape, whole):
        x = jax.random.normal(key, shape, jnp.float32)
        return (jnp.round(x * 2) if whole else x).astype(dt)

    for whole in (True, False):
        c, s = ((jnp.round(cos * 8) / 8, jnp.round(sin * 8) / 8) if whole
                else (cos, sin))
        # 1/sqrt(192) is exact on neither path; float32 rotates what it
        # scaled, whose products are not exact
        scale = 0.125 if whole and dtype == "float32" else 1 / math.sqrt(192)
        args = (draw(keys[0], (B, S, H * 192), whole),
                draw(keys[1], (B, S, H * 256), whole),
                draw(keys[2], (B, S, 512 + 64), whole))
        cts = (draw(keys[3], (B, H, 1, S, 192), whole),
               draw(keys[4], (B, H, S, 192), whole),
               draw(keys[5], (B, H, S, 128), whole))
        got, got_vjp = jax.vjp(lambda *a: lp.qkv_to_heads(
            *a, lp.pair_tables(c, s), scale=scale, interpret=True), *args)
        want, want_vjp = jax.vjp(lambda *a: _plain_to_heads(
            *a, c, s, scale), *args)
        assert [a.shape for a in got] == [a.shape for a in cts]
        assert all(a.dtype == dt for a in got)
        got = [a[..., HALF_SPLIT] if a.shape[-1] == 192 else a for a in got]
        plain_cts = [a[..., HALF_SPLIT] if a.shape[-1] == 192 else a
                     for a in cts]
        pairs = list(zip(got + list(got_vjp(cts)),
                         list(want) + list(want_vjp(tuple(plain_cts)))))
        for a, b in pairs:
            assert a.shape == b.shape and a.dtype == b.dtype
            if whole:
                np.testing.assert_array_equal(np.asarray(a, np.float32),
                                              np.asarray(b, np.float32))
            elif dtype == "float32":
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=0,
                    atol=2.0 ** -22 * float(jnp.abs(b).max()))
            else:
                _within_ulps(a, b, 6)
        # the kv_a cotangent is the shared key's alone
        assert not np.asarray(pairs[-1][0])[..., :512].any()

    o = draw(keys[6], (B, H, 1, S, 128), False)
    rows, back = jax.vjp(functools.partial(lp.heads_to_rows, interpret=True),
                         o)
    want = jnp.moveaxis(o[:, :, 0], 1, 2).reshape(B, S, H * 128)
    np.testing.assert_array_equal(np.asarray(rows, np.float32),
                                  np.asarray(want, np.float32))
    np.testing.assert_array_equal(np.asarray(back(want)[0], np.float32),
                                  np.asarray(o, np.float32))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_fused_latent_path_gives_the_plain_paths_gradients(latent_kernels,
                                                          monkeypatch, dtype):
    """One ``JoyAIBlock`` at the published latent widths (4 heads,
    S = 1,024) where the blocked kernel runs (interpret mode), the fused
    path against the plain path in front of the same kernel
    (``latent_pallas.fits`` made to say no: ``interleaved_rope``, the
    concatenations, ``splash_grouped_attention``'s scale and transposes):
    the block's output, and the gradients of x and of every
    latent-attention weight and norm. The two paths differ only in the
    order of the kernel's sums over the rotary lanes and in where the
    shared key's cotangent is rounded: a relative L2 of 7e-5 at most in
    bfloat16 and 3e-7 in float32 on the CPU, bounded at 1e-3 and 2e-6 (the
    harness's own bfloat16 tolerance, ``checks.MODEL_TOL``, is 0.1)."""
    from commefficient_tpu.models import joyai
    from commefficient_tpu.ops import latent_pallas as lp
    lcfg = JoyAIConfig.from_hf(_published_latent(4),
                               compute_dtype=jnp.dtype(dtype))
    S = 1024
    block = joyai.JoyAIBlock(lcfg, False)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, S, 32)).astype(
        lcfg.compute_dtype)
    rope = joyai.rotary_tables(lcfg, jnp.arange(S))
    params = block.init(jax.random.PRNGKey(3), x, rope)

    def run(params, x):
        y, _ = block.apply(params, x, rope)
        return (y.astype(jnp.float32) ** 2).mean(), y

    fused = jax.value_and_grad(run, argnums=(0, 1), has_aux=True)(params, x)
    monkeypatch.setattr(lp, "fits", lambda *a: False)
    plain = jax.value_and_grad(run, argnums=(0, 1), has_aux=True)(params, x)
    tol = {"float32": 2e-6, "bfloat16": 1e-3}[dtype]
    assert tol < checks.MODEL_TOL[dtype]["grad_rel_l2"]
    rel = lambda a, b: float(jnp.linalg.norm((a - b).astype(jnp.float32))
                             / jnp.linalg.norm(b.astype(jnp.float32)))
    assert rel(fused[0][1], plain[0][1]) < tol
    names = ("q_a_proj", "q_a_layernorm", "q_b_proj", "kv_a_proj_with_mqa",
             "kv_a_layernorm", "kv_b_proj", "o_proj")
    grads = [(fused[1][0]["params"][n], plain[1][0]["params"][n])
             for n in names] + [(fused[1][1], plain[1][1])]
    for a, b in grads:
        a, b = jax.tree.leaves(a), jax.tree.leaves(b)
        assert len(a) == len(b) == 1
        assert rel(a[0], b[0]) < tol, rel(a[0], b[0])
