"""models/laguna.py (window and full attention with per-layer head counts,
a routed expert layer that holds a share of its experts) against its plain
reference, at tiny widths on the CPU; its loss, its counters, its layer
signal groups, and one federated sketch round through FedRuntime."""

import dataclasses
import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.losses import make_laguna_loss
from commefficient_tpu.models import gpt2 as attn
from commefficient_tpu.models.laguna import (MOE_COUNTERS, ExpertLayer,
                                             LagunaConfig, LagunaLM,
                                             laguna_model_flops,
                                             rope_tables)
from perfbench.families import laguna_moe as fam
from perfbench.families import laguna_moe_reference as ref
from perfbench.harness import checks

ROPE = {
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                          "partial_rotary_factor": 1}}
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]


def tiny(layers=5, held=(0, 4), experts=16, top_k=4):
    """A config.json in the published key set, every width tiny."""
    n = layers - 1
    return {
        "vocab_size": 64, "hidden_size": 32, "intermediate_size": 64,
        "num_hidden_layers": layers, "num_attention_heads": 2,
        "num_key_value_heads": 2, "head_dim": 8, "rms_norm_eps": 1e-6,
        "num_experts": held[1] - held[0], "num_experts_published": experts,
        "experts_held": list(held), "num_experts_per_tok": top_k,
        "moe_intermediate_size": 16, "shared_expert_intermediate_size": 16,
        "sliding_window": 8, "moe_routed_scaling_factor": 2.5,
        "rope_parameters": ROPE,
        "layer_types": ["full_attention"] + (PERIOD * 3)[:n],
        "mlp_layer_types": ["dense"] + ["sparse"] * n,
        "num_attention_heads_per_layer": [2] + [
            4 if t == "sliding_attention" else 2 for t in (PERIOD * 3)[:n]],
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


@functools.lru_cache(maxsize=None)
def _case(layers):
    """One seeded model, one item, and the plain reference's loss and
    gradient on them (compiled once for both compute dtypes)."""
    cfg, b = built(tiny(layers), "float32")
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
@pytest.mark.parametrize("layers", [9, 5], ids=["dense+2periods", "cut5"])
def test_program_matches_the_plain_reference(layers, dtype):
    """Loss and gradient on seeded weights, under the harness's own
    tolerances (perfbench/harness/checks.MODEL_TOL)."""
    cfg, b, batch, mask, ref_loss, ref_grad = _case(layers)
    assert b.lcfg.num_hidden_layers == layers
    assert b.lcfg.layer_types.count("full_attention") == (3 if layers == 9
                                                          else 2)
    model = LagunaLM(dataclasses.replace(b.lcfg,
                                         compute_dtype=jnp.dtype(dtype)))
    grad_err, loss_err = _errors(make_laguna_loss(model, b.pad_id, 8),
                                 b.params, batch, mask, ref_loss, ref_grad)
    tol = checks.MODEL_TOL[dtype]
    assert grad_err <= tol["grad_rel_l2"] and loss_err <= tol[
        "loss_rel"], (grad_err, loss_err)
    if dtype == "float32":      # same arithmetic, another order of sums
        assert grad_err < 2e-5, grad_err
    else:                       # and bf16 is not float32 in disguise
        assert grad_err > 1e-4, grad_err


def test_a_bf16_reference_fails_the_float32_tolerance():
    """The harness's own comparison (perturbed weights), against the
    deliberately wrong reference."""
    cfg, b = _case(5)[:2]
    out = checks.model_step(fam, b, cfg, seed=5, variant="bf16", n=1)
    assert not out["ok"] and out["grad_rel_l2"] > 5 * out["tol"][
        "grad_rel_l2"], out


def _expert_layer(config, held, x, params):
    """The program's ExpertLayer for the share ``held`` of ``params``
    (the whole layer's: router (E, experts), stacked expert matrices)."""
    lcfg = LagunaConfig.from_hf({**config, "experts_held": list(held)},
                                compute_dtype=jnp.float32)
    lo, hi = held
    share = {"router": params["router"],
             **{k: params[k][lo:hi] for k in
                ("experts_gate", "experts_up", "experts_down")}}
    return ExpertLayer(lcfg).apply({"params": share}, x)


def _whole_layer_params(config, seed, scale=0.3):
    E, I = config["hidden_size"], config["moe_intermediate_size"]
    n = config["num_experts_published"]
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return {"router": scale * jax.random.normal(ks[0], (E, n)),
            "experts_gate": scale * jax.random.normal(ks[1], (n, E, I)),
            "experts_up": scale * jax.random.normal(ks[2], (n, E, I)),
            "experts_down": scale * jax.random.normal(ks[3], (n, I, E))}


def _mm(a, b):
    with jax.default_matmul_precision("highest"):
        return a @ b


def test_the_shares_of_one_sparse_layer_add_up_to_the_whole():
    """Guide section 4: the partial outputs of all four shares of a
    16-expert layer, and the shared expert counted once, are the uncut
    reference's output."""
    config = tiny(5)
    p = _whole_layer_params(config, 1)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, 32))
    shared = {n: {"kernel": 0.3 * jax.random.normal(
        jax.random.PRNGKey(7 + i), s)} for i, (n, s) in enumerate(
            [("gate_proj", (32, 16)), ("up_proj", (32, 16)),
             ("down_proj", (16, 32))])}
    whole = dict(config, num_experts=16)
    want = (ref._experts(x.reshape(-1, 32), p, whole, (0, 16), _mm)
            + ref._swiglu(x.reshape(-1, 32), shared, _mm))
    got = ref._swiglu(x.reshape(-1, 32), shared, _mm)
    shares = 0.0
    for lo in range(0, 16, 4):
        y, counts = _expert_layer(config, (lo, lo + 4), x, p)
        got = got + y.reshape(-1, 32)
        shares += float(counts["held_share"])
        assert float(counts["dropped"]) == 0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert abs(shares - 1.0) < 1e-6      # every routed slot has one home


@pytest.mark.parametrize("case", ["all_to_one_held", "none_to_any_held"])
def test_a_skewed_router_drops_nothing(case):
    """Every token on one held expert, and no token on any: both match
    the reference and the counters say so."""
    config = tiny(5, held=(4, 8), experts=32, top_k=2)
    p = _whole_layer_params(config, 4)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (64, 32))) + 0.5
    col = jnp.arange(32)
    if case == "all_to_one_held":      # expert 5 first, the rest unheld
        bias = jnp.where(col == 5, 4.0, jnp.where(
            (col >= 4) & (col < 8), -4.0, 0.0))
    else:
        bias = jnp.where((col >= 4) & (col < 8), -4.0, 0.0)
    p["router"] = 0.01 * p["router"] + bias[None, :] / x.shape[1]
    y, counts = _expert_layer(config, (4, 8), x, p)
    share = {k: (v if k == "router" else v[4:8]) for k, v in p.items()}
    want = ref._experts(x, share, dict(config, num_experts=32), (4, 8), _mm)
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


def test_expert_gradients_match_the_reference():
    config = tiny(5, held=(4, 8), experts=32, top_k=2)
    p = _whole_layer_params(config, 6)
    x = jax.random.normal(jax.random.PRNGKey(8), (64, 32))
    share = lambda q: {k: (v if k == "router" else v[4:8])
                       for k, v in q.items()}

    def prog(q, x):
        lcfg = LagunaConfig.from_hf(config, compute_dtype=jnp.float32)
        return (ExpertLayer(lcfg).apply({"params": share(q)}, x)[0]
                ** 2).sum()

    def plain(q, x):
        return (ref._experts(x, share(q), dict(config, num_experts=32),
                             (4, 8), _mm) ** 2).sum()

    g1, g2 = jax.grad(prog, (0, 1))(p, x), jax.grad(plain, (0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-4)


def test_window_mask_equals_a_dense_mask_reference():
    S, H, KV, D, W = 40, 4, 2, 8, 8          # S > window
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (S, H, D))
    k = jax.random.normal(ks[1], (S, KV, D))
    v = jax.random.normal(ks[2], (S, KV, D))
    got = attn.dense_grouped_attention(q, k, v, window=W)
    i, j = np.arange(S)[:, None], np.arange(S)[None, :]
    mask = (i - j >= 0) & (i - j < W)
    want = np.zeros((S, H, D), np.float32)
    for h in range(H):
        s = np.asarray(q[:, h]) @ np.asarray(k[:, h // 2]).T / math.sqrt(D)
        s = np.where(mask, s, -np.inf)
        pr = np.exp(s - s.max(-1, keepdims=True))
        want[:, h] = (pr / pr.sum(-1, keepdims=True)) @ np.asarray(
            v[:, h // 2])
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    full = attn.dense_grouped_attention(q, k, v)
    assert np.abs(np.asarray(full)[W:] - want[W:]).max() > 1e-3
    np.testing.assert_allclose(np.asarray(full)[:W], want[:W], rtol=1e-5,
                               atol=1e-5)
    # the reference's block-of-queries attention is the same function
    np.testing.assert_allclose(np.asarray(ref._attention(q, k, v, W)), want,
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("H", [48, 64])
def test_query_head_i_reads_kv_head_i_over_group(H):
    S, KV, D = 4, 8, 16
    q = jax.random.normal(jax.random.PRNGKey(H), (S, H, D))
    k = jnp.zeros((S, KV, D))
    v = jnp.broadcast_to(jnp.arange(KV, dtype=jnp.float32)[None, :, None],
                         (S, KV, D))
    out = attn.auto_grouped_attention(q, k, v)
    want = np.arange(H) // (H // KV)
    np.testing.assert_allclose(np.asarray(out)[:, :, 0],
                               np.broadcast_to(want, (S, H)), atol=1e-6)


def test_config_reads_the_published_keys_and_a_share():
    with open("perfbench/configs/laguna_xs2_share32.json") as f:
        config = json.load(f)
    lcfg = LagunaConfig.from_hf(config)
    assert (lcfg.num_experts, lcfg.experts_held, lcfg.n_held) == (
        256, (0, 8), 8)
    assert lcfg.num_attention_heads_per_layer == (48, 64, 64, 64, 48)
    assert lcfg.layer_types[1:4] == ("sliding_attention",) * 3
    assert lcfg.mlp_layer_types == ("dense",) + ("sparse",) * 4
    assert lcfg.full_rope.rope_type == "yarn"
    assert lcfg.full_rope.partial_rotary_factor == 0.5
    # a plain published file: every expert held
    plain = {k: v for k, v in config.items() if k not in (
        "experts_held", "num_experts_published")}
    plain["num_experts"] = 256
    assert LagunaConfig.from_hf(plain).experts_held == (0, 256)
    # d of the cell, from shapes alone
    shapes = jax.eval_shape(LagunaLM(lcfg).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 1, 8), jnp.int32))
    d = sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))
    assert d == 389_634_048
    # and its operations: 1.6-2.1 GFLOP a position, forward and backward
    per_position = laguna_model_flops(lcfg, 1, 4096)
    assert 1.6e9 < per_position < 2.2e9, per_position


def test_yarn_tables_follow_the_reference_formula():
    from commefficient_tpu.models.laguna import RopeSpec
    for kind, D in (("full_attention", 128), ("sliding_attention", 128)):
        spec = RopeSpec.from_dict(ROPE[kind])
        cos, sin = rope_tables(spec, D, jnp.arange(4096))
        inv, scale = ref._inv_freq(ROPE[kind], D)
        ang = np.arange(4096)[:, None] * inv[None, :]
        np.testing.assert_allclose(np.asarray(cos), np.cos(ang) * scale,
                                   atol=2e-3)
        assert cos.shape[-1] * 2 == int(
            D * ROPE[kind]["partial_rotary_factor"])
    assert abs(scale - 1.0) < 1e-9          # plain rotary is not scaled


def test_expert_leaves_are_layer_signal_groups_of_their_own():
    from commefficient_tpu.telemetry.layer_signals import make_group_spec
    b = _case(5)[1]
    spec = make_group_spec(b.params, "coarse")
    sizes = dict(zip(spec.names, spec.sizes))
    for l in range(1, 5):
        assert sizes[f"layers_{l}/experts"] == 4 * 3 * 32 * 16
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
    assert rt.cfg.num_results_train == 2 + len(MOE_COUNTERS)
    state = rt.init_state()
    batch = {k: v[:4, None] for k, v in b.dataset.arrays.items()}
    ids, mask = np.arange(4), np.ones((4, 1), bool)
    losses = []
    for _ in range(4):
        state, m = rt.round(state, ids, batch, mask, 0.5)
        res = [np.asarray(r) for r in m["results"]]
        losses.append(float(res[0].mean()))
    assert np.isfinite(losses).all() and losses[-1] < losses[0], losses
    moe = dict(zip(MOE_COUNTERS, (float(r.mean()) for r in res[2:])))
    assert moe["dropped"] == 0
    assert 0 < moe["tokens_per_expert_min"] <= moe[
        "tokens_per_expert_mean"] <= moe["tokens_per_expert_max"] <= 32
    # 32 tokens x top-4 of 16 experts, 4 held: a quarter of the slots
    assert 0.1 < moe["held_share"] < 0.45, moe


def test_counters_ride_the_round_event_and_pass_the_schema(tmp_path):
    from commefficient_tpu.telemetry.run import RunTelemetry
    from commefficient_tpu.telemetry.schema import (MOE_COUNTER_FIELDS,
                                                    SCHEMA_VERSION,
                                                    validate_event,
                                                    validate_file)
    assert SCHEMA_VERSION == 12
    assert MOE_COUNTER_FIELDS == MOE_COUNTERS   # the validator imports no jax
    tel = RunTelemetry(str(tmp_path), "test", cfg=None)
    moe = dict(zip(MOE_COUNTERS, (96.0, 128.4, 171.0, 0.0313, 0.0)))
    tel.round_event(rnd=1, epoch=1, lr=0.04, loss=9.3, acc=0.01,
                    n_valid=8.0, download_bytes=None, upload_bytes=None,
                    host_s=0.0, dispatch_s=0.0, device_s=0.0, moe=moe)
    tel.round_event(rnd=2, epoch=1, lr=0.04, loss=9.2, acc=0.01,
                    n_valid=8.0, download_bytes=None, upload_bytes=None,
                    host_s=0.0, dispatch_s=0.0, device_s=0.0)
    tel.write_summary(aborted=False, n_rounds=2, total_download_mib=0.0,
                      total_upload_mib=0.0, final=None)
    tel.close()
    path = str(tmp_path / "telemetry.jsonl")
    assert validate_file(path) == []
    rounds = [json.loads(l) for l in open(path) if '"round"' in l
              and json.loads(l)["event"] == "round"]
    assert rounds[0]["moe"] == moe and rounds[1]["moe"] is None
    bad = dict(rounds[0], moe={"held_share": "x"})
    assert any("moe." in p for p in validate_event(bad))
    # a stream from before the counters need not carry them
    old = {k: v for k, v in rounds[0].items() if k != "moe"}
    assert validate_event(old, version=11) == []
    assert validate_event(old, version=12) != []


def test_watched_text_puts_an_instruction_on_one_line():
    from commefficient_tpu.telemetry.compilewatch import WatchedText

    class Exe:
        def as_text(self):
            return ('  %splash.1 = custom-call(%a), frontend_attributes={'
                    'kernel_metadata={\n"xprof_metadata":"{}"\n}}, '
                    'metadata={op_name="x/fed_attention/y"}\n'
                    '  %add.2 = add(%a, %b)\n}\n')

        memory_analysis = staticmethod(lambda: "kept")

    w = WatchedText(Exe())
    lines = w.as_text().splitlines()
    assert len(lines) == 3 and "fed_attention" in lines[0]
    assert lines[0].lstrip().startswith("%splash.1")
    assert w.memory_analysis() == "kept"


def test_the_driver_trains_it_and_the_round_event_carries_the_counters(
        tmp_path, monkeypatch):
    """``gpt2_train --model laguna --model_checkpoint <config.json>``:
    the normal entry point, FedPERSONA, DeviceStore / FedSampler /
    RoundPipeline, FedRuntime.round, validation; one round at ``--test``."""
    from commefficient_tpu import gpt2_train
    from commefficient_tpu.telemetry.schema import validate_file
    config = tmp_path / "config.json"
    config.write_text(json.dumps(tiny(5)))
    monkeypatch.chdir(tmp_path)
    summary = gpt2_train.main([
        "--test", "--model", "laguna", "--model_checkpoint", str(config),
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
    assert 0 < rounds[0]["moe"]["held_share"] < 1
    manifest = events[0]
    assert manifest["config"]["num_results_train"] == 2 + len(MOE_COUNTERS)


def test_padded_positions_go_to_no_expert():
    """Thousands of identical pad positions would all follow one router
    decision; the layer gives them nothing and counts them nowhere, and
    the tokens' outputs are what they are without the padding."""
    config = tiny(5, held=(4, 8), experts=32, top_k=2)
    p = _whole_layer_params(config, 9)
    x = jax.random.normal(jax.random.PRNGKey(10), (64, 32))
    x = x.at[40:].set(x[40])                       # 24 identical "pads"
    valid = jnp.arange(64) < 40
    lcfg = LagunaConfig.from_hf(config, compute_dtype=jnp.float32)
    share = {k: (v if k == "router" else v[4:8]) for k, v in p.items()}
    layer = ExpertLayer(lcfg)
    y, counts = layer.apply({"params": share}, x, valid)
    y_tokens, counts_tokens = layer.apply({"params": share}, x[:40])
    np.testing.assert_allclose(np.asarray(y[:40]), np.asarray(y_tokens),
                               rtol=1e-6, atol=1e-6)
    assert np.abs(np.asarray(y[40:])).max() == 0
    np.testing.assert_array_equal(np.asarray(counts["tokens"]),
                                  np.asarray(counts_tokens["tokens"]))


def _loss(model, ids):
    def loss(p):
        hidden, head, _ = model.apply(p, ids)
        return (hidden @ head.T).mean() + (hidden ** 2).mean()
    return loss


def _grad(model, params, ids):
    return jax.jit(jax.value_and_grad(_loss(model, ids)))(params)


def _bit_equal(a, b):
    assert float(a[0]) == float(b[0])
    for x, y in zip(jax.tree.leaves(a[1]), jax.tree.leaves(b[1]), strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_remat_changes_nothing_on_the_plain_path():
    """``cfg.remat`` keeps only what carries ``GROUPED_ATTN_RESIDUAL``,
    and on the plain attention path nothing does: loss and every gradient
    leaf are the unwrapped blocks' to the bit. (In float32: under bf16
    the CPU compiler rounds a recomputed block's fusions differently,
    with or without a policy.)"""
    b = _case(5)[1]
    ids = fam.sample_batch(b, 1, 5)["input_ids"]
    out = [_grad(LagunaLM(dataclasses.replace(
        b.lcfg, compute_dtype=jnp.float32, remat=remat)), b.params, ids)
        for remat in (False, True)]
    _bit_equal(*out)
    assert max(float(jnp.abs(g).max())
               for g in jax.tree.leaves(out[1][1])) > 0


@pytest.fixture
def blocked_kernel(monkeypatch):
    """``LagunaBlock`` takes its TPU branch here, with the blocked kernel
    and the rotary and gate kernels around it (``ops/rope_pallas.py``)
    in interpret mode: one full and one window layer, 2
    query heads over 1 KV head of 128, S = 1,024 (two blocks)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(sk, "make_splash_mqa_single_device",
                        functools.partial(sk.make_splash_mqa_single_device,
                                          interpret=True))
    from commefficient_tpu.models import laguna
    for name in ("rope_to_heads", "gate_from_heads"):
        monkeypatch.setattr(laguna, name, functools.partial(
            getattr(laguna, name), interpret=True))
    hf = tiny(2)
    hf.update(num_key_value_heads=1, head_dim=128, sliding_window=512,
              num_attention_heads_per_layer=[2, 2])

    lcfg = LagunaConfig.from_hf(hf, compute_dtype=jnp.float32)
    assert lcfg.layer_types == ("full_attention", "sliding_attention")
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 1024), 0, 64)
    params = jax.jit(LagunaLM(lcfg).init)(jax.random.PRNGKey(0), ids)
    return lambda remat=True: (
        LagunaLM(dataclasses.replace(lcfg, remat=remat)), params, ids)


def _residuals(capsys, model, params, ids):
    capsys.readouterr()
    jax.ad_checkpoint.print_saved_residuals(_loss(model, ids), params)
    return sorted(capsys.readouterr().out.strip().splitlines())


def test_block_remat_keeps_the_blocked_kernels_two_residuals(
        blocked_kernel, capsys, monkeypatch):
    """What the backward pass is handed from each remat'd block whose
    attention is the blocked kernel: beside what full remat hands it (the
    block's arguments and output), the kernel's output and logsumexp and
    nothing else of the block's interior."""
    from commefficient_tpu.models import laguna
    kept = _residuals(capsys, *blocked_kernel())
    monkeypatch.setattr(laguna, "GROUPED_ATTN_RESIDUAL", "carried_by_nothing")
    full = _residuals(capsys, *blocked_kernel())
    extra = list(kept)
    for line in full:
        extra.remove(line)
    # (B, KV, G, S, D) and (B, KV, G, S), a layer
    assert sorted(line.split()[0] for line in extra) == sorted([
        "f32[1,1,2,1024,128]", "f32[1,1,2,1024]"] * 2), extra
    assert all("splash_attention" in line for line in extra), extra
    none = _residuals(capsys, *blocked_kernel(remat=False))
    assert len(none) > len(kept) + 20


def test_kept_residuals_give_full_remats_gradients_to_the_bit(
        blocked_kernel, monkeypatch):
    """The kept values are the ones the second forward would have
    recomputed, from the same kernel on the same inputs: in float32 the
    gradients are full remat's, and no remat's, to the bit. (Under bf16
    on the chip they are not: the compiler rounds inside its fusions, and
    the fusions change with what is kept; PERF.md section 6, PR 33.)"""
    from commefficient_tpu.models import laguna
    kept = _grad(*blocked_kernel())
    plain = _grad(*blocked_kernel(remat=False))
    monkeypatch.setattr(laguna, "GROUPED_ATTN_RESIDUAL", "carried_by_nothing")
    full = _grad(*blocked_kernel())
    _bit_equal(kept, full)
    _bit_equal(kept, plain)
    assert min(float(jnp.abs(g).max())
               for g in jax.tree.leaves(kept[1])) > 0


def _plain_to_heads(x, cos, sin, heads, scale):
    """``LagunaBlock``'s plain path up to the blocked kernel's operand:
    ``apply_rope``, ``splash_grouped_attention``'s scale and transpose."""
    from commefficient_tpu.models.laguna import apply_rope
    B, S, width = x.shape
    D = width // math.prod(heads)
    q = apply_rope(x.reshape(B, S, -1, D), cos, sin)
    if scale is not None:
        q = (q * scale).astype(q.dtype)
    return jnp.moveaxis(q.reshape((B, S) + heads + (D,)), 1, -2)


def _plain_from_heads(o, gate):
    """The way back: the transpose, ``LagunaBlock``'s gate product."""
    B, S, H = gate.shape
    o = jnp.moveaxis(o.reshape(B, H, S, -1), 1, 2)
    return (o.astype(jnp.float32) * gate[..., None]).astype(
        o.dtype).reshape(B, S, -1)


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
@pytest.mark.parametrize("kind,G", [("sliding_attention", 8),
                                    ("full_attention", 6)],
                         ids=["window_64_heads", "full_48_heads_yarn"])
def test_rotary_and_gate_kernels_equal_the_plain_path(kind, G, dtype):
    """``ops/rope_pallas.py`` in interpret mode against the plain path
    around the blocked kernel, values and ``jax.vjp``: q (64 heads, half
    = 64 on a window layer; 48 heads, half = 32 under the YaRN tables with
    their ``attention_factor`` on a full layer) rotated, rounded, scaled
    and head-major as (KV, G), with k (8 KV heads) the same unscaled in
    the one call; the gated way back; a batch of two sequences of 128
    positions (two passes of the kernels' inner loop), the second padded
    with zeros from position 80. Two sets of operands. Where every
    product and sum is exact (whole numbers times tables in eighths) the
    two are equal to the bit in both dtypes: same permutation, signs,
    scale and order of roundings. Under the real tables XLA's CPU backend
    contracts ``a * b + c * d`` into a fused multiply-add, and not the
    same product in the two programs, so float32 agrees to a float32 ulp
    of the products and bfloat16 to one bfloat16 ulp a rounding (on the
    chip, which has no such instruction, the forward passes are equal to
    the bit: PERF.md, PR 35). The gate has one product an element and is
    equal to the bit everywhere."""
    from commefficient_tpu.models.laguna import RopeSpec
    from commefficient_tpu.ops.rope_pallas import (gate_from_heads,
                                                   rope_to_heads)
    dt = jnp.dtype(dtype)
    KV, D, B, S = 8, 128, 2, 128
    H = KV * G
    cos, sin = rope_tables(RopeSpec.from_dict(ROPE[kind]), D, jnp.arange(S))
    assert cos.shape[-1] == (64 if G == 8 else 32)
    if kind == "full_attention":
        assert float(jnp.abs(cos).max()) > 1.4        # the attention factor
    eighths = lambda t: jnp.round(t * 8) / 8
    keys = jax.random.split(jax.random.PRNGKey(H), 7)
    pad = ((jnp.arange(S) < 80)[None, :, None]
           | (jnp.arange(B) == 0)[:, None, None]).astype(dt)

    def draw(key, shape, whole):
        x = jax.random.normal(key, shape, jnp.float32)
        return (jnp.round(x * 2) if whole else x).astype(dt)

    for whole in (True, False):
        c, s = (eighths(cos), eighths(sin)) if whole else (cos, sin)
        # the transpose scales first: 1/sqrt(D) is exact on neither
        # path, and what float32 rotates after it has no exact products
        scale = 0.125 if whole and dtype == "float32" else 1 / math.sqrt(D)
        q = draw(keys[0], (B, S, H * D), whole) * pad
        k = draw(keys[1], (B, S, KV * D), whole) * pad
        cts = (draw(keys[2], (B, KV, G, S, D), whole),
               draw(keys[3], (B, KV, S, D), whole))
        got, got_vjp = jax.vjp(lambda q, k: rope_to_heads(
            q, k, c, s, head_dim=D, scale=scale, interpret=True), q, k)
        want, want_vjp = jax.vjp(lambda q, k: (
            _plain_to_heads(q, c, s, (KV, G), scale),
            _plain_to_heads(k, c, s, (KV,), None)), q, k)
        assert [a.shape for a in got] == [a.shape for a in cts]
        assert got[0].dtype == got[1].dtype == dt
        for a, b in zip(got + got_vjp(cts), want + want_vjp(cts)):
            if whole:
                np.testing.assert_array_equal(np.asarray(a, np.float32),
                                              np.asarray(b, np.float32))
            elif dtype == "float32":
                # an ulp of the products, which may cancel in the sum
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=0,
                    atol=2.0 ** -22 * float(jnp.abs(b).max()))
            else:
                # one ulp at each of q's two roundings: the second is
                # of a product with 0.088, whose ulp may be half as wide
                _within_ulps(a, b, 6)

        o = draw(keys[4], (B, KV, G, S, D), whole)
        gate = jax.nn.sigmoid(jax.random.normal(keys[5], (B, S, H)))
        ct = draw(keys[6], (B, S, H * D), whole)
        got, got_vjp = jax.vjp(functools.partial(
            gate_from_heads, interpret=True), o, gate)
        want, want_vjp = jax.vjp(_plain_from_heads, o, gate)
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
        (d_o, d_gate), (want_o, want_gate) = got_vjp(ct), want_vjp(ct)
        np.testing.assert_array_equal(
            np.asarray(d_o, np.float32),
            np.asarray(want_o, np.float32).reshape(d_o.shape))
        assert d_gate.dtype == jnp.float32
        # a sum of 128 float32 products, in the kernel's order
        np.testing.assert_allclose(np.asarray(d_gate),
                                   np.asarray(want_gate), rtol=1e-5,
                                   atol=1e-5)


def test_blocked_path_is_the_plain_path_with_the_blocked_kernel(
        blocked_kernel, monkeypatch):
    """``LagunaBlock`` where the blocked kernel runs (the rotary and gate
    kernels around it, interpret mode) against the same model on the
    plain path (``apply_rope``, the plain attention, the gate in XLA), in
    float32: loss and gradients agree as two float32 softmaxes do."""
    from commefficient_tpu.models import laguna
    model, params, ids = blocked_kernel(remat=False)
    calls = []
    monkeypatch.setattr(laguna, "rope_to_heads", lambda *a, _f=laguna.
                        rope_to_heads, **kw: calls.append(1) or _f(*a, **kw))
    blocked = _grad(model, params, ids)
    assert len(calls) == model.cfg.num_hidden_layers
    plain = _grad(LagunaLM(model.cfg, attn_impl=attn.dense_grouped_attention),
                  params, ids)
    assert len(calls) == model.cfg.num_hidden_layers
    np.testing.assert_allclose(float(blocked[0]), float(plain[0]), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(blocked[1]), jax.tree.leaves(plain[1]),
                    strict=True):
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4 * scale, rtol=0)
