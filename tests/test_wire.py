"""Int8 quantized sketch wire (--wire_dtype int8; ops/wire.py).

Numpy-reference checks of the quantizer (bit-exact hash + rounding),
stochastic-rounding determinism incl. across a resume, unbiasedness,
EF absorption, int8==f32 trajectory parity, the exact wire byte
accounting, the eligibility fail-fasts, the schema-v9 wire fields and
the teleview --wire_bytes_growth gate.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.config import FedConfig, parse_args
from commefficient_tpu.core import FedRuntime
from commefficient_tpu.ops.wire import (INT8_MAX, dequantize_accum,
                                        dequantize_table, quantize_table,
                                        wire_round_trip, wire_uniform)

# ---------------------------------------------------------------- numpy ref

_M32 = np.uint32(0xFFFFFFFF)


def _np_mix32(h):
    h = h.astype(np.uint64)
    h ^= h >> np.uint64(16)
    h = (h * np.uint64(0x85EBCA6B)) & np.uint64(0xFFFFFFFF)
    h ^= h >> np.uint64(13)
    h = (h * np.uint64(0xC2B2AE35)) & np.uint64(0xFFFFFFFF)
    h ^= h >> np.uint64(16)
    return h.astype(np.uint32)


def _np_wire_uniform(r, c, seed, round_idx, salt):
    rows = np.arange(r, dtype=np.uint64)
    cols = np.arange(c, dtype=np.uint64)
    base = ((rows[:, None] * np.uint64(0x01000193) + cols[None, :])
            & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    seed_mix = np.uint32((seed * 0x9E3779B1 + 0x7F4A7C15) & 0xFFFFFFFF)
    h = _np_mix32(base ^ seed_mix)
    rs = _np_mix32(np.uint32((round_idx * 0x85EBCA77
                              + salt * 0xC2B2AE3D) & 0xFFFFFFFF))
    h = _np_mix32((h.astype(np.uint64) + np.uint64(rs))
                  .astype(np.uint32) & _M32)
    return (h >> np.uint32(8)).astype(np.float32) * np.float32(2.0 ** -24)


def _np_quantize(table, block, seed, round_idx, salt):
    r, c = table.shape
    g = table.astype(np.float32).reshape(r, c // block, block)
    absmax = np.max(np.abs(g), axis=2)
    scale = (absmax / np.float32(INT8_MAX)).astype(np.float32)
    safe = np.where(scale > 0, scale, np.float32(1.0)).astype(np.float32)
    x = (g / safe[:, :, None]).astype(np.float32)
    u = _np_wire_uniform(r, c, seed, round_idx, salt)
    q = np.floor((x + u.reshape(r, c // block, block))
                 .astype(np.float32))
    q = np.clip(q, -INT8_MAX, INT8_MAX)
    return q.reshape(r, c).astype(np.int8), scale


def test_uniform_matches_numpy_reference():
    u = np.asarray(wire_uniform(7, 96, seed=21, round_idx=jnp.int32(5),
                                salt=jnp.int32(3)))
    ref = _np_wire_uniform(7, 96, 21, 5, 3)
    assert (u == ref).all()
    assert 0.0 <= u.min() and u.max() < 1.0
    # well spread (a broken mixer collapses toward constants)
    assert abs(u.mean() - 0.5) < 0.05


def test_quantize_matches_numpy_reference():
    rng = np.random.RandomState(0)
    t = rng.randn(3, 256).astype(np.float32)
    q, s = quantize_table(jnp.asarray(t), 64, seed=21,
                          round_idx=jnp.int32(7), salt=jnp.int32(1))
    qn, sn = _np_quantize(t, 64, 21, 7, 1)
    assert (np.asarray(s) == sn).all()
    assert (np.asarray(q) == qn).all()
    # dequantize round-trips within one quantization step per cell
    d = np.asarray(dequantize_table(q, s, 64))
    per_block_scale = np.repeat(sn, 64, axis=1)
    assert (np.abs(d - t) <= per_block_scale + 1e-7).all()
    assert np.abs(d - t).max() > 0  # the wire genuinely quantizes


def test_stochastic_rounding_deterministic_and_round_keyed():
    rng = np.random.RandomState(1)
    t = jnp.asarray(rng.randn(2, 128).astype(np.float32))
    q1, _ = quantize_table(t, 64, seed=3, round_idx=jnp.int32(9),
                           salt=jnp.int32(0))
    q2, _ = quantize_table(t, 64, seed=3, round_idx=jnp.int32(9),
                           salt=jnp.int32(0))
    assert (np.asarray(q1) == np.asarray(q2)).all()
    q3, _ = quantize_table(t, 64, seed=3, round_idx=jnp.int32(10),
                           salt=jnp.int32(0))
    q4, _ = quantize_table(t, 64, seed=3, round_idx=jnp.int32(9),
                           salt=jnp.int32(1))
    assert (np.asarray(q1) != np.asarray(q3)).any()
    assert (np.asarray(q1) != np.asarray(q4)).any()


def test_stochastic_rounding_unbiased():
    rng = np.random.RandomState(2)
    t = jnp.asarray(rng.randn(2, 128).astype(np.float32))
    f = jax.jit(lambda r: wire_round_trip(t, 64, seed=5, round_idx=r,
                                          salt=jnp.int32(0)))
    N = 2000
    acc = np.zeros((2, 128), np.float64)
    for r in range(N):
        acc += np.asarray(f(jnp.int32(r)))
    bias = acc / N - np.asarray(t)
    _, s = quantize_table(t, 64, seed=5, round_idx=jnp.int32(0),
                          salt=jnp.int32(0))
    # per-cell bias of an unbiased rounder is N(0, scale^2/12N)-ish;
    # 6 sigma over 256 cells with headroom
    bound = 6 * float(np.max(np.asarray(s))) / np.sqrt(12 * N)
    assert np.abs(bias).max() < max(bound, 1e-3), (np.abs(bias).max(),
                                                   bound)


def test_zero_and_nan_blocks():
    t = jnp.zeros((2, 128), jnp.float32)
    out = wire_round_trip(t, 64, seed=1, round_idx=jnp.int32(1), salt=0)
    assert (np.asarray(out) == 0).all()
    tn = t.at[1, 70].set(jnp.nan)
    outn = np.asarray(wire_round_trip(tn, 64, seed=1,
                                      round_idx=jnp.int32(1), salt=0))
    # the NaN poisons exactly its own block — the wire never launders a
    # non-finite upload into finite int8 cells
    assert np.isnan(outn[1, 64:]).all()
    assert np.isfinite(outn[0]).all() and np.isfinite(outn[1, :64]).all()


def test_dequantize_accum_matches_per_source_sum():
    rng = np.random.RandomState(3)
    qs, ss, ref = [], [], np.zeros((3, 128), np.float32)
    for i in range(4):
        t = rng.randn(3, 128).astype(np.float32)
        q, s = quantize_table(jnp.asarray(t), 32, seed=9,
                              round_idx=jnp.int32(2), salt=jnp.int32(i))
        qs.append(np.asarray(q))
        ss.append(np.asarray(s))
        ref += np.asarray(dequantize_table(q, s, 32))
    out = dequantize_accum(jnp.asarray(np.stack(qs)),
                           jnp.asarray(np.stack(ss)), 32)
    assert np.allclose(np.asarray(out), ref, rtol=1e-6, atol=1e-6)


def test_sketch_class_wire_entry_points():
    """The impl-agnostic quantize_wire/dequantize_wire methods on both
    sketch classes are thin delegates to ops/wire.py — pinned here so
    the convenience surface can never drift from the real quantizer."""
    from commefficient_tpu.ops.circulant import make_circulant_sketch
    from commefficient_tpu.ops.sketch import make_sketch
    rng = np.random.RandomState(4)
    t = jnp.asarray(rng.randn(3, 256).astype(np.float32))
    for cs in (make_sketch(1000, 256, 3),
               make_circulant_sketch(1000, 256, 3)):
        q, s = cs.quantize_wire(t, 64, seed=7, round_idx=jnp.int32(2),
                                salt=jnp.int32(1))
        qr, sr = quantize_table(t, 64, seed=7, round_idx=jnp.int32(2),
                                salt=jnp.int32(1))
        assert (np.asarray(q) == np.asarray(qr)).all()
        assert (np.asarray(s) == np.asarray(sr)).all()
        d = cs.dequantize_wire(q, s, 64)
        assert (np.asarray(d)
                == np.asarray(dequantize_table(qr, sr, 64))).all()


# --------------------------------------------------- config + accounting


def test_upload_wire_bytes_accounting():
    base = dict(mode="sketch", error_type="virtual", num_rows=3,
                num_cols=512, grad_size=4096)
    f32 = FedConfig(**base)
    assert f32.wire_dtype == "float32"
    assert f32.upload_wire_bytes() == 4.0 * 3 * 512
    bf16 = FedConfig(wire_dtype="bfloat16", **base)
    assert bf16.upload_wire_bytes() == 2.0 * 3 * 512
    int8 = FedConfig(wire_dtype="int8", wire_block=64, **base)
    # 1 byte/cell + 4 bytes of f32 scale per 64-cell block
    assert int8.upload_wire_bytes() == 3 * 512 + 4 * 3 * (512 // 64)
    # the runtime passes its resolved effective block
    assert int8.upload_wire_bytes(block=128) == 3 * 512 + 4 * 3 * 4
    # dense modes keep the 4-byte float wire
    unc = FedConfig(mode="uncompressed", error_type="none",
                    grad_size=1000)
    assert unc.upload_wire_bytes() == 4.0 * 1000


def test_sketch_dtype_alias_resolution():
    # direct construction: wire inherits the legacy field
    cfg = FedConfig(mode="sketch", error_type="virtual",
                    sketch_dtype="bfloat16")
    assert cfg.wire_dtype == "bfloat16"
    # an explicit bf16 wire syncs the rht transform compute dtype
    cfg2 = FedConfig(mode="sketch", error_type="virtual",
                     wire_dtype="bfloat16")
    assert cfg2.sketch_dtype == "bfloat16"
    # int8 wire leaves sketch_dtype f32 (no bf16 transform implied)
    cfg3 = FedConfig(mode="sketch", error_type="virtual",
                     wire_dtype="int8")
    assert cfg3.sketch_dtype == "float32"
    # an explicit int8 wire WINS over the bf16 alias: sketch_dtype is
    # forced back to f32 so the runtime's bf16 rounding branch can
    # never shadow the int8 wire (and byte accounting stays truthful)
    cfg4 = FedConfig(mode="sketch", error_type="virtual",
                     sketch_dtype="bfloat16", wire_dtype="int8")
    assert cfg4.sketch_dtype == "float32"
    assert cfg4.wire_dtype == "int8"
    cfg5 = parse_args(["--mode", "sketch", "--sketch_dtype", "bfloat16",
                       "--wire_dtype", "int8"])
    assert cfg5.sketch_dtype == "float32" and cfg5.wire_dtype == "int8"
    # ... and an explicit f32 wire wins too: the runtime's bf16 branch
    # keys off sketch_dtype, so leaving it bf16 would arm a wire the
    # config claims is f32
    cfg6 = parse_args(["--mode", "sketch", "--sketch_dtype", "bfloat16",
                       "--wire_dtype", "float32"])
    assert cfg6.sketch_dtype == "float32" and cfg6.wire_dtype == "float32"
    assert cfg6.upload_wire_bytes() == 4.0 * cfg6.upload_floats


def test_sketch_dtype_parse_time_deprecation(capsys):
    cfg = parse_args(["--mode", "sketch", "--sketch_dtype", "bfloat16"])
    err = capsys.readouterr().err
    assert "deprecated" in err and "--wire_dtype" in err
    assert cfg.wire_dtype == "bfloat16"
    # explicit --wire_dtype wins over the alias
    cfg2 = parse_args(["--mode", "sketch", "--sketch_dtype", "bfloat16",
                       "--wire_dtype", "int8"])
    assert cfg2.wire_dtype == "int8"
    # no alias, no warning
    capsys.readouterr()
    cfg3 = parse_args(["--mode", "sketch"])
    assert "deprecated" not in capsys.readouterr().err
    assert cfg3.wire_dtype == "float32"


def test_int8_fail_fasts():
    with pytest.raises(ValueError, match="mode sketch"):
        FedConfig(mode="uncompressed", error_type="none",
                  wire_dtype="int8")
    with pytest.raises(ValueError, match="rht"):
        FedConfig(mode="sketch", error_type="virtual", sketch_impl="rht",
                  wire_dtype="int8")
    with pytest.raises(ValueError, match="dense"):
        FedConfig(mode="sketch", error_type="virtual",
                  sketch_server_state="dense", wire_dtype="int8")
    with pytest.raises(ValueError, match="wire_block"):
        FedConfig(mode="sketch", error_type="virtual", wire_block=4)
    with pytest.raises(ValueError, match="wire_dtype"):
        FedConfig(mode="sketch", error_type="virtual", wire_dtype="fp8")


# ------------------------------------------------------- runtime trajectory

_D, _C = 12, 10


def _linear_loss():
    key = jax.random.PRNGKey(0xDEF)
    P = jax.random.normal(jax.random.fold_in(key, 1), (_D, _C),
                          jnp.float32)

    def loss_fn(params, batch, mask):
        logits = batch["x"] @ params["w"]
        m = mask.astype(jnp.float32)
        denom = jnp.maximum(m.sum(), 1.0)
        lp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(lp, batch["target"][:, None],
                                   axis=1)[:, 0]
        loss = (nll * m).sum() / denom
        acc = ((logits.argmax(1) == batch["target"]) * m).sum() / denom
        return loss, (acc,)

    def batch_for(W, B, g):
        k1 = jax.random.fold_in(key, 1000 + g)
        x = jax.random.normal(k1, (W, B, _D), jnp.float32)
        t = jnp.argmax(x @ P, axis=-1).astype(jnp.int32)
        return {"x": x, "target": t}

    return loss_fn, batch_for


def _wire_cfg(**kw):
    base = dict(mode="sketch", error_type="virtual", local_momentum=0.0,
                virtual_momentum=0.9, weight_decay=0.0, num_workers=4,
                local_batch_size=8, k=8, num_rows=3, num_cols=64,
                num_blocks=2, num_clients=4, track_bytes=True,
                num_results_train=2)
    base.update(kw)
    return FedConfig(**base)


def _run_rounds(cfg, n_rounds, state=None, start=1):
    loss_fn, batch_for = _linear_loss()
    rt = FedRuntime(cfg, {"w": jnp.zeros((_D, _C), jnp.float32)},
                    loss_fn, num_clients=cfg.num_workers)
    if state is None:
        state = rt.init_state()
    ids = jnp.arange(cfg.num_workers, dtype=jnp.int32)
    mask = jnp.ones((cfg.num_workers, 8), bool)
    losses, err_norms = [], []
    for g in range(start, start + n_rounds):
        state, m = rt.round(state, ids, batch_for(cfg.num_workers, 8, g),
                            mask, 0.3)
        losses.append(float(np.asarray(m["results"][0]).mean()))
        err_norms.append(float(np.linalg.norm(np.asarray(state.Verror))))
    return rt, state, np.asarray(losses), np.asarray(err_norms)


def test_int8_trajectory_parity_and_ef_absorption():
    """int8 == f32 within the committed band on a short learning curve
    (the hard-v2-style dryrun contract), and the quantized run's EF
    accumulator stays bounded relative to f32 — the rounding residual
    is ABSORBED, not accumulated (it is zero-mean by construction)."""
    _, _, l32, e32 = _run_rounds(_wire_cfg(), 16)
    _, _, l8, e8 = _run_rounds(_wire_cfg(wire_dtype="int8"), 16)
    assert np.all(np.isfinite(l8))
    # learning happened in both arms and the curves track each other
    assert l8[-1] < l8[0]
    assert abs(l8[-1] - l32[-1]) <= 0.10 * abs(l32[-1]) + 1e-3, (l8, l32)
    # EF absorption: bounded vs the f32 run's accumulator trajectory
    assert e8[-1] <= 2.0 * e32[-1] + 1e-3, (e8, e32)
    assert np.all(e8 <= 2.0 * np.maximum(e32, e32.max()) + 1e-3)


def test_int8_bitwise_replay_across_resume():
    """The rounding draws key off the CHECKPOINTED round counter: a run
    split at round 3 and continued from a state snapshot in a FRESH
    runtime replays rounds 4..6 bitwise."""
    cfg = _wire_cfg(wire_dtype="int8")
    loss_fn, batch_for = _linear_loss()
    ids = jnp.arange(4, dtype=jnp.int32)
    mask = jnp.ones((4, 8), bool)

    def rounds(rt, state, lo, hi):
        ls = []
        for g in range(lo, hi + 1):
            state, m = rt.round(state, ids, batch_for(4, 8, g), mask, 0.3)
            ls.append(np.asarray(m["results"][0]))
        return state, np.stack(ls)

    rt_a = FedRuntime(cfg, {"w": jnp.zeros((_D, _C), jnp.float32)},
                      loss_fn, num_clients=4)
    _, la = rounds(rt_a, rt_a.init_state(), 1, 6)

    rt_b = FedRuntime(cfg, {"w": jnp.zeros((_D, _C), jnp.float32)},
                      loss_fn, num_clients=4)
    sb, lb_head = rounds(rt_b, rt_b.init_state(), 1, 3)
    snap = jax.tree.map(lambda x: None if x is None else np.asarray(x),
                        sb)
    del rt_b, sb
    rt_c = FedRuntime(cfg, {"w": jnp.zeros((_D, _C), jnp.float32)},
                      loss_fn, num_clients=4)
    sc = jax.tree.map(lambda x: None if x is None else jnp.asarray(x),
                      snap)
    _, lb_tail = rounds(rt_c, sc, 4, 6)
    lb = np.concatenate([lb_head, lb_tail])
    assert (la == lb).all(), (la, lb)


def test_int8_upload_bytes_in_round_metrics():
    cfg = _wire_cfg(wire_dtype="int8")
    loss_fn, batch_for = _linear_loss()
    rt = FedRuntime(cfg, {"w": jnp.zeros((_D, _C), jnp.float32)},
                    loss_fn, num_clients=4)
    state = rt.init_state()
    ids = jnp.arange(4, dtype=jnp.int32)
    _, m = rt.round(state, ids, batch_for(4, 8, 1),
                    jnp.ones((4, 8), bool), 0.3)
    up = float(np.asarray(m["upload_bytes"]).sum())
    # effective block on one device: min(wire_block, c) = 64
    expected = 4 * cfg.upload_wire_bytes(block=rt._wire_block)
    assert up == expected
    assert up < 4 * 4.0 * cfg.upload_floats  # genuinely below f32


def test_int8_mesh_reduce_matches_numpy_reference(devices):
    """The quantized all_to_all reduce (ops/wire.int8_reduce_scatter
    under shard_map) equals the numpy reference: per-device quantize
    (salt = device index) -> dequantize -> sum, column-shard layout."""
    if len(devices) < 8:
        pytest.skip("needs 8 virtual devices")
    from jax.sharding import Mesh, PartitionSpec as P

    from commefficient_tpu.ops.wire import REDUCE_SALT, int8_reduce_scatter
    from jax import shard_map

    n, r, c, blk = 8, 3, 512, 64
    mesh = Mesh(np.array(devices[:8]), ("clients",))
    rng = np.random.RandomState(11)
    partials = rng.randn(n, r, c).astype(np.float32)

    def blk_fn(part, step):
        return int8_reduce_scatter(part[0], axis="clients", n_shards=n,
                                   block=blk, seed=21, round_idx=step)

    out = shard_map(blk_fn, mesh=mesh,
                    in_specs=(P("clients", None, None), P()),
                    out_specs=P(None, "clients"),
                    check_vma=False)(jnp.asarray(partials),
                                     jnp.int32(5))
    out = np.asarray(out)
    assert out.shape == (r, c)
    ref = np.zeros((r, c), np.float32)
    for i in range(n):
        # the reduce quantizer salts in its own namespace (REDUCE_SALT
        # offset) so it can never share a draw stream with a slot-
        # salted per-client upload in the same round
        q, s = _np_quantize(partials[i], blk, 21, 5, REDUCE_SALT + i)
        ref += np.repeat(s, blk, axis=1) * q.astype(np.float32)
    assert np.allclose(out, ref, rtol=1e-5, atol=1e-5), (
        np.abs(out - ref).max())


# ------------------------------------------------- telemetry + tooling


def test_collective_wire_bytes_model():
    from commefficient_tpu.telemetry.collectives import (
        collective_wire_bytes, table_reduce_wire_bytes)
    rs = {"kind": "reduce-scatter", "bytes": 768, "n_elements": 192}
    a2a = {"kind": "all-to-all", "bytes": 1536, "n_elements": 1536}
    ar = {"kind": "all-reduce", "bytes": 100, "n_elements": 25}
    ag = {"kind": "all-gather", "bytes": 800, "n_elements": 200}
    n = 8
    assert collective_wire_bytes(rs, n) == 768 * 7
    assert collective_wire_bytes(a2a, n) == 1536 * 7 / 8
    assert collective_wire_bytes(ar, n) == 2 * 100 * 7 / 8
    assert collective_wire_bytes(ag, n) == 800 * 7 / 8
    assert collective_wire_bytes(rs, 1) == 0.0
    # only the table-REDUCE kinds count
    assert table_reduce_wire_bytes([rs, a2a, ar, ag], n) == \
        768 * 7 + 1536 * 7 / 8
    # the ISSUE-14 ratio at the gate geometry: int8 cells + f32 scales
    # vs the f32 reduce-scatter of the same (3, 512) table
    scales = {"kind": "all-to-all", "bytes": 96, "n_elements": 24}
    f32_bytes = table_reduce_wire_bytes([rs], n)
    int8_bytes = table_reduce_wire_bytes([a2a, scales], n)
    assert int8_bytes <= 0.30 * f32_bytes


def test_schema_v9_wire_fields():
    from commefficient_tpu.telemetry.schema import validate_event
    ev = {"event": "collectives", "t": 0.0, "seq": 1, "name": "round_step",
          "n_collectives": 3, "counts": {"all-to-all": 2},
          "total_bytes": 2000, "ops": []}
    # a v8 stream legitimately omits the wire fields...
    assert validate_event(ev, version=8) == []
    # ...a v9 stream must carry them...
    problems = validate_event(ev, version=9)
    assert any("wire_dtype" in p for p in problems)
    assert any("table_reduce_bytes" in p for p in problems)
    # ...and they type-check (null allowed — single-device runs)
    ev.update(wire_dtype="int8", table_reduce_bytes=1428.0)
    assert validate_event(ev, version=9) == []
    ev.update(wire_dtype=None, table_reduce_bytes=None)
    assert validate_event(ev, version=9) == []
    sig = {"event": "signals", "t": 0.0, "seq": 2, "round": 1,
           "mode": "sketch"}
    for k in ("grad_norm", "grad_true_norm", "grad_l2estimate",
              "velocity_norm", "error_norm", "error_l2estimate",
              "update_norm", "support_density", "topk_overlap",
              "download_bytes", "upload_bytes", "client_download_bytes",
              "client_upload_bytes"):
        sig[k] = None
    assert any("wire_dtype" in p for p in validate_event(sig, version=9))
    sig["wire_dtype"] = "bfloat16"
    assert validate_event(sig, version=9) == []
    bench = {"event": "bench", "t": 0.0, "seq": 3, "metric": "x",
             "result": {}}
    assert any("wire_dtype" in p
               for p in validate_event(bench, version=9))
    bench["wire_dtype"] = "float32"
    assert validate_event(bench, version=9) == []


def test_telemetry_events_carry_wire_dtype(tmp_path):
    from commefficient_tpu.telemetry import RunTelemetry
    from commefficient_tpu.telemetry.schema import validate_file
    cfg = _wire_cfg(wire_dtype="int8")
    tel = RunTelemetry(str(tmp_path), "test", cfg=cfg)
    tel.bench_event("m", {"value": 1.0})
    tel.collectives_event("round_step", [
        {"kind": "all-to-all", "n_elements": 1536, "dtype": "s8",
         "bytes": 1536, "combined_in": 0}])
    tel.write_summary(aborted=False, n_rounds=0)
    tel.close()
    assert validate_file(tel.path) == []
    events = [json.loads(ln) for ln in open(tel.path)]
    bench = next(e for e in events if e["event"] == "bench")
    assert bench["wire_dtype"] == "int8"
    coll = next(e for e in events if e["event"] == "collectives")
    assert coll["wire_dtype"] == "int8"
    # manifest sketch geometry names the wire too
    man = events[0]
    assert man["sketch"]["wire_dtype"] == "int8"


def _load_teleview():
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "teleview.py")
    spec = importlib.util.spec_from_file_location("teleview_wire", path)
    tv = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tv)
    return tv


def _mini_stream(path, table_reduce_bytes):
    events = [
        {"event": "manifest", "t": 0.0, "seq": 0, "schema": 9,
         "run_type": "t", "jax_version": "0", "backend": "cpu",
         "device_kind": "cpu", "device_count": 8, "mesh_shape": [8],
         "mesh_axes": ["clients"], "grad_size": 10, "sketch": None,
         "config": {}, "stream_id": "t-0-0"},
        {"event": "collectives", "t": 1.0, "seq": 1, "name": "round_step",
         "n_collectives": 1, "counts": {"all-to-all": 2},
         "total_bytes": 2000, "ops": [], "wire_dtype": "int8",
         "table_reduce_bytes": table_reduce_bytes},
        {"event": "summary", "t": 2.0, "seq": 2, "run_type": "t",
         "aborted": False, "n_rounds": 1, "total_download_mib": None,
         "total_upload_mib": None, "wall_time_s": 1.0,
         "event_counts": {}, "final": None},
    ]
    with open(path, "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")
    return str(path)


def test_teleview_wire_bytes_growth_gate(tmp_path):
    tv = _load_teleview()
    a = _mini_stream(tmp_path / "a.jsonl", 1428.0)
    b_ok = _mini_stream(tmp_path / "b.jsonl", 1450.0)     # +1.5%
    b_bad = _mini_stream(tmp_path / "c.jsonl", 5376.0)    # re-widened
    assert tv.main(["diff", a, b_ok]) == 0
    assert tv.main(["diff", a, b_bad]) == 1
    # explicit threshold slackening passes
    assert tv.main(["diff", a, b_bad, "--wire_bytes_growth", "10"]) == 0
