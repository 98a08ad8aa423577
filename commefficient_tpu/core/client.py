"""Client-side computation: microbatched gradients, local compression state,
and the FedAvg local-SGD loop.

Re-designs CommEfficient/fed_worker.py (process_batch / local_step /
forward_grad / the fedavg branch of worker_loop) as pure functions over a
*static-shape* per-client batch. The reference runs a Python loop over
variable-size client batches inside worker processes; here every client batch
is padded to a fixed shape with a validity mask, microbatching is a
``lax.scan``, and the whole per-client step is ``vmap``-ed (or shard_map-ed)
over the round's client axis by the runtime.

Loss-function contract
----------------------
``loss_fn(params_pytree, batch_pytree, mask) -> (mean_loss, metrics_tuple)``
where every leaf of ``batch_pytree`` has a leading batch axis, ``mask`` is a
float/bool validity vector over that axis, and ``mean_loss``/metrics are means
over *valid* items. (The reference's ``compute_loss_train`` returns
``(loss, *metrics)``, cv_train.py:67-83.)
"""

from __future__ import annotations

import math
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from commefficient_tpu.config import FedConfig
from commefficient_tpu.ops import clip_by_l2_norm, topk
from commefficient_tpu.telemetry.profiling import phase


class ClientOut(NamedTuple):
    transmit: jax.Array                # transmitted-space quantity, x n_c
    velocity: Optional[jax.Array]      # updated local velocity row (or None)
    error: Optional[jax.Array]         # updated local error row (or None)
    results: Tuple[jax.Array, ...]     # (mean_loss, *metrics) over the batch
    n_valid: jax.Array                 # () number of valid datums processed
    # per-client population stats (telemetry/clients.py CLIENT_GRAD_KEYS
    # -> scalar), threaded only when the runtime's telemetry gating asks
    # for them (FedRuntime._client_stats) — None otherwise so the arrays
    # are compiled out entirely under --no_telemetry/--no_client_stats
    stats: Optional[dict] = None


# fold constant decorrelating the noise-attack draw from every other
# consumer of the per-client round key (DP noise uses the key directly)
_ADV_FOLD = 0xAD5E


def flip_labels(batch: dict, adv: jax.Array, num_classes: int,
                key: str = "target") -> dict:
    """Label-flipping injection (data space): adversarial clients train
    on ``(C-1) - y`` — the standard flip of the label-poisoning
    literature. ``adv`` is the round's (W,) per-slot adversary mask;
    applied on the full (W, B, ...) batch BEFORE the client compute, so
    it works identically under the vmap, fused and fedavg paths."""
    if key not in batch:
        raise ValueError(
            f"--adversary labelflip needs a {key!r} batch leaf (integer "
            f"class labels); this batch has {sorted(batch)} — label "
            "flipping is only defined for classification datasets")
    t = batch[key]
    advb = adv.reshape((-1,) + (1,) * (t.ndim - 1))
    return {**batch, key: jnp.where(advb, (num_classes - 1) - t, t)}


def inject_adversary(cfg: FedConfig, tx: jax.Array, adv: jax.Array,
                     rngs: jax.Array,
                     n_valid: Optional[jax.Array] = None) -> jax.Array:
    """Update-space adversarial injection, applied to the per-client
    transmitted quantities ``tx`` (W, ...) — dense gradients, sketch
    tables or fedavg weight deltas alike (every kind below commutes with
    the datum weighting already folded into ``tx``):

    - signflip: upload x -1 (gradient-ascent poisoning);
    - scale:    upload x adversary_scale (the boosted / model-replacement
                attack);
    - noise:    upload + adversary_scale * N(0, I) in transmitted space,
                drawn per client from its round key (deterministic);
    - nan:      upload all-NaN (the broken-client case
                --nonfinite_action exists to survive).

    A slot with no valid datums (``n_valid == 0``) uploads NOTHING — a
    masked-out client (scenario participation, quarantine bench) has no
    upload to corrupt, so injecting into its zero placeholder would
    fabricate strikes for a client that never participated.
    """
    kind = cfg.adversary
    if kind in ("none", "labelflip"):
        return tx
    if n_valid is not None:
        adv = adv & (n_valid > 0)
    advb = adv.reshape((-1,) + (1,) * (tx.ndim - 1))
    if kind == "signflip":
        return jnp.where(advb, -tx, tx)
    if kind == "scale":
        return jnp.where(advb, cfg.adversary_scale * tx, tx)
    if kind == "noise":
        noise = jax.vmap(
            lambda r: jax.random.normal(jax.random.fold_in(r, _ADV_FOLD),
                                        tx.shape[1:], tx.dtype))(rngs)
        return jnp.where(advb, tx + cfg.adversary_scale * noise, tx)
    if kind == "nan":
        return jnp.where(advb, jnp.full_like(tx, jnp.nan), tx)
    raise ValueError(f"unknown adversary kind {kind!r}")


def quarantine_zero(tx: jax.Array, n_valid: jax.Array,
                    results: Tuple[jax.Array, ...]
                    ) -> Tuple[jax.Array, jax.Array,
                               Tuple[jax.Array, ...], jax.Array]:
    """Per-client nonfinite containment (``--nonfinite_action
    quarantine``): a client whose transmitted quantity OR loss went
    nonfinite is zeroed out of the round — its upload, its datum count
    (so the aggregate normalization excludes it) and its metric
    contributions (so the epoch accumulators stay finite). Returns
    ``(tx', n_valid', results', finite)`` with ``finite`` the (W,) bool
    flags the host-side QuarantineLedger consumes."""
    flat = tx.reshape(tx.shape[0], -1)
    fin = jnp.isfinite(flat).all(axis=1) & jnp.isfinite(results[0])
    finb = fin.reshape((-1,) + (1,) * (tx.ndim - 1))
    tx = jnp.where(finb, tx, 0.0)
    n_valid = jnp.where(fin, n_valid, 0.0)
    results = tuple(jnp.where(fin, r, 0.0) for r in results)
    return tx, n_valid, results, fin


def int8_wire_uploads(cfg: FedConfig, tx: jax.Array, step: jax.Array,
                      block: int, slot0=0) -> jax.Array:
    """Simulated int8 wire on PER-CLIENT table uploads (--wire_dtype
    int8, non-deferred encode — the path that keeps per-client tables
    for the table clip): each client's (r, c) table quantizes with
    per-column-block abs-max scales + stochastic rounding and
    dequantizes in f32 before the server sum — the server only ever
    sees what crossed the wire. Draws key off (seed, round, GLOBAL
    slot, cell): ``slot0`` offsets the local slot index by the mesh
    shard's base so shards never share a rounding stream. The residual
    ``tx - tx'`` is ordinary compression noise to the server EF."""
    from commefficient_tpu.ops.wire import wire_round_trip
    W = tx.shape[0]
    slots = jnp.arange(W, dtype=jnp.int32) + slot0
    return jax.vmap(
        lambda t, w: wire_round_trip(t, block, seed=cfg.seed,
                                     round_idx=step, salt=w))(tx, slots)


# coalesce adjacent gradient leaves into at-least-this-many-element
# chunks before the streaming encode: biases/layernorm leaves are tiny,
# and one encode_accum per 768-element leaf would pay the per-range
# block padding (and op count) hundreds of times per microbatch.
# Measured best CPU-ledger packing at 1024 (the encode working set stays
# a few blocks while the chunk count stays O(d / 1024)).
_ENCODE_CHUNK_MIN = 1024
# ... and split anything bigger than this into bounded ranges: one
# encode_accum's working set is ~4 chunk-sized buffers (signs, signed
# values, rolled, padding copy), so an uncapped 2M-element kernel leaf
# would put ~32 MB of encode temporaries next to the cotangents the
# fusion exists to shrink. Measured on the CPU ledger: capping at 64k
# cut the fused client scan's temp ~30% with no measurable wall cost
# (the cap only bounds PEAK residency; total encode work is unchanged).
# The cap SCALES with the sketch's d (see _encode_chunk_max): a fixed
# 64k cap at GPT-2 124M would unroll ~1900 encode_accum calls into the
# scan body — a compile-time explosion — while d/32 keeps the chunk
# count O(32) and the working set at ~d/8, far under the d*4 the
# fusion removes.
_ENCODE_CHUNK_MAX = 65536


def _encode_chunk_max(d: int) -> int:
    return max(_ENCODE_CHUNK_MAX, d // 32)


def encode_grad_tree(cs, table, gtree, scale=None, token=None,
                     min_chunk: int = _ENCODE_CHUNK_MIN,
                     max_chunk: int = 0):
    """Encode a gradient PYTREE into a carry sketch table, leaf range by
    leaf range, without ever concatenating the (d,) dense vector.

    The leaves are walked in ravel order (``jax.flatten_util``'s leaf
    order — the layout every ``unravel`` consumer shares), adjacent
    small leaves are coalesced into >= ``min_chunk``-element contiguous
    chunks, oversized leaves are split into <= ``max_chunk`` ranges (the
    encode working set stays bounded), and each chunk streams through
    ``cs.encode_accum`` at its static global offset. Chunks are encoded
    in REVERSE ravel order — the order the backward PRODUCES cotangents
    (last layer first) — so the table-accumulation chain never forces an
    early layer's not-yet-computed gradient ahead of a ready one, and
    the scheduler may free each cotangent at its encode. (XLA's CPU
    scheduler still keeps most of the tree resident — ~1.9x d*4 measured
    against the theoretical interleave; a scan-structured model that
    owns its backward gets all the way under d*4 via the
    ``streaming_grad`` hook, models/stream_mlp.py.) Exception: when the
    sketch's fused Pallas encode kernel is eligible (TPU, aligned
    shifts — CirculantSketch._use_pallas_encode), the whole-vector route
    is faster than per-chunk rolls, so the tree IS raveled once and
    encoded in one kernel call — one (d,) buffer inside the scan step
    instead of the unfused path's persistent (d,) carry pair. That
    ravel is the only d-long pass XLA makes there: it ends in the
    m·c - d zeros of the last block (on the last leaf, or a leaf of
    their own), ``scale`` goes to the kernel as a scalar and the kernel
    makes its wrap padding in VMEM (ops/circulant_pallas.py v7).

    Returns ``table + encode(scale * ravel(gtree))`` up to fp addition
    order (sketch linearity; pinned by tests/test_fused_encode.py).
    """
    with phase("fed_sketch_encode"):
        leaves = jax.tree_util.tree_leaves(gtree)
        if getattr(cs, "_use_pallas_encode", lambda: False)():
            # the ravel lands at the kernel's padded length in the pass
            # it makes anyway. The zeros that close the last block ride
            # on the last leaf where a copy of it is cheap (under 1/64
            # of the ravel): every operand is then still a leaf, and XLA
            # goes on moving the leaves' bf16 -> f32 converts past the
            # concatenate (ResNet-50: leaves born f32 hold 26 MB more at
            # the round's peak). Behind a large last leaf (GPT-2's
            # embedding) they are a leaf of their own
            flats = [l.reshape(-1) for l in leaves]
            size = sum(f.shape[0] for f in flats)
            tail = cs.m * cs.c - size
            if 64 * flats[-1].shape[0] <= size:
                flats[-1] = jnp.pad(flats[-1], (0, tail))
            else:
                flats.append(jnp.zeros((tail,), flats[-1].dtype))
            flat = jnp.concatenate(flats)
            return cs.encode_accum(table, flat, 0, scale=scale, token=token)
        if max_chunk <= 0:
            max_chunk = _encode_chunk_max(int(getattr(cs, "d", 0)))
        chunks = []          # (static start, [flat leaf pieces])
        cur, cur_n, cur_start, off = [], 0, 0, 0
        for leaf in leaves:
            flat = leaf.reshape(-1)
            n, pos = int(flat.size), 0
            while n - pos > 0:
                if not cur:
                    cur_start = off + pos
                take = min(n - pos, max_chunk - cur_n)
                cur.append(flat[pos:pos + take]
                           if (pos or take < n) else flat)
                cur_n += take
                pos += take
                if cur_n >= max_chunk:
                    chunks.append((cur_start, cur))
                    cur, cur_n = [], 0
            off += n
            if cur_n >= min_chunk:
                chunks.append((cur_start, cur))
                cur, cur_n = [], 0
        if cur:
            chunks.append((cur_start, cur))
        for start, pieces in reversed(chunks):
            vals = pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)
            table = cs.encode_accum(table, vals, start, scale=scale,
                                    token=token)
        return table


def fused_encode_blockers(cfg: FedConfig, signals: bool = False) -> list:
    """Config-level blockers of the fused sketch encode
    (``--sketch_fused_encode``), mirroring the fail-fast style of
    ``validate_async_combo`` / ``validate_defense_combo``: every entry
    names the dense-space consumer that makes accumulating in table
    space unsound, and what to change. Returns the (possibly empty)
    blocker list; ``FedRuntime`` merges in the topology/impl-dependent
    blockers (dense-preimage server state, the rht transform, defenses
    on the deferred-dense uploads, vmap-path grad stats) and raises
    under ``--sketch_fused_encode on``. ``signals`` is whether the
    per-round signal diagnostics are actually live (telemetry on, no
    async split) — ``--signals_exact`` only blocks then.
    """
    problems = []
    if cfg.mode != "sketch":
        problems.append(
            f"--mode {cfg.mode} has no sketch encode to fuse")
        return problems
    if cfg.do_dp:
        problems.append(
            "--dp clips and noises the DENSE per-client gradient "
            "(l2_norm_clip + worker noise) before the encode; fusing "
            "would skip the privacy mechanism. Drop --dp, or run the "
            "unfused round")
    if cfg.sketch_dense_clip:
        problems.append(
            "--sketch_dense_clip clips the DENSE worker gradient before "
            "the encode; the fused path never materializes it. Use the "
            "table-Frobenius clip (--max_grad_norm without "
            "--sketch_dense_clip), which stays available fused")
    if cfg.signals_exact and signals:
        problems.append(
            "--signals_exact threads a dense shadow EF accumulator pair "
            "(and the exact dense-error top-k) through the round — both "
            "need the dense aggregated gradient the fusion removes. "
            "Drop --signals_exact (or --no_signals)")
    return problems


def _num_microbatches(cfg: FedConfig, batch_size: int) -> Tuple[int, int]:
    if cfg.microbatch_size > 0:
        mb = min(batch_size, cfg.microbatch_size)
    else:
        mb = batch_size
    return math.ceil(batch_size / mb), mb


def make_forward_grad(
    cfg: FedConfig,
    loss_fn: Callable,
    unravel: Callable[[jax.Array], Any],
    batch_size: int,
    defer_encode: bool = False,
    with_stats: bool = False,
    fused_encode: bool = False,
):
    """Build the microbatched forward/backward (reference fed_worker.py:249-335).

    Returns ``fwd(params_vec, batch, mask, rng, cs) ->
    (g, results, n_valid, stats)`` where ``g`` is in transmitted space:
    the accumulated sum over microbatches of per-microbatch mean
    gradients (matching the reference's ``loss.backward()``
    accumulation), with decoupled weight decay ``wd/num_workers * w``
    added (reference utils.py:254-259), grad-norm clipping, optional DP
    clip+noise, and mode compression (sketch encode).

    ``with_stats`` (telemetry/clients.py): also return per-client scalar
    diagnostics — the dense gradient norm before any clip
    (``grad_norm_pre``), after all clips and DP noise but before encode
    (``grad_norm_post``), and whether the applicable clip actually bound
    (``clip_frac``, NaN when no clip applies). ``stats`` is None when
    disabled, so the extra reductions are compiled out.

    ``fused_encode`` (sketch mode only; FedRuntime gates soundness):
    the microbatch scan carries the (r, c) Count Sketch TABLE instead of
    the (d,) dense gradient sum — each microbatch's gradient is taken
    against the parameter PYTREE (no ravel concat) and streamed into the
    carry via ``encode_grad_tree`` (sum-of-sketches == sketch-of-sum,
    the FetchSGD linearity), so a per-microbatch gradient lives only
    inside one scan step and the returned ``g`` IS the client's table.
    The weight-decay term encodes separately by the same linearity.
    Escape hatch for scan-structured models: a ``loss_fn`` carrying a
    ``streaming_grad`` attribute — ``streaming_grad(params_vec,
    mb_batch, mb_mask, cs, table, scale=None) -> (table, loss,
    metrics)`` — owns its own backward and streams per-LAYER gradients
    into the table (no whole-model gradient pytree at all; contract
    pinned by tests/test_fused_encode.py). Requires no dense-space
    consumer (dense clip/DP/stats) — the runtime validates; asserted
    here. The table-Frobenius clip stays available (per-table op).
    """
    num_iters, mb = _num_microbatches(cfg, batch_size)
    pad_to = num_iters * mb
    if fused_encode:
        # max_grad_norm WITHOUT --sketch_dense_clip is the table-
        # Frobenius clip — a per-table op the fused path applies to its
        # own carry below, so it stays available (as today)
        assert cfg.mode == "sketch" and not with_stats \
            and not cfg.do_dp and not cfg.sketch_dense_clip, \
            "fused_encode eligibility is the runtime's job (see " \
            "FedRuntime); an ineligible combination reached the client"

    def loss_on_vec(vec, mb_batch, mb_mask):
        loss, metrics = loss_fn(unravel(vec), mb_batch, mb_mask)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_on_vec, has_aux=True)
    # fused-encode: differentiate w.r.t. the PYTREE. Mathematically the
    # same leaf cotangents (unravel is slice+reshape; its VJP is the
    # concatenation we are eliminating) — but the concat never happens,
    # and neither does its in-scan transpose (one pad-to-(d,)-and-add
    # per leaf, measured 131x d·4 temp on the CPU backend).
    tree_grad_fn = (jax.value_and_grad(loss_fn, has_aux=True)
                    if fused_encode else None)
    stream = (getattr(loss_fn, "streaming_grad", None)
              if fused_encode else None)

    def fwd(params_vec, batch, mask, rng, cs=None):
        # ``cs`` is threaded as a CALL-TIME argument (not a closure): its
        # arrays — at GPT-2 scale the int8 sign table alone is ~670 MB —
        # must be jit inputs, not constants baked into (and shipped with)
        # the serialized HLO
        mask = mask.astype(jnp.float32)
        if pad_to != batch_size:
            pad = pad_to - batch_size
            batch = jax.tree.map(
                lambda t: jnp.pad(t, [(0, pad)] + [(0, 0)] * (t.ndim - 1)),
                batch)
            mask = jnp.pad(mask, (0, pad))
        micro_batches = jax.tree.map(
            lambda t: t.reshape((num_iters, mb) + t.shape[1:]), batch)
        micro_masks = mask.reshape(num_iters, mb)

        params = unravel(params_vec) if fused_encode else None

        def body(carry, inp):
            g_acc, loss_acc, metrics_acc = carry
            mb_batch, mb_mask = inp
            if fused_encode:
                # g_acc is the (r, c) table: the per-microbatch gradient
                # exists only inside this step (as leaf cotangents, or
                # not at all on the streaming path)
                if stream is not None:
                    g_acc, loss, metrics = stream(params_vec, mb_batch,
                                                  mb_mask, cs, g_acc)
                else:
                    (loss, metrics), gtree = tree_grad_fn(
                        params, mb_batch, mb_mask)
                    g_acc = encode_grad_tree(cs, g_acc, gtree, token=loss)
            else:
                (loss, metrics), g = grad_fn(params_vec, mb_batch, mb_mask)
                g_acc = g_acc + g
            w = mb_mask.sum()
            metrics_acc = jax.tree.map(
                lambda a, m: a + m * w, metrics_acc, tuple(metrics))
            return (g_acc, loss_acc + loss * w, metrics_acc), None

        # probe metrics structure without running the model twice: metrics
        # accumulators start at zero scalars shaped like the loss outputs
        metrics_zero = tuple(
            jnp.zeros(()) for _ in range(cfg.num_results_train - 1))
        if fused_encode:
            assert cs is not None, "fused encode requires the runtime's sketch"
            g_init = cs.empty_table()
        else:
            g_init = jnp.zeros_like(params_vec)
        init = (g_init, jnp.zeros(()), metrics_zero)
        (g, loss_sum, metrics_sum), _ = lax.scan(
            body, init, (micro_batches, micro_masks))

        n_valid = mask.sum()
        denom = jnp.maximum(n_valid, 1.0)
        results = (loss_sum / denom,) + tuple(
            m / denom for m in metrics_sum)

        # decoupled weight decay (reference utils.py:254-259). Seq-sharded
        # rounds sum per-shard terms then divide by the shard count in the
        # runtime's aggregation, so no per-shard correction is needed here.
        # Fused-encode: the wd term is linear too, so it encodes straight
        # into the table (whole-vector range — the Pallas route when
        # eligible) instead of forcing a dense g back into existence.
        if cfg.weight_decay != 0:
            if fused_encode:
                with phase("fed_sketch_encode"):
                    g = cs.encode_accum(
                        g, params_vec, 0,
                        scale=cfg.weight_decay / cfg.num_workers,
                        token=loss_sum)
            else:
                g = g + (cfg.weight_decay / cfg.num_workers) * params_vec
        stats = None
        if with_stats:
            # telemetry/clients.py: the clip threshold this client's
            # gradient is measured against — DP takes precedence (its
            # clip runs after, on the already-clipped gradient, and is
            # the binding one for DP runs); NaN when nothing clips
            pre = jnp.sqrt(jnp.vdot(g, g)).astype(jnp.float32)
            if cfg.do_dp:
                thresh = jnp.float32(cfg.l2_norm_clip)
            elif cfg.max_grad_norm is not None and (
                    cfg.mode != "sketch" or cfg.sketch_dense_clip):
                thresh = jnp.float32(cfg.max_grad_norm * num_iters)
            else:
                thresh = jnp.float32(jnp.nan)
            stats = {
                "grad_norm_pre": pre,
                "clip_frac": jnp.where(jnp.isnan(thresh), jnp.nan,
                                       (pre > thresh).astype(jnp.float32)),
            }
        # grad-norm clipping for dense modes (reference fed_worker.py:290-292;
        # threshold scales with the number of accumulation steps). Not
        # available seq-sharded (the runtime forbids it): the clip needs the
        # norm of the SUMMED client gradient, which per-shard norms cannot
        # provide (partials are not orthogonal). --sketch_dense_clip
        # extends the same PRE-encode clip to sketch mode (the reference
        # can only clip the post-encode table, fed_worker.py:318-319 — by
        # sketch linearity the same rescaling at a matched threshold, but
        # with bare instead of x num_iters threshold semantics; measured
        # study in runs/gpt2_conv/README.md).
        if cfg.max_grad_norm is not None and (
                cfg.mode != "sketch" or cfg.sketch_dense_clip):
            g = clip_by_l2_norm(g, cfg.max_grad_norm * num_iters)
        # differential privacy (reference fed_worker.py:304-309)
        if cfg.do_dp:
            g = clip_by_l2_norm(g, cfg.l2_norm_clip)
            if cfg.dp_mode == "worker":
                noise = cfg.noise_multiplier * jnp.sqrt(
                    1.0 * cfg.num_workers) * jax.random.normal(
                        rng, g.shape, g.dtype)
                g = g + noise
        if with_stats:
            # post-clip/post-noise dense norm: what this client actually
            # contributes through the channel (measured BEFORE the
            # sketch encode so the space matches grad_norm_pre)
            stats["grad_norm_post"] = jnp.sqrt(
                jnp.vdot(g, g)).astype(jnp.float32)
        # mode compression (reference fed_worker.py:312-333). When
        # ``defer_encode`` the runtime exploits sketch linearity
        # (sum-of-sketches == sketch-of-sum) to encode ONCE after the
        # cross-client sum instead of once per client — legal whenever no
        # per-client nonlinearity acts on the table (no table clip).
        # Fused-encode: ``g`` already IS this client's table, so only
        # the per-table ops (the Frobenius clip) remain.
        if cfg.mode == "sketch" and fused_encode:
            if cfg.max_grad_norm is not None and not cfg.sketch_dense_clip:
                # reference semantics: clip the TABLE (fed_worker.py:318)
                g = cs.clip(g, cfg.max_grad_norm)
        elif cfg.mode == "sketch" and not defer_encode:
            assert cs is not None, "sketch mode requires the runtime's sketch"
            with phase("fed_sketch_encode"):
                table = cs.encode(g)
            if cfg.max_grad_norm is not None and not cfg.sketch_dense_clip:
                # reference semantics: clip the TABLE (fed_worker.py:318)
                table = cs.clip(table, cfg.max_grad_norm)
            g = table
        return g, results, n_valid, stats

    return fwd


def make_fused_grad(
    cfg: FedConfig,
    loss_fn: Callable,
    unravel: Callable[[jax.Array], Any],
    batch_size: int,
    fused_encode: bool = False,
):
    """Jointly-computed round gradient: one microbatch scan over ALL of the
    round's clients instead of ``vmap(per-client scan)``.

    The aggregation the server consumes is ``sum_c n_c * g_c`` where
    ``g_c = sum_mb grad(mean loss of mb) + wd-term`` (fed_worker.py:190 +
    fed_aggregator.py:332 weighting). When no per-client nonlinearity
    intervenes (no local momentum/error rows, no per-client clip/DP/table
    op — ``FedRuntime._fused`` checks), that sum is linear in the
    per-microbatch gradients, so it can be accumulated into ONE (d,)
    buffer with each microbatch's gradient weighted by its client's datum
    count. The vmapped path instead materializes a per-client (W, d)
    gradient (2.9 GB at GPT-2 92M x 8 clients) and, inside the backward,
    W separate embedding-gradient accumulators — the profiler measured
    ~67 ms/round of the flagship GPT-2 round in exactly those per-client
    wte-gradient buffers (runs/profile_gpt2/BREAKDOWN.md).

    ``fused_encode`` (sketch mode; FedRuntime gates soundness) goes one
    step further down the same linearity: the scan carry is the (r, c)
    Count Sketch TABLE, each microbatch's gradient pytree streams into
    it via ``encode_grad_tree`` scaled by its client's datum count, and
    the round's ONE (d,) accumulator disappears too — the returned ``g``
    is the round's summed table (sketch-of-weighted-sum). The runtime's
    deferred encode-once then becomes a no-op (the degenerate case).

    Exactness relies on microbatches never straddling clients: requires
    ``batch_size % microbatch == 0`` (checked by the runtime's
    eligibility predicate). Per-client results/n_valid keep their (W,)
    shapes — each microbatch's owning client index rides the scan xs.
    """
    num_iters, mb = _num_microbatches(cfg, batch_size)
    assert num_iters * mb == batch_size, (num_iters, mb, batch_size)
    if fused_encode:
        assert cfg.mode == "sketch" and not cfg.do_dp \
            and not cfg.sketch_dense_clip and cfg.max_grad_norm is None, \
            "fused_encode eligibility is the runtime's job (see FedRuntime)"

    def loss_on_vec(vec, mb_batch, mb_mask):
        return loss_fn(unravel(vec), mb_batch, mb_mask)

    grad_fn = jax.value_and_grad(loss_on_vec, has_aux=True)
    # fused-encode: differentiate w.r.t. the PYTREE (see make_forward_grad
    # — same cotangents, no concat and no in-scan pad-to-(d,) transpose)
    tree_grad_fn = (jax.value_and_grad(loss_fn, has_aux=True)
                    if fused_encode else None)
    stream = (getattr(loss_fn, "streaming_grad", None)
              if fused_encode else None)

    def fused(params_vec, batch, mask, cs=None):
        W = mask.shape[0]
        maskf = mask.astype(jnp.float32)
        n_per_client = maskf.sum(axis=1)                     # (W,)
        flat = jax.tree.map(
            lambda t: t.reshape((W * num_iters, mb) + t.shape[2:]), batch)
        flat_mask = maskf.reshape(W * num_iters, mb)
        n_res = cfg.num_results_train

        client_of_mb = jnp.repeat(jnp.arange(W), num_iters)
        nc_of_mb = jnp.repeat(n_per_client, num_iters)

        params = unravel(params_vec) if fused_encode else None

        def body(carry, inp):
            g_acc, sums = carry
            mb_batch, mb_mask, c, nc = inp
            if fused_encode:
                # g_acc is the round's (r, c) table: the microbatch
                # gradient exists only inside this step, scaled by its
                # client's datum count on the way in (linearity)
                if stream is not None:
                    g_acc, loss, metrics = stream(params_vec, mb_batch,
                                                  mb_mask, cs, g_acc,
                                                  scale=nc)
                else:
                    (loss, metrics), gtree = tree_grad_fn(
                        params, mb_batch, mb_mask)
                    g_acc = encode_grad_tree(cs, g_acc, gtree, scale=nc,
                                             token=loss)
            else:
                (loss, metrics), g = grad_fn(params_vec, mb_batch, mb_mask)
                g_acc = g_acc + g * nc
            w = mb_mask.sum()
            sums = sums.at[:, c].add(
                jnp.stack((loss,) + tuple(metrics)) * w)
            return (g_acc, sums), None

        # decoupled weight decay, summed over the round's clients (equal to
        # the per-client term (wd/W)*w scaled by n_c and summed); fused-
        # encode streams it into a table by the same linearity
        wd_scale = (cfg.weight_decay / cfg.num_workers) * n_per_client.sum()
        wd_table = None
        if fused_encode:
            assert cs is not None, "fused encode requires the runtime's sketch"
            g_init = cs.empty_table()
            if cfg.weight_decay != 0:
                # made before the scan and added after it: the barrier
                # holds XLA to that order, so the whole f32 parameter
                # vector (all-gathered on a mesh) is dead before the
                # clients' activations come alive. Left to itself XLA
                # kept a d-long copy of it across the loop once the
                # encode was one kernel call and a pad (+90 MB on a peak
                # of 4.09 GiB a chip, ResNet-50 on four chips, PR 31)
                with phase("fed_sketch_encode"):
                    wd_table = cs.encode_accum(g_init, params_vec, 0,
                                               scale=wd_scale)
                wd_table, g_init = lax.optimization_barrier(
                    (wd_table, g_init))
        else:
            g_init = jnp.zeros_like(params_vec)
        init = (g_init, jnp.zeros((n_res, W)))
        (g, sums), _ = lax.scan(
            body, init, (flat, flat_mask, client_of_mb, nc_of_mb))
        if wd_table is not None:
            with phase("fed_sketch_encode"):
                g = g + wd_table
        elif cfg.weight_decay != 0:
            g = g + wd_scale * params_vec
        denom = jnp.maximum(n_per_client, 1.0)
        results = tuple(sums[j] / denom for j in range(n_res))
        return g, results, n_per_client

    return fused


def make_client_step(
    cfg: FedConfig,
    loss_fn: Callable,
    unravel: Callable[[jax.Array], Any],
    batch_size: int,
    defer_encode: bool = False,
    with_stats: bool = False,
    fused_encode: bool = False,
):
    """Single-round client step: forward_grad + local momentum / error /
    local-topk pipeline (reference fed_worker.py:184-230).

    Returns ``step(params_vec, batch, mask, velocity, error, rng, cs)
    -> ClientOut``.
    ``velocity``/``error`` are this client's persistent rows (or None when the
    mode doesn't allocate them, reference fed_aggregator.py:105-129).

    ``fused_encode`` (sketch mode — which forbids local momentum/error
    rows, so the post-fwd pipeline below is shape-agnostic): ``g`` comes
    back as this client's (r, c) table and the datum-count weighting /
    quarantine / injection all act on it by sketch linearity.

    Seq-sharded rounds (runtime seq axis): the loss closure itself carries
    the seq semantics (losses.make_gpt2_train_loss seq_axis); this step is
    per-shard linear and the runtime handles the cross-shard sum/scale.
    """
    fwd = make_forward_grad(cfg, loss_fn, unravel, batch_size,
                            defer_encode=defer_encode,
                            with_stats=with_stats,
                            fused_encode=fused_encode)

    def step(params_vec, batch, mask, velocity, error, rng,
             cs=None) -> ClientOut:
        g, results, n_valid, stats = fwd(params_vec, batch, mask, rng, cs)
        # weight by datum count: the server divides by the round's total
        # (reference fed_worker.py:190, fed_aggregator.py:332)
        g = g * n_valid

        new_velocity, new_error = velocity, error
        if cfg.local_momentum > 0:
            new_velocity = cfg.local_momentum * velocity + g
            base = new_velocity
        else:
            base = g

        if cfg.error_type == "local":
            new_error = error + base
            to_transmit = new_error
        else:
            to_transmit = base

        if cfg.mode == "local_topk":
            to_transmit = topk(to_transmit, k=cfg.k, approx=cfg.approx_topk)
            nz = to_transmit != 0
            if new_error is not None:
                new_error = jnp.where(nz, 0.0, new_error)   # error feedback
            if cfg.local_momentum > 0:
                new_velocity = jnp.where(nz, 0.0, new_velocity)  # factor mask

        if stats is not None:
            # update-contribution norm: the transmitted quantity AFTER
            # local momentum / error feedback / local-topk — dense L2,
            # or table Frobenius for the non-deferred sketch encode
            stats["tx_norm"] = jnp.sqrt(
                jnp.vdot(to_transmit, to_transmit)).astype(jnp.float32)
        return ClientOut(to_transmit, new_velocity, new_error, results,
                         n_valid, stats)

    return step


def make_fedavg_client(
    cfg: FedConfig,
    loss_fn: Callable,
    unravel: Callable[[jax.Array], Any],
    batch_size: int,
    with_stats: bool = False,
):
    """FedAvg local-SGD loop (reference fed_worker.py:61-113).

    The client's whole (padded) dataset arrives as one batch; it is split
    into ``fedavg_batch_size`` chunks, trained for ``num_fedavg_epochs``
    epochs of local SGD with per-step decay ``fedavg_lr_decay**step``, and
    the dataset-size-weighted weight delta is transmitted.

    Returns ``step(params_vec, batch, mask, lr, rng) -> ClientOut``
    (fedavg transmits raw weight deltas; no sketch argument).
    """
    if cfg.fedavg_batch_size == -1:
        chunk = batch_size
    else:
        chunk = min(cfg.fedavg_batch_size, batch_size)
    n_chunks = math.ceil(batch_size / chunk)
    pad_to = n_chunks * chunk
    fwd = make_forward_grad(cfg, loss_fn, unravel, chunk)

    def step(params_vec, batch, mask, lr, rng) -> ClientOut:
        mask = mask.astype(jnp.float32)
        n_c = mask.sum()
        if pad_to != batch_size:
            pad = pad_to - batch_size
            batch = jax.tree.map(
                lambda t: jnp.pad(t, [(0, pad)] + [(0, 0)] * (t.ndim - 1)),
                batch)
            mask = jnp.pad(mask, (0, pad))
        chunks = jax.tree.map(
            lambda t: t.reshape((n_chunks, chunk) + t.shape[1:]), batch)
        chunk_masks = mask.reshape(n_chunks, chunk)

        n_steps = n_chunks * cfg.num_fedavg_epochs
        rngs = jax.random.split(rng, n_steps).reshape(
            (cfg.num_fedavg_epochs, n_chunks) + rng.shape)

        def chunk_body(carry, inp):
            w, step_idx, res_acc = carry
            c_batch, c_mask, c_rng = inp
            g, results, n_valid, _ = fwd(w, c_batch, c_mask, c_rng)
            # fully-padded chunks (mask all zero) must be no-ops: no SGD
            # step (g would still carry the weight-decay term), no decay
            # advance, no metric contribution — the reference only ever
            # iterates real minibatches (fed_worker.py:68-77)
            valid = (n_valid > 0).astype(jnp.float32)
            # g is the (possibly multi-microbatch) mean-gradient sum; the
            # reference divides the transmitted sum back by the chunk size
            # before stepping (fed_worker.py:96-100) — our fwd already
            # returns the per-chunk mean accumulation, so apply it directly.
            decay = cfg.fedavg_lr_decay ** step_idx
            w = w - g * (lr * decay * valid)
            res_acc = jax.tree.map(lambda a, r: a + r * n_valid,
                                   res_acc, tuple(results))
            return (w, step_idx + valid, res_acc), None

        def epoch_body(carry, epoch_rngs):
            # inner scan closes over the one resident copy of the chunks
            # (reference's epoch x chunk loops, fed_worker.py:82-101)
            carry, _ = lax.scan(chunk_body, carry,
                                (chunks, chunk_masks, epoch_rngs))
            return carry, None

        res_zero = tuple(jnp.zeros(()) for _ in range(cfg.num_results_train))
        (w_final, _, res_acc), _ = lax.scan(
            epoch_body, (params_vec, 0.0, res_zero), rngs)

        # datum-weighted means over the client's real data
        total = jnp.maximum(n_c * cfg.num_fedavg_epochs, 1.0)
        results = tuple(r / total for r in res_acc)
        # dataset-size weighting (reference fed_worker.py:104-108)
        transmit = (params_vec - w_final) * n_c
        stats = None
        if with_stats:
            # fedavg transmits a weight delta, not a gradient: the
            # per-chunk gradient norms are not the population signal
            # (and straddle the local SGD trajectory), so only the
            # update-contribution norm is meaningful — the rest stay
            # NaN ("not applicable"), never silently zero
            nan = jnp.full((), jnp.nan, jnp.float32)
            stats = {"grad_norm_pre": nan, "grad_norm_post": nan,
                     "clip_frac": nan,
                     "tx_norm": jnp.sqrt(
                         jnp.vdot(transmit, transmit)).astype(jnp.float32)}
        return ClientOut(transmit, None, None, results, n_c, stats)

    return step


def make_val_step(cfg: FedConfig, loss_fn: Callable,
                  unravel: Callable[[jax.Array], Any]):
    """Masked evaluation (reference fed_worker.py:179-181 with
    compute_grad=False): returns (results_tuple, n_valid)."""

    def val(params_vec, batch, mask):
        mask = mask.astype(jnp.float32)
        loss, metrics = loss_fn(unravel(params_vec), batch, mask)
        return (loss,) + tuple(metrics), mask.sum()

    return val


def topk_down_weights(cfg: FedConfig, ps_weights: jax.Array,
                      worker_weights: jax.Array) -> jax.Array:
    """Download-compression emulation (reference fed_worker.py:232-247):
    the client's stale weights advance by the top-k of its lag."""
    diff = ps_weights - worker_weights
    return worker_weights + topk(diff, k=cfg.k, approx=cfg.approx_topk)
