"""Server-side update rules for the five federated modes.

Pure-functional re-design of the reference's ``get_server_update`` dispatch
and ``_server_helper_*`` family (CommEfficient/fed_aggregator.py:469-613).
The reference mutates momentum/error buffers in place and pokes per-client
velocity arrays through module globals; here every rule is

    (gradient, Vvelocity, Verror, lr) -> (update, Vvelocity', Verror', mask)

with no side effects, so the whole thing jits and differentiates state
threading explicitly. ``mask`` is the boolean nonzero-support of the update in
*transmitted* space (dense coords, or sketch-table cells), returned so the
runtime can apply the reference's momentum-factor-masking to participating
clients' local velocities (fed_aggregator.py:528-533 — note the reference has
a latent bug there: ``g_participating_clients`` is assigned without ``global``
at fed_aggregator.py:220, so its masking never fires; we implement the
documented intent).

Error-feedback/masking scatters (`Verror[update.nonzero()] = 0`) are expressed
as ``jnp.where`` with the support mask — branch-free, fusable, no scatters.

Legal (mode x error_type x momentum) combinations follow the reference's
runtime asserts (fed_worker.py:221-228, fed_aggregator.py:484-486, 512,
545, 573-576); see ``validate_mode_combo``.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from commefficient_tpu.config import FedConfig
from commefficient_tpu.ops.topk import (local_topk_candidates,
                                        merge_topk_candidates,
                                        topk_with_idx)

# a k-sparse weight update as (indices, values), see _support
Support = Tuple[jax.Array, jax.Array]

# Measured divergence envelopes (round 5). local_topk with LOCAL error
# feedback learns only with the LR cut far below the dense-stable value:
# the committed hard-v2 run at lr 0.1 sat at chance (9.7%), the numpy
# transcription of the reference's own dynamics (scripts/local_topk_sim
# --sweep) shows loss ratios of ~4e5x at lr 0.1 / k/d=0.08 and learning
# only at lr ~0.005-0.01, and the TPU confirmation arms learned at 0.01
# and not 0.1 (runs/README.md "local_topk ... with receipts").
LOCAL_TOPK_EF_STABLE_LR = 0.02
# subtract-EF at high collision load: every GPT-2-scale arm (d/c ~ 176)
# diverged at rounds 7-29, with LATER divergence at LOWER load — a dose
# response (runs/gpt2_conv/README.md) — while d/c ~ 13 (CIFAR flagship)
# is the rule's decisive win. The boundary between those measurements:
SUBTRACT_EF_STABLE_LOAD = 100.0


def check_regime_health(cfg: FedConfig) -> List[str]:
    """Warnings for configurations round 5 MEASURED divergent.

    Unlike ``validate_mode_combo`` (illegal combinations), these configs
    are legal and exist to be studied — but a user reaching one by
    accident deserves the measurement up front, not 24 epochs of chance
    accuracy (VERDICT weak #3). Returns human-readable warnings; the
    caller prints them to stderr, or raises under --strict_regimes.
    Needs cfg.grad_size resolved (the collision load is d/c), so it runs
    at runtime init alongside validate_mode_combo.
    """
    warnings: List[str] = []
    if (cfg.mode == "local_topk" and cfg.error_type == "local"
            and cfg.lr_scale is not None
            and cfg.lr_scale > LOCAL_TOPK_EF_STABLE_LR):
        warnings.append(
            f"mode=local_topk with error_type=local at lr_scale="
            f"{cfg.lr_scale} is in the MEASURED divergent regime: local "
            "error feedback at real compression needs the lr cut to "
            f"~{LOCAL_TOPK_EF_STABLE_LR} or below (hard-v2 at lr 0.1 sat "
            "at chance; the reference's own dynamics, transcribed in "
            "scripts/local_topk_sim.py --sweep, diverge identically — "
            "runs/README.md). Cut --lr_scale, or use error_type=none "
            "(tolerates ~10x higher lr and recovered most of true_topk's "
            "quality at the same compression)")
    if (cfg.mode == "sketch" and cfg.sketch_ef == "subtract"
            and cfg.sketch_server_state != "dense" and cfg.grad_size
            and cfg.grad_size / cfg.num_cols >= SUBTRACT_EF_STABLE_LOAD):
        warnings.append(
            f"--sketch_ef subtract at collision load d/c = "
            f"{cfg.grad_size / cfg.num_cols:.0f} (d={cfg.grad_size}, "
            f"c={cfg.num_cols}) is in the MEASURED divergent regime: "
            "every GPT-2-scale arm at d/c ~ 176 died by round 29, with "
            "a dose response in d/c (runs/gpt2_conv/README.md). Use "
            f"d/c < {SUBTRACT_EF_STABLE_LOAD:.0f} (raise --num_cols), "
            "or DROP --sketch_ef subtract and use --sketch_server_state "
            "dense (its own exact-support EF rule is already leak-free "
            "AND stable at this load; the two flags together are "
            "rejected), or the default --sketch_ef zero")
    return warnings


def validate_regimes(cfg: FedConfig) -> None:
    """Print measured-divergence warnings (stderr — stdout belongs to
    the byte-stable console loggers); raise under --strict_regimes."""
    warnings = check_regime_health(cfg)
    if not warnings:
        return
    if cfg.strict_regimes:
        raise ValueError(
            "--strict_regimes: refusing measured-divergent config:\n  "
            + "\n  ".join(warnings))
    import sys
    for w in warnings:
        print(f"WARNING: {w}", file=sys.stderr)


def validate_defense_combo(cfg: FedConfig, mesh=None,
                           seq_axis=None) -> None:
    """Reject adversary/defense/quarantine configurations that cannot be
    implemented soundly on this topology — the fail-fast companion of
    validate_mode_combo for the robustness subsystem."""
    robust = (cfg.defense != "none" or cfg.adversary != "none"
              or cfg.nonfinite_action != "abort")
    if not robust:
        return
    if seq_axis is not None:
        # inside a seq-sharded round each shard holds only its PARTIAL
        # per-client gradient: per-client norms/finite flags/injections
        # computed per shard would describe partials, not clients (the
        # same reason max_grad_norm is forbidden with a seq axis)
        raise ValueError(
            "--adversary/--defense/--nonfinite_action quarantine are "
            "unsupported with a seq mesh axis: they act on PER-CLIENT "
            "transmitted quantities, and a seq-sharded round only ever "
            "holds per-shard partials of them")
    if cfg.defense == "trim" and mesh is not None:
        raise ValueError(
            "--defense trim needs the per-coordinate cross-client sort, "
            "which requires every client's full transmitted vector on "
            "one device — unavailable on a mesh (the client axis is "
            "sharded). Use --defense normclip on a mesh (its cross-shard "
            "cost is one W-sized norm all-gather), or drop the mesh.")
    if cfg.adversary == "labelflip":
        from commefficient_tpu.config import FED_DATASETS
        n_cls = FED_DATASETS.get(cfg.dataset_name, 0)
        if n_cls < 2:
            raise ValueError(
                f"--adversary labelflip needs a classification dataset "
                f"with >= 2 classes; {cfg.dataset_name!r} has "
                f"{n_cls if n_cls > 0 else 'no fixed class count'} — use "
                "signflip/scale/noise/nan for update-space attacks "
                "instead")


def robust_aggregate(cfg: FedConfig, tx: jax.Array, n_valid: jax.Array,
                     ref_thresh: Optional[jax.Array] = None,
                     axis_name: Optional[str] = None):
    """Robust aggregation of the per-client transmitted quantities
    (``--defense``), traced inside the jitted round's client block.

    ``tx`` is (W, ...) — each client's datum-weighted upload (dense
    gradient x n_c, sketch table x n_c, or fedavg delta x n_c);
    ``n_valid`` its (W,) datum counts. All statistics are over the
    PER-DATUM update ``tx_i / n_i`` so differently-sized clients are
    commensurable. Returns ``(agg, cur_med, stats)`` where ``agg``
    replaces the plain ``tx.sum(axis=0)``, ``cur_med`` is this round's
    median per-datum norm (the rolling-reference feed, normclip only —
    None otherwise) and ``stats`` holds the defense-event scalars.

    - **normclip** (Sun et al. 2019): clip each client's per-datum norm
      to ``ref x defense_clip_mult`` where ``ref`` is the rolling median
      of past rounds' median norms (``ref_thresh``; NaN on the first
      round falls back to THIS round's median — itself robust to a <50%
      adversarial cohort). An l2 clip is a rescaling, so it commutes
      with the linear sketch: clipping the dense gradient then encoding
      equals encoding then scaling the table by the same factor
      (pinned by tests/test_defense.py). On a mesh the per-shard norms
      all-gather over ``axis_name`` (W floats) so every shard clips
      against the same global median.
    - **trim** (Yin et al. 2018): per-coordinate trimmed mean — sort
      each coordinate across clients, drop ``floor(trim_frac * V)`` at
      each extreme (V = clients that carried data this round, NOT the
      slot count W: benched/masked placeholders hold no vote, see the
      in-body comment), average the rest uniformly, and rescale by the
      round's datum total so the caller's ``agg / n_total``
      normalization yields the trimmed mean itself. Single device only
      (validate_defense_combo).
    """
    from jax import lax

    W = tx.shape[0]
    denom = jnp.maximum(n_valid, 1.0)
    denb = denom.reshape((W,) + (1,) * (tx.ndim - 1))
    valid = n_valid > 0

    if cfg.defense == "trim":
        assert axis_name is None, "trim is single-device (validated)"
        # zero-datum slots (quarantine-benched, participation-masked)
        # carry NO vote: counting their 0/1 = 0 placeholder updates as
        # honest clients would silently dilute the trimmed mean toward
        # zero (with 2 live clients in an 8-slot round the defended
        # update would shrink 4x). Validity is PER-SLOT, so every
        # coordinate has the same count V of real values — push the
        # invalid slots to +inf, sort, and average ranks [t, V-t) with
        # a traced rank mask (t stays a fraction of the LIVE cohort).
        # A nonfinite upload from a live slot sorts last too: with
        # t >= 1 the trim absorbs it (that IS the defense); at t == 0
        # it poisons the mean and the nan_round abort fires as before.
        validb = valid.reshape((W,) + (1,) * (tx.ndim - 1))
        V = valid.sum()
        t = jnp.floor(cfg.defense_trim_frac * V).astype(jnp.int32)
        u = jnp.where(validb, tx / denb, jnp.inf)
        s = jnp.sort(u, axis=0)             # per-coordinate order stats
        rank = jnp.arange(W).reshape((W,) + (1,) * (tx.ndim - 1))
        keep = (rank >= t) & (rank < V - t)
        n_kept = jnp.maximum(V - 2 * t, 1)
        core_mean = jnp.where(keep, s, 0.0).sum(axis=0) / n_kept
        agg = core_mean * n_valid.sum()
        nan = jnp.full((), jnp.nan, jnp.float32)
        stats = {"clip_frac": nan, "clip_thresh": nan, "clipped_mass": nan,
                 "trim_frac": (2.0 * t / jnp.maximum(V, 1)
                               ).astype(jnp.float32)}
        return agg, None, stats

    assert cfg.defense == "normclip", cfg.defense
    flat = tx.reshape(W, -1)
    norms = jnp.sqrt((flat * flat).sum(axis=1)).astype(jnp.float32) / denom
    usable = valid & jnp.isfinite(norms)
    med_in = jnp.where(usable, norms, jnp.nan)
    if axis_name is not None:
        med_in = lax.all_gather(med_in, axis_name, tiled=True)
    cur_med = jnp.nanmedian(med_in).astype(jnp.float32)
    ref = jnp.where(jnp.isnan(ref_thresh), cur_med, ref_thresh)
    thresh = jnp.float32(cfg.defense_clip_mult) * ref
    factors = jnp.minimum(1.0, thresh / jnp.maximum(norms, 1e-12))
    factors = jnp.where(usable, factors, 1.0)
    agg = (tx * factors.reshape((W,) + (1,) * (tx.ndim - 1))).sum(axis=0)
    n_clipped = ((factors < 1.0) & usable).sum().astype(jnp.float32)
    removed_sq = jnp.where(
        usable, ((1.0 - factors) * norms * denom) ** 2, 0.0).sum()
    n_part = usable.sum().astype(jnp.float32)
    if axis_name is not None:
        n_clipped = lax.psum(n_clipped, axis_name)
        removed_sq = lax.psum(removed_sq, axis_name)
        n_part = lax.psum(n_part, axis_name)
    stats = {
        "clip_frac": n_clipped / jnp.maximum(n_part, 1.0),
        "clip_thresh": thresh,
        "clipped_mass": jnp.sqrt(removed_sq).astype(jnp.float32),
        "trim_frac": jnp.full((), jnp.nan, jnp.float32),
    }
    return agg, cur_med, stats


def validate_mode_combo(cfg: FedConfig) -> None:
    """Reject illegal mode/error/momentum combinations up front.

    The reference lets several illegal combos crash deep inside a worker
    process (fed_worker.py:221-228) or, worse, silently not train (sketch
    with error_type=none zero-sketches Verror forever,
    fed_aggregator.py:578-590); we fail fast with an explanation.
    """
    m, e = cfg.mode, cfg.error_type
    if m == "sketch":
        if (cfg.sketch_impl == "rht" and cfg.grad_size
                and cfg.num_rows * cfg.num_cols < cfg.grad_size):
            # measured (tests/test_learning.py sketch-regime study): at
            # r*c < d the SRHT top-k-over-JL-estimates update EXPANDS the
            # accumulated error instead of contracting it and training
            # diverges within tens of rounds — on every topology, with
            # either error-feedback rule. The count-sketch cell-zeroing
            # rule (circ/hash impls) dissipates k/c of the table's error
            # mass per round and is stable; circ is the default. Hard
            # error by default (the repo's fail-fast philosophy);
            # --allow_divergent_rht opts back in (e.g. to reproduce the
            # divergence study) with a stderr warning — stdout stays
            # machine-readable for the bench/driver contract.
            msg = ("sketch_impl=rht with r*c "
                   f"({cfg.num_rows * cfg.num_cols}) < grad_size "
                   f"({cfg.grad_size}) diverges under error feedback in "
                   "practice (measured: tests/test_learning.py); use "
                   "sketch_impl=circ (default) or hash for compressing "
                   "configurations — rht is safe only when r*c >= d")
            if not cfg.allow_divergent_rht:
                raise ValueError(
                    msg + ". Pass --allow_divergent_rht to proceed anyway.")
            import sys
            print(f"WARNING: {msg}", file=sys.stderr)
        if cfg.sketch_ef == "subtract" and (
                cfg.sketch_server_state == "dense"
                or cfg.sketch_impl == "rht"):
            # the dense-preimage server path (forced for rht's dense
            # transform, opt-in via --sketch_server_state dense) keeps
            # momentum/error as exact (d,) pre-images and zeroes them at
            # the update support — it has no table cells, so neither
            # table-space EF rule applies and the requested subtract rule
            # would be SILENTLY ignored (ADVICE.md). An EF study arm run
            # through this path would measure the wrong rule; fail fast.
            which = ("sketch_server_state=dense"
                     if cfg.sketch_server_state == "dense"
                     else "sketch_impl=rht (its dense transform admits no "
                          "table-cell rule)")
            raise ValueError(
                f"--sketch_ef subtract has no effect with {which}: that "
                "server path applies its own error-feedback rule (exact "
                "support zeroing on dense pre-images / the estimate-space "
                "equivalent) and would silently ignore the requested "
                "table-space subtract. Drop --sketch_ef subtract (these "
                "paths are already leak-free), or use sketch_impl=circ/"
                "hash with sketch_server_state=table to study the "
                "subtract rule.")
        if e != "virtual":
            raise ValueError(
                "mode=sketch requires error_type=virtual (FetchSGD). "
                "error_type=none would unsketch an all-zero error table and "
                "never update; error_type=local allocates client error rows "
                "that the reference's own worker forbids for sketch "
                "(fed_worker.py:221-222 — its server-side 'local' branch at "
                "fed_aggregator.py:579-580 is unreachable dead code), and "
                "unmasked client error rows grow without bound")
        if cfg.local_momentum > 0:
            raise ValueError("mode=sketch cannot use local momentum "
                             "(reference assert fed_worker.py:227-228)")
    elif m == "true_topk":
        if e != "virtual":
            raise ValueError("mode=true_topk requires error_type=virtual "
                             "(reference assert fed_aggregator.py:512)")
    elif m == "local_topk":
        if e not in ("local", "none"):
            raise ValueError("mode=local_topk requires error_type local|none "
                             "(reference assert fed_aggregator.py:545)")
    elif m == "fedavg":
        if e != "none" or cfg.local_momentum != 0:
            raise ValueError("fedavg requires error_type=none and "
                             "local_momentum=0 (reference utils.py:225-228)")
    elif m == "uncompressed":
        if e == "local":
            raise ValueError("mode=uncompressed cannot use local error "
                             "(reference assert fed_worker.py:221-222)")


def server_update(
    cfg: FedConfig,
    gradient: jax.Array,
    Vvelocity: jax.Array,
    Verror: jax.Array,
    lr: jax.Array,
    cs=None,
    dp_rng: Optional[jax.Array] = None,
    dense_preimage: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array, Optional[jax.Array],
           Optional[Support]]:
    """Dispatch to the mode's update rule (reference fed_aggregator.py:469-481).

    ``gradient`` is the aggregated transmitted quantity, already averaged by
    datum count (reference fed_aggregator.py:332). ``lr`` may be a scalar or a
    per-parameter vector (Fixup param groups, fed_aggregator.py:411-427).
    Returns (weight_update, Vvelocity', Verror', support_mask_or_None,
    support): ``support`` is the weight update once more as its k
    ``(indices, values)``, where the rule selects k winners and one
    scalar lr scales them (``_support``); None for a dense update.
    """
    rho = cfg.virtual_momentum
    if cfg.mode == "fedavg":
        # reference fed_aggregator.py:483-495: running average of weight
        # deltas; LR was already applied on the client, so update==Vvelocity.
        Vvel = gradient + rho * Vvelocity
        return Vvel, Vvel, Verror, None, None

    if cfg.mode == "uncompressed":
        # reference fed_aggregator.py:497-509
        Vvel = gradient + rho * Vvelocity
        grad = Vvel
        if cfg.do_dp and cfg.dp_mode == "server":
            noise = cfg.noise_multiplier * jax.random.normal(
                dp_rng, grad.shape, grad.dtype)
            grad = grad + noise
        return grad * lr, Vvel, Verror, None, None

    if cfg.mode == "true_topk":
        # reference fed_aggregator.py:511-542
        Vvel = gradient + rho * Vvelocity
        Verr = Verror + Vvel
        update, upd_idx = topk_with_idx(Verr, k=cfg.k,
                                        approx=cfg.approx_topk)
        support = _support(upd_idx, Verr[upd_idx], lr)
        mask = update != 0
        # error feedback + momentum factor masking at the update support
        Verr = jnp.where(mask, 0.0, Verr)
        Vvel = jnp.where(mask, 0.0, Vvel)
        if cfg.error_decay < 1.0:
            Verr = cfg.error_decay * Verr
        return update * lr, Vvel, Verr, mask, support

    if cfg.mode == "local_topk":
        # reference fed_aggregator.py:544-566: momentum accumulates onto the
        # already-sparse summed worker top-k; no virtual error, no masking.
        Vvel = gradient + rho * Vvelocity
        return Vvel * lr, Vvel, Verror, None, None

    if cfg.mode == "sketch":
        # FetchSGD core, reference fed_aggregator.py:568-613. All state lives
        # in (r, c) sketch-table space; tables are linear so the psum'd
        # worker tables equal the sketch of the summed gradient.
        assert cs is not None
        if dense_preimage:
            # Single-device SRHT fast path (runtime._dense_preimage):
            # momentum/error live as dense (d,) pre-images; ``gradient``
            # arrives dense (deferred encode skipped entirely), and ONE
            # enc+dec round-trip of the error injects the sketch noise —
            # that round-trip is exactly what the server "sees" through the
            # compressed channel. Because the pre-images are exact, the
            # reference's error feedback and momentum factor masking
            # ("zero Verror/Vvelocity where the update is nonzero",
            # fed_aggregator.py:596-611) apply EXACTLY at the support — the
            # structure of the true_topk rule with the sketch round-trip
            # inserted before the top-k. Reduces to true_topk bit-for-bit in
            # the lossless limit.
            Vvel = gradient + rho * Vvelocity
            Verr = Verror + Vvel
            ests = cs.decode(cs.encode(Verr))
            update, upd_idx = topk_with_idx(ests, k=cfg.k,
                                            approx=cfg.approx_topk)
            Verr = Verr.at[upd_idx].set(0.0)           # error feedback
            Vvel = Vvel.at[upd_idx].set(0.0)           # momentum mask
            if cfg.error_decay < 1.0:
                Verr = cfg.error_decay * Verr
            return (update * lr, Vvel, Verr, None,
                    _support(upd_idx, ests[upd_idx], lr))
        Vvel = gradient + rho * Vvelocity
        Verr = Verror + Vvel  # virtual error (the only legal type, see above)
        if getattr(cs, "dense_transform", False):
            # SRHT sketch (ops/rht.py): the transform of a k-sparse update is
            # dense, so "zero the occupied cells" (reference
            # fed_aggregator.py:596-611) would wipe the whole table. The
            # equivalent rule in estimate space: subtract the sketch of the
            # quantity the reference zeroes — the update itself for Verror,
            # and the velocity's estimated values at the update support for
            # Vvelocity (momentum factor masking). In the lossless limit
            # (c >= d', exact decode) this is bit-for-bit the reference rule.
            ests_err, ests_vel = cs.decode(jnp.stack([Verr, Vvel]))
            update, upd_idx = topk_with_idx(ests_err, k=cfg.k,
                                            approx=cfg.approx_topk)
            vel_at_support = jnp.zeros_like(ests_vel).at[upd_idx].set(
                ests_vel[upd_idx])
            enc_upd, enc_vel = cs.encode(jnp.stack([update, vel_at_support]))
            Verr = Verr - enc_upd
            Vvel = Vvel - enc_vel
            if cfg.error_decay < 1.0:
                Verr = cfg.error_decay * Verr
            return (update * lr, Vvel, Verr, None,
                    _support(upd_idx, ests_err[upd_idx], lr))
        update, upd_idx = cs.unsketch_with_idx(
            Verr, k=cfg.k, approx=cfg.approx_topk)
        support = _support(upd_idx, update[upd_idx], lr)
        # re-sketch the update to find which table cells it occupies
        # (reference fed_aggregator.py:593-595) — the update is k-sparse, so
        # the sparse encode is exact at O(k·r) instead of O(d·r)
        sketched_update = cs.encode_at(update, upd_idx)
        if cfg.sketch_ef == "subtract":
            # Subtractive error feedback (TPU-native extension, see
            # config.py sketch_ef): remove exactly the extracted estimates
            # instead of zeroing whole cells — colliding coordinates keep
            # their accumulated error. Momentum factor masking becomes
            # "subtract the velocity's estimated values at the support"
            # (the same transformation the reference's zeroing applies to
            # the cells, restricted to the extracted mass). Lossless limit
            # (c >= d, no collisions): bit-for-bit the zero rule.
            Vvel = Vvel - cs.encode_vals_at(cs.decode_at(Vvel, upd_idx),
                                            upd_idx)
            Verr = Verr - sketched_update
            mask = None
        else:
            mask = sketched_update != 0
            Vvel = jnp.where(mask, 0.0, Vvel)
            Verr = jnp.where(mask, 0.0, Verr)
        if cfg.error_decay < 1.0:
            Verr = cfg.error_decay * Verr
        return update * lr, Vvel, Verr, mask, support

    raise ValueError(f"unknown mode {cfg.mode}")


def _support(idx: jax.Array, vals: jax.Array,
             lr: jax.Array) -> Optional[Support]:
    """The k-sparse form ``(indices, values)`` of the update ``scatter(idx,
    vals) * lr`` — what lets the layer signals reduce k winners and never
    pass over d (telemetry/layer_signals.py). ``vals`` are the winners as
    the top-k scattered them (the same gather of the same operand, so XLA
    keeps one). A per-parameter lr vector (Fixup groups) leaves the dense
    update as the only form: None."""
    return None if jnp.ndim(lr) else (idx.astype(jnp.int32), vals * lr)


def sharded_sketch_server_update(
    cfg: FedConfig,
    agg_shard: jax.Array,
    Vvel_shard: jax.Array,
    Verr_shard: jax.Array,
    lr: jax.Array,
    cs,
    *,
    axis: str,
    n_shards: int,
    d_pad: int,
) -> Tuple[jax.Array, jax.Array, jax.Array, Optional[Support]]:
    """The sketch-mode server tail, SHARDED — traced inside a
    ``shard_map`` over ``axis`` (core/runtime.py wraps it; the
    replicated twin is ``server_update``'s table branch, and the
    sharded==replicated round-parity gate in ``dryrun_multichip`` pins
    the two to the same numerics).

    Per-shard view (device i of n): ``agg_shard``/``Vvel_shard``/
    ``Verr_shard`` are (r, c/n) COLUMN shards of the datum-normalized
    aggregate table and the momentum/EF state (the aggregate arrives
    reduce-scattered — the client block's ``psum_scatter`` replaced the
    replicated table psum). The tail:

    1. momentum + virtual error, elementwise on the shards (table-space
       linearity: column shards update independently);
    2. ONE small (r, c)-sized all-gather of the error table (stacked
       with the velocity table under the subtract-EF rule, which also
       needs velocity estimates at the winners) — the table is the
       compressed payload, gathering it is cheap by design;
    3. shard-local range decode: device i decodes ONLY global
       coordinates [i*d_pad/n, (i+1)*d_pad/n) (``cs.decode_range``;
       coordinates >= d decode to exactly 0) — the dense (d,) estimate
       vector NEVER materializes on any device, per-device temp drops
       from O(d) to O(d/n). Where the Pallas kernels serve the sketch
       (the TPU, aligned c) that is the decode kernel over the whole
       blocks covering the range (14 of 51 at d = 25.5M, n = 4) and one
       slice; elsewhere the per-element gather form, which a TPU runs
       one element at a time (295 of a 372 ms round there, PR 28's
       ledger). The estimates are the same bits either way;
    4. local top-k candidates + an (n, k_loc)-sized candidate
       all-gather + order-stable merge = the global top-k
       (ops/topk.local_topk_candidates / merge_topk_candidates —
       bitwise the unsharded selection, ties included);
    5. error feedback re-encoded from the k sparse winners
       (``encode_vals_at``, O(k*r) — every shard computes the tiny full
       update table and keeps its column slice), zero-rule cell masking
       or subtract-rule estimate subtraction exactly as the replicated
       branch;
    6. the update leaves as the device's dense (d_pad/n,) coordinate
       shard — matching ``ps_weights``'s sharding, so the weight apply
       runs fully sharded with no further collective.

    ``lr`` is a replicated scalar or the device's (d_pad/n,) shard of
    the per-parameter LR vector. Returns ``(update_shard, Vvel_shard',
    Verr_shard', support)``: ``support`` is ``server_update``'s, the k
    global winners as every shard holds them after the merge.
    """
    from jax import lax

    rho = cfg.virtual_momentum
    Vvel = agg_shard + rho * Vvel_shard
    Verr = Verr_shard + Vvel

    if cfg.sketch_ef == "subtract":
        full = lax.all_gather(jnp.stack([Verr, Vvel]), axis, axis=2,
                              tiled=True)
        Verr_full, Vvel_full = full[0], full[1]
    else:
        Verr_full = lax.all_gather(Verr, axis, axis=1, tiled=True)
        Vvel_full = None

    i = lax.axis_index(axis)
    blk = d_pad // n_shards
    start = i * blk
    ests = cs.decode_range(Verr_full, start, blk)
    loc_vals, loc_idx = local_topk_candidates(ests, cfg.k, start,
                                              approx=cfg.approx_topk)
    cand_v = lax.all_gather(loc_vals, axis)        # (n, k_loc) — the
    cand_i = lax.all_gather(loc_idx, axis)         # ~n*k*8-byte payload
    win_vals, win_idx = merge_topk_candidates(cand_v, cand_i, cfg.k)

    # dense update SHARD: scatter the winners that land in my range
    # (top-k indices are distinct, so set() is sound; out-of-range
    # winners drop)
    rel = win_idx - start
    in_range = (rel >= 0) & (rel < blk)
    update = jnp.zeros((blk,), jnp.float32).at[
        jnp.where(in_range, rel, blk)].set(
            jnp.where(in_range, win_vals, 0.0), mode="drop")

    # error feedback from the k-sparse winners: the same re-encode the
    # replicated branch does (encode_at(update, idx) ==
    # encode_vals_at(vals, idx) by construction)
    c_loc = Verr.shape[1]
    sk_upd = cs.encode_vals_at(win_vals, win_idx)
    sk_upd_sh = lax.dynamic_slice_in_dim(sk_upd, i * c_loc, c_loc, axis=1)
    if cfg.sketch_ef == "subtract":
        vel_ests = cs.decode_at(Vvel_full, win_idx)
        sk_vel = cs.encode_vals_at(vel_ests, win_idx)
        Vvel = Vvel - lax.dynamic_slice_in_dim(sk_vel, i * c_loc, c_loc,
                                               axis=1)
        Verr = Verr - sk_upd_sh
    else:
        mask = sk_upd_sh != 0
        Vvel = jnp.where(mask, 0.0, Vvel)
        Verr = jnp.where(mask, 0.0, Verr)
    if cfg.error_decay < 1.0:
        Verr = cfg.error_decay * Verr
    return update * lr, Vvel, Verr, _support(win_idx, win_vals, lr)
