"""Configuration for CommEfficient-TPU.

Keeps the reference's flag vocabulary (reference: CommEfficient/utils.py:102-230)
so users of the original framework can carry their invocations over, but stores
everything in a typed, hashable dataclass that can be closed over by ``jax.jit``
(the reference threads an argparse Namespace through every function instead).

TPU-specific additions: ``mesh_shape``/``mesh_axes`` for the device mesh,
``param_dtype``/``compute_dtype`` for bfloat16 compute, and
``max_client_batch`` (static per-client batch bound — XLA needs static shapes
where the reference used dynamic per-client batches).
"""

from __future__ import annotations

import argparse
import dataclasses
import os
from typing import Optional, Tuple

# the compile cache's home when the environment names none: one fixed
# path inside the checkout (the path is part of the cache key, so a
# directory that moves never hits)
DEFAULT_COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")

MODES = ("sketch", "true_topk", "local_topk", "fedavg", "uncompressed")
ERROR_TYPES = ("none", "local", "virtual")
DP_MODES = ("worker", "server")
ALERT_ACTIONS = ("log", "warn", "checkpoint", "abort")
# adversarial client injection (data/scenarios.py AdversaryPlan):
# deterministic per-client fates keyed off (seed, client_id)
ADVERSARY_KINDS = ("none", "labelflip", "signflip", "scale", "noise", "nan")
# robust aggregation in transmitted space (core/server.py)
DEFENSES = ("none", "normclip", "trim")
# sketch-table wire dtypes (--wire_dtype; ops/wire.py): what a table
# cell costs on the ICI/upload wire — f32, bf16 rounding, or int8
# block-quantized with stochastic rounding
WIRE_DTYPES = ("float32", "bfloat16", "int8")
# what the round does with a nonfinite per-client update (core/runtime.py)
NONFINITE_ACTIONS = ("abort", "quarantine")

# reference: CommEfficient/utils.py:37-44
FED_DATASETS = {
    "CIFAR10": 10,
    "CIFAR100": 100,
    "EMNIST": 62,
    "ImageNet": 1000,
    "PERSONA": -1,
}


def num_classes_of_dataset(dataset_name: str) -> int:
    return FED_DATASETS[dataset_name]


@dataclasses.dataclass(frozen=True)
class FedConfig:
    """Static configuration of a federated run.

    Field names follow the reference flags (CommEfficient/utils.py:102-230);
    ``do_*`` booleans keep the reference's argparse ``dest`` names.
    """

    # meta
    mode: str = "sketch"
    do_test: bool = False
    use_tensorboard: bool = False
    seed: int = 21

    # data / model
    model: str = "ResNet9"
    dataset_name: str = "CIFAR10"
    dataset_dir: str = "./dataset"
    do_finetune: bool = False
    do_checkpoint: bool = False
    checkpoint_path: str = "./checkpoint"
    # per-shard streaming checkpoint writes (peak host memory = one shard);
    # required when the state exceeds checkpoint.DEFAULT_MAX_HOST_BYTES
    checkpoint_sharded: bool = False
    # TPU-native improvement over the reference (which can only save final
    # weights, cv_train.py:418-421): periodic full-FedState checkpoints and
    # exact mid-run resume (see checkpoint.py)
    checkpoint_every: int = 0     # epochs between mid-run checkpoints; 0=off
    do_resume: bool = False
    # opt-in to resuming checkpoints written before params fingerprinting
    # existed (their flat-weight layout cannot be verified; see checkpoint.py)
    resume_unverified: bool = False
    finetune_path: str = "./finetune"
    finetuned_from: Optional[str] = None
    do_batchnorm: bool = False
    # images per class for the synthetic CIFAR fallback (no-network runs);
    # the real pickles/tree take precedence when present
    synthetic_per_class: int = 64
    # non-saturating synthetic regime for time-to-accuracy studies
    # (data/fed_cifar.py _synthetic_cifar hard=True): shared-base
    # prototypes + heavy pixel noise (+ train-only label noise) so a
    # 24-epoch accuracy curve stays well below 100% and keeps climbing
    synthetic_hard: bool = False
    synthetic_label_noise: float = 0.0
    # train WITHOUT data augmentation (normalize-only transform).
    # Implied by --synthetic_hard; needed standalone for any synthetic
    # regime whose class evidence is per-pixel (crop/flip/shift
    # augmentation scrambles prototype pixels and training flatlines at
    # chance — measured on both CIFAR-hard and synthetic EMNIST)
    no_augment: bool = False
    num_results_train: int = 2
    num_results_val: int = 2

    # compression (reference defaults utils.py:142-147)
    k: int = 50_000
    num_cols: int = 500_000
    num_rows: int = 5
    num_blocks: int = 20
    do_topk_down: bool = False
    # pin --num_cols exactly as given. By default (False) the circulant
    # sketch AUTO-SIZES num_cols up to the nearest TPU-efficient value at
    # model-build time (see auto_num_cols): the reference's default
    # c=500,000 was a GPU/csvec choice (utils.py:142-145) that (a) is
    # never 1024-aligned, disqualifying both Pallas kernels, and (b) at
    # GPT-2 scale can exceed the static-roll block budget and fall into
    # the measured ~100x take_along_axis cliff (ops/circulant.py). The
    # rounding grows the upload budget by < 0.3% at flagship sizes; pass
    # --exact_num_cols to reproduce the reference geometry bit-for-bit.
    exact_num_cols: bool = False

    # optimization (reference defaults utils.py:150-162)
    local_momentum: float = 0.9
    virtual_momentum: float = 0.0
    weight_decay: float = 5e-4
    num_epochs: float = 24.0
    num_fedavg_epochs: int = 1
    fedavg_batch_size: int = -1
    fedavg_lr_decay: float = 1.0
    error_type: str = "none"
    lr_scale: Optional[float] = 0.4
    pivot_epoch: float = 5.0
    # GPT-2 LR warmup (TPU-native opt-in; the reference's GPT-2 schedule
    # is linear -> 0 from full LR at step 0): ramp 0 -> lr_scale over
    # pivot_epoch, then linear -> 0. The CV driver always ramps (its
    # reference does); this flag only affects gpt2_train.
    lr_warmup: bool = False

    # federation / parallelization
    num_clients: Optional[int] = None
    num_workers: int = 1          # clients sampled per round
    do_iid: bool = False

    # batching (reference utils.py:190-195)
    local_batch_size: int = 8     # -1 => client's whole dataset
    valid_batch_size: int = 8
    microbatch_size: int = -1     # -1 => whole batch in one fwd/bwd

    # GPT-2 (reference utils.py:183-207)
    model_checkpoint: str = "gpt2"
    num_candidates: int = 2
    max_history: int = 2
    # static packed sequence length for PERSONA (0 = driver default, 280).
    # TPU-native knob: the reference pads per batch dynamically
    # (personachat_collate_fn); static shapes make padding a compile-time
    # cost, so a corpus with short dialogues should set this to its true
    # max length instead of paying 280-token attention on padding
    max_seq_len: int = 0
    lm_coef: float = 1.0
    mc_coef: float = 1.0
    max_grad_norm: Optional[float] = None
    personality_permutations: int = 1
    eval_before_start: bool = False

    # differential privacy (reference utils.py:210-214)
    do_dp: bool = False
    dp_mode: str = "worker"
    l2_norm_clip: float = 1.0
    noise_multiplier: float = 0.0

    # simulated per-client communication byte tracking (the reference always
    # tracks; here it can be disabled for pure-throughput benchmarks)
    track_bytes: bool = True

    # --- TPU-native additions (no reference equivalent) ---
    mesh_shape: Tuple[int, ...] = ()      # () => single device
    mesh_axes: Tuple[str, ...] = ("clients",)
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    # static upper bound on a client's dataset size; used to pad
    # `local_batch_size == -1` (whole-client) batches to a fixed shape
    max_client_batch: int = 512
    sketch_seed: int = 42
    # sketch implementation (all are linear (r, c) tables):
    # - "circ" (default): circulant count sketch — count-sketch cell
    #   semantics (stable cell-zeroing error feedback) built from static
    #   rolls instead of scatter/gather: ~30x faster than "hash" on TPU
    #   (ops/circulant.py);
    # - "hash": count sketch with exact CSVec cell semantics (the
    #   reference's own hash family); O(d*r) scatter/gather encode/decode;
    # - "rht": SRHT — signs + Kronecker-Hadamard on the MXU + subsample;
    #   fast but EMPIRICALLY DIVERGENT under FetchSGD error feedback
    #   whenever r*c << d (top-k over uniformly-noisy JL estimates is not
    #   a contraction). Safe only near the lossless regime r*c >= d; the
    #   runtime warns otherwise.
    sketch_impl: str = "circ"
    # opt-in override for the rht compressing-regime hard error (see
    # core/server.py validate_mode_combo): rht at r*c < d measurably
    # diverges under error feedback; this flag exists to reproduce that
    # study, not to train with
    allow_divergent_rht: bool = False
    # DEPRECATED alias of --wire_dtype (kept as a real field: for rht it
    # still selects the transform compute dtype, and pre-PR-14 configs/
    # checkpoints name it). __post_init__ resolves: an empty wire_dtype
    # inherits sketch_dtype, and a bfloat16 wire syncs sketch_dtype so
    # the rht transform compute follows the wire. Parse-time use of
    # --sketch_dtype warns (see parse_args).
    sketch_dtype: str = "float32"
    # sketch-table WIRE dtype ("float32" | "bfloat16" | "int8"; "" =
    # inherit the deprecated --sketch_dtype alias). What a table cell
    # costs on the wire — per-client uploads AND every table-shaped
    # collective:
    # - bfloat16: uploads/psum/psum_scatter payloads travel rounded to
    #   bf16 — half the ICI payload at ~2^-8 relative cell rounding;
    #   server math stays fp32.
    # - int8 (ops/wire.py): uploads quantize with per-column-block
    #   symmetric abs-max scales and STOCHASTIC rounding (unbiased;
    #   draws keyed off (seed, global_round, block) — deterministic and
    #   replay/resume-safe), the mesh table reduce becomes an
    #   all_to_all of int8 column shards + f32 scales with shard-local
    #   dequantize-accumulate in f32 (int8 summation over W clients
    #   would overflow), and the rounding residual is left to the
    #   server error-feedback state. ~0.27x the f32 wire bytes (scales
    #   included; ledger-gated <= 0.30x by dryrun_multichip). Requires
    #   mode=sketch with a table server state (circ/hash impl; on a
    #   mesh additionally the sharded server tail — the quantized
    #   reduce is shard-shaped). Fail-fast on ineligible combinations.
    wire_dtype: str = ""
    # int8 wire quantization granularity: columns per abs-max scale
    # block. Larger = less scale overhead (4/block bytes per cell);
    # smaller = tighter scales. Shrunk automatically to the per-device
    # column shard when the mesh shard is narrower; must then divide it.
    wire_block: int = 256
    # rht row-at-a-time transforms (memory mode): -1 auto (on at dp >= 2^25),
    # 0 force batched, 1 force scanned. bf16 single-vector round-trips fit
    # batched even at GPT-2 scale and run ~2x faster
    sketch_scan_rows: int = -1
    # circulant-sketch pallas kernel policy: "auto" (default) = fused
    # encode AND decode when eligible (TPU, 1024-aligned shifts, VMEM
    # budget — decode measured 21 ms vs 129 ms at d=124M; encode lifts
    # the fused flagship round 76.5k -> 85.2k tok/s), "on" = force-enable
    # (same set; kept for explicitness), "off" = XLA paths only
    pallas: str = "auto"

    # Sketch-mode error-feedback rule (TPU-native extension; the reference
    # only has "zero"):
    # - "zero" (default): the reference's cell-zeroing — re-encode the
    #   k-sparse update and zero every table cell it occupies
    #   (fed_aggregator.py:596-611). Dissipates ~k/c of EVERY coordinate's
    #   accumulated error per row per round (colliding coordinates lose
    #   their whole cell), which under small bounded increments (gradient
    #   clipping) destroys slow-accumulating signal before it can win the
    #   top-k — the measured clip x sketch stall (runs/gpt2_conv/README.md
    #   finding 5).
    # - "subtract": subtract the encoded update from Verror (and the
    #   velocity's estimated values at the support from Vvelocity) —
    #   removes exactly the extracted mass, preserving colliding
    #   coordinates' accumulated error. Equals "zero" bit-for-bit in the
    #   lossless limit (tests/test_core.py TestSketchEFVariants); at real
    #   compression it trades the leak for residual decode noise left in
    #   the table, bounded per round by the (clipped) increment norm.
    sketch_ef: str = "zero"
    # Where the server's momentum/error live in sketch mode (TPU-native
    # extension; the reference always keeps them as (r, c) tables,
    # fed_aggregator.py:568-613):
    # - "table" (default): the reference's FetchSGD — all server state in
    #   table space; EF per --sketch_ef.
    # - "dense": momentum/error kept as dense (d,) pre-images; each round
    #   ONE encode+decode round-trip of the error injects exactly the
    #   compression noise the table channel imposes (the upload is still
    #   the r x c table — byte accounting unchanged), and error feedback /
    #   momentum masking zero the exact update support like true_topk.
    #   Leak-free AND noise-dissipation-free-but-stable (state is exact),
    #   at the cost of O(d) server memory — which the reference's PS
    #   already spends on weights/velocities for every dense mode
    #   (fed_aggregator.py:105-129). Single-device only (on a mesh it
    #   would turn the table-sized psum back into a d-sized one);
    #   requires deferred encode (no per-client table clip — use
    #   --sketch_dense_clip for clipping).
    sketch_server_state: str = "table"
    # Uniform table-space error decay (TPU-native extension): after the
    # round's error feedback, Verror *= error_decay (sketch and true_topk
    # modes). 1.0 = off. A blunt stabilizer for regimes where accumulated
    # table mass dominates fresh gradients; part of the sketch-vs-dense
    # study battery (runs/gpt2_conv/README.md).
    error_decay: float = 1.0

    # TPU-optimized approximate top-k (lax.approx_max_k, 0.95 recall) for
    # the sparsification selects; exact lax.top_k when False
    approx_topk: bool = False
    # profiling: write a jax profiler trace (tensorboard-viewable) of the
    # rounds in --profile_rounds to this directory (the reference's analogue
    # is its cProfile hooks, fed_aggregator.py:46-52)
    profile_dir: str = ""
    # which 1-based global rounds the trace covers, "START:STOP" inclusive
    # (telemetry/profiling.py); the default reproduces the old hardcoded
    # steady-state window, rounds 2-4
    profile_rounds: str = "2:4"
    # run telemetry (telemetry/): telemetry.jsonl event stream in the
    # run's logdir — manifest, per-round records, compile/memory events,
    # NaN diagnostics, end-of-run summary. --no_telemetry disables.
    telemetry: bool = True
    # per-round record granularity: emit a round event every N rounds
    # (0 = none). Each emitted record costs one host sync of the round's
    # metrics — on a v5e 2.7 ms to fetch the ready metrics pytree, and
    # the ResNet-9 round went from 87.2 to 91.1 ms when every round
    # waited for its own completion instead of being chained (PR 21
    # chip run; PERF.md). The default -1 is AUTO: every round under
    # --test (the smoke contract wants round records), every 64 rounds
    # otherwise. Set 1 explicitly for convergence studies where
    # per-round curves matter.
    telemetry_every: int = -1
    # peak FLOP/s of one accelerator for MFU accounting
    # (telemetry/utilization.py): 0 = look the device_kind up in the
    # built-in per-generation table; set explicitly for chips the table
    # does not know (or to pin a different MFU denominator, e.g. fp32
    # peak on CPU smoke runs)
    peak_flops: float = 0.0
    # peak HBM bandwidth in GB/s for roofline attribution
    # (telemetry/utilization.py): 0 = look the device_kind up in the
    # built-in per-generation table; set explicitly for chips the table
    # does not know. Unknown chip + no override = null roofline fields
    # in the utilization events (never a verdict against a guess).
    peak_hbm_gbps: float = 0.0
    # compression-signal health diagnostics (telemetry/signals.py):
    # cheap on-device norms (aggregated gradient, EF accumulators,
    # update support, sketch collision proxies) computed inside the
    # jitted round and emitted as `signals` telemetry events at the
    # --telemetry_every cadence. --no_signals drops them from the round
    # step entirely (they cost a handful of fused reductions per round,
    # plus two table-sized all-gathers in mesh sketch mode); they are
    # also auto-dropped under --no_telemetry, which leaves no consumer.
    signals: bool = True
    # per-client population statistics (telemetry/clients.py): per-client
    # loss / gradient norms pre+post clip / clip saturation / update-
    # contribution norm / exact bytes, reduced ON DEVICE to quantile
    # summaries along the round's client axis and emitted as schema-v3
    # `client_stats` events at the --telemetry_every cadence (host-side
    # participation ledger included). --no_client_stats drops them from
    # the jitted round; like signals they are also auto-dropped under
    # --no_telemetry (no hot-path work for a stream nobody reads).
    client_stats: bool = True
    # participation-ledger backing (telemetry/population.py): "off" =
    # the exact per-client host dict (O(population) memory and
    # checkpoint sidecar), "on" = the bounded-memory sketch ledger
    # (count-min counts, space-saving heavy hitters, KMV distinct
    # sample, P2 stream quantiles — <= 8 MiB regardless of population),
    # "auto" = exact below 10^5 registered clients, sketch at/above.
    # Event fields are identical in both modes; the `estimated` flag
    # (client_stats + population events, schema v11) says which wrote
    # them — the sketch never fakes exactness.
    population_sketch: str = "auto"
    # online anomaly monitor (telemetry/health.py) action when a rule
    # fires: "log" = alert event only; "warn" = + stderr line;
    # "checkpoint" = + one-shot flight-recorder bundle (FedState snapshot
    # via the checkpoint layer, last-N telemetry events, alert context)
    # into <logdir>/postmortem on the FIRST firing; "abort" = all of the
    # above, then stop training like the NaN abort (summary records
    # aborted=True). The monitor only exists when telemetry is on.
    alert_action: str = "log"
    # rolling-history length (observations) for the monitor's median/MAD
    # z-scores; also the per-rule refire cooldown
    alert_window: int = 32
    # robust z-score threshold for the statistical rules (median/MAD z;
    # 6.0 is deliberately loose — the monitor must stay silent on healthy
    # noisy streams, see tests/test_health.py's false-positive gate)
    alert_zscore: float = 6.0
    # heavy-hitter recovery quality (topk_overlap): compares the
    # decompressed update's support against the exact top-k of the DENSE
    # error — needs a dense reference, so it is opt-in: true_topk /
    # dense-preimage sketch reconstruct it from existing state (one extra
    # O(d) top-k per round); table-state sketch additionally carries a
    # dense shadow error accumulator (2 x O(d) state, single-device
    # deferred-encode only)
    signals_exact: bool = False
    # layer-wise compression attribution (telemetry/layer_signals.py):
    # partition the model pytree into named parameter groups (coarse =
    # path-pattern groups — embed/attn/mlp/norm-bias per block for the
    # GPT-2 layout, stage-level for conv nets; leaf = one group per
    # pytree leaf) and reduce the round's dense quantities per group
    # inside the jitted round — per-group gradient/update/EF mass,
    # top-k support counts, heavy-hitter recovery under
    # --signals_exact. Emitted as schema-v10 `layer_signals` events at
    # the signals cadence; "off" compiles the group machinery out
    # entirely (round HLO byte-identical, tested). Gated exactly like
    # signals: --no_signals / --no_telemetry / async drop it too. Cost:
    # a few reductions over the spec's static ranges (ops/segments.py);
    # nothing d-long is resident for it.
    signal_groups: str = "coarse"
    # fail (instead of warn) on configurations round 5 MEASURED divergent
    # — see core/server.py check_regime_health: local_topk with local
    # error feedback at dense-stable lr, subtract-EF at high collision
    # load. The measurements: runs/README.md (local_topk envelope),
    # runs/gpt2_conv/README.md (subtract dose-response)
    strict_regimes: bool = False
    # persistent XLA compilation cache directory (see
    # enable_compilation_cache_dir for who decides where it lives).
    # Flag spelling: --compile_cache (alias --compilation_cache_dir)
    compilation_cache_dir: str = DEFAULT_COMPILATION_CACHE_DIR
    # round input pipeline (core/pipeline.py): prefetch round t+1's client
    # indices + batch on a background thread while round t executes.
    # Bit-identical losses to the inline path (dryrun-asserted — all
    # randomness is keyed by the round index); --no_pipeline reverts to
    # the fully synchronous fetch->dispatch loop
    pipeline: bool = True
    # how many rounds the prefetcher runs ahead (queue bound). 2 =
    # double-buffered: one batch in flight to the device, one staged
    prefetch_depth: int = 2

    # --- async buffered aggregation (core/async_agg.py; FedBuff-style,
    # Nguyen et al. 2022). Off by default: the lockstep round is the
    # reference-parity path. When on, the driver keeps up to
    # ``max_inflight`` cohort computations in flight, merges each
    # cohort's transmitted-space sum into a server-side buffer as it
    # "lands" (simulated arrival order from data/scenarios.py), applies
    # ``staleness_discount`` per merged cohort, and commits the buffered
    # aggregate through the normal server momentum+EF step once
    # ``buffer_goal`` cohorts have merged. Sound only for modes whose
    # server consumes the cohort uploads purely through their weighted
    # SUM — no per-client persistent rows, no topk_down (see
    # core/async_agg.validate_async_combo, which fails fast otherwise).
    async_agg: bool = False
    # cohorts kept in flight (K). Dispatching past K forces the
    # earliest in-flight cohort to land first — the simulated "pool is
    # full" wait. Each in-flight cohort holds one transmitted-space
    # array on device.
    max_inflight: int = 4
    # cohorts merged per commit (M). 1 commits every landing cohort;
    # with max_inflight 1 and no scenario latency that reduces exactly
    # to the synchronous round (bit-identical, dryrun-asserted).
    buffer_goal: int = 1
    # staleness discount applied to a cohort merged s commits after its
    # dispatch: "none" = 1, "poly" = (1+s)^-alpha (FedBuff's default
    # shape; alpha 0.5 reproduces its 1/sqrt(1+s)), "exp" =
    # exp(-alpha*s). All rules give weight exactly 1.0 at s=0.
    staleness_discount: str = "poly"
    staleness_alpha: float = 0.5

    # --- straggler scenario engine (data/scenarios.py): per-cohort
    # simulated latency / dropout / dynamic partial participation,
    # seeded deterministically off (seed, global round index) so runs
    # replay exactly. Only meaningful with --async_agg (the lockstep
    # loop has no notion of a late cohort) — configuring a scenario
    # without it fails fast instead of silently doing nothing.
    scenario: str = "none"          # none | uniform | lognormal | stragglers
    scenario_latency: float = 1.0   # base latency, in cohort-dispatch ticks
    scenario_spread: float = 0.5    # uniform half-width / lognormal sigma
    scenario_straggler_frac: float = 0.1   # "stragglers" kind: slow fraction
    scenario_straggler_mult: float = 10.0  # ... and their latency multiplier
    scenario_dropout: float = 0.0   # per-cohort probability of never landing
    scenario_participation: float = 1.0  # fraction of worker slots kept
    # --- adversarial client injection (data/scenarios.py AdversaryPlan).
    # A deterministic --adversary_frac fraction of the client universe is
    # hostile, keyed off (seed, client_id) — the same client misbehaves
    # every time it is sampled, across resumes and prefetch interleavings.
    # Kinds: labelflip (train on (C-1)-y — data space, needs a
    # classification dataset), signflip (upload x -1), scale (upload
    # x adversary_scale — the boosted/model-replacement attack), noise
    # (upload + adversary_scale * N(0, I) in transmitted space), nan
    # (upload all-NaN — the broken-client case --nonfinite_action
    # handles). Unlike the latency scenario, injection works in BOTH the
    # synchronous and async rounds (it acts at cohort compute, which both
    # paths share).
    adversary: str = "none"
    adversary_frac: float = 0.0
    # scale attack multiplier / noise attack sigma
    adversary_scale: float = 10.0
    # --- robust aggregation in transmitted space (core/server.py):
    # - normclip: per-client update-norm clipping to a robust threshold —
    #   rolling-median of past rounds' median per-datum update norms
    #   (defense_window rounds, FedState.defense_ref) x defense_clip_mult
    #   (Sun et al. 2019). Sound in table space too: an l2 clip is a
    #   rescaling, and rescaling commutes with the linear sketch.
    # - trim: per-coordinate trimmed-mean aggregation — drop the
    #   defense_trim_frac highest and lowest per-client values per
    #   coordinate, average the rest uniformly (Yin et al. 2018). Single
    #   device only (the cross-client sort needs every client's full
    #   vector in one place; on a mesh use normclip).
    # Off by default; the defended round's HLO is byte-identical to the
    # pre-defense round when off (same discipline as signals).
    defense: str = "none"
    defense_clip_mult: float = 3.0
    defense_window: int = 8
    defense_trim_frac: float = 0.1
    # --- nonfinite recovery (core/runtime.py + core/quarantine.py):
    # - abort (default): the pre-existing behavior — the first nonfinite
    #   per-client update poisons the aggregate, the device flag fires,
    #   the run stops at the epoch boundary.
    # - quarantine: the nonfinite client's upload is zeroed OUT of the
    #   aggregate inside the jitted round (its datum count and metrics
    #   contributions too), the client id is logged to a host-side
    #   QuarantineLedger, and the client is benched for
    #   quarantine_backoff rounds, retried, and permanently ejected
    #   after quarantine_strikes strikes. A FULLY-nonfinite round (no
    #   finite client left) still aborts. Costs one (W,)-bool host fetch
    #   per round for the ledger.
    nonfinite_action: str = "abort"
    quarantine_backoff: int = 8
    quarantine_strikes: int = 3
    # --- preemption / fault tolerance (core/preempt.py) ---
    # graceful-preemption drain budget, seconds: on SIGTERM/SIGINT the
    # driver loop finishes the in-flight round, drains the input
    # pipeline / async pool (flushing any open buffer through the
    # epoch-flush path), writes an out-of-cadence checkpoint tagged
    # `preempt` (round-granular meta, so the resume is exact), emits a
    # final `fault` telemetry event and exits 0 — all within this
    # budget. A SECOND signal force-exits immediately. Must be > 0.
    preempt_grace: float = 30.0
    # host-side hang watchdog (core/preempt.RoundWatchdog): arms a
    # deadline around each round's dispatch+sync, derived from the
    # rolling median round time (MAD-floored like the health.py rules)
    # x watchdog_mult. On expiry it fires a critical `round_stall`
    # alert through the AnomalyMonitor and records an events-only
    # flight-recorder bundle (the state fetch itself could hang). Also
    # arms bounded exponential-backoff RETRIES around the retryable
    # host-side input phases (device_put / gather dispatch). Off by
    # default: it adds a thread and retry semantics the lockstep tests
    # must opt into.
    watchdog: bool = False
    # stall deadline = watchdog_mult x (rolling median + MAD envelope);
    # must be >= 1 (a sub-1 multiplier would declare the MEDIAN round
    # stalled)
    watchdog_mult: float = 10.0
    # fixed run directory for telemetry/tensorboard artifacts; empty =
    # the timestamped make_logdir default. A resumed run pointed at its
    # predecessor's logdir APPENDS to the existing events.jsonl with a
    # `resume` lineage record (telemetry/run.py) instead of clobbering
    # it.
    logdir: str = ""

    # rematerialize transformer blocks on backward (memory/FLOPs trade)
    do_remat: bool = False
    # selective-remat policy (jax.checkpoint_policies attribute name, e.g.
    # dots_with_no_batch_dims_saveable) applied when do_remat; "" = full
    remat_policy: str = ""
    # chunked LM cross-entropy: compute vocab logits ``lm_chunk`` tokens at
    # a time, forward and (recomputed) backward, instead of materializing
    # the full (tokens, vocab) fp32 tensor (+ cotangent) — the GPT-2
    # microbatch-8 memory enabler (losses._chunked_lm_nll). It bounds the
    # float32 logits alone: how many chunks' cotangents the backward keeps
    # before it adds them into the head's gradient follows the shapes
    # (losses.CE_GROUP_BYTES). 0 = dense
    lm_chunk: int = 0
    # GPT-2 attention implementation: "auto" (default — dense below
    # S=1024, flash above, the measured crossover on v5e:
    # scripts/bench_longctx.py), "dense" (materialized logits), "flash"
    # (fused TPU Pallas kernel, O(S) attention memory; falls back to
    # dense off-TPU/unaligned S)
    attn_impl: str = "auto"
    # sketch-mode worker-gradient clipping (TPU-native extension): apply
    # --max_grad_norm to the DENSE per-client gradient before encoding
    # (threshold x num_iters, the same semantics as the dense modes)
    # instead of the reference's post-encode table clip
    # (fed_worker.py:318-319, bare threshold). Because an l2 clip is a
    # rescaling and the encode is linear, the two placements apply the
    # SAME operation at a matched threshold (pinned by
    # test_sketch_dense_clip_wiring); this flag aligns the threshold
    # semantics across modes. Measured finding (runs/gpt2_conv/
    # README.md): clipping that rescues the dense modes degrades
    # sketch-mode error feedback at every measured threshold — prefer
    # unclipped sketch on from-scratch regimes. Disables the
    # fused-clients fast path (the clip is per-client); deferred encode
    # survives (clipped dense gradients still sum before one encode).
    sketch_dense_clip: bool = False
    # Fused sketch encode (core/client.py): encode each per-microbatch
    # gradient straight into the (r, c) Count Sketch table inside the
    # microbatch scan — the scan carry is the table, so the dense (d,)
    # gradient SUM never materializes in HBM (at GPT-2 124M the scan
    # carry pair alone is ~1 GB of temp). Sound exactly when the encode
    # deferral is sound AND nothing downstream consumes the dense
    # per-client/aggregate gradient:
    # - "auto" (default): engage when eligible, silently fall back to
    #   the unfused path otherwise (numerics never change silently —
    #   the fallback IS the old path);
    # - "on": require it — fail fast with the blocking reason
    #   (--sketch_dense_clip, DP clip+noise, --signals_exact's dense
    #   shadow accumulator, the single-device signals dense capture,
    #   a defense that clips dense per-client norms, the rht impl,
    #   per-client grad stats on the vmap path);
    # - "off": never (the pre-fusion round, bit-identical HLO).
    # See README "Fused sketch encode" for the soundness matrix.
    sketch_fused_encode: str = "auto"
    # Sharded sketch SERVER tail (core/server.py
    # sharded_sketch_server_update): on a mesh, replace the round's
    # replicated table psum with a psum_scatter over table columns
    # (each device owns c/n columns of the momentum/EF state — the
    # dense-mode reduce_scatter analogue), re-gather the small (r, c)
    # error table, range-decode only the device's d_pad/n coordinate
    # slice, take a local top-k and merge an (n, k)-sized candidate
    # all-gather into the global top-k — no device ever materializes
    # the dense (d,) decode estimates, so per-device server temp drops
    # from O(d) to O(d/n + n*k):
    # - "auto" (default): engage on an eligible mesh (table-state
    #   sketch, no seq axis, num_cols divisible by the mesh size),
    #   silently fall back to the replicated tail otherwise (the
    #   fallback IS the pre-sharding round — numerics never change
    #   silently);
    # - "on": require it — fail fast listing every blocker;
    # - "off": never (the replicated server tail, for A/B gates).
    sketch_sharded_server: str = "auto"
    # jointly-computed round gradient (core/client.py make_fused_grad):
    # when no per-client nonlinearity exists, accumulate the round's
    # aggregate into ONE (d,) buffer instead of vmap's per-client (W, d)
    # gradient. Exact up to summation order; measured ~15% off the
    # flagship GPT-2 round. Auto-disabled when ineligible (local state,
    # clip, DP, topk_down, fedavg/local_topk, seq sharding, straddling
    # microbatches); this flag forces the vmap path everywhere.
    fused_clients: bool = True

    # filled in at model-build time, like the reference's args.grad_size
    # (fed_aggregator.py:88). Frozen dataclass => use `replace`.
    grad_size: int = 0

    def __post_init__(self):
        # normalize the documented implication once, so every consumer
        # can read cfg.no_augment directly (a hard-regime run that
        # re-enabled augmentation would flatline at chance)
        if self.synthetic_hard and not self.no_augment:
            object.__setattr__(self, "no_augment", True)
        assert self.mode in MODES, self.mode
        assert self.error_type in ERROR_TYPES, self.error_type
        assert self.dp_mode in DP_MODES, self.dp_mode
        assert self.pallas in ("auto", "on", "off"), self.pallas
        assert self.sketch_ef in ("zero", "subtract"), self.sketch_ef
        assert self.sketch_server_state in ("table", "dense"), \
            self.sketch_server_state
        assert 0.0 < self.error_decay <= 1.0, self.error_decay
        if self.error_decay < 1.0:
            # silently ignoring the flag would let a decay study run
            # undecayed (same fail-fast rationale as sketch_dense_clip)
            assert self.mode in ("sketch", "true_topk"), \
                "--error_decay only applies to modes with virtual error " \
                "(sketch, true_topk)"
        assert self.attn_impl in ("auto", "dense", "flash"), self.attn_impl
        # ---- wire dtype resolution (--wire_dtype generalizes the
        # deprecated --sketch_dtype alias; see the field comments)
        assert self.sketch_dtype in ("float32", "bfloat16"), \
            self.sketch_dtype
        if self.wire_dtype == "":
            object.__setattr__(self, "wire_dtype", self.sketch_dtype)
        if self.wire_dtype not in WIRE_DTYPES:
            raise ValueError(
                f"--wire_dtype {self.wire_dtype!r} not in {WIRE_DTYPES}")
        if self.wire_dtype == "bfloat16" and self.sketch_dtype != "bfloat16":
            # keep the rht transform compute dtype following the wire,
            # exactly as --sketch_dtype bfloat16 always did
            object.__setattr__(self, "sketch_dtype", "bfloat16")
        if self.wire_dtype == "float32" and self.sketch_dtype != "float32":
            # an EXPLICIT f32 wire wins over the deprecated bf16 alias
            # (the empty-wire inheritance above already ran, so a
            # float32 here was requested, not defaulted): leaving
            # sketch_dtype at bf16 would keep the runtime's bf16 wire
            # armed while wire_dtype/telemetry/byte accounting all claim
            # f32. An rht user wanting the bf16 TRANSFORM passes the
            # alias alone — the wire then inherits bf16, as it always
            # did.
            object.__setattr__(self, "sketch_dtype", "float32")
        if self.wire_dtype == "int8" and self.sketch_dtype != "float32":
            # an explicit int8 wire WINS over the deprecated bf16 alias
            # (leaving sketch_dtype at bf16 would arm the runtime's bf16
            # rounding branch, which shadows the int8 wire on the
            # per-client/single-device paths while the byte accounting
            # reports int8 — the silently-wrong-wire failure this
            # resolution exists to prevent; rht, the only other
            # consumer of sketch_dtype, is rejected with int8 below)
            object.__setattr__(self, "sketch_dtype", "float32")
        if self.wire_block < 8:
            raise ValueError(
                f"--wire_block {self.wire_block} must be >= 8: each block "
                "pays 4 bytes of f32 scale, so blocks below 8 columns "
                "spend more on scales than a bf16 wire spends on cells")
        if self.wire_dtype == "int8":
            # fail fast on combinations the quantized wire cannot serve
            # (the silently-ignored-flag contract); topology-dependent
            # blockers (mesh without the sharded server tail, the
            # dense-preimage auto path) fail at runtime init where the
            # mesh is resolved
            if self.mode != "sketch":
                raise ValueError(
                    f"--wire_dtype int8 requires --mode sketch (mode="
                    f"{self.mode} has no table-shaped wire to quantize; "
                    "dense-mode payloads keep their f32 wire)")
            if self.sketch_impl == "rht":
                raise ValueError(
                    "--wire_dtype int8 is unsupported with sketch_impl="
                    "rht: its dense transform has no cell-addressable "
                    "table to block-quantize (use circ or hash)")
            if self.sketch_server_state == "dense":
                raise ValueError(
                    "--wire_dtype int8 is unsupported with "
                    "--sketch_server_state dense: that server path "
                    "consumes the dense aggregated gradient, so no table "
                    "crosses the wire to quantize")
        assert self.sketch_fused_encode in ("auto", "on", "off"), \
            self.sketch_fused_encode
        if self.sketch_fused_encode == "on" and self.mode != "sketch":
            raise ValueError(
                f"--sketch_fused_encode on requires --mode sketch (mode="
                f"{self.mode} has no sketch encode to fuse); drop the flag "
                "or use --sketch_fused_encode auto (a no-op off sketch "
                "mode)")
        assert self.sketch_sharded_server in ("auto", "on", "off"), \
            self.sketch_sharded_server
        if self.sketch_sharded_server == "on" and self.mode != "sketch":
            raise ValueError(
                f"--sketch_sharded_server on requires --mode sketch (mode="
                f"{self.mode} has no sketch server tail to shard); drop "
                "the flag or use --sketch_sharded_server auto (a no-op "
                "off sketch mode)")
        if self.signal_groups not in ("coarse", "leaf", "off"):
            raise ValueError(
                f"--signal_groups {self.signal_groups!r} not in "
                "('coarse', 'leaf', 'off')")
        if self.population_sketch not in ("auto", "on", "off"):
            raise ValueError(
                f"--population_sketch {self.population_sketch!r} not in "
                "('auto', 'on', 'off')")
        assert self.telemetry_every >= -1, self.telemetry_every
        assert self.alert_action in ALERT_ACTIONS, self.alert_action
        assert self.alert_window >= 4, self.alert_window
        assert self.alert_zscore > 0, self.alert_zscore
        if self.pipeline and self.prefetch_depth < 1:
            # depth < 1 with pipelining on used to silently degrade to the
            # inline fetch (RoundPipeline treated depth<=0 as "threading
            # off") — a user asking for prefetch would get none and no
            # message. Fail with the fix spelled out instead.
            raise ValueError(
                f"--prefetch_depth {self.prefetch_depth} is invalid with "
                "the round input pipeline enabled: the prefetcher needs a "
                "queue bound of at least 1 (2 = double-buffered). Pass "
                "--prefetch_depth >= 1, or --no_pipeline to run the fetch "
                "inline.")
        if self.prefetch_depth < 1:
            raise ValueError(
                f"--prefetch_depth {self.prefetch_depth} must be >= 1")
        # async buffered aggregation (mode-compatibility guards live in
        # core/async_agg.validate_async_combo, next to validate_mode_combo)
        assert self.staleness_discount in ("none", "poly", "exp"), \
            self.staleness_discount
        assert self.staleness_alpha > 0, self.staleness_alpha
        if self.async_agg:
            if self.buffer_goal < 1:
                raise ValueError(
                    f"--buffer_goal {self.buffer_goal} must be >= 1")
            if self.max_inflight < 1:
                raise ValueError(
                    f"--max_inflight {self.max_inflight} must be >= 1")
        assert self.scenario in ("none", "uniform", "lognormal",
                                 "stragglers"), self.scenario
        assert 0.0 <= self.scenario_dropout < 1.0, self.scenario_dropout
        assert 0.0 < self.scenario_participation <= 1.0, \
            self.scenario_participation
        if not self.async_agg and (
                self.scenario != "none" or self.scenario_dropout > 0
                or self.scenario_participation < 1.0):
            # a scenario without async aggregation would silently do
            # nothing — the lockstep loop never consults it (the exact
            # silently-ignored-flag failure the repo fails fast on)
            raise ValueError(
                "--scenario/--scenario_dropout/--scenario_participation "
                "require --async_agg: the synchronous round loop has no "
                "notion of a late, dropped or partially-participating "
                "cohort, so the scenario would be silently ignored.")
        # adversarial injection / defense / quarantine (the robustness
        # subsystem): validate the numerics here, mode/topology
        # compatibility at runtime init (core/server.validate_defense_combo
        # needs the resolved mesh)
        assert self.adversary in ADVERSARY_KINDS, self.adversary
        assert self.defense in DEFENSES, self.defense
        assert self.nonfinite_action in NONFINITE_ACTIONS, \
            self.nonfinite_action
        if not 0.0 <= self.adversary_frac <= 1.0:
            raise ValueError(
                f"--adversary_frac {self.adversary_frac} must be in [0, 1]")
        if self.adversary != "none" and self.adversary_frac == 0.0:
            # an attack study with zero adversaries would silently
            # measure a clean run (the silently-ignored-flag contract)
            raise ValueError(
                f"--adversary {self.adversary} with --adversary_frac 0 "
                "injects nothing; pass --adversary_frac > 0 (fraction of "
                "the client universe that is hostile)")
        if self.adversary == "none" and self.adversary_frac > 0.0:
            raise ValueError(
                f"--adversary_frac {self.adversary_frac} without "
                "--adversary selects clients that then do nothing; pass "
                f"--adversary {{{','.join(ADVERSARY_KINDS[1:])}}}")
        if self.adversary_scale <= 0:
            raise ValueError(
                f"--adversary_scale {self.adversary_scale} must be > 0 "
                "(scale attack multiplier / noise sigma)")
        if self.defense_clip_mult <= 0:
            raise ValueError(
                f"--defense_clip_mult {self.defense_clip_mult} must be > 0")
        if self.defense_window < 1:
            raise ValueError(
                f"--defense_window {self.defense_window} must be >= 1")
        if not 0.0 <= self.defense_trim_frac < 0.5:
            raise ValueError(
                f"--defense_trim_frac {self.defense_trim_frac} must be in "
                "[0, 0.5): trimming half or more of the clients per side "
                "leaves nothing to average")
        if self.quarantine_backoff < 1:
            raise ValueError(
                f"--quarantine_backoff {self.quarantine_backoff} must be "
                ">= 1 (rounds a struck client sits out before a retry)")
        if self.quarantine_strikes < 1:
            raise ValueError(
                f"--quarantine_strikes {self.quarantine_strikes} must be "
                ">= 1 (strikes before permanent ejection)")
        # preemption / watchdog numerics (validated unconditionally, the
        # scenario/defense-validator pattern: a bad value must fail at
        # parse time, not when the first SIGTERM arrives)
        if self.preempt_grace <= 0:
            raise ValueError(
                f"--preempt_grace {self.preempt_grace} must be > 0 "
                "seconds (the graceful-drain budget after the first "
                "SIGTERM/SIGINT; a second signal always force-exits)")
        if self.watchdog_mult < 1:
            raise ValueError(
                f"--watchdog_mult {self.watchdog_mult} must be >= 1: the "
                "stall deadline is this multiple of the rolling median "
                "round time, and a sub-1 multiplier would declare the "
                "median round stalled")
        if self.watchdog and (not self.telemetry
                              or self.telemetry_every == 0):
            # the deadline history only fills on synced (record) rounds
            # and the stall alert lands in the stream: without telemetry
            # (or with records disabled) the watchdog would silently
            # never arm — the exact silently-ignored-flag failure this
            # repo fails fast on
            raise ValueError(
                "--watchdog requires telemetry round records to arm "
                "(its deadline history fills on synced record rounds "
                "and its round_stall alert goes to the stream): drop "
                "--no_telemetry / set --telemetry_every != 0, or drop "
                "--watchdog.")
        if self.profile_dir:
            # a bad window spec must fail at startup, not at round START
            from commefficient_tpu.telemetry.profiling import \
                parse_profile_rounds
            parse_profile_rounds(self.profile_rounds)
        if self.sketch_dense_clip:
            # silently ignoring the flag would let a clip study run
            # unclipped — the exact wrong-conclusion failure it exists
            # to prevent
            assert self.mode == "sketch" and self.max_grad_norm is not None, \
                "--sketch_dense_clip requires --mode sketch and " \
                "--max_grad_norm"
        if self.mode == "fedavg":
            # reference invariants: utils.py:225-228
            assert self.local_batch_size == -1
            assert self.local_momentum == 0
            assert self.error_type == "none"

    def replace(self, **kw) -> "FedConfig":
        return dataclasses.replace(self, **kw)

    @property
    def telemetry_round_every(self) -> int:
        """Resolved --telemetry_every (-1 = auto; see the field comment):
        per-round records under --test, every 64 rounds otherwise."""
        if self.telemetry_every != -1:
            return self.telemetry_every
        return 1 if self.do_test else 64

    @property
    def transmitted_shape(self) -> Tuple[int, ...]:
        """Shape of the quantity a client uploads (reference: fed_aggregator.py:116-121)."""
        if self.mode == "sketch":
            return (self.num_rows, self.num_cols)
        return (self.grad_size,)

    @property
    def upload_floats(self) -> int:
        """Floats uploaded per participating client per round
        (reference byte table: fed_aggregator.py:291-299)."""
        return {
            "uncompressed": self.grad_size,
            "true_topk": self.grad_size,
            "local_topk": self.k,
            "sketch": self.num_rows * self.num_cols,
            "fedavg": self.grad_size,
        }[self.mode]

    def upload_wire_bytes(self, block: Optional[int] = None) -> float:
        """Exact simulated per-client upload bytes under the wire dtype
        (the paper's first-class metric; reference byte table
        fed_aggregator.py:291-299 counted 4 bytes/float).

        float32 (and every non-sketch mode): 4 bytes per transmitted
        float — byte-identical to the pre-wire accounting. bfloat16:
        2 bytes per table cell. int8: 1 byte per cell PLUS 4 bytes of
        f32 scale per ``block`` cells per row (``block`` defaults to
        cfg.wire_block; the runtime passes its resolved effective block
        so the accounting matches what actually crosses the wire).
        """
        if self.mode != "sketch" or self.wire_dtype == "float32":
            return 4.0 * self.upload_floats
        cells = self.num_rows * self.num_cols
        if self.wire_dtype == "bfloat16":
            return 2.0 * cells
        b = int(block or self.wire_block)
        n_scales = self.num_rows * (-(-self.num_cols // b))
        return float(cells + 4 * n_scales)

    @property
    def needs_client_velocities(self) -> bool:
        # reference: fed_aggregator.py:127-129
        return self.local_momentum > 0

    @property
    def needs_client_errors(self) -> bool:
        # reference: fed_aggregator.py:124-126
        return self.error_type == "local"

    def default_num_clients(self) -> int:
        if self.num_clients is not None:
            return self.num_clients
        # reference hardcoded table: fed_aggregator.py:68-72. Like the
        # reference, fail loudly (KeyError) for datasets with no natural
        # client count (e.g. ImageNet) instead of inventing one.
        defaults = {"EMNIST": 3500, "PERSONA": 17568,
                    "CIFAR10": 10, "CIFAR100": 100}
        return defaults[self.dataset_name]


def auto_num_cols(num_cols: int) -> int:
    """TPU-efficient sketch width for the circulant impl (VERDICT r4 weak
    #1): round ``num_cols`` up to the next multiple of 1024 (vreg-aligned
    shifts => both Pallas kernels eligible, ops/circulant_pallas.py) —
    but ONLY when the rounding grows the user's upload budget by <= 5%
    (at the reference default 500,000 -> 500,736 it is +0.15%). Small
    deliberately-tiny tables (e.g. unit-test geometries like c=320, where
    +1024 would triple the budget and change the compression regime) are
    left untouched. The extreme-d/c gather cliff keeps its loud warning
    (ops/circulant.py make_circulant_sketch) rather than an automatic
    multi-x budget increase. ``--exact_num_cols`` bypasses this entirely.
    """
    align = 1024
    c = -(-num_cols // align) * align
    if c != num_cols and (c - num_cols) / num_cols > 0.05:
        return num_cols
    return c


def enable_compilation_cache(cfg: "FedConfig") -> Optional[str]:
    """Persistent XLA compile cache for a run; see
    :func:`enable_compilation_cache_dir`."""
    return enable_compilation_cache_dir(cfg.compilation_cache_dir)


def enable_compilation_cache_dir(cache_dir: str) -> Optional[str]:
    """Turn on JAX's persistent compile cache and return the directory
    in use (None = off).

    The machine's operator places the cache: where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, and this
    function sets nothing — ``cache_dir`` is ignored, whatever it says
    (a throw-away machine may be handed a cache that outlives it, and
    only its operator knows where). It does make that directory if it is
    not there yet: JAX does not, every write to it then fails with a
    warning, and each run compiles everything again. Where the variable
    is unset,
    ``cache_dir`` is used: by default the fixed
    in-checkout ``DEFAULT_COMPILATION_CACHE_DIR``; ``""`` disables. A
    directory that cannot be created raises — a run that silently
    recompiles for minutes each start is a fault, not a fallback."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        os.makedirs(env_dir, exist_ok=True)
        return env_dir
    if not cache_dir:
        return None
    import jax
    path = os.path.abspath(os.path.expanduser(cache_dir))
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 10)
    return path


def add_args(parser: argparse.ArgumentParser, default_lr: Optional[float] = None):
    """Reference flag surface (CommEfficient/utils.py:102-230), minus the
    CUDA/process plumbing flags (--port, --device, --num_devices,
    --share_ps_gpu, dataloader workers) that have no meaning in a
    single-program SPMD runtime; plus TPU mesh flags."""
    p = parser
    p.add_argument("--test", action="store_true", dest="do_test")
    p.add_argument("--mode", choices=MODES, default="sketch")
    p.add_argument("--tensorboard", dest="use_tensorboard", action="store_true")
    p.add_argument("--seed", type=int, default=21)

    p.add_argument("--model", default="ResNet9")
    p.add_argument("--finetune", action="store_true", dest="do_finetune")
    p.add_argument("--checkpoint", action="store_true", dest="do_checkpoint")
    p.add_argument("--checkpoint_path", type=str, default="./checkpoint")
    p.add_argument("--checkpoint_sharded", action="store_true")
    p.add_argument("--checkpoint_every", type=int, default=0)
    p.add_argument("--resume", action="store_true", dest="do_resume")
    p.add_argument("--resume_unverified", action="store_true")
    p.add_argument("--finetune_path", type=str, default="./finetune")
    p.add_argument("--finetuned_from", type=str, choices=list(FED_DATASETS))
    p.add_argument("--num_results_train", type=int, default=2)
    p.add_argument("--num_results_val", type=int, default=2)
    p.add_argument("--dataset_name", type=str, default="CIFAR10",
                   choices=list(FED_DATASETS))
    p.add_argument("--dataset_dir", type=str, default="./dataset")
    p.add_argument("--batchnorm", action="store_true", dest="do_batchnorm")
    p.add_argument("--synthetic_per_class", type=int, default=64)
    p.add_argument("--synthetic_hard", action="store_true")
    p.add_argument("--synthetic_label_noise", type=float, default=0.0)
    p.add_argument("--no_augment", action="store_true",
                   help="train normalize-only (no crop/flip/shift); "
                        "implied by --synthetic_hard")

    p.add_argument("--k", type=int, default=50_000)
    p.add_argument("--num_cols", type=int, default=500_000)
    p.add_argument("--num_rows", type=int, default=5)
    p.add_argument("--num_blocks", type=int, default=20)
    p.add_argument("--topk_down", action="store_true", dest="do_topk_down")
    p.add_argument("--exact_num_cols", action="store_true",
                   help="pin --num_cols exactly (skip the TPU-efficient "
                        "auto-rounding of the circulant sketch width)")

    p.add_argument("--local_momentum", type=float, default=0.9)
    p.add_argument("--virtual_momentum", type=float, default=0.0)
    p.add_argument("--weight_decay", type=float, default=5e-4)
    p.add_argument("--num_epochs", type=float, default=24)
    p.add_argument("--num_fedavg_epochs", type=int, default=1)
    p.add_argument("--fedavg_batch_size", type=int, default=-1)
    p.add_argument("--fedavg_lr_decay", type=float, default=1.0)
    p.add_argument("--error_type", choices=ERROR_TYPES, default="none")
    p.add_argument("--lr_scale", type=float, default=default_lr)
    p.add_argument("--pivot_epoch", type=float, default=5)
    p.add_argument("--lr_warmup", action="store_true",
                   help="GPT-2 only: linear 0 -> lr_scale warmup peaking "
                        "at --pivot_epoch (the reference starts at full "
                        "LR; see gpt2_train.make_gpt2_schedule)")

    p.add_argument("--num_clients", type=int)
    p.add_argument("--num_workers", type=int, default=1)
    p.add_argument("--iid", action="store_true", dest="do_iid")

    p.add_argument("--model_checkpoint", type=str, default="gpt2")
    p.add_argument("--num_candidates", type=int, default=2)
    p.add_argument("--max_history", type=int, default=2)
    p.add_argument("--max_seq_len", type=int, default=0,
                   help="PERSONA packed sequence length; 0 = driver default")
    p.add_argument("--local_batch_size", type=int, default=8)
    p.add_argument("--valid_batch_size", type=int, default=8)
    p.add_argument("--microbatch_size", type=int, default=-1)
    p.add_argument("--lm_coef", type=float, default=1.0)
    p.add_argument("--mc_coef", type=float, default=1.0)
    p.add_argument("--max_grad_norm", type=float)
    p.add_argument("--personality_permutations", type=int, default=1)
    p.add_argument("--eval_before_start", action="store_true")

    p.add_argument("--dp", action="store_true", dest="do_dp")
    p.add_argument("--dp_mode", choices=DP_MODES, default="worker")
    p.add_argument("--l2_norm_clip", type=float, default=1.0)
    p.add_argument("--noise_multiplier", type=float, default=0.0)

    p.add_argument("--no_track_bytes", dest="track_bytes",
                   action="store_false", default=True)

    # TPU-native
    p.add_argument("--mesh_shape", type=str, default="",
                   help="comma-separated mesh, e.g. '4,2'; empty = single device")
    p.add_argument("--mesh_axes", type=str, default="clients")
    p.add_argument("--compute_dtype", type=str, default="bfloat16")
    p.add_argument("--param_dtype", type=str, default="float32")
    p.add_argument("--max_client_batch", type=int, default=512)
    p.add_argument("--sketch_seed", type=int, default=42)
    p.add_argument("--allow_divergent_rht", action="store_true")
    p.add_argument("--sketch_impl", choices=("circ", "hash", "rht"),
                   default="circ")
    p.add_argument("--sketch_dtype", choices=("float32", "bfloat16"),
                   default=None,
                   help="DEPRECATED alias of --wire_dtype (parse-time "
                        "warning; kept for old invocations — rht "
                        "transform compute dtype still follows it)")
    p.add_argument("--wire_dtype", choices=WIRE_DTYPES, default="",
                   help="sketch-table wire dtype: float32 (default), "
                        "bfloat16 (half the table payload, ~2^-8 cell "
                        "rounding), or int8 (block-quantized with "
                        "stochastic rounding + f32 scales, ~0.27x the "
                        "f32 wire; residual absorbed by server EF — "
                        "see ops/wire.py)")
    p.add_argument("--wire_block", type=int, default=256,
                   help="int8 wire: columns per abs-max scale block "
                        "(scale overhead = 4/block bytes per cell)")
    p.add_argument("--sketch_scan_rows", type=int, default=-1,
                   choices=(-1, 0, 1))
    p.add_argument("--pallas", choices=("auto", "on", "off"), default="auto",
                   help="circulant-sketch pallas kernels: auto/on = fused "
                        "encode+decode when eligible, off = XLA paths only")
    p.add_argument("--sketch_ef", choices=("zero", "subtract"),
                   default="zero",
                   help="sketch error-feedback rule: zero = reference "
                        "cell-zeroing; subtract = remove exactly the "
                        "extracted estimates (no collateral cell loss)")
    p.add_argument("--sketch_server_state", choices=("table", "dense"),
                   default="table",
                   help="sketch-mode server momentum/error: table = "
                        "reference FetchSGD (r x c state); dense = (d,) "
                        "pre-images with exact-support EF and one "
                        "enc+dec noise round-trip (single device, "
                        "deferred encode only; upload unchanged)")
    p.add_argument("--error_decay", type=float, default=1.0,
                   help="multiply Verror by this factor each round after "
                        "error feedback (sketch/true_topk); 1.0 = off")
    p.add_argument("--approx_topk", action="store_true")
    p.add_argument("--profile_dir", type=str, default="")
    p.add_argument("--profile_rounds", type=str, default="2:4",
                   help="1-based inclusive round window for the profiler "
                        "trace, START:STOP (with --profile_dir)")
    p.add_argument("--no_telemetry", dest="telemetry", action="store_false",
                   default=True,
                   help="disable the telemetry.jsonl event stream")
    p.add_argument("--telemetry_every", type=int, default=-1,
                   help="emit a per-round telemetry record every N rounds "
                        "(each record syncs the round's metrics to host; "
                        "0 = none, -1 = auto: 1 under --test, 64 "
                        "otherwise)")
    p.add_argument("--peak_flops", type=float, default=0.0,
                   help="peak FLOP/s of one accelerator for the MFU "
                        "accounting in `utilization` telemetry events; "
                        "0 = per-device_kind table "
                        "(telemetry/utilization.py)")
    p.add_argument("--peak_hbm_gbps", type=float, default=0.0,
                   help="peak HBM bandwidth (GB/s) of one accelerator "
                        "for the roofline attribution in `utilization` "
                        "telemetry events; 0 = per-device_kind table "
                        "(telemetry/utilization.py)")
    p.add_argument("--no_signals", dest="signals", action="store_false",
                   default=True,
                   help="drop the per-round compression-signal health "
                        "diagnostics from the jitted round step")
    p.add_argument("--no_client_stats", dest="client_stats",
                   action="store_false", default=True,
                   help="drop the per-client population statistics "
                        "(quantile summaries + participation ledger) "
                        "from the jitted round step")
    p.add_argument("--population_sketch", choices=("auto", "on", "off"),
                   default="auto",
                   help="participation-ledger backing (telemetry/"
                        "population.py): on = bounded-memory streaming "
                        "sketches (<= 8 MiB at any population size, "
                        "fields marked estimated), off = exact per-"
                        "client dict (O(population) memory), auto = "
                        "exact below 1e5 registered clients, sketch "
                        "at/above")
    p.add_argument("--alert_action", choices=ALERT_ACTIONS, default="log",
                   help="anomaly-monitor action on a fired rule: log = "
                        "alert event only; warn = + stderr; checkpoint = "
                        "+ one-shot flight-recorder bundle (state "
                        "snapshot, last-N events, alert context); abort "
                        "= + stop training")
    p.add_argument("--alert_window", type=int, default=32,
                   help="rolling median/MAD history length (and per-rule "
                        "refire cooldown) for the anomaly monitor")
    p.add_argument("--alert_zscore", type=float, default=6.0,
                   help="robust z-score threshold for the monitor's "
                        "statistical rules")
    p.add_argument("--signals_exact", action="store_true",
                   help="compute topk_overlap (heavy-hitter recovery vs "
                        "the exact dense error top-k); adds an O(d) "
                        "top-k per round, and a dense shadow error "
                        "accumulator for table-state sketch")
    p.add_argument("--signal_groups", choices=("coarse", "leaf", "off"),
                   default="coarse",
                   help="layer-wise compression attribution "
                        "(telemetry/layer_signals.py): parameter-group "
                        "granularity of the per-group recovery signals "
                        "emitted as layer_signals events — coarse = "
                        "path-pattern groups (per-block attn/mlp/"
                        "norm-bias, embed, head; stage-level for conv "
                        "nets), leaf = one group per pytree leaf, off = "
                        "compiled out of the round entirely")
    p.add_argument("--strict_regimes", action="store_true",
                   help="fail at startup (instead of warning) on "
                        "configurations measured divergent in round 5 "
                        "(see core/server.py check_regime_health)")
    p.add_argument("--compile_cache", "--compilation_cache_dir",
                   dest="compilation_cache_dir", type=str,
                   default=DEFAULT_COMPILATION_CACHE_DIR,
                   help="persistent XLA compile cache DIR; empty disables. "
                        "Ignored when JAX_COMPILATION_CACHE_DIR is set: "
                        "the environment places the cache then")
    p.add_argument("--no_pipeline", dest="pipeline", action="store_false",
                   default=True,
                   help="disable the round input pipeline (inline "
                        "fetch->dispatch; bit-identical losses, no "
                        "prefetch overlap)")
    p.add_argument("--prefetch_depth", type=int, default=2,
                   help="rounds the input pipeline prefetches ahead "
                        "(2 = double-buffered; must be >= 1 with the "
                        "pipeline enabled)")
    p.add_argument("--async_agg", action="store_true",
                   help="FedBuff-style async buffered aggregation "
                        "(core/async_agg.py): keep --max_inflight cohorts "
                        "in flight, merge landed cohort sums with "
                        "--staleness_discount weighting, commit the "
                        "buffer through the server momentum+EF step every "
                        "--buffer_goal cohorts")
    p.add_argument("--max_inflight", type=int, default=4,
                   help="cohort computations kept in flight (K); each "
                        "holds one transmitted-space array on device")
    p.add_argument("--buffer_goal", type=int, default=1,
                   help="cohorts merged per server commit (M); 1 commits "
                        "every landing cohort")
    p.add_argument("--staleness_discount",
                   choices=("none", "poly", "exp"), default="poly",
                   help="merge weight for a cohort s commits stale: none "
                        "= 1, poly = (1+s)^-alpha, exp = exp(-alpha*s)")
    p.add_argument("--staleness_alpha", type=float, default=0.5,
                   help="staleness discount exponent/rate (poly 0.5 = "
                        "FedBuff's 1/sqrt(1+s))")
    p.add_argument("--scenario",
                   choices=("none", "uniform", "lognormal", "stragglers"),
                   default="none",
                   help="straggler scenario engine (data/scenarios.py): "
                        "per-cohort simulated latency distribution; "
                        "requires --async_agg")
    p.add_argument("--scenario_latency", type=float, default=1.0,
                   help="base cohort latency, in dispatch ticks")
    p.add_argument("--scenario_spread", type=float, default=0.5,
                   help="latency spread (uniform half-width / lognormal "
                        "sigma)")
    p.add_argument("--scenario_straggler_frac", type=float, default=0.1,
                   help="'stragglers' kind: fraction of cohorts that are "
                        "slow")
    p.add_argument("--scenario_straggler_mult", type=float, default=10.0,
                   help="'stragglers' kind: latency multiplier of the "
                        "slow cohorts")
    p.add_argument("--scenario_dropout", type=float, default=0.0,
                   help="per-cohort probability of never landing (the "
                        "compute is skipped; nothing merges)")
    p.add_argument("--scenario_participation", type=float, default=1.0,
                   help="fraction of the round's worker slots that "
                        "actually participate (the rest are masked out "
                        "per cohort, deterministically)")
    p.add_argument("--adversary", choices=ADVERSARY_KINDS, default="none",
                   help="adversarial client injection: a deterministic "
                        "--adversary_frac of the client universe (keyed "
                        "off (seed, client_id)) label-flips, sign-flips, "
                        "boosts, noises or NaN-poisons its uploads; works "
                        "in sync and async rounds")
    p.add_argument("--adversary_frac", type=float, default=0.0,
                   help="fraction of the client universe that is "
                        "adversarial (required > 0 with --adversary)")
    p.add_argument("--adversary_scale", type=float, default=10.0,
                   help="scale-attack multiplier / noise-attack sigma")
    p.add_argument("--defense", choices=DEFENSES, default="none",
                   help="robust aggregation in transmitted space: "
                        "normclip = per-client update-norm clip to a "
                        "rolling-median x --defense_clip_mult threshold; "
                        "trim = per-coordinate trimmed-mean (single "
                        "device)")
    p.add_argument("--defense_clip_mult", type=float, default=3.0,
                   help="normclip threshold = rolling median per-datum "
                        "update norm x this multiplier")
    p.add_argument("--defense_window", type=int, default=8,
                   help="rounds of per-round median norms kept for the "
                        "normclip rolling-median reference")
    p.add_argument("--defense_trim_frac", type=float, default=0.1,
                   help="trim: per-coordinate fraction of clients dropped "
                        "at EACH extreme before averaging (in [0, 0.5))")
    p.add_argument("--nonfinite_action", choices=NONFINITE_ACTIONS,
                   default="abort",
                   help="nonfinite per-client update: abort = the "
                        "pre-existing all-or-nothing NaN abort; "
                        "quarantine = zero the client out of the "
                        "aggregate, bench it --quarantine_backoff rounds, "
                        "eject after --quarantine_strikes strikes (a "
                        "fully-nonfinite round still aborts)")
    p.add_argument("--quarantine_backoff", type=int, default=8,
                   help="rounds a struck client sits out before a retry")
    p.add_argument("--quarantine_strikes", type=int, default=3,
                   help="strikes before permanent ejection")
    p.add_argument("--preempt_grace", type=float, default=30.0,
                   help="graceful-preemption drain budget in seconds: "
                        "on SIGTERM/SIGINT, drain the pipeline/async "
                        "pool, write a `preempt`-tagged checkpoint "
                        "(round-granular meta) and exit 0 within this "
                        "budget; a second signal force-exits")
    p.add_argument("--watchdog", action="store_true",
                   help="arm the hang watchdog: a host thread deadlines "
                        "each round at --watchdog_mult x the rolling "
                        "median round time, fires a critical "
                        "round_stall alert + events-only flight-"
                        "recorder bundle on expiry, and wraps the "
                        "retryable input phases (device_put/gather "
                        "dispatch) in bounded exponential-backoff "
                        "retries")
    p.add_argument("--watchdog_mult", type=float, default=10.0,
                   help="stall deadline multiplier over the rolling "
                        "median round time (>= 1)")
    p.add_argument("--logdir", type=str, default="",
                   help="fixed run directory for telemetry/tensorboard "
                        "(empty = timestamped); a resumed run pointed "
                        "at its predecessor's logdir APPENDS to the "
                        "telemetry stream with a resume lineage record")
    p.add_argument("--remat", action="store_true", dest="do_remat")
    p.add_argument("--remat_policy", type=str, default="")
    p.add_argument("--lm_chunk", type=int, default=0)
    p.add_argument("--attn_impl", choices=("auto", "dense", "flash"),
                   default="auto",
                   help="GPT-2 attention: auto = dense below S=1024, "
                        "flash above (measured crossover)")
    p.add_argument("--no_fused_clients", dest="fused_clients",
                   action="store_false", default=True)
    p.add_argument("--sketch_fused_encode", choices=("auto", "on", "off"),
                   default="auto",
                   help="encode each per-microbatch gradient into the "
                        "sketch table inside the microbatch scan (table "
                        "carry; the dense (d,) gradient sum never hits "
                        "HBM): auto = when sound, on = require (fail "
                        "fast otherwise), off = the pre-fusion round")
    p.add_argument("--sketch_sharded_server", choices=("auto", "on", "off"),
                   default="auto",
                   help="shard the sketch server tail over the mesh "
                        "(reduce-scattered table, shard-local range "
                        "decode + candidate top-k merge; no device ever "
                        "holds the dense (d,) estimates): auto = on an "
                        "eligible mesh, on = require (fail fast "
                        "otherwise), off = the replicated tail")
    p.add_argument("--sketch_dense_clip", action="store_true",
                   help="clip the dense worker gradient before sketch "
                        "encode (threshold x num_iters) instead of the "
                        "reference's post-encode table clip")
    return parser


def parse_args(argv=None, default_lr: Optional[float] = None) -> FedConfig:
    parser = argparse.ArgumentParser()
    add_args(parser, default_lr=default_lr)
    ns = parser.parse_args(argv)
    kw = vars(ns)
    mesh_shape = tuple(int(x) for x in kw.pop("mesh_shape").split(",") if x)
    mesh_axes = tuple(x for x in kw.pop("mesh_axes").split(",") if x)
    if kw.get("sketch_dtype") is not None:
        # deprecated alias (ISSUE 14): --sketch_dtype keeps working but
        # warns once at parse time; an explicit --wire_dtype wins
        import sys
        print("WARNING: --sketch_dtype is a deprecated alias of "
              "--wire_dtype (it now also covers the int8 quantized "
              "wire); update the invocation.", file=sys.stderr)
        if not kw.get("wire_dtype"):
            kw["wire_dtype"] = kw["sketch_dtype"]
    else:
        kw["sketch_dtype"] = "float32"
    return FedConfig(mesh_shape=mesh_shape, mesh_axes=mesh_axes, **kw)
