"""CV experiment driver: federated ResNets on CIFAR10/100, FEMNIST, ImageNet.

Parity target: reference CommEfficient/cv_train.py (421 LoC) — same flag
surface, same five modes, same epoch loop shape (fractional epochs, skip
underfull rounds, NaN abort, per-epoch TableLogger/TSV rows with train/test
loss+acc and simulated per-client down/up MiB, end-of-run checkpoint),
driven by the same triangular LR schedule (0 -> lr_scale @ pivot_epoch -> 0).

Run:  python -m commefficient_tpu.cv_train --dataset_name CIFAR10 \
          --model ResNet9 --mode sketch --error_type virtual ...
"""

from __future__ import annotations

import math
import os
import sys
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from commefficient_tpu import models
from commefficient_tpu.config import (FedConfig, enable_compilation_cache,
                                      num_classes_of_dataset, parse_args)
from commefficient_tpu.core import FedRuntime, PreemptGuard, RoundPipeline
from commefficient_tpu.data import (
    FedSampler,
    ValSampler,
    get_dataset,
    transforms_for,
)
from commefficient_tpu.data.device_store import make_device_store
from commefficient_tpu.data.fed_sampler import mask_blocked
from commefficient_tpu.faults import maybe_fault
from commefficient_tpu.losses import make_cv_loss
from commefficient_tpu.telemetry import (ProfilerWindow, UtilizationTracker,
                                         layer_signals_to_host,
                                         signals_to_host, tracing)
from commefficient_tpu.telemetry import maybe_create as make_telemetry
from commefficient_tpu.telemetry.clients import (client_stats_to_host,
                                                 make_ledger)
from commefficient_tpu.telemetry.health import AnomalyMonitor, FlightRecorder
from commefficient_tpu.telemetry.schema import MOE_COUNTER_FIELDS
from commefficient_tpu.utils import (
    PiecewiseLinear,
    TableLogger,
    TSVLogger,
    Timer,
    make_logdir,
)


def fixup_lr_multiplier(params, unravel_shape_ref: jax.Array) -> jax.Array:
    """Per-parameter LR multipliers for Fixup models: 0.1 on scalar
    bias/scale params, 1.0 elsewhere (reference param groups,
    cv_train.py:361-371 + FedOptimizer.get_lr, fed_aggregator.py:411-427)."""
    flat_paths = jax.tree_util.tree_flatten_with_path(params)[0]
    pieces = []
    for path, leaf in flat_paths:
        names = "/".join(str(getattr(p, "key", p)) for p in path)
        mult = 0.1 if ("bias" in names or "scale" in names) else 1.0
        pieces.append(np.full(int(np.prod(leaf.shape)), mult, np.float32))
    vec = np.concatenate(pieces)
    assert vec.size == unravel_shape_ref.size
    return jnp.asarray(vec)


def build_model(cfg: FedConfig, num_classes: int):
    kwargs = {"num_classes": num_classes}
    if cfg.do_test:
        # tiny model for the smoke path (reference cv_train.py:329-336)
        kwargs["channels"] = {"prep": 1, "layer1": 1, "layer2": 1,
                              "layer3": 1}
    ctor = models.get_model(cfg.model)
    if cfg.model == "ResNet9":
        kwargs["do_batchnorm"] = cfg.do_batchnorm
    elif cfg.model != "FixupResNet9":
        kwargs.pop("channels", None)
    return ctor(**kwargs)


def build_mesh(cfg: FedConfig):
    """Honor --mesh_shape/--mesh_axes (TPU-native flags): returns a Mesh or
    None for plain single-device jit. Says how many of the visible
    devices the run uses — without --mesh_shape a four-chip host runs on
    chip 0 alone, which should be read off the log, not a profile."""
    dev = jax.devices()[0]
    visible = f"{jax.device_count()} visible {dev.platform} device(s)"
    if not cfg.mesh_shape:
        print(f"devices: using 1 of {visible} ({dev.device_kind}; "
              "no --mesh_shape, plain jit on device 0)")
        return None
    from commefficient_tpu.parallel import make_mesh
    mesh = make_mesh(cfg.mesh_shape, cfg.mesh_axes)
    n = mesh.shape[mesh.axis_names[0]]
    if cfg.num_workers % n != 0:
        raise ValueError(
            f"--num_workers {cfg.num_workers} must be divisible by the "
            f"mesh axis size {n}")
    print(f"devices: using {mesh.size} of {visible} ({dev.device_kind}; "
          f"mesh {dict(mesh.shape)})")
    return mesh


def setup_checkpointing(cfg: FedConfig, runtime: FedRuntime, name: str):
    """Shared --checkpoint/--checkpoint_every/--resume wiring.
    Returns (ckpt_mgr_or_None, start_epoch, restored_state_or_None,
    resume_info). ``resume_info`` is None for a fresh start; on resume
    it carries the round-granular position plus everything the epoch
    loop needs to continue EXACTLY — {"round_in_epoch": rounds already
    trained in start_epoch (0 for epoch-cadence checkpoints),
    "global_round", "ledgers": the host-ledger sidecar
    (core/preempt.collect_ledger_state), "checkpoint": the restored
    generation, "fallbacks": integrity fallbacks the restore performed
    (for `fault` telemetry)}."""
    if not (cfg.do_checkpoint or cfg.do_resume or cfg.checkpoint_every):
        return None, 0, None, None
    # use the runtime's RESOLVED config from here on: num_cols may have
    # been auto-sized at runtime init (config.auto_num_cols), and the
    # sketch-generation marker below must describe the tables actually
    # built — a marker computed from the caller's pre-runtime copy would
    # let a geometry-mismatched resume slip past the guard
    cfg = runtime.cfg
    from commefficient_tpu.checkpoint import (CheckpointManager,
                                              params_fingerprint)
    mgr = CheckpointManager(os.path.join(cfg.checkpoint_path, name),
                            sharded=cfg.checkpoint_sharded)
    # the layout alone is fingerprinted: shapes, no d-long ravel
    fp = params_fingerprint(jax.eval_shape(
        runtime.unravel,
        jax.ShapeDtypeStruct((cfg.grad_size,), jnp.float32)))
    # sketch state (Vvelocity/Verror tables) is only meaningful under the
    # EXACT sketch construction that encoded it: record a generation
    # marker so a resume under different shifts/signs (e.g. the r3 change
    # to 1024-aligned shifts for aligned num_cols) refuses instead of
    # decoding the tables into garbage
    sketch_gen = None
    if cfg.mode == "sketch":
        sketch_gen = (f"{cfg.sketch_impl}-"
                      + ("aligned1024" if (cfg.sketch_impl == "circ"
                                           and cfg.num_cols % 1024 == 0)
                         else "v1")
                      + f"-{cfg.num_rows}x{cfg.num_cols}-{cfg.sketch_seed}"
                      # dense pre-image server state stores (d,) buffers,
                      # not tables — a cross-state resume must refuse
                      + ("-densestate"
                         if cfg.sketch_server_state == "dense" else ""))
    # async-aggregation vintage marker: records that (and how) this run
    # buffers, so a resume can refuse an unverifiable ledger BEFORE any
    # state is materialized (see checkpoint._check_async_gen). Written as
    # None by synchronous runs — absent and None are the same vintage.
    async_gen = None
    if cfg.async_agg:
        async_gen = (f"v1-{cfg.staleness_discount}"
                     f"-a{cfg.staleness_alpha}"
                     f"-M{cfg.buffer_goal}-K{cfg.max_inflight}")
    mgr.default_meta = {"params_fingerprint": fp, "sketch_gen": sketch_gen,
                        "async_gen": async_gen}
    if cfg.do_resume:
        # the sketch-generation marker is checked against the checkpoint's
        # META (inside restore_latest) BEFORE any state is materialized —
        # in particular a table-state checkpoint resumed under
        # --sketch_server_state dense fails with the layout explanation
        # instead of a raw array-shape error mid-load. The async marker
        # is checked the same way: a pre-async checkpoint resumed into an
        # --async_agg run refuses with the buffer-ledger explanation
        # unless --resume_unverified opts into a fresh, empty buffer
        restored, meta = mgr.restore_latest(
            sharding=runtime._state_sharding, expect_fingerprint=fp,
            allow_missing_fingerprint=cfg.resume_unverified,
            d_pad=runtime.d_pad, num_clients=runtime.num_clients,
            d_row_pad=runtime.d_row_pad,
            expect_sketch_gen=sketch_gen,
            sketch_mismatch_ok=cfg.resume_unverified,
            expect_async_gen=async_gen,
            async_mismatch_ok=cfg.resume_unverified)
        if restored is not None:
            saved_gen = meta.get("sketch_gen")
            if saved_gen != sketch_gen and sketch_gen is not None:
                # only reachable under --resume_unverified (same-layout
                # mismatch). Discard-and-continue: fresh tables, weights
                # kept — resuming with mismatched tables would silently
                # decode garbage every round
                restored = restored.replace(
                    Vvelocity=jnp.zeros_like(restored.Vvelocity),
                    Verror=jnp.zeros_like(restored.Verror))
                print("WARNING: sketch generation changed "
                      f"({saved_gen!r} -> {sketch_gen!r}); momentum/error "
                      "tables RESET, resuming from weights only",
                      file=sys.stderr)
            if runtime._signals_shadow and restored.sig_Verror is None:
                # checkpoints written before the --signals_exact shadow
                # accumulators existed (or with signals off) restore
                # None here; re-zero them so the topk_overlap signal
                # stays LIVE on the resumed run — the shadow (not the
                # run) restarts from zero, as core/state.py documents
                zeros = jnp.zeros((runtime.cfg.grad_size,), jnp.float32)
                restored = restored.replace(sig_Vvelocity=zeros,
                                            sig_Verror=jnp.zeros_like(zeros))
            elif restored.sig_Verror is not None \
                    and not runtime._signals_shadow:
                # the reverse direction: a --signals_exact checkpoint
                # resumed WITHOUT the flag would otherwise thread the
                # dead dense shadow pair (2 x d fp32 — ~1 GB at GPT-2
                # scale) through every round and future checkpoint;
                # drop it so the state matches this runtime's template
                restored = restored.replace(sig_Vvelocity=None,
                                            sig_Verror=None)
            # --defense normclip rolling reference: a checkpoint written
            # before it existed (or with a different window) re-inits it
            # to NaN — the clip reference (not the run) restarts cold,
            # falling back to the resumed rounds' own medians; a ring
            # resumed into a run without normclip is dropped
            ring_n = (runtime.cfg.defense_window
                      if runtime._defense_ring else None)
            cur_ring = restored.defense_ref
            if ring_n is not None and (cur_ring is None
                                       or cur_ring.shape[0] != ring_n):
                restored = restored.replace(defense_ref=jnp.full(
                    (ring_n,), jnp.nan, jnp.float32))
            elif ring_n is None and cur_ring is not None:
                restored = restored.replace(defense_ref=None)
            # async buffer reconciliation (core/async_agg.py): a missing
            # buffer initializes EMPTY, a NON-EMPTY one (mid-epoch
            # postmortem) is LOUDLY restarted — the epoch replays from
            # its boundary, so restoring the buffer would double-count
            # its cohorts; and an async checkpoint resumed synchronously
            # drops the fields to match this runtime's template
            from commefficient_tpu.core.async_agg import \
                reconcile_resumed_state
            restored, async_msgs = reconcile_resumed_state(restored,
                                                           runtime)
            for m in async_msgs:
                print(f"WARNING: {m}", file=sys.stderr)
            start = int(meta.get("epoch", 0))
            # round-granular position (schema: CheckpointManager.save) —
            # epoch-cadence checkpoints sit at round 0, a preempt-tagged
            # generation mid-epoch carries the rounds already trained so
            # the epoch loop can rebuild the SAME (seed, epoch) sampler
            # and skip exactly that many rounds (RoundPipeline skip=)
            start_round = int(meta.get("round_in_epoch", 0))
            resume_info = {
                "round_in_epoch": start_round,
                "global_round": int(meta.get("global_round", -1)),
                "ledgers": meta.get("ledgers"),
                "checkpoint": mgr._path(start, start_round,
                                        meta.get("tag")),
                "fallbacks": list(mgr.restore_fallbacks),
            }
            print(f"resumed from epoch {start}"
                  + (f" + {start_round} rounds (preempt checkpoint)"
                     if start_round else ""))
            return mgr, start, restored, resume_info
    return mgr, 0, None, None


def build_datasets(cfg: FedConfig):
    ds_cls = get_dataset(cfg.dataset_name)
    kw = {}
    if cfg.dataset_name in ("CIFAR10", "CIFAR100", "ImageNet"):
        kw["synthetic_per_class"] = cfg.synthetic_per_class
    if cfg.synthetic_hard:
        # the flag is a CIFAR synthetic-GENERATOR knob; on any config
        # where the generator would not run, silently proceeding would
        # also silently disable train augmentation below — fail fast
        if cfg.dataset_name not in ("CIFAR10", "CIFAR100"):
            raise ValueError(
                "--synthetic_hard is a CIFAR synthetic-generator knob; "
                f"it does nothing for {cfg.dataset_name}")
        if ds_cls._has_real_source(cfg.dataset_dir):
            raise ValueError(
                f"--synthetic_hard set but real data exists under "
                f"{cfg.dataset_dir} (the dataset would train on it and "
                "ignore the flag); remove the flag or point "
                "--dataset_dir elsewhere")
    if cfg.dataset_name in ("CIFAR10", "CIFAR100"):
        kw["synthetic_hard"] = cfg.synthetic_hard
        kw["synthetic_label_noise"] = cfg.synthetic_label_noise
    # the hard synthetic regime's class evidence is per-prototype-pixel:
    # random-crop/flip augmentation scrambles it and training flatlines
    # at chance (same reason tests/test_learning.py trains its synthetic
    # runs un-augmented), so hard-mode runs train on the normalize-only
    # transform; --no_augment requests the same standalone (any
    # per-pixel-prototype synthetic regime, e.g. EMNIST's).
    # cfg.no_augment is already normalized to include synthetic_hard.
    train_transform = transforms_for(
        cfg.dataset_name, train=not cfg.no_augment, seed=cfg.seed)
    if cfg.do_test:
        kw["synthetic"] = True
    train_ds = ds_cls(cfg.dataset_dir, train=True, do_iid=cfg.do_iid,
                      num_clients=cfg.num_clients,
                      transform=train_transform, **kw)
    val_ds = ds_cls(cfg.dataset_dir, train=False,
                    transform=transforms_for(cfg.dataset_name, False), **kw)
    return train_ds, val_ds


def run_validation(runtime: FedRuntime, state, val_ds, cfg: FedConfig,
                   val_store=None):
    """Validation sweep. With a DeviceStore, every batch is gathered on
    device and the per-batch sums accumulate on device — exactly one host
    fetch for the whole sweep (see data/device_store.py)."""
    acc_sums = None
    host_sums = [0.0, 0.0, 0.0]
    for idx, mask in ValSampler(len(val_ds), cfg.valid_batch_size):
        if val_store is not None:
            batch = val_store.round_batch(idx, None)
        else:
            batch = val_ds.gather(idx)
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
        results, n_valid = runtime.val(state, batch, jnp.asarray(mask))
        contrib = jnp.stack([results[0] * n_valid, results[1] * n_valid,
                             n_valid])
        acc_sums = contrib if acc_sums is None else acc_sums + contrib
        if cfg.do_test:
            break
    if acc_sums is not None:
        host_sums = np.asarray(acc_sums)
    total = max(float(host_sums[2]), 1.0)
    return float(host_sums[0]) / total, float(host_sums[1]) / total


def make_writer(cfg: FedConfig, logdir: Optional[str] = None):
    """TensorBoard writer when --tensorboard is set (reference utils.py:51-64
    + cv_train.py:407-411); gated on torch's SummaryWriter being available.
    ``logdir`` shares the run directory with telemetry — make_logdir
    timestamps at second resolution, so two independent calls can split
    one run's artifacts across sibling directories."""
    if not cfg.use_tensorboard:
        return None
    try:
        from torch.utils.tensorboard import SummaryWriter
    except Exception:
        print("WARNING: --tensorboard set but SummaryWriter unavailable")
        return None
    return SummaryWriter(log_dir=logdir or make_logdir(cfg))


def train(cfg: FedConfig, runtime: FedRuntime, state, train_ds, val_ds,
          lr_mult: Optional[jax.Array] = None, loggers=(), timer=None,
          ckpt_mgr=None, start_epoch: int = 0, writer=None, schedule=None,
          telemetry=None, model_flops_per_round: Optional[float] = None,
          resume_info=None, guard=None, round_counters=()):
    """The shared driver loop. ``round_counters`` names what the training
    loss returns after (loss, accuracy): the round's telemetry event
    carries their means over the round's items, the expert layers'
    under ``moe`` (models/layers.MOE_COUNTERS; a non-zero ``dropped``
    raises), any other under its own name (models/joyai.ROUND_COUNTERS:
    ``main_nll``, ``mtp_nll``).
    Returns ``(state, summary)``; ``summary``
    is None when the run ended before its schedule — a preemption drain
    (an orderly handoff) or an abort (non-finite update, alert abort,
    quarantine exhausted). A caller that must tell the two apart passes
    its own ``guard`` (core/preempt.PreemptGuard) and reads
    ``guard.requested`` afterwards; see :func:`finish_run`."""
    timer = timer or Timer()
    # rounds already trained inside start_epoch (round-granular resume:
    # a preempt-tagged checkpoint written mid-epoch; 0 everywhere else)
    start_round = int((resume_info or {}).get("round_in_epoch", 0))
    # profiler window over --profile_rounds (telemetry/profiling.py);
    # replaces the window previously hardcoded to rounds 2-4 of this
    # driver only
    prof = ProfilerWindow(cfg.profile_dir, cfg.profile_rounds)
    # span tracer + MFU/starvation accounting (telemetry/tracing.py,
    # telemetry/utilization.py): a tracer this loop drains into the
    # stream is installed only when a telemetry stream exists — with
    # --no_telemetry the spans stay in the process's default ring. Either
    # way every span is a "fed:" annotation in a profiler trace
    # (--profile_rounds), on the same clock as the device's ops
    tracer = util = None
    monitor = recorder = ledger = None
    if telemetry is not None:
        tracer = tracing.install(tracing.SpanTracer())
        util = UtilizationTracker(telemetry, peak_flops=cfg.peak_flops,
                                  peak_hbm_gbps=cfg.peak_hbm_gbps,
                                  watcher=telemetry.watcher(),
                                  # schema v7: the round's mesh topology,
                                  # so per-chip throughput normalizes
                                  # from the stream alone
                                  n_devices=(runtime.mesh.size
                                             if runtime.mesh is not None
                                             else 1),
                                  mesh_shape=(list(runtime.mesh.shape
                                                   .values())
                                              if runtime.mesh is not None
                                              else None))
        if model_flops_per_round:
            # analytic MFU numerator (gpt2_train passes one: XLA's cost
            # analysis under-counts scanned rounds, models/gpt2.py)
            util.set_flops_per_round(model_flops_per_round)
        # online anomaly monitor (telemetry/health.py): fed every
        # monitored event the stream writes (set_monitor forwarding);
        # under --alert_action checkpoint/abort the flight recorder
        # snapshots state + recent events on the FIRST fired rule
        monitor = AnomalyMonitor(telemetry, action=cfg.alert_action,
                                 window=cfg.alert_window,
                                 z_thresh=cfg.alert_zscore)
        telemetry.set_monitor(monitor)
        if cfg.alert_action in ("checkpoint", "abort"):
            recorder = FlightRecorder(telemetry.logdir, telemetry)
        if cfg.client_stats:
            # host-side participation accounting over the whole client
            # universe — observes the sampler's (host-resident) ids, so
            # it costs no device traffic and runs EVERY round. The
            # backing is policy-selected (telemetry/clients.make_ledger):
            # exact dict for small universes, bounded-memory sketches
            # (telemetry/population.py) at population scale
            ledger = make_ledger(train_ds.num_clients,
                                 cfg.population_sketch)
    # async buffered aggregation (core/async_agg.py): the round splits
    # into dispatch-time cohort compute and buffer-goal commits; the
    # scenario engine (data/scenarios.py) decides each cohort's
    # latency/dropout/participation deterministically off the global
    # round index. One aggregator for the whole run; the epoch boundary
    # flushes it, so checkpoints never straddle an open buffer.
    async_agg = None
    if cfg.async_agg:
        from commefficient_tpu.core.async_agg import (AsyncAggregator,
                                                      commit_loss)
        from commefficient_tpu.data.scenarios import make_scenario
        async_agg = AsyncAggregator(runtime, scenario=make_scenario(cfg))
        print(f"async aggregation: K={async_agg.max_inflight} in flight, "
              f"commit every M={async_agg.buffer_goal} cohorts, "
              f"{async_agg.discount} staleness discount"
              + ("" if async_agg.scenario is None
                 else f", scenario={cfg.scenario}"))
    # robustness subsystem (core/runtime.py does the in-round work; this
    # loop owns the host half): the quarantine ledger benches/ejects
    # clients whose uploads went nonfinite — the device already zeroed
    # them out of the aggregate, this just stops re-dispatching them —
    # and the schema-v5 `defense` event reports what the defense did
    qledger = None
    if cfg.nonfinite_action == "quarantine":
        from commefficient_tpu.core.quarantine import QuarantineLedger
        qledger = QuarantineLedger(backoff=cfg.quarantine_backoff,
                                   strikes=cfg.quarantine_strikes)
    # ---- preemption / fault-tolerance layer (core/preempt.py) ----
    # restore the host-ledger sidecar a round-granular checkpoint
    # carried: quarantine strikes/benches/ejections (a restart must NOT
    # re-admit known-bad clients), participation coverage, and the
    # anomaly monitor's rolling histories — then announce the resume
    # lineage (and any corrupt-generation fallbacks) into the stream
    from commefficient_tpu.core.preempt import (RoundWatchdog,
                                                collect_ledger_state,
                                                restore_ledger_state,
                                                with_retries)
    if resume_info is not None:
        restore_ledger_state(resume_info.get("ledgers"), qledger=qledger,
                             participation=ledger, monitor=monitor)
        if telemetry is not None:
            for fb in resume_info.get("fallbacks") or ():
                telemetry.fault_event(
                    rnd=-1, kind="corrupt_checkpoint",
                    detail=fb.get("error"), checkpoint=fb.get("path"))

    def _ledger_sidecar():
        return collect_ledger_state(qledger=qledger, participation=ledger,
                                    monitor=monitor, telemetry=telemetry)

    # graceful preemption: the FIRST SIGTERM/SIGINT sets a flag this
    # loop notices at the next round boundary (drain within
    # --preempt_grace: close the pipeline, flush the async pool, write
    # a preempt-tagged round-granular checkpoint, fsync a final fault
    # event, exit 0); a SECOND signal force-exits. Constructed here;
    # INSTALLED (and the watchdog thread started) immediately before
    # the try whose finally reclaims them — an exception in the setup
    # code between must not leak a replaced signal handler or a thread
    if guard is None:
        guard = PreemptGuard(cfg.preempt_grace)
    # hang watchdog (--watchdog): deadline each round's dispatch+sync at
    # watchdog_mult x the rolling median round time; on expiry fire a
    # critical round_stall alert THROUGH the monitor and record an
    # events-only flight-recorder bundle (never a state fetch — that is
    # the operation that may be hung)
    watchdog = None
    if cfg.watchdog:
        def _on_stall(rnd, elapsed, deadline):
            msg = (f"round {rnd} exceeded its stall deadline: "
                   f"{elapsed:.1f}s > {deadline:.1f}s")
            print(f"WATCHDOG: {msg}", file=sys.stderr)
            if monitor is not None:
                monitor.external_alert(rnd=rnd, rule="round_stall",
                                       metric="round.wall_s",
                                       value=float(elapsed))
            if telemetry is not None:
                telemetry.fault_event(rnd=rnd, kind="round_stall",
                                      detail=msg)
                telemetry.fsync()
            if recorder is not None:
                recorder.record(None, {"rule": "round_stall",
                                       "round": int(rnd),
                                       "elapsed_s": float(elapsed),
                                       "deadline_s": float(deadline)})
    adv_plan = getattr(runtime, "adversary_plan", None)
    defense_on = (cfg.defense != "none" or cfg.adversary != "none"
                  or cfg.nonfinite_action == "quarantine")
    if cfg.adversary != "none" and adv_plan is not None:
        n_adv = int(adv_plan.universe_mask(train_ds.num_clients).sum())
        print(f"adversary injection: {cfg.adversary} on {n_adv}/"
              f"{train_ds.num_clients} clients "
              f"(frac {cfg.adversary_frac}), defense={cfg.defense}, "
              f"nonfinite_action={cfg.nonfinite_action}")
    # device-resident data path: upload the dataset once, gather + augment
    # each round's batch on device, accumulate metrics on device, and fetch
    # once per epoch — the reference's per-round stream-and-read pattern
    # (cv_train.py:193-229) would put a host sync on every round's
    # critical path (data/device_store.py). On a mesh the arrays replicate
    # across devices and train batches come out already sharded over the
    # round's client axis.
    train_store = make_device_store(
        train_ds, cfg.dataset_name, True, mesh=runtime.mesh,
        out_shardings=(runtime.batch_sharding()
                       if runtime.mesh is not None else None),
        no_augment=cfg.no_augment)
    val_store = make_device_store(val_ds, cfg.dataset_name, False,
                                  mesh=runtime.mesh)
    if train_store is not None:
        print(f"device-resident data: train "
              f"{train_store.nbytes / 2**20:.0f} MiB"
              + (f", val {val_store.nbytes / 2**20:.0f} MiB"
                 if val_store else ""))
    if train_store is None or val_store is None:
        # the host gathers at least one of the two streams: say with
        # which implementation (a failed C++ build is a slower gather,
        # not an error)
        from commefficient_tpu.data import native
        print("host gather path: "
              + ("native (native/fedloader.cpp)" if native.available()
                 else f"numpy ({native.unavailable_reason()})"))
    data_key = jax.random.PRNGKey(cfg.seed ^ 0xDA7A)
    if schedule is None:
        # CV default: the cifar10_fast triangular ramp
        # (reference cv_train.py:393-404)
        schedule = PiecewiseLinear(
            [0.0, cfg.pivot_epoch, float(cfg.num_epochs)],
            [0.0, cfg.lr_scale if cfg.lr_scale is not None else 0.4, 0.0])

    # one sampler per epoch, seeded by (seed, epoch): an interrupted run
    # resumed at epoch E replays exactly the round sequence the
    # uninterrupted run would have used from epoch E on (see checkpoint.py)
    def epoch_sampler(epoch: int) -> FedSampler:
        return FedSampler(train_ds.data_per_client, cfg.num_workers,
                          cfg.local_batch_size,
                          max_client_batch=cfg.max_client_batch,
                          seed=cfg.seed + 7919 * epoch)

    spe = max(epoch_sampler(0).epoch_rounds(), 1)
    total_download_mb = total_upload_mb = 0.0
    # resume: the global counter continues from the EXACT round the
    # checkpoint recorded. epoch_rounds() is an upper bound (a sampler
    # can strand an underfull tail and end an epoch early), so deriving
    # the counter as start_epoch * spe can over-number the resumed
    # rounds — shifting every LR lookup and round-keyed RNG off the
    # uninterrupted trajectory. Pre-meta checkpoints (global_round
    # unrecorded) keep the old derivation.
    resume_global = int((resume_info or {}).get("global_round", -1))
    global_round = (resume_global if resume_global >= 0
                    else start_epoch * spe + start_round)
    rounds_run = 0
    summary = None

    # round input fetch, shared by the pipelined and inline paths
    # (core/pipeline.py): all randomness keys off the GLOBAL round index,
    # so prefetching ahead cannot change what trains
    def _fetch_round(rnd, g_round: int):
        if train_store is not None:
            return train_store.round_batch(
                rnd.idx, jax.random.fold_in(data_key, g_round))
        b = train_ds.gather(rnd.idx)
        return {k: jnp.asarray(v) for k, v in b.items()}

    if cfg.watchdog:
        # the retryable host-side phases (DeviceStore gather dispatch /
        # host gather + device_put) get bounded exponential-backoff
        # retries before the round is declared dead — gated on the
        # watchdog opt-in so the lockstep paths keep strict fail-fast
        def fetch_round(rnd, g_round: int):
            def _note(attempt, err):
                if telemetry is not None:
                    telemetry.fault_event(
                        rnd=g_round, kind="fetch_retry",
                        detail=f"attempt {attempt}: {err}")
            return with_retries(lambda: _fetch_round(rnd, g_round),
                                attempts=3, desc=f"round {g_round} input "
                                "fetch", on_retry=_note)
    else:
        fetch_round = _fetch_round

    if cfg.eval_before_start:
        test_loss, test_acc = run_validation(runtime, state, val_ds, cfg,
                                             val_store=val_store)
        print(f"Test acc at epoch 0: {test_acc:0.4f}")

    def _preempt_drain(state, cur_epoch, r_in_epoch, pipe,
                       existing_ckpt=None):
        """The --preempt_grace drain: reclaim the prefetch thread, flush
        the async pool through the existing epoch-flush path (no open
        buffer ever reaches a checkpoint), write an out-of-cadence
        `preempt`-tagged checkpoint with round-granular meta + the
        host-ledger sidecar, and fsync the stream behind a final
        `fault` event. The caller returns (state, None) and the driver
        process exits 0 — a preemption is an orderly handoff, not a
        failure. The grace budget is ENFORCED: a drain that wedges
        (checkpoint save against a hung device, a flush stuck in a dead
        collective) is force-exited when the remaining budget runs out
        — the resume then falls back to the last durable checkpoint.
        ``existing_ckpt`` names an epoch-cadence checkpoint of the SAME
        state written moments ago (the preemption-during-validation
        case): re-saving multi-GB state inside the grace window would
        only burn the budget, so the drain reuses it."""
        remaining = max(cfg.preempt_grace - (guard.grace_used_s() or 0.0),
                        1.0)
        force_timer = guard.force_exit_after(remaining)
        try:
            return _drain_body(state, cur_epoch, r_in_epoch, pipe,
                               existing_ckpt)
        finally:
            force_timer.cancel()

    def _flush_async(state):
        """Drain the in-flight pool and commit any partial buffer,
        recording each commit — ONE implementation for the epoch
        boundary and the preempt drain, so checkpoints written by
        either always see a closed buffer with identical semantics."""
        if async_agg is None:
            return state
        flush_lr = schedule(global_round / spe)
        flush_lr_arr = (jnp.asarray(flush_lr, jnp.float32)
                        if lr_mult is None else flush_lr * lr_mult)
        state, fcommits = async_agg.flush(state, flush_lr_arr)
        if telemetry is not None:
            for c in fcommits:
                telemetry.async_round_event(rec=c, lr=float(flush_lr),
                                            loss=commit_loss(c),
                                            with_device=True)
        return state

    def _drain_body(state, cur_epoch, r_in_epoch, pipe, existing_ckpt):
        if pipe is not None:
            pipe.close()
        state = _flush_async(state)
        ck_path = None
        if existing_ckpt is not None:
            ck_path = existing_ckpt
        elif ckpt_mgr is not None:
            ck_path = ckpt_mgr.save(
                state, cur_epoch,
                meta={"global_round": int(global_round),
                      "ledgers": _ledger_sidecar()},
                round_in_epoch=r_in_epoch, tag="preempt")
        else:
            print("PREEMPT WARNING: no checkpoint manager configured — "
                  "draining WITHOUT a checkpoint; progress since the "
                  "last save is lost on restart", file=sys.stderr)
        grace = guard.grace_used_s()
        print(f"PREEMPT: drained at epoch {cur_epoch} + {r_in_epoch} "
              f"round(s) (global round {global_round})"
              + (f"; checkpoint {ck_path}" if ck_path else "")
              + (f"; grace used {grace:.1f}s of {cfg.preempt_grace:.0f}s"
                 if grace is not None else ""))
        prof.finalize(lambda: jax.block_until_ready(state.ps_weights))
        if telemetry is not None:
            telemetry.fault_event(rnd=global_round, kind="preempt",
                                  signal=guard.signal_name, grace_s=grace,
                                  checkpoint=ck_path)
            telemetry.span_event(tracer)
            telemetry.write_summary(
                aborted=True, n_rounds=rounds_run,
                total_download_mib=total_download_mb,
                total_upload_mib=total_upload_mb,
                final=telemetry.last_epoch)
            telemetry.fsync()
        return state

    pipe = None
    # arm the preemption layer LAST: the finally below owns handler
    # restoration and thread reclamation, so nothing between creation
    # and here may raise with them live
    guard.install()
    if cfg.watchdog:
        watchdog = RoundWatchdog(_on_stall, mult=cfg.watchdog_mult)
    try:
        for epoch in range(start_epoch, math.ceil(cfg.num_epochs)):
            epoch_fraction = (cfg.num_epochs - epoch
                              if epoch == math.ceil(cfg.num_epochs) - 1 else 1.0)
            ep_sums = None   # device accumulator: [loss*w, acc*w, w, down, up]
            # round input pipeline: the prefetcher owns the fractional-
            # epoch cap (reference cv_train.py:194-196) and the global
            # round numbering; with --no_pipeline it degrades to the same
            # fetch inline (bit-identical rounds, see core/pipeline.py)
            # round-granular resume: the resumed epoch rebuilds its
            # (seed, epoch) sampler and fast-forwards past the rounds
            # the preempt checkpoint already trained (skip=; fetches
            # nothing for them, numbering continues exactly)
            epoch_skip = start_round if epoch == start_epoch else 0
            r_in_epoch = epoch_skip
            pipe = RoundPipeline(
                epoch_sampler(epoch), fetch_round,
                start_round=global_round - epoch_skip,
                max_rounds=(1 if cfg.do_test
                            else int(math.ceil(spe * epoch_fraction))),
                depth=cfg.prefetch_depth, enabled=cfg.pipeline,
                skip=epoch_skip)
            for item in pipe:
                if guard.requested:
                    # graceful preemption: the just-fetched item has NOT
                    # trained — r_in_epoch counts only consumed rounds,
                    # so the resume replays exactly from here
                    state = _preempt_drain(state, epoch, r_in_epoch,
                                           pipe)
                    return state, None
                rnd, batch = item.rnd, item.batch
                global_round = item.global_round
                r_in_epoch += 1
                maybe_fault("pre_round", global_round)
                if qledger is not None:
                    # bench quarantined clients at DISPATCH time (the
                    # prefetched Round is shared state — never mutated):
                    # their slots keep static shapes, contribute no data
                    rnd = mask_blocked(rnd, qledger.blocked(global_round))
                t_loop = time.perf_counter()
                # host_s = what the loop WAITED for this round's input
                # (inline: the fetch itself; pipelined: the queue wait —
                # the prefetch overlap is exactly host_s shrinking)
                host_s = item.wait_s
                lr = schedule(global_round / spe)
                lr_arr = (jnp.asarray(lr, jnp.float32) if lr_mult is None
                          else lr * lr_mult)
                prof.maybe_start(global_round)
                if watchdog is not None:
                    # deadline the dispatch+sync (the phases a hung
                    # collective or wedged transfer actually blocks)
                    watchdog.arm(global_round)
                commits = ()
                if async_agg is not None:
                    # metrics is None for a scenario-dropped cohort (no
                    # compute happened — nothing to record or accumulate)
                    state, metrics, commits = async_agg.step(
                        state, rnd, global_round, batch, lr_arr)
                else:
                    state, metrics = runtime.round(
                        state, rnd.client_ids, batch, rnd.mask, lr_arr)
                t_dispatch = time.perf_counter()
                prof.maybe_stop(global_round,
                                lambda: jax.block_until_ready(state.ps_weights))
                every = cfg.telemetry_round_every
                record = (telemetry is not None and every
                          and global_round % every == 0
                          and metrics is not None)
                maybe_fault("mid_round", global_round)
                t_device = t_dispatch
                if record:
                    # each round record costs ONE host sync of the round's
                    # metrics — the price of round-granularity observability
                    # (see config.telemetry_every); the device-side epoch
                    # accumulation below is unchanged either way
                    with tracing.span("device_wait"):
                        jax.block_until_ready(metrics)
                    t_device = time.perf_counter()
                if watchdog is not None:
                    # only synced (record) rounds feed the deadline
                    # history — a dispatch-only duration is not a round
                    # time (see RoundWatchdog.disarm)
                    watchdog.disarm(observe=record)
                if util is not None and metrics is not None:
                    # device_s is only measured on synced (record) rounds;
                    # the tracker treats None as "not measured", not zero.
                    # Scenario-dropped cohorts are not observed at all: no
                    # device work ran, and counting them as rounds would
                    # quietly deflate the window's per-round MFU
                    util.observe_round(
                        host_s=host_s,
                        dispatch_s=t_dispatch - t_loop,
                        device_s=(t_device - t_dispatch) if record
                        else None)
                # ---- untimed tail: every phase boundary above is already
                # captured, so the host fetch + JSONL writes below (and
                # their flush latency) land in NO measured phase — they
                # are visible instead as the telemetry_emit span
                if ledger is not None and metrics is not None:
                    # sampler ids/mask are host arrays: no device fetch.
                    # In async mode the scenario may have masked slots
                    # out of the cohort — observe the EFFECTIVE
                    # participation the aggregator reports, not the
                    # sampler's intent
                    if async_agg is not None:
                        obs_ids, obs_n = metrics["participation"]
                    else:
                        obs_ids = rnd.client_ids
                        obs_n = np.asarray(rnd.mask).sum(axis=1)
                    ledger.observe(global_round, obs_ids, obs_n)
                if qledger is not None and metrics is not None \
                        and metrics.get("client_finite") is not None:
                    # quarantine strikes: ONE (W,)-bool fetch per round —
                    # the documented host-sync price of quarantine mode
                    # (the device zeroing already protected the round)
                    fin = np.asarray(metrics["client_finite"])
                    struck = qledger.observe(
                        global_round, np.asarray(rnd.client_ids), fin)
                    if ledger is not None and struck:
                        # the population ledger's quarantine-strike
                        # heavy-hitter stream: which clients keep
                        # uploading garbage, at any universe size
                        ledger.observe_strikes(struck)
                    for cid in struck:
                        if cid in qledger.ejected:
                            what = "EJECTED (strikes exhausted)"
                        else:
                            what = (f"benched {cfg.quarantine_backoff} "
                                    f"rounds (strike "
                                    f"{qledger.strikes[cid]}/"
                                    f"{qledger.max_strikes})")
                        print(f"QUARANTINE: client {cid} uploaded a "
                              f"nonfinite update at round {global_round}; "
                              f"{what}", file=sys.stderr)
                    if len(qledger.ejected) >= train_ds.num_clients:
                        # every client permanently ejected: no data
                        # source remains, and letting the loop keep
                        # dispatching fully-masked rounds would burn the
                        # whole budget on a silently "successful" run
                        print("QUARANTINE ABORT: all "
                              f"{train_ds.num_clients} clients are "
                              "permanently ejected (nonfinite strikes "
                              "exhausted) — no data remains, TERMINATING")
                        prof.finalize(lambda: jax.block_until_ready(
                            state.ps_weights))
                        if telemetry is not None:
                            telemetry.alert_event(
                                rnd=global_round,
                                rule="quarantine_exhausted",
                                severity="critical",
                                metric="defense.ejected",
                                value=float(len(qledger.ejected)),
                                action=cfg.alert_action)
                            # final residency snapshot, then the bundle:
                            # a quarantine-exhausted postmortem ships the
                            # memory timeline (memory.json) like the
                            # NaN-abort path does
                            telemetry.memory_event("quarantine_exhausted")
                            if recorder is not None:
                                recorder.record(state, {
                                    "rule": "quarantine_exhausted",
                                    "round": int(global_round),
                                    "ejected": len(qledger.ejected)})
                            telemetry.span_event(tracer)
                            telemetry.write_summary(
                                aborted=True, n_rounds=rounds_run + 1,
                                total_download_mib=total_download_mb,
                                total_upload_mib=total_upload_mb,
                                final=telemetry.last_epoch)
                            telemetry.fsync()
                        return state, None
                if record:
                    with tracing.span("telemetry_emit"):
                        res = [np.asarray(r) for r in metrics["results"]]
                        nv = np.asarray(metrics["n_valid"], np.float64)
                        tot = max(float(nv.sum()), 1.0)
                        acc_idx = 1 if len(res) > 1 else 0
                        down_total = up_total = None
                        down_clients = up_clients = None
                        if cfg.track_bytes:
                            # exact per-client byte costs: the round metrics
                            # scatter them at client_ids over (num_clients,)
                            down_all = np.asarray(metrics["download_bytes"])
                            up_all = np.asarray(metrics["upload_bytes"])
                            down_total = float(down_all.sum())
                            up_total = float(up_all.sum())
                            ids = np.asarray(rnd.client_ids)
                            down_clients = [float(x) for x in down_all[ids]]
                            up_clients = [float(x) for x in up_all[ids]]
                        moe, named = None, {}
                        if round_counters:
                            named = {name: float((r * nv).sum() / tot)
                                     for name, r in zip(round_counters,
                                                        res[2:])}
                            moe = {name: named.pop(name)
                                   for name in MOE_COUNTER_FIELDS}
                            if moe.get("dropped"):
                                raise RuntimeError(
                                    f"round {global_round}: the expert "
                                    f"dispatch dropped tokens: {moe}")
                        telemetry.round_event(
                            rnd=global_round, epoch=epoch + 1, lr=float(lr),
                            loss=float((res[0] * nv).sum() / tot),
                            acc=float((res[acc_idx] * nv).sum() / tot),
                            n_valid=float(nv.sum()), moe=moe, **named,
                            download_bytes=down_total,
                            upload_bytes=up_total,
                            host_s=host_s,
                            dispatch_s=t_dispatch - t_loop,
                            device_s=t_device - t_dispatch)
                        if metrics.get("signals"):
                            # compression-signal health, same cadence / same
                            # host sync as the round record (signals.py)
                            telemetry.signals_event(
                                rnd=global_round, mode=cfg.mode,
                                signals=signals_to_host(metrics["signals"]),
                                download_bytes=down_total,
                                upload_bytes=up_total,
                                client_download_bytes=down_clients,
                                client_upload_bytes=up_clients)
                        if metrics.get("layer_signals"):
                            # layer-wise attribution (layer_signals.py):
                            # per-group vectors, same cadence — the
                            # group_starvation monitor rule feeds off
                            # this event via the stream forwarding
                            telemetry.layer_signals_event(
                                rnd=global_round, mode=cfg.mode,
                                signal_groups=cfg.signal_groups,
                                groups=runtime.group_spec.names,
                                sizes=runtime.group_spec.sizes,
                                values=layer_signals_to_host(
                                    metrics["layer_signals"]))
                        if metrics.get("client_stats") is not None \
                                and ledger is not None:
                            # per-client population quantiles (device-
                            # reduced, telemetry/clients.py) + the
                            # participation ledger snapshot
                            # async: the scenario may have masked slots
                            # out — count the EFFECTIVE participants
                            # (slots that carried data), matching what
                            # the quantile weights and the ledger saw
                            n_part = (int((np.asarray(obs_n) > 0).sum())
                                      if async_agg is not None
                                      else len(np.asarray(rnd.client_ids)))
                            quantiles = client_stats_to_host(
                                metrics["client_stats"], rnd.client_ids)
                            # the loss-argmax heavy-hitter stream: the
                            # round's worst client id, already computed
                            # on device for the quantile record
                            ledger.observe_loss_argmax(
                                (quantiles.get("loss") or {})
                                .get("argmax_client"))
                            telemetry.client_stats_event(
                                rnd=global_round,
                                n_participants=n_part,
                                quantiles=quantiles,
                                participation=ledger.snapshot(
                                    global_round))
                        if ledger is not None:
                            # population-scale participation summary
                            # (schema v11): the ledger's full universe
                            # view — exact or sketch-estimated, its
                            # `estimated` flag says which; feeds the
                            # coverage_stall / hh_churn monitor rules
                            telemetry.population_event(
                                snapshot=ledger.population_snapshot(
                                    global_round))
                        if defense_on:
                            # schema-v5 defense record: device scalars
                            # (already synced with the metrics above) +
                            # the quarantine ledger + injected counts
                            dd = metrics.get("defense")
                            inj = None
                            if adv_plan is not None:
                                # a hostile slot only INJECTS if it
                                # carries data: inject_adversary skips
                                # zero-datum slots (benched/participation-
                                # masked clients upload nothing), so the
                                # count must too or the stream reports
                                # injection from clients that sat out
                                if async_agg is not None:
                                    ids_a, n_a = metrics["participation"]
                                    slots = metrics.get("adversary_slots")
                                    if slots is None:
                                        slots = adv_plan.slot_mask(
                                            np.asarray(ids_a))
                                    live = np.asarray(n_a) > 0
                                else:
                                    slots = adv_plan.slot_mask(
                                        np.asarray(rnd.client_ids))
                                    live = np.asarray(rnd.mask).any(axis=1)
                                inj = {cfg.adversary: int(
                                    (np.asarray(slots) & live).sum())}
                            telemetry.defense_event(
                                rnd=global_round,
                                defense=cfg.defense,
                                adversary=cfg.adversary,
                                nonfinite_action=cfg.nonfinite_action,
                                device=(signals_to_host(dd) if dd
                                        else {}),
                                quarantine=(qledger.snapshot(global_round)
                                            if qledger is not None
                                            else None),
                                injected=inj)
                        # MFU/starvation over the window since the last
                        # record, and the window's spans — the tail of
                        # this round's trace lands in the next drain
                        util.emit(global_round)
                    telemetry.span_event(tracer)
                if telemetry is not None and commits:
                    # async commit records (schema v4 async_round): the
                    # host-side staleness/discount bookkeeping is free
                    # and emitted for EVERY commit; the device-derived
                    # fields (loss, buffer_n, EF norms) cost a host sync
                    # each, so they ride only the record cadence — off
                    # it they are null, never fake zeros
                    for c in commits:
                        telemetry.async_round_event(
                            rec=c, lr=float(lr),
                            loss=(commit_loss(c) if record else None),
                            with_device=record)
                if record or (telemetry is not None and commits):
                    # ---- alert actions (telemetry/health.py): the
                    # monitor already wrote its alert events while the
                    # records above were emitted (async_round included);
                    # here the driver owns the side effects that need
                    # the live state
                    if recorder is not None:
                        req = monitor.pop_snapshot_request()
                        if req is not None:
                            recorder.record(state, req)
                    if monitor is not None and monitor.abort_requested:
                        last = monitor.alerts[-1]
                        print(f"ALERT ABORT (--alert_action abort): rule "
                              f"{last['rule']} on {last['metric']} at "
                              f"round {last['round']}, TERMINATING")
                        prof.finalize(lambda: jax.block_until_ready(
                            state.ps_weights))
                        telemetry.span_event(tracer)
                        telemetry.write_summary(
                            aborted=True, n_rounds=rounds_run + 1,
                            total_download_mib=total_download_mb,
                            total_upload_mib=total_upload_mb,
                            final=telemetry.last_epoch)
                        telemetry.fsync()
                        return state, None
                if metrics is None:
                    # scenario-dropped cohort: no compute happened, so
                    # there is nothing to count or accumulate
                    if cfg.do_test:
                        break
                    continue
                rounds_run += 1
                if telemetry is not None and rounds_run == 1:
                    # device memory after the first round: weights + server
                    # state + the round's working set are all live by now
                    telemetry.memory_event("round_1")
                # accumulate on device: no host fetch inside the round loop
                w = metrics["n_valid"]
                contrib = jnp.stack([
                    (metrics["results"][0] * w).sum(),
                    (metrics["results"][1] * w).sum(),
                    w.sum(),
                    (metrics["download_bytes"].sum()
                     if cfg.track_bytes else jnp.zeros(())),
                    (metrics["upload_bytes"].sum()
                     if cfg.track_bytes else jnp.zeros(())),
                ])
                ep_sums = contrib if ep_sums is None else ep_sums + contrib
                if cfg.do_test:
                    break

            # reclaim the prefetch thread at the epoch boundary. In the
            # normal case every round was consumed; on the early-exit
            # paths (--test) unconsumed prefetched batches are dropped —
            # a stateful host-transform RNG may have advanced for them,
            # which is fine only because nothing trains on this dataset
            # stream afterwards (see RoundPipeline.close)
            pipe.close()
            # drain the in-flight pool and commit any partial buffer:
            # epochs (and therefore checkpoints, which are written at
            # epoch granularity below) never straddle an open buffer —
            # shared with the preempt drain (_flush_async)
            state = _flush_async(state)
            if util is not None:
                # close the round window at the epoch boundary: the
                # validation sweep below must not dilute the round MFU
                util.emit(global_round)
            if telemetry is not None:
                # residency snapshot at the END of the round phase —
                # the epoch_<n> snapshot below lands after validation,
                # so its delta_peak_bytes attributes validation's
                # high-water growth while this one owns the rounds'
                telemetry.memory_event(f"rounds_{epoch + 1}")
            sums = (np.asarray(ep_sums) if ep_sums is not None
                    else np.zeros(5))
            train_time = timer()
            # NaN abort, checked at the epoch boundary (the reference checks per
            # round, cv_train.py:222-224 — per-round host fetches are what this
            # loop exists to avoid). The device-side flag reports the exact
            # offending round and gates every checkpoint write below, so
            # poisoned state is never persisted.
            nan_round = int(state.nan_round)
            if nan_round >= 0 or np.isnan(sums[0]):
                which = (f"first non-finite update at round {nan_round}"
                         if nan_round >= 0 else f"epoch loss {sums[0]} is NaN")
                print(f"TRAINING DIVERGED ({which}), TERMINATING")
                prof.finalize(lambda: jax.block_until_ready(state.ps_weights))
                if telemetry is not None:
                    # a postmortem's LAST events name what killed the
                    # run: a final critical alert, then the structured
                    # nan_abort — and the flight recorder (when armed)
                    # snapshots the state/events before the return
                    telemetry.alert_event(
                        rnd=nan_round if nan_round >= 0 else global_round,
                        rule="nonfinite_abort", severity="critical",
                        metric="loss", action=cfg.alert_action)
                    # final residency snapshot BEFORE the bundle, so the
                    # postmortem's memory.json timeline ends at the abort
                    telemetry.memory_event("nan_abort")
                    if recorder is not None:
                        recorder.record(state, {
                            "rule": "nonfinite_abort", "reason": which,
                            "round": int(nan_round)})
                    # structured divergence diagnostic: which round went
                    # non-finite, under what mode/clip/sketch config, and the
                    # last records known finite — instead of only the bare
                    # console line above
                    telemetry.nan_abort(nan_round=nan_round, reason=which,
                                        cfg=runtime.cfg)
                    telemetry.span_event(tracer)  # keep the partial trace
                    telemetry.write_summary(
                        aborted=True, n_rounds=rounds_run,
                        total_download_mib=total_download_mb,
                        total_upload_mib=total_upload_mb,
                        final=telemetry.last_epoch)
                    # never hand a truncated stream to the postmortem:
                    # everything above must survive the process dying
                    # right after this return (fsync'd)
                    telemetry.fsync()
                return state, None
            total = max(float(sums[2]), 1.0)
            train_loss = float(sums[0]) / total
            train_acc = float(sums[1]) / total
            download_mb = float(sums[3]) / (1024 * 1024)
            upload_mb = float(sums[4]) / (1024 * 1024)
            total_download_mb += download_mb
            total_upload_mb += upload_mb

            with tracing.span("validation"):
                test_loss, test_acc = run_validation(
                    runtime, state, val_ds, cfg, val_store=val_store)
            test_time = timer()

            summary = {
                "epoch": epoch + 1,
                "lr": schedule(global_round / spe),
                "train_time": train_time,
                "train_loss": train_loss,
                "train_acc": train_acc,
                "test_loss": test_loss,
                "test_acc": test_acc,
                "down (MiB)": round(download_mb),
                "up (MiB)": round(upload_mb),
                "total_time": timer.total_time,
            }
            for logger in loggers:
                logger.append(summary)
            if telemetry is not None:
                telemetry.epoch_event(summary, test_time=test_time)
                telemetry.memory_event(f"epoch_{epoch + 1}")
                telemetry.span_event(tracer)  # incl. the validation span
                # rules fired by the epoch-boundary utilization flush
                # (e.g. mfu_cliff) get their side effects here, not a
                # full record-cadence later
                if recorder is not None:
                    req = monitor.pop_snapshot_request()
                    if req is not None:
                        recorder.record(state, req)
                if monitor is not None and monitor.abort_requested:
                    last = monitor.alerts[-1]
                    print(f"ALERT ABORT (--alert_action abort): rule "
                          f"{last['rule']} on {last['metric']} at round "
                          f"{last['round']}, TERMINATING")
                    telemetry.write_summary(
                        aborted=True, n_rounds=rounds_run,
                        total_download_mib=total_download_mb,
                        total_upload_mib=total_upload_mb,
                        final=telemetry.last_epoch)
                    telemetry.fsync()
                    return state, None
            if writer is not None:
                # reference scalar set (cv_train.py:150-158)
                writer.add_scalar("Loss/train", train_loss, epoch)
                writer.add_scalar("Loss/test", test_loss, epoch)
                writer.add_scalar("Acc/train", train_acc, epoch)
                writer.add_scalar("Acc/test", test_acc, epoch)
                writer.add_scalar("Time/train", train_time, epoch)
                writer.add_scalar("Time/test", test_time, epoch)
                writer.add_scalar("Time/total", timer.total_time, epoch)
                writer.add_scalar("Lr", summary["lr"], epoch)
            epoch_ck_path = None
            if (ckpt_mgr is not None and cfg.checkpoint_every
                    and (epoch + 1) % cfg.checkpoint_every == 0):
                # epoch-cadence checkpoints carry the SAME round-
                # granular meta + host-ledger sidecar as the preempt
                # path: even an epoch-granular resume must not silently
                # un-bench/un-eject quarantined clients or reset the
                # monitor's rolling envelopes
                epoch_ck_path = ckpt_mgr.save(
                    state, epoch + 1,
                    meta={"summary": summary,
                          "global_round": int(global_round),
                          "ledgers": _ledger_sidecar()})
                if telemetry is not None:
                    # the third phase of the residency attribution:
                    # delta_peak_bytes here is the checkpoint writer's
                    # high-water contribution (host-side gathers of a
                    # sharded state can spike device residency too)
                    telemetry.memory_event(f"checkpoint_{epoch + 1}")
            if guard.requested:
                # preemption landed during validation/checkpointing:
                # drain at the epoch boundary (epoch+1 complete, 0
                # rounds into the next). A cadence checkpoint written
                # just above holds this exact state (the async pool was
                # flushed BEFORE it) — reuse it instead of re-saving
                # inside the grace window
                state = _preempt_drain(state, epoch + 1, 0, pipe,
                                       existing_ckpt=epoch_ck_path)
                return state, None
            if cfg.do_test:
                break

    except BaseException:
        # an unhandled crash (OOM, data error, Ctrl-C) inside the
        # profiler window must still close the process-global trace
        # (the rounds captured so far become a partial trace) —
        # mirrors bench_common.timed_rounds' guard
        prof.abort()
        raise
    finally:
        # reclaim the prefetch thread however the loop ends (abort
        # returns, NaN aborts, exceptions) — close() is idempotent, so
        # the epoch-boundary close above makes this a no-op normally
        if pipe is not None:
            pipe.close()
        # restore the process's previous signal handlers and reclaim
        # the watchdog thread on every exit path — no leaked handlers
        # or threads, whatever killed the loop
        guard.uninstall()
        if watchdog is not None:
            watchdog.close()
        # release the process-global span tracer however the loop ends
        # (the tail below only DRAINS the local tracer object, which
        # stays valid after uninstall)
        if tracer is not None:
            tracing.uninstall()
    # a window whose STOP lies beyond the last round (or that a --test /
    # fractional-epoch break cut short) still yields its partial trace
    prof.finalize(lambda: jax.block_until_ready(state.ps_weights))
    n_clients = train_ds.num_clients
    print(f"Total Download (MiB): {total_download_mb:0.2f}")
    print(f"Total Upload (MiB): {total_upload_mb:0.2f}")
    print(f"Avg Download Per Client: {total_download_mb / n_clients:0.2f}")
    print(f"Avg Upload Per Client: {total_upload_mb / n_clients:0.2f}")
    if telemetry is not None:
        telemetry.span_event(tracer)  # any spans since the last epoch
        telemetry.write_summary(aborted=False, n_rounds=rounds_run,
                                total_download_mib=total_download_mb,
                                total_upload_mib=total_upload_mb,
                                final=telemetry.last_epoch)
    return state, summary


def finish_run(summary, guard, on_finish, runtime, state) -> None:
    """What both drivers do once ``train`` has returned: a run that
    stopped early for any reason but a preemption is a FAILED process
    (``SystemExit`` with the reason, exit code 1) — a diverged run must
    never look like a finished one to whatever launched it; a drained
    preemption keeps its documented exit 0. Then hand the live run to
    ``on_finish(runtime, state, summary)`` if the caller gave one
    (chip_smoke.py asserts on device placement and the compiled round
    through it)."""
    if summary is None and not guard.requested:
        raise SystemExit(
            "run aborted before its schedule completed (see the "
            "TERMINATING line above): exiting non-zero")
    if on_finish is not None:
        on_finish(runtime, state, summary)


def main(argv=None, *, on_finish=None):
    cfg = parse_args(argv, default_lr=0.4)
    enable_compilation_cache(cfg)
    np.random.seed(cfg.seed)
    if cfg.do_test:
        # shrink sketch to smoke size (reference cv_train.py:329-336)
        cfg = cfg.replace(num_cols=10, num_rows=1, k=10)

    timer = Timer()
    train_ds, val_ds = build_datasets(cfg)
    cfg = cfg.replace(num_clients=train_ds.num_clients)

    num_classes = num_classes_of_dataset(
        cfg.finetuned_from if cfg.do_finetune else cfg.dataset_name)
    model = build_model(cfg, num_classes)

    sample = train_ds.gather(np.zeros((1,), np.int64))
    init_x = jnp.asarray(sample["image"])
    params = model.init(jax.random.PRNGKey(cfg.seed), init_x)

    # stateless batch-norm eval caveat (models/layers.py BatchStatNorm):
    # small eval batches compound stat noise with DEPTH — measured
    # chance-level val accuracy at depth 50 with batch 8 where batch 256
    # tracks train accuracy. Warn whenever a batch-normed model will
    # evaluate on small batches.
    bsn_scopes = set()
    for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]:
        keys = [str(getattr(p, "key", p)) for p in path]
        for i, k in enumerate(keys):
            if "BatchStatNorm" in k:
                bsn_scopes.add("/".join(keys[: i + 1]))
                break
    n_bsn = len(bsn_scopes)
    # threshold between ResNet-9's 8 norm layers (measured robust at
    # batch 8) and the 20+ of the torchvision-family depth-18+ ports
    if n_bsn > 10 and cfg.valid_batch_size < 64:
        print(f"WARNING: {cfg.model} stacks {n_bsn} batch-stat norm "
              f"layers and --valid_batch_size {cfg.valid_batch_size} < "
              "64: eval batches normalize by their OWN statistics, and "
              "small-batch stat noise compounds with depth (measured: "
              "chance-level val accuracy at depth 50 / batch 8 where "
              "batch 256 tracks train). Raise --valid_batch_size.",
              file=sys.stderr)

    frozen = None
    if cfg.do_finetune:
        params, frozen = load_finetune_params(cfg, model, params)

    loss_fn = make_cv_loss(model, cfg.compute_dtype, frozen_params=frozen)
    runtime = FedRuntime(cfg, params, loss_fn,
                         num_clients=train_ds.num_clients,
                         mesh=build_mesh(cfg))
    state = runtime.init_state()

    lr_mult = None
    if cfg.model.startswith("Fixup"):
        print("using fixup learning rates")
        lr_mult = fixup_lr_multiplier(params, runtime.initial_weights)

    ckpt_mgr, start_epoch, restored, resume_info = setup_checkpointing(
        cfg, runtime, cfg.model)
    if restored is not None:
        state = restored

    print(f"Finished initializing in {timer():.2f} seconds")
    # ONE logdir for the whole run: telemetry and the tensorboard writer
    # must share it (make_logdir timestamps at second resolution — two
    # calls can split the artifacts across sibling directories).
    # --logdir pins it: a resumed run pointed at its predecessor's
    # directory APPENDS to the stream behind a `resume` lineage record
    logdir = (cfg.logdir or make_logdir(cfg)
              if cfg.telemetry or cfg.use_tensorboard else None)
    # telemetry opens against the runtime's RESOLVED config (grad_size
    # filled in, num_cols auto-sized) so the manifest records the run
    # that actually executes
    telemetry = make_telemetry(
        runtime.cfg, "cv_train", logdir=logdir,
        resume_info=(None if resume_info is None else {
            "round": resume_info["global_round"],
            "epoch": start_epoch,
            "checkpoint": resume_info["checkpoint"]}))
    if telemetry is not None:
        telemetry.instrument(runtime)
        telemetry.memory_event("init")
    tsv = TSVLogger()
    guard = PreemptGuard(cfg.preempt_grace)
    try:
        state, summary = train(cfg, runtime, state, train_ds, val_ds,
                               lr_mult=lr_mult, loggers=(TableLogger(), tsv),
                               timer=timer, ckpt_mgr=ckpt_mgr,
                               start_epoch=start_epoch,
                               writer=make_writer(cfg, logdir=logdir),
                               telemetry=telemetry,
                               resume_info=resume_info, guard=guard)
    finally:
        if telemetry is not None:
            telemetry.close()
    print(tsv)
    finish_run(summary, guard, on_finish, runtime, state)

    if cfg.do_checkpoint and summary is not None:
        os.makedirs(cfg.checkpoint_path, exist_ok=True)
        path = os.path.join(cfg.checkpoint_path, cfg.model + ".npz")
        np.savez(path, ps_weights=np.asarray(runtime.flat_weights(state)))
        print(f"saved checkpoint to {path}")
    return summary


def load_finetune_params(cfg: FedConfig, model, params):
    """Finetune mode (reference cv_train.py:342-352, 377-384): load saved
    weights, then split the pytree into the trainable head and the frozen
    backbone, so the federated vector covers only the head."""
    path = os.path.join(cfg.finetune_path, cfg.model + ".npz")
    loaded = np.load(path)["ps_weights"]
    from commefficient_tpu.ops import make_unraveler
    _, unravel = make_unraveler(params)
    full = unravel(jnp.asarray(loaded))
    head_keys = [k for k in full["params"]
                 if k in ("head", "classifier", "fc")]
    assert head_keys, "no recognisable head to finetune"
    num_new = num_classes_of_dataset(cfg.dataset_name)
    # re-init the head at the new class count (reference
    # finetune_parameters, models/resnet9.py:105-113)
    sample_head = params["params"][head_keys[0]]
    new_head = jax.tree.map(
        lambda t: jnp.zeros(t.shape[:-1] + (num_new,), t.dtype), sample_head)
    trainable = {"params": {head_keys[0]: new_head}}
    frozen = {"params": {k: v for k, v in full["params"].items()
                         if k not in head_keys}}
    return trainable, frozen


if __name__ == "__main__":
    main()
