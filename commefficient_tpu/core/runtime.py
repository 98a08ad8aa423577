"""FedRuntime: the single-program federated round.

This is the TPU-native collapse of the reference's entire process
architecture (SURVEY.md §2.8): the parameter-server process
(fed_aggregator.py), the per-GPU worker processes (fed_worker.py), the
batch/result multiprocessing queues, the /dev/shm shared-memory tensors and
the NCCL reduce all become ONE jitted function

    round_step(state: FedState, client_ids, batch, mask, lr)
        -> (state', metrics)

in which the round's clients are a leading array axis. Per-client gradients
are computed under ``vmap`` (single device) or ``shard_map`` over the
``clients`` mesh axis with a ``psum`` aggregation (see parallel/), which is
the ICI equivalent of the reference's ``torch.distributed.reduce(sum_g, 0)``
(fed_worker.py:138, fed_aggregator.py:329).

State stays on device between rounds; the only host traffic is the incoming
batch and the outgoing scalar metrics — the reference instead bounces the
full weight vector host<->device every round (fed_worker.py:41,
fed_aggregator.py:455).
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from commefficient_tpu.config import FedConfig
from commefficient_tpu.core import client as client_lib
from commefficient_tpu.core.server import (Support, robust_aggregate,
                                           server_update,
                                           sharded_sketch_server_update,
                                           validate_defense_combo,
                                           validate_mode_combo,
                                           validate_regimes)
from commefficient_tpu.core.state import FedState
from commefficient_tpu.ops import make_unraveler, ravel_params
from commefficient_tpu.ops.sketch import make_sketch_impl
from commefficient_tpu.telemetry import tracing
from commefficient_tpu.telemetry.clients import (CLIENT_GRAD_KEYS,
                                                 summarize_per_client)
from commefficient_tpu.telemetry.profiling import phase
from commefficient_tpu.telemetry.signals import round_signals


# every FedRuntime of the process marks its spans with an ordinal of its
# own (``runtime=`` in the tracer), so the rounds of a second runtime (a
# reference check after a timed window) are told from the first's
_ORDINALS = itertools.count()


class _ClientHalf(NamedTuple):
    """What ``FedRuntime._client_half`` leaves for the round or the
    cohort; an entry is None where the configuration has no such thing."""
    agg: jax.Array                  # UN-normalized transmitted-space sum
    n_total: jax.Array              # its datum count
    results: Tuple[jax.Array, ...]  # per client: loss first
    n_valid: jax.Array
    velocity: Optional[jax.Array]   # the clients' new rows, home layout
    error: Optional[jax.Array]
    sig_dense: Optional[jax.Array]  # dense summed gradient, for the signals
    client_finite: Optional[jax.Array]
    defense_stats: Optional[Dict[str, jax.Array]]
    cur_med: Optional[jax.Array]    # this round's normclip ring entry
    download_bytes: Optional[jax.Array]
    upload_bytes: Optional[jax.Array]
    client_last_round: Optional[jax.Array]
    client_weights: Optional[jax.Array]
    client_stats: Optional[Dict[str, Any]]


class _ServerHalf(NamedTuple):
    """What ``FedRuntime._server_half`` leaves for the round or the
    commit."""
    fields: Dict[str, jax.Array]    # into ``state.replace``: ps_weights,
    #                                 Vvelocity, Verror, coord_last_update
    update: jax.Array               # as the rule gave it, before padding:
    #                                 what the signals measure
    support: Optional[Support]      # the same update as its k (indices,
    #                                 values), where the rule selected them
    applied: jax.Array              # the (d_pad,) update the weights took
    sup_mask: Optional[jax.Array]
    bad: jax.Array                  # update or aggregate non-finite


class FedRuntime:
    """Owns the jitted round/val steps and the state layout for a model.

    Parameters
    ----------
    cfg : FedConfig (grad_size is filled in here, like fed_aggregator.py:88)
    params : the model parameter pytree (initial weights)
    loss_fn_train / loss_fn_val : see core.client loss contract
    batch_size : static per-client batch (local_batch_size, or
        max_client_batch when local_batch_size == -1)
    num_clients : total simulated clients
    mesh : optional jax.sharding.Mesh; when given, the round is pjit-sharded
        per parallel.mesh.FedShardings (clients over the mesh axis, dense
        federated vectors sharded, XLA inserts the ICI collectives)
    """

    def __init__(self, cfg: FedConfig, params: Any,
                 loss_fn_train: Callable,
                 loss_fn_val: Optional[Callable] = None,
                 num_clients: Optional[int] = None,
                 mesh=None,
                 seq_spec: Optional[Dict[str, int]] = None):
        self.ordinal = next(_ORDINALS)
        with self._span("runtime_init"):
            self._build(cfg, params, loss_fn_train, loss_fn_val,
                        num_clients, mesh, seq_spec)

    def _span(self, name: str):
        """A host span marked with this runtime's ordinal."""
        return tracing.span(name, runtime=self.ordinal)

    def _build(self, cfg, params, loss_fn_train, loss_fn_val, num_clients,
               mesh, seq_spec):
        grad_size, unravel = make_unraveler(params)
        cfg = cfg.replace(grad_size=grad_size)
        # a loss that reports more than (loss, one metric) says so itself
        # (losses.make_laguna_loss: the expert layers' counters)
        n_results = getattr(loss_fn_train, "num_results", None)
        if n_results is not None:
            cfg = cfg.replace(num_results_train=int(n_results))
        if (cfg.mode == "sketch" and cfg.sketch_impl == "circ"
                and not cfg.exact_num_cols):
            # TPU-efficient sketch width (config.auto_num_cols): align to
            # the Pallas kernels and keep static rolls out of the gather
            # cliff. Replaced BEFORE the sketch is built so upload byte
            # accounting (cfg.upload_floats) reflects the real table.
            from commefficient_tpu.config import auto_num_cols
            c = auto_num_cols(cfg.num_cols)
            if c != cfg.num_cols:
                print(f"auto-sized sketch num_cols {cfg.num_cols} -> {c} "
                      "(1024-aligned for the Pallas kernels; "
                      "--exact_num_cols pins the original)")
                cfg = cfg.replace(num_cols=c)
        validate_mode_combo(cfg)
        # measured-divergence guardrails (VERDICT r5 weak #3): warn — or
        # fail under --strict_regimes — on configs round 5 measured
        # divergent; runs here (not parse time) because the collision
        # load needs the resolved grad_size/num_cols
        validate_regimes(cfg)
        self.cfg = cfg
        self.unravel = unravel
        # the caller's tree, by reference: the runtime holds nothing d-long
        # of its own beside the states it hands out
        self._init_params = params
        self.mesh = mesh
        # sequence/context parallelism: a mesh with a "seq" axis runs every
        # client's model seq-sharded (ring attention; see parallel/ring.py
        # and the seq_axis machinery in models/gpt2.py + losses.py).
        # ``seq_spec`` maps batch leaf names -> the index of their sequence
        # dimension (leaves absent from it replicate over the seq axis).
        self._seq_axis = ("seq" if (mesh is not None
                                    and "seq" in mesh.axis_names) else None)
        self._seq_shards = (mesh.shape["seq"] if self._seq_axis else 1)
        if self._seq_shards == 1:
            # a size-1 seq axis is a degenerate layout, not sequence
            # parallelism — treat it as absent (no seq_spec required, no
            # mode restrictions, no gradient rescale)
            self._seq_axis = None
        self._seq_spec = seq_spec or {}
        if self._seq_axis:
            if not self._seq_spec:
                raise ValueError(
                    "the mesh has a 'seq' axis but no seq_spec was given: "
                    "without one the batch replicates over seq and every "
                    "shard silently duplicates the full forward/backward. "
                    "Pass seq_spec (and a seq-sharded loss/model, see "
                    "gpt2_train.py), or drop the seq axis from mesh_axes.")
            # the per-shard client pipeline must be LINEAR in the gradient
            # (shards sum): modes with per-client nonlinearities are out
            if cfg.mode not in ("uncompressed", "true_topk", "sketch"):
                raise ValueError(
                    f"mode={cfg.mode} is incompatible with a seq mesh axis "
                    "(per-client nonlinear pipeline; use uncompressed/"
                    "true_topk/sketch)")
            if (cfg.do_topk_down or cfg.do_dp
                    or cfg.needs_client_velocities
                    or cfg.needs_client_errors):
                raise ValueError(
                    "topk_down / DP / local client state are not supported "
                    "with a seq mesh axis")
            if cfg.max_grad_norm is not None:
                raise ValueError(
                    "max_grad_norm is unsupported with a seq mesh axis: "
                    "clipping needs the norm of the client's SUMMED "
                    "gradient, which per-shard partial norms cannot "
                    "provide (and the sketch table clip is per-client "
                    "nonlinear)")
        # measured (not assumed) autodiff scale of the seq-axis psum
        # transpose — see the rescale site in _round_step
        self._seq_grad_scale = (self._probe_seq_grad_scale()
                                if self._seq_axis else 1.0)
        self.num_clients = (num_clients if num_clients is not None
                            else cfg.default_num_clients())
        if mesh is not None:
            # pad the client-state row count up to a mesh-divisible size
            from commefficient_tpu.parallel.mesh import FedShardings
            self.shardings = FedShardings(mesh)
            n_dev = mesh.shape[self.shardings.axis]
            self.num_clients = -(-self.num_clients // n_dev) * n_dev
            n_dense = mesh.size  # dense vectors shard over ALL mesh axes
            # pad the dense federated vector too, so the SERVER state
            # (ps_weights, dense Vvelocity/Verror, coord_last_update) always
            # shards evenly over the mesh: the dense-mode client sum arrives
            # by reduce_scatter (each device owns d_pad/n coordinates of the
            # summed gradient), the elementwise server math runs sharded,
            # and XLA all-gathers only where globality is required (the
            # top-k select, and the per-round weight broadcast every client
            # needs anyway). Without this, any d not divisible by the mesh
            # fell back to a fully-replicated (d,) all-reduce — at GPT-2
            # scale a 500 MB collective where a shard-sized one suffices
            # (ref aggregation: fed_aggregator.py:326-332, 446-458).
            self.d_pad = -(-cfg.grad_size // n_dense) * n_dense
            # Dense per-client rows (velocity/error) store COLUMN-sharded
            # — (num_clients, d_row_pad) with the row length sharded over
            # the clients axis — so the round's gather/scatter by
            # client_ids is device-local and the layout change to/from
            # per-client full rows is one all_to_all of W·d/n elements
            # (parallel/mesh.py FedShardings.for_state; replaces the W·d
            # all-reduce pair of the row-sharded layout — the reference
            # analogue is zero-traffic /dev/shm rows,
            # fed_aggregator.py:119-129). Sketch-mode table rows stay in
            # the row layout.
            self.d_row_pad = -(-cfg.grad_size // n_dev) * n_dev
            self._rows_cols = (cfg.mode not in ("sketch", "fedavg")
                               and (cfg.needs_client_velocities
                                    or cfg.needs_client_errors))
        else:
            self.shardings = None
            self.d_pad = cfg.grad_size
            self.d_row_pad = cfg.grad_size
            self._rows_cols = False
        self._axis = self.shardings.axis if self.shardings else None
        # --- robustness subsystem (adversary injection / robust
        # aggregation / nonfinite quarantine). Everything below is gated
        # at TRACE time on config flags that default off, so the round's
        # HLO stays byte-identical to the pre-defense round when unused
        # (identity-tested, same discipline as signals/client_stats).
        validate_defense_combo(cfg, mesh=mesh, seq_axis=self._seq_axis)
        self._adversary = cfg.adversary != "none"
        # update-space kinds act on per-client transmitted quantities
        # (vmap path); labelflip acts on the batch and stays
        # fused-compatible
        self._adv_inject = cfg.adversary in ("signflip", "scale",
                                             "noise", "nan")
        self._labelflip = cfg.adversary == "labelflip"
        self._quarantine = cfg.nonfinite_action == "quarantine"
        self._defense_ring = cfg.defense == "normclip"
        self.adversary_plan = None
        self._adv_universe = None
        self._flip_classes = 0
        if self._adversary:
            from commefficient_tpu.data.scenarios import make_adversary
            self.adversary_plan = make_adversary(cfg)
            # the per-client assignment over the whole universe, baked
            # into the jitted round as a tiny boolean constant — the
            # device and the host (telemetry counts, the scenario
            # engine's CohortFate.adversary) read the SAME draw
            self._adv_universe = jnp.asarray(
                self.adversary_plan.universe_mask(self.num_clients))
            if self._labelflip:
                from commefficient_tpu.config import num_classes_of_dataset
                # validate_defense_combo already rejected non-classifiable
                # datasets; resolve the flip arity here
                self._flip_classes = num_classes_of_dataset(
                    cfg.dataset_name)
        self.batch_size = (cfg.local_batch_size if cfg.local_batch_size > 0
                           else cfg.max_client_batch)
        self.cs = None
        if cfg.mode == "sketch":
            self.cs = make_sketch_impl(
                cfg.sketch_impl, cfg.grad_size, cfg.num_cols, cfg.num_rows,
                cfg.num_blocks, seed=cfg.sketch_seed, dtype=cfg.sketch_dtype,
                scan_rows=cfg.sketch_scan_rows, pallas=cfg.pallas)
            if cfg.sketch_impl == "circ":
                # which implementation this run's encode/decode take —
                # said out loud, so a kernel that gave way to the XLA
                # rolls is seen and not inferred from a slow round
                blocker = self.cs.pallas_blocker()
                print("sketch kernel path: "
                      + ("pallas" if blocker is None else f"xla ({blocker})"))
        # sketch-table wire dtype (--sketch_dtype): uploads/psum payloads
        # travel rounded to this dtype; all server math stays fp32
        self._table_dtype = (jnp.dtype(cfg.sketch_dtype)
                             if cfg.mode == "sketch" else jnp.float32)
        # Sketch linearity: sum-of-client-sketches == sketch-of-summed-grads,
        # so the O(d·r) encode can run once per round instead of once per
        # client — unless a per-client nonlinearity (table clip) intervenes.
        # (The reference necessarily encodes per worker because aggregation
        # happens across processes via NCCL, fed_worker.py:312-320.)
        # On a mesh the deferral is per-SHARD: each device sums its local
        # clients' dense gradients and encodes once, then the (r, c) tables
        # psum over ICI — encode work drops from per-client to per-device
        # and the collective stays table-sized (the TPU analogue of the
        # reference's encode-before-NCCL-reduce).
        # (the post-encode TABLE clip is per-client and kills deferral;
        # the pre-encode dense clip preserves sketch linearity — the sum
        # of clipped dense gradients encodes once)
        self._defer_encode = (cfg.mode == "sketch"
                              and (cfg.max_grad_norm is None
                                   or cfg.sketch_dense_clip))
        # With deferred encode on a single device, the server can keep
        # momentum/error as dense (d,) PRE-IMAGES instead of (r, c) tables:
        # one enc+dec round-trip of the error per round injects the sketch's
        # compression noise (that round-trip IS what the server sees through
        # the compressed channel), and the reference's error-feedback /
        # momentum-masking zeroing applies EXACTLY at the update support —
        # the true_topk rule structure with the sketch round-trip inserted.
        # See core/server.py dense_preimage branch; reduces to both the
        # table-space rule and true_topk in the lossless limit.
        # Single-device ONLY: on a mesh the pre-image trick would turn the
        # table-sized psum back into a d-sized dense psum — there the
        # per-shard encode + table-space subtractive rule applies instead.
        # Always on for the SRHT transform (its dense transform admits no
        # cell rule); opt-in for circ/hash via --sketch_server_state dense
        # (round-5 study: the table-space rules either leak accumulated
        # error [zero] or amplify decode noise [subtract] at GPT-2-scale
        # collision load — the dense pre-image is leak-free AND stable,
        # at O(d) server memory the reference's PS already spends on every
        # dense mode).
        self._dense_preimage = (self._defer_encode and mesh is None
                                and (getattr(self.cs, "dense_transform",
                                             False)
                                     or cfg.sketch_server_state == "dense"))
        if (cfg.mode == "sketch" and cfg.sketch_server_state == "dense"
                and not self._dense_preimage):
            raise ValueError(
                "--sketch_server_state dense requires a single device "
                "(no mesh) and deferred encode (no per-client table "
                "clip — use --sketch_dense_clip to clip)")
        # ---- sharded sketch SERVER tail (core/server.py
        # sharded_sketch_server_update): replace the replicated table
        # psum with a psum_scatter over table columns, run momentum+EF
        # on the column shards, decode only this device's d_pad/n
        # coordinate range, and merge an (n, k) candidate all-gather
        # into the global top-k — no device ever materializes the dense
        # (d,) estimates. Eligibility decided ONCE here (the
        # fused-encode pattern): "auto" silently falls back to the
        # replicated tail (the fallback IS the pre-sharding round —
        # numerics never change silently), "on" fails fast listing
        # every blocker.
        ss_problems = []
        if cfg.mode == "sketch":
            if mesh is None:
                ss_problems.append(
                    "no mesh: there is nothing to shard the server tail "
                    "over (the single-device round already holds the "
                    "whole table)")
            else:
                if self._dense_preimage:
                    ss_problems.append(
                        "the dense-preimage server state has no table "
                        "to reduce-scatter")
                if self._seq_axis is not None:
                    ss_problems.append(
                        "a seq mesh axis: the table's column shards "
                        "live on the clients axis only (the state's "
                        "sketch_table layout), which a seq-sharded "
                        "aggregation cannot feed without a reshard "
                        "every round")
                if (getattr(self.cs, "dense_transform", False)
                        or not hasattr(self.cs, "decode_range")):
                    ss_problems.append(
                        f"sketch_impl={cfg.sketch_impl} has a dense "
                        "transform (no cell-addressable table, no "
                        "range-restricted decode, and an estimate-"
                        "space EF rule); use circ or hash")
                n_dev = mesh.shape[self._axis]
                if cfg.num_cols % max(n_dev, 1) != 0:
                    ss_problems.append(
                        f"num_cols={cfg.num_cols} is not divisible by "
                        f"the clients mesh axis ({n_dev} devices): the "
                        "reduce-scattered column shards must tile "
                        "evenly (pick --num_cols as a multiple of the "
                        "device count; the circ auto-sizing's 1024-"
                        "aligned widths already are for meshes up to "
                        "1024 chips)")
        self._sharded_server = (cfg.mode == "sketch"
                                and cfg.sketch_sharded_server != "off"
                                and not ss_problems)
        if cfg.sketch_sharded_server == "on" and not self._sharded_server:
            raise ValueError(
                "--sketch_sharded_server on: the sharded server tail is "
                "unavailable for this configuration (use auto to fall "
                "back to the replicated tail instead):\n  "
                + "\n  ".join(ss_problems))
        # The REPLICATED server tail on a mesh decodes under GSPMD, and
        # a Mosaic kernel cannot be partitioned automatically (the TPU
        # lowering raises "Mosaic kernels cannot be automatically
        # partitioned. Please wrap the call in a shard_map." — met on
        # four v5e chips, PR 21). That tail's decode takes the XLA rolls.
        # What runs inside shard_map keeps its kernels: the client
        # block's encode, and the sharded tail's range decode (below).
        self._server_tail_xla = (
            cfg.mode == "sketch" and cfg.sketch_impl == "circ"
            and mesh is not None and not self._sharded_server
            and self.cs.kernel_path == "pallas")
        if self._server_tail_xla:
            if cfg.pallas == "on":
                raise ValueError(
                    "--pallas on: the replicated server tail on a mesh "
                    "cannot host the Pallas decode (Mosaic kernels are not "
                    "partitioned automatically); keep the sharded tail "
                    "(--sketch_sharded_server auto) or pass --pallas auto "
                    "to decode with the XLA rolls there")
            print("sketch kernel path, server tail: xla (replicated tail "
                  "on a mesh runs under GSPMD, which cannot partition a "
                  "Mosaic call)")
        # The SHARDED tail runs inside shard_map, so its range decode
        # (CirculantSketch.decode_range) takes the Pallas decode kernel
        # over the blocks that cover the chip's d_pad/n coordinates
        # wherever the kernels serve the sketch, and the gather form
        # elsewhere. Every round or none: this line is its hit share.
        self._server_tail_pallas = False
        if self._sharded_server and cfg.sketch_impl == "circ":
            blocker = self.cs.pallas_blocker()
            self._server_tail_pallas = blocker is None
            if blocker is None:
                from commefficient_tpu.ops.circulant_pallas import (
                    range_cover_blocks)
                nb = range_cover_blocks(
                    self.cs.c, self.d_pad // mesh.shape[self._axis])
                print("sketch kernel path, server tail: pallas (range "
                      f"decode, {nb} of {self.cs.m} blocks a chip)")
            else:
                print("sketch kernel path, server tail: xla (gather "
                      f"form: {blocker})")
        # ---- int8 quantized wire (--wire_dtype int8; ops/wire.py):
        # clients quantize their table contribution with per-column-
        # block abs-max scales + stochastic rounding (draws keyed off
        # (seed, global_round, device/slot, cell) — deterministic,
        # replay/resume-safe), the mesh table reduce becomes an
        # all_to_all of int8 column shards + f32 scales with
        # shard-local dequantize-accumulate in f32 (int8 summation over
        # W clients would overflow; f32 local accumulation keeps the
        # server momentum/EF numerics untouched), and the rounding
        # residual lands in the aggregate where the server error
        # feedback absorbs it. int8 is an EXPLICIT request, so every
        # blocker is a hard error (no silent auto-fallback — a
        # compression study must never silently measure the f32 wire);
        # config.__post_init__ already rejected the topology-free
        # blockers (non-sketch mode, rht, dense server state).
        self._int8_wire = False
        self._wire_block = 0
        if cfg.mode == "sketch" and cfg.wire_dtype == "int8":
            problems = []
            if self._dense_preimage:
                problems.append(
                    "the dense-preimage server state consumes the dense "
                    "aggregated gradient — no table crosses the wire")
            if mesh is not None and not self._sharded_server:
                problems.append(
                    "a mesh without the sharded server tail: the "
                    "quantized reduce is an all_to_all of int8 COLUMN "
                    "SHARDS, which only the reduce-scattered tail "
                    "consumes (sharded-server blockers:\n    "
                    + "\n    ".join(ss_problems or ["(disabled by flag)"])
                    + ")")
            n_dev = mesh.shape[self._axis] if mesh is not None else 1
            shard_c = cfg.num_cols // max(n_dev, 1)
            blk = min(cfg.wire_block, shard_c)
            if shard_c == 0 or shard_c % max(blk, 1):
                problems.append(
                    f"--wire_block {cfg.wire_block} does not tile the "
                    f"per-device column shard ({shard_c} cols on "
                    f"{n_dev} devices): pick a --wire_block dividing "
                    "num_cols / n_devices")
            if problems:
                raise ValueError(
                    "--wire_dtype int8 is unavailable for this "
                    "configuration:\n  " + "\n  ".join(problems))
            self._int8_wire = True
            self._wire_block = blk
        # exact per-client simulated upload bytes under the wire dtype
        # (4 * upload_floats for the f32 wire — the pre-wire constant,
        # so the f32 round's HLO stays byte-identical)
        self._upload_bytes = cfg.upload_wire_bytes(self._wire_block
                                                   or None)
        # compression-signal health diagnostics (telemetry/signals.py):
        # cheap on-device reductions appended to the round's metrics.
        # Gated on telemetry too: with --no_telemetry nothing ever reads
        # them, and in sketch mode on a mesh the l2estimate diagnostics
        # cost two table-sized all-gathers per round — never pay a hot-
        # path collective for a stream nobody consumes. Async buffered
        # aggregation (core/async_agg.py) splits the round around the
        # signal computation sites (the signals compare the round's agg
        # against the SAME round's server update, which async decouples),
        # so signals are off there — loudly, not silently: the
        # async_round event's EF norms are the async health channel.
        self._signals = (cfg.signals and cfg.telemetry
                         and not cfg.async_agg)
        if cfg.signals and cfg.telemetry and cfg.async_agg:
            import sys
            print("NOTE: --async_agg disables the per-round `signals` "
                  "diagnostics (they compare a round's aggregate against "
                  "the same round's server update, which buffered "
                  "aggregation decouples); commit-granularity EF norms "
                  "are emitted on the `async_round` events instead. Pass "
                  "--no_signals to silence this note.", file=sys.stderr)
        # the dense pre-encode aggregate exists only where the deferred
        # encode runs once on one device — capture it there so sketch
        # mode gets grad_true_norm (the collision-noise reference); on a
        # mesh each shard encodes its own partial sum and the global
        # dense aggregate never materializes (by design — restoring it
        # would cost the d-sized collective the encode deferral removes)
        self._signals_dense_cap = (self._signals and cfg.mode == "sketch"
                                   and self._defer_encode
                                   and not self._dense_preimage
                                   and mesh is None)
        # --signals_exact on TABLE-state sketch additionally threads a
        # dense shadow EF accumulator pair through FedState (see
        # signals.py round_signals) — same availability condition
        self._signals_shadow = (self._signals_dense_cap
                                and cfg.signals_exact)
        # per-client population stats (telemetry/clients.py): quantile
        # summaries of per-client loss / grad norms / clip saturation /
        # contribution norm / bytes, reduced on device along the client
        # axis. Gated exactly like signals — with --no_telemetry (or
        # --no_client_stats) nothing ever reads them, so the per-client
        # reductions are compiled out of the round entirely.
        self._client_stats = cfg.client_stats and cfg.telemetry
        # defense-event scalars (clip fraction/mass, trim fraction,
        # nonfinite count): tiny extra reductions, but still only
        # computed when a telemetry stream exists to read them — the
        # defense ARITHMETIC itself (clip/trim/zeroing) is never gated
        # on telemetry, only its observability is
        self._defense_stats = cfg.telemetry and (
            cfg.defense != "none" or self._adversary or self._quarantine)

        loss_fn_val = loss_fn_val if loss_fn_val is not None else loss_fn_train
        # Fused client gradients: when nothing nonlinear happens per client
        # (no local momentum/error rows, no per-client clip/table-op/DP
        # noise, no per-client weights, no seq sharding), the round's
        # aggregate sum_c n_c*g_c is linear in the microbatch gradients and
        # can be computed by ONE scan into ONE (d,) buffer instead of
        # vmap's per-client (W, d) gradient — see make_fused_grad. Exact
        # (up to summation order); --no_fused_clients forces the vmap path.
        n_iters, mb = client_lib._num_microbatches(cfg, self.batch_size)
        self._fused = (
            cfg.fused_clients
            and cfg.mode in ("sketch", "true_topk", "uncompressed")
            and cfg.local_momentum == 0 and cfg.error_type != "local"
            and not cfg.do_dp and cfg.max_grad_norm is None
            and not cfg.do_topk_down
            and self._seq_axis is None
            # update-space injection, robust aggregation and per-client
            # nonfinite flags all need the per-client transmitted
            # quantities the fused accumulator sums away; labelflip is
            # data-space and stays fused-eligible
            and not self._adv_inject and cfg.defense == "none"
            and not self._quarantine
            and n_iters * mb == self.batch_size)
        self._fused_fn = None
        # per-client GRADIENT stats only exist where a per-client
        # gradient does (the vmap path and fedavg). The fused path sums
        # every client's microbatches into ONE (d,) buffer by design —
        # disabling it to observe would cost the measured ~15% hot-path
        # win, so its grad-stat quantiles come out NaN instead while the
        # loss/bytes population stats stay live (see _round_step tail).
        # Seq-sharded rounds are excluded for CORRECTNESS, not cost:
        # inside the shard_map each shard holds only its PARTIAL
        # gradient, whose norm is not the client's norm (partials are
        # not orthogonal — the same reason max_grad_norm is forbidden
        # with a seq axis), so a per-shard norm replicated out as the
        # client stat would be fabricated data.
        self._client_grad_stats = (self._client_stats and not self._fused
                                   and self._seq_axis is None)
        # ---- fused sketch encode (ROADMAP item 1; core/client.py
        # make_forward_grad / make_fused_grad): the microbatch scan
        # carries the (r, c) sketch TABLE instead of the dense (d,)
        # gradient sum, so the dense gradient never materializes in HBM
        # (telemetry/memory_ledger.py SKETCH_ENCODE_FUSED is the
        # committed acceptance gate). Eligibility is decided ONCE here
        # from config + topology: "auto" silently falls back to the
        # unfused round (the fallback IS the pre-fusion path — numerics
        # never change silently), "on" fails fast listing every blocker.
        fe_problems = client_lib.fused_encode_blockers(
            cfg, signals=self._signals)
        if cfg.mode == "sketch":
            if self._dense_preimage:
                fe_problems.append(
                    "the dense-preimage server state (sketch_impl=rht / "
                    "--sketch_server_state dense) consumes the dense "
                    "aggregated gradient — there is no table to "
                    "accumulate into")
            elif (getattr(self.cs, "dense_transform", False)
                    or not hasattr(self.cs, "encode_accum")):
                fe_problems.append(
                    f"sketch_impl={cfg.sketch_impl} has a dense transform "
                    "(no streaming range encode); use circ or hash")
            if cfg.defense != "none" and self._defer_encode:
                fe_problems.append(
                    f"--defense {cfg.defense} measures per-client norms on "
                    "the dense deferred-encode uploads; fusing would move "
                    "the defense to table-Frobenius space and silently "
                    "change its clipping/trimming numerics")
            if self._client_grad_stats:
                fe_problems.append(
                    "per-client grad-norm stats (telemetry/clients.py) "
                    "measure dense gradient norms on the vmap path; pass "
                    "--no_client_stats (or --no_telemetry)")
        self._fused_encode = (cfg.mode == "sketch"
                              and cfg.sketch_fused_encode != "off"
                              and not fe_problems)
        if cfg.sketch_fused_encode == "on" and not self._fused_encode:
            raise ValueError(
                "--sketch_fused_encode on: the fused sketch encode is "
                "unsound for this configuration (use auto to fall back "
                "to the unfused round instead):\n  "
                + "\n  ".join(fe_problems))
        if self._fused_encode and self._signals_dense_cap:
            import sys
            print("NOTE: the fused sketch encode removes the dense "
                  "aggregated gradient the sketch-mode signals capture "
                  "(grad_true_norm and the collision-noise reference go "
                  "null). Pass --sketch_fused_encode off to keep them at "
                  "the cost of the dense (d,) materialization.",
                  file=sys.stderr)
            self._signals_dense_cap = False
        # ---- layer-wise compression attribution (telemetry/
        # layer_signals.py): named parameter groups over the ravel-order
        # coordinate line, reduced per group inside the jitted round from
        # the group spec's static ranges (ops/segments.py: the update's k
        # winners by index where the server rule names them, a dense
        # operand in one pass of 1,024-wide blocks). Gated exactly like
        # the scalar signals — off, the group machinery is compiled out
        # entirely (HLO identity-tested); on, it adds NO argument and no
        # d-long integer to the round (round_step's d-long arguments are
        # ps_weights and coord_last_update); on a mesh each device
        # reduces its own coordinate shard of a dense operand and ONE
        # small psum recombines — never a per-group collective unroll
        # (dryrun-ledger-gated).
        self._layer_signals = (self._signals
                               and cfg.signal_groups != "off")
        # the per-group DENSE gradient mass needs a dense aggregated
        # gradient in the round: dense modes have it as the transmitted
        # quantity itself; sketch only via the dense-preimage state or
        # the single-device deferred-encode capture. Fused-encode and
        # mesh sketch rounds emit it null — never fake zero — because
        # restoring the dense gradient would cost exactly the (d,)
        # buffer / collective those paths exist to remove (the PR-4
        # client-stats NaN contract, applied to groups).
        self._layer_grad_mass = (self._layer_signals
                                 and (cfg.mode != "sketch"
                                      or self._dense_preimage
                                      or self._signals_dense_cap))
        self.group_spec = None
        if self._layer_signals:
            from commefficient_tpu.telemetry.layer_signals import \
                make_group_spec
            self.group_spec = make_group_spec(params, cfg.signal_groups)
            assert self.group_spec.d == cfg.grad_size, (
                self.group_spec.d, cfg.grad_size)
        if cfg.mode == "fedavg":
            self._client_fn = client_lib.make_fedavg_client(
                cfg, loss_fn_train, unravel, self.batch_size,
                with_stats=self._client_grad_stats)
        elif self._fused:
            self._fused_fn = client_lib.make_fused_grad(
                cfg, loss_fn_train, unravel, self.batch_size,
                fused_encode=self._fused_encode)
            self._client_fn = None
        else:
            self._client_fn = client_lib.make_client_step(
                cfg, loss_fn_train, unravel, self.batch_size,
                defer_encode=self._defer_encode,
                with_stats=self._client_grad_stats,
                fused_encode=self._fused_encode)
        self._val_fn_inner = client_lib.make_val_step(cfg, loss_fn_val, unravel)

        sh = self.shardings
        state_sh = cs_sh = batch_sh = clients_sh = None
        if sh is not None:
            state_sh = sh.for_state(cfg, self._state_template())
            cs_sh = jax.tree.map(lambda _: sh.replicated, self.cs)
            batch_sh = self.batch_sharding()
            clients_sh = sh.round_axis
        self._state_sharding = state_sh

        def jit_step(fn, in_shardings, out_shardings):
            """A step that donates its state; the shardings are pinned
            on a mesh and left to JAX without one."""
            if sh is None:
                return jax.jit(fn, donate_argnums=(0,))
            return jax.jit(fn, donate_argnums=(0,),
                           in_shardings=in_shardings,
                           out_shardings=out_shardings)

        self._round = jit_step(
            self._round_step,
            (state_sh, clients_sh, batch_sh, clients_sh, None, cs_sh),
            (state_sh, None))
        if self.mesh is not None:
            # mesh-parallel validation: val items are independent, so the
            # batch shards over EVERY mesh axis (flattened) and each device
            # evaluates its slice; per-shard means recombine as
            # datum-weighted sums under two scalar psums. The reference
            # instead runs val through the worker queues with no reduce
            # (fed_aggregator.py:337-364) — here an n-device mesh evaluates
            # n× faster instead of idling n-1 devices.
            self._val = jax.jit(self._val_step_sharded)
        else:
            self._val = jax.jit(self._val_step)

        # async buffered aggregation (core/async_agg.py): the round's two
        # halves as programs of their own, a cohort step (dispatch time)
        # and a commit step (buffer-goal time), plus a trivial merge.
        # Built only under --async_agg — the synchronous path compiles
        # nothing new.
        self._cohort = self._commit_jit = self._merge_jit = None
        if cfg.async_agg:
            from commefficient_tpu.core.async_agg import validate_async_combo
            validate_async_combo(cfg)
            self._cohort = jit_step(
                self._cohort_step,
                (state_sh, clients_sh, batch_sh, clients_sh, None, cs_sh),
                (state_sh, None))
            self._commit_jit = jit_step(
                self._commit_step, (state_sh, None, cs_sh), (state_sh, None))
            self._merge_jit = jit_step(
                self._merge_step, (state_sh, None, None, None), state_sh)

    def set_compile_watcher(self, watcher) -> None:
        """Compile observability hook (telemetry.JitWatcher): wraps the
        jitted round/val steps so every lowering+compile — including
        recompiles from shape changes or donation misses — is timed,
        cost-analyzed and logged instead of stalling silently. Call
        before the first round. A repeat call is a no-op: the wrapper
        needs the raw jitted functions' AOT surface, so double-wrapping
        would silently break the observation it exists to provide."""
        if getattr(self, "compile_watcher", None) is not None:
            return
        self.compile_watcher = watcher
        self._round = watcher.wrap("round_step", self._round)
        self._val = watcher.wrap("val_step", self._val)
        if self._cohort is not None:
            self._cohort = watcher.wrap("cohort_step", self._cohort)
        if self._commit_jit is not None:
            self._commit_jit = watcher.wrap("commit_step", self._commit_jit)

    def _probe_seq_grad_scale(self) -> float:
        """Measure how the round's cross-seq-shard gradient sum over-counts
        a replicated parameter's gradient on THIS mesh under THIS jax.

        Mirrors the round's exact structure: jax.grad INSIDE a
        shard_map(check_vma=False) block of a loss whose differentiable
        path crosses exactly one seq-axis psum (the seq-sharded token
        mean), followed by the seq-axis psum the aggregation applies.
        True d(loss)/d(w) of the probe function is 1, so the returned
        value IS the over-count factor (seq_shards under jax 0.9's
        psum->psum transpose with vma checking off; 1 if a future jax
        emits per-shard partial gradients). De-fangs the jax-version
        landmine flagged in VERDICT r4 weak #5 — the constant is probed,
        not assumed."""
        ax = self._seq_axis
        n = self._seq_shards

        def blk(w):
            g = jax.grad(lambda w: lax.psum(w, ax) / n)(w)
            return lax.psum(g, ax)

        out = shard_map(blk, mesh=self.mesh, in_specs=P(),
                        out_specs=P(), check_vma=False)(
                            jnp.asarray(1.0, jnp.float32))
        scale = float(out)
        assert scale > 0, scale
        return scale

    def _batch_pspec(self, seq_dim: Optional[int]) -> P:
        """PartitionSpec for one batch leaf: clients on dim 0, and (when
        seq-sharded) the seq axis at ``seq_dim``."""
        ax = self.shardings.axis
        if self._seq_axis is None or seq_dim is None:
            return P(ax)
        return P(*([ax] + [None] * (seq_dim - 1) + [self._seq_axis]))

    def batch_sharding(self):
        """Per-leaf NamedShardings for the batch jit argument — the layout
        any batch producer (e.g. a DeviceStore) must emit on a mesh.
        Without a seq axis every leaf shards on its leading (client) dim;
        with one, ``seq_spec`` must name every batch leaf (value = its
        sequence dim index, or None to replicate over seq)."""
        if self._seq_axis is None or not self._seq_spec:
            return self.shardings.round_axis
        return {k: NamedSharding(self.mesh, self._batch_pspec(sd))
                for k, sd in self._seq_spec.items()}

    # ------------------------------------------------------------------ state

    @property
    def initial_weights(self) -> jax.Array:
        """The flat fp32 vector of the tree the runtime was built from,
        ravelled anew on every read; nothing keeps it."""
        return ravel_params(self._init_params)[0]

    def _state_template(self):
        """Structure-only FedState (no allocation) for sharding layout."""
        return jax.eval_shape(
            self._make_state, jax.random.PRNGKey(0),
            jax.ShapeDtypeStruct((self.d_pad,), jnp.float32))

    def init_state(self, seed: Optional[int] = None) -> FedState:
        with self._span("init_state"):
            return self._init_state(seed)

    def _init_state(self, seed: Optional[int]) -> FedState:
        # the key is made here from the Python integer: as a jit argument
        # a seed past 2**31 overflows the int32 it would be parsed into
        rng = jax.random.PRNGKey(self.cfg.seed if seed is None else seed)
        # first, while nothing else of the state exists: the tree, the
        # ravel's temporaries and this vector are the set-up's high-water
        # mark, and the temporaries are gone before the next leaf is made
        weights = ravel_params(self._init_params, pad_to=self.d_pad)[0]
        if self._state_sharding is not None:
            # create the state directly in its sharded layout — no single
            # device ever holds the full per-client arrays. The weights are
            # a jit ARGUMENT: as a closure constant they would be serialized
            # into the HLO shipped to the compiler (0.5 GB at GPT-2 scale)
            return jax.jit(self._make_state,
                           out_shardings=self._state_sharding)(rng, weights)
        return self._make_state(rng, weights)

    def _make_state(self, rng, weights) -> FedState:
        """``weights``: the (d_pad,) vector made for this state alone."""
        cfg = self.cfg
        # Server-side transmitted-space state lives at the mesh-padded
        # length so it shards evenly (see __init__). Per-client dense rows
        # are at true d single-device; on a mesh they live at d_row_pad in
        # the COLUMN-sharded home layout (see __init__ / parallel.mesh).
        # Sketch-table shapes are unaffected. Dense pre-image states for
        # the single-device SRHT path (see __init__) are dense too.
        dense = self._dense_preimage or cfg.mode != "sketch"
        server_tx = (self.d_pad,) if dense else cfg.transmitted_shape
        # dense client rows live at d_row_pad on a mesh (column-sharded
        # home layout, see __init__) and at true d single-device
        client_tx = ((self.d_row_pad,) if self._rows_cols
                     else (cfg.grad_size,) if dense
                     else cfg.transmitted_shape)
        d = cfg.grad_size
        n = self.num_clients
        zeros_tx = jnp.zeros(server_tx, jnp.float32)

        def maybe(shape, cond):
            return jnp.zeros(shape, jnp.float32) if cond else None

        return FedState(
            # no copy: every init_state() ravels a vector of its own, so
            # the round step may donate it with the rest of the state
            ps_weights=weights,
            Vvelocity=zeros_tx,
            Verror=jnp.zeros_like(zeros_tx),
            step=jnp.zeros((), jnp.int32),
            rng=rng,
            client_velocities=maybe((n,) + client_tx,
                                    cfg.needs_client_velocities),
            client_errors=maybe((n,) + client_tx, cfg.needs_client_errors),
            # every client starts with the initial PS weights
            # (reference fed_aggregator.py:105-111)
            client_weights=(jnp.broadcast_to(weights[:d], (n, d))
                            if cfg.do_topk_down else None),
            coord_last_update=(jnp.full((self.d_pad,), -1, jnp.int32)
                               if cfg.track_bytes else None),
            client_last_round=(jnp.zeros((n,), jnp.int32)
                               if cfg.track_bytes else None),
            nan_round=jnp.full((), -1, jnp.int32),
            sig_Vvelocity=maybe((d,), self._signals_shadow),
            sig_Verror=maybe((d,), self._signals_shadow),
            # async buffered aggregation (core/async_agg.py): the merge
            # buffer lives in FedState so it shards/checkpoints exactly
            # like the server EF state it feeds
            async_buffer=maybe(server_tx, cfg.async_agg),
            async_buffer_n=maybe((), cfg.async_agg),
            # normclip rolling reference: NaN = "round not yet seen"
            # (nanmedian ignores it) — a zero-init would anchor the
            # threshold at zero and clip everything on round 2
            defense_ref=(jnp.full((cfg.defense_window,), jnp.nan,
                                  jnp.float32)
                         if self._defense_ring else None),
        )

    # ------------------------------------------------- robustness tail

    def _transmit_tail(self, tx, out, adv, ref, client_rngs, step=None):
        """The per-client transmitted-space tail of the client block:
        adversarial injection -> nonfinite quarantine -> wire rounding ->
        robust (or plain-sum) aggregation. ``tx`` is None on the fused
        path (the aggregate is already accumulated; the robustness flags
        that need per-client uploads force the vmap path) — then agg
        comes back None and the caller keeps its own. Everything is
        compiled out at the flag defaults. Returns ``(agg_or_None,
        results, n_valid, stats, client_finite, defense_stats,
        cur_med)``."""
        cfg = self.cfg
        results, n_valid, stats = out.results, out.n_valid, out.stats
        client_finite = cur_med = defense_stats = agg = None
        if tx is not None:
            if self._adv_inject:
                tx = client_lib.inject_adversary(cfg, tx, adv,
                                                 client_rngs,
                                                 n_valid=n_valid)
                if stats is not None:
                    # the population stats must describe what each
                    # client actually UPLOADED: recomputing tx_norm on
                    # the post-injection transmit is what lets the
                    # update_norm_outlier monitor rule see a boosted
                    # client at all (the client step measured the
                    # honest pre-injection value)
                    flat = tx.reshape(tx.shape[0], -1)
                    stats = {**stats, "tx_norm": jnp.sqrt(
                        (flat * flat).sum(axis=1)).astype(jnp.float32)}
            if self._quarantine:
                tx, n_valid, results, client_finite = \
                    client_lib.quarantine_zero(tx, n_valid, results)
            td = self._table_dtype
            wire = (td != jnp.float32 and not self._dense_preimage
                    and cfg.mode == "sketch")
            if wire and not self._defer_encode and tx.ndim == 3:
                tx = tx.astype(td).astype(jnp.float32)
            elif (self._int8_wire and not self._defer_encode
                  and tx.ndim == 3):
                # per-client int8 uploads (the non-deferred path keeps
                # per-client tables — table clip): each slot quantizes
                # with its GLOBAL slot index as salt so draws stay
                # independent across mesh shards, and the server sums
                # the dequantized f32 reconstructions
                tx = client_lib.int8_wire_uploads(
                    cfg, tx, step, self._wire_block,
                    slot0=(lax.axis_index(self._axis) * tx.shape[0]
                           if self._axis is not None else 0))
            if cfg.defense != "none":
                agg, cur_med, defense_stats = robust_aggregate(
                    cfg, tx, n_valid, ref_thresh=ref,
                    axis_name=self._axis)
            else:
                agg = tx.sum(axis=0)
        return agg, results, n_valid, stats, client_finite, \
            defense_stats, cur_med

    def _defense_scalars(self, defense_stats, client_finite):
        """The ``metrics['defense']`` dict (schema-v5 scalars; NaN = not
        applicable for the configured defense/action, serialized null),
        or None when the robustness observability is off."""
        if not self._defense_stats:
            return None
        nan = jnp.full((), jnp.nan, jnp.float32)
        d = (dict(defense_stats) if defense_stats is not None
             else {"clip_frac": nan, "clip_thresh": nan,
                   "clipped_mass": nan, "trim_frac": nan})
        d["nonfinite_clients"] = (
            (~client_finite).sum().astype(jnp.float32)
            if client_finite is not None else nan)
        return d

    @staticmethod
    def _download_coord_counts(coord_last_update: jax.Array,
                               thresholds: jax.Array) -> jax.Array:
        """Per-client count of coordinates updated at-or-after the
        client's last download (the download-byte accounting): counts[w]
        = |{i : coord_last_update[i] >= thresholds[w]}|, (W,) int32.

        One scalar reduction a client over the vector as it lies. The W
        reductions share their operand, so XLA fuses them as siblings of
        ONE read at the rate of the memory (a flat int32 vector is tiled
        1,024 wide on the TPU as it is: no view, no pad, no tail), and
        nothing (W, d) exists. The broadcast-compare-reduce over a minor
        axis asked for a (W, d) s32 on the CPU, and the scan over
        65,536-wide blocks that avoided it until PR 37 paid a pad and a
        relayout copy of the vector and a copy of every block for it
        (36.9 ms a round in Laguna where this read is 2.1; PERF.md
        section 6). On a mesh GSPMD reduces each chip's coordinate shard
        where it lies and one small all-reduce adds the W counts
        (tests/test_tpu_compile.py pins both compiles)."""
        return jnp.stack([
            (coord_last_update >= thresholds[w]).sum(dtype=jnp.int32)
            for w in range(thresholds.shape[0])])

    def _download_ledger(self, state: FedState, client_ids: jax.Array):
        """The dispatch-time half of the byte ledger (``track_bytes``):
        what each of the round's clients downloads (coordinates changed
        since its last round) and uploads, per slot and scattered over
        the client universe, and the clients' new last-round marks. Returns
        ``(download_bytes, upload_bytes, down_slot, up_slot,
        client_last_round)``."""
        with phase("fed_byte_ledger"):
            thresholds = state.client_last_round[client_ids]
            whole = lambda x: x
            if self.shardings is not None:
                # the W thresholds and the W counts whole on every chip
                # (each compares its coordinate shard with all of them):
                # read off or stacked into a vector sharded by client,
                # every scalar is a collective-permute of its own
                whole = functools.partial(
                    lax.with_sharding_constraint,
                    shardings=self.shardings.replicated)
            counts = whole(self._download_coord_counts(
                state.coord_last_update, whole(thresholds)))
            # per-SLOT byte vectors kept alive for the client_stats
            # quantiles (telemetry/clients.py) — the scatters below are
            # the same data keyed by client id over the whole universe
            down_slot = 4.0 * counts.astype(jnp.float32)
            # exact wire-dtype payload (cfg.upload_wire_bytes): the f32
            # wire keeps the pre-wire 4*upload_floats constant
            up_slot = jnp.full(client_ids.shape, self._upload_bytes,
                               jnp.float32)
            download_bytes = jnp.zeros(self.num_clients, jnp.float32).at[
                client_ids].set(down_slot)
            upload_bytes = jnp.zeros(self.num_clients, jnp.float32).at[
                client_ids].set(up_slot)
            client_last_round = state.client_last_round.at[client_ids].set(
                state.step)
        return download_bytes, upload_bytes, down_slot, up_slot, \
            client_last_round

    # ------------------------------------------------------- server dispatch

    def _apply_server_update(self, state: FedState, agg: jax.Array,
                             lr: jax.Array, server_rng: jax.Array, cs=None):
        """Mode + topology dispatch of the server update rule
        (``_server_half`` calls it). Returns ``(update, Vvel, Verr,
        sup_mask, support)``; the sharded tail's update is a mesh-padded
        (d_pad,) sharded vector, the replicated sketch decode's a
        true-d one (the caller's padding block handles both)."""
        cfg = self.cfg
        server_lr = jnp.asarray(1.0) if cfg.mode == "fedavg" else lr
        if (cfg.mode == "sketch" and not self._dense_preimage
                and server_lr.ndim == 1 and not self._sharded_server):
            # the replicated sketch branch multiplies lr against the
            # TRUE-d decoded update (its state is the table, not a
            # padded dense vector); the sharded tail multiplies
            # per-shard against d_pad-length update shards, so there
            # the vector stays mesh-padded (padding coords get
            # multiplier 1 against an identically-zero update)
            server_lr = server_lr[: cfg.grad_size]
        if self._sharded_server:
            update, Vvel, Verr, support = self._sharded_server_apply(
                agg, state.Vvelocity, state.Verror, server_lr, cs)
            return update, Vvel, Verr, None, support
        if self._server_tail_xla:
            cs = dataclasses.replace(cs, pallas="off")
        return server_update(cfg, agg, state.Vvelocity, state.Verror,
                             server_lr, cs=cs, dp_rng=server_rng,
                             dense_preimage=self._dense_preimage)

    def _sharded_server_apply(self, agg: jax.Array, Vvel_prev: jax.Array,
                              Verr_prev: jax.Array, server_lr: jax.Array,
                              cs=None):
        """shard_map wrapper of core/server.sharded_sketch_server_update:
        the reduce-scattered aggregate and the column-sharded
        momentum/EF tables enter in the state's sketch_table layout
        (P(None, clients) — no reshard), the update leaves as the
        dense-vector layout's (d_pad,) coordinate shards (P(clients) —
        matching ps_weights, so the weight apply runs sharded with no
        further collective); the k winners every device holds after
        the merge leave replicated (``support``, None under a
        per-parameter lr)."""
        ax = self._axis
        tab = P(None, ax)
        n_dev = self.mesh.shape[ax]
        lr_vec = server_lr.ndim == 1

        def blk(agg, vvel, verr, lr, cs):
            return sharded_sketch_server_update(
                self.cfg, agg, vvel, verr, lr, cs, axis=ax,
                n_shards=n_dev, d_pad=self.d_pad)

        fn = shard_map(blk, mesh=self.mesh,
                       in_specs=(tab, tab, tab,
                                 P(ax) if lr_vec else P(),
                                 jax.tree.map(lambda _: P(), cs)),
                       out_specs=(P(ax), tab, tab,
                                  None if lr_vec else (P(), P())),
                       check_vma=False)
        return fn(agg, Vvel_prev, Verr_prev, server_lr, cs)

    def _int8_reduce_scatter(self, agg: jax.Array,
                             step: jax.Array) -> jax.Array:
        """The quantized table reduce (called INSIDE the round's
        shard_map): per-device int8 quantization of the local partial
        table, an all_to_all of int8 column shards + f32 scales, and a
        shard-local f32 dequantize-accumulate — returning the same
        (r, c/n) column-shard layout the psum_scatter produced, so the
        sharded server tail consumes it unchanged (ops/wire.py
        int8_reduce_scatter owns the arithmetic)."""
        from commefficient_tpu.ops.wire import int8_reduce_scatter
        return int8_reduce_scatter(
            agg, axis=self._axis, n_shards=self.mesh.shape[self._axis],
            block=self._wire_block, seed=self.cfg.seed, round_idx=step)

    def _mesh_aggregate(self, agg: jax.Array, n_total: jax.Array, step):
        """The cross-chip aggregation of the client block (called INSIDE
        its shard_map): the client sum over every mesh axis and the
        datum count over the clients axis. Returns ``(agg, n_total)``."""
        cfg = self.cfg
        td = self._table_dtype
        with phase("fed_table_reduce"):
            # the aggregation spans every mesh axis: clients sum across
            # the clients axis, and (in seq mode) each client's partial
            # per-shard gradients sum across the seq axis — one fused
            # collective either way
            all_axes = tuple(self.mesh.axis_names)
            if agg.ndim == 1:
                # dense modes: reduce_scatter the client sum so each
                # device receives only its d_pad/n shard of the summed
                # gradient — the server update then runs fully sharded.
                # (The ICI analogue of encode-before-reduce for dense
                # payloads; reference reduce: fed_aggregator.py:326-332)
                agg = lax.psum_scatter(
                    jnp.pad(agg, (0, self.d_pad - cfg.grad_size)),
                    all_axes, scatter_dimension=0, tiled=True)
            elif self._sharded_server:
                # sharded server tail: reduce-SCATTER over table
                # columns replaces the replicated table psum (the
                # dense-mode analogue above) — each device receives
                # only its c/n column shard of the summed table, the
                # (r, c) replicated result never exists, and the
                # momentum/EF tail runs on the shards
                # (core/server.sharded_sketch_server_update). The
                # bfloat16 wire covers this collective exactly like
                # the psum it replaces (the barrier pins the payload
                # dtype against XLA hoisting the f32 convert back
                # through the reduce); the int8 wire replaces the
                # reduce itself with the quantized all_to_all +
                # shard-local dequantize-accumulate.
                if self._int8_wire:
                    agg = self._int8_reduce_scatter(agg, step)
                elif td != jnp.float32:
                    agg = lax.optimization_barrier(lax.psum_scatter(
                        agg.astype(td), self._axis,
                        scatter_dimension=1, tiled=True))
                    agg = agg.astype(jnp.float32)
                else:
                    agg = lax.psum_scatter(agg, self._axis,
                                           scatter_dimension=1,
                                           tiled=True)
            else:
                # sketch tables are already the compressed payload: one
                # table-sized psum (analogue of encode-before-NCCL);
                # --sketch_dtype bfloat16 halves this payload — the
                # multichip bandwidth lever (accumulation inside the
                # collective is then bf16 too; measured impact in
                # tests/test_parallel.py + README)
                if td != jnp.float32 and agg.ndim == 2:
                    # the barrier pins the collective's payload dtype:
                    # without it XLA hoists the f32 convert back
                    # through the all-reduce and the wire stays f32
                    agg = lax.optimization_barrier(
                        lax.psum(agg.astype(td), all_axes))
                    agg = agg.astype(jnp.float32)
                else:
                    agg = lax.psum(agg, all_axes)
            if self._seq_axis is not None:
                # shard_map autodiff with vma checking off transposes
                # psum to psum, so each seq shard's gradient comes out
                # scaled (every differentiable path in the seq-sharded
                # loss crosses exactly ONE psum — the LM token mean or
                # the MC logit reduction; verified uniform by
                # tests/test_seqparallel.py's round equivalence). The
                # cross-shard sum above therefore over-counts by a
                # factor that DEPENDS ON THE JAX VERSION's transpose
                # rule (as of jax 0.9 with check_vma=False it is
                # seq_shards; with vma checking on it would be 1).
                # Rather than hard-code a jax internal, the factor is
                # MEASURED at runtime init by differentiating a known
                # seq-sharded function on this mesh under the same
                # check_vma setting (_probe_seq_grad_scale) — a jax
                # upgrade that changes the transpose changes the probe
                # identically. tests/test_seqparallel.py::
                # test_seq_sharded_round_matches_dense stays as the
                # end-to-end guard.
                agg = agg / self._seq_grad_scale
            # datum counts are identical on every seq shard (the mask
            # replicates over seq) — sum over clients only
            n_total = lax.psum(n_total, self._axis)
        return agg, n_total

    # ------------------------------------------------------------- round step
    #
    # A round has two halves. ``_client_half`` is everything up to the
    # un-normalized client sum, ``_server_half`` everything from the
    # normalized aggregate to the new weights. The synchronous round runs
    # both in one program; async buffered aggregation (FedBuff-style,
    # core/async_agg.py) runs them apart: cohort gradients are computed
    # against the weights AT DISPATCH, land out of order, merge into the
    # FedState buffer by (staleness-weighted) addition, and the server
    # half runs when the buffer goal is reached. With max_inflight=1,
    # buffer_goal=1 and no scenario latency, cohort + merge + commit is
    # bit-identical to the round (tests/test_async_agg.py,
    # tests/test_sharded_server.py).

    def _client_half(self, state: FedState, client_ids: jax.Array,
                     batch: Any, mask: jax.Array, lr: jax.Array, cs,
                     client_rngs: jax.Array) -> _ClientHalf:
        """The client half of a round: the dispatch-time byte ledger, the
        weights and rows each client reads, the client block (vmapped over
        the round's client axis; on a mesh shard_mapped, so each device
        sums and, deferred, sketch-encodes its local clients before ONE
        explicit collective over ICI: the analogue of the reference's
        per-worker compute + NCCL reduce, fed_worker.py:131,138 +
        fed_aggregator.py:329-332) and the per-client population stats.
        The caller splits ``state.rng``. Under --async_agg there are no
        per-client rows and no top-k-down weights (validate_async_combo);
        they trace as None and compile out."""
        cfg = self.cfg
        num_workers = client_ids.shape[0]

        # ---- download byte accounting, before this round's update
        # (re-design of reference fed_aggregator.py:239-289; see state.py).
        # Under --async_agg the step counter advances per COMMIT, so the
        # client reads the weights of server version ``state.step``
        download_bytes = upload_bytes = None
        down_slot = up_slot = None
        client_last_round = state.client_last_round
        if cfg.track_bytes:
            download_bytes, upload_bytes, down_slot, up_slot, \
                client_last_round = self._download_ledger(state, client_ids)

        # ---- per-client weights (download path)
        client_weights = state.client_weights
        with phase("fed_client_step"):
            if cfg.do_topk_down:
                stale = state.client_weights[client_ids]
                ps_true = state.ps_weights[: cfg.grad_size]
                used_weights = jax.vmap(
                    lambda w: client_lib.topk_down_weights(
                        cfg, ps_true, w))(stale)
                client_weights = state.client_weights.at[client_ids].set(
                    used_weights)
                params_axis = 0
            else:
                # all clients read the current PS weights
                # (reference fed_worker.py:159)
                used_weights = state.ps_weights
                params_axis = None

            # ---- per-client persistent rows
            vel_rows = (state.client_velocities[client_ids]
                        if state.client_velocities is not None else None)
            err_rows = (state.client_errors[client_ids]
                        if state.client_errors is not None else None)
        has_vel = vel_rows is not None
        has_err = err_rows is not None

        # ---- robustness inputs: per-slot adversary assignment (the
        # baked universe constant indexed by this round's client ids)
        # and the normclip rolling-median reference (NaN while the ring
        # is cold — robust_aggregate falls back to the round's own
        # median). Both None (and compiled out) when the flags are off.
        adv_slot = (self._adv_universe[client_ids]
                    if self._adversary else None)
        ref_thresh = (jnp.nanmedian(state.defense_ref)
                      if self._defense_ring else None)

        def client_block(used_weights, batch, mask, vel_rows, err_rows,
                         client_rngs, lr, adv, ref, step, cs):
            if self._rows_cols and self._axis is not None:
                # home->compute layout: each device holds a (W, d_row_pad/n)
                # column slice of all round rows; ONE all_to_all turns it
                # into the (W/n, d_row_pad) full rows of its local clients
                def rows_to_compute(x):
                    with phase("fed_table_reduce"):
                        full = lax.all_to_all(x, self._axis, split_axis=0,
                                              concat_axis=1, tiled=True)
                    return full[:, : cfg.grad_size]
                if vel_rows is not None:
                    vel_rows = rows_to_compute(vel_rows)
                if err_rows is not None:
                    err_rows = rows_to_compute(err_rows)
            if params_axis is None:
                # clients read the (padded, possibly sharded) PS weights;
                # the slice back to true d happens here, inside the block,
                # where the weights are already a full local copy
                used = used_weights[: cfg.grad_size]
            else:
                used = used_weights
            if self._labelflip:
                # data-space injection: adversarial clients train on
                # flipped labels (core/client.flip_labels) — applied on
                # the whole (W, B) batch so every client path (vmap,
                # fused, fedavg) sees it identically
                batch = client_lib.flip_labels(batch, adv,
                                               self._flip_classes)
            # --sketch_dtype bfloat16 wire (see config.py): per-client
            # table uploads round to bf16 before the server's accumulation
            # (non-deferred encode only — deferred encode has no
            # per-client table), and the cross-device SUM rounds once — by
            # the bf16 psum on a mesh, explicitly here on a single device
            # (quantization points matched up to psum partial-sum order).
            td = self._table_dtype
            wire = (td != jnp.float32 and not self._dense_preimage
                    and cfg.mode == "sketch")
            tx = None
            if cfg.mode == "fedavg":
                # fedavg applies the LR on the CLIENT against true-d
                # weights; a per-param vector arrives mesh-padded for the
                # server consumers, so slice it back here
                lr_c = lr[: cfg.grad_size] if lr.ndim == 1 else lr
                out = jax.vmap(
                    self._client_fn,
                    in_axes=(params_axis, 0, 0, None, 0))(
                        used, batch, mask, lr_c, client_rngs)
                tx = out.transmit
            elif self._fused:
                # jointly-computed round gradient (make_fused_grad): ONE
                # (d,) accumulator over all local clients' microbatches —
                # no per-client (W, d) gradient materialization (the
                # robustness flags that need per-client uploads force
                # the vmap path, see __init__). Under the fused sketch
                # encode the accumulator is the (r, c) table itself.
                agg, f_results, f_nvalid = self._fused_fn(used, batch,
                                                          mask, cs)
                out = client_lib.ClientOut(None, None, None, f_results,
                                           f_nvalid)
            else:
                out = jax.vmap(
                    self._client_fn,
                    in_axes=(params_axis, 0, 0,
                             0 if has_vel else None,
                             0 if has_err else None, 0, None))(
                        used, batch, mask, vel_rows, err_rows,
                        client_rngs, cs)
                tx = out.transmit
            # ---- per-client transmitted-space tail (injection ->
            # quarantine -> wire -> robust aggregation); compiled out
            # entirely at the flag defaults
            t_agg, results, n_valid, stats, client_finite, \
                defense_stats, cur_med = self._transmit_tail(
                    tx, out, adv, ref, client_rngs, step)
            if t_agg is not None:
                agg = t_agg
            sig_dense = None
            if (self._defer_encode and not self._dense_preimage
                    and not self._fused_encode):
                # fused-encode: the clients already accumulated in table
                # space, so the deferred encode-once is a no-op (its
                # degenerate case) and no dense aggregate exists to
                # capture (_signals_dense_cap was cleared in __init__)
                if self._signals_dense_cap:
                    # keep the dense summed gradient alive for the signal
                    # norms/shadow (single device only — the buffer
                    # already exists here, this just extends its lifetime
                    # to the round step's tail)
                    sig_dense = agg
                with phase("fed_sketch_encode"):
                    agg = cs.encode(agg)
            if wire and self._axis is None and agg.ndim == 2:
                agg = agg.astype(td).astype(jnp.float32)
            elif (self._int8_wire and self._axis is None
                  and agg.ndim == 2 and self._defer_encode):
                # single-device deferred/fused encode: one table crosses
                # the simulated wire (the per-device-partial analogue of
                # the mesh quantize; per-client tables were already
                # quantized in _transmit_tail on the non-deferred path)
                from commefficient_tpu.ops.wire import wire_round_trip
                agg = wire_round_trip(agg, self._wire_block,
                                      seed=cfg.seed, round_idx=step,
                                      salt=0)
            n_total = n_valid.sum()
            if self._axis is not None:
                agg, n_total = self._mesh_aggregate(agg, n_total, step)
            vel_out, err_out = out.velocity, out.error
            if client_finite is not None:
                # a struck client's persistent local rows must not absorb
                # its nonfinite round — keep the previous rows (still in
                # the compute layout here, matching vel_out/err_out)
                if vel_out is not None:
                    finb = client_finite.reshape(
                        (-1,) + (1,) * (vel_out.ndim - 1))
                    vel_out = jnp.where(finb, vel_out, vel_rows)
                if err_out is not None:
                    finb = client_finite.reshape(
                        (-1,) + (1,) * (err_out.ndim - 1))
                    err_out = jnp.where(finb, err_out, err_rows)
            if self._rows_cols and self._axis is not None:
                # compute->home layout: the reverse all_to_all routes each
                # updated row's columns back to their owning shards
                def rows_to_home(x):
                    xp = jnp.pad(
                        x, ((0, 0), (0, self.d_row_pad - cfg.grad_size)))
                    with phase("fed_table_reduce"):
                        return lax.all_to_all(xp, self._axis, split_axis=1,
                                              concat_axis=0, tiled=True)
                if vel_out is not None:
                    vel_out = rows_to_home(vel_out)
                if err_out is not None:
                    err_out = rows_to_home(err_out)
            return agg, n_total, vel_out, err_out, results, \
                n_valid, sig_dense, stats, client_finite, \
                defense_stats, cur_med

        if self._axis is not None:
            ax = self._axis
            row = P(ax)
            if self._seq_axis and self._seq_spec:
                batch_specs = {k: self._batch_pspec(sd)
                               for k, sd in self._seq_spec.items()}
            else:
                batch_specs = jax.tree.map(lambda _: row, batch)
            # dense client rows arrive/leave in the column-sharded home
            # layout (see __init__); sketch table rows keep the row layout
            row_spec = P(None, ax) if self._rows_cols else row
            in_specs = (
                row if params_axis == 0 else P(),
                batch_specs,
                row,
                row_spec if has_vel else None,
                row_spec if has_err else None,
                row,
                P(),
                row if self._adversary else None,      # adv slot mask
                P() if self._defense_ring else None,   # normclip reference
                P() if self._int8_wire else None,      # wire round key
                jax.tree.map(lambda _: P(), cs),
            )
            # dense modes leave the block as a reduce_scattered shard of
            # the summed gradient (over ALL axes); sketch leaves as a
            # COLUMN-sharded reduce-scattered table under the sharded
            # server tail (the state tables' home layout, so the tail
            # consumes it in place), or a replicated (psum'd) table on
            # the replicated path
            dense_agg_spec = P(tuple(self.mesh.axis_names))
            if cfg.mode != "sketch":
                agg_spec = dense_agg_spec
            elif self._sharded_server:
                agg_spec = P(None, ax)
            else:
                agg_spec = P()
            out_specs = (
                agg_spec,
                P(),
                row_spec if (cfg.mode != "fedavg" and has_vel) else None,
                row_spec if (cfg.mode != "fedavg" and has_err) else None,
                tuple(row for _ in range(cfg.num_results_train)),
                row,
                None,   # sig_dense: never captured on a mesh (see __init__)
                # per-client stat scalars shard like every other
                # per-client quantity (telemetry/clients.py)
                ({k: row for k in CLIENT_GRAD_KEYS}
                 if self._client_grad_stats else None),
                # per-client finite flags (quarantine)
                row if self._quarantine else None,
                # defense scalars leave the block psum'd/replicated
                ({k: P() for k in ("clip_frac", "clip_thresh",
                                   "clipped_mass", "trim_frac")}
                 if cfg.defense != "none" else None),
                P() if self._defense_ring else None,   # cur_med
            )
            # check_vma off: the client step's scan carries start as
            # replicated zeros and become device-varying on the first
            # iteration, which the strict varying-axis checker rejects
            client_block = shard_map(client_block, mesh=self.mesh,
                                     in_specs=in_specs, out_specs=out_specs,
                                     check_vma=False)

        step_arg = state.step if self._int8_wire else None
        with phase("fed_client_step"):
            agg, n_total, vel_new, err_new, results, n_valid, sig_dense, \
                grad_stats, client_finite, defense_stats, cur_med = \
                client_block(used_weights, batch, mask, vel_rows, err_rows,
                             client_rngs, lr, adv_slot, ref_thresh, step_arg,
                             cs)

        # ---- per-client population stats (telemetry/clients.py): quantile
        # summaries along the client axis, riding the same async metrics
        # fetch as the loss — per-client vectors never leave the device
        client_stats = None
        if self._client_stats:
            with phase("fed_client_stats"):
                per_client = {"loss": results[0]}
                if grad_stats is not None:
                    per_client.update(grad_stats)
                else:
                    # fused path: no per-client gradient exists (see __init__
                    # _client_grad_stats) — NaN quantiles, never fake zeros
                    nan_w = jnp.full((num_workers,), jnp.nan, jnp.float32)
                    per_client.update({k: nan_w for k in CLIENT_GRAD_KEYS})
                if cfg.track_bytes:
                    per_client["upload_bytes"] = up_slot
                    per_client["download_bytes"] = down_slot
                rep = None
                if self.mesh is not None:
                    # one W-sized all-gather for the WHOLE summary: without
                    # the replication constraint every per-key quantile
                    # lowers to its own tiny collectives (launch-count
                    # pathology, see summarize_per_client)
                    rep_sh = NamedSharding(self.mesh, P())

                    def rep(x, _sh=rep_sh):
                        return lax.with_sharding_constraint(x, _sh)
                client_stats = summarize_per_client(per_client, n_valid,
                                                    replicate_fn=rep)

        return _ClientHalf(
            agg=agg, n_total=n_total, results=results, n_valid=n_valid,
            velocity=vel_new, error=err_new, sig_dense=sig_dense,
            client_finite=client_finite, defense_stats=defense_stats,
            cur_med=cur_med,
            download_bytes=download_bytes, upload_bytes=upload_bytes,
            client_last_round=client_last_round,
            client_weights=client_weights, client_stats=client_stats)

    def _client_health(self, state: FedState, half: _ClientHalf):
        """What the round and the cohort both do with the client half's
        health outputs: the client-side non-finite test, the normclip
        ring write and the ``metrics['defense']`` scalars. Returns
        ``(bad, defense_ref, defense)``; the caller ors ``bad`` with its
        own tests and marks ``nan_round``."""
        with phase("fed_server_tail"):
            if self._quarantine:
                # per-client nonfinites were zeroed OUT of the aggregate in
                # the client block (their losses too) — only a round with no
                # finite DATA-CARRYING client left, or nonfinite SERVER
                # state, still aborts. A nonfinite flag can only come from a
                # live slot (benched/masked placeholders upload finite
                # zeros), so "fully-nonfinite round" == some client went
                # nonfinite AND no finite client with data remains
                # (n_valid is post-zeroing: > 0 iff live AND finite)
                bad = ((~half.client_finite).any()
                       & ~(half.n_valid > 0).any())
            else:
                # the reference's host check is on the loss
                # (cv_train.py:222-224)
                bad = ~jnp.isfinite(half.results[0]).all()

            # normclip rolling reference: this round's median per-datum norm
            # enters the ring AFTER the round used the PAST medians — the
            # attack round cannot vouch for its own normality. A cohort
            # keys the ring off the server version: commits between
            # dispatches share a slot, which only shortens the window
            defense_ref = state.defense_ref
            if self._defense_ring:
                defense_ref = state.defense_ref.at[
                    jnp.mod(state.step, self.cfg.defense_window)].set(
                        half.cur_med)

            defense = self._defense_scalars(half.defense_stats,
                                            half.client_finite)
        return bad, defense_ref, defense

    @staticmethod
    def _mark_nan_round(state: FedState, bad: jax.Array,
                        *more: jax.Array) -> jax.Array:
        """Device-side divergence detection: the FIRST round (server
        version) at which any of the caller's non-finite tests fired."""
        with phase("fed_server_tail"):
            for flag in more:
                bad = bad | flag
            return jnp.where((state.nan_round < 0) & bad, state.step,
                             state.nan_round)

    def _server_half(self, state: FedState, agg: jax.Array, lr: jax.Array,
                     server_rng: jax.Array, cs=None) -> _ServerHalf:
        """The server half of a round, from the NORMALIZED aggregate: the
        mode's momentum + error-feedback update (the sharded sketch tail
        on an eligible mesh, core/server.py's replicated rules
        otherwise), the weight apply, the changed-coordinate marks of the
        byte ledger, and the non-finite test on update and aggregate (a
        NaN gradient does not always survive the top-k select into the
        update). The caller owns ``rng``, ``step``, ``nan_round`` and any
        buffer."""
        cfg = self.cfg
        with phase("fed_server_tail"):
            update, Vvel, Verr, sup_mask, support = \
                self._apply_server_update(state, agg, lr, server_rng, cs)
            padded = update
            if self.d_pad != cfg.grad_size:
                if update.shape[0] == cfg.grad_size:
                    # sketch decode produces a true-d update; pad to the
                    # server's sharded length
                    padded = jnp.pad(update, (0, self.d_pad - cfg.grad_size))
                else:
                    # keep the padding coordinates exactly zero (server-side
                    # DP noise would otherwise drift them and pollute the
                    # changed-coordinate byte accounting)
                    padded = jnp.where(
                        jnp.arange(self.d_pad) < cfg.grad_size, update, 0.0)
            ps_weights = state.ps_weights - padded

        # ---- byte accounting: record which coordinates changed this
        # round. One dense pass even where the update is k winners: a
        # scatter of 50,000 marks takes the chip 4.4 ms whatever d, this
        # pass 1.7 ms at d = 1.2e8 and 5.3 at 3.9e8 (PERF.md section 6)
        coord_last_update = state.coord_last_update
        if cfg.track_bytes:
            with phase("fed_byte_ledger"):
                coord_last_update = jnp.where(
                    padded != 0, state.step, state.coord_last_update)

        with phase("fed_server_tail"):
            bad = ~jnp.isfinite(padded).all() | ~jnp.isfinite(agg).all()
        fields = dict(ps_weights=ps_weights, Vvelocity=Vvel, Verror=Verr,
                      coord_last_update=coord_last_update)
        return _ServerHalf(fields, update, support, padded, sup_mask, bad)

    def _round_signals(self, state: FedState, agg, srv: _ServerHalf,
                       sig_dense, cs):
        """The round's compression-signal health (telemetry/signals.py)
        and layer-wise attribution (telemetry/layer_signals.py): on-device
        scalars and (G,) vectors fetched asynchronously alongside the
        loss, measured on the update BEFORE it is padded so true-d
        slicing stays uniform. Returns ``(signals, layer_signals,
        sig_Vvelocity, sig_Verror)``; all pass-through when signals are
        off."""
        cfg = self.cfg
        update = srv.update
        Vvel, Verr = srv.fields["Vvelocity"], srv.fields["Verror"]
        signals = layer_signals = None
        sig_vel_new, sig_err_new = state.sig_Vvelocity, state.sig_Verror
        if self._signals:
            with phase("fed_signals"):
                signals, sig_vel_new, sig_err_new = round_signals(
                    cfg, agg=agg, update=update,
                    Vvel_prev=state.Vvelocity, Verr_prev=state.Verror,
                    Vvel_new=Vvel, Verr_new=Verr, cs=cs,
                    dense_agg=sig_dense,
                    sig_vel=state.sig_Vvelocity, sig_err=state.sig_Verror)
        if self._layer_signals:
            # per-group reductions of the same pre-padding quantities the
            # scalar signals just measured — the conservation laws (group
            # masses sum to the whole-vector norms squared, support counts
            # sum to nnz) are dryrun-gated against exactly that pairing
            from commefficient_tpu.telemetry.layer_signals import \
                layer_group_signals
            # dense gradient / dense EF sources, where the round holds
            # them (see __init__._layer_grad_mass; None -> null fields)
            dense = cfg.mode != "sketch" or self._dense_preimage
            grad_dense = (agg if dense
                          else sig_dense if self._layer_grad_mass
                          else None)
            err_dense = (Verr if dense
                         else sig_err_new if sig_err_new is not None
                         else None)
            with phase("fed_layer_signals"):
                err_pre = None
                if cfg.signals_exact:
                    # the SAME dense pre-feedback error round_signals'
                    # topk_overlap selects against (signals.py documents
                    # the two availability paths) — recomputed here from
                    # the pre-update state so the modules stay decoupled
                    rho = cfg.virtual_momentum
                    if state.sig_Verror is not None and sig_dense is not None:
                        err_pre = (state.sig_Verror + sig_dense
                                   + rho * state.sig_Vvelocity)
                    elif cfg.mode == "true_topk" or (cfg.mode == "sketch"
                                                     and dense):
                        err_pre = (state.Verror + agg
                                   + rho * state.Vvelocity)[: cfg.grad_size]
                layer_signals = layer_group_signals(
                    cfg, spec=self.group_spec, update=update,
                    support=srv.support, grad_dense=grad_dense,
                    err_dense=err_dense, err_pre=err_pre, mesh=self.mesh)
        return signals, layer_signals, sig_vel_new, sig_err_new

    def _round_step(self, state: FedState, client_ids: jax.Array,
                    batch: Any, mask: jax.Array, lr: jax.Array, cs=None):
        """The synchronous round: both halves in one program."""
        cfg = self.cfg
        keys = jax.random.split(state.rng, client_ids.shape[0] + 2)
        rng, server_rng, client_rngs = keys[0], keys[1], keys[2:]
        half = self._client_half(state, client_ids, batch, mask, lr, cs,
                                 client_rngs)
        with phase("fed_server_tail"):
            total = jnp.maximum(half.n_total, 1.0)
            agg = half.agg / total
        sig_dense = half.sig_dense
        if sig_dense is not None:
            # same normalization as agg: the signals compare like with like
            with phase("fed_signals"):
                sig_dense = sig_dense / total
        srv = self._server_half(state, agg, lr, server_rng, cs)
        signals, layer_signals, sig_vel_new, sig_err_new = \
            self._round_signals(state, agg, srv, sig_dense, cs)

        with phase("fed_server_tail"):
            # ---- write back per-client rows
            client_velocities = state.client_velocities
            if half.velocity is not None and client_velocities is not None:
                new_rows = half.velocity
                if cfg.mode == "true_topk" and srv.sup_mask is not None:
                    # momentum factor masking on participating clients'
                    # local velocities (intended behavior of
                    # fed_aggregator.py:528-533) — the server mask is in
                    # padded space; rows are at true d single-device, at
                    # d_row_pad in the mesh home layout (padding coords are
                    # identically 0 and where() keeps them 0)
                    sm = srv.sup_mask[: cfg.grad_size]
                    if self._rows_cols:
                        sm = jnp.pad(sm, (0, self.d_row_pad - cfg.grad_size))
                    new_rows = jnp.where(sm[None, :], 0.0, new_rows)
                client_velocities = client_velocities.at[client_ids].set(
                    new_rows)
            client_errors = state.client_errors
            if half.error is not None and client_errors is not None:
                client_errors = client_errors.at[client_ids].set(half.error)

        client_bad, defense_ref, defense = self._client_health(state, half)
        new_state = state.replace(
            rng=rng,
            step=state.step + 1,
            client_velocities=client_velocities,
            client_errors=client_errors,
            client_weights=half.client_weights,
            client_last_round=half.client_last_round,
            nan_round=self._mark_nan_round(state, srv.bad, client_bad),
            sig_Vvelocity=sig_vel_new,
            sig_Verror=sig_err_new,
            defense_ref=defense_ref,
            **srv.fields)
        metrics = {
            "results": half.results,         # tuple of (num_workers,) arrays
            "n_valid": half.n_valid,
            "download_bytes": half.download_bytes,
            "upload_bytes": half.upload_bytes,
            "signals": signals,              # dict of scalars, or None
            # dict of (G,) per-group vectors, or None (layer_signals.py)
            "layer_signals": layer_signals,
            "client_stats": half.client_stats,   # quantile summaries, or None
            "defense": defense,              # dict of scalars, or None
            # (W,) bool, quarantine mode only: the host-side ledger's
            # per-round feed (False = zeroed out of this aggregate)
            "client_finite": half.client_finite,
        }
        return new_state, metrics

    def _val_step(self, ps_weights: jax.Array, batch: Any, mask: jax.Array):
        return self._val_fn_inner(ps_weights[: self.cfg.grad_size], batch,
                                  mask)

    def _val_step_sharded(self, ps_weights: jax.Array, batch: Any,
                          mask: jax.Array):
        """Mesh-parallel val: batch items shard over every mesh axis; each
        device evaluates its slice with the full (all-gathered) weights;
        per-shard means recombine as valid-ITEM-weighted sums — exactly
        the convention the host loops already use ACROSS batches
        (run_validation / compat._call_val accumulate results*n_valid).
        For per-item losses (CV) this equals the dense whole-batch step
        up to fp32 reduction order (asserted by tests/test_parallel.py::
        test_sharded_val_matches_dense). For metrics whose within-shard
        mean is over a different unit (GPT-2's per-TOKEN lm NLL), the
        item weighting is an approximation of the whole-batch token mean
        — the same approximation the cross-batch accumulation already
        makes, just at shard granularity."""
        axes = tuple(self.mesh.axis_names)
        nres = self.cfg.num_results_val

        def block(w_shard, batch, mask):
            w = lax.all_gather(w_shard, axes, tiled=True)
            res, n = self._val_fn_inner(w[: self.cfg.grad_size], batch, mask)
            num = lax.psum(jnp.stack([r * n for r in res]), axes)
            den = lax.psum(n, axes)
            safe = jnp.maximum(den, 1.0)
            return tuple(num[i] / safe for i in range(len(res))), den

        item = P(axes)
        return shard_map(
            block, mesh=self.mesh,
            in_specs=(P(axes), jax.tree.map(lambda _: item, batch), item),
            out_specs=(tuple(P() for _ in range(nres)), P()),
            check_vma=False)(ps_weights, batch, mask)

    # -------------------------------------------- async buffered aggregation

    def _cohort_step(self, state: FedState, client_ids: jax.Array,
                     batch: Any, mask: jax.Array, lr: jax.Array, cs=None):
        """The client half as its own program, stopping BEFORE the datum
        normalization and server update. Advances only the dispatch-time
        state (rng, download byte accounting, nan flag, defense ring) and
        returns the cohort payload: the UNNORMALIZED transmitted-space
        sum, its datum count, per-client results/stats, and the round's
        exact byte costs."""
        keys = jax.random.split(state.rng, client_ids.shape[0] + 1)
        rng, client_rngs = keys[0], keys[1:]
        half = self._client_half(state, client_ids, batch, mask, lr, cs,
                                 client_rngs)
        with phase("fed_server_tail"):
            # dispatch-side divergence detection: a poisoned cohort sum must
            # be flagged before it can merge into the buffer
            bad = ~jnp.isfinite(half.agg).all()
        client_bad, defense_ref, defense = self._client_health(state, half)
        new_state = state.replace(
            rng=rng, client_last_round=half.client_last_round,
            nan_round=self._mark_nan_round(state, bad, client_bad),
            defense_ref=defense_ref)
        payload = {
            "sum": half.agg,             # UNNORMALIZED weighted client sum
            "n_total": half.n_total,     # datum count of this cohort
            "results": half.results,
            "n_valid": half.n_valid,
            "download_bytes": half.download_bytes,
            "upload_bytes": half.upload_bytes,
            "client_stats": half.client_stats,
            "defense": defense,
            "client_finite": half.client_finite,
        }
        return new_state, payload

    def _merge_step(self, state: FedState, cohort_sum: jax.Array,
                    n_total: jax.Array, weight: jax.Array) -> FedState:
        """Fold one landed cohort into the buffer: pure weighted addition
        (the merge soundness condition — sketch tables and dense sums are
        both linear in the uploads). The datum count accumulates RAW,
        not discounted: the commit divides the weighted sum by the true
        datum total (FedBuff's divide-by-K), so a stale cohort's
        contribution is genuinely attenuated by its weight instead of
        the discount cancelling between numerator and denominator."""
        return state.replace(
            async_buffer=state.async_buffer + weight * cohort_sum,
            async_buffer_n=state.async_buffer_n + n_total)

    def _commit_step(self, state: FedState, lr: jax.Array, cs=None):
        """The server half as its own program: normalize the buffered
        aggregate, run ``_server_half`` on it, and reset the buffer.
        ``step`` advances here: it is the server version."""
        rng, server_rng = jax.random.split(state.rng)
        with phase("fed_server_tail"):
            total = jnp.maximum(state.async_buffer_n, 1.0)
            agg = state.async_buffer / total
        srv = self._server_half(state, agg, lr, server_rng, cs)
        new_state = state.replace(
            rng=rng,
            nan_round=self._mark_nan_round(state, srv.bad),
            step=state.step + 1,
            async_buffer=jnp.zeros_like(state.async_buffer),
            async_buffer_n=jnp.zeros_like(state.async_buffer_n),
            **srv.fields)
        # commit health scalars for the async_round telemetry event: the
        # post-commit EF-accumulator norms are the staleness-divergence
        # signal telemetry/health.py watches
        with phase("fed_signals"):
            metrics = {
                "update_norm": jnp.linalg.norm(srv.applied),
                "error_norm": jnp.linalg.norm(srv.fields["Verror"]),
                "velocity_norm": jnp.linalg.norm(srv.fields["Vvelocity"]),
                "buffer_n": state.async_buffer_n,
            }
        return new_state, metrics

    def _prep_lr(self, lr) -> jax.Array:
        lr = jnp.asarray(lr, jnp.float32)
        if lr.ndim == 1 and lr.shape[0] != self.d_pad:
            # per-param LR vector (Fixup groups): pad to the server's
            # mesh-padded length (padding coords get multiplier 1; their
            # update is identically 0)
            lr = jnp.pad(lr, (0, self.d_pad - lr.shape[0]),
                         constant_values=1.0)
        return lr

    def cohort(self, state: FedState, client_ids, batch, mask, lr
               ) -> Tuple[FedState, Dict]:
        """Dispatch one cohort's client compute (--async_agg). Same
        argument contract as :meth:`round`; returns (state', payload)
        where payload carries the unnormalized transmitted-space sum the
        AsyncAggregator merges."""
        assert self._cohort is not None, "--async_agg is off"
        with self._span("cohort_dispatch"):
            return self._cohort(state, jnp.asarray(client_ids, jnp.int32),
                                batch, jnp.asarray(mask),
                                self._prep_lr(lr), self.cs)

    def merge(self, state: FedState, cohort_sum, n_total,
              weight: float) -> FedState:
        """Merge a landed cohort into the buffer with its staleness
        weight. ``weight == 1.0`` into an EMPTY buffer swaps the arrays
        in directly — bitwise-exact, the sync-equivalence path (the
        generic path computes ``buffer + w*sum``, and 0 + x flips the
        sign of -0.0 coordinates)."""
        return self._merge_jit(state, cohort_sum,
                               jnp.asarray(n_total, jnp.float32),
                               jnp.asarray(weight, jnp.float32))

    def merge_first(self, state: FedState, cohort_sum,
                    n_total) -> FedState:
        """Weight-1.0 merge into an empty buffer: a pytree swap, no
        arithmetic (see :meth:`merge`). On a mesh the cohort sum is
        re-laid-out to the buffer's canonical state sharding first — a
        pure layout copy, bitwise identical — so the commit/cohort jits'
        pinned in_shardings keep matching."""
        n_total = jnp.asarray(n_total, jnp.float32)
        if self._state_sharding is not None:
            cohort_sum = jax.device_put(cohort_sum,
                                        self._state_sharding.async_buffer)
            n_total = jax.device_put(n_total,
                                     self._state_sharding.async_buffer_n)
        return state.replace(async_buffer=cohort_sum,
                             async_buffer_n=n_total)

    def commit(self, state: FedState, lr) -> Tuple[FedState, Dict]:
        """Commit the buffered aggregate through the server step."""
        assert self._commit_jit is not None, "--async_agg is off"
        with self._span("commit_dispatch"):
            return self._commit_jit(state, self._prep_lr(lr), self.cs)

    # -------------------------------------------------------------- user API

    def round(self, state: FedState, client_ids, batch, mask, lr
              ) -> Tuple[FedState, Dict]:
        """Run one federated round. ``client_ids``: (num_workers,) int32;
        ``batch``: pytree with leaves (num_workers, batch_size, ...);
        ``mask``: (num_workers, batch_size); ``lr``: scalar or (d,) vector."""
        # round_dispatch = the async dispatch: round_stage (the host's
        # arguments onto the device) and round_launch (the jitted call's
        # return, the compile watcher's signature check included); device
        # completion lands in the caller's "device_wait" span. A compile
        # shows up as a multi-second round_launch with compile_lower /
        # compile_backend spans under it
        with self._span("round_dispatch"):
            with self._span("round_stage"):
                client_ids = jnp.asarray(client_ids, jnp.int32)
                mask = jnp.asarray(mask)
                lr = self._prep_lr(lr)
            with self._span("round_launch"):
                return self._round(state, client_ids, batch, mask, lr,
                                   self.cs)

    def val(self, state: FedState, batch, mask):
        """Masked evaluation on the current PS weights; returns
        (results_tuple, n_valid). On a mesh the batch pads up to a
        mesh-divisible item count (padding items are masked out) and
        shards over all devices — see _val_step_sharded."""
        with self._span("val_dispatch"):
            mask = jnp.asarray(mask)
            if self.mesh is not None:
                n = self.mesh.size
                N = mask.shape[0]
                Np = -(-N // n) * n
                if Np != N:
                    batch = jax.tree.map(
                        lambda t: jnp.pad(
                            t, [(0, Np - N)] + [(0, 0)] * (t.ndim - 1)),
                        batch)
                    mask = jnp.pad(mask, (0, Np - N))
            return self._val(state.ps_weights, batch, mask)

    def flat_weights(self, state: FedState) -> jax.Array:
        """The true-d flat weight vector (mesh padding sliced off) — the
        ONE accessor every consumer of ``state.ps_weights`` outside the
        round step must use; a padded vector does not unravel."""
        return state.ps_weights[: self.cfg.grad_size]

    def get_params(self, state: FedState):
        """Materialize the model parameter pytree from the flat PS weights
        (reference __getattr__ trick, fed_aggregator.py:372-376)."""
        return self.unravel(self.flat_weights(state))
