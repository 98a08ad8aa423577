"""Async buffered aggregation: FedBuff-style rounds over the federated
runtime (ROADMAP item 3; Nguyen et al. 2022, "Federated Learning with
Buffered Asynchronous Aggregation").

Why this exists
---------------
The runtime is lockstep: every round blocks on its full client cohort,
so round capacity is capped by the slowest simulated cohort and there is
no story for stragglers, churn, or partial participation. Production
federated systems aggregate asynchronously — clients upload whenever
they finish, the server folds updates into a buffer, discounts stale
ones, and commits when the buffer reaches a goal size. The FetchSGD
lineage makes this unusually cheap here: the Count Sketch is LINEAR, so
cohort uploads landing out of order merge into one sketch buffer by
pure addition, and the server's momentum/error-feedback state stays
"virtual" exactly as the synchronous server does (PAPER.md §2.1/§2.3).

What runs where
---------------
:class:`AsyncAggregator` is the host-side controller, generalizing
core/pipeline.py's prefetch thread into a bounded in-flight pool over
SERVER work:

- ``dispatch`` (every driver tick): one cohort (a sampler round of
  ``num_workers`` clients) is computed against the CURRENT weights via
  ``FedRuntime.cohort`` — the client half of the synchronous round,
  stopping before the server update. The payload (the unnormalized
  transmitted-space sum + datum count) stays on device; up to
  ``max_inflight`` (K) payloads are held. jax's async dispatch means
  the host loop never blocks on cohort compute.
- ``land`` (simulated arrival order, data/scenarios.py): the cohort's
  sum merges into the ``FedState.async_buffer`` by staleness-weighted
  addition. Staleness s = commits between the cohort's dispatch and its
  merge; the weight is ``staleness_weight(cfg.staleness_discount, s,
  cfg.staleness_alpha)`` — discounting happens in COMPRESSED/EF space
  (a scalar times a linear sketch is the sketch of the scaled
  gradient, so the discount commutes with decoding).
- ``commit`` (every ``buffer_goal`` (M) merged cohorts, or at the
  epoch-boundary flush): ``FedRuntime.commit`` normalizes the buffer by
  its RAW datum count (FedBuff's divide-by-K: the denominator ignores
  the discounts, so a stale cohort's contribution is genuinely
  attenuated by its weight instead of the discount cancelling) and runs
  the mode's UNCHANGED server momentum+EF step (core/server.py), then
  zeroes the buffer. The FedState ``step`` counter counts commits — the
  server version.

Sync equivalence
----------------
With K=1, M=1 and no scenario latency every cohort lands and commits in
its own tick with staleness 0 (weight exactly 1.0, all discount rules),
and the first-merge path swaps the cohort sum into the empty buffer
without arithmetic — the composition cohort→merge→commit is
bit-identical to the fused synchronous round (asserted per sound mode by
``__graft_entry__.dryrun_multichip`` and tests/test_async_agg.py).
One scope caveat: the split steps advance ``state.rng`` differently
from the fused round (a W+1 split at dispatch plus a 2-split at commit,
vs one W+2 split), so the bitwise claim covers configurations that
CONSUME no per-round randomness — which is every sound mode without DP.
Async + DP remains sound (worker noise/clip are per-client ops before
the sum; server noise draws at commit), it just follows a different —
still deterministic — noise stream than the lockstep run.

Wire composition
----------------
The quantized sketch wire (``--wire_dtype``; ops/wire.py) composes for
free: the cohort step applies the wire BEFORE its payload leaves the
executable (bf16 rounding or int8 quantize->all_to_all->dequantize on
the collective, per-client round-trips single-device), so by the time a
cohort sum reaches :meth:`AsyncAggregator` it is an ordinary f32 array
— buffer merges stay pure f32 additions and the staleness discount
multiplies dequantized values (a scalar times the dequantized table is
the dequantization of nothing the wire ever carried — the discount is
server-side, after the wire, exactly like the sync normalization). The
int8 rounding draws key off the server version (``state.step``), which
K=1/M=1 shares with the sync round — the bit-identity gate covers the
int8 arm in ``__graft_entry__._wire_gate``.

Soundness
---------
Buffered merging is sound exactly when the server consumes the cohort
uploads ONLY through their weighted sum. Modes with per-client
persistent rows break that: local momentum rows are masked with the
SAME round's server support (momentum factor masking), and local error
rows / topk_down client weights are written at dispatch from state the
commit hasn't produced yet. :func:`validate_async_combo` fails fast on
those combinations — see the README soundness matrix.
"""

from __future__ import annotations

import bisect
import math
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from commefficient_tpu.config import FedConfig
from commefficient_tpu.faults import maybe_fault

DISCOUNT_RULES = ("none", "poly", "exp")


def staleness_weight(rule: str, staleness: float, alpha: float = 0.5
                     ) -> float:
    """Merge weight of a cohort ``staleness`` commits old.

    - ``none``: 1 (plain FedBuff averaging);
    - ``poly``: (1+s)^-alpha — alpha 0.5 is FedBuff's 1/sqrt(1+s);
    - ``exp``: exp(-alpha*s).

    Every rule returns EXACTLY 1.0 at s=0 (the sync-equivalence
    contract) and decreases monotonically in s.
    """
    s = float(staleness)
    if s < 0:
        raise ValueError(f"staleness must be >= 0, got {s}")
    if rule == "none":
        return 1.0
    if rule == "poly":
        return float((1.0 + s) ** (-float(alpha)))
    if rule == "exp":
        return float(math.exp(-float(alpha) * s))
    raise ValueError(f"unknown staleness discount {rule!r}; "
                     f"choices: {DISCOUNT_RULES}")


def validate_async_combo(cfg: FedConfig) -> None:
    """Reject mode combinations where buffered merge is unsound.

    The buffer consumes cohort uploads only through their weighted sum;
    any per-client persistent state written at dispatch from commit-time
    information cannot be reproduced out of order. Mirrors the fail-fast
    contract of core/server.validate_mode_combo."""
    if not cfg.async_agg:
        return
    problems: List[str] = []
    if cfg.needs_client_velocities:
        problems.append(
            "local_momentum > 0 keeps per-client velocity rows that the "
            "synchronous round masks with the SAME round's server support "
            "(momentum factor masking) — the split client block finishes "
            "before that support exists, so the masking semantics cannot "
            "be reproduced. Use local_momentum 0 (rely on "
            "--virtual_momentum, which lives in server state and splits "
            "soundly)")
    if cfg.needs_client_errors:
        problems.append(
            "error_type=local keeps per-client error rows written at "
            "dispatch; the split round's client block has no row "
            "plumbing (and under buffering the rows would accumulate "
            "against interleaved server versions the synchronous rule "
            "never sees). Use error_type none (local_topk) or virtual "
            "(sketch/true_topk — virtual EF lives in server state and "
            "splits soundly)")
    if cfg.do_topk_down:
        problems.append(
            "--topk_down keeps per-client stale weight vectors updated "
            "at dispatch from the current server weights — the split "
            "client block has no weight-row plumbing (and under "
            "buffering a client's record diverges from what it actually "
            "downloaded). Drop --topk_down")
    if problems:
        raise ValueError(
            "--async_agg: buffered merge is unsound for this "
            "configuration:\n  " + "\n  ".join(problems))


def reconcile_resumed_state(state, runtime) -> Tuple[Any, List[str]]:
    """Make a restored FedState consistent with this runtime's async
    configuration. Returns (state, messages-to-print).

    - async run resuming a checkpoint WITHOUT buffer fields (pre-async
      vintage, reachable only past the restore-time meta guard): the
      buffer starts EMPTY — safe, nothing double-counts.
    - async run resuming a NON-EMPTY buffer (a mid-epoch postmortem
      bundle): the buffer is LOUDLY restarted. The epoch replays from
      its boundary, so its cohorts will be recomputed — restoring the
      buffer would double-count every one of them.
    - sync run resuming an async-mode checkpoint: the buffer fields are
      dropped (warning if non-empty) so the state matches the sync
      runtime's template.
    """
    import jax.numpy as jnp

    msgs: List[str] = []
    if runtime.cfg.async_agg:
        if state.async_buffer is None:
            tmpl = runtime._state_template()
            state = state.replace(
                async_buffer=jnp.zeros(tmpl.async_buffer.shape,
                                       jnp.float32),
                async_buffer_n=jnp.zeros((), jnp.float32))
            msgs.append(
                "async buffer initialized EMPTY: the checkpoint predates "
                "async buffered aggregation (no buffer state to restore; "
                "nothing double-counts)")
        else:
            n = float(np.asarray(state.async_buffer_n))
            if n > 0:
                state = state.replace(
                    async_buffer=jnp.zeros_like(state.async_buffer),
                    async_buffer_n=jnp.zeros_like(state.async_buffer_n))
                msgs.append(
                    f"resume mid-buffer: RESTARTING the partial async "
                    f"buffer ({n:.0f} buffered datums discarded). The "
                    "epoch replays from its boundary, so keeping the "
                    "buffer would double-count its cohorts")
    elif state.async_buffer is not None:
        n = float(np.asarray(state.async_buffer_n)) \
            if state.async_buffer_n is not None else 0.0
        if n > 0:
            msgs.append(
                f"discarding a non-empty async buffer ({n:.0f} datums) "
                "from an async-mode checkpoint resumed synchronously")
        state = state.replace(async_buffer=None, async_buffer_n=None)
    return state, msgs


class _InFlight:
    """One dispatched-but-unlanded cohort: device payload + bookkeeping."""

    __slots__ = ("cohort", "version", "arrival", "sum", "n_total",
                 "results", "n_valid")

    def __init__(self, cohort, version, arrival, payload):
        self.cohort = int(cohort)
        self.version = int(version)       # server commits at dispatch
        self.arrival = float(arrival)     # simulated arrival tick
        self.sum = payload["sum"]         # device array, dropped at merge
        self.n_total = payload["n_total"]
        self.results = payload["results"]
        self.n_valid = payload["n_valid"]

    def __lt__(self, other):              # bisect.insort ordering
        return (self.arrival, self.cohort) < (other.arrival, other.cohort)


def commit_loss(rec: Dict[str, Any]) -> Optional[float]:
    """Datum-weighted mean dispatch loss of a commit's merged cohorts.
    Syncs the cohort result refs to host — call only at the telemetry
    record cadence (the fetch-once discipline of the driver loop)."""
    num = den = 0.0
    for res0, n_valid in rec.get("loss_refs", ()):
        r = np.asarray(res0, np.float64)
        n = np.asarray(n_valid, np.float64)
        num += float((r * n).sum())
        den += float(n.sum())
    if den <= 0:
        return None
    v = num / den
    return v if math.isfinite(v) else None


class AsyncAggregator:
    """Bounded in-flight pool + staleness-weighted buffer over a
    FedRuntime built with ``cfg.async_agg``.

    Driver contract (cv_train.train): one :meth:`step` per sampler
    round; at the epoch boundary one :meth:`flush` (land everything,
    commit any partial buffer) so epochs — and therefore checkpoints —
    never straddle an open buffer. ``step``/``flush`` return the list of
    commit records produced, each carrying the merged cohorts' measured
    staleness/discounts plus device refs for the ``async_round``
    telemetry event.
    """

    def __init__(self, runtime, scenario=None, *,
                 max_inflight: Optional[int] = None,
                 buffer_goal: Optional[int] = None,
                 discount: Optional[str] = None,
                 alpha: Optional[float] = None):
        cfg = runtime.cfg
        if not cfg.async_agg:
            raise ValueError("AsyncAggregator needs a runtime built with "
                             "cfg.async_agg=True (the cohort/commit steps "
                             "are only jitted then)")
        validate_async_combo(cfg)
        sc_plan = getattr(scenario, "adversary", None)
        rt_plan = getattr(runtime, "adversary_plan", None)
        if sc_plan is not None and rt_plan is not None:
            # the scenario's per-cohort adversary annotation
            # (CohortFate.adversary) and the universe mask the jitted
            # round actually applies are two AdversaryPlan instances
            # that must describe the SAME assignment — a seed/frac
            # mismatch would make the telemetry/ledger view silently
            # diverge from the injected reality
            a = (sc_plan.kind, sc_plan.frac, sc_plan.seed, sc_plan.scale)
            b = (rt_plan.kind, rt_plan.frac, rt_plan.seed, rt_plan.scale)
            if a != b:
                raise ValueError(
                    f"scenario adversary plan {a} disagrees with the "
                    f"runtime's {b}: build both from the same FedConfig "
                    "(make_scenario/make_adversary with matching seeds)")
        self.runtime = runtime
        self.scenario = scenario
        self.max_inflight = int(max_inflight if max_inflight is not None
                                else cfg.max_inflight)
        self.buffer_goal = int(buffer_goal if buffer_goal is not None
                               else cfg.buffer_goal)
        self.discount = (discount if discount is not None
                         else cfg.staleness_discount)
        self.alpha = float(alpha if alpha is not None
                           else cfg.staleness_alpha)
        assert self.max_inflight >= 1 and self.buffer_goal >= 1
        self._inflight: List[_InFlight] = []      # sorted by (arrival, id)
        self._pending: List[Dict[str, Any]] = []  # merged, uncommitted
        self.commits = 0          # host mirror of the server version delta
        self.dispatched = 0
        self.dropped = 0
        self.merged = 0
        self.staleness_max_seen = 0
        self._staleness_sum = 0.0

    # ------------------------------------------------------------- observers

    @property
    def inflight(self) -> int:
        return len(self._inflight)

    @property
    def pending(self) -> int:
        return len(self._pending)

    @property
    def staleness_mean_seen(self) -> float:
        return self._staleness_sum / max(self.merged, 1)

    # ----------------------------------------------------------------- steps

    def step(self, state, rnd, global_round: int, batch, lr
             ) -> Tuple[Any, Optional[Dict[str, Any]],
                        List[Dict[str, Any]]]:
        """One driver tick: land overdue cohorts, free a pool slot if
        full, apply the scenario fate, dispatch this tick's cohort, and
        land zero-latency arrivals. Returns ``(state, cohort_metrics,
        commit_records)``; ``cohort_metrics`` is None for a dropped
        cohort (no compute happened)."""
        commits: List[Dict[str, Any]] = []
        tick = int(global_round)
        state = self._land_due(state, tick, lr, commits)
        mask_np = np.asarray(rnd.mask)
        fate = (self.scenario.fate(tick, mask_np,
                                   client_ids=rnd.client_ids)
                if self.scenario is not None else None)
        if fate is not None and fate.dropped:
            # decided BEFORE the pool-full wait: a dropped cohort never
            # needs a slot, so it must not force an in-flight cohort to
            # land early (that would skew the measured staleness)
            self.dropped += 1
            return state, None, commits
        while len(self._inflight) >= self.max_inflight:
            # the pool is full: the simulated dispatch waits for the
            # earliest in-flight cohort, exactly like a real bounded
            # upload queue
            state = self._land_earliest(state, lr, commits)
        eff_mask = fate.mask if fate is not None else mask_np
        state, payload = self.runtime.cohort(
            state, rnd.client_ids, batch, eff_mask, lr)
        # crash-matrix kill-point: the pool holds in-flight cohorts and
        # this tick's dispatch just happened — a death here must resume
        # bit-identically (the epoch replays; the buffer was never
        # checkpointed open, see reconcile_resumed_state)
        maybe_fault("async_pool", tick)
        self.dispatched += 1
        latency = float(fate.latency) if fate is not None else 0.0
        bisect.insort(self._inflight,
                      _InFlight(tick, self.commits, tick + latency,
                                payload))
        state = self._land_due(state, tick, lr, commits)
        metrics = {
            "results": payload["results"],
            "n_valid": payload["n_valid"],
            "download_bytes": payload["download_bytes"],
            "upload_bytes": payload["upload_bytes"],
            "signals": None,
            "layer_signals": None,
            "client_stats": payload["client_stats"],
            # robustness channel (core/runtime._cohort_step): the
            # defense-event scalars and the quarantine ledger's
            # per-client finite flags ride the cohort payload — the
            # driver's defense wiring is path-agnostic
            "defense": payload["defense"],
            "client_finite": payload["client_finite"],
            # host-resident effective participation for the ledger (the
            # scenario may have masked slots out of this cohort)
            "participation": (np.asarray(rnd.client_ids),
                              eff_mask.sum(axis=1)),
            # the scenario's per-slot adversary annotation
            # (CohortFate.adversary): the driver's defense event counts
            # injections from the SAME draw the dispatch saw instead of
            # re-deriving it against the ledger's view of the round
            "adversary_slots": (fate.adversary if fate is not None
                                else None),
        }
        return state, metrics, commits

    def flush(self, state, lr) -> Tuple[Any, List[Dict[str, Any]]]:
        """Epoch-boundary drain: land every in-flight cohort (in arrival
        order) and commit whatever the buffer holds — a partial commit
        below ``buffer_goal`` is flagged ``partial`` in its record, so
        no open buffer ever crosses an epoch (or reaches a checkpoint)."""
        commits: List[Dict[str, Any]] = []
        while self._inflight:
            state = self._land_earliest(state, lr, commits)
        if self._pending:
            state, rec = self._commit(state, lr, partial=True)
            commits.append(rec)
        return state, commits

    # -------------------------------------------------------------- internals

    def _land_due(self, state, tick: int, lr, commits) -> Any:
        while self._inflight and self._inflight[0].arrival <= tick:
            state = self._land_earliest(state, lr, commits)
        return state

    def _land_earliest(self, state, lr, commits) -> Any:
        item = self._inflight.pop(0)
        staleness = self.commits - item.version
        weight = staleness_weight(self.discount, staleness, self.alpha)
        if not self._pending and weight == 1.0:
            # empty buffer, weight 1: swap the cohort sum in directly —
            # no arithmetic, the bitwise sync-equivalence path
            state = self.runtime.merge_first(state, item.sum, item.n_total)
        else:
            state = self.runtime.merge(state, item.sum, item.n_total,
                                       weight)
        # the buffer owns (and the next merge/commit donates) these
        # device arrays now — drop the refs so nothing reads a donated
        # buffer later
        item.sum = item.n_total = None
        self.merged += 1
        self._staleness_sum += staleness
        self.staleness_max_seen = max(self.staleness_max_seen, staleness)
        self._pending.append({
            "cohort": item.cohort,
            "staleness": int(staleness),
            "weight": float(weight),
            "loss_ref": (item.results[0], item.n_valid),
        })
        if len(self._pending) >= self.buffer_goal:
            state, rec = self._commit(state, lr, partial=False)
            commits.append(rec)
        return state

    def _commit(self, state, lr, partial: bool
                ) -> Tuple[Any, Dict[str, Any]]:
        state, m = self.runtime.commit(state, lr)
        self.commits += 1
        pend, self._pending = self._pending, []
        st = [p["staleness"] for p in pend]
        ws = [p["weight"] for p in pend]
        rec = {
            "round": self.commits,
            "n_cohorts": len(pend),
            "cohorts": [p["cohort"] for p in pend],
            "staleness_mean": float(np.mean(st)),
            "staleness_max": int(max(st)),
            "discount_mean": float(np.mean(ws)),
            "discount_min": float(min(ws)),
            "partial": bool(partial),
            "buffer_n": m["buffer_n"],        # device scalar refs: sync
            "update_norm": m["update_norm"],  # only at the record cadence
            "error_norm": m["error_norm"],
            "velocity_norm": m["velocity_norm"],
            "loss_refs": [p["loss_ref"] for p in pend],
        }
        return state, rec
