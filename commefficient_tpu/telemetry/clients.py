"""Per-client population statistics: what each of the round's W clients
actually did, without ever shipping a per-client vector off device.

FetchSGD federates a client POPULATION, but until this module only the
population's mean loss and summed bytes left the jitted round — a single
diverging client, a DP clip that saturates for half the cohort, or a
participation skew that starves most of the universe were all invisible
until they surfaced as an aggregate NaN. Two halves close that gap:

- **Device side** (:func:`summarize_per_client`, called inside
  ``FedRuntime._round_step``): per-client scalars — loss, gradient norm
  pre/post clip, clip saturation, update-contribution norm, exact bytes
  — are reduced along the existing client vmap axis to quantile
  summaries (p5/p25/p50/p75/p95/max/mean + argmax slot). Only those
  scalars ride the round's async metrics fetch, so the JSONL cost is
  independent of ``num_workers`` and there is no extra host sync.
  Everything is gated exactly like signals.py: computed only when a
  telemetry stream exists to read it (``FedRuntime._client_stats``), and
  compiled out entirely under ``--no_telemetry`` / ``--no_client_stats``
  (identity-tested in tests/test_clients.py).

- **Host side** (:class:`ParticipationLedger`): per-client sample
  counts, coverage fraction and staleness, accumulated from the
  sampler's (host-resident) ``client_ids``/``mask`` every round — no
  device traffic — and snapshotted into the same schema-v3
  ``client_stats`` event at the record cadence.

NaN means "not applicable for this mode/path" (e.g. per-client gradient
norms under the fused-clients fast path, where no per-client gradient
ever materializes) and serializes as JSON null — never silently zero,
the signals.py convention.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import numpy as np

# per-client scalars the CLIENT step can produce (core/client.py); the
# round adds "loss" (results[0] is already per-client) and, under
# --track_bytes, the exact per-slot byte costs
CLIENT_GRAD_KEYS = ("grad_norm_pre", "grad_norm_post", "clip_frac",
                    "tx_norm")
CLIENT_STAT_KEYS = ("loss",) + CLIENT_GRAD_KEYS + ("upload_bytes",
                                                   "download_bytes")
QUANTILE_PCTS = (5.0, 25.0, 50.0, 75.0, 95.0)
QUANTILE_FIELDS = ("p5", "p25", "p50", "p75", "p95", "max", "mean")


def summarize_per_client(per_client: Dict[str, Any], n_valid: Any,
                         replicate_fn=None) -> Dict[str, Dict[str, Any]]:
    """On-device quantile reduction of per-client (W,) stat vectors.

    Traced inside the jitted round step. Slots whose client processed no
    valid datum (fully-padded rounds) are excluded via NaN-masking;
    stats that arrive as NaN (not applicable) stay NaN through the
    quantiles. Returns ``{key: {"q": (5,) array, "max": (), "mean": (),
    "argmax": () int}}`` — the host maps ``argmax`` (a round SLOT) to a
    real client id via the round's ``client_ids``.

    Every stat is stacked into ONE (K, W) matrix before the reduction,
    and on a mesh the runtime passes ``replicate_fn`` (a sharding
    constraint to replicated): one W-sized all-gather covers the whole
    summary, instead of per-key quantile reductions each lowering to
    their own cross-device collectives (measured: ~30 extra tiny
    all-reduces per round without this — the very launch-count
    pathology the collective ledger exists to catch).
    """
    import jax.numpy as jnp

    keys = sorted(per_client)
    mat = jnp.stack([jnp.asarray(per_client[k], jnp.float32)
                     for k in keys])                       # (K, W)
    valid = jnp.asarray(n_valid) > 0
    if replicate_fn is not None:
        mat = replicate_fn(mat)
        valid = replicate_fn(valid)
    masked = jnp.where(valid[None, :], mat, jnp.nan)
    finite = valid[None, :] & jnp.isfinite(mat)
    pcts = jnp.asarray(QUANTILE_PCTS, jnp.float32)
    q = jnp.nanpercentile(masked, pcts, axis=1)            # (5, K)
    mx = jnp.nanmax(masked, axis=1)
    mean = jnp.nanmean(masked, axis=1)
    # argmax over valid finite entries only; meaningless (and nulled by
    # the host conversion) when max itself is NaN
    arg = jnp.argmax(jnp.where(finite, mat, -jnp.inf), axis=1)
    return {k: {"q": q[:, i].astype(jnp.float32),
                "max": mx[i].astype(jnp.float32),
                "mean": mean[i].astype(jnp.float32),
                "argmax": arg[i]}
            for i, k in enumerate(keys)}


def client_stats_to_host(summary: Optional[Dict[str, Dict[str, Any]]],
                         client_ids) -> Dict[str, Dict[str, Any]]:
    """Fetch a device summary (the caller has synced the metrics pytree)
    into the ``quantiles`` dict of a ``client_stats`` event: every key
    maps to {p5,...,p95,max,mean,argmax_client}, non-finite -> None."""
    if not summary:
        return {}
    # ONE batched device->host fetch of the whole pytree: the per-field
    # float() conversions below would otherwise each issue their own
    # synchronous transfer (~50 per event). (A plain-numpy summary
    # passes through device_get unchanged.)
    import jax
    summary = jax.device_get(summary)
    ids = np.asarray(client_ids)

    def fin(x) -> Optional[float]:
        x = float(np.asarray(x))
        return x if np.isfinite(x) else None

    out: Dict[str, Dict[str, Any]] = {}
    for key, s in summary.items():
        q = np.asarray(s["q"], np.float64)
        rec: Dict[str, Any] = {
            name: fin(q[i]) for i, name in enumerate(
                ("p5", "p25", "p50", "p75", "p95"))}
        rec["max"] = fin(s["max"])
        rec["mean"] = fin(s["mean"])
        slot = int(np.asarray(s["argmax"]))
        rec["argmax_client"] = (int(ids[slot])
                                if rec["max"] is not None
                                and 0 <= slot < len(ids) else None)
        out[key] = rec
    return out


def quantiles_ordered(rec: Dict[str, Any]) -> bool:
    """p5 <= p25 <= ... <= p95 <= max over the non-null fields of one
    stat's quantile record — the dryrun/test sanity predicate."""
    seq = [rec.get(k) for k in ("p5", "p25", "p50", "p75", "p95", "max")]
    seq = [v for v in seq if v is not None]
    return all(a <= b + 1e-9 for a, b in zip(seq, seq[1:]))


class ParticipationLedger:
    """Host-side participation accounting for the client universe.

    ``observe`` is called every round with the sampler's host-resident
    ``client_ids`` and per-slot valid-datum counts (no device fetch);
    ``snapshot`` folds the ledger into the participation fields of a
    ``client_stats`` event: coverage (distinct participants over the
    universe), per-seen-client sample-count quantiles, and staleness
    (rounds since each seen client last participated).
    """

    estimated = False

    def __init__(self, num_clients: int):
        self.num_clients = max(int(num_clients), 1)
        self._samples: Dict[int, float] = {}
        self._last_round: Dict[int, int] = {}
        self._loss_wins: Dict[int, float] = {}
        self._strikes: Dict[int, float] = {}
        from commefficient_tpu.telemetry.population import P2Quantile
        self._p2 = {"obs_count_p50": P2Quantile(0.50),
                    "obs_count_p95": P2Quantile(0.95),
                    "gap_p50": P2Quantile(0.50),
                    "gap_p95": P2Quantile(0.95)}

    def observe(self, rnd: int, client_ids, samples_per_slot=None) -> None:
        # zero-sample slots did not participate: the async scenario
        # engine's partial-participation masking zeroes whole slots
        # (data/scenarios.py), and crediting them would reset the
        # client's staleness without it having contributed anything.
        # Sync rounds never produce these (the sampler only yields
        # slots with data). _aggregate drops them, dedups repeated ids
        # within the batch and returns ascending unique ids — the bulk
        # form of the old per-slot loop (equivalence pinned in
        # tests/test_population.py).
        from commefficient_tpu.telemetry.population import _aggregate
        uniq, sums = _aggregate(client_ids, samples_per_slot)
        rnd = int(rnd)
        for c, n in zip(uniq.tolist(), sums.tolist()):
            c = int(c)
            prev = self._last_round.get(c)
            if prev is not None:
                self._p2["gap_p50"].add(rnd - prev)
                self._p2["gap_p95"].add(rnd - prev)
            self._samples[c] = self._samples.get(c, 0.0) + float(n)
            self._last_round[c] = rnd
            self._p2["obs_count_p50"].add(n)
            self._p2["obs_count_p95"].add(n)

    def observe_loss_argmax(self, client_id: Optional[int]) -> None:
        """One round's highest-loss client (the client_stats
        quantiles[...]["argmax_client"] channel); weight 1 per round."""
        if client_id is not None:
            c = int(client_id)
            self._loss_wins[c] = self._loss_wins.get(c, 0.0) + 1.0

    def observe_strikes(self, client_ids: Sequence[int]) -> None:
        """Quarantine strikes this round (core/quarantine.py ledger)."""
        for c in client_ids:
            c = int(c)
            self._strikes[c] = self._strikes.get(c, 0.0) + 1.0

    @property
    def distinct(self) -> int:
        return len(self._samples)

    def state_dict(self) -> Dict[str, Any]:
        """JSON-serializable ledger state for checkpoint meta: a resumed
        run keeps its coverage/staleness view of the universe instead of
        reporting coverage ~0 until every client is re-seen."""
        return {
            "samples": {str(c): n for c, n in self._samples.items()},
            "last_round": {str(c): r
                           for c, r in self._last_round.items()},
            "loss_wins": {str(c): n for c, n in self._loss_wins.items()},
            "strikes": {str(c): n for c, n in self._strikes.items()},
            "p2": {k: v.state_dict() for k, v in self._p2.items()},
        }

    def load_state_dict(self, d: Dict[str, Any]) -> None:
        if d and d.get("sketch"):
            raise ValueError(
                "checkpoint ledger sidecar holds SKETCH participation "
                "state (--population_sketch on) but this run uses the "
                "exact ledger; resume with the ledger mode the "
                "checkpoint was written under (or drop the sidecar to "
                "start coverage accounting fresh)")
        self._samples = {int(c): float(n)
                         for c, n in (d.get("samples") or {}).items()}
        self._last_round = {int(c): int(r)
                            for c, r in (d.get("last_round") or {}).items()}
        # pre-v11 sidecars legitimately lack the heavy-hitter / P2 keys
        self._loss_wins = {int(c): float(n)
                           for c, n in (d.get("loss_wins") or {}).items()}
        self._strikes = {int(c): float(n)
                         for c, n in (d.get("strikes") or {}).items()}
        for k, v in (d.get("p2") or {}).items():
            if k in self._p2:
                self._p2[k].load_state_dict(v)

    def snapshot(self, rnd: int) -> Dict[str, Any]:
        if not self._samples:
            return {"coverage": 0.0, "distinct_clients": 0,
                    "counts_p50": None, "counts_max": None,
                    "staleness_p50": None, "staleness_max": None,
                    "estimated": False}
        counts = np.fromiter(self._samples.values(), np.float64)
        stale = np.asarray([rnd - lr for lr in self._last_round.values()],
                           np.float64)
        return {
            "coverage": len(counts) / self.num_clients,
            "distinct_clients": int(len(counts)),
            "counts_p50": float(np.percentile(counts, 50)),
            "counts_max": float(counts.max()),
            "staleness_p50": float(np.percentile(stale, 50)),
            "staleness_max": float(stale.max()),
            "estimated": False,
        }

    def memory_bytes(self) -> int:
        """Resident-footprint model: ~76B per dict entry (int key +
        float value + slot), 4 dicts — O(population), which is exactly
        why :mod:`~commefficient_tpu.telemetry.population` exists."""
        n = (len(self._samples) + len(self._last_round)
             + len(self._loss_wins) + len(self._strikes))
        return n * 76 + 4 * 256

    def population_snapshot(self, rnd: int) -> Dict[str, Any]:
        """The schema-v11 ``population`` event body — same fields as
        PopulationLedger.population_snapshot, exact values, sketch
        parameters null, ``estimated: False``. The obs_count/gap
        quantiles are P2 estimates in BOTH modes (the per-participation
        streams are unbounded); everything else here is exact."""
        def top10(d: Dict[int, float]):
            order = sorted(d, key=lambda c: (-d[c], c))[:10]
            return [[int(c), float(d[c])] for c in order]

        base = self.snapshot(rnd)
        have = bool(self._samples)
        counts = (np.fromiter(self._samples.values(), np.float64)
                  if have else None)
        stale = (np.asarray([rnd - lr
                             for lr in self._last_round.values()],
                            np.float64) if have else None)
        return {
            "round": int(rnd),
            "estimated": False,
            "registered": self.num_clients,
            "distinct": float(len(self._samples)),
            "coverage": base["coverage"],
            "counts_p50": base["counts_p50"],
            "counts_p95": (float(np.percentile(counts, 95))
                           if have else None),
            "counts_max": base["counts_max"],
            "staleness_p50": base["staleness_p50"],
            "staleness_p95": (float(np.percentile(stale, 95))
                              if have else None),
            "staleness_max": base["staleness_max"],
            "obs_count_p50": self._p2["obs_count_p50"].value(),
            "obs_count_p95": self._p2["obs_count_p95"].value(),
            "gap_p50": self._p2["gap_p50"].value(),
            "gap_p95": self._p2["gap_p95"].value(),
            "top_sampled": top10(self._samples),
            "top_loss": top10(self._loss_wins),
            "top_strikes": top10(self._strikes),
            "memory_bytes": float(self.memory_bytes()),
            "cm_epsilon": None,
            "cm_delta": None,
            "hh_k": None,
            "sample_size": None,
        }


def make_ledger(num_clients: int, population_sketch: str = "auto", *,
                seed: int = 0):
    """Ledger construction policy for the drivers: ``auto`` uses the
    exact ledger below :data:`~commefficient_tpu.telemetry.population.
    AUTO_SKETCH_THRESHOLD` registered clients and the bounded-memory
    sketch ledger at/above it; ``on``/``off`` force the choice. Both
    ledgers emit identical event fields; only ``estimated`` differs."""
    from commefficient_tpu.telemetry.population import (
        AUTO_SKETCH_THRESHOLD, PopulationLedger)
    if population_sketch not in ("auto", "on", "off"):
        raise ValueError(f"population_sketch must be auto|on|off, "
                         f"got {population_sketch!r}")
    sketch = (population_sketch == "on"
              or (population_sketch == "auto"
                  and int(num_clients) >= AUTO_SKETCH_THRESHOLD))
    if sketch:
        return PopulationLedger(num_clients, seed=seed)
    return ParticipationLedger(num_clients)
