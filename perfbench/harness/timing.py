"""The timing loop: chunks of rounds through the live input path.

A chunk is ``sync_every`` rounds dispatched back to back — each round's
input taken from the program's ``RoundPipeline`` over a ``FedSampler`` over
the ``DeviceStore``, as ``cv_train.train`` does, a new sampler and pipeline
per epoch — and ended by ``jax.block_until_ready(state)`` plus a host read
of the round's metrics, which is what the shipped driver does every
``telemetry_every`` rounds. ``round_ms`` is the median over chunks of chunk
wall time over rounds in the chunk, on ``time.perf_counter``.

(The arithmetic of ``bench_common.timed_rounds`` — chained rounds, one
barrier, dispatch / wait split — copied here so that the yardstick does not
move with the program; PR 21 showed ``block_until_ready`` is a true barrier
on this runtime.)
"""

from __future__ import annotations

import contextlib
import time

import numpy as np


class RoundSource:
    """Endless stream of ``RoundInput`` items, epoch after epoch."""

    def __init__(self, dataset, store, cfg, seed):
        import jax
        self.dataset, self.store, self.cfg, self.seed = (dataset, store,
                                                         cfg, seed)
        self.data_key = jax.random.PRNGKey(seed ^ 0xDA7A)
        self.global_round = 0
        self.epoch = 0
        self.epochs_started = 0
        self._pipe = None

    def _fetch(self, rnd, g_round):
        import jax
        return self.store.round_batch(
            rnd.idx, jax.random.fold_in(self.data_key, g_round))

    def _open(self):
        from commefficient_tpu.core import RoundPipeline
        from commefficient_tpu.data import FedSampler
        cfg = self.cfg
        sampler = FedSampler(self.dataset.data_per_client, cfg.num_workers,
                             cfg.local_batch_size,
                             max_client_batch=cfg.max_client_batch,
                             seed=self.seed + 7919 * self.epoch)
        self._pipe = RoundPipeline(
            sampler, self._fetch, start_round=self.global_round,
            max_rounds=max(sampler.epoch_rounds(), 1),
            depth=cfg.prefetch_depth, enabled=cfg.pipeline)
        self.epochs_started += 1

    def __next__(self):
        for _ in range(3):
            if self._pipe is None:
                self._open()
            try:
                item = next(self._pipe)
            except StopIteration:
                self._pipe.close()
                self._pipe = None
                self.epoch += 1
                continue
            self.global_round = item.global_round
            return item
        raise RuntimeError("the sampler yields no round: fewer clients "
                           "with data than --num_workers?")

    def close(self):
        if self._pipe is not None:
            self._pipe.close()
            self._pipe = None


def _loss_of(metrics):
    """Datum-weighted mean loss of a round, read on the host."""
    res0 = np.asarray(metrics["results"][0], np.float64)
    nv = np.asarray(metrics["n_valid"], np.float64)
    return float((res0 * nv).sum() / max(nv.sum(), 1.0))


class Loop:
    """Holds the run's state between warm-up, window and traced stretch."""

    def __init__(self, runtime, state, source, lr, sync_every):
        self.runtime, self.state, self.source = runtime, state, source
        self.lr, self.sync_every = lr, int(sync_every)
        self.last_metrics = None
        self.failed = 0

    def chunk(self, rounds, annotate=None):
        """Run ``rounds`` rounds and sync. Returns the chunk's record."""
        import jax
        ann = annotate or (lambda name: contextlib.nullcontext())
        wait_s = fetch_s = dispatch_s = 0.0
        t0 = time.perf_counter()
        for _ in range(rounds):
            ta = time.perf_counter()
            with ann("bench:fetch"):
                item = next(self.source)
            tb = time.perf_counter()
            with ann("bench:dispatch"):
                self.state, metrics = self.runtime.round(
                    self.state, item.rnd.client_ids, item.batch,
                    item.rnd.mask, self.lr)
            tc = time.perf_counter()
            wait_s += item.wait_s
            fetch_s += tb - ta
            dispatch_s += tc - tb
        with ann("bench:sync"):
            ts = time.perf_counter()
            jax.block_until_ready(self.state)
            loss = _loss_of(metrics)
        t1 = time.perf_counter()
        self.last_metrics = metrics
        if not np.isfinite(loss):
            self.failed += rounds
        return {"rounds": rounds, "wall_s": t1 - t0, "wait_s": wait_s,
                "fetch_s": fetch_s, "dispatch_s": dispatch_s,
                "sync_s": t1 - ts, "loss": loss}

    def window(self, seconds):
        """Whole chunks until ``seconds`` have passed."""
        chunks = []
        t_end = time.perf_counter() + seconds
        while time.perf_counter() < t_end:
            chunks.append(self.chunk(self.sync_every))
        return chunks


def summarize(chunks):
    per_round = np.array([c["wall_s"] / c["rounds"] for c in chunks])
    rounds = sum(c["rounds"] for c in chunks)
    tot = lambda k: sum(c[k] for c in chunks)
    return {
        "round_ms": float(np.median(per_round) * 1e3),
        "round_ms_mean": float(tot("wall_s") / rounds * 1e3),
        "round_ms_max_chunk": float(per_round.max() * 1e3),
        "round_ms_min_chunk": float(per_round.min() * 1e3),
        "chunks": len(chunks), "rounds": rounds,
        "input_wait_ms": float(tot("wait_s") / rounds * 1e3),
        "fetch_call_ms": float(tot("fetch_s") / rounds * 1e3),
        "dispatch_ms": float(tot("dispatch_s") / rounds * 1e3),
        "sync_ms_per_chunk": float(tot("sync_s") / len(chunks) * 1e3),
        "first_loss": chunks[0]["loss"], "last_loss": chunks[-1]["loss"],
    }
