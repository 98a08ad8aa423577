"""Round input pipeline: prefetch round t+1's host work while round t runs.

Why this exists
---------------
The shared driver loop (cv_train.train) was fully synchronous per round:
assemble the batch (host gather or DeviceStore dispatch), dispatch the
round, and — at the record cadence — block on the metrics. The span/
utilization telemetry built in PRs 3-4 measured the consequence: on any
config whose input path does real host work (ImageNet's host gather, the
PERSONA pack, any no-DeviceStore fallback), ``input_wait_frac`` charges
the whole fetch to the round's critical path even though the device is
idle-waiting the entire time. The fix is the classic input pipeline: a
background thread runs ahead of the compute loop by ``depth`` rounds, so
round t+1's gather/``device_put`` overlaps round t's device execution and
the consumer's wait collapses to (ideally) zero.

Determinism contract
--------------------
Pipelining MUST NOT change what trains — ``__graft_entry__.
dryrun_multichip`` asserts bit-identical per-round losses pipelined vs
not. That holds because nothing the worker does depends on *when* it
runs:

- the sampler is iterated only by the worker (or only inline), in round
  order, so its RandomState draws are identical either way;
- per-round data augmentation randomness derives from the round index
  (``jax.random.fold_in(data_key, global_round)`` — split ahead of time,
  stateless), never from shared mutable RNG touched by two threads;
- host-transform RNGs (e.g. CifarTrain's) advance once per gather in
  round order on a single thread, exactly like the inline path;
- the jitted round consumes the same arrays in the same order — the
  pipeline never reorders or drops rounds.

``enabled=False`` (the ``--no_pipeline`` escape hatch) runs the same
fetch inline on the caller's thread: one code path builds the
:class:`RoundInput`, so the two modes differ only in *where* the fetch
runs. The jitted round step itself never sees the flag — the compiled
HLO is identical either way (pinned by tests/test_pipeline.py, the same
zero-cost-when-off contract as signals/client_stats).

Failure semantics
-----------------
An exception inside the worker's fetch is captured and re-raised on the
consumer's next ``__next__`` — the driver's existing abort/cleanup paths
fire exactly as if the fetch had been inline. ``close()`` (idempotent;
also the context-manager exit) stops the worker, drains the queue so a
blocked put wakes, and joins the thread — no leaked threads, asserted by
the tests. The worker is a daemon as a last-ditch guard: a fetch hung in
foreign code cannot wedge interpreter shutdown.

Span accounting
---------------
The worker wraps each fetch in the existing ``data_fetch`` span (the
true cost of the input path, now off the critical path); the consumer's
queue wait is the new ``data_wait`` span and is what the driver reports
as the round's ``host_s`` — so ``utilization.input_wait_frac`` measures
what the loop actually *waited*, while the ``data_fetch`` spans keep the
input path's real cost visible in the teleview timeline. Overlap shows
up as data_fetch spans (worker tid) running under round dispatch spans
(main tid).

Both threads say which global round their work is for
(``tracing.set_round``): the worker before it fetches round g, the
consumer when it is asked for round g, so the ``data_fetch``, the
``data_wait`` and the runtime's ``round_dispatch`` of one round carry the
same ``round`` in the tracer. What an epoch's turnover costs the loop is
``pipeline_close`` (drain and join), ``pipeline_open`` (the constructor,
thread start included) and the first round's ``data_wait``, which finds
the queue empty. The ``data_wait`` span of a round handed out carries
``ready``: whether its batch was already queued when asked for (the call
that meets the end-of-epoch sentinel hands out no round and has no mark;
the inline path has no ``data_wait``, so none is ready). The call that
meets the sentinel is work for the next epoch's first round and says so;
once the epoch is exhausted or closed the thread is in no round (None)
until the next ``pipeline_open``.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Iterable, Iterator, NamedTuple, Optional

from commefficient_tpu.telemetry import tracing

# queue message kinds (worker -> consumer)
_ITEM, _DONE, _ERR = "item", "done", "err"


class RoundInput(NamedTuple):
    """One prefetched round, as the driver loop consumes it."""

    rnd: Any            # the sampler's Round (client_ids, idx, mask)
    global_round: int   # 1-based global round index (rng/schedule key)
    batch: Any          # batch pytree (device arrays once dispatched)
    wait_s: float       # seconds the CONSUMER waited for this input —
                        # the round's true input-starvation time
    fetch_s: float      # seconds the fetch itself took (worker wall)


class RoundPipeline:
    """Iterator of :class:`RoundInput` over one epoch's sampler.

    Parameters
    ----------
    rounds : iterable of sampler rounds (consumed on the worker thread
        when enabled, inline otherwise — never both).
    fetch : ``fetch(rnd, global_round) -> batch``. Must derive any
        randomness from ``global_round`` (or advance a private RNG once
        per call) — see the module determinism contract.
    start_round : global round counter BEFORE this epoch; yielded rounds
        are numbered ``start_round + 1 ...``.
    max_rounds : stop after this many rounds (the fractional-epoch cap);
        None = run the sampler out.
    depth : prefetch queue bound. ``depth=2`` double-buffers: one batch
        in flight to the device, one staged behind it.
    enabled : False = inline fetch on the caller's thread (identical
        outputs, zero threads — the ``--no_pipeline`` path).
    skip : consume (but never fetch) the first ``skip`` sampler rounds —
        the round-granular resume path: a run checkpointed ``skip``
        rounds into an epoch rebuilds the SAME ``(seed, epoch)`` sampler
        and fast-forwards past the rounds it already trained. The
        sampler's RandomState draws replay identically (it is iterated
        in order either way) and index-keyed fetch randomness is
        untouched, so the first yielded round is bit-identical to what
        the uninterrupted run would have trained next. Counted against
        ``max_rounds`` (the cap is the epoch's ABSOLUTE round index).
    """

    def __init__(self, rounds: Iterable, fetch: Callable[[Any, int], Any],
                 *, start_round: int, max_rounds: Optional[int] = None,
                 depth: int = 2, enabled: bool = True, skip: int = 0):
        self._rounds = iter(rounds)
        self._fetch = fetch
        self._start = int(start_round)
        self._max = max_rounds if max_rounds is None else int(max_rounds)
        if skip < 0:
            raise ValueError(f"skip must be >= 0, got {skip}")
        self._skip = int(skip)
        self.rounds_out = 0  # rounds handed out
        if enabled and depth < 1:
            # this used to silently degrade to the inline fetch — a
            # caller asking for prefetch got none and no message. The
            # config layer rejects it too (FedConfig.__post_init__);
            # this guard covers direct constructions.
            raise ValueError(
                f"RoundPipeline(depth={depth}) with enabled=True: the "
                "prefetcher needs a queue bound >= 1 (2 = double-"
                "buffered). Pass depth >= 1, or enabled=False for the "
                "inline fetch.")
        self.threaded = bool(enabled)
        self._exhausted = False
        self._thread: Optional[threading.Thread] = None
        # opening the epoch is work for its first round
        tracing.set_round(self._next_round())
        with tracing.span("pipeline_open"):
            if self.threaded:
                self._q: queue.Queue = queue.Queue(
                    maxsize=max(int(depth), 1))
                self._stop = threading.Event()
                self._thread = threading.Thread(
                    target=self._worker, name="round-prefetch", daemon=True)
                self._thread.start()
            else:
                self._inline = self._inline_iter()

    # ------------------------------------------------------------ iteration

    def __iter__(self) -> Iterator[RoundInput]:
        return self

    def _next_round(self) -> int:
        """The global round the next ``__next__`` hands out (rounds come
        in the sampler's order, none dropped)."""
        return self._start + self._skip + self.rounds_out + 1

    def __next__(self) -> RoundInput:
        if self._exhausted:
            raise StopIteration
        tracing.set_round(self._next_round())
        if not self.threaded:
            try:
                item = next(self._inline)
            except StopIteration:
                self._exhausted = True
                tracing.set_round(None)
                raise
            self.rounds_out += 1
            return item
        ready = not self._q.empty()
        t0 = time.perf_counter()
        with tracing.span("data_wait") as waited:
            kind, payload = self._q.get()
            if kind is _ITEM:
                waited.set(ready=ready)
        wait = time.perf_counter() - t0
        if kind is _ERR:
            self._exhausted = True
            self.close()
            raise payload
        if kind is _DONE:
            self._exhausted = True
            self.close()
            raise StopIteration
        self.rounds_out += 1
        return payload._replace(wait_s=wait)

    def _inline_iter(self) -> Iterator[RoundInput]:
        for i, rnd in enumerate(self._rounds):
            if self._max is not None and i >= self._max:
                return
            if i < self._skip:
                continue          # already-trained round: advance the
            g = self._start + i + 1  # sampler, fetch nothing
            t0 = time.perf_counter()
            with tracing.span("data_fetch"):
                batch = self._fetch(rnd, g)
            dt = time.perf_counter() - t0
            # inline, the wait IS the fetch — host_s keeps its pre-
            # pipeline meaning on the --no_pipeline path
            yield RoundInput(rnd, g, batch, dt, dt)

    # --------------------------------------------------------------- worker

    def _worker(self) -> None:
        try:
            for i, rnd in enumerate(self._rounds):
                if self._max is not None and i >= self._max:
                    break
                if self._stop.is_set():
                    return
                if i < self._skip:
                    continue      # resume fast-forward (see class doc)
                g = self._start + i + 1
                tracing.set_round(g)
                t0 = time.perf_counter()
                with tracing.span("data_fetch"):
                    batch = self._fetch(rnd, g)
                item = RoundInput(rnd, g, batch, 0.0,
                                  time.perf_counter() - t0)
                if not self._put((_ITEM, item)):
                    return          # close() requested mid-epoch
        except BaseException as e:   # noqa: BLE001 — relayed, not swallowed
            self._put((_ERR, e))
            return
        self._put((_DONE, None))

    def _put(self, msg) -> bool:
        """Bounded put that a concurrent close() can always unwedge."""
        while not self._stop.is_set():
            try:
                self._q.put(msg, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    # ------------------------------------------------------------- shutdown

    def close(self, join_timeout: float = 30.0) -> None:
        """Stop the worker and reclaim the thread. Idempotent; safe from
        any driver exit path (normal exhaustion, break, abort return,
        exception unwind). Prefetched-but-unconsumed batches are simply
        dropped. NOTE: fetching them may already have advanced a
        STATEFUL host-transform RNG past the consumed prefix (index-
        keyed randomness is unaffected) — harmless for the driver, which
        only closes early on paths that stop training (abort, --test) or
        at the epoch boundary after consuming every round; do not close
        a pipeline mid-stream and keep fetching from the same dataset
        expecting inline-identical augmentation draws."""
        if self._thread is not None:
            with tracing.span("pipeline_close"):
                self._stop.set()
                # drain so a worker blocked in put() observes the stop event
                while True:
                    try:
                        self._q.get_nowait()
                    except queue.Empty:
                        break
                self._thread.join(timeout=join_timeout)
            if self._thread.is_alive():  # pragma: no cover — hung fetch
                import sys
                print("WARNING: round-prefetch thread did not join within "
                      f"{join_timeout}s (fetch hung?); left as daemon",
                      file=sys.stderr)
            self._thread = None
        tracing.set_round(None)  # what follows is for no round

    def __enter__(self) -> "RoundPipeline":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

