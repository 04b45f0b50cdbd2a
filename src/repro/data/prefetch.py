"""Straggler-tolerant host-side prefetching (DESIGN.md §7).

A background thread keeps a bounded queue of ready batches. ``get`` takes the
next batch; if the producer misses the deadline (slow disk / remote storage /
straggling feature service), the consumer proceeds with the most recent
*backup* batch instead of stalling the whole mesh — bounded staleness, counted
and reported. This is the standard data-echo / backup-batch trick for keeping
thousand-chip steps from being gated on one slow host.

For the fused multi-batch ingest pipeline, ``superbatches``/``stack_batches``
assemble K ``(W, n_valid)`` batches into the superbatch unit
``TriangleCountEngine.ingest_chunk`` consumes in a single dispatch; the
double buffering itself (stage chunk k+1 while chunk k computes) lives in the
consumers (``engine.service.run_stream``, ``engine.ingest_stream``) via
``TriangleCountEngine.stage_chunk``.

Resilience (docs/robustness.md): the producer thread is the
``prefetch.get`` fault site of ``repro.engine.faults`` — a flaky source can
be made to raise (optionally ridden out by a ``RetryPolicy``), stall, or
*redeliver* an item. Every item is tagged with a sequence number on the
producer side and deduplicated on the consumer side, so at-least-once
delivery from the source still yields exactly-once ingestion — an estimator
stream that ingests a replayed batch biases ``m_seen`` forever.
"""
from __future__ import annotations

import queue
import threading
from typing import Callable, Iterable, Iterator, Optional

import numpy as np
from jax.profiler import TraceAnnotation


_DONE = object()  # sentinel distinct from any legitimate batch (even None)


class PrefetchQueue:
    # Thread model, machine-checked by repro-lint RL40x (docs/lint.md): the
    # producer thread owns its delivery/fault counters, the consumer (get)
    # owns the dedup/staleness state; ``q`` is the channel, and ``_error``/
    # ``done`` cross back to the consumer only after the _DONE sentinel is
    # observed (queue put/get gives the happens-before edge).
    _thread_ownership = {
        "producer": {
            "methods": ("_produce", "_source_fault"),
            "attrs": ("redelivered", "retries", "done", "_error"),
        },
        "consumer": {
            "methods": ("get",),
            "attrs": ("backup", "stale_steps", "late_drops",
                      "duplicate_drops", "_last_seq", "_drop_next",
                      "unmatched_standins"),
        },
    }

    def __init__(
        self,
        source: Iterator,
        depth: int = 4,
        deadline_s: Optional[float] = None,
        retry=None,  # Optional[repro.engine.faults.RetryPolicy] for the source
    ):
        self.q: queue.Queue = queue.Queue(maxsize=depth)
        self.deadline_s = deadline_s
        self.retry = retry
        self.backup = None
        self.stale_steps = 0
        self.late_drops = 0  # late batches discarded after a backup stood in
        self.duplicate_drops = 0  # redelivered items deduped by sequence number
        self.redelivered = 0  # items the producer enqueued more than once
        self.retries = 0  # transient source faults ridden out by backoff
        self._last_seq = -1  # newest sequence number delivered to the consumer
        # stand-ins whose awaited item turned out to be end-of-stream (the
        # straggling next() raised StopIteration instead of yielding): the
        # consumer already ingested one batch the source never produced.
        # Unavoidable — at miss time "slow item" and "slow end" are
        # indistinguishable — but recorded so the drift is observable.
        self.unmatched_standins = 0
        self.done = False
        self._drop_next = 0  # pending late items to discard on arrival
        # producer-thread exception, re-raised from get(): without this, a
        # source that crashes mid-stream (e.g. on its ragged final batch)
        # looks exactly like a clean end of stream and the consumer silently
        # truncates — the daemon thread's traceback goes nowhere
        self._error: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._produce, args=(source,), daemon=True
        )
        self._thread.start()

    def _produce(self, source):
        try:
            source, seq = iter(source), 0
            while True:
                with TraceAnnotation("repro.prefetch.produce", seq=seq):
                    item = next(source, _DONE)
                if item is _DONE:
                    break
                kind = self._source_fault()
                self.q.put((seq, item))
                if kind == "duplicate":
                    # at-least-once source: redeliver the same sequence
                    # number; the consumer dedups it in get()
                    self.redelivered += 1
                    self.q.put((seq, item))
                seq += 1
        except BaseException as e:  # noqa: BLE001 — forwarded, not swallowed
            self._error = e
        finally:
            self.done = True
            self.q.put(_DONE)

    def _source_fault(self):
        """Consult the ``prefetch.get`` fault site, riding out transient
        raises with the configured RetryPolicy (producer-side backoff)."""
        # lazy import: repro.data sits below repro.engine in the import graph
        from repro.engine.faults import active_fault_plan, check_fault, with_retries

        if active_fault_plan() is None:
            return None

        def _count(attempt, exc):
            self.retries += 1

        return with_retries(self.retry, check_fault, "prefetch.get", on_retry=_count)

    def get(self):
        """Next batch, or the backup batch on deadline miss (stale += 1).

        A deadline miss substitutes the backup batch *in place of* the late
        one, so when the late item finally lands in the queue it is a
        duplicate the stream already accounted for — it is dropped on
        arrival (``late_drops``). Without the drop the consumer would ingest
        the backup AND later replay the real batch, so the stream position
        (``m_seen``) would drift one batch long per miss.

        At most ONE stand-in per late item: while a dropped-on-arrival item
        is still outstanding, the next ``get`` waits for it without a
        deadline instead of echoing the backup again — consecutive misses
        are all gated on the SAME straggler, and re-echoing would mint
        stand-ins for source items that may not exist (an unbounded drift at
        end of stream). Staleness per source item is therefore bounded by
        one backup batch, and total batches delivered (real + stale) equals
        the source length whenever the awaited item actually arrives. The
        one unfixable corner: a miss whose "late item" turns out to be the
        END of the stream (the final ``next()`` was slow to raise
        StopIteration) has already delivered a stand-in for an item that
        never existed — that +1 drift is counted in ``unmatched_standins``
        (surfaced as ``StreamReport.phantom_batches`` by the service loop).

        Items redelivered by an at-least-once source (the ``duplicate``
        fault kind, or any future real source that replays on reconnect)
        carry an already-seen sequence number and are dropped here
        (``duplicate_drops``) — ingesting one would bias ``m_seen``.
        """
        while True:
            try:
                # no deadline while a late item is outstanding: its stand-in
                # was already delivered, so there is nothing fresh to echo
                timeout = self.deadline_s if not self._drop_next else None
                entry = self.q.get(timeout=timeout)
            except queue.Empty:
                if self.backup is None:
                    entry = self.q.get()  # first batch: nothing to fall back on
                else:
                    self.stale_steps += 1
                    self._drop_next += 1  # the late item is now a duplicate
                    return self.backup, True
            if entry is _DONE:
                if self._error is not None:
                    raise self._error  # producer crashed: not end-of-stream
                if self._drop_next:
                    # the awaited "late item" was actually end-of-stream:
                    # its stand-in counted a batch the source never produced
                    self.unmatched_standins += self._drop_next
                    self._drop_next = 0
                raise StopIteration
            seq, item = entry
            if seq <= self._last_seq:
                # redelivery of an item already handed out (exactly-once dedup)
                self.duplicate_drops += 1
                continue
            self._last_seq = seq
            if self._drop_next:
                # the backup already stood in for this batch — discard it
                self._drop_next -= 1
                self.late_drops += 1
                continue
            self.backup = item
            return item, False

    def backlog(self) -> int:
        """Batches currently queued ahead of the consumer — the service
        loops' backpressure signal (degraded-mode queries kick in when this
        reaches ``ResilienceConfig.backpressure_depth``)."""
        return self.q.qsize()


class TenantQueues:
    """Bounded per-tenant ingest queues for the elastic serving tier
    (``repro.engine.service.ElasticServeLoop``).

    Each resident tenant gets one FIFO capped at ``depth`` batches, so a
    stalled or flooding tenant cannot grow host memory without bound. When a
    queue is full ``put`` applies the overflow ``policy``: ``"drop"``
    discards the NEWEST batch (the arriving one) and counts it in
    ``dropped``; ``"stall"`` refuses it (returns False) and counts the
    refusal in ``stalls`` — the producer owns the retry. Both counters feed
    the serve loop's diag JSON; the consumer side (``take``) dequeues up to
    ``chunk_size`` batches per tick, front-packed for the fused dispatch.

    Thread-safe: producers ``put`` from request threads while the serve
    loop's consumer thread ``take``s. Dropping a batch breaks that tenant's
    exactly-once stream contract by design — it is load shedding, visible in
    ``dropped`` — so accuracy-sensitive producers should run ``"stall"``
    and retry; exactly-once *delivery* (dedup of a flaky source) stays
    ``PrefetchQueue``'s job upstream.
    """

    # Machine-checked by repro-lint RL403 (docs/lint.md): every access to
    # the queue map and shed/stall counters must hold the lock.
    _lock_guarded = ("_queues", "dropped", "stalls")

    def __init__(self, depth: int = 64, policy: str = "drop"):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        if policy not in ("drop", "stall"):
            raise ValueError(f"policy must be 'drop' or 'stall', got {policy!r}")
        self.depth = depth
        self.policy = policy
        self.dropped = 0  # batches shed by the 'drop' policy (newest-first)
        self.stalls = 0  # puts refused by the 'stall' policy (backpressure)
        self._lock = threading.Lock()
        self._queues: dict = {}

    def add_tenant(self, tid) -> None:
        with self._lock:
            self._queues.setdefault(tid, [])

    def remove_tenant(self, tid) -> int:
        """Drop a tenant's queue; returns how many pending batches died
        with it (they were never ingested)."""
        with self._lock:
            return len(self._queues.pop(tid, []))

    def put(self, tid, item) -> bool:
        """Enqueue one ``(W, n_valid)`` batch for ``tid``. Returns False when
        the batch was shed (full queue under 'drop') or refused (full queue
        under 'stall', or unknown tenant)."""
        with self._lock:
            q = self._queues.get(tid)
            if q is None:
                return False
            if len(q) >= self.depth:
                if self.policy == "drop":
                    self.dropped += 1
                else:
                    self.stalls += 1
                return False
            q.append(item)
            return True

    def take(self, tid, k: int = 1) -> list:
        """Dequeue up to ``k`` batches for ``tid`` (oldest first) — one
        front-packed chunk lane for the fused dispatch."""
        with self._lock:
            q = self._queues.get(tid)
            if not q:
                return []
            out, self._queues[tid] = q[:k], q[k:]
            return out

    def backlog(self, tid=None) -> int:
        """Pending batches for one tenant, or total across all tenants —
        the serve loop's backpressure signal for degraded queries."""
        with self._lock:
            if tid is not None:
                return len(self._queues.get(tid, ()))
            return sum(len(q) for q in self._queues.values())

    def tenants(self) -> tuple:
        with self._lock:
            return tuple(self._queues)

    def diag(self) -> dict:
        with self._lock:
            return {
                "queue_depth": self.depth,
                "queue_policy": self.policy,
                "queue_dropped": self.dropped,
                "queue_stalls": self.stalls,
                "queue_backlog": sum(len(q) for q in self._queues.values()),
            }


def stack_batches(
    buf: list, batch_size: Optional[int] = None
) -> tuple[np.ndarray, np.ndarray]:
    """Stack K ``(W, n_valid)`` batches into one superbatch ``(Ws, n_valids)``.

    Single-stream ``(s, 2)`` batches stack to ``(K, s, 2)`` / ``(K,)``;
    per-tenant ``(T, s, 2)`` batches stack to ``(T, K, s, 2)`` / ``(T, K)``.
    ``batch_size`` zero-pads short batches up to ``s`` first (the ``n_valid``
    mask already excludes the padding rows from the update).
    """
    Ws, nvs = [], []
    for W, nv in buf:
        W = np.asarray(W, dtype=np.int32)
        if batch_size is not None and W.shape[-2] < batch_size:
            pad = [(0, 0)] * (W.ndim - 2) + [
                (0, batch_size - W.shape[-2]),
                (0, 0),
            ]
            W = np.pad(W, pad)
        Ws.append(W)
        nvs.append(np.asarray(nv, dtype=np.int32))
    # axis=-3 lands the new K axis after any leading tenant axis
    return np.stack(Ws, axis=-3), np.stack(nvs, axis=-1)


def superbatches(
    batch_iter: Iterable, k: int, batch_size: Optional[int] = None
) -> Iterator:
    """Group a ``(W, n_valid)`` iterator into K-stacked superbatches.

    Yields ``("chunk", (Ws, n_valids))`` for each full group of ``k`` and
    ``("batch", (W, n_valid))`` for the ragged tail — the two unit types
    ``ingest_chunk`` / ``ingest`` consume.
    """
    buf: list = []
    for item in batch_iter:
        buf.append(item)
        if len(buf) == k:
            yield "chunk", stack_batches(buf, batch_size)
            buf = []
    for item in buf:
        yield "batch", item


def work_stealing_shards(
    shard_fns: list[Callable[[], Iterator]],
) -> Iterator:
    """Strict round-robin over per-file shard iterators, dropping a shard
    from the rotation only when it is **exhausted** (``StopIteration``).

    This is *exhaustion-only* skipping, not latency-based work stealing: a
    slow shard is still waited on every rotation (``next()`` blocks), so one
    straggling file gates the merged stream. Wrap the merged iterator in
    ``PrefetchQueue(deadline_s=...)`` for bounded-staleness straggler
    tolerance; this helper only load-balances shard *lengths* (short shards
    leave the rotation early and the rest keep yielding). The pinned
    behavior — interleaving order and blocking on slow shards — is
    ``tests/test_prefetch.py::TestWorkStealing``.
    """
    iters = [fn() for fn in shard_fns]
    live = list(range(len(iters)))
    while live:
        for i in list(live):
            try:
                yield next(iters[i])
            except StopIteration:
                live.remove(i)
