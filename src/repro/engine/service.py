"""Stream service loop: engine + prefetch + checkpoints + rolling queries.

``run_stream`` is the production ingestion loop every driver shares. It is
plan-agnostic: the engine owns device placement, so the same loop drives a
one-device bank, a shardmap single stream, or a tenant-sharded mesh bank
(docs/scaling.md) without a branch.

Pipeline
--------
  * batches flow through ``repro.data.prefetch.PrefetchQueue`` so host-side
    generation/IO overlaps device compute (with the backup-batch straggler
    fallback disabled by default — estimator streams must not replay edges,
    so no deadline is set unless the caller opts in);
  * with ``engine.config.chunk_size = K > 1`` the loop assembles K-batch
    superbatches and double-buffers their device upload behind the in-flight
    chunk's compute; reports and checkpoints then land at chunk granularity,
    while ``engine.step`` keeps counting batches;
  * ``report_every`` invokes ``on_report(step, estimates, edges_seen)``
    mid-stream with the rolling per-tenant estimates — ONE batched
    multi-tenant query per report step. On sharded plans that query runs
    device-resident (per-shard partial reductions + fixed-order combine; see
    "Device-resident queries" in ``docs/scaling.md``), so serving never
    gathers the bank to host; and because the engine caches the answer per
    step, every further query at the same step — ``estimate_tenant`` calls
    from a callback, the interactive loop in ``launch.stream_serve``, the
    final post-stream report — is a cache hit, not a second dispatch.

Resilience (docs/robustness.md)
-------------------------------
Both loops take a ``ResilienceConfig``. By default every batch is validated
(self-loops, negative/out-of-range ids, sign mixing) and a poisoned batch is
*quarantined* to a dead-letter buffer — one bad producer record must not
kill a serving loop. Transient ingest/stage faults are ridden out with
bounded exponential backoff (``with_retries``); retry exhaustion propagates,
because at that point the safest state is the last checkpoint. Report
queries degrade instead of dying: a timed-out/faulted device dispatch falls
back to the gather oracle inside ``engine.estimate``, and when the prefetch
backlog passes ``backpressure_depth`` the loop answers from the engine's
estimate cache — stale, tagged with its age — rather than spending device
time the ingest path needs.

Checkpoint / resume contract
----------------------------
The engine snapshot (see "Snapshot format" in ``repro.engine.engine``) is
saved every ``ckpt_every`` batches plus once at the end, through
``repro.train.checkpoint.CheckpointManager`` (atomic manifest, checksums,
keep-k, async) with metadata {config_hash, r, batch, tenants, source_pos}.
On start the loop walks the saved snapshots newest-first and restores the
first one that *verifies* — torn or bit-corrupt checkpoints are counted
(``diag.ckpt_corrupt_skipped``) and skipped, never restored. It then
*skips* the already-consumed prefix of the iterator: ``source_pos`` records
the stream position in SOURCE items (ingested + quarantined), so resume
stays exact even when poisoned batches were quarantined mid-stream. That
skip counts whole batches, which is why auto-resume refuses a changed
``batch_size`` (the skip would mis-position the stream) even though
``engine.restore`` itself is batch-size independent. Everything else may
change between runs: mesh shape, execution plan, chunk size. A killed run
continues bit-for-bit thanks to the counter-based RNG (batch ``i`` always
folds ``i`` into the root key, regardless of which process replays it) —
the kill-point chaos matrix in ``tests/test_faults.py`` proves the final
state matches an unfaulted run exactly (``m_seen``/``dyn_step`` included).
"""
from __future__ import annotations

import concurrent.futures
import inspect
import queue as queue_mod
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable, Optional

import numpy as np
from jax.profiler import TraceAnnotation

from repro.data.prefetch import PrefetchQueue, TenantQueues, superbatches
from repro.engine.engine import SnapshotMismatch, TriangleCountEngine
from repro.engine.faults import (
    DeadLetterBuffer,
    ResilienceConfig,
    validate_batch,
    validate_signed_item,
    with_retries,
)
from repro.train.checkpoint import CheckpointCorrupt, CheckpointManager, config_hash


@dataclass
class StreamReport:
    """What one run_stream() call did (host-side accounting)."""

    batches: int = 0  # batches ingested by THIS call (excludes resumed ones)
    edges: int = 0  # max over tenants of edges ingested by this call
    seconds: float = 0.0
    resumed_from: int = 0  # engine step restored from a checkpoint, 0 if fresh
    stale_batches: int = 0
    # stale stand-ins whose awaited late batch turned out to be end-of-stream
    # (the source never produced it): m_seen ran this many batches long —
    # see PrefetchQueue.get; 0 whenever the stream ends with a real batch
    phantom_batches: int = 0
    queries: int = 0  # batched multi-tenant report queries answered mid-stream
    # -- resilience accounting (docs/robustness.md) -------------------------
    retries: int = 0  # ingest/stage attempts retried after transient faults
    quarantined_batches: int = 0  # invalid batches diverted to dead letters
    duplicate_batches: int = 0  # redelivered batches deduped by seq number
    degraded_queries: int = 0  # report queries answered from the stale cache
    max_staleness: int = 0  # worst stale-answer age, in ingest batches
    query_fallbacks: int = 0  # device queries that degraded to the gather oracle
    dead_letters: Optional[DeadLetterBuffer] = field(default=None, repr=False)

    @property
    def edges_per_s(self) -> float:
        return self.edges / self.seconds if self.seconds > 0 else 0.0


QueryCallback = Callable[[int, np.ndarray, np.ndarray], None]
# (answer_step, per-tenant estimates, per-tenant edges_seen) -> None.
# A callback may additionally declare a ``stale_age`` keyword parameter: it
# receives 0 for fresh answers and the answer's age in ingest batches when
# the loop served a cached (degraded) answer under backpressure — in that
# case answer_step is the step the ANSWER corresponds to, not the current
# stream position.


def _restore_latest(
    engine: TriangleCountEngine, ckpt_dir: Optional[str]
) -> tuple[Optional[CheckpointManager], bool, Optional[dict]]:
    """Open ``ckpt_dir`` and restore the newest VERIFIED checkpoint into
    ``engine``, walking back through the keep-k snapshots past any torn or
    corrupt one (counted in ``diag.ckpt_corrupt_skipped``). Returns
    (manager or None, whether a state was restored, that snapshot's
    manifest or None).

    Keys the engine's snapshot template grew over time (``scheme``, then
    ``dyn_step``) are popped from the template when the saved manifest
    predates them — ``engine.restore`` defaults both. The window-state keys
    are NOT optional: a window/decay engine restoring from a checkpoint
    without them must fail (the live-edge ring cannot be reconstructed), and
    the KeyError surfaces as SnapshotMismatch here. Config mismatches are
    NOT walked past: restoring an older snapshot would silently rewind the
    stream when the real problem is a wrong --ckpt-dir."""
    if ckpt_dir is None:
        return None, False, None
    ckpt = CheckpointManager(ckpt_dir, async_save=True)
    full = engine.snapshot()
    for step in reversed(ckpt.steps()):
        try:
            saved = ckpt.manifest(step)
        except CheckpointCorrupt:
            engine.diag.ckpt_corrupt_skipped += 1
            continue
        template = dict(full)
        if saved is not None and "keys" in saved:
            # manifest keys are tree_flatten_with_path names: a top-level
            # snapshot entry 'dyn_step' is recorded as "['dyn_step']"
            names = set(saved["keys"])
            for optional in ("scheme", "dyn_step"):
                if optional not in names and f"[{optional!r}]" not in names:
                    template.pop(optional, None)
        try:
            restored, manifest = ckpt.restore(template, step=step)
        except CheckpointCorrupt:
            # torn/bit-flipped snapshot: walk back to the previous one
            # rather than crash — and NEVER restore it
            engine.diag.ckpt_corrupt_skipped += 1
            continue
        except (AssertionError, KeyError) as e:
            raise SnapshotMismatch(
                f"checkpoint in {ckpt_dir!r} does not fit this engine "
                f"(r={engine.config.r}, tenants={engine.config.n_tenants}); "
                "point --ckpt-dir at a fresh directory or match the saved "
                f"config. Underlying error: {e}"
            ) from e
        # the resume skip counts BATCHES, so resuming under a different
        # batch_size would mis-position the stream (skip the wrong edges)
        ckpt_bs = int(np.asarray(restored["config"])[1])
        if ckpt_bs != engine.config.batch_size:
            raise SnapshotMismatch(
                f"checkpoint in {ckpt_dir!r} was written with "
                f"batch_size={ckpt_bs}, engine has "
                f"{engine.config.batch_size}; the stream loops resume by "
                "skipping whole batches, so the sizes must match "
                "(re-batching needs manual engine.restore + stream "
                "positioning)"
            )
        engine.restore(restored)
        return ckpt, True, manifest
    return ckpt, False, None


def _wants_stale_age(cb: Optional[QueryCallback]) -> bool:
    if cb is None:
        return False
    try:
        return "stale_age" in inspect.signature(cb).parameters
    except (TypeError, ValueError):  # builtins / C callables
        return False


def run_stream(
    engine: TriangleCountEngine,
    batch_iter: Iterable,
    *,
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 0,
    report_every: int = 0,
    on_report: Optional[QueryCallback] = None,
    prefetch_depth: int = 4,
    deadline_s: Optional[float] = None,
    resilience: Optional[ResilienceConfig] = None,
) -> StreamReport:
    """Drain ``batch_iter`` ((W, n_valid) pairs) into ``engine``.

    If ``ckpt_dir`` is given the engine first restores from the newest
    checkpoint there that verifies (walking back past torn/corrupt ones) and
    *skips* the already-consumed prefix of the iterator, then saves every
    ``ckpt_every`` batches plus once at the end.

    With ``engine.config.chunk_size = K > 1`` batches are assembled into
    K-superbatches ingested in one dispatch each, with the next superbatch's
    device upload double-buffered behind the current one's compute; the state
    is bit-identical to per-batch ingestion, but reports and checkpoints land
    at chunk granularity (``engine.step`` still counts batches, so resume
    skipping is unaffected).

    ``resilience`` (default: validation on, FaultInjected-only retries,
    no query timeout, no backpressure) controls quarantine, retry/backoff,
    and degraded-mode queries — see the module docstring.
    """
    res = resilience if resilience is not None else ResilienceConfig()
    rep = StreamReport()
    rep.dead_letters = DeadLetterBuffer(res.dead_letter_capacity)
    ckpt, restored, manifest = _restore_latest(engine, ckpt_dir)
    if restored:
        rep.resumed_from = engine.step

    pf = PrefetchQueue(
        iter(batch_iter),
        depth=prefetch_depth,
        deadline_s=deadline_s,
        retry=res.retry,
    )
    meta = {
        "r": engine.config.r,
        "batch": engine.config.batch_size,
        "tenants": engine.config.n_tenants,
    }
    # resume position in SOURCE items (ingested + quarantined). Checkpoints
    # since the source_pos field record it exactly; older ones fall back to
    # engine.step, which is exact when nothing was quarantined.
    skip = engine.step
    if manifest is not None and "source_pos" in manifest:
        skip = int(manifest["source_pos"])
    K = engine.config.chunk_size
    fallbacks0 = engine.diag.query_fallbacks
    wants_age = _wants_stale_age(on_report)
    t0 = time.time()

    def _count_retry(attempt, exc):
        rep.retries += 1

    # committed[0] = source position of the newest INGESTED batch; batches
    # consumed-but-still-buffered (superbatch assembly, staged chunks) are
    # deliberately excluded, so a checkpoint never skips an uningested batch
    committed = [skip]
    pend: deque = deque()  # source positions of admitted, not-yet-ingested batches

    def _admit(pos: int, W, nv) -> bool:
        if not res.validate:
            return True
        with TraceAnnotation("repro.stream.validate", step=engine.step):
            reason = validate_batch(W, nv, max_vertex=res.max_vertex)
        if reason is None:
            return True
        # single-batch quarantine: a poisoned record must not kill the loop
        rep.quarantined_batches += 1
        rep.dead_letters.put(reason, pos, (W, nv))
        return False

    def _emit_report() -> None:
        with TraceAnnotation("repro.stream.report", step=engine.step):
            astep, ests, age = _answer_query(engine, pf, res, rep, engine.step)
            if wants_age:
                on_report(astep, ests, engine.edges_seen(), stale_age=age)
            else:
                on_report(astep, ests, engine.edges_seen())
        rep.queries += 1

    def after_ingest(n_batches: int, n_edges: int) -> None:
        for _ in range(n_batches):
            if pend:
                committed[0] = pend.popleft()
        rep.batches += n_batches
        rep.edges += n_edges
        if report_every and engine.step % report_every == 0 and on_report:
            # one batched multi-tenant query; callbacks re-querying the same
            # step (estimate_tenant etc.) hit the engine's per-step cache
            _emit_report()
        if ckpt and ckpt_every and rep.batches % ckpt_every == 0:
            with TraceAnnotation("repro.stream.checkpoint", step=engine.step):
                ckpt.save(
                    engine.step,
                    engine.snapshot(),
                    {"config_hash": config_hash(meta), **meta,
                     "source_pos": committed[0]},
                )

    def drained():
        """Post-skip (position, batch) pairs out of the prefetch queue; the
        fetches of the skipped prefix carry ``skipped=1`` in their span."""
        seen = 0
        while True:
            try:
                with TraceAnnotation(
                    "repro.stream.fetch", step=engine.step,
                    skipped=int(seen < skip),
                ):
                    batch, stale = pf.get()
            except StopIteration:
                return
            rep.stale_batches += int(stale)
            seen += 1
            if seen > skip:
                yield seen, batch

    def admitted():
        """Validated batches, with their source positions parked in ``pend``
        until the ingest dispatch that contains them commits."""
        for pos, (W, nv) in drained():
            if _admit(pos, W, nv):
                pend.append(pos)
                yield W, nv

    if K <= 1:
        for W, nv in admitted():
            with_retries(res.retry, engine.ingest, W, nv, on_retry=_count_retry)
            # nv is host batch metadata from the prefetch generator,
            # never a device array  # repro-lint: ignore[RL302, RL303]
            after_ingest(1, int(np.asarray(nv).max()))
    else:
        # double buffering: dispatch compute on the staged superbatch (async,
        # returns immediately), then stage the next one — its device upload
        # overlaps the in-flight chunk's compute
        pending = None  # staged-on-device superbatch
        for kind, payload in superbatches(
            admitted(), K, engine.config.batch_size
        ):
            if pending is not None:
                with_retries(
                    res.retry, engine.ingest_chunk, pending, on_retry=_count_retry
                )
                after_ingest(K, pending.edges)
                pending = None
            if kind == "chunk":
                pending = with_retries(
                    res.retry, engine.stage_chunk, *payload, on_retry=_count_retry
                )
            else:  # ragged tail: per-batch
                W, nv = payload
                with_retries(
                    res.retry, engine.ingest, W, nv, on_retry=_count_retry
                )
                # host batch metadata  # repro-lint: ignore[RL302, RL303]
                after_ingest(1, int(np.asarray(nv).max()))
        if pending is not None:
            with_retries(
                res.retry, engine.ingest_chunk, pending, on_retry=_count_retry
            )
            after_ingest(K, pending.edges)
    engine.sync()  # async dispatches must land before the throughput clock stops
    rep.seconds = time.time() - t0
    rep.phantom_batches = pf.unmatched_standins
    rep.duplicate_batches = pf.duplicate_drops
    rep.retries += pf.retries
    rep.query_fallbacks = engine.diag.query_fallbacks - fallbacks0
    if ckpt:
        with TraceAnnotation("repro.stream.checkpoint", step=engine.step):
            ckpt.wait()
            ckpt.save(
                engine.step,
                engine.snapshot(),
                {"config_hash": config_hash(meta), **meta,
                 "source_pos": committed[0]},
            )
            ckpt.wait()
    return rep


def _answer_query(
    engine: TriangleCountEngine,
    pf: PrefetchQueue,
    res: ResilienceConfig,
    rep: StreamReport,
    position: int,
) -> tuple[int, np.ndarray, int]:
    """One report query: ``(answer_step, estimates, stale_age)``.

    When the prefetch backlog has reached ``res.backpressure_depth`` the
    answer comes from the engine's estimate cache — possibly stale, tagged
    with its age in ingest batches — so query latency never steals device
    time from an ingest path that is already behind. Otherwise it is a fresh
    ``engine.estimate`` (itself degrading device->gather on fault/timeout).
    """
    if res.backpressure_depth and pf.backlog() >= res.backpressure_depth:
        cached = engine.cached_estimate()
        if cached is not None:
            astep, ests = cached
            age = engine.step - astep
            if age > 0:
                rep.degraded_queries += 1
                rep.max_staleness = max(rep.max_staleness, age)
                return astep, ests, age
            return position, ests, 0  # cache is current: a normal hit
    return position, engine.estimate(timeout_s=res.query_timeout_s), 0


def run_signed_stream(
    engine: TriangleCountEngine,
    batch_iter: Iterable,
    *,
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 0,
    report_every: int = 0,
    on_report: Optional[QueryCallback] = None,
    prefetch_depth: int = 4,
    deadline_s: Optional[float] = None,
    resilience: Optional[ResilienceConfig] = None,
) -> StreamReport:
    """Drain a SIGNED batch iterator into ``engine`` (the turnstile loop).

    Items are ``(W, n_valid)`` pairs (inserts) or ``(W, n_valid, sign)``
    triples with sign +1/-1 (``repro.data.graph_stream.signed_batches``).
    The service surface mirrors ``run_stream`` — prefetch overlap,
    checkpoint/resume with corrupt-snapshot walk-back, quarantine, retries,
    degraded queries — with every cursor keyed on ``engine.dyn_step`` (the
    signed-batch position) instead of ``step``, because deletion batches
    advance the stream without advancing the RNG cursor. Resume skips
    ``source_pos`` items of the iterator (dyn_step for pre-upgrade
    checkpoints) and checkpoints are saved under the dyn_step index, so a
    killed churn stream continues bit-for-bit. Chunked ingest does not apply
    here (deletions break insert runs at arbitrary points); drive
    ``engine.ingest_signed_stream`` directly when dispatch fusion matters
    more than checkpoints.
    """
    res = resilience if resilience is not None else ResilienceConfig()
    rep = StreamReport()
    rep.dead_letters = DeadLetterBuffer(res.dead_letter_capacity)
    ckpt, restored, manifest = _restore_latest(engine, ckpt_dir)
    if restored:
        rep.resumed_from = engine.dyn_step

    pf = PrefetchQueue(
        iter(batch_iter),
        depth=prefetch_depth,
        deadline_s=deadline_s,
        retry=res.retry,
    )
    meta = {
        "r": engine.config.r,
        "batch": engine.config.batch_size,
        "tenants": engine.config.n_tenants,
    }
    skip = engine.dyn_step  # signed items already folded into the state
    if manifest is not None and "source_pos" in manifest:
        skip = int(manifest["source_pos"])
    fallbacks0 = engine.diag.query_fallbacks
    wants_age = _wants_stale_age(on_report)
    t0 = time.time()
    seen = 0
    committed = skip  # source position of the newest applied item

    def _count_retry(attempt, exc):
        rep.retries += 1

    while True:
        try:
            item, stale = pf.get()
        except StopIteration:
            break
        rep.stale_batches += int(stale)
        seen += 1
        if seen <= skip:
            continue
        if res.validate:
            reason = validate_signed_item(item, max_vertex=res.max_vertex)
            if reason is not None:
                rep.quarantined_batches += 1
                rep.dead_letters.put(reason, seen, item)
                continue
        if len(item) > 2 and int(item[2]) < 0:
            with_retries(
                res.retry, engine.delete, item[0], item[1], on_retry=_count_retry
            )
        else:
            with_retries(
                res.retry, engine.ingest, item[0], item[1], on_retry=_count_retry
            )
        committed = seen
        rep.batches += 1
        # host batch metadata  # repro-lint: ignore[RL302, RL303]
        rep.edges += int(np.max(np.asarray(item[1])))
        if report_every and engine.dyn_step % report_every == 0 and on_report:
            astep, ests, age = _answer_query(
                engine, pf, res, rep, engine.dyn_step
            )
            if wants_age:
                on_report(astep, ests, engine.edges_seen(), stale_age=age)
            else:
                on_report(astep, ests, engine.edges_seen())
            rep.queries += 1
        if ckpt and ckpt_every and rep.batches % ckpt_every == 0:
            ckpt.save(
                engine.dyn_step,
                engine.snapshot(),
                {"config_hash": config_hash(meta), **meta,
                 "source_pos": committed},
            )
    engine.sync()
    rep.seconds = time.time() - t0
    rep.phantom_batches = pf.unmatched_standins
    rep.duplicate_batches = pf.duplicate_drops
    rep.retries += pf.retries
    rep.query_fallbacks = engine.diag.query_fallbacks - fallbacks0
    if ckpt:
        ckpt.wait()
        ckpt.save(
            engine.dyn_step,
            engine.snapshot(),
            {"config_hash": config_hash(meta), **meta,
             "source_pos": committed},
        )
        ckpt.wait()
    return rep


# ---------------------------------------------------------------------------
# elastic serving: concurrent ingest/query over a slab-allocated bank
# ---------------------------------------------------------------------------
@dataclass
class ServeStats:
    """Host-side accounting for one ElasticServeLoop run."""

    ticks: int = 0  # consumer-loop iterations that did work
    ingest_dispatches: int = 0  # banked device dispatches (1 per tick with work)
    batches: int = 0  # per-tenant batches folded into those dispatches
    queries_answered: int = 0
    degraded_queries: int = 0  # answered from the stale cache under backpressure
    max_staleness: int = 0  # worst stale-answer age, in bank versions
    retries: int = 0  # ingest dispatches retried after transient faults
    control_ops: int = 0  # add/evict/snapshot/restore ops applied
    evicted_pending: int = 0  # queued batches that died with an evicted tenant


class ElasticServeLoop:
    """The elastic serving tier: ONE consumer thread drains bounded
    per-tenant queues into an ``ElasticBankEngine`` while queries and
    tenancy ops (hot-add / evict / per-tenant snapshot / restore) are
    answered **between dispatches** — concurrently with ingest, because a
    dispatched banked update returns as soon as XLA enqueues it, so queries
    and slot ops overlap the in-flight compute rather than waiting for the
    stream to drain.

    Producers are thread-safe and never block the device: ``submit`` puts a
    batch on that tenant's bounded queue (``repro.data.prefetch.
    TenantQueues`` — full queues shed or stall per policy, counted);
    ``query``/``add_tenant``/``evict_tenant``/``snapshot_tenant``/
    ``restore_tenant`` return ``concurrent.futures.Future``s resolved by the
    consumer thread. Per tick the loop (1) applies queued tenancy ops, (2)
    assembles one front-packed banked batch — up to ``chunk_size`` queued
    batches per tenant — and dispatches it through the bank's cached
    tier programs (transient ``engine.ingest``/``engine.ingest_chunk``
    faults ridden out by ``ResilienceConfig.retry``), then (3) answers
    every waiting query from the version-keyed estimate cache or the
    device-resident path. When the total queue backlog reaches
    ``resilience.backpressure_depth``, queries degrade to the newest cached
    answer (tagged with its staleness) instead of spending device time the
    ingest path needs — same contract as ``run_stream``'s report queries.

    Snapshots under live traffic are exact: the consumer thread serializes
    the slot read against ingest dispatches, so ``snapshot_tenant`` observes
    a batch boundary of that tenant's stream while its neighbors keep
    ingesting. With a ``checkpoint`` manager attached, snapshots save
    through the verified (atomic manifest + checksum) machinery and
    ``restore_tenant(tid, step=...)`` restores only what verifies.
    """

    # Thread model, machine-checked by repro-lint RL40x (docs/lint.md): the
    # consumer thread solely owns bank mutations and stats counters; the
    # queues/events/SimpleQueues are the thread-safe channels between them;
    # start/stop (the caller thread) own the thread handle itself.
    _thread_ownership = {
        "consumer": {
            "methods": ("_run", "_apply_control", "_dispatch_ingest",
                        "_answer_queries", "_answer_one"),
            "attrs": ("bank", "stats"),
        },
        "lifecycle": {
            "methods": ("start", "stop"),
            "attrs": ("_thread",),
        },
    }

    def __init__(
        self,
        bank,
        *,
        queues: Optional[TenantQueues] = None,
        queue_depth: int = 64,
        queue_policy: str = "drop",
        resilience: Optional[ResilienceConfig] = None,
        checkpoint: Any = None,  # CheckpointManager | path str | None
        idle_wait_s: float = 0.005,
    ):
        self.bank = bank
        self.queues = (
            queues
            if queues is not None
            else TenantQueues(depth=queue_depth, policy=queue_policy)
        )
        self.res = resilience if resilience is not None else ResilienceConfig()
        if isinstance(checkpoint, str):
            checkpoint = CheckpointManager(checkpoint, async_save=True)
        self.ckpt: Optional[CheckpointManager] = checkpoint
        self.stats = ServeStats()
        self._idle_wait_s = idle_wait_s
        self._control: queue_mod.SimpleQueue = queue_mod.SimpleQueue()
        self._queries: queue_mod.SimpleQueue = queue_mod.SimpleQueue()
        self._work = threading.Event()
        self._stop = threading.Event()
        self._idle = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- producer-facing API (thread-safe) ----------------------------------
    def submit(self, tid, W, n_valid=None) -> bool:
        """Enqueue one batch for ``tid``. False = shed/refused (full queue
        per the queue policy, or tenant not resident)."""
        # producer-side staging of host batch data before enqueue
        ok = self.queues.put(
            tid, (np.asarray(W, np.int32), n_valid)  # repro-lint: ignore[RL303]
        )
        if ok:
            self._kick()
        return ok

    def query(self, tid) -> concurrent.futures.Future:
        """Async per-tenant estimate. Resolves to a dict
        ``{tenant, estimate, version, stale_age}`` — ``stale_age > 0`` marks
        a degraded (cached) answer served under ingest backpressure."""
        fut: concurrent.futures.Future = concurrent.futures.Future()
        self._queries.put((tid, fut))
        self._kick()
        return fut

    def add_tenant(self, tid, seed=None) -> concurrent.futures.Future:
        return self._control_op(("add", tid, seed))

    def evict_tenant(self, tid) -> concurrent.futures.Future:
        return self._control_op(("evict", tid, None))

    def snapshot_tenant(self, tid, save: bool = False) -> concurrent.futures.Future:
        """Resolves to the tenant's snapshot dict; ``save=True`` also writes
        it through the attached CheckpointManager (verified, async) under
        the tenant's current step."""
        return self._control_op(("snapshot", tid, save))

    def restore_tenant(self, tid, snap=None, step=None) -> concurrent.futures.Future:
        """Restore ``tid`` from an in-memory snapshot dict, or (with
        ``step=``) from the attached CheckpointManager — only a snapshot
        that passes manifest verification is ever loaded."""
        if snap is None and step is None:
            raise ValueError("restore_tenant needs snap= or step=")
        return self._control_op(("restore", tid, (snap, step)))

    def _control_op(self, op) -> concurrent.futures.Future:
        fut: concurrent.futures.Future = concurrent.futures.Future()
        self._control.put((op, fut))
        self._kick()
        return fut

    def _kick(self) -> None:
        self._idle.clear()
        self._work.set()

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "ElasticServeLoop":
        if self._thread is not None:
            raise RuntimeError("serve loop already started")
        self._thread = threading.Thread(
            target=self._run, name="elastic-serve", daemon=True
        )
        self._thread.start()
        return self

    def stop(self, drain: bool = True) -> ServeStats:
        """Stop the consumer thread; ``drain=True`` (default) first finishes
        every queued batch, query, and tenancy op."""
        if drain:
            self.drain()
        self._stop.set()
        self._work.set()
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self.ckpt is not None:
            self.ckpt.wait()
        return self.stats

    def drain(self, timeout_s: Optional[float] = None) -> bool:
        """Block until queues, queries, and control ops are all consumed and
        the bank's dispatches have landed. True on success, False on
        timeout."""
        deadline = None if timeout_s is None else time.time() + timeout_s
        while True:
            if self._idle.wait(timeout=0.05):
                self.bank.sync()
                return True
            if deadline is not None and time.time() > deadline:
                return False

    def __enter__(self) -> "ElasticServeLoop":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop(drain=exc == (None, None, None))

    def report(self) -> dict:
        """Merged diag: serve stats + bank counters + queue counters."""
        out = {k: getattr(self.stats, k) for k in vars(self.stats)}
        out.update(self.bank.diag.as_dict())
        out.update(self.queues.diag())
        return out

    # -- consumer thread ----------------------------------------------------
    def _run(self) -> None:
        while True:
            did = self._apply_control()
            did = self._dispatch_ingest() or did
            # queries answered HERE overlap the ingest dispatch still
            # computing on device (async dispatch) — concurrent, not
            # between-stream
            did = self._answer_queries() or did
            if did:
                self.stats.ticks += 1
                continue
            if (
                self.queues.backlog() == 0
                and self._control.empty()
                and self._queries.empty()
            ):
                self._idle.set()
                if self._stop.is_set():
                    return
                self._work.wait(timeout=self._idle_wait_s)
                self._work.clear()

    def _apply_control(self) -> bool:
        did = False
        while True:
            try:
                op, fut = self._control.get_nowait()
            except queue_mod.Empty:
                return did
            if not fut.set_running_or_notify_cancel():
                continue
            kind, tid, arg = op
            try:
                if kind == "add":
                    slot = self.bank.hot_add(tid, seed=arg)
                    self.queues.add_tenant(tid)
                    fut.set_result(slot)
                elif kind == "evict":
                    lost = self.queues.remove_tenant(tid)
                    self.stats.evicted_pending += lost
                    self.bank.evict(tid)
                    fut.set_result(lost)
                elif kind == "snapshot":
                    snap = self.bank.snapshot_tenant(tid)
                    if arg and self.ckpt is not None:
                        meta = {
                            "r": self.bank.r,
                            "batch": self.bank.batch_size,
                            "tenants": 1,
                            "tenant_id": str(tid),
                        }
                        self.ckpt.save(
                            int(snap["step"]),
                            snap,
                            {"config_hash": config_hash(meta), **meta},
                        )
                    fut.set_result(snap)
                elif kind == "restore":
                    snap, step = arg
                    if snap is None:
                        if self.ckpt is None:
                            raise ValueError(
                                "restore by step needs a checkpoint manager"
                            )
                        # an async save of this very step may still be in
                        # flight — land it before reading the store
                        self.ckpt.wait()
                        snap, _ = self.ckpt.restore(
                            self.bank.snapshot_template(), step=step
                        )
                    slot = self.bank.restore_tenant(tid, snap)
                    self.queues.add_tenant(tid)
                    fut.set_result(slot)
                else:  # pragma: no cover - internal
                    raise ValueError(f"unknown control op {kind!r}")
                self.stats.control_ops += 1
            except BaseException as e:  # noqa: BLE001 — delivered to the caller
                fut.set_exception(e)
            did = True

    def _dispatch_ingest(self) -> bool:
        K = self.bank.chunk_size
        work = {}
        n_batches = 0
        for tid in self.bank.tenants():
            items = self.queues.take(tid, K if K > 1 else 1)
            if items:
                work[tid] = items
                n_batches += len(items)
        if not work:
            return False

        def _count_retry(attempt, exc):
            self.stats.retries += 1

        if K > 1:
            with_retries(
                self.res.retry,
                self.bank.ingest_chunk,
                work,
                on_retry=_count_retry,
            )
        else:
            with_retries(
                self.res.retry,
                self.bank.ingest,
                {tid: items[0] for tid, items in work.items()},
                on_retry=_count_retry,
            )
        self.stats.ingest_dispatches += 1
        self.stats.batches += n_batches
        return True

    def _answer_queries(self) -> bool:
        did = False
        while True:
            try:
                tid, fut = self._queries.get_nowait()
            except queue_mod.Empty:
                return did
            if not fut.set_running_or_notify_cancel():
                continue
            try:
                fut.set_result(self._answer_one(tid))
                self.stats.queries_answered += 1
            except BaseException as e:  # noqa: BLE001 — delivered to the caller
                fut.set_exception(e)
            did = True

    def _answer_one(self, tid) -> dict:
        bank = self.bank
        depth = self.res.backpressure_depth
        if depth and self.queues.backlog() >= depth:
            cached = bank.cached_estimate()
            if cached is not None:
                v, ests = cached
                age = bank.version - v
                if age > 0:
                    self.stats.degraded_queries += 1
                    self.stats.max_staleness = max(
                        self.stats.max_staleness, age
                    )
                e = ests[bank.slot_of(tid)]
                return {
                    "tenant": tid,
                    "estimate": float(e) if np.ndim(e) == 0 else e,
                    "version": v,
                    "stale_age": age,
                }
        e = bank.estimate_tenant(tid)
        return {
            "tenant": tid,
            "estimate": e,
            "version": bank.version,
            "stale_age": 0,
        }
