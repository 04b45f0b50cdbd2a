"""TriangleCountEngine: a long-lived, multi-tenant streaming triangle counter.

The paper's algorithm is a *continuously running* estimator over an unbounded
edge stream; this module packages it as a service-grade object instead of a
one-shot script:

  * ``ingest(W)`` incorporates one batch of edges (fixed batch shape -> one
    compiled program for the whole stream, however long it runs).
  * ``estimate()`` answers a rolling median-of-means query at any point
    mid-stream without disturbing ingestion state. On sharded plans the
    query runs **device-resident** (per-shard partial reductions + a
    fixed-order combine — only the O(T) answer reaches host, never the
    O(T * r) bank); ``estimate(gather=True)`` forces the gather-to-host
    oracle it is asserted bit-identical against. Answers are cached per
    ``step`` so repeated queries between ingests cost one dispatch total;
    freshness is keyed on the step (an ingest leaves the previous answer
    addressable for degraded backpressure serving — ``cached_estimate``),
    while deletions and restores clear the cache outright. Queries degrade
    rather than die: a timed-out or faulted device dispatch falls back to
    the gather oracle (docs/robustness.md).
  * ``snapshot()`` / ``restore()`` round-trip the complete engine state
    (estimators + RNG cursor) through host memory or a CheckpointManager, so
    a killed process resumes bit-for-bit.

Estimator schemes
-----------------
``EngineConfig.scheme`` names the estimator scheme (``repro.core.schemes``):
what the bank computes and what ``estimate()`` returns per tenant — a scalar
triangle count for ``global``/``naive``, an ``(n_vertices,)`` vector of
per-vertex counts for ``local``. The engine never references state fields by
name: it initializes state through ``scheme.init_state``, the execution plans
jit ``scheme.bulk_update``/``chunk_update`` with shardings derived from the
scheme's axis roles, and the snapshot walks the state pytree's own field
names. Two service-surface assumptions remain on the state shape: it must be
a NamedTuple exposing an ``m_seen`` stream-length leaf (``edges_seen()`` and
the CLIs read it), and its field names must avoid the snapshot's reserved
keys (``root_keys``/``step``/``dyn_step``/``config``/``scheme``/
``window_edges``/``window_expiry``/``window_len``). Every NBSI-state scheme
satisfies both by construction; a scheme with a novel state pytree must too.
Schemes with the NBSI update (``global``/``local``) share compiled programs
and are bit-identical in state for equal seeds.

State layout
------------
The engine owns a *bank* of ``n_tenants`` independent estimator sets stored as
one state pytree with a leading tenant axis; for the NBSI schemes that is
``EstimatorState``:

  f1      (T, r, 2) int32   level-1 edges, -1 sentinel when unset
  chi     (T, r)    int32   neighborhood sizes |Gamma(f1)|
  f2      (T, r, 2) int32   level-2 edges, canonical (min, max)
  has_f3  (T, r)    bool    closing-edge-seen flags
  m_seen  (T,)      int64   per-tenant stream length

One ``jax.vmap``-ed ``bulk_update_all`` under one ``jax.jit`` updates every
tenant per batch: N concurrent streams (or N accuracy tiers of one stream)
share one compiled program — no per-stream recompilation or dispatch overhead.
On a mesh with a ``tenants`` axis the bank *shards*: the tenant dimension
splits over that axis and the estimator dimension over every remaining axis
(the banked_pjit_* plans in ``repro.engine.backends``), so a million-tenant
bank is a data-layout problem, not a loop. Single-tenant engines may instead
pick the pjit or explicit-collective shard_map paths from
``repro.core.distributed``; the engine watches shardmap's overflow diagnostic
and escalates the routing capacity factor (one recompile) when hot vertices
overflow a bucket.

RNG contract
------------
Randomness is counter-based: batch ``i`` of tenant ``t`` uses
``fold_in(PRNGKey(seeds[t]), i)``. No RNG state mutates outside the ``step``
cursor, so tenant ``t`` of any bank — vmapped, tenant-sharded, chunked,
restored — is **bit-for-bit identical** to a standalone single-stream run
seeded the same way; tests assert exact array equality, not statistical
closeness.

Snapshot format
---------------
``snapshot()`` / ``bank_snapshot()`` return a flat dict of **host numpy**
arrays: the state fields above (always with the leading tenant axis, even
for unbanked plans), ``root_keys (T, 2)``, ``step ()`` int64 (the batch
cursor), ``dyn_step ()`` int64 (the signed-batch cursor; pre-dynamic
snapshots lack it and restore as ``step``), ``config`` = [r, batch_size,
n_tenants] int64, and ``scheme`` (the scheme name as a 0-d str array) for the
restore handshake — restoring into an engine running a different scheme
raises ``SnapshotMismatch``; snapshots written before the scheme layer
existed lack the key and restore as ``global``. Window/decay engines add the
fixed-capacity live-edge ring: ``window_edges (T, C, 2)`` int32,
``window_expiry (T, C)`` int64 (-1 padding), ``window_len (T,)`` int64, with
``C`` = the window length (or the decay TTL cap) — restoring a windowed
engine from a snapshot without them (or with a different capacity) raises
``SnapshotMismatch``. The format carries no mesh or chunking information —
restore
device_puts the bank through the *target* engine's plan sharding, so a
snapshot taken on a 4-device 2-D mesh restores onto one device, a different
mesh shape, or a different tenants-per-device split, bit-identically
(gather-to-host on save, reshard-on-restore). The dict is a plain pytree and
round-trips through ``repro.train.checkpoint.CheckpointManager`` unchanged.
"""
from __future__ import annotations

import concurrent.futures
from dataclasses import dataclass, replace
from typing import Any, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.distributed import auto_axes
from repro.core.estimate import effective_groups
from repro.core.schemes import EstimatorScheme, resolve_scheme
from repro.engine.backends import BackendPlan, select_backend
from repro.engine.faults import FaultInjected, check_fault


@dataclass(frozen=True)
class EngineConfig:
    """Static configuration; every field participates in program shape, so a
    snapshot can only be restored into an engine with an equal config."""

    r: int  # estimators per tenant
    batch_size: int  # s: fixed ingest width (shorter batches are padded)
    n_tenants: int = 1
    # requested median-of-means groups for estimate(); rounded down to
    # effective_groups(r, groups) — the largest divisor of r <= groups — so
    # every estimator always participates (nothing is silently trimmed)
    groups: int = 9
    seeds: Optional[tuple[int, ...]] = None  # per-tenant RNG seeds
    backend: str = "auto"  # auto | any name in repro.engine.backends.BACKENDS
    # estimator scheme: what the bank computes (repro.core.schemes registry).
    # scheme_params is a ((name, value), ...) tuple (a dict is normalized at
    # construction), e.g. scheme="local",
    # scheme_params=(("n_vertices", 10_000), ("n_pools", 8))
    scheme: str = "global"
    scheme_params: Optional[tuple] = None
    # mesh axis the bank's tenant dim shards over (banked_pjit_* plans);
    # every other mesh axis shards the estimator dim
    tenant_axis: str = "tenants"
    capacity_factor: float = 2.0  # shardmap routing capacity (see distributed.py)
    # K: batches fused per dispatch (lax.scan inside one jit). Pure dispatch
    # granularity — state and RNG stream are identical for any K, so snapshots
    # restore across engines with different chunk_size.
    chunk_size: int = 1
    # fully-dynamic modes (mutually exclusive). window=N keeps only the most
    # recent N inserted edges per tenant live (count-based sliding window):
    # the engine tracks insertions in a host-side ring and authors expiry
    # deletion batches through scheme.expire as the window slides. decay=D
    # (> 1) gives each inserted edge an independent geometric lifetime with
    # mean D batches-of-one-edge (exponential decay), deterministically
    # derived from (tenant seed, insertion position) so restores and the test
    # oracle reproduce identical lifetimes. Both modes assume each edge key
    # is inserted at most once while a previous copy is live (the turnstile
    # single-live-copy contract). 0 / 0.0 = insertion-only (the default; the
    # ingest path is bit-identical to pre-dynamic engines).
    window: int = 0
    decay: float = 0.0

    def __post_init__(self):
        if isinstance(self.scheme_params, dict):
            object.__setattr__(
                self, "scheme_params", tuple(sorted(self.scheme_params.items()))
            )
        if self.groups < 1:
            raise ValueError(
                f"groups must be >= 1, got {self.groups}; estimate() uses "
                "effective_groups(r, groups) so no estimator is ever dropped"
            )
        if self.window < 0:
            raise ValueError(f"window must be >= 0, got {self.window}")
        if self.decay != 0.0 and self.decay <= 1.0:
            raise ValueError(
                f"decay must be > 1 (mean edge lifetime), got {self.decay}"
            )
        if self.window and self.decay:
            raise ValueError(
                "window and decay are mutually exclusive dynamic modes; "
                f"got window={self.window}, decay={self.decay}"
            )

    def resolved_scheme(self) -> EstimatorScheme:
        """The EstimatorScheme instance this config names (validated)."""
        scheme = resolve_scheme(self.scheme, self.scheme_params)
        scheme.validate(self.r)
        return scheme

    def effective_groups(self) -> int:
        """The group count estimate() actually uses (divisor rule)."""
        return effective_groups(self.r, self.groups)

    def tenant_seeds(self) -> tuple[int, ...]:
        if self.seeds is not None:
            if len(self.seeds) != self.n_tenants:
                raise ValueError(
                    f"seeds has {len(self.seeds)} entries for "
                    f"{self.n_tenants} tenants"
                )
            return tuple(self.seeds)
        return tuple(range(self.n_tenants))


@dataclass
class EngineDiagnostics:
    """Rolling operational counters (host-side, not part of the snapshot)."""

    batches_ingested: int = 0
    edges_ingested: int = 0
    overflow_batches: int = 0  # shardmap batches that reported bucket overflow
    capacity_escalations: int = 0  # recompiles triggered by overflow
    backend: str = ""
    queries_answered: int = 0  # estimate() calls (any path)
    query_cache_hits: int = 0  # answered from the per-step estimate cache
    delete_batches: int = 0  # explicit turnstile deletion batches applied
    edges_deleted: int = 0  # max-over-tenants valid edges in those batches
    window_expired: int = 0  # edges expired by the window/decay clock
    # overflow scalars from a pre-restore stream discarded by restore() —
    # they describe batches the restored state never saw, so draining them
    # would trigger a bogus capacity escalation (and recompile)
    pending_overflow_dropped: int = 0
    # -- resilience (docs/robustness.md) -------------------------------
    query_fallbacks: int = 0  # device-path queries answered by the gather oracle
    query_timeouts: int = 0  # ... of those, due to the per-query timeout
    ckpt_corrupt_skipped: int = 0  # torn/corrupt checkpoints walked past on restore


class SnapshotMismatch(ValueError):
    """Snapshot config does not match the engine it is being restored into."""


@dataclass(frozen=True)
class StagedChunk:
    """A K-batch superbatch already broadcast to the tenant axis and resident
    on device (``TriangleCountEngine.stage_chunk``). Staging the next chunk
    while the current one computes double-buffers the host→device upload out
    of the ingest critical path."""

    Wb: Any  # (n_tenants, K, s, 2) int32 device array
    nv: Any  # (n_tenants, K) int32 device array
    edges: int  # host-side max-over-tenants total valid edges (for diag)
    # host-side copies kept for the window clock (None when the engine runs
    # insertion-only — no host memory spent on static streams)
    W_host: Any = None  # (n_tenants, K, s, 2) int32
    nv_host: Any = None  # (n_tenants, K) int64


def _snapshot_config(snap: dict) -> tuple:
    return tuple(int(x) for x in np.asarray(snap["config"]).tolist())


class TriangleCountEngine:
    """Long-lived multi-stream triangle-count service (see module docstring)."""

    def __init__(self, config: EngineConfig, mesh: Any = None):
        if config.r <= 0 or config.batch_size <= 0 or config.n_tenants <= 0:
            raise ValueError(f"bad config: {config}")
        if config.chunk_size <= 0:
            raise ValueError(f"chunk_size must be >= 1, got {config.chunk_size}")
        self.config = config
        self.mesh = mesh = auto_axes(mesh)
        self.scheme: EstimatorScheme = config.resolved_scheme()
        self.plan: BackendPlan = select_backend(config, mesh)
        self._update = self.plan.build(config, mesh)
        self._update_chunk = (
            self.plan.build_chunk(config, mesh) if config.chunk_size > 1 else None
        )
        self.diag = EngineDiagnostics(backend=self.plan.name)
        self._step = 0  # batches ingested so far (the RNG fold_in counter)
        # dyn_step counts EXTERNAL signed batches (insert + delete); it is
        # the resume cursor for signed streams, where `step` alone (inserts
        # only, the RNG cursor) cannot name a position
        self._dyn_step = 0
        self._delete = None  # jitted deletion program, built on first use
        # the window/decay clock: per-tenant total insertions, maintained
        # host-side so expiry checks never sync on device m_seen (equal to it
        # by construction; rebuilt from the snapshot's m_seen on restore)
        self._inserted = np.zeros((config.n_tenants,), np.int64)
        # per-tenant FIFO of live (u, v, expire_at) triples; only populated
        # in window/decay mode. expire_at = insert position + window (or the
        # edge's deterministic TTL); an edge is dead once expire_at < clock.
        self._dynamic = bool(config.window or config.decay)
        self._win: list[list] = [[] for _ in range(config.n_tenants)]
        self._pending_overflow: list = []  # device scalars, drained lazily
        self._root_keys = jnp.stack(
            [jax.random.PRNGKey(s) for s in config.tenant_seeds()]
        )
        self._state = self._init_bank()
        # per-tenant estimate under one jit; groups is static. This is the
        # gather-to-host path: always built, because it is the ORACLE the
        # device-resident query is asserted against (estimate(gather=True))
        # and the only path for unsharded plans / unshardable schemes.
        scheme, groups = self.scheme, config.groups
        self._estimate = jax.jit(
            jax.vmap(lambda st: scheme.estimate(st, groups=groups))
        )
        # device-resident query: answers where the state lives (None when the
        # plan is unsharded or the scheme's estimate cannot shard)
        self._estimate_device = (
            self.plan.build_estimate(config, mesh)
            if self.plan.build_estimate is not None
            else None
        )
        # per-step estimate cache: {step: (n_tenants, ...) ndarray}. Repeated
        # queries between ingests (serving: many tenants polling one bank
        # state) cost one dispatch total. Freshness is keyed on step, so an
        # ingest leaves the previous answer in place for degraded
        # (backpressure) serving via cached_estimate(); deletions and
        # restores clear it outright because they change the bank without
        # advancing step.
        self._est_cache: dict = {}
        # lazily-built single worker for timeout-bounded device queries
        self._query_pool: Optional[concurrent.futures.ThreadPoolExecutor] = None

    # -- construction -------------------------------------------------------
    def _init_bank(self):
        one = self.scheme.init_state(self.config.r)
        if self.plan.banked:
            bank = jax.tree.map(
                lambda x: jnp.broadcast_to(
                    x[None], (self.config.n_tenants,) + x.shape
                ),
                one,
            )
            return self._place_bank(bank)
        return one

    def _place_bank(self, bank):
        """Lay the bank out the way this engine's plan expects: sharded over
        the mesh for tenant-sharded plans, default device otherwise."""
        if self.plan.bank_sharding is not None:
            return jax.device_put(
                bank, self.plan.bank_sharding(self.config, self.mesh)
            )
        return bank

    @property
    def n_tenants(self) -> int:
        return self.config.n_tenants

    @property
    def step(self) -> int:
        """Number of INSERT batches ingested (also the RNG fold_in cursor).
        Deletions never advance it — that is what keeps all-insertion
        turnstile streams bit-identical to the insertion-only path."""
        return self._step

    @property
    def dyn_step(self) -> int:
        """Number of external signed batches applied (inserts + deletions).
        The resume cursor for signed streams; equals ``step`` on
        insertion-only streams."""
        return self._dyn_step

    def edges_seen(self) -> np.ndarray:
        """(n_tenants,) int64: stream length ingested per tenant."""
        with TraceAnnotation("repro.engine.wait", step=self._step):
            m = np.asarray(self._state.m_seen)
        return m if m.ndim else np.broadcast_to(m, (self.n_tenants,)).copy()

    # -- ingestion ----------------------------------------------------------
    def _pad(self, W: np.ndarray) -> tuple[np.ndarray, int]:
        s = self.config.batch_size
        n = W.shape[0]
        if n > s:
            raise ValueError(
                f"batch of {n} edges exceeds batch_size={s}; split it first "
                "(repro.data.graph_stream.batches)"
            )
        if n < s:
            W = np.concatenate(
                [W, np.zeros((s - n, 2), dtype=np.int32)], axis=0
            )
        return np.ascontiguousarray(W, dtype=np.int32), n

    def ingest(
        self,
        W: np.ndarray,
        n_valid: Optional[Any] = None,
    ) -> None:
        """Incorporate one batch of edges into every tenant.

        W is either ``(<=s, 2)`` — the same edges broadcast to all tenants
        (accuracy-tier mode: tenants differ only by RNG seed) — or
        ``(n_tenants, <=s, 2)`` per-tenant batches. ``n_valid`` overrides the
        inferred count (scalar or per-tenant) when W is pre-padded.
        """
        check_fault("engine.ingest")  # chaos site: fires before any mutation
        with TraceAnnotation("repro.engine.stage", step=self._step):
            W = np.asarray(W)
            T = self.n_tenants
            if W.ndim == 2:
                Wp, n = self._pad(W)
                nv = np.full((T,), n if n_valid is None else int(n_valid), np.int32)
                Wb = np.broadcast_to(Wp[None], (T,) + Wp.shape)
            elif W.ndim == 3:
                if W.shape[0] != T:
                    raise ValueError(
                        f"got {W.shape[0]} tenant batches for {T} tenants"
                    )
                padded = [self._pad(W[t]) for t in range(T)]
                Wb = np.stack([p[0] for p in padded])
                if n_valid is None:
                    nv = np.array([p[1] for p in padded], np.int32)
                else:
                    nv = np.broadcast_to(np.asarray(n_valid, np.int32), (T,)).copy()
            else:
                raise ValueError(f"W must be (s,2) or (T,s,2), got {W.shape}")

            Wb_host, nv_host = Wb, nv  # window clock reads these after dispatch
            keys = jax.vmap(jax.random.fold_in, in_axes=(0, None))(
                self._root_keys, self._step
            )
            if not self.plan.banked:  # distributed single-tenant backends
                Wb, nv, keys = Wb[0], jnp.int32(int(nv[0])), keys[0]
                Wb = jnp.asarray(Wb)
            elif self.plan.batch_w_sharding is not None:
                # host -> shards in one copy (no staging hop via the default
                # device)
                Wb = jax.device_put(
                    Wb, self.plan.batch_w_sharding(self.config, self.mesh)
                )
            else:
                Wb = jnp.asarray(Wb)
            nv = jnp.asarray(nv)
        with TraceAnnotation("repro.engine.dispatch", step=self._step):
            out = self._update(self._state, Wb, nv, keys)
        if self.plan.reports_overflow:
            # don't int() the overflow here: that would sync the host to the
            # device every batch and kill prefetch overlap. Drain every few
            # batches (and at every query/snapshot) instead — escalation lands
            # a few batches late, which is fine: state stays a valid NBSI
            # realization either way.
            self._state, overflow = out
            self._pending_overflow.append(overflow)
            if len(self._pending_overflow) >= 8:
                self._drain_overflow()
        else:
            self._state = out
        self._step += 1
        self._dyn_step += 1
        # the cache is keyed on step, so the old answer is now stale-but-
        # addressable: kept for degraded backpressure serving (cached_estimate)
        self.diag.batches_ingested += 1
        self.diag.edges_ingested += int(np.max(nv_host))
        self._track_inserts(Wb_host, nv_host)
        self._flush_expired()

    def _drain_overflow(self) -> None:
        if not self._pending_overflow:
            return
        pending, self._pending_overflow = self._pending_overflow, []
        with TraceAnnotation("repro.engine.wait", step=self._step):
            total = sum(int(o) for o in pending)
        if total > 0:
            self._escalate_capacity(total)

    def _escalate_capacity(self, overflow: int) -> None:
        """Hot vertices overflowed a routing bucket: the affected queries were
        answered conservatively (state stays a valid NBSI realization but loses
        those samples' contribution), so widen the buckets for future batches.
        One recompile per escalation; estimator state is untouched."""
        self.diag.overflow_batches += 1
        self.diag.capacity_escalations += 1
        self.config = replace(
            self.config, capacity_factor=self.config.capacity_factor * 2.0
        )
        self._update = self.plan.build(self.config, self.mesh)

    # -- chunked (fused multi-batch) ingestion ------------------------------
    def stage_chunk(self, Ws, n_valids=None) -> StagedChunk:
        """Broadcast + device_put a K-batch superbatch ahead of ingest_chunk.

        Ws: (K, s, 2) — broadcast to all tenants — or (n_tenants, K, s, 2)
        per-tenant; every batch must already be padded to batch_size (use
        ``repro.data.prefetch.stack_batches`` on a ``graph_stream.batches``
        run). ``n_valids``: (K,) or (n_tenants, K); None means all-full.

        Staging is separated from ingestion so callers (run_stream) can upload
        chunk k+1 while chunk k computes — double buffering the transfer.
        """
        with TraceAnnotation("repro.engine.stage", step=self._step):
            K, s, T = self.config.chunk_size, self.config.batch_size, self.n_tenants
            if self._update_chunk is None:
                raise ValueError(
                    "chunked ingest needs EngineConfig(chunk_size > 1) on a "
                    "banked plan ('single' or 'banked_pjit_*')"
                )
            arr = np.asarray(Ws, dtype=np.int32)
            if arr.ndim == 3:
                if arr.shape != (K, s, 2):
                    raise ValueError(f"chunk must be ({K}, {s}, 2), got {arr.shape}")
                Wb_host = np.broadcast_to(arr[None], (T, K, s, 2))
            elif arr.ndim == 4:
                if arr.shape != (T, K, s, 2):
                    raise ValueError(
                        f"chunk must be ({T}, {K}, {s}, 2), got {arr.shape}"
                    )
                Wb_host = arr
            else:
                raise ValueError(
                    f"chunk must be (K,s,2) or (T,K,s,2), got {arr.shape}"
                )
            check_fault("engine.stage_chunk")  # chaos site: before the device put
            if self.plan.chunk_w_sharding is not None:
                # sharded plan: device_put straight through the plan's input
                # sharding — one host->shards copy, no staging hop via the
                # default device
                Wb = jax.device_put(
                    Wb_host, self.plan.chunk_w_sharding(self.config, self.mesh)
                )
            else:
                Wb = jnp.asarray(Wb_host)
            if n_valids is None:
                nv_host = np.full((T, K), s, np.int64)
            else:
                nv_host = np.broadcast_to(
                    np.asarray(n_valids, np.int64), (T, K)
                )
            # max over tenants per batch, summed over K — matches what K
            # sequential ingest() calls would accumulate into diag.edges_ingested
            edges = int(nv_host.max(axis=0).sum())
            nv = jnp.asarray(nv_host, dtype=jnp.int32)
            return StagedChunk(
                Wb=Wb,
                nv=nv,
                edges=edges,
                W_host=Wb_host if self._dynamic else None,
                nv_host=np.asarray(nv_host, np.int64),
            )

    def ingest_chunk(self, Ws, n_valids=None) -> None:
        """Incorporate ``chunk_size`` batches in ONE device dispatch.

        Accepts the same shapes as ``stage_chunk`` (or an already-staged
        ``StagedChunk``). Bit-for-bit identical to ``chunk_size`` sequential
        ``ingest`` calls: the scan folds the same per-batch counter into the
        same per-tenant root keys, so snapshots, estimates, and resumes are
        interchangeable between chunked and per-batch ingestion.
        """
        check_fault("engine.ingest_chunk")  # chaos site: before any mutation
        c = Ws if isinstance(Ws, StagedChunk) else self.stage_chunk(Ws, n_valids)
        K = self.config.chunk_size
        with TraceAnnotation("repro.engine.dispatch", step=self._step):
            self._state = self._update_chunk(
                self._state, c.Wb, c.nv, self._root_keys, self._step
            )
        self._step += K
        self._dyn_step += K
        # step-keyed cache: the pre-chunk answer stays addressable for
        # degraded backpressure serving (cached_estimate)
        self.diag.batches_ingested += K
        self.diag.edges_ingested += c.edges
        if c.W_host is not None:
            for k in range(K):
                self._track_inserts(c.W_host[:, k], c.nv_host[:, k])
        else:
            self._inserted += c.nv_host.sum(axis=1)
        # one expiry flush per chunk, not per fused batch: within a chunk the
        # window clock advances K batches before dead edges are patched out.
        # Statistically harmless — a dead edge lingering in a sample is
        # always wiped when its deletion lands (the patch rules key on the
        # edge itself, not on when it died), so the post-flush state has the
        # same unbiasedness as per-batch flushing — but it is why windowed
        # chunked ingest is oracle-equal, not bit-equal, to per-batch.
        self._flush_expired()

    def ingest_stream(
        self, batch_iter: Iterable[tuple[np.ndarray, int]]
    ) -> int:
        """Drain a ``(W, n_valid)`` iterator (e.g. graph_stream.batches).

        With ``chunk_size > 1`` the iterator is assembled into K-batch
        superbatches ingested under one dispatch each (the ragged tail falls
        back to per-batch ingestion — state is identical either way), and the
        next superbatch is staged on device while the current one computes.
        """
        from repro.data.prefetch import superbatches

        K = self.config.chunk_size
        n = 0
        if K <= 1:
            for W, nv in batch_iter:
                self.ingest(W, nv)
                n += 1
            return n
        pending: Optional[StagedChunk] = None
        for kind, payload in superbatches(
            batch_iter, K, self.config.batch_size
        ):
            if pending is not None:
                self.ingest_chunk(pending)
                n += K
                pending = None
            if kind == "chunk":
                pending = self.stage_chunk(*payload)
            else:  # ragged tail: per-batch
                self.ingest(*payload)
                n += 1
        if pending is not None:
            self.ingest_chunk(pending)
            n += K
        return n

    def sync(self) -> None:
        """Block until all dispatched ingest work has completed on device."""
        with TraceAnnotation("repro.engine.wait", step=self._step):
            self._drain_overflow()
            jax.block_until_ready(self._state)

    # -- turnstile deletions / windowed expiry ------------------------------
    def _delete_program(self):
        """The plan's jitted deletion update, built on first use (insertion-
        only streams never pay its compile)."""
        if self._delete is None:
            if self.plan.build_delete is None:
                raise ValueError(
                    f"backend {self.plan.name!r} has no deletion path"
                )
            self._delete = self.plan.build_delete(self.config, self.mesh)
        return self._delete

    def _apply_delete(self, Db: np.ndarray, nv: np.ndarray) -> None:
        """Dispatch one (T, s, 2) deletion batch through the plan's deletion
        program. Internal: does not advance ``dyn_step`` or touch the window
        buffers — both the explicit ``delete()`` path and the window clock's
        expiry flush funnel through here."""
        fn = self._delete_program()
        if not self.plan.banked:
            self._state = fn(
                self._state, jnp.asarray(Db[0]), jnp.int32(int(nv[0]))
            )
        else:
            self._state = fn(
                self._state, jnp.asarray(Db), jnp.asarray(nv, dtype=jnp.int32)
            )
        self._est_cache = {}  # the bank changed: cached answers are stale

    def delete(self, D: np.ndarray, n_valid: Optional[Any] = None) -> None:
        """Turnstile-delete one batch of edges from every tenant.

        Shape conventions mirror ``ingest``: ``(<=s, 2)`` broadcast to all
        tenants or ``(n_tenants, <=s, 2)`` per-tenant. Each deleted edge must
        be live (previously inserted, not yet deleted/expired) — the
        single-live-copy contract ``repro.core.bulk.bulk_delete_update``
        documents. Deletions consume no RNG and never advance ``step``, so
        a signed stream containing zero deletions leaves the engine
        bit-identical to the insertion-only path.
        """
        D = np.asarray(D)
        T = self.n_tenants
        if D.ndim == 2:
            Dp, n = self._pad(D)
            nv = np.full((T,), n if n_valid is None else int(n_valid), np.int32)
            Db = np.broadcast_to(Dp[None], (T,) + Dp.shape)
        elif D.ndim == 3:
            if D.shape[0] != T:
                raise ValueError(
                    f"got {D.shape[0]} tenant batches for {T} tenants"
                )
            padded = [self._pad(D[t]) for t in range(T)]
            Db = np.stack([p[0] for p in padded])
            if n_valid is None:
                nv = np.array([p[1] for p in padded], np.int32)
            else:
                nv = np.broadcast_to(np.asarray(n_valid, np.int32), (T,)).copy()
        else:
            raise ValueError(f"D must be (s,2) or (T,s,2), got {D.shape}")
        self._apply_delete(Db, nv)
        if self._dynamic:
            self._forget_window(Db, nv)
        self._dyn_step += 1
        self.diag.delete_batches += 1
        self.diag.edges_deleted += int(np.max(nv))

    def ingest_signed_stream(self, batch_iter: Iterable) -> int:
        """Drain a signed batch iterator (``graph_stream.signed_batches``).

        Items are ``(W, n_valid)`` pairs (inserts) or ``(W, n_valid, sign)``
        triples with sign +1/-1. Consecutive insert runs are fed through
        ``ingest_stream`` — chunked ingest, staging, and the RNG cursor
        behave exactly as on an unsigned stream, so an all-insertion signed
        stream is structurally the same code path and therefore bit-identical
        to ``ingest_stream``. Deletion batches apply between runs in stream
        order. Returns the number of batches applied (= dyn_step delta).
        """
        it = iter(batch_iter)
        lookahead: list = []  # holds the deletion that ended an insert run

        def insert_run():
            while True:
                if lookahead:
                    item = lookahead.pop()
                else:
                    try:
                        item = next(it)
                    except StopIteration:
                        return
                if len(item) > 2 and int(item[2]) < 0:
                    lookahead.append(item)
                    return
                yield item[0], item[1]

        n = 0
        while True:
            n += self.ingest_stream(insert_run())
            if not lookahead:
                return n
            W, nv, _sign = lookahead.pop()
            self.delete(W, nv)
            n += 1

    def _window_capacity(self) -> int:
        """Max live entries a tenant's window buffer can hold after a flush
        (and the snapshot's fixed window-array width): the window length, or
        the decay TTL cap."""
        if self.config.window:
            return self.config.window
        from repro.data.graph_stream import decay_cap

        return decay_cap(self.config.decay)

    def _track_inserts(self, W: np.ndarray, nv: np.ndarray) -> None:
        """Advance the per-tenant insertion clock past one applied batch; in
        window/decay mode also record each edge's expiry position."""
        nv = np.asarray(nv, np.int64).reshape(-1)
        if not self._dynamic:
            self._inserted += nv
            return
        from repro.data.graph_stream import decay_ttls

        seeds = self.config.tenant_seeds()
        for t in range(self.n_tenants):
            n = int(nv[t])
            start = int(self._inserted[t])
            if n == 0:
                continue
            pos = start + np.arange(n, dtype=np.int64)
            if self.config.window:
                exp = pos + self.config.window
            else:
                exp = pos + decay_ttls(seeds[t], start, n, self.config.decay)
            rows, buf = W[t], self._win[t]
            for j in range(n):
                buf.append((int(rows[j, 0]), int(rows[j, 1]), int(exp[j])))
            self._inserted[t] = start + n

    def _flush_expired(self) -> None:
        """Author expiry deletion batches for every edge the window clock has
        slid past (``expire_at < inserted``) and patch them out of the bank.
        No-op when nothing expired; loops when more than one batch width of
        edges expired at once (chunked ingest, decay bursts)."""
        if not self._dynamic:
            return
        T, s = self.n_tenants, self.config.batch_size
        expired: list[list] = []
        total = 0
        for t in range(T):
            clock = int(self._inserted[t])
            buf = self._win[t]
            dead = [e for e in buf if e[2] < clock]
            if dead:
                self._win[t] = [e for e in buf if e[2] >= clock]
            expired.append(dead)
            total += len(dead)
        if total == 0:
            return
        self.diag.window_expired += total
        while any(expired):
            Db = np.zeros((T, s, 2), np.int32)
            nv = np.zeros((T,), np.int32)
            for t in range(T):
                take, expired[t] = expired[t][:s], expired[t][s:]
                nv[t] = len(take)
                for j, (u, v, _) in enumerate(take):
                    Db[t, j] = (u, v)
            self._apply_delete(Db, nv)

    def _forget_window(self, Db: np.ndarray, nv: np.ndarray) -> None:
        """Drop explicitly deleted edges from the window buffers so the
        window clock cannot author a second deletion for them later."""
        for t in range(self.n_tenants):
            n = int(nv[t])
            if n == 0:
                continue
            gone = {
                (min(int(Db[t, j, 0]), int(Db[t, j, 1])),
                 max(int(Db[t, j, 0]), int(Db[t, j, 1])))
                for j in range(n)
            }
            self._win[t] = [
                e for e in self._win[t]
                if (min(e[0], e[1]), max(e[0], e[1])) not in gone
            ]

    # -- queries ------------------------------------------------------------
    def estimate(
        self, *, gather: bool = False, timeout_s: Optional[float] = None
    ) -> np.ndarray:
        """Rolling per-tenant estimates: shape ``(n_tenants,)`` for scalar
        schemes (the paper's Thm 3.4 median-of-means), ``(n_tenants, ...)``
        for vector schemes (e.g. ``local``: per-vertex counts).

        On a sharded plan the query runs **device-resident** (the plan's
        ``build_estimate`` program: per-shard partial reductions + a
        fixed-order combine — ``repro.core.distributed.make_banked_estimate``
        / ``make_sharded_estimate``), so only the O(T) answer crosses to
        host, never the O(T * r) bank. ``gather=True`` forces the
        gather-to-host oracle — the pre-sharding program the device path is
        asserted bit-identical against (``tests/_bank_driver.py``); it
        bypasses the cache so it always recomputes.

        Answers are cached per ``step``: repeated queries between ingests
        (the serving pattern — many tenants polling one bank state) cost one
        device dispatch total. Freshness is keyed on the step, so the
        previous answer stays addressable (``cached_estimate``) for degraded
        backpressure serving; deletions and restores clear the cache.

        ``timeout_s`` bounds the device-resident dispatch: on expiry (or an
        injected ``engine.estimate`` fault) the query *degrades* to the
        gather oracle — bit-identical, just O(T*r) slower — instead of
        failing the serve loop, counted in ``diag.query_fallbacks`` /
        ``diag.query_timeouts``.
        """
        with TraceAnnotation("repro.engine.estimate", step=self._step):
            self._drain_overflow()
            if not gather:
                cached = self._est_cache.get(self._step)
                if cached is not None:
                    self.diag.queries_answered += 1
                    self.diag.query_cache_hits += 1
                    return cached
            out = None
            if not gather and self._estimate_device is not None:
                try:
                    out = self._query_device(timeout_s)
                    if not self.plan.banked:
                        out = out[None]
                except (FaultInjected, TimeoutError) as e:
                    # graceful degradation: fall through to the gather oracle
                    # below rather than killing the serving loop
                    if isinstance(e, TimeoutError):
                        self.diag.query_timeouts += 1
                    self.diag.query_fallbacks += 1
                    out = None
            if out is None:
                st = self._state
                if not self.plan.banked:
                    st = jax.tree.map(lambda x: x[None], st)
                elif self.plan.bank_sharding is not None:
                    # the gather-to-host oracle: materialize the bank and answer
                    # on the default device — the same program as an unsharded
                    # engine, bit-identical across mesh shapes, O(T*r) bytes
                    # per query
                    with TraceAnnotation("repro.engine.wait", step=self._step):
                        st = jax.tree.map(np.asarray, st)
                out = self._estimate(st)
                with TraceAnnotation("repro.engine.wait", step=self._step):
                    out = np.asarray(out)
            self.diag.queries_answered += 1
            if not gather:
                self._est_cache = {self._step: out}
            return out

    def _query_device(self, timeout_s: Optional[float]) -> np.ndarray:
        """Dispatch the device-resident query program, optionally bounded by
        a wall-clock timeout. The dispatch itself keeps running on a worker
        thread past the deadline (XLA programs are not cancellable); the
        caller just stops waiting and serves the degraded answer."""

        step = self._step

        def call() -> np.ndarray:
            check_fault("engine.estimate")  # chaos site: the device dispatch
            out = self._estimate_device(self._state)
            with TraceAnnotation("repro.engine.wait", step=step):
                return np.asarray(out)

        if timeout_s is None:
            return call()
        if self._query_pool is None:
            self._query_pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="engine-query"
            )
        fut = self._query_pool.submit(call)
        try:
            with TraceAnnotation("repro.engine.wait", step=step):
                return fut.result(timeout=timeout_s)
        except concurrent.futures.TimeoutError:
            raise TimeoutError(f"device query exceeded {timeout_s:.3f}s") from None

    def cached_estimate(self) -> Optional[tuple[int, np.ndarray]]:
        """The most recent cached answer as ``(answer_step, estimates)``, or
        None if nothing is cached. This is the degraded serving path: under
        ingest backpressure the service loops answer reports from here —
        tagged stale with age ``engine.step - answer_step`` — instead of
        dispatching a query the backlog can't afford. Never dispatches."""
        if not self._est_cache:
            return None
        s = max(self._est_cache)
        return s, self._est_cache[s]

    def estimate_tenant(self, tenant: int = 0):
        """One tenant's estimate: a float for scalar schemes, else an array.
        Served from the per-step cache, so polling T tenants between two
        ingests costs one query dispatch, not T."""
        e = self.estimate()[tenant]
        return float(e) if np.ndim(e) == 0 else e

    def estimate_tenants(self, tenants: Iterable[int]) -> np.ndarray:
        """Batched multi-tenant query: rows of ``estimate()`` for the given
        tenant ids, answered from ONE (cached) bank query."""
        ests = self.estimate()
        return ests[np.asarray(list(tenants), dtype=np.int64)]

    # -- snapshot / restore -------------------------------------------------
    def snapshot(self) -> dict:
        """Complete engine state as a flat dict of host numpy arrays
        (see "Snapshot format" in the module docstring).

        Gather-to-host: sharded banks are materialized as full host arrays, so
        the dict is mesh-independent and round-trips through
        ``repro.train.checkpoint.CheckpointManager`` unchanged.
        """
        self._drain_overflow()
        self._flush_expired()  # no dead edge may outlive the snapshot
        st = self._state
        if not self.plan.banked:
            st = jax.tree.map(lambda x: x[None], st)
        with TraceAnnotation("repro.engine.wait", step=self._step):
            snap = {f: np.asarray(getattr(st, f)) for f in st._fields}
            snap["root_keys"] = np.asarray(self._root_keys)
        snap["step"] = np.int64(self._step)
        snap["dyn_step"] = np.int64(self._dyn_step)
        snap["config"] = np.array(
            [self.config.r, self.config.batch_size, self.config.n_tenants],
            np.int64,
        )
        snap["scheme"] = np.array(self.scheme.name)
        if self._dynamic:
            # fixed-capacity window arrays (CheckpointManager restores into a
            # template of EXACT shapes, so the width is the structural bound
            # _window_capacity guarantees, not the current fill level)
            T, C = self.n_tenants, self._window_capacity()
            we = np.zeros((T, C, 2), np.int32)
            wx = np.full((T, C), -1, np.int64)
            wl = np.zeros((T,), np.int64)
            for t, buf in enumerate(self._win):
                wl[t] = len(buf)
                for j, (u, v, x) in enumerate(buf):
                    we[t, j] = (u, v)
                    wx[t, j] = x
            snap["window_edges"] = we
            snap["window_expiry"] = wx
            snap["window_len"] = wl
        return snap

    # mesh-portability contract: bank_snapshot gathers to host, bank_restore
    # reshards onto the target plan — the names docs/scaling.md teaches
    bank_snapshot = snapshot

    def restore(self, snap: dict) -> None:
        """Restore from a snapshot() dict (shape-checked against config).

        ``r`` and ``n_tenants`` must match; ``batch_size`` may differ (the
        estimator state is batch-size independent — Theorem 4.1's batch
        invariance — so a restored stream can legally re-batch). The scheme
        handshake: a snapshot carries its scheme name and refuses to restore
        into an engine running a different scheme; pre-scheme snapshots (no
        ``scheme`` key) are ``global``. Reshard-on-restore: the bank is
        device_put through *this* engine's plan sharding, so the snapshot may
        come from any mesh shape or tenants-per-device split (or none at all).
        """
        got = _snapshot_config(snap)
        want = (self.config.r, self.config.batch_size, self.config.n_tenants)
        if (got[0], got[2]) != (want[0], want[2]):
            raise SnapshotMismatch(
                f"snapshot (r, batch_size, n_tenants)={got} != engine {want}"
            )
        snap_scheme = str(np.asarray(snap.get("scheme", "global")))
        if snap_scheme != self.scheme.name:
            raise SnapshotMismatch(
                f"snapshot was written by scheme {snap_scheme!r}; this engine "
                f"runs {self.scheme.name!r} (pass scheme={snap_scheme!r} or "
                "use from_snapshot, which adopts the snapshot's scheme)"
            )
        state_cls = type(self._state)
        host = state_cls(
            **{f: np.asarray(snap[f]) for f in state_cls._fields}
        )
        if not self.plan.banked:
            bank = jax.tree.map(lambda x: jnp.asarray(x[0]), host)
        elif self.plan.bank_sharding is not None:
            # host -> shards directly; no staging copy on the default device
            bank = self._place_bank(host)
        else:
            bank = jax.tree.map(jnp.asarray, host)
        # undrained overflow scalars describe PRE-restore batches; draining
        # them after the state swap would escalate capacity (and recompile)
        # for a stream the restored engine never ingested — discard them,
        # counted in diag.pending_overflow_dropped
        if self._pending_overflow:
            self.diag.pending_overflow_dropped += len(self._pending_overflow)
            self._pending_overflow = []
        self._est_cache = {}  # cached answers describe the pre-restore bank
        self._state = bank
        self._root_keys = jnp.asarray(snap["root_keys"])
        self._step = int(snap["step"])
        # pre-dynamic snapshots carry no dyn_step: insertion-only streams
        # have dyn_step == step by construction
        self._dyn_step = int(snap.get("dyn_step", snap["step"]))
        # the window clock equals the device insertion counter (deletions
        # never touch m_seen), so it restores from the state itself
        self._inserted = self.edges_seen().astype(np.int64).copy()
        T = self.n_tenants
        if self._dynamic:
            if "window_edges" not in snap:
                raise SnapshotMismatch(
                    "engine runs a window/decay mode but the snapshot has no "
                    "window state (taken by an insertion-only engine?) — the "
                    "live-edge ring cannot be reconstructed"
                )
            we = np.asarray(snap["window_edges"])
            wx = np.asarray(snap["window_expiry"])
            wl = np.asarray(snap["window_len"])
            want_shape = (T, self._window_capacity(), 2)
            if we.shape != want_shape:
                raise SnapshotMismatch(
                    f"snapshot window state {we.shape} != engine capacity "
                    f"{want_shape}: the snapshot was taken under a different "
                    "window/decay configuration"
                )
            self._win = [
                [
                    (int(we[t, j, 0]), int(we[t, j, 1]), int(wx[t, j]))
                    for j in range(int(wl[t]))
                ]
                for t in range(T)
            ]
        else:
            # a windowed snapshot restoring into an insertion-only engine is
            # legal — the bank is a valid patched state; edges simply stop
            # expiring from here on
            self._win = [[] for _ in range(T)]

    bank_restore = restore

    @classmethod
    def from_snapshot(
        cls,
        snap: dict,
        *,
        batch_size: Optional[int] = None,
        mesh: Any = None,
        **config_kwargs,
    ) -> "TriangleCountEngine":
        r, s, t = _snapshot_config(snap)
        if "scheme" not in config_kwargs and "scheme" in snap:
            # adopt the snapshot's scheme; parameterized schemes (local)
            # still need scheme_params from the caller
            config_kwargs["scheme"] = str(np.asarray(snap["scheme"]))
        cfg = EngineConfig(
            r=r,
            batch_size=batch_size if batch_size is not None else s,
            n_tenants=t,
            **config_kwargs,
        )
        eng = cls(cfg, mesh=mesh)
        eng.restore(snap)
        return eng
