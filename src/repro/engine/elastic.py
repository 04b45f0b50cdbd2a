"""ElasticBankEngine: a slab-allocated multi-tenant bank with hot-add/evict.

``TriangleCountEngine`` compiles its programs for a FIXED ``(n_tenants, r)``
bank: onboarding tenant N+1 means a new config, a full recompile, and a
restart. This module is the serving-tier answer — the bank is a **slab**:

  * **Capacity tiers.** The bank always holds ``capacity`` slots (a power of
    two). Every program — banked update, chunked update, device-resident
    query, slot read/write, RNG-key fold — is compiled once per capacity
    tier and cached. Hot-adding or evicting a tenant within capacity reuses
    the cached programs: zero compiles (``tests/test_elastic_bank.py`` pins
    this with a real XLA compile counter, ``XlaCompileCounter``).
  * **Pad-and-mask.** Free slots ride along in every dispatch with
    ``n_valid=0`` batches. A zero-valid batch is a bitwise state no-op under
    the NBSI update (no reservoir replacement, no chi increment, no closing
    probe, ``m_seen += 0``), so inactive neighbors are never touched — the
    masking is free and exact, not approximate.
  * **Grow by doubling.** When the free list empties, capacity doubles: the
    next tier's programs are built (ONE tier build, counted in
    ``diag.tier_compiles``) and a jitted concat widens the live bank in
    place — live slots keep their buffers bit-for-bit; new slots are fresh.
    Capacity never shrinks (slabs are cheap; programs are not).
  * **Per-slot RNG cursors.** The fixed engine folds one global ``step``
    into every tenant's root key; elastic slots join at different times, so
    each slot carries its OWN cursor: batch ``i`` of the slot uses
    ``fold_in(PRNGKey(seed), i)`` — exactly the fixed engine's contract.
    Chunked ingest uses the per-tenant-``step0`` program variant
    (``BackendPlan.build_chunk_elastic``); each slot's lane is front-packed
    (real batches first, ``n_valid=0`` padding after) so lane ``k`` folds
    cursor ``step0 + k``. A tenant's state after hot-add + ingest is
    therefore **bit-identical** to the same stream on a fresh fixed-size
    engine — across banked plans and chunk sizes.
  * **Per-tenant snapshots.** ``snapshot_tenant`` emits a standard
    single-tenant ``TriangleCountEngine`` snapshot dict (leading ``(1,
    ...)`` axis, ``root_keys (1, 2)``, the slot's cursor as ``step``), so it
    restores into a fresh fixed-size engine, round-trips through
    ``repro.train.checkpoint.CheckpointManager`` (the PR-7 verified
    machinery), and ``restore_tenant`` accepts snapshots from either
    source. One tenant can be snapshotted/restored while its neighbors keep
    ingesting — slot ops are ``O(slab)``, not ``O(world)``.

The elastic tier runs on the banked plans only (``single`` and the
``banked_pjit_*`` pair — ``BackendPlan.banked``); it is insertion-only (no
window/decay/turnstile modes — snapshot a tenant into a fixed engine for
those). ``repro.engine.service.ElasticServeLoop`` drives it with bounded
per-tenant queues and concurrent queries; docs/serving.md is the handbook.
"""
from __future__ import annotations

from dataclasses import asdict, dataclass, field
from typing import Any, Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distributed import auto_axes
from repro.engine.backends import BackendPlan, select_backend
from repro.engine.engine import EngineConfig, SnapshotMismatch, _snapshot_config
from repro.engine.faults import FaultInjected, check_fault

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class XlaCompileCounter:
    """Process-wide count of REAL XLA backend compiles, via
    ``jax.monitoring``'s ``backend_compile`` duration event. This is the
    instrument behind the compile-once-per-capacity guarantee: after a tier
    is built and warmed, hot-add/evict/ingest/query within that capacity
    must not move this counter at all. (Tier builds move it by more than
    one — XLA sub-compiles are not 1:1 with user programs — which is why
    ``diag.tier_compiles`` counts tier builds and this counter proves the
    zero side.)"""

    _installed = False
    count = 0

    @classmethod
    def install(cls) -> None:
        if cls._installed:
            return
        cls._installed = True

        def _listener(event: str, duration: float, **kwargs) -> None:
            if event == _COMPILE_EVENT:
                cls.count += 1

        jax.monitoring.register_event_duration_secs_listener(_listener)

    @classmethod
    def snapshot(cls) -> int:
        """Install (idempotent) and return the current compile count."""
        cls.install()
        return cls.count


@dataclass
class ElasticDiagnostics:
    """Host-side operational counters for the elastic bank."""

    backend: str = ""
    capacity: int = 0
    tier_compiles: int = 0  # capacity-tier program-set builds (the slab unit)
    grows: int = 0  # capacity doublings
    hot_adds: int = 0
    evictions: int = 0
    restores: int = 0
    snapshots_taken: int = 0
    batches_ingested: int = 0  # per-slot batches, summed
    edges_ingested: int = 0
    queries_answered: int = 0
    query_cache_hits: int = 0
    query_fallbacks: int = 0  # device-path queries degraded to the gather oracle
    tiers: List[int] = field(default_factory=list)  # capacities built, in order

    def as_dict(self) -> dict:
        return asdict(self)


class ElasticBankEngine:
    """Slab-allocated tenant bank (see module docstring).

    Mutating entry points (``ingest``/``ingest_chunk``/``hot_add``/``evict``/
    ``restore_tenant``) are NOT thread-safe — ``ElasticServeLoop`` serializes
    them on its consumer thread; direct users must do the same.
    """

    #: plans the elastic tier runs on (``BackendPlan.banked``)
    BANKED = ("single", "banked_pjit_independent", "banked_pjit_coordinated")

    def __init__(
        self,
        r: int,
        batch_size: int,
        *,
        capacity: int = 2,
        backend: str = "auto",
        mesh: Any = None,
        scheme: str = "global",
        scheme_params: Optional[tuple] = None,
        groups: int = 9,
        chunk_size: int = 1,
        tenant_axis: str = "tenants",
    ):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.r = int(r)
        self.batch_size = int(batch_size)
        self.groups = int(groups)
        self.chunk_size = int(chunk_size)
        self.mesh = mesh = auto_axes(mesh)
        self._scheme_name = scheme
        self._scheme_params = scheme_params
        self._tenant_axis = tenant_axis
        cap = 1
        while cap < capacity:
            cap *= 2
        # resolve the plan ONCE (auto must not flip plans between tiers);
        # validates scheme/mesh/divisibility through the normal machinery
        cfg0 = self._tier_config(cap, backend)
        plan = select_backend(cfg0, mesh)
        if not plan.banked:
            raise ValueError(
                f"elastic banks need a banked plan {self.BANKED}; "
                f"backend {backend!r} resolved to {plan.name!r}"
            )
        self._backend = plan.name
        self.scheme = cfg0.resolved_scheme()
        # one fresh slot, reused by hot_add/evict scrubs and tier growth
        one = self.scheme.init_state(self.r)
        self._fresh_one = jax.tree.map(lambda x: jnp.asarray(x)[None], one)
        self._state_cls = type(one)

        self.diag = ElasticDiagnostics(backend=self._backend)
        self._tiers: Dict[int, dict] = {}
        self._tenants: Dict[Any, int] = {}  # tenant id -> slot
        self._next_seed = 0
        self._version = 0  # bumped on every state mutation; the query-cache key
        self._est_cache: Dict[int, np.ndarray] = {}

        self.capacity = cap
        self._steps = np.zeros((cap,), np.int64)  # per-slot RNG cursors
        self._active = np.zeros((cap,), bool)
        self._free: List[int] = list(range(cap))
        self._root_keys = jnp.stack(
            [jax.random.PRNGKey(0) for _ in range(cap)]
        )
        self._enter_tier(cap)
        self._state = self._place_bank(
            jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (cap,) + x.shape), one
            )
        )
        self._warm_tier()

    # -- tier machinery -----------------------------------------------------
    def _tier_config(self, cap: int, backend: Optional[str] = None):
        return EngineConfig(
            r=self.r,
            batch_size=self.batch_size,
            n_tenants=cap,
            groups=self.groups,
            backend=backend if backend is not None else self._backend,
            scheme=self._scheme_name,
            scheme_params=self._scheme_params,
            tenant_axis=self._tenant_axis,
            chunk_size=self.chunk_size,
        )

    def _enter_tier(self, cap: int) -> None:
        if cap not in self._tiers:
            self._tiers[cap] = self._build_tier(cap)
            self.diag.tier_compiles += 1
            self.diag.tiers.append(cap)
        self._tier = self._tiers[cap]
        self.capacity = cap
        self.diag.capacity = cap

    def _build_tier(self, cap: int) -> dict:
        """Assemble every program the bank needs at this capacity. Building
        is one python-side closure pass; the XLA compiles happen on first
        dispatch — ``_warm_tier`` forces them all inside the tier window so
        steady-state churn stays compile-free."""
        cfg = self._tier_config(cap)
        plan: BackendPlan = select_backend(cfg, self.mesh)
        scheme, groups = self.scheme, self.groups
        bank_sh = (
            plan.bank_sharding(cfg, self.mesh)
            if plan.bank_sharding is not None
            else None
        )
        # root keys shard like the bank's tenant axis (what the banked
        # update programs expect); committed outputs must say so explicitly
        # or the post-grow keys arrive replicated and the update rejects them
        key_sh = None
        if bank_sh is not None and self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            key_sh = NamedSharding(self.mesh, P(self._tenant_axis, None))

        def slot_write(bank, slot, one):
            return jax.tree.map(
                lambda b, o: jax.lax.dynamic_update_slice_in_dim(
                    b, o.astype(b.dtype), slot, axis=0
                ),
                bank,
                one,
            )

        def slot_read(bank, slot):
            return jax.tree.map(
                lambda b: jax.lax.dynamic_slice_in_dim(b, slot, 1, axis=0),
                bank,
            )

        def key_set(keys, slot, one_key):
            return jax.lax.dynamic_update_slice_in_dim(
                keys, one_key.astype(keys.dtype), slot, axis=0
            )

        return {
            "config": cfg,
            "plan": plan,
            "update": plan.build(cfg, self.mesh),
            "chunk": (
                plan.build_chunk_elastic(cfg, self.mesh)
                if self.chunk_size > 1
                else None
            ),
            "estimate": jax.jit(
                jax.vmap(lambda st: scheme.estimate(st, groups=groups))
            ),
            "estimate_device": (
                plan.build_estimate(cfg, self.mesh)
                if plan.build_estimate is not None
                else None
            ),
            "fold": jax.jit(jax.vmap(jax.random.fold_in)),
            "slot_write": jax.jit(
                slot_write,
                out_shardings=bank_sh,
                donate_argnums=(0,),
            ),
            "slot_read": jax.jit(slot_read),
            "key_set": jax.jit(key_set, out_shardings=key_sh),
            "key_sh": key_sh,  # capacity growth re-places keys through this
        }

    def _warm_tier(self) -> None:
        """Dispatch every tier program once so its XLA compile lands NOW,
        inside the tier window. Each warm call is a state no-op: the warm
        ingest/chunk carry ``n_valid=0`` batches (bitwise no-ops under
        pad-and-mask), the warm slot write writes back what the warm slot
        read just read, and the warm key set re-sets an existing key."""
        C, s, K = self.capacity, self.batch_size, self.chunk_size
        t = self._tier
        # key_set first: its output sharding is part of the type the chunk
        # program is compiled for, so warming that program on keys placed
        # any other way would leave the first real chunk to compile again.
        # The warmup round-trip pre-compiles key_set with a host-fed
        # operand, once per capacity tier  # repro-lint: ignore[RL303]
        k0 = jnp.asarray(np.asarray(self._root_keys)[0:1])
        self._root_keys = t["key_set"](self._root_keys, np.int32(0), k0)
        keys = t["fold"](self._root_keys, self._cursors())
        zW = self._put_batch(np.zeros((C, s, 2), np.int32))
        self._state = t["update"](
            self._state, zW, jnp.zeros((C,), jnp.int32), keys
        )
        if t["chunk"] is not None:
            zWk = self._put_chunk(np.zeros((C, K, s, 2), np.int32))
            self._state = t["chunk"](
                self._state,
                zWk,
                jnp.zeros((C, K), jnp.int32),
                self._root_keys,
                self._cursors(),
            )
        if t["estimate_device"] is not None:
            jax.block_until_ready(t["estimate_device"](self._state))
        jax.block_until_ready(t["estimate"](self._gathered_state()))
        one = t["slot_read"](self._state, np.int32(0))
        self._state = t["slot_write"](self._state, np.int32(0), one)
        jax.block_until_ready(self._state)

    def _cursors(self):
        """The per-slot step cursors as a device array. The host array is
        copied first: on the CPU backend ``jnp.asarray`` may alias an
        aligned numpy buffer without copying, and the dispatch that reads
        it is asynchronous, so bumping ``_steps`` right after a dispatch
        could otherwise change the RNG cursors that dispatch sees."""
        return jnp.asarray(self._steps.copy())

    def _place_bank(self, bank):
        plan = self._tier["plan"]
        if plan.bank_sharding is not None:
            return jax.device_put(
                bank, plan.bank_sharding(self._tier["config"], self.mesh)
            )
        return bank

    def _put_batch(self, Wb: np.ndarray):
        plan, cfg = self._tier["plan"], self._tier["config"]
        if plan.batch_w_sharding is not None:
            return jax.device_put(Wb, plan.batch_w_sharding(cfg, self.mesh))
        return jnp.asarray(Wb)

    def _put_chunk(self, Wb: np.ndarray):
        plan, cfg = self._tier["plan"], self._tier["config"]
        if plan.chunk_w_sharding is not None:
            return jax.device_put(Wb, plan.chunk_w_sharding(cfg, self.mesh))
        return jnp.asarray(Wb)

    def _gathered_state(self):
        if self._tier["plan"].bank_sharding is not None:
            return jax.tree.map(np.asarray, self._state)
        return self._state

    # -- introspection ------------------------------------------------------
    @property
    def backend(self) -> str:
        return self._backend

    @property
    def n_active(self) -> int:
        return len(self._tenants)

    @property
    def version(self) -> int:
        """Mutation counter: bumps on every ingest/add/evict/restore. The
        query cache is keyed on it, so a cached answer is fresh iff its key
        equals the current version."""
        return self._version

    def tenants(self) -> Tuple[Any, ...]:
        return tuple(self._tenants)

    def slot_of(self, tid) -> int:
        return self._tenants[tid]

    def step_of(self, tid) -> int:
        """The tenant's RNG cursor: batches ingested since its hot-add."""
        return int(self._steps[self._tenants[tid]])

    def sync(self) -> None:
        jax.block_until_ready(self._state)

    # -- tenancy ------------------------------------------------------------
    def hot_add(self, tid, seed: Optional[int] = None) -> int:
        """Place a new tenant in a free slot (growing capacity if none is
        free) with a fresh estimator state seeded ``PRNGKey(seed)``. O(slab):
        one slot write + one key write on cached programs; live neighbors'
        buffers are untouched."""
        if tid in self._tenants:
            raise ValueError(f"tenant {tid!r} is already resident")
        if not self._free:
            self._grow()
        slot = self._free.pop(0)
        if seed is None:
            seed = self._next_seed
        self._next_seed = max(self._next_seed, seed + 1)
        t = self._tier
        self._state = t["slot_write"](
            self._state, np.int32(slot), self._fresh_one
        )
        self._root_keys = t["key_set"](
            self._root_keys, np.int32(slot), jax.random.PRNGKey(seed)[None]
        )
        self._steps[slot] = 0
        self._active[slot] = True
        self._tenants[tid] = slot
        self._version += 1
        self.diag.hot_adds += 1
        return slot

    def evict(self, tid, scrub: bool = True) -> int:
        """Remove a tenant; its slot returns to the free list. ``scrub``
        overwrites the slot with fresh state (one O(slab) dispatch) so
        evicted data does not linger in the bank; pass False to make evict a
        pure host-side bookkeeping op (the next hot_add scrubs anyway)."""
        slot = self._tenants.pop(tid)
        if scrub:
            self._state = self._tier["slot_write"](
                self._state, np.int32(slot), self._fresh_one
            )
        self._steps[slot] = 0
        self._active[slot] = False
        self._free.append(slot)
        self._free.sort()
        self._version += 1
        self.diag.evictions += 1
        return slot

    def _grow(self) -> None:
        # the widening itself runs on HOST: gather, concatenate fresh slots,
        # re-place through the new tier's shardings. A jitted sharded-concat
        # is NOT safe here — XLA's SPMD partitioner (observed on 0.4.x CPU)
        # miscompiles concat under a sharded input mesh, double-counting the
        # replicated fields (same bug family as the iota-into-sharded-concat
        # note in repro.core.distributed; neither reproduces on jax 0.9 —
        # ROADMAP C8). Growing is rare (amortized by the doubling), so the
        # one host round-trip is the robust trade.
        new_cap = self.capacity * 2
        pad = new_cap // 2
        host = jax.tree.map(np.asarray, self._state)
        fresh = jax.tree.map(np.asarray, self._fresh_one)
        keys = np.concatenate(
            # repro-lint: ignore[RL303] capacity doubling: the slab migrates
            [np.asarray(self._root_keys)]
            # repro-lint: ignore[RL303] through host once per O(log) grow
            + [np.asarray(jax.random.PRNGKey(0))[None]] * pad
        )
        self._enter_tier(new_cap)
        widened = jax.tree.map(
            lambda b, f: np.concatenate(
                [b, np.broadcast_to(f, (pad,) + f.shape[1:])]
            ),
            host,
            fresh,
        )
        self._state = self._place_bank(widened)
        key_sh = self._tier["key_sh"]
        self._root_keys = (
            jax.device_put(keys, key_sh)
            if key_sh is not None
            else jnp.asarray(keys)
        )
        self._free.extend(range(new_cap // 2, new_cap))
        self._steps = np.concatenate(
            [self._steps, np.zeros((new_cap // 2,), np.int64)]
        )
        self._active = np.concatenate(
            [self._active, np.zeros((new_cap // 2,), bool)]
        )
        self._version += 1
        self.diag.grows += 1
        self._warm_tier()

    # -- ingest -------------------------------------------------------------
    def _pad(self, W: np.ndarray, n_valid: Optional[int] = None):
        s = self.batch_size
        # W arrives as host batch data from the generator/queues; this is
        # input normalization, not a device read-back
        W = np.asarray(W, np.int32)  # repro-lint: ignore[RL303]
        n = W.shape[0] if n_valid is None else int(n_valid)
        if W.shape[0] > s:
            raise ValueError(
                f"batch of {W.shape[0]} edges exceeds batch_size={s}"
            )
        if W.shape[0] < s:
            W = np.concatenate(
                [W, np.zeros((s - W.shape[0], 2), np.int32)], axis=0
            )
        return np.ascontiguousarray(W), n

    def ingest(self, batches: Mapping[Any, Any]) -> None:
        """Incorporate one batch per listed tenant in ONE banked dispatch.

        ``batches`` maps tenant id -> ``(W, n_valid)`` (or bare ``W``,
        ``(<=s, 2)``). Unlisted slots ride along with ``n_valid=0`` — a
        bitwise no-op that does not advance their cursor. A listed tenant's
        cursor advances by one even if its batch is empty, mirroring the
        fixed engine's ``ingest``.
        """
        check_fault("engine.ingest")  # chaos site: fires before any mutation
        C, s = self.capacity, self.batch_size
        Wb = np.zeros((C, s, 2), np.int32)
        nv = np.zeros((C,), np.int32)
        touched = []
        edges = 0
        for tid, item in batches.items():
            slot = self._tenants[tid]
            W, n = item if isinstance(item, tuple) else (item, None)
            Wb[slot], nv[slot] = self._pad(W, n)
            touched.append(slot)
            edges += int(nv[slot])
        keys = self._tier["fold"](self._root_keys, self._cursors())
        self._state = self._tier["update"](
            self._state, self._put_batch(Wb), jnp.asarray(nv), keys
        )
        for slot in touched:
            self._steps[slot] += 1
        self._version += 1
        self.diag.batches_ingested += len(touched)
        self.diag.edges_ingested += edges

    def ingest_chunk(self, batches: Mapping[Any, Sequence]) -> None:
        """Incorporate up to ``chunk_size`` batches per listed tenant in ONE
        fused dispatch (the PR-8 chunked pipeline, per-slot ``step0``).

        ``batches`` maps tenant id -> a sequence of ``(W, n_valid)`` pairs
        (length <= chunk_size). Each slot's lane is front-packed: its real
        batches occupy chunk positions ``0..j-1`` and fold cursors
        ``step0..step0+j-1`` — bit-identical to ``j`` sequential ``ingest``
        calls — while trailing ``n_valid=0`` padding (and unlisted slots'
        whole lanes) are no-ops.
        """
        if self._tier["chunk"] is None:
            raise ValueError(
                "chunked elastic ingest needs chunk_size > 1 at construction"
            )
        check_fault("engine.ingest_chunk")  # chaos site: before any mutation
        C, K, s = self.capacity, self.chunk_size, self.batch_size
        Wb = np.zeros((C, K, s, 2), np.int32)
        nv = np.zeros((C, K), np.int32)
        advance = {}
        edges = 0
        for tid, items in batches.items():
            slot = self._tenants[tid]
            if len(items) > K:
                raise ValueError(
                    f"{len(items)} batches for tenant {tid!r} exceed "
                    f"chunk_size={K}"
                )
            for k, item in enumerate(items):
                W, n = item if isinstance(item, tuple) else (item, None)
                Wb[slot, k], nv[slot, k] = self._pad(W, n)
                edges += int(nv[slot, k])
            advance[slot] = len(items)
        self._state = self._tier["chunk"](
            self._state,
            self._put_chunk(Wb),
            jnp.asarray(nv),
            self._root_keys,
            self._cursors(),
        )
        total = 0
        for slot, j in advance.items():
            self._steps[slot] += j
            total += j
        self._version += 1
        self.diag.batches_ingested += total
        self.diag.edges_ingested += edges

    # -- queries ------------------------------------------------------------
    def estimate(self, *, gather: bool = False) -> np.ndarray:
        """Per-slot estimates, shape ``(capacity, ...)`` — rows of inactive
        slots are the fresh-state estimate (0 triangles) and meaningless.
        Device-resident on sharded plans with the gather oracle as fallback
        (``gather=True`` forces it, bypassing the cache); answers are cached
        per ``version`` so repeated queries between mutations cost one
        dispatch total."""
        if not gather:
            cached = self._est_cache.get(self._version)
            if cached is not None:
                self.diag.queries_answered += 1
                self.diag.query_cache_hits += 1
                return cached
        out = None
        if not gather and self._tier["estimate_device"] is not None:
            try:
                check_fault("engine.estimate")  # chaos site: device dispatch
                # the answer itself: O(capacity) scalars cross by design
                out = np.asarray(  # repro-lint: ignore[RL303]
                    self._tier["estimate_device"](self._state)
                )
            except FaultInjected:
                self.diag.query_fallbacks += 1
                out = None
        if out is None:
            # gather-oracle fallback: host answer by definition
            out = np.asarray(  # repro-lint: ignore[RL303]
                self._tier["estimate"](self._gathered_state())
            )
        self.diag.queries_answered += 1
        if not gather:
            self._est_cache = {self._version: out}
        return out

    def cached_estimate(self) -> Optional[Tuple[int, np.ndarray]]:
        """Most recent cached answer as ``(version, estimates)`` — the
        degraded serving path: under ingest backpressure the serve loop
        answers from here (tagged stale with age ``version - key``) instead
        of dispatching. Never dispatches."""
        if not self._est_cache:
            return None
        v = max(self._est_cache)
        return v, self._est_cache[v]

    def estimate_tenant(self, tid):
        e = self.estimate()[self._tenants[tid]]
        return float(e) if np.ndim(e) == 0 else e

    def estimate_tenants(self, tids: Iterable) -> np.ndarray:
        ests = self.estimate()
        idx = np.asarray([self._tenants[t] for t in tids], np.int64)
        return ests[idx]

    def edges_seen(self, tid) -> int:
        slot = self._tenants[tid]
        # index on device first: transfer one scalar, not the whole slab
        return int(self._state.m_seen[slot])

    # -- per-tenant snapshot / restore --------------------------------------
    def snapshot_tenant(self, tid) -> dict:
        """One tenant's complete state as a standard single-tenant
        ``TriangleCountEngine`` snapshot dict (host numpy): it restores into
        a fresh fixed-size engine (``TriangleCountEngine.from_snapshot``)
        bit-identically, round-trips ``CheckpointManager``, and feeds
        ``restore_tenant``. O(slab) — only this slot's rows leave device."""
        slot = self._tenants[tid]
        one = self._tier["slot_read"](self._state, np.int32(slot))
        snap = {f: np.asarray(getattr(one, f)) for f in one._fields}
        snap["root_keys"] = np.asarray(self._root_keys)[slot : slot + 1].copy()
        snap["step"] = np.int64(self._steps[slot])
        snap["dyn_step"] = np.int64(self._steps[slot])
        snap["config"] = np.array([self.r, self.batch_size, 1], np.int64)
        snap["scheme"] = np.array(self.scheme.name)
        self.diag.snapshots_taken += 1
        return snap

    def snapshot_template(self) -> dict:
        """A zero-filled single-tenant snapshot with this bank's exact
        shapes/dtypes — the template ``CheckpointManager.restore`` verifies
        a saved per-tenant snapshot against before ``restore_tenant`` will
        accept it."""
        snap = {
            f: np.zeros_like(np.asarray(getattr(self._fresh_one, f)))
            for f in self._state_cls._fields
        }
        snap["root_keys"] = np.zeros((1, 2), np.uint32)
        snap["step"] = np.int64(0)
        snap["dyn_step"] = np.int64(0)
        snap["config"] = np.array([self.r, self.batch_size, 1], np.int64)
        snap["scheme"] = np.array(self.scheme.name)
        return snap

    def restore_tenant(self, tid, snap: dict) -> int:
        """Load a single-tenant snapshot into ``tid``'s slot (hot-adding the
        tenant first if absent): state rows, root key, and RNG cursor. The
        source may be ``snapshot_tenant`` or a 1-tenant fixed engine's
        ``snapshot()`` — the formats are the same."""
        got = _snapshot_config(snap)
        if got[0] != self.r or got[2] != 1:
            raise SnapshotMismatch(
                f"snapshot (r, batch_size, n_tenants)={got} does not fit an "
                f"elastic slot with r={self.r} (need n_tenants=1)"
            )
        snap_scheme = str(np.asarray(snap.get("scheme", "global")))
        if snap_scheme != self.scheme.name:
            raise SnapshotMismatch(
                f"snapshot was written by scheme {snap_scheme!r}; this bank "
                f"runs {self.scheme.name!r}"
            )
        if tid not in self._tenants:
            self.hot_add(tid)
        slot = self._tenants[tid]
        one = self._state_cls(
            **{
                f: jnp.asarray(np.asarray(snap[f]))
                for f in self._state_cls._fields
            }
        )
        t = self._tier
        self._state = t["slot_write"](self._state, np.int32(slot), one)
        self._root_keys = t["key_set"](
            self._root_keys,
            np.int32(slot),
            jnp.asarray(np.asarray(snap["root_keys"])),
        )
        self._steps[slot] = int(snap["step"])
        self._version += 1
        self.diag.restores += 1
        return slot
