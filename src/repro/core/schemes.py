"""Pluggable estimator schemes: one streaming engine, many triangle queries.

The paper's estimator answers exactly one query — the *global* triangle count
tau. Everything above it (distributed plans, the engine, snapshots, CLIs,
benchmarks) used to reference the five ``EstimatorState`` fields by name, so
adding a sibling query meant forking the stack. This module is the seam that
makes a scheme a one-file addition instead:

``EstimatorScheme``
    ``init_state(r)`` / ``bulk_update(state, W, n_valid, key)`` /
    ``chunk_update(state, Ws, n_valids, key, step0)`` /
    ``estimate(state, groups)`` plus a per-leaf **axis-role spec**
    (``axis_roles()``) naming how each state leaf relates to the estimator
    dimension. ``repro.core.distributed`` and ``repro.engine.backends``
    *derive* mesh shardings for any scheme's state pytree from those roles
    instead of hand-constructing ``EstimatorState``-of-``NamedSharding``s.
    Schemes with ``shardable_estimate = True`` additionally expose the
    query as a per-shard ``partial_estimate`` + fixed-order
    ``combine_estimates`` pair, which is what lets sharded engines answer
    ``estimate()`` device-resident (``make_banked_estimate``) instead of
    gathering the bank to host — group sums for ``global``/``naive``,
    pool-local attribution scatters for ``local``.

Axis roles (the vocabulary the sharding derivation understands):
  * ``"estimator"``  — leading axis is the r-estimator axis (e.g. ``chi``);
    shards over the mesh's estimator axes, trailing axes replicated.
  * ``"pair"``       — the (r, 2) edge layout (``f1``/``f2``): estimator
    axis leading, the 2-endpoint axis replicated. Derives the same spec as
    ``"estimator"`` but names the layout so schemes stay self-describing.
  * ``"replicated"`` — no estimator axis anywhere (e.g. the ``m_seen``
    stream-length scalar); replicated across estimator shards. Banked plans
    still prepend the tenant axis to every role.

Registered schemes:
  * ``global`` — the paper's query: one median-of-means scalar per tenant
    (``repro.core.bulk`` + ``repro.core.estimate``, unchanged semantics).
  * ``naive``  — the Section 1 strawman update (edge-at-a-time over all r
    estimators, O(r*s) work per batch) behind the same interface; kept as a
    registered scheme so the property tests and benchmarks can drive the
    baseline through the identical stack. No coordinated shard_map kernel
    (``update_kind = "naive"``).
  * ``local``  — per-vertex triangle counts via vertex-partitioned estimator
    pools (REPT, arXiv:1811.09136; CoCoS, arXiv:1802.04249). The r
    estimators split into ``n_pools`` contiguous pools; vertices hash to an
    owning pool; pool p runs the paper's NBSI update and *attributes* its
    closed triangles only to the vertices it owns. The ingest update is
    byte-for-byte ``bulk_update_all`` — the sampled triangle's three
    vertices (f1 ∪ f2) are already in the state, so per-vertex attribution
    is purely an estimate-time scatter. Restricting the *update* to a
    partition's substream would be wrong: a triangle containing an owned
    vertex v can open with the one edge NOT incident to v's partition, so
    every pool must watch the full stream (REPT keeps a shared edge sample
    for the same reason and partitions only the counters). Because state
    and update coincide with ``global``, the local scheme runs on all six
    execution plans, chunked ingest, and cross-mesh snapshots with zero
    backend changes.

Unbiasedness of the local estimate: Lemma 3.2 gives each triangle T a
contribution of exactly 1 to E[X] per estimator, via the unique sampling path
(f1, f2) = (first, second) edge of T. Hence for any vertex v,
``E[X * 1{v in sampled triangle}] = L_v``, the local count. Pool p's
per-vertex mean over its ``r / n_pools`` estimators is therefore unbiased for
every vertex it owns (the REPT aggregation). Theorem 3.4's median-of-means
sharpening is deliberately NOT applied per vertex: the per-vertex indicator
``X * 1{v in tri}`` is sparse (most estimators contribute 0 to any given
vertex), so the median of group means is 0 unless more than half the groups
hit v — a severe small-count downward bias the global scalar never suffers.
``sum_v L_v = 3 * tau`` is the cheap cross-check the CLIs print.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.bulk import (
    bulk_delete_chunk,
    bulk_delete_update,
    bulk_update_all,
    bulk_update_chunk,
)
from repro.core.estimate import (
    coarse_estimates,
    combine_group_sums,
    estimate,
    partial_group_sums,
)
from repro.core.state import EstimatorState, init_state
from repro.primitives.ingest import ingest_backend

# ---------------------------------------------------------------------------
# axis roles
# ---------------------------------------------------------------------------
ROLE_ESTIMATOR = "estimator"
ROLE_PAIR = "pair"
ROLE_REPLICATED = "replicated"
ROLES = (ROLE_ESTIMATOR, ROLE_PAIR, ROLE_REPLICATED)

# the NBSI tuple's roles — every scheme whose state is EstimatorState shares it
NBSI_STATE_ROLES = EstimatorState(
    f1=ROLE_PAIR,
    chi=ROLE_ESTIMATOR,
    f2=ROLE_PAIR,
    has_f3=ROLE_ESTIMATOR,
    m_seen=ROLE_REPLICATED,
)

_HASH_MULT = jnp.uint32(2654435761)


def vertex_pool(v: jax.Array, n_pools: int) -> jax.Array:
    """Owning pool of vertex ``v`` in [0, n_pools): multiplicative hash (the
    same family the shard_map plan uses for vertex ownership)."""
    return ((v.astype(jnp.uint32) * _HASH_MULT) % jnp.uint32(n_pools)).astype(
        jnp.int32
    )


# ---------------------------------------------------------------------------
# the scheme interface
# ---------------------------------------------------------------------------
class EstimatorScheme:
    """Base scheme: the paper's NBSI state and bulk update, query unspecified.

    Subclasses override ``estimate`` (and, for non-NBSI updates, the state /
    update methods plus ``axis_roles``). ``update_kind`` declares whether the
    update is the paper's bulkUpdateAll (``"nbsi"``) — required for the
    explicit-collective ``shardmap`` plan, whose routed-multisearch kernel
    hardcodes that math — or something else.
    """

    name: str = "?"
    update_kind: str = "nbsi"

    # -- state / update (NBSI defaults; override for non-NBSI schemes) ------
    def init_state(self, r: int) -> EstimatorState:
        return init_state(r)

    def bulk_update(self, state, W, n_valid, key):
        return bulk_update_all(state, W, n_valid, key)

    def chunk_update(self, state, Ws, n_valids, key, step0=0):
        """K stacked batches under one dispatch, same fold_in(key, step0+i)
        counter contract as ``bulk_update_chunk`` (bit-equal to K sequential
        ``bulk_update`` calls for any scheme that uses this default)."""
        steps = jnp.asarray(step0, jnp.int64) + jnp.arange(
            Ws.shape[0], dtype=jnp.int64
        )

        def step(st, xs):
            W, nv, i = xs
            return self.bulk_update(st, W, nv, jax.random.fold_in(key, i)), None

        state, _ = jax.lax.scan(step, state, (Ws, n_valids, steps))
        return state

    # -- turnstile deletions / window expiry --------------------------------
    # The fully-dynamic extension (CoCoS, arXiv:1802.04249): a deletion batch
    # patches the sample so dead edges can never contribute, without touching
    # any sampling decision (m_seen stays the insertion counter, no RNG is
    # consumed, no step advances). Both the turnstile `delete` path and the
    # sliding-window/decay `expire` path are the SAME state transition — the
    # engine merely differs in who authored the deletion batch (the stream vs
    # the window clock) — so `expire` aliases `delete_update` here and
    # schemes override only if their semantics diverge. For the `local`
    # scheme the default is already pool-local: attribution happens at
    # estimate time from the patched sample, and the patch itself is
    # elementwise per estimator (REPT's deletion scatter, arXiv:1811.09136).
    def delete_update(self, state, D, n_valid):
        """Fold one batch of edge deletions into the state (no RNG; see
        ``repro.core.bulk.bulk_delete_update`` for the unbiasedness
        argument and the single-live-copy contract)."""
        return bulk_delete_update(state, D, n_valid)

    def delete_chunk_update(self, state, Ds, n_valids):
        """K stacked deletion batches under one dispatch; bit-equal to K
        sequential ``delete_update`` calls (deletions carry no RNG)."""
        return bulk_delete_chunk(state, Ds, n_valids)

    def expire(self, state, D, n_valid):
        """Window/decay expiry: identical transition to ``delete_update``
        (an expired edge is a deletion authored by the window clock)."""
        return self.delete_update(state, D, n_valid)

    def axis_roles(self):
        """Pytree with the state's structure, each leaf a role string."""
        return NBSI_STATE_ROLES

    # -- query --------------------------------------------------------------
    def estimate(self, state, groups: int = 9) -> jax.Array:
        raise NotImplementedError

    # -- shardable query (the device-resident path) -------------------------
    # A scheme whose estimate factors through a per-shard partial reduction
    # sets shardable_estimate = True and implements the pair below; the
    # execution plans then answer queries where the state lives
    # (repro.core.distributed.make_banked_estimate / make_sharded_estimate)
    # instead of gathering the bank to host. The contract:
    #
    #   estimate(state, groups)
    #     == combine_estimates(stack([partial_estimate(shard_i, offset_i)
    #                                 for contiguous shards i in order]))
    #
    # bit for bit on integer-exact float64 coarse estimates (see "Shardable
    # decomposition" in repro.core.estimate), with partial_estimate returning
    # a FIXED shape independent of the shard so partials stack/all_gather.
    shardable_estimate: bool = False

    def partial_estimate(self, state, *, offset, r: int, groups: int = 9):
        """Per-shard partial reduction over the contiguous estimator slice
        ``[offset, offset + r_local)`` of an r-estimator bank. ``offset`` may
        be a traced scalar (``axis_index * r_local`` on device shards)."""
        raise NotImplementedError(
            f"scheme {self.name!r} has no shardable estimate stage"
        )

    def combine_estimates(self, partials, *, r: int, groups: int = 9):
        """Final estimate from ``(n_shards, ...)`` stacked partials, reduced
        in shard-index order (the fixed combine order every mesh layout
        shares)."""
        raise NotImplementedError(
            f"scheme {self.name!r} has no shardable estimate stage"
        )

    def validate(self, r: int) -> None:
        """Raise ValueError if this scheme cannot run with ``r`` estimators.

        Called by ``EngineConfig``/engine construction so a bad combination
        fails at build time, never mid-stream."""
        if r < 1:
            raise ValueError(f"scheme {self.name!r} needs r >= 1, got {r}")


class GlobalScheme(EstimatorScheme):
    """The paper's query: one global triangle count per tenant (Thm 3.4)."""

    name = "global"
    shardable_estimate = True  # group sums factor over contiguous shards

    def chunk_update(self, state, Ws, n_valids, key, step0=0):
        return bulk_update_chunk(state, Ws, n_valids, key, step0)

    def estimate(self, state, groups: int = 9) -> jax.Array:
        return estimate(state, groups)

    def partial_estimate(self, state, *, offset, r: int, groups: int = 9):
        return partial_group_sums(coarse_estimates(state), offset, r, groups)

    def combine_estimates(self, partials, *, r: int, groups: int = 9):
        return combine_group_sums(partials, r, groups)


class NaiveScheme(GlobalScheme):
    """Section 1's strawman: the same global query over the edge-at-a-time
    parallel update (O(r*s) work per batch). Registered so baselines drive
    the identical engine/benchmark stack; no shard_map kernel exists for it.
    """

    name = "naive"
    update_kind = "naive"

    def bulk_update(self, state, W, n_valid, key):
        return naive_parallel_update(state, W, n_valid, key)

    def chunk_update(self, state, Ws, n_valids, key, step0=0):
        return EstimatorScheme.chunk_update(self, state, Ws, n_valids, key, step0)


@dataclass(frozen=True)
class LocalScheme(EstimatorScheme):
    """Per-vertex triangle counts via vertex-partitioned estimator pools.

    ``estimate(state, groups)`` returns ``(n_vertices,)`` float64 — vertex
    v's estimated incident-triangle count L_v. The r estimators form
    ``n_pools`` contiguous pools; vertex v is owned by pool
    ``vertex_pool(v, n_pools)`` and only that pool's estimators attribute to
    it, so on a sharded bank the attribution scatter stays pool-local (the
    CoCoS layout). Within a pool the per-vertex aggregate is the plain mean
    (unbiased, Lemma 3.2); ``groups`` is accepted for interface uniformity
    but unused — per-vertex median-of-means biases sparse counts to zero
    (see the module docstring). State and update are exactly the global
    scheme's, which is what buys every backend for free.
    """

    n_vertices: int
    n_pools: int = 1
    name = "local"

    def validate(self, r: int) -> None:
        super().validate(r)
        if self.n_vertices < 1:
            raise ValueError(
                f"local scheme needs n_vertices >= 1, got {self.n_vertices}"
            )
        if self.n_pools < 1 or r % self.n_pools:
            raise ValueError(
                f"local scheme needs n_pools >= 1 dividing r={r}, got "
                f"n_pools={self.n_pools}"
            )

    shardable_estimate = True  # the attribution scatter is shard-local

    def _attribution_sums(self, state, offset, r: int) -> jax.Array:
        """(n_vertices,) float64 pool-local attribution sums over the
        contiguous estimator slice held in ``state`` (global indices
        ``offset + i`` — pool membership is a function of the global index,
        so a shard straddling a pool boundary attributes each estimator to
        its own pool regardless of where the shard cut falls)."""
        with jax.named_scope("estimate"):
            r_pool = r // self.n_pools
            x = coarse_estimates(state)  # (r_local,) f64, E[X] = tau each
            u, v = state.f1[:, 0], state.f1[:, 1]
            a, b = state.f2[:, 0], state.f2[:, 1]
            # the sampled triangle's third vertex: f2's endpoint not shared with f1
            o2 = jnp.where((a == u) | (a == v), b, a)
            tri = jnp.stack([u, v, o2])  # (3, r_local) — the triangle's vertices

            r_local = state.chi.shape[0]
            pool = (
                (offset + jnp.arange(r_local, dtype=jnp.int32)) // r_pool
            ).astype(jnp.int32)
            closed = state.has_f3 & (u >= 0) & (a >= 0)
            take = (
                closed[None, :]
                & (tri >= 0)
                & (tri < self.n_vertices)
                & (vertex_pool(tri, self.n_pools) == pool[None, :])
            )
            vert = jnp.where(take, tri, self.n_vertices)  # out of bounds -> drop
            vals = jnp.where(take, x[None, :], 0.0)
            if ingest_backend() == "pallas":
                # kernel path: the scatter as a segment_sum (kernels/segment_sum
                # one-hot MXU form). Bit-exact vs .at[].add: coarse estimates are
                # integer-valued f64 (chi * m_seen), so every partial sum here is
                # exact (< 2**53) and summation order cannot matter.
                from repro.kernels.ops import segment_sum_op

                return segment_sum_op(
                    vals.reshape(-1)[:, None],
                    vert.reshape(-1).astype(jnp.int32),
                    self.n_vertices,
                )[:, 0]
            return (
                jnp.zeros((self.n_vertices,), jnp.float64)
                .at[vert]
                .add(vals, mode="drop")
            )

    def estimate(self, state, groups: int = 9) -> jax.Array:
        del groups  # see class docstring: pool mean, not median-of-means
        r = state.chi.shape[0]
        self.validate(r)
        # vertex v's pool contributes exactly r_pool estimators (pools are
        # contiguous index blocks), so the unbiased estimate is sum / r_pool
        return self._attribution_sums(state, 0, r) / (r // self.n_pools)

    def partial_estimate(self, state, *, offset, r: int, groups: int = 9):
        del groups
        return self._attribution_sums(state, offset, r)

    def combine_estimates(self, partials, *, r: int, groups: int = 9):
        del groups
        return jnp.sum(partials, axis=0) / (r // self.n_pools)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
SCHEMES: Dict[str, Callable[..., EstimatorScheme]] = {}


def register_scheme(name: str, factory: Callable[..., EstimatorScheme]) -> None:
    """Add a scheme factory (``factory(**params) -> EstimatorScheme``).

    ``tools/check_docs.py`` requires every registered name to appear in the
    docs (scaling handbook + paper map), so registration is a doc contract.
    """
    SCHEMES[name] = factory


register_scheme("global", GlobalScheme)
register_scheme("naive", NaiveScheme)
register_scheme("local", LocalScheme)

GLOBAL = GlobalScheme()  # the default instance most call sites share


def resolve_scheme(
    name, params: Optional[dict | tuple] = None
) -> EstimatorScheme:
    """Scheme instance from a registry name + params (or pass one through)."""
    if isinstance(name, EstimatorScheme):
        return name
    if name not in SCHEMES:
        raise ValueError(
            f"unknown scheme {name!r}; registered: {sorted(SCHEMES)}"
        )
    try:
        return SCHEMES[name](**dict(params or {}))
    except TypeError as e:
        raise ValueError(
            f"bad params for scheme {name!r}: {e} "
            "(e.g. the local scheme needs n_vertices)"
        ) from e


# ---------------------------------------------------------------------------
# the Section 1 naive-parallel update (the O(r*m) strawman baseline)
# ---------------------------------------------------------------------------
def _edge_update(state: EstimatorState, inputs):
    """One stream arrival against all estimators (vectorized naive scheme)."""
    (edge, key) = inputs
    u, v = edge[0], edge[1]
    r = state.r
    m_new = state.m_seen + 1
    k1, k2 = jax.random.split(key)

    take1 = jax.random.uniform(k1, (r,)) < 1.0 / m_new.astype(jnp.float32)
    f1 = jnp.where(take1[:, None], edge[None, :], state.f1)
    chi = jnp.where(take1, 0, state.chi)
    f2 = jnp.where(take1[:, None], jnp.int32(-1), state.f2)
    has_f3 = state.has_f3 & ~take1

    live = ~take1 & (f1[:, 0] >= 0)
    adj = live & (
        (f1[:, 0] == u) | (f1[:, 0] == v) | (f1[:, 1] == u) | (f1[:, 1] == v)
    )
    chi = chi + adj.astype(jnp.int32)
    take2 = adj & (
        jax.random.uniform(k2, (r,)) < 1.0 / jnp.maximum(chi, 1).astype(jnp.float32)
    )
    ce = jnp.stack([jnp.minimum(u, v), jnp.maximum(u, v)])
    f2 = jnp.where(take2[:, None], ce[None, :], f2)
    has_f3 = has_f3 & ~take2

    chk = adj & ~take2 & (f2[:, 0] >= 0)
    a, b = f2[:, 0], f2[:, 1]
    u_sh = (f1[:, 0] == a) | (f1[:, 0] == b)
    o1 = jnp.where(u_sh, f1[:, 1], f1[:, 0])
    a_sh = (a == f1[:, 0]) | (a == f1[:, 1])
    o2 = jnp.where(a_sh, b, a)
    closes = (jnp.minimum(o1, o2) == ce[0]) & (jnp.maximum(o1, o2) == ce[1])
    has_f3 = has_f3 | (chk & closes)

    return EstimatorState(f1, chi, f2, has_f3, m_new), None


def naive_parallel_update(state: EstimatorState, W, n_valid, key):
    """Process a batch edge-at-a-time across all estimators (O(r*s) work)."""
    s = W.shape[0]
    keys = jax.random.split(key, s)

    def body(st, inp):
        edge, k, i = inp
        new_st, _ = _edge_update(st, (edge, k))
        skip = i >= n_valid
        return jax.tree.map(lambda a, b: jnp.where(skip, a, b), st, new_st), None

    idx = jnp.arange(s, dtype=jnp.int32)
    state, _ = jax.lax.scan(body, state, (W, keys, idx))
    return state


naive_parallel_update_jit = jax.jit(naive_parallel_update, donate_argnums=(0,))
