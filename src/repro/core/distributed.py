"""Coordinated bulk-parallel update on a TPU mesh (DESIGN.md Section 5).

Every builder here is **scheme-generic**: it takes an
``repro.core.schemes.EstimatorScheme`` and derives the shardings for the
scheme's state pytree from its per-leaf axis roles
(``scheme_state_specs`` — roles ``estimator`` / ``pair`` / ``replicated``),
instead of hand-constructing ``EstimatorState``-of-``NamedSharding``s. The
``w_mode`` argument (formerly confusingly also called "scheme") picks how the
batch W is distributed; the *estimator scheme* picks what is computed.

The paper's distinction between "independent bulk parallel" (every processor
re-does the batch work; total work O(p * s log s)) and "coordinated" (shared
structure built once; O(s log s)) lifts from cache lines to ICI links:

* ``make_pjit_update(mesh, w_mode)`` — one jit program over the whole mesh.
    - w_mode="independent":     W replicated; each device sorts the full batch
      for its estimator shard, p-times duplicated sort FLOPs. The multisearch
      sorts queries and keys together, so XLA all-gathers each search's
      queries and every device runs the whole merge (PERF.md §7).
    - w_mode="coordinated_xla": W sharded; XLA's SPMD partitioner inserts the
      collectives for the global sort/searches automatically.

* ``make_coordinated_update(mesh)`` — the explicit shard_map scheme:
    1. Arcs are **hash-partitioned by src** with one all_to_all: every arc of a
       vertex lands on its owner device, so ranks computed locally *are* global
       ranks (the sample-sort key-range partitioning of the PCO algorithm,
       specialized to the (src, ·) composite keys the queries use).
    2. The closing-edge index is hash-partitioned by canonical min-endpoint.
    3. All estimator lookups (level-1 extract, Q1 rank/degree, Q2 naming-system
       decode, Q3 closing) become **routed multisearches**: queries travel to
       the owner shard via a capacity-padded all_to_all, are answered with
       local searchsorted, and return by the inverse exchange. Estimator state
       never moves — only 8/16-byte query records do.

Capacity: like MoE dispatch, per-(sender,receiver) buffers are padded to
``cap = ceil(volume/p * capacity_factor)``. Hot vertices can overflow a bucket;
the update returns an ``overflow`` diagnostic that production monitors (and
bumps the factor between batches — state is unaffected by a re-run). Tests
assert zero overflow at the sizes exercised.

* ``make_banked_pjit_update(mesh, w_mode, tenant_axis)`` — the *tenant-sharded
  bank*: ``vmap(scheme.bulk_update)`` over the leading tenant axis inside one
  jit over the whole mesh. The bank's tenant dimension shards over the mesh
  axis named ``tenant_axis`` and the estimator dimension shards over every
  remaining mesh axis, giving the 2-D ``(tenants, estimators)`` layout when
  both exist. Per-tenant programs are embarrassingly parallel along the tenant
  axis (zero cross-tenant collectives by construction); within a tenant the
  ``w_mode`` choice mirrors the single-tenant plans: "independent" replicates
  W across the estimator axes, "coordinated_xla" ships W sharded and gathers
  it per tenant group before the structure build (see make_banked_pjit_update
  for why the build itself stays replicated).
  ``make_banked_pjit_chunk_update`` is the K-batch fused variant
  (``scheme.chunk_update`` under the same shardings).

* ``make_banked_estimate(mesh, r, tenant_axis)`` / ``make_sharded_estimate``
  — the *device-resident query path*: answer ``estimate()`` where the state
  lives instead of gathering the bank to host. Each device runs the
  scheme's ``partial_estimate`` over its shard (group sums for the scalar
  schemes, pool-local attribution scatters for ``local``), all_gathers the
  fixed-shape partials across the estimator axes only (axis-index order),
  and applies ``scheme.combine_estimates`` — a fixed-order combine that is
  bit-identical to the gathered oracle (see "Shardable decomposition" in
  ``repro.core.estimate``). Only the O(T)-sized answer leaves the mesh.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.core.schemes import (
    GLOBAL,
    ROLE_ESTIMATOR,
    ROLE_PAIR,
    ROLE_REPLICATED,
    EstimatorScheme,
    resolve_scheme,
)
from repro.core.state import EstimatorState
from repro.primitives.segscan import segment_starts, segmented_iota
from repro.primitives.search import exact_multisearch
from repro.primitives.sort import pack2, sort_by_key

INF64 = jnp.int64(0x7FFFFFFFFFFFFFFF)
_HASH_MULT = jnp.uint32(2654435761)


def _shard_map(f, mesh, *, in_specs, out_specs):
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def auto_axes(mesh):
    """``mesh`` with every axis typed ``Auto`` (None passes through).

    Every plan here places arrays through ``NamedSharding`` and
    ``with_sharding_constraint``, which only accept ``Auto`` axes, while
    ``jax.make_mesh`` types its axes ``Explicit`` unless told otherwise. The
    engines pass the mesh they are given through this, so a mesh from plain
    ``jax.make_mesh`` runs the same programs as one from
    ``repro.launch.mesh.make_stream_mesh``."""
    if mesh is None or all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return mesh.update(axis_types=(AxisType.Auto,) * len(mesh.axis_names))


# --------------------------------------------------------------------------
# axis-role -> sharding derivation (works for ANY scheme's state pytree)
# --------------------------------------------------------------------------
def scheme_state_specs(
    scheme: EstimatorScheme, estimator_axes, *, tenant_axis: str | None = None
):
    """PartitionSpec pytree for ``scheme``'s state, derived from its axis
    roles: ``estimator``/``pair`` leaves shard their leading axis over
    ``estimator_axes`` (trailing axes replicated), ``replicated`` leaves
    replicate everywhere. With ``tenant_axis`` every leaf gains a leading
    tenant dimension sharded over that mesh axis (the banked layout). This is
    the single derivation every execution plan uses — a new scheme never
    hand-builds shardings."""
    # accept a registry name too; in particular a pre-rename caller passing
    # scheme="independent" (the old spelling of w_mode) gets the registry's
    # clear "unknown scheme" error instead of an AttributeError deep inside
    scheme = resolve_scheme(scheme)
    e = tuple(estimator_axes) if estimator_axes else None
    prefix = (tenant_axis,) if tenant_axis else ()
    shapes = jax.eval_shape(lambda: scheme.init_state(2))  # ndims, no devices

    def leaf(role, shaped):
        nd = len(shaped.shape)
        if role == ROLE_REPLICATED:
            parts = (None,) * nd
        elif role in (ROLE_ESTIMATOR, ROLE_PAIR):
            parts = (e,) + (None,) * (nd - 1)
        else:
            raise ValueError(
                f"scheme {scheme.name!r} leaf has unknown axis role {role!r}"
            )
        return P(*prefix, *parts)

    return jax.tree.map(leaf, scheme.axis_roles(), shapes)


def scheme_state_sharding(
    mesh,
    scheme: EstimatorScheme,
    estimator_axes,
    *,
    tenant_axis: str | None = None,
):
    """NamedSharding pytree over ``mesh`` for ``scheme``'s state."""
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        scheme_state_specs(scheme, estimator_axes, tenant_axis=tenant_axis),
    )


# --------------------------------------------------------------------------
# pjit paths
# --------------------------------------------------------------------------
def make_pjit_update(
    mesh, w_mode: str = "coordinated_xla", scheme: EstimatorScheme = GLOBAL
):
    """jit-compiled bulk update with mesh shardings (see module docstring)."""
    scheme = resolve_scheme(scheme)  # names OK; old scheme=w_mode strings err
    axes = tuple(mesh.axis_names)
    rep = NamedSharding(mesh, P())
    w_sh = rep if w_mode == "independent" else NamedSharding(mesh, P(axes, None))
    state_sh = scheme_state_sharding(mesh, scheme, axes)
    return jax.jit(
        scheme.bulk_update,
        in_shardings=(state_sh, w_sh, rep, rep),
        out_shardings=state_sh,
        donate_argnums=(0,),
    )


# --------------------------------------------------------------------------
# tenant-sharded banked pjit paths
# --------------------------------------------------------------------------
def split_tenant_axis(mesh, tenant_axis: str = "tenants"):
    """(tenant_axis_size, estimator_axes, estimator_axes_size) for ``mesh``.

    The tenant axis is the mesh axis literally named ``tenant_axis``; every
    other axis shards the estimator dimension. Raises if the axis is absent —
    callers that want a fallback should check ``tenant_axis in mesh.axis_names``
    first (``select_backend``'s auto policy does).
    """
    if tenant_axis not in mesh.axis_names:
        raise ValueError(
            f"mesh axes {tuple(mesh.axis_names)} have no {tenant_axis!r} axis; "
            "build one with repro.launch.mesh.make_stream_mesh('tenants=...')"
        )
    e_axes = tuple(a for a in mesh.axis_names if a != tenant_axis)
    t_size = mesh.shape[tenant_axis]
    e_size = mesh.size // t_size
    return t_size, e_axes, e_size


def banked_state_sharding(
    mesh, tenant_axis: str = "tenants", scheme: EstimatorScheme = GLOBAL
):
    """NamedSharding pytree for a (n_tenants, r, ...) estimator bank: tenants
    over ``tenant_axis``, estimators over the remaining axes — derived from
    the scheme's axis roles, so any scheme's state lays out the same way. The
    engine uses this to place a freshly initialized or snapshot-restored
    bank, so restore reshards onto whatever mesh the target engine runs
    (mesh-portable snapshots)."""
    _, e_axes, _ = split_tenant_axis(mesh, tenant_axis)
    return scheme_state_sharding(mesh, scheme, e_axes, tenant_axis=tenant_axis)


def banked_batch_w_sharding(
    mesh, w_mode: str = "coordinated_xla", tenant_axis: str = "tenants"
) -> NamedSharding:
    """Input sharding for a (T, s, 2) batch — what ``make_banked_pjit_update``
    expects and what the engine's per-batch ``ingest`` device_puts through
    (host -> shards in one copy)."""
    _, e_axes, _ = split_tenant_axis(mesh, tenant_axis)
    t, e = tenant_axis, (e_axes if e_axes else None)
    return NamedSharding(
        mesh, P(t, None, None) if w_mode == "independent" else P(t, e, None)
    )


def make_banked_pjit_update(
    mesh,
    w_mode: str = "coordinated_xla",
    tenant_axis: str = "tenants",
    scheme: EstimatorScheme = GLOBAL,
):
    """Tenant-sharded bank update: jit(vmap(scheme.bulk_update)) over the mesh.

    Signature matches the engine's banked call convention:
    ``f(state_bank, Wb (T,s,2), n_valid (T,), keys (T,2)) -> state_bank``.
    Tenant dim -> ``tenant_axis``; estimator dim -> the remaining axes.
    w_mode="independent" replicates W across the estimator axes; with
    "coordinated_xla" W *arrives* sharded across them (the host->device
    transfer is distributed) and is all-gathered within each tenant group
    before the batch-structure build. Keeping the structure build replicated
    per group is deliberate: XLA's partitioner (observed on 0.4.x CPU)
    miscompiles iota-into-sharded-concat fusions when the tenant dim and the
    batch dim shard simultaneously (not reproduced on jax 0.9; ROADMAP C8)
    — and every device in a tenant group needs the full batch structure for
    its estimator shard's multisearches anyway.
    The estimator-dim work (reservoir draws, Q1/Q2/Q3 query vectors) stays
    sharded in both modes; the searches do not: the merge sorts each query
    vector with its keys, so the partitioner all-gathers the queries within
    the tenant group and every device of the group merges all of them.
    ``make_banked_pjit_chunk_update`` is the K-batch fused variant
    (``scheme.chunk_update`` under the same shardings).
    """
    scheme = resolve_scheme(scheme)
    state_sh = banked_state_sharding(mesh, tenant_axis, scheme)
    t = tenant_axis
    w_in = banked_batch_w_sharding(mesh, w_mode, tenant_axis)
    w_gathered = NamedSharding(mesh, P(t, None, None))
    t_only = NamedSharding(mesh, P(t))
    t_rep = NamedSharding(mesh, P(t, None))

    def banked(state, Wb, n_valid, keys):
        Wb = jax.lax.with_sharding_constraint(Wb, w_gathered)
        return jax.vmap(scheme.bulk_update)(state, Wb, n_valid, keys)

    return jax.jit(
        banked,
        in_shardings=(state_sh, w_in, t_only, t_rep),
        out_shardings=state_sh,
        donate_argnums=(0,),
    )


def banked_chunk_w_sharding(
    mesh, w_mode: str = "coordinated_xla", tenant_axis: str = "tenants"
) -> NamedSharding:
    """Input sharding for a staged (T, K, s, 2) superbatch — what
    ``make_banked_pjit_chunk_update`` expects and what the engine's
    ``stage_chunk`` device_puts through (host -> shards in one copy)."""
    _, e_axes, _ = split_tenant_axis(mesh, tenant_axis)
    t, e = tenant_axis, (e_axes if e_axes else None)
    return NamedSharding(
        mesh,
        P(t, None, None, None) if w_mode == "independent" else P(t, None, e, None),
    )


def make_banked_pjit_chunk_update(
    mesh,
    w_mode: str = "coordinated_xla",
    tenant_axis: str = "tenants",
    scheme: EstimatorScheme = GLOBAL,
    per_tenant_step0: bool = False,
):
    """K-batch fused variant of ``make_banked_pjit_update``:
    ``f(state_bank, Wb (T,K,s,2), n_valids (T,K), root_keys (T,2), step0)``.
    Same shardings with a replicated scan axis; the counter-based RNG keeps it
    bit-identical to K sequential banked updates (see scheme.chunk_update).

    ``per_tenant_step0=True`` makes step0 a ``(T,)`` vector sharded over the
    tenant axis instead of a replicated scalar — the elastic-bank variant,
    where slots join the bank at different times and therefore sit at
    different RNG cursors (``repro.engine.elastic``). Batch ``i`` of slot
    ``t`` still folds ``step0[t] + i``, so each slot's stream stays
    bit-identical to a fixed-size engine at the same cursor."""
    scheme = resolve_scheme(scheme)
    state_sh = banked_state_sharding(mesh, tenant_axis, scheme)
    t = tenant_axis
    w_in = banked_chunk_w_sharding(mesh, w_mode, tenant_axis)
    w_gathered = NamedSharding(mesh, P(t, None, None, None))
    t_rep = NamedSharding(mesh, P(t, None))
    rep = NamedSharding(mesh, P())
    step_in = 0 if per_tenant_step0 else None
    step_sh = NamedSharding(mesh, P(t)) if per_tenant_step0 else rep

    def banked_chunk(state, Wb, n_valids, keys, step0):
        Wb = jax.lax.with_sharding_constraint(Wb, w_gathered)
        return jax.vmap(scheme.chunk_update, in_axes=(0, 0, 0, 0, step_in))(
            state, Wb, n_valids, keys, step0
        )

    return jax.jit(
        banked_chunk,
        in_shardings=(state_sh, w_in, t_rep, t_rep, step_sh),
        out_shardings=state_sh,
        donate_argnums=(0,),
    )


# --------------------------------------------------------------------------
# turnstile deletion paths
# --------------------------------------------------------------------------
def make_pjit_delete(mesh, scheme: EstimatorScheme = GLOBAL):
    """jit-compiled deletion update with mesh shardings.

    ``f(state, D (s,2), n_valid) -> state``. The deletion kernel is
    elementwise per estimator (one fused multisearch against the replicated
    deletion batch, no collectives, no RNG), so ONE builder serves the
    ``pjit_independent``, ``pjit_coordinated``, *and* ``shardmap`` plans: the
    same jitted program shards correctly under any estimator layout. D and
    n_valid are replicated — deletion batches are small relative to the r
    axis, and every shard must test its own samples against the full batch.
    """
    scheme = resolve_scheme(scheme)
    axes = tuple(mesh.axis_names)
    rep = NamedSharding(mesh, P())
    state_sh = scheme_state_sharding(mesh, scheme, axes)
    return jax.jit(
        scheme.delete_update,
        in_shardings=(state_sh, rep, rep),
        out_shardings=state_sh,
        donate_argnums=(0,),
    )


def make_banked_delete(
    mesh, tenant_axis: str = "tenants", scheme: EstimatorScheme = GLOBAL
):
    """Tenant-sharded bank deletion: jit(vmap(scheme.delete_update)).

    Signature matches the engine's banked call convention minus the RNG:
    ``f(state_bank, Db (T,s,2), n_valid (T,)) -> state_bank``. Each tenant's
    deletion batch lands on that tenant's shard group (P(t, None, None) —
    same layout as the independent ingest path); the estimator-dim patch is
    elementwise, so no within-group gather is needed and both banked w_modes
    share this one builder.
    """
    scheme = resolve_scheme(scheme)
    state_sh = banked_state_sharding(mesh, tenant_axis, scheme)
    t = tenant_axis
    d_in = NamedSharding(mesh, P(t, None, None))
    t_only = NamedSharding(mesh, P(t))
    return jax.jit(
        jax.vmap(scheme.delete_update),
        in_shardings=(state_sh, d_in, t_only),
        out_shardings=state_sh,
        donate_argnums=(0,),
    )


# --------------------------------------------------------------------------
# device-resident query path (sharded estimates)
# --------------------------------------------------------------------------
def _estimate_out_ndim(scheme: EstimatorScheme, r: int, groups: int) -> int:
    """ndim of one tenant's estimate (0 for scalar schemes, 1 for local)."""
    shaped = jax.eval_shape(
        lambda: scheme.estimate(scheme.init_state(r), groups=groups)
    )
    return len(shaped.shape)


def make_banked_estimate(
    mesh,
    r: int,
    tenant_axis: str = "tenants",
    scheme: EstimatorScheme = GLOBAL,
    groups: int = 9,
    partials_only: bool = False,
):
    """Device-resident query over a tenant-sharded bank: jit(shard_map) that
    answers ``f(state_bank) -> (n_tenants, ...)`` estimates WITHOUT gathering
    the bank — only the (tenants, g)- or (tenants, n_vertices)-sized partials
    move, never the O(T * r) state.

    Each device reduces its own (tenant-shard, estimator-shard) block with
    ``scheme.partial_estimate`` (group sums for ``global``/``naive``,
    pool-local attribution scatters for ``local``), all_gathers the
    fixed-shape partials within its tenant group (deterministic axis-index
    order), and runs ``scheme.combine_estimates`` — the fixed-order combine
    that reproduces the gathered oracle bit for bit (see "Shardable
    decomposition" in ``repro.core.estimate``). The tenant axis stays
    collective-free; the output shards over it.

    ``partials_only=True`` builds the diagnostic half-program that stops
    after the per-shard reduction — output ``(e_size, n_tenants, *partial)``
    with NO all_gather and no combine. It answers nothing useful by itself;
    ``benchmarks/query_serve.py --breakdown`` times it against the full
    program to isolate the per-query all_gather fixed cost (the ROADMAP
    item-4 small-T crossover).
    """
    scheme = resolve_scheme(scheme)
    if not scheme.shardable_estimate:
        raise ValueError(
            f"scheme {scheme.name!r} has no shardable estimate stage; "
            "query via the gather-to-host path instead"
        )
    _, e_axes, e_size = split_tenant_axis(mesh, tenant_axis)
    if r % e_size:
        raise ValueError(
            f"r={r} must divide over the estimator axes (product {e_size})"
        )
    r_local = r // e_size
    state_spec = scheme_state_specs(scheme, e_axes, tenant_axis=tenant_axis)

    def partials(bank):
        off = (
            jax.lax.axis_index(e_axes) * r_local if e_axes else jnp.int32(0)
        )
        return jax.vmap(
            lambda st: scheme.partial_estimate(
                st, offset=off, r=r, groups=groups
            )
        )(bank)  # (T_local, *partial_shape) — fixed shape per scheme

    if partials_only:
        part_nd = len(
            jax.eval_shape(
                lambda: scheme.partial_estimate(
                    scheme.init_state(r_local), offset=0, r=r, groups=groups
                )
            ).shape
        )
        out_spec = P(
            e_axes if e_axes else None, tenant_axis, *((None,) * part_nd)
        )
        return jax.jit(
            _shard_map(
                lambda bank: partials(bank)[None],
                mesh,
                in_specs=(state_spec,),
                out_specs=out_spec,
            )
        )

    out_nd = _estimate_out_ndim(scheme, r, groups)
    out_spec = P(tenant_axis, *((None,) * out_nd))

    def query(bank):
        partial = partials(bank)
        if e_axes and e_size > 1:
            parts = jax.lax.all_gather(partial, e_axes)  # (e, T_local, ...)
        else:
            parts = partial[None]
        return jax.vmap(
            lambda p: scheme.combine_estimates(p, r=r, groups=groups),
            in_axes=1,
        )(parts)  # (T_local, *out_shape), identical on every group member

    return jax.jit(
        _shard_map(query, mesh, in_specs=(state_spec,), out_specs=out_spec)
    )


def make_sharded_estimate(
    mesh, r: int, scheme: EstimatorScheme = GLOBAL, groups: int = 9
):
    """Device-resident query for the single-tenant sharded plans (pjit_*,
    shardmap): estimator dim sharded over ALL mesh axes, output replicated.
    Same partial/combine contract as ``make_banked_estimate``; returns
    ``f(state) -> estimate`` (no tenant axis)."""
    scheme = resolve_scheme(scheme)
    if not scheme.shardable_estimate:
        raise ValueError(
            f"scheme {scheme.name!r} has no shardable estimate stage; "
            "query via the gather-to-host path instead"
        )
    axes = tuple(mesh.axis_names)
    p = mesh.size
    if r % p:
        raise ValueError(f"r={r} must divide the mesh size {p}")
    r_local = r // p
    state_spec = scheme_state_specs(scheme, axes)
    out_nd = _estimate_out_ndim(scheme, r, groups)
    out_spec = P(*((None,) * out_nd))

    def query(state):
        off = jax.lax.axis_index(axes) * r_local
        partial = scheme.partial_estimate(state, offset=off, r=r, groups=groups)
        parts = jax.lax.all_gather(partial, axes) if p > 1 else partial[None]
        return scheme.combine_estimates(parts, r=r, groups=groups)

    return jax.jit(
        _shard_map(query, mesh, in_specs=(state_spec,), out_specs=out_spec)
    )


# --------------------------------------------------------------------------
# explicit coordinated shard_map path
# --------------------------------------------------------------------------
def _bucket(x, p):
    """Multiplicative hash bucket in [0, p) — owner device of vertex x."""
    return ((x.astype(jnp.uint32) * _HASH_MULT) % jnp.uint32(p)).astype(jnp.int32)


def _route_round_trip(payload, row_valid, dest, axes, p, cap, answer_fn, n_ans):
    """Send (q, k) int32 payload rows to ``dest`` devices, answer, send back.

    answer_fn(recv_payload (p*cap, k), recv_valid (p*cap,)) -> (p*cap, n_ans) i32.
    Returns (ans (q, n_ans), overflow_count). Overflowed rows answer 0.
    """
    q, k = payload.shape
    slot_key = dest.astype(jnp.int64) * (q + 1) + jnp.arange(q)
    _, order = sort_by_key(slot_key, jnp.arange(q))
    d_sorted = dest[order]
    slot = segmented_iota(segment_starts(d_sorted.astype(jnp.int64)))
    send_idx = d_sorted.astype(jnp.int64) * cap + slot
    ok = (slot < cap) & row_valid[order]
    overflow = jnp.sum((slot >= cap) & row_valid[order])
    # not-ok rows are routed out of bounds; mode="drop" discards them
    safe_idx = jnp.where(ok, send_idx, p * cap)

    send_buf = jnp.zeros((p * cap, k), jnp.int32)
    send_buf = send_buf.at[safe_idx].set(payload[order], mode="drop")
    send_valid = (
        jnp.zeros((p * cap,), jnp.int32)
        .at[safe_idx]
        .max(ok.astype(jnp.int32), mode="drop")
    )

    recv = jax.lax.all_to_all(send_buf, axes, 0, 0, tiled=True)
    recv_valid = (
        jax.lax.all_to_all(send_valid, axes, 0, 0, tiled=True).astype(bool)
    )

    ans = answer_fn(recv, recv_valid)  # (p*cap, n_ans)
    back = jax.lax.all_to_all(ans, axes, 0, 0, tiled=True)

    gather_idx = jnp.where(ok, send_idx, 0)
    out_sorted = jnp.where(ok[:, None], back[gather_idx], 0)
    out = jnp.zeros((q, n_ans), jnp.int32).at[order].set(out_sorted)
    return out, overflow


class _LocalStruct(NamedTuple):
    """Per-device shard of the shared structure (arcs of owned vertices)."""

    key_desc: jax.Array  # (n,) int64 pack2(src, S-1-pos)
    key_rank: jax.Array  # (n,) int64 pack2(src, rank)
    src: jax.Array
    dst: jax.Array
    pos: jax.Array
    rank: jax.Array
    ekey: jax.Array  # (ne,) int64 pack2(min,max) of owned closing-index edges
    epos: jax.Array


def _build_structures(W, pos_g, valid_e, axes, p, S, cap_a, cap_e):
    """all_to_all arcs/edges to owner shards, then sort + rank locally."""
    src = jnp.concatenate([W[:, 0], W[:, 1]])
    dst = jnp.concatenate([W[:, 1], W[:, 0]])
    pos = jnp.concatenate([pos_g, pos_g])
    valid_a = jnp.concatenate([valid_e, valid_e])

    arcs = jnp.stack([src, dst, pos], axis=1)
    recv, ovf_a = _route_one_way(arcs, valid_a, _bucket(src, p), axes, p, cap_a)
    a_src, a_dst, a_pos, a_valid = (
        recv[:, 0],
        recv[:, 1],
        recv[:, 2],
        recv[:, 3].astype(bool),
    )
    kd = jnp.where(a_valid, pack2(a_src, (S - 1) - a_pos), INF64)
    # slim sort: src and pos are recoverable from the packed key, so the sort
    # carries only (key, dst) — 12B/record instead of 20B (EXPERIMENTS §Perf-3)
    kd_s, dst_s = sort_by_key(kd, a_dst)
    src_s = (kd_s >> 32).astype(jnp.int32)
    pos_s = (S - 1) - (kd_s & jnp.int64(0xFFFFFFFF)).astype(jnp.int32)
    n_val = jnp.sum(a_valid)
    rank_s = segmented_iota(segment_starts(src_s.astype(jnp.int64)))
    kr = jnp.where(jnp.arange(kd_s.shape[0]) < n_val, pack2(src_s, rank_s), INF64)

    emin = jnp.minimum(W[:, 0], W[:, 1])
    emax = jnp.maximum(W[:, 0], W[:, 1])
    edges = jnp.stack([emin, emax, pos_g], axis=1)
    recv_e, ovf_e = _route_one_way(
        edges, valid_e, _bucket(emin, p), axes, p, cap_e
    )
    e_valid = recv_e[:, 3].astype(bool)
    ek = jnp.where(e_valid, pack2(recv_e[:, 0], recv_e[:, 1]), INF64)
    ek_s, epos_s = sort_by_key(ek, recv_e[:, 2])

    struct = _LocalStruct(
        key_desc=kd_s,
        key_rank=kr,
        src=src_s,
        dst=dst_s,
        pos=pos_s,
        rank=rank_s,
        ekey=ek_s,
        epos=epos_s,
    )
    return struct, ovf_a + ovf_e


def _route_one_way(payload, row_valid, dest, axes, p, cap):
    """Like _route_round_trip but the records stay at the destination."""
    q, k = payload.shape
    slot_key = dest.astype(jnp.int64) * (q + 1) + jnp.arange(q)
    _, order = sort_by_key(slot_key, jnp.arange(q))
    d_sorted = dest[order]
    slot = segmented_iota(segment_starts(d_sorted.astype(jnp.int64)))
    send_idx = d_sorted.astype(jnp.int64) * cap + slot
    ok = (slot < cap) & row_valid[order]
    overflow = jnp.sum((slot >= cap) & row_valid[order])
    safe_idx = jnp.where(ok, send_idx, p * cap)  # drop not-ok rows
    buf = jnp.zeros((p * cap, k + 1), jnp.int32)
    rows = jnp.concatenate(
        [payload[order], ok[:, None].astype(jnp.int32)], axis=1
    )
    buf = buf.at[safe_idx].set(rows, mode="drop")
    recv = jax.lax.all_to_all(buf, axes, 0, 0, tiled=True)
    return recv, overflow


def make_coordinated_update(
    mesh, r: int, s: int, capacity_factor: float = 2.0,
    scheme: EstimatorScheme = GLOBAL,
):
    """Explicit coordinated bulk update over ``mesh`` (all axes flattened).

    r: total estimators; s: total batch size. Both divisible by device count.
    Returns jit(f)(state, W, n_valid, key) -> (state, overflow_count) with the
    estimator/W shardings baked in. The routed-multisearch kernel below *is*
    the paper's bulkUpdateAll, so only schemes that share that update
    (``scheme.update_kind == "nbsi"``: global, local) can run it; their state
    specs are still derived from the axis roles like every other plan.
    """
    scheme = resolve_scheme(scheme)
    if scheme.update_kind != "nbsi":
        raise ValueError(
            f"scheme {scheme.name!r} (update_kind={scheme.update_kind!r}) has "
            "no coordinated shard_map kernel; use a pjit or single plan"
        )
    axes = tuple(mesh.axis_names)
    p = mesh.size
    assert r % p == 0 and s % p == 0, (r, s, p)
    s_local = s // p
    cap_a = max(int(2 * s_local * capacity_factor / p), 8)
    cap_e = max(int(s_local * capacity_factor / p), 8)
    cap_q = max(int(2 * (r // p) * capacity_factor / p), 8)

    def update(state: EstimatorState, W, n_valid, key):
        me = jax.lax.axis_index(axes)
        r_local = state.f1.shape[0]
        pos_g = me.astype(jnp.int32) * s_local + jnp.arange(s_local, dtype=jnp.int32)
        valid_e = pos_g < n_valid
        dev_key = jax.random.fold_in(key, me)
        k1, k2, k3 = jax.random.split(dev_key, 3)

        struct, ovf_build = _build_structures(
            W, pos_g, valid_e, axes, p, S=s, cap_a=cap_a, cap_e=cap_e
        )

        # ---- Step 1: level-1 reservoir; fetch W[idx] from owner shard ----
        m = state.m_seen
        total = m + n_valid.astype(jnp.int64)
        t = jax.random.randint(
            k1, (r_local,), jnp.int64(0), jnp.maximum(total, 1), dtype=jnp.int64
        )
        replace = (t >= m) & (total > 0)
        idx = jnp.clip(
            t - m, 0, jnp.maximum(n_valid.astype(jnp.int64) - 1, 0)
        ).astype(jnp.int32)

        def fetch_edge(recv, recv_valid):
            local = recv[:, 0] - me.astype(jnp.int32) * s_local
            local = jnp.clip(local, 0, s_local - 1)
            return W[local]

        edge_ans, ovf1 = _route_round_trip(
            idx[:, None], replace, idx // s_local, axes, p, cap_q, fetch_edge, 2
        )
        f1 = jnp.where(replace[:, None], edge_ans, state.f1)
        chi_minus = jnp.where(replace, 0, state.chi)
        f2 = jnp.where(replace[:, None], jnp.int32(-1), state.f2)
        has_f3 = state.has_f3 & ~replace
        f1_bpos = jnp.where(replace, idx, -1)

        # ---- Step 2: rank queries (u and v stacked into one routed batch) ----
        u, v = f1[:, 0], f1[:, 1]
        have_f1 = u >= 0
        ep = jnp.concatenate([u, v])
        bp = jnp.concatenate([f1_bpos, f1_bpos])
        qvalid = jnp.concatenate([have_f1, have_f1])

        def rank_answer(recv, recv_valid):
            endp, bpos = recv[:, 0], recv[:, 1]
            fresh = bpos >= 0
            j, found = exact_multisearch(
                struct.key_desc, pack2(endp, (s - 1) - bpos)
            )
            r_fresh = jnp.where(found, struct.rank[jnp.maximum(j, 0)], 0)
            lo = jnp.searchsorted(
                struct.key_desc, pack2(endp, jnp.zeros_like(bpos))
            )
            hi = jnp.searchsorted(
                struct.key_desc, pack2(endp, jnp.full_like(bpos, s))
            )
            deg = (hi - lo).astype(jnp.int32)
            return jnp.where(fresh, r_fresh, deg)[:, None]

        payload = jnp.stack([ep, bp], axis=1)
        rk, ovf2 = _route_round_trip(
            payload, qvalid, _bucket(ep, p), axes, p, cap_q, rank_answer, 1
        )
        ld, rd = rk[:r_local, 0], rk[r_local:, 0]
        chi_plus = ld + rd
        chi = chi_minus + chi_plus

        coin = jax.random.uniform(k2, (r_local,), dtype=jnp.float32)
        p_new = chi_plus.astype(jnp.float32) / jnp.maximum(
            chi.astype(jnp.float32), 1.0
        )
        take_new = have_f1 & (chi_plus > 0) & (coin < p_new)
        phi = jax.random.randint(
            k3, (r_local,), 0, jnp.maximum(chi_plus, 1), dtype=jnp.int32
        )
        t_src = jnp.where(phi < ld, u, v)
        t_rank = jnp.where(phi < ld, phi, phi - ld)

        def decode_answer(recv, recv_valid):
            ts, tr = recv[:, 0], recv[:, 1]
            j, found = exact_multisearch(struct.key_rank, pack2(ts, tr))
            j = jnp.maximum(j, 0)
            a, b = struct.src[j], struct.dst[j]
            return jnp.stack(
                [
                    jnp.where(found, jnp.minimum(a, b), -1),
                    jnp.where(found, jnp.maximum(a, b), -1),
                    jnp.where(found, struct.pos[j], -1),
                ],
                axis=1,
            )

        dec, ovf3 = _route_round_trip(
            jnp.stack([t_src, t_rank], axis=1),
            take_new,
            _bucket(t_src, p),
            axes,
            p,
            cap_q,
            decode_answer,
            3,
        )
        found2 = dec[:, 0] >= 0
        take_new = take_new & found2
        f2 = jnp.where(take_new[:, None], dec[:, :2], f2)
        f2_bpos = jnp.where(take_new, dec[:, 2], -1)
        has_f3 = has_f3 & ~take_new

        # ---- Step 3: closing-edge lookups ----
        a, b = f2[:, 0], f2[:, 1]
        have_wedge = have_f1 & (a >= 0)
        u_sh = (u == a) | (u == b)
        o1 = jnp.where(u_sh, v, u)
        a_sh = (a == u) | (a == v)
        o2 = jnp.where(a_sh, b, a)
        cmin, cmax = jnp.minimum(o1, o2), jnp.maximum(o1, o2)

        def close_answer(recv, recv_valid):
            j, found = exact_multisearch(
                struct.ekey, pack2(recv[:, 0], recv[:, 1])
            )
            return jnp.where(found, struct.epos[jnp.maximum(j, 0)], -1)[:, None]

        cls, ovf4 = _route_round_trip(
            jnp.stack([cmin, cmax], axis=1),
            have_wedge,
            _bucket(cmin, p),
            axes,
            p,
            cap_q,
            close_answer,
            1,
        )
        p3 = cls[:, 0]
        closed_now = have_wedge & (p3 >= 0) & (p3 > f2_bpos)
        has_f3 = has_f3 | closed_now

        new_state = EstimatorState(
            f1=f1,
            chi=chi,
            f2=f2,
            has_f3=has_f3,
            m_seen=state.m_seen + n_valid.astype(jnp.int64),
        )
        overflow = ovf_build + ovf1 + ovf2 + ovf3 + ovf4
        return new_state, jax.lax.psum(overflow, axes)

    rep = P()
    state_spec = scheme_state_specs(scheme, axes)
    shmapped = _shard_map(
        update,
        mesh,
        in_specs=(state_spec, P(axes, None), rep, rep),
        out_specs=(state_spec, rep),
    )
    return jax.jit(shmapped, donate_argnums=(0,))
