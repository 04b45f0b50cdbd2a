"""Backend selection for TriangleCountEngine.

One engine API, six execution plans over the same ``bulk_update_all``
semantics (and therefore the same estimate distribution — counter-based RNG
makes the paths interchangeable mid-stream):

  single                   jit(vmap(bulk_update_all)) over the tenant axis
                           (a bank of one runs the update on its row).
                           The default on one device; N streams share one
                           program, all state on one device.
  pjit_independent         paper Section 5's "independent bulk parallel": W
                           replicated, each device sorts the whole batch for
                           its estimator shard; p-times duplicated sort work.
                           The multisearch merges queries and keys in one
                           sort, so the partitioner all-gathers each search's
                           queries and every device merges all of them
                           (PERF.md §7). Single-tenant.
  pjit_coordinated         W sharded; XLA's SPMD partitioner inserts the
                           collectives for the global sorts/searches.
                           Single-tenant.
  shardmap                 the explicit coordinated scheme (hash-partitioned
                           arcs + routed multisearches,
                           repro.core.distributed). Reports a bucket-overflow
                           diagnostic the engine watches. Single-tenant.
  banked_pjit_independent  the tenant-sharded bank: the bank's tenant dim
                           shards over the mesh axis named
                           ``config.tenant_axis``, estimators over every
                           remaining axis (the 2-D (tenants, estimators)
                           layout when both exist); W replicated across the
                           estimator axes.
  banked_pjit_coordinated  same layout with W sharded across the estimator
                           axes — SPMD collectives stay *inside* each tenant
                           group; the tenant axis itself is collective-free.
                           In both banked plans each search's queries are
                           all-gathered within the tenant group, as above.

Chunked ingest (``build_chunk``, on the single + banked plans) routes through
``scheme.chunk_update`` -> ``repro.core.bulk.bulk_update_chunk``, which
dispatches on ``repro.primitives.ingest.ingest_backend()``: "scan" replays
the reference per-batch loop, "xla" runs the fused hoisted-RNG pipeline, and
"pallas" additionally lands the whole chunk in the resident
``kernels/fused_ingest.py`` kernel (each reservoir tile touched once per
chunk). All three are bit-identical — the plans above need no awareness of
which one is active, and the signed/turnstile delete path
(``bulk_delete_chunk``) dispatches the same way. See docs/engine.md for the
dispatch table.

``select_backend`` implements the "auto" policy: a multi-tenant bank on a mesh
with a divisible tenants axis -> a banked plan (coordinated when an estimator
axis exists and shapes divide it, else independent); a bank without such a
mesh -> single. Single tenant: no mesh (or a 1-device mesh) -> single; a real
mesh with divisible shapes -> shardmap (the paper's recommended coordinated
scheme); otherwise pjit_coordinated as the safe fallback.
docs/scaling.md is the full decision handbook.

Every plan is **scheme-generic**: the builders resolve
``EngineConfig.scheme`` through ``repro.core.schemes`` and jit the scheme's
own update, with state shardings derived from the scheme's axis roles
(``repro.core.distributed.scheme_state_sharding``) — no plan references state
fields by name. Sharded plans also carry a ``build_estimate`` builder — the
device-resident query program (``make_banked_estimate`` /
``make_sharded_estimate``) the engine prefers over gathering the bank to
host; it is None exactly when the scheme has no shardable estimate stage
(or r does not divide the mesh), in which case ``estimate()`` keeps the
gather path. The one restriction: ``shardmap``'s routed-multisearch kernel
hardcodes the paper's NBSI update, so schemes with a different update
(``update_kind != "nbsi"``, i.e. ``naive``) fall back to ``pjit_coordinated``
under "auto" and are rejected when named explicitly.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax

from repro.core.schemes import EstimatorScheme, resolve_scheme

BACKENDS = (
    "single",
    "pjit_independent",
    "pjit_coordinated",
    "shardmap",
    "banked_pjit_independent",
    "banked_pjit_coordinated",
)


@dataclass(frozen=True)
class BackendPlan:
    """How the engine executes ingest: a name plus a builder returning the
    jitted update callable for a given config/mesh."""

    name: str
    banked: bool  # state carries a leading (n_tenants,) axis
    reports_overflow: bool  # update returns (state, overflow)
    build: Callable[..., Callable]
    # builder for the K-batch fused ingest (state, Ws, n_valids, keys, step0);
    # None = the plan cannot chunk (chunk_size must stay 1)
    build_chunk: Optional[Callable] = None
    # elastic-bank variant of build_chunk: step0 is a (T,) per-slot cursor
    # vector instead of a replicated scalar, because slots in a slab-allocated
    # bank join at different times (repro.engine.elastic). Present exactly
    # where build_chunk is — the elastic tier is restricted to banked plans.
    build_chunk_elastic: Optional[Callable] = None
    # (config, mesh) -> EstimatorState of NamedShardings for the bank, or None
    # for plans whose state lives unsharded on the default device. The engine
    # device_puts fresh and snapshot-restored banks through this, which is
    # what makes snapshots portable across mesh shapes.
    bank_sharding: Optional[Callable] = None
    # (config, mesh) -> NamedSharding for a (T, s, 2) batch / a staged
    # (T, K, s, 2) superbatch; ingest/stage_chunk device_put through these so
    # sharded plans upload host->shards once instead of host->device 0->reshard
    batch_w_sharding: Optional[Callable] = None
    chunk_w_sharding: Optional[Callable] = None
    # (config, mesh) -> jitted device-resident query (state -> estimates), or
    # None when this (plan, scheme, shape) combination must answer queries by
    # gathering the bank to host. Sharded plans set it so estimate() runs
    # where the state lives (repro.core.distributed.make_banked_estimate /
    # make_sharded_estimate); the gather path stays available as the oracle.
    build_estimate: Optional[Callable] = None
    # (config, mesh) -> jitted deletion update (the turnstile path). Banked
    # plans: f(state_bank, Db (T,s,2), n_valid (T,)); unbanked plans:
    # f(state, D (s,2), n_valid). The deletion kernel is elementwise per
    # estimator and carries no RNG, so every plan supports it — shardmap
    # included (it shares the pjit builder; no routed collectives needed).
    build_delete: Optional[Callable] = None


def _tenant_axis(config) -> str:
    return getattr(config, "tenant_axis", "tenants")


def config_scheme(config) -> EstimatorScheme:
    """Resolve the EstimatorScheme an engine config names (default global)."""
    return resolve_scheme(
        getattr(config, "scheme", "global"),
        getattr(config, "scheme_params", None),
    )


def _over_tenants(fn: Callable, in_axes=0) -> Callable:
    """``jax.vmap(fn, in_axes)`` over the bank's leading tenant axis; a bank
    of one tenant runs ``fn`` on its row and gets the axis back after.

    On a TPU a sort over a (1, n) array tiles 1 x 128 where one over (n,)
    tiles by 1024: the multisearch's merge sorts ran 7-8x slower vmapped over
    one tenant (PERF.md §6). The choice is made while tracing, from the
    bank's shape; the name stays ``fn``'s, which names the compiled module.
    """
    batched = jax.vmap(fn, in_axes=in_axes)

    @functools.wraps(fn)
    def run(state, *args):
        if jax.tree.leaves(state)[0].shape[0] != 1:
            return batched(state, *args)
        axes = in_axes if isinstance(in_axes, tuple) else (in_axes,) * (len(args) + 1)
        row = [
            a if ax is None else jax.tree.map(lambda x: x[0], a)
            for a, ax in zip((state, *args), axes)
        ]
        return jax.tree.map(lambda x: x[None], fn(*row))

    return run


def _build_single(config, mesh) -> Callable:
    scheme = config_scheme(config)
    return jax.jit(_over_tenants(scheme.bulk_update), donate_argnums=(0,))


def _build_single_chunk(config, mesh) -> Callable:
    # scan over the K axis inside the jit; the stream key and batch cursor
    # ride in unvmapped/traced so one compiled program serves the whole stream
    scheme = config_scheme(config)
    return jax.jit(
        _over_tenants(scheme.chunk_update, in_axes=(0, 0, 0, 0, None)),
        donate_argnums=(0,),
    )


def _build_single_chunk_elastic(config, mesh) -> Callable:
    # per-slot step0 vector: each bank slot folds its OWN cursor, so slots
    # that joined at different stream positions stay on their own RNG streams
    scheme = config_scheme(config)
    return jax.jit(
        _over_tenants(scheme.chunk_update, in_axes=(0, 0, 0, 0, 0)),
        donate_argnums=(0,),
    )


def _build_pjit(w_mode: str):
    def build(config, mesh) -> Callable:
        from repro.core.distributed import make_pjit_update

        return make_pjit_update(
            mesh, w_mode=w_mode, scheme=config_scheme(config)
        )

    return build


def _build_banked_pjit(w_mode: str):
    def build(config, mesh) -> Callable:
        from repro.core.distributed import make_banked_pjit_update

        return make_banked_pjit_update(
            mesh,
            w_mode=w_mode,
            tenant_axis=_tenant_axis(config),
            scheme=config_scheme(config),
        )

    return build


def _build_banked_pjit_chunk(w_mode: str, per_tenant_step0: bool = False):
    def build(config, mesh) -> Callable:
        from repro.core.distributed import make_banked_pjit_chunk_update

        return make_banked_pjit_chunk_update(
            mesh,
            w_mode=w_mode,
            tenant_axis=_tenant_axis(config),
            scheme=config_scheme(config),
            per_tenant_step0=per_tenant_step0,
        )

    return build


def _build_single_delete(config, mesh) -> Callable:
    scheme = config_scheme(config)
    return jax.jit(_over_tenants(scheme.delete_update), donate_argnums=(0,))


def _build_pjit_delete(config, mesh) -> Callable:
    from repro.core.distributed import make_pjit_delete

    return make_pjit_delete(mesh, scheme=config_scheme(config))


def _build_banked_delete(config, mesh) -> Callable:
    from repro.core.distributed import make_banked_delete

    return make_banked_delete(
        mesh, tenant_axis=_tenant_axis(config), scheme=config_scheme(config)
    )


def _banked_sharding(config, mesh):
    from repro.core.distributed import banked_state_sharding

    return banked_state_sharding(
        mesh, tenant_axis=_tenant_axis(config), scheme=config_scheme(config)
    )


def _banked_batch_w_sharding(w_mode: str):
    def f(config, mesh):
        from repro.core.distributed import banked_batch_w_sharding

        return banked_batch_w_sharding(
            mesh, w_mode=w_mode, tenant_axis=_tenant_axis(config)
        )

    return f


def _banked_chunk_w_sharding(w_mode: str):
    def f(config, mesh):
        from repro.core.distributed import banked_chunk_w_sharding

        return banked_chunk_w_sharding(
            mesh, w_mode=w_mode, tenant_axis=_tenant_axis(config)
        )

    return f


def _build_banked_estimate(config, mesh) -> Optional[Callable]:
    from repro.core.distributed import make_banked_estimate

    scheme = config_scheme(config)
    if not scheme.shardable_estimate:
        return None  # estimate() falls back to the gather-to-host oracle
    return make_banked_estimate(
        mesh,
        config.r,
        tenant_axis=_tenant_axis(config),
        scheme=scheme,
        groups=config.groups,
    )


def _build_sharded_estimate(config, mesh) -> Optional[Callable]:
    from repro.core.distributed import make_sharded_estimate

    scheme = config_scheme(config)
    # the pjit plans tolerate r not dividing the mesh (XLA pads); the
    # shard_map query does not — gather-to-host covers that corner
    if not scheme.shardable_estimate or config.r % _mesh_size(mesh):
        return None
    return make_sharded_estimate(
        mesh, config.r, scheme=scheme, groups=config.groups
    )


def _build_shardmap(config, mesh) -> Callable:
    from repro.core.distributed import make_coordinated_update

    return make_coordinated_update(
        mesh,
        r=config.r,
        s=config.batch_size,
        capacity_factor=config.capacity_factor,
        scheme=config_scheme(config),
    )


def _banked_plan(w_mode: str) -> BackendPlan:
    return BackendPlan(
        f"banked_pjit_{w_mode.replace('_xla', '')}",
        banked=True,
        reports_overflow=False,
        build=_build_banked_pjit(w_mode),
        build_chunk=_build_banked_pjit_chunk(w_mode),
        build_chunk_elastic=_build_banked_pjit_chunk(
            w_mode, per_tenant_step0=True
        ),
        bank_sharding=_banked_sharding,
        batch_w_sharding=_banked_batch_w_sharding(w_mode),
        chunk_w_sharding=_banked_chunk_w_sharding(w_mode),
        build_estimate=_build_banked_estimate,
        build_delete=_build_banked_delete,
    )


_PLANS = {
    "single": BackendPlan(
        "single", True, False, _build_single, _build_single_chunk,
        build_chunk_elastic=_build_single_chunk_elastic,
        build_delete=_build_single_delete,
    ),
    "pjit_independent": BackendPlan(
        "pjit_independent", False, False, _build_pjit("independent"),
        build_estimate=_build_sharded_estimate,
        build_delete=_build_pjit_delete,
    ),
    "pjit_coordinated": BackendPlan(
        "pjit_coordinated", False, False, _build_pjit("coordinated_xla"),
        build_estimate=_build_sharded_estimate,
        build_delete=_build_pjit_delete,
    ),
    "shardmap": BackendPlan(
        "shardmap", False, True, _build_shardmap,
        build_estimate=_build_sharded_estimate,
        build_delete=_build_pjit_delete,
    ),
    "banked_pjit_independent": _banked_plan("independent"),
    "banked_pjit_coordinated": _banked_plan("coordinated_xla"),
}


def _mesh_size(mesh: Any) -> int:
    return int(mesh.size) if mesh is not None else 1


def _banked_mesh_fit(config, mesh) -> Optional[tuple[int, int]]:
    """(t_size, e_size) when ``mesh`` can host this bank tenant-sharded:
    it has the tenant axis, the axis divides n_tenants, and any estimator
    axes divide r. None when the bank must fall back to ``single``."""
    if mesh is None:
        return None
    ta = _tenant_axis(config)
    if ta not in mesh.axis_names:
        return None
    t_size = int(mesh.shape[ta])
    e_size = int(mesh.size) // t_size
    if t_size < 1 or config.n_tenants % t_size != 0:
        return None
    if e_size > 1 and config.r % e_size != 0:
        return None
    return t_size, e_size


def select_backend(config, mesh: Optional[Any] = None) -> BackendPlan:
    """Resolve config.backend (possibly "auto") to a concrete BackendPlan."""
    scheme = config_scheme(config)  # validates the scheme name/params early
    name = config.backend
    p = _mesh_size(mesh)
    if name == "auto":
        fit = _banked_mesh_fit(config, mesh) if p > 1 else None
        if fit is not None:
            t_size, e_size = fit
            # an estimator axis with divisible batches earns the W shard;
            # otherwise replicate W per tenant group (pure tenant split)
            name = (
                "banked_pjit_coordinated"
                if e_size > 1 and config.batch_size % e_size == 0
                else "banked_pjit_independent"
            )
        elif config.n_tenants > 1 or p <= 1:
            name = "single"
        elif (
            scheme.update_kind == "nbsi"
            and config.r % p == 0
            and config.batch_size % p == 0
        ):
            name = "shardmap"
        else:
            name = "pjit_coordinated"
    if name not in _PLANS:
        raise ValueError(f"unknown backend {name!r}; choose from {BACKENDS}")
    plan = _PLANS[name]
    if name == "shardmap" and scheme.update_kind != "nbsi":
        raise ValueError(
            f"backend 'shardmap' hardcodes the paper's NBSI update; scheme "
            f"{scheme.name!r} (update_kind={scheme.update_kind!r}) cannot run "
            "it — use 'single' or a pjit plan"
        )
    if not plan.banked and config.n_tenants > 1:
        raise ValueError(
            f"backend {name!r} is single-tenant; multi-tenant banks need "
            "'single', a banked_pjit_* plan, or 'auto'"
        )
    if plan.name != "single" and mesh is None:
        raise ValueError(f"backend {name!r} requires a mesh")
    if plan.name.startswith("banked_"):
        fit = _banked_mesh_fit(config, mesh)
        if fit is None:
            raise ValueError(
                f"backend {name!r} needs a mesh with a "
                f"{_tenant_axis(config)!r} axis whose size divides "
                f"n_tenants={config.n_tenants} and whose remaining axes "
                f"divide r={config.r}; got mesh "
                f"{dict(mesh.shape) if mesh is not None else None}"
            )
        _, e_size = fit
        if (
            plan.name == "banked_pjit_coordinated"
            and e_size > 1
            and config.batch_size % e_size != 0
        ):
            # fail here, not at the first ingest: the coordinated plan shards
            # W's batch dim over the estimator axes
            raise ValueError(
                f"banked_pjit_coordinated needs batch_size "
                f"({config.batch_size}) divisible by the estimator axes "
                f"product ({e_size}); use banked_pjit_independent (W "
                "replicated per tenant group) instead"
            )
    if plan.name == "shardmap" and (
        config.r % p != 0 or config.batch_size % p != 0
    ):
        raise ValueError(
            f"shardmap needs r ({config.r}) and batch_size "
            f"({config.batch_size}) divisible by mesh size {p}"
        )
    if getattr(config, "chunk_size", 1) > 1 and plan.build_chunk is None:
        raise ValueError(
            f"backend {name!r} does not support chunked ingest; "
            "chunk_size > 1 needs a banked plan ('single' or 'banked_pjit_*')"
        )
    return plan
