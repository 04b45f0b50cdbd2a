"""Production mesh construction.

A function (not a module-level constant) so importing this module never touches
jax device state. Per pod: 16x16 = 256 chips as ("data", "model"); multi-pod
adds a leading "pod" axis (2 pods = 512 chips, pod axis mapped across DCN/ICI
superlinks).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _axis_kw(n: int) -> dict:
    # the engines' programs constrain shardings by name, which needs Auto
    # axes; jax.make_mesh defaults to Explicit
    return {"axis_types": (AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_kw(len(axes)))


def make_test_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh over however many (host) devices exist — for unit tests."""
    return jax.make_mesh(shape, axes, **_axis_kw(len(axes)))


def make_stream_mesh(spec: str):
    """Mesh for the streaming engine from a CLI ``--mesh`` spec.

    Spec grammar (axes appear in the order written):
      ""                        -> None (no mesh; the engine runs ``single``)
      "8"                       -> 8-way estimator sharding, axes ("estimators",)
      "tenants=2"               -> pure tenant sharding over 2 devices
      "tenants=2,estimators=4"  -> the 2-D banked layout over 8 devices

    The axis matching ``EngineConfig.tenant_axis`` (default "tenants") carries
    the bank's tenant dimension; every other axis shards the estimator
    dimension (see repro.core.distributed.banked_state_sharding).
    docs/scaling.md maps specs to execution plans.
    """
    spec = spec.strip()
    if not spec:
        return None
    names, sizes = [], []
    for part in spec.split(","):
        part = part.strip()
        if "=" in part:
            name, _, size = part.partition("=")
        else:
            name, size = "estimators", part
        try:
            n = int(size)
        except ValueError:
            raise ValueError(
                f"bad --mesh entry {part!r}; want N or axis=N "
                "(e.g. 'tenants=2,estimators=4')"
            ) from None
        if n < 1 or name.strip() in names:
            raise ValueError(f"bad --mesh spec {spec!r}")
        names.append(name.strip())
        sizes.append(n)
    return jax.make_mesh(tuple(sizes), tuple(names), **_axis_kw(len(names)))


def mesh_axes(mesh) -> tuple[str, ...]:
    return tuple(mesh.axis_names)
