"""Compile for a described TPU v5e, without one attached.

The TPU compiler ships with jaxlib's TPU plug-in and compiles for a chip that
is only described (``jax.experimental.topologies``). Nothing runs, so these
tests say nothing about results or speed; they catch what interpret mode
cannot: kernels the Mosaic compiler refuses, and ingest programs that do not
compile or fit at the paper's shapes.

The topology is described inside a module fixture (never at import time):
only one process may load the TPU library, and every test worker imports
this file. Kernels are compiled through ``repro.kernels.ops`` with
``_on_tpu`` patched to True, so they are built exactly as on a chip
(``interpret=False``). A kernel the compiler refuses is a strict xfail
carrying the compiler's first line; none of those is what ``auto`` selects.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro  # noqa: F401  (x64, as in every program run)
from repro.kernels import ops

R, S, K = 2**21, 2**20, 4  # configs/triangle_stream.py bulk_s1m_r2m


@pytest.fixture(scope="module")
def chip():
    """A SingleDeviceSharding on one chip of a described v5e:2x2, with the
    kernel wrappers steered to their TPU (compiled) form."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs under /tmp
        from jax.experimental import topologies

        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        mp.setattr(ops, "_on_tpu", lambda: True)
        yield SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_case(name, sh):
    """(wrapper, argument shapes) for one kernel at its real tile size."""
    i32, i64 = jnp.int32, jnp.int64
    if name == "multisearch_counts":
        # the fused Q1 structure: 2s int64 pack2 keys, a 3r/32 query slab
        return ops.multisearch_counts_op, (
            _shape(sh, (2 * S,), i64), _shape(sh, (3 * 2**16,), i64))
    if name == "segment_sum_f32":
        return (
            lambda v, i: ops.segment_sum_op(v, i, 4096),
            (_shape(sh, (2**16, 128), jnp.float32), _shape(sh, (2**16,), i32)),
        )
    if name == "segment_sum_local_attribution":
        # the local scheme's call: f64 coarse estimates, one column
        return (
            lambda v, i: ops.segment_sum_op(v, i, 4096),
            (_shape(sh, (3 * 2**16, 1), jnp.float64),
             _shape(sh, (3 * 2**16,), i32)),
        )
    if name == "segscan":
        return ops.segscan_op, (
            _shape(sh, (2 * S,), i32), _shape(sh, (2 * S,), jnp.bool_))
    if name == "bitonic_sort_tiles":
        return ops.bitonic_sort_tiles_op, (
            _shape(sh, (2**16,), i64), _shape(sh, (2**16,), i32))
    if name == "fused_ingest":
        k, s, r = 8, 4096, 65536
        return ops.fused_ingest_op, tuple(
            _shape(sh, shape, dt)
            for shape, dt in [
                ((r, 2), i32), ((r,), i32), ((r, 2), i32), ((r,), jnp.bool_),
                ((k, 2 * s), i64), ((k, 2 * s), i64), ((k, 2 * s), i32),
                ((k, 2 * s), i32), ((k, 2 * s), i32),
                ((k, s), i64), ((k, s), i32),
                ((k, r), jnp.bool_), ((k, r, 2), i32), ((k, r), i32),
                ((k, r), jnp.float32), ((k, r), jnp.uint32),
                ((k, r), jnp.uint32),
            ]
        )
    raise AssertionError(name)


def _refused(first_line, raises):
    return pytest.mark.xfail(strict=True, raises=raises, reason=first_line)


@pytest.mark.parametrize(
    "name",
    [
        "multisearch_counts",
        "segment_sum_f32",
        pytest.param(
            "segment_sum_local_attribution",
            marks=_refused(
                "NotImplementedError: 64-bit types are not supported",
                NotImplementedError,
            ),
        ),
        pytest.param(
            "segscan",
            marks=_refused(
                "RecursionError: maximum recursion depth exceeded",
                RecursionError,
            ),
        ),
        pytest.param(
            "bitonic_sort_tiles",
            marks=_refused(
                "ZeroDivisionError: integer modulo by zero", ZeroDivisionError
            ),
        ),
        pytest.param(
            "fused_ingest",
            marks=_refused(
                "RecursionError: maximum recursion depth exceeded",
                RecursionError,
            ),
        ),
    ],
)
def test_kernel_compiles_for_v5e(chip, name):
    fn, args = _kernel_case(name, chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", ["bulk_update_all", "bulk_update_chunk"])
def test_ingest_program_compiles_at_paper_shape(chip, name):
    """The engine's per-batch and K-batch ingest programs at the paper's
    shape, as ``auto`` builds them with the TPU steered on (about one and
    two minutes of compiling on a CPU host)."""
    from repro.core.schemes import GLOBAL
    from repro.engine import EngineConfig, select_backend
    from repro.primitives.ingest import ingest_backend
    from repro.primitives.search import multisearch_backend

    cfg = EngineConfig(r=R, batch_size=S, chunk_size=K)
    plan = select_backend(cfg, None)
    assert plan.name == "single"
    assert (ingest_backend(), multisearch_backend()) == ("xla", "xla")
    bank = jax.eval_shape(
        lambda: jax.tree.map(lambda x: x[None], GLOBAL.init_state(R))
    )
    bank = jax.tree.map(lambda a: _shape(chip, a.shape, a.dtype), bank)
    keys = _shape(chip, (1, 2), jnp.uint32)
    if name == "bulk_update_all":
        fn, args = plan.build(cfg, None), (
            bank, _shape(chip, (1, S, 2), jnp.int32),
            _shape(chip, (1,), jnp.int32), keys)
    else:
        fn, args = plan.build_chunk(cfg, None), (
            bank, _shape(chip, (1, K, S, 2), jnp.int32),
            _shape(chip, (1, K), jnp.int32), keys, _shape(chip, (), jnp.int64))
    compiled = fn.lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 4 * 2**30, used  # far inside a v5e's 16 GB
    text = compiled.as_text()
    assert "tpu_custom_call" not in text  # the XLA path
    # a bank of one runs on its row: no sort is over a (1, n) batch, which
    # the TPU tiles 1 x 128 (the chunk program's structure build sorts its
    # K batches as one (K, n) batch)
    sorts = re.findall(r"= \(?[su]\d+\[([\d,]*)\][^=]* sort\(", text)
    assert sorts and not any(d.startswith("1,") for d in sorts), sorts


def test_merge_scans_keep_their_scope(chip):
    """The merge's running count and minimum run along 128-entry rows, which
    the TPU compiler leaves under the ``multisearch/merge`` scope where
    ``multisearch_share`` reads them; only the carry across the rows (1/128
    of the entries) is rewritten without it."""
    from repro.primitives.search import multisearch_bounds

    n, q = 2**12, 2**14
    args = _shape(chip, (n,), jnp.int64), _shape(chip, (q,), jnp.int64)
    text = jax.jit(multisearch_bounds).lower(*args).compile().as_text()
    entry = text[text.index("\nENTRY"):]
    rows = [
        line for line in entry.splitlines()
        if " reduce-window(" in line
        and int(re.search(r"\[(\d+),128\]", line + "[0,128]").group(1)) * 128 >= n + q
    ]
    assert len(rows) == 2, rows  # the running count and the running minimum
    for line in rows:
        assert "/multisearch/merge/" in line, line
