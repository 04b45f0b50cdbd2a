"""jit'd public wrappers around the Pallas kernels.

On TPU the kernels run compiled (interpret=False), so a kernel the TPU
compiler refuses raises there (tests/test_tpu_compile.py records which do);
everywhere else (CPU hosts, unit tests) they run in interpret mode, which
executes the same kernel body in Python — the BlockSpec tiling, grid
sequencing, and SMEM carry logic are exercised identically.
"""
from __future__ import annotations

import jax


from repro.kernels import bitonic, fused_ingest, multisearch, segment_sum, segscan
from repro.kernels import ref as _ref

Array = jax.Array


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def segscan_op(values: Array, flags: Array, *, block: int = 1024) -> Array:
    """Segmented inclusive sum scan (kernel-backed)."""
    return segscan.segscan(values, flags, block=block, interpret=not _on_tpu())


def multisearch_counts_op(
    sorted_keys: Array, queries: Array, *, q_block: int = 256, k_block: int = 2048
) -> tuple[Array, Array]:
    """(count_lt, count_le) insertion points (kernel-backed).

    The "pallas" target of ``repro.primitives.search.multisearch_bounds``
    — the fused per-structure lookups on the bulk-update hot path land here
    when that backend is forced."""
    return multisearch.multisearch_counts(
        sorted_keys,
        queries,
        q_block=q_block,
        k_block=k_block,
        interpret=not _on_tpu(),
    )


def bitonic_sort_tiles_op(
    keys: Array, values: Array, *, tile: int = 1024
) -> tuple[Array, Array]:
    """Per-tile (key, value) sort (kernel-backed)."""
    return bitonic.bitonic_sort_tiles(
        keys, values, tile=tile, interpret=not _on_tpu()
    )


def segment_sum_op(
    values: Array, segment_ids: Array, num_segments: int, **kw
) -> Array:
    """GNN scatter (kernel-backed one-hot MXU formulation)."""
    return segment_sum.segment_sum_kernel(
        values, segment_ids, num_segments, interpret=not _on_tpu(), **kw
    )


def fused_ingest_op(
    f1: Array, chi: Array, f2: Array, has_f3: Array,
    key_desc: Array, key_rank: Array, src: Array, dst: Array, pos: Array,
    ekey: Array, epos: Array,
    replace: Array, w_sel: Array, f1_bpos: Array, coin: Array,
    phi_hi: Array, phi_lo: Array,
    *, est_block: int = 256,
) -> tuple[Array, Array, Array, Array]:
    """Resident K-batch NBSI ingest (kernel-backed).

    This is the "pallas" target of ``repro.core.bulk.bulk_update_chunk`` —
    the whole per-chunk batch loop lands here when the ingest backend
    resolves to "pallas", touching each reservoir tile once per chunk."""
    return fused_ingest.fused_ingest(
        f1, chi, f2, has_f3,
        key_desc, key_rank, src, dst, pos, ekey, epos,
        replace, w_sel, f1_bpos, coin, phi_hi, phi_lo,
        est_block=est_block,
        interpret=not _on_tpu(),
    )


# re-export oracles so callers can assert against the contract
segscan_ref = _ref.segscan_ref
multisearch_counts_ref = _ref.multisearch_counts_ref
bitonic_sort_tiles_ref = _ref.bitonic_sort_tiles_ref
segment_sum_ref = _ref.segment_sum_ref
fused_ingest_ref = _ref.fused_ingest_ref
delete_hits_ref = _ref.delete_hits_ref
