"""Pallas TPU kernel: multisearch (batched searchsorted) via chunked counting.

The paper's multisearch (Lemma 3.5) answers r queries against a sorted
structure with merge-based, cache-oblivious accesses. A TPU has no efficient
random gather, so per-query binary search (log s gathers) is the wrong shape;
instead we use the count decomposition

    searchsorted_left(K, q)  = sum over chunks C of |{k in C : k < q}|
    searchsorted_right(K, q) = sum over chunks C of |{k in C : k <= q}|

Each (query-tile, key-chunk) grid cell does a dense broadcast compare-reduce in
VMEM — pure VPU work, zero gathers, bandwidth-optimal in keys (each key chunk
is streamed through VMEM once per query tile). The key-chunk grid axis
accumulates into the same output block (sequential TPU grid => safe).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

Array = jax.Array


LANE = 128  # keys stream past the queries one (1, LANE) row at a time


def _split_keys(keys: Array) -> tuple[Array, Array]:
    """int64 keys -> (hi, lo) int32 halves whose lexicographic signed order
    is the int64 order: ``hi`` is the arithmetic top half, ``lo`` the bottom
    half with its sign bit flipped (unsigned order as signed). Mosaic holds
    no 64-bit vectors, so the kernel compares the halves."""
    k = keys.astype(jnp.int64)
    hi = (k >> 32).astype(jnp.int32)
    lo = jax.lax.bitcast_convert_type(
        (k & 0xFFFFFFFF).astype(jnp.uint32) ^ jnp.uint32(0x80000000),
        jnp.int32,
    )
    return hi, lo


def _count_kernel(khi_ref, klo_ref, qhi_ref, qlo_ref, lt_ref, le_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        lt_ref[...] = jnp.zeros_like(lt_ref)
        le_ref[...] = jnp.zeros_like(le_ref)

    qhi = qhi_ref[...]  # (Q, 1)
    qlo = qlo_ref[...]

    def row(i, acc):
        lt, le = acc
        khi = khi_ref[pl.ds(i, 1), :]  # (1, lane)
        klo = klo_ref[pl.ds(i, 1), :]
        below = khi < qhi  # (Q, lane)
        tie = khi == qhi
        lt = lt + (below | (tie & (klo < qlo))).astype(jnp.int32)
        le = le + (below | (tie & (klo <= qlo))).astype(jnp.int32)
        return lt, le

    zero = jnp.zeros((qhi.shape[0], khi_ref.shape[1]), jnp.int32)
    lt, le = jax.lax.fori_loop(0, khi_ref.shape[0], row, (zero, zero))
    lt_ref[...] += jnp.sum(lt, axis=1, keepdims=True, dtype=jnp.int32)
    le_ref[...] += jnp.sum(le, axis=1, keepdims=True, dtype=jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("q_block", "k_block", "interpret")
)
def multisearch_counts(
    sorted_keys: Array,
    queries: Array,
    *,
    q_block: int = 256,
    k_block: int = 2048,
    interpret: bool = True,
) -> tuple[Array, Array]:
    """Return (count_lt, count_le) per query — the searchsorted left/right
    insertion points into ``sorted_keys`` (which must be sorted ascending).

    Keys and queries are integers of up to 64 bits; both are split into
    int32 halves (``_split_keys``) before the kernel. Keys are laid out as
    ``(n / LANE, LANE)`` rows and queries as a ``(q, 1)`` column, so each
    grid cell compares a ``(q_block, 1)`` query tile with ``k_block /
    LANE`` key rows, one ``(q_block, LANE)`` compare per row.

    Padding: keys are padded with +INF (count as never-less), queries padded
    with anything (results for the pad tail are discarded). A query equal to
    +INF would count the key padding in count_le, so count_le is clamped to n
    (count_lt needs no clamp: nothing is < the padding).

    Empty inputs short-circuit: with ``n == 0`` the key grid would have zero
    chunks, the kernel would never run, and the output buffers would be
    returned **uninitialized** (the ``le`` clamp would mask only half of
    that); every insertion point into an empty structure is 0, so both
    counts are returned as zeros without launching. ``q == 0`` is symmetric
    (nothing to answer).
    """
    n = sorted_keys.shape[0]
    q = queries.shape[0]
    if n == 0 or q == 0:
        zeros = jnp.zeros((q,), jnp.int32)
        return zeros, zeros
    maxval = jnp.array(jnp.iinfo(sorted_keys.dtype).max, sorted_keys.dtype)
    n_pad = pl.cdiv(n, k_block) * k_block
    q_pad = pl.cdiv(q, q_block) * q_block
    khi, klo = _split_keys(
        jnp.pad(sorted_keys, (0, n_pad - n), constant_values=maxval)
    )
    qhi, qlo = _split_keys(jnp.pad(queries, (0, q_pad - q)))
    # a key block narrower than a lane row (small interpret-mode tests)
    # is one row of its own width
    lane = min(LANE, k_block)
    if k_block % lane:
        raise ValueError(f"k_block={k_block} is not a multiple of {LANE}")

    # block indices stay int32: a literal 0 is an int64 index under x64,
    # which Mosaic cannot lower
    key_spec = pl.BlockSpec((k_block // lane, lane), lambda i, j: (j, j * 0))
    q_spec = pl.BlockSpec((q_block, 1), lambda i, j: (i, i * 0))
    lt, le = pl.pallas_call(
        _count_kernel,
        grid=(q_pad // q_block, n_pad // k_block),
        in_specs=[key_spec, key_spec, q_spec, q_spec],
        out_specs=[q_spec, q_spec],
        out_shape=[
            jax.ShapeDtypeStruct((q_pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((q_pad, 1), jnp.int32),
        ],
        interpret=interpret,
    )(
        khi.reshape(-1, lane), klo.reshape(-1, lane),
        qhi.reshape(-1, 1), qlo.reshape(-1, 1),
    )
    return lt[:q, 0], jnp.minimum(le[:q, 0], n)


def exact_multisearch_kernel(sorted_keys, queries, **kw):
    """Index of an exact match (first occurrence) or -1 — kernel-backed variant
    of repro.primitives.search.exact_multisearch."""
    lt, le = multisearch_counts(sorted_keys, queries, **kw)
    found = le > lt
    return jnp.where(found, lt, -1), found
