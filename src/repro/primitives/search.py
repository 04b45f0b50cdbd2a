"""Multisearch primitives (paper Lemma 3.5).

The paper's cache-oblivious merge-based multisearch answers m lookups against a
sorted sequence of n key-value pairs in O(sort(n)+sort(m)) misses. With both
sides presorted it degrades to O(scan(n+m)). We express each lookup set as a
vectorized binary search (``jnp.searchsorted``) over presorted int64 keys;
the Pallas kernel in repro.kernels.multisearch is a gather-free counting
variant, selectable by name.

``multisearch_bounds`` is the hot-path entry point: one call answers both
insertion points (left/right) for a whole fused query vector. "auto" resolves
to ``jnp.searchsorted`` on every platform: the counting kernel compares every
query with every key (O(q*n) work), so whether it should ever be the default
is for a chip benchmark to decide. Callers that fuse their lookups into one
query vector per sorted structure pay one multisearch per structure instead
of one per query role.
"""
from __future__ import annotations

import os

from typing import Optional

import jax
import jax.numpy as jnp

Array = jax.Array


MULTISEARCH_BACKENDS = ("auto", "xla", "pallas")

_backend = os.environ.get("REPRO_MULTISEARCH_BACKEND", "auto")
if _backend not in MULTISEARCH_BACKENDS:
    raise ValueError(
        f"REPRO_MULTISEARCH_BACKEND={_backend!r} is not one of "
        f"{MULTISEARCH_BACKENDS}"
    )


def set_multisearch_backend(name: str) -> None:
    """Force the multisearch backend: "auto" (= "xla" on every platform),
    "xla" (jnp.searchsorted), or "pallas" (counting kernel; compiled on TPU,
    interpret mode elsewhere — slow, for parity testing only). The choice is resolved at trace
    time, so switching also clears the jit caches — otherwise already-compiled
    programs would silently keep their old backend forever."""
    if name not in MULTISEARCH_BACKENDS:
        raise ValueError(
            f"unknown multisearch backend {name!r}; "
            f"choose from {MULTISEARCH_BACKENDS}"
        )
    global _backend
    if name != _backend:
        _backend = name
        jax.clear_caches()


def multisearch_backend() -> str:
    """The backend ``multisearch_bounds`` resolves to right now."""
    return "xla" if _backend == "auto" else _backend


# XLA binary-search flavor. Every method computes identical insertion
# points, so this is purely a performance knob. "scan" (the jnp default) is
# deliberately pinned: "scan_unrolled" looks ~1.6x faster in a standalone
# searchsorted microbenchmark on CPU, but embedded in the full chunk-ingest
# program it is ~3.7x SLOWER end-to-end (measured on the r=65536, s=4096,
# K=8 cell: 225ms -> 742ms per chunk) — the unrolled bisection bloats the
# program and defeats fusion around it. Benchmark any change to this knob
# with benchmarks/fused.py, not with an isolated searchsorted loop.
_XLA_SEARCH_METHOD = "scan"


def multisearch_bounds(sorted_keys: Array, queries: Array) -> tuple[Array, Array]:
    """(count_lt, count_le) per query: the searchsorted left/right insertion
    points into ``sorted_keys``, int32, answered in one fused multisearch.

    This is the backend-dispatched hot-path primitive: by default two
    ``jnp.searchsorted`` binary searches; with the backend forced to "pallas"
    the chunked counting kernel from ``repro.kernels.multisearch`` — dense
    compare-reduce in VMEM, zero gathers, both bounds from the same streaming
    pass over the keys.
    """
    with jax.named_scope("multisearch"):
        if multisearch_backend() == "pallas":
            from repro.kernels.ops import multisearch_counts_op

            return multisearch_counts_op(sorted_keys, queries)
        lt = jnp.searchsorted(
            sorted_keys, queries, side="left", method=_XLA_SEARCH_METHOD
        ).astype(jnp.int32)
        le = jnp.searchsorted(
            sorted_keys, queries, side="right", method=_XLA_SEARCH_METHOD
        ).astype(jnp.int32)
        return lt, le


def multisearch_lt(sorted_keys: Array, queries: Array) -> Array:
    """count_lt only — the left insertion point, int32.

    The fused ingest pipeline (repro.core.bulk) proves several of its ``le``
    bounds redundant (a fresh f1's own arc is always present; exact-match
    hits reduce to one gather at the ``lt`` point), so its query roles pay
    for one side instead of two. Backend-dispatched like
    ``multisearch_bounds``; on "pallas" the counting kernel computes both
    bounds in its single streaming pass anyway, so this simply drops ``le``.
    """
    with jax.named_scope("multisearch"):
        if multisearch_backend() == "pallas":
            from repro.kernels.ops import multisearch_counts_op

            return multisearch_counts_op(sorted_keys, queries)[0]
        return jnp.searchsorted(
            sorted_keys, queries, side="left", method=_XLA_SEARCH_METHOD
        ).astype(jnp.int32)


def exact_multisearch(
    sorted_keys: Array, queries: Array, valid_n: Optional[Array] = None
) -> tuple[Array, Array]:
    """For each query key, the index of a matching entry in sorted_keys, or -1.

    ``valid_n``: optional scalar — only the first ``valid_n`` entries are real
    (the tail is sentinel padding); matches beyond it are rejected.
    """
    n = sorted_keys.shape[0]
    i = jnp.searchsorted(sorted_keys, queries, side="left")
    i_c = jnp.minimum(i, n - 1)
    found = (i < n) & (sorted_keys[i_c] == queries)
    if valid_n is not None:
        found = found & (i < valid_n)
    return jnp.where(found, i_c, -1), found


def count_eq(sorted_keys: Array, queries: Array) -> Array:
    """Number of entries equal to each query key (degree queries)."""
    lo = jnp.searchsorted(sorted_keys, queries, side="left")
    hi = jnp.searchsorted(sorted_keys, queries, side="right")
    return (hi - lo).astype(jnp.int32)


def predecessor_multisearch(sorted_keys: Array, queries: Array) -> Array:
    """Index of the entry with the largest key <= query, or -1 (predEQMultiSearch)."""
    i = jnp.searchsorted(sorted_keys, queries, side="right") - 1
    return i  # -1 when every key > query
