"""Multisearch primitives (paper Lemma 3.5).

The paper's cache-oblivious merge-based multisearch answers q lookups against
a sorted sequence of n keys in O(sort(n + q)) work: merge the queries into
the keys and read each query's insertion point off the merged order.

``multisearch_bounds`` (both insertion points) and ``multisearch_lt`` (the
left one) are the hot-path entry points: one call answers a whole fused
query vector. "auto" resolves to "xla" on every platform, and "xla" is the
merge (``_merge_bounds``): one sort of keys and queries together, a running
count of keys, and one un-permute back to query order. On a TPU v5e it
answered every measured shape at least as fast as the bisection of
``jnp.searchsorted`` (one dependent gather per query and step), from 2^10
queries into 2^15 keys (0.70 against 0.89 ms) to 2^23 queries into 2^21
keys (85 ms against 8.7 s), and tied at 2^14 queries into 2^21 keys
(``PERF.md`` §6). The Pallas kernel in ``repro.kernels.multisearch`` is a
gather-free counting variant that compares every query with every key,
selectable by name. Callers that fuse their lookups into one query vector
per sorted structure pay one multisearch per structure instead of one per
query role.
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
    "xla" (the merge), or "pallas" (counting kernel; compiled on TPU,
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


def _running(x: Array, scan, op, unit: int, reverse: bool = False) -> Array:
    """The running ``op`` of a 1-D ``x`` (``scan`` is ``lax.cumsum`` or
    ``lax.cummin``, ``unit`` the identity of ``op``), scanned along rows of
    128 entries and carried across the rows.

    The TPU compiler turns a 1-D scan into such rows itself, with ops that
    lose the named scope; written out, the rows keep it, and only the carry
    across rows (1/128 of the entries) is a 1-D scan of its own.
    """
    m = x.shape[0]
    rows = jnp.pad(x, (0, -m % 128), constant_values=unit).reshape(-1, 128)
    within = scan(rows, axis=1, reverse=reverse)
    unit_row = jnp.full((1,), unit, x.dtype)
    if reverse:
        carry = jnp.concatenate([scan(within[:, 0], reverse=True)[1:], unit_row])
    else:
        carry = jnp.concatenate([unit_row, scan(within[:, -1])[:-1]])
    return op(within, carry[:, None]).reshape(-1)[:m]


def _merge_bounds(sorted_keys: Array, queries: Array, le: bool):
    """The insertion points from one merged order (paper Lemma 3.5): sort
    the queries and the keys together, queries ahead of equal keys, and
    count the keys before each query's slot.

    Each entry carries its index in ``concat(queries, keys)`` as the last
    sort key, so a query (index < q) sorts ahead of the keys equal to it and
    the order is total. The int64 values sort as two int32 keys, the high
    word and the low word less 2^31, which order exactly as the int64 does.
    ``lt`` at a query's slot is the inclusive running count of keys there;
    ``le`` is that count at the end of the slot's run of equal values, a
    reverse running minimum over the run ends. One sort by the carried
    index puts both back in query order.
    """
    n, q = sorted_keys.shape[0], queries.shape[0]
    vals = jnp.concatenate([queries, sorted_keys])
    hi = (vals >> 32).astype(jnp.int32)
    lo = ((vals & 0xFFFFFFFF) - (1 << 31)).astype(jnp.int32)
    idx = jnp.arange(n + q, dtype=jnp.int32)
    hi, lo, idx = jax.lax.sort((hi, lo, idx), num_keys=3, is_stable=False)
    lt = _running((idx >= q).astype(jnp.int32), jax.lax.cumsum, jnp.add, 0)
    out = [lt]
    if le:
        last = jnp.ones((min(n + q, 1),), bool)
        run_end = jnp.concatenate([(hi[:-1] != hi[1:]) | (lo[:-1] != lo[1:]), last])
        ends = jnp.where(run_end, lt, n)
        out.append(_running(ends, jax.lax.cummin, jnp.minimum, n, reverse=True))
    out = jax.lax.sort((idx, *out), num_keys=1, is_stable=False)[1:]
    return tuple(o[:q] for o in out) if le else out[0][:q]


def multisearch_bounds(sorted_keys: Array, queries: Array) -> tuple[Array, Array]:
    """(count_lt, count_le) per query: the searchsorted left/right insertion
    points into ``sorted_keys``, int32, answered in one fused multisearch.

    This is the backend-dispatched hot-path primitive: on "xla" the merge;
    with the backend forced to "pallas" the chunked counting kernel from
    ``repro.kernels.multisearch`` — dense compare-reduce in VMEM, zero
    gathers, both bounds from the same streaming pass over the keys.
    """
    with jax.named_scope("multisearch"):
        if multisearch_backend() == "pallas":
            from repro.kernels.ops import multisearch_counts_op

            return multisearch_counts_op(sorted_keys, queries)
        with jax.named_scope("merge"):
            return _merge_bounds(sorted_keys, queries, le=True)


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
        with jax.named_scope("merge"):
            return _merge_bounds(sorted_keys, queries, le=False)


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
