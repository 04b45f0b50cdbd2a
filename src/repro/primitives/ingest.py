"""Ingest-pipeline backend dispatch (the fused-chunk seam).

``repro.core.bulk.bulk_update_chunk`` — the K-batch ingest every chunking
execution plan jits — resolves its implementation through this module, in the
same style as ``repro.primitives.search``:

  "scan"    the reference path: ``lax.scan`` of ``bulk_update_all`` over the
            K batches. Every other backend is required to be bit-identical to
            it (asserted by tests/test_fused_ingest.py), so it doubles as the
            oracle.
  "xla"     the fused XLA pipeline: per-batch randomness and rank structures
            are hoisted out of the scan (the counter-based RNG makes every
            draw a pure function of (stream key, batch index, batch sizes)),
            and the in-scan searches run lt-trimmed ``scan_unrolled``
            multisearches. What "auto" selects, on every platform.
  "pallas"  the resident kernel (``repro.kernels.fused_ingest``): one
            pallas_call walks all K batches over each reservoir tile, so the
            estimator state is read and written once per *chunk* instead of
            ~once per pipeline stage per batch. Structures are built by the
            ``kernels/bitonic.py`` + ``kernels/segscan.py`` path. Selectable
            by name only: the TPU compiler refuses these kernels at the
            paper's shapes (tests/test_tpu_compile.py). Compiled on TPU,
            interpret mode elsewhere (slow; parity testing only).
  "auto"    "xla" on every platform.

The choice is resolved at trace time, so switching clears the jit caches —
otherwise already-compiled engine programs would keep their old pipeline
forever.

This module also holds ``randint_from_bits``: the span arithmetic of
``jax.random.randint`` replayed on pre-drawn raw bits. The Pallas kernel
cannot run threefry per batch step, but ``randint``'s bit draws are
state-independent — only the cheap modular arithmetic depends on the span —
so the fused paths hoist ``jax.random.bits`` per batch and replay the span
math where the span (chi+) becomes known. Bit-identical to
``jax.random.randint`` (pinned by tests/test_fused_ingest.py).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

Array = jax.Array


INGEST_BACKENDS = ("auto", "xla", "pallas", "scan")

_backend = os.environ.get("REPRO_INGEST_BACKEND", "auto")
if _backend not in INGEST_BACKENDS:
    raise ValueError(
        f"REPRO_INGEST_BACKEND={_backend!r} is not one of {INGEST_BACKENDS}"
    )


def set_ingest_backend(name: str) -> None:
    """Force the chunked-ingest pipeline backend (see module docstring)."""
    if name not in INGEST_BACKENDS:
        raise ValueError(
            f"unknown ingest backend {name!r}; choose from {INGEST_BACKENDS}"
        )
    global _backend
    if name != _backend:
        _backend = name
        jax.clear_caches()


def ingest_backend() -> str:
    """The pipeline ``bulk_update_chunk`` resolves to right now
    ("scan", "xla", or "pallas")."""
    return "xla" if _backend == "auto" else _backend


def split_randint_key(key: Array) -> tuple[Array, Array]:
    """The (bits_hi_key, bits_lo_key) pair ``jax.random.randint`` derives
    internally from its key — draw ``jax.random.bits`` on each to hoist a
    randint's raw bits out of a scan/kernel."""
    k_hi, k_lo = jax.random.split(key)
    return k_hi, k_lo


def randint_from_bits(hi_bits: Array, lo_bits: Array, maxval: Array) -> Array:
    """``jax.random.randint(key, shape, 0, maxval, dtype=int32)`` replayed on
    pre-drawn 32-bit words (``hi_bits``/``lo_bits`` from ``jax.random.bits``
    on ``split_randint_key(key)``).

    Requires ``maxval >= 1`` elementwise (the callers draw over
    ``maximum(span, 1)``), which is what lets the reference's
    empty-span/overflow selects drop out. Bit-identical to ``randint`` —
    the exact (2^16 % span)^2 multiplier chain from jax's implementation.
    """
    span = maxval.astype(jnp.uint32)
    multiplier = jnp.uint32(2**16) % span
    multiplier = (multiplier * multiplier) % span
    offset = ((hi_bits % span) * multiplier + (lo_bits % span)) % span
    return offset.astype(jnp.int32)
