"""Pallas TPU kernel: segment-sum (the GNN message-passing scatter).

TPU has no efficient random scatter; the idiomatic formulation is a one-hot
matmul: for a value block V (B, d) with segment ids s, the contribution to
output rows [o, o+OB) is  onehot(s - o)^T @ V  — an MXU contraction, fully
dense, no data-dependent control flow. Grid = (out_blocks, value_blocks); the
value-block axis accumulates into the same output block (sequential TPU grid).

This mirrors benchmarks' chunked multisearch: work O(n * m / OB) trades FLOPs
(nearly free on the MXU) for zero gathers — the same trade the paper makes by
replacing hash tables with sorts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

Array = jax.Array


def _segsum_kernel(ids_ref, v_ref, out_ref, *, out_block: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ids = ids_ref[...]  # (B,)
    base = pl.program_id(0) * out_block
    local = ids - base
    iota = jax.lax.broadcasted_iota(jnp.int32, (ids.shape[0], out_block), 1)
    onehot = (local[:, None] == iota).astype(v_ref.dtype)  # (B, OB)
    out_ref[...] += jnp.einsum(
        "bo,bd->od", onehot, v_ref[...], preferred_element_type=out_ref.dtype
    )


@functools.partial(
    jax.jit, static_argnames=("num_segments", "v_block", "out_block", "interpret")
)
def segment_sum_kernel(
    values: Array,  # (n, d)
    segment_ids: Array,  # (n,) int32; out-of-range ids are dropped
    num_segments: int,
    *,
    v_block: int = 1024,
    out_block: int = 256,
    interpret: bool = True,
) -> Array:
    n, d = values.shape
    if num_segments == 0:
        return jnp.zeros((0, d), values.dtype)
    if n == 0:
        # no values: the sum over an empty set is zeros for every segment; a
        # zero-size value grid would be malformed (PR 8 oracle-harness finding)
        return jnp.zeros((num_segments, d), values.dtype)
    n_pad = pl.cdiv(n, v_block) * v_block
    m_pad = pl.cdiv(num_segments, out_block) * out_block
    v = jnp.pad(values, ((0, n_pad - n), (0, 0)))
    ids = jnp.pad(segment_ids, (0, n_pad - n), constant_values=-1)

    grid = (m_pad // out_block, n_pad // v_block)
    out = pl.pallas_call(
        functools.partial(_segsum_kernel, out_block=out_block),
        grid=grid,
        # block indices stay int32: a literal 0 is an int64 index under
        # x64, which Mosaic cannot lower
        in_specs=[
            pl.BlockSpec((v_block,), lambda i, j: (j,)),
            pl.BlockSpec((v_block, d), lambda i, j: (j, j * 0)),
        ],
        out_specs=pl.BlockSpec((out_block, d), lambda i, j: (i, i * 0)),
        out_shape=jax.ShapeDtypeStruct((m_pad, d), values.dtype),
        interpret=interpret,
    )(ids, v)
    return out[:num_segments]
