"""rankAll (paper Definition 4.2 / Lemma 4.3) and the batch closing-edge index.

Given a batch W of s edges (last batch may be padded; ``n_valid`` marks the real
prefix), build the shared structure every estimator queries against:

  * 2s directed arcs {src, dst, pos}, sorted by (src asc, pos desc). In that
    order, rank(src->dst) = offset within the src segment (segmented iota) —
    exactly Lemma 4.3's sort + scan-with-reset.
  * By the paper's observation after Fig. 2, the same order is also sorted by
    (src asc, rank asc), so Q2 lookups ("src = u, rank = a") reuse the array.
  * A (min,max)-sorted copy of W for closing-edge (Step 3) exact multisearch.

All lookups are multisearches over packed int64 keys. Invalid (padding) arcs get
key = +INF so they sort to the tail and are excluded by key inequality alone.

Because rank is the segment offset, any stored rank is recoverable from two
insertion points alone: rank(arc at index j) = j - searchsorted(key_desc,
pack2(src, 0)). The fused Q1 path in core/bulk.py leans on this identity to
answer rank AND degree queries gather-free from one multisearch — key_desc is
therefore the only structure the Q1 roles ever touch.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.primitives.segscan import (
    segment_starts,
    segmented_cummax,
    segmented_iota,
)
from repro.primitives.sort import pack2, sort_by_key

INF64 = jnp.int64(0x7FFFFFFFFFFFFFFF)


class RankStructure(NamedTuple):
    """Shared per-batch structure (paper Section 4.3). All arrays length 2s except
    the edge index (length s)."""

    # arcs sorted by (src asc, pos desc)  ==  (src asc, rank asc)
    key_desc: jax.Array  # (2s,) int64: pack2(src, s-1-pos); INF for padding
    key_rank: jax.Array  # (2s,) int64: pack2(src, rank);    INF for padding
    src: jax.Array  # (2s,) int32
    dst: jax.Array  # (2s,) int32
    pos: jax.Array  # (2s,) int32
    rank: jax.Array  # (2s,) int32
    # batch edges sorted by canonical (min,max) key
    ekey: jax.Array  # (s,) int64: pack2(min, max); INF for padding
    epos: jax.Array  # (s,) int32

    @property
    def s(self) -> int:
        return self.ekey.shape[0]


def rank_all(W: jax.Array, n_valid: jax.Array) -> RankStructure:
    """Build the RankStructure for batch ``W`` ((s,2) int32, first n_valid real)."""
    with jax.named_scope("rank_all"):
        s = W.shape[0]
        pos1 = jnp.arange(s, dtype=jnp.int32)
        valid_e = pos1 < n_valid

        # --- directed arcs, both orientations (paper: map + concat) ---
        src = jnp.concatenate([W[:, 0], W[:, 1]])
        dst = jnp.concatenate([W[:, 1], W[:, 0]])
        pos = jnp.concatenate([pos1, pos1])
        valid_a = jnp.concatenate([valid_e, valid_e])

        # sort by (src asc, pos desc): minor key = s-1-pos
        kd = pack2(src, (s - 1) - pos)
        kd = jnp.where(valid_a, kd, INF64)
        kd_s, src_s, dst_s, pos_s = sort_by_key(kd, src, dst, pos)

        # rank = offset within src segment (scan-with-reset over the sorted arcs)
        starts = segment_starts(src_s.astype(jnp.int64))
        rank_s = segmented_iota(starts)

        kr = pack2(src_s, rank_s)
        n_valid_a = 2 * n_valid
        kr = jnp.where(jnp.arange(2 * s) < n_valid_a, kr, INF64)

        # --- closing-edge index: canonical (min,max) sorted edges ---
        emin = jnp.minimum(W[:, 0], W[:, 1])
        emax = jnp.maximum(W[:, 0], W[:, 1])
        ek = jnp.where(valid_e, pack2(emin, emax), INF64)
        ek_s, epos_s = sort_by_key(ek, pos1)

        return RankStructure(
            key_desc=kd_s,
            key_rank=kr,
            src=src_s,
            dst=dst_s,
            pos=pos_s,
            rank=rank_s,
            ekey=ek_s,
            epos=epos_s,
        )


def rank_all_chunk(
    Ws: jax.Array, n_valids: jax.Array, *, use_kernels: bool = False
) -> RankStructure:
    """Stacked RankStructure over K batches — every array gains a leading K
    axis. The fused chunk pipeline (repro.core.bulk) hoists this out of its
    scan so structures are built once per chunk, in one (batched) sort
    dispatch instead of K.

    ``use_kernels=True`` routes the builds through the Pallas kernels
    (interpret mode off-TPU): ``kernels/bitonic.py`` sorts each batch's arcs
    and closing edges as one in-VMEM tile per batch, and
    ``kernels/segscan.py`` computes the Lemma 4.3 ranks (scan-with-reset
    over the sorted arcs). The bitonic network is not stable, so the two
    places the reference's stable argsort order is observable are patched
    exactly: equal *arc* keys only arise for the two orientations of a
    self-loop (identical payloads — order is unobservable), and equal
    *closing-edge* keys (duplicate edges in a multigraph batch) are fixed by
    a segmented cummax so the right insertion point still reads the last
    copy's position. The resulting ingest state is bit-identical to the
    ``rank_all`` build (asserted by tests/test_fused_ingest.py); only the
    padding tails — masked to INF64 / never dereferenced — may differ.
    """
    n_valids = jnp.asarray(n_valids, dtype=jnp.int32)
    if not use_kernels:
        return jax.vmap(rank_all)(Ws, n_valids)
    with jax.named_scope("rank_all"):
        return _rank_all_chunk_kernels(Ws, n_valids)


def _next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def _rank_all_chunk_kernels(Ws: jax.Array, n_valids: jax.Array) -> RankStructure:
    from repro.kernels.ops import bitonic_sort_tiles_op, segscan_op

    K, s, _ = Ws.shape
    pos1 = jnp.arange(s, dtype=jnp.int32)
    valid_e = pos1[None, :] < n_valids[:, None]  # (K, s)

    # --- directed arcs, both orientations ---
    src = jnp.concatenate([Ws[:, :, 0], Ws[:, :, 1]], axis=1)  # (K, 2s)
    dst = jnp.concatenate([Ws[:, :, 1], Ws[:, :, 0]], axis=1)
    pos2 = jnp.tile(pos1, 2)  # (2s,)
    valid_a = jnp.concatenate([valid_e, valid_e], axis=1)

    kd = pack2(src, (s - 1) - pos2[None, :])
    kd = jnp.where(valid_a, kd, INF64)

    # one bitonic tile per batch: pad each row to a power of two with INF64
    # (the kernel's own pad value), sort all K tiles in one kernel launch,
    # carry the within-row arc index as payload and gather the columns back
    tile = _next_pow2(2 * s)
    arc = jnp.broadcast_to(
        jnp.arange(2 * s, dtype=jnp.int32)[None, :], (K, 2 * s)
    )
    kd_p = jnp.pad(kd, ((0, 0), (0, tile - 2 * s)), constant_values=INF64)
    arc_p = jnp.pad(arc, ((0, 0), (0, tile - 2 * s)))
    ks, perm = bitonic_sort_tiles_op(
        kd_p.reshape(-1), arc_p.reshape(-1), tile=tile
    )
    # real keys are < INF64, so the first 2s slots of each sorted tile hold
    # every real arc; the sliced-off tail is all-INF64 padding
    kd_s = ks.reshape(K, tile)[:, : 2 * s]
    perm = perm.reshape(K, tile)[:, : 2 * s]
    src_s = jnp.take_along_axis(src, perm, axis=1)
    dst_s = jnp.take_along_axis(dst, perm, axis=1)
    pos_s = jnp.take_along_axis(
        jnp.broadcast_to(pos2[None, :], (K, 2 * s)), perm, axis=1
    )

    # Lemma 4.3 ranks via the segscan kernel: flatten the K rows — each row
    # opens with a start flag, so the SMEM carry never crosses batches
    prev = jnp.concatenate([src_s[:, :1], src_s[:, :-1]], axis=1)
    starts = (src_s != prev).at[:, 0].set(True)
    rank_s = (
        segscan_op(
            jnp.ones((K * 2 * s,), jnp.int32), starts.reshape(-1)
        ).reshape(K, 2 * s)
        - 1
    ).astype(jnp.int32)

    n_valid_a = 2 * n_valids
    kr = pack2(src_s, rank_s)
    kr = jnp.where(
        jnp.arange(2 * s)[None, :] < n_valid_a[:, None], kr, INF64
    )

    # --- closing-edge index ---
    emin = jnp.minimum(Ws[:, :, 0], Ws[:, :, 1])
    emax = jnp.maximum(Ws[:, :, 0], Ws[:, :, 1])
    ek = jnp.where(valid_e, pack2(emin, emax), INF64)
    tile_e = _next_pow2(s)
    ek_p = jnp.pad(ek, ((0, 0), (0, tile_e - s)), constant_values=INF64)
    ep_p = jnp.pad(
        jnp.broadcast_to(pos1[None, :], (K, s)), ((0, 0), (0, tile_e - s))
    )
    eks, eps = bitonic_sort_tiles_op(
        ek_p.reshape(-1), ep_p.reshape(-1), tile=tile_e
    )
    ek_s = eks.reshape(K, tile_e)[:, :s]
    epos_s = eps.reshape(K, tile_e)[:, :s].astype(jnp.int32)
    # restore the stable-sort guarantee step 3 reads (see segmented_cummax)
    eprev = jnp.concatenate([ek_s[:, :1], ek_s[:, :-1]], axis=1)
    estarts = (ek_s != eprev).at[:, 0].set(True)
    epos_s = segmented_cummax(
        epos_s.reshape(-1), estarts.reshape(-1)
    ).reshape(K, s)

    return RankStructure(
        key_desc=kd_s,
        key_rank=kr,
        src=src_s,
        dst=dst_s,
        pos=pos_s,
        rank=rank_s,
        ekey=ek_s,
        epos=epos_s,
    )
