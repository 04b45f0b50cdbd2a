"""Graph500 Kronecker edge stream, seeded per run, and its endless laps.

The stream follows the Graph500 specification's Kernel 1 generator:
``2^scale`` vertices, ``edge_factor * 2^scale`` draws, one bit of each
endpoint per level from the initiator ``[[A, B], [C, D]]``, then vertex
labels and edge order permuted. Draws are independent and the order is a
random permutation, so the first ``draws`` edges of that stream are
themselves ``draws`` independent draws: the pool is that prefix, made at the
stream's own scale for the cost of its length. Self-loops and repeats of an
edge are removed, because the estimator counts triangles of a simple graph.

The draw itself comes from a fixed ``pool_seed`` in the configuration and is
cached under ``bench/.cache/``: every run sees the same graph up to
isomorphism, so runs with different seeds do the same work in another
order. The run's ``--seed`` draws what Graph500 randomises afterwards: the
vertex labels and the edge order.

A window that uses up the pool continues with a further copy whose vertex
ids are offset by ``lap * 2^scale``. Copies are vertex-disjoint, so the work
per edge stays statistically the same and no duplicate edge ever appears.
"""
from __future__ import annotations

import pathlib

import numpy as np

CACHE = pathlib.Path(__file__).resolve().parents[1] / ".cache"


def kronecker_draws(scale: int, m: int, abcd, seed: int) -> np.ndarray:
    """(m, 2) int64 raw endpoint draws over ``2^scale`` vertices (Graph500
    Kernel 1, before its permutations)."""
    a, b, c, _d = abcd
    rng = np.random.default_rng(seed)
    ij = np.zeros((2, m), np.int64)
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    for level in range(scale):
        ii = rng.random(m, dtype=np.float32) > ab
        jj = rng.random(m, dtype=np.float32) > np.where(ii, c_norm, a_norm)
        ij[0] += ii.astype(np.int64) << level
        ij[1] += jj.astype(np.int64) << level
    return ij.T


def simple_edges(draws: np.ndarray) -> np.ndarray:
    """Distinct undirected non-loop edges as canonical (min, max) int32 rows,
    in ascending key order."""
    lo = np.minimum(draws[:, 0], draws[:, 1])
    hi = np.maximum(draws[:, 0], draws[:, 1])
    keep = lo != hi
    keys = np.unique((lo[keep] << 32) | hi[keep])
    return np.stack([keys >> 32, keys & 0xFFFFFFFF], axis=1).astype(np.int32)


def pool(spec: dict, cache: pathlib.Path | None = CACHE) -> np.ndarray:
    """The deduplicated Kronecker pool that a configuration names
    (``graph_scale``, ``draws``, ``initiator``, ``pool_seed``), from the
    cache when an earlier run made it."""
    key = (
        f"kron_s{spec['graph_scale']}_m{spec['draws']}"
        f"_{'-'.join(str(x) for x in spec['initiator'])}_p{spec['pool_seed']}.npy"
    )
    path = None if cache is None else cache / key
    if path is not None and path.exists():
        return np.load(path)
    edges = simple_edges(
        kronecker_draws(
            spec["graph_scale"], spec["draws"], spec["initiator"],
            spec["pool_seed"],
        )
    )
    if path is not None:
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_suffix(".tmp.npy")
        np.save(tmp, edges)
        tmp.replace(path)
    return edges


class LapStream:
    """One stream over a pool: a seeded edge order and vertex relabelling,
    repeated in vertex-disjoint laps. ``take(start, n)`` returns stream edges
    ``[start, start + n)`` as an ``(n, 2)`` int32 array."""

    def __init__(self, ordered: np.ndarray, n_vertices: int, labels: np.ndarray):
        self.n_vertices = int(n_vertices)
        self.size = len(ordered)
        self._ordered = ordered
        self._labels = labels

    def take(self, start: int, n: int) -> np.ndarray:
        k = start + np.arange(n, dtype=np.int64)
        lap = k // self.size
        if n and (int(lap[-1]) + 1) * self.n_vertices > 2**31:
            raise ValueError(f"stream position {start + n} runs out of int32 ids")
        rows = self._ordered[k % self.size]
        ids = self._labels[rows].astype(np.int64) + (lap * self.n_vertices)[:, None]
        return ids.astype(np.int32)


def stream(edges: np.ndarray, n_vertices: int, seed: int) -> LapStream:
    """The run's stream: labels and order drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    labels = np.arange(n_vertices, dtype=np.int32)
    rng.shuffle(labels)
    order = rng.permutation(len(edges))
    return LapStream(edges[order], n_vertices, labels)
