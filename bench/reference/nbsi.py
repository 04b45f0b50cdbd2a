"""Plain reference of the streaming triangle estimator (Pavan et al.'s
neighbourhood sampling, batched as in Tangwongsan, Pavan and Tirthapura,
arXiv:1308.2166, Section 4), written from the paper and independent of the
program: it imports nothing of ``repro`` and shares none of its structures.

Per estimator the state is a level-1 edge ``f1``, a level-2 edge ``f2``
adjacent to ``f1`` and arriving after it, ``chi`` = how many edges adjacent
to ``f1`` arrived after it, and whether the edge closing the wedge
``(f1, f2)`` arrived after ``f2``. For a batch ``W`` of ``n`` edges, on
top of ``m`` edges already seen:

1. level 1: draw ``t`` uniform in ``[0, m + n)``; ``t >= m`` replaces ``f1``
   by ``W[t - m]`` and resets the rest (reservoir sampling over the stream);
2. level 2: ``chi+`` = edges of ``W`` adjacent to ``f1`` and after it; with
   probability ``chi+ / (chi + chi+)`` the new ``f2`` is the ``phi``-th of
   them, ``phi`` uniform in ``[0, chi+)``, counted from the most recent;
3. closing: the wedge closes if its closing edge is in ``W`` after ``f2``.

The estimate is the median over groups of the mean of ``chi * m`` over the
estimators whose wedge closed.

Randomness follows the system's stated contract, so that a correct program
and this reference reach the same state bit for bit: batch ``i`` of a stream
seeded ``seed`` uses ``key = fold_in(PRNGKey(seed), i)``, ``k1, k2 =
split(key)``, ``t = randint(k1, 0, m + n)`` (int64), ``k_coin, k_phi =
split(k2)``, the coin ``uniform(k_coin)`` (float32) compared with the float32
quotient ``chi+ / max(chi + chi+, 1)``, and ``phi = randint(k_phi, 0,
max(chi+, 1))`` (int32). The draws are made with ``jax.random`` on the host
CPU device; everything else is numpy.

The one float in the update is that quotient, and a platform may round a
float32 division either way (the TPU's is not correctly rounded). Where the
coin lies within ``TIE_ULPS`` float32 ulps of the quotient, the level-2
choice is the platform's: the reference marks the estimator ``unsure`` until its level-1
edge is next replaced, which resets what that choice decided (``f2`` and
the closing flag). ``mismatches`` leaves those two fields of unsure
estimators out, and ``settled`` takes them from the program, so the
estimate is compared over the same state.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

BFLOAT16 = ml_dtypes.bfloat16

FIELDS = ("f1", "chi", "f2", "has_f3", "m_seen")
# how far from the float32 quotient a coin counts as a tie, in ulps of the
# quotient: the largest error seen in a TPU v5e float32 division (a / b over
# all integers 1 <= a <= b <= 4096)
TIE_ULPS = 2


@dataclass
class State:
    f1: np.ndarray  # (r, 2) int32, -1 when unset
    chi: np.ndarray  # (r,) int32
    f2: np.ndarray  # (r, 2) int32, canonical (min, max), -1 when unset
    has_f3: np.ndarray  # (r,) bool
    m_seen: int
    unsure: np.ndarray  # (r,) bool: f2 and has_f3 rest on a coin tie

    @classmethod
    def fresh(cls, r: int) -> "State":
        return cls(
            f1=np.full((r, 2), -1, np.int32),
            chi=np.zeros((r,), np.int32),
            f2=np.full((r, 2), -1, np.int32),
            has_f3=np.zeros((r,), bool),
            m_seen=0,
            unsure=np.zeros((r,), bool),
        )


class Draws:
    """The contract's random draws for one stream, on the host CPU device."""

    def __init__(self, seed: int, r: int):
        self.cpu = jax.devices("cpu")[0]
        self.r = r
        with jax.default_device(self.cpu):
            self.root = jax.random.PRNGKey(seed)
        self._first = jax.jit(self._first_draws, static_argnums=(2,))
        self._phi = jax.jit(self._phi_draw)

    @staticmethod
    def _first_draws(key, total, r):
        k1, k2 = jax.random.split(key)
        t = jax.random.randint(
            k1, (r,), jnp.int64(0), jnp.maximum(total, 1), dtype=jnp.int64
        )
        k_coin, k_phi = jax.random.split(k2)
        coin = jax.random.uniform(k_coin, (r,), dtype=jnp.float32)
        return t, coin, k_phi

    @staticmethod
    def _phi_draw(k_phi, span):
        return jax.random.randint(k_phi, span.shape, 0, span, dtype=jnp.int32)

    def first(self, step: int, total: int):
        with jax.default_device(self.cpu):
            key = jax.random.fold_in(self.root, step)
            t, coin, k_phi = self._first(key, jnp.int64(total), self.r)
            return np.asarray(t), np.asarray(coin), k_phi

    def phi(self, k_phi, span: np.ndarray) -> np.ndarray:
        with jax.default_device(self.cpu):
            return np.asarray(self._phi(k_phi, jnp.asarray(span, jnp.int32)))


def _pack(hi, lo) -> np.ndarray:
    return (np.asarray(hi, np.int64) << 32) | np.asarray(lo, np.int64)


def update(st: State, W: np.ndarray, draws: Draws, step: int,
           coin_dtype=np.float32) -> State:
    """Fold one batch ``W`` ((n, 2) int32, all rows real) into every
    estimator; ``step`` is the batch's index in its stream. ``coin_dtype``
    is the precision of the level-2 quotient: float32 as the system states
    it; the check's control lowers it."""
    n = len(W)
    m = st.m_seen
    t, coin, k_phi = draws.first(step, m + n)

    # 1. level 1: reservoir over the stream so far
    repl = (t >= m) & (m + n > 0)
    idx = np.clip(t - m, 0, max(n - 1, 0))
    fresh_edges = W[idx] if n else np.full((len(t), 2), -1, np.int32)
    f1 = np.where(repl[:, None], fresh_edges, st.f1)
    chi = np.where(repl, 0, st.chi)
    f2 = np.where(repl[:, None], -1, st.f2)
    has = st.has_f3 & ~repl
    p1 = np.where(repl, idx, -1)  # position of f1 in W, -1 when older

    # 2. level 2. Arcs of W in both orientations, sorted by (src, pos): the
    # arcs of x after position p are a contiguous run ending the x segment.
    pos = np.arange(n, dtype=np.int64)
    src = np.concatenate([W[:, 0], W[:, 1]])
    dst = np.concatenate([W[:, 1], W[:, 0]])
    apos = np.concatenate([pos, pos])
    akey = _pack(src, apos)
    o = np.argsort(akey, kind="stable")
    akey, src, dst, apos = akey[o], src[o], dst[o], apos[o]

    u, v = f1[:, 0], f1[:, 1]
    have = u >= 0
    uq, vq = np.maximum(u, 0), np.maximum(v, 0)
    end_u = np.searchsorted(akey, _pack(uq, 0xFFFFFFFF), "right")
    end_v = np.searchsorted(akey, _pack(vq, 0xFFFFFFFF), "right")
    after_u = np.searchsorted(akey, _pack(uq, p1 + 1), "left")
    after_v = np.searchsorted(akey, _pack(vq, p1 + 1), "left")
    ld = np.where(have, end_u - after_u, 0).astype(np.int32)
    rd = np.where(have, end_v - after_v, 0).astype(np.int32)
    chi_plus = ld + rd
    chi_new = chi + chi_plus
    p_new = chi_plus.astype(coin_dtype) / np.maximum(
        chi_new.astype(coin_dtype), coin_dtype(1.0)
    )
    take = have & (chi_plus > 0) & (coin.astype(coin_dtype) < p_new)
    p32 = chi_plus.astype(np.float32) / np.maximum(chi_new.astype(np.float32), np.float32(1))
    band = TIE_ULPS * np.spacing(p32).astype(np.float64)
    tie = have & (chi_plus > 0) & (np.abs(coin.astype(np.float64) - p32) <= band)
    unsure = (st.unsure & ~repl) | tie
    phi = draws.phi(k_phi, np.maximum(chi_plus, 1))
    j = np.where(phi < ld, end_u - 1 - phi, end_v - 1 - (phi - ld))
    j = np.clip(j, 0, max(2 * n - 1, 0))
    if n:
        cand = np.stack([np.minimum(src[j], dst[j]), np.maximum(src[j], dst[j])], 1)
        cand_pos = apos[j]
    else:
        cand, cand_pos = f2, np.full_like(p1, -1)
    f2 = np.where(take[:, None], cand, f2)
    p2 = np.where(take, cand_pos, -1)  # position of f2 in W, -1 when older
    has = has & ~take

    # 3. closing edge: in W, after f2 (the last copy, were W a multigraph)
    a, b = f2[:, 0], f2[:, 1]
    wedge = have & (a >= 0)
    o1 = np.where((u == a) | (u == b), v, u)
    o2 = np.where((a == u) | (a == v), b, a)
    ckey = _pack(np.maximum(np.minimum(o1, o2), 0), np.maximum(o1, o2))
    ekey = _pack(np.minimum(W[:, 0], W[:, 1]), np.maximum(W[:, 0], W[:, 1]))
    eo = np.argsort(ekey, kind="stable")
    ekey, epos = ekey[eo], pos[eo]
    k = np.searchsorted(ekey, ckey, "right") - 1
    kc = np.maximum(k, 0)
    found = (k >= 0) & (ekey[kc] == ckey) if n else np.zeros_like(wedge)
    p3 = epos[kc] if n else np.full_like(p2, -1)
    has = has | (wedge & found & (p3 > p2))

    return State(
        f1=f1.astype(np.int32),
        chi=chi_new.astype(np.int32),
        f2=f2.astype(np.int32),
        has_f3=has,
        m_seen=m + n,
        unsure=unsure,
    )


def effective_groups(r: int, groups: int) -> int:
    """The largest divisor of ``r`` that is at most ``groups`` (1 when
    ``groups > r``): every estimator belongs to exactly one group."""
    if groups > r:
        return 1
    g = max(1, groups)
    while r % g:
        g -= 1
    return g


def estimate(st: State, groups: int, dtype=np.float64) -> float:
    """Median over groups of the mean coarse estimate ``chi * m`` (0 where
    the wedge has not closed), computed in ``dtype``."""
    r = len(st.chi)
    x = np.where(st.has_f3, st.chi.astype(dtype) * dtype(st.m_seen), dtype(0))
    g = effective_groups(r, groups)
    means = x.reshape(g, r // g).mean(axis=1, dtype=dtype)
    return float(np.median(means).astype(dtype))


def _field(program: dict, ref: State, f: str):
    got = np.asarray(program[f])
    want = np.asarray(getattr(ref, f))
    return (got.reshape(want.shape) if got.size == want.size else got), want


def mismatches(program: dict, ref: State) -> dict:
    """Per field, how many elements of a program snapshot (one tenant,
    leading axis of 1 allowed) differ from the reference; ``f2`` and
    ``has_f3`` of unsure estimators are left out."""
    out = {}
    for f in FIELDS:
        got, want = _field(program, ref, f)
        if got.shape != want.shape:
            out[f] = int(want.size)
            continue
        differ = got != want
        if f in ("f2", "has_f3"):
            differ = differ & ~(ref.unsure[:, None] if differ.ndim == 2 else ref.unsure)
        out[f] = int(np.sum(differ))
    return out


def settled(program: dict, ref: State) -> State:
    """The reference state with ``f2`` and ``has_f3`` of unsure estimators
    taken from the program snapshot (where its shapes match)."""
    f2, want_f2 = _field(program, ref, "f2")
    has, want_has = _field(program, ref, "has_f3")
    if f2.shape != want_f2.shape or has.shape != want_has.shape:
        return ref
    u = ref.unsure
    return State(
        f1=ref.f1, chi=ref.chi, m_seen=ref.m_seen, unsure=u,
        f2=np.where(u[:, None], f2, want_f2).astype(np.int32),
        has_f3=np.where(u, has, want_has),
    )
