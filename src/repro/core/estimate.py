"""Turning NBSI tuples into a triangle-count estimate (paper Lemma 3.2, Thm 3.4).

Per estimator: X = chi * m if the closing edge has been seen else 0; E[X] = tau.
The sharp estimate is a median-of-means over g groups of r/g estimators each.

Group-count rule: ``groups`` is a *request*, honored exactly when it divides
``r`` and otherwise rounded down to ``effective_groups(r, groups)`` — the
largest divisor of ``r`` that is <= ``groups``; an *unsatisfiable* request
(``groups > r``) degrades to ONE group, i.e. the plain unbiased mean (the
same fallback the pre-rule code used there). Every estimator always
participates; nothing is trimmed. (The pre-PR-4 behavior silently dropped
the trailing ``r % groups`` estimators.)

Deliberate carve-out: asking for exactly ``groups == r`` IS honored and
yields a median over size-1 groups, which on sparse coarse estimates (most
X are 0) biases toward zero. That is what the caller literally requested —
the rule only *rounds down* infeasible requests, it never second-guesses
feasible ones. Callers who want robustness on sparse data should request
``groups << r`` (the Theorem 3.4 regime) or use the mean (groups=1).

Shardable decomposition (the device-resident query path)
--------------------------------------------------------
The median-of-means factors through per-shard partial group sums: a shard
owning the contiguous estimator slice ``[offset, offset + r_local)`` computes
``partial_group_sums`` — its coarse estimates scatter-added into the ``g``
group bins by *global* estimator index — and ``combine_group_sums`` adds the
per-shard partials (shard-index order, a fixed (e, g) -> (g) reduction),
divides by the group size, and takes the median. Numerically this is the
same value ``estimate`` computes on the gathered state: every coarse
estimate is the product of two integers (``chi * m_seen``) held exactly in
float64, so the group sums are exact integers whenever ``tau * m < 2^53``
and addition order cannot change them; the combine additionally fixes the
reduction order so the answer is deterministic for a given mesh even
outside that regime. ``repro.core.distributed.make_banked_estimate`` /
``make_sharded_estimate`` run this decomposition where the bank lives,
``tests/_bank_driver.py`` asserts bit-identity against the gathered oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.state import EstimatorState


def effective_groups(r: int, groups: int) -> int:
    """Largest divisor of ``r`` that is <= ``groups`` (and >= 1); an
    unsatisfiable request (``groups > r``) collapses to 1, the unbiased mean
    (parity with the pre-rule fallback). ``groups == r`` is feasible and
    honored — see the module docstring's carve-out note.

    The group count actually used by ``estimate``: 9 groups over r=512
    estimators become 8 groups of 64, never 9 groups of 56 plus 8 silently
    dropped estimators. ``EngineConfig`` validates ``groups >= 1`` up front so
    a bank can never be configured into the degenerate trim.
    """
    if r < 1:
        raise ValueError(f"need at least one estimator, got r={r}")
    if groups > r:
        return 1
    g = max(1, int(groups))
    while r % g:
        g -= 1
    return g


def coarse_estimates(state: EstimatorState) -> jax.Array:
    """(r,) float64 unbiased coarse estimates (Lemma 3.2)."""
    x = state.chi.astype(jnp.float64) * state.m_seen.astype(jnp.float64)
    return jnp.where(state.has_f3, x, 0.0)


def estimate(state: EstimatorState, groups: int = 9) -> jax.Array:
    """Median-of-means aggregate (Theorem 3.4) over all r estimators.

    ``groups`` that does not divide ``r`` is rounded down to
    ``effective_groups(r, groups)`` — see the module docstring for the rule.
    """
    with jax.named_scope("estimate"):
        x = coarse_estimates(state)
        r = x.shape[0]
        g = effective_groups(r, groups)
        return jnp.median(jnp.mean(x.reshape(g, r // g), axis=1))


def partial_group_sums(
    x_local: jax.Array, offset, r: int, groups: int
) -> jax.Array:
    """(g,) float64 partial group sums from the contiguous coarse-estimate
    slice ``x_local`` starting at global estimator index ``offset`` (a traced
    scalar on device shards). Groups are contiguous index blocks of
    ``r // g``, so a shard may straddle a group boundary — each element lands
    in the bin its *global* index names; bins the shard does not touch stay
    exactly 0.0 and contribute nothing to the combine."""
    with jax.named_scope("estimate"):
        g = effective_groups(r, groups)
        gid = (offset + jnp.arange(x_local.shape[0])) // (r // g)
        return jnp.zeros((g,), jnp.float64).at[gid].add(x_local)


def combine_group_sums(partials: jax.Array, r: int, groups: int) -> jax.Array:
    """Median-of-means from stacked (n_shards, g) partial group sums.

    The cross-shard reduction is the fixed (shard-index-ordered) sum over the
    leading axis; dividing by the group size and taking the median then
    reproduces ``estimate`` exactly (see "Shardable decomposition" in the
    module docstring for why the split point cannot change the value)."""
    with jax.named_scope("estimate"):
        g = effective_groups(r, groups)
        return jnp.median(jnp.sum(partials, axis=0) / (r // g))


estimate_jit = jax.jit(estimate, static_argnums=(1,))
