"""bulkUpdateAll (paper Section 4): incorporate a batch of edges into all r
estimators while maintaining the neighborhood sampling invariant (NBSI).

One jit-compiled pure function: (state, W, n_valid, key) -> state'. The three
steps map 1:1 onto the paper, and each stage is a public, reusable piece
(``step1_level1`` / ``rank_queries`` / ``step2_level2`` / ``step3_closing``)
that ``repro.core.schemes`` composes into pluggable estimator schemes:

  Step 1  level-1 reservoir over E ∪ W            (map + extract/combine)
  Step 2  rankAll(W) + multisearch for ld/rd, chi+, and the (src, rank)
          "naming system" decode of the new level-2 edge (Q1/Q2 queries)
  Step 3  exact multisearch of the wedge complement against the (min,max)
          sorted batch, with the pos > pos(f2) arrival check

All lookups against a given sorted structure are fused: the Q1 rank and degree
queries for both f1 endpoints are one concatenated query vector answered by a
single multisearch over ``R.key_desc``, the Q2 decode is one multisearch over
``R.key_rank``, and the closing-edge check is one multisearch over ``R.ekey`` —
three multisearch passes per batch (down from six-plus independent
searchsorted calls), matching Theorem 4.1's O(sort(r) + sort(s)) memory-access
accounting. ``repro.primitives.search.multisearch_bounds`` answers each pass
(``jnp.searchsorted`` by default, the Pallas counting kernel by name).

``bulk_update_chunk`` scans K stacked batches inside one jit dispatch; because
randomness is counter-based (jax.random.fold_in of the stream key with the
batch index), the result is bit-for-bit identical to K sequential
``bulk_update_all`` calls — the result distribution is also identical
regardless of device count or batch sharding, as required for elastic
re-scaling and for the coordinated/independent paths to be interchangeable.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.rank import INF64, RankStructure, rank_all, rank_all_chunk
from repro.core.state import EstimatorState
from repro.primitives.ingest import ingest_backend, randint_from_bits
from repro.primitives.search import multisearch_bounds, multisearch_lt
from repro.primitives.sort import pack2


def step1_level1(state: EstimatorState, W, n_valid, key):
    """Reservoir-sample level-1 edges over E ∪ W (paper Section 4.2).

    Draw t ~ U[0, m + |W|); t >= m selects replacement edge W[t - m]. For batch
    size 1 this is exactly classic reservoir sampling.
    """
    with jax.named_scope("step1"):
        r = state.r
        m = state.m_seen
        total = m + n_valid.astype(jnp.int64)
        with jax.named_scope("rng"):
            t = jax.random.randint(
                key, (r,), jnp.int64(0), jnp.maximum(total, 1), dtype=jnp.int64
            )
        replace = (t >= m) & (total > 0)
        idx = jnp.clip(
            t - m, 0, jnp.maximum(n_valid.astype(jnp.int64) - 1, 0)
        ).astype(jnp.int32)
        f1 = jnp.where(replace[:, None], W[idx], state.f1)
        chi = jnp.where(replace, 0, state.chi)
        f2 = jnp.where(replace[:, None], jnp.int32(-1), state.f2)
        has_f3 = state.has_f3 & ~replace
        f1_bpos = jnp.where(replace, idx, -1)  # ephemeral: position of f1 within W
        return f1, chi, f2, has_f3, f1_bpos


def rank_queries(R: RankStructure, u, v, f1_bpos):
    """rank(endpoint -> other) for both f1 endpoints (paper Observation 4.4),
    fused into ONE multisearch over ``R.key_desc``.

    In the (src asc, pos desc) order the stored rank of an arc is its offset
    within the src segment (Lemma 4.3), so both Q1 variants reduce to a
    subtraction of two insertion points:

      fresh f1 (in W at pos p): its own arc has key pack2(endpoint, s-1-p);
        rank = idx(own arc) - seg_start(endpoint).
      old f1 (p = -1, paper footnote 5): the same key expression degenerates
        to pack2(endpoint, s) — one past the segment — so the subtraction
        yields the segment width = deg_W(endpoint).

    Four query roles (own-arc/segment-end for u and v, segment starts for u
    and v) ride in one concatenated query vector: one pass over the structure
    answers everything.
    """
    with jax.named_scope("q1"):
        s = R.s
        zero = jnp.zeros_like(f1_bpos)
        q = jnp.concatenate(
            [
                pack2(u, (s - 1) - f1_bpos),  # fresh: own arc; old: segment end
                pack2(v, (s - 1) - f1_bpos),
                pack2(u, zero),  # segment starts
                pack2(v, zero),
            ]
        )
        lt, le = multisearch_bounds(R.key_desc, q)
        r = u.shape[0]
        hi_u, hi_v = lt[:r], lt[r : 2 * r]
        lo_u, lo_v = lt[2 * r : 3 * r], lt[3 * r :]
        w_u = (hi_u - lo_u).astype(jnp.int32)
        w_v = (hi_v - lo_v).astype(jnp.int32)
        # a fresh f1's own arc is guaranteed present; mask anyway (belt + braces)
        fresh = f1_bpos >= 0
        miss_u = fresh & ~(le[:r] > hi_u)
        miss_v = fresh & ~(le[r : 2 * r] > hi_v)
        return jnp.where(miss_u, 0, w_u), jnp.where(miss_v, 0, w_v)


def step2_level2(f1, chi_minus, f2, has_f3, f1_bpos, R: RankStructure, key):
    """Update level-2 edges and chi (paper Section 4.3)."""
    u, v = f1[:, 0], f1[:, 1]
    have_f1 = u >= 0

    ld, rd = rank_queries(R, u, v, f1_bpos)
    ld = jnp.where(have_f1, ld, 0)
    rd = jnp.where(have_f1, rd, 0)
    chi_plus = ld + rd
    chi_new = chi_minus + chi_plus

    k_coin, k_phi = jax.random.split(key)
    with jax.named_scope("rng"):
        coin = jax.random.uniform(k_coin, (f1.shape[0],), dtype=jnp.float32)
    p_new = chi_plus.astype(jnp.float32) / jnp.maximum(
        chi_new.astype(jnp.float32), 1.0
    )
    take_new = have_f1 & (chi_plus > 0) & (coin < p_new)

    # draw phi in [0, chi+) and decode via the (src, rank) naming system:
    # one Q2 multisearch over key_rank
    with jax.named_scope("rng"):
        phi = jax.random.randint(
            k_phi, (f1.shape[0],), 0, jnp.maximum(chi_plus, 1), dtype=jnp.int32
        )
    with jax.named_scope("q2"):
        t_src = jnp.where(phi < ld, u, v)
        t_rank = jnp.where(phi < ld, phi, phi - ld)
        lt, le = multisearch_bounds(R.key_rank, pack2(t_src, t_rank))
        found = le > lt
        j = jnp.minimum(lt, R.key_rank.shape[0] - 1)
        cand_a, cand_b = R.src[j], R.dst[j]
        cand = jnp.stack(
            [jnp.minimum(cand_a, cand_b), jnp.maximum(cand_a, cand_b)], axis=-1
        )
        cand_pos = R.pos[j]
        take_new = take_new & found  # guaranteed when chi_plus>0; belt+braces

        f2_new = jnp.where(take_new[:, None], cand, f2)
        f2_bpos = jnp.where(take_new, cand_pos, -1)  # ephemeral
        has_f3 = has_f3 & ~take_new
    return f2_new, chi_new, has_f3, f2_bpos


def step3_closing(f1, f2, has_f3, f2_bpos, R: RankStructure):
    """Detect closing edges in W (paper Section 4.4).

    The closing edge of the wedge (f1, f2) joins the two non-shared endpoints.
    It must appear after f2: for f2 sampled from this batch at pos p2, require
    batch pos > p2; for older f2 any batch pos qualifies (f2_bpos = -1). One
    multisearch over the (min,max)-sorted batch answers every estimator.
    """
    with jax.named_scope("closing"):
        u, v = f1[:, 0], f1[:, 1]
        a, b = f2[:, 0], f2[:, 1]
        have_wedge = (u >= 0) & (a >= 0)

        u_shared = (u == a) | (u == b)
        o1 = jnp.where(u_shared, v, u)
        a_shared = (a == u) | (a == v)
        o2 = jnp.where(a_shared, b, a)
        cmin = jnp.minimum(o1, o2)
        cmax = jnp.maximum(o1, o2)

        lt, le = multisearch_bounds(R.ekey, pack2(cmin, cmax))
        found = le > lt
        # the arrival rule is existential — ANY copy after f2 closes the
        # wedge — so on duplicate-edge (multigraph) batches take the LAST
        # copy's pos: the sort is stable, so the duplicate run [lt, le) is
        # pos-ascending
        p3 = R.epos[jnp.maximum(le - 1, 0)]
        closed_now = have_wedge & found & (p3 > f2_bpos)
        return has_f3 | closed_now


def bulk_update_all(
    state: EstimatorState, W: jax.Array, n_valid: jax.Array, key: jax.Array
) -> EstimatorState:
    """Process one batch of edges into all estimators (paper Theorem 4.1).

    W: (s, 2) int32; first n_valid rows are real edges (tail is padding).
    Cost: O(sort(r) + sort(s)) memory accesses, O(log^2(r+s)) depth — sorts and
    multisearches only (one fused multisearch per sorted structure), no
    per-estimator scalar work.
    """
    n_valid = jnp.asarray(n_valid, dtype=jnp.int32)
    k1, k2 = jax.random.split(key)

    f1, chi_m, f2, has_f3, f1_bpos = step1_level1(state, W, n_valid, k1)
    R = rank_all(W, n_valid)
    f2, chi, has_f3, f2_bpos = step2_level2(
        f1, chi_m, f2, has_f3, f1_bpos, R, k2
    )
    has_f3 = step3_closing(f1, f2, has_f3, f2_bpos, R)

    return EstimatorState(
        f1=f1,
        chi=chi,
        f2=f2,
        has_f3=has_f3,
        m_seen=state.m_seen + n_valid.astype(jnp.int64),
    )


bulk_update_all_jit = jax.jit(bulk_update_all, donate_argnums=(0,))


def _bulk_update_chunk_scan(
    state: EstimatorState,
    Ws: jax.Array,
    n_valids: jax.Array,
    key: jax.Array,
    step0=0,
) -> EstimatorState:
    """The reference chunk pipeline: ``lax.scan`` of ``bulk_update_all``.

    Every fused backend below is required to be bit-identical to this scan,
    so it doubles as the oracle (``set_ingest_backend("scan")`` pins it).
    """
    steps = jnp.asarray(step0, jnp.int64) + jnp.arange(
        Ws.shape[0], dtype=jnp.int64
    )

    def step(st, xs):
        W, nv, i = xs
        return bulk_update_all(st, W, nv, jax.random.fold_in(key, i)), None

    state, _ = jax.lax.scan(step, state, (Ws, n_valids, steps))
    return state


def _chunk_randomness(state: EstimatorState, n_valids, key, steps):
    """Every random draw of a K-batch chunk, hoisted out of the scan.

    The counter-based RNG makes each batch's draws a pure function of
    (stream key, step index) and the step-1 spans a pure function of
    (m_seen at entry, batch sizes) — so all of it vectorizes over K up
    front (one threefry dispatch per role instead of K), bit-identical to
    the in-scan draws by vmap semantics. The step-2 phi draw is the one
    state-dependent draw (its span is chi+), so only its *raw bits* hoist;
    the span arithmetic is replayed in-scan by ``randint_from_bits``.

    Returns (m_before (K,), totals (K,), t (K,r), coin (K,r),
    phi_hi (K,r), phi_lo (K,r)).
    """
    with jax.named_scope("rng"):
        r = state.r
        nv64 = n_valids.astype(jnp.int64)
        m_before = state.m_seen + jnp.cumsum(nv64) - nv64
        totals = m_before + nv64

        bkeys = jax.vmap(lambda i: jax.random.fold_in(key, i))(steps)
        k12 = jax.vmap(jax.random.split)(bkeys)  # bulk_update_all's (k1, k2)
        kcp = jax.vmap(jax.random.split)(k12[:, 1])  # step2's (k_coin, k_phi)
        kbits = jax.vmap(jax.random.split)(kcp[:, 1])  # randint's internal split

        t = jax.vmap(
            lambda k, total: jax.random.randint(
                k, (r,), jnp.int64(0), jnp.maximum(total, 1), dtype=jnp.int64
            )
        )(k12[:, 0], totals)
        coin = jax.vmap(
            lambda k: jax.random.uniform(k, (r,), dtype=jnp.float32)
        )(kcp[:, 0])
        phi_hi = jax.vmap(lambda k: jax.random.bits(k, (r,), jnp.uint32))(
            kbits[:, 0]
        )
        phi_lo = jax.vmap(lambda k: jax.random.bits(k, (r,), jnp.uint32))(
            kbits[:, 1]
        )
        return m_before, totals, t, coin, phi_hi, phi_lo


def _step2_fused(f1, chi_minus, f2, has_f3, f1_bpos, R: RankStructure,
                 coin, phi_hi, phi_lo):
    """``step2_level2`` with hoisted coin/phi randomness and lt-trimmed
    searches — value-identical to the reference on every lane.

    The dropped ``le`` bounds are provably redundant: a fresh f1's own arc
    is always present in the structure (so the Q1 miss masks never fire),
    and the Q2 exact-match test ``le > lt`` is equivalent to one key
    comparison at the lt insertion point. That prices the Q1 roles at 4r
    search sides (down from 8r) and Q2 at r (down from 2r).
    """
    u, v = f1[:, 0], f1[:, 1]
    have_f1 = u >= 0
    with jax.named_scope("q1"):
        s = R.s
        zero = jnp.zeros_like(f1_bpos)
        q = jnp.concatenate(
            [
                pack2(u, (s - 1) - f1_bpos),
                pack2(v, (s - 1) - f1_bpos),
                pack2(u, zero),
                pack2(v, zero),
            ]
        )
        lt4 = multisearch_lt(R.key_desc, q)
        r = u.shape[0]
        ld = (lt4[:r] - lt4[2 * r : 3 * r]).astype(jnp.int32)
        rd = (lt4[r : 2 * r] - lt4[3 * r :]).astype(jnp.int32)
    ld = jnp.where(have_f1, ld, 0)
    rd = jnp.where(have_f1, rd, 0)
    chi_plus = ld + rd
    chi_new = chi_minus + chi_plus

    p_new = chi_plus.astype(jnp.float32) / jnp.maximum(
        chi_new.astype(jnp.float32), 1.0
    )
    take_new = have_f1 & (chi_plus > 0) & (coin < p_new)

    with jax.named_scope("rng"):
        phi = randint_from_bits(phi_hi, phi_lo, jnp.maximum(chi_plus, 1))
    with jax.named_scope("q2"):
        t_src = jnp.where(phi < ld, u, v)
        t_rank = jnp.where(phi < ld, phi, phi - ld)
        qk = pack2(t_src, t_rank)
        n2 = R.key_rank.shape[0]
        lt = multisearch_lt(R.key_rank, qk)
        j = jnp.minimum(lt, n2 - 1)
        found = (lt < n2) & (R.key_rank[j] == qk)
        cand_a, cand_b = R.src[j], R.dst[j]
        cand = jnp.stack(
            [jnp.minimum(cand_a, cand_b), jnp.maximum(cand_a, cand_b)], axis=-1
        )
        cand_pos = R.pos[j]
        take_new = take_new & found

        f2_new = jnp.where(take_new[:, None], cand, f2)
        f2_bpos = jnp.where(take_new, cand_pos, -1)
        has_f3 = has_f3 & ~take_new
    return f2_new, chi_new, has_f3, f2_bpos


def _bulk_update_chunk_fused(
    state: EstimatorState, Ws, n_valids, key, step0, *, use_kernels: bool
) -> EstimatorState:
    """The fused K-batch pipeline (ROADMAP item 1; paper §5's one-pass
    regime). Randomness, step-1 reservoir selects, and all K rank
    structures are hoisted out of the per-batch loop; what remains per
    batch is pure state math plus lt-trimmed multisearches.

    ``use_kernels=False`` (the "xla" backend) runs that residue as a
    ``lax.scan``; ``use_kernels=True`` (the "pallas" backend) hands the
    entire loop to ``repro.kernels.fused_ingest`` — one resident kernel
    whose grid walks reservoir tiles, so each tile of estimator state is
    read and written once per *chunk* instead of once per pipeline stage
    per batch.
    """
    K = Ws.shape[0]
    n_valids = jnp.asarray(n_valids, dtype=jnp.int32)
    steps = jnp.asarray(step0, jnp.int64) + jnp.arange(K, dtype=jnp.int64)

    m_before, totals, t, coin, phi_hi, phi_lo = _chunk_randomness(
        state, n_valids, key, steps
    )

    # hoisted step-1 selects: the reservoir decisions are deterministic in
    # (t, m_seen trajectory), and m_seen's trajectory is just a cumsum
    nv64 = n_valids.astype(jnp.int64)
    with jax.named_scope("step1"):
        replace = (t >= m_before[:, None]) & (totals[:, None] > 0)
        idx = jnp.clip(
            t - m_before[:, None], 0, jnp.maximum(nv64 - 1, 0)[:, None]
        ).astype(jnp.int32)
        w_sel = jax.vmap(lambda W, ix: W[ix])(Ws, idx)  # (K, r, 2)
        f1_bpos = jnp.where(replace, idx, -1)

    R = rank_all_chunk(Ws, n_valids, use_kernels=use_kernels)
    m_out = state.m_seen + jnp.sum(nv64)

    if use_kernels:
        from repro.kernels.ops import fused_ingest_op

        f1, chi, f2, has_f3 = fused_ingest_op(
            state.f1, state.chi, state.f2, state.has_f3,
            R.key_desc, R.key_rank, R.src, R.dst, R.pos, R.ekey, R.epos,
            replace, w_sel, f1_bpos, coin, phi_hi, phi_lo,
        )
        return EstimatorState(
            f1=f1, chi=chi, f2=f2, has_f3=has_f3, m_seen=m_out
        )

    def step(carry, xs):
        f1, chi, f2, has_f3 = carry
        rep, wsel, f1b, cn, hb, lb, Rk = xs
        with jax.named_scope("step1"):
            f1 = jnp.where(rep[:, None], wsel, f1)
            chi_m = jnp.where(rep, 0, chi)
            f2 = jnp.where(rep[:, None], jnp.int32(-1), f2)
            has_f3 = has_f3 & ~rep
        f2, chi, has_f3, f2_bpos = _step2_fused(
            f1, chi_m, f2, has_f3, f1b, Rk, cn, hb, lb
        )
        has_f3 = step3_closing(f1, f2, has_f3, f2_bpos, Rk)
        return (f1, chi, f2, has_f3), None

    (f1, chi, f2, has_f3), _ = jax.lax.scan(
        step,
        (state.f1, state.chi, state.f2, state.has_f3),
        (replace, w_sel, f1_bpos, coin, phi_hi, phi_lo, R),
    )
    return EstimatorState(f1=f1, chi=chi, f2=f2, has_f3=has_f3, m_seen=m_out)


def bulk_update_chunk(
    state: EstimatorState,
    Ws: jax.Array,
    n_valids: jax.Array,
    key: jax.Array,
    step0=0,
) -> EstimatorState:
    """Fold a stack of K batches into the state under ONE dispatch.

    Ws: (K, s, 2) int32 stacked batches; n_valids: (K,) their valid prefixes.
    ``key`` is the *stream* key (not pre-folded); batch i derives its key as
    ``fold_in(key, step0 + i)`` — the identical counter-based stream the
    per-batch path uses — so the result is bit-for-bit equal to

        for i in range(K):
            state = bulk_update_all(state, Ws[i], n_valids[i],
                                    jax.random.fold_in(key, step0 + i))

    (asserted exactly by tests/test_core.py::TestChunkedUpdate and across
    backends by tests/test_fused_ingest.py). ``step0`` is a traced scalar:
    resuming a stream at any batch cursor reuses the compiled program.

    The implementation dispatches on ``repro.primitives.ingest`` at trace
    time: "scan" runs the reference per-batch scan; "xla" (what "auto"
    selects on every platform) runs the fused pipeline with hoisted
    randomness/structures and lt-trimmed searches; "pallas" additionally
    hands the batch loop to the resident fused-ingest kernel. All three are
    bit-identical — the backend knob trades dispatch/memory traffic, never
    results. Every execution
    plan that chunks (``single`` and the banked plans) inherits the fused
    path through ``scheme.chunk_update`` with no signature change.
    """
    backend = ingest_backend()
    if backend == "scan":
        return _bulk_update_chunk_scan(state, Ws, n_valids, key, step0)
    return _bulk_update_chunk_fused(
        state, Ws, n_valids, key, step0, use_kernels=(backend == "pallas")
    )


bulk_update_chunk_jit = jax.jit(bulk_update_chunk, donate_argnums=(0,))


# ---------------------------------------------------------------------------
# turnstile deletions (CoCoS-style liveness patching, arXiv:1802.04249)
# ---------------------------------------------------------------------------
def delete_keys(D: jax.Array, n_valid: jax.Array) -> jax.Array:
    """Sorted canonical int64 keys of a deletion batch.

    D: (s, 2) int32; the first ``n_valid`` rows are edges to delete (order
    within a deletion batch is irrelevant — deletion is a set operation).
    Padding rows map to the INF64 sentinel so they can never match a state
    key; real keys are pack2(min, max) of non-negative vertex ids.
    """
    n_valid = jnp.asarray(n_valid, dtype=jnp.int32)
    dmin = jnp.minimum(D[:, 0], D[:, 1])
    dmax = jnp.maximum(D[:, 0], D[:, 1])
    key = jnp.where(
        jnp.arange(D.shape[0], dtype=jnp.int32) < n_valid,
        pack2(dmin, dmax),
        INF64,
    )
    return jnp.sort(key)


def bulk_delete_update(
    state: EstimatorState, D: jax.Array, n_valid: jax.Array
) -> EstimatorState:
    """Process one batch of edge DELETIONS into all estimators.

    The turnstile extension of the NBSI state (the CoCoS correction,
    arXiv:1802.04249, mapped onto this paper's two-level sample): an
    estimator's sample is patched so that no dead edge can ever contribute to
    the coarse estimate, while every sampling decision that was made remains
    exactly the insertion-only one:

      * f1 deleted   -> full reset of the slot (f1 = -1, chi = 0, f2 = -1,
        has_f3 = False): the level-1 sample is gone, and everything below it
        was conditioned on f1.
      * f2 deleted   -> drop the level-2 edge and the closing flag, keep f1
        and chi (chi counts arrivals after f1, a pure insertion statistic).
      * the wedge's closing edge deleted -> clear has_f3 (the wedge is open
        again; a future re-insertion closes it through step 3 as usual).

    ``m_seen`` is NOT decremented: it is the estimator's importance weight
    (total insertion arrivals), and the reservoir/resampling draws in steps
    1-2 are functions of that insertion counter alone. Unbiasedness for the
    *live* graph follows: for a triangle whose three edges are live at query
    time, none of its edges ever appears in a deletion batch, so the
    probability that an estimator tracks it — P(f1 = e1) * P(f2 = e2 | f1)
    * 1{e3 after e2} = 1/(m * chi) — is untouched by this patch (kills only
    fire on estimators whose sample already held a dead edge, i.e. paths
    that could not have detected the live triangle); and every dead
    copy-triple's contribution is zeroed by one of the three rules above.
    Hence E[chi * m_seen * 1{has_f3}] = tau_live exactly, per Lemma 3.2's
    argument. Contract: at most one live copy per edge key (delete-then-
    reinsert is fine — batches are processed in arrival order and the new
    copy re-enters sampling; deleting one copy of a key while another is
    still live is not, since the key match cannot tell copies apart).

    Deterministic (no RNG, no step counter): deleting never advances the
    stream cursor, which is what keeps all-insertion turnstile streams
    bit-identical to the insertion-only path.
    """
    with jax.named_scope("delete"):
        dkey = delete_keys(D, n_valid)
        lt, le = multisearch_bounds(dkey, _delete_queries(state))
        return _apply_delete_hits(state, le > lt)


def _delete_queries(state: EstimatorState) -> jax.Array:
    """The (3r,) fused membership-query vector of a deletion batch: the f1
    edge, the f2 edge, and the wedge's closing edge per estimator. Unset
    slots (-1 endpoints) pack to negative keys that cannot match a real (or
    sentinel) delete key, and are masked in ``_apply_delete_hits`` besides
    (belt + braces)."""
    u, v = state.f1[:, 0], state.f1[:, 1]
    a, b = state.f2[:, 0], state.f2[:, 1]
    # the wedge's closing edge joins the two non-shared endpoints (step 3)
    u_shared = (u == a) | (u == b)
    o1 = jnp.where(u_shared, v, u)
    a_shared = (a == u) | (a == v)
    o2 = jnp.where(a_shared, b, a)
    return jnp.concatenate(
        [
            pack2(jnp.minimum(u, v), jnp.maximum(u, v)),
            pack2(jnp.minimum(a, b), jnp.maximum(a, b)),
            pack2(jnp.minimum(o1, o2), jnp.maximum(o1, o2)),
        ]
    )


def _apply_delete_hits(state: EstimatorState, hit: jax.Array) -> EstimatorState:
    """Elementwise clears for one deletion batch, from the (3r,) hit mask of
    ``_delete_queries``. See ``bulk_delete_update`` for the semantics."""
    have_f1 = state.f1[:, 0] >= 0
    have_f2 = have_f1 & (state.f2[:, 0] >= 0)
    r = state.r
    hit_f1 = hit[:r] & have_f1
    hit_f2 = hit[r : 2 * r] & have_f2
    hit_f3 = hit[2 * r :] & have_f2

    f1 = jnp.where(hit_f1[:, None], jnp.int32(-1), state.f1)
    chi = jnp.where(hit_f1, 0, state.chi)
    f2 = jnp.where((hit_f1 | hit_f2)[:, None], jnp.int32(-1), state.f2)
    has_f3 = state.has_f3 & ~(hit_f1 | hit_f2 | hit_f3)
    return EstimatorState(
        f1=f1, chi=chi, f2=f2, has_f3=has_f3, m_seen=state.m_seen
    )


bulk_delete_update_jit = jax.jit(bulk_delete_update, donate_argnums=(0,))


def bulk_delete_chunk(
    state: EstimatorState, Ds: jax.Array, n_valids: jax.Array
) -> EstimatorState:
    """Fold a stack of K deletion batches into the state under ONE dispatch.

    Ds: (K, s, 2); n_valids: (K,). Deletion batches commute and carry no RNG,
    so this is trivially bit-identical to K sequential ``bulk_delete_update``
    calls — the scan exists purely to amortize dispatch overhead on
    high-churn streams (the deletion arm of the chunked ingest pipeline).

    Like ``bulk_update_chunk`` this dispatches on the ingest backend: under
    "xla"/"pallas" the K key sorts are hoisted out of the scan (one batched
    sort dispatch) and the membership test is lt-trimmed to one gathered key
    comparison per query — ``le > lt`` is an exact-match test, so both forms
    are bit-identical. The deletion arm has no resident kernel of its own
    (it is already one elementwise pass), so "pallas" shares the hoisted
    XLA form.
    """
    if ingest_backend() == "scan":

        def step(st, xs):
            D, nv = xs
            return bulk_delete_update(st, D, nv), None

        state, _ = jax.lax.scan(step, state, (Ds, n_valids))
        return state

    with jax.named_scope("delete"):
        dkeys = jax.vmap(delete_keys)(Ds, n_valids)  # (K, s) hoisted sorts
        n = dkeys.shape[1]

        def step(st, dk):
            q = _delete_queries(st)
            lt = multisearch_lt(dk, q)
            j = jnp.minimum(lt, n - 1)
            hit = (lt < n) & (dk[j] == q)
            return _apply_delete_hits(st, hit), None

        state, _ = jax.lax.scan(step, state, dkeys)
        return state


bulk_delete_chunk_jit = jax.jit(bulk_delete_chunk, donate_argnums=(0,))
