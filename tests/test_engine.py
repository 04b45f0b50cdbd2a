"""Engine tests: multi-tenant bit-exactness vs independent runs, snapshot /
restore round-trips (in-memory and through CheckpointManager), padding,
backend selection, and CLI-equivalence with the seed driver loop."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bulk_update_all_jit, estimate, init_state
from repro.data.graph_stream import batches, erdos_renyi_stream
from repro.engine import (
    EngineConfig,
    SnapshotMismatch,
    TriangleCountEngine,
    run_stream,
    select_backend,
)

R, BS = 512, 32


def seed_driver_state(edges, r, bs, seed):
    """The seed launch/stream.py loop, verbatim: the CLI-equivalence oracle."""
    state = init_state(r)
    key = jax.random.PRNGKey(seed)
    for i, (W, nv) in enumerate(batches(edges, bs)):
        state = bulk_update_all_jit(
            state, jnp.asarray(W), jnp.int32(nv), jax.random.fold_in(key, i)
        )
    return jax.tree.map(np.asarray, state)


def assert_tenant_equals(engine, tenant, ref_state):
    snap = engine.snapshot()
    for f in ref_state._fields:
        np.testing.assert_array_equal(
            snap[f][tenant], getattr(ref_state, f), err_msg=f
        )


class TestMultiTenant:
    def test_bank_matches_independent_runs_bitforbit(self):
        """T tenants over distinct streams == T standalone runs, exactly."""
        T = 3
        streams = [erdos_renyi_stream(30, 200, seed=s) for s in range(T)]
        eng = TriangleCountEngine(
            EngineConfig(r=R, batch_size=BS, n_tenants=T,
                         seeds=(100, 101, 102))
        )
        its = [list(batches(st, BS)) for st in streams]
        for i in range(len(its[0])):
            W = np.stack([its[t][i][0] for t in range(T)])
            nv = np.array([its[t][i][1] for t in range(T)])
            eng.ingest(W, nv)
        ests = eng.estimate()
        for t in range(T):
            ref = seed_driver_state(streams[t], R, BS, seed=100 + t)
            assert_tenant_equals(eng, t, ref)
            assert float(ests[t]) == float(estimate(
                jax.tree.map(jnp.asarray, ref), groups=9))

    def test_broadcast_stream_accuracy_tiers(self):
        """One (s,2) batch fans out to all tenants; seeds differ, m agrees."""
        edges = erdos_renyi_stream(25, 150, seed=4)
        eng = TriangleCountEngine(
            EngineConfig(r=R, batch_size=BS, n_tenants=3)
        )
        for W, nv in batches(edges, BS):
            eng.ingest(W, nv)
        assert (eng.edges_seen() == len(edges)).all()
        snap = eng.snapshot()
        # different seeds -> different realizations of the same stream
        assert not np.array_equal(snap["f1"][0], snap["f1"][1])

    def test_single_tenant_matches_seed_driver(self):
        """The rewritten CLI path (engine, T=1) reproduces the seed loop."""
        edges = erdos_renyi_stream(30, 240, seed=9)
        eng = TriangleCountEngine(
            EngineConfig(r=R, batch_size=BS, n_tenants=1, seeds=(7,))
        )
        run_stream(eng, batches(edges, BS))
        ref = seed_driver_state(edges, R, BS, seed=7)
        assert_tenant_equals(eng, 0, ref)
        assert float(eng.estimate()[0]) == float(
            estimate(jax.tree.map(jnp.asarray, ref), groups=9)
        )

    @pytest.mark.parametrize("program", ["build", "build_chunk", "build_delete"])
    def test_single_plan_bank_of_one_matches_its_row_in_a_bank(self, program):
        """The ``single`` plan runs a bank of one tenant on its row and a
        larger bank vmapped: tenant 0 comes out the same either way, and the
        compiled module keeps the update's name."""
        from repro.core.schemes import GLOBAL

        cfg = EngineConfig(r=R, batch_size=BS)
        fn = getattr(select_backend(cfg, None), program)(cfg, None)
        module = {"build": "bulk_update", "build_chunk": "chunk_update",
                  "build_delete": "delete_update"}[program]
        rng = np.random.default_rng(2)
        W = rng.integers(0, 20, size=(2, 3, BS, 2)).astype(np.int32)
        nv = np.array([[BS, BS, 9], [BS, 5, BS]], np.int32)
        keys = np.stack([np.asarray(jax.random.PRNGKey(s)) for s in (4, 6)])
        if program == "build_chunk":
            args = lambda T: (W[:T], nv[:T], keys[:T], 0)  # noqa: E731
        elif program == "build":
            args = lambda T: (W[:T, 0], nv[:T, 0], keys[:T])  # noqa: E731
        else:
            args = lambda T: (W[:T, 0], nv[:T, 0])  # noqa: E731
        out = {}
        for T in (1, 2):
            bank = jax.tree.map(
                lambda x: jnp.stack([x] * T), GLOBAL.init_state(R)
            )
            if program == "build_delete":  # something to delete from
                bank = select_backend(cfg, None).build(cfg, None)(
                    bank, W[:T, 1], nv[:T, 1], keys[:T]
                )
            text = fn.lower(bank, *args(T)).as_text()
            assert text.startswith(f"module @jit_{module} "), text[:80]
            out[T] = jax.tree.map(np.asarray, fn(bank, *args(T)))
        for f in out[1]._fields:
            np.testing.assert_array_equal(
                getattr(out[1], f)[0], getattr(out[2], f)[0], err_msg=f
            )


class TestSnapshotRestore:
    def test_midstream_roundtrip_bitforbit(self):
        edges = erdos_renyi_stream(30, 200, seed=2)
        its = list(batches(edges, BS))
        half = len(its) // 2
        cfg = EngineConfig(r=R, batch_size=BS, n_tenants=2, seeds=(1, 2))

        a = TriangleCountEngine(cfg)
        for W, nv in its[:half]:
            a.ingest(W, nv)
        snap = a.snapshot()
        for W, nv in its[half:]:
            a.ingest(W, nv)

        b = TriangleCountEngine(cfg)
        b.restore(snap)
        assert b.step == half
        for W, nv in its[half:]:
            b.ingest(W, nv)

        sa, sb = a.snapshot(), b.snapshot()
        for f in ("f1", "chi", "f2", "has_f3", "m_seen", "step"):
            np.testing.assert_array_equal(sa[f], sb[f], err_msg=f)
        np.testing.assert_array_equal(a.estimate(), b.estimate())

    def test_from_snapshot_and_mismatch(self):
        eng = TriangleCountEngine(EngineConfig(r=R, batch_size=BS))
        eng.ingest(np.array([[0, 1], [1, 2]], np.int32))
        snap = eng.snapshot()
        c = TriangleCountEngine.from_snapshot(snap)
        assert c.config.r == R and c.step == 1
        wrong = TriangleCountEngine(EngineConfig(r=R * 2, batch_size=BS))
        with pytest.raises(SnapshotMismatch):
            wrong.restore(snap)

    def test_checkpoint_manager_roundtrip(self, tmp_path):
        """Snapshots survive the atomic npz checkpoint path used by drivers."""
        edges = erdos_renyi_stream(20, 100, seed=3)
        cfg = EngineConfig(r=R, batch_size=BS, n_tenants=2)
        eng = TriangleCountEngine(cfg)
        rep = run_stream(eng, batches(edges, BS),
                         ckpt_dir=str(tmp_path), ckpt_every=2)
        assert rep.resumed_from == 0 and rep.batches == len(list(batches(edges, BS)))

        eng2 = TriangleCountEngine(cfg)
        rep2 = run_stream(eng2, batches(edges, BS),
                          ckpt_dir=str(tmp_path), ckpt_every=2)
        assert rep2.resumed_from == eng.step and rep2.batches == 0
        np.testing.assert_array_equal(eng.estimate(), eng2.estimate())

        # resuming under a different batch size would skip the wrong edges
        rebatched = TriangleCountEngine(
            EngineConfig(r=R, batch_size=BS * 2, n_tenants=2)
        )
        with pytest.raises(SnapshotMismatch):
            run_stream(rebatched, batches(edges, BS * 2),
                       ckpt_dir=str(tmp_path), ckpt_every=2)

        # r mismatch gets the clear SnapshotMismatch, not a raw AssertionError
        with pytest.raises(SnapshotMismatch):
            run_stream(
                TriangleCountEngine(
                    EngineConfig(r=R * 2, batch_size=BS, n_tenants=2)
                ),
                batches(edges, BS), ckpt_dir=str(tmp_path), ckpt_every=2,
            )


class TestIngestShapes:
    def test_ragged_tail_is_padded(self):
        eng = TriangleCountEngine(EngineConfig(r=64, batch_size=16))
        eng.ingest(np.array([[0, 1], [1, 2], [0, 2]], np.int32))
        assert eng.edges_seen()[0] == 3
        with pytest.raises(ValueError):
            eng.ingest(np.zeros((17, 2), np.int32))

    def test_bad_tenant_axis(self):
        eng = TriangleCountEngine(EngineConfig(r=64, batch_size=16, n_tenants=2))
        with pytest.raises(ValueError):
            eng.ingest(np.zeros((3, 8, 2), np.int32))


class TestChunkedIngest:
    """chunk_size is pure dispatch granularity: state, estimates, snapshots,
    and resumes are bit-identical to the per-batch engine."""

    def test_run_stream_chunked_bitexact(self):
        edges = erdos_renyi_stream(30, 250, seed=6)  # 8 batches: ragged tail
        base = TriangleCountEngine(
            EngineConfig(r=R, batch_size=BS, n_tenants=2, seeds=(5, 6))
        )
        run_stream(base, batches(edges, BS))
        chunked = TriangleCountEngine(
            EngineConfig(r=R, batch_size=BS, n_tenants=2, seeds=(5, 6),
                         chunk_size=4)
        )
        rep = run_stream(chunked, batches(edges, BS))
        assert rep.batches == base.step == chunked.step
        assert rep.edges == len(edges)
        sa, sb = base.snapshot(), chunked.snapshot()
        for f in ("f1", "chi", "f2", "has_f3", "m_seen", "step", "root_keys"):
            np.testing.assert_array_equal(sa[f], sb[f], err_msg=f)
        np.testing.assert_array_equal(base.estimate(), chunked.estimate())

    def test_snapshot_restores_across_chunk_sizes(self):
        """A chunked engine's snapshot restores into a per-batch engine (and
        back) — chunk_size is not part of the persisted state."""
        edges = erdos_renyi_stream(25, 180, seed=8)
        its = list(batches(edges, BS))
        half = (len(its) // 2) or 1
        a = TriangleCountEngine(
            EngineConfig(r=R, batch_size=BS, chunk_size=3)
        )
        a.ingest_stream(its[:half])
        b = TriangleCountEngine.from_snapshot(a.snapshot())  # chunk_size=1
        assert b.config.chunk_size == 1
        for W, nv in its[half:]:
            a.ingest(W, nv)
            b.ingest(W, nv)
        np.testing.assert_array_equal(a.estimate(), b.estimate())
        sa, sb = a.snapshot(), b.snapshot()
        for f in ("f1", "chi", "f2", "has_f3", "m_seen", "step"):
            np.testing.assert_array_equal(sa[f], sb[f], err_msg=f)

    def test_ingest_stream_pads_short_batches(self):
        """Unpadded (<s, 2) batches — which per-batch ingest() accepts — must
        also flow through the chunked assembly (stack_batches pads them)."""
        rng = np.random.default_rng(0)
        items = [
            (rng.integers(0, 20, (n, 2)).astype(np.int32), n)
            for n in (3, 16, 7, 5, 16)
        ]
        a = TriangleCountEngine(EngineConfig(r=64, batch_size=16, chunk_size=2))
        a.ingest_stream(iter(items))
        b = TriangleCountEngine(EngineConfig(r=64, batch_size=16))
        for W, nv in items:
            b.ingest(W, nv)
        sa, sb = a.snapshot(), b.snapshot()
        for f in ("f1", "chi", "f2", "has_f3", "m_seen", "step"):
            np.testing.assert_array_equal(sa[f], sb[f], err_msg=f)
        assert a.diag.edges_ingested == b.diag.edges_ingested == 3 + 16 + 7 + 5 + 16

    def test_per_tenant_edge_accounting_matches_per_batch(self):
        """diag.edges_ingested for a per-tenant chunk == what K sequential
        per-tenant ingest() calls record (per-batch max over tenants, summed)."""
        Wb = np.zeros((2, 2, 16, 2), np.int32)  # (T, K, s, 2)
        nv = np.array([[10, 0], [0, 10]], np.int32)
        a = TriangleCountEngine(
            EngineConfig(r=64, batch_size=16, n_tenants=2, chunk_size=2)
        )
        a.ingest_chunk(Wb, nv)
        b = TriangleCountEngine(EngineConfig(r=64, batch_size=16, n_tenants=2))
        for k in range(2):
            b.ingest(Wb[:, k], nv[:, k])
        assert a.diag.edges_ingested == b.diag.edges_ingested == 20

    def test_chunk_shape_validation(self):
        eng = TriangleCountEngine(
            EngineConfig(r=64, batch_size=16, chunk_size=2)
        )
        with pytest.raises(ValueError):
            eng.ingest_chunk(np.zeros((3, 16, 2), np.int32))  # K mismatch
        unchunked = TriangleCountEngine(EngineConfig(r=64, batch_size=16))
        with pytest.raises(ValueError):
            unchunked.ingest_chunk(np.zeros((2, 16, 2), np.int32))

    def test_chunked_needs_single_backend(self):
        with pytest.raises(ValueError):
            select_backend(
                EngineConfig(r=64, batch_size=16, chunk_size=4,
                             backend="pjit_coordinated"), None
            )


class TestQueryCache:
    """The per-step estimate cache: repeated queries between ingests cost one
    dispatch; any ingest or restore invalidates; gather=True (the oracle)
    always recomputes."""

    def test_cache_hit_between_ingests_and_invalidation_on_ingest(self):
        edges = erdos_renyi_stream(25, 120, seed=1)
        its = list(batches(edges, 16))
        eng = TriangleCountEngine(
            EngineConfig(r=64, batch_size=16, n_tenants=3)
        )
        eng.ingest(*its[0])
        a = eng.estimate()
        b = eng.estimate()
        assert b is a  # same object: answered from the cache
        assert eng.diag.queries_answered == 2
        assert eng.diag.query_cache_hits == 1
        # estimate_tenant / estimate_tenants read through the same cache
        assert eng.estimate_tenant(1) == float(a[1])
        np.testing.assert_array_equal(
            eng.estimate_tenants([2, 0]), a[[2, 0]]
        )
        assert eng.diag.query_cache_hits == 3
        # ingest leaves the old answer step-keyed (degraded backpressure
        # serving reads it via cached_estimate) but the next query at the
        # NEW step recomputes against the new bank
        eng.ingest(*its[1])
        assert eng._est_cache.get(eng.step) is None
        astep, stale = eng.cached_estimate()
        assert astep == eng.step - 1 and stale is a
        c = eng.estimate()
        assert c is not a
        # ... and the fresh answer replaces the stale one in the cache
        astep, cur = eng.cached_estimate()
        assert astep == eng.step and cur is c
        # the oracle path never serves from (or populates) the cache
        d = eng.estimate(gather=True)
        np.testing.assert_array_equal(c, d)
        assert d is not c

    def test_restore_invalidates_cache(self):
        edges = erdos_renyi_stream(25, 120, seed=2)
        its = list(batches(edges, 16))
        eng = TriangleCountEngine(EngineConfig(r=64, batch_size=16))
        eng.ingest(*its[0])
        snap = eng.snapshot()
        eng.ingest(*its[1])
        stale = eng.estimate()
        eng.restore(snap)
        assert eng._est_cache == {}
        fresh = eng.estimate()
        assert not np.array_equal(stale, fresh) or eng.step == 1
        # the restored answer matches a never-restored engine at that step
        ref = TriangleCountEngine(EngineConfig(r=64, batch_size=16))
        ref.ingest(*its[0])
        np.testing.assert_array_equal(fresh, ref.estimate())


class TestRestoreClearsPendingOverflow:
    def test_restore_drops_prerestore_overflow_scalars(self):
        """Regression: restore() used to leave _pending_overflow populated,
        so overflow scalars from the PRE-restore stream could trigger a bogus
        capacity escalation (and recompile) on the restored engine. The
        shardmap plan is the only overflow-reporting plan; a 1-device mesh
        exercises it hermetically."""
        mesh = jax.make_mesh((1,), ("data",))
        eng = TriangleCountEngine(
            EngineConfig(r=64, batch_size=16, seeds=(0,), backend="shardmap"),
            mesh=mesh,
        )
        assert eng.plan.name == "shardmap" and eng.plan.reports_overflow
        edges = erdos_renyi_stream(20, 64, seed=4)
        its = list(batches(edges, 16))
        for W, nv in its[:2]:
            eng.ingest(W, nv)
        snap = eng.snapshot()
        eng.ingest(*its[2])
        assert eng._pending_overflow  # undrained device scalars in flight
        # simulate a hot-vertex stream: a nonzero overflow count is pending
        eng._pending_overflow.append(np.int64(5))
        escalations_before = eng.diag.capacity_escalations
        eng.restore(snap)
        assert eng._pending_overflow == []
        assert eng.diag.pending_overflow_dropped >= 2
        # the next drain (sync / estimate / snapshot) must not escalate
        eng.sync()
        assert eng.diag.capacity_escalations == escalations_before
        # and the restored engine continues the stream normally
        eng.ingest(*its[2])
        assert eng.step == 3


class TestDeadlineMissAccounting:
    def test_m_seen_equals_stream_length_under_forced_misses(self):
        """Regression for the prefetch late-duplicate replay: with the backup
        batch standing in for a late one, the engine must still ingest
        exactly len(stream) edges — the late duplicate is dropped, not
        replayed (PrefetchQueue.get)."""
        import time

        edges = erdos_renyi_stream(30, 128, seed=7)
        its = list(batches(edges, 32))
        assert len(its) == 4 and all(nv == 32 for _, nv in its)

        eng = TriangleCountEngine(EngineConfig(r=64, batch_size=32))

        def slow_iter():
            yield from its[:3]
            # hold the last batch back until the consumer's deadline fired
            # and the backup stood in for it (step hits 4 only via the
            # stale ingest) — a deterministic miss, immune to compile time
            while eng.step < 4:
                time.sleep(0.005)
            yield its[3]

        rep = run_stream(eng, slow_iter(), deadline_s=0.15)
        assert rep.stale_batches == 1
        assert rep.batches == len(its)
        assert int(eng.edges_seen()[0]) == len(edges)


class TestBackendSelection:
    def test_auto_without_mesh_is_single(self):
        cfg = EngineConfig(r=64, batch_size=16)
        assert select_backend(cfg, None).name == "single"
        assert select_backend(
            EngineConfig(r=64, batch_size=16, n_tenants=4), None
        ).name == "single"

    def test_distributed_backends_validated(self):
        cfg = EngineConfig(r=64, batch_size=16, backend="shardmap")
        with pytest.raises(ValueError):  # no mesh
            select_backend(cfg, None)
        with pytest.raises(ValueError):  # unknown name
            select_backend(
                EngineConfig(r=64, batch_size=16, backend="nope"), None
            )
        with pytest.raises(ValueError):  # multi-tenant on a 1-tenant plan
            select_backend(
                EngineConfig(r=64, batch_size=16, n_tenants=2,
                             backend="pjit_coordinated"), None
            )

    def test_auto_on_mesh_prefers_shardmap(self):
        mesh = jax.make_mesh((1,), ("data",))
        # 1-device mesh: single still wins
        assert select_backend(
            EngineConfig(r=64, batch_size=16), mesh
        ).name == "single"

    def test_banked_plans_need_fitting_mesh(self):
        banked = EngineConfig(
            r=64, batch_size=16, n_tenants=2, backend="banked_pjit_independent"
        )
        with pytest.raises(ValueError):  # no mesh at all
            select_backend(banked, None)
        with pytest.raises(ValueError):  # mesh lacks the tenants axis
            select_backend(banked, jax.make_mesh((1,), ("data",)))
        # a custom tenant_axis name is matched against the mesh axes
        with pytest.raises(ValueError):
            select_backend(
                EngineConfig(r=64, batch_size=16, n_tenants=2,
                             backend="banked_pjit_independent",
                             tenant_axis="streams"),
                jax.make_mesh((1,), ("tenants",)),
            )
        plan = select_backend(banked, jax.make_mesh((1,), ("tenants",)))
        assert plan.banked and plan.bank_sharding is not None
        assert plan.build_chunk is not None  # banked plans can chunk

    def test_banked_engine_on_degenerate_mesh_matches_single(self):
        """A 1-device 'tenants' mesh exercises the sharded code path (device_put
        through bank_sharding, in_shardings jit) without multiple devices."""
        edges = erdos_renyi_stream(25, 120, seed=3)
        mesh = jax.make_mesh((1,), ("tenants",))
        cfg = EngineConfig(r=64, batch_size=16, n_tenants=2, seeds=(4, 5))
        ref = TriangleCountEngine(cfg)
        eng = TriangleCountEngine(
            EngineConfig(r=64, batch_size=16, n_tenants=2, seeds=(4, 5),
                         backend="banked_pjit_coordinated"),
            mesh=mesh,
        )
        for W, nv in batches(edges, 16):
            ref.ingest(W, nv)
            eng.ingest(W, nv)
        sa, sb = ref.snapshot(), eng.bank_snapshot()
        for f in ("f1", "chi", "f2", "has_f3", "m_seen", "step"):
            np.testing.assert_array_equal(sa[f], sb[f], err_msg=f)
        np.testing.assert_array_equal(ref.estimate(), eng.estimate())
        # snapshot from the sharded plan restores into a plain engine
        clone = TriangleCountEngine.from_snapshot(eng.bank_snapshot())
        assert clone.plan.name == "single"
        np.testing.assert_array_equal(ref.estimate(), clone.estimate())
