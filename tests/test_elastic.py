"""Elastic multi-tenant bank + serve loop (single device; the sharded-plan
checks run in tests/_elastic_driver.py under a forced 8-device host).

The issue-9 acceptance pins live here:
  * compile-once-per-capacity: a churn sequence that doubles capacity once
    builds exactly one new tier; hot-add/evict within capacity triggers
    ZERO XLA backend compiles after warm-up (XlaCompileCounter);
  * bit-identity: a tenant hot-added into a churning bank and fed a stream
    (per-batch and chunked) finishes bit-identical to the same stream on a
    fresh fixed-size engine;
  * snapshot/restore of one tenant under concurrent ingest of the others
    is bit-exact, and the snapshot restores into a plain single-tenant
    TriangleCountEngine (and back);
  * the serve loop answers queries concurrently with ingest, degrades
    under backpressure with tagged staleness, and retries injected faults.
"""
import numpy as np
import pytest

import repro  # noqa: F401  (x64)
from repro.data.graph_stream import batches, erdos_renyi_stream
from repro.data.prefetch import TenantQueues
from repro.engine import (
    ElasticBankEngine,
    ElasticServeLoop,
    EngineConfig,
    ResilienceConfig,
    TriangleCountEngine,
    XlaCompileCounter,
    install_fault_plan,
    parse_fault_plan,
)

R, S = 256, 16


def _stream(seed=5, m=160):
    return list(batches(erdos_renyi_stream(30, m, seed=seed), S))


def _fixed(seed, chunk=1):
    return TriangleCountEngine(EngineConfig(
        r=R, batch_size=S, n_tenants=1, seeds=(seed,), backend="single",
        chunk_size=chunk,
    ))


def _assert_snap_equal(a: dict, b: dict, ctx: str) -> None:
    for f in ("f1", "chi", "f2", "has_f3", "m_seen", "step", "root_keys"):
        np.testing.assert_array_equal(a[f], b[f], err_msg=f"{ctx}:{f}")


@pytest.fixture(autouse=True)
def _no_faults():
    install_fault_plan(None)
    yield
    install_fault_plan(None)


class TestElasticBank:
    def test_compile_once_per_capacity(self):
        its = _stream()
        bank = ElasticBankEngine(R, S, capacity=2, backend="single")
        assert bank.diag.tier_compiles == 1
        bank.hot_add("a", seed=1)
        bank.hot_add("b", seed=2)
        bank.ingest({"a": its[0]})
        bank.estimate()
        # within-capacity churn on the warmed tier: zero real XLA compiles
        c0 = XlaCompileCounter.snapshot()
        bank.evict("a")
        bank.hot_add("c", seed=3)
        bank.ingest({"b": its[1], "c": its[0]})
        bank.estimate()
        bank.snapshot_tenant("c")
        assert XlaCompileCounter.snapshot() == c0, "churn must not compile"
        assert bank.diag.tier_compiles == 1 and bank.capacity == 2
        # the doubling: exactly one new tier program set
        bank.hot_add("d", seed=4)  # fills slot 2? no: cap 2 full -> grows
        assert bank.capacity == 4
        assert bank.diag.tier_compiles == 2 and bank.diag.grows == 1
        # post-grow churn rides the (warmed) new tier compile-free
        bank.hot_add("e", seed=5)
        c1 = XlaCompileCounter.snapshot()
        bank.evict("e")
        bank.hot_add("f", seed=6)
        bank.ingest({"b": its[2], "d": its[0], "f": its[0]})
        bank.estimate()
        assert XlaCompileCounter.snapshot() == c1
        assert bank.diag.tier_compiles == 2

    @pytest.mark.parametrize("chunk", [1, 3])
    def test_hot_add_bit_identity_vs_fixed(self, chunk):
        """A tenant that joins a churning bank mid-life sees exactly the
        stream a dedicated fixed engine would: same RNG schedule (per-slot
        step cursors), same state, same estimate."""
        its = _stream()
        bank = ElasticBankEngine(
            R, S, capacity=2, backend="single", chunk_size=chunk)
        bank.hot_add("warm", seed=99)
        bank.ingest({"warm": its[3]})  # pre-existing traffic, then churn
        bank.evict("warm")
        bank.hot_add("a", seed=7)
        bank.hot_add("b", seed=8)
        if chunk == 1:
            for W, nv in its:
                bank.ingest({"a": (W, nv)})
            for W, nv in its[:4]:
                bank.ingest({"b": (W, nv)})
        else:
            for i in range(0, len(its), chunk):
                bank.ingest_chunk({"a": its[i:i + chunk]})
            bank.ingest_chunk({"b": its[:chunk]})
            bank.ingest_chunk({"b": its[chunk:4]})
        ref_a, ref_b = _fixed(7, chunk), _fixed(8, chunk)
        for W, nv in its:
            ref_a.ingest(W, nv)
        for W, nv in its[:4]:
            ref_b.ingest(W, nv)
        _assert_snap_equal(
            ref_a.bank_snapshot(), bank.snapshot_tenant("a"), "a")
        _assert_snap_equal(
            ref_b.bank_snapshot(), bank.snapshot_tenant("b"), "b")
        ests = bank.estimate()
        assert float(ests[bank.slot_of("a")]) == float(ref_a.estimate()[0])
        assert float(ests[bank.slot_of("b")]) == float(ref_b.estimate()[0])

    def test_step_cursors_reach_the_device_as_a_copy(self):
        """On the CPU backend jnp.asarray aliases a 64-byte-aligned host
        buffer instead of copying it, and dispatch is asynchronous: bumping
        a slot's cursor right after an ingest dispatch must not change the
        cursors that dispatch reads (it did, now and then, under load)."""
        bank = ElasticBankEngine(R, S, capacity=2, backend="single")
        buf = np.zeros(16, np.int64)
        off = (-buf.ctypes.data % 64) // 8
        bank._steps = buf[off:off + 2]  # aligned, so aliasable
        cursors = bank._cursors()
        bank._steps[0] += 5
        assert int(cursors[0]) == 0

    def test_snapshot_restore_under_concurrent_ingest(self):
        """Freeze tenant a, keep feeding b, evict a, restore a: a's state is
        bit-exact at its snapshot point and b never noticed."""
        its = _stream()
        bank = ElasticBankEngine(R, S, capacity=2, backend="single")
        bank.hot_add("a", seed=1)
        bank.hot_add("b", seed=2)
        for W, nv in its[:5]:
            bank.ingest({"a": (W, nv), "b": (W, nv)})
        snap = bank.snapshot_tenant("a")
        bank.evict("a")
        for W, nv in its[5:8]:
            bank.ingest({"b": (W, nv)})  # live traffic while a is gone
        bank.restore_tenant("a", snap)
        _assert_snap_equal(snap, bank.snapshot_tenant("a"), "a-restored")
        for W, nv in its[5:]:
            bank.ingest({"a": (W, nv)})
        for W, nv in its[8:]:
            bank.ingest({"b": (W, nv)})
        ref_a, ref_b = _fixed(1), _fixed(2)
        for W, nv in its:
            ref_a.ingest(W, nv)
            ref_b.ingest(W, nv)
        _assert_snap_equal(
            ref_a.bank_snapshot(), bank.snapshot_tenant("a"), "a-final")
        _assert_snap_equal(
            ref_b.bank_snapshot(), bank.snapshot_tenant("b"), "b-final")

    def test_snapshot_crosses_into_fixed_engine(self):
        """The per-tenant snapshot IS a valid single-tenant engine snapshot:
        restore it into a plain TriangleCountEngine, continue the stream
        there, and hand it back — bit-identical throughout."""
        its = _stream()
        bank = ElasticBankEngine(R, S, capacity=2, backend="single")
        bank.hot_add("a", seed=3)
        half = len(its) // 2
        for W, nv in its[:half]:
            bank.ingest({"a": (W, nv)})
        solo = TriangleCountEngine.from_snapshot(bank.snapshot_tenant("a"))
        for W, nv in its[half:]:
            solo.ingest(W, nv)
        bank.evict("a")
        bank.restore_tenant("a", solo.bank_snapshot())
        ref = _fixed(3)
        for W, nv in its:
            ref.ingest(W, nv)
        _assert_snap_equal(
            ref.bank_snapshot(), bank.snapshot_tenant("a"), "roundtrip")

    def test_empty_batch_is_a_state_noop(self):
        """nv=0 dispatches advance the step cursor but leave the slot's
        state bit-identical — the pad-and-mask cornerstone that lets free
        slots ride along in every banked dispatch."""
        its = _stream()
        bank = ElasticBankEngine(R, S, capacity=2, backend="single")
        bank.hot_add("a", seed=1)
        bank.ingest({"a": its[0]})
        before = bank.snapshot_tenant("a")
        bank.ingest({"a": (np.zeros((S, 2), np.int32), 0)})
        after = bank.snapshot_tenant("a")
        for f in ("f1", "chi", "f2", "has_f3", "m_seen"):
            np.testing.assert_array_equal(before[f], after[f], err_msg=f)
        assert int(after["step"]) == int(before["step"]) + 1

    def test_eviction_isolated_from_neighbors(self):
        """Evicting (with scrub) then re-adding a different tenant into the
        same slot never perturbs the resident neighbor."""
        its = _stream()
        bank = ElasticBankEngine(R, S, capacity=2, backend="single")
        bank.hot_add("a", seed=1)
        bank.hot_add("b", seed=2)
        bank.ingest({"a": its[0], "b": its[0]})
        b_before = bank.snapshot_tenant("b")
        bank.evict("a")
        bank.hot_add("a2", seed=9)
        bank.ingest({"a2": its[1]})
        _assert_snap_equal(b_before, bank.snapshot_tenant("b"), "b")

    def test_rejects_unbanked_plan(self):
        with pytest.raises(ValueError, match="banked"):
            ElasticBankEngine(R, S, capacity=2, backend="shardmap")


class TestElasticServeLoop:
    def test_concurrent_ingest_and_query_bit_exact(self):
        its = _stream()
        bank = ElasticBankEngine(
            R, S, capacity=2, backend="single", chunk_size=3)
        with ElasticServeLoop(bank) as loop:
            loop.add_tenant("a", seed=7).result(30)
            loop.add_tenant("b", seed=8).result(30)
            for W, nv in its:
                assert loop.submit("a", W, nv)
            for W, nv in its[:4]:
                assert loop.submit("b", W, nv)
            fut = loop.query("a")  # races the ingest it just queued behind
            assert fut.result(30)["tenant"] == "a"
            assert loop.drain(30)
            final = loop.query("a").result(30)
        ref = _fixed(7, chunk=3)
        for W, nv in its:
            ref.ingest(W, nv)
        assert final["estimate"] == float(ref.estimate()[0])
        assert final["stale_age"] == 0
        assert loop.stats.queries_answered == 2
        assert loop.stats.batches == len(its) + 4

    def test_backpressure_degrades_with_tagged_staleness(self):
        its = _stream()
        bank = ElasticBankEngine(R, S, capacity=2, backend="single")
        loop = ElasticServeLoop(  # consumer NOT started: deterministic
            bank, resilience=ResilienceConfig(backpressure_depth=1))
        bank.hot_add("a", seed=1)
        loop.queues.add_tenant("a")
        bank.ingest({"a": its[0]})
        bank.estimate()  # populate the version-keyed cache...
        bank.ingest({"a": its[1]})  # ...then move the bank past it
        loop.queues.put("a", its[2])  # backlog 1 >= depth -> degrade
        ans = loop._answer_one("a")
        assert ans["stale_age"] >= 1
        assert loop.stats.degraded_queries == 1
        assert loop.stats.max_staleness == ans["stale_age"]
        # backlog below depth: fresh answer again
        loop.queues.take("a")
        ans = loop._answer_one("a")
        assert ans["stale_age"] == 0

    def test_ingest_fault_is_retried(self):
        its = _stream()
        install_fault_plan(parse_fault_plan("engine.ingest:raise@1", seed=0))
        bank = ElasticBankEngine(R, S, capacity=2, backend="single")
        with ElasticServeLoop(bank) as loop:
            loop.add_tenant("a", seed=7).result(30)
            for W, nv in its[:3]:
                loop.submit("a", W, nv)
            loop.drain(30)
        assert loop.stats.retries >= 1
        ref = _fixed(7)
        for W, nv in its[:3]:
            ref.ingest(W, nv)
        _assert_snap_equal(
            ref.bank_snapshot(), bank.snapshot_tenant("a"), "retried")

    def test_evict_drops_pending_and_restore_rejoins(self):
        its = _stream()
        bank = ElasticBankEngine(R, S, capacity=2, backend="single")
        loop = ElasticServeLoop(bank)  # not started: queue is inspectable
        bank.hot_add("a", seed=1)
        loop.queues.add_tenant("a")
        loop.queues.put("a", its[0])
        loop.queues.put("a", its[1])
        lost = loop.queues.remove_tenant("a")
        assert lost == 2 and loop.queues.backlog() == 0
