"""repro.service — sharded, batched PMwCAS execution service.

Covers the router bijections, the conflict-defer scheduling rule, the
stacked-vs-serial kernel dispatch differential, cross-shard
serialization and its crash atomicity (the decision-journal redo), and
the KVService front against a single-structure reference.
"""
import pathlib

import numpy as np
import pytest

from repro import PMemPool, SimulatedCrash
from repro.pmwcas import (DurableBackend, KernelBackend, MwCASOp, SimBackend,
                          make_backend, register_backend)
from repro.service import (BatchScheduler, CROSS_SHARD, CrossShardJournal,
                           KVService, RowBatch, SerialShardExecutor,
                           ServiceError, ShardRouter, StackedKernelExecutor,
                           build_rounds)
from repro.structures import (FULL, HashMap, INSERT, KVOp, OK, WorkloadSpec,
                              client_streams, compile_workload, interleave,
                              load_phase, partition_ops, replay_effects)


# ---------------------------------------------------------------------------
# router
# ---------------------------------------------------------------------------

def test_router_range_and_hash_are_bijections():
    for policy in ("range", "hash"):
        r = ShardRouter(4, words_per_shard=8, policy=policy)
        seen = set()
        for addr in range(32):
            s, l = r.shard_of_addr(addr), r.local(addr)
            assert 0 <= s < 4
            assert r.global_addr(s, l) == addr
            seen.add((s, l))
        assert len(seen) == 32


def test_router_classify_local_and_cross():
    r = ShardRouter(4, words_per_shard=8)
    local = r.classify(MwCASOp([(8, 0, 1), (9, 1, 2)]))
    assert local.shard == 1 and not local.is_cross
    assert local.local.addrs == (0, 1)            # translated
    cross = r.classify(MwCASOp([(2, 0, 1), (9, 0, 1), (30, 0, 1)]))
    assert cross.is_cross and cross.shard == CROSS_SHARD
    assert set(cross.parts) == {0, 1, 3}
    assert cross.parts[3][0].addr == 6            # 30 -> shard 3, local 6


def test_router_rejects_bad_addresses():
    r = ShardRouter(2, words_per_shard=4)
    with pytest.raises(ValueError):
        r.shard_of_addr(8)                        # beyond shard space
    with pytest.raises(TypeError):
        r.classify(MwCASOp([("slot", 0, 1)]))
    with pytest.raises(ValueError):
        ShardRouter(2, policy="range")            # needs words_per_shard
    with pytest.raises(ValueError):
        ShardRouter(2, words_per_shard=4, policy="bogus")
    # hash policy bounds too: array shards silently drop out-of-range
    # scatters, so an unbounded address must be rejected up front
    rh = ShardRouter(2, words_per_shard=8, policy="hash")
    with pytest.raises(ValueError):
        rh.local(40)
    with pytest.raises(ValueError):
        rh.classify(MwCASOp([(16, 0, 1)]))


def test_scheduler_rejects_out_of_space_addresses():
    _, sched = _kernel_sched(n_shards=2, words=8)
    with pytest.raises(ValueError):
        sched.submit(MwCASOp([(40, 0, 1)]))       # would write nothing
    assert sched.pending_count == 0


def test_router_key_routing_spreads_and_is_stable():
    r = ShardRouter(4, words_per_shard=8)
    shards = [r.shard_of_key(k) for k in range(1, 257)]
    assert set(shards) == {0, 1, 2, 3}
    assert shards == [r.shard_of_key(k) for k in range(1, 257)]


# ---------------------------------------------------------------------------
# round formation: the conflict-defer rule
# ---------------------------------------------------------------------------

def test_build_rounds_defers_duplicate_targets_and_caps():
    # K = 1 rows are not bucket pairs: the claim-set rule forms the round
    ops = [MwCASOp([(0, 0, 1)]), MwCASOp([(1, 0, 1)]),
           MwCASOp([(0, 1, 2)]),                # dup target -> defer
           MwCASOp([(2, 0, 1)])]
    q = RowBatch.pack(ops, ops)
    assert q.addr.shape == (4, 1)
    rounds, leftovers, defers, overflows = build_rounds({0: q}, round_cap=2)
    assert [e.addrs for e in rounds[0]] == [(0,), (1,)]
    assert rounds[0].addr.tolist() == [[0], [1]]
    # the dup-target op deferred, the 4th op hit the cap
    assert [e.addrs for e in leftovers[0]] == [(0,), (2,)]
    assert defers[0] == 1 and overflows[0] == 1


# ---------------------------------------------------------------------------
# scheduler: conflict-defer, at-most-once, stats
# ---------------------------------------------------------------------------

def _kernel_sched(n_shards=2, words=8, round_cap=8, **kw):
    backends = [KernelBackend(n_words=words, use_kernel=False)
                for _ in range(n_shards)]
    router = ShardRouter(n_shards, words_per_shard=words)
    return backends, BatchScheduler(backends, router, round_cap=round_cap,
                                    **kw)


def test_scheduler_defer_then_definitive_verdict():
    _, sched = _kernel_sched()
    f1 = sched.submit(MwCASOp([(0, 0, 5)]))
    f2 = sched.submit(MwCASOp([(0, 0, 7)]))      # same target, same expected
    f3 = sched.submit(MwCASOp([(1, 0, 9)]))
    assert sched.step() == 2                     # f1 + f3; f2 deferred
    assert f1.success and f3.success and not f2.done
    assert sched.stats.shards[0].defers == 1
    assert sched.step() == 1                     # f2 executes, fails (a)
    assert f2.done and not f2.success
    assert f2.latency_rounds == 2 and f1.latency_rounds == 1
    assert sched.read(0) == 5 and sched.read(1) == 9
    # at-most-once: nothing left queued
    assert sched.pending_count == 0 and sched.step() == 0


def test_scheduler_matches_single_backend_reference():
    """Sharding must not change verdicts: disjoint per-shard traffic vs
    the same ops on one flat backend."""
    rng = np.random.default_rng(7)
    n_shards, words = 4, 8
    ops = []
    for _ in range(40):
        shard = int(rng.integers(n_shards))
        k = int(rng.integers(1, 3))
        addrs = sorted(rng.choice(words, size=k, replace=False).tolist())
        ops.append(MwCASOp([(shard * words + a, 0, 1 + int(rng.integers(4)))
                            for a in addrs]))
    backends, sched = _kernel_sched(n_shards, words)
    futs = sched.submit_many(ops)
    sched.drain()
    flat = KernelBackend(n_words=n_shards * words, use_kernel=False)
    # replay in completion order (the service's linearization) on the flat
    # table: every future's verdict must reproduce
    order = sorted(futs, key=lambda f: (f.latency_rounds, f.seq))
    for f in order:
        (ref,) = flat.execute([f.op])
        assert ref.success == f.success, f.op
    table = np.concatenate([b.values() for b in backends])
    assert np.array_equal(table, flat.values())


def test_scheduler_sim_shards_agree_with_kernel_shards():
    words, n_shards = 6, 2
    ops = [MwCASOp.increment([s * words + a], [0])
           for s in range(n_shards) for a in (0, 2, 4)]
    router = ShardRouter(n_shards, words_per_shard=words)
    sims = [SimBackend(words) for _ in range(n_shards)]
    s_sched = BatchScheduler(sims, router)
    kernels = [KernelBackend(n_words=words, use_kernel=False)
               for _ in range(n_shards)]
    k_sched = BatchScheduler(kernels, router)
    sf = s_sched.submit_many(ops)
    kf = k_sched.submit_many(ops)
    s_sched.drain(), k_sched.drain()
    assert [f.success for f in sf] == [f.success for f in kf] == [True] * 6
    for s in range(n_shards):
        assert np.array_equal(sims[s].values(), kernels[s].values())


def test_stacked_executor_matches_serial():
    rng = np.random.default_rng(3)
    n_shards, words = 4, 16

    def build(executor):
        backends = [KernelBackend(n_words=words, use_kernel=False)
                    for _ in range(n_shards)]
        sched = BatchScheduler(
            backends, ShardRouter(n_shards, words_per_shard=words),
            round_cap=4, executor=executor)
        return backends, sched

    ops = []
    for _ in range(60):
        shard = int(rng.integers(n_shards))
        k = int(rng.integers(1, 4))
        addrs = sorted(rng.choice(words, size=k, replace=False).tolist())
        ops.append(MwCASOp([(shard * words + a, 0, 1) for a in addrs]))
    stacked = StackedKernelExecutor()
    b1, s1 = build(stacked)
    b2, s2 = build(SerialShardExecutor())
    f1 = s1.submit_many(ops)
    f2 = s2.submit_many(ops)
    s1.drain(), s2.drain()
    assert [f.success for f in f1] == [f.success for f in f2]
    for x, y in zip(b1, b2):
        assert np.array_equal(x.values(), y.values())
    assert stacked.stacked_dispatches >= 1   # the vmapped path actually ran


def test_select_executor():
    """One executor for every deployment: a lone kernel shard stacks,
    and durable shards run serially behind it."""
    kv = KVService(1, n_buckets=8, round_cap=4, use_kernel=False)
    dur = KVService(2, backend=[DurableBackend(), DurableBackend()],
                    n_buckets=8, round_cap=4)
    for svc in (kv, dur):
        assert isinstance(svc.executor, StackedKernelExecutor)
        assert svc.apply([KVOp(INSERT, 5, 5)])[0].status == OK
    assert kv.executor.stats.dispatches == 1
    assert kv.executor.stats.serial_rounds == 0
    assert kv.executor.shapes == {(1, 4, 2, 16, False)}
    assert dur.executor.stats.dispatches == 0
    assert dur.executor.stats.serial_rounds == 1


# ---------------------------------------------------------------------------
# cross-shard ops: serialization + atomicity
# ---------------------------------------------------------------------------

def test_cross_shard_op_executes_atomically_and_serialized():
    _, sched = _kernel_sched(n_shards=3, words=8)
    flocal = sched.submit(MwCASOp([(0, 0, 1)]))
    fx = sched.submit(MwCASOp([(1, 0, 2), (9, 0, 3), (17, 0, 4)]))
    sched.drain()
    assert flocal.success and fx.success
    assert (sched.read(1), sched.read(9), sched.read(17)) == (2, 3, 4)
    assert sched.stats.cross_rounds == 1 and sched.stats.cross_ops == 1


def test_cross_shard_validation_failure_moves_nothing():
    _, sched = _kernel_sched(n_shards=2, words=8)
    sched.submit(MwCASOp([(9, 0, 7)]))
    sched.drain()
    fx = sched.submit(MwCASOp([(0, 0, 1), (9, 0, 2)]))   # 9 now holds 7
    sched.drain()
    assert fx.done and not fx.success
    assert sched.read(0) == 0 and sched.read(9) == 7


def test_two_cross_ops_in_one_global_round_serialize():
    _, sched = _kernel_sched(n_shards=2, words=8)
    fa = sched.submit(MwCASOp([(0, 0, 1), (8, 0, 1)]))
    fb = sched.submit(MwCASOp([(0, 0, 2), (8, 0, 2)]))   # same words
    sched.drain()
    assert fa.success and not fb.success      # b validated after a applied
    assert sched.read(0) == 1 and sched.read(8) == 1


# ---------------------------------------------------------------------------
# the decision journal
# ---------------------------------------------------------------------------

def test_journal_lifecycle(tmp_path):
    pool = PMemPool(tmp_path / "j")
    j = CrossShardJournal(pool)
    j.decide("x1", [(0, 1, 0, 5), (1, 2, 0, 6)])
    assert [r["id"] for r in j.pending()] == ["x1"]
    assert j.targets_of(j.pending()[0]) == [(0, 1, 0, 5), (1, 2, 0, 6)]
    j.complete("x1")
    assert j.pending() == [] and len(j) == 1
    assert j.prune() == 1 and len(j) == 0


def test_journal_torn_decision_record_is_dropped(tmp_path):
    pool = PMemPool(tmp_path / "j")
    pool.write("xwal/x9.json", b"{ not json")
    j = CrossShardJournal(pool)
    assert j.pending() == []                  # torn -> never decided


# ---------------------------------------------------------------------------
# crash during a sharded round (the satellite): a durable shard crashes
# at every persist of a mixed multi-shard batch
# ---------------------------------------------------------------------------

_W, _S = 8, 3


def _mixed_batch():
    return [
        MwCASOp([(0, 0, 1)]),                 # shard 0
        MwCASOp([(8, 0, 2)]),                 # shard 1
        MwCASOp([(16, 0, 3)]),                # shard 2
        MwCASOp([(1, 0, 4), (9, 0, 5)]),      # cross 0-1
        MwCASOp([(10, 0, 6), (17, 0, 7)]),    # cross 1-2
        MwCASOp([(2, 0, 8)]),                 # shard 0 again
    ]


_FINAL = {0: 1, 8: 2, 16: 3, 1: 4, 9: 5, 10: 6, 17: 7, 2: 8}
_CROSS_PAIRS = [[(1, 4), (9, 5)], [(10, 6), (17, 7)]]


def _crash_sweep(root: pathlib.Path, crash_shard, crash_journal,
                 group_commit=True):
    """Sweep crash points over the chosen pool; assert (i) client-
    committed ops survive, (ii) no cross-shard op is half-applied."""
    crash_at, swept = 0, 0
    while True:
        tag = f"c{crash_at}_"
        pools = [PMemPool(root / f"{tag}s{i}",
                          crash_after_persists=(
                              crash_at if i == crash_shard else None))
                 for i in range(_S)]
        backends = [DurableBackend(pool=p, group_commit=group_commit)
                    for p in pools]
        jpool = PMemPool(root / f"{tag}j",
                         crash_after_persists=(
                             crash_at if crash_journal else None))
        sched = BatchScheduler(
            backends, ShardRouter(_S, words_per_shard=_W), round_cap=4,
            journal=CrossShardJournal(jpool))
        futs = sched.submit_many(_mixed_batch())
        crashed = False
        try:
            sched.drain()
        except SimulatedCrash:
            crashed = True
        # recover: each crashed pool via its own WAL, then journal redo
        recovered = [b.crash() for b in backends]
        sched2 = BatchScheduler(
            recovered, ShardRouter(_S, words_per_shard=_W), round_cap=4,
            journal=CrossShardJournal(jpool.crash()))
        sched2.recover()
        for f in futs:                        # committed ops survive
            if f.done and f.success:
                for t in f.op.targets:
                    assert sched2.read(t.addr) == t.desired, \
                        (crash_at, f.op)
        for pairs in _CROSS_PAIRS:            # never half-applied
            vals = [sched2.read(a) for a, _d in pairs]
            assert vals == [d for _a, d in pairs] or vals == [0, 0], \
                (crash_at, pairs, vals)
        swept += 1
        if not crashed:
            for addr, val in _FINAL.items():  # clean run: everything landed
                assert sched2.read(addr) == val
            return swept
        crash_at += 1
        assert crash_at < 200, "sweep did not terminate"


def test_crash_during_sharded_round_shard_pool(tmp_path):
    swept = _crash_sweep(tmp_path / "perop", crash_shard=1,
                         crash_journal=False, group_commit=False)
    assert swept > 5                # the sweep actually crossed the batch
    # coalesced commit: far fewer fences on the shard pool, all swept
    gswept = _crash_sweep(tmp_path / "group", crash_shard=1,
                          crash_journal=False)
    assert 1 < gswept < swept


def test_crash_during_sharded_round_journal_pool(tmp_path):
    swept = _crash_sweep(tmp_path, crash_shard=None, crash_journal=True)
    assert swept > 1


def test_recover_is_idempotent(tmp_path):
    pools = [PMemPool(tmp_path / f"s{i}") for i in range(2)]
    backends = [DurableBackend(pool=p) for p in pools]
    journal = CrossShardJournal(PMemPool(tmp_path / "j"))
    # decide an op that was never applied anywhere: redo must apply it
    journal.decide("x0", [(0, 0, 0, 3), (1, 0, 0, 4)])
    sched = BatchScheduler(backends, ShardRouter(2, words_per_shard=4),
                           journal=journal)
    assert sched.recover() == 1
    assert sched.read(0) == 3 and sched.read(4) == 4
    assert sched.recover() == 0               # idempotent


# ---------------------------------------------------------------------------
# KVService: the structures front
# ---------------------------------------------------------------------------

def _spec(**kw):
    base = dict(n_ops=96, n_keys=24, read=0.3, update=0.3, insert=0.3,
                delete=0.1, batch=8, alpha=0.99, seed=5)
    base.update(kw)
    return WorkloadSpec(**base)


def test_kvservice_matches_flat_hashmap_reference():
    spec = _spec()
    ops = load_phase(spec) + compile_workload(spec)
    svc = KVService(4, structure="hashmap", n_buckets=2 * spec.n_keys,
                    round_cap=8, use_kernel=False)
    got = svc.apply(ops)
    ref_map = HashMap(KernelBackend(n_words=16 * spec.n_keys,
                                    use_kernel=False), 8 * spec.n_keys)
    want = ref_map.apply(ops)
    assert [r.status for r in got] == [r.status for r in want]
    assert svc.check_integrity() == ref_map.check_integrity()
    # client-side replay agrees too
    assert svc.items() == replay_effects(
        [(r.op, r.status) for r in got])


def test_kvservice_many_clients_interleaved():
    spec = _spec(n_ops=64)
    streams = client_streams(spec, 8)
    assert len(streams) == 8 and all(len(s) == 8 for s in streams)
    svc = KVService(4, structure="hashmap", n_buckets=64, round_cap=8,
                    use_kernel=False)
    futs = []
    for client, stream in enumerate(streams):
        futs += [svc.submit(op, client=client) for op in stream]
    svc.drain()
    assert all(f.done for f in futs)
    svc.check_integrity()
    st = svc.stats
    assert st.completed == len(futs) == st.submitted
    assert st.p99_latency_rounds >= st.p50_latency_rounds >= 1
    assert 0 < st.occupancy <= 1
    assert st.steps < len(futs)               # batching actually batched


def test_kvservice_round_cap_bounds_occupancy():
    svc = KVService(1, structure="hashmap", n_buckets=64, round_cap=2,
                    use_kernel=False)
    svc.apply([KVOp(INSERT, k, k) for k in range(1, 11)])
    s = svc.stats.shards[0]
    assert s.rounds >= 5 and s.overflows > 0
    assert svc.stats.occupancy <= 1.0


def test_kvservice_bztree_shards_split_and_gc():
    svc = KVService(2, structure="bztree", leaf_cap=2, root_cap=4,
                    n_regions=6, round_cap=4, use_kernel=False)
    res = svc.apply([KVOp(INSERT, k, k) for k in range(1, 13)])
    assert all(r.status == OK for r in res)
    before = svc.check_integrity()
    assert len(before) == 12
    assert sum(t.splits for t in svc.structs) >= 2
    freed = svc.gc_regions()
    assert freed >= 1                         # frozen originals reclaimed
    assert svc.check_integrity() == before


def test_kvservice_durable_crash_recover(tmp_path):
    spec = _spec(n_ops=48)
    svc = KVService(2, structure="hashmap", backend="durable",
                    n_buckets=48, durable_root=tmp_path)
    svc.apply(load_phase(spec) + compile_workload(spec))
    before = svc.check_integrity()
    svc2 = svc.crash()
    assert svc2.check_integrity() == before
    # and the recovered service keeps serving
    (r,) = svc2.apply([KVOp(INSERT, 1023, 9)])
    assert r.status in (OK, "exists")


def test_kvservice_custom_backend_factory():
    made = []

    def factory(n_words):
        b = KernelBackend(n_words=n_words, use_kernel=False)
        made.append(b)
        return b

    svc = KVService(3, structure="hashmap", backend=factory, n_buckets=8)
    assert len(made) == 3 and svc.backends == made
    register_backend("kernel_oracle_test",
                     lambda n_words=None, **kw: KernelBackend(
                         n_words=n_words, use_kernel=False))
    try:
        assert isinstance(make_backend("kernel_oracle_test", n_words=4),
                          KernelBackend)
    finally:
        from repro.pmwcas import BACKEND_FACTORIES
        BACKEND_FACTORIES.pop("kernel_oracle_test")


def test_partition_ops_matches_service_routing():
    from repro.structures import key_shard
    ops = compile_workload(_spec(n_ops=40))
    parts = partition_ops(ops, 4)
    router = ShardRouter(4, words_per_shard=8)
    assert router.shard_of_key(17) == key_shard(17, 4)   # one definition
    for s, part in enumerate(parts):
        assert all(router.shard_of_key(op.key) == s for op in part)
    assert sum(len(p) for p in parts) == len(ops)
    merged = interleave(client_streams(_spec(n_ops=32), 4))
    assert len(merged) == 32


def test_kvservice_scan_covers_every_shard():
    """Scans are keyspace-wide: the count must sum over all shard
    partitions, not just the shard the scan key hashes to."""
    keys = list(range(1, 25))
    for structure, kw in (("hashmap", dict(n_buckets=32)),
                          ("bztree", dict(leaf_cap=4, root_cap=8,
                                          n_regions=10))):
        svc = KVService(4, structure=structure, round_cap=8,
                        use_kernel=False, **kw)
        svc.apply([KVOp(INSERT, k, k) for k in keys])
        (r,) = svc.apply([KVOp("scan", 1)])
        assert r.status == OK and r.value == len(keys), (structure, r)
        (r,) = svc.apply([KVOp("scan", 13)])
        assert r.value == len([k for k in keys if k >= 13])


def test_kvservice_region_exhaustion_is_counted():
    """The typed OutOfRegions reaches the service: exhaustion-FULL is
    distinguishable from root-FULL in the shard stats."""
    svc = KVService(1, structure="bztree", leaf_cap=2, root_cap=8,
                    n_regions=2, round_cap=4, use_kernel=False)
    res = svc.apply([KVOp(INSERT, k, k) for k in range(1, 9)])
    assert FULL in {r.status for r in res}
    assert svc.stats.shards[0].out_of_regions >= 1


def test_kvservice_exhaustion_counts_attempts_not_queue_delay():
    # queue delay never exhausts: a tiny round cap forces long queues,
    # yet every op completes OK because it never loses a round
    svc = KVService(1, structure="hashmap", n_buckets=64, round_cap=1,
                    max_op_rounds=1, use_kernel=False)
    res = svc.apply([KVOp(INSERT, k, k) for k in range(1, 13)])
    assert all(r.status == OK for r in res)
    # genuine retry churn does: with a zero attempt budget, the split
    # retry of a full-leaf insert exhausts instead of retrying
    tsvc = KVService(1, structure="bztree", leaf_cap=2, root_cap=4,
                     n_regions=4, max_op_rounds=0, use_kernel=False)
    res = tsvc.apply([KVOp(INSERT, k, k) for k in (1, 2, 3)])
    assert [r.status for r in res] == [OK, OK, "exhausted"]


def test_scheduler_drain_raises_instead_of_spinning():
    _, sched = _kernel_sched()
    sched.submit(MwCASOp([(0, 0, 1)]))
    with pytest.raises(ServiceError):
        sched.drain(max_steps=0)
