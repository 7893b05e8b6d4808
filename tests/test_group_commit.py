"""Round-level group commit + cached stacked dispatch (DESIGN.md Sec. 9).

The tentpole invariants:

- a coalesced round commits under ONE persist fence, and a crash at
  EVERY persist of the coalesced path recovers to either "round
  invisible" (record absent) or "round fully applied" (record durable →
  redo) — never a torn round;
- pruning a round record first flushes the state it guards, so the
  durable truth never has a gap;
- the stacked kernel dispatch never retraces across same-bucket
  steady-state rounds (the trace cache survives stats resets).
"""
import numpy as np
import pytest

from repro import Committer, MarkerCommitter, PMemPool, SimulatedCrash
from repro.pmwcas import (DurabilityStats, DurableBackend, KernelBackend,
                          MwCASOp)
from repro.service import (BatchScheduler, CrossShardJournal, KVService,
                           RowBatch, ShardRouter, StackedKernelExecutor)
from repro.structures import (INSERT, KVOp, UPDATE,
                              check_durable_crash_sweep)


# ---------------------------------------------------------------------------
# committer: the round protocol itself
# ---------------------------------------------------------------------------

def test_commit_round_one_fence_and_verdicts(tmp_path):
    pool = PMemPool(tmp_path)
    c = Committer(pool)
    p0 = pool.persist_count
    ok = c.commit_round(
        [("a1", [("x", 0, 1), ("y", 0, 2)]),
         ("a2", [("z", 0, 3)]),
         ("a3", [("x", 0, 7)]),          # collides with a1 -> loses
         ("a4", [("w", 5, 6)])],         # stale expected -> loses
        {"x": b"X1", "y": b"Y2", "z": b"Z3", "w": b"W6"})
    assert ok == [True, True, False, False]
    assert pool.persist_count - p0 == 1          # the single round fence
    assert (c.slot_version("x"), c.slot_version("y"),
            c.slot_version("z"), c.slot_version("w")) == (1, 2, 3, 0)
    assert pool.read("data/x.v1.bin") == b"X1"
    s = c.stats
    assert s.fences == 1 and s.round_commits == 1 and s.ops_committed == 2
    assert s.flushes_issued == 1
    # two winners would have paid (3*2+2) + (3*1+2) = 13 per-op persists
    assert s.flushes_saved == 12


def test_commit_round_no_op_versions_fail(tmp_path):
    c = Committer(PMemPool(tmp_path))
    assert c.commit_round([("a", [("x", 0, 0)])], {"x": b"p"}) == [False]
    assert c.slot_version("x") == 0


def test_round_records_replay_in_commit_order(tmp_path):
    """Two durable round records advancing the same slot, finalize
    writes lost to the crash: replay must run in commit order or the
    second round's expected values never match."""
    pool = PMemPool(tmp_path)
    c = Committer(pool)
    assert c.commit_round([("a", [("x", 0, 1)])], {"x": b"v1"}) == [True]
    assert c.commit_round([("b", [("x", 1, 2)])], {"x": b"v2"}) == [True]
    crashed = pool.crash()                  # drops every lazy slot write
    c2 = Committer(crashed)
    c2.recover()
    assert c2.slot_version("x") == 2
    assert crashed.read("data/x.v2.bin") == b"v2"


def test_prune_flushes_round_effects_before_dropping(tmp_path):
    """The round record is the only durable copy of its effects; prune
    must flush slots+data first or a later crash loses committed
    state."""
    pool = PMemPool(tmp_path)
    c = Committer(pool)
    c.commit_round([("a", [("x", 0, 1), ("y", 0, 2)])],
                   {"x": b"X", "y": b"Y"})
    assert c.prune_completed() == 1
    assert pool.listdir("wal") == []
    c2 = Committer(pool.crash())
    c2.recover()
    assert c2.slot_version("x") == 1 and c2.slot_version("y") == 2
    assert pool.read("data/x.v1.bin") == b"X"


def test_prune_before_recover_redoes_rounds_first(tmp_path):
    """Prune is safe at ANY point, including on a reopened pool before
    recover(): the visible slot state may still predate a durable round
    record (the lazy finalize writes died with the process), and prune
    must redo the round before flushing and dropping its only durable
    copy — or the committed op is lost forever."""
    pool = PMemPool(tmp_path)
    c = Committer(pool)
    assert c.commit_round([("op1", [("x", 0, 1)])],
                          {"x": b"payload-v1"}) == [True]
    # process dies: lazy writes gone, the round record alone survives
    reopened = pool.crash()
    c2 = Committer(reopened)
    assert c2.prune_completed() == 1          # NO recover() first
    assert c2.slot_version("x") == 1
    assert reopened.read("data/x.v1.bin") == b"payload-v1"
    # and the state is durable: a further crash/recover is a fixpoint
    c3 = Committer(reopened.crash())
    c3.recover()
    assert c3.slot_version("x") == 1


def test_marker_committer_opts_out_of_group_commit(tmp_path):
    b = DurableBackend(pool=PMemPool(tmp_path), committer="marker",
                       group_commit=True)
    assert not b.group_commit               # markers are per-slot by design
    (r,) = b.execute([MwCASOp([("x", 0, 1)])])
    assert r.success and b.read("x") == 1
    assert isinstance(b.committer, MarkerCommitter)
    assert b.durability_stats.op_commits == 1


def test_group_commit_flag_survives_crash(tmp_path):
    b = DurableBackend(pool=PMemPool(tmp_path), group_commit=True)
    assert b.crash().group_commit
    b2 = DurableBackend(pool=PMemPool(tmp_path / "b"), group_commit=False)
    assert not b2.crash().group_commit


# ---------------------------------------------------------------------------
# the crash window of a coalesced round: crash at every persist
# ---------------------------------------------------------------------------

def test_coalesced_round_crashes_atomically(tmp_path):
    """Crash at every persist through TWO multi-op rounds driven
    straight through DurableBackend.execute.  Every recovered state
    must be a round PREFIX: a round is invisible (its record never
    became durable) or fully applied (record durable -> redo) — ops of
    one round never land separately."""
    round1 = [MwCASOp([("a", 0, 1), ("b", 0, 2)]),
              MwCASOp([("c", 0, 3)])]
    round2 = [MwCASOp([("a", 1, 4)]),
              MwCASOp([("d", 0, 5), ("e", 0, 6)])]
    states = {  # slot values after 0, 1, 2 committed rounds
        0: (0, 0, 0, 0, 0),
        1: (1, 2, 3, 0, 0),
        2: (4, 2, 3, 5, 6),
    }
    crash_at = 0
    seen = set()
    while True:
        pool = PMemPool(tmp_path / f"c{crash_at}",
                        crash_after_persists=crash_at)
        b = DurableBackend(pool=pool)
        committed = 0
        crashed = False
        try:
            assert all(r.success for r in b.execute(round1))
            committed = 1
            assert all(r.success for r in b.execute(round2))
            committed = 2
        except SimulatedCrash:
            crashed = True
        rec = b.crash()
        got = tuple(rec.read(n) for n in "abcde")
        allowed = [states[k] for k in range(committed, 3)]
        assert got in allowed, (crash_at, got, allowed)
        seen.add(got)
        # a second crash/recover cycle is a fixpoint
        rec2 = rec.crash()
        assert tuple(rec2.read(n) for n in "abcde") == got, crash_at
        if not crashed:
            assert got == states[2]
            # both torn-round outcomes actually occurred across the sweep
            assert states[0] in seen and states[2] in seen
            return
        crash_at += 1
        assert crash_at < 50, "sweep did not terminate"


def test_structure_sweep_through_batched_rounds(tmp_path):
    """The extended checker: a hash-map workload applied in BATCHES, so
    the coalesced path commits real multi-op rounds, swept crash-at-
    every-persist (including prune + second recovery in the checker's
    teardown)."""
    ops = [KVOp(INSERT, 5, 100), KVOp(INSERT, 7, 200),
           KVOp(INSERT, 9, 300), KVOp(UPDATE, 5, 111),
           KVOp(INSERT, 12, 400), KVOp(UPDATE, 7, 222)]
    n = check_durable_crash_sweep(ops, n_buckets=8, root=tmp_path,
                                  group_commit=True, batch=3)
    assert n >= 2                  # one fence per batch round (+ teardown)


def test_scheduler_round_is_one_fence_per_durable_shard(tmp_path):
    """Service rounds map 1:1 onto commit fences: a wave over durable
    shards pays exactly one persist per shard round, not one per op."""
    pools = [PMemPool(tmp_path / f"s{i}") for i in range(2)]
    backends = [DurableBackend(pool=p) for p in pools]
    sched = BatchScheduler(backends, ShardRouter(2, words_per_shard=8),
                           round_cap=8)
    ops = [MwCASOp([(a, 0, 1)]) for a in (0, 1, 2)] + \
          [MwCASOp([(8 + a, 0, 1)]) for a in (0, 1, 2, 3)]
    p0 = sum(p.persist_count for p in pools)
    futs = sched.submit_many(ops)
    sched.drain()
    assert all(f.success for f in futs)
    assert sum(p.persist_count for p in pools) - p0 == 2   # one per shard
    d = sched.durability_stats()
    assert d.fences == 2 and d.ops_committed == 7
    assert d.flushes_saved == (3 * 5 - 1) + (4 * 5 - 1)


# ---------------------------------------------------------------------------
# cached stacked dispatch: the retrace counters
# ---------------------------------------------------------------------------

def _kernel_rounds(n_shards, words, wave, b_per_shard, k):
    """One wave of same-bucket rounds: b_per_shard ops of width k per
    shard as rows, fresh addresses per wave so every op wins."""
    rounds = {}
    for s in range(n_shards):
        ops = []
        for i in range(b_per_shard):
            base = (wave * b_per_shard + i) * k
            ops.append(MwCASOp([((base + j) % words, 0, 1)
                                for j in range(k)]).sorted())
        rounds[s] = RowBatch.pack(ops, ops)
    return rounds


def test_stacked_dispatch_zero_retraces_across_steady_state():
    n_shards, words = 4, 64
    backends = [KernelBackend(n_words=words, use_kernel=False)
                for _ in range(n_shards)]
    ex = StackedKernelExecutor(round_cap=4)
    ex.execute(backends, _kernel_rounds(n_shards, words, 0, 3, 2))
    assert ex.stats.traces == 1 and ex.stats.hits == 0
    for wave in range(1, 6):
        # varying B (<= cap) and varying shard subsets stay in-bucket
        rounds = _kernel_rounds(n_shards, words, wave, 1 + wave % 3, 2)
        if wave % 2:
            rounds.pop(wave % n_shards)        # a shard sits this wave out
        ex.execute(backends, rounds)
    assert ex.stats.traces == 1                # zero steady-state retraces
    assert ex.stats.hits == 5
    assert ex.stats.dispatches == 6
    # a genuinely new bucket (wider K) does retrace, once
    ex.execute(backends, _kernel_rounds(n_shards, words, 9, 2, 3))
    ex.execute(backends, _kernel_rounds(n_shards, words, 11, 2, 3))
    assert ex.stats.traces == 2 and ex.stats.hits == 6


def test_stacked_dispatch_with_idle_shards_matches_serial():
    """Shape stability stacks ALL kernel shards — shards without a round
    ride along as padding and their tables must come back unchanged."""
    n_shards, words = 4, 16
    stacked = [KernelBackend(n_words=words, use_kernel=False)
               for _ in range(n_shards)]
    serial = [KernelBackend(n_words=words, use_kernel=False)
              for _ in range(n_shards)]
    ex = StackedKernelExecutor(round_cap=4)
    rounds = {0: [MwCASOp([(1, 0, 5)])], 2: [MwCASOp([(3, 0, 7)])]}
    out = ex.execute(stacked, {s: RowBatch.pack(ops, ops)
                               for s, ops in rounds.items()})
    assert set(out) == {0, 2} and out[0] == [True] and out[2] == [True]
    for s, ops in rounds.items():
        serial[s].execute(ops)
    for a, b in zip(stacked, serial):
        assert np.array_equal(a.values(), b.values())


def test_kvservice_steady_state_waves_never_retrace():
    """The acceptance counter: after warmup (load phase), a measurement
    window of same-bucket waves recompiles NOTHING — reset_stats zeroes
    the counters but keeps the trace cache warm."""
    svc = KVService(4, structure="hashmap", n_buckets=32, round_cap=4,
                    use_kernel=False)
    svc.apply([KVOp(INSERT, k, k) for k in range(1, 33)])      # warmup
    svc.reset_stats()
    svc.apply([KVOp(UPDATE, k, k + 100) for k in range(1, 33)])
    d = svc.stats.dispatch
    assert d is not None
    assert d.traces == 0, f"steady-state retraces: {d}"
    assert d.hits == d.dispatches > 0
    assert svc.stats.as_row()["traces"] == 0


def test_one_kernel_shard_stacks_without_retracing():
    """A lone kernel shard takes the stacked dispatch at B = round_cap:
    after the warm-up, waves of every round length from 1 to the cap
    reuse its one compiled shape."""
    svc = KVService(1, structure="hashmap", n_buckets=64, round_cap=8,
                    use_kernel=False)
    svc.apply([KVOp(INSERT, k, k) for k in range(1, 41)])      # warmup
    assert svc.executor.stats.traces == 1
    svc.reset_stats()
    lengths = []
    for n in (1, 3, 8, 5, 2, 7):
        svc.apply([KVOp(UPDATE, k, 100 + k) for k in range(1, n + 1)])
        lengths.append(svc.stats.shards[0].ops_executed - sum(lengths))
    assert lengths == [1, 3, 8, 5, 2, 7]
    d = svc.executor.stats
    assert d.traces == 0 and d.hits == d.dispatches == 6
    assert d.serial_rounds == 0
    assert svc.executor.shapes == {(1, 8, 2, svc.words_per_shard, False)}


def test_serial_executor_counts_rounds():
    # a sim shard is not a kernel: its rounds run serially
    svc = KVService(1, structure="hashmap", backend="sim", n_buckets=16,
                    round_cap=4)
    svc.apply([KVOp(INSERT, k, k) for k in range(1, 9)])
    d = svc.stats.dispatch
    assert d is not None and d.serial_rounds > 0 and d.dispatches == 0


# ---------------------------------------------------------------------------
# journal prune cadence (the ROADMAP satellite)
# ---------------------------------------------------------------------------

def test_journal_prunes_on_cadence_and_stays_bounded(tmp_path):
    pool = PMemPool(tmp_path / "j")
    backends = [KernelBackend(n_words=8, use_kernel=False)
                for _ in range(2)]
    sched = BatchScheduler(backends, ShardRouter(2, words_per_shard=8),
                           journal=CrossShardJournal(pool),
                           journal_prune_every=4)
    journal_sizes = []
    val = {0: 0, 8: 0}
    for i in range(16):
        fut = sched.submit(MwCASOp([(0, val[0], val[0] + 1),
                                    (8, val[8], val[8] + 1)]))
        sched.drain()
        assert fut.success
        val[0] += 1
        val[8] += 1
        journal_sizes.append(len(sched.journal))
    # pruned every 4 global rounds: the journal never exceeds the cadence
    assert max(journal_sizes) <= 4
    assert sched.stats.journal_pruned >= 12
    # long-running regression: the size saw-tooths instead of growing —
    # every cadence boundary (rounds 4, 8, 12, 16) drops to zero
    assert [journal_sizes[i] for i in (3, 7, 11, 15)] == [0, 0, 0, 0]


def test_journal_prune_cadence_zero_disables(tmp_path):
    pool = PMemPool(tmp_path / "j")
    backends = [KernelBackend(n_words=8, use_kernel=False)
                for _ in range(2)]
    sched = BatchScheduler(backends, ShardRouter(2, words_per_shard=8),
                           journal=CrossShardJournal(pool),
                           journal_prune_every=0)
    for i in range(6):
        sched.submit(MwCASOp([(0, i, i + 1), (8, i, i + 1)]))
        sched.drain()
    assert len(sched.journal) == 6 and sched.stats.journal_pruned == 0
    with pytest.raises(ValueError):
        BatchScheduler(backends, ShardRouter(2, words_per_shard=8),
                       journal_prune_every=-1)


# ---------------------------------------------------------------------------
# stats plumbing
# ---------------------------------------------------------------------------

def test_durability_stats_merge_and_row():
    a = DurabilityStats(flushes_issued=2, flushes_saved=10, fences=1,
                        round_commits=1, op_commits=0, ops_committed=3)
    b = DurabilityStats(flushes_issued=1, flushes_saved=5, fences=1,
                        round_commits=1, op_commits=2, ops_committed=4)
    merged = DurabilityStats().merge(a).merge(b)
    assert merged.flushes_issued == 3 and merged.flushes_saved == 15
    assert merged.ops_committed == 7
    assert merged.as_row()["fences"] == 2
    assert abs(merged.flushes_per_commit - 3 / 7) < 1e-12


def test_kvservice_durability_stats_none_for_kernel_shards():
    svc = KVService(2, structure="hashmap", n_buckets=8, use_kernel=False)
    assert svc.durability_stats() is None
