"""Elastic scale-out: hash-map directory doubling and online key-range
shard migration — one decide/materialize/swing protocol at two layers
(DESIGN.md Sec. 12), property-tested against a dict oracle, crash-swept
at every persist, and differentially verified across substrates.

The tree instance of the protocol (root splits) is covered in
``test_structures.py``; this file owns the map and service instances.
"""
import pytest

from repro.pmwcas import DurableBackend, KernelBackend
from repro.structures import (DELETE, EXHAUSTED, FULL, HashMap, INSERT,
                              KVOp, NOT_FOUND, OK,
                              READ, SCAN, UPDATE,
                              check_hashmap_resize_sweep,
                              run_struct_differential)
from repro.service import (KVService, ShardRouter,
                           check_migration_crash_sweep)
from repro import SimulatedCrash

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                # deterministic fallback
    HAVE_HYPOTHESIS = False


def elastic_map(n_buckets=4, max_doublings=3, backend=None):
    backend = backend or KernelBackend(
        n_words=HashMap.words_needed(n_buckets, max_doublings),
        use_kernel=False)
    return HashMap(backend, n_buckets, max_doublings=max_doublings)


# ---------------------------------------------------------------------------
# directory doubling: layout and unit semantics
# ---------------------------------------------------------------------------

def test_words_needed_layouts():
    # legacy (max_doublings=0): exactly the historical 2n words, no header
    assert HashMap.words_needed(16) == 32
    assert HashMap.words_needed(16, 0, base=5) == 37
    # elastic: header (gen word + reserved) + every generation's array
    assert HashMap.words_needed(4, 1) == 2 + 2 * 4 * 3     # gens 0,1
    assert HashMap.words_needed(4, 2) == 2 + 2 * 4 * 7     # gens 0,1,2
    m = elastic_map(4, 2)
    assert m.hdr == 2 and m.cap(0) == 4 and m.cap(2) == 16
    legacy = HashMap(KernelBackend(n_words=8, use_kernel=False), 4)
    assert legacy.hdr == 0 and legacy.n_words == 8


def test_doubling_growth_is_unbounded_until_cap():
    """Inserting far past gen-0 capacity grows the directory through
    repeated doublings — no FULL until max_doublings is spent."""
    m = elastic_map(4, max_doublings=3)          # 4 -> 32 buckets
    keys = list(range(10, 290, 10))              # 28 keys >> 4 buckets
    res = m.apply([KVOp(INSERT, k, k + 1) for k in keys])
    assert all(r.status == OK for r in res), [r.status for r in res]
    assert m.gen >= 2 and m.resizes >= 2
    assert m.keys_migrated > 0
    assert m.check_integrity() == {k: k + 1 for k in keys}


def test_doubling_exhausts_to_full():
    m = elastic_map(2, max_doublings=1)          # 2 -> 4 buckets, then FULL
    res = m.apply([KVOp(INSERT, k, 1) for k in range(10, 80, 10)])
    statuses = [r.status for r in res]
    assert statuses.count(OK) == 4               # final capacity
    assert statuses.count(FULL) == 3
    assert m.gen == 1 and not m.migrating


def test_split_brain_ops_during_migration():
    """Client ops proceed while the doubling is in flight: lookups see
    both generations, mutations carry the generation guard."""
    m = elastic_map(4, max_doublings=2)
    m.apply([KVOp(INSERT, k, k) for k in (11, 22, 33, 44)])
    assert m.begin_resize()
    assert m.migrating
    res = m.apply([KVOp(INSERT, 55, 5), KVOp(UPDATE, 22, 220),
                   KVOp(READ, 33), KVOp(DELETE, 44)])
    assert [r.status for r in res] == [OK, OK, OK, OK]
    assert res[2].value == 33
    # finalize and verify: the union survived the swing
    for _ in range(16):
        if not m.migrating:
            break
        m.resize_step()
    assert not m.migrating and m.gen == 1
    assert m.check_integrity() == {11: 11, 22: 220, 33: 33, 55: 5}


def test_doubling_survives_crash_mid_pump(tmp_path):
    """Crash between pump rounds: recovery replays the WAL, the gen
    word still carries MIG_BIT, and a fresh attach completes the
    doubling."""
    backend = DurableBackend(tmp_path / "d")
    m = HashMap(backend, 4, max_doublings=2)
    m.apply([KVOp(INSERT, k, k) for k in (11, 22, 33, 44)])
    assert m.begin_resize()
    m.resize_step(max_moves=1)                   # partial pump
    before = m.items()
    m2 = HashMap(backend.crash(), 4, max_doublings=2)
    assert m2.migrating                          # decision survived
    assert m2.check_integrity() == before
    assert m2.ensure_room(max_steps=16)
    assert m2.gen == 1 and m2.check_integrity() == before


def test_resize_crash_sweep(tmp_path):
    """Tentpole acceptance: crash at EVERY persist through a workload
    that drives gen 0 -> 1 -> 2 (decide, pump moves, guarded
    split-brain ops, finalize swing)."""
    kvops = [KVOp(INSERT, k, k * 3) for k in range(7, 90, 7)]
    kvops += [KVOp(UPDATE, 14, 999), KVOp(DELETE, 21)]
    swept = check_hashmap_resize_sweep(kvops, 3, tmp_path,
                                       max_doublings=2, batch=3)
    assert swept > 10


# ---------------------------------------------------------------------------
# directory doubling: property tests vs a dict oracle
# ---------------------------------------------------------------------------

def _oracle_apply(model, op):
    """Sequential dict semantics, returning the expected status."""
    if op.kind == INSERT:
        if op.key in model:
            return "exists"
        model[op.key] = op.value
        return OK
    if op.kind == UPDATE:
        if op.key not in model:
            return NOT_FOUND
        model[op.key] = op.value
        return OK
    if op.kind == DELETE:
        if op.key not in model:
            return NOT_FOUND
        del model[op.key]
        return OK
    if op.kind == READ:
        return OK if op.key in model else NOT_FOUND
    return OK                                     # SCAN never fails


def _check_against_oracle(plan):
    """Run (kind, key, value, resize?) steps on an elastic map and a
    dict; statuses and final items must agree, and the map's invariants
    must hold mid- and post-growth.  FULL is only legal once the
    doubling budget is spent AND the final generation truly has no slot
    left; with the headroom sized here it must not happen."""
    m = elastic_map(4, max_doublings=3)          # headroom: 32 buckets
    model = {}
    for kind, key, value, pump in plan:
        if pump and m.gen < 3 and not m.migrating:
            assert m.begin_resize()              # adversarial mid-op growth
        op = KVOp(kind, key, value if kind in (INSERT, UPDATE) else 0)
        (r,) = m.apply([op])
        expect = _oracle_apply(model, op)
        assert r.status == expect, (kind, key, r.status, expect)
        if kind == READ and r.status == OK:
            assert r.value == model[key]
    if m.migrating:
        assert m.ensure_room(max_steps=64)
    assert m.check_integrity() == model


def _plan_from_rng(rng, n_steps=40):
    kinds = [INSERT, UPDATE, DELETE, READ]
    plan = []
    for _ in range(n_steps):
        kind = kinds[int(rng.integers(4))]
        key = int(rng.integers(1, 25))
        value = int(rng.integers(1, 1 << 16))
        plan.append((kind, key, value, bool(rng.random() < 0.1)))
    return plan


if HAVE_HYPOTHESIS:
    @settings(max_examples=40, deadline=None)
    @given(st.lists(
        st.tuples(st.sampled_from([INSERT, UPDATE, DELETE, READ]),
                  st.integers(1, 24), st.integers(1, 1 << 16),
                  st.booleans()),
        min_size=1, max_size=40))
    def test_doubling_matches_dict_oracle(plan):
        _check_against_oracle(plan)
else:
    @pytest.mark.parametrize("seed", range(8))
    def test_doubling_matches_dict_oracle(seed):
        """Deterministic stand-in for the hypothesis property (the
        dependency is optional): seeded random interleavings of client
        ops and adversarial mid-workload resizes vs a dict oracle."""
        import numpy as np
        _check_against_oracle(_plan_from_rng(
            np.random.default_rng(seed)))


def test_guarded_retry_never_loses_an_update():
    """The generation guard makes mutations conditional on the doubling
    epoch; a losing guard must RETRY (next round), never drop the op —
    pumping the resize between every single-op apply maximizes guard
    traffic."""
    m = elastic_map(4, max_doublings=2)
    m.apply([KVOp(INSERT, k, 1) for k in range(1, 9)])   # forces growth
    assert m.gen >= 1 or m.migrating
    for k in range(1, 9):
        (r,) = m.apply([KVOp(UPDATE, k, k * 7)])
        assert r.status == OK
    if m.migrating:
        m.ensure_room(max_steps=64)
    assert m.check_integrity() == {k: k * 7 for k in range(1, 9)}


# ---------------------------------------------------------------------------
# directory doubling: cross-substrate differential
# ---------------------------------------------------------------------------

def test_elastic_differential_growth_rounds_zero_skips(tmp_path):
    """Growth rounds — generation CAS, 4-word pump moves, guarded
    split-brain ops — run in kernel/durable lockstep and shadow-verify
    on the simulator with ZERO expressibility skips: at most one
    gen-guarded mutation compiles per round, so conservative and
    winner-blocking verdicts provably coincide."""
    kvops = [KVOp(INSERT, k, k * 2) for k in range(5, 100, 5)]
    kvops += [KVOp(UPDATE, 25, 7), KVOp(DELETE, 30), KVOp(READ, 25)]
    rep = run_struct_differential(kvops, n_buckets=4, max_doublings=3,
                                  durable_root=tmp_path / "diff",
                                  use_kernel=False)
    assert rep.agree, rep.summary()
    assert rep.sim_rounds_skipped == 0, rep.summary()
    assert rep.sim_rounds_checked > 5
    # growth really happened: more live keys than gen-0 capacity
    assert len(rep.items["kernel"]) > 4


# ---------------------------------------------------------------------------
# online key-range shard migration (service layer)
# ---------------------------------------------------------------------------

def _loaded_service(root, n_shards=3, n_buckets=32, chunk=4, **kw):
    svc = KVService(n_shards, backend="durable", n_buckets=n_buckets,
                    durable_root=root, migration_chunk=chunk, **kw)
    keys = {k: k * 10 for k in range(100, 200, 3)}
    res = svc.apply([KVOp(INSERT, k, v) for k, v in sorted(keys.items())])
    assert all(r.status == OK for r in res)
    return svc, keys


def test_migration_moves_range_and_survives_crash(tmp_path):
    svc, keys = _loaded_service(tmp_path / "m")
    before = svc.check_integrity()
    svc.migrate_range(100, 160, 2)
    assert svc.router.ranges == [(100, 160, 2)]
    assert svc.check_integrity() == before       # items are invariant
    assert svc.stats.migrations == 1 and svc.stats.keys_moved > 0
    assert svc.stats.mig_pause_waves and svc.stats.mig_pause_waves[0] >= 1
    for k in range(100, 160, 3):
        assert svc.router.shard_of_key(k) == 2
        assert svc.lookup(k) == keys[k]
    # the swing is durable: route table + record survive a crash
    svc2 = svc.crash()
    assert svc2.router.ranges == [(100, 160, 2)]
    assert svc2.check_integrity() == before


def test_migration_holds_and_releases_inflight_writes(tmp_path):
    """Writes covering the range (and all scans) park until the swing,
    then re-route and land on the destination — the copy can never
    diverge from a racing client write."""
    svc, keys = _loaded_service(tmp_path / "h")
    svc.start_migration(100, 160, 2)
    fut = svc.submit(KVOp(UPDATE, 103, 4242))    # in-range: held
    scan = svc.submit(KVOp(SCAN, 1))             # scans hold too
    out = svc.submit(KVOp(READ, 199))            # out of range: proceeds
    assert svc.pending_count == 3
    for _ in range(200):
        if fut.done and scan.done:
            break
        svc.step()
    assert fut.status == OK and scan.status == OK and out.status == OK
    assert scan.result.value == len(keys)        # no double-counted copy
    assert svc.lookup(103) == 4242
    assert svc.router.shard_of_key(103) == 2
    assert svc.check_integrity()[103] == 4242


def test_migration_crash_mid_copy_is_invisible(tmp_path):
    """A crash while the copy is in flight rolls the migration back:
    no route change, no residue, the MIGRATING record aborted."""
    svc, keys = _loaded_service(tmp_path / "c", chunk=2)
    before = svc.check_integrity()
    svc.start_migration(100, 160, 2)
    svc.step(); svc.step()                       # partial copy
    assert svc._migrations
    svc2 = svc.crash()
    assert svc2.router.ranges == []
    assert svc2.check_integrity() == before
    assert svc2.mig_log.pending() == []
    assert not svc2._migrations


def test_migration_crash_mid_swing_rolls_forward(tmp_path):
    """Once the ROUTED record persists, a crash anywhere in the rest of
    the swing recovers to the COMPLETED migration."""
    svc, keys = _loaded_service(tmp_path / "s")
    before = svc.check_integrity()
    # trap the decision log right after the ROUTED persist (decide is
    # persist 1 relative to now, mark_routed is persist 2)
    svc.mig_pool.crash_after = svc.mig_pool.persist_count + 2
    with pytest.raises(SimulatedCrash):
        svc.migrate_range(100, 160, 2)
    svc2 = svc.crash()
    assert svc2.router.ranges == [(100, 160, 2)]
    assert svc2.check_integrity() == before
    assert svc2.mig_log.pending() == []
    for k in range(100, 160, 3):
        assert svc2.lookup(k) == keys[k]


def test_migration_crash_sweep(tmp_path):
    """Tentpole acceptance: a crash trap on every pool (each shard WAL
    + the decision log) at every persist ordinal leaves the migration
    invisible or completed — never a torn route or a lost key."""
    load = {k: k * 10 for k in range(100, 150, 3)}
    swept = check_migration_crash_sweep(
        load, tmp_path, lo=100, hi=130, dst=2,
        n_shards=3, n_buckets=16, migration_chunk=3)
    assert swept >= 8


def test_remigration_trims_older_route_overrides(tmp_path):
    """A later migration may re-migrate part of an earlier one's range;
    the newest override must win and the older row is trimmed."""
    svc, keys = _loaded_service(tmp_path / "t")
    before = svc.check_integrity()
    svc.migrate_range(100, 160, 2)
    svc.migrate_range(130, 180, 0)
    assert svc.router.ranges == [(100, 130, 2), (130, 180, 0)]
    assert svc.check_integrity() == before
    for k, v in keys.items():
        assert svc.lookup(k) == v
    svc2 = svc.crash()                           # both swings durable
    assert svc2.router.ranges == [(100, 130, 2), (130, 180, 0)]
    assert svc2.check_integrity() == before


def test_migration_guards():
    r = ShardRouter(3, words_per_shard=64)
    r.set_range(10, 20, 1)
    r.set_range(15, 30, 2)                       # trims the first row
    assert r.ranges == [(10, 15, 1), (15, 30, 2)]
    assert r.shard_of_key(12) == 1 and r.shard_of_key(17) == 2
    r.clear_range(12, 18)                        # partial clear trims both
    assert r.ranges == [(10, 12, 1), (18, 30, 2)]
    with pytest.raises(ValueError):
        r.set_range(5, 5, 0)                     # empty range
    with pytest.raises(ValueError):
        r.set_range(0, 5, 9)                     # shard out of range


def test_migration_requires_decision_log_on_durable_shards(tmp_path):
    """Durable shards without a decision log would lose the route table
    on crash while keeping the moved keys — refused loudly."""
    backends = [DurableBackend(tmp_path / f"b{s}") for s in range(2)]
    svc = KVService(2, backend=backends, n_buckets=16)
    assert svc.mig_log is None
    with pytest.raises(ValueError, match="decision log"):
        svc.start_migration(1, 10, 0)


def test_overlapping_inflight_migration_rejected(tmp_path):
    svc, _ = _loaded_service(tmp_path / "o")
    svc.start_migration(100, 160, 2)
    with pytest.raises(RuntimeError, match="overlaps"):
        svc.start_migration(150, 170, 0)
    svc.drain()                                  # finish the first one


# ---------------------------------------------------------------------------
# acceptance: elastic service absorbs 4x its initial capacity
# ---------------------------------------------------------------------------

def test_service_absorbs_4x_initial_capacity(tmp_path):
    """The headline acceptance: a durable sharded service with elastic
    shards absorbs 4x its initial aggregate capacity with ZERO
    EXHAUSTED/FULL — every shard doubles its directory as it fills."""
    n_shards, n_buckets = 2, 8
    svc = KVService(n_shards, backend="durable", n_buckets=n_buckets,
                    max_doublings=4, durable_root=tmp_path / "x")
    n_keys = 4 * n_shards * n_buckets            # 64 keys vs 16 buckets
    res = svc.apply([KVOp(INSERT, k, k + 7)
                     for k in range(1, n_keys + 1)])
    statuses = [r.status for r in res]
    assert statuses.count(FULL) == 0 and statuses.count(EXHAUSTED) == 0
    assert all(s == OK for s in statuses)
    assert svc.check_integrity() == {k: k + 7
                                     for k in range(1, n_keys + 1)}
    assert all(st.gen >= 1 for st in svc.structs), \
        "every shard must have grown"
    # and the grown state is durable
    svc2 = svc.crash()
    assert svc2.check_integrity() == {k: k + 7
                                      for k in range(1, n_keys + 1)}
