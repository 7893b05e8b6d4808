"""The structures layer: lock-free persistent data structures built only
on the public ``repro.pmwcas`` surface, exercised on the kernel and
durable backends, shadow-verified on the simulator, and crash-swept on
both persistent substrates."""
import dataclasses

import numpy as np
import pytest

from repro.pmwcas import (DurableBackend, KernelBackend, MwCASOp,
                          ops_from_arrays, zipf_probs)
from repro.structures import (BzTreeIndex, DELETE, EXISTS, FULL,
                              FreeListAllocator, DoubleFree, HashMap,
                              INNER_BIT, INSERT,
                              KVOp, LEAF_DEAD, LeafNode, NODE_FROZEN,
                              NODE_FULL, NODE_OK, NOT_FOUND, OK,
                              OutOfRegions, READ, SCAN,
                              SortedNode, SplitError, TOMBSTONE, TornStructure,
                              UPDATE, WorkloadSpec, YCSB_A, YCSB_B, YCSB_C,
                              YCSB_E, check_durable_crash_sweep,
                              check_sim_crash_sweep, check_tree_crash_sweep,
                              compile_workload, conservative_verdicts,
                              kernel_round_arrays, load_phase, read_pointer,
                              run_struct_differential, run_workload,
                              shadow_batch, swap_pointer,
                              winner_blocking_verdicts)


def oracle_map(n_buckets=16, n_words=None, **kw):
    return HashMap(KernelBackend(n_words=n_words or 2 * n_buckets,
                                 use_kernel=False, **kw), n_buckets)


def oracle_tree(leaf_cap=4, root_cap=4, n_regions=6, **kw):
    n = BzTreeIndex.words_needed(leaf_cap, root_cap, n_regions)
    return BzTreeIndex(KernelBackend(n_words=n, use_kernel=False, **kw),
                       leaf_cap=leaf_cap, root_cap=root_cap,
                       n_regions=n_regions)


# ---------------------------------------------------------------------------
# operation model
# ---------------------------------------------------------------------------

def test_kvop_validation():
    with pytest.raises(ValueError):
        KVOp("bump", 1)                        # unknown kind
    with pytest.raises(ValueError):
        KVOp(INSERT, 0, 1)                     # key 0 is the EMPTY word
    with pytest.raises(ValueError):
        KVOp(INSERT, TOMBSTONE, 1)             # key collides with tombstone
    with pytest.raises(ValueError):
        KVOp(INSERT, 5, 0)                     # value 0 means "no value"
    KVOp(READ, 5)                              # reads need no value


# ---------------------------------------------------------------------------
# hash map: sequential semantics
# ---------------------------------------------------------------------------

def test_hashmap_insert_read_update_delete():
    h = oracle_map()
    assert all(h.apply([KVOp(INSERT, 5, 100), KVOp(INSERT, 7, 200)]))
    (r,) = h.apply([KVOp(READ, 5)])
    assert r.status == OK and r.value == 100
    (r,) = h.apply([KVOp(UPDATE, 5, 111)])
    assert r.status == OK and h.lookup(5) == 111
    (r,) = h.apply([KVOp(DELETE, 7)])
    assert r.status == OK
    (r,) = h.apply([KVOp(READ, 7)])
    assert r.status == NOT_FOUND and r.value is None
    assert h.check_integrity() == {5: 111}


def test_hashmap_miss_paths():
    h = oracle_map()
    assert h.apply([KVOp(UPDATE, 9, 1)])[0].status == NOT_FOUND
    assert h.apply([KVOp(DELETE, 9)])[0].status == NOT_FOUND
    assert all(h.apply([KVOp(INSERT, 9, 1)]))
    assert h.apply([KVOp(INSERT, 9, 2)])[0].status == EXISTS
    assert h.lookup(9) == 1                    # losing insert changed nothing


def test_hashmap_full_and_tombstone_reuse():
    h = oracle_map(n_buckets=4)
    keys = [3, 7, 11, 15]
    assert all(h.apply([KVOp(INSERT, k, k) for k in keys]))
    assert h.apply([KVOp(INSERT, 99, 1)])[0].status == FULL
    # delete one -> its tombstone is reused by the next insert
    assert all(h.apply([KVOp(DELETE, 7)]))
    assert all(h.apply([KVOp(INSERT, 99, 42)]))
    assert h.check_integrity() == {3: 3, 11: 11, 15: 15, 99: 42}
    # probe chains survive the tombstone: every key still findable
    for k in (3, 11, 15):
        assert h.lookup(k) == k


def test_hashmap_one_mwcas_per_mutation():
    """The tentpole claim: insert/update/delete compile to exactly one
    2-word MwCASOp over the bucket's (key word, value word) pair."""
    h = oracle_map()
    snap = h.snapshot()
    op = h.compile_op(KVOp(INSERT, 5, 100), snap)
    assert isinstance(op, MwCASOp) and op.k == 2
    (kw, vw) = op.addrs
    assert vw == kw + 1 and kw % 2 == 0        # adjacent pair, sorted
    h.apply([KVOp(INSERT, 5, 100)])
    snap = h.snapshot()
    upd = h.compile_op(KVOp(UPDATE, 5, 7), snap)
    assert upd.k == 2 and upd.targets[0].expected == upd.targets[0].desired
    dele = h.compile_op(KVOp(DELETE, 5), snap)
    assert dele.k == 2 and dele.targets[0].desired == TOMBSTONE
    assert dele.targets[1].desired == 0


# ---------------------------------------------------------------------------
# hash map: concurrent batches (the one-shot semantics)
# ---------------------------------------------------------------------------

def test_hashmap_concurrent_duplicate_insert():
    h = oracle_map()
    res = h.apply([KVOp(INSERT, 5, 100), KVOp(INSERT, 5, 300)])
    assert [r.status for r in res] == [OK, EXISTS]
    assert h.lookup(5) == 100                  # lower index won


def test_hashmap_concurrent_update_vs_delete():
    """Update guards the key word, delete moves it: the two ops conflict
    on both words, so exactly one commits per round — never a value
    written into a dead bucket."""
    for first, second in [(KVOp(UPDATE, 5, 9), KVOp(DELETE, 5)),
                          (KVOp(DELETE, 5), KVOp(UPDATE, 5, 9))]:
        h = oracle_map()
        h.apply([KVOp(INSERT, 5, 1)])
        res = h.apply([first, second])
        # lower index wins round 1; the loser recompiles: after a delete
        # the update misses, after an update the delete still applies
        assert res[0].status == OK
        assert res[1].status == (NOT_FOUND if first.kind == DELETE else OK)
        h.check_integrity()
        if first.kind == DELETE:
            assert h.lookup(5) is None
        else:
            assert h.lookup(5) is None         # update then delete


def test_hashmap_conflict_rounds_make_progress():
    """Keys forced into one probe neighborhood: every round commits at
    least one op (lowest index passes (a) and wins), so a batch of N
    finishes in <= N rounds."""
    h = oracle_map(n_buckets=4)
    keys = [3, 7, 11, 15]                      # all compete for 4 buckets
    res = h.apply([KVOp(INSERT, k, k) for k in keys])
    assert all(r.status == OK for r in res)
    assert h.rounds_run <= len(keys)
    assert h.check_integrity() == {k: k for k in keys}


def test_hashmap_reads_see_pre_batch_snapshot():
    """Ops inside one apply() are concurrent: a READ linearizes at the
    round snapshot and cannot observe a same-batch INSERT."""
    h = oracle_map()
    res = h.apply([KVOp(INSERT, 5, 100), KVOp(READ, 5)])
    assert res[0].status == OK and res[1].status == NOT_FOUND
    (r,) = h.apply([KVOp(READ, 5)])            # next batch sees it
    assert r.value == 100


def test_hashmap_scan_counts_live_keys():
    h = oracle_map()
    h.apply([KVOp(INSERT, k, k) for k in (2, 4, 6)])
    (r,) = h.apply([KVOp(SCAN, 4)])
    assert r.status == OK and r.value == 2     # keys >= 4: {4, 6}


def test_torn_structure_detected():
    """check_integrity flags a key word without its value word (a state
    no MwCAS history can produce — the detector the crash sweeps rely
    on)."""
    kb = KernelBackend(n_words=8, use_kernel=False)
    h = HashMap(kb, 4)
    (res,) = kb.execute([MwCASOp([(h.key_addr(1), 0, 77)])])   # torn write
    assert res.success
    with pytest.raises(TornStructure):
        h.check_integrity()


def test_hashmap_on_real_pallas_kernel():
    """One batch through the actual Pallas kernel path (interpret mode)."""
    h = HashMap(KernelBackend(n_words=16, use_kernel=True), 8)
    res = h.apply([KVOp(INSERT, 3, 30), KVOp(INSERT, 5, 50)])
    assert all(r.status == OK for r in res)
    assert h.check_integrity() == {3: 30, 5: 50}


# ---------------------------------------------------------------------------
# hash map: durability
# ---------------------------------------------------------------------------

def test_hashmap_durable_crash_recover_attach(tmp_path):
    db = DurableBackend(tmp_path)
    h = HashMap(db, 8)
    assert all(h.apply([KVOp(INSERT, 5, 100), KVOp(INSERT, 7, 200)]))
    assert all(h.apply([KVOp(UPDATE, 5, 111)]))
    h2 = HashMap(db.crash(), 8)                # fresh map over recovery
    assert h2.check_integrity() == {5: 111, 7: 200}


def test_hashmap_durable_crash_at_every_persist(tmp_path):
    """Acceptance: sweep the crash point across every persist of a whole
    insert/update/delete workload — recovery never shows a torn bucket
    pair or loses a committed effect."""
    ops = [KVOp(INSERT, 5, 100), KVOp(INSERT, 7, 200), KVOp(UPDATE, 5, 111),
           KVOp(DELETE, 7), KVOp(INSERT, 9, 300)]
    n = check_durable_crash_sweep(ops, n_buckets=8, root=tmp_path / "perop",
                                  group_commit=False)
    assert n > 20                              # the sweep covered the protocol
    # the coalesced path: one fence per op-round, so the clean run needs
    # far fewer persists — and every one of them is still swept
    g = check_durable_crash_sweep(ops, n_buckets=8, root=tmp_path / "group")
    assert 0 < g < n


# ---------------------------------------------------------------------------
# simulator shadow: crash sweep + verdict semantics
# ---------------------------------------------------------------------------

def test_sim_shadow_crash_sweep():
    """Acceptance: structure rounds shadowed into the cycle-accurate
    simulator survive micro-op-granularity crashes with per-op
    atomicity (driven through SimSession.crash_at)."""
    h = oracle_map(n_buckets=8)
    snap = h.snapshot()
    batch = [h.compile_op(KVOp(INSERT, k, 10 * k), snap)
             for k in (3, 5, 9, 12)]
    assert all(isinstance(op, MwCASOp) for op in batch)
    checked = check_sim_crash_sweep(batch, n_steps=1200)
    assert checked >= 10


def test_verdict_semantics_helpers():
    ops = [MwCASOp([(0, 0, 1), (1, 0, 1)]),    # wins
           MwCASOp([(1, 0, 1), (2, 0, 1)]),    # blocked by winner 0
           MwCASOp([(2, 0, 1), (3, 0, 1)])]    # chained: semantics split
    cons = conservative_verdicts(ops)
    wb = winner_blocking_verdicts(ops)
    assert cons.tolist() == [True, False, False]
    assert wb.tolist() == [True, False, True]


# ---------------------------------------------------------------------------
# the structure differential (acceptance)
# ---------------------------------------------------------------------------

def test_struct_differential_workload(tmp_path):
    """A conflict-bearing logical workload agrees across kernel and
    durable backends, and every shadow-expressible round's verdicts
    match the cycle-accurate simulator."""
    ops = ([KVOp(INSERT, k, 10 * k) for k in (3, 7, 11, 15)]     # same chain
           + [KVOp(INSERT, 3, 5), KVOp(INSERT, 21, 9)])
    rep = run_struct_differential(ops, n_buckets=4,
                                  durable_root=tmp_path, use_kernel=False)
    assert rep.agree, rep.summary()
    assert rep.sim_rounds_checked >= 1
    assert rep.statuses["kernel"].count(OK) == 4
    assert FULL in rep.statuses["kernel"]      # 5th distinct key can't fit


def test_struct_differential_mixed_mutations(tmp_path):
    ops = [KVOp(INSERT, 5, 100), KVOp(INSERT, 13, 200),
           KVOp(UPDATE, 5, 111), KVOp(DELETE, 13), KVOp(INSERT, 5, 1)]
    rep = run_struct_differential(ops, n_buckets=8,
                                  durable_root=tmp_path, use_kernel=False)
    assert rep.agree, rep.summary()
    assert rep.items["kernel"] == rep.items["durable"]


def test_struct_differential_native_sim_no_shadow_skips(tmp_path):
    """The sim replays REAL rounds natively — actual desired payloads
    (wide values, TOMBSTONE deletes) and mixed op kinds in one round —
    so no round is skipped for expressibility.  The only legitimate
    skip is a genuine semantic divergence (winner-blocking !=
    conservative verdicts), which this conflict-light workload avoids."""
    ops = ([KVOp(INSERT, k, (k << 8) | 1) for k in (2, 6, 10, 14)]
           + [KVOp(UPDATE, 2, 123456), KVOp(DELETE, 6),
              KVOp(INSERT, 18, 7), KVOp(DELETE, 10), KVOp(INSERT, 6, 999)])
    rep = run_struct_differential(ops, n_buckets=8, durable_root=tmp_path,
                                  use_kernel=False)
    assert rep.agree, rep.summary()
    assert rep.sim_rounds_checked >= 3
    assert rep.sim_rounds_skipped == 0, \
        "native replay must not skip rounds for expressibility"


def test_tree_differential_native_sim_mixed_width_rounds(tmp_path):
    """BzTree rounds mix op widths (meta-word CAS vs slot+meta inserts);
    the native replay pads each op privately and still verifies every
    round (no winner-blocking divergence in this workload)."""
    spec = WorkloadSpec(n_ops=16, n_keys=10, read=0.1, update=0.3,
                        insert=0.4, delete=0.2, seed=5, batch=4)
    ops = load_phase(spec) + compile_workload(spec)
    rep = run_struct_differential(ops, structure="bztree", leaf_cap=2,
                                  root_cap=8, n_regions=10,
                                  durable_root=tmp_path, use_kernel=False)
    assert rep.agree, rep.summary()
    assert rep.sim_rounds_checked >= 3
    assert rep.sim_rounds_skipped == 0
    assert rep.items["kernel"] == rep.items["durable"]


# ---------------------------------------------------------------------------
# BzTree-style sorted node
# ---------------------------------------------------------------------------

def test_node_insert_and_sorted_view():
    kb = KernelBackend(n_words=32, use_kernel=False)
    node = SortedNode(kb, base=2, capacity=6)
    for k in (42, 7, 19):
        assert node.insert(k) == NODE_OK
    assert node.raw_slots() == [42, 7, 19]     # arrival order on medium
    assert node.keys() == [7, 19, 42]          # sorted on read
    assert node.search(19) and not node.search(20)
    assert node.insert(7) == "exists"


def test_node_concurrent_inserts_serialize():
    """All pending inserts target the same (meta, slot) pair each round:
    exactly one winner per round, everyone lands eventually."""
    kb = KernelBackend(n_words=32, use_kernel=False)
    node = SortedNode(kb, base=0, capacity=6)
    sts = node.insert_batch([5, 9, 3, 7])
    assert sts == [NODE_OK] * 4
    assert node.keys() == [3, 5, 7, 9]
    assert node.count == 4


def test_node_full_freeze_split():
    kb = KernelBackend(n_words=64, use_kernel=False)
    node = SortedNode(kb, base=0, capacity=4)
    assert node.insert_batch([10, 30, 20, 40]) == [NODE_OK] * 4
    assert node.insert(50) == NODE_FULL
    left, right, sep = node.split(10, 20)      # fresh zeroed regions
    assert node.frozen and node.insert(60) == NODE_FROZEN
    assert left.keys() == [10, 20] and right.keys() == [30, 40]
    assert sep == 30
    assert not left.frozen and left.insert(15) == NODE_OK
    # atomic pointer install: readers swing from old to new in one CAS
    ptr = 50
    assert swap_pointer(kb, ptr, 0, left.base)
    assert read_pointer(kb, ptr) == left.base
    assert not swap_pointer(kb, ptr, 0, right.base)   # stale expected


def test_node_split_needs_zeroed_region():
    kb = KernelBackend(n_words=64, use_kernel=False)
    node = SortedNode(kb, base=0, capacity=4)
    node.insert_batch([1, 2, 3, 4])
    kb.execute([MwCASOp([(21, 0, 99)])])       # dirty word in right region
    with pytest.raises(SplitError):
        node.split(10, 20)


def test_node_on_durable_backend(tmp_path):
    db = DurableBackend(tmp_path)
    node = SortedNode(db, base=0, capacity=4)
    assert node.insert_batch([42, 7, 19, 23]) == [NODE_OK] * 4
    left, right, sep = node.split(10, 20)
    assert (left.keys(), right.keys(), sep) == ([7, 19], [23, 42], 23)
    # the split (one wide MwCAS) survives a crash as a unit
    db2 = db.crash()
    l2 = SortedNode(db2, 10, 4)
    r2 = SortedNode(db2, 20, 4)
    assert l2.keys() == [7, 19] and r2.keys() == [23, 42]
    assert SortedNode(db2, 0, 4).frozen        # original stays frozen


# ---------------------------------------------------------------------------
# free-list allocator
# ---------------------------------------------------------------------------

def test_freelist_alloc_free_roundtrip():
    fl = FreeListAllocator(16, region_base=100, region_words=8)
    grants = fl.alloc([2, 3, 0])
    assert grants[2] == [] and len(grants[0]) == 2 and len(grants[1]) == 3
    assert fl.n_free == 11
    assert fl.region(grants[0][0]) == 100 + grants[0][0] * 8
    fl.free(grants[1])
    assert fl.n_free == 14
    with pytest.raises(DoubleFree):
        fl.free(grants[1])                     # already back on the list


def test_freelist_exhaustion_is_typed():
    fl = FreeListAllocator(4)
    with pytest.raises(OutOfRegions) as exc:   # supply for one, not both
        fl.alloc([3, 3])
    # the exception names the starved request and keeps the grants the
    # same call already claimed (the caller owns them)
    assert exc.value.requests == (1,)
    served = [g for g in exc.value.grants if g is not None]
    assert len(served) == 1 and len(served[0]) == 3 and fl.n_free == 1
    # legacy mode: a None grant instead of the typed error
    fl2 = FreeListAllocator(4)
    grants = fl2.alloc([3, 3], on_exhausted="none")
    assert grants[0] is not None and grants[1] is None
    # raw contended reservations: lower batch index wins atomically
    fl3 = FreeListAllocator(8)
    ok = fl3.reserve([[0, 1], [1, 2], [3, 4]])
    assert ok == [True, False, True]
    assert fl3.n_free == 4                     # loser claimed nothing


# ---------------------------------------------------------------------------
# workload compiler
# ---------------------------------------------------------------------------

def test_workload_compile_deterministic_and_mixed():
    spec = WorkloadSpec(n_ops=200, n_keys=32, read=0.4, update=0.3,
                        insert=0.2, delete=0.1, seed=7)
    ops1, ops2 = compile_workload(spec), compile_workload(spec)
    assert ops1 == ops2                        # seeded determinism
    kinds = {op.kind for op in ops1}
    assert kinds == {READ, UPDATE, INSERT, DELETE}
    assert all(1 <= op.key <= spec.n_keys for op in ops1)


def test_workload_zipf_skew_concentrates_keys():
    uniform = compile_workload(WorkloadSpec(n_ops=400, n_keys=64, seed=1))
    skewed = compile_workload(WorkloadSpec(n_ops=400, n_keys=64, seed=1,
                                           alpha=1.2))
    def top_share(ops):
        _, counts = np.unique([op.key for op in ops], return_counts=True)
        return np.sort(counts)[-4:].sum() / len(ops)
    assert top_share(skewed) > top_share(uniform) + 0.1


def test_workload_invalid_mix_rejected():
    with pytest.raises(ValueError):
        WorkloadSpec(read=0.9, update=0.9, insert=0, delete=0, scan=0)


def test_workload_end_to_end_with_stats():
    spec = WorkloadSpec(n_ops=48, n_keys=16, seed=3, batch=8, alpha=0.9)
    h = oracle_map(n_buckets=32)
    h.apply(load_phase(spec))
    stats = run_workload(h, spec)
    assert stats.n_ops == 48
    assert sum(stats.by_status.values()) == 48
    assert stats.by_status.get(OK, 0) > 0
    assert stats.mwcas_won <= stats.mwcas_submitted
    h.check_integrity()


def test_kernel_round_arrays_wire_form():
    """The structure layer hands the Pallas kernel its native
    int32[B,K]-with-(-1)-padding wire format."""
    h = oracle_map(n_buckets=8)
    ops = [KVOp(INSERT, 3, 30), KVOp(INSERT, 5, 50), KVOp(READ, 3)]
    addr, exp, des, mwcas = kernel_round_arrays(h, ops)
    assert addr.shape == (2, 2)                # the READ compiles to no CAS
    assert addr.dtype == np.int32 and (addr >= 0).all()
    assert (des[:, 1] == [30, 50]).all()       # value words carried


# ---------------------------------------------------------------------------
# workload compiler: edge cases
# ---------------------------------------------------------------------------

def test_zipf_alpha_zero_is_uniform():
    p = zipf_probs(16, 0.0)
    assert p.shape == (16,)
    assert np.allclose(p, 1 / 16) and np.isclose(p.sum(), 1.0)


def test_zipf_single_key_universe():
    assert np.allclose(zipf_probs(1, 0.0), [1.0])
    assert np.allclose(zipf_probs(1, 1.2), [1.0])


def test_workload_single_key_universe_runs():
    """n_keys=1 degenerates every rank to the same key; the compiler and
    the retry loop must both survive it (alpha irrelevant)."""
    spec = WorkloadSpec(n_ops=12, n_keys=1, read=0.25, update=0.25,
                        insert=0.25, delete=0.25, seed=3, batch=4)
    ops = compile_workload(spec)
    assert {op.key for op in ops} == {1}
    h = oracle_map(n_buckets=2)
    stats = run_workload(h, spec, ops=ops)
    assert sum(stats.by_status.values()) == 12
    h.check_integrity()


def test_scan_heavy_mix_round_trips_kernel_arrays():
    """A YCSB-E (scan-heavy) round still produces a faithful kernel wire
    form: scans compile to no CAS, the inserts round-trip exactly
    through ops_to_arrays/ops_from_arrays."""
    spec = dataclasses.replace(YCSB_E, n_ops=32, n_keys=8, seed=5)
    ops = compile_workload(spec)
    kinds = {op.kind for op in ops}
    assert SCAN in kinds and INSERT in kinds
    tree = oracle_tree()
    addr, exp, des, mwcas = kernel_round_arrays(tree, ops)
    assert addr.shape[0] == len(mwcas) < len(ops)   # scans dropped
    assert all(op.k == 3 for op in mwcas)           # tree inserts: 3-word
    assert [op.targets for op in ops_from_arrays(addr, exp, des)] == \
        [op.targets for op in mwcas]


def test_shadow_batch_pads_mixed_widths():
    """Tree rounds mix 2- and 3-word ops; the shadow pads every op to
    one uniform width with private fresh words, leaving the conflict
    graph (and hence the verdicts) unchanged."""
    ops = [MwCASOp([(10, 0, 1), (11, 0, 2), (12, 0, 3)]),   # 3-word
           MwCASOp([(10, 0, 0), (13, 5, 6)]),               # shares 10
           MwCASOp([(14, 1, 2)])]                           # independent
    n, shadow = shadow_batch(ops)
    assert {op.k for op in shadow} == {3}                   # uniform now
    assert all(op.is_increment() for op in shadow)
    assert all(list(op.addrs) == sorted(op.addrs) for op in shadow)
    assert n == 5 + 3                                       # 5 real + 3 pad
    cons = conservative_verdicts(shadow)
    assert cons.tolist() == conservative_verdicts(ops).tolist()
    assert winner_blocking_verdicts(shadow).tolist() == \
        winner_blocking_verdicts(ops).tolist()


# ---------------------------------------------------------------------------
# multi-node BzTree index (the tentpole)
# ---------------------------------------------------------------------------

def test_tree_insert_read_update_delete():
    t = oracle_tree()
    assert all(t.apply([KVOp(INSERT, 5, 100), KVOp(INSERT, 7, 200)]))
    (r,) = t.apply([KVOp(READ, 5)])
    assert r.status == OK and r.value == 100
    assert t.apply([KVOp(INSERT, 5, 1)])[0].status == EXISTS
    (r,) = t.apply([KVOp(UPDATE, 5, 111)])
    assert r.status == OK and t.lookup(5) == 111
    (r,) = t.apply([KVOp(DELETE, 7)])
    assert r.status == OK
    assert t.apply([KVOp(READ, 7)])[0].status == NOT_FOUND
    assert t.apply([KVOp(UPDATE, 7, 1)])[0].status == NOT_FOUND
    assert t.check_integrity() == {5: 111}


def test_tree_insert_is_three_words_update_two():
    """The leaf op shapes: insert = (meta bump, key slot, value slot) in
    ONE 3-word MwCAS; update/delete = (meta guard, value word)."""
    t = oracle_tree()
    snap = t.snapshot()
    ins = t.compile_op(KVOp(INSERT, 5, 100), snap)
    assert isinstance(ins, MwCASOp) and ins.k == 3
    assert ins.targets[0].desired == ins.targets[0].expected + 1
    t.apply([KVOp(INSERT, 5, 100)])
    snap = t.snapshot()
    upd = t.compile_op(KVOp(UPDATE, 5, 7), snap)
    dele = t.compile_op(KVOp(DELETE, 5), snap)
    assert upd.k == 2 and upd.targets[0].expected == upd.targets[0].desired
    assert dele.k == 2 and dele.targets[1].desired == LEAF_DEAD


def test_tree_split_preserves_items_and_routing():
    t = oracle_tree(leaf_cap=4, root_cap=4, n_regions=6)
    keys = (50, 20, 80, 10, 60, 30, 70, 40, 90)
    res = t.apply([KVOp(INSERT, k, k) for k in keys])
    assert all(r.status == OK for r in res)
    assert t.splits >= 1 and t.root_count() >= 1
    assert t.check_integrity() == {k: k for k in keys}
    assert len(t.leaf_bases()) == t.root_count() + 1
    # every key routes to the leaf that holds it, and reads agree
    for k in keys:
        assert t.lookup(k) == k
    (r,) = t.apply([KVOp(SCAN, 50)])
    assert r.value == len([k for k in keys if k >= 50])


def test_tree_split_is_exactly_two_mwcas_rounds():
    """Split propagation = the wide materialize op, then the 2-word
    swing — with only the 1-word freeze in front (DESIGN §7/§12).  The
    FIRST split is a ROOT split: the wide op carries both half images,
    the new 1-entry root image and the pending word."""
    t = oracle_tree(leaf_cap=2, root_cap=4, n_regions=4)
    t.apply([KVOp(INSERT, 5, 50), KVOp(INSERT, 3, 30)])
    executed = []
    real_execute = t.backend.execute
    t.backend.execute = lambda ops: (executed.append(list(ops)),
                                     real_execute(ops))[1]
    (r,) = t.apply([KVOp(INSERT, 9, 90)])      # forces the root split
    assert r.status == OK and t.splits == 1 and t.root_splits == 1
    widths = [[op.k for op in batch] for batch in executed]
    # freeze (1-word), round 1 (ONE wide op: both 1-key half images of
    # meta+key+value, the 4-word new root image, the pending word),
    # round 2 (the 2-word super/pending swing), then the retried
    # insert (3-word)
    assert widths == [[1], [2 * 3 + 4 + 1], [2], [3]]


def test_tree_nonroot_split_is_exactly_two_mwcas_rounds():
    """Once an inner root exists, a leaf split is the original DESIGN §7
    protocol: wide materialize + invisible parent pre-entry, then the
    2-word count-bump/pointer-swing install."""
    t = oracle_tree(leaf_cap=2, root_cap=4, n_regions=4)
    t.apply([KVOp(INSERT, k, 10 * k) for k in (5, 3, 9)])   # root split
    assert t.root_count() == 1
    executed = []
    real_execute = t.backend.execute
    t.backend.execute = lambda ops: (executed.append(list(ops)),
                                     real_execute(ops))[1]
    (r,) = t.apply([KVOp(INSERT, 8, 80)])      # splits the right leaf
    assert r.status == OK and t.splits == 2 and t.root_splits == 1
    widths = [[op.k for op in batch] for batch in executed]
    # freeze, wide op (two 1-key half images + 2-word pre-entry),
    # 2-word install, retried insert
    assert widths == [[1], [2 * 3 + 2], [2], [3]]


def test_tree_pre_entry_invisible_until_install():
    """Round 1 pre-publishes the parent entry beyond the count: readers
    (and the integrity checker) still see the pre-split tree; the 2-word
    install is the linearization point."""
    t = oracle_tree(leaf_cap=2, root_cap=4, n_regions=6)
    t.apply([KVOp(INSERT, k, 10 * k) for k in (5, 3, 9)])   # inner root
    root = t.root_base()
    n = t.root_count()
    assert n == 1
    before = t.check_integrity()
    leaf = LeafNode(t.backend, t.leaf_bases()[1], 2)   # the full [5, 9] leaf
    (grant,) = t.allocator.alloc([1])
    pair = t.allocator.region(grant[0])
    sep = leaf.keys()[1]
    leaf.split(pair, pair + t.leaf_words,
               extra_targets=[(t.sep_addr(n), 0, sep),
                              (t.child_addr(n), 0, pair + t.leaf_words)])
    assert t.root_count() == n                 # entry not visible
    assert t.check_integrity() == before       # pre-split tree intact
    assert t._install(root, n, sep, pair + t.leaf_words)
    assert t.root_count() == n + 1             # now fully linked
    assert t.check_integrity() == before
    assert t.leaf_bases()[1:] == [pair, pair + t.leaf_words]


def test_tree_completes_pending_split_after_crash(tmp_path):
    """Crash between round 1 and the install leaves a frozen leaf and an
    invisible pre-entry; the next mutation completes the split from
    persisted state alone (left half derived from the pair region)."""
    db = DurableBackend(tmp_path)
    kw = dict(leaf_cap=2, root_cap=4, n_regions=6)
    t = BzTreeIndex(db, **kw)
    t.apply([KVOp(INSERT, k, 10 * k) for k in (5, 3, 9)])   # inner root
    n = t.root_count()
    leaf = LeafNode(db, t.leaf_bases()[1], 2)  # the full [5, 9] leaf
    (grant,) = t.allocator.alloc([1])
    pair = t.allocator.region(grant[0])
    sep = leaf.keys()[1]
    leaf.split(pair, pair + t.leaf_words,
               extra_targets=[(t.sep_addr(n), 0, sep),
                              (t.child_addr(n), 0, pair + t.leaf_words)])
    before = t.check_integrity()
    t2 = BzTreeIndex(db.crash(), **kw)         # attach over recovery
    assert t2.check_integrity() == before
    (r,) = t2.apply([KVOp(INSERT, 7, 70)])     # lands on the frozen leaf
    assert r.status == OK
    assert t2.root_count() == n + 1
    assert t2.check_integrity() == {**before, 7: 70}


def test_tree_completes_pending_root_split_after_crash(tmp_path):
    """Crash between root-split round 1 and the super swing leaves the
    pending word pointing at a fully materialized new root while super
    still routes to the frozen old root; the next mutation completes
    the swing from the pending word alone."""
    db = DurableBackend(tmp_path)
    kw = dict(leaf_cap=2, root_cap=4, n_regions=4)
    t = BzTreeIndex(db, **kw)
    t.apply([KVOp(INSERT, 5, 50), KVOp(INSERT, 3, 30)])
    # perform ROOT-SPLIT ROUND 1 by hand: both halves + new root image
    # + pending word in one wide MwCAS, then "crash" before the swing
    leaf = LeafNode(db, t.root_base(), 2)
    (grant,) = t.allocator.alloc([1])
    region = t.allocator.region(grant[0])
    left, right = region, region + t.leaf_words
    sep = leaf.keys()[1]
    new_root = region + 2 * t.leaf_words
    leaf.split(left, right, extra_targets=[
        (new_root, 0, 1 | INNER_BIT), (new_root + 1, 0, left),
        (new_root + 2, 0, sep), (new_root + 3, 0, right),
        (t.pending_addr, 0, new_root)])
    t2 = BzTreeIndex(db.crash(), **kw)         # attach over recovery
    assert t2.root_base() == t.root_base()     # swing not yet visible
    assert int(t2.backend.read(t2.pending_addr)) == new_root
    assert t2.check_integrity() == {3: 30, 5: 50}
    (r,) = t2.apply([KVOp(INSERT, 9, 90)])     # completes the swing
    assert r.status == OK
    assert t2.root_base() == new_root and t2.root_count() == 1
    assert int(t2.backend.read(t2.pending_addr)) == 0
    assert t2.check_integrity() == {3: 30, 5: 50, 9: 90}


def test_tree_delete_revive_and_consolidation():
    t = oracle_tree(leaf_cap=2, root_cap=2, n_regions=5)
    t.apply([KVOp(INSERT, 5, 50), KVOp(INSERT, 3, 30)])
    t.apply([KVOp(DELETE, 5)])
    # re-insert of a dead key revives the slot in place (no count bump)
    (r,) = t.apply([KVOp(INSERT, 5, 55)])
    assert r.status == OK and t.check_integrity() == {3: 30, 5: 55}
    assert len(t.leaf_bases()) == 1            # no split happened
    # a full leaf with < 2 live keys consolidates instead of splitting
    t.apply([KVOp(DELETE, 5), KVOp(DELETE, 3)])
    (r,) = t.apply([KVOp(INSERT, 7, 70)])
    assert r.status == OK
    assert t.consolidations == 1 and t.splits == 0
    assert t.check_integrity() == {7: 70}


def test_tree_region_exhaustion_does_not_wedge_leaf():
    """Regression: a failed split for lack of regions must not leave the
    leaf frozen — updates/deletes of its live keys keep working."""
    t = oracle_tree(leaf_cap=2, root_cap=4, n_regions=1)   # bootstrap
    t.apply([KVOp(INSERT, 5, 50), KVOp(INSERT, 3, 30)])    # eats region 0
    (r,) = t.apply([KVOp(INSERT, 9, 90)])
    assert r.status == FULL                    # nowhere to split into
    (r,) = t.apply([KVOp(UPDATE, 5, 55)])      # live keys stay mutable
    assert r.status == OK and t.lookup(5) == 55
    (r,) = t.apply([KVOp(DELETE, 3)])
    assert r.status == OK
    assert t.check_integrity() == {5: 55}


def test_tree_region_gc_reclaims_frozen_originals():
    """Split originals keep their regions claimed forever without GC;
    ``gc_regions`` frees every region no routing state references and
    the tree can grow again.  ``ensure_room`` now runs a GC pass
    itself before reporting OutOfRegions, so growth rides through
    region exhaustion without caller intervention."""
    t = oracle_tree(leaf_cap=2, root_cap=8, n_regions=3)
    # region 0: bootstrap leaf; the root split eats region 1, freezing
    # the original in region 0; the next leaf split eats region 2 —
    # after that every further split must reclaim residue via auto-GC
    res = t.apply([KVOp(INSERT, k, k) for k in (10, 20, 30, 40)])
    assert all(r.status == OK for r in res) and t.splits >= 1
    before = t.check_integrity()
    assert t.allocator.n_free == 0
    # no region left: the next split succeeds anyway because
    # ensure_room GCs the frozen originals first
    (r,) = t.apply([KVOp(INSERT, 50, 50)])
    assert r.status == OK
    assert t.check_integrity() == {**before, 50: 50}
    freed = t.gc_regions()
    assert freed >= 0                          # idempotent / re-runnable
    assert t.check_integrity() == {**before, 50: 50}


def test_tree_region_gc_protects_pending_split(tmp_path):
    """A crash between split rounds leaves a half-materialized pair
    referenced only by the INVISIBLE pre-entry; GC must keep it (the
    next mutation completes the split from exactly that state)."""
    kw = dict(leaf_cap=2, root_cap=4, n_regions=4)
    from repro import PMemPool, SimulatedCrash
    # find a crash point that lands between root-split round 1 and the
    # super swing: pending word set, super still on the frozen old
    # root.  The per-op protocol keeps the persist granularity this
    # hunt was calibrated for (group commit collapses it to one fence
    # per round)
    for crash_at in range(6, 200):
        pool = PMemPool(tmp_path / f"c{crash_at}",
                        crash_after_persists=crash_at)
        t = BzTreeIndex(DurableBackend(pool=pool, group_commit=False),
                        **kw)
        try:
            t.apply([KVOp(INSERT, 5, 50), KVOp(INSERT, 3, 30),
                     KVOp(INSERT, 9, 90)])
        except SimulatedCrash:
            t2 = BzTreeIndex(DurableBackend(pool=pool.crash(),
                                            group_commit=False), **kw)
            if t2.root_count() == 0 and \
                    int(t2.backend.read(t2.pending_addr)):
                break
    else:
        pytest.skip("no crash point hit the inter-round window")
    pending = t2.backend.read(t2.pending_addr)
    t2.gc_regions()
    # the pending new root (and its halves, sharing the region)
    # survived GC and the split still completes
    assert t2.backend.read(t2.pending_addr) == pending
    res = t2.apply([KVOp(INSERT, 7, 70)])
    assert res[0].status == OK
    items = t2.check_integrity()
    assert items[7] == 70 and t2.root_count() >= 1


def test_tree_gc_on_durable_crash_recover(tmp_path):
    kw = dict(leaf_cap=2, root_cap=4, n_regions=4)
    db = DurableBackend(tmp_path)
    t = BzTreeIndex(db, **kw)
    t.apply([KVOp(INSERT, k, k) for k in (5, 3, 9, 7)])
    assert t.splits >= 1
    before = t.check_integrity()
    db2 = db.crash()
    t2 = BzTreeIndex(db2, **kw)                # attach reclaims residue
    freed = t2.gc_regions()
    assert freed >= 1
    assert t2.check_integrity() == before
    # GC is durable: another crash/recover sees the same tree and the
    # same free regions
    t3 = BzTreeIndex(db2.crash(), **kw)
    assert t3.check_integrity() == before
    assert t3.allocator.n_free >= freed


def test_tree_root_split_unbounds_growth():
    """root_cap no longer caps the tree: a full inner root splits and
    the tree grows a level (the elastic scale-out tentpole).  FULL now
    only means region exhaustion."""
    t = oracle_tree(leaf_cap=2, root_cap=2, n_regions=32)
    keys = list(range(10, 130, 10))
    res = t.apply([KVOp(INSERT, k, k) for k in keys])
    # 2x the old hard ceiling (root_cap+1 leaves * leaf_cap = 6 keys)
    assert all(r.status == OK for r in res)
    assert t.root_splits >= 2 and t.height() >= 3
    assert t.check_integrity() == {k: k for k in keys}
    for k in keys:
        assert t.lookup(k) == k


def test_tree_on_real_pallas_kernel():
    """One splitting workload through the actual Pallas kernel path."""
    n = BzTreeIndex.words_needed(2, 4, 4)
    t = BzTreeIndex(KernelBackend(n_words=n, use_kernel=True),
                    leaf_cap=2, root_cap=4, n_regions=4)
    res = t.apply([KVOp(INSERT, k, 10 * k) for k in (5, 3, 9)])
    assert all(r.status == OK for r in res) and t.splits == 1
    assert t.check_integrity() == {5: 50, 3: 30, 9: 90}


def test_tree_durable_crash_recover_attach(tmp_path):
    kw = dict(leaf_cap=2, root_cap=4, n_regions=4)
    db = DurableBackend(tmp_path)
    t = BzTreeIndex(db, **kw)
    assert all(t.apply([KVOp(INSERT, k, k) for k in (5, 3, 9, 7)]))
    assert t.splits >= 1
    before = t.check_integrity()
    t2 = BzTreeIndex(db.crash(), **kw)
    assert t2.check_integrity() == before == {3: 3, 5: 5, 7: 7, 9: 9}


def test_tree_crash_sweep_through_split(tmp_path):
    """Acceptance: crash at EVERY persist point of a workload that
    drives a leaf split — recovery always shows the pre-split or the
    fully-linked post-split tree, never a torn one."""
    ops = [KVOp(INSERT, 5, 50), KVOp(INSERT, 3, 30), KVOp(INSERT, 9, 90),
           KVOp(UPDATE, 5, 55), KVOp(DELETE, 3)]
    n = check_tree_crash_sweep(ops, tmp_path / "perop", leaf_cap=2,
                               root_cap=4, n_regions=4, group_commit=False)
    assert n > 40                              # the sweep crossed the split
    g = check_tree_crash_sweep(ops, tmp_path / "group", leaf_cap=2,
                               root_cap=4, n_regions=4)
    assert 0 < g < n                           # coalesced path: fewer fences


def test_tree_sim_shadow_crash_sweep():
    """A compiled tree round (mixed widths) shadows into the
    cycle-accurate simulator crash sweep via the padded shadow batch."""
    t = oracle_tree(leaf_cap=4, root_cap=4, n_regions=6)
    t.apply([KVOp(INSERT, k, k) for k in (10, 20, 30)])
    snap = t.snapshot()
    batch = [t.compile_op(op, snap)
             for op in [KVOp(INSERT, 40, 4), KVOp(UPDATE, 10, 1),
                        KVOp(DELETE, 20)]]
    assert {op.k for op in batch} == {2, 3}    # genuinely mixed widths
    _, shadow = shadow_batch(batch)
    checked = check_sim_crash_sweep(shadow, n_steps=1500)
    assert checked >= 10


@pytest.mark.parametrize("mix", [YCSB_A, YCSB_B, YCSB_C, YCSB_E])
def test_tree_ycsb_differential(tmp_path, mix):
    """Acceptance: YCSB A/B/C plus the scan mix run against BzTreeIndex
    on kernel AND durable backends in lockstep, every client round
    shadow-verified on the simulator."""
    spec = dataclasses.replace(mix, n_ops=20, n_keys=10, seed=13, batch=4)
    ops = load_phase(spec) + compile_workload(spec)
    rep = run_struct_differential(ops, structure="bztree", leaf_cap=2,
                                  root_cap=8, n_regions=10,
                                  durable_root=tmp_path, use_kernel=False)
    assert rep.agree, rep.summary()
    assert rep.sim_rounds_checked >= 1
    assert rep.items["kernel"] == rep.items["durable"]


def test_tree_ycsb_workload_stats():
    """The generalized run_workload drives the tree end to end and the
    split counters surface in the stats vocabulary."""
    spec = WorkloadSpec(n_ops=48, n_keys=24, read=0.3, update=0.3,
                        insert=0.3, delete=0.05, scan=0.05, seed=7,
                        batch=8, alpha=0.9)
    t = oracle_tree(leaf_cap=4, root_cap=8, n_regions=10)
    t.apply(load_phase(spec))
    stats = run_workload(t, spec)
    assert stats.n_ops == 48 == sum(stats.by_status.values())
    assert stats.by_status.get(OK, 0) > 0
    assert t.splits >= 1
    t.check_integrity()
