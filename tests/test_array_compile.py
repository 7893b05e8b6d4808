"""The hash map's array compile (``HashMap.compile_batch``) and the row
batches it hands to round formation and the stacked dispatch.

- differential: a seeded queue compiled in one array pass gives, op by
  op, what ``compile_op`` gives against the same snapshot — answers,
  CAS targets, resize and full verdicts — over tombstones, probe runs
  that wrap the array's end, misses, repeated keys, full tables and the
  4- and 16-shard clustering of the multiplier the router shares;
- ``build_rounds`` forms the same rounds from bucket-pair rows in its
  first-occurrence pass as in its claim-set loop, takes the claim-set
  loop for any other rows (a doubling's guarded 3- and 5-word rows
  among them), and the stacked executor ships the arrays
  ``ops_to_arrays`` would;
- engagement: a hash-map service compiles every op in the array pass,
  on kernel and durable shards alike, SCANs and exhausted ops answered
  before it; each deployment it does not take (a doubling in flight,
  BzTree shards, a ``compile_op`` set on the instance) compiles none
  there, with the same answers;
- served equivalence: one seeded YCSB-A stream gives the same answers,
  table and defer counts on either path, and the array path builds no
  ``MwCASOp``.
"""
import dataclasses

import numpy as np
import pytest

import repro.service.executor as executor_mod
from repro.pmwcas import KernelBackend, MwCASOp, ops_to_arrays
from repro.service import (KVService, RowBatch, StackedKernelExecutor,
                           build_rounds)
from repro.structures import (DELETE, EXHAUSTED, EXISTS, FULL, HashMap,
                              INSERT, KVOp, NOT_FOUND, NeedsResize, OK, READ,
                              SCAN, StructResult, TOMBSTONE, UPDATE, YCSB_A,
                              compile_workload, key_shard)
from repro.structures.hashmap import MIG_BIT


def _verdicts_per_op(hm, ops, snap):
    """compile_op's verdict for each op, in a comparable form."""
    out = []
    for op in ops:
        c = hm.compile_op(op, snap)
        if isinstance(c, StructResult):
            out.append(("answer", c.status, c.value))
        elif isinstance(c, NeedsResize):
            out.append(("resize", c.gen))
        else:
            out.append(("row", tuple((t.addr, t.expected, t.desired)
                                     for t in c.targets)))
    return out


def _verdicts_batched(hm, ops, snap):
    """compile_batch's verdicts, in the same form, op by op."""
    cb = hm.compile_batch(ops, snap)
    out = [None] * len(ops)
    for i, status, value in zip(cb.answered.tolist(), cb.status, cb.value):
        out[i] = ("answer", status, value)
    for i in cb.resize.tolist():
        out[i] = ("resize", cb.gen)
    for r, i in enumerate(cb.rows.tolist()):
        out[i] = ("row", tuple(zip(cb.addr[r].tolist(), cb.exp[r].tolist(),
                                   cb.des[r].tolist())))
    assert None not in out                  # every op has one verdict
    assert cb.addr.dtype == np.int32 and cb.exp.dtype == np.uint32
    return out


def _table(rng, n_buckets, max_doublings=0, gen=0, *, fill=0.6,
           tomb=0.2, shards=1, shard=0):
    """A map and a snapshot of generation ``gen`` with live keys,
    tombstones and EMPTY buckets; with ``shards`` > 1 only keys routed to
    ``shard`` (the router's multiplier clusters their home buckets).
    Returns the map, the snapshot, the live keys and the key universe."""
    hm = HashMap(KernelBackend(n_words=8, use_kernel=False), n_buckets,
                 max_doublings=max_doublings)
    snap = np.zeros(hm.n_words, np.int64)
    if hm.hdr:
        snap[0] = gen
    off, cap = hm.arr_off(gen), hm.cap(gen)
    universe = [k for k in range(1, 4 * cap * shards + 2)
                if key_shard(k, shards) == shard]
    for k in rng.permutation(universe)[:int(fill * cap)].tolist():
        # insert through the probe, as the map would
        b = hm._home(k, gen)
        while snap[off + 2 * b] not in (0, TOMBSTONE):
            b = (b + 1) % cap
        snap[off + 2 * b] = k
        snap[off + 2 * b + 1] = int(rng.integers(1, 1 << 20))
    words = snap[off:off + 2 * cap:2]
    live = np.flatnonzero(words != 0)
    for b in rng.permutation(live)[:int(tomb * live.size)].tolist():
        snap[off + 2 * b], snap[off + 2 * b + 1] = TOMBSTONE, 0
    present = [int(k) for k in words if k not in (0, TOMBSTONE)]
    return hm, snap, present, universe


def _queue(rng, present, universe, n, kinds=(READ, INSERT, UPDATE, DELETE)):
    """``n`` ops, most on live keys and the rest anywhere in the universe
    (misses), with keys repeated inside the queue."""
    out = []
    for _ in range(n):
        pool = present if present and rng.random() < 0.7 else universe
        k = int(pool[int(rng.integers(len(pool)))])
        kind = kinds[int(rng.integers(len(kinds)))]
        value = int(rng.integers(1, 1 << 20)) if kind in (INSERT, UPDATE) \
            else 0
        out.append(KVOp(kind, k, value))
    return out


LAYOUTS = {
    # name: (n_buckets, max_doublings, gen, fill, tomb, shards)
    "sparse": (64, 0, 0, 0.3, 0.1, 1),
    "tombstones": (64, 0, 0, 0.7, 0.5, 1),
    "tiny_wraps": (5, 0, 0, 0.6, 0.3, 1),
    "full_no_room": (16, 0, 0, 1.0, 0.0, 1),
    "full_resize": (16, 2, 0, 1.0, 0.0, 1),
    "full_last_gen": (8, 1, 1, 1.0, 0.0, 1),
    "elastic_gen1": (16, 2, 1, 0.6, 0.2, 1),
    "cluster4": (256, 0, 0, 0.5, 0.1, 4),
    "cluster16": (256, 0, 0, 0.48, 0.1, 16),
}


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_compile_batch_matches_compile_op(layout, seed):
    n_buckets, md, gen, fill, tomb, shards = LAYOUTS[layout]
    rng = np.random.default_rng([seed, len(layout)])
    hm, snap, present, universe = _table(
        rng, n_buckets, md, gen, fill=fill, tomb=tomb, shards=shards,
        shard=seed % shards)
    ops = _queue(rng, present, universe, 4 * n_buckets)
    want = _verdicts_per_op(hm, ops, snap)
    assert _verdicts_batched(hm, ops, snap) == want
    kinds = {v[0] if v[0] != "answer" else v[1] for v in want}
    assert {OK, NOT_FOUND, EXISTS} <= kinds
    if layout.startswith("full"):
        assert ("resize" in kinds) == (layout == "full_resize")
        assert (FULL in kinds) == (layout != "full_resize")
    else:
        assert "row" in kinds


def test_compile_batch_probes_across_the_arrays_end():
    """A run that starts in the last bucket and continues at bucket 0:
    the key is found there, and the writable bucket is the first
    tombstone on the way, before the end."""
    hm = HashMap(KernelBackend(n_words=8, use_kernel=False), 8)
    snap = np.zeros(hm.n_words, np.int64)
    home7 = [k for k in range(1, 1000) if hm._home(k) == 7]
    key, other, missing = home7[:3]
    snap[2 * 7] = TOMBSTONE                     # bucket 7: tombstone
    snap[0], snap[1] = other, 5                 # bucket 0: other key
    snap[2], snap[3] = key, 9                   # bucket 1: the key
    ops = [KVOp(READ, key), KVOp(INSERT, missing, 3), KVOp(UPDATE, key, 4),
           KVOp(DELETE, other), KVOp(INSERT, key, 2)]
    got = _verdicts_batched(hm, ops, snap)
    assert got == _verdicts_per_op(hm, ops, snap)
    assert got == [("answer", OK, 9),
                   ("row", ((14, TOMBSTONE, missing), (15, 0, 3))),
                   ("row", ((2, key, key), (3, 9, 4))),
                   ("row", ((0, other, TOMBSTONE), (1, 5, 0))),
                   ("answer", EXISTS, 9)]


def test_compile_batch_leaves_scans_and_doublings_to_compile_op():
    hm = HashMap(KernelBackend(n_words=8, use_kernel=False), 8,
                 max_doublings=1)
    snap = np.zeros(hm.n_words, np.int64)
    with pytest.raises(ValueError, match="SCAN"):
        hm.compile_batch([KVOp(READ, 1), KVOp(SCAN, 1)], snap)
    snap[0] = 1 << 30                           # MIG_BIT: in flight
    with pytest.raises(ValueError, match="doubling"):
        hm.compile_batch([KVOp(READ, 1)], snap)


# ---------------------------------------------------------------------------
# round formation and the stacked arrays
# ---------------------------------------------------------------------------

def _row_and_op_queues(hm, ops, snap):
    """The same compiled writes as the array pass's rows and as the
    rows packed from ``compile_op``'s MwCASOps (returned too); the
    entries are the queue positions."""
    cb = hm.compile_batch(ops, snap)
    entries = cb.rows.tolist()
    per_op = [hm.compile_op(ops[i], snap) for i in entries]
    assert all(isinstance(op, MwCASOp) for op in per_op)
    return (RowBatch(entries, cb.addr, cb.exp, cb.des),
            RowBatch.pack(list(entries), per_op), per_op)


def _padded(rows):
    """The same rows with one more column of padding: no longer bucket
    pairs, so round formation takes its claim-set loop."""
    pad = np.full((len(rows), 1), -1, np.int32)
    zero = np.zeros((len(rows), 1), np.uint32)
    return RowBatch(list(rows.entries), np.hstack([rows.addr, pad]),
                    np.hstack([rows.exp, zero]), np.hstack([rows.des, zero]))


@pytest.mark.parametrize("round_cap", [1, 3, 8, 64])
@pytest.mark.parametrize("seed", range(3))
def test_build_rounds_on_rows_matches_mwcas_ops(seed, round_cap):
    """The first-occurrence pass over the array pass's pair rows forms
    the rounds, leftovers and counts the claim-set loop forms over the
    same rows, and the rows are those of ``compile_op``'s MwCASOps."""
    rng = np.random.default_rng(seed)
    hm, snap, present, universe = _table(rng, 32, fill=0.5, tomb=0.2)
    # few keys, many ops: repeated buckets, so conflict-defers
    ops = _queue(rng, present[:10], universe[:14], 60)
    rows, packed, _ops = _row_and_op_queues(hm, ops, snap)
    assert np.array_equal(rows.addr, packed.addr)
    assert np.array_equal(rows.exp, packed.exp)
    assert np.array_equal(rows.des, packed.des)
    r1, l1, d1, o1 = build_rounds({0: rows, 1: rows}, round_cap)
    r2, l2, d2, o2 = build_rounds({0: _padded(rows), 1: _padded(rows)},
                                  round_cap)
    for s in (0, 1):
        assert r1[s].entries == r2[s].entries
        assert l1.get(s, []) == l2.get(s, [])
        assert np.array_equal(r1[s].addr, r2[s].addr[:, :2])
        assert np.array_equal(r1[s].exp, r2[s].exp[:, :2])
        assert np.array_equal(r1[s].des, r2[s].des[:, :2])
    assert d1 == d2 and o1 == o2
    assert d1[0] > 0 and (round_cap > 3 or o1[0] > 0)


@pytest.mark.parametrize("addr,sched,later,defers", [
    ([[0, 1, 2], [2, 3, 4], [5, 6, 7]], [0, 2], [1], 1),
    ([[0, 2], [2, 4], [1, 3]], [0, 2], [1], 1),
    ([[0, 1], [1, 2], [3, 4]], [0, 2], [1], 1),
    ([[0, 1], [3, -1], [3, 4], [5, 6]], [0, 1, 3], [2], 1),
], ids=["three", "gap", "parity", "padding"])
def test_build_rounds_refuses_rows_that_are_not_bucket_pairs(
        addr, sched, later, defers):
    """Rows that are not one generation's bucket pairs (three targets,
    a gap, two parities, padding) take the claim-set loop over their
    real addresses; a first-occurrence pass would schedule every row of
    these, as their first addresses differ."""
    addr = np.asarray(addr, np.int32)
    rows = RowBatch(list(range(len(addr))), addr,
                    np.zeros(addr.shape, np.uint32),
                    np.zeros(addr.shape, np.uint32))
    rounds, leftovers, d, o = build_rounds({0: rows}, 4)
    assert rounds[0].entries == sched
    assert np.array_equal(rounds[0].addr, addr[sched])
    assert leftovers[0] == later
    assert d == {0: defers} and o == {0: 0}


def test_a_doublings_guarded_rows_form_claim_set_rounds():
    """While a doubling is in flight every write carries the generation
    word as a guard: an insert is 3 words, an update of a key not yet
    moved a 5-word move-on-write.  Packed at K = 5 with padding, the
    rows share the guard, so each round takes one (the claim-set loop),
    as ``HashMap.apply`` serializes guarded ops; the rest defer."""
    hm = HashMap(KernelBackend(n_words=64, use_kernel=False), 8,
                 max_doublings=1)
    assert all(r.status == OK
               for r in hm.apply([KVOp(INSERT, k, k) for k in range(1, 6)]))
    assert hm.begin_resize()
    snap = hm.snapshot()
    assert snap[0] & MIG_BIT
    ops = [KVOp(UPDATE, 2, 20), KVOp(INSERT, 40, 4), KVOp(DELETE, 3),
           KVOp(UPDATE, 4, 44)]
    compiled = [hm.compile_op(op, snap) for op in ops]
    assert [op.k for op in compiled] == [5, 3, 3, 5]
    rows = RowBatch.pack(list(range(4)), compiled)
    assert rows.addr.shape == (4, 5) and (rows.addr[1:3, 3:] == -1).all()
    assert (rows.addr[:, 0] == hm.gen_addr).all()
    rounds, leftovers, d, o = build_rounds({0: rows}, 4)
    assert rounds[0].entries == [0] and leftovers[0] == [1, 2, 3]
    assert d == {0: 3} and o == {0: 0}
    assert rounds[0].to_ops() == compiled[:1]
    # served: a wave with a doubling in flight executes one op
    svc = KVService(1, n_buckets=8, max_doublings=1, round_cap=4,
                    use_kernel=False)
    svc.apply([KVOp(INSERT, k, k) for k in range(1, 6)])
    assert svc.structs[0].begin_resize()
    svc.reset_stats()
    futs = svc.submit_many(ops)
    svc.step()
    assert svc.structs[0].migrating
    sh = svc.stats.shards[0]
    assert (sh.rounds, sh.ops_executed, sh.defers) == (1, 1, 3)
    svc.drain()
    assert [f.result.status for f in futs] == [OK] * 4
    assert svc.items() == {1: 1, 2: 20, 4: 44, 5: 5, 40: 4}


@pytest.mark.parametrize("round_cap", [8, None])
def test_stacked_executor_ships_rows_as_ops_to_arrays(round_cap,
                                                      monkeypatch):
    """One dispatch from RowBatch rounds and one from the MwCASOp rounds
    they stand for: the same padded [S, B, K] arrays, each shard's rows
    what ``ops_to_arrays`` packs, the same padding bytes, verdicts and
    tables; shard 1 has no round and rides along as padding."""
    seen = []
    real = executor_mod.pmwcas_apply_stacked

    def spy(words, addr, exp, des, **kw):
        seen.append([np.asarray(x) for x in (addr, exp, des)])
        return real(words, addr, exp, des, **kw)

    monkeypatch.setattr(executor_mod, "pmwcas_apply_stacked", spy)
    hm, snap, present, universe = _table(np.random.default_rng(3), 16,
                                         fill=0.5, tomb=0.2)
    queues = {}
    for s in (0, 2):
        ops = _queue(np.random.default_rng(s), present, universe, 12,
                     kinds=(UPDATE, INSERT, DELETE))
        rows, _packed, per_op = _row_and_op_queues(hm, ops, snap)
        r1, _l, _d, _o = build_rounds({s: rows}, 8)
        sched = [per_op[rows.entries.index(e)] for e in r1[s]]
        queues[s] = (r1[s], RowBatch.pack(r1[s].entries, sched), sched)
    results = []
    for side in (0, 1):
        backends = [KernelBackend(values=snap.astype(np.uint32),
                                  use_kernel=False) for _ in range(3)]
        ex = StackedKernelExecutor(round_cap)
        verdicts = ex.execute(backends, {s: q[side]
                                         for s, q in queues.items()})
        results.append((verdicts, ex.stats.bytes_padded,
                        [b.values().tolist() for b in backends]))
    assert results[0] == results[1]
    assert results[0][1] > 0
    rows_seen, ops_seen = seen
    for x, y in zip(rows_seen, ops_seen):
        assert x.dtype == y.dtype and np.array_equal(x, y)
    addr, exp, des = rows_seen
    assert addr.shape == (3, 8, 2)
    assert (addr[1] == -1).all() and not exp[1].any() and not des[1].any()
    for s, (_rows, _packed, ops) in queues.items():
        a, e, d = ops_to_arrays(ops)
        n = len(ops)
        assert np.array_equal(addr[s, :n], a) and (addr[s, n:] == -1).all()
        assert np.array_equal(exp[s, :n], e) and not exp[s, n:].any()
        assert np.array_equal(des[s, :n], d) and not des[s, n:].any()


# ---------------------------------------------------------------------------
# engagement: which deployments the array pass takes
# ---------------------------------------------------------------------------

def _mixed_answers(svc, keys, seed=0):
    rng = np.random.default_rng(seed)
    ops = []
    for k in rng.choice(keys, size=40).tolist():
        r = rng.random()
        ops.append(KVOp(READ, k) if r < 0.4 else
                   KVOp(UPDATE, k, int(rng.integers(1, 1 << 20)))
                   if r < 0.8 else KVOp(DELETE, k) if r < 0.9 else
                   KVOp(INSERT, k + 1000, 5))
    svc.reset_stats()
    out = [(r.status, r.value) for r in svc.apply(ops)]
    return out, svc.stats.ops_compiled, svc.stats.ops_compiled_batched


def _wrap_compile_op(svc):
    for struct in svc.structs:
        compile_op = struct.compile_op
        struct.compile_op = (lambda op, snap, compile_op=compile_op:
                             compile_op(op, snap))


def _hashmap_service(backend="kernel", n_shards=4, **kw):
    svc = KVService(n_shards, backend=backend, n_buckets=32, round_cap=4,
                    use_kernel=False, **kw)
    svc.apply([KVOp(INSERT, k, k) for k in range(1, 41)])
    return svc


@pytest.mark.parametrize("n_shards", [1, 4])
def test_array_pass_compiles_every_op_of_a_kernel_service(n_shards):
    """4 shards or a lone kernel shard, each stacked: the same answers
    and table as the per-op path."""
    svc = _hashmap_service(n_shards=n_shards)
    got, compiled, batched = _mixed_answers(svc, list(range(1, 41)))
    assert svc.executor.stats.dispatches > 0
    assert svc.executor.stats.serial_rounds == 0
    assert compiled >= 40 and batched == compiled
    ref = _hashmap_service(n_shards=n_shards)
    _wrap_compile_op(ref)
    want, compiled, batched = _mixed_answers(ref, list(range(1, 41)))
    assert batched == 0 and compiled >= 40
    assert got == want and svc.items() == ref.items()


@pytest.mark.parametrize("aside", ["scan", "exhausted"])
def test_a_wave_with_a_scan_or_an_exhausted_op_compiles_per_op(aside):
    """A SCAN, or an op past ``max_op_rounds``, is answered before the
    compile, and the rest of its shard's queue compiles in the array
    pass that same wave, with the answers of the per-op path."""
    keys = [k for k in range(1, 41) if key_shard(k, 4) == 0]
    ops = [KVOp(UPDATE, keys[0], 7), KVOp(UPDATE, keys[1], 8),
           KVOp(READ, keys[0])]
    ops.insert(1, KVOp(SCAN, keys[3]) if aside == "scan"
               else KVOp(UPDATE, keys[2], 9))
    answers = []
    for per_op in (False, True):
        svc = _hashmap_service()
        if per_op:
            _wrap_compile_op(svc)
        svc.max_op_rounds = 2
        svc.reset_stats()
        futs = svc.submit_many(ops)
        if aside == "exhausted":
            svc._queues[0][1].attempts = 3       # past max_op_rounds
        svc.step()
        # the aside op is answered, not compiled; the rest compile
        assert svc.stats.ops_compiled == 3
        assert svc.stats.ops_compiled_batched == (0 if per_op else 3)
        nxt = svc.apply([KVOp(READ, keys[1])])[0]
        assert svc.stats.ops_compiled_batched == (0 if per_op else 4)
        answers.append([(f.result.status, f.result.value) for f in futs]
                       + [(nxt.status, nxt.value)])
    assert answers[0] == answers[1]
    assert answers[0][1] == ((OK, 41 - keys[3]) if aside == "scan"
                             else (EXHAUSTED, None))
    assert answers[0][2] == (OK, None) and answers[0][4] == (OK, 8)


def test_array_pass_skips_a_doubling_in_flight():
    """While a shard's doubling is in flight every op of it compiles per
    op against the split-brain table; once it has finished, the array
    pass takes the map again."""
    keys = [k for k in range(1, 200) if key_shard(k, 2) == 0][:12]
    ops = [KVOp(UPDATE, keys[0], 70), KVOp(READ, keys[0]),
           KVOp(INSERT, keys[-1] + 1000, 5), KVOp(DELETE, keys[1])]
    out = []
    for per_op in (False, True):
        svc = KVService(2, n_buckets=16, max_doublings=1, round_cap=4,
                        use_kernel=False)
        svc.apply([KVOp(INSERT, k, k) for k in keys])
        assert svc.structs[0].begin_resize()
        if per_op:
            _wrap_compile_op(svc)
        svc.reset_stats()
        # one op a wave: each wave's pump moves two keys of twelve
        answers = [svc.apply([op])[0] for op in ops]
        assert svc.structs[0].migrating
        assert svc.stats.ops_compiled == len(ops)
        assert svc.stats.ops_compiled_batched == 0
        out.append(([(r.status, r.value) for r in answers], svc.items()))
    assert out[0] == out[1]
    assert out[0][0][:2] == [(OK, None), (OK, 70)]
    svc = KVService(2, n_buckets=16, max_doublings=1, round_cap=4,
                    use_kernel=False)
    svc.apply([KVOp(INSERT, k, k) for k in keys])
    assert svc.structs[0].ensure_room()
    svc.reset_stats()
    assert svc.apply([KVOp(READ, keys[2])])[0].value == keys[2]
    assert svc.stats.ops_compiled_batched == svc.stats.ops_compiled == 1


def test_array_pass_hands_a_full_generation_to_the_doubling():
    """Inserts that find no writable bucket leave the array pass as
    NeedsResize: the shard publishes the doubling, they recompile per op
    against the split-brain table, and the answers and table are those
    of the per-op path."""
    keys = list(range(1, 25))
    out = []
    for per_op in (False, True):
        svc = KVService(2, n_buckets=4, max_doublings=2, round_cap=4,
                        use_kernel=False)
        if per_op:
            _wrap_compile_op(svc)
        res = svc.apply([KVOp(INSERT, k, 100 + k) for k in keys])
        assert all(st.gen >= 1 for st in svc.structs)
        batched = svc.stats.ops_compiled_batched
        assert (batched > 0) != per_op
        out.append(([(r.status, r.value) for r in res], svc.items()))
    assert out[0] == out[1]
    assert out[0][1] == {k: 100 + k for k in keys}


def test_array_pass_skips_durable_shards(tmp_path):
    """Durable hash-map shards take the array pass too; their serial
    rounds build the MwCASOps from the rows, with the answers and table
    of the per-op path."""
    svc = _hashmap_service("durable", durable_root=tmp_path / "rows")
    got, compiled, batched = _mixed_answers(svc, list(range(1, 41)))
    assert compiled >= 40 and batched == compiled
    assert svc.executor.stats.serial_rounds > 0
    assert svc.executor.stats.dispatches == 0
    ref = _hashmap_service("durable", durable_root=tmp_path / "ops")
    _wrap_compile_op(ref)
    want, compiled, batched = _mixed_answers(ref, list(range(1, 41)))
    assert batched == 0 and compiled >= 40
    assert got == want and svc.items() == ref.items()


def test_array_pass_skips_bztree_shards():
    svc = KVService(2, structure="bztree", leaf_cap=4, root_cap=8,
                    n_regions=8, round_cap=4, use_kernel=False)
    svc.reset_stats()
    svc.apply([KVOp(INSERT, k, k) for k in range(1, 13)])
    res = svc.apply([KVOp(READ, 5)])
    assert res[0].status == OK and res[0].value == 5
    assert svc.stats.ops_compiled >= 13
    assert svc.stats.ops_compiled_batched == 0


def test_array_pass_skips_a_compile_op_set_on_the_instance():
    """What ``bench.faults.altered_answer`` does: the wrapper sees every
    op, so the fault it plants shows in the answers."""
    svc = _hashmap_service()
    for struct in svc.structs:
        compile_op = struct.compile_op

        def altered(op, snap, compile_op=compile_op):
            out = compile_op(op, snap)
            if op.kind == READ and isinstance(out, StructResult) \
                    and out.value is not None:
                out = dataclasses.replace(out, value=out.value + 1)
            return out

        struct.compile_op = altered
    svc.reset_stats()
    assert svc.apply([KVOp(READ, 7)])[0].value == 8
    assert svc.stats.ops_compiled == 1
    assert svc.stats.ops_compiled_batched == 0


# ---------------------------------------------------------------------------
# served equivalence
# ---------------------------------------------------------------------------

def _served(n_shards, per_op, monkeypatch=None):
    svc = KVService(n_shards, n_buckets=96, round_cap=4, use_kernel=False)
    svc.apply([KVOp(INSERT, k, k) for k in range(1, 161)])
    if per_op:
        _wrap_compile_op(svc)
    svc.reset_stats()
    made = []
    if monkeypatch is not None:
        init = MwCASOp.__init__

        def counted(self, targets):
            made.append(1)
            init(self, targets)

        monkeypatch.setattr(MwCASOp, "__init__", counted)
    ops = compile_workload(dataclasses.replace(
        YCSB_A, n_ops=1200, n_keys=160, alpha=0.9, seed=n_shards))
    futs = []
    # 8 ops a shard submitted between waves
    for op in ops:
        futs.append(svc.submit(op))
        if len(futs) % (8 * n_shards) == 0:
            svc.step()
    svc.drain()
    if monkeypatch is not None:
        monkeypatch.undo()
    st = svc.stats
    return dict(answers=[(f.result.status, f.result.value, f.done_step)
                         for f in futs],
                items=svc.items(),
                defers=[sh.defers for sh in st.shards],
                overflows=[sh.overflows for sh in st.shards],
                rounds=[sh.rounds for sh in st.shards],
                compiled=st.ops_compiled, batched=st.ops_compiled_batched,
                made=len(made))


@pytest.mark.parametrize("n_shards", [4, 16])
def test_served_stream_is_the_same_on_either_path(n_shards, monkeypatch):
    rows = _served(n_shards, per_op=False, monkeypatch=monkeypatch)
    ops = _served(n_shards, per_op=True)
    assert rows["made"] == 0                 # no MwCASOp on the array path
    assert rows["batched"] == rows["compiled"] > 0 and ops["batched"] == 0
    for name in ("answers", "items", "defers", "overflows", "rounds",
                 "compiled"):
        assert rows[name] == ops[name], name
    assert sum(rows["defers"]) > 0 and sum(rows["overflows"]) > 0
    assert {s for s, _v, _w in rows["answers"]} == {OK}
