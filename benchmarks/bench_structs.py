"""Structure-layer benchmarks: the paper's "productive uses of PMwCAS"
made measurable.  YCSB-style mixes drive the lock-free hash map on the
kernel backend (wall ops/s, retry rate) and the durable backend
(persists per op); one compiled round is shadowed through the
cycle-accurate simulator so every variant also reports modeled CAS/op
and flush/op — the same cost vocabulary as the paper-figure benches.
BzTree node insert/split and free-list reservation round out the
structure suite."""
from __future__ import annotations

import dataclasses
import time

from repro.pmwcas import (CNT_CAS, CNT_FLUSH, DurableBackend, KernelBackend,
                          OURS, SimBackend)
from repro.structures import (BzTreeIndex, FreeListAllocator, HashMap,
                              NODE_OK, SortedNode, WorkloadSpec, YCSB_A,
                              YCSB_B, YCSB_C, YCSB_E, compile_workload,
                              load_phase, run_workload, shadow_batch)

from .common import emit


def _shadow_costs(hmap):
    """Modeled CAS/flush per op of the last executed rounds (sim shadow)."""
    cas = flush = n = 0
    # cap the shadow at two rounds: each distinct (B, words) shape pays
    # one engine compile, and two rounds already average the cost
    for trace in hmap.last_history[:2]:
        n_words, shadow = shadow_batch(trace.ops)
        sim = SimBackend(n_words, algorithm=OURS)
        sim.execute(shadow)
        cas += float(sim.counters[:, CNT_CAS].sum())
        flush += float(sim.counters[:, CNT_FLUSH].sum())
        n += len(shadow)
    return (cas / n, flush / n) if n else (0.0, 0.0)


def _loaded_map(backend, spec: WorkloadSpec) -> HashMap:
    hmap = HashMap(backend, spec.n_keys * 2)
    hmap.apply(load_phase(spec))
    return hmap


def _hashmap_cell(name: str, hmap: HashMap, spec: WorkloadSpec, *,
                  shadow: bool = False):
    ops = compile_workload(spec)
    t0 = time.time()
    stats = run_workload(hmap, spec, ops=ops)
    dt = time.time() - t0
    hmap.check_integrity()
    derived = (f"ops_per_s={stats.n_ops / dt:.0f};"
               f"ok={stats.by_status.get('ok', 0)};"
               f"rounds={stats.rounds};"
               f"retries_per_op={stats.retries_per_op:.3f};"
               f"cas_ops_per_op={stats.cas_ops_per_op:.3f}")
    if shadow:
        cas, flush = _shadow_costs(hmap)
        derived += f";cas_per_op={cas:.2f};flush_per_op={flush:.2f}"
    emit(f"{name},{dt / stats.n_ops * 1e6:.1f},{derived}")
    return stats


def run(quick: bool = False):
    n_ops, n_keys = (48, 16) if quick else (256, 64)
    base = WorkloadSpec(n_ops=n_ops, n_keys=n_keys, batch=8, seed=11)
    mixes = [
        ("ycsb_a", dataclasses.replace(YCSB_A, n_ops=n_ops, n_keys=n_keys,
                                       batch=8, seed=11)),
        ("ycsb_b", dataclasses.replace(YCSB_B, n_ops=n_ops, n_keys=n_keys,
                                       batch=8, seed=11)),
        ("mixed", base),
    ]
    skews = (0.0,) if quick else (0.0, 0.99)

    # -- hash map on the kernel backend (jnp oracle; use_kernel on TPU) ------
    for mix_name, spec in mixes:
        for alpha in skews:
            spec_a = dataclasses.replace(spec, alpha=alpha)
            _hashmap_cell(
                f"structs_hashmap_{mix_name}_zipf{alpha:g}",
                _loaded_map(KernelBackend(n_words=2 * spec_a.n_keys * 2,
                                          use_kernel=False), spec_a),
                spec_a, shadow=(mix_name == "mixed"))

    # -- hash map on the durable committer (real persists) -------------------
    d_spec = dataclasses.replace(base, n_ops=min(n_ops, 64))
    backend = DurableBackend()
    dmap = _loaded_map(backend, d_spec)
    p0 = backend.pool.persist_count                    # exclude load phase
    stats = _hashmap_cell("structs_hashmap_durable", dmap, d_spec)
    persists = backend.pool.persist_count - p0
    emit(f"structs_hashmap_durable_persists,0.0,"
         f"persists_per_commit={persists / max(1, stats.mwcas_won):.2f}")

    # -- BzTree node: insert throughput + split latency -----------------------
    cap = 8 if quick else 32
    kb = KernelBackend(n_words=4 * (cap + 1), use_kernel=False)
    node = SortedNode(kb, base=0, capacity=cap)
    t0 = time.time()
    sts = node.insert_batch(list(range(1, cap + 1)))
    dt = time.time() - t0
    assert all(s == NODE_OK for s in sts)
    emit(f"structs_node_insert_cap{cap},{dt / cap * 1e6:.1f},"
         f"keys={cap};rounds={cap}")           # one winner per round
    t0 = time.time()
    left, right, _sep = node.split(cap + 1, 2 * (cap + 1))
    dt = time.time() - t0
    emit(f"structs_node_split_cap{cap},{dt * 1e6:.1f},"
         f"left={left.count};right={right.count};one_wide_mwcas=k"
         f"{left.count + right.count + 2}")

    # -- free-list allocator over reserve_slots -------------------------------
    n_slots = 64 if quick else 256
    fl = FreeListAllocator(n_slots, use_kernel=False)
    t0 = time.time()
    grants = fl.alloc([4] * (n_slots // 8))
    dt = time.time() - t0
    served = sum(1 for g in grants if g)
    emit(f"structs_freelist_alloc{n_slots},{dt / len(grants) * 1e6:.1f},"
         f"served={served}/{len(grants)};free={fl.n_free}")


def _loaded_tree(backend_factory, spec: WorkloadSpec, *, leaf_cap: int,
                 root_cap: int, n_regions: int) -> BzTreeIndex:
    n_words = BzTreeIndex.words_needed(leaf_cap, root_cap, n_regions)
    tree = BzTreeIndex(backend_factory(n_words), leaf_cap=leaf_cap,
                       root_cap=root_cap, n_regions=n_regions)
    tree.apply(load_phase(spec))
    return tree


def _tree_cell(name: str, tree: BzTreeIndex, spec: WorkloadSpec, *,
               shadow: bool = False):
    ops = compile_workload(spec)
    s0 = (tree.splits, tree.consolidations)
    t0 = time.time()
    stats = run_workload(tree, spec, ops=ops)
    dt = time.time() - t0
    tree.check_integrity()
    derived = (f"ops_per_s={stats.n_ops / dt:.0f};"
               f"ok={stats.by_status.get('ok', 0)};"
               f"rounds={stats.rounds};"
               f"retries_per_op={stats.retries_per_op:.3f};"
               f"cas_ops_per_op={stats.cas_ops_per_op:.3f};"
               f"splits={tree.splits - s0[0]};"
               f"leaves={len(tree.leaf_bases())}")
    if shadow:
        cas, flush = _shadow_costs(tree)
        derived += f";cas_per_op={cas:.2f};flush_per_op={flush:.2f}"
    emit(f"{name},{dt / stats.n_ops * 1e6:.1f},{derived}")
    return stats


def run_tree(quick: bool = False):
    """The multi-node section: YCSB A/B/C + the scan-heavy E mix on the
    two-level BzTree (kernel + durable backends), plus a split-latency
    micro-bench — ``BENCH_tree.json``."""
    n_ops, n_keys = (32, 12) if quick else (160, 48)
    leaf_cap = 4 if quick else 8
    root_cap = max(4, 2 * n_keys // leaf_cap)
    n_regions = root_cap + 2
    shape = dict(leaf_cap=leaf_cap, root_cap=root_cap, n_regions=n_regions)
    mixes = [("ycsb_a", YCSB_A), ("ycsb_b", YCSB_B), ("ycsb_c", YCSB_C),
             ("ycsb_e_scan", YCSB_E)]
    skews = (0.0,) if quick else (0.0, 0.99)

    # -- tree on the kernel backend (jnp oracle; use_kernel on TPU) -----------
    for mix_name, mix in mixes:
        for alpha in skews:
            spec = dataclasses.replace(mix, n_ops=n_ops, n_keys=n_keys,
                                       batch=8, seed=11, alpha=alpha)
            tree = _loaded_tree(
                lambda n: KernelBackend(n_words=n, use_kernel=False),
                spec, **shape)
            _tree_cell(f"tree_{mix_name}_zipf{alpha:g}", tree, spec,
                       shadow=(mix_name == "ycsb_a" and alpha == 0.0))

    # -- tree on the durable committer (real persists, incl. split WALs) -----
    d_spec = dataclasses.replace(YCSB_A, n_ops=min(n_ops, 48),
                                 n_keys=n_keys, batch=8, seed=11)
    holder = {}

    def durable_factory(n_words):
        holder["backend"] = DurableBackend()
        return holder["backend"]

    dtree = _loaded_tree(durable_factory, d_spec, **shape)
    p0 = holder["backend"].pool.persist_count       # exclude load phase
    stats = _tree_cell("tree_ycsb_a_durable", dtree, d_spec)
    persists = holder["backend"].pool.persist_count - p0
    pruned = holder["backend"].prune_completed()    # WAL hygiene pass
    emit(f"tree_durable_persists,0.0,"
         f"persists_per_commit={persists / max(1, stats.mwcas_won):.2f};"
         f"wal_pruned={pruned}")

    # -- split latency: fill one leaf past capacity, time the two rounds -----
    cap = 8 if quick else 32
    n_words = BzTreeIndex.words_needed(cap, 4, 4)
    tree = BzTreeIndex(KernelBackend(n_words=n_words, use_kernel=False),
                       leaf_cap=cap, root_cap=4, n_regions=4)
    from repro.structures import INSERT, KVOp
    tree.apply([KVOp(INSERT, k, k) for k in range(1, cap + 1)])
    t0 = time.time()
    tree.apply([KVOp(INSERT, cap + 1, 1)])          # triggers the split
    dt = time.time() - t0
    assert tree.splits == 1
    emit(f"tree_split_cap{cap},{dt * 1e6:.1f},"
         f"splits={tree.splits};leaves={len(tree.leaf_bases())};"
         f"wide_k={2 * (1 + 2 * (cap // 2)) + 2}")


if __name__ == "__main__":
    run()
    run_tree()
