"""A persistent lock-free RANGE INDEX in ~60 lines of driver code — the
multi-node payoff of the paper's PMwCAS (DESIGN.md Sec. 7):

1. A two-level BzTree fills until leaves overflow; every split is the
   one-wide-MwCAS half materialization followed by a 2-word parent
   install (pointer swing + separator count bump).
2. The scan-heavy YCSB-E mix — the workload range indexes exist for —
   runs against the tree on the batched kernel backend.
3. The same tree on the durable descriptor-WAL backend, then a crash:
   a fresh index attaches to the recovered words with zero lost commits
   and no torn node, and the WAL is pruned of spent descriptors.
4. The three-substrate differential: kernel and durable trees agree
   op-by-op and every CAS round is shadow-verified on the simulator.

Run:  PYTHONPATH=src python examples/range_index.py
"""
import dataclasses

from repro.pmwcas import DurableBackend, KernelBackend
from repro.structures import (BzTreeIndex, INSERT, KVOp, SCAN, YCSB_E,
                              compile_workload, load_phase,
                              run_struct_differential, run_workload)

SHAPE = dict(leaf_cap=4, root_cap=8, n_regions=10)
SPEC = dataclasses.replace(YCSB_E, n_ops=64, n_keys=24, batch=8,
                           alpha=0.9, seed=42)

print("=== 1. grow a two-level BzTree through leaf splits ===")
n_words = BzTreeIndex.words_needed(**SHAPE)
tree = BzTreeIndex(KernelBackend(n_words=n_words, use_kernel=False), **SHAPE)
tree.apply([KVOp(INSERT, k, 100 + k) for k in range(1, 17)])
print(f"  16 inserts -> {tree.splits} splits, "
      f"{len(tree.leaf_bases())} leaves, root holds {tree.root_count()} "
      f"separators")
tree.check_integrity()

print("\n=== 2. YCSB-E (scan-heavy) on the range index ===")
stats = run_workload(tree, SPEC)
(scan,) = tree.apply([KVOp(SCAN, 8)])
print(f"  {stats.n_ops} logical ops -> {stats.mwcas_submitted} MwCAS "
      f"({stats.rounds} rounds); outcomes "
      f"{dict(sorted(stats.by_status.items()))}")
print(f"  scan(key >= 8) counts {scan.value} live keys across "
      f"{len(tree.leaf_bases())} leaves")

print("\n=== 3. the same tree on the durable backend + crash ===")
db = DurableBackend()
dtree = BzTreeIndex(db, **SHAPE)
dtree.apply(load_phase(SPEC))
before = dtree.check_integrity()
pruned = db.prune_completed()                    # WAL hygiene
recovered = BzTreeIndex(db.crash(), **SHAPE)     # crash + attach
after = recovered.check_integrity()
assert after == before, "lost or torn state across the crash!"
print(f"  {len(before)} live keys before crash == {len(after)} after "
      f"recovery; {pruned} spent WAL descriptors pruned; no torn node")

print("\n=== 4. three-substrate differential on a splitting workload ===")
ops = load_phase(SPEC) + compile_workload(
    dataclasses.replace(SPEC, n_ops=32, scan=0.25, insert=0.45, read=0.2,
                        update=0.1))
rep = run_struct_differential(ops, structure="bztree", use_kernel=False,
                              **SHAPE)
print("  " + rep.summary().replace("\n", "\n  "))
assert rep.agree and rep.sim_rounds_checked >= 1
print("range_index OK")
