"""A persistent lock-free KV store in ~60 lines of driver code — the
paper's "productive uses of PMwCAS" claim, running on the structures
layer:

1. A YCSB-style workload (Zipfian keys, mixed ops) on the lock-free
   hash map over the batched kernel backend; every mutation is one
   2-word PMwCAS.
2. The same logical workload on the durable descriptor-WAL backend —
   then a crash: recovery reattaches the map with zero lost commits
   and zero torn bucket pairs.
3. The three-substrate differential: kernel and durable agree op-by-op,
   and every CAS round is shadow-verified on the cycle-accurate
   simulator.
4. A BzTree-style node fills up, splits with ONE wide PMwCAS, and a
   parent pointer swings atomically — the index building block.

Run:  PYTHONPATH=src python examples/kv_store.py
"""
import dataclasses

from repro.pmwcas import DurableBackend, KernelBackend
from repro.structures import (HashMap, SortedNode, YCSB_A, NODE_FULL,
                              compile_workload, load_phase,
                              run_struct_differential, run_workload,
                              swap_pointer, read_pointer)

SPEC = dataclasses.replace(YCSB_A, n_ops=96, n_keys=24, batch=8,
                           alpha=0.99, seed=42)

print("=== 1. YCSB-A on the lock-free hash map (kernel backend) ===")
kmap = HashMap(KernelBackend(n_words=4 * SPEC.n_keys, use_kernel=False),
               2 * SPEC.n_keys)
kmap.apply(load_phase(SPEC))
stats = run_workload(kmap, SPEC)
print(f"  {stats.n_ops} logical ops -> {stats.mwcas_submitted} MwCAS "
      f"({stats.rounds} rounds, {stats.retries_per_op:.3f} retries/op)")
print(f"  outcomes: {dict(sorted(stats.by_status.items()))}")
kmap.check_integrity()

print("\n=== 2. same workload, durable backend + crash ===")
db = DurableBackend()
dmap = HashMap(db, 2 * SPEC.n_keys)
dmap.apply(load_phase(SPEC))
run_workload(dmap, SPEC)
before = dmap.check_integrity()
recovered = HashMap(db.crash(), 2 * SPEC.n_keys)   # crash + reattach
after = recovered.check_integrity()
assert after == before, "lost or torn state across the crash!"
print(f"  {len(before)} live keys before crash == {len(after)} after "
      f"recovery; no torn bucket pairs")

print("\n=== 3. three-substrate differential on a conflict workload ===")
ops = compile_workload(dataclasses.replace(
    SPEC, n_ops=32, n_keys=8, read=0.2, update=0.2, insert=0.5, delete=0.1))
rep = run_struct_differential(ops, n_buckets=8, use_kernel=False)
print("  " + rep.summary().replace("\n", "\n  "))
assert rep.agree and rep.sim_rounds_checked >= 1

print("\n=== 4. BzTree node: fill, split (one wide PMwCAS), install ===")
kb = KernelBackend(n_words=64, use_kernel=False)
ROOT_PTR = 40
node = SortedNode(kb, base=0, capacity=8)
node.insert_batch([50, 20, 80, 10, 60, 30, 70, 40])
assert node.insert(90) == NODE_FULL
left, right, sep = node.split(10, 20)
swap_pointer(kb, ROOT_PTR, 0, left.base)
print(f"  split {node.keys()} -> {left.keys()} | {right.keys()} "
      f"(separator {sep})")
assert node.frozen and node.keys() == sorted(left.keys() + right.keys())
print(f"  root pointer now -> node@{read_pointer(kb, ROOT_PTR)}; frozen "
      f"original still intact: {node.keys()}")
print("kv_store OK")
