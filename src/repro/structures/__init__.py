"""repro.structures — lock-free persistent data structures on PMwCAS.

The paper's closing claim is that a practical PMwCAS enables lock-free
persistent data structures; this package is that claim made executable.
Every structure is implemented ONLY against the public ``repro.pmwcas``
surface (``MwCASOp`` + the ``Backend`` protocol), so each one runs
unchanged on the cycle-accurate simulator (shadowed), the batched Pallas
kernel, and the durable descriptor-WAL committer:

- :class:`HashMap` — fixed-capacity open-addressing map; insert/update/
  delete each compile to ONE 2-word MwCAS (key word + value word).
- :class:`SortedNode` — BzTree-style sorted-array node; insert is a
  2-word MwCAS (meta + slot), split freezes then materializes both
  halves with ONE wide MwCAS.
- :class:`BzTreeIndex` — the multi-node payoff: a two-level BzTree of
  :class:`LeafNode` KV leaves under a separator-routing root, leaf
  splits = the one-wide-MwCAS split + a 2-word parent install
  (DESIGN.md Sec. 7).
- :class:`FreeListAllocator` — atomic K-slot reservation layered on
  ``reserve_slots`` (the serving-layer primitive).
- workload compiler — YCSB-style mixes with Zipfian key popularity
  (A/B/C plus the scan-heavy E for the range index), compiled to the
  shared logical-op vocabulary and batched into the kernel's
  ``ops_to_arrays`` wire form.
- checkers + differential — structure-level crash-consistency sweeps
  (durable crash-at-every-persist for map and tree, simulator micro-op
  crash sweep) and :func:`run_struct_differential`, the three-substrate
  agreement check for whole logical workloads.

See DESIGN.md Sec. 6 for operation compilation, per-backend semantics
and the crash invariants, and Sec. 7 for the multi-node tree.
"""
from .bztree import (COUNT_MASK, FROZEN_BIT, NODE_EXHAUSTED, NODE_EXISTS,
                     NODE_FROZEN, NODE_FULL, NODE_OK, SortedNode, SplitError,
                     read_pointer, swap_pointer)
from .bztree_index import (BzTreeIndex, INNER_BIT, LEAF_DEAD, LeafNode,
                           NeedsSplit)
from .checkers import (CrashCheckError, check_durable_crash_sweep,
                       check_hashmap_resize_sweep, check_sim_crash_sweep,
                       check_tree_crash_sweep, replay_effects)
from .differential import (StructDifferentialReport, conservative_verdicts,
                           run_struct_differential, shadow_batch,
                           winner_blocking_verdicts)
from .freelist import DoubleFree, FreeListAllocator, OutOfRegions
from .hashmap import (CompiledBatch, DELETE, EMPTY, EXHAUSTED, EXISTS, FULL,
                      HashMap, INSERT, KVOp, MIG_BIT, NOT_FOUND, NeedsResize,
                      OK, READ, RoundTrace, SCAN, StructResult, TOMBSTONE,
                      TornStructure, UPDATE)
from .workload import (LOAD, WorkloadSpec, WorkloadStats, YCSB_A, YCSB_B,
                       YCSB_C, YCSB_E, batches, client_streams,
                       compile_workload, interleave, kernel_round_arrays,
                       key_shard, load_phase, partition_ops, run_workload)

__all__ = [
    # hash map
    "HashMap", "KVOp", "StructResult", "RoundTrace", "TornStructure",
    "CompiledBatch",
    "NeedsResize", "EMPTY", "TOMBSTONE", "MIG_BIT",
    "READ", "INSERT", "UPDATE", "DELETE", "SCAN",
    "OK", "EXISTS", "NOT_FOUND", "FULL", "EXHAUSTED",
    # bztree node
    "SortedNode", "SplitError", "swap_pointer", "read_pointer",
    "FROZEN_BIT", "COUNT_MASK",
    "NODE_OK", "NODE_FULL", "NODE_FROZEN", "NODE_EXISTS", "NODE_EXHAUSTED",
    # multi-node tree
    "BzTreeIndex", "LeafNode", "LEAF_DEAD", "NeedsSplit", "INNER_BIT",
    # allocator
    "FreeListAllocator", "DoubleFree", "OutOfRegions",
    # workload
    "WorkloadSpec", "WorkloadStats", "YCSB_A", "YCSB_B", "YCSB_C", "YCSB_E",
    "LOAD",
    "compile_workload", "load_phase", "batches", "run_workload",
    "kernel_round_arrays", "client_streams", "interleave", "key_shard",
    "partition_ops",
    # checkers + differential
    "check_durable_crash_sweep", "check_sim_crash_sweep",
    "check_tree_crash_sweep", "check_hashmap_resize_sweep",
    "replay_effects",
    "CrashCheckError",
    "run_struct_differential", "StructDifferentialReport",
    "conservative_verdicts", "winner_blocking_verdicts", "shadow_batch",
]
