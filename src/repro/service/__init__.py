"""repro.service — sharded, batched PMwCAS execution for many clients.

The paper's throughput levers are fewer CASes and descriptor-as-WAL
batching; this package applies both one level up, where many logical
clients multiplex onto the kernel/durable substrates:

- :class:`ShardRouter` — partitions the word space into S shards
  (range or interleaved-hash), each shard owning its own backend
  instance; bijective global<->local address translation, plus
  multiplicative-hash key routing for the KV front.
- :class:`BatchScheduler` — async raw-op layer: clients ``submit``
  :class:`repro.pmwcas.MwCASOp`\\ s and get :class:`OpFuture`\\ s; queued
  ops coalesce into conflict-free per-shard rounds (duplicate-target
  ops are DEFERRED to the next round, never executed to certain
  failure), all shard rounds execute in one wave, and cross-shard ops
  run in a serialized global round (journaled when shards are durable,
  so no crash can half-apply one).
- :class:`StackedKernelExecutor` — kernel shards' rounds stacked into
  one ``jax.vmap``-ped ``pmwcas_apply`` dispatch: S rounds, one device
  call, for any S; every round travels as a :class:`RowBatch`.
- :class:`KVService` — the structures front: per-shard
  :class:`repro.structures.HashMap` / ``BzTreeIndex`` partitions,
  logical :class:`repro.structures.KVOp` submissions compiled
  per-snapshot and retried across waves, split/GC protocols included.
- :class:`ServiceStats` — per-shard round counts, batch occupancy,
  defer/conflict rates, p50/p99 op latency in rounds.

See DESIGN.md Sec. 8 for the architecture and the cross-shard
serialization argument; ``examples/kv_service.py`` is the walkthrough.
"""
from .checkers import check_migration_crash_sweep
from .executor import (DispatchStats, RowBatch, SerialShardExecutor,
                       StackedKernelExecutor, build_rounds, execute_wave,
                       schedule_wave)
from .journal import (CrossShardJournal, MIG_COMPLETED, MIG_MIGRATING,
                      MIG_ROUTED, MigrationLog)
from .router import CROSS_SHARD, RoutedOp, ShardRouter
from .scheduler import BatchScheduler, OpFuture, ServiceError
from .service import KVFuture, KVService
from .stats import (ServiceStats, ShardStats, collect_durability,
                    fresh_stats)

__all__ = [
    "ShardRouter", "RoutedOp", "CROSS_SHARD",
    "BatchScheduler", "OpFuture", "ServiceError",
    "KVService", "KVFuture",
    "SerialShardExecutor", "StackedKernelExecutor", "DispatchStats",
    "RowBatch",
    "build_rounds", "schedule_wave", "execute_wave",
    "CrossShardJournal",
    "MigrationLog", "MIG_MIGRATING", "MIG_ROUTED", "MIG_COMPLETED",
    "check_migration_crash_sweep",
    "ServiceStats", "ShardStats", "collect_durability", "fresh_stats",
]
