"""A sharded, batched KV service in ~70 lines of driver code — the
many-client layer on top of the lock-free structures:

1. Eight clients fire a Zipf-skewed mutation-heavy workload at a
   4-shard service; per-shard conflict-free rounds execute in one wave
   per step (kernel shards in ONE stacked vmapped dispatch), duplicate-
   target ops are deferred instead of executed-to-lose, and per-op
   latency comes back in rounds (p50/p99).
2. The same workload on a single shard: aggregate round throughput
   drops — sharding is the scaling lever (the service benchmark gates
   on this).
3. A durable service: every commit is a real descriptor-WAL persist;
   the service crashes, every shard recovers from its own WAL, nothing
   is lost or torn.
4. The raw scheduler: a cross-shard MwCAS runs in a serialized global
   round under a decision journal, so even a crash between shard
   applications cannot half-apply it.

Run:  PYTHONPATH=src python examples/kv_service.py
"""
import dataclasses
import pathlib
import tempfile

from repro import PMemPool
from repro.pmwcas import DurableBackend, MwCASOp
from repro.service import (BatchScheduler, CrossShardJournal, KVService,
                           ShardRouter)
from repro.structures import (WorkloadSpec, client_streams, load_phase)

SPEC = WorkloadSpec(n_ops=160, n_keys=32, read=0.1, update=0.55,
                    insert=0.25, delete=0.1, alpha=0.9, seed=7)
N_CLIENTS = 8


def drive(svc):
    """Load the key universe, then submit 8 interleaved client streams."""
    svc.apply(load_phase(SPEC, fraction=1.0))
    svc.reset_stats()
    streams = client_streams(SPEC, N_CLIENTS)
    for i in range(max(len(s) for s in streams)):
        for client, stream in enumerate(streams):
            if i < len(stream):
                svc.submit(stream[i], client=client)
    svc.drain()
    svc.check_integrity()
    return svc.stats


print("=== 1. 8 clients on a 4-shard service (stacked kernel rounds) ===")
svc4 = KVService(4, structure="hashmap", n_buckets=2 * SPEC.n_keys,
                 round_cap=4, use_kernel=False)
st4 = drive(svc4)
print("  " + st4.summary().replace("\n", "\n  "))
print(f"  executor: {type(svc4.executor).__name__} "
      f"({svc4.executor.stacked_dispatches} stacked dispatches)")

print("\n=== 2. same traffic, one shard: round throughput drops ===")
svc1 = KVService(1, structure="hashmap", n_buckets=8 * SPEC.n_keys,
                 round_cap=4, use_kernel=False)
st1 = drive(svc1)
print(f"  S=4: {st4.ops_per_step:.1f} ops/round-wave   "
      f"S=1: {st1.ops_per_step:.1f} ops/round-wave")
assert st4.ops_per_step > st1.ops_per_step, "sharding must scale"
assert svc1.items() == svc4.items(), "sharding must not change results"

print("\n=== 3. durable service: crash every shard, recover via WALs ===")
with tempfile.TemporaryDirectory() as tmp:
    dsvc = KVService(2, structure="hashmap", backend="durable",
                     n_buckets=2 * SPEC.n_keys, durable_root=tmp,
                     round_cap=4)
    small = dataclasses.replace(SPEC, n_ops=48)
    dsvc.apply(load_phase(small) + sum(client_streams(small, 4), []))
    before = dsvc.check_integrity()
    recovered = dsvc.crash()                      # drop caches, replay WALs
    after = recovered.check_integrity()
    assert after == before, "lost or torn state across the crash!"
    print(f"  {len(before)} live keys before crash == {len(after)} after; "
          f"no shard torn")

print("\n=== 4. cross-shard MwCAS: serialized + journaled ===")
with tempfile.TemporaryDirectory() as tmp:
    root = pathlib.Path(tmp)
    shards = [DurableBackend(root / f"s{i}") for i in range(2)]
    sched = BatchScheduler(shards, ShardRouter(2, words_per_shard=8),
                           journal=CrossShardJournal(PMemPool(root / "j")))
    f_local = sched.submit(MwCASOp([(0, 0, 1)]))          # shard 0
    f_cross = sched.submit(MwCASOp([(1, 0, 2), (9, 0, 3)]))  # spans 0+1
    sched.drain()
    assert f_local.success and f_cross.success
    assert (sched.read(1), sched.read(9)) == (2, 3)
    print(f"  local + cross committed; {sched.stats.cross_rounds} global "
          f"round, journal holds {len(sched.journal)} decision record(s)")
print("kv_service OK")
