"""Service-layer benchmarks: many clients on the sharded MwCAS service.

The section the ISSUE acceptance reads: aggregate round throughput
(completions per round wave — the substrate-independent unit; shard
rounds in one wave execute concurrently, kernel shards in ONE stacked
dispatch) must SCALE WITH SHARD COUNT on a Zipf-skewed many-client
workload.  The ``service_scaling`` row records s4/s1 explicitly and the
bench asserts S=4 strictly beats S=1, so a scaling regression fails CI
rather than just drifting.

Also measured: client-count sensitivity, defer/conflict rates and
p50/p99 latency in rounds, the durable service (real persists per op +
crash/recover), the BzTree-sharded service, and the raw scheduler's
cross-shard serialization cost.
"""
from __future__ import annotations

import dataclasses
import time

from repro.pmwcas import KernelBackend, MwCASOp
from repro.service import BatchScheduler, KVService, ShardRouter
from repro.structures import WorkloadSpec, client_streams, load_phase

from .common import emit, slo_observe

# Mutation-heavy so nearly every logical op compiles to a CAS (reads and
# misses complete at compile time and never occupy a round slot): the
# scaling lever under test is per-wave CAS capacity (round_cap x S), and
# a read-dominated mix would measure the compiler, not the substrate.
SPEC = WorkloadSpec(n_ops=192, n_keys=48, read=0.1, update=0.55,
                    insert=0.25, delete=0.1, alpha=0.9, seed=23)


def _run_service(svc: KVService, streams, load) -> dict:
    """Load, reset the measurement window, then submit every client's
    stream round-robin (the many-client arrival order) and drain."""
    svc.apply(load)
    svc.reset_stats()
    n = 0
    t0 = time.time()
    for i in range(max(len(s) for s in streams)):
        for client, stream in enumerate(streams):
            if i < len(stream):
                svc.submit(stream[i], client=client)
                n += 1
    svc.drain()
    dt = time.time() - t0
    svc.check_integrity()
    row = svc.stats.as_row()
    row["n_ops"] = n
    row["dt"] = dt
    return row


def _emit_kv(name: str, row: dict):
    extra = ""
    if "traces" in row:          # stacked dispatch ran: trace-cache row
        extra = (f";traces={row['traces']};"
                 f"dispatch_hits={row['dispatch_hits']};"
                 f"serial_rounds={row['serial_rounds']}")
    if "queue_us_p50" in row:    # the op-lifecycle latency breakdown
        extra += (f";queue_us_p50={row['queue_us_p50']:.1f};"
                  f"queue_us_p99={row['queue_us_p99']:.1f};"
                  f"dispatch_us_p50={row['dispatch_us_p50']:.1f};"
                  f"dispatch_us_p99={row['dispatch_us_p99']:.1f};"
                  f"persist_us_p50={row['persist_us_p50']:.1f};"
                  f"persist_us_p99={row['persist_us_p99']:.1f};"
                  f"retry_waves_max={row['retry_waves_max']}")
        # the three components partition each op's latency BY
        # CONSTRUCTION (KVService._answer), so their means must
        # reconcile with the latency mean to rounding noise
        parts = (row["queue_us_mean"] + row["dispatch_us_mean"]
                 + row["persist_us_mean"])
        lat = row["latency_us_mean"]
        assert abs(parts - lat) <= 0.02 * lat + 1e-6, (
            f"{name}: queue+dispatch+persist means ({parts:.3f}us) do "
            f"not reconcile with latency_us mean ({lat:.3f}us) — the "
            "lifecycle breakdown no longer partitions latency")
    emit(f"{name},{row['dt'] / row['n_ops'] * 1e6:.1f},"
         f"ops_per_s={row['n_ops'] / row['dt']:.0f};"
         f"ops_per_round={row['ops_per_step']:.2f};"
         f"steps={row['steps']:.0f};rounds={row['rounds']:.0f};"
         f"occupancy={row['occupancy']:.2f};"
         f"defer_rate={row['defer_rate']:.3f};"
         f"conflict_rate={row['conflict_rate']:.3f};"
         f"p50_rounds={row['p50_latency_rounds']:.0f};"
         f"p99_rounds={row['p99_latency_rounds']:.0f};"
         f"p50_us={row['p50_latency_us']:.1f};"
         f"p99_us={row['p99_latency_us']:.1f}" + extra)
    slo_observe(p99_latency_us=row["p99_latency_us"],
                ops_per_s=row["n_ops"] / row["dt"],
                **({"persist_us_p99": row["persist_us_p99"]}
                   if "persist_us_p99" in row else {}))


def run(quick: bool = False):
    spec = dataclasses.replace(SPEC, n_ops=96, n_keys=32) if quick else SPEC
    n_clients = 8
    round_cap = 4
    # full key universe pre-loaded: updates/deletes hit, so nearly every
    # logical op occupies a round slot (see SPEC comment)
    load = load_phase(spec, fraction=1.0)
    streams = client_streams(spec, n_clients)

    # -- KV service: throughput vs shard count (Zipf-skewed, 8 clients) ------
    shard_counts = (1, 4) if quick else (1, 2, 4)
    ops_per_round = {}
    us_per_call = {}
    traces = {}
    for s_count in shard_counts:
        svc = KVService(s_count, structure="hashmap",
                        n_buckets=-(-2 * spec.n_keys // s_count),
                        round_cap=round_cap, use_kernel=False)
        row = _run_service(svc, streams, load)
        ops_per_round[s_count] = row["ops_per_step"]
        us_per_call[s_count] = row["dt"] / row["n_ops"] * 1e6
        traces[s_count] = row.get("traces")
        _emit_kv(f"service_kv_S{s_count}_c{n_clients}_zipf{spec.alpha:g}",
                 row)

    # -- the acceptance rows: round throughput must scale AND the stacked
    # dispatch must be retrace-free in steady state (wall-clock ops/s
    # therefore scales too, instead of inverting under recompiles) -----------
    s_lo, s_hi = min(shard_counts), max(shard_counts)
    speedup = ops_per_round[s_hi] / max(ops_per_round[s_lo], 1e-9)
    emit(f"service_scaling,0.0,"
         f"ops_per_round_s{s_lo}={ops_per_round[s_lo]:.2f};"
         f"ops_per_round_s{s_hi}={ops_per_round[s_hi]:.2f};"
         f"speedup={speedup:.2f};"
         f"us_ratio_s{s_hi}_vs_s{s_lo}="
         f"{us_per_call[s_hi] / us_per_call[s_lo]:.2f};"
         f"traces_s{s_hi}={traces[s_hi]:.0f}")
    assert ops_per_round[s_hi] > ops_per_round[s_lo], (
        f"sharding must scale round throughput: S={s_hi} gave "
        f"{ops_per_round[s_hi]:.2f} ops/round vs S={s_lo} "
        f"{ops_per_round[s_lo]:.2f}")
    assert traces[s_hi] == 0, (
        f"stacked dispatch retraced {traces[s_hi]} times in the "
        "measurement window; shape bucketing has regressed")
    assert us_per_call[s_hi] <= 1.5 * us_per_call[s_lo], (
        f"S={s_hi} wall clock per call ({us_per_call[s_hi]:.0f}us) must "
        f"stay within 1.5x of S={s_lo} ({us_per_call[s_lo]:.0f}us) — "
        "the stacked dispatch is supposed to be cached, not recompiled")

    # -- client-count sensitivity at fixed S ---------------------------------
    for c in ((2,) if quick else (2, 16)):
        svc = KVService(4, structure="hashmap",
                        n_buckets=-(-2 * spec.n_keys // 4),
                        round_cap=round_cap, use_kernel=False)
        row = _run_service(svc, client_streams(spec, c), load)
        _emit_kv(f"service_kv_S4_c{c}_zipf{spec.alpha:g}", row)

    # -- BzTree-sharded service (splits + GC under service traffic) ----------
    t_spec = dataclasses.replace(spec, n_ops=min(spec.n_ops, 96),
                                 read=0.3, delete=0.0, insert=0.3,
                                 update=0.4)
    tsvc = KVService(2, structure="bztree", leaf_cap=4,
                     root_cap=max(4, t_spec.n_keys // 2),
                     n_regions=max(6, t_spec.n_keys // 2 + 2),
                     round_cap=round_cap, use_kernel=False)
    row = _run_service(tsvc, client_streams(t_spec, n_clients),
                       load_phase(t_spec))
    splits = sum(t.splits for t in tsvc.structs)
    freed = tsvc.gc_regions()
    _emit_kv("service_tree_S2", row)
    emit(f"service_tree_gc,0.0,splits={splits};regions_freed={freed}")

    # -- durable service: real persists per committed op + crash/recover -----
    d_spec = dataclasses.replace(spec, n_ops=min(spec.n_ops, 64))
    dsvc = KVService(2, structure="hashmap", backend="durable",
                     n_buckets=2 * d_spec.n_keys, round_cap=round_cap)
    d_load = load_phase(d_spec)
    d_streams = client_streams(d_spec, n_clients)
    row = _run_service(dsvc, d_streams, d_load)
    persists = sum(b.pool.persist_count for b in dsvc.backends)
    t0 = time.time()
    rec = dsvc.crash()
    recover_ms = (time.time() - t0) * 1e3
    assert rec.check_integrity() == dsvc.check_integrity()
    _emit_kv("service_kv_S2_durable", row)
    dstats = dsvc.durability_stats()
    emit(f"service_durable_recover,{recover_ms * 1e3:.0f},"
         f"persists_total={persists};"
         f"persists_per_commit="
         f"{persists / max(1, sum(s.ops_won for s in dsvc.stats.shards)):.2f};"
         f"flushes_saved={dstats.flushes_saved};fences={dstats.fences}")

    # -- raw scheduler: cross-shard serialization cost -----------------------
    n_shards, words = 4, 32
    for cross_pct in (0, 12):
        backends = [KernelBackend(n_words=words, use_kernel=False)
                    for _ in range(n_shards)]
        sched = BatchScheduler(
            backends, ShardRouter(n_shards, words_per_shard=words),
            round_cap=round_cap)
        ops = []
        n_raw = 32 if quick else 128
        for i in range(n_raw):
            if cross_pct and i % (100 // cross_pct) == 0:
                a = (i * 5) % words
                ops.append(MwCASOp([(a, 0, 1),
                                    (words + (a + 1) % words, 0, 1)]))
            else:
                shard = i % n_shards
                ops.append(MwCASOp([(shard * words + (i * 3) % words,
                                     0, 1)]))
        futs = sched.submit_many(ops)
        t0 = time.time()
        sched.step()                       # absorb first-dispatch compile
        sched.drain()
        dt = time.time() - t0
        ok = sum(1 for f in futs if f.success)
        emit(f"service_sched_cross{cross_pct},{dt / n_raw * 1e6:.1f},"
             f"ops_per_s={n_raw / dt:.0f};ok={ok};"
             f"ops_per_round={sched.stats.ops_per_step:.2f};"
             f"cross_rounds={sched.stats.cross_rounds}")


if __name__ == "__main__":
    run()
