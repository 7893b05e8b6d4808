"""Where a service wave's host time goes, and what tracing records.

- ``enable_tracing()`` records the wave spans only; the per-op
  lifecycle instants (``op.submit``/``op.requeue``/``op.ack_held``/
  ``op.complete``) need ``enable_tracing(ops=True)``;
- ``ServiceStats.ops_compiled`` counts every op a wave compiles, once
  per wave it is compiled in, with tracing on or off;
- ``snapshot_ns``/``compile_ns``/``complete_ns`` time the whole-table
  snapshots, the per-op compile and the completions while tracing is
  enabled, and stay 0 while it is not;
- a completion records into the ``ServiceStats`` histograms only, never
  into the global registry.
"""
import pytest

from repro.obs import (disable_tracing, enable_tracing, get_registry,
                       get_tracer, reset_metrics)
from repro.pmwcas import MwCASOp, make_backend
from repro.service import BatchScheduler, KVService, ShardRouter
from repro.structures import KVOp

OP_EVENTS = ("op.submit", "op.requeue", "op.ack_held", "op.complete")


@pytest.fixture(autouse=True)
def _quiesce_obs():
    yield
    disable_tracing()
    get_tracer().clear()
    reset_metrics()


def _loaded(**kw) -> KVService:
    svc = KVService(2, structure="hashmap", n_buckets=32, round_cap=2,
                    use_kernel=False, **kw)
    svc.apply([KVOp("insert", k, k) for k in range(1, 9)])
    svc.reset_stats()
    return svc


def _mixed(svc: KVService, n: int = 12):
    """``n`` ops, updates and reads alternating over 8 keys, drained."""
    futs = [svc.submit(KVOp("update", 1 + i % 8, 100 + i) if i % 2
                       else KVOp("read", 1 + i % 8), client=i % 3)
            for i in range(n)]
    svc.drain()
    assert all(f.done for f in futs)
    return futs


def _names(events):
    return {e["name"] for e in events}


def test_default_tracing_records_no_per_op_events():
    svc = _loaded()
    enable_tracing().clear()
    _mixed(svc)
    names = _names(get_tracer().events())
    assert {"service.wave", "wave.compile", "wave.snapshot"} <= names
    assert not names & set(OP_EVENTS)


def test_ops_tracing_records_the_lifecycle():
    svc = _loaded()
    enable_tracing(ops=True).clear()
    futs = _mixed(svc)
    events = get_tracer().events()
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e["args"].get("op_id"))
    ids = {f.op_id for f in futs}
    assert set(by_name["op.submit"]) == set(by_name["op.complete"]) == ids
    # round_cap 2 on 2 shards defers some of the 6 updates
    assert set(by_name["op.requeue"]) <= ids


@pytest.mark.parametrize("ops", [False, True])
def test_scheduler_op_events_follow_the_ops_flag(ops):
    backends = [make_backend("kernel", n_words=8, use_kernel=False)
                for _ in range(2)]
    sched = BatchScheduler(backends, ShardRouter(2, words_per_shard=8),
                           round_cap=4)
    enable_tracing(ops=ops).clear()
    futs = sched.submit_many([MwCASOp([(a, 0, 1)]) for a in (0, 1, 9)])
    sched.drain()
    assert all(f.success for f in futs)
    names = _names(get_tracer().events())
    assert ({"op.submit", "op.complete"} <= names) == ops
    assert bool(names & set(OP_EVENTS)) == ops


@pytest.mark.parametrize("traced", [False, True])
def test_ops_compiled_counts_every_compile(traced):
    svc = _loaded()
    if traced:
        enable_tracing().clear()
    _mixed(svc, n=20)
    st = svc.stats
    # each compile ends in an answer, or in a defer, an overflow or a
    # lost round, after which the op compiles again in a later wave
    again = sum(sh.defers + sh.overflows + sh.ops_executed - sh.ops_won
                for sh in st.shards)
    assert again > 0
    assert st.ops_compiled == st.completed + again
    assert st.ops_compiled_batched == st.ops_compiled


@pytest.mark.parametrize("traced", [False, True])
def test_wave_timers_run_only_under_tracing(traced):
    svc = _loaded()
    if traced:
        enable_tracing().clear()
    _mixed(svc)
    st = svc.stats
    timers = (st.snapshot_ns, st.compile_ns, st.complete_ns)
    if not traced:
        assert timers == (0, 0, 0)
        return
    assert all(t > 0 for t in timers)
    events = get_tracer().events()
    snapshots = [e["dur"] for e in events if e["name"] == "wave.snapshot"]
    assert st.snapshot_ns == pytest.approx(sum(snapshots) * 1e3, abs=1e3)
    # the three timers are disjoint parts of the waves
    waves_ns = sum(e["dur"] for e in events
                   if e["name"] == "service.wave") * 1e3
    assert sum(timers) < waves_ns


def test_held_acks_are_timed_once(tmp_path):
    """Epoch mode withholds acks; their later release counts into
    ``complete_ns`` too, and the traced run answers every op."""
    svc = KVService(2, structure="hashmap", backend="durable",
                    n_buckets=32, round_cap=4, epoch_rounds=4,
                    durable_root=tmp_path)
    svc.apply([KVOp("insert", k, k) for k in range(1, 9)])
    svc.reset_stats()
    enable_tracing().clear()
    _mixed(svc)
    st = svc.stats
    assert st.acks_held > 0
    assert st.complete_ns > 0
    waves_ns = sum(e["dur"] for e in get_tracer().events()
                   if e["name"] == "service.wave") * 1e3
    assert st.snapshot_ns + st.compile_ns + st.complete_ns < waves_ns


def test_reset_stats_zeroes_the_wave_counters():
    svc = _loaded()
    enable_tracing().clear()
    _mixed(svc)
    assert svc.stats.ops_compiled and svc.stats.complete_ns
    svc.reset_stats()
    st = svc.stats
    assert (st.ops_compiled, st.snapshot_ns, st.compile_ns,
            st.complete_ns) == (0, 0, 0, 0)


def test_completions_leave_the_registry_alone():
    svc = _loaded()
    reset_metrics()
    _mixed(svc)
    assert svc.stats.queue_us.count == svc.stats.completed > 0
    reg = get_registry()
    for name in ("queue_us", "dispatch_us", "persist_us", "retry_waves"):
        assert reg.series(name) == [], name
