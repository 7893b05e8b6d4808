"""repro.obs — metrics registry, span tracer, exporters, adapters, and
the accounting contracts the rest of the stack now relies on:

- the committer keeps TWO ledgers of the same commits (its
  ``DurabilityStats`` and the registry counters) through one helper, so
  the two must agree to the exact integer;
- stats survive crash/recover MONOTONE (no zeroing, no double-count);
- ``KVService.reset_stats`` opens a fresh measurement window (registry
  zeroed in place) without cooling the executor's trace cache;
- the WAL recovery span decomposes into named child phases (the
  acceptance criterion benchmarks and traces both read).
"""
import json

import numpy as np
import pytest

from repro.obs import (NULL_SPAN, Counter, Histogram, MetricsRegistry,
                       SpanTracer, chrome_trace, disable_tracing,
                       enable_tracing, export_jsonl, fold_durability,
                       fold_service, get_registry, get_tracer,
                       reset_metrics, span, span_tree,
                       validate_chrome_trace)
from repro.pmwcas import DurabilityStats, DurableBackend, MwCASOp
from repro.service import KVService, fresh_stats
from repro.structures import KVOp


@pytest.fixture(autouse=True)
def _quiesce_obs():
    """Leave the process-global tracer/registry clean for other tests."""
    yield
    disable_tracing()
    get_tracer().clear()
    reset_metrics()


# -- registry ------------------------------------------------------------------

def test_registry_get_or_create_and_label_series():
    reg = MetricsRegistry()
    a = reg.counter("flushes", component="committer")
    b = reg.counter("flushes", component="committer")
    assert a is b                       # same (name, labels) -> same object
    c = reg.counter("flushes", component="scheduler")
    assert c is not a                   # labels distinguish series
    a.inc(3)
    c.inc()
    assert reg.value("flushes", component="committer") == 3
    assert reg.total("flushes") == 4    # across every label combination
    assert reg.value("never_touched") == 0   # absent -> 0, not KeyError


def test_registry_reset_zeroes_in_place():
    reg = MetricsRegistry()
    held = reg.counter("x").inc(7)
    g = reg.gauge("y").set(1.5)
    h = reg.histogram("z").record(10.0)
    reg.reset()
    # the objects callers hold onto survive and read zero
    assert held is reg.counter("x") and held.value == 0
    assert g.value == 0.0
    assert h.count == 0 and h.samples == []


def test_histogram_percentiles_and_bounded_window():
    h = Histogram("lat", window=64)
    for us in range(1, 101):
        h.record(float(us))
    assert len(h.samples) == 64         # window bounds memory...
    assert h.count == 100               # ...lifetime count does not
    assert h.total_us == sum(range(1, 101))
    assert h.max_us == 100.0
    # percentiles are over the WINDOW (recent traffic): samples 37..100
    assert 60.0 <= h.p50_us <= 75.0
    assert h.p99_us >= 99.0
    assert h.summary()["count"] == 100


WINDOW = Histogram.DEFAULT_WINDOW
BATCH_SIZES = [0, 1, WINDOW - 1, 3 * WINDOW]


def _floats(rng, n):
    # uneven magnitudes, so a sum in another order would differ
    return list(rng.lognormal(3.0, 2.0, size=n))


def _same_histogram(a: Histogram, b: Histogram):
    assert a.samples == b.samples
    assert (a.count, a.total_us, a.max_us) == (b.count, b.total_us,
                                               b.max_us)
    assert (a.p50_us, a.p99_us) == (b.p50_us, b.p99_us)


@pytest.mark.parametrize("n", BATCH_SIZES)
def test_histogram_record_many_equals_record_in_order(n):
    rng = np.random.default_rng(n)
    prior, batch = _floats(rng, WINDOW - 2), _floats(rng, n)
    one, many = Histogram("one"), Histogram("many")
    for us in prior:
        one.record(us)
        many.record(us)
    for us in batch:
        one.record(us)
    assert many.record_many(batch) is many
    _same_histogram(one, many)


@pytest.mark.parametrize("n", BATCH_SIZES)
def test_record_completions_equals_record_completion_in_order(n):
    rng = np.random.default_rng(n + 1)
    rows = [(int(rng.integers(1, 9)),
             ("ok", "not_found", "exists", "full")[int(rng.integers(4))],
             *_floats(rng, 4), int(rng.integers(0, 3)))
            for _ in range(WINDOW - 2 + n)]
    one, many = fresh_stats(2, round_cap=4), fresh_stats(2, round_cap=4)
    names = ("latency_us", "queue_us", "dispatch_us", "persist_us",
             "retry_waves")
    for i, (rounds, status, *values) in enumerate(rows):
        one.record_completion(rounds, status, **dict(zip(names, values)))
        if i < WINDOW - 2:
            many.record_completion(rounds, status,
                                   **dict(zip(names, values)))
    if n:
        many.record_completions(*zip(*rows[WINDOW - 2:]))
    assert many.completed == one.completed == len(rows)
    assert many.latencies == one.latencies
    assert list(many.by_status.items()) == list(one.by_status.items())
    for name in names:
        _same_histogram(getattr(one, name), getattr(many, name))


def test_counter_allows_corrective_negative_deltas():
    c = Counter("flushes_saved")
    c.inc(5).inc(-2)
    assert c.value == 3


# -- tracer --------------------------------------------------------------------

def test_disabled_tracer_is_the_null_singleton():
    t = SpanTracer()
    sp = t.span("anything", k=1)
    assert sp is NULL_SPAN
    with sp as s:
        s.set(ignored=True)             # no-op, no error
    assert len(t) == 0


def test_enabled_spans_record_nesting_as_parent_args():
    t = SpanTracer()
    t.enable()
    with t.span("outer", a=1):
        with t.span("inner") as sp:
            sp.set(found=3)
    events = t.events()
    assert [e["name"] for e in events] == ["inner", "outer"]  # exit order
    inner, outer = events
    assert inner["ph"] == outer["ph"] == "X"
    assert inner["args"]["parent"] == "outer"
    assert inner["args"]["found"] == 3
    assert "parent" not in outer["args"] and outer["args"]["a"] == 1
    assert inner["ts"] >= outer["ts"] >= 0
    assert span_tree(events) == {"outer": ["inner"]}


def test_ring_buffer_drops_oldest_and_counts():
    t = SpanTracer(capacity=4)
    t.enable()
    for i in range(6):
        with t.span(f"s{i}"):
            pass
    assert len(t) == 4
    assert t.dropped == 2
    assert [e["name"] for e in t.events()] == ["s2", "s3", "s4", "s5"]
    t.clear()
    assert len(t) == 0 and t.dropped == 0


def test_instant_events_record_when_enabled_only():
    t = SpanTracer()
    t.instant("off")
    assert len(t) == 0
    t.enable()
    t.instant("on", shard=2)
    (ev,) = t.events()
    assert ev["ph"] == "i" and ev["args"] == {"shard": 2}


# -- exporters -----------------------------------------------------------------

def _traced():
    t = SpanTracer()
    t.enable()
    with t.span("parent"):
        with t.span("child", n=1):
            pass
        t.instant("tick")
    return t


def test_chrome_trace_validates_and_survives_json_roundtrip(tmp_path):
    t = _traced()
    obj = json.loads(json.dumps(chrome_trace(t)))
    validate_chrome_trace(obj)
    assert obj["traceEvents"][0]["ph"] == "M"   # process_name metadata
    assert obj["otherData"]["dropped_events"] == 0
    names = [e["name"] for e in obj["traceEvents"]]
    assert {"parent", "child", "tick"} <= set(names)


def test_export_jsonl_one_event_per_line(tmp_path):
    t = _traced()
    path = export_jsonl(tmp_path / "events.jsonl", t)
    lines = path.read_text().splitlines()
    assert len(lines) == len(t)
    assert all(isinstance(json.loads(ln), dict) for ln in lines)


@pytest.mark.parametrize("bad", [
    "not a dict",
    {},                                              # no traceEvents
    {"traceEvents": [{"ph": "X", "ts": 0, "dur": 1}]},   # nameless
    {"traceEvents": [{"name": "x", "ph": "Q", "ts": 0}]},  # unknown phase
    {"traceEvents": [{"name": "x", "ph": "X", "ts": -1, "dur": 1}]},
    {"traceEvents": [{"name": "x", "ph": "X", "ts": 0}]},  # X without dur
    {"traceEvents": [{"name": "x", "ph": "i", "ts": 0, "pid": "one"}]},
])
def test_validator_rejects_malformed_traces(bad):
    with pytest.raises(ValueError):
        validate_chrome_trace(bad)


# -- adapters ------------------------------------------------------------------

def test_fold_durability_is_idempotent():
    reg = MetricsRegistry()
    stats = DurabilityStats(flushes_issued=10, flushes_saved=4, fences=3,
                            round_commits=3, ops_committed=9)
    fold_durability(stats, reg, backend="durable")
    fold_durability(stats, reg, backend="durable")   # fold twice: same
    assert reg.value("durability.flushes_issued", backend="durable") == 10
    assert reg.value("durability.flushes_per_commit",
                     backend="durable") == stats.flushes_per_commit
    assert len(reg.series("durability.flushes_issued")) == 1


def test_fold_service_covers_latency_and_shards():
    from repro.service import fresh_stats
    reg = MetricsRegistry()
    stats = fresh_stats(2, round_cap=4)
    stats.record_completion(3, "ok", latency_us=120.0)
    stats.record_completion(5, "ok", latency_us=480.0)
    fold_service(stats, reg)
    assert reg.value("service.completed") == 2
    assert reg.value("service.p99_latency_us") > 0
    assert reg.value("service.shard.rounds", shard=0) == 0
    assert reg.value("service.by_status", status="ok") == 2


# -- the committer's two ledgers ----------------------------------------------

def _mutate(backend, rounds=3, width=4, start=0):
    for r in range(start, start + rounds):
        ops = [MwCASOp([(2 * i, r, r + 1), (2 * i + 1, r, r + 1)])
               for i in range(width)]
        assert all(res.success for res in backend.execute(ops))


def test_committer_stats_and_registry_agree_exactly(tmp_path):
    reset_metrics()
    b = DurableBackend(root=tmp_path)
    _mutate(b)
    st = b.committer.stats
    assert st.flushes_issued > 0 and st.ops_committed > 0
    reg = get_registry()
    for field in ("flushes_issued", "flushes_saved", "fences",
                  "round_commits", "op_commits", "ops_committed"):
        assert reg.value(field, component="committer") == \
            getattr(st, field), field


def test_recovery_span_decomposes_and_times_itself(tmp_path):
    b = DurableBackend(root=tmp_path)
    _mutate(b)
    reset_metrics()
    enable_tracing().clear()
    try:
        b2 = b.crash()
    finally:
        disable_tracing()
    tree = span_tree(get_tracer().events())
    assert "wal.recover" in tree.get("backend.crash_recover", [])
    # the acceptance bar: recovery decomposes into >= 3 named phases
    assert len(tree["wal.recover"]) >= 3, tree["wal.recover"]
    hist = get_registry().histogram("recover_us", component="committer")
    assert hist.count >= 1 and hist.total_us > 0
    assert b2.read(0) == b.read(0)


def test_durability_stats_monotone_across_backend_crash(tmp_path):
    b = DurableBackend(root=tmp_path)
    _mutate(b)
    before = b.committer.stats
    snap = (before.flushes_issued, before.fences, before.ops_committed)
    b2 = b.crash()
    after = b2.committer.stats
    assert after is before             # the SAME ledger, carried through
    assert (after.flushes_issued, after.fences,
            after.ops_committed) == snap   # recovery bills nothing twice
    _mutate(b2, rounds=1, start=3)     # words hold 3 after the warm-up
    assert after.ops_committed > snap[2]   # and it keeps counting


# -- service-level lifecycle (satellites 1-3) ---------------------------------

def _drive(svc, n=24, key0=1):
    for i in range(n):
        svc.submit(KVOp("insert", key0 + i, i + 1), client=i % 4)
    svc.drain()


def test_service_wall_clock_percentiles(tmp_path):
    svc = KVService(2, structure="hashmap", n_buckets=64, use_kernel=False)
    _drive(svc)
    row = svc.stats.as_row()
    assert row["p99_latency_us"] >= row["p50_latency_us"] > 0
    assert svc.stats.latency_us.count == svc.stats.completed


def test_service_stats_monotone_across_crash(tmp_path):
    svc = KVService(2, structure="hashmap", backend="durable",
                    n_buckets=64, durable_root=tmp_path)
    _drive(svc)
    s = svc.stats
    steps0, sub0, done0 = s.steps, s.submitted, s.completed
    d0 = svc.durability_stats()
    svc2 = svc.crash()
    assert svc2.stats is s             # the window survives the crash
    assert (s.steps, s.submitted, s.completed) == (steps0, sub0, done0)
    d1 = svc2.durability_stats()
    for field in ("flushes_issued", "fences", "ops_committed"):
        assert getattr(d1, field) >= getattr(d0, field), field
    _drive(svc2, n=8, key0=1001)
    assert s.completed > done0 and s.steps > steps0


def test_reset_stats_zeroes_registry_window(tmp_path):
    svc = KVService(2, structure="hashmap", backend="durable",
                    n_buckets=64, durable_root=tmp_path)
    _drive(svc)
    reg = get_registry()
    assert reg.value("flushes_issued", component="committer") > 0
    svc.reset_stats()
    assert reg.value("flushes_issued", component="committer") == 0
    assert svc.stats.completed == 0
    d_mid = svc.durability_stats().flushes_issued   # cumulative ledger
    _drive(svc, n=8, key0=2001)        # the next window counts afresh
    window = reg.value("flushes_issued", component="committer")
    assert window > 0
    assert window == svc.durability_stats().flushes_issued - d_mid


def test_reset_stats_keeps_trace_cache_warm():
    svc = KVService(2, structure="hashmap", n_buckets=64, use_kernel=False)
    _drive(svc)                        # warm-up: traces the shapes
    assert svc.stats.dispatch is not None
    svc.reset_stats()
    _drive(svc, key0=101)              # fresh keys, same dispatch shapes
    assert svc.stats.dispatch is not None
    assert svc.stats.dispatch.traces == 0, \
        "reset_stats must not cool the executor's trace cache"
    assert svc.stats.dispatch.hits > 0
