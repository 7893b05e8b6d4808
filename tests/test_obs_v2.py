"""repro.obs v2 — the causal-lifecycle / provenance / SLO contracts:

- every persist fence carries a ``(component, reason)`` provenance
  label (outermost frame names the business initiator, innermost the
  mechanical cause) and fences over already-clean lines are flagged
  redundant — zero on the group-commit hot path, honestly nonzero on
  the per-op protocol's conservative read barrier;
- ops carry a stable ``op_id`` from submit through requeue to
  completion, and their latency decomposes into
  ``queue_us + dispatch_us + persist_us == latency_us`` exactly;
- the SpanTracer counts EVERY dropped event (ring overflow and
  enable-time shrink) in both its own ledger and the registry
  ``spans_dropped`` counter, and an overflowed buffer still exports a
  schema-valid Chrome trace;
- SloSpecs evaluate over sliding windows with multi-window burn rates
  and the report validates against the ``SLO_<section>.json`` schema.
"""
import dataclasses
import json
import threading

import pytest

from repro.obs import (SloEngine, SloSpec, SpanTracer, chrome_trace,
                       current_flush_reason, disable_tracing,
                       enable_tracing, export_jsonl, flush_reason,
                       get_registry, get_tracer, reset_metrics, span,
                       span_tree, validate_chrome_trace,
                       validate_slo_report)
from repro.service import KVService
from repro.structures import KVOp


@pytest.fixture(autouse=True)
def _quiesce_obs():
    """Leave the process-global tracer/registry clean for other tests."""
    yield
    disable_tracing()
    get_tracer().clear()
    reset_metrics()


# -- flush provenance ----------------------------------------------------------

def test_flush_reason_outermost_component_innermost_reason():
    assert current_flush_reason() == ("pmem", "unattributed")
    with flush_reason("service", "journal_decide"):
        assert current_flush_reason() == ("service", "journal_decide")
        with flush_reason("committer", "descriptor"):
            # business initiator (outermost) + mechanical cause (innermost)
            assert current_flush_reason() == ("service", "descriptor")
        assert current_flush_reason() == ("service", "journal_decide")
    assert current_flush_reason() == ("pmem", "unattributed")


def test_flush_reason_is_thread_local():
    seen = {}

    def worker():
        seen["worker"] = current_flush_reason()

    with flush_reason("structures", "doubling_pump"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    assert seen["worker"] == ("pmem", "unattributed")


def test_pmem_redundant_fence_detection(tmp_path):
    from repro import PMemPool
    pool = PMemPool(tmp_path)
    reg = get_registry()
    with flush_reason("test", "first_write"):
        pool.write_persist("a.bin", b"x")       # dirty line: real fence
    assert reg.value("flush_fences", component="test",
                     reason="first_write") == 1
    assert reg.total("redundant_fences") == 0
    with flush_reason("test", "paranoia"):
        pool.persist("a.bin")                   # clean line: redundant
    assert reg.value("redundant_fences", component="test",
                     reason="paranoia") == 1
    # durable delete of a file that never existed is redundant too
    with flush_reason("test", "ghost_delete"):
        pool.delete_persist("never_there.bin")
    assert reg.value("redundant_fences", component="test",
                     reason="ghost_delete") == 1
    # deleting a real durable file is NOT redundant
    with flush_reason("test", "real_delete"):
        pool.delete_persist("a.bin")
    assert reg.total("redundant_fences") == 2
    assert reg.value("flush_fences", component="test",
                     reason="real_delete") == 1


def _drive_durable_service(group_commit: bool, n_ops: int = 24):
    svc = KVService(2, structure="hashmap", backend="durable",
                    n_buckets=32, round_cap=4, group_commit=group_commit)
    svc.apply([KVOp("insert", k, k + 1) for k in range(1, 13)])
    svc.reset_stats()                    # window start: registry zeroed
    for i in range(n_ops):
        svc.submit(KVOp("update", 1 + (i % 12), i + 100), client=i % 4)
    svc.drain()
    return svc


def test_group_commit_hot_path_zero_redundant_fences():
    _drive_durable_service(group_commit=True)
    reg = get_registry()
    assert reg.total("flush_fences") > 0, "window issued no fences at all"
    assert reg.total("redundant_fences") == 0, (
        "the coalesced group-commit path issued a redundant fence — "
        "the instruction class the paper removes is back")


def test_per_op_read_barrier_pays_redundant_fences_with_labels():
    _drive_durable_service(group_commit=False)
    reg = get_registry()
    assert reg.total("redundant_fences") > 0, (
        "the per-op read barrier should fence steady-state clean slot "
        "lines; the redundancy detector is dead")
    # the redundant fences are attributed to the barrier, by label
    assert reg.value("redundant_fences", component="committer",
                     reason="read_barrier") > 0
    # the taxonomy is present on the real fences too
    for reason in ("data_prepare", "reserve"):
        assert reg.value("flush_fences", component="committer",
                         reason=reason) > 0, reason


# -- op lifecycle: op_id threading + latency partition -------------------------

def test_op_lifecycle_instants_and_breakdown_identity():
    svc = KVService(2, structure="hashmap", n_buckets=32, round_cap=2,
                    use_kernel=False)
    svc.apply([KVOp("insert", k, k) for k in range(1, 9)])
    svc.reset_stats()
    enable_tracing(ops=True).clear()
    try:
        futs = [svc.submit(KVOp("update", 1 + (i % 8), i + 100), client=0)
                for i in range(12)]
        svc.drain()
    finally:
        disable_tracing()
    assert all(f.done for f in futs)
    events = get_tracer().events()
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    submits = {e["args"]["op_id"] for e in by_name["op.submit"]}
    completes = {e["args"]["op_id"] for e in by_name["op.complete"]}
    # every submitted op completed under the SAME op_id
    assert submits == completes == {f.op_id for f in futs}
    # a round_cap of 2 with 12 ops on 2 shards forces requeues; each
    # requeue instant names the op it deferred
    if "op.requeue" in by_name:
        assert {e["args"]["op_id"]
                for e in by_name["op.requeue"]} <= submits
    # the breakdown partitions latency per completion event, exactly
    # (args are rounded to 0.1us, so allow the rounding slack)
    for e in by_name["op.complete"]:
        a = e["args"]
        total = a["queue_us"] + a["dispatch_us"] + a["persist_us"]
        assert total == pytest.approx(a["latency_us"], abs=0.3)
    # and the histograms carry the same partition in aggregate
    st = svc.stats
    assert st.queue_us.count == st.latency_us.count
    parts = (st.queue_us.mean_us + st.dispatch_us.mean_us
             + st.persist_us.mean_us)
    assert parts == pytest.approx(st.latency_us.mean_us, rel=0.02)


def test_durable_service_attributes_persist_share():
    svc = _drive_durable_service(group_commit=True)
    st = svc.stats
    assert st.persist_us.count > 0
    assert st.persist_us.total_us > 0, (
        "durable waves fence to disk; the persist_us leg of the "
        "breakdown must be nonzero")
    assert (st.queue_us.mean_us + st.dispatch_us.mean_us
            + st.persist_us.mean_us) == pytest.approx(
        st.latency_us.mean_us, rel=0.02)
    # every completion recorded its persist leg in the stats histogram
    assert st.persist_us.count == st.latency_us.count == st.completed


def test_retry_waves_histogram_counts_split_retries():
    # retry_waves counts executed-and-lost rounds plus split retries
    # (scheduling defers recompile for free) — a tiny-leaf BzTree under
    # an insert burst forces splits, so some op must retry its wave
    svc = KVService(1, structure="bztree", leaf_cap=4, root_cap=16,
                    n_regions=24, round_cap=4, use_kernel=False)
    svc.reset_stats()
    for i in range(16):
        svc.submit(KVOp("insert", 10 + i, 1000 + i), client=i % 4)
    svc.drain()
    st = svc.stats
    assert st.retry_waves.count == st.completed
    assert st.retry_waves.max_us >= 1, (
        "16 inserts through 4-entry leaves must split and retry someone")
    assert st.queue_us.count == st.dispatch_us.count == st.completed


# -- SpanTracer drop accounting ------------------------------------------------

def test_ring_overflow_counts_drops_in_both_ledgers_and_exports():
    reset_metrics()
    tracer = SpanTracer(capacity=8)
    tracer.enable()
    for i in range(20):
        with tracer.span(f"s{i}"):
            pass
    assert len(tracer) == 8
    assert tracer.dropped == 12
    assert get_registry().value("spans_dropped", component="obs") == 12
    # an overflowed buffer still exports a schema-valid Chrome trace
    # that reports what it lost
    obj = chrome_trace(tracer)
    validate_chrome_trace(obj)
    assert obj["otherData"]["dropped_events"] == 12


def test_enable_shrink_counts_discarded_events():
    reset_metrics()
    tracer = SpanTracer(capacity=16)
    tracer.enable()
    for i in range(10):
        with tracer.span(f"s{i}"):
            pass
    # shrinking below the buffered count used to lose events SILENTLY;
    # now the 6 oldest land in both drop ledgers
    tracer.enable(capacity=4)
    assert len(tracer) == 4
    assert tracer.dropped == 6
    assert get_registry().value("spans_dropped", component="obs") == 6
    assert [e["name"] for e in tracer.events()] == \
        ["s6", "s7", "s8", "s9"]
    validate_chrome_trace(chrome_trace(tracer))


# -- exporters over gnarly traces ----------------------------------------------

def test_export_jsonl_round_trip(tmp_path):
    tracer = SpanTracer(capacity=64)
    tracer.enable()
    with tracer.span("outer", layer=1):
        with tracer.span("inner"):
            pass
        tracer.instant("mark", k="v")
    path = export_jsonl(tmp_path / "events.jsonl", tracer)
    lines = path.read_text().splitlines()
    parsed = [json.loads(ln) for ln in lines]
    assert parsed == tracer.events()
    # buffer order: inner closes first, instants interleave faithfully
    assert [e["name"] for e in parsed] == ["inner", "mark", "outer"]


def test_span_tree_nested_cross_thread_with_dropped_gap():
    tracer = SpanTracer(capacity=6)      # tight: the gap is real
    tracer.enable()

    def worker():
        with tracer.span("w.outer"):
            with tracer.span("w.inner"):
                pass

    with tracer.span("main.outer"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
        for i in range(6):               # push the oldest events out
            with tracer.span("main.child"):
                pass
    tree = span_tree(tracer.events())
    # nesting is per thread: the worker's stack never nests under main's
    assert tree.get("w.outer", []) == ["w.inner"] or \
        "w.outer" not in tree            # w.* may have fallen off the ring
    assert "main.child" in tree.get("main.outer", [])
    assert "w.inner" not in tree.get("main.outer", [])
    assert tracer.dropped > 0            # the gap actually happened
    validate_chrome_trace(chrome_trace(tracer))


# -- SLO engine ----------------------------------------------------------------

def test_slo_spec_kinds_and_validation():
    ceil = SloSpec("lat", "p99_us", 100.0, "ceiling")
    floor = SloSpec("tput", "ops", 10.0, "floor")
    assert ceil.violated(101.0) and not ceil.violated(100.0)
    assert floor.violated(9.0) and not floor.violated(10.0)
    with pytest.raises(ValueError):
        SloSpec("bad", "m", 1.0, "sideways")
    with pytest.raises(ValueError):
        SloSpec("bad", "m", 1.0, "ceiling", error_budget=1.0)


def test_slo_multi_window_burn_fires_only_on_both():
    spec = SloSpec("lat", "p99_us", 100.0, "ceiling", error_budget=0.25)
    eng = SloEngine([spec], short_window=4, long_window=16)
    # long history of good samples, then a short burst of violations:
    # short window burns, long window stays within budget -> still ok
    for _ in range(14):
        eng.observe({"p99_us": 50.0})
    for _ in range(2):
        eng.observe({"p99_us": 500.0})
    res = eng.evaluate()[0]
    assert res["burn_short"] >= 1.0 and res["burn_long"] < 1.0
    assert res["ok"]
    # sustained violations burn both windows -> fires
    for _ in range(16):
        eng.observe({"p99_us": 500.0})
    res = eng.evaluate()[0]
    assert res["burn_short"] >= 1.0 and res["burn_long"] >= 1.0
    assert not res["ok"]


def test_slo_missing_metric_reports_zero_evaluations():
    eng = SloEngine([SloSpec("ghost", "nope_us", 1.0, "ceiling")])
    eng.observe({"something_else": 5.0})
    res = eng.evaluate()[0]
    assert res["evaluations"] == 0 and res["ok"]
    assert "last" not in res


def test_slo_report_validates_and_rejects_malformed():
    eng = SloEngine([SloSpec("lat", "p99_us", 100.0, "ceiling",
                             error_budget=0.1)])
    eng.observe({"p99_us": 50.0})
    doc = validate_slo_report(eng.report(section="unit", quick=True))
    assert doc["section"] == "unit" and doc["observations"] == 1
    bad = json.loads(json.dumps(doc))
    bad["specs"][0]["violations"] = 99       # > evaluations
    with pytest.raises(ValueError):
        validate_slo_report(bad)
    with pytest.raises(ValueError):
        validate_slo_report({"specs": [], "ok": "yes",
                             "observations": 0,
                             "windows": {"short": 1, "long": 1}})


# -- chaos: SLOs evaluated during the fault schedule ---------------------------

def test_chaos_scenario_carries_in_run_slo_verdict(tmp_path):
    from repro.chaos import default_scenarios, run_scenario
    sc = next(s for s in default_scenarios(seed=3, waves=8)
              if s.backend == "durable")
    sc = dataclasses.replace(sc, waves=8)
    rep = run_scenario(sc, durable_root=str(tmp_path / "pm"))
    assert rep.slo is not None
    validate_slo_report(rep.slo)
    assert rep.slo["section"] == f"chaos.{sc.family}"
    evals = sum(s["evaluations"] for s in rep.slo["specs"])
    assert evals > 0, "SLOs were never evaluated during the waves"
    assert rep.slo["observations"] == rep.waves_run


def test_chaos_fault_injections_are_trace_instants(tmp_path):
    from repro.chaos import default_scenarios, run_scenario
    sc = next(s for s in default_scenarios(seed=0)
              if s.family == "hot_key_storm")
    enable_tracing().clear()
    try:
        rep = run_scenario(sc, durable_root=(
            str(tmp_path / "pm") if sc.backend == "durable" else None))
    finally:
        disable_tracing()
    assert rep.faults_fired > 0
    faults = [e for e in get_tracer().events()
              if e["name"] == "chaos.fault"]
    assert faults, "faults fired but no chaos.fault instant was traced"
    assert all(e["ph"] == "i" and "kind" in e["args"] for e in faults)
