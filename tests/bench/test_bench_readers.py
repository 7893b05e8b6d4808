"""Every span and counter the program emits in the window reaches the
per-layer readers, with no edit to the harness; the readers of the
wave's snapshot, per-op compile and completion; and idle attribution
kept to the wave's own spans."""
import dataclasses
import time
from types import SimpleNamespace

import pytest

from bench import devtrace, run, spec
from bench.devtrace import Event
from bench.traced import TracedRun

PEAKS = {"hbm_bytes_per_s": 819e9}
HARNESS = spec.ROOT / "bench" / "run.py"


def test_a_new_span_and_counter_reach_a_reader_without_a_harness_edit(
        tiny_cell, monkeypatch):
    from repro.obs import get_registry, span
    from repro.service import KVService

    step = KVService.step

    def step_with_new_probes(self, *a, **kw):
        with span("test.new_span"):
            out = step(self, *a, **kw)
        get_registry().counter("test_new_counter", component="test").inc(2)
        return out

    monkeypatch.setattr(KVService, "step", step_with_new_probes)
    seen = {}

    def new_reader(traced):
        seen["run"] = traced
        return traced.registry_total("test_new_counter")

    metric_reader = spec.metric_reader
    monkeypatch.setattr(spec, "metric_reader", lambda name: (
        new_reader if name == "test_new_metric" else metric_reader(name)))
    cell = dataclasses.replace(tiny_cell, per_layer=tiny_cell.per_layer + [
        {"name": "test_new_metric", "unit": "1"}])
    harness = HARNESS.read_bytes()
    r = run.run_cell(cell, 2 ** 31 + 53, 1.0, True, peaks=PEAKS,
                     t_start=time.perf_counter())
    assert HARNESS.read_bytes() == harness
    assert r["correct"], r["checks"]
    traced = seen["run"]
    waves = traced.counters["waves"]
    # zeroed at the window's start, so both count the window's steps
    assert r["metrics"]["test_new_metric"]["value"] == 2 * waves
    assert len(traced.spans["test.new_span"]) == waves
    assert "wave.snapshot" in traced.spans
    assert traced.counters["service.steps"] == waves


def test_the_service_counters_reach_the_readers_under_their_names(
        tiny_cell, monkeypatch):
    seen = {}
    reader = spec.metric_reader("op_compile_us")

    def spy(traced):
        seen["run"] = traced
        return reader(traced)

    metric_reader = spec.metric_reader
    monkeypatch.setattr(spec, "metric_reader", lambda name: (
        spy if name == "op_compile_us" else metric_reader(name)))
    r = run.run_cell(tiny_cell, 2 ** 31 + 59, 1.0, True, peaks=PEAKS,
                     t_start=time.perf_counter())
    c = seen["run"].counters
    # the six keys the first readers read keep their names
    assert {"waves", "dispatches", "ops_executed", "shards", "round_cap",
            "writes_ok"} <= set(c)
    assert c["service.ops_compiled"] >= c["service.completed"] > 0
    assert c["service.compile_ns"] > 0 and c["service.complete_ns"] > 0
    # lists, dicts, histograms and the round_cap setting stay out
    assert not {"service.latencies", "service.by_status",
                "service.latency_us", "service.shards",
                "service.round_cap"} & set(c)
    for name in ("wave_snapshot_ms", "op_compile_us", "op_complete_us"):
        assert r["metrics"][name]["value"] > 0


def _traced(spans=None, counters=None):
    return TracedRun(config={}, peaks={}, spans=spans or {},
                     counters=counters or {}, device=None)


def _read(name, traced):
    return spec.metric_reader(name)(traced)


def test_the_wave_readers_on_known_values():
    traced = _traced(
        spans={"wave.snapshot": [1.0, 2.0, 3.0], "wave.compile": [9.0]},
        counters={"waves": 2, "service.compile_ns": 12_000,
                  "service.ops_compiled": 4, "service.complete_ns": 9_000,
                  "service.completed": 3})
    assert _read("wave_snapshot_ms", traced) == 3.0      # 6 ms / 2 waves
    assert _read("op_compile_us", traced) == 3.0         # 12 us / 4 ops
    assert _read("op_complete_us", traced) == 3.0        # 9 us / 3 ops


@pytest.mark.parametrize("name,spans,counters", [
    ("wave_snapshot_ms", {"wave.snapshot": [1.0]}, {"waves": 0}),
    ("wave_snapshot_ms", {}, {"waves": 3}),
    ("op_compile_us", {}, {"service.compile_ns": 5,
                           "service.ops_compiled": 0}),
    ("op_complete_us", {}, {"service.complete_ns": 5,
                            "service.completed": 0}),
    ("op_complete_us", {}, {}),
])
def test_a_wave_reader_on_a_zero_denominator_reads_nothing(name, spans,
                                                           counters):
    assert _read(name, _traced(spans, counters)) is None


@dataclasses.dataclass
class _Stats:
    """A ``ServiceStats`` in small: a setting, a count and a list."""
    round_cap: int
    steps: int = 0
    latencies: list = dataclasses.field(default_factory=list)


def test_the_window_counters_sum_a_registry_name_over_its_labels(
        monkeypatch):
    from repro.obs import metrics
    registry = metrics.MetricsRegistry()
    registry.counter("fences", component="committer", shard=0).inc(3)
    registry.counter("fences", component="committer", shard=1).inc(4)
    registry.gauge("depth", shard=0).set(9)
    registry.histogram("waits_us").record(5.0)
    monkeypatch.setattr("repro.obs.get_registry", lambda: registry)
    c = run.window_counters(SimpleNamespace(stats=_Stats(16, steps=2)))
    assert c == {"service.steps": 2, "registry.fences": 7,
                 "registry.depth": 9}
    traced = _traced(counters=c)
    assert traced.registry_total("fences") == 7
    assert traced.registry_total("absent") == 0



def test_a_nested_span_takes_no_idle_time_from_the_wave_spans():
    dev = "/device:TPU:0"
    events = [Event(dev, "XLA Ops", "%a = add(...)", 0, 10),
              Event(dev, "XLA Ops", "%b = add(...)", 90, 10)]
    # perf-clock spans as the window collects them: (ts_us, dur_us, name)
    spans = [(0.0, 0.1, "service.wave"), (0.01, 0.06, "wave.compile"),
             (0.02, 0.02, "wave.snapshot"), (0.07, 0.02, "wave.complete")]

    def breakdown(kept):
        host = [(ts * 1e3, (ts + dur) * 1e3, name) for ts, dur, name in kept]
        return devtrace.reduce(events, (0, 100), host, "jit_x").idle_by_host

    wave_only = [s for s in spans if s[2] != "wave.snapshot"]
    assert breakdown(run.attributed(spans)) == breakdown(wave_only)
    assert dict(breakdown(wave_only)) == pytest.approx(
        {"wave.compile": 60e-9, "wave.complete": 20e-9})
    # kept, the nested span would have taken 20 ns from wave.compile
    assert dict(breakdown(spans))["wave.snapshot"] == pytest.approx(20e-9)
