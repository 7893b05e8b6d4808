"""The yardstick's arithmetic: bytes from committed writes only, and the
reduction from a device trace to busy, idle and program time."""
import pytest

from bench import devtrace, spec, work
from bench.devtrace import Event
from bench.traced import TracedRun

DEV = "/device:TPU:0"


def test_write_bytes_count_one_row_and_its_cells_per_write():
    assert work.write_bytes(0, 2) == 0
    # one committed write of a key word and a value word: 2 cells, 1 row
    assert work.write_bytes(1, 2) == 2 * work.CELL_BYTES + work.ROW_BYTES
    assert work.write_bytes(4, 2) == 4 * (2 * 20 + 1)


def _trace():
    ops = [(0, 10), (5, 15), (20, 30), (95, 120)]     # ns
    events = [Event(DEV, "XLA Ops", f"%op{i} = u32[8] add(...)", s, e - s)
              for i, (s, e) in enumerate(ops)]
    events += [Event(DEV, "XLA Modules", "jit_pmwcas_apply_stacked(7)", 0, 15),
               Event(DEV, "XLA Modules", "jit_pmwcas_apply_stacked(7)", 20, 10),
               Event(DEV, "XLA Modules", "jit_other(1)", 95, 25),
               Event("/host:CPU", "python", "bench.step", 0, 40)]
    return events


def test_busy_idle_and_program_time_of_a_synthetic_trace():
    events = _trace()
    ops = [(e.start_ns, e.end_ns) for e in events if e.line == "XLA Ops"]
    assert devtrace.busy_ns(ops, 0, 100) == 15 + 10 + 5
    assert devtrace.gaps(ops, 0, 100) == [(15, 20), (30, 95)]
    assert devtrace.module_ns(events, "jit_pmwcas_apply_stacked") == 25
    host = [(0, 40, "bench.step"), (2, 18, "wave.dispatch"),
            (60, 90, "bench.clients")]
    idle = devtrace.attribute(devtrace.gaps(ops, 0, 100), host, "loop")
    assert idle == {"wave.dispatch": 3, "bench.step": 12,
                    "bench.clients": 30, "loop": 25}
    dt = devtrace.reduce(events, (0, 100), host, "jit_pmwcas_apply_stacked")
    assert (dt.window_ns, dt.busy_ns, dt.devices, dt.apply_ns) == (
        100, 30, 1, 25)
    assert dt.top_ops[0] == ("jit_other/op3", 25e-9)
    assert dt.top_ops[1] == ("jit_pmwcas_apply_stacked/op0", 10e-9)
    assert devtrace.reduce([e for e in events if e.plane != DEV],
                           (0, 100), host, "jit") is None


def test_readers_on_known_values():
    dt = devtrace.reduce(_trace(), (0, 100), [], "jit_pmwcas_apply_stacked")
    config = spec.load_config("wordkv_1m_s4")
    peaks = {"hbm_bytes_per_s": 1e9}
    run = TracedRun(config=config, peaks=peaks,
                    spans={"wave.compile": [2.0, 4.0]},
                    counters=dict(waves=2, dispatches=2, ops_executed=512,
                                  shards=4, round_cap=128, writes_ok=10),
                    device=dt)

    def read(name):
        return spec.metric_reader(name)(run)

    assert read("wave_compile_ms") == 3.0
    assert read("wave_dispatch_ms") is None          # nothing to read
    assert read("round_fill_pct") == pytest.approx(50.0)
    assert read("device_idle_pct") == pytest.approx(70.0)
    # 10 writes x 41 B at 1 GB/s = 410 ns, over 25 ns of apply: too fast
    # for that peak, which is what a reading above 100% would mean
    assert read("apply_roofline") == pytest.approx(100 * 410 / 25)
    run.device = None
    assert read("device_idle_pct") is None
    assert read("apply_roofline") is None


def test_a_recorded_cpu_trace_reads_back(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileOptions, TraceAnnotation
    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with TraceAnnotation("bench.window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = devtrace.events_from_xplane(devtrace.find_xplane(tmp_path))
    notes = [e for e in events if e.name == "bench.window"]
    assert len(notes) == 1 and notes[0].dur_ns > 0
