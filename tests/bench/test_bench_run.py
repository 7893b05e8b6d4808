"""A whole benchmark run on the CPU at a tiny size: the harness minus
its look for a chip.  A sound run is correct; the control is not."""
import json
import os
import subprocess
import sys
import time

from bench import faults, run, spec

PEAKS = {"hbm_bytes_per_s": 819e9}


def _run(cell, plant=None, trace=False, seed=2 ** 31 + 17):
    return run.run_cell(cell, seed, 1.0, trace, peaks=PEAKS,
                        t_start=time.perf_counter(), plant=plant)


def test_a_sound_run_is_correct_and_reports_its_metrics(tiny_cell):
    r = _run(tiny_cell)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {m["name"] for m in tiny_cell.end_to_end}
    assert r["metrics"]["ops_per_s"]["value"] > 0
    assert list(r)[-1] == "checks"
    assert all(c["value"] == 0 for c in r["checks"].values())


def test_a_traced_run_reads_the_program_spans(tiny_cell):
    r = _run(tiny_cell, trace=True)
    assert r["correct"], r["checks"]
    # the CPU has no device plane: the device readers report nothing
    assert {"wave_compile_ms", "wave_dispatch_ms",
            "round_fill_pct"} <= set(r["metrics"])
    assert "device_idle_pct" not in r["metrics"]
    assert 0 < r["metrics"]["round_fill_pct"]["value"] <= 100


def test_answers_are_stamped_by_the_harness_not_the_program(tiny_cell):
    """The replay orders answers by the steps the harness drove: a
    program that stamps its futures with wrong waves changes nothing."""
    from repro.service import service

    complete = service.KVService._complete

    def misstamped(self, fut, *a, **kw):
        complete(self, fut, *a, **kw)
        fut.done_step, fut.seq = 0, -fut.seq

    service.KVService._complete = misstamped
    try:
        r = _run(tiny_cell)
    finally:
        service.KVService._complete = complete
    assert r["correct"], r["checks"]


def test_the_control_is_not_correct(tiny_cell):
    r = _run(tiny_cell, plant=faults.PLANTS[faults.CONTROL])
    assert not r["correct"]
    assert r["checks"]["mismatched_answers"]["value"] > 0


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-m", "bench.run", "--workload",
                        "kv4_ycsb_a_uniform", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=spec.ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_the_benchmark_names_resolve_to_files():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"], bench)
        assert cell.clients == (cell.config["shards"]
                                * cell.config["round_cap"])
    for m in bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))
    assert json.loads(json.dumps(spec.device_peaks("TPU v5 lite")))
    try:
        spec.device_peaks("TPU v0")
    except spec.SpecError:
        pass
    else:
        raise AssertionError("an unknown device kind must fail")
