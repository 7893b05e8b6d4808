"""Run one benchmark cell on the chip and print its result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  In order:

1. refuse to run without a TPU (or with fewer chips than the cell asks
   for), or on a device ``bench/peaks.json`` does not know: exit 1 and
   print no result;
2. keep JAX's persistent compilation cache in
   ``$JAX_COMPILATION_CACHE_DIR``, or in ``.jax_cache/`` of the checkout;
3. build the configuration's ``KVService`` and load every record through
   ``submit``/``step``, keeping ``shards x round_cap`` inserts queued;
4. warm up with a few waves of the cell's own closed-loop mix;
5. run the closed loop for ``--seconds`` (with ``--trace 1`` under the
   program's spans and a ``jax.profiler`` trace);
6. answer every op still in flight, then replay every answered op on the
   plain reference (``bench.reference``) and compare the final table;
7. for a durable deployment (shards that recover from a medium), crash
   the service with no barrier before it: put its root back to the bytes
   that the process's syncs made durable (``bench.durable``, the
   harness's own model, not the program's), restart the service on that
   root, recover every shard, and compare the recovered table with the
   reference's: every acknowledged write has to survive;
8. print the checks, each beside its limit, as the last lines on
   standard error, and the result as the last line of standard output.

Set-up (``setup_s``) runs from the start of the process to the end of
the warm-up, and so holds every compilation; any compilation inside the
window fails the run.  The reference, and the crash and recovery after
it, run after the window and after the device's peak memory is read,
and are counted in neither.

Whether a deployment is durable is read from its shards: where they
can recover from a medium, the service is built on a fresh directory of
the checkout, ``.bench/durable/<cell>-<pid>/``, removed after the run;
the run prints that directory's filesystem type and fails where it is
one that an ``fsync`` cannot make durable.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import array  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

from bench import devtrace, durable, reference, spec, ycsb  # noqa: E402
from bench.traced import TracedRun  # noqa: E402

sys.path.insert(0, str(spec.ROOT / "src"))

# the program's spans that idle time is attributed to (the readers see
# every span), and its stacked apply program
PROGRAM_SPANS = ("service.wave", "wave.compile", "wave.schedule",
                 "wave.dispatch", "wave.complete",
                 "executor.stacked_dispatch")
APPLY_PROGRAM = "jit_pmwcas_apply_stacked"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
# a durable deployment's medium: a directory per run under DURABLE_DIR
DURABLE_DIR = spec.ROOT / ".bench" / "durable"


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """``jax.monitoring`` listeners: XLA compilations (persistent-cache
    loads included) in all and while ``active``, and persistent-cache
    misses."""

    def __init__(self):
        self.active = False
        self.count = 0
        self.total = 0
        self.cache_misses = 0

    def __call__(self, event: str, duration_secs: float, **_kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.total += 1
            self.count += self.active

    def event(self, event: str, **_kw) -> None:
        self.cache_misses += event == CACHE_MISS_EVENT


class Answers:
    """One row per answered op: what the reference replays.  The wave is
    the harness's own count of the steps it drove, and the order the
    harness's own count of submissions: the replay takes no ordering
    from the program."""

    def __init__(self):
        self.wave = array.array("q")
        self.seq = array.array("q")
        self.kind = array.array("b")
        self.key = array.array("q")
        self.value = array.array("q")
        self.status = array.array("b")
        self.result = array.array("q")

    def __len__(self) -> int:
        return len(self.wave)

    def add(self, wave: int, seq: int, kind: int, key: int, value: int,
            result) -> None:
        self.wave.append(wave)
        self.seq.append(seq)
        self.kind.append(kind)
        self.key.append(key)
        self.value.append(value)
        self.status.append(reference.STATUS.get(result.status,
                                                reference.OTHER))
        self.result.append(reference.NO_VALUE if result.value is None
                           else result.value)

    def columns(self) -> List[np.ndarray]:
        return [np.frombuffer(a, a.typecode) for a in
                (self.wave, self.seq, self.kind, self.key, self.value,
                 self.status, self.result)]


class ClosedLoop:
    """``n`` clients, each with one op outstanding; a client draws its
    next op from the shared stream once its previous op is answered.

    The loop counts the steps it drives (``steps``) and stamps each
    answer with the step after which it first saw the op done: the
    service completes ops only inside ``step()``, so that is the wave
    that answered it."""

    def __init__(self, svc, stream: ycsb.OpStream, n: int,
                 answers: Answers):
        from repro.structures import KVOp, READ, UPDATE
        self._op = KVOp
        self._names = {ycsb.READ: READ, ycsb.UPDATE: UPDATE}
        self.svc, self.stream, self.answers = svc, stream, answers
        self.futs: List = [None] * n
        self.ops: List = [None] * n       # (kind, key, value, seq)
        self.t_submit = [0.0] * n
        self.step_marks: List[int] = []    # perf_counter_ns before a step
        self.steps = 0
        self.submitted = 0
        self.submit(list(range(n)))

    def submit(self, clients: List[int]) -> None:
        kinds, keys, values = self.stream.draw(len(clients))
        names, op_type, svc = self._names, self._op, self.svc
        clock = time.perf_counter
        seq = self.submitted
        for c, k, key, v in zip(clients, kinds.tolist(), keys.tolist(),
                                values.tolist()):
            if k != ycsb.UPDATE:
                v = 0
            op = op_type(names[k], key, v)
            self.ops[c] = (k, key, v, seq)
            seq += 1
            self.t_submit[c] = clock()
            self.futs[c] = svc.submit(op, client=c)
        self.submitted = seq

    def _step(self) -> None:
        self.svc.step()
        self.steps += 1

    def wave(self, latencies: Optional[array.array] = None,
             annotate: bool = False) -> int:
        """One service step, then every answered client's next op.
        Returns the ops answered in the step."""
        if annotate:
            from jax.profiler import TraceAnnotation
            self.step_marks.append(time.perf_counter_ns())
            with TraceAnnotation("bench.step"):
                self._step()
            with TraceAnnotation("bench.clients"):
                return self._answer(latencies)
        self._step()
        return self._answer(latencies)

    def _record(self) -> List[int]:
        """Record every op first seen done after this step; return its
        clients."""
        acked = [c for c, f in enumerate(self.futs)
                 if f is not None and f.done]
        add, futs, ops, wave = (self.answers.add, self.futs, self.ops,
                                self.steps)
        for c in acked:
            k, key, v, seq = ops[c]
            add(wave, seq, k, key, v, futs[c].result)
        return acked

    def _answer(self, latencies: Optional[array.array]) -> int:
        t = time.perf_counter()
        acked = self._record()
        if latencies is not None:
            t_submit = self.t_submit
            latencies.extend(t - t_submit[c] for c in acked)
        self.submit(acked)
        return len(acked)

    def finish(self, max_steps: int = 1000) -> None:
        """Answer every op still in flight, one step at a time; submit
        nothing new."""
        for _ in range(max_steps):
            if all(f is None for f in self.futs):
                return
            self._step()
            for c in self._record():
                self.futs[c] = None
        raise RuntimeError(f"ops still in flight after {max_steps} steps")


class WindowTrace:
    """``--trace 1``: the program's spans and a ``jax.profiler`` trace
    over the window, inside one ``bench.window`` annotation."""

    def __init__(self):
        self.spans: List = []        # (ts_us, dur_us, name), perf clock
        self.log_dir = tempfile.mkdtemp(prefix="bench_trace_")

    def __enter__(self) -> "WindowTrace":
        import jax
        from jax.profiler import ProfileOptions, TraceAnnotation
        from repro.obs import enable_tracing, get_tracer
        opts = ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        get_tracer().clear()
        enable_tracing()
        self._note = TraceAnnotation("bench.window")
        self._note.__enter__()
        return self

    def collect(self) -> None:
        """Keep every complete span the program emitted in the wave;
        drop its per-op instants."""
        from repro.obs import get_tracer
        tracer = get_tracer()
        self.spans += [(e["ts"], e["dur"], e["name"])
                       for e in tracer.events() if e.get("ph") == "X"]
        tracer.clear()

    def __exit__(self, *exc) -> bool:
        import jax
        from repro.obs import disable_tracing, get_tracer
        self._note.__exit__(*exc)
        disable_tracing()
        get_tracer().clear()
        jax.profiler.stop_trace()
        return False

    def span_ms(self) -> Dict[str, List[float]]:
        out: Dict[str, List[float]] = {}
        for _ts, dur, name in self.spans:
            out.setdefault(name, []).append(dur / 1e3)
        return out


def load(svc, keys: np.ndarray, values: np.ndarray, queued: int):
    """Insert every record through ``submit``/``step``, topping the queue
    up to ``queued`` after each step.  Returns (waves, inserts not OK)."""
    from repro.structures import INSERT, KVOp, OK
    keys_l, values_l = keys.tolist(), values.tolist()
    n, i, waves, errors = len(keys_l), 0, 0, 0
    limit = 20 * (n // max(1, queued) + 1) + 100
    pending: List = []
    while i < n or pending:
        if waves >= limit:
            raise RuntimeError(f"load did not finish in {limit} waves")
        j = min(n, i + queued - len(pending))
        pending += [svc.submit(KVOp(INSERT, keys_l[x], values_l[x]))
                    for x in range(i, j)]
        i = max(i, j)
        svc.step()
        waves += 1
        still = []
        for f in pending:
            if not f.done:
                still.append(f)
            elif f.result.status != OK:
                errors += 1
        pending = still
    return waves, errors


def crash_and_recover(medium: durable.Medium, restart: Callable,
                      state: Dict[int, int]):
    """Crash the service whose root ``medium`` watched: put the root
    back to what was synced, ``restart()`` a service on it and recover
    every shard; compare the recovered table with ``state``, every
    acknowledged write.  Returns the checks and the seconds the restart
    and recovery took."""
    reverted, dropped = medium.crash()
    say(f"crash: {reverted} files put back to their synced bytes, "
        f"{dropped} never synced dropped")
    t0 = time.perf_counter()
    svc = restart()
    for b in svc.backends:
        b.recover()
    recover_s = time.perf_counter() - t0
    items = svc.items()
    try:
        integrity_errors = int(svc.check_integrity() != items)
    except (AssertionError, RuntimeError) as e:
        say(f"recovered integrity check failed: {e}")
        integrity_errors = 1
    return {
        "recovered_mismatched_keys": (reference.differing_keys(items,
                                                               state), 0),
        "recovered_integrity_errors": (integrity_errors, 0),
    }, recover_s


def _percentile_ms(lat: array.array, q: float) -> Optional[float]:
    if not len(lat):
        return None
    return float(np.percentile(np.frombuffer(lat, "d"), q)) * 1e3


def attributed(spans: List) -> List:
    """The spans idle time is attributed to: ``PROGRAM_SPANS`` only, so
    a span nested in one of them (``wave.snapshot`` in ``wave.compile``)
    takes no idle time from it."""
    return [s for s in spans if s[2] in PROGRAM_SPANS]


def window_counters(svc) -> Dict[str, int]:
    """The window's counts beside the six fixed ones: every ``int`` field
    of the service's ``ServiceStats`` that starts at 0 (``round_cap``,
    a setting, is not one) as ``service.<field>``, and every counter and
    gauge of the program's metrics registry, summed over its labels, as
    ``registry.<name>``.  ``reset_stats()`` zeroes both at the window's
    start."""
    from repro.obs import get_registry
    stats = svc.stats
    counts = {f"service.{f.name}": getattr(stats, f.name)
              for f in dataclasses.fields(stats)
              if f.default == 0 and type(getattr(stats, f.name)) is int}
    for s in get_registry().series():
        if s.kind in ("counter", "gauge"):
            key = f"registry.{s.name}"
            counts[key] = counts.get(key, 0) + s.value
    return counts


def _read_trace(log_dir: str, loop: ClosedLoop, spans: List,
                apply_prefix: str) -> Optional[devtrace.DeviceTrace]:
    """Reduce the profiler trace of the window.  The program's spans run
    on ``perf_counter``; they are put on the trace's clock by the offset
    between each ``bench.step`` annotation and the mark taken just
    before it."""
    path = devtrace.find_xplane(log_dir)
    if path is None:
        return None
    events = devtrace.events_from_xplane(path)
    host = sorted((e for e in events
                   if not e.plane.startswith(devtrace.DEVICE_PREFIX)
                   and e.name.startswith("bench.")),
                  key=lambda e: e.start_ns)
    steps = [e for e in host if e.name == "bench.step"]
    windows = [e for e in host if e.name == "bench.window"]
    if not steps or not windows:
        return None
    window = (windows[0].start_ns, windows[0].end_ns)
    offset = statistics.median(e.start_ns - m for e, m in
                               zip(steps, loop.step_marks))
    host_spans = [(e.start_ns, e.end_ns, e.name) for e in host
                  if e.name != "bench.window"]
    host_spans += [(ts * 1e3 + offset, (ts + dur) * 1e3 + offset, name)
                   for ts, dur, name in spans]
    return devtrace.reduce(events, window, host_spans, apply_prefix)


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             peaks: Dict, t_start: float,
             plant: Optional[Callable] = None) -> Dict:
    """One run of ``cell`` on the default devices (no chip check here).

    ``plant(svc)``, where given, is called after the load and before the
    warm-up and may return an ``undo()`` called once the window's ops are
    answered: the controls and fault tests break the served path with
    it."""
    import jax
    from repro.service import KVService

    cfg = cell.config
    service_args = dict(structure=cfg["structure"], backend=cfg["backend"],
                        n_buckets=cfg["buckets_per_shard"],
                        round_cap=cfg["round_cap"])
    root = medium = None
    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    jax.monitoring.register_event_listener(compiles.event)
    try:
        svc = KVService(cfg["shards"], **service_args)
        if any(callable(getattr(b, "recover", None)) for b in svc.backends):
            # shards that recover from a medium: built again on a root in
            # the checkout, whose syncs the harness keeps, and crashed
            # after the window
            root = DURABLE_DIR / f"{cell.name}-{os.getpid()}"
            shutil.rmtree(root, ignore_errors=True)
            root.mkdir(parents=True)
            medium = durable.Medium(root).start()
            fs = durable.filesystem_type(root)
            say(f"durable medium: {root} on {fs}")

            def restart():
                return KVService(cfg["shards"], durable_root=str(root),
                                 **service_args)
            svc = restart()
        keys, values = ycsb.load_records(cfg["recordcount"], seed)
        t0 = time.perf_counter()
        load_waves, load_errors = load(svc, keys, values,
                                       cfg["shards"] * cfg["round_cap"])
        say(f"load: {len(keys)} records in {load_waves} waves, "
            f"{time.perf_counter() - t0:.3f} s, {load_errors} not OK")

        answers = Answers()
        loop = ClosedLoop(svc, ycsb.OpStream(cell.traffic,
                                             cfg["recordcount"], seed),
                          cell.clients, answers)
        undo = plant(svc) if plant is not None else None
        for _ in range(cell.traffic["warmup_waves"]):
            loop.wave()
        gc.collect()
        setup_s = time.perf_counter() - t_start
        say(f"set-up: {setup_s:.3f} s ({cell.clients} clients, "
            f"{compiles.total} compilations, {compiles.cache_misses} "
            "persistent-cache misses)")

        svc.reset_stats()
        latencies = array.array("d")
        traced = WindowTrace() if trace else None
        n0 = len(answers)
        with traced or contextlib.nullcontext():
            compiles.active = True
            waves = 0
            t0 = time.perf_counter()
            t_end = t0 + seconds
            while True:
                loop.wave(latencies, annotate=trace)
                waves += 1
                if traced:
                    traced.collect()
                if time.perf_counter() >= t_end:
                    break
            t1 = time.perf_counter()
            compiles.active = False
        n1 = len(answers)
        stats, dstats = svc.stats, svc.executor.stats
        counters = dict(waves=waves, dispatches=dstats.dispatches,
                        ops_executed=stats.ops_executed,
                        shards=cfg["shards"], round_cap=cfg["round_cap"])
        counters.update(window_counters(svc))
        retraces = dstats.traces

        loop.finish()
        if undo is not None:
            undo()
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.local_devices())

        # the plain reference, after the window and the memory reading
        t_ref = time.perf_counter()
        cols = answers.columns()
        window_rows = slice(n0, n1)
        kinds, status = cols[2][window_rows], cols[5][window_rows]
        counters["writes_ok"] = int(((kinds == ycsb.UPDATE)
                                     & (status == reference.OK)).sum())
        failed = int((status == reference.OTHER).sum())
        rep = reference.replay(dict(zip(keys.tolist(), values.tolist())),
                               *cols)
        items = svc.items()
        try:
            integrity_errors = int(svc.check_integrity() != items)
        except (AssertionError, RuntimeError) as e:
            say(f"integrity check failed: {e}")
            integrity_errors = 1
        checks = {
            "load_errors": (load_errors, 0),
            "mismatched_answers": (rep.mismatched, 0),
            "mismatched_keys": (reference.differing_keys(items, rep.state),
                                0),
            "integrity_errors": (integrity_errors, 0),
            "window_retraces": (retraces, 0),
            "window_compiles": (compiles.count, 0),
        }
        for ex in rep.examples:
            say(f"mismatch: {ex}")
        say(f"reference: {rep.checked} answers replayed, "
            f"{time.perf_counter() - t_ref:.3f} s")

        if medium is not None:
            # no barrier: a deployment's clients call none, so every
            # answered op must be durable already
            checks["volatile_medium"] = (int(fs in durable.VOLATILE_FS), 0)
            recovered, recover_s = crash_and_recover(medium, restart,
                                                     rep.state)
            checks.update(recovered)
            say(f"recover_s: {recover_s:.3f} ({cfg['shards']} shards, "
                f"{len(rep.state)} keys held by the reference)")
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)
        jax.monitoring.unregister_event_listener(compiles.event)
        if medium is not None:
            medium.stop()
            shutil.rmtree(root, ignore_errors=True)

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    metrics: Dict[str, Dict] = {}
    breakdown = None
    if not trace:
        window_s = t1 - t0
        values_now = {"ops_per_s": (n1 - n0) / window_s,
                      "p50_latency_ms": _percentile_ms(latencies, 50),
                      "p95_latency_ms": _percentile_ms(latencies, 95),
                      "setup_s": setup_s}
        for m in cell.end_to_end:
            if values_now.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values_now[m["name"]],
                                      "unit": m["unit"]}
        say(f"window: {n1 - n0} ops in {waves} waves, {window_s:.3f} s; "
            f"p99 {_percentile_ms(latencies, 99)} ms, max "
            f"{_percentile_ms(latencies, 100)} ms")
    else:
        dtrace = _read_trace(traced.log_dir, loop,
                             attributed(traced.spans), APPLY_PROGRAM)
        shutil.rmtree(traced.log_dir, ignore_errors=True)
        run = TracedRun(config=cfg, peaks=peaks, spans=traced.span_ms(),
                        counters=counters, device=dtrace)
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if dtrace is not None:
            device["busy_s"] = dtrace.busy_ns / 1e9
            device["window_s"] = dtrace.window_ns / 1e9
            breakdown = {"device_ops": [list(x) for x in dtrace.top_ops],
                         "idle_gaps": [list(x) for x in dtrace.idle_by_host]}
        say(f"traced window: {waves} waves, counters {counters}")

    correct = all(v <= limit for v, limit in checks.values())
    result = {"correct": correct, "attempted": n1 - n0 + cell.clients,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v, "limit": limit}
                        for k, (v, limit) in checks.items()}
    for k, (v, limit) in checks.items():
        say(f"check {k}: {v} (limit {limit})")
    return result


def chip_peaks(cell: spec.Cell) -> Optional[Dict]:
    """The peaks of the chip this process runs on, once the compilation
    cache is set; None (after saying why) where JAX finds no TPU or fewer
    chips than the cell asks for.  A device ``bench/peaks.json`` does not
    know raises."""
    import repro.service  # noqa: F401  (the system under test)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        say(f"no TPU with {cell.chips} chip(s): JAX sees "
            f"{len(devices)} {devices[0].platform} device(s); "
            "this benchmark runs only on the chip")
        return None
    peaks = spec.device_peaks(devices[0].device_kind)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):   # else JAX reads it
        jax.config.update("jax_compilation_cache_dir",
                          str(spec.ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return peaks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    peaks = chip_peaks(cell)
    if peaks is None:
        return 1
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      peaks=peaks, t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
