"""Benchmark aggregator — one section per paper table/figure plus the
framework-level benches.  Prints ``name,us_per_call,derived`` CSV and,
per section, writes a machine-readable ``BENCH_<section>.json`` (the
same rows as structured records: ops/s, CAS/op, flush/op, ... per
variant) so successive runs form a perf trajectory.

Each JSON-emitting section also runs under the span tracer and writes a
``TRACE_<section>.json`` Chrome trace (Perfetto-loadable) next to its
BENCH file — pass ``--no-trace`` to skip (e.g. when timing the benches
themselves) — plus an ``SLO_<section>.json`` burn-rate verdict: the
section's queued :func:`benchmarks.common.slo_observe` observations
replayed through the specs in :mod:`benchmarks.slo_specs` (always at
least one evaluated spec, via the per-section ``elapsed_s`` ceiling).
JAX's persistent compilation cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, else to ``.jax_cache/`` at the repository root.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only SECTION]
                                            [--json-dir DIR | --no-json]
                                            [--no-trace]
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import platform
import sys
import time


def write_section_json(directory: pathlib.Path, section: str, rows: list,
                       quick: bool, elapsed_s: float) -> pathlib.Path:
    out = {
        "section": section,
        "quick": quick,
        "elapsed_s": round(elapsed_s, 3),
        "unix_time": int(time.time()),
        "python": platform.python_version(),
        "rows": rows,
    }
    path = directory / f"BENCH_{section}.json"
    path.write_text(json.dumps(out, indent=2, sort_keys=True) + "\n")
    return path


def write_slo_json(directory: pathlib.Path, section: str,
                   observations: list, quick: bool,
                   elapsed_s: float) -> pathlib.Path:
    from repro.obs import SloEngine, validate_slo_report

    from .slo_specs import for_section
    engine = SloEngine(for_section(section))
    for obs in observations:
        engine.observe(obs)
    # every section gets the wall-clock observation, so the report always
    # carries >= 1 evaluated spec even with no explicit slo_observe calls
    engine.observe({"elapsed_s": elapsed_s})
    doc = validate_slo_report(
        engine.report(section=section, quick=quick,
                      unix_time=int(time.time())))
    path = directory / f"SLO_{section}.json"
    path.write_text(json.dumps(doc, indent=2, sort_keys=True) + "\n")
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced sweeps (CI)")
    ap.add_argument("--only", default=None,
                    help="threads|words|skew|blocks|ckpt|kernels|diff|"
                         "structs|tree|service|durable|chaos|elastic")
    ap.add_argument("--json-dir", default=".",
                    help="directory for BENCH_<section>.json (default: cwd)")
    ap.add_argument("--no-json", action="store_true",
                    help="skip the machine-readable output")
    ap.add_argument("--no-trace", action="store_true",
                    help="skip the per-section TRACE_<section>.json")
    args = ap.parse_args()

    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):  # else JAX reads it
        jax.config.update("jax_compilation_cache_dir", str(
            pathlib.Path(__file__).resolve().parents[1] / ".jax_cache"))
    from . import (bench_blocks, bench_chaos, bench_ckpt, bench_diff,
                   bench_durable, bench_elastic, bench_kernels,
                   bench_service, bench_skew, bench_structs, bench_threads,
                   bench_words, common)
    sections = {
        "threads": bench_threads.run,   # paper Figs. 9 & 10
        "words": bench_words.run,       # paper Figs. 11 & 12
        "skew": bench_skew.run,         # paper Fig. 13
        "blocks": bench_blocks.run,     # paper Fig. 14
        "ckpt": bench_ckpt.run,         # Sec. 4 insight at file granularity
        "kernels": bench_kernels.run,   # TPU-adaptation micro-benches
        "diff": bench_diff.run,         # cross-backend differential smoke
        "structs": bench_structs.run,   # lock-free structures on PMwCAS
        "tree": bench_structs.run_tree,  # multi-node BzTree index (Sec. 7)
        "service": bench_service.run,   # sharded many-client service (Sec. 8)
        "durable": bench_durable.run,   # per-op vs group commit (Sec. 9)
        "chaos": bench_chaos.run,       # fault harness + lin. check (Sec. 10)
        "elastic": bench_elastic.run,   # online growth + migration (Sec. 12)
    }
    if args.only and args.only not in sections:
        ap.error(f"unknown section {args.only!r}; "
                 f"choose from {', '.join(sections)}")
    names = [args.only] if args.only else list(sections)
    json_dir = None
    if not args.no_json:
        json_dir = pathlib.Path(args.json_dir)
        json_dir.mkdir(parents=True, exist_ok=True)
    trace = json_dir is not None and not args.no_trace
    if trace:
        from repro.obs import (disable_tracing, enable_tracing,
                               export_chrome_trace, get_tracer)
    print("name,us_per_call,derived")
    for name in names:
        print(f"# --- {name} ---", flush=True)
        common.drain_rows()                     # anything stray stays out
        common.drain_slo()
        if trace:
            enable_tracing().clear()
        t0 = time.time()
        try:
            sections[name](quick=args.quick)
        finally:
            if trace:
                disable_tracing()
        rows = common.drain_rows()
        if json_dir is not None:
            elapsed = time.time() - t0
            path = write_section_json(json_dir, name, rows, args.quick,
                                      elapsed)
            print(f"# wrote {path}", file=sys.stderr, flush=True)
            spath = write_slo_json(json_dir, name, common.drain_slo(),
                                   args.quick, elapsed)
            print(f"# wrote {spath}", file=sys.stderr, flush=True)
            if trace and len(get_tracer()):
                tpath = export_chrome_trace(
                    json_dir / f"TRACE_{name}.json")
                print(f"# wrote {tpath}", file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
