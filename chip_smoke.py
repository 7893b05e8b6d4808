#!/usr/bin/env python3
"""Chip smoke: the sharded KV service on one TPU, end to end, through its
compiled Pallas kernel.

    python chip_smoke.py [--keys N] [--seed S]

Everything runs in this one process (a chip belongs to one process).
Phases, in order; any failure raises, so the script exits non-zero and
prints no verdict:

1. device: JAX's default device must be a TPU — there is no CPU fallback;
2. dispatch: ``KVService(4, hashmap, kernel)`` with 2**19 buckets per
   shard (2**20 uint32 words, 4 MiB per shard) is built, and its own
   stacked dispatch (``pmwcas_apply_stacked`` at the service's shard
   count, table width and round cap) compiles with the Pallas kernel
   inside it (``tpu_custom_call`` in the compiled text);
3. kernel: ``pmwcas_success_pallas`` agrees with ``pmwcas_success_ref``
   on one seeded, conflict-heavy batch of ``ROUND_CAP`` rows;
4. allocator: ``FreeListAllocator`` on the kernel grants what it grants
   on the jnp oracle, through the calls a BzTree makes;
5. load: the service takes YCSB's 1,000,000 records through
   ``submit``/``drain``;
6. run: YCSB-A (50/50 read/update, Zipf 0.99, 8 client streams); every
   stacked dispatch of phases 5 and 6 had the shape phase 2 checked.

Every op result of phases 4 and 5 is checked against a plain dict that
replays the same ops in the service's wave order (reads and immediate
verdicts see the state at the start of their wave, the wave's winning
writes apply after them); ``items()`` must equal the dict and
``check_integrity()`` must pass.  The last stdout line is the JSON
verdict ``{"ok": true, "device": {...}}``.  The lines before it (device,
bytes on device, compile and load seconds, ops answered, dispatches,
retraces) are for reading, not claims.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` for its persistent compilation
cache; when it is unset the cache goes to ``.jax_cache/`` beside this
script.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

N_SHARDS = 4
N_BUCKETS = 2 ** 19          # per shard: 2**20 words = 4 MiB
ROUND_CAP = 1024             # largest power of two <= 1024 Mosaic accepts
N_KEYS = 1_000_000           # YCSB's default record count
N_OPS = 4096
N_CLIENTS = 8
ALPHA = 0.99
LOAD_WINDOW = 3 * ROUND_CAP  # load ops in flight per submit/drain
OP_WORDS = 2                 # a hash-map insert or update: key + value
N_REGIONS = 37               # allocator phase: slots in the free list


def say(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


def require_tpu():
    """The first device JAX sees, which must be a TPU."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"no TPU found: JAX's default device is {dev.platform} "
            f"({dev.device_kind}); this smoke runs only on a TPU")
    return dev


def build_service(n_buckets: int = N_BUCKETS, round_cap: int = ROUND_CAP):
    from repro.service import KVService, StackedKernelExecutor
    svc = KVService(N_SHARDS, structure="hashmap", backend="kernel",
                    n_buckets=n_buckets, round_cap=round_cap)
    check(isinstance(svc.executor, StackedKernelExecutor),
          f"kernel shards run on {svc.executor.name}, not stacked")
    return svc


def compile_dispatch(svc) -> tuple:
    """Compile the service's own stacked dispatch — the jitted
    ``pmwcas_apply_stacked`` the executor calls, with its static
    arguments — at the service's shard count, table width and round cap,
    for rounds of ``OP_WORDS``-word ops.  Returns the executor's shape key
    for it and the compiled text."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.pmwcas_apply.ops import pmwcas_apply_stacked
    S, W = len(svc.backends), svc.backends[0].n_words
    B, K = svc.executor.round_cap, OP_WORDS
    check(all(b.n_words == W and b.use_kernel for b in svc.backends),
          "the kernel shards differ in table width or kernel use")
    sds = jax.ShapeDtypeStruct
    lowered = pmwcas_apply_stacked.lower(
        sds((S, W), jnp.uint32), sds((S, B, K), jnp.int32),
        sds((S, B, K), jnp.uint32), sds((S, B, K), jnp.uint32),
        use_kernel=True)
    return (S, B, K, W, True), lowered.compile().as_text()


def conflict_batch(B: int, K: int, seed: int):
    """A seeded batch where rows share addresses heavily (B rows of K
    distinct addresses over B words) and a quarter fail their expected
    check; padding included."""
    import numpy as np
    rng = np.random.default_rng(seed)
    words = rng.integers(0, 4, B).astype(np.uint32)
    addr = np.stack([np.sort(rng.choice(B, K, replace=False))
                     for _ in range(B)]).astype(np.int32)
    addr[rng.random((B, K)) < 0.1] = -1
    cur = words[np.maximum(addr, 0)]
    exp = np.where(rng.random((B, 1)) < 0.75, cur,
                   rng.integers(0, 4, (B, K))).astype(np.uint32)
    return addr, cur, exp


def check_kernel_verdicts(B: int, K: int, seed: int) -> dict:
    import jax.numpy as jnp
    import numpy as np
    from repro.pmwcas import pmwcas_success_pallas, pmwcas_success_ref
    addr, cur, exp = (jnp.asarray(a) for a in conflict_batch(B, K, seed))
    got = np.asarray(pmwcas_success_pallas(addr, cur, exp))
    want = np.asarray(pmwcas_success_ref(addr, cur, exp))
    check(got.shape == (B,), f"kernel verdict shape {got.shape}")
    bad = np.nonzero(got != want)[0]
    check(not len(bad), f"kernel verdicts differ from the reference at "
          f"rows {bad[:16].tolist()} ({len(bad)} rows)")
    check(0 < got.sum() < B, "degenerate batch: every row won or lost")
    return {"rows": B, "k": K, "won": int(got.sum())}


def check_allocator() -> dict:
    """Run the allocator calls a BzTree makes — single-slot allocs, a
    multi-request alloc, frees of one and of several slots, the recovery
    reserve of one slot per request (some already claimed) — on the
    kernel and on the jnp oracle, and compare every answer."""
    from repro.structures import FreeListAllocator

    def script(fl):
        out = [fl.alloc([1]) for _ in range(4)]
        out.append(fl.alloc([2, 3, 1]))
        fl.free(out[1][0])
        fl.free(out[4][1])
        out.append(fl.reserve([[s] for s in range(0, N_REGIONS, 3)]))
        return out + [fl.mask().tolist()]

    got = script(FreeListAllocator(N_REGIONS))
    want = script(FreeListAllocator(N_REGIONS, use_kernel=False))
    check(got == want, f"allocator on the kernel {got} != oracle {want}")
    return {"slots": N_REGIONS, "free_after": sum(got[-1]),
            "reserve_granted": sum(got[-2])}


def replay_waves(futures, state: dict) -> dict:
    """Check every completed future against a dict replayed in the
    service's wave order; returns the dict after the last wave."""
    from repro.structures import EXISTS, INSERT, NOT_FOUND, OK, READ, UPDATE
    state = dict(state)
    by_wave = collections.defaultdict(list)
    for f in futures:
        check(f.done, f"op {f.op_id} never completed")
        by_wave[f.done_step].append(f)
    for wave in sorted(by_wave):
        writes = {}
        for f in by_wave[wave]:
            op, r = f.op, f.result
            have = state.get(op.key)
            if op.kind == READ:
                want = (OK, have) if have is not None else (NOT_FOUND, None)
                got = (r.status, r.value)
            elif op.kind in (INSERT, UPDATE) and r.status == OK:
                check(op.key not in writes,
                      f"wave {wave} wrote key {op.key} twice")
                check((have is None) == (op.kind == INSERT),
                      f"{op} won against dict state {have}")
                writes[op.key] = op.value
                continue
            elif op.kind == INSERT:
                want, got = (EXISTS, True), (r.status, have is not None)
            elif op.kind == UPDATE:
                want, got = (NOT_FOUND, True), (r.status, have is None)
            else:
                raise RuntimeError(f"smoke sends no {op.kind} ops")
            check(got == want, f"{op} in wave {wave}: service said {got}, "
                  f"the dict says {want}")
        state.update(writes)
    return state


def run_service(svc, n_keys: int, seed: int) -> dict:
    """Phases 5 and 6 (see the module docstring); returns the numbers the
    smoke prints."""
    import jax
    from repro.structures import (LOAD, YCSB_A, client_streams,
                                  load_phase)
    out = {}
    out["device_table_bytes"] = sum(
        int(b.word_table().nbytes) for b in svc.backends)

    load = load_phase(dataclasses.replace(LOAD, n_keys=n_keys, seed=seed),
                      fraction=1.0)
    t0 = time.perf_counter()
    futures = []
    for i in range(0, len(load), LOAD_WINDOW):
        futures += svc.submit_many(load[i:i + LOAD_WINDOW])
        svc.drain()
    out["load_s"] = time.perf_counter() - t0
    out["load_waves"] = svc.stats.steps
    state = replay_waves(futures, {})
    check(len(state) == n_keys, f"load left {len(state)} of {n_keys} keys")
    out["load_traces"] = svc.executor.stats.traces
    mem = jax.devices()[0].memory_stats() or {}
    out["device_bytes_in_use"] = mem.get("bytes_in_use", "not reported")

    svc.reset_stats()
    spec = dataclasses.replace(YCSB_A, n_keys=n_keys, n_ops=N_OPS,
                               alpha=ALPHA, seed=seed + 1)
    streams = client_streams(spec, N_CLIENTS)
    t0 = time.perf_counter()
    futures = []
    for i in range(max(len(s) for s in streams)):
        for client, stream in enumerate(streams):
            if i < len(stream):
                futures.append(svc.submit(stream[i], client=client))
    svc.drain()
    out["run_s"] = time.perf_counter() - t0
    state = replay_waves(futures, state)
    check(svc.items() == state, "service items differ from the dict")
    check(svc.check_integrity() == state, "integrity check disagrees")
    st = svc.executor.stats
    out.update(ops_answered=len(futures), run_waves=svc.stats.steps,
               stacked_dispatches=st.dispatches, run_traces=st.traces)
    check(st.traces == 0, f"the run window retraced {st.traces} times")
    check(st.dispatches > 0 and st.serial_rounds == 0,
          f"kernel shards left the stacked dispatch: {st}")
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--keys", type=int, default=N_KEYS,
                    help="records loaded (a cut below 1,000,000 is printed)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    dev = require_tpu()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):  # else JAX reads it
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    say(f"device: {device}")

    svc = build_service()
    t0 = time.perf_counter()
    key, text = compile_dispatch(svc)
    S, B, K, W, _ = key
    say(f"service's stacked dispatch [S={S}, W={W}, B={B}, K={K}] "
        f"compile s: {time.perf_counter() - t0:.3f}")
    check("tpu_custom_call" in text,
          "the stacked dispatch holds no Pallas kernel (tpu_custom_call)")
    say("stacked dispatch holds the kernel: tpu_custom_call present")

    verdicts = check_kernel_verdicts(ROUND_CAP, 4, args.seed)
    say(f"kernel verdicts == pmwcas_success_ref on the chip: {verdicts}")
    alloc = check_allocator()
    say(f"allocator on the kernel == allocator on the oracle: {alloc}")

    if args.keys < N_KEYS:
        say(f"load CUT: {args.keys} keys instead of {N_KEYS}")
    out = run_service(svc, args.keys, args.seed)
    check(svc.executor.shapes == {key}, f"the service dispatched "
          f"{sorted(svc.executor.shapes)}, not only the checked {key}")
    say(f"word tables on device: {out['device_table_bytes']} bytes "
        f"({out['device_table_bytes'] / 2 ** 20:g} MiB); device bytes in "
        f"use after the load: {out['device_bytes_in_use']}")
    say(f"load: {args.keys} keys in {out['load_waves']} waves, "
        f"{out['load_s']:.3f} s, {out['load_traces']} traces; "
        "every insert OK and matched by the dict")
    say(f"YCSB-A: {out['ops_answered']} ops answered in "
        f"{out['run_waves']} waves, {out['run_s']:.3f} s, "
        f"{out['stacked_dispatches']} stacked dispatches, "
        f"{out['run_traces']} retraces; every result, items() and "
        "check_integrity() match the dict; every dispatch had the "
        "checked shape")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
