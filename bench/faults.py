"""The control and the faults, planted under the served path.

Each is ``plant(svc) -> undo``: ``bench.run.run_cell`` calls it after the
load and before the warm-up, and calls ``undo()`` once the window's ops
are answered, before it reads the final table.  The benchmark's own runs
plant nothing; ``bench.control`` and the tests in ``tests/bench`` do.

- ``stale_snapshot`` is the control.  The cells state no precision, so
  the control breaks the configuration's guarantee, per-key
  linearizability in wave order: each shard compiles a wave against the
  snapshot of the wave before, the tempting shortcut of reusing (or
  copying ahead) the per-wave snapshot.  Reads then answer from state
  one wave old.
- ``frozen_state``: a step that returns its state unchanged (the stacked
  apply's new word tables are dropped; verdicts still come back).
- ``half_batch``: half of each shard's round left out of the apply and
  answered as committed.
- ``altered_answer``: a read answer altered where it is produced.
- ``skipped_persist``, for a durable deployment: every durable shard's
  ``persist`` does nothing, so its writes stay unpersisted and the crash
  after the window drops them, though every answer and the served table
  are right.
- ``unsynced_persist``, for a durable deployment: ``persist`` keeps its
  bookkeeping (the file is counted as persisted, and the program's own
  crash model would keep it) but skips its ``fsync``: the later or rarer
  flush that only the harness's own model of the medium
  (``bench.durable``) catches.

No cell runs on more than one chip, so no fault drops an exchange
between chips.
"""
from __future__ import annotations

import sys
from typing import Callable, Dict

import numpy as np


def stale_snapshot(svc) -> Callable[[], None]:
    for struct in svc.structs:
        fresh, prev = struct.snapshot, []

        def snapshot(fresh=fresh, prev=prev):
            now = fresh()
            out = prev.pop() if prev else now
            prev.append(now)
            return out

        struct.snapshot = snapshot

    def undo():
        for struct in svc.structs:
            del struct.snapshot
    return undo


def frozen_state(svc) -> Callable[[], None]:
    for b in svc.backends:
        b.set_word_table = lambda new: None

    def undo():
        for b in svc.backends:
            del b.set_word_table
    return undo


def half_batch(svc) -> Callable[[], None]:
    import jax.numpy as jnp
    import repro.service.executor as executor
    apply = executor.pmwcas_apply_stacked

    def half(words, addr, exp, des, **kw):
        addr = np.array(addr)
        real = (addr >= 0).any(axis=-1)
        for s in range(addr.shape[0]):
            rows = np.nonzero(real[s])[0]
            addr[s, rows[len(rows) // 2:]] = -1   # all-padding rows "win"
        return apply(words, jnp.asarray(addr), exp, des, **kw)

    executor.pmwcas_apply_stacked = half

    def undo():
        executor.pmwcas_apply_stacked = apply
    return undo


def altered_answer(svc) -> Callable[[], None]:
    from repro.structures import READ, StructResult
    for struct in svc.structs:
        compile_op = struct.compile_op

        def altered(op, snap, compile_op=compile_op):
            out = compile_op(op, snap)
            if (op.kind == READ and isinstance(out, StructResult)
                    and out.value is not None):
                out.value += 1
            return out

        struct.compile_op = altered

    def undo():
        for struct in svc.structs:
            del struct.compile_op
    return undo


def skipped_persist(svc) -> Callable[[], None]:
    pools = [b.pool for b in svc.backends if hasattr(b, "pool")]
    if not pools:
        raise TypeError("skipped_persist needs durable shards; this "
                        "service has none")
    for pool in pools:
        pool.persist = lambda rel: None

    def undo():
        for pool in pools:
            del pool.persist
    return undo


class _NoSync:
    """An ``os`` module whose ``fsync`` and ``fdatasync`` do nothing."""

    def __init__(self, real):
        self.real = real

    def __getattr__(self, name):
        return getattr(self.real, name)

    @staticmethod
    def fsync(fd):
        pass

    fdatasync = fsync


def unsynced_persist(svc) -> Callable[[], None]:
    modules = {sys.modules[type(b.pool).__module__]
               for b in svc.backends if hasattr(b, "pool")}
    if not modules:
        raise TypeError("unsynced_persist needs durable shards; this "
                        "service has none")
    for module in modules:
        module.os = _NoSync(module.os)

    def undo():
        for module in modules:
            module.os = module.os.real
    return undo


CONTROL = "stale_snapshot"
PLANTS: Dict[str, Callable] = {
    "stale_snapshot": stale_snapshot,
    "frozen_state": frozen_state,
    "half_batch": half_batch,
    "altered_answer": altered_answer,
    "skipped_persist": skipped_persist,
    "unsynced_persist": unsynced_persist,
}
