"""KVService: many logical clients on sharded persistent structures.

The service front for the structures layer: S shards, each owning its
own backend instance (built through the ``repro.pmwcas`` factory hooks)
and its own structure partition (:class:`repro.structures.HashMap` or
:class:`repro.structures.BzTreeIndex`).  Keys are routed by
multiplicative hash, so every logical op is shard-local by construction
— cross-shard atomicity only arises at the raw-op layer
(:class:`repro.service.BatchScheduler`), never for single-key KV ops.

Execution is the structures' snapshot-compile/round-execute loop lifted
across shards: each ``step`` compiles every shard's pending ops against
that shard's snapshot, forms ONE conflict-free round per shard (the
conflict-defer rule: duplicate-target ops wait a round instead of
executing to certain failure), and runs all shard rounds in a single
wave — kernel shards through the stacked vmapped dispatch, so S rounds
cost one device call.  CAS losers recompile against the next snapshot;
tree shards run the split protocol between waves, exactly like
``BzTreeIndex.apply`` does between rounds.
"""
from __future__ import annotations

import operator
import pathlib
import time
from typing import Callable, Dict, List, Optional, Sequence, Union

from repro import PMemPool
from repro.obs import (flush_reason, instant, op_tracing, reset_metrics,
                       span, tracing_enabled)
from repro.pmwcas import Backend, make_backend
from repro.structures import (BzTreeIndex, DELETE, EXHAUSTED, FULL, HashMap,
                              INSERT, KVOp, NeedsResize, NeedsSplit, OK,
                              OutOfRegions, SCAN, StructResult)

from .executor import (DispatchStats, RowBatch, StackedKernelExecutor,
                       execute_wave, schedule_wave)
from .journal import MIG_MIGRATING, MIG_ROUTED, MigrationLog
from .router import ShardRouter
from .stats import ServiceStats, collect_durability, fresh_stats


class KVFuture:
    """Client handle for one submitted logical op."""

    __slots__ = ("op", "client", "shard", "seq", "op_id", "submit_step",
                 "submit_ns", "done", "done_step", "result")

    def __init__(self, op: KVOp, client, shard: int, seq: int,
                 submit_step: int):
        self.op = op
        self.client = client
        self.shard = shard
        self.seq = seq
        # the stable causal identity: every trace event of this op's
        # lifecycle (submit -> defer/requeue -> dispatch -> complete)
        # carries it, so the timeline reassembles from the trace alone
        self.op_id = f"kv{seq}"
        self.submit_step = submit_step
        self.submit_ns = time.perf_counter_ns()
        self.done = False
        # the wave that DECIDED the op (epoch mode can ack later than it
        # decides; history checkers need the decision wave)
        self.done_step: Optional[int] = None
        self.result: Optional[StructResult] = None

    @property
    def status(self) -> Optional[str]:
        return self.result.status if self.done else None

    def __repr__(self) -> str:
        state = f"done {self.result.status}" if self.done else "pending"
        return f"KVFuture(client={self.client}, shard={self.shard}, {state})"


_OP = operator.attrgetter("future.op")
_ATTEMPTS = operator.attrgetter("attempts")
_KIND = operator.attrgetter("kind")


class _PendingKV:
    """Queue entry: a future and its retry count.  A wave's compiled CAS
    travels beside it, as its row in the shard's :class:`RowBatch`.

    ``attempts`` counts EXECUTED-and-lost CAS rounds plus split retries —
    not waves spent queued behind the round cap.  Queue delay is latency,
    not failure; only genuine retry churn can exhaust an op.
    """

    __slots__ = ("future", "attempts")

    def __init__(self, future: KVFuture):
        self.future = future
        self.attempts = 0


class _Migration:
    """One in-flight key-range migration (service-side state; the
    durable truth is the :class:`MigrationLog` record)."""

    __slots__ = ("mig_id", "lo", "hi", "dst", "held", "start_step",
                 "start_ns")

    def __init__(self, mig_id: str, lo: int, hi: int, dst: int,
                 start_step: int):
        self.mig_id = mig_id
        self.lo = lo
        self.hi = hi
        self.dst = dst
        self.held: List[_PendingKV] = []     # ops parked until the swing
        self.start_step = start_step
        self.start_ns = time.perf_counter_ns()

    def covers(self, key: int) -> bool:
        return self.lo <= key < self.hi


class KVService:
    """Sharded, batched KV execution service (see module docstring).

    ``backend`` is a registered backend kind (``"kernel"``/``"durable"``/
    custom), a factory callable, or a list of pre-built per-shard
    backends.  ``structure`` selects the per-shard partition type:
    ``"hashmap"`` (sized by ``n_buckets`` per shard) or ``"bztree"``
    (sized by ``leaf_cap``/``root_cap``/``n_regions`` per shard).
    """

    def __init__(self, n_shards: int, *,
                 structure: str = "hashmap",
                 backend: Union[str, Callable[..., Backend],
                                Sequence[Backend]] = "kernel",
                 n_buckets: int = 64, max_doublings: int = 0,
                 leaf_cap: int = 4, root_cap: int = 8, n_regions: int = 8,
                 round_cap: int = 16, max_op_rounds: Optional[int] = None,
                 durable_root: Union[str, pathlib.Path, None] = None,
                 group_commit: bool = True,
                 epoch_rounds: int = 1, checkpoint_every: int = 0,
                 wal_prune_every: int = 0,
                 migration_pool=None, migration_chunk: int = 8,
                 use_kernel: bool = True, executor=None):
        if n_shards < 1:
            raise ValueError("need at least one shard")
        if structure not in ("hashmap", "bztree"):
            raise ValueError(f"unknown structure {structure!r}")
        self.structure = structure
        self.n_buckets = n_buckets
        self.max_doublings = max_doublings
        self.tree_shape = dict(leaf_cap=leaf_cap, root_cap=root_cap,
                               n_regions=n_regions)
        if structure == "hashmap":
            words = HashMap.words_needed(n_buckets, max_doublings)
        else:
            words = BzTreeIndex.words_needed(leaf_cap, root_cap, n_regions)
        self.words_per_shard = words
        self.router = ShardRouter(n_shards, words_per_shard=words,
                                  policy="range")
        self.epoch_rounds = max(1, int(epoch_rounds))
        self.checkpoint_every = max(0, int(checkpoint_every))
        self.backends = self._build_backends(
            backend, n_shards, words, durable_root, group_commit,
            self.epoch_rounds, self.checkpoint_every, use_kernel)
        self.structs = [self._attach(b) for b in self.backends]
        # epoch ack gate (DESIGN.md Sec. 14): batches of decisions made
        # while ANY durable shard has an open epoch are withheld here,
        # (step, dispatch_start_ns, persist_share_us, answered) in decide
        # order, until the global durability frontier passes them
        self._held: List[tuple] = []
        self._epoch_open_since: Dict[int, int] = {}
        self._epochs_closed_seen: Dict[int, int] = {}
        self.round_cap = round_cap
        self.max_op_rounds = (2 * round_cap + 8 if max_op_rounds is None
                              else max_op_rounds)
        if wal_prune_every < 0:
            raise ValueError("wal_prune_every must be >= 0")
        self.wal_prune_every = wal_prune_every
        self.executor = executor or StackedKernelExecutor(round_cap)
        self.stats: ServiceStats = fresh_stats(n_shards, round_cap)
        self._queues: List[List[_PendingKV]] = [[] for _ in range(n_shards)]
        self._seq = 0
        # online key-range migration (decide -> copy -> swing; DESIGN.md
        # Sec. 12): the durable decision log lives in its own pool so
        # its persists are crash-sweepable like any shard's
        if migration_chunk < 1:
            raise ValueError("migration_chunk must be >= 1")
        self.migration_chunk = migration_chunk
        if migration_pool is None and durable_root is not None:
            migration_pool = PMemPool(pathlib.Path(durable_root) / "miglog")
        elif isinstance(migration_pool, (str, pathlib.Path)):
            migration_pool = PMemPool(migration_pool)
        self.mig_pool = migration_pool
        self.mig_log = (MigrationLog(migration_pool)
                        if migration_pool is not None else None)
        self._migrations: List[_Migration] = []
        self._mig_seq = 0
        self._recover_migrations()

    # -- construction ----------------------------------------------------------
    @staticmethod
    def _build_backends(spec, n_shards, words, durable_root, group_commit,
                        epoch_rounds, checkpoint_every,
                        use_kernel) -> List[Backend]:
        if isinstance(spec, (list, tuple)):
            if len(spec) != n_shards:
                raise ValueError(f"{len(spec)} backends for {n_shards} "
                                 "shards")
            return list(spec)
        out = []
        for s in range(n_shards):
            if spec == "kernel":
                kw = dict(n_words=words, use_kernel=use_kernel)
            elif spec == "durable":
                root = (None if durable_root is None
                        else pathlib.Path(durable_root) / f"shard{s}")
                kw = dict(root=root, group_commit=group_commit,
                          epoch_rounds=epoch_rounds,
                          checkpoint_every=checkpoint_every)
            else:                       # sim / custom kind / factory
                kw = dict(n_words=words)
            out.append(make_backend(spec, **kw))
        return out

    def _attach(self, backend: Backend):
        if self.structure == "hashmap":
            return HashMap(backend, self.n_buckets,
                           max_doublings=self.max_doublings)
        return BzTreeIndex(backend, **self.tree_shape)

    # -- submission ------------------------------------------------------------
    def submit(self, op: KVOp, client=0) -> KVFuture:
        shard = self.router.shard_of_key(op.key)
        fut = KVFuture(op, client, shard, self._seq, self.stats.steps)
        self._seq += 1
        self.stats.submitted += 1
        if op_tracing():
            instant("op.submit", op_id=fut.op_id, client=client,
                    shard=shard, kind=op.kind, step=self.stats.steps)
        mig = self._covering_migration(op)
        if mig is not None:
            # park until the routing swings; released ops re-route
            mig.held.append(_PendingKV(fut))
        else:
            self._queues[shard].append(_PendingKV(fut))
        return fut

    def submit_many(self, ops: Sequence[KVOp], client=0) -> List[KVFuture]:
        return [self.submit(op, client) for op in ops]

    @property
    def pending_count(self) -> int:
        # held acks count as pending: the client has no verdict yet, and
        # drain() must not return while an epoch still owes them a fence
        return sum(len(q) for q in self._queues) \
            + sum(len(m.held) for m in self._migrations) \
            + sum(len(held[3]) for held in self._held)

    # -- execution -------------------------------------------------------------
    def step(self) -> int:
        """One service wave: compile, form rounds, execute, complete —
        plus one copy chunk of every in-flight migration (the
        incremental materialize; the swing runs the wave the copy
        drains).  Returns the number of futures completed this wave."""
        if not self.pending_count and not self._migrations:
            return 0
        self.stats.steps += 1
        with span("service.wave", step=self.stats.steps) as sp:
            completed = self._execute_step()
            if self._migrations:
                self._advance_migrations()
            if (self.wal_prune_every and
                    self.stats.steps % self.wal_prune_every == 0):
                # per-shard WAL hygiene on a wave cadence (the committer
                # analogue of the scheduler's journal_prune_every):
                # without it a long-running durable service grows wal/
                # one record per committed round, forever
                self.prune_wal()
            if self._held and not any(self._queues) \
                    and not self._migrations:
                # only withheld acks remain: no further round will close
                # the epochs naturally, so pay the barrier now (this is
                # what makes drain() a durability barrier)
                self.sync_epochs()
            self._settle_epochs()
            sp.set(completed=completed)
        return completed

    def _execute_step(self) -> int:
        completed = 0
        compiled_queues: Dict[int, RowBatch] = {}
        with span("wave.compile"):
            for s in range(len(self.structs)):
                if not self._queues[s]:
                    continue
                ready, done = self._compile_shard(s)
                completed += done
                if ready:
                    compiled_queues[s] = ready
        if not compiled_queues:
            return completed
        with span("wave.schedule"):
            rounds, leftovers = schedule_wave(compiled_queues,
                                              self.round_cap, self.stats)
            # deferred ops recompile next wave (their snapshot is stale
            # by construction once this wave's round commits)
            for s, later in leftovers.items():
                self._requeue(s, later)
        with span("wave.dispatch", shards=len(rounds)):
            dispatch_start_ns = time.perf_counter_ns()
            persist_ns0 = self._persist_ns_total()
            wave = execute_wave(self.executor, self.backends, rounds,
                                self.stats)
        with span("wave.complete"):
            # this op's persist share: the wave's fence wall-clock is a
            # group property (one round record covers every winner), so
            # it splits evenly across the winners it made durable
            persist_wave_ns = self._persist_ns_total() - persist_ns0
            winners = sum(1 for pairs in wave.values()
                          for _p, ok in pairs if ok)
            persist_share_us = (persist_wave_ns / 1e3 / winners
                                if winners else 0.0)
            timed = tracing_enabled()
            for s, pairs in wave.items():
                completed += self._finish_all(
                    [(p, OK, None) for p, ok in pairs if ok], timed,
                    dispatch_start_ns=dispatch_start_ns,
                    persist_share_us=persist_share_us)
                losers = [p for p, ok in pairs if not ok]
                for pending in losers:
                    pending.attempts += 1        # recompile next wave
                self._requeue(s, losers)
        return completed

    def _finish_all(self, answered: List[tuple], timed: bool,
                    dispatch_start_ns: Optional[int] = None,
                    persist_share_us: float = 0.0) -> int:
        """Answer one batch of ``(pending, status, value)`` decided this
        wave: one shard's compile-time answers, its FULL verdicts, or
        its round winners (with the wave's ``dispatch_start_ns`` and
        per-winner ``persist_share_us``).  The epoch ack gate is checked
        once: answering changes no backend state, so every op of the
        batch meets the same gate.  While ANY durable shard has an open
        epoch the whole batch is withheld, and released in decide order
        once every shard has durably passed the deciding step.  A global
        gate (not per-shard) because cross-shard reads (scans) observe
        every shard's visible state: acking a scan before a slower
        shard's epoch closes could expose a round a crash then revokes.
        With ``timed`` (tracing enabled) the time adds to
        ``stats.complete_ns``.  Returns how many."""
        if not answered:
            return 0
        t0 = time.perf_counter_ns() if timed else 0
        step = self.stats.steps
        if any(getattr(b, "epoch_pending", 0) for b in self.backends):
            self._held.append((step, dispatch_start_ns, persist_share_us,
                               answered))
            self.stats.acks_held += len(answered)
            if op_tracing():
                for pending, status, _value in answered:
                    instant("op.ack_held", op_id=pending.future.op_id,
                            status=status, step=step)
        else:
            self._answer(answered, step, dispatch_start_ns,
                         persist_share_us)
        if timed:
            self.stats.complete_ns += time.perf_counter_ns() - t0
        return len(answered)

    def _answer(self, answered: List[tuple], decided_step: int,
                dispatch_start_ns: Optional[int],
                persist_share_us: float) -> None:
        """Complete a batch of ``(pending, status, value)`` decided in
        ``decided_step``: the one completion path, for answers the gate
        lets through and for held acks released later.  One clock read
        stamps the whole batch (no client sees an answer before
        ``step()`` returns), and the statistics are recorded in bulk.

        Each op's latency decomposes into queue (submit -> the wave's
        dispatch start), persist (the op's share of the wave's fence
        wall-clock) and dispatch (the rest); the three sum to latency_us
        exactly.  Compile-time answers (reads, EXHAUSTED, FULL) never
        reach a dispatch, so their whole latency is queueing."""
        steps = self.stats.steps
        now_ns = time.perf_counter_ns()
        complete = self._complete
        share = max(persist_share_us, 0.0)
        rows = []
        row = rows.append
        for pending, status, value in answered:
            fut = pending.future
            rounds = steps - fut.submit_step
            if rounds < 1:
                rounds = 1
            complete(fut, status, value, decided_step, rounds)
            latency_us = (now_ns - fut.submit_ns) / 1e3
            if dispatch_start_ns is None:
                row((rounds, status, latency_us, latency_us, 0.0, 0.0,
                     pending.attempts))
                continue
            # min(max(.., 0.0), ..) written out, with their tie rules
            queue_us = (dispatch_start_ns - fut.submit_ns) / 1e3
            if 0.0 > queue_us:
                queue_us = 0.0
            if latency_us < queue_us:
                queue_us = latency_us
            rest_us = latency_us - queue_us
            persist_us = rest_us if rest_us < share else share
            row((rounds, status, latency_us, queue_us, rest_us - persist_us,
                 persist_us, pending.attempts))
        stats = self.stats
        stats.record_completions(*zip(*rows))
        stats.complete_batches += 1
        if op_tracing():
            for (pending, _s, _v), (_r, status, latency_us, queue_us,
                                    dispatch_us, persist_us, retry_waves) \
                    in zip(answered, rows):
                instant("op.complete", op_id=pending.future.op_id,
                        status=status, latency_us=round(latency_us, 1),
                        queue_us=round(queue_us, 1),
                        dispatch_us=round(dispatch_us, 1),
                        persist_us=round(persist_us, 1),
                        retry_waves=retry_waves, step=steps)

    def _complete(self, fut: KVFuture, status: str, value,
                  done_step: int, rounds: int) -> None:
        """Hand one future its answer: the only per-op call of
        :meth:`_answer`, a method so that a test can substitute it."""
        fut.done = True
        fut.done_step = done_step
        fut.result = StructResult(fut.op, status, value, rounds)

    def _persist_ns_total(self) -> int:
        """Wall-clock the durable shards have spent inside persist
        fences, summed (0 for kernel/sim deployments)."""
        total = 0
        for b in self.backends:
            pool = getattr(b, "pool", None)
            if pool is not None:
                total += pool.persist_ns
        return total

    def prune_wal(self) -> int:
        """Durably drop spent descriptor records on every shard whose
        backend supports it; returns records pruned (also accumulated in
        ``stats.wal_pruned``)."""
        pruned = 0
        for b in self.backends:
            prune = getattr(b, "prune_completed", None)
            if prune is not None:
                pruned += prune()
        self.stats.wal_pruned += pruned
        return pruned

    def drain(self, max_steps: Optional[int] = None) -> int:
        """Step until no op is pending.  Per-op round budgets
        (``max_op_rounds`` -> EXHAUSTED) bound the loop."""
        limit = ((self.pending_count + 4) * (self.max_op_rounds + 2)
                 if max_steps is None else max_steps)
        done = 0
        for _ in range(limit):
            if not self.pending_count:
                break
            done += self.step()
        if self.pending_count:
            raise RuntimeError(
                f"service drain did not converge in {limit} steps")
        return done

    def apply(self, ops: Sequence[KVOp], client=0) -> List[StructResult]:
        """Synchronous convenience: submit a batch, drain, return results
        in submission order (the ``HashMap.apply`` signature, served)."""
        futs = self.submit_many(ops, client)
        self.drain()
        return [f.result for f in futs]

    # -- wave internals --------------------------------------------------------
    def _compile_shard(self, s: int):
        """Compile shard ``s``'s queue against one snapshot.  Ops past
        ``max_op_rounds`` and SCANs are answered first; the rest compile
        in the array pass (:meth:`HashMap.compile_batch`) or per op.
        Immediate results complete; split/resize requests run the
        structure's grow protocol (ops recompile next wave); CAS-compiled
        ops return for round formation as one :class:`RowBatch`."""
        struct = self.structs[s]
        if getattr(struct, "hdr", 0) and struct.migrating:
            # an in-flight directory doubling pumps a chunk per wave
            with flush_reason("structures", "doubling_pump"):
                struct.resize_step(max_moves=max(len(self._queues[s]), 2))
        with span("wave.snapshot", shard=s) as sp:
            snap = struct.snapshot()
        self.stats.snapshot_ns += sp.dur_ns
        timed = tracing_enabled()
        queue = self._queues[s]
        answered: List[tuple] = []
        if (max(map(_ATTEMPTS, queue)) > self.max_op_rounds
                or SCAN in map(_KIND, map(_OP, queue))):
            queue, answered = self._answer_aside(s, queue, snap)
        # the array pass for a hash map outside a doubling; a compile_op
        # set on the instance (a wrapper that must see every op, as
        # bench.faults.altered_answer plants) keeps the per-op path
        compile = (self._compile_rows
                   if getattr(struct.compile_op, "__func__", None)
                   is HashMap.compile_op and not struct.gen_state(snap)[1]
                   else self._compile_ops)
        ready, compiled, resizes, splits = compile(s, queue, snap, timed)
        done = self._finish_all(answered + compiled, timed)
        self._queues[s] = []
        later: List[_PendingKV] = []
        if resizes:
            # publish the doubling decision; the waiters recompile next
            # wave against the split-brain table (room is immediate: a
            # fresh generation has twice the buckets)
            with flush_reason("structures", "doubling_swing"):
                began = struct.begin_resize()
            if began:
                for pending in resizes:
                    pending.attempts += 1
                later.extend(resizes)
            else:
                done += self._finish_all(
                    [(p, FULL, None) for p in resizes], timed)
        if splits:
            # grow first; this wave's compiled ops would mostly lose
            # (the split freezes their leaf's meta), so everything on
            # this shard recompiles next wave — BzTreeIndex.apply's rule
            for leaf_base, waiters in sorted(splits.items()):
                try:
                    grew = self.structs[s].ensure_room(leaf_base)
                except OutOfRegions:
                    grew = False
                    self.stats.shards[s].out_of_regions += 1
                if grew:
                    for pending in waiters:
                        pending.attempts += 1
                    later.extend(waiters)
                else:
                    done += self._finish_all(
                        [(p, FULL, None) for p in waiters], timed)
            self._requeue(s, ready.entries + later)
            return [], done
        self._requeue(s, later)
        return ready, done

    def _answer_aside(self, s: int, queue: List[_PendingKV], snap):
        """Answer the ops no compile takes: EXHAUSTED past
        ``max_op_rounds``, and each SCAN's count over every shard
        partition (each against its own wave snapshot — disjoint key
        sets, so a plain sum).  Returns ``(the rest of the queue,
        answered)``."""
        rest: List[_PendingKV] = []
        answered: List[tuple] = []
        for pending in queue:
            op = pending.future.op
            if pending.attempts > self.max_op_rounds:
                answered.append((pending, EXHAUSTED, None))
            elif op.kind == SCAN:
                own = self.structs[s].compile_op(op, snap)
                value = own.value
                if own.status == OK:
                    value = (value or 0) + sum(
                        other.compile_op(op, other.snapshot()).value or 0
                        for s2, other in enumerate(self.structs)
                        if s2 != s)
                answered.append((pending, own.status, value))
            else:
                rest.append(pending)
        return rest, answered

    def _compile_ops(self, s: int, queue: List[_PendingKV], snap,
                     timed: bool):
        """The per-op path: ``compile_op`` on each queued op, the CASes
        packed into one :class:`RowBatch`.  Returns ``(ready, answered,
        resizes, splits)``."""
        struct = self.structs[s]
        clock = time.perf_counter_ns
        compile_ns = 0
        ready: List[_PendingKV] = []
        ops = []
        # (pending, status, value): answered here, completed once the
        # whole queue is compiled
        answered: List[tuple] = []
        splits: Dict[int, List[_PendingKV]] = {}
        resizes: List[_PendingKV] = []
        for pending in queue:
            op = pending.future.op
            if timed:
                t0 = clock()
                compiled = struct.compile_op(op, snap)
                compile_ns += clock() - t0
            else:
                compiled = struct.compile_op(op, snap)
            if isinstance(compiled, NeedsResize):
                resizes.append(pending)
            elif isinstance(compiled, StructResult):
                answered.append((pending, compiled.status, compiled.value))
            elif isinstance(compiled, NeedsSplit):
                splits.setdefault(compiled.leaf_base, []).append(pending)
            else:
                ready.append(pending)
                ops.append(compiled)
        self.stats.ops_compiled += len(queue)
        self.stats.compile_ns += compile_ns
        return RowBatch.pack(ready, ops), answered, resizes, splits

    def _compile_rows(self, s: int, queue: List[_PendingKV], snap,
                      timed: bool):
        """The array pass: the whole queue in one
        :meth:`HashMap.compile_batch`, its writes handed on as one
        :class:`RowBatch` in queue order.  ``compile_ns`` times the whole
        pass.  Returns ``(ready, answered, resizes, splits)``."""
        t0 = time.perf_counter_ns() if timed else 0
        compiled = self.structs[s].compile_batch(list(map(_OP, queue)),
                                                 snap)
        pick = queue.__getitem__
        answered = list(zip(map(pick, compiled.answered.tolist()),
                            compiled.status, compiled.value))
        ready = list(map(pick, compiled.rows.tolist()))
        resizes = list(map(pick, compiled.resize.tolist()))
        rows = RowBatch(ready, compiled.addr, compiled.exp, compiled.des)
        stats = self.stats
        stats.ops_compiled += len(queue)
        stats.ops_compiled_batched += len(queue)
        if timed:
            stats.compile_ns += time.perf_counter_ns() - t0
        return rows, answered, resizes, {}

    def _requeue(self, s: int, entries: List[_PendingKV]) -> None:
        """Merge entries back into the shard queue in submission order
        (FIFO fairness across defers, losses and recompiles)."""
        if entries:
            if op_tracing():
                for pending in entries:
                    instant("op.requeue", op_id=pending.future.op_id,
                            shard=s, attempts=pending.attempts,
                            step=self.stats.steps)
            self._queues[s].extend(entries)
            self._queues[s].sort(key=lambda p: p.future.seq)

    # -- epoch ack gate (DESIGN.md Sec. 14) ------------------------------------
    def _settle_epochs(self) -> None:
        """End-of-wave epoch bookkeeping: note which shards hold an open
        epoch (and since when), then release held acks up to the global
        durability frontier — the last step EVERY durable shard has
        durably passed.  A shard that paid a fence this wave restarts
        its open-since mark: whatever epoch is open now only holds
        rounds from this wave."""
        open_since = self._epoch_open_since
        for s, b in enumerate(self.backends):
            pending = getattr(b, "epoch_pending", 0)
            stats = getattr(getattr(b, "committer", None), "stats", None)
            closed = getattr(stats, "epochs_closed", 0)
            fenced = closed > self._epochs_closed_seen.get(s, closed)
            self._epochs_closed_seen[s] = closed
            if not pending:
                open_since.pop(s, None)
            elif fenced:
                open_since[s] = self.stats.steps
            else:
                open_since.setdefault(s, self.stats.steps)
        if self._held:
            frontier = (min(open_since.values()) - 1 if open_since
                        else None)
            self._release_held(frontier)

    def _release_held(self, frontier: Optional[int]) -> None:
        """Ack held batches whose deciding step the frontier has passed
        (``None`` = everything), in decide order, each with its own
        step, dispatch start and persist share."""
        if not self._held:
            return
        timed = tracing_enabled()
        t0 = time.perf_counter_ns() if timed else 0
        keep: List[tuple] = []
        for held in self._held:
            if frontier is None or held[0] <= frontier:
                step, dispatch_start_ns, persist_share_us, answered = held
                self._answer(answered, step, dispatch_start_ns,
                             persist_share_us)
            else:
                keep.append(held)
        self._held = keep
        if timed:
            self.stats.complete_ns += time.perf_counter_ns() - t0

    def sync_epochs(self) -> int:
        """Explicit durability barrier: close every shard's open epoch
        (one fence each) and release every withheld ack.  Returns rounds
        made durable across shards."""
        synced = 0
        for b in self.backends:
            sync = getattr(b, "sync", None)
            if sync is not None:
                synced += sync()
        if synced:
            self.stats.epoch_syncs += 1
        self._epoch_open_since.clear()
        self._release_held(None)
        return synced

    # -- online key-range migration --------------------------------------------
    def _covering_migration(self, op: KVOp) -> Optional[_Migration]:
        """The in-flight migration that must hold this op, if any.
        Scans are held by ANY migration: their count sums every shard,
        and during a copy a key is (correctly) present on two shards."""
        for m in self._migrations:
            if m.covers(op.key) or op.kind == SCAN:
                return m
        return None

    def start_migration(self, lo: int, hi: int, dst: int) -> str:
        """Decide: persist the MIGRATING record and start holding the
        range.  The copy then proceeds one chunk per ``step`` wave; the
        swing (route flip + cleanup + held-op release) runs in the wave
        the copy drains.  Returns the migration id."""
        if not lo < hi:
            raise ValueError(f"empty key range [{lo}, {hi})")
        if not 0 <= dst < len(self.structs):
            raise ValueError(f"shard {dst} out of range")
        for m in self._migrations:
            if lo < m.hi and m.lo < hi:
                raise RuntimeError(
                    f"range [{lo}, {hi}) overlaps in-flight migration "
                    f"{m.mig_id}")
        if self.mig_log is None and any(
                getattr(b, "pool", None) is not None for b in self.backends):
            # crash-capable shards without a decision log would lose the
            # route table on crash while keeping the moved keys — silent
            # misrouting; make it a loud configuration error instead
            raise ValueError(
                "durable shards need a migration decision log: pass "
                "migration_pool= or durable_root= to KVService")
        mig_id = f"mig{self._mig_seq:04d}"
        self._mig_seq += 1
        if self.mig_log is not None:
            self.mig_log.decide(mig_id, lo, hi, dst)    # decide persist
        m = _Migration(mig_id, lo, hi, dst, self.stats.steps)
        self._migrations.append(m)
        self.stats.migrations += 1
        # ops already queued for the range (and all scans) park too
        for s in range(len(self._queues)):
            keep = []
            for pending in self._queues[s]:
                op = pending.future.op
                if m.covers(op.key) or op.kind == SCAN:
                    m.held.append(pending)
                else:
                    keep.append(pending)
            self._queues[s] = keep
        m.held.sort(key=lambda p: p.future.seq)
        return mig_id

    def migrate_range(self, lo: int, hi: int, dst: int,
                      max_steps: int = 10_000) -> str:
        """Synchronous convenience: start a migration and step the
        service until it (and everything it held) completes."""
        mig_id = self.start_migration(lo, hi, dst)
        for _ in range(max_steps):
            if not any(m.mig_id == mig_id for m in self._migrations):
                return mig_id
            self.step()
        raise RuntimeError(f"migration {mig_id} did not converge in "
                           f"{max_steps} steps")

    def _advance_migrations(self) -> None:
        for m in list(self._migrations):
            with span("service.migration_chunk", mig=m.mig_id):
                copied = self._copy_chunk(m)
            if copied == 0:
                self._swing_migration(m)
                self._migrations.remove(m)

    def _copy_chunk(self, m: _Migration) -> int:
        """Materialize: copy up to ``migration_chunk`` in-range keys to
        the destination in one batched-MwCAS ``apply``.  Returns keys
        copied; 0 means the copy has drained."""
        dst_struct = self.structs[m.dst]
        already = set(dst_struct.items())
        batch: List[KVOp] = []
        for s, struct in enumerate(self.structs):
            if s == m.dst:
                continue
            for k, v in sorted(struct.items().items()):
                if m.covers(k) and k not in already:
                    batch.append(KVOp(INSERT, k, v))
                    if len(batch) >= self.migration_chunk:
                        break
            if len(batch) >= self.migration_chunk:
                break
        if not batch:
            return 0
        moved = 0
        for r in dst_struct.apply(batch):
            if r.status == FULL:
                raise RuntimeError(
                    f"migration {m.mig_id}: destination shard {m.dst} is "
                    "full — size it for the range or make it elastic")
            if r.status == OK:
                moved += 1
        self.stats.keys_moved += moved
        return len(batch)

    def _swing_migration(self, m: _Migration) -> None:
        """Swing: ROUTED record persist (the linearization point), then
        the route table, then cleanup + release.  A crash after the
        first persist rolls forward; before it, back."""
        with span("service.migration_swing", mig=m.mig_id):
            # the ROUTED record redirects reads to the destination, so
            # every copied key must be durable there FIRST — close the
            # destination's open epoch before the linearization point
            sync = getattr(self.backends[m.dst], "sync", None)
            if sync is not None:
                sync()
            if self.mig_log is not None:
                self.mig_log.mark_routed(m.mig_id)
            self.router.set_range(m.lo, m.hi, m.dst)
            if self.mig_log is not None:
                self.mig_log.save_routes(self.router.ranges)
            self._cleanup_range(m.lo, m.hi, m.dst)
            if self.mig_log is not None:
                self.mig_log.complete(m.mig_id)
        self.stats.mig_pause_waves.append(
            max(1, self.stats.steps - m.start_step))
        self.stats.mig_pause_us.record(
            (time.perf_counter_ns() - m.start_ns) / 1e3)
        # release: held ops re-route (the override now wins) and rejoin
        # the wave loop in submission order
        for pending in sorted(m.held, key=lambda p: p.future.seq):
            shard = self.router.shard_of_key(pending.future.op.key)
            pending.future.shard = shard
            self._requeue(shard, [pending])

    def _cleanup_range(self, lo: int, hi: int, dst: int) -> None:
        """Delete now-unroutable source copies of [lo, hi): in-range
        keys living where the CURRENT route table does not send them.
        At swing time that is every source copy; at recovery-redo time
        the routing check also protects keys a LATER migration has
        since legitimately moved elsewhere."""
        for s, struct in enumerate(self.structs):
            if s == dst:
                continue
            dels = [KVOp(DELETE, k) for k in sorted(struct.items())
                    if lo <= k < hi and self.router.shard_of_key(k) != s]
            if dels:
                struct.apply(dels)

    def _recover_migrations(self) -> None:
        """Redo/rollback from the decision log (constructor + crash).

        MIGRATING records roll BACK: the migration never routed, so
        in-range keys on the destination that do not route there are
        half-copied residue — delete them, drop the record.  ROUTED
        records roll FORWARD: re-install the override, re-persist the
        route table, redo the cleanup, mark COMPLETED.  Every redo step
        is idempotent, so a crash during recovery just recovers again.
        """
        if self.mig_log is None:
            return
        self.router.ranges = self.mig_log.load_routes()
        seqs = [int(r["id"][3:]) for r in self.mig_log.records()
                if r["id"].startswith("mig") and r["id"][3:].isdigit()]
        self._mig_seq = 1 + max(seqs) if seqs else 0
        pend = self.mig_log.pending()
        # install every pending ROUTED override FIRST, in decision order
        # (ids are monotone, records() sorts by them): COMPLETED marks
        # are lazy, so several routed migrations may replay at once, and
        # each cleanup below must judge against the FINAL route table —
        # an earlier record's redo must not delete keys a later
        # migration has since moved onto their rightful shard
        routed = [r for r in pend if r["state"] == MIG_ROUTED]
        for rec in routed:
            self.router.set_range(rec["lo"], rec["hi"], rec["dst"])
        if routed:
            self.mig_log.save_routes(self.router.ranges)
        for rec in pend:
            lo, hi, dst = rec["lo"], rec["hi"], rec["dst"]
            if rec["state"] == MIG_MIGRATING:
                # rollback: half-copied residue is any in-range key on
                # the destination that does not route there
                struct = self.structs[dst]
                dels = [KVOp(DELETE, k) for k in sorted(struct.items())
                        if lo <= k < hi
                        and self.router.shard_of_key(k) != dst]
                if dels:
                    struct.apply(dels)
                self.mig_log.abort(rec["id"])
            else:                                   # ROUTED: roll forward
                self._cleanup_range(lo, hi, dst)
                self.mig_log.complete(rec["id"])

    # -- reads / integrity -----------------------------------------------------
    def lookup(self, key: int) -> Optional[int]:
        key_shard = self.router.shard_of_key(key)
        return self.structs[key_shard].lookup(key)

    def items(self) -> Dict[int, int]:
        out: Dict[int, int] = {}
        for struct in self.structs:
            out.update(struct.items())
        return out

    def check_integrity(self) -> Dict[int, int]:
        """Per-shard structure invariants + the routing invariant (no
        key lives on a shard it doesn't route to).  During an in-flight
        migration the destination legitimately holds not-yet-routed
        copies of in-range keys; those are exempt from the routing and
        duplicate checks but must MATCH the source value — held writes
        guarantee the copy can never diverge."""
        out: Dict[int, int] = {}
        copies: Dict[int, int] = {}
        for s, struct in enumerate(self.structs):
            items = struct.check_integrity()
            for k, v in items.items():
                route = self.router.shard_of_key(k)
                if route != s:
                    if any(m.dst == s and m.covers(k)
                           for m in self._migrations):
                        copies[k] = v
                        continue
                    raise RuntimeError(
                        f"key {k} lives on shard {s} but routes to "
                        f"{route}")
                if k in out:
                    raise RuntimeError(f"key {k} live on two shards")
                out[k] = v
        for k, v in copies.items():
            if k in out and out[k] != v:
                raise RuntimeError(
                    f"migration copy of key {k} diverged: source holds "
                    f"{out[k]}, destination copy holds {v}")
        return out

    def gc_regions(self) -> int:
        """Region GC across every tree shard (no-op for hash maps)."""
        return sum(getattr(s, "gc_regions", lambda: 0)()
                   for s in self.structs)

    def reset_stats(self) -> None:
        """Start a fresh measurement window (e.g. after a load phase).

        The global metrics registry resets with the window (it is the
        same measurement — benchmarks read both and compare them), and
        the executor's dispatch counters reset too, but the executor's
        TRACE CACHE survives — a warmed-up service must show zero
        retraces in the new window, and that is exactly what the
        benchmark asserts."""
        self.stats = fresh_stats(len(self.backends), self.round_cap)
        if hasattr(self.executor, "stats"):
            self.executor.stats = DispatchStats()
        reset_metrics()

    def durability_stats(self):
        """Merged committer flush accounting over the durable shards
        (None when no shard is durable)."""
        return collect_durability(self.backends)

    # -- durability ------------------------------------------------------------
    def crash(self) -> "KVService":
        """Durable services only: crash every shard (drop unpersisted
        writes), recover each from its own WAL, and re-attach the
        structure partitions.  Returns the recovered service.

        The measurement window SURVIVES the crash: the recovered service
        keeps this service's ``ServiceStats`` (steps, completions,
        latency windows — all monotone across the cycle; the backends
        likewise carry their ``DurabilityStats`` through
        ``DurableBackend.crash``) and its executor, whose trace cache a
        crash has no reason to invalidate."""
        with span("service.crash_recover", shards=len(self.backends)):
            recovered = []
            for b in self.backends:
                crash = getattr(b, "crash", None)
                if crash is None:
                    raise TypeError(
                        f"backend {b.name} cannot crash/recover")
                recovered.append(crash())
            new = KVService(len(recovered), structure=self.structure,
                            backend=recovered, n_buckets=self.n_buckets,
                            max_doublings=self.max_doublings,
                            round_cap=self.round_cap,
                            max_op_rounds=self.max_op_rounds,
                            wal_prune_every=self.wal_prune_every,
                            epoch_rounds=self.epoch_rounds,
                            checkpoint_every=self.checkpoint_every,
                            migration_pool=(self.mig_pool.crash()
                                            if self.mig_pool is not None
                                            else None),
                            migration_chunk=self.migration_chunk,
                            **self.tree_shape)
            new.stats = self.stats
            new.executor = self.executor
        return new
