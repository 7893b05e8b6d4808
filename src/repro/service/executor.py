"""Shard-round execution engines.

One service step produces at most one CAS round per shard; the executor
runs all of those rounds "concurrently".  For kernel shards concurrency
is real data parallelism: every shard round is padded to a common
``[B, K]`` shape, the shard word tables are stacked into ``[S, W]``, and
ONE ``jax.vmap``-ped ``pmwcas_apply`` resolves every shard's round in a
single device dispatch — the batched analogue of S cores retiring their
CAS rounds in the same cycle, and the reason service throughput scales
with shard count instead of paying one dispatch per shard.

Every shard round is a :class:`RowBatch`, the compiled ops' rows.
Kernel shards stack by table width and kernel flag, a lone kernel shard
included; a shard whose backend is not a kernel (durable, sim) gets one
``execute`` call per round, with :class:`MwCASOp`\\ s built from its rows.

The stacked dispatch is CACHED, not just batched (DESIGN.md Sec. 9.2):
every distinct ``[S, B, K]`` shape fed to the jitted dispatch pays an
XLA retrace, so the executor pins all three axes — S is the FULL kernel
shard group (shards with no round this wave ride along as all-padding
rows), B is the scheduler's ``round_cap``, K is the next power of two —
and steady-state waves reuse one compiled program.  ``DispatchStats``
counts traces vs cache hits and the padding bytes the stability costs;
the stacked word tables are donated to the dispatch so the device never
holds two copies per wave.

Round FORMATION also lives here (:func:`build_rounds`): the service's
conflict-defer rule — an op whose targets collide with an op already in
this round's claim set is pushed to the NEXT round instead of being
executed-to-lose.  Under the deterministic one-shot semantics a
duplicate-target op is guaranteed to fail condition (b), so executing it
would burn batch slots and CAS work on a known outcome; deferral keeps
every submitted CAS a potential winner (the paper's fewer-CASes lever,
applied at the batching layer).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Hashable, List, Optional, Sequence, Set, Tuple

import numpy as np

from repro.obs import span
from repro.pmwcas import (Backend, KernelBackend, MwCASOp, ops_from_arrays,
                          ops_to_arrays, pmwcas_apply_stacked)


@dataclasses.dataclass
class DispatchStats:
    """Trace-cache accounting for the stacked kernel dispatch.

    ``traces`` counts dispatches whose ``[S, B, K]`` (+ table width and
    kernel flags) shape had never been seen by this executor — each one
    is an XLA recompile.  ``hits`` are dispatches served by an
    already-compiled shape; a steady-state service must retrace ZERO
    times (the bench asserts it).  ``bytes_padded`` is what shape
    stability costs: pad cells shipped to the device per dispatch
    (addr+exp+des, 4 bytes each)."""
    traces: int = 0
    hits: int = 0
    dispatches: int = 0          # stacked device calls issued
    serial_rounds: int = 0       # rounds executed by per-shard fallback
    bytes_padded: int = 0

    def as_row(self) -> Dict[str, int]:
        return dataclasses.asdict(self)


class RowBatch:
    """A shard's compiled CAS ops in array form, one row per entry: the
    one form a shard round takes from compile to the device.

    Row ``i`` of ``addr``/``exp``/``des`` (``int32``/``uint32``/``uint32``
    ``[n, K]``, the :func:`~repro.pmwcas.ops_to_arrays` wire format: real
    targets first, ``addr = -1`` padding after) is the CAS of
    ``entries[i]``.  Iterating yields the entries, so round formation and
    completion treat it as the queue it replaces; the stacked executor
    copies the rows straight into its padded arrays, and only a
    non-kernel backend's ``execute`` gets :class:`MwCASOp`\\ s, built
    from the rows by :meth:`to_ops`.
    """

    __slots__ = ("entries", "addr", "exp", "des")

    def __init__(self, entries: List, addr: np.ndarray, exp: np.ndarray,
                 des: np.ndarray):
        self.entries = entries
        self.addr = addr
        self.exp = exp
        self.des = des

    @classmethod
    def pack(cls, entries: List, ops: Sequence[MwCASOp]) -> "RowBatch":
        """``entries`` with their compiled ``ops`` as rows, at the widest
        op's K."""
        if not ops:
            return cls(entries, np.empty((0, 0), np.int32),
                       np.empty((0, 0), np.uint32),
                       np.empty((0, 0), np.uint32))
        return cls(entries, *ops_to_arrays(ops))

    def __len__(self) -> int:
        return len(self.entries)

    def __iter__(self):
        return iter(self.entries)

    def take(self, pos: np.ndarray) -> "RowBatch":
        """The entries at positions ``pos`` with their rows."""
        entries = self.entries
        return RowBatch([entries[i] for i in pos.tolist()], self.addr[pos],
                        self.exp[pos], self.des[pos])

    def to_ops(self) -> List[MwCASOp]:
        return ops_from_arrays(self.addr, self.exp, self.des)


def _bucket_pairs(addr: np.ndarray) -> bool:
    """Whether every row is one bucket's adjacent pair of words, every
    pair starting at one parity (the hash map's writes in one
    generation): two such rows share a target exactly when they share
    their first address."""
    if addr.shape[1] != 2:
        return False
    lead = addr[:, 0].astype(np.int64)
    return bool((addr[:, 1] == lead + 1).all()
                and not ((lead - lead[:1]) % 2).any())


def _first_occurrence_round(addr: np.ndarray, round_cap: int):
    """The FIFO rule over bucket-pair rows, as one pass over first
    addresses: the first row of each address is scheduled while the
    round has room, and a later row of the same address is deferred when
    that first one was scheduled and overflows when it was not."""
    lead = addr[:, 0].astype(np.int64)
    _, first, inverse = np.unique(lead, return_index=True,
                                  return_inverse=True)
    head = first[inverse.reshape(-1)]
    is_first = head == np.arange(lead.size)
    sched = is_first & (np.cumsum(is_first) <= round_cap)
    later = np.flatnonzero(~sched)
    n_defer = int((~is_first & sched[head]).sum())
    return np.flatnonzero(sched), later, n_defer, later.size - n_defer


def _claim_set_round(addr: np.ndarray, round_cap: int):
    """The FIFO rule over any rows: each row's real (>= 0) addresses
    against the round's claim set."""
    claimed: set = set()
    sched: List[int] = []
    later: List[int] = []
    n_defer = 0
    for i, row in enumerate(addr.tolist()):
        targets = {a for a in row if a >= 0}
        if targets & claimed:
            n_defer += 1           # conflict-defer wins the attribution
            later.append(i)
        elif len(sched) >= round_cap:
            later.append(i)
        else:
            claimed |= targets
            sched.append(i)
    return (np.asarray(sched, np.int64), np.asarray(later, np.int64),
            n_defer, len(later) - n_defer)


def build_rounds(queues: Dict[int, RowBatch], round_cap: int
                 ) -> Tuple[Dict[int, RowBatch], Dict[int, list],
                            Dict[int, int], Dict[int, int]]:
    """Form one conflict-free round per shard from FIFO queues.

    ``queues`` maps shard -> its :class:`RowBatch` in queue order.
    Returns ``(rounds, leftovers, defers, overflows)``: ``rounds[s]``
    the rows scheduled this round, ``leftovers[s]`` the entries to retry
    next round (conflict-deferred or over ``round_cap``, original order
    preserved), and the two defer counters per shard.  An entry that
    shares a target with one already scheduled is deferred, whether or
    not the round is full (conflict-defer wins the attribution).  Rows
    that are all bucket pairs take one first-occurrence pass; any other
    rows take the claim-set loop; both apply that one rule.
    """
    rounds: Dict[int, RowBatch] = {}
    leftovers: Dict[int, list] = {}
    defers: Dict[int, int] = {}
    overflows: Dict[int, int] = {}
    for shard, batch in queues.items():
        form = (_first_occurrence_round if _bucket_pairs(batch.addr)
                else _claim_set_round)
        pos, rest, n_defer, n_over = form(batch.addr, round_cap)
        if pos.size:
            rounds[shard] = batch.take(pos)
        if rest.size:
            leftovers[shard] = [batch.entries[i] for i in rest.tolist()]
        defers[shard] = n_defer
        overflows[shard] = n_over
    return rounds, leftovers, defers, overflows


def schedule_wave(queues: Dict[int, RowBatch], round_cap: int, stats
                  ) -> Tuple[Dict[int, RowBatch], Dict[int, list]]:
    """:func:`build_rounds` plus defer/overflow accounting into a
    :class:`~repro.service.ServiceStats` — the wave-formation step both
    the raw scheduler and the KV front run."""
    rounds, leftovers, defers, overflows = build_rounds(queues, round_cap)
    for s, n in defers.items():
        stats.shards[s].defers += n
    for s, n in overflows.items():
        stats.shards[s].overflows += n
    return rounds, leftovers


def execute_wave(executor, backends: Sequence[Backend],
                 rounds: Dict[int, RowBatch], stats
                 ) -> Dict[int, List[Tuple[object, bool]]]:
    """Run one wave of formed shard rounds and record the per-shard
    round/CAS accounting; returns ``{shard: [(entry, won)]}`` for the
    caller to complete futures / requeue losers from."""
    verdicts = executor.execute(backends, rounds)
    stats.dispatch = getattr(executor, "stats", None)
    out: Dict[int, List[Tuple[object, bool]]] = {}
    for s, entries in rounds.items():
        st = stats.shards[s]
        st.rounds += 1
        st.ops_executed += len(entries)
        pairs = []
        for ok, entry in zip(verdicts[s], entries):
            if ok:
                st.ops_won += 1
            pairs.append((entry, bool(ok)))
        out[s] = pairs
    return out


class SerialShardExecutor:
    """Reference engine: one ``backend.execute`` call per shard round,
    with the round's rows as :class:`MwCASOp`\\ s."""

    name = "serial"

    def __init__(self):
        self.stats = DispatchStats()

    def execute(self, backends: Sequence[Backend],
                rounds: Dict[int, RowBatch]) -> Dict[int, List[bool]]:
        out: Dict[int, List[bool]] = {}
        for shard, batch in rounds.items():
            with span("executor.serial_round", shard=shard, ops=len(batch)):
                verdicts = backends[shard].execute(batch.to_ops())
            out[shard] = [bool(r.success) for r in verdicts]
            self.stats.serial_rounds += 1
        return out


class StackedKernelExecutor:
    """Kernel shard rounds in ONE vmapped dispatch per kernel group (a
    lone kernel shard included); serial fallback for every other
    backend.  ``last_stacked`` records how many shard rounds the most
    recent call actually stacked (tests and benches read it).

    Every distinct stacked shape pays one XLA retrace, so the dispatch
    is pinned to SHAPE BUCKETS ``[S, B_bucket, K_bucket]``:

    - **S** is the whole kernel shard group, every wave — a shard with
      no round this wave rides along as all-padding rows rather than
      shrinking the stack (a varying S would retrace);
    - **B_bucket** is ``round_cap`` when known (rounds never exceed it),
      else the next power of two of the widest round;
    - **K_bucket** is the next power of two of the widest op.

    Padded rows/slots are ``addr = -1`` no-ops.  The stacked word-table
    temporary is donated to the dispatch (`pmwcas_apply_stacked`), and
    ``stats``/:class:`DispatchStats` counts traces vs cache hits plus
    the padding bytes bucketing ships — steady-state waves must be
    all hits.  ``shapes`` holds every ``(S, B, K, n_words, use_kernel)``
    dispatched so far.
    """

    name = "stacked"

    def __init__(self, round_cap: Optional[int] = None):
        self._serial = SerialShardExecutor()
        self.round_cap = round_cap
        self.last_stacked = 0
        self.stacked_dispatches = 0
        self.stats = DispatchStats()
        self.shapes: Set[Hashable] = set()      # mirror of XLA's trace cache

    @staticmethod
    def _group_key(backend: KernelBackend) -> Hashable:
        return (backend.n_words, backend.use_kernel)

    def execute(self, backends: Sequence[Backend],
                rounds: Dict[int, RowBatch]) -> Dict[int, List[bool]]:
        import jax.numpy as jnp
        # group EVERY kernel shard (not just those with a round this
        # wave): group membership fixes the stacked S axis
        groups: Dict[Hashable, List[int]] = {}
        rest: Dict[int, RowBatch] = {}
        for shard, b in enumerate(backends):
            if isinstance(b, KernelBackend):
                groups.setdefault(self._group_key(b), []).append(shard)
        for shard, batch in rounds.items():
            if not isinstance(backends[shard], KernelBackend):
                rest[shard] = batch
        out: Dict[int, List[bool]] = {}
        self.last_stacked = 0
        for key, shards in groups.items():
            active = [s for s in shards if s in rounds]
            if not active:
                continue
            n_words, use_kernel = key
            B = max(len(rounds[s]) for s in active)
            if self.round_cap and self.round_cap >= B:
                B = self.round_cap
            else:
                B = 1 << (B - 1).bit_length()    # capless: pow2 bucket
            widths = {s: _round_width(rounds[s]) for s in active}
            K = max(k for k, _cells in widths.values())
            K = 1 << (K - 1).bit_length()        # next power of two
            shape = (len(shards), B, K, n_words, use_kernel)
            if shape in self.shapes:
                self.stats.hits += 1
                traced = False
            else:
                self.shapes.add(shape)
                self.stats.traces += 1
                traced = True
            addr = np.full((len(shards), B, K), -1, np.int32)
            exp = np.zeros((len(shards), B, K), np.uint32)
            des = np.zeros((len(shards), B, K), np.uint32)
            for i, s in enumerate(shards):
                if s not in rounds:
                    continue
                r = rounds[s]
                n, k = len(r), widths[s][0]
                addr[i, :n, :k] = r.addr[:, :k]
                exp[i, :n, :k] = r.exp[:, :k]
                des[i, :n, :k] = r.des[:, :k]
            real_cells = sum(cells for _k, cells in widths.values())
            self.stats.bytes_padded += \
                (len(shards) * B * K - real_cells) * 3 * 4
            with span("executor.stacked_dispatch", shards=len(shards),
                      B=B, K=K, traced=traced):
                words = jnp.stack([backends[s].word_table()
                                   for s in shards])
                new, success = pmwcas_apply_stacked(
                    words, jnp.asarray(addr), jnp.asarray(exp),
                    jnp.asarray(des), use_kernel=use_kernel)
                success = np.asarray(success)
            for i, s in enumerate(shards):
                backends[s].set_word_table(new[i])
                if s in rounds:
                    out[s] = [bool(v)
                              for v in success[i, :len(rounds[s])]]
            self.last_stacked += len(active)
            self.stacked_dispatches += 1
            self.stats.dispatches += 1
        if rest:
            out.update(self._serial.execute(backends, rest))
            self.stats.serial_rounds += len(rest)
        return out


def _round_width(r: RowBatch) -> Tuple[int, int]:
    """(targets of the widest op, targets in all) of one shard round:
    its real (>= 0) addresses, which each row holds first."""
    real = r.addr >= 0
    cells = np.count_nonzero(real)
    if cells == real.size:              # no padding: every column real
        return real.shape[1], cells
    return int(real.sum(1).max()), cells
