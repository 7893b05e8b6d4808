"""YCSB core-workload traffic, drawn from a seed.

Follows the YCSB core workload's semantics (``CoreWorkload``,
``UniformLongGenerator``, ``ScrambledZipfianGenerator``,
``Utils.fnvhash64``): one operation chooser over the traffic file's mix
and one key chooser over records ``1..recordcount``, shared by every
client, so all clients see one popularity (one hot set under zipfian).
Ops are drawn in fixed blocks from the seed, so a seed gives the same op
sequence whatever sizes the clients ask for.  Values are uniform 32-bit
words in ``[1, 2**32 - 2]`` (0 and ``2**32 - 1`` are the table's empty
and tombstone markers).

This module is the benchmark's own generator: it imports nothing of the
program.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

READ, UPDATE = 0, 1
KINDS = {"read": READ, "update": UPDATE}

VALUE_MAX = (1 << 32) - 2
BLOCK = 1 << 16

# ScrambledZipfianGenerator draws from a zipfian over ITEM_COUNT items
# with YCSB's precomputed zeta for the default constant, then hashes the
# rank into the record range.
ZIPF_ITEM_COUNT = 10_000_000_000
ZIPF_CONSTANT = 0.99
ZIPF_ZETAN = 26.46902820178302

FNV_OFFSET_BASIS_64 = np.uint64(0xCBF29CE484222325)
FNV_PRIME_64 = np.uint64(1099511628211)


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed & ((1 << 64) - 1), stream])


def fnvhash64(values: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64`` (FNV-1a over the 8 bytes of a long,
    then ``Math.abs``), vectorised."""
    val = np.asarray(values).astype(np.uint64)
    h = np.full(val.shape, FNV_OFFSET_BASIS_64, np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h = (h ^ (val & np.uint64(0xFF))) * FNV_PRIME_64
            val = val >> np.uint64(8)
    signed = h.view(np.int64)
    return np.abs(signed)


def zipfian_ranks(u: np.ndarray, theta: float = ZIPF_CONSTANT,
                  items: int = ZIPF_ITEM_COUNT,
                  zetan: float = ZIPF_ZETAN) -> np.ndarray:
    """YCSB ``ZipfianGenerator.nextValue`` for uniform draws ``u``:
    rank 0 is the most popular item."""
    alpha = 1.0 / (1.0 - theta)
    zeta2 = 1.0 + 0.5 ** theta
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    uz = u * zetan
    ranks = np.floor(items * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    ranks = np.where(uz < 1.0 + 0.5 ** theta, 1, ranks)
    return np.where(uz < 1.0, 0, ranks)


class OpStream:
    """The shared op sequence of one run: ``draw(n)`` hands out the next
    ``n`` ops as ``(kinds, keys, values)`` arrays."""

    def __init__(self, traffic: Dict, recordcount: int, seed: int):
        mix = {k: float(v) for k, v in traffic["mix"].items() if v}
        unknown = sorted(set(mix) - set(KINDS))
        if unknown:
            raise ValueError(f"the generator draws no {unknown} ops yet")
        self.kinds = np.array([KINDS[k] for k in mix], np.int8)
        self.probs = np.array(list(mix.values()))
        self.dist = traffic["request_distribution"]
        if self.dist == "zipfian":
            if float(traffic["zipfian_constant"]) != ZIPF_CONSTANT:
                raise ValueError("scrambled zipfian needs YCSB's default "
                                 f"constant {ZIPF_CONSTANT} (its zeta is "
                                 "precomputed)")
        elif self.dist != "uniform":
            raise ValueError(f"unknown request distribution {self.dist!r}")
        self.recordcount = recordcount
        self._rng = _rng(seed, 1)
        self._buf = (np.empty(0, np.int8), np.empty(0, np.int64),
                     np.empty(0, np.int64))
        self._pos = 0

    def keys_for(self, u: np.ndarray) -> np.ndarray:
        """Key chooser: uniform draws ``u`` in [0, 1) -> keys in
        ``1..recordcount``."""
        n = self.recordcount
        if self.dist == "uniform":
            return np.minimum((u * n).astype(np.int64), n - 1) + 1
        return fnvhash64(zipfian_ranks(u)) % n + 1

    def _block(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        r = self._rng
        kinds = self.kinds[r.choice(len(self.kinds), BLOCK, p=self.probs)]
        keys = self.keys_for(r.random(BLOCK))
        values = r.integers(1, VALUE_MAX, BLOCK, endpoint=True)
        return kinds, keys, values

    def draw(self, n: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        parts = []
        while n > 0:
            if self._pos == len(self._buf[0]):
                self._buf, self._pos = self._block(), 0
            take = min(n, len(self._buf[0]) - self._pos)
            parts.append(tuple(a[self._pos:self._pos + take]
                               for a in self._buf))
            self._pos += take
            n -= take
        if not parts:
            return (np.empty(0, np.int8), np.empty(0, np.int64),
                    np.empty(0, np.int64))
        return tuple(np.concatenate(cols) for cols in zip(*parts))


def load_records(recordcount: int, seed: int
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """The load phase's records: keys ``1..recordcount`` in order, each
    with a value drawn from the seed."""
    keys = np.arange(1, recordcount + 1, dtype=np.int64)
    values = _rng(seed, 0).integers(1, VALUE_MAX, recordcount,
                                    endpoint=True)
    return keys, values
