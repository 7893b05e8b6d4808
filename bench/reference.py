"""Plain reference for the KV cells: a dict replayed in wave order.

The configuration's guarantee is per-key linearizability in wave order:
every op answered in wave ``w`` sees the state left by waves ``< w``,
and the wave's committed writes apply after its reads, at most one per
key.  This replays the answered ops of a run on a plain dict under that
rule and counts every answer, and every final key, that differs.

It imports nothing of the program and takes nothing the program made:
the initial records come from the seed (``bench.ycsb.load_records``),
the ops and answers from what the clients were told, and each answer's
wave and order from the harness's own count of the steps it drove and
the ops it submitted.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Mapping

import numpy as np

from .ycsb import READ, UPDATE

# answer codes (the service's status words, coded by the harness)
OK, NOT_FOUND, EXISTS, OTHER = 0, 1, 2, 9
STATUS = {"ok": OK, "not_found": NOT_FOUND, "exists": EXISTS}
NO_VALUE = -1


@dataclasses.dataclass
class Replay:
    checked: int
    mismatched: int
    examples: List[str]
    state: Dict[int, int]


def replay(state: Mapping[int, int], wave: np.ndarray, seq: np.ndarray,
           kind: np.ndarray, key: np.ndarray, value: np.ndarray,
           status: np.ndarray, result: np.ndarray) -> Replay:
    """Replay answered ops (one row each) on a copy of ``state``.

    ``wave`` is the wave that answered the op, ``seq`` its submission
    order, ``status``/``result`` the answer (``NO_VALUE`` where the
    answer carries no value).  Returns the mismatch count, a few
    examples and the state after the last wave."""
    state = dict(state)
    order = np.lexsort((seq, wave))
    cols = [np.asarray(c)[order].tolist()
            for c in (wave, kind, key, value, status, result)]
    mismatched, examples = 0, []
    writes: Dict[int, int] = {}
    current = None
    for w, k, key_, val, st, res in zip(*cols):
        if w != current:
            state.update(writes)
            writes, current = {}, w
        have = state.get(key_)
        if k == READ:
            want = (OK, have) if have is not None else (NOT_FOUND, NO_VALUE)
        elif k == UPDATE:
            want = (OK, NO_VALUE) if have is not None else (NOT_FOUND,
                                                            NO_VALUE)
            if have is not None and st == OK:
                if key_ in writes:
                    want = ("one write per key per wave", None)
                writes[key_] = val
        else:
            want = (f"no reference for op kind {k}", None)
        if (st, res) != want:
            mismatched += 1
            if len(examples) < 5:
                examples.append(f"wave {w} kind {k} key {key_}: answered "
                                f"{(st, res)}, reference {want}")
    state.update(writes)
    return Replay(len(cols[0]), mismatched, examples, state)


def differing_keys(items: Mapping[int, int], state: Mapping[int, int]
                   ) -> int:
    """Keys whose final value differs, is missing or is extra."""
    if items == state:
        return 0
    return sum(1 for k in set(items) | set(state)
               if items.get(k) != state.get(k))
