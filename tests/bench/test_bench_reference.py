"""The plain reference replays answers in wave order and flags every
answer or final key that a corrupted run gets wrong."""
import numpy as np
import pytest

from bench import reference as ref
from bench.ycsb import READ, UPDATE

OK, NF = ref.OK, ref.NOT_FOUND
NV = ref.NO_VALUE


def _cols(rows):
    return [np.array(c) for c in zip(*rows)]


def _history():
    # (wave, seq, kind, key, value, status, result)
    return [
        (1, 0, READ, 1, 0, OK, 10),
        (1, 1, UPDATE, 1, 11, OK, NV),
        (1, 2, READ, 2, 0, OK, 20),          # same wave: sees the start
        (2, 3, READ, 1, 0, OK, 11),          # next wave: sees the write
        (2, 4, UPDATE, 3, 5, NF, NV),        # missing key
        (2, 5, READ, 3, 0, NF, NV),
    ]


def test_a_sound_history_replays_clean():
    rep = ref.replay({1: 10, 2: 20}, *_cols(_history()))
    assert (rep.checked, rep.mismatched) == (6, 0)
    assert rep.state == {1: 11, 2: 20}
    assert ref.differing_keys({1: 11, 2: 20}, rep.state) == 0


@pytest.mark.parametrize("row, col, bad", [
    (0, 6, 99),            # a read answered the wrong value
    (2, 5, NF),            # a read missed a live key
    (3, 6, 10),            # a read answered a stale value
    (4, 5, OK),            # an update of a missing key claimed success
    (1, 0, 2),             # the write was answered a wave late
])
def test_a_corrupted_answer_is_flagged(row, col, bad):
    rows = [list(r) for r in _history()]
    rows[row][col] = bad
    rep = ref.replay({1: 10, 2: 20}, *_cols(rows))
    assert rep.mismatched >= 1 and rep.examples


def test_two_writes_to_one_key_in_one_wave_are_flagged():
    rows = [(1, 0, UPDATE, 1, 11, OK, NV), (1, 1, UPDATE, 1, 12, OK, NV)]
    assert ref.replay({1: 10}, *_cols(rows)).mismatched == 1


def test_differing_keys_counts_changed_missing_and_extra():
    assert ref.differing_keys({1: 1, 2: 3, 4: 4}, {1: 1, 2: 2, 3: 3}) == 3
