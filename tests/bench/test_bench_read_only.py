"""A read-only mix (YCSB-C, ``ycsb_c_zipfian``) at a CPU size, which no
cell runs: a sound run is correct and a read answer altered where it is
produced is not, while the control, a snapshot one wave old, cannot fail
a window with no writes."""
import dataclasses
import time

import pytest

from bench import faults, run, spec

PEAKS = {"hbm_bytes_per_s": 819e9}


def _run(cell, plant=None):
    return run.run_cell(cell, 2 ** 31 + 71, 1.0, False, peaks=PEAKS,
                        t_start=time.perf_counter(), plant=plant)


@pytest.fixture
def read_only_cell(tiny_cell):
    traffic = spec.load_traffic("ycsb_c_zipfian")
    assert traffic["mix"] == {"read": 1.0}
    return dataclasses.replace(tiny_cell, traffic=traffic)


@pytest.mark.parametrize("plant,correct", [
    (None, True),
    # no write, so a snapshot one wave old is the same snapshot
    (faults.CONTROL, True),
    ("altered_answer", False),
])
def test_a_read_only_mix_fails_only_an_altered_answer(read_only_cell, plant,
                                                      correct):
    r = _run(read_only_cell, plant=plant and faults.PLANTS[plant])
    assert r["correct"] is correct, r["checks"]
    mismatched = r["checks"]["mismatched_answers"]["value"]
    assert (mismatched == 0) is correct
