"""Each fault the cells can have, planted under the timed path of a
tiny CPU run, makes ``correct`` come out false.  (No cell spans chips,
so there is no exchange between chips to leave out.)"""
import time

import pytest

from bench import faults, run


@pytest.mark.parametrize("name", ["frozen_state", "half_batch",
                                  "altered_answer"])
def test_a_planted_fault_is_not_correct(tiny_cell, name):
    r = run.run_cell(tiny_cell, 2 ** 31 + 29, 1.0, False,
                     peaks={"hbm_bytes_per_s": 819e9},
                     t_start=time.perf_counter(),
                     plant=faults.PLANTS[name])
    assert not r["correct"]
    wrong = (r["checks"]["mismatched_answers"]["value"]
             + r["checks"]["mismatched_keys"]["value"])
    assert wrong > 0
