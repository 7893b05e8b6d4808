"""Benchmark harness tests: CPU only, at a size a test run holds."""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


@pytest.fixture
def tiny_cell():
    """The 4-shard YCSB-A cell cut to 4 x 256 buckets, round_cap 16 (64
    clients) and 400 records: the same code path at a CPU size."""
    from bench import spec
    bench = spec.load_benchmark()
    cell = spec.load_cell("kv4_ycsb_a_uniform", bench)
    config = dict(cell.config, buckets_per_shard=256, round_cap=16,
                  recordcount=400)
    return spec.Cell(name=cell.name, chips=1, config=config,
                     traffic=cell.traffic, end_to_end=cell.end_to_end,
                     per_layer=cell.per_layer)
