"""Chip benchmark of the sharded KV service: data files plus one harness.

``python3 -m bench.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the chip and
prints one JSON result line.  Each configuration, traffic mix and
per-layer metric is a file of its own, found by name:

- ``bench/configs/<config>.json``: the deployment (shards, buckets,
  round cap, record count, guarantees, source, cuts);
- ``bench/traffic/<traffic>.json``: the YCSB mix, key distribution and
  loop kind;
- ``bench/metrics/<metric>.py``: one reader per per-layer metric;
- ``bench/peaks.json``: device peaks by ``device_kind``.

The yardstick (YCSB generator, plain reference, byte count, trace
reduction) lives here and imports nothing of the program.
"""
