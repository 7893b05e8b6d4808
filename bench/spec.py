"""Find a cell's configuration, traffic, metrics and peaks by name.

Everything here is data lookup: ``BENCHMARK.json`` at the root names the
cells and metrics, and each name resolves to a file under ``bench/``.
Adding a cell, a configuration, a traffic mix or a per-layer metric adds
files and entries; no code here changes.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import pathlib
from typing import Callable, Dict, List, Optional

ROOT = pathlib.Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"


class SpecError(ValueError):
    """A cell, file or device that the benchmark does not know."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]

    @property
    def clients(self) -> int:
        """Closed-loop clients: one per round slot (``shards x
        round_cap``, the most writes one wave dispatches)."""
        return self.config["shards"] * self.config["round_cap"]


def _read_json(path: pathlib.Path) -> Dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"missing benchmark file {path}") from None


def load_benchmark() -> Dict:
    return _read_json(ROOT / "BENCHMARK.json")


def load_config(name: str) -> Dict:
    return _read_json(BENCH / "configs" / f"{name}.json")


def load_traffic(name: str) -> Dict:
    traffic = _read_json(BENCH / "traffic" / f"{name}.json")
    mix = sum(traffic["mix"].values())
    if abs(mix - 1.0) > 1e-9:
        raise SpecError(f"traffic {name}: mix sums to {mix}, not 1")
    return traffic


def load_cell(name: str, bench: Optional[Dict] = None) -> Cell:
    bench = load_benchmark() if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"unknown workload {name!r}; BENCHMARK.json has "
                        f"{sorted(cells)}")
    w = cells[name]

    def applies(metric: Dict) -> bool:
        return "workloads" not in metric or name in metric["workloads"]

    return Cell(name=name, chips=int(w["chips"]),
                config=load_config(w["config"]),
                traffic=load_traffic(w["traffic"]),
                end_to_end=[m for m in bench["end_to_end"] if applies(m)],
                per_layer=[m for m in bench["per_layer"] if applies(m)])


def metric_reader(name: str) -> Callable:
    """``bench/metrics/<name>.py``'s ``read(run)``: the value, or None
    where the traced run holds nothing to read."""
    if not (BENCH / "metrics" / f"{name}.py").is_file():
        raise SpecError(f"no reader bench/metrics/{name}.py")
    return importlib.import_module(f"bench.metrics.{name}").read


def device_peaks(device_kind: str) -> Dict:
    """The peaks row of ``bench/peaks.json`` for this device; a device
    that is not in the table is an error, never a default."""
    peaks = _read_json(BENCH / "peaks.json")["devices"]
    if device_kind not in peaks:
        raise SpecError(f"no peaks for device kind {device_kind!r} in "
                        f"bench/peaks.json (has {sorted(peaks)})")
    return peaks[device_kind]
