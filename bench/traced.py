"""What a traced run hands to the per-layer readers in ``bench/metrics``.

A reader is ``read(run: TracedRun) -> float | None``: it takes its
number from the program's spans, the window's counters or the reduced
device trace, and returns None where the run holds nothing to read.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

from .devtrace import DeviceTrace


@dataclasses.dataclass
class TracedRun:
    config: Dict
    peaks: Dict
    # the program's spans in the window: name -> durations in ms
    spans: Dict[str, List[float]]
    # window counts: waves, dispatches, ops_executed, writes_ok, ...
    counters: Dict[str, float]
    device: Optional[DeviceTrace]

    def span_mean_ms(self, name: str) -> Optional[float]:
        durs = self.spans.get(name)
        return sum(durs) / len(durs) if durs else None
