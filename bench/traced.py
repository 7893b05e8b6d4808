"""What a traced run hands to the per-layer readers in ``bench/metrics``.

A reader is ``read(run: TracedRun) -> float | None``: it takes its
number from the program's spans, the window's counters or the reduced
device trace, and returns None where the run holds nothing to read.
Every complete span the program emits in the window, every count of its
``ServiceStats`` and every counter and gauge of its metrics registry
reach the reader, so a reader of a new span or counter is a new file in
``bench/metrics`` and nothing else.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

from .devtrace import DeviceTrace


@dataclasses.dataclass
class TracedRun:
    config: Dict
    peaks: Dict
    # every span the program completed in the window: name -> ms each
    spans: Dict[str, List[float]]
    # window counts: waves, dispatches, ops_executed, writes_ok; the
    # settings shards and round_cap; service.<field> for every count of
    # ServiceStats; registry.<name> for every registry counter and gauge,
    # summed over its labels
    counters: Dict[str, float]
    device: Optional[DeviceTrace]

    def span_mean_ms(self, name: str) -> Optional[float]:
        durs = self.spans.get(name)
        return sum(durs) / len(durs) if durs else None

    def registry_total(self, name: str) -> float:
        """One registry name summed over its labels (0 where absent)."""
        return self.counters.get(f"registry.{name}", 0)
