"""Dispatch: mean ``wave.dispatch`` span per wave that dispatched
(stacking the shard tables, the stacked apply, the write-back)."""


def read(run):
    return run.span_mean_ms("wave.dispatch")
