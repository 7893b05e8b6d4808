"""Wave compile: the whole-table snapshots of a wave, ms per wave (the
``wave.snapshot`` spans of every shard in the window over the window's
waves)."""


def read(run):
    durs = run.spans.get("wave.snapshot")
    waves = run.counters.get("waves")
    if not durs or not waves:
        return None
    return sum(durs) / waves
