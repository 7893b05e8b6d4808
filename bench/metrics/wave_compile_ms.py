"""Wave compile: mean ``wave.compile`` span per wave (``KVService``
compiling every queued op against its shard's snapshot)."""


def read(run):
    return run.span_mean_ms("wave.compile")
