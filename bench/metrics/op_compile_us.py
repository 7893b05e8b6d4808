"""Wave compile: ``compile_op`` time per op compiled, in microseconds
(``ServiceStats.compile_ns`` over ``ops_compiled``; timed while tracing
is on)."""


def read(run):
    c = run.counters
    ops = c.get("service.ops_compiled")
    if not ops:
        return None
    return c["service.compile_ns"] / ops / 1e3
