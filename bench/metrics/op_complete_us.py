"""Completion: time completing answered ops, per op completed, in
microseconds (``ServiceStats.complete_ns`` over ``completed``; timed
while tracing is on)."""


def read(run):
    c = run.counters
    ops = c.get("service.completed")
    if not ops:
        return None
    return c["service.complete_ns"] / ops / 1e3
