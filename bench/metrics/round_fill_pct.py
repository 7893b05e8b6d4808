"""Round formation: ops executed over the round slots dispatched
(stacked dispatches x shards x round_cap), in percent."""


def read(run):
    c = run.counters
    slots = c["dispatches"] * c["shards"] * c["round_cap"]
    if not slots:
        return None
    return 100.0 * c["ops_executed"] / slots
