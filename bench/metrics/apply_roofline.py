"""Kernel: the least time the window's committed writes need at the
chip's memory bandwidth, over the device time of the stacked apply
program, in percent.  The bytes come from ``bench.work`` (real cells
and rows only); the apply is bound by bytes, as it does no arithmetic
worth counting against the compute peak."""
from bench.work import write_bytes


def read(run):
    dev = run.device
    if dev is None or not dev.apply_ns or not run.counters["writes_ok"]:
        return None
    words = 1 + run.config["value_words"]
    least_s = write_bytes(run.counters["writes_ok"], words) \
        / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (dev.apply_ns / 1e9)
