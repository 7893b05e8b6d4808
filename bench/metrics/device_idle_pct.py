"""Device: the share of the traced window in which no operation ran on
the device (mean over the chips used), in percent."""


def read(run):
    dev = run.device
    if dev is None or not dev.window_ns:
        return None
    return 100.0 * (1.0 - dev.busy_ns / dev.window_ns)
