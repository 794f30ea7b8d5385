"""device_idle_share: share of the traced window in which no operation
ran on the device (1 - union of op intervals / window), mean over the
cell's chips, in percent."""


def read(run):
    if run.trace is None:
        return None
    return run.trace.idle_share * 100.0
