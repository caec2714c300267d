"""Device: the share of the traced window in which no op ran on the chip
(1 - union of the device's op intervals / window), in percent."""


def read(run):
    if run.trace is None:
        return None
    idle = run.trace.idle_share()
    return None if idle is None else 100.0 * idle
