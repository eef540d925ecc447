"""Share of the traced segment in which no operation ran on the device:
1 - (union of the device's operation intervals / window), averaged over
the chips."""
SOURCE = "device_trace"
LAYER = "device"
MOVES = "samples_per_s"
UNIT = "%"
BETTER = "lower"


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace.window
    return 100.0 * (1.0 - run.trace.busy_seconds(lo, hi) / ((hi - lo) * 1e-9))
