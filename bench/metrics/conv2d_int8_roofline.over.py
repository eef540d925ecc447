"""Share of its roofline that the ``conv2d_int8`` kernel reaches over the
traced segment: the least time the chip needs for the conv layers of each
plan call the trace holds whole (ops and bytes from the configuration's
shapes, ``bench/workcount.py``, the dispatch's real requests only) over
the device time of the kernel's events in those calls. The i-th plan
call in the trace is the segment's i-th dispatch."""
from bench import workcount

SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "samples_per_s"
UNIT = "%"
BETTER = "higher"
KERNEL = "conv2d_int8"
KIND = "conv"


def read(run):
    if run.trace is None or run.peak is None:
        return None
    calls = run.trace.plan_calls()
    layers = [layer for layer in run.layers if layer.kind == KIND]
    if not layers or len(calls) > len(run.dispatches):
        return None
    least = device_s = 0.0
    for call, dispatch in zip(calls, run.dispatches):
        if KERNEL in call:
            device_s += call[KERNEL][0]
            least += sum(workcount.least_seconds(layer, dispatch.n_real,
                                                 run.peak)
                         for layer in layers)
    return 100.0 * least / device_s if device_s > 0 else None
