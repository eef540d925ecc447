"""Mean wall time of one ``ServingPipeline.execute_batch_async`` call
(host staging into the slot, ``device_put``, launch) over the calls that
started in the untraced window, timed by the benchmark's own wrappers."""
import numpy as np

SOURCE = "host_clock"
LAYER = "host dispatch"
MOVES = "samples_per_s"
UNIT = "ms"
BETTER = "lower"


def read(run):
    calls = [s for t, s in run.dispatch_calls if run.in_window(t)]
    return float(np.mean(calls)) * 1e3 if calls else None
