"""95th percentile, over every request due in the untraced window, of
the wait from its due time to the scheduler step that dispatched its
batch."""
import numpy as np

SOURCE = "program_span"
LAYER = "scheduler"
MOVES = "p95_latency_ms"
UNIT = "ms"
BETTER = "lower"


def read(run):
    ok = ~np.isnan(run.dispatched)
    if not ok.any():
        return None
    return float(np.percentile((run.dispatched[ok] - run.due[ok]) * 1e3, 95))
