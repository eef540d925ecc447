"""95th percentile, over the dispatches of the untraced window, of the
time from dispatch to outputs on the host (the scheduler's DispatchRecord
``service_time``, rewritten at retirement; it includes the wait for
lazy retirement)."""
import numpy as np

SOURCE = "program_span"
LAYER = "host dispatch"
MOVES = "p95_latency_ms"
UNIT = "ms"
BETTER = "lower"


def read(run):
    waits = [d.service_time for d in run.dispatches
             if run.in_window(d.started)]
    return float(np.percentile(waits, 95)) * 1e3 if waits else None
