"""Mean share of real requests in the dispatched batches of the untraced
window (n_real / rung, from the scheduler's DispatchRecords): how much
of each compiled rung the scheduler fills when the queue never
empties."""
import numpy as np

SOURCE = "program_counter"
LAYER = "scheduler"
MOVES = "samples_per_s"
UNIT = "%"
BETTER = "higher"


def read(run):
    fills = [d.n_real / d.rung for d in run.dispatches
             if run.in_window(d.started)]
    return float(np.mean(fills)) * 100.0 if fills else None
