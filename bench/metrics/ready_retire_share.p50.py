"""Share of the untraced window's dispatches whose batch the dispatcher
retired as soon as its outputs were ready, from its idle loop (the
scheduler's DispatchRecord ``retired_by == "ready"``), rather than when
a later dispatch needed its staging slot or at a barrier. None where the
program's records do not name what retired a batch."""
SOURCE = "program_counter"
LAYER = "host dispatch"
MOVES = "p50_latency_ms"
UNIT = "%"
BETTER = "higher"


def read(run):
    window = [d for d in run.dispatches
              if run.in_window(d.started) and not d.failed]
    if not window or not all(hasattr(d, "retired_by") for d in window):
        return None
    return 100.0 * sum(d.retired_by == "ready" for d in window) / len(window)
