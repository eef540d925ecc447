"""The readings that the limits in a configuration's ``limits`` are set
from, on the chip, at a cell's own size and load, in one process:

* the program: for each ``--seeds`` seed, a whole run of the cell (new
  weights, pool, calibration and compile, one window of ``--seconds``
  at the cell's traffic, the drain) compared with the plain reference
  exactly as ``bench/run.py`` compares it;
* the control: for each ``--control-seeds`` seed, the reference itself
  computed at the next precision below the configuration's (int4 for
  int8: every conv/dense weight per output channel and every input per
  tensor at its absmax over the calibration samples), put in the
  program's place for the same requests and compared the same way.

Each seed's line carries every number ``bench/compare.py`` reads, the
ones its configuration does not compare included.

    python3 bench/limits.py --workload cnet_accel.poisson_over --seconds 10 \\
        --seeds 101 102 ... --control-seeds 201 202 203

One JSON line per seed; the last line gives, per number, the largest
program reading and the smallest control reading.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

# the next precision below the one the configuration states
CONTROL_BITS = {"int8": 4}


def control_readings(cell, seed: int, seconds: float):
    from bench import arrivals, compare, harness
    from bench.reference import module
    cfg = cell.config
    ref = module(cfg)
    params, pool = harness.make_data(ref, cfg, seed)
    f32 = compare.reference_outputs(ref, cfg, params, pool)
    low = compare.rounded_outputs(ref, cfg, params, pool,
                                  CONTROL_BITS[cfg["precision"]])
    plan = arrivals.schedule(cell.traffic, seed, seconds, cfg["pool_size"])
    served = [{k: low[k][i] for k in low} for i in plan.pool_index]
    numbers, widest = compare.readings(
        cfg, served, plan.pool_index, f32, 0,
        compare.rounded_outputs(ref, cfg, params, pool, 8))
    return dict(numbers, out_max=widest)


def program_readings(cell, seed: int, seconds: float):
    from bench import compare, harness
    state = harness.setup(cell.config, seed, cell.chips)
    win = harness.serve(state, cell.traffic, seed, seconds)
    run, served, served_index, missing = harness.collect(state, win)
    harness.log_window(harness.stderr_log, win, run)
    cfg, ref, params, pool = state.cfg, state.ref, state.params, state.pool
    state.gc_watch.close()
    del state, run
    gc.unfreeze()
    gc.collect()
    ref_out = compare.reference_outputs(ref, cfg, params, pool)
    numbers, widest = compare.readings(
        cfg, served, served_index, ref_out, missing,
        compare.rounded_outputs(ref, cfg, params, pool, 8))
    numbers["dispatcher_errors"] = float(win.error is not None)
    return dict(numbers, out_max=widest)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, nargs="*", default=[])
    p.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = p.parse_args(argv)
    from bench import harness, spec
    cell = spec.load_cell(args.workload)
    try:
        harness.device_check(cell.chips)
    except harness.NoChip as ex:
        print(f"limits: {ex}", file=sys.stderr)
        return 2
    worst, least = {}, {}
    for kind, seeds, fn in (("program", args.seeds, program_readings),
                            ("control", args.control_seeds,
                             control_readings)):
        for seed in seeds:
            numbers = fn(cell, seed, args.seconds)
            print(json.dumps({"workload": cell.name, "kind": kind,
                              "seed": seed, **numbers}), flush=True)
            for k, v in numbers.items():
                if kind == "program":
                    worst[k] = max(worst.get(k, v), v)
                else:
                    least[k] = min(least.get(k, v), v)
    print(json.dumps({"workload": cell.name, "program_max": worst,
                      "control_min": least}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
