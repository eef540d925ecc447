"""Run one cell of the chip benchmark.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

``<name>`` is a ``workloads`` entry of ``BENCHMARK.json``. With
``--trace 0`` the last line of standard output is the cell's end-to-end
metrics; with ``--trace 1`` the window runs under the profiler and the
line holds the cell's per-layer metrics, the device's busy and window
seconds and a breakdown. The numbers compared for ``correct`` are the
last lines of standard error and the last key of the result line.

Exit codes: 0 correct, 1 not correct, 2 no TPU (or too few chips): no
result is printed.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

# libtpu logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    if args.seed < 0:
        p.error("--seed must be a non-negative whole number")
    from bench import harness, spec
    cell = spec.load_cell(args.workload)
    try:
        result = harness.run(cell, args.seed, args.seconds,
                             bool(args.trace), T_PROCESS)
    except harness.NoChip as ex:
        print(f"bench: {ex}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
