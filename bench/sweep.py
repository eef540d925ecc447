"""Find a configuration's knee once, on the chip: serve one window per
offered Poisson rate, in one process (set-up paid once), and print per
rate what was completed, the latency tail and the backlog at the close.

    python3 bench/sweep.py --config cnet_accel --seed 7 --seconds 10 \\
        --rates 1000 1200 1400 1600

The knee is the highest offered rate whose p95 latency stays under the
configuration's deadline and whose backlog at the close holds at most 1%
of the window's requests (no growth over the window). The cells' traffic files
hold rates fixed from it; the benchmark never searches for a rate.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--config", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    args = p.parse_args(argv)
    from bench import harness, spec
    with open(os.path.join(spec.BENCH_DIR, "configs",
                           args.config + ".json")) as f:
        cfg = json.load(f)
    try:
        state = harness.setup(cfg, args.seed, 1)
    except harness.NoChip as ex:
        print(f"sweep: {ex}", file=sys.stderr)
        return 2
    knee = None
    for rate in args.rates:
        win = harness.serve(state, {"kind": "poisson", "rate_hz": rate},
                            args.seed, args.seconds)
        run, _, _, missing = harness.collect(state, win)
        e2e = harness.end_to_end(run, 0.0)
        holds = (e2e["p95_latency_ms"] < cfg["deadline_s"] * 1e3
                 and win.backlog <= 0.01 * len(win.due) and missing == 0
                 and win.error is None)
        knee = rate if holds else knee
        print(json.dumps({
            "config": args.config, "offered_hz": rate,
            "requests": len(win.due),
            "samples_per_s": e2e["samples_per_s"],
            "p50_latency_ms": e2e["p50_latency_ms"],
            "p95_latency_ms": e2e["p95_latency_ms"],
            "backlog_at_close": win.backlog, "missing": missing,
            "compiles_in_window": win.n_compiles,
            "generator_late_p99_ms": harness.percentile(win.late, 99) * 1e3,
            "holds": holds}), flush=True)
    print(json.dumps({"config": args.config, "knee_hz": knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
