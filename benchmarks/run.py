"""Benchmark driver — one section per paper table / figure + the roofline.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run --only table1,roofline
    PYTHONPATH=src python -m benchmarks.run --list     # what exists

Unknown section names and missing benchmark modules fail with a clear
one-line message and a non-zero exit, never a raw traceback.
"""
from __future__ import annotations

import argparse
import importlib
import sys
import time

from repro.launch.compile_cache import enable_compile_cache

# section name -> (module, needs_dryrun_ledger, gate, description) —
# `gate` sections return an exit code that fails the driver at the end
# instead of aborting the remaining sections.
SECTIONS = {
    "table1": ("benchmarks.table1_model_stats", False, False,
               "Table I model stats: params/ops vs the paper's counts"),
    "table2": ("benchmarks.table2_footprint", False, False,
               "Table II memory footprint: fp32 vs int8 deployments"),
    "table3": ("benchmarks.table3_performance", False, False,
               "Table III latency/energy: measured host + modeled ZCU104"),
    "throughput": ("benchmarks.throughput", False, False,
                   "batched-vs-per-sample throughput per backend/rung"),
    "serving": ("benchmarks.serving_load", False, True,
                "continuous-batching serving under Poisson/burst traces"),
    "energy": ("benchmarks.energy_dispatch", False, True,
               "modeled J/inference table + envelope-constrained serving"),
    "fusion": ("benchmarks.fusion", False, True,
               "pass-pipeline gates: fused DDR bytes / J/inf vs op-by-op"),
    "autotune": ("benchmarks.autotune", False, True,
                 "autotuner gates: tuned vs heuristic tile configs, "
                 "prepacked arenas, bit-exactness"),
    "pipeline": ("benchmarks.pipeline", False, True,
                 "pipelined-runtime gates: modeled stage overlap, "
                 "pipelined==sync identity, overlap-ledger invariants"),
    "trace": ("benchmarks.trace_frontend", False, True,
              "jaxpr front-end gates: traced==hand-built structure + "
              "bit-exactness, never-hand-built demo serve"),
    "faults": ("benchmarks.faults", False, True,
               "degraded-mode gates: SEU storms detected+recovered, "
               "watchdog reboot zero-loss, inert-controller identity"),
    "radiation": ("benchmarks.radiation", False, True,
                  "orbit-aware radiation gates: sampled SAA-pass storm "
                  "recovered bit-exact, ECC/TMR regime switch, "
                  "checkpoint-cadence optimum, inert-radiation identity"),
    "table45": ("benchmarks.table45_context", False, False,
                "Tables IV/V context: device/toolchain comparison"),
    "fig_power": ("benchmarks.fig_power_phases", False, False,
                  "Figs 9-13 power-over-time serving phases"),
    "roofline": ("benchmarks.roofline", True, False,
                 "LM roofline sweep (needs the dryrun ledger)"),
    "lm": ("benchmarks.lm_energy", False, True,
           "LM serving gates: compiled decode over int8 KV slots, "
           "prefill/decode rung ladder, tokens/s vs recompute"),
}


def _load(name: str):
    module = SECTIONS[name][0]
    try:
        return importlib.import_module(module)
    except ImportError as ex:
        sys.exit(f"benchmark section {name!r} is broken: cannot import "
                 f"{module} ({ex})")


def _run_section(name: str, failures: list) -> None:
    mod = _load(name)
    _, needs_ledger, gate, _ = SECTIONS[name]
    entry = mod.run if name == "roofline" else mod.main
    if name == "roofline":
        print("== Roofline (3 terms per arch x shape, single-pod 256 "
              "chips, scan-corrected) ==")
    try:
        # gate sections take an argv list; plain sections take none
        rc = entry([]) if gate else entry()
    except FileNotFoundError:
        if needs_ledger:
            print(f"no dryrun ledger — skipping {name} (run "
                  "`PYTHONPATH=src python -m repro.launch.dryrun` first)",
                  file=sys.stderr)
            return
        raise
    if gate and rc:
        # keep running the remaining sections; fail at the end
        failures.append(f"{name} gate")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help=f"comma-list of {sorted(SECTIONS)}")
    ap.add_argument("--list", action="store_true",
                    help="print available sections and exit")
    args = ap.parse_args()
    if args.list:
        width = max(len(n) for n in SECTIONS)
        for name, (_, needs_ledger, gate, desc) in SECTIONS.items():
            tags = "".join([" [gate]" if gate else "",
                            " [needs-ledger]" if needs_ledger else ""])
            print(f"{name:{width}s}  {desc}{tags}")
        return
    wanted = (list(SECTIONS) if not args.only
              else [w.strip() for w in args.only.split(",") if w.strip()])
    unknown = [w for w in wanted if w not in SECTIONS]
    if unknown:
        sys.exit(f"unknown benchmark section(s) {unknown}; choose from "
                 f"{', '.join(sorted(SECTIONS))}")

    enable_compile_cache()
    t0 = time.time()
    failures: list = []
    for name in SECTIONS:
        if name not in wanted:
            continue
        _run_section(name, failures)
        print()
    print(f"benchmarks done in {time.time()-t0:.1f}s")
    if failures:
        sys.exit(f"failed: {', '.join(failures)}")


if __name__ == "__main__":
    main()
