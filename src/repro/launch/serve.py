"""Serving launcher — the paper's on-board inference scenario.

Two modes:

* ``space``: serve one or more of the six space use-case models through
  the continuous-batching scheduler (dual-backend engine + precompiled
  batch ladder + deadline flushing), with each use case's selective-
  downlink predicate (the paper's motivating workload). ``--model``
  takes a comma list to co-serve several models from one process;
  requests arrive on a per-model Poisson trace at ``--rate`` req/s.
  ``--backend`` also takes a comma list (primary first) — under
  ``--power-budget WATTS`` dispatch becomes energy-aware: every batch
  must be admitted by the orbital power envelope (sustained watts over a
  sliding ``--window-s`` window, ``--burst-j`` allowance, optional
  ``--peak-w`` instantaneous cap) and falls back to the cheaper-power
  backends when the budget refuses the primary.
* ``lm``: autoregressive serving. Default (``--lm-compiled``) is the
  scheduler-native path (DESIGN.md §15): the decoder-block op graph
  compiles through the same Planned -> Lowered -> Compiled chain as the
  CNNs, prefill rides the compiled batch ladder, decode batches across
  in-flight requests at their static int8 KV-cache slots, and tokens
  stream with per-phase telemetry. ``--lm-legacy`` keeps the raw
  jit-function loop for an assigned LM architecture (reduced config on
  CPU; production configs go through the dry-run/pod path).

Usage::

    PYTHONPATH=src python -m repro.launch.serve \
        --model baseline_net,vae_encoder --backend flex --requests 64
    PYTHONPATH=src python -m repro.launch.serve \
        --model logistic_net --backend accel,cpu \
        --power-budget 3 --window-s 1 --clock modeled
    PYTHONPATH=src python -m repro.launch.serve --mode lm \
        --backend accel --requests 8 --tokens 6 --slots 4
    PYTHONPATH=src python -m repro.launch.serve --mode lm --lm-legacy \
        --arch tinyllama-1.1b --smoke --tokens 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

import os

from repro.configs import get_arch, reduced
from repro.core import faults as faults_mod
from repro.core import radiation as radiation_mod
from repro.core.energy import PowerEnvelope
from repro.core.engine import Engine
from repro.core.scheduler import (BACKENDS, ContinuousBatchingScheduler,
                                  capped_ladder, poisson_arrivals)
from repro.core import inspector
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models import SPACE_MODELS, synthetic_requests
from repro.nn import model as model_lib
from repro.nn.dims import compute_dims

# selective-downlink predicates per use case (the paper's decision layer)
KEEP_PREDICATES = {
    # MMS: keep only magnetosheath/magnetopause crossings (classes 2, 3)
    "baseline_net": lambda out: int(out["region"]) >= 2,
    "reduced_net": lambda out: int(out["region"]) >= 2,
    "logistic_net": lambda out: int(out["region"]) >= 2,
    # ESPERTA: keep if any of the six models warns
    "multi_esperta": lambda out: any(
        float(np.max(v)) > 0 for k, v in out.items() if k.startswith("warn")),
    # CNet: keep high predicted X-ray flux
    "cnet_plus_scalar": lambda out: float(np.max(list(out.values())[0])) > 0.0,
    # VAE: everything downlinks (it IS the compressed product)
    "vae_encoder": lambda out: True,
}


def serve_space(args) -> int:
    names = [n.strip() for n in args.model.split(",") if n.strip()]
    unknown = [n for n in names if n not in SPACE_MODELS]
    if unknown or not names:
        raise SystemExit(f"unknown model(s) {unknown}; choose from "
                         f"{', '.join(sorted(SPACE_MODELS))}")
    backends = tuple(b.strip() for b in args.backend.split(",") if b.strip())
    bad = [b for b in backends if b not in BACKENDS]
    if bad or not backends:
        raise SystemExit(f"unknown backend(s) {bad}; choose from "
                         f"{', '.join(BACKENDS)}")
    ladder = capped_ladder(args.batch)

    envelope = None
    if args.power_budget is not None or args.peak_w is not None:
        envelope = PowerEnvelope(
            sustained_w=(float("inf") if args.power_budget is None
                         else args.power_budget),
            peak_w=args.peak_w, burst_j=args.burst_j,
            window_s=args.window_s)
        print(f"[envelope] sustained={args.power_budget} W  "
              f"peak={args.peak_w} W  burst={args.burst_j} J  "
              f"window={args.window_s} s  clock={args.clock}")
    elif args.burst_j != 0.0 or args.window_s != 10.0:
        raise SystemExit("--burst-j/--window-s configure the power "
                         "envelope; pass --power-budget and/or --peak-w "
                         "to enable it")
    if (args.tuning_cache or args.autotune_measure) and not args.autotune:
        raise SystemExit("--tuning-cache/--autotune-measure configure the "
                         "plan-time autotuner; pass --autotune to enable it")
    sched = ContinuousBatchingScheduler(envelope=envelope, clock=args.clock,
                                        pipeline=args.pipeline,
                                        staging_buffers=args.staging_buffers)
    if args.pipeline:
        print(f"[pipeline] async ticket dispatch on, "
              f"{args.staging_buffers} staging buffer(s) per (model, rung)")
    rad_mode = args.radiation != "off"
    rad_flags = (args.base_upset_rate is not None
                 or args.saa_factor is not None
                 or args.protection != "none"
                 or args.checkpoint_cadence is not None)
    if rad_flags and not rad_mode:
        raise SystemExit("--base-upset-rate/--saa-factor/--protection/"
                         "--checkpoint-cadence configure the orbital "
                         "radiation model; pass --radiation orbit to "
                         "enable it")
    fault_mode = (args.fault_rate > 0.0 or args.self_test_period is not None
                  or rad_mode)
    if fault_mode and "accel" not in backends:
        raise SystemExit("--fault-rate/--self-test-period model SEUs in "
                         "the accel weight arenas; include 'accel' in "
                         "--backend")
    if fault_mode and args.recovery == "demote" and len(backends) < 2:
        raise SystemExit("--recovery demote quarantines the primary "
                         "backend; register a fallback (e.g. accel,cpu)")
    if (not fault_mode and (args.fault_seed != 0
                            or args.recovery != "repack")):
        raise SystemExit("--fault-seed/--recovery configure fault "
                         "injection; pass --fault-rate and/or "
                         "--self-test-period to enable it")

    trace = []
    canaries = {}
    for mi, name in enumerate(names):
        m = SPACE_MODELS[name]
        graph = m.build_graph()
        engine = Engine(graph, m.init_params(jax.random.PRNGKey(1)),
                        fuse=not args.no_fuse, autotune=args.autotune,
                        tuning_cache=args.tuning_cache,
                        autotune_measure=args.autotune_measure)
        print(inspector.inspect(graph).summary())

        reqs = synthetic_requests(m, args.requests, seed=mi)
        if "accel" in backends:
            print(f"[ptq] {name}: calibrating on 4 samples")
            engine.calibrate(reqs[:4])

        sched.register(name, engine, backend=backends, ladder=ladder,
                       keep_predicate=KEEP_PREDICATES.get(name),
                       warmup_sample=reqs[0] if reqs else None)
        canaries[name] = reqs[:1]
        trace += [(t, name, r) for t, r in
                  zip(poisson_arrivals(args.rate, args.requests, seed=mi),
                      reqs)]

    controller = None
    if fault_mode:
        horizon = max((t for t, _, _ in trace), default=0.0) + 1.0
        upsets: tuple = ()
        self_test = args.self_test_period
        if rad_mode:
            renv = radiation_mod.RadiationEnvironment(
                base_rate=(2.0 if args.base_upset_rate is None
                           else args.base_upset_rate),
                saa_factor=(40.0 if args.saa_factor is None
                            else args.saa_factor))
            upsets = renv.sample_upsets(args.fault_seed, horizon)
            if self_test is None:
                self_test = 0.05        # canary detection for 'none' mode
            print(f"[radiation] orbit model: base={renv.base_rate:g}/s  "
                  f"SAA x{renv.saa_factor:g} over "
                  f"{renv.saa_window[0]:.2f}-{renv.saa_window[1]:.2f} s  "
                  f"-> {len(upsets)} upset(s) sampled over {horizon:.2f} s"
                  f"  protection={args.protection}")
            if args.checkpoint_cadence is not None:
                # price one ledger checkpoint at the modeled save cost (a
                # state_dict .npz is small; dominated by the host write)
                plan = radiation_mod.optimize_cadence(
                    renv, horizon_s=horizon, checkpoint_cost_s=1e-3)
                print(f"[radiation] checkpoint cadence: T*="
                      f"{plan.cadence_s*1e3:.2f} ms "
                      f"({plan.n_checkpoints} checkpoints, expected "
                      f"replay+overhead {plan.expected_cost_s*1e3:.2f} ms "
                      f"over the horizon)")
        controller = faults_mod.FaultController(faults_mod.FaultConfig(
            seed=args.fault_seed, fault_rate=args.fault_rate,
            horizon_s=horizon if args.fault_rate > 0 else 0.0,
            self_test_period=self_test,
            recovery=args.recovery, upsets=upsets,
            protection=args.protection))
        sched.attach_faults(controller)
        for name in names:
            controller.arm(sched, name, canaries[name])
        print(f"[faults] armed {len(names)} model(s): rate="
              f"{args.fault_rate}/s  self-test period="
              f"{self_test} s  recovery={args.recovery}")

    if args.checkpoint and os.path.exists(args.checkpoint):
        # the watchdog-reboot path: a fresh process re-registers the same
        # models (reloading the pristine bitstream + weights), then
        # resumes the accepted-request ledger from the checkpoint.
        sched.load_state_dict(faults_mod.load_checkpoint(args.checkpoint))
        pending = sched.pending()
        done = {c.rid for c in sched.completions}
        print(f"[checkpoint] restored {args.checkpoint}: "
              f"{len(done)} completed, {pending} queued")
        trace = []                 # the checkpoint owns the accepted queue

    t0 = time.perf_counter()
    end = sched.serve_trace(trace)
    wall = time.perf_counter() - t0
    print(f"[serve] {len(trace)} requests over {len(names)} model(s)  "
          f"virtual={end:.3f} s  wall={wall:.3f} s")
    print(sched.summary())
    if controller is not None:
        rep = controller.report()
        print(f"[faults] injected={rep['n_injected']}  detected="
              f"{rep['n_detected']}  recovered={rep['n_recovered']}  "
              f"self-tests={rep['n_self_tests']}  overhead="
              f"{rep['overhead_energy_j']*1e3:.3f} mJ  max detection "
              f"latency={rep['max_detection_latency_s']*1e3:.2f} ms")
    if args.checkpoint:
        faults_mod.save_checkpoint(args.checkpoint, sched.state_dict())
        print(f"[checkpoint] saved {args.checkpoint}")
    missing = {name: t.n_submitted - t.n_completed
               for name, t in sched.telemetry().items()
               if t.n_completed != t.n_submitted}
    if missing:
        print(f"[serve] requests not completed: {missing}")
        return 1
    return 0


def serve_lm_compiled(args) -> int:
    """The scheduler-native LM path (DESIGN.md §15): decoder-block op
    graph -> PTQ -> compiled prefill ladder + jitted decode rungs over
    static int8 KV slots -> LMScheduler token streaming."""
    from repro.core.lm import LMEngine
    from repro.core.scheduler import LMRequest, LMScheduler
    from repro.models import lm as lm_model

    backend = args.backend.split(",")[0].strip()
    cfg = lm_model.DEFAULT_CONFIG
    graph = lm_model.build_graph(cfg)
    params = lm_model.init_params(jax.random.PRNGKey(0), cfg)
    engine = Engine(graph, params, autotune=args.autotune,
                    tuning_cache=args.tuning_cache if args.autotune
                    else None)
    if backend == "accel":
        calib = [lm_model.synthetic_input(k, cfg) for k in
                 jax.random.split(jax.random.PRNGKey(1), 8)]
        engine.calibrate(calib)
    lm = LMEngine(engine, backend=backend, n_slots=args.slots,
                  max_new_tokens=max(args.tokens, 1))
    print(lm.plan.summary())
    sched = LMScheduler(lm)
    rng = np.random.default_rng(7)
    for rid in range(args.requests):
        sched.submit(LMRequest(
            rid=rid,
            x=rng.normal(size=(cfg.seq_len, cfg.d_model)
                         ).astype(np.float32) * 0.5,
            max_new_tokens=max(args.tokens, 1)))
    comps = sched.run()
    print(sched.summary())
    sample = comps[0].tokens[:16] if comps else ()
    print(f"[lm] sample continuation: {list(sample)}")
    return 0 if len(comps) == args.requests else 1


def serve_lm(args) -> int:
    import dataclasses
    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = reduced(cfg)
    if args.kv8 and cfg.attends:
        cfg = dataclasses.replace(cfg, kv_quant=True)   # §Perf B2 int8 cache
    dims = compute_dims(cfg, tp=1)
    params = model_lib.init_params(cfg, dims, jax.random.PRNGKey(0))
    if args.w8:
        # §Perf B1: int8 weight storage, dequantized bf16 at use sites
        from repro.core import lm_quant
        params = lm_quant.dequantize_params(lm_quant.quantize_params(params))

    b, s = args.batch, args.prompt_len
    s_max = s + args.tokens
    prefill = jax.jit(make_prefill_step(cfg, dims, s_max=s_max))
    decode = jax.jit(make_decode_step(cfg, dims), donate_argnums=(1,))

    key = jax.random.PRNGKey(7)
    if cfg.frontend == "text":
        prompt = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
        batch = {"tokens": prompt}
    else:
        batch = {"embeds": jax.random.normal(key, (b, s, dims.d_model),
                                             jnp.bfloat16)}

    t0 = time.perf_counter()
    logits, cache = prefill(params, batch)
    jax.block_until_ready(logits)
    t_pre = time.perf_counter() - t0

    toks = jnp.argmax(logits, axis=-1)[:, None]
    out_tokens = [toks]
    t0 = time.perf_counter()
    for i in range(args.tokens):
        inp = toks if cfg.frontend == "text" else jax.random.normal(
            jax.random.fold_in(key, i), (b, 1, dims.d_model), jnp.bfloat16)
        logits, cache = decode(params, cache, inp, jnp.int32(s + i))
        toks = jnp.argmax(logits, axis=-1)[:, None]
        out_tokens.append(toks)
    jax.block_until_ready(toks)
    t_dec = time.perf_counter() - t0

    print(f"[lm] prefill {b}x{s}: {t_pre*1e3:.1f} ms  "
          f"({b*s/t_pre:.0f} tok/s)")
    print(f"[lm] decode {args.tokens} steps: {t_dec*1e3:.1f} ms  "
          f"({b*args.tokens/t_dec:.1f} tok/s)")
    sample = jnp.concatenate(out_tokens, axis=1)[0, :16]
    print(f"[lm] sample continuation: {list(np.asarray(sample))}")
    return 0


def trace_demo(args) -> int:
    """Jaxpr front-end demo (DESIGN.md §14): trace the depthwise-
    separable cloud-mask CNN — a model with no hand-built graph anywhere
    in models/ — and drive it trace -> inspect -> PTQ -> autotune ->
    scheduler serve."""
    from repro.frontend.demo import run_demo
    backends = tuple(b.strip() for b in args.backend.split(",") if b.strip())
    facts = run_demo(n_requests=args.requests, rate_hz=args.rate,
                     batch_top=args.batch, autotune=args.autotune,
                     backends=backends, verbose=True)
    print(f"[trace-demo] {facts['n_completed']}/{facts['n_requests']} "
          f"served, {facts['n_kept']} kept for downlink "
          f"({facts['mac_coverage']:.1%} of MACs on accel, "
          f"{facts['n_segments']} segments)")
    return 0 if facts["n_completed"] == facts["n_requests"] else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="space", choices=["space", "lm"])
    ap.add_argument("--trace-demo", action="store_true",
                    help="jaxpr front-end demo (DESIGN.md §14): trace "
                         "the depthwise-separable cloud-mask CNN (never "
                         "hand-built) and serve it end to end; honours "
                         "--requests/--rate/--batch/--backend/--autotune")
    ap.add_argument("--model", default="baseline_net",
                    help="comma list of space models to co-serve "
                         f"({', '.join(sorted(SPACE_MODELS))})")
    ap.add_argument("--backend", default="flex",
                    help="comma list of backends, primary first "
                         "(cpu, flex, accel); later entries are the "
                         "power-envelope fallbacks")
    ap.add_argument("--requests", type=int, default=64,
                    help="requests per model")
    ap.add_argument("--batch", type=int, default=16,
                    help="top batch-ladder rung")
    ap.add_argument("--rate", type=float, default=256.0,
                    help="per-model Poisson arrival rate (req/s)")
    # orbital power envelope (space mode)
    ap.add_argument("--power-budget", type=float, default=None,
                    help="sustained power budget in W (enables "
                         "energy-aware dispatch)")
    ap.add_argument("--peak-w", type=float, default=None,
                    help="instantaneous power cap in W")
    ap.add_argument("--burst-j", type=float, default=0.0,
                    help="burst energy allowance in J per window")
    ap.add_argument("--window-s", type=float, default=10.0,
                    help="sliding accounting window in s")
    ap.add_argument("--clock", default="measured",
                    choices=["measured", "modeled"],
                    help="virtual-clock source: host wall time per batch "
                         "or the plan's modeled latency (deterministic)")
    ap.add_argument("--pipeline", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="async pipelined dispatch (DESIGN.md §12): "
                         "staging/compute/readback overlap across "
                         "batches; --no-pipeline reproduces the fully "
                         "synchronous path (identical dispatches and "
                         "outputs)")
    ap.add_argument("--staging-buffers", type=int, default=2,
                    help="host staging slots per (model, rung) = max "
                         "in-flight dispatches (2 = double buffering)")
    ap.add_argument("--no-fuse", action="store_true",
                    help="skip the graph-compiler pass pipeline "
                         "(DESIGN.md §10) and serve the op-by-op plans")
    ap.add_argument("--autotune", action="store_true",
                    help="plan-time kernel tile search + prepacked "
                         "weight arenas (DESIGN.md §11); off = the "
                         "heuristic kernel blocks, bit-for-bit")
    ap.add_argument("--tuning-cache", default=None, metavar="PATH",
                    help="JSON tuning-cache path: warm caches skip all "
                         "candidate evaluations across processes")
    ap.add_argument("--autotune-measure", action="store_true",
                    help="refine the autotuner's top-K picks by "
                         "wall-clock measurement (measures the Pallas "
                         "interpreter on non-TPU hosts)")
    # degraded-mode fault injection + checkpointing (space mode; §13)
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="SEU injection rate in faults per virtual "
                         "second (Poisson, seeded); flips bits in the "
                         "accel prepacked weight arenas")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the fault schedule and flip targets")
    ap.add_argument("--self-test-period", type=float, default=None,
                    metavar="S",
                    help="run an in-band golden-canary self-test per "
                         "model every S virtual seconds (low-priority "
                         "scheduler work; detects silent corruption)")
    ap.add_argument("--recovery", default="repack",
                    choices=["repack", "demote"],
                    help="on canary mismatch: re-pack arenas from "
                         "pristine host weights, or quarantine the "
                         "primary backend (dispatch falls back) until a "
                         "delayed repair")
    # orbit-aware radiation environment (space mode; §16)
    ap.add_argument("--radiation", default="off", choices=["off", "orbit"],
                    help="orbit-aware upset model (DESIGN.md §16): sample "
                         "a typed single/MBU/control upset schedule from "
                         "the eclipse-phase + SAA rate trace (seeded by "
                         "--fault-seed) instead of / on top of the flat "
                         "--fault-rate Poisson storm")
    ap.add_argument("--base-upset-rate", type=float, default=None,
                    metavar="R",
                    help="GCR background upset rate in upsets per virtual "
                         "second (default 2.0)")
    ap.add_argument("--saa-factor", type=float, default=None, metavar="X",
                    help="South Atlantic Anomaly rate multiplier over the "
                         "orbit-relative SAA window (default 40)")
    ap.add_argument("--protection", default="none",
                    choices=["none", "ecc", "tmr"],
                    help="arena protection mode: canary-only detection, "
                         "SEC ECC per byte-interleaved domain (+12.5%% "
                         "footprint + scrub), or TMR (3x footprint, "
                         "upsets voted away)")
    ap.add_argument("--checkpoint-cadence", default=None, metavar="auto",
                    help="print the expected-replay-loss-optimal ledger "
                         "checkpoint cadence for the radiation "
                         "environment (pass 'auto')")
    ap.add_argument("--checkpoint", default=None, metavar="PATH",
                    help="scheduler-ledger checkpoint (.npz): restored "
                         "at startup if present (the watchdog-reboot "
                         "path — zero accepted requests lost), saved at "
                         "exit")
    # lm mode
    ap.add_argument("--lm-compiled", dest="lm_compiled", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="lm mode: serve the decoder-block op graph "
                         "through the compiled prefill/decode rung "
                         "ladder with int8 KV-cache slots (DESIGN.md "
                         "§15); --lm-legacy selects the raw jit loop")
    ap.add_argument("--lm-legacy", dest="lm_compiled",
                    action="store_false",
                    help="lm mode: the pre-§15 raw jit prefill/decode "
                         "loop over an --arch config")
    ap.add_argument("--slots", type=int, default=4,
                    help="lm mode: KV-cache slots (max in-flight "
                         "decode requests)")
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--kv8", action="store_true",
                    help="int8 KV cache (lm mode; §Perf B2)")
    ap.add_argument("--w8", action="store_true",
                    help="int8 PTQ weights (lm mode; §Perf B1)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.trace_demo:
        return trace_demo(args)
    if args.mode == "space":
        return serve_space(args)
    if args.lm_compiled:
        return serve_lm_compiled(args)
    return serve_lm(args)


if __name__ == "__main__":
    raise SystemExit(main())
