"""One run of one cell, on the machine it is started on.

Set-up (:func:`setup`): name the device (no TPU, or fewer chips than the
cell asks for: :class:`NoChip`, before the program is touched), turn on
the program's compile cache, make the weights and a pool of distinct
requests on the device from the seed in one jitted call, PTQ-calibrate
the engine on the chip, compile the configuration's backend at its
ladder, and register it with the pipelined
``ContinuousBatchingScheduler``, whose warm-up runs every rung twice.

The window (:func:`serve`): the scheduler serves in wall-clock mode
(``start`` / ``submit`` / ``stop``) for ``seconds``, while a generator
thread submits each request at its due time on the open-loop schedule
of the traffic file, passing the due time as the arrival. Latency runs
from the due time to the moment the request's batch is retired to the
host, so a stall is charged to every request behind it. Requests still
queued when the window closes are served after it (the drain) and
compared too; they count toward latency but not toward the window's
rate.

With ``trace`` the run serves the same untraced window first, and the
per-layer metrics whose source is the host's clock or the program's
records (``bench/metrics``) are read from it, at the operating point of
the end-to-end metrics: the profiler slows the host. A traced segment
of ``TRACE_S`` seconds of the same traffic follows, from an empty queue
and an idle device, and the metrics read from the device trace come
from it.

After serving (:func:`run`): device peak memory is read, the program
is freed, the plain reference runs over the pool on the chip and every
completion is compared with it (``bench/compare.py``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import os
import shutil
import sys
import tempfile
import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import arrivals, compare, spec, trace_reduce, workcount
from bench.reference import module as reference_module

SRC = os.path.join(spec.ROOT, "src")
LEAD_S = 0.05           # the first due time, after the window's clock starts
GEN_JOIN_S = 60.0       # the generator ends by the window's close
POOL_BLOCK = 32         # pooled inputs made per call
# the traced segment's length: a 10 s trace took the host past 20 GiB
# while it was collected (TPU v5e host)
TRACE_S = 3.0


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def stderr_log(line: str) -> None:
    print(line, file=sys.stderr, flush=True)


def device_check(chips: int, require_tpu: bool = True):
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"needs {chips} chip(s); JAX found {len(devices)}")
    return devices


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, high bits included."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def make_data(ref, cfg: Dict, seed: int, log: Callable[[str], None] = None):
    """(float32 weights on the device, host pool of inputs [N, ...]) from
    the seed: the weights in one jitted call, the pool in blocks of
    ``POOL_BLOCK`` inputs by one compiled program (the TPU compiler's time
    for a threefry draw grows with its size: 20 s for the whole CNet
    pool in one call, 2.5 s for a block)."""
    import jax
    t0 = time.monotonic()
    k_w, k_in = jax.random.split(seed_key(seed))
    params = jax.jit(lambda k: ref.init(cfg, k))(k_w)
    t1 = time.monotonic()
    n = cfg["pool_size"]
    block = min(POOL_BLOCK, n)
    if n % block:
        raise ValueError(f"pool_size {n} is not a multiple of {block}")
    make = jax.jit(lambda k: ref.inputs(cfg, k, block))
    blocks = [jax.device_get(make(jax.random.fold_in(k_in, i)))
              for i in range(n // block)]
    pool = {k: np.concatenate([b[k] for b in blocks]) for k in blocks[0]}
    if log is not None:
        log(f"[bench] data weights_s={t1 - t0:.3f} "
            f"pool_s={time.monotonic() - t1:.3f}")
    return params, pool


class CompileCounter:
    """Compiles, traces and persistent-cache reads, with their times."""

    def __init__(self):
        import jax
        self.times: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if (event.startswith("/jax/core/compile")
                or event.startswith("/jax/compilation_cache/cache_retr")):
            self.times.append(time.monotonic())

    def between(self, lo: float, hi: float) -> int:
        return sum(1 for t in self.times if lo <= t <= hi)


class GcWatch:
    """The garbage collector's pauses, with the generation collected: a
    host stall inside the window is either one of these or not."""

    def __init__(self):
        self.pauses: List = []          # (end, generation, seconds)
        self._t0 = None
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append((time.monotonic(), info["generation"],
                                time.perf_counter() - self._t0))
            self._t0 = None

    def close(self) -> None:
        gc.callbacks.remove(self._on)

    def summary(self, lo: float, hi: float) -> str:
        out = []
        for gen in (0, 1, 2):
            s = [p for t, g, p in self.pauses if g == gen and lo <= t <= hi]
            out.append(f"gen{gen}=n{len(s)}/sum{sum(s) * 1e3:.1f}"
                       f"/max{max(s, default=0.0) * 1e3:.1f}ms")
        return " ".join(out)


def _span(tracing: bool, name: str):
    if not tracing:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Recorder:
    """The benchmark's own wrappers on the scheduler instance and its
    pipelines: when each request's batch was dispatched (the scheduler's
    clock at the step that picked it) and retired (result on the host),
    and how long each ``execute_batch_async`` (staging, transfer,
    launch) took.
    ``tracing`` turns their profiler spans on."""
    tracing: bool = False
    retired: Dict[int, float] = dataclasses.field(default_factory=dict)
    started: Dict[int, float] = dataclasses.field(default_factory=dict)
    dispatch_calls: List = dataclasses.field(default_factory=list)

    def install(self, sched, model: str) -> None:
        retire, step = sched._retire, sched.step

        def wrapped_retire(inf):
            with _span(self.tracing, "bench.retire"):
                retire(inf)
            t = time.monotonic()
            for req in inf.reqs:
                self.retired[req.rid] = t
                self.started[req.rid] = inf.started

        def wrapped_step(now, force=False):
            with _span(self.tracing, "bench.step"):
                return step(now, force)
        sched._retire, sched.step = wrapped_retire, wrapped_step
        for rungs in sched._svcs[model].pipelines.values():
            for pipe in rungs.values():
                pipe.execute_batch_async = self._timed(
                    pipe.execute_batch_async)

    def _timed(self, fn: Callable) -> Callable:
        def wrapped(reqs, rng=None):
            t0 = time.monotonic()
            with _span(self.tracing, "bench.dispatch"):
                ticket = fn(reqs, rng=rng)
            self.dispatch_calls.append((t0, time.monotonic() - t0))
            return ticket
        return wrapped


@dataclasses.dataclass
class State:
    """Everything set-up made: the device, the data and the program."""
    cfg: Dict
    ref: object
    devices: List
    params: Dict
    pool: Dict[str, np.ndarray]
    reqs: List[Dict[str, np.ndarray]]
    engine: object
    sched: object
    rec: Recorder
    compiles: CompileCounter
    gc_watch: GcWatch


def setup(cfg: Dict, seed: int, chips: int, require_tpu: bool = True,
          log: Callable[[str], None] = stderr_log) -> State:
    devices = device_check(chips, require_tpu)
    dev = devices[0]
    log(f"[bench] device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    import jax
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    from repro.core.engine import Engine
    from repro.core.scheduler import ContinuousBatchingScheduler
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import SPACE_MODELS

    cache_dir = enable_compile_cache()
    os.makedirs(cache_dir, exist_ok=True)
    # every program set-up compiles is kept, so that a run's set-up after
    # the first in a checkout compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = CompileCounter()
    ref = reference_module(cfg)
    t = time.monotonic()
    params, pool = make_data(ref, cfg, seed, log)
    reqs = [{k: v[i] for k, v in pool.items()}
            for i in range(cfg["pool_size"])]
    data_s = time.monotonic() - t
    engine = Engine(SPACE_MODELS[cfg["model"]].build_graph(), params)
    t = time.monotonic()
    engine.calibrate(reqs[:cfg["calibration_samples"]])
    calib_s = time.monotonic() - t
    t = time.monotonic()
    for rung in cfg["ladder"]:
        engine.compile(cfg["backend"], rung)
    compile_s = time.monotonic() - t
    sched = ContinuousBatchingScheduler(pipeline=True)
    t = time.monotonic()
    sched.register(cfg["model"], engine, backend=cfg["backend"],
                   ladder=cfg["ladder"], deadline_s=cfg["deadline_s"],
                   warmup_sample=reqs[0])
    warmup_s = time.monotonic() - t
    log(f"[bench] setup data_s={data_s:.3f} calibrate_s={calib_s:.3f} "
        f"compile_s={compile_s:.3f} warmup_s={warmup_s:.3f} "
        f"demoted={sorted(engine.planned(cfg['backend']).demoted)} "
        f"compile_cache={cache_dir}")
    rec = Recorder()
    rec.install(sched, cfg["model"])
    # what set-up allocated (traced programs, compiled plans) moves out of
    # the collector's reach, so a full collection in the window does not
    # walk it
    gc.collect()
    gc.freeze()
    return State(cfg, ref, devices, params, pool, reqs, engine, sched, rec,
                 compiles, GcWatch())


@dataclasses.dataclass
class Window:
    """One served window: per request its due time, id and pooled input,
    and what the run saw at the close."""
    seconds: float
    t_start: float
    t_end: float
    due: np.ndarray
    rids: np.ndarray
    late: np.ndarray
    pool_index: np.ndarray
    backlog: int
    error: Optional[BaseException]
    n_compiles: int
    gc_pauses: str
    drain_s: float
    trace: Optional[trace_reduce.Summary]


def serve(state: State, traffic: Dict, seed: int, seconds: float,
          trace: bool = False, keep_trace: Optional[str] = None) -> Window:
    """Serve one open-loop window, under the profiler with ``trace``;
    returns after the drain."""
    import jax
    sched, model = state.sched, state.cfg["model"]
    plan = arrivals.schedule(traffic, seed, seconds, state.cfg["pool_size"])
    n = len(plan.offsets)
    # the generator's loop touches only plain lists, so that it holds the
    # interpreter's lock for as little as it can
    rids: List[int] = [-1] * n
    late: List[float] = [0.0] * n
    inputs = [state.reqs[j] for j in plan.pool_index.tolist()]
    state.rec.tracing = trace
    trace_dir = None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1      # the bench.* spans, not the runtime's
        opts.enable_hlo_proto = False
        trace_dir = keep_trace or tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    sched.start()
    t_start = time.monotonic() + LEAD_S
    due = (t_start + plan.offsets).tolist()
    t_end = t_start + seconds

    def generate():
        submit, clock, sleep = sched.submit, time.monotonic, time.sleep
        for i in range(n):
            wait = due[i] - clock()
            if wait > 0:
                sleep(wait)
            if trace:
                with _span(True, "bench.submit"):
                    rids[i] = submit(model, inputs[i], arrival=due[i])
            else:
                rids[i] = submit(model, inputs[i], arrival=due[i])
            late[i] = clock() - due[i]

    gen = threading.Thread(target=generate, name="bench-generator")
    time.sleep(max(t_start - time.monotonic(), 0.0))
    with _span(trace, "bench.window"):
        gen.start()
        time.sleep(max(t_end - time.monotonic(), 0.0))
    if trace:
        jax.profiler.stop_trace()
        state.rec.tracing = False
    gen.join(GEN_JOIN_S)
    backlog = sched.pending()
    error = None
    try:
        sched.stop(drain=True)
    except Exception as ex:     # the dispatcher died; its batch requeued
        error = ex
    drain_s = time.monotonic() - t_end
    summary = None
    if trace:
        summary = trace_reduce.Summary(*trace_reduce.load(trace_dir))
        if keep_trace is None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    return Window(seconds, t_start, t_end, np.asarray(due),
                  np.asarray(rids, np.int64), np.asarray(late),
                  plan.pool_index, backlog, error,
                  state.compiles.between(t_start, t_end),
                  state.gc_watch.summary(t_start, t_end), drain_s, summary)


@dataclasses.dataclass
class Run:
    """What the per-layer readers (``bench/metrics``) see of one served
    window, ``[t_start, t_end]``."""
    seconds: float
    t_start: float                      # window opens (monotonic)
    t_end: float
    due: np.ndarray                     # per request
    dispatched: np.ndarray              # its batch picked; nan if never
    done: np.ndarray                    # retired to the host; nan if never
    dispatches: List                    # the program's DispatchRecords
    dispatch_calls: List                # (start, seconds) per call
    samples_per_s: float
    layers: List
    peak: Optional[Dict[str, float]]
    trace: Optional[trace_reduce.Summary]

    def in_window(self, t: float) -> bool:
        return self.t_start <= t <= self.t_end


def percentile(x: np.ndarray, q: float) -> float:
    return float(np.percentile(x, q)) if len(x) else float("nan")


def collect(state: State, win: Window):
    """(Run, served outputs, their pooled inputs, number missing)."""
    done = np.array([state.rec.retired.get(int(r), np.nan)
                     for r in win.rids])
    dispatched = np.array([state.rec.started.get(int(r), np.nan)
                           for r in win.rids])
    complete = ~np.isnan(done)
    by_rid = {c.rid: c.outputs for c in state.sched.completions}
    served = [by_rid[int(r)] for r in win.rids[complete]]
    n_done_window = int(np.sum(done[complete] <= win.t_end))
    dispatches = [d for d in state.sched.dispatches if not d.failed
                  and win.t_start - LEAD_S <= d.started]
    kind = state.devices[0].device_kind
    peak = (workcount.peaks(kind) if state.devices[0].platform == "tpu"
            else None)
    run = Run(win.seconds, win.t_start, win.t_end, win.due, dispatched,
              done, dispatches, [c for c in state.rec.dispatch_calls
                                 if c[0] >= win.t_start - LEAD_S],
              n_done_window / win.seconds, state.ref.layers(state.cfg),
              peak, win.trace)
    return run, served, win.pool_index[complete], int((~complete).sum())


def end_to_end(run: Run, setup_s: float) -> Dict[str, float]:
    complete = ~np.isnan(run.done)
    lat_ms = (run.done[complete] - run.due[complete]) * 1e3
    return {"samples_per_s": run.samples_per_s,
            "p50_latency_ms": percentile(lat_ms, 50),
            "p95_latency_ms": percentile(lat_ms, 95),
            "setup_s": setup_s}


def log_window(log, win: Window, run: Run, name: str = "window") -> None:
    complete = ~np.isnan(run.done)
    n = len(run.due)
    log(f"[bench] {name} attempted={n} completed={int(complete.sum())} "
        f"completed_in_window={int(np.sum(run.done[complete] <= run.t_end))}"
        f" backlog_at_close={win.backlog} drain_s={win.drain_s:.3f} "
        f"dispatches={len(run.dispatches)} "
        f"compiles_in_window={win.n_compiles} generator_late_ms "
        f"p50={percentile(win.late, 50) * 1e3:.3f} "
        f"p99={percentile(win.late, 99) * 1e3:.3f} "
        f"max={(win.late.max() if n else 0.0) * 1e3:.3f} "
        f"max_at_s={(win.due[win.late.argmax()] - win.t_start if n else 0.0):.3f}"
        f" gc_pauses {win.gc_pauses}")
    if win.error is not None:
        log(f"[bench] dispatcher error: {win.error!r}")


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool,
        t_process: float, require_tpu: bool = True,
        log: Callable[[str], None] = stderr_log,
        keep_trace: Optional[str] = None) -> Dict:
    """One run of ``cell``; returns the result line as a dict."""
    log(f"[bench] cell={cell.name} seed={seed} seconds={seconds} "
        f"trace={int(trace)}")
    state = setup(cell.config, seed, cell.chips, require_tpu, log)
    win = serve(state, cell.traffic, seed, seconds)
    setup_s = win.t_start - t_process
    main, served, served_index, n_missing = collect(state, win)
    log_window(log, win, main)
    errors = [win.error]
    attempted = len(win.due)
    traced = twin = None
    if trace:
        twin = serve(state, cell.traffic, seed, TRACE_S, True, keep_trace)
        traced, t_served, t_index, t_missing = collect(state, twin)
        log_window(log, twin, traced, "traced")
        served += t_served
        served_index = np.concatenate([served_index, t_index])
        n_missing += t_missing
        errors.append(twin.error)
        attempted += len(twin.due)
    devices = state.devices[:cell.chips]
    stats = [d.memory_stats() or {} for d in devices]
    mem_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)

    # the plain reference over the pool, once the program is freed
    cfg, ref, params, pool = state.cfg, state.ref, state.params, state.pool
    state.gc_watch.close()
    del state
    gc.unfreeze()
    gc.collect()
    ref_out = compare.reference_outputs(ref, cfg, params, pool)
    int8_out = (compare.rounded_outputs(ref, cfg, params, pool, 8)
                if compare.INT8_RATIO in cfg.get("limits", {}) else None)
    numbers, widest = compare.readings(cfg, served, served_index, ref_out,
                                       n_missing, int8_out)
    numbers["dispatcher_errors"] = float(sum(e is not None for e in errors))
    correct, table = compare.verdict(cfg, numbers)
    for name, value in dict(numbers, out_max=widest).items():
        if name not in table:
            log(f"[bench] read {name}={value!r} (not compared)")

    metrics = {}
    if not trace:
        e2e = end_to_end(main, setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        # the device's trace from the traced segment; the host's clock
        # and the program's records from the untraced window
        for m in cell.per_layer:
            source = traced if m.spec["source"] == "device_trace" else main
            value = m.read(source)
            if value is not None:
                metrics[m.spec["name"]] = {"value": value,
                                           "unit": m.spec["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": n_missing, "metrics": metrics, "device": device}
    if twin is not None:
        lo, hi = twin.trace.window
        device["busy_s"] = twin.trace.busy_seconds(lo, hi)
        device["window_s"] = (hi - lo) * 1e-9
        result["breakdown"] = twin.trace.breakdown()
    for name, row in table.items():
        log(f"[bench] compared {name}={row['value']!r} "
            f"limit={row['limit']!r}")
    result["compared"] = table
    return result
