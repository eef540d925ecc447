"""Smoke run of the space-serving main path on one TPU chip.

    python chip_smoke.py

For each of the six space nets (``SPACE_MODELS``, at their published
widths) it builds the engine from a seed and PTQ-calibrates it on the
chip (the quantize kernel compiled by Mosaic; its int8 weight codes
must agree with the interpreter's to one step). Then, for each compiled
backend — ``accel`` (the Pallas int8 kernels) and ``flex`` (XLA f32) —
it registers the engine with a ``ContinuousBatchingScheduler``
(``pipeline=True``, rung ladder 1/4/16) and serves a seeded Poisson
trace of requests through ``serve_trace``. Then it checks that every
request completed and compares each served output with a reference
computed in the same process on the host CPU device:

* the f32 reference is the flex plan placed on the CPU (IEEE f32);
* both chip backends are held to the model's pinned PTQ bound
  (``repro.core.conformance.ACCEL_ATOL``), argmax classes may flip only
  on near-ties (the margin rule), ESPERTA's thresholded ``warn*`` flags
  are exempt, and the VAE's ``sample`` draw (its noise comes from the
  scheduler's per-dispatch keys) is only checked to be finite. flex is
  held to the int8 path's bound because f32 matmuls on the MXU at the
  default precision round their operands to bf16: 8 significant bits
  per element, finer everywhere than int8's 1/254 of the tensor's
  absmax, so no f32 element can move further than its int8 counterpart;
* accel is also compared with the Pallas interpreter's output of the
  same quantized plan (the chip's calibration, copied to a CPU twin of
  the engine) on the CPU; whether the two agree bit for bit is
  printed, not required (sigmoid, division and the f32 flex-tail nodes
  may round differently on the chip).

One line per model reports its calibration (the PTQ-demoted nodes and
how many weight codes differ from the interpreter's) and one line per
(model, backend) the rungs compiled, the compile seconds, the errors
and the compile-cache hits. The last line is
``{"ok": true, "device": {...}}`` only when every check passed; any
failure exits non-zero and never prints it. Without a TPU it exits 2
before touching the program. No speed is measured here.
"""
from __future__ import annotations

import json
import os
import sys
import time

N_REQUESTS = 18                  # one full rung-16 dispatch plus a ragged tail
N_CALIB = 4
LADDER = (1, 4, 16)
RATE_HZ = 400.0                  # Poisson arrivals/s, well above service rate
SEED = 0
MODELS = ("cnet_plus_scalar", "vae_encoder", "baseline_net", "reduced_net",
          "logistic_net", "multi_esperta")
BACKENDS = ("accel", "flex")


class CacheCounter:
    """Counts JAX persistent-compilation-cache lookups and hits."""

    def __init__(self, jax):
        self.requests = self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def stack(reqs):
    import numpy as np
    return {k: np.stack([r[k] for r in reqs]) for k in reqs[0]}


def check_ptq_codes(name, graph, params, chip_engine, cpu):
    """The chip's PTQ weight codes (the quantize kernel compiled by
    Mosaic) against the interpreter's on the same weights: scales equal
    to f32 rounding, codes at most one step apart (a reciprocal rounded
    differently can move a .5 tie). Returns how many codes differ."""
    import jax
    import numpy as np
    from repro.core.quantize import quantize_weights
    with jax.default_device(cpu):
        want = quantize_weights(graph, params)
    n_diff = 0
    for node, q in chip_engine.quantized.items():
        msg = f"{name}/ptq/{node}"
        np.testing.assert_allclose(np.asarray(q.w_scale),
                                   np.asarray(want[node].w_scale),
                                   rtol=1e-6, err_msg=msg)
        d = np.abs(np.asarray(q.w_q, np.int32)
                   - np.asarray(want[node].w_q, np.int32))
        assert d.max() <= 1, f"{msg}: codes differ by {d.max()}"
        n_diff += int(np.count_nonzero(d))
    return n_diff


def references(graph, params, chip_engine, reqs, cpu):
    """(f32 outputs, interpreted accel outputs), both on the CPU; the
    interpreter runs the chip engine's calibration, so both accel runs
    execute the same quantized plan."""
    import jax
    import numpy as np
    from repro.core.engine import Engine
    with jax.default_device(cpu):
        engine = Engine(graph, params)
        engine.share_calibration(chip_engine, device=cpu)
        batch = stack(reqs)
        rngs = jax.random.split(jax.random.PRNGKey(SEED), len(reqs))
        f32 = engine.run_batch(batch, "flex", rngs)
        interp = engine.run_batch(batch, "accel", rngs)
        return ({k: np.asarray(v) for k, v in f32.items()},
                {k: np.asarray(v) for k, v in interp.items()})


def serve(name, engine, backend, reqs, cache):
    """Compile the ladder, then serve ``reqs`` on a Poisson trace through
    the pipelined scheduler. Returns (outputs stacked by request, compile
    seconds, cache hits during compile, dispatch (rung, n_real) list)."""
    from repro.core.scheduler import (ContinuousBatchingScheduler,
                                      poisson_arrivals)
    hits0 = cache.hits
    t0 = time.perf_counter()
    for rung in LADDER:
        engine.compile(backend, rung)
    compile_s = time.perf_counter() - t0
    sched = ContinuousBatchingScheduler(pipeline=True)
    sched.register(name, engine, backend=backend, ladder=LADDER,
                   warmup_sample=reqs[0])
    trace = [(t, name, r) for t, r in
             zip(poisson_arrivals(RATE_HZ, len(reqs), seed=SEED), reqs)]
    sched.serve_trace(trace)
    by_rid = {c.rid: c.outputs for c in sched.completions}
    if sorted(by_rid) != list(range(len(reqs))):
        raise AssertionError(
            f"{name}/{backend}: completed request ids {sorted(by_rid)}, "
            f"expected 0..{len(reqs) - 1}")
    outs = stack([by_rid[i] for i in range(len(reqs))])
    dispatches = [(d.rung, d.n_real) for d in sched.dispatches
                  if not d.failed]
    return outs, compile_s, cache.hits - hits0, dispatches


def compare(name, backend, graph, got, ref, interp):
    """Apply the agreement rules; returns (max abs err, max rel err,
    bit-exact vs the interpreter or None). Raises on a failed check."""
    import numpy as np
    from repro.core.conformance import (ACCEL_ATOL, DECISION_OF,
                                        assert_flips_margin_bounded,
                                        is_decision)
    atol = ACCEL_ATOL[name]
    random_outs = {n for n in graph.outputs
                   if graph.nodes[n].op == "sample_normal"}
    assert set(got) == set(ref), (name, backend, set(got), set(ref))
    max_abs = max_rel = 0.0
    for k in sorted(ref):
        a, r = got[k], ref[k]
        msg = f"{name}/{backend}/{k}"
        assert a.shape == r.shape and a.dtype == r.dtype, (
            msg, a.shape, a.dtype, r.shape, r.dtype)
        if np.issubdtype(a.dtype, np.integer):
            if k in DECISION_OF:
                assert_flips_margin_bounded(a, r, ref[DECISION_OF[k]], atol,
                                            msg)
            else:
                np.testing.assert_array_equal(a, r, err_msg=msg)
            continue
        assert np.all(np.isfinite(a)), f"{msg}: non-finite output"
        if k in random_outs or is_decision(k):
            continue
        err = float(np.max(np.abs(a.astype(np.float64) - r)))
        max_abs = max(max_abs, err)
        max_rel = max(max_rel, err / max(float(np.max(np.abs(r))), 1e-30))
        np.testing.assert_allclose(a, r, rtol=1e-5, atol=atol, err_msg=msg)
    exact = None
    if backend == "accel":
        exact = all(np.array_equal(got[k], interp[k]) for k in interp
                    if k not in random_outs)
    return max_abs, max_rel, exact


def run(cache) -> None:
    """Serve and check every (model, backend); raises on any failure."""
    import jax
    from repro.core.conformance import ACCEL_ATOL
    from repro.core.engine import Engine
    from repro.models import SPACE_MODELS, synthetic_requests
    cpu = jax.devices("cpu")[0]
    chip = jax.devices()[0]
    for mi, name in enumerate(MODELS):
        model = SPACE_MODELS[name]
        graph = model.build_graph()
        with jax.default_device(cpu):
            reqs = synthetic_requests(model, N_REQUESTS, seed=SEED + mi)
            params = model.init_params(jax.random.PRNGKey(SEED))
        engine = Engine(graph, jax.device_put(params, chip))
        t0 = time.perf_counter()
        engine.calibrate(reqs[:N_CALIB])            # PTQ on the chip
        calib_s = time.perf_counter() - t0
        n_diff = check_ptq_codes(name, graph, params, engine, cpu)
        print(f"[smoke] {name:16s} ptq   calibrated on {chip.platform} in "
              f"{calib_s:.2f}s (compile included) n_calib={N_CALIB} "
              f"demoted={engine.planned('accel').demoted} "
              f"codes_off_by_one_vs_interpret={n_diff}", flush=True)
        ref, interp = references(graph, params, engine, reqs, cpu)
        for backend in BACKENDS:
            got, compile_s, hits, dispatches = serve(name, engine, backend,
                                                     reqs, cache)
            max_abs, max_rel, exact = compare(name, backend, graph, got,
                                              ref, interp)
            line = (f"[smoke] {name:16s} {backend:5s} rungs="
                    f"{','.join(map(str, LADDER))} compile_s={compile_s:.2f}"
                    f" cache_hits={hits} served={len(reqs)}/{len(reqs)}"
                    f" dispatches={dispatches} max_abs_err={max_abs:.3e}"
                    f" max_rel_err={max_rel:.3e} atol={ACCEL_ATOL[name]:g}")
            if exact is not None:
                line += f" bitexact_vs_interpret={exact}"
            print(line, flush=True)


def main() -> int:
    # the host-side references need JAX's CPU backend next to the chip's
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    cache = CacheCounter(jax)
    print(f"[smoke] device_kind={devices[0].device_kind} "
          f"count={len(devices)} compile_cache={cache_dir}", flush=True)
    run(cache)
    print(f"[smoke] compile cache: {cache.hits} hit(s) of "
          f"{cache.requests} lookup(s)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
