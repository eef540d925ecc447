"""Serving-layer regression tests: pipeline ragged tails, the empty
stream, and the continuous-batching scheduler.

* Empty request stream returns a zero-request ServeStats (seed crashed
  with ``reqs[0]`` IndexError).
* Ragged-tail losslessness: for stream lengths NOT divisible by the
  batch size, pipeline/scheduler outputs are BIT-identical to the same
  compiled plan run on a manually padded batch — staging, padding, and
  slice-off introduce no numeric change whatsoever.
* Per-sample equivalence: pipeline/scheduler outputs match a loop of
  single-sample ``Engine.run`` calls. On the fully-int8 accel path this
  is bit-exact (static scales, int32 accumulation); fp32 flex matmuls
  reduce in a batch-size-dependent order, so the flex bound is float
  associativity (~1e-6 relative), with bitwise equality additionally
  asserted for the int8-exact model/backend cell.
* Scheduler: co-serves two models round-robin, drops/duplicates nothing,
  dispatches only ladder rungs, precompiles the ladder (serving never
  re-traces), and the async wall-clock mode completes every request.
* Pipelined wall-clock mode retires a batch as soon as its outputs are
  done when the dispatcher idles, oldest first, recovers from a failure
  found there as from one found at dispatch, and names each record's
  cause of retirement.
* Power envelope: tightening the budget mid-trace degrades dispatch
  (smaller rungs, cpu/flex fallback, recorded deferrals) without ever
  dropping or duplicating a request and with a clean envelope audit; a
  peak cap below the DPU's power excludes it outright; a model no
  backend of which can ever fit is rejected at register time.
"""
import time

import jax
import numpy as np
import pytest

from repro.core.energy import PowerEnvelope
from repro.core.engine import Engine
from repro.core.pipeline import (DispatchTicket, ServeStats, ServingPipeline,
                                 stage_batch)
from repro.core.scheduler import (ContinuousBatchingScheduler,
                                  bursty_arrivals, poisson_arrivals)
from repro.models import SPACE_MODELS, synthetic_requests

# two cheap space models, one per paper toolchain family
MODELS = ("logistic_net", "multi_esperta")


@pytest.fixture(scope="module")
def engines():
    out = {}
    for name in MODELS:
        m = SPACE_MODELS[name]
        e = Engine(m.build_graph(), m.init_params(jax.random.PRNGKey(0)))
        e.calibrate([m.synthetic_input(jax.random.PRNGKey(i))
                     for i in range(2)])
        out[name] = (m, e)
    return out


def _requests(m, n, seed=3):
    return synthetic_requests(m, n, seed=seed)


# ---------------------------------------------------------------------------
# empty stream (seed regression: IndexError at reqs[0])
# ---------------------------------------------------------------------------


def test_empty_stream_returns_zero_stats(engines):
    _, e = engines["logistic_net"]
    pipe = ServingPipeline(e, backend="flex", batch_size=4)
    stats = pipe.run([])
    assert isinstance(stats, ServeStats)
    assert stats.n_requests == 0 and stats.n_kept == 0
    assert stats.fps == 0.0 and stats.phases.wall == 0.0
    assert stats.downlink_reduction == 1.0  # nothing sent


def test_stage_batch_rejects_empty_and_oversize(engines):
    m, _ = engines["logistic_net"]
    with pytest.raises(ValueError):
        stage_batch([], 4)
    with pytest.raises(ValueError):
        stage_batch(_requests(m, 5), 4)


# ---------------------------------------------------------------------------
# ragged tails
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["flex", "accel"])
@pytest.mark.parametrize("name", MODELS)
def test_ragged_tail_bit_identical_to_padded_plan(name, backend, engines):
    """Pipeline output for a ragged stream == the SAME compiled plan fed a
    manually padded batch, bit for bit: the serving layer's staging,
    padding, and slicing add zero numeric perturbation."""
    m, e = engines[name]
    B, L = 4, 7                                   # 7 % 4 != 0
    reqs = _requests(m, L)
    pipe = ServingPipeline(e, backend=backend, batch_size=B)

    for lo in range(0, L, B):
        chunk = reqs[lo:lo + B]
        got = pipe.execute_batch(chunk).outputs
        padded = chunk + [chunk[-1]] * (B - len(chunk))
        ref = e.run_batch(
            {k: np.stack([np.asarray(r[k], np.float32) for r in padded])
             for k in padded[0]}, backend)
        for k in ref:
            np.testing.assert_array_equal(
                got[k], np.asarray(ref[k])[:len(chunk)],
                err_msg=f"{name}/{backend}/{k} chunk@{lo}")


@pytest.mark.parametrize("backend", ["flex", "accel"])
@pytest.mark.parametrize("name", MODELS)
def test_ragged_tail_matches_per_sample_engine_run(name, backend, engines):
    """Pipeline over a ragged stream == a loop of per-sample Engine.run.
    Bit-for-bit on the fully-int8 cell; float-associativity tolerance on
    fp32 cells (batched gemms reduce in batch-size-dependent order)."""
    m, e = engines[name]
    B, L = 4, 7
    reqs = _requests(m, L)
    pipe = ServingPipeline(e, backend=backend, batch_size=B)
    outs = []
    for lo in range(0, L, B):
        res = pipe.execute_batch(reqs[lo:lo + B])
        outs += [{k: v[i] for k, v in res.outputs.items()}
                 for i in range(len(res.keep))]
    assert len(outs) == L
    bit_exact = name == "multi_esperta" and backend == "accel"
    for i, req in enumerate(reqs):
        single = e.run(req, backend)
        for k in single:
            a, b = outs[i][k], np.asarray(single[k])
            if bit_exact:
                np.testing.assert_array_equal(a, b,
                                              err_msg=f"{name}/{backend}/{k}")
            else:
                np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6,
                                           err_msg=f"{name}/{backend}/{k}")


@pytest.mark.parametrize("backend", ["flex", "accel"])
@pytest.mark.parametrize("name", MODELS)
def test_scheduler_ragged_stream_matches_per_sample(name, backend, engines):
    """Scheduler-served outputs (ladder dispatch + deadline flushes over a
    non-rung-aligned stream) match per-sample Engine.run, request by
    request."""
    m, e = engines[name]
    L = 11                                        # not on any rung boundary
    reqs = _requests(m, L)
    sched = ContinuousBatchingScheduler()
    sched.register(name, e, backend=backend, ladder=(1, 4),
                   warmup_sample=reqs[0])
    trace = [(0.001 * i, name, r) for i, r in enumerate(reqs)]
    sched.serve_trace(trace)

    comps = {c.rid: c for c in sched.completions}
    assert len(comps) == L
    bit_exact = name == "multi_esperta" and backend == "accel"
    for rid, req in enumerate(reqs):
        single = e.run(req, backend)
        for k in single:
            a, b = comps[rid].outputs[k], np.asarray(single[k])
            if bit_exact:
                np.testing.assert_array_equal(a, b,
                                              err_msg=f"{name}/{backend}/{k}")
            else:
                np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6,
                                           err_msg=f"{name}/{backend}/{k}")


# ---------------------------------------------------------------------------
# staging-buffer reuse (DESIGN.md §12)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["flex", "accel"])
@pytest.mark.parametrize("name", MODELS)
def test_reused_arena_bit_exact_vs_fresh_allocation(name, backend, engines):
    """Ragged tails staged into a REUSED host arena slot produce outputs
    bit-identical to the freshly-allocating `stage_batch` path — including
    a shrinking batch reusing a slot still holding a longer batch's rows
    (every row is rewritten: real samples + repeat-last padding)."""
    m, e = engines[name]
    B = 4
    reqs = _requests(m, 9)
    arena_pipe = ServingPipeline(e, backend=backend, batch_size=B,
                                 staging_buffers=1)
    fresh_pipe = ServingPipeline(e, backend=backend, batch_size=B,
                                 staging_buffers=1)
    fresh_pipe.arena.acquire()      # hog the slot -> always falls back

    # full batch, then shrinking ragged tails through the SAME slot
    for lo, hi in ((0, 4), (4, 6), (6, 7)):
        chunk = reqs[lo:hi]
        got = arena_pipe.execute_batch(chunk, rng=jax.random.PRNGKey(lo))
        ref = fresh_pipe.execute_batch(chunk, rng=jax.random.PRNGKey(lo))
        assert got.keep == ref.keep
        for k in ref.outputs:
            np.testing.assert_array_equal(
                got.outputs[k], ref.outputs[k],
                err_msg=f"{name}/{backend}/{k} chunk [{lo}:{hi}]")
    assert arena_pipe.arena.n_staged == 3       # all via the one slot
    assert arena_pipe.arena.n_fallback == 0
    assert arena_pipe.arena.n_free == 1         # every slot returned
    assert fresh_pipe.arena.n_fallback == 3     # reference path never staged


def test_arena_slot_contents_match_stage_batch(engines):
    """Buffer-level check of the bit-exactness contract: a reused slot's
    contents equal `stage_batch`'s fresh stack for the same requests."""
    m, e = engines["logistic_net"]
    B = 4
    reqs = _requests(m, 6)
    pipe = ServingPipeline(e, backend="flex", batch_size=B,
                           staging_buffers=1)
    slot = pipe.arena.acquire()
    for chunk in (reqs[:4], reqs[4:]):          # reuse, incl. ragged tail
        bufs = pipe.arena.stage(slot, chunk)
        ref = stage_batch(chunk, B)
        assert set(bufs) == set(ref)
        for k in ref:
            np.testing.assert_array_equal(bufs[k], np.asarray(ref[k]))
    pipe.arena.release(slot)


@pytest.mark.parametrize("backend", ["flex", "accel"])
def test_arena_reuse_never_retraces(backend, engines):
    """Reused staging buffers hit the SAME compiled executable: no plan
    re-trace across slot reuse, ragged lengths, or the fallback path."""
    m, e = engines["multi_esperta"]
    reqs = _requests(m, 11)
    pipe = ServingPipeline(e, backend=backend, batch_size=4,
                           staging_buffers=1)
    before = e.planned(backend).n_traces
    tickets = [pipe.execute_batch_async(reqs[:4]),
               pipe.execute_batch_async(reqs[4:8])]   # 2nd one falls back
    for t in tickets:
        t.retire()
    pipe.execute_batch(reqs[8:])                      # ragged slot reuse
    assert e.planned(backend).n_traces == before
    assert pipe.arena.n_fallback == 1


# ---------------------------------------------------------------------------
# direct rows: large inputs go to the runtime as submitted (DESIGN.md §12)
# ---------------------------------------------------------------------------

# nets whose image rows exceed memory.DIRECT_ROW_BYTES
LARGE_MODELS = ("cnet_plus_scalar", "vae_encoder")


@pytest.fixture(scope="module")
def large_engines():
    out = {}
    for name in LARGE_MODELS:
        m = SPACE_MODELS[name]
        e = Engine(m.build_graph(), m.init_params(jax.random.PRNGKey(0)))
        e.calibrate([m.synthetic_input(jax.random.PRNGKey(0))])
        out[name] = (m, e)
    return out


def _plan_on_stage_batch(pipe, chunk, rng):
    """The compiled plan fed `stage_batch`'s host-stacked batch, with the
    rng split `_dispatch` makes: the reference of the direct-row path."""
    rngs = jax.random.split(rng, pipe.batch_size + 1)
    out = pipe._plan(stage_batch(chunk, pipe.batch_size), rngs[1:])
    return {k: np.asarray(v)[:len(chunk)] for k, v in out.items()}


@pytest.mark.parametrize("backend", ["flex", "accel"])
@pytest.mark.parametrize("name", LARGE_MODELS)
def test_direct_rows_bit_identical_to_stage_batch(name, backend,
                                                  large_engines):
    """A full batch, then shrinking ragged tails (4, 2, 1 rows) through
    ONE reused slot: rows handed to the runtime and stacked on the device
    give outputs bit-identical to the plan run on `stage_batch`."""
    m, e = large_engines[name]
    B = 4
    reqs = _requests(m, 7)
    pipe = ServingPipeline(e, backend=backend, batch_size=B,
                           staging_buffers=1)
    assert pipe.staging.direct_shapes == {"image": m.build_graph()
                                          .graph_inputs["image"]}
    for lo, hi in ((0, 4), (4, 6), (6, 7)):
        chunk = reqs[lo:hi]
        rng = jax.random.PRNGKey(lo)
        got = pipe.execute_batch(chunk, rng=rng)
        ref = _plan_on_stage_batch(pipe, chunk, rng)
        assert set(got.outputs) == set(ref)
        for k in ref:
            np.testing.assert_array_equal(
                got.outputs[k], ref[k],
                err_msg=f"{name}/{backend}/{k} chunk [{lo}:{hi}]")
    assert pipe.arena.n_staged == 3             # all via the one slot
    assert pipe.arena.n_fallback == 0
    assert pipe.arena.n_free == 1               # every slot returned


@pytest.mark.parametrize("name", LARGE_MODELS)
def test_assemble_compiles_once_and_plan_never_retraces(name,
                                                        large_engines):
    """The on-device stack compiles once per pipeline (a ragged tail is
    padded to the rung by repeating its last row, so every call has the
    rung's shape), and the plan never re-traces across direct, ragged
    and fallback batches."""
    m, e = large_engines[name]
    reqs = _requests(m, 11)
    pipe = ServingPipeline(e, backend="flex", batch_size=4,
                           staging_buffers=1)
    before = e.planned("flex").n_traces
    tickets = [pipe.execute_batch_async(reqs[:4]),
               pipe.execute_batch_async(reqs[4:8])]   # 2nd one falls back
    assert tickets[0].rows and not tickets[1].rows
    for t in tickets:
        t.retire()
    assert not tickets[0].rows                  # released at retirement
    for hi in (10, 9):
        pipe.execute_batch(reqs[8:hi])          # ragged, 2 then 1 rows
    assert pipe.n_assemble_traces == 1
    assert e.planned("flex").n_traces == before
    assert pipe.arena.n_fallback == 1


def test_direct_row_shape_mismatch_raises_and_frees_slot(large_engines):
    m, e = large_engines["vae_encoder"]
    pipe = ServingPipeline(e, backend="flex", batch_size=4,
                           staging_buffers=1)
    bad = {"image": np.zeros((64, 256, 3), np.float32)}
    with pytest.raises(ValueError, match="row shape"):
        pipe.execute_batch([bad])
    assert pipe.arena.n_free == 1 and pipe.n_assemble_traces == 0


def test_row_counters_direct_vs_staged(engines, large_engines):
    """CNet frames go direct and its scalar is staged; every input of
    multi_esperta is staged. The scheduler sums the counters."""
    sched = ContinuousBatchingScheduler(clock="modeled", pipeline=True)
    trace = []
    for name, (m, e) in (("cnet_plus_scalar",
                          large_engines["cnet_plus_scalar"]),
                         ("multi_esperta", engines["multi_esperta"])):
        sched.register(name, e, backend="flex", ladder=(1, 4))
        trace += [(0.001 * i, name, r)
                  for i, r in enumerate(_requests(m, 6))]
    sched.serve_trace(sorted(trace, key=lambda x: x[0]))
    tel = sched.telemetry()
    cnet, esperta = tel["cnet_plus_scalar"], tel["multi_esperta"]
    assert (cnet.n_rows_direct, cnet.n_rows_staged) == (6, 6)
    n_keys = len(SPACE_MODELS["multi_esperta"].build_graph().graph_inputs)
    assert (esperta.n_rows_direct, esperta.n_rows_staged) == (0, 6 * n_keys)
    assert cnet.n_staging_fallbacks == esperta.n_staging_fallbacks == 0
    assert "rows direct=6  staged=6" in sched.summary()


# ---------------------------------------------------------------------------
# scheduler behavior
# ---------------------------------------------------------------------------


def _co_serve(engines, trace_fn, n=40):
    sched = ContinuousBatchingScheduler()
    trace = []
    for mi, name in enumerate(MODELS):
        m, e = engines[name]
        reqs = _requests(m, n, seed=7 + mi)
        sched.register(name, e, backend="flex", ladder=(1, 4, 16),
                       warmup_sample=reqs[0])
        trace += [(t, name, r)
                  for t, r in zip(trace_fn(n, seed=30 + mi), reqs)]
    sched.serve_trace(trace)
    return sched, trace


def test_scheduler_co_serves_two_models_no_drop_no_dup(engines):
    sched, trace = _co_serve(
        engines, lambda n, seed: poisson_arrivals(400.0, n, seed=seed))
    rids = [c.rid for c in sched.completions]
    assert len(rids) == len(trace)                # nothing dropped
    assert len(set(rids)) == len(rids)            # nothing duplicated
    per_model = {name: sum(1 for c in sched.completions if c.model == name)
                 for name in MODELS}
    assert all(v == len(trace) // 2 for v in per_model.values())


def test_scheduler_bursty_trace_integrity(engines):
    sched, trace = _co_serve(
        engines,
        lambda n, seed: bursty_arrivals(n, burst_size=8, gap_s=0.02,
                                        seed=seed))
    rids = sorted(c.rid for c in sched.completions)
    assert rids == list(range(len(trace)))


def test_scheduler_dispatches_only_ladder_rungs(engines):
    sched, _ = _co_serve(
        engines, lambda n, seed: poisson_arrivals(300.0, n, seed=seed), n=37)
    assert sched.dispatches
    for d in sched.dispatches:
        assert d.rung in (1, 4, 16)
        assert 1 <= d.n_real <= d.rung


def test_scheduler_precompiles_ladder_and_never_retraces(engines):
    m, e = engines["logistic_net"]
    reqs = _requests(m, 25)
    sched = ContinuousBatchingScheduler()
    sched.register("logistic_net", e, backend="flex", ladder=(1, 4, 16),
                   warmup_sample=reqs[0])
    traces_before = e.planned("flex").n_traces
    trace = [(0.002 * i, "logistic_net", r) for i, r in enumerate(reqs)]
    sched.serve_trace(trace)
    assert e.planned("flex").n_traces == traces_before   # zero serving traces
    assert len(sched.completions) == len(reqs)


def test_scheduler_telemetry_fields(engines):
    sched, trace = _co_serve(
        engines, lambda n, seed: poisson_arrivals(500.0, n, seed=seed))
    tel = sched.telemetry()
    assert set(tel) == set(MODELS)
    for name, t in tel.items():
        assert t.n_completed == t.n_submitted == len(trace) // 2
        assert t.p99_latency_ms >= t.p50_latency_ms >= 0.0
        assert 0.0 < t.mean_batch_fill <= 1.0
        assert t.n_dispatches == sum(
            h["dispatches"] for h in t.fill_hist.values())
        d = t.to_dict()                           # JSON-ready
        import json
        json.dumps(d)


def test_scheduler_keep_predicate_threads_through(engines):
    m, e = engines["multi_esperta"]
    reqs = _requests(m, 20)
    sched = ContinuousBatchingScheduler()
    sched.register("multi_esperta", e, backend="flex", ladder=(1, 4),
                   keep_predicate=lambda out: False,
                   warmup_sample=reqs[0])
    sched.serve_trace([(0.001 * i, "multi_esperta", r)
                       for i, r in enumerate(reqs)])
    tel = sched.telemetry()["multi_esperta"]
    assert tel.n_kept == 0 and tel.downlink_reduction == 1.0
    assert all(not c.kept for c in sched.completions)


def test_scheduler_execution_error_requeues_batch(engines):
    """A batch that fails mid-execute is put back at the queue head (no
    silent loss) and the error surfaces to the caller."""
    m, e = engines["logistic_net"]
    good = _requests(m, 3)
    bad = {"wrong_key": np.zeros((2, 2), np.float32)}   # stage KeyError
    sched = ContinuousBatchingScheduler()
    sched.register("logistic_net", e, backend="flex", ladder=(1, 4),
                   warmup_sample=good[0])
    with pytest.raises(Exception):
        sched.serve_trace([(0.0, "logistic_net", good[0]),
                           (0.001, "logistic_net", bad),
                           (0.002, "logistic_net", good[1])])
    done = len(sched.completions)
    assert done + sched.pending() == 3                  # nothing dropped
    svc = sched._svcs["logistic_net"]
    assert any(r.inputs is bad for r in svc.queue)      # poison still queued


def test_scheduler_async_error_requeues_and_reraises(engines):
    m, e = engines["logistic_net"]
    good = _requests(m, 2)
    bad = {"wrong_key": np.zeros((2, 2), np.float32)}
    sched = ContinuousBatchingScheduler()
    sched.register("logistic_net", e, backend="flex", ladder=(1,),
                   warmup_sample=good[0])
    sched.start(poll_s=0.0005)
    sched.submit("logistic_net", bad)
    deadline = time.time() + 10.0
    while sched._thread_error is None and time.time() < deadline:
        time.sleep(0.001)                               # wait for the thread
    with pytest.raises(Exception):
        sched.stop(drain=False)
    assert sched.pending() == 1                         # poison re-queued


# ---------------------------------------------------------------------------
# power-envelope degradation
# ---------------------------------------------------------------------------


def test_envelope_tightening_mid_trace_no_loss_and_deferrals(engines):
    """The budget collapses mid-trace (sunlight -> eclipse step scheduled
    on the envelope): dispatch must degrade — smaller rungs, fallback
    backend, recorded deferrals — but NEVER drop or duplicate a request,
    and the envelope ledger must audit clean."""
    m, e = engines["logistic_net"]
    reqs = _requests(m, 48)
    env = PowerEnvelope(6.0, window_s=0.001)
    env.set_budget(0.005, sustained_w=0.5)      # the mid-trace tightening
    sched = ContinuousBatchingScheduler(envelope=env, clock="modeled")
    sched.register("logistic_net", e, backend=("accel", "cpu"),
                   ladder=(1, 4, 16), warmup_sample=reqs[0])
    trace = [(0.0002 * i, "logistic_net", r) for i, r in enumerate(reqs)]
    sched.serve_trace(trace)

    rids = [c.rid for c in sched.completions]
    assert len(rids) == len(trace)                   # nothing dropped
    assert len(set(rids)) == len(rids)               # nothing duplicated
    tel = sched.telemetry()["logistic_net"]
    assert tel.n_deferrals > 0                       # degradation recorded
    assert tel.n_deferrals == len(sched.deferrals)
    assert tel.backend_counts.get("cpu", 0) > 0      # fell back off the DPU
    assert tel.energy_j > 0 and tel.j_per_inference > 0
    audit = sched.envelope_report()
    assert audit["n_violations"] == 0, audit
    # post-tightening, only the admissible low-power backend dispatches
    late = [d for d in sched.dispatches if d.started > 0.01]
    assert late and all(d.backend == "cpu" for d in late)


def test_envelope_peak_cap_excludes_primary_backend(engines):
    """A peak cap below the DPU's busy power forces every dispatch onto
    the fallback backend, with identical results integrity."""
    m, e = engines["multi_esperta"]
    reqs = _requests(m, 12)
    env = PowerEnvelope(10.0, peak_w=3.0, window_s=0.01)
    sched = ContinuousBatchingScheduler(envelope=env, clock="modeled")
    sched.register("multi_esperta", e, backend=("accel", "flex"),
                   ladder=(1, 4), warmup_sample=reqs[0])
    sched.serve_trace([(0.0005 * i, "multi_esperta", r)
                       for i, r in enumerate(reqs)])
    assert len(sched.completions) == len(reqs)
    assert sched.dispatches
    assert all(d.backend == "flex" for d in sched.dispatches)
    assert sched.envelope_report()["n_violations"] == 0


def test_envelope_infeasible_model_rejected_at_register(engines):
    """An envelope that could never admit any backend of a model fails
    loudly at register time, not by starving the queue later."""
    m, e = engines["logistic_net"]
    env = PowerEnvelope(1e-6, peak_w=1e-3, window_s=0.01)
    sched = ContinuousBatchingScheduler(envelope=env)
    with pytest.raises(ValueError, match="envelope"):
        sched.register("logistic_net", e, backend=("accel", "cpu"),
                       ladder=(1, 4))


def test_envelope_never_admissible_mid_schedule_raises(engines):
    """A schedule that passes register-time feasibility (via its early
    regime) but can never admit once the budget collapses must surface a
    RuntimeError from serve_trace — not return with requests stranded."""
    m, e = engines["logistic_net"]
    reqs = _requests(m, 8)
    env = PowerEnvelope(6.0, window_s=0.001)
    env.set_budget(0.005, sustained_w=1e-9, peak_w=1e-6)
    sched = ContinuousBatchingScheduler(envelope=env, clock="modeled")
    sched.register("logistic_net", e, backend=("flex", "cpu"),
                   ladder=(1, 4), warmup_sample=reqs[0])
    trace = [(0.006 + 0.0002 * i, "logistic_net", r)
             for i, r in enumerate(reqs)]        # all after the collapse
    with pytest.raises(RuntimeError, match="envelope"):
        sched.serve_trace(trace)
    assert sched.pending() == len(reqs)          # queued, not dropped


def test_envelope_deferrals_deduped_per_blocked_head(engines):
    """Re-polling a blocked queue must not grow the deferral ledger: one
    record per blocked batch-head, however often step() is called."""
    m, e = engines["logistic_net"]
    reqs = _requests(m, 4)
    env = PowerEnvelope(6.0, window_s=0.001)
    env.set_budget(0.005, sustained_w=1e-9, peak_w=1e-6)
    sched = ContinuousBatchingScheduler(envelope=env, clock="modeled")
    sched.register("logistic_net", e, backend=("flex", "cpu"),
                   ladder=(1, 4), warmup_sample=reqs[0])
    for i, r in enumerate(reqs):
        sched.submit("logistic_net", r, arrival=0.006 + 0.0001 * i)
    for k in range(50):                          # async-style re-polling
        assert sched.step(0.01 + 1e-5 * k) is None
    assert len(sched.deferrals) == 1
    assert sched.telemetry()["logistic_net"].n_deferrals == 1


def test_envelope_dispatch_records_energy_fields(engines):
    m, e = engines["multi_esperta"]
    reqs = _requests(m, 6)
    sched = ContinuousBatchingScheduler(envelope=PowerEnvelope(6.0),
                                        clock="modeled")
    sched.register("multi_esperta", e, backend="flex", ladder=(1, 4),
                   warmup_sample=reqs[0])
    sched.serve_trace([(0.0005 * i, "multi_esperta", r)
                       for i, r in enumerate(reqs)])
    for d in sched.dispatches:
        assert d.backend == "flex"
        assert d.energy_j > 0 and d.power_w > 0
        assert d.energy_j == pytest.approx(d.power_w * d.modeled_latency_s)
    # the envelope ledger saw exactly one draw per dispatch
    assert sched.envelope_report()["n_draws"] == len(sched.dispatches)


def test_scheduler_async_mode_completes_everything(engines):
    m, e = engines["logistic_net"]
    reqs = _requests(m, 13)
    sched = ContinuousBatchingScheduler()
    sched.register("logistic_net", e, backend="flex", ladder=(1, 4),
                   warmup_sample=reqs[0])
    sched.start(poll_s=0.0005)
    try:
        rids = [sched.submit("logistic_net", r) for r in reqs]
    finally:
        sched.stop(drain=True)
    got = sorted(c.rid for c in sched.completions)
    assert got == sorted(rids)


# ---------------------------------------------------------------------------
# retirement on readiness (pipelined wall-clock mode)
# ---------------------------------------------------------------------------


def _pipelined_esperta(engines, keep_predicate=None, staging_buffers=2):
    m, e = engines["multi_esperta"]
    reqs = _requests(m, 8)
    sched = ContinuousBatchingScheduler(pipeline=True,
                                        staging_buffers=staging_buffers)
    sched.register("multi_esperta", e, backend="flex", ladder=(1, 4),
                   keep_predicate=keep_predicate, warmup_sample=reqs[0])
    return sched, reqs


def _wait_for(cond, timeout_s=10.0):
    deadline = time.time() + timeout_s
    while not cond() and time.time() < deadline:
        time.sleep(0.001)
    return cond()


def test_ticket_ready_checks_without_retiring(engines):
    class Pending:
        def is_ready(self):
            return False

    m, e = engines["multi_esperta"]
    pipe = ServingPipeline(e, backend="flex", batch_size=4)
    ticket = pipe.execute_batch_async(_requests(m, 4))
    assert _wait_for(ticket.ready)
    assert not ticket.retired and ticket.slot is not None
    ticket.retire()
    assert ticket.ready()
    host = {"y": np.zeros(4, np.float32)}      # no is_ready: on the host
    assert DispatchTicket(pipe, host, 4, None, 0.0, 0.0).ready()
    assert not DispatchTicket(pipe, dict(host, z=Pending()), 4, None,
                              0.0, 0.0).ready()


def test_lone_batch_retires_while_the_dispatcher_runs(engines):
    """A batch with no dispatch after it reaches the host once its
    outputs are done, from the dispatcher's idle loop: not at the next
    dispatch, and not only at stop()."""
    sched, reqs = _pipelined_esperta(engines)
    sched.start(poll_s=0.0005)
    try:
        rids = [sched.submit("multi_esperta", r) for r in reqs[:4]]
        done = _wait_for(lambda: len(sched.completions) == 4)
    finally:
        sched.stop(drain=True)
    assert done
    assert sorted(c.rid for c in sched.completions) == sorted(rids)
    assert [d.retired_by for d in sched.dispatches] == ["ready"]


def test_ready_retirement_keeps_dispatch_order(engines):
    """A ready ticket behind one whose outputs are not done waits: the
    idle loop retires nothing past the head, then both in order."""
    sched, reqs = _pipelined_esperta(engines, staging_buffers=3)
    for batch in (reqs[:4], reqs[4:]):
        for r in batch:
            sched.submit("multi_esperta", r)
        assert sched.step(time.monotonic()) is not None
    head, tail = sched._inflight
    assert _wait_for(tail.ticket.ready)
    gate = {"open": False}
    head.ticket.ready = lambda: gate["open"]
    sched._retire_ready()
    assert len(sched._inflight) == 2 and not sched.completions
    gate["open"] = True
    sched._retire_ready()
    assert not sched._inflight
    assert [c.rid for c in sched.completions] == list(range(8))
    assert [d.retired_by for d in sched.dispatches] == ["ready", "ready"]


def test_idle_retirement_error_requeues_and_reraises(engines):
    """An async failure found by the idle loop recovers as one found at
    dispatch does: the batch back at the queue head in its order, the
    record failed, the error re-raised by stop()."""
    armed = {"on": False}

    def exploding_keep(out):
        if armed["on"]:
            raise RuntimeError("keep predicate exploded")
        return True

    sched, reqs = _pipelined_esperta(engines, keep_predicate=exploding_keep)
    armed["on"] = True
    sched.start(poll_s=0.0005)
    rids = [sched.submit("multi_esperta", r) for r in reqs[:4]]
    # one dispatch and no other: only the idle loop can have retired it
    assert _wait_for(lambda: sched._thread_error is not None)
    with pytest.raises(RuntimeError, match="exploded"):
        sched.stop(drain=False)
    svc = sched._svcs["multi_esperta"]
    assert [r.rid for r in svc.queue] == rids
    assert len(sched.dispatches) == 1 and sched.dispatches[0].failed
    assert not sched.completions


@pytest.mark.parametrize("drive", ["wall_clock", "trace"])
def test_retirement_causes_count_every_dispatch(drive, engines):
    sched, _ = _pipelined_esperta(engines)
    m, _ = engines["multi_esperta"]
    reqs = _requests(m, 23, seed=5)
    if drive == "wall_clock":
        sched.start(poll_s=0.0005)
        try:
            for r in reqs:
                sched.submit("multi_esperta", r)
        finally:
            sched.stop(drain=True)
    else:
        sched.serve_trace([(0.001 * i, "multi_esperta", r)
                           for i, r in enumerate(reqs)])
    tel = sched.telemetry()["multi_esperta"]
    assert tel.n_completed == len(reqs)
    assert (tel.n_retired_ready + tel.n_retired_slot + tel.n_retired_sync
            == tel.n_dispatches == len(sched.dispatches))
    assert all(d.retired_by in ("ready", "slot", "sync")
               for d in sched.dispatches)
    if drive == "trace":                  # no idle loop off the wall clock
        assert tel.n_retired_ready == 0 and tel.n_retired_sync >= 1
    assert (f"retire: ready={tel.n_retired_ready}  "
            f"slot={tel.n_retired_slot}  sync={tel.n_retired_sync}"
            in sched.summary())


# ---------------------------------------------------------------------------
# EWMA seeding from plan-time cost signatures (ISSUE 5 satellite)
# ---------------------------------------------------------------------------


def test_service_estimates_seeded_from_cost_signature(engines):
    """Registration alone (no warmup, no dispatch) seeds every
    (backend, rung) service-time estimate from the plan's modeled
    CostSignature latency, so the very FIRST ragged-tail flush decision
    has a cadence-correct margin instead of the old cold-start 0."""
    m, e = engines["logistic_net"]
    sched = ContinuousBatchingScheduler()
    sched.register("logistic_net", e, backend="flex", ladder=(1, 4))
    svc = sched._svcs["logistic_net"]
    assert svc.est_service                      # non-empty before warmup
    for (backend, rung), est in svc.est_service.items():
        assert est == pytest.approx(svc.costs[(backend, rung)].latency_s)
    assert svc.flush_margin() > 0.0


def test_seed_is_prior_first_observation_replaces(engines):
    m, e = engines["logistic_net"]
    sched = ContinuousBatchingScheduler()
    sched.register("logistic_net", e, backend="flex", ladder=(1,))
    svc = sched._svcs["logistic_net"]
    seeded = svc.est_service[("flex", 1)]
    # first observation REPLACES the modeled prior outright (scales can
    # differ wildly between host wall time and the modeled ZCU104)...
    svc.observe_service("flex", 1, 0.5)
    assert svc.est_service[("flex", 1)] == pytest.approx(0.5)
    assert svc.est_service[("flex", 1)] != seeded
    # ...and later observations EWMA as before
    svc.observe_service("flex", 1, 0.1)
    assert svc.est_service[("flex", 1)] == pytest.approx(0.3)


def test_warmup_observation_overrides_seed(engines):
    m, e = engines["logistic_net"]
    reqs = _requests(m, 1)
    sched = ContinuousBatchingScheduler()
    sched.register("logistic_net", e, backend="flex", ladder=(1, 4),
                   warmup_sample=reqs[0])
    svc = sched._svcs["logistic_net"]
    # warmed keys carry measured host time, not the modeled seed
    for key in svc.est_service:
        assert key not in svc._seeded


def test_modeled_clock_estimates_stay_modeled_after_warmup(engines):
    m, e = engines["logistic_net"]
    reqs = _requests(m, 1)
    sched = ContinuousBatchingScheduler(clock="modeled")
    sched.register("logistic_net", e, backend="flex", ladder=(1, 4),
                   warmup_sample=reqs[0])
    svc = sched._svcs["logistic_net"]
    for key, est in svc.est_service.items():
        assert est == pytest.approx(svc.costs[key].latency_s)


def test_first_flush_decision_uses_seeded_margin(engines):
    """With a seeded margin the first ragged request is flushed BEFORE
    its deadline (deadline - margin), not at it: pick() fires at the
    seeded flush time with no dispatch history at all."""
    m, e = engines["logistic_net"]
    reqs = _requests(m, 1)
    sched = ContinuousBatchingScheduler(clock="modeled", flush_safety=2.0)
    sched.register("logistic_net", e, backend="flex", ladder=(1, 4),
                   deadline_s=0.15)
    svc = sched._svcs["logistic_net"]
    sched.submit("logistic_net", reqs[0], arrival=0.0)
    ft = svc.flush_time()
    assert ft == pytest.approx(0.15 - svc.flush_margin())
    assert svc.flush_margin() > 0.0
    assert svc.pick(ft - 1e-6) is None          # not due yet
    assert svc.pick(ft + 1e-6) is not None      # due at the seeded time


# ---------------------------------------------------------------------------
# error-path bugfixes (ISSUE 7 satellites): staging-slot leak on failed
# retirement, and failed-dispatch records poisoning telemetry
# ---------------------------------------------------------------------------


def test_failed_retirement_releases_slot_and_poisons_ticket(engines):
    """A keep-predicate crash during retire() must hand the staging slot
    back to the pool (the seed leaked it: a few failures starved the
    arena into permanent fallback allocation) and must poison the
    ticket — a second retire() of the abandoned batch raises instead of
    silently returning garbage."""
    from repro.core.pipeline import ServingPipeline
    m, e = engines["logistic_net"]
    boom = {"armed": False}

    def exploding_keep(out):
        if boom["armed"]:
            raise RuntimeError("keep predicate exploded")
        return True

    pipe = ServingPipeline(e, backend="flex", batch_size=4,
                           keep_predicate=exploding_keep)
    reqs = _requests(m, 4)
    pipe.execute_batch(reqs)                     # warm path, keep fine
    boom["armed"] = True
    n_free = pipe.arena.n_free
    ticket = pipe.execute_batch_async(reqs)
    assert pipe.arena.n_free == n_free - 1       # slot owned in flight
    with pytest.raises(RuntimeError, match="exploded"):
        ticket.retire()
    assert pipe.arena.n_free == n_free           # the leak: slot returned
    assert not pipe._inflight                    # and the ticket unlinked
    with pytest.raises(RuntimeError, match="failed retirement"):
        ticket.retire()
    boom["armed"] = False
    repeat = pipe.execute_batch(reqs)            # pool intact afterwards
    assert repeat.keep == [True] * 4
    assert pipe.arena.n_fallback == 0


def test_failed_dispatch_record_excluded_from_telemetry(engines):
    """When an async retirement fails, the already-appended dispatch
    record must be marked failed so the re-dispatch of the SAME batch
    does not double-count it in fill/latency/energy telemetry — and the
    requeued requests keep their ORIGINAL arrivals and deadlines."""
    m, e = engines["logistic_net"]
    reqs = _requests(m, 4)
    boom = {"armed": False}

    def exploding_keep(out):
        if boom["armed"]:
            boom["armed"] = False                # only the first batch
            raise RuntimeError("keep predicate exploded")
        return True

    sched = ContinuousBatchingScheduler(clock="modeled", pipeline=True)
    sched.register("logistic_net", e, backend="flex", ladder=(4,),
                   keep_predicate=exploding_keep, warmup_sample=reqs[0])
    boom["armed"] = True
    trace = [(0.001 * i, "logistic_net", r) for i, r in enumerate(reqs)]
    with pytest.raises(RuntimeError, match="exploded"):
        sched.serve_trace(trace)

    svc = sched._svcs["logistic_net"]
    assert [r.arrival for r in svc.queue] == [t for t, _, _ in trace]
    assert all(r.deadline == r.arrival + svc.deadline_s
               for r in svc.queue)               # originals, not re-stamped
    assert len(sched.dispatches) == 1 and sched.dispatches[0].failed

    sched.serve_trace([])                        # drain the requeued batch
    assert sorted(c.rid for c in sched.completions) == list(range(4))
    ok = [d for d in sched.dispatches if not d.failed]
    failed = [d for d in sched.dispatches if d.failed]
    assert len(ok) == 1 and len(failed) == 1
    tel = sched.telemetry()["logistic_net"]
    assert tel.n_dispatches == 1                 # seed double-counted: 2
    assert tel.n_failed_dispatches == 1
    assert tel.n_completed == 4
    assert tel.n_staging_fallbacks == 0          # and the slot came back
