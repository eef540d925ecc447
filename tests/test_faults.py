"""Degraded-mode fault suite tests (DESIGN.md §13).

* The SEU injector is deterministic per seed, actually corrupts what the
  COMPILED plans compute (weights are runtime arguments, not baked
  trace-time constants — and corrupting them never re-traces), and
  ``repack_weights`` restores the arena bit-exact from the pristine
  host copies.
* Golden canaries pin a digest at arm time, detect a flip, and verify
  recovery; staging-buffer flips are transient by construction.
* The fault controller under ``clock="modeled"``: detection within the
  self-test period (+aging allowance), repack recovery, demote recovery
  through backend quarantine (dispatch falls back, repair un-quarantines),
  zero requests dropped or duplicated — and a fully inert controller
  leaves the scheduler dispatch-for-dispatch bit-identical to serving
  without one.
* Checkpoint/restore: ``state_dict`` -> one pickle-free .npz ->
  ``load_state_dict`` round-trips every ledger field, and a simulated
  watchdog reboot mid-trace completes every accepted request exactly
  once, identically to the uninterrupted run.
* ``serve_trace(stop_at=...)``: every arrival at or before the returned
  time was absorbed (queued, in flight, or completed), none after.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import energy, faults
from repro.core.engine import Engine
from repro.core.scheduler import ContinuousBatchingScheduler, bursty_arrivals
from repro.models import SPACE_MODELS, synthetic_requests

MODEL = "multi_esperta"             # six int8 dense heads -> real arenas
CO_MODEL = "logistic_net"
BACKENDS = ("accel", "cpu")
LADDER = (1, 4)
N = 24
PERIOD = 0.05


@pytest.fixture(scope="module")
def engines():
    out = {}
    for name in (MODEL, CO_MODEL):
        m = SPACE_MODELS[name]
        e = Engine(m.build_graph(), m.init_params(jax.random.PRNGKey(0)))
        e.calibrate([m.synthetic_input(jax.random.PRNGKey(i))
                     for i in range(2)])
        out[name] = (m, e)
    return out


@pytest.fixture()
def accel_plan(engines):
    """The shared accel plan, guaranteed pristine again afterwards."""
    _, e = engines[MODEL]
    plan = e.planned("accel")
    yield plan
    plan.repack_weights()


def _sched(engines, names=(MODEL,), **kw):
    sched = ContinuousBatchingScheduler(clock="modeled", **kw)
    trace = []
    for mi, name in enumerate(names):
        m, e = engines[name]
        reqs = synthetic_requests(m, N, seed=5 + mi)
        sched.register(name, e, backend=BACKENDS, ladder=LADDER,
                       warmup_sample=reqs[0])
        trace += [(t, name, r) for t, r in
                  zip(bursty_arrivals(N, burst_size=4, gap_s=0.01,
                                      seed=20 + mi), reqs)]
    return sched, trace


def _controller(sched, engines, names=(MODEL,), **cfg_kw):
    ctl = faults.FaultController(faults.FaultConfig(**cfg_kw))
    sched.attach_faults(ctl)
    for mi, name in enumerate(names):
        m, _ = engines[name]
        ctl.arm(sched, name, synthetic_requests(m, 1, seed=5 + mi))
    return ctl


# ---------------------------------------------------------------------------
# injector + arena repack
# ---------------------------------------------------------------------------


def test_injector_deterministic_per_seed(accel_plan):
    a = faults.SEUInjector(seed=7).flip(accel_plan)
    accel_plan.repack_weights()
    b = faults.SEUInjector(seed=7).flip(accel_plan)
    accel_plan.repack_weights()
    c = faults.SEUInjector(seed=8).flip(accel_plan)
    assert a == b
    assert a != c                   # byte/bit space is ~1e4: seeds differ


def test_flip_corrupts_compiled_output_without_retrace(engines, accel_plan):
    """THE load-bearing property: weights are runtime arguments of the
    compiled executables, so a bit flip in the live arena changes what
    the already-compiled plan computes — with zero re-traces — and
    repacking restores it bit-exact."""
    m, e = engines[MODEL]
    inputs = m.synthetic_batch(jax.random.PRNGKey(11), 2)
    rngs = jax.random.split(jax.random.PRNGKey(7), 2)
    before = {k: np.asarray(v)
              for k, v in e.run_batch(inputs, "accel", rngs).items()}
    n_traces = accel_plan.n_traces

    node, byte, bit = faults.SEUInjector(seed=0).flip(accel_plan)
    corrupt = e.run_batch(inputs, "accel", rngs)
    assert accel_plan.n_traces == n_traces
    assert any(not np.array_equal(np.asarray(corrupt[k]), before[k])
               for k in before), (
        f"flip of {node}[{byte}]:{bit} did not reach the executable")

    nbytes = accel_plan.repack_weights()
    assert nbytes > 0
    after = e.run_batch(inputs, "accel", rngs)
    assert accel_plan.n_traces == n_traces
    for k in before:
        np.testing.assert_array_equal(np.asarray(after[k]), before[k])
    for name in accel_plan.weight_arena:
        np.testing.assert_array_equal(
            np.asarray(accel_plan.weight_arena[name]),
            accel_plan.host_weights[name])


def test_flip_pinned_target(accel_plan):
    node = max(accel_plan.weight_arena,
               key=lambda n: accel_plan.host_weights[n].nbytes)
    got = faults.SEUInjector(seed=0).flip(accel_plan, node=node,
                                          byte=1, bit=5)
    assert got == (node, 1, 5)
    host = accel_plan.host_weights[node]
    flipped = np.array(accel_plan.weight_arena[node])
    diff = host.view(np.uint8).reshape(-1) ^ \
        flipped.view(np.uint8).reshape(-1)
    assert diff[1] == (1 << 5) and int(diff.sum()) == (1 << 5)


def test_injector_rejects_empty_arena(engines):
    _, e = engines[MODEL]
    plan = e.planned("flex")        # fp32 plans carry no quantized arena
    assert plan.weight_arena == {}
    with pytest.raises(ValueError, match="no quantized weight arena"):
        faults.SEUInjector(seed=0).flip(plan)


def test_staging_flip_is_transient(engines):
    from repro.core.pipeline import ServingPipeline
    m, e = engines[MODEL]
    pipe = ServingPipeline(e, backend="accel", batch_size=4)
    reqs = synthetic_requests(m, 4, seed=3)
    ref = pipe.execute_batch(reqs, rng=jax.random.PRNGKey(0))
    faults.SEUInjector(seed=0).flip_staging(pipe.arena, slot=0)
    again = pipe.execute_batch(reqs, rng=jax.random.PRNGKey(0))
    for k in ref.outputs:           # stage() rewrote every row
        np.testing.assert_array_equal(again.outputs[k], ref.outputs[k])


@pytest.mark.parametrize("name,staged", [("vae_encoder", None),
                                         ("cnet_plus_scalar",
                                          "background_flux")])
def test_staging_flip_targets_host_staged_buffers_only(name, staged):
    """Rows handed to the runtime directly have no slot buffer: the flip
    lands in a host-staged one (the CNet scalar), and a slot that holds
    none (the VAE's) raises instead of picking from nothing."""
    from repro.core.pipeline import ServingPipeline
    m = SPACE_MODELS[name]
    e = Engine(m.build_graph(), m.init_params(jax.random.PRNGKey(0)))
    pipe = ServingPipeline(e, backend="flex", batch_size=4)
    inj = faults.SEUInjector(seed=0)
    if staged is None:
        with pytest.raises(ValueError, match="holds no host buffer"):
            inj.flip_staging(pipe.arena, slot=0)
        assert inj.n_flips == 0
    else:
        buf, _, _ = inj.flip_staging(pipe.arena, slot=0)
        assert buf == staged


# ---------------------------------------------------------------------------
# canaries
# ---------------------------------------------------------------------------


def test_output_digest_sensitive_and_stable():
    out = {"a": np.arange(6, dtype=np.float32).reshape(2, 3)}
    d1 = faults.output_digest(out)
    assert d1 == faults.output_digest(
        {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3)})
    perturbed = {"a": out["a"].copy()}
    perturbed["a"][1, 2] += 0.5
    assert faults.output_digest(perturbed) != d1
    assert faults.output_digest({"b": out["a"]}) != d1


def test_canary_detects_flip_and_recovery(engines, accel_plan):
    m, _ = engines[MODEL]
    sched, _ = _sched(engines)
    ctl = _controller(sched, engines, seed=0)
    canary = ctl._models[MODEL].canary
    ok, _ = canary.check()
    assert ok
    ctl.injector.flip(accel_plan)
    ok, got = canary.check()
    assert not ok and got != canary.digest
    accel_plan.repack_weights()
    ok, _ = canary.check()
    assert ok


# ---------------------------------------------------------------------------
# the controller under the modeled clock
# ---------------------------------------------------------------------------


def test_repack_storm_detects_recovers_drops_nothing(engines):
    sched, trace = _sched(engines)
    ctl = _controller(sched, engines, seed=0, fault_times=(0.012,),
                      self_test_period=PERIOD, recovery="repack")
    sched.serve_trace(trace)
    rep = ctl.report()
    assert rep["n_injected"] == 1
    assert rep["n_detected"] == 1 and rep["n_recovered"] == 1
    (ev,) = rep["events"]
    bound = PERIOD * (1 + ctl.config.aging_fraction) + 0.01
    assert ev["detected_at"] - ev["t_injected"] <= bound
    assert ev["recovered_at"] >= ev["detected_at"]
    assert ev["action"] == "repack"
    assert rep["overhead_energy_j"] > 0 and rep["n_self_tests"] >= 1
    assert sorted(c.rid for c in sched.completions) == list(range(N))
    # modeled clock: EWMA estimates ARE the signatures -> no drift
    for ratios in ctl.drift_report(sched).values():
        assert all(r == 1.0 for r in ratios.values())


def test_demote_storm_falls_back_then_repairs(engines):
    sched, trace = _sched(engines)
    # detect early (short period) so the quarantine window still overlaps
    # live bursts — the fallback dispatches are the point of this test
    ctl = _controller(sched, engines, seed=0, fault_times=(0.005,),
                      self_test_period=0.02, recovery="demote",
                      repair_delay_s=0.03)
    sched.serve_trace(trace)
    rep = ctl.report()
    assert rep["n_detected"] == 1 and rep["n_recovered"] == 1
    assert rep["events"][0]["action"] == "demote+repack"
    assert not sched._svcs[MODEL].quarantined     # repaired + lifted
    assert any(d.backend != BACKENDS[0] for d in sched.dispatches
               if d.model == MODEL), "no fallback dispatch ran while " \
        "the primary backend was quarantined"
    assert sorted(c.rid for c in sched.completions) == list(range(N))


def test_demote_requires_fallback_backend(engines):
    m, e = engines[MODEL]
    reqs = synthetic_requests(m, 2, seed=5)
    sched = ContinuousBatchingScheduler(clock="modeled")
    sched.register(MODEL, e, backend="accel", ladder=(1,),
                   warmup_sample=reqs[0])
    ctl = _controller(sched, engines, seed=0, fault_times=(0.0,),
                      self_test_period=0.001, recovery="demote")
    with pytest.raises(RuntimeError, match="fallback backend"):
        sched.serve_trace([(0.0, MODEL, reqs[0])])
    ctl._models[MODEL].plan.repack_weights()


def test_inert_controller_is_bit_identical_to_no_controller(engines):
    plain, trace = _sched(engines, names=(MODEL, CO_MODEL))
    plain.serve_trace(trace)
    armed, _ = _sched(engines, names=(MODEL, CO_MODEL))
    ctl = _controller(armed, engines, names=(MODEL, CO_MODEL))
    armed.serve_trace(trace)
    assert ctl.report()["n_self_tests"] == 0
    assert armed.dispatches == plain.dispatches
    assert len(armed.completions) == len(plain.completions)
    for a, b in zip(armed.completions, plain.completions):
        assert (a.rid, a.model, a.kept, a.arrival, a.finished, a.rung,
                a.n_real) == (b.rid, b.model, b.kept, b.arrival,
                              b.finished, b.rung, b.n_real)
        for k in b.outputs:
            np.testing.assert_array_equal(a.outputs[k], b.outputs[k])


def test_fault_config_validation_and_schedule():
    with pytest.raises(ValueError, match="repack|demote"):
        faults.FaultConfig(recovery="reboot")
    assert faults.FaultConfig().schedule() == []
    cfg = faults.FaultConfig(seed=3, fault_rate=100.0, horizon_s=0.5)
    times = cfg.schedule()
    assert times == cfg.schedule()                  # seed-deterministic
    assert times == sorted(times)
    assert all(0 < t < 0.5 for t in times)
    assert faults.FaultConfig(fault_times=(0.3, 0.1)).schedule() == \
        [0.1, 0.3]


def test_half_specified_poisson_storm_names_missing_field():
    """A rate without a horizon (or vice versa) used to yield a silently
    empty schedule; now the error names the field that is missing."""
    with pytest.raises(ValueError, match="horizon_s"):
        faults.FaultConfig(fault_rate=5.0)
    with pytest.raises(ValueError, match="fault_rate"):
        faults.FaultConfig(horizon_s=1.0)
    # the inert default and every fully-specified shape stay valid
    assert faults.FaultConfig().upset_schedule() == []
    assert faults.FaultConfig(fault_rate=5.0, horizon_s=1.0).schedule()
    # an explicit schedule (times or typed upsets) needs no rate/horizon
    assert faults.FaultConfig(fault_times=(0.1,), horizon_s=1.0)
    from repro.core.radiation import UpsetEvent
    cfg = faults.FaultConfig(upsets=(UpsetEvent(0.2), UpsetEvent(0.1)))
    assert [ev.t for ev in cfg.upset_schedule()] == [0.1, 0.2]


def test_repack_cost_pricing():
    hw = energy.BACKEND_HW["accel"]
    small = energy.repack_cost(hw, 1024)
    big = energy.repack_cost(hw, 1 << 20)
    assert 0 < small.seconds < big.seconds
    assert 0 < small.energy_j < big.energy_j
    bw = hw.stage_bw or hw.hbm_bw
    expect = hw.overhead_s + 1024 / bw
    assert small.seconds == pytest.approx(expect)


# ---------------------------------------------------------------------------
# checkpoint / restore
# ---------------------------------------------------------------------------


def _walk_equal(a, b):
    if isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
        return (np.asarray(a).dtype == np.asarray(b).dtype
                and np.array_equal(np.asarray(a), np.asarray(b)))
    if isinstance(a, dict):
        return (isinstance(b, dict) and set(a) == set(b)
                and all(_walk_equal(a[k], b[k]) for k in a))
    if isinstance(a, (list, tuple)):
        return (isinstance(b, (list, tuple)) and len(a) == len(b)
                and all(_walk_equal(x, y) for x, y in zip(a, b)))
    return a == b


def test_checkpoint_file_roundtrip(tmp_path):
    state = {"version": 1, "pi": 3.5, "name": "sched", "flag": True,
             "nested": {"arr": np.arange(5, dtype=np.int8),
                        "list": [np.ones((2, 2), np.float32), "x", None]},
             "empty": {}}
    path = str(tmp_path / "ck.npz")
    faults.save_checkpoint(path, state)
    loaded = faults.load_checkpoint(path)
    assert _walk_equal(loaded, state)
    # the format contract: loadable with pickling disabled
    with np.load(path, allow_pickle=False) as data:
        assert "__meta__" in data


def test_scheduler_state_dict_roundtrip(engines, tmp_path):
    sched, trace = _sched(engines, names=(MODEL, CO_MODEL))
    now = sched.serve_trace(trace, stop_at=0.02)
    state = sched.state_dict()
    path = str(tmp_path / "sched.npz")
    faults.save_checkpoint(path, state)
    assert _walk_equal(faults.load_checkpoint(path), state)

    fresh, _ = _sched(engines, names=(MODEL, CO_MODEL))
    fresh.load_state_dict(faults.load_checkpoint(path))
    assert _walk_equal(fresh.state_dict(), state)
    assert fresh.pending() == sched.pending()
    assert len(fresh.completions) == len(sched.completions)


def test_load_state_dict_rejects_mismatched_registration(engines, tmp_path):
    sched, trace = _sched(engines)
    sched.serve_trace(trace, stop_at=0.01)
    state = sched.state_dict()
    other = ContinuousBatchingScheduler(clock="modeled")
    with pytest.raises(ValueError):
        other.load_state_dict(state)   # models never registered


def test_stop_at_absorbs_exactly_the_elapsed_arrivals(engines):
    sched, trace = _sched(engines)
    stop = 0.02
    now = sched.serve_trace(trace, stop_at=stop)
    assert now >= stop - 1e-12
    due = [e for e in trace if e[0] <= now + 1e-12]
    n_absorbed = len(sched.completions) + sched.pending()
    assert n_absorbed == len(due), (
        "arrivals at or before the returned stop time must be queued, "
        "dispatched, or completed — never dropped")


def test_drift_report_window_semantics(engines):
    """Windowed drift cells with zero retired dispatches are None —
    never nan/inf (the 0/0 that used to leak out of an empty window)."""
    sched, trace = _sched(engines)
    ctl = _controller(sched, engines, seed=0)
    end = sched.serve_trace(trace)

    # a window ending long after the last dispatch retired: every cell
    # is empty, every ratio is None, nothing is nan/inf
    empty = ctl.drift_report(sched, window_s=1e-6, now=end + 100.0)
    assert empty[MODEL]
    assert all(r is None for r in empty[MODEL].values())

    # a window covering the whole run: dispatched cells carry finite
    # ratios (exactly 1.0 under the modeled clock), the rest are None
    full = ctl.drift_report(sched, window_s=end + 1.0, now=end)
    used = {(d.backend, d.rung) for d in sched.dispatches
            if d.model == MODEL and not d.failed}
    for cell, r in full[MODEL].items():
        b, rung = cell.split("/b")
        if (b, int(rung)) in used:
            assert r == pytest.approx(1.0)
        else:
            assert r is None
        assert r is None or np.isfinite(r)

    # the un-windowed EWMA path never emits nan/inf either
    for ratios in ctl.drift_report(sched).values():
        assert all(r is None or np.isfinite(r) for r in ratios.values())


def test_midstorm_checkpoint_roundtrip_is_dispatch_identical(engines,
                                                             tmp_path):
    """Watchdog reboot in the MIDDLE of a fault storm: checkpointing
    {scheduler, controller} state and restoring both into a fresh
    process resumes the timeline dispatch-for-dispatch identically to
    the uninterrupted run — zero requests lost or duplicated, and the
    post-cut upsets replay bit-exact from the restored injector RNG."""
    from repro.core.radiation import UpsetEvent
    storm = dict(seed=0, self_test_period=0.01,
                 upsets=(UpsetEvent(0.005), UpsetEvent(0.008, "mbu", 3),
                         UpsetEvent(0.038), UpsetEvent(0.045, "mbu", 2)))

    full, trace = _sched(engines)
    ctl_full = _controller(full, engines, **storm)
    full.serve_trace(trace)

    first, _ = _sched(engines)
    ctl_first = _controller(first, engines, **storm)
    cut = first.serve_trace(trace, stop_at=0.03)   # pre-cut storm done,
    assert all(e.recovered_at is not None          # post-cut still pending
               for e in ctl_first.events)
    assert ctl_first._pending
    path = str(tmp_path / "midstorm.npz")
    faults.save_checkpoint(path, {"sched": first.state_dict(),
                                  "faults": ctl_first.state_dict()})

    second, _ = _sched(engines)                    # fresh arm = reboot
    ctl_second = _controller(second, engines, **storm)
    ck = faults.load_checkpoint(path)
    second.load_state_dict(ck["sched"])
    ctl_second.load_state_dict(ck["faults"])
    second.serve_trace([e for e in trace if e[0] > cut + 1e-12],
                       start=cut)

    rep = ctl_second.report()
    assert rep["n_injected"] == 4
    assert rep["n_detected"] == 4 and rep["n_recovered"] == 4
    assert sorted(c.rid for c in second.completions) == \
        list(range(len(trace)))                    # zero loss, zero dup
    assert second.dispatches == full.dispatches
    meta = [(c.rid, c.model, c.kept, c.arrival, c.finished, c.rung,
             c.n_real) for c in second.completions]
    assert meta == [(c.rid, c.model, c.kept, c.arrival, c.finished,
                     c.rung, c.n_real) for c in full.completions]
    # the two storms' ledgers agree event-for-event
    assert [dataclasses_asdict_stable(e) for e in ctl_second.events] == \
        [dataclasses_asdict_stable(e) for e in ctl_full.events]


def dataclasses_asdict_stable(ev):
    import dataclasses as _dc
    return _dc.asdict(ev)


def test_watchdog_reboot_loses_nothing(engines, tmp_path):
    names = (MODEL, CO_MODEL)
    full, trace = _sched(engines, names=names)
    full.serve_trace(trace)

    first, _ = _sched(engines, names=names)
    now = first.serve_trace(trace, stop_at=0.02)
    path = str(tmp_path / "reboot.npz")
    faults.save_checkpoint(path, first.state_dict())

    second, _ = _sched(engines, names=names)   # fresh engines = reboot
    second.load_state_dict(faults.load_checkpoint(path))
    second.serve_trace([e for e in trace if e[0] > now + 1e-12],
                       start=now)

    assert sorted(c.rid for c in second.completions) == \
        list(range(len(trace)))
    meta = [(c.rid, c.model, c.kept, c.arrival, c.finished, c.rung,
             c.n_real) for c in second.completions]
    assert meta == [(c.rid, c.model, c.kept, c.arrival, c.finished,
                     c.rung, c.n_real) for c in full.completions]
    assert second.dispatches == full.dispatches
    by_rid = {c.rid: c for c in full.completions}
    post = [c for c in second.completions if c.outputs]
    assert post, "no post-reboot completions exercised the restored queue"
    for c in post:
        for k in c.outputs:
            np.testing.assert_array_equal(c.outputs[k],
                                          by_rid[c.rid].outputs[k])
