"""The comparison that decides ``correct`` fails when it should: the
lower-precision control, and faults planted in the timed path.

These run the harness on the CPU (the program's Pallas kernels in the
interpreter) without its look for a chip, at a size a test run can
hold: the configurations' real widths, a pool of 16 requests, rungs
1 and 4, one second of Poisson traffic.
"""
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from bench import compare, harness, limits, spec

SMALL_TRAFFIC = {"kind": "poisson", "rate_hz": 40.0, "gap_seed": 0}


def small(cell_name):
    cell = spec.load_cell(cell_name)
    cfg = dict(cell.config, pool_size=16, ladder=[1, 4])
    return cell._replace(config=cfg, traffic=SMALL_TRAFFIC)


@pytest.fixture(autouse=True)
def no_compile_cache():
    jax.config.update("jax_enable_compilation_cache", False)
    yield


def run_small(cell_name, seed=2 ** 31 + 5):
    return harness.run(small(cell_name), seed, 1.0, False,
                       time.monotonic(), require_tpu=False,
                       log=lambda line: None)


@pytest.mark.parametrize("cell_name", ["cnet_accel.poisson_over",
                                       "vae_accel.poisson_over"])
@pytest.mark.parametrize("seed", [11, 12, 13])
def test_int4_control_is_not_correct(cell_name, seed):
    cell = small(cell_name)
    numbers = limits.control_readings(cell, seed, 1.0)
    correct, table = compare.verdict(cell.config, numbers)
    assert not correct, table


def over_a_limit(result):
    """The compared numbers of the configuration's own limits that read
    over them."""
    return [k for k, row in result["compared"].items()
            if k not in compare.EXACT and row["value"] > row["limit"]]


def roll_answers(out):
    """Each request gets its batch neighbour's answer."""
    return {k: jnp.roll(v, 1, axis=0) for k, v in out.items()}


def drop_half(out):
    """The second half of the batch is left out: its rows repeat the
    answers of the first half."""
    def half(v):
        b = v.shape[0]
        return v.at[b - b // 2:].set(v[:b // 2]) if b > 1 else v
    return {k: half(v) for k, v in out.items()}


def alter_first(out):
    """One answer per batch altered where it is produced."""
    return {k: v.at[0].add(1.0) for k, v in out.items()}


def test_unbroken_run_is_correct():
    result = run_small("vae_accel.poisson_over")
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 20


@pytest.mark.parametrize("fault", [roll_answers, drop_half, alter_first])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    from repro.core.plan import CompiledPlan
    call = CompiledPlan.__call__
    monkeypatch.setattr(CompiledPlan, "__call__",
                        lambda self, inputs, rngs: fault(
                            call(self, inputs, rngs)))
    result = run_small("vae_accel.poisson_over")
    assert not result["correct"]
    assert over_a_limit(result), result["compared"]


def test_lost_requests_are_not_correct(monkeypatch):
    from repro.core.scheduler import ContinuousBatchingScheduler
    submit = ContinuousBatchingScheduler.submit

    def drop_every_tenth(self, model, inputs, arrival=None):
        rid = submit(self, model, inputs, arrival)
        if rid % 10 == 9:
            with self._lock:
                self._svcs[model].queue.pop()
        return rid
    monkeypatch.setattr(ContinuousBatchingScheduler, "submit",
                        drop_every_tenth)
    result = run_small("vae_accel.poisson_over")
    assert not result["correct"]
    assert result["failed"] > 0
    assert result["compared"]["missing"]["value"] == result["failed"]


def test_run_without_a_chip_exits_2_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"),
         "--workload", "cnet_accel.poisson_over", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 2, proc.stderr
    assert proc.stdout == ""
    assert "needs a TPU" in proc.stderr
