"""Autotuner + prepacked weight arena tests (DESIGN.md §11).

Pins the four contracts ISSUE 5 gates on:

* every candidate tile config — and the prepacked kernel paths — is
  bit-exact to the heuristic default (int8 cells exactly equal);
* the tuning cache is deterministic: same graph -> same picks, and a
  warm cache performs ZERO candidate evaluations;
* ``Engine(..., autotune=False)`` (the default) reproduces today's
  plans node-for-node;
* tuned plans are never worse than the heuristic default under the
  kernel-level pricer, and the packed (padded) weight footprint is what
  the arena budget and cost signatures charge.
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import autotune as autotune_mod
from repro.core.autotune import Autotuner, KernelConfig, TuningCache
from repro.core.engine import Engine
from repro.kernels import ops as kops
from repro.models import SPACE_MODELS

CHEAP_MODELS = ("logistic_net", "reduced_net", "multi_esperta")
N_CALIB = 4


_ENGINES = {}


def engines(name: str):
    """(model, default engine, autotuned engine), memoized per module —
    calibration is shared so the interpret-mode cost is paid once."""
    if name not in _ENGINES:
        m = SPACE_MODELS[name]
        e0 = Engine(m.build_graph(), m.init_params(jax.random.PRNGKey(0)))
        e0.calibrate([m.synthetic_input(jax.random.PRNGKey(i))
                      for i in range(N_CALIB)])
        e1 = Engine(m.build_graph(), m.init_params(jax.random.PRNGKey(0)),
                    autotune=True)
        e1.share_calibration(e0)
        _ENGINES[name] = (m, e0, e1)
    return _ENGINES[name]


# ---------------------------------------------------------------------------
# Kernel-level bit-exactness across the whole candidate space
# ---------------------------------------------------------------------------


def test_int8_matmul_bit_exact_across_tile_configs():
    rng = np.random.default_rng(0)
    m, k, n = 5, 70, 13
    x = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int8)
    w = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
    xs = jnp.asarray(rng.uniform(0.01, 1, m), jnp.float32)
    ws = jnp.asarray(rng.uniform(0.01, 1, n), jnp.float32)
    b = jnp.asarray(rng.uniform(-1, 1, n), jnp.float32)
    ref = kops.int8_matmul(x, w, xs, ws, b, act="relu")
    for cfg in autotune_mod.dense_candidates(m, k, n):
        out = kops.int8_matmul(x, w, xs, ws, b, act="relu",
                               bm=cfg.bm, bn=cfg.bn, bk=cfg.bk)
        assert np.array_equal(np.asarray(ref), np.asarray(out)), cfg


def test_int8_matmul_prepacked_bit_exact():
    rng = np.random.default_rng(1)
    m, k, n = 4, 50, 10
    x = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int8)
    w = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
    xs = jnp.asarray(rng.uniform(0.01, 1, m), jnp.float32)
    ws = jnp.asarray(rng.uniform(0.01, 1, n), jnp.float32)
    b = jnp.asarray(rng.uniform(-1, 1, n), jnp.float32)
    for requant in (None, 0.37):
        ref = kops.int8_matmul(x, w, xs, ws, b, requant_scale=requant)
        for bk, bn in ((8, 8), (64, 16), (128, 128)):
            kp, np_ = -(-k // bk) * bk, -(-n // bn) * bn
            wp = jnp.pad(w, ((0, kp - k), (0, np_ - n)))
            wsp = jnp.pad(ws, (0, np_ - n), constant_values=1.0)
            bp = jnp.pad(b, (0, np_ - n))
            out = kops.int8_matmul(x, wp, xs, wsp, bp,
                                   requant_scale=requant, bm=8, bn=bn,
                                   bk=bk, prepacked=True, n_out=n)
            assert ref.dtype == out.dtype
            assert np.array_equal(np.asarray(ref), np.asarray(out)), (bk, bn)


@pytest.mark.parametrize("stride,padding", [(1, "SAME"), (2, "SAME"),
                                            (1, "VALID")])
def test_conv2d_int8_bit_exact_across_tile_configs(stride, padding):
    rng = np.random.default_rng(2)
    h, wd, cin, cout, kk = 9, 7, 3, 12, 3
    x = jnp.asarray(rng.integers(-127, 128, (2, h, wd, cin)), jnp.int8)
    w = jnp.asarray(rng.integers(-127, 128, (kk, kk, cin, cout)), jnp.int8)
    ws = jnp.asarray(rng.uniform(0.01, 1, cout), jnp.float32)
    b = jnp.asarray(rng.uniform(-1, 1, cout), jnp.float32)
    ref = kops.conv2d_int8(x, w, ws, b, x_scale=0.5, stride=stride,
                           padding=padding, act="relu")
    for cfg in autotune_mod.conv_candidates(h, wd, cin, kk, kk, cout,
                                            stride, padding):
        out = kops.conv2d_int8(
            x, w, ws, b, x_scale=0.5, stride=stride, padding=padding,
            act="relu",
            rows_per_block=cfg.rows_per_block or 8,
            cout_per_block=cfg.cout_per_block)
        assert np.array_equal(np.asarray(ref), np.asarray(out)), cfg


def test_conv2d_int8_prepacked_prepadded_bit_exact():
    rng = np.random.default_rng(3)
    from repro.kernels.conv2d import conv_geometry, pad_input
    from repro.kernels.epilogue import pad_channel_params
    h, wd, cin, cout, kk = 10, 10, 4, 9, 3
    x = jnp.asarray(rng.integers(-127, 128, (2, h, wd, cin)), jnp.int8)
    w = jnp.asarray(rng.integers(-127, 128, (kk, kk, cin, cout)), jnp.int8)
    ws = jnp.asarray(rng.uniform(0.01, 1, cout), jnp.float32)
    b = jnp.asarray(rng.uniform(-1, 1, cout), jnp.float32)
    ref = kops.conv2d_int8(x, w, ws, b, x_scale=0.5, stride=2,
                           requant_scale=0.11)
    rows, bc = 3, 8
    g = conv_geometry(h, wd, kk, kk, 2, "SAME", rows)
    xp = pad_input(x, g)
    pad_c = -(-cout // bc) * bc - cout
    wp = jnp.pad(w, ((0, 0), (0, 0), (0, 0), (0, pad_c)))
    wsp, bp = pad_channel_params(ws, b, pad_c)
    out = kops.conv2d_int8(xp, wp, wsp, bp, x_scale=0.5, stride=2,
                           requant_scale=0.11, rows_per_block=rows,
                           cout_per_block=bc, cout=cout, pre_padded=True,
                           in_hw=(h, wd))
    assert ref.dtype == out.dtype == jnp.int8
    assert np.array_equal(np.asarray(ref), np.asarray(out))


# ---------------------------------------------------------------------------
# Engine-level equivalence: tuned plans bit-exact to untuned, all models
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(SPACE_MODELS))
def test_prepacked_vs_on_the_fly_equivalence(name):
    """Autotuned (prepacked arenas + tuned tiles) == heuristic engine,
    bit-exact, on both backends, for all six models."""
    m, e0, e1 = engines(name)
    n = 2
    inputs = m.synthetic_batch(jax.random.PRNGKey(9), n)
    rngs = jax.random.split(jax.random.PRNGKey(3), n)
    for backend in ("flex", "accel"):
        a = e0.run_batch(inputs, backend, rngs)
        b = e1.run_batch(inputs, backend, rngs)
        for k in a:
            assert a[k].dtype == b[k].dtype
            assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), \
                (name, backend, k)


def test_autotune_off_reproduces_plans_node_for_node():
    """Engine() (the default) and an explicitly-untuned engine build the
    same plans as before the autotuner existed: no tuning state, no
    packed weights, identical graphs/segments/qplans."""
    m, e0, _ = engines("reduced_net")
    e_off = Engine(m.build_graph(), m.init_params(jax.random.PRNGKey(0)),
                   autotune=False)
    e_off.share_calibration(e0)
    for backend in ("flex", "accel"):
        p0, p1 = e0.planned(backend), e_off.planned(backend)
        assert p0.tuner is None and p1.tuner is None
        assert not p0._tuning and not p0.packed
        assert p0.graph.order == p1.graph.order
        assert [(s.backend, s.nodes) for s in p0.segments] == \
            [(s.backend, s.nodes) for s in p1.segments]
        assert sorted(p0.qplans) == sorted(p1.qplans)
        # untuned cost signatures are the pre-autotune model, unchanged
        s0, s1 = p0.cost_signature(8), p1.cost_signature(8)
        assert s0 == s1


# ---------------------------------------------------------------------------
# Cache determinism + the no-research contract
# ---------------------------------------------------------------------------


def _tuned_configs(engine, backend="accel", rungs=(1, 4)):
    out = {}
    for r in rungs:
        engine.compile(backend, r)
    plan = engine.planned(backend)
    for r, dec in plan._tuning.items():
        out[r] = {n: d.config for n, d in dec.items()}
    return out


def test_cache_roundtrip_same_graph_same_picks(tmp_path):
    cache_path = str(tmp_path / "tuning.json")
    m, e0, _ = engines("reduced_net")

    def fresh():
        e = Engine(m.build_graph(), m.init_params(jax.random.PRNGKey(0)),
                   autotune=True, tuning_cache=cache_path)
        e.share_calibration(e0)
        return e

    e1 = fresh()
    picks1 = _tuned_configs(e1)
    assert e1.tuner.stats["evaluated"] > 0
    assert len(e1.tuner.cache) > 0

    # a brand-new engine with the warm JSON cache: identical picks and
    # ZERO candidate evaluations (the acceptance-criteria assertion)
    e2 = fresh()
    picks2 = _tuned_configs(e2)
    assert picks1 == picks2
    assert e2.tuner.stats["evaluated"] == 0
    assert e2.tuner.stats["cache_hits"] == e2.tuner.stats["nodes"]


def test_second_lower_same_engine_no_research():
    m, e0, _ = engines("logistic_net")
    e = Engine(m.build_graph(), m.init_params(jax.random.PRNGKey(0)),
               autotune=True)
    e.share_calibration(e0)
    e.compile("accel", 4)
    evaluated = e.tuner.stats["evaluated"]
    n0 = e.planned("accel").n_traces
    e.compile("accel", 4)               # plan cache: no tuning, no trace
    assert e.tuner.stats["evaluated"] == evaluated
    assert e.planned("accel").n_traces == n0


# ---------------------------------------------------------------------------
# Pricing gates + packed-footprint accounting
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", CHEAP_MODELS)
@pytest.mark.parametrize("backend", ["flex", "accel"])
def test_tuned_never_worse_than_default_pricing(name, backend):
    _, _, e1 = engines(name)
    plan = e1.planned(backend)
    for rung in (1, 32):
        tuned = plan.cost_signature(rung)
        default = plan.default_cost_signature(rung)
        assert tuned.latency_s <= default.latency_s * (1 + 1e-9)
        assert tuned.j_per_inference <= default.j_per_inference * (1 + 1e-9)


def test_packed_footprint_charged_to_arena_and_signature():
    from repro.core import energy as energy_mod
    _, _, e1 = engines("reduced_net")
    plan = e1.planned("accel")
    plan.lower(4)                       # triggers pack at pack_batch
    assert plan.packed, "accel plan should prepack its quantized nodes"
    unpacked = energy_mod.weight_bytes(plan.graph, "accel",
                                       set(plan.qplans))
    packed = energy_mod.weight_bytes(plan.graph, "accel",
                                     set(plan.qplans),
                                     plan._packed_bytes)
    assert packed >= unpacked           # padding only ever adds bytes
    for nm, p in plan.packed.items():
        assert p.packed_bytes >= 1
    assert plan.arena.weight_bytes == packed
    # the arena budget shrank by exactly the packing overhead
    hw = energy_mod.BACKEND_HW["accel"]
    assert plan.arena.bram_budget == int(hw.onchip_bytes) - packed


def test_as_text_prints_tile_configs_and_packed_bytes():
    _, _, e1 = engines("reduced_net")
    plan = e1.planned("accel")
    plan.lower(4)
    text = plan.as_text()
    assert "autotune @ batch" in text
    assert "tile " in text
    assert "packed=" in text
    # flex plans print the HLS schedule configs
    fplan = e1.planned("flex")
    fplan.lower(4)
    assert "unroll x" in fplan.as_text()


# ---------------------------------------------------------------------------
# Measured refinement (opt-in) + cache key stability
# ---------------------------------------------------------------------------


def test_measured_refinement_smoke():
    tuner = Autotuner(TuningCache(None), measure=True, measure_top_k=2,
                      measure_repeats=1)
    from repro.core import energy as energy_mod
    hw = energy_mod.BACKEND_HW["accel"]
    dec = tuner._search("int8_dense", (4, 64, 16), hw, True, None)
    assert dec.source == "measured"
    assert tuner.stats["measured"] > 0
    assert dec.modeled_s > 0


def test_cache_key_sensitive_to_shape_backend_and_hw():
    from repro.core import energy as energy_mod
    hw_a = energy_mod.BACKEND_HW["accel"]
    hw_f = energy_mod.BACKEND_HW["flex"]
    k0 = autotune_mod.cache_key("int8_dense", (4, 64, 16), "accel", hw_a)
    assert k0 == autotune_mod.cache_key("int8_dense", (4, 64, 16),
                                        "accel", hw_a)
    assert k0 != autotune_mod.cache_key("int8_dense", (8, 64, 16),
                                        "accel", hw_a)
    assert k0 != autotune_mod.cache_key("int8_dense", (4, 64, 16),
                                        "flex", hw_f)
    assert k0 != autotune_mod.cache_key(
        "int8_dense", (4, 64, 16), "accel", hw_a,
        fixed=KernelConfig(bn=16, bk=64))
    # residency changes the stored restream pricing; the measured
    # refinement changes the winner itself — both get their own entries
    assert k0 != autotune_mod.cache_key("int8_dense", (4, 64, 16),
                                        "accel", hw_a, resident=False)
    assert k0 != autotune_mod.cache_key("int8_dense", (4, 64, 16),
                                        "accel", hw_a, measured=True)


def test_stale_cache_schema_discarded(tmp_path):
    import json
    path = tmp_path / "tuning.json"
    path.write_text(json.dumps({"version": -1, "entries": {"x": {}}}))
    cache = TuningCache(str(path))
    assert len(cache) == 0


def test_corrupt_cache_file_is_cold_not_fatal(tmp_path, capsys):
    """A truncated/corrupt/foreign cache file (half-written at the last
    power cut — the exact scenario a tuning cache exists for) must load
    as a COLD cache with a one-line warning, never crash plan building.
    The seed raised json.JSONDecodeError from the constructor."""
    for blob in ('{"version": 1, "entries": {"trunc',      # cut mid-write
                 "\x00\x7fELF garbage",                    # not JSON at all
                 "[1, 2, 3]",                              # JSON, not a dict
                 '"just a string"'):
        path = tmp_path / "tuning.json"
        path.write_text(blob)
        cache = TuningCache(str(path))
        assert len(cache) == 0
        out = capsys.readouterr().out
        assert "ignoring" in out and "cold cache" in out
    # and an unreadable path (directory) degrades the same way
    cache = TuningCache(str(tmp_path))
    assert len(cache) == 0
    assert "cold cache" in capsys.readouterr().out


def test_corrupt_cache_recovers_end_to_end(tmp_path):
    """An Engine pointed at a corrupt cache file still autotunes (cold),
    then persists a fresh valid cache over the corpse."""
    import json
    path = tmp_path / "tuning.json"
    path.write_text('{"version": 1, "entries"')            # torn write
    m = SPACE_MODELS["multi_esperta"]
    e = Engine(m.build_graph(), m.init_params(jax.random.PRNGKey(0)),
               autotune=True, tuning_cache=str(path))
    e.calibrate([m.synthetic_input(jax.random.PRNGKey(i))
                 for i in range(2)])
    e.compile("accel", 4)                                  # tunes + saves
    payload = json.loads(path.read_text())                 # valid again
    assert payload["version"] == autotune_mod.SCHEMA_VERSION
    assert isinstance(payload["entries"], dict)
