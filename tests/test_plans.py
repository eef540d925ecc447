"""Staged execution plans: batching equivalence, cache behavior, kernels.

* batched-vs-single-sample equivalence for every use-case model on flex
  and accel (fp32 within 1e-5; the int8 kernel path bit-exact).
* plan-cache behavior: compiling twice returns the same executable and
  does not re-trace; a new batch size traces exactly once more; calling
  a compiled plan never traces.
* the Pallas int8 conv2d vs lax.conv_general_dilated across
  stride/padding combos.
* the PTQ fidelity gate demotes below-noise-floor layers to flex.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import Engine
from repro.core.opgraph import Graph
from repro.core.plan import partition_segments
from repro.kernels import ops as kops
from repro.models import SPACE_MODELS


@pytest.fixture(scope="module")
def engines():
    out = {}
    for name, m in SPACE_MODELS.items():
        e = Engine(m.build_graph(), m.init_params(jax.random.PRNGKey(0)))
        e.calibrate([m.synthetic_input(jax.random.PRNGKey(i))
                     for i in range(2)])
        out[name] = (m, e)
    return out


# ---------------------------------------------------------------------------
# batched == per-sample
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["flex", "accel"])
@pytest.mark.parametrize("name", sorted(SPACE_MODELS))
def test_batched_matches_single(name, backend, engines):
    m, e = engines[name]
    B = 3
    inputs = m.synthetic_batch(jax.random.PRNGKey(5), B)
    rngs = jax.random.split(jax.random.PRNGKey(11), B)
    batched = e.run_batch(inputs, backend, rngs)
    for i in range(B):
        single = e.run({k: v[i] for k, v in inputs.items()}, backend, rngs[i])
        for k in batched:
            a = np.asarray(batched[k][i], np.float32)
            b = np.asarray(single[k], np.float32)
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5,
                                       err_msg=f"{name}/{backend}/{k}")


def test_int8_path_bit_exact_across_batch():
    """A fully quantized conv+dense graph must be BIT-identical between
    batch-1 and batch-N execution (int32 accumulation, static scales)."""
    g = Graph("int8_exact")
    x = g.input("x", (16, 16, 4))
    c = g.add("conv2d", [x], name="conv", kernel=(3, 3), features=8)
    r = g.add("relu", [c], name="act")
    d = g.add("dense", [r], name="head", features=8)
    g.mark_output(d)
    e = Engine(g, _graph_params(g), ptq_demote_threshold=1e9)
    rng = np.random.default_rng(0)
    calib = [{"x": rng.standard_normal((16, 16, 4)).astype(np.float32)}
             for _ in range(2)]
    e.calibrate(calib)
    B = 5
    xs = rng.standard_normal((B, 16, 16, 4)).astype(np.float32)
    rngs = jax.random.split(jax.random.PRNGKey(0), B)
    batched = e.run_batch({"x": xs}, "accel", rngs)
    plan = e.planned("accel")
    # pass-pipeline structure: conv+relu fused under the act node's name,
    # then requant-chained straight into the dense head (int8 in-flight)
    assert set(plan.qplans) == {"act", "head"}
    assert plan.graph.nodes["act"].op == "fused"
    assert plan.graph.nodes["act"].attrs["param_of"] == "conv"
    assert plan.qplans["act"].requant_scale is not None
    assert plan.qplans["head"].int8_input
    for i in range(B):
        single = e.run({"x": xs[i]}, "accel", rngs[i])
        np.testing.assert_array_equal(np.asarray(batched["head"][i]),
                                      np.asarray(single["head"]))
    # the fuse=False escape hatch keeps the legacy per-node structure and
    # the exact same int8 outputs
    e0 = Engine(g, _graph_params(g), ptq_demote_threshold=1e9, fuse=False)
    e0.calibrate(calib)
    plan0 = e0.planned("accel")
    assert set(plan0.qplans) == {"conv", "head"}
    assert plan0.fused_into == {"act": "conv"}      # legacy epilogue alias
    legacy = e0.run_batch({"x": xs}, "accel", rngs)
    np.testing.assert_array_equal(np.asarray(batched["head"]),
                                  np.asarray(legacy["head"]))


def _graph_params(g):
    from repro.models.common import init_graph_params
    return init_graph_params(g, jax.random.PRNGKey(1))


# ---------------------------------------------------------------------------
# plan cache
# ---------------------------------------------------------------------------


def test_plan_cache_no_retrace_on_reuse(engines):
    m, e = engines["multi_esperta"]
    p4 = e.compile("flex", 4)
    n0 = p4.n_traces
    assert e.compile("flex", 4) is p4               # cache hit
    inputs = m.synthetic_batch(jax.random.PRNGKey(0), 4)
    rngs = jax.random.split(jax.random.PRNGKey(1), 4)
    p4(inputs, rngs)
    p4(inputs, rngs)
    assert p4.n_traces == n0                        # calls never re-trace
    p8 = e.compile("flex", 8)                       # new shape -> one trace
    assert p8.n_traces == n0 + 1
    assert e.compile("flex", 8) is p8


def test_plan_cache_is_per_instance():
    m = SPACE_MODELS["multi_esperta"]
    e1 = Engine(m.build_graph(), m.init_params())
    e2 = Engine(m.build_graph(), m.init_params())
    p1, p2 = e1.compile("flex", 2), e2.compile("flex", 2)
    assert p1 is not p2
    assert e1.planned("flex").n_traces == 1
    assert e2.planned("flex").n_traces == 1


def test_calibrate_invalidates_accel_plans():
    m = SPACE_MODELS["multi_esperta"]
    e = Engine(m.build_graph(), m.init_params())
    calib = [m.synthetic_input(jax.random.PRNGKey(i)) for i in range(2)]
    e.calibrate(calib)
    stale = e.compile("accel", 2)
    e.calibrate(calib)                              # new scales
    assert e.compile("accel", 2) is not stale


# ---------------------------------------------------------------------------
# segment partitioning + PTQ gate
# ---------------------------------------------------------------------------


def test_segments_cover_graph_in_order(engines):
    """Segments cover the (pass-rewritten) plan graph exactly, in order,
    as maximal same-backend runs."""
    for name, (m, e) in engines.items():
        plan = e.planned("accel")
        flat = [n for seg in plan.segments for n in seg.nodes]
        want = [n for n in plan.graph.order
                if plan.graph.nodes[n].op != "input"]
        assert flat == want, name
        for a, b in zip(plan.segments, plan.segments[1:]):
            assert a.backend != b.backend, name     # maximal runs


def test_partition_segments_groups_runs():
    g = SPACE_MODELS["vae_encoder"].build_graph()
    segs = partition_segments(
        g, {n: ("flex" if n == "sample" else "accel") for n in g.order})
    assert [s.backend for s in segs] == ["accel", "flex"]
    assert segs[1].nodes == ("sample",)


def test_ptq_gate_demotes_noise_floor_layers():
    # one hot voxel (a particle hit) in the calibration set sets the
    # per-tensor activation scale ~1000x above the rest of the data, so
    # on the other sample every head input rounds to zero and the head's
    # int8 output is pure noise: the gate must route it to flex, and the
    # accel run must then match flex exactly on that node
    m = SPACE_MODELS["logistic_net"]
    e = Engine(m.build_graph(), m.init_params(jax.random.PRNGKey(0)))
    hot = np.array(m.synthetic_input(jax.random.PRNGKey(0))["dist"])
    hot[(0,) * hot.ndim] = 1e3 * np.abs(hot).max()
    e.calibrate([m.synthetic_input(jax.random.PRNGKey(1)), {"dist": hot}])
    plan = e.planned("accel")
    assert e._ptq_err["head"] > e.ptq_demote_threshold
    assert "head" in plan.demoted
    x = m.synthetic_input(jax.random.PRNGKey(3))
    a = e.run(x, "flex")
    b = e.run(x, "accel")
    np.testing.assert_array_equal(np.asarray(a["head"]),
                                  np.asarray(b["head"]))


# ---------------------------------------------------------------------------
# Pallas conv kernels vs lax reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("stride,padding", [
    (1, "SAME"), (2, "SAME"), (1, "VALID"), (2, "VALID"),
])
def test_pallas_conv2d_matches_lax(stride, padding):
    """The int8 conv with a fused int8 requant epilogue, channel-tiled
    and row-blocked, reproduces the int32-exact lax conv bit for bit."""
    rng = np.random.default_rng(stride * 7 + len(padding))
    x_q = jnp.asarray(rng.integers(-127, 128, (2, 14, 18, 3)), jnp.int8)
    w_q = jnp.asarray(rng.integers(-127, 128, (3, 3, 3, 8)), jnp.int8)
    ws = jnp.asarray(rng.random(8) * 1e-3 + 1e-4, jnp.float32)
    b = jnp.asarray(rng.standard_normal(8) * 0.1, jnp.float32)
    xs, rq = 0.031, 0.02
    got = kops.conv2d_int8(x_q, w_q, ws, b, x_scale=xs, stride=stride,
                           padding=padding, requant_scale=rq,
                           rows_per_block=3, cout_per_block=4)
    acc = jax.lax.conv_general_dilated(
        x_q.astype(jnp.int32), w_q.astype(jnp.int32), (stride, stride),
        padding, dimension_numbers=("NHWC", "HWIO", "NHWC"))
    want = acc.astype(jnp.float32) * (ws * jnp.float32(xs)) + b
    want = jnp.clip(jnp.round(want / jnp.float32(rq)), -127,
                    127).astype(jnp.int8)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("stride,padding", [
    (1, "SAME"), (2, "SAME"), (1, "VALID"), (2, "VALID"),
])
@pytest.mark.parametrize("relu", [False, True])
def test_pallas_conv2d_int8_matches_lax_int32(stride, padding, relu):
    """int8 conv must reproduce the int32-exact lax conv + epilogue."""
    rng = np.random.default_rng(stride + len(padding) + relu)
    x_q = jnp.asarray(rng.integers(-127, 128, (2, 13, 17, 5)), jnp.int8)
    w_q = jnp.asarray(rng.integers(-127, 128, (3, 3, 5, 7)), jnp.int8)
    ws = jnp.asarray(rng.random(7) * 0.1 + 1e-3, jnp.float32)
    bias = jnp.asarray(rng.standard_normal(7), jnp.float32)
    xs = 0.031
    got = kops.conv2d_int8(x_q, w_q, ws, bias, x_scale=xs, stride=stride,
                           padding=padding, relu=relu)
    acc = jax.lax.conv_general_dilated(
        x_q.astype(jnp.int32), w_q.astype(jnp.int32), (stride, stride),
        padding, dimension_numbers=("NHWC", "HWIO", "NHWC"))
    want = acc.astype(jnp.float32) * (ws * xs)[None, None, None, :] + bias
    if relu:
        want = jnp.maximum(want, 0.0)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("m,k,n", [(5, 18, 3), (1, 200, 1), (130, 433, 92)])
def test_int8_matmul_pads_unaligned_shapes(m, k, n):
    """No more tiny-divisor blocks: awkward shapes pad to aligned tiles."""
    rng = np.random.default_rng(m + k + n)
    x_q = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int8)
    w_q = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
    xs = jnp.asarray(rng.random(m) * 0.1 + 1e-3, jnp.float32)
    ws = jnp.asarray(rng.random(n) * 0.1 + 1e-3, jnp.float32)
    got = kops.int8_matmul(x_q, w_q, xs, ws)
    want = (np.asarray(x_q, np.int64) @ np.asarray(w_q, np.int64)
            ).astype(np.float32) * np.asarray(xs)[:, None] \
        * np.asarray(ws)[None, :]
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-4)
