"""Cross-backend conformance sweep: every space model x {cpu, flex,
accel} x batch rungs {1, 4, 16, 32} against the cpu (eager fp32)
reference — so backend-selection changes (now made at serve time by the
energy-aware dispatcher) can never silently change results.

The contract, per dtype and path:

* **integer outputs** (argmax region classes): EXACT on cpu/flex. On
  accel a class may flip ONLY where the fp32 logit margin is inside the
  pinned PTQ bound (each logit moves at most ``atol``, so a decisive
  margin — > 2x atol — can never flip), and only for a small fraction of
  samples: backend selection must never change a classification the
  fp32 path is decisive about.
* **flex** float outputs: float-associativity tolerance vs cpu (jitted
  vs eager fp32 reduce in different orders; measured <= ~1e-6).
* **accel** float outputs: within the model's pinned PTQ error bound vs
  cpu (static int8 scales; bounds measured on the fixed fixture and
  pinned with ~4x headroom — a plan/quantizer change that degrades PTQ
  fidelity fails here first). Thresholded *decision* outputs
  (ESPERTA's ``warn*``) are exempt from the cpu comparison — PTQ
  legitimately moves near-threshold warnings (the paper's "noticeable"
  PTQ note) — but they remain pinned by rung-invariance below.
* **int8 path rung-invariance**: on accel, rows of a batch-32 dispatch
  are BIT-identical to the batch-1/4/16 dispatches of the same requests
  whenever the plan is fully quantized (static scales + int32
  accumulation make the int8 kernels batch-shape-invariant); plans with
  fp32 matmul nodes on their flex tail get float-associativity
  tolerance instead.
"""
import os

import jax
import numpy as np
import pytest

from repro.core.conformance import (ACCEL_ATOL, DECISION_OF, FLEX_TOL,
                                    assert_flips_margin_bounded,
                                    is_decision)
from repro.core.engine import Engine
from repro.core.opgraph import base_op
from repro.models import SPACE_MODELS

# CONFORMANCE_TOP_RUNG caps the sweep (CI runs the conv-heavy models at
# a small rung so the full cross-backend contract still runs there; the
# uncapped 6x3x4 sweep is tier-1/slow)
_TOP_RUNG = int(os.environ.get("CONFORMANCE_TOP_RUNG", "32"))
RUNGS = tuple(r for r in (1, 4, 16, 32) if r <= _TOP_RUNG) or (1,)
TOP = RUNGS[-1]
BACKENDS = ("cpu", "flex", "accel")
N_CALIB = 4
INPUT_KEY, PARAM_KEY, RNG_KEY = 123, 0, 7

_STATE = {}


def _state(name):
    """Per-model engine + fixed fixture + memoized per-cell outputs (each
    of the 72 sweep cells is computed exactly once across the module)."""
    if name not in _STATE:
        m = SPACE_MODELS[name]
        e = Engine(m.build_graph(),
                   m.init_params(jax.random.PRNGKey(PARAM_KEY)))
        e.calibrate([m.synthetic_input(jax.random.PRNGKey(i))
                     for i in range(N_CALIB)])
        _STATE[name] = {
            "engine": e,
            "inputs": m.synthetic_batch(jax.random.PRNGKey(INPUT_KEY), TOP),
            "rngs": jax.random.split(jax.random.PRNGKey(RNG_KEY), TOP),
            "outs": {},
        }
    return _STATE[name]


def _outputs(name, backend, rung):
    st = _state(name)
    if (backend, rung) not in st["outs"]:
        out = st["engine"].run_batch(
            {k: v[:rung] for k, v in st["inputs"].items()},
            backend, st["rngs"][:rung])
        st["outs"][(backend, rung)] = {k: np.asarray(v)
                                       for k, v in out.items()}
    return st["outs"][(backend, rung)]


@pytest.mark.slow
@pytest.mark.parametrize("rung", RUNGS)
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("name", sorted(SPACE_MODELS))
def test_backend_matches_cpu_reference(name, backend, rung):
    ref = _outputs(name, "cpu", TOP)
    got = _outputs(name, backend, rung)
    assert set(got) == set(ref), (name, backend)
    for k in ref:
        a, r = got[k], ref[k][:rung]
        msg = f"{name}/{backend}/b{rung}/{k}"
        assert a.shape == r.shape, msg
        if np.issubdtype(a.dtype, np.integer):
            if backend == "accel" and k in DECISION_OF:
                assert_flips_margin_bounded(
                    a, r, ref[DECISION_OF[k]][:rung], ACCEL_ATOL[name], msg)
            else:
                np.testing.assert_array_equal(a, r, err_msg=msg)
        elif backend == "accel":
            if is_decision(k):
                continue                 # pinned by rung-invariance below
            np.testing.assert_allclose(a, r, rtol=1e-5,
                                       atol=ACCEL_ATOL[name], err_msg=msg)
        else:
            np.testing.assert_allclose(a, r, err_msg=msg, **FLEX_TOL)


@pytest.mark.parametrize("name", sorted(SPACE_MODELS))
def test_accel_rung_invariance(name):
    """Same requests through every accel rung: bit-exact for fully
    quantized plans, float-associativity otherwise — dispatch rung choice
    (including the envelope's rung degradation) cannot change results."""
    st = _state(name)
    plan = st["engine"].planned("accel")
    pure_int8 = not any(
        base_op(plan.graph.nodes[n]) in ("dense", "conv2d", "conv3d")
        for seg in plan.segments if seg.backend == "flex"
        for n in seg.nodes)
    top = _outputs(name, "accel", TOP)
    for rung in RUNGS[:-1]:
        small = _outputs(name, "accel", rung)
        for k in top:
            a, b = top[k][:rung], small[k]
            msg = f"{name}/accel b{TOP}[:{rung}] vs b{rung}/{k}"
            if pure_int8 or np.issubdtype(a.dtype, np.integer):
                np.testing.assert_array_equal(a, b, err_msg=msg)
            else:
                np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6,
                                           err_msg=msg)


@pytest.mark.parametrize("name", sorted(SPACE_MODELS))
def test_flex_rung_invariance(name):
    """Flex rows are rung-invariant to float associativity: the ladder
    and the scheduler's padding cannot perturb fp32 results."""
    top = _outputs(name, "flex", TOP)
    for rung in RUNGS[:-1]:
        small = _outputs(name, "flex", rung)
        for k in top:
            np.testing.assert_allclose(
                top[k][:rung], small[k], rtol=1e-6, atol=1e-6,
                err_msg=f"{name}/flex b{TOP}[:{rung}] vs b{rung}/{k}")


# ---------------------------------------------------------------------------
# fused vs unfused (the graph-compiler pass pipeline, DESIGN.md §10)
# ---------------------------------------------------------------------------

FUSED_RUNG = min(4, TOP)


@pytest.mark.parametrize("name", sorted(SPACE_MODELS))
def test_fused_matches_unfused(name):
    """The pass pipeline must be a pure optimization: fused plans are
    BIT-exact to the fuse=False escape hatch on both backends — int8
    because the monotone quantizer commutes with the fused chain ops,
    fp32 because fusion executes the identical op sequence inside one
    plan node (same XLA program)."""
    st = _state(name)
    m = SPACE_MODELS[name]
    e0 = Engine(m.build_graph(),
                m.init_params(jax.random.PRNGKey(PARAM_KEY)), fuse=False)
    e0.calibrate([m.synthetic_input(jax.random.PRNGKey(i))
                  for i in range(N_CALIB)])
    inputs = {k: v[:FUSED_RUNG] for k, v in st["inputs"].items()}
    rngs = st["rngs"][:FUSED_RUNG]
    for backend in ("flex", "accel"):
        fused = _outputs(name, backend, FUSED_RUNG)
        unfused = e0.run_batch(inputs, backend, rngs)
        for k in fused:
            np.testing.assert_array_equal(
                fused[k], np.asarray(unfused[k]),
                err_msg=f"{name}/{backend}/fused-vs-unfused/{k}")
    # the escape hatch reproduces the pre-pass plan node-for-node: no
    # rewritten nodes, segments covering the source graph exactly
    plan0 = e0.planned("accel")
    assert plan0.graph is e0.graph
    assert all(n.op not in ("fused", "const")
               for n in plan0.graph.nodes.values())
    flat = [n for seg in plan0.segments for n in seg.nodes]
    assert flat == [n for n in e0.graph.order
                    if e0.graph.nodes[n].op != "input"]
