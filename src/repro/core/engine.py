"""Dual-backend inference engine — compile once, serve batches.

Three execution backends for an op graph (DESIGN.md §2):

* ``cpu``   — the ARM-CPU baseline analog: the same batched program run
              op-by-op under ``jax.disable_jit``, fp32. Slow on purpose;
              it is the measured "1x".
* ``flex``  — the Vitis-HLS analog: fp32 math, jit-compiled by XLA.
              Supports *every* operator (sigmoid, 3-D conv/pool,
              comparators, sampling) at IEEE-754 fp32.
* ``accel`` — the Vitis-AI/DPU analog: INT8 PTQ weights, Pallas MXU
              kernels for conv2d (shift-and-matmul, no HBM im2col) and
              dense, fused ReLU + dequant epilogues; only a restricted
              operator set (core/inspector.py). Unsupported — or
              PTQ-infidelity-demoted — nodes fall back to flex, exactly
              the paper's partial-offload arrangement.

Execution is staged (core/plan.py, DESIGN.md §7): ``compile(backend,
batch_size)`` runs the inspector once, rewrites the graph through the
graph-compiler pass pipeline (core/passes.py, DESIGN.md §10: constant
folding, DCE, epilogue fusion, int8 requant chains — disable with
``Engine(..., fuse=False)``), partitions it into contiguous accel/flex
segments, folds PTQ weight/activation scales into per-node constants,
plans the static BRAM/DDR activation arena (core/memory.py), and emits
ONE jitted batched callable — inputs carry a leading batch dim
end-to-end. Compiled plans are cached per instance keyed by (backend,
batch size), so steady-state serving never re-traces; ``run``/
``run_batch`` are thin wrappers over the cache. Weight residency mirrors
the paper's BRAM policy: quantized weights are device-resident plan
constants (VMEM residency on real TPU is the kernels' block lifetime).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import inspector as inspector_mod
from repro.core.opgraph import Graph
from repro.core.plan import (BATCHED_OP_IMPLS, CompiledPlan, EagerPlan,
                             ExecutionPlan)
from repro.core.quantize import QuantizedLayer

# ---------------------------------------------------------------------------
# Single-sample fp32 op implementations (calibration tracing + references) —
# derived from the batched table so the math executed at calibration time
# can never drift from the math the plans serve.
# ---------------------------------------------------------------------------


def _single_sample(op_impl: Callable) -> Callable:
    def f(xs, p, a, rng):
        sub = None if rng is None else _raw_keys(rng)[None]
        return op_impl([x[None] for x in xs], p, a, sub)[0]
    return f


OP_IMPLS: Dict[str, Callable] = {
    op: _single_sample(impl) for op, impl in BATCHED_OP_IMPLS.items()}


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class EnginePlan:
    graph: Graph
    assignment: Dict[str, str]          # node -> 'accel' | 'flex'
    coverage: float                     # fraction of MACs on the accel path


class Engine:
    """Executes an op graph on a chosen backend (or a partitioned mix)."""

    def __init__(self, graph: Graph, params: Dict[str, Dict[str, jax.Array]],
                 ptq_demote_threshold: float = 0.2, fuse: bool = True,
                 autotune: bool = False, tuning_cache=None,
                 autotune_measure: bool = False,
                 autotune_pack_batch: int = 32):
        self.graph = graph
        self.params = params
        self.ptq_demote_threshold = ptq_demote_threshold
        # fuse=False is the escape hatch: skip the graph-compiler pass
        # pipeline (DESIGN.md §10) and build the pre-pass per-node plans
        self.fuse = fuse
        # autotune=False (the default) reproduces the heuristic kernel
        # blocks bit-for-bit; autotune=True runs the plan-time tile
        # search + weight prepack (DESIGN.md §11). ``tuning_cache`` is a
        # JSON path (or a TuningCache) — warm caches skip ALL candidate
        # evaluations; ``autotune_measure`` additionally wall-clocks the
        # model's top-K picks (opt-in: on this host it measures the
        # Pallas interpreter, on a TPU the compiled Mosaic kernels).
        self.autotune = autotune
        self.autotune_pack_batch = autotune_pack_batch
        self._tuner = None
        if autotune:
            from repro.core.autotune import Autotuner, TuningCache
            cache = (tuning_cache if isinstance(tuning_cache, TuningCache)
                     else TuningCache(tuning_cache))
            self._tuner = Autotuner(cache, measure=autotune_measure)
        elif tuning_cache is not None or autotune_measure:
            # silently dropping these would serve heuristic plans while
            # the caller believes a warm cache is in play
            raise ValueError(
                "tuning_cache/autotune_measure require autotune=True")
        self._quant: Optional[Dict[str, QuantizedLayer]] = None
        self._calib: Dict[str, float] = {}
        self._ptq_err: Dict[str, float] = {}
        # per-instance plan caches (an lru_cache on a bound method would pin
        # `self` — and its quantized weights — for the process lifetime)
        self._planned: Dict[str, ExecutionPlan] = {}
        self._compiled: Dict[tuple, object] = {}

    @property
    def tuner(self):
        """The engine's Autotuner (None when ``autotune=False``) — its
        ``stats``/``cache`` are the re-search observability surface."""
        return self._tuner

    # -- planning (paper: run the inspector, then choose the toolchain) -----

    def plan(self) -> EnginePlan:
        assignment = inspector_mod.assign_backends(self.graph)
        macs = self.graph.n_macs or 1
        accel_macs = sum(n.macs for n in self.graph.nodes.values()
                         if assignment[n.name] == "accel")
        return EnginePlan(self.graph, assignment, accel_macs / macs)

    # -- PTQ ----------------------------------------------------------------

    def calibrate(self, sample_inputs: List[Dict[str, np.ndarray]]) -> None:
        """Post-training quantization: record per-node activation absmax
        over a calibration set, quantize weights per-output-channel, and
        measure per-node PTQ error (the plan-time demotion gate)."""
        from repro.core.quantize import (_trace, calibrate_graph,
                                         ptq_error_ratios, quantize_weights)
        traces = [_trace(self, s) for s in sample_inputs]   # one fp32 pass
        self._calib = calibrate_graph(self, sample_inputs, traces=traces)
        self._quant = quantize_weights(self.graph, self.params)
        self._ptq_err = ptq_error_ratios(self, sample_inputs, self._quant,
                                         self._calib, traces=traces)
        # new scales/weights invalidate any previously folded accel plan
        self._planned.pop("accel", None)
        self._compiled = {k: v for k, v in self._compiled.items()
                          if k[0] != "accel"}

    @property
    def quantized(self) -> Optional[Dict[str, QuantizedLayer]]:
        """The PTQ int8 weights per conv/dense node (None before
        :meth:`calibrate`)."""
        return self._quant

    def share_calibration(self, other: "Engine", device=None) -> None:
        """Adopt ``other``'s PTQ calibration state (same graph topology
        and the same params): activation absmax, quantized weights, and
        the per-node PTQ error map. The twin-engine idiom the benchmarks
        and tests use to pay interpret-mode calibration once per model
        instead of once per engine variant. ``device`` places the
        adopted quantized weights there (e.g. a host-CPU twin of an
        engine calibrated on the chip)."""
        self._quant = other._quant
        if device is not None and self._quant is not None:
            self._quant = {
                k: QuantizedLayer(*jax.device_put((q.w_q, q.w_scale, q.bias),
                                                  device))
                for k, q in self._quant.items()}
        self._calib = other._calib
        self._ptq_err = other._ptq_err
        self._planned.pop("accel", None)
        self._compiled = {k: v for k, v in self._compiled.items()
                          if k[0] != "accel"}

    # -- staged compilation --------------------------------------------------

    def planned(self, backend: str = "flex") -> ExecutionPlan:
        """The **Planned** stage for a backend (inspector + PTQ folding run
        exactly once; cached per instance)."""
        key = "accel" if backend == "accel" else "flex"
        if key not in self._planned:
            self._planned[key] = ExecutionPlan(
                self.graph, self.params, key,
                quant=self._quant, act_absmax=self._calib,
                ptq_err=self._ptq_err,
                ptq_demote_threshold=self.ptq_demote_threshold,
                fuse=self.fuse, tuner=self._tuner,
                pack_batch=self.autotune_pack_batch)
        return self._planned[key]

    def compile(self, backend: str = "flex", batch_size: int = 1):
        """The **Compiled** stage: one batched executable per (backend,
        batch-size), cached — calling it never re-traces."""
        if backend not in ("cpu", "flex", "accel"):
            raise ValueError(backend)
        key = (backend, batch_size)
        if key not in self._compiled:
            planned = self.planned(backend)
            if backend == "cpu":
                self._compiled[key] = EagerPlan(planned, batch_size)
            else:
                self._compiled[key] = planned.lower(batch_size).compile()
        return self._compiled[key]

    # -- execution ----------------------------------------------------------

    def run(self, inputs: Dict[str, jax.Array], backend: str = "flex",
            rng: Optional[jax.Array] = None) -> Dict[str, jax.Array]:
        """Single-sample execution (the paper measures per-inference) —
        a batch-1 view over the compiled plan."""
        if rng is None:
            rng = jax.random.PRNGKey(0)
        batched = self.run_batch(
            {k: jnp.asarray(v, jnp.float32)[None] for k, v in inputs.items()},
            backend, rngs=_raw_keys(rng)[None])
        return {k: v[0] for k, v in batched.items()}

    def run_batch(self, inputs: Dict[str, jax.Array], backend: str = "flex",
                  rngs: Optional[jax.Array] = None) -> Dict[str, jax.Array]:
        """Batched execution: every input carries a leading batch dim;
        ``rngs`` is one PRNG key per sample ([B, 2])."""
        staged = {}
        batch = None
        for name, shape in self.graph.graph_inputs.items():
            x = jnp.asarray(inputs[name], jnp.float32)
            assert x.ndim == len(shape) + 1 and x.shape[1:] == shape, \
                (name, x.shape, shape)
            if batch is None:
                batch = x.shape[0]
            assert x.shape[0] == batch, (name, x.shape, batch)
            staged[name] = x
        if rngs is None:
            rngs = jax.random.split(jax.random.PRNGKey(0), batch)
        rngs = _raw_keys(rngs)
        assert rngs.shape == (batch, 2), rngs.shape
        return self.compile(backend, batch)(staged, rngs)


def _raw_keys(rng: jax.Array) -> jax.Array:
    """Accept both old-style uint32 keys and new-style typed keys."""
    if jax.dtypes.issubdtype(rng.dtype, jax.dtypes.prng_key):
        rng = jax.random.key_data(rng)
    return jnp.asarray(rng, jnp.uint32)
