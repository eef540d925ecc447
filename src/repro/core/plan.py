"""Staged execution plans: Planned -> Lowered -> Compiled (DESIGN.md §7,
§10).

The paper's deployment flow is ahead-of-time by construction: the
inspector partitions the model, the quantizer folds scales, the compiler
emits a bitstream, and serving only ever *runs*. The seed engine instead
re-derived all of that per call. This module is the JaCe-style staged
chain that moves every decision to plan time:

* :class:`ExecutionPlan` (**Planned**) — built once per (engine, backend):
  the inspector's backend assignment, the PTQ fidelity gate (nodes whose
  calibration-time quantization error is too large are demoted to the
  flex path), then the graph-compiler **pass pipeline**
  (`core/passes.py`: constant folding, dead-node elimination, epilogue
  fusion, int8 producer->consumer requant fusion), the contiguous
  accel/flex *segments* over the REWRITTEN graph, PTQ weight/activation
  scales folded into per-node constants, and the static BRAM/DDR
  activation arena (`core/memory.py`) that prices the plan's
  :class:`~repro.core.energy.CostSignature`. ``fuse=False`` skips the
  pass pipeline entirely and reproduces the pre-pass plans node-for-node
  (the escape hatch the conformance suite pins).
* :class:`LoweredPlan` (**Lowered**) — the plan traced for one concrete
  batch size: a single jitted callable over ``[B, ...]`` inputs; every op
  implementation is natively batched (no per-sample ``x[None]``).
* :class:`CompiledPlan` (**Compiled**) — the XLA executable. Calling it
  never re-traces; the engine caches one per (backend, batch-size), so
  steady-state serving runs at whatever rate the hardware allows.

Random ops thread a per-sample key array ``rngs [B, 2]`` through the plan
(split per random node, vmapped over the batch), so row *i* of a batched
run is bit-identical to a single-sample run with key ``rngs[i]``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import autotune as autotune_mod
from repro.core import energy as energy_mod
from repro.core import memory as memory_mod
from repro.core.opgraph import (RANDOM_OPS, Graph, Node, base_op,
                                consumers, param_node)
from repro.core.passes import PassContext, PassManager, PassReport
from repro.kernels import ops as kops
from repro.kernels.conv2d import (DEFAULT_CONV_ROWS, conv_geometry,
                                  pad_input)


# ---------------------------------------------------------------------------
# Batched fp32 op implementations (leading batch dim everywhere)
# ---------------------------------------------------------------------------


def _conv2d_b(x, p, a):
    out = jax.lax.conv_general_dilated(
        x.astype(jnp.float32), p["w"].astype(jnp.float32),
        window_strides=(a.get("stride", 1),) * 2,
        padding=a.get("padding", "SAME"),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=a.get("groups", 1))
    return out + p["b"]


def _conv3d_b(x, p, a):
    out = jax.lax.conv_general_dilated(
        x.astype(jnp.float32), p["w"].astype(jnp.float32),
        window_strides=(a.get("stride", 1),) * 3,
        padding=a.get("padding", "SAME"),
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC"))
    return out + p["b"]


def _pool_b(x, a, ndim, op):
    k, s = a["kernel"], a.get("stride", a["kernel"])
    window = (1,) + (k,) * ndim + (1,)
    strides = (1,) + (s,) * ndim + (1,)
    if op == "max":
        # dtype-aware identity: the int8-domain chain pools int8 exactly
        # (max commutes with the monotone quantizer — DESIGN.md §10)
        init = (jnp.iinfo(x.dtype).min
                if jnp.issubdtype(x.dtype, jnp.integer) else -jnp.inf)
        return jax.lax.reduce_window(x, jnp.asarray(init, x.dtype),
                                     jax.lax.max, window, strides, "VALID")
    out = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides, "VALID")
    return out / (k ** ndim)


def _dense_b(x, p, a):
    if a.get("per_position", False):
        # token-wise projection: contract the LAST axis only; leading
        # (batch, position) axes broadcast through jnp.matmul
        out = x @ p["w"]
    else:
        out = x.reshape(x.shape[0], -1) @ p["w"]
    if "b" in p:
        out = out + p["b"]
    return out


def _reshape_b(x, a):
    tgt = list(a["shape"])
    if -1 in tgt:
        rest = int(np.prod([d for d in tgt if d != -1]))
        tgt[tgt.index(-1)] = int(np.prod(x.shape[1:])) // rest
    return x.reshape((x.shape[0],) + tuple(tgt))


def _attention_b(xs, a, config=None):
    """Batched flash attention over [B, S, H, hd] q/k/v. ``kv_int8``
    round-trips K/V through the per-(pos, head) int8 quantizer — the
    same codes the KV-cache arena stores, so prefill output is
    bit-identical to what cached decode reconstructs."""
    q, k, v = (t.astype(jnp.float32) for t in xs)
    if a.get("kv_int8", False):
        from repro.core import lm_quant
        k = lm_quant.dequantize_kv(*lm_quant.quantize_kv(k), jnp.float32)
        v = lm_quant.dequantize_kv(*lm_quant.quantize_kv(v), jnp.float32)
    bq = config.bq if config is not None and config.bq else a.get("bq", 256)
    bk = config.bk if config is not None and config.bk else a.get("bk", 256)
    return kops.flash_attention(q, k, v, causal=a.get("causal", True),
                                bq=bq, bk=bk)


def _ssd_b(xs, p, a, config=None):
    x, B_, C_, dt = (t.astype(jnp.float32) for t in xs)
    chunk = (config.chunk if config is not None and config.chunk
             else a.get("chunk", 256))
    y, _ = kops.ssd(x, B_, C_, dt, p["A"], chunk=chunk)
    return y


def _concat_axis(a) -> int:
    ax = a.get("axis", -1)
    return ax + 1 if ax >= 0 else ax


def _sample_normal_b(xs, rngs):
    mu, logvar = xs
    eps = jax.vmap(lambda k, m: jax.random.normal(k, m.shape))(rngs, mu)
    return mu + jnp.exp(0.5 * logvar) * eps


BATCHED_OP_IMPLS: Dict[str, Callable] = {
    "conv2d": lambda x, p, a, rng: _conv2d_b(x[0], p, a),
    "conv3d": lambda x, p, a, rng: _conv3d_b(x[0], p, a),
    "maxpool2d": lambda x, p, a, rng: _pool_b(x[0], a, 2, "max"),
    "avgpool2d": lambda x, p, a, rng: _pool_b(x[0], a, 2, "avg"),
    "maxpool3d": lambda x, p, a, rng: _pool_b(x[0], a, 3, "max"),
    "avgpool3d": lambda x, p, a, rng: _pool_b(x[0], a, 3, "avg"),
    "dense": lambda x, p, a, rng: _dense_b(x[0], p, a),
    "attention": lambda x, p, a, rng: _attention_b(x, a),
    "ssd": lambda x, p, a, rng: _ssd_b(x, p, a),
    "reshape": lambda x, p, a, rng: _reshape_b(x[0], a),
    "flatten": lambda x, p, a, rng: x[0].reshape(x[0].shape[0], -1),
    "relu": lambda x, p, a, rng: jnp.maximum(x[0], 0.0),
    "leaky_relu": lambda x, p, a, rng: jnp.where(
        x[0] > 0, x[0], a.get("alpha", 0.01) * x[0]),
    "sigmoid": lambda x, p, a, rng: jax.nn.sigmoid(x[0]),
    "tanh": lambda x, p, a, rng: jnp.tanh(x[0]),
    "softplus": lambda x, p, a, rng: jax.nn.softplus(x[0]),
    "exp": lambda x, p, a, rng: jnp.exp(x[0]),
    "concat": lambda x, p, a, rng: jnp.concatenate(x, axis=_concat_axis(a)),
    "add": lambda x, p, a, rng: x[0] + x[1],
    "sub": lambda x, p, a, rng: x[0] - x[1],
    "mul": lambda x, p, a, rng: x[0] * x[1],
    "greater": lambda x, p, a, rng: (x[0] > a["threshold"]).astype(
        jnp.float32),
    "sample_normal": lambda x, p, a, rng: _sample_normal_b(x, rng),
    "argmax": lambda x, p, a, rng: jnp.argmax(
        x[0].reshape(x[0].shape[0], -1), axis=1).astype(jnp.int32),
}


def _run_fused_f32(node: Node, xs, params) -> jax.Array:
    """An fp32 ``fused`` node: the base op, then its element-wise
    epilogue(s) — identical math to the unfused node pair, one plan node
    (what XLA fuses anyway; here it also fuses the *plan*, so the arena
    never allocates the intermediate)."""
    y = BATCHED_OP_IMPLS[node.attrs["base_op"]](
        xs, params.get(param_node(node), {}), node.attrs, None)
    for e in node.attrs.get("epilogue", ()):
        y = BATCHED_OP_IMPLS[e]([y], {}, {}, None)
    return y


# ---------------------------------------------------------------------------
# Plan-time folding
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Segment:
    """A contiguous run of nodes on one backend (the paper's partial
    offload unit — e.g. the VAE's sampling tail on the flex path)."""
    backend: str                    # 'accel' | 'flex'
    nodes: Tuple[str, ...]


@dataclasses.dataclass
class QuantNodePlan:
    """PTQ constants folded into a quantized node at plan time."""
    op: str                         # 'conv2d' | 'dense' (base compute op)
    w_q: jax.Array                  # dense: [K, N]; conv: [KH, KW, Cin, Cout]
    w_scale: jax.Array              # [N] per-output-channel
    bias: Optional[jax.Array]
    act_scale: float                # static per-tensor input scale
    act: Optional[str] = None       # fused activation epilogue
    requant_scale: Optional[float] = None   # int8 output at this scale
    int8_input: bool = False        # producer already delivered int8
    stride: int = 1
    padding: str = "SAME"
    per_position: bool = False      # dense over the last axis only (LM)


def partition_segments(graph: Graph, assignment: Dict[str, str]
                       ) -> List[Segment]:
    """Group ``graph.order`` into contiguous same-backend runs. Inputs
    and plan-time constants are structural — they move no data at run
    time, so they must never split a contiguous backend run (the arena
    charges real DDR round-trips at segment boundaries)."""
    segs: List[Segment] = []
    run: List[str] = []
    cur: Optional[str] = None
    for name in graph.order:
        if graph.nodes[name].op in ("input", "const"):
            continue
        b = assignment[name]
        if b != cur and run:
            segs.append(Segment(cur, tuple(run)))
            run = []
        cur = b
        run.append(name)
    if run:
        segs.append(Segment(cur, tuple(run)))
    return segs


class ExecutionPlan:
    """**Planned** stage: everything derivable without a batch size.

    Holds the folded graph program; :meth:`lower` binds a batch size and
    traces, :meth:`compile` (on the lowered stage) produces the reusable
    executable. ``n_traces`` counts lowerings — steady-state serving must
    not grow it.

    With ``fuse=True`` (the default) the op graph is rewritten by the
    pass pipeline before partitioning, and a static activation arena
    prices the plan's cost signature. ``fuse=False`` reproduces the
    pre-pass per-node plans exactly.
    """

    def __init__(self, graph: Graph, params: Dict[str, Dict[str, jax.Array]],
                 backend: str,
                 quant: Optional[Dict[str, Any]] = None,
                 act_absmax: Optional[Dict[str, float]] = None,
                 ptq_err: Optional[Dict[str, float]] = None,
                 ptq_demote_threshold: float = 0.2,
                 fuse: bool = True,
                 pass_manager: Optional[PassManager] = None,
                 tuner: Optional[autotune_mod.Autotuner] = None,
                 pack_batch: int = 32):
        from repro.core import inspector as inspector_mod
        self.source_graph = graph
        self.params = params
        self.backend = backend
        self.fuse = fuse
        self.n_traces = 0
        # plan-time autotuning (DESIGN.md §11): tuner=None is the escape
        # hatch that reproduces the heuristic kernels bit-for-bit.
        # Weight-layout dims are tuned ONCE at `pack_batch` (weights are
        # packed once for the mission); per-rung tuning covers only the
        # activation-schedule knobs against that fixed layout.
        self.tuner = tuner
        self.pack_batch = pack_batch
        self._tuning: Dict[int, Dict[str, autotune_mod.TuningDecision]] = {}
        self._layouts: Optional[Dict[str, autotune_mod.KernelConfig]] = None
        self.packed: Dict[str, Any] = {}
        self._packed_bytes: Dict[str, int] = {}
        # live int8 weight buffers (fed to executables as ARGUMENTS, not
        # baked-in trace constants) + pristine host copies for re-pack
        # recovery (DESIGN.md §13)
        self._weight_arena: Optional[Dict[str, jax.Array]] = None
        self._host_weights: Dict[str, np.ndarray] = {}

        assignment = inspector_mod.assign_backends(graph)
        self.demoted: List[str] = []
        self.qplans: Dict[str, QuantNodePlan] = {}
        self.fused_into: Dict[str, str] = {}    # legacy: relu node -> producer
        self.pass_report: Optional[PassReport] = None
        self.arena: Optional[memory_mod.ArenaPlan] = None
        # static KV-cache arena (LM decode) — attached post-construction
        # by the LM engine via attach_kv_plan()
        self.kv_plan: Optional[memory_mod.KVCachePlan] = None

        if backend == "accel":
            if quant is None:
                raise RuntimeError(
                    "accel backend needs calibrate() first (PTQ)")
            # PTQ fidelity gate first, on the source graph: calibration-
            # time quantization error too large -> run fp32 on the flex
            # path (the engine-level analog of the paper's QAT remark).
            for name in graph.order:
                node = graph.nodes[name]
                if (assignment[name] != "accel"
                        or node.op not in ("conv2d", "dense")
                        or name not in quant):
                    continue
                err = (ptq_err or {}).get(name, 0.0)
                if err > ptq_demote_threshold:
                    assignment[name] = "flex"
                    self.demoted.append(name)
        else:
            assignment = {n: "flex" for n in assignment}

        if fuse:
            ctx = PassContext(
                params=params, assignment=assignment,
                quant=quant if backend == "accel" else None,
                act_absmax=act_absmax if backend == "accel" else None)
            self.graph, self.pass_report = (
                pass_manager or PassManager()).run(graph, ctx)
            assignment = ctx.assignment
            if backend == "accel":
                self._fold_quant_fused(quant, act_absmax, assignment)
        else:
            self.graph = graph
            if backend == "accel":
                self._fold_quant_legacy(quant, act_absmax, assignment)

        self.assignment = assignment
        self.segments = partition_segments(self.graph, assignment)
        if fuse:
            self.arena = self._plan_arena()
        self._lowered: Dict[int, "LoweredPlan"] = {}

    # -- PTQ folding ---------------------------------------------------------

    def _act_scale(self, act_absmax: Optional[Dict[str, float]],
                   inp: str) -> float:
        from repro.core.quantize import act_scale
        absmax = (act_absmax or {}).get(inp)
        if absmax is None:
            raise RuntimeError(
                f"no calibration absmax for {inp!r} (accel plan)")
        return act_scale(absmax)

    def _fold_quant_fused(self, quant, act_absmax, assignment) -> None:
        """Quantized-node constants over the pass-rewritten graph: the
        fusion decisions arrive as node attrs (epilogue / requant_scale /
        int8_input) and fold straight into the QuantNodePlan."""
        for name in self.graph.order:
            node = self.graph.nodes[name]
            bop = base_op(node)
            if (assignment.get(name) != "accel"
                    or bop not in ("conv2d", "dense")):
                continue
            pkey = param_node(node)
            if pkey not in quant:
                continue
            q = quant[pkey]
            s = self._act_scale(act_absmax, node.inputs[0])
            epi = node.attrs.get("epilogue", ())
            common = dict(
                w_scale=q.w_scale, bias=q.bias, act_scale=s,
                act=epi[0] if epi else None,
                requant_scale=node.attrs.get("requant_scale"),
                int8_input=bool(node.attrs.get("int8_input")),
                per_position=bool(node.attrs.get("per_position")))
            if bop == "conv2d":
                w4 = q.w_q.reshape(self.params[pkey]["w"].shape)
                self.qplans[name] = QuantNodePlan(
                    "conv2d", w4, stride=node.attrs.get("stride", 1),
                    padding=node.attrs.get("padding", "SAME"), **common)
            else:
                self.qplans[name] = QuantNodePlan("dense", q.w_q, **common)

    def _fold_quant_legacy(self, quant, act_absmax, assignment) -> None:
        """The pre-pass (fuse=False) folding: per-node quantization with
        sole-consumer ReLU epilogues recorded as node aliases
        (``fused_into``) — node-for-node what the seed planner built."""
        cons = consumers(self.graph)
        for name in self.graph.order:
            node = self.graph.nodes[name]
            if (assignment[name] != "accel"
                    or node.op not in ("conv2d", "dense")
                    or name not in quant):
                continue
            q = quant[name]
            s = self._act_scale(act_absmax, node.inputs[0])
            fused = False
            cs = cons[name]
            if (len(cs) == 1 and self.graph.nodes[cs[0]].op == "relu"
                    and name not in self.graph.outputs
                    and assignment.get(cs[0]) == "accel"):
                fused = True
                self.fused_into[cs[0]] = name
            act = "relu" if fused else None
            if node.op == "conv2d":
                w4 = q.w_q.reshape(self.params[name]["w"].shape)
                self.qplans[name] = QuantNodePlan(
                    "conv2d", w4, q.w_scale, q.bias, s, act=act,
                    stride=node.attrs.get("stride", 1),
                    padding=node.attrs.get("padding", "SAME"))
            else:
                self.qplans[name] = QuantNodePlan(
                    "dense", q.w_q, q.w_scale, q.bias, s, act=act,
                    per_position=bool(node.attrs.get("per_position")))

    # -- arena ---------------------------------------------------------------

    def _quantized_names(self) -> set:
        return set(self.qplans)

    def _plan_arena(self) -> memory_mod.ArenaPlan:
        hw = energy_mod.BACKEND_HW[self.backend]
        w_bytes = energy_mod.weight_bytes(self.graph, self.backend,
                                          self._quantized_names(),
                                          self._packed_bytes or None)
        # BRAM-resident KV slots shrink the activation budget exactly
        # like resident weights do
        kv_bram = self.kv_plan.bram_bytes if self.kv_plan is not None else 0
        resident = w_bytes + kv_bram
        budget = max(int(hw.onchip_bytes) - resident, 0) \
            if resident <= hw.onchip_bytes else int(hw.onchip_bytes)
        act_dtype = {}
        for name, node in self.graph.nodes.items():
            if (node.attrs.get("int8")
                    or node.attrs.get("requant_scale") is not None):
                act_dtype[name] = 1     # int8-domain value
        return memory_mod.plan_arena(self.graph, self.segments, budget,
                                     act_dtype, backend=self.backend,
                                     weight_bytes=w_bytes)

    # -- autotuning (DESIGN.md §11) ------------------------------------------

    def _ensure_autotuned(self, batch_size: int) -> None:
        """Tune (and, on the accel path, prepack) once per batch rung.
        The packing step runs first, at ``pack_batch``: it fixes the
        weight-layout dims, builds the tile-aligned device buffers, and
        re-budgets the activation arena against the PACKED footprint —
        then every rung's schedule search is constrained to that layout."""
        if self.tuner is None or batch_size in self._tuning:
            return
        if self.backend == "accel" and self._layouts is None:
            pack = self.tuner.tune_plan(self, self.pack_batch)
            self._layouts = {
                n: d.config for n, d in pack.items()
                if d.kind in autotune_mod.INT8_KINDS}
            self.packed = autotune_mod.build_packed_weights(
                self, self._layouts)
            self._packed_bytes = {n: p.packed_bytes
                                  for n, p in self.packed.items()}
            self._weight_arena = None       # rebuild over packed buffers
            if self.arena is not None:
                self.arena = self._plan_arena()
            self._tuning[self.pack_batch] = pack
            if batch_size == self.pack_batch:
                return
        layouts = self._layouts if self.backend == "accel" else None
        self._tuning[batch_size] = self.tuner.tune_plan(
            self, batch_size, layouts=layouts)

    # -- the live weight arena (DESIGN.md §13) -------------------------------

    @property
    def weight_arena(self) -> Dict[str, jax.Array]:
        """Live int8 weight buffers, one per quantized node (the packed
        tile-aligned buffer when a prepacked entry exists, the raw
        ``w_q`` otherwise). Executables receive this dict as a RUNTIME
        argument on every call, so a bit flip injected here (or a
        re-pack recovery) takes effect without re-tracing — exactly the
        on-device weight memory an SEU would hit. Scales and biases stay
        trace-time constants: they are small fp32 host-derived tables,
        outside the modeled SEU cross-section."""
        if self._weight_arena is None:
            arena: Dict[str, jax.Array] = {}
            for name, qp in self.qplans.items():
                pk = self.packed.get(name)
                arena[name] = pk.w_q if pk is not None else qp.w_q
            self._weight_arena = arena
            self._host_weights = {n: np.array(a) for n, a in arena.items()}
        return self._weight_arena

    @property
    def host_weights(self) -> Dict[str, np.ndarray]:
        """Pristine host-side copies of the arena (captured at arena
        build, before any fault could touch device state) — the re-pack
        recovery source."""
        self.weight_arena
        return self._host_weights

    def repack_weights(self, names: Optional[List[str]] = None) -> int:
        """Restore arena entries from the pristine host copies (the
        recovery ladder's 're-pack' rung). Returns the bytes rewritten,
        which the fault controller prices as recovery work."""
        arena = self.weight_arena
        total = 0
        for name in (names if names is not None else list(arena)):
            arena[name] = jnp.asarray(self._host_weights[name])
            total += self._host_weights[name].nbytes
        return total

    # -- the batched program -------------------------------------------------

    def batched_fn(self, tuning: Optional[Dict[str, Any]] = None
                   ) -> Callable:
        """The plan as a python callable
        ``f(inputs[B,...], rngs[B,2], weights)``. ``tuning`` (node ->
        TuningDecision, one batch rung) binds the autotuned tile configs;
        ``weights`` is the live :attr:`weight_arena` dict — quantized
        nodes consume their int8 buffer from it at run time (prepacked
        entries arrive tile-aligned; no per-call weight padding)."""
        graph, params = self.graph, self.params
        qplans, fused_into = self.qplans, self.fused_into
        packed = self.packed

        def f(inputs: Dict[str, jax.Array], rngs: jax.Array,
              weights: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
            vals: Dict[str, jax.Array] = {}
            batch = rngs.shape[0]
            for name in graph.graph_inputs:
                vals[name] = inputs[name].astype(jnp.float32)
            # plan-time constants are structural (outside the segments,
            # like inputs): materialize them up front, keeping the dtype
            # the folded op produced (a folded bool/int result must not
            # silently become float32 — fuse=False would return its own)
            for name in graph.order:
                node = graph.nodes[name]
                if node.op == "const":
                    v = jnp.asarray(node.attrs["value"])
                    vals[name] = jnp.broadcast_to(v, (batch,) + v.shape)
            for seg in self.segments:
                for name in seg.nodes:
                    node = graph.nodes[name]
                    if name in fused_into:      # ReLU folded into producer
                        vals[name] = vals[fused_into[name]]
                        continue
                    xs = [vals[i] for i in node.inputs]
                    if name in qplans:
                        dec = tuning.get(name) if tuning else None
                        vals[name] = _run_quantized(
                            qplans[name], xs[0],
                            config=dec.config if dec else None,
                            packed=packed.get(name),
                            w_q=weights[name])
                        continue
                    if node.op == "fused":      # fp32 fused (flex path)
                        vals[name] = _run_fused_f32(node, xs, params)
                        continue
                    if node.op in ("attention", "ssd"):
                        # LM kernels take their tuned block shapes from
                        # the rung's decision set (numerics-neutral)
                        dec = tuning.get(name) if tuning else None
                        cfg = dec.config if dec else None
                        vals[name] = (
                            _attention_b(xs, node.attrs, cfg)
                            if node.op == "attention" else
                            _ssd_b(xs, params.get(name, {}),
                                   node.attrs, cfg))
                        continue
                    sub = None
                    if node.op in RANDOM_OPS:
                        nxt = jax.vmap(jax.random.split)(rngs)  # [B, 2, 2]
                        rngs_, sub = nxt[:, 0], nxt[:, 1]
                        rngs = rngs_
                    vals[name] = BATCHED_OP_IMPLS[node.op](
                        xs, params.get(name, {}), node.attrs, sub)
            return {o: vals[o] for o in graph.outputs}

        return f

    # -- staging -------------------------------------------------------------

    def lower(self, batch_size: int) -> "LoweredPlan":
        if batch_size in self._lowered:
            return self._lowered[batch_size]
        self._ensure_autotuned(batch_size)
        in_sds = {
            name: jax.ShapeDtypeStruct((batch_size,) + tuple(shape),
                                       jnp.float32)
            for name, shape in self.graph.graph_inputs.items()}
        rng_sds = jax.ShapeDtypeStruct((batch_size, 2), jnp.uint32)
        w_sds = {name: jax.ShapeDtypeStruct(a.shape, a.dtype)
                 for name, a in self.weight_arena.items()}
        lowered = jax.jit(
            self.batched_fn(self._tuning.get(batch_size))).lower(
                in_sds, rng_sds, w_sds)
        self.n_traces += 1
        lp = LoweredPlan(self, batch_size, lowered)
        self._lowered[batch_size] = lp
        return lp

    def cost_signature(self, batch_size: int,
                       backend: Optional[str] = None
                       ) -> energy_mod.CostSignature:
        """Plan-time modeled cost of one ``batch_size`` dispatch on this
        plan's backend (``backend`` overrides for the cpu/EagerPlan view,
        which executes the flex plan on the eager baseline hardware).

        Fused plans price DDR traffic from the static arena; the eager
        cpu view and unfused plans keep the op-by-op bytes model — every
        activation round-trips DDR, exactly what per-node dispatch does.
        """
        # always pass the exact quantized set — an accel plan whose nodes
        # were ALL PTQ-demoted runs fp32 and must be priced at fp32
        # widths, not the assume-int8 graph-only approximation
        if backend is None and self.tuner is not None:
            self._ensure_autotuned(batch_size)
            return self._charge_kv(self.tuned_cost_signature(
                batch_size, self._tuning[batch_size],
                packed_bytes=self._packed_bytes or None))
        if self.arena is not None and backend is None:
            return self._charge_kv(energy_mod.plan_cost_signature(
                self.graph, self.backend, batch_size, self.arena,
                quantized=self._quantized_names()))
        return self._charge_kv(energy_mod.cost_signature(
            self.graph, backend or self.backend, batch_size,
            quantized=self._quantized_names()))

    def attach_kv_plan(self, kv_plan: memory_mod.KVCachePlan) -> None:
        """Charge a static KV-cache arena to this plan: BRAM-resident
        slots shrink the activation-arena budget exactly like resident
        weights, and every cost signature reports the packed KV
        footprint (``kv_resident_bytes``)."""
        self.kv_plan = kv_plan
        if self.arena is not None:
            self.arena = self._plan_arena()

    def _charge_kv(self, sig: energy_mod.CostSignature
                   ) -> energy_mod.CostSignature:
        if self.kv_plan is None:
            return sig
        return dataclasses.replace(
            sig, kv_resident_bytes=float(self.kv_plan.total_bytes))

    def stage_costs(self, batch_size: int,
                    backend: Optional[str] = None
                    ) -> Tuple[energy_mod.StageCost, ...]:
        """The plan's pipeline-stage decomposition at ``batch_size``
        (DESIGN.md §12): host stage_in -> one stage per segment on its
        backend resource -> host readback. Priced from the same node
        times (tuned when the autotuner ran) and the same bytes model as
        the serial signature. The ``backend`` override (the EagerPlan
        cpu view) has no staging channel or segment pipeline — it is one
        monolithic eager stage."""
        if backend is not None and backend != self.backend:
            sig = self.cost_signature(batch_size, backend=backend)
            return (energy_mod.StageCost("eager", backend, sig.latency_s),)
        node_times = None
        if self.tuner is not None:
            self._ensure_autotuned(batch_size)
            node_times = {n: d.modeled_s
                          for n, d in self._tuning[batch_size].items()}
        return energy_mod.stage_costs(
            self.graph, self.backend, batch_size, self.segments,
            arena=self.arena, quantized=self._quantized_names(),
            node_times=node_times,
            packed_bytes=self._packed_bytes or None)

    def pipelined_cost_signature(self, batch_size: int,
                                 backend: Optional[str] = None
                                 ) -> energy_mod.CostSignature:
        """`cost_signature` with the pipelined-latency term filled in:
        the longest stage of `stage_costs` — the steady-state per-batch
        interval when staging, segment compute, and readback overlap
        across batches. Every other field (latency_s, energy_j, ...) is
        byte-for-byte the serial signature."""
        sig = self.cost_signature(batch_size, backend=backend)
        stages = self.stage_costs(batch_size, backend=backend)
        return dataclasses.replace(
            sig, pipelined_latency_s=max(s.seconds for s in stages))

    def default_cost_signature(self, batch_size: int
                               ) -> energy_mod.CostSignature:
        """The heuristic-default configs priced through the SAME
        kernel-level pricer (and the same packed footprint) as the tuned
        signature — THE baseline every default-vs-tuned comparison uses
        (benchmarks/autotune.py, benchmarks/throughput.py): comparing
        tuned numbers against the coarse roofline would mix two models."""
        return self.tuned_cost_signature(
            batch_size, autotune_mod.price_defaults(self, batch_size),
            packed_bytes=self._packed_bytes or None)

    def tuned_cost_signature(self, batch_size: int,
                             decisions: Dict[str, Any],
                             packed_bytes: Optional[Dict[str, int]] = None
                             ) -> energy_mod.CostSignature:
        """The plan's cost signature with the kernel-level pricing of a
        decision set substituted for the coarse per-node roofline term —
        the one pricer both the tuned plan AND the benchmark's
        heuristic-default baseline (`autotune.price_defaults`) go
        through, so default-vs-tuned comparisons never mix models."""
        node_times = {n: d.modeled_s for n, d in decisions.items()}
        extra = sum(d.extra_bytes for d in decisions.values())
        if self.arena is not None:
            return energy_mod.plan_cost_signature(
                self.graph, self.backend, batch_size, self.arena,
                quantized=self._quantized_names(), node_times=node_times,
                extra_bytes=extra, packed_bytes=packed_bytes)
        return energy_mod.cost_signature(
            self.graph, self.backend, batch_size,
            quantized=self._quantized_names(), node_times=node_times,
            extra_bytes=extra, packed_bytes=packed_bytes)

    # -- reporting -----------------------------------------------------------

    def summary(self) -> str:
        n_fused = sum(1 for n in self.graph.nodes.values()
                      if n.op == "fused")
        lines = [f"ExecutionPlan[{self.graph.name}/{self.backend}]: "
                 f"{len(self.segments)} segment(s), "
                 f"{len(self.qplans)} quantized node(s), "
                 f"{n_fused + len(self.fused_into)} fused epilogue(s), "
                 f"fuse={'on' if self.fuse else 'off'}"]
        for seg in self.segments:
            lines.append(f"  [{seg.backend:5s}] {seg.nodes[0]} .. "
                         f"{seg.nodes[-1]} ({len(seg.nodes)} nodes)")
        if self.pass_report is not None and self.pass_report.n_rewrites:
            lines.append("  passes:")
            lines.append(self.pass_report.summary())
        if self.demoted:
            lines.append(f"  PTQ-demoted to flex: {self.demoted}")
        if self.arena is not None:
            a = self.arena
            lines.append(
                f"  arena: peak {a.bram_peak:,}/{a.bram_budget:,} B BRAM, "
                f"{a.n_spilled} spill(s), "
                f"{a.ddr_bytes_per_sample:,} DDR B/sample")
        if self.kv_plan is not None:
            lines.append("  " + self.kv_plan.summary())
        return "\n".join(lines)

    def as_text(self) -> str:
        """Full textual plan dump: the rewritten graph, per-node backend
        and quantization state, fusion groups, and the arena table."""
        lines = [self.summary(), "", self.graph.summary()]
        if self.qplans:
            lines.append("")
            for name, qp in self.qplans.items():
                bits = [f"s_in={qp.act_scale:.3g}"]
                if qp.act:
                    bits.append(f"act={qp.act}")
                if qp.requant_scale is not None:
                    bits.append(f"requant={qp.requant_scale:.3g}")
                if qp.int8_input:
                    bits.append("int8-in")
                lines.append(f"  int8 {name:24s} {qp.op:7s} "
                             + " ".join(bits))
        if self._tuning:
            lines.append("")
            for bsz in sorted(self._tuning):
                lines.append(f"  autotune @ batch {bsz}:")
                for name, d in self._tuning[bsz].items():
                    cfg = d.config
                    if d.kind == "int8_dense":
                        desc = f"tile {cfg.bm}x{cfg.bn}x{cfg.bk}"
                    elif d.kind == "int8_conv":
                        desc = f"rows/blk {cfg.rows_per_block}"
                        if cfg.cout_per_block:
                            desc += f" cout/blk {cfg.cout_per_block}"
                    elif d.kind == "attention":
                        desc = f"blocks bq={cfg.bq} bk={cfg.bk}"
                    elif d.kind == "ssd":
                        desc = f"chunk {cfg.chunk}"
                    else:
                        desc = f"unroll x{cfg.unroll}"
                    pk = self.packed.get(name)
                    pb = (f"  packed={pk.packed_bytes:,} B"
                          if pk is not None else "")
                    lines.append(
                        f"    {name:24s} {desc:20s} "
                        f"t={d.modeled_s*1e6:9.2f} us "
                        f"(default {d.default_s*1e6:9.2f} us, "
                        f"x{d.speedup:.2f}) [{d.source}]{pb}")
        if self.arena is not None:
            lines.append("")
            lines.append(self.arena.summary())
        return "\n".join(lines)


def _run_quantized(qp: QuantNodePlan, x: jax.Array,
                   config: Optional[Any] = None,
                   packed: Optional[Any] = None,
                   w_q: Optional[jax.Array] = None) -> jax.Array:
    """One fused kernel per quantized layer: static-scale requantize ->
    int8 MXU matmul/conv -> dequant (+bias, +act, +requantize) epilogue.

    Static scales are the DPU contract (and what makes the plan a fixed
    program): activations beyond the calibration-set absmax SATURATE at
    +-127, exactly as on the real accelerator — serve-time inputs must be
    covered by a representative calibration set (DESIGN.md §7). When the
    producer already requantized (``int8_input``), the incoming int8
    values are consumed directly — the fp32 intermediate never existed.

    With ``packed`` (a prepacked weight-arena entry, DESIGN.md §11) the
    kernels consume tile-aligned device buffers directly: weight padding
    happened once at plan time, input staging (quantize + the conv's
    SAME pad, geometry computed once at lowering) is all that remains
    per call. ``config`` binds the rung's autotuned tile schedule; both
    paths are bit-exact to the heuristic default.

    ``w_q`` is the node's live weight-arena buffer (a runtime argument
    of the traced program — DESIGN.md §13); when omitted, the plan-time
    constant (``packed.w_q`` / ``qp.w_q``) is baked in as before.
    """
    s = qp.act_scale
    wq = w_q if w_q is not None else (
        packed.w_q if packed is not None else qp.w_q)
    if qp.op == "dense":
        # per_position folds every leading (batch, position) axis into
        # the matmul M dim — one int8 GEMM for the whole token batch —
        # and restores the leading axes afterwards
        lead = x.shape[:-1] if qp.per_position else (x.shape[0],)
        x2 = (x.reshape(-1, x.shape[-1]) if qp.per_position
              else x.reshape(x.shape[0], -1))
        x_q = x2 if qp.int8_input else jnp.clip(
            jnp.round(x2 / s), -127, 127).astype(jnp.int8)
        scales = jnp.full((x2.shape[0],), s, jnp.float32)
        if packed is not None:
            out = kops.int8_matmul(
                x_q, wq, scales, packed.w_scale, packed.bias,
                act=qp.act, requant_scale=qp.requant_scale,
                bm=(config.bm if config and config.bm else 128),
                bn=packed.bn, bk=packed.bk, prepacked=True,
                n_out=packed.n)
        else:
            out = kops.int8_matmul(
                x_q, wq, scales, qp.w_scale,
                qp.bias, act=qp.act, requant_scale=qp.requant_scale)
        if qp.per_position:
            out = out.reshape(tuple(lead) + (out.shape[-1],))
        return out
    x_q = x if qp.int8_input else jnp.clip(
        jnp.round(x / s), -127, 127).astype(jnp.int8)
    if packed is not None:
        h, w = int(x_q.shape[1]), int(x_q.shape[2])
        kh, kw = int(wq.shape[0]), int(wq.shape[1])
        rows = (config.rows_per_block
                if config and config.rows_per_block else DEFAULT_CONV_ROWS)
        geom = conv_geometry(h, w, kh, kw, qp.stride, qp.padding, rows)
        x_q = pad_input(x_q, geom)       # plan-time geometry, one staging
        return kops.conv2d_int8(
            x_q, wq, packed.w_scale, packed.bias, x_scale=s,
            stride=qp.stride, padding=qp.padding, act=qp.act,
            requant_scale=qp.requant_scale, rows_per_block=rows,
            cout_per_block=packed.cout_per_block, cout=packed.cout,
            pre_padded=True, in_hw=(h, w))
    return kops.conv2d_int8(
        x_q, wq, qp.w_scale, qp.bias, x_scale=s,
        stride=qp.stride, padding=qp.padding, act=qp.act,
        requant_scale=qp.requant_scale)


class LoweredPlan:
    """**Lowered** stage: traced for one batch size, not yet an executable."""

    def __init__(self, plan: ExecutionPlan, batch_size: int, lowered):
        self.plan = plan
        self.batch_size = batch_size
        self.lowered = lowered
        self._compiled: Optional[CompiledPlan] = None

    def as_text(self) -> str:
        return self.lowered.as_text()

    def compile(self) -> "CompiledPlan":
        if self._compiled is None:
            self._compiled = CompiledPlan(self.plan, self.batch_size,
                                          self.lowered.compile())
        return self._compiled


class CompiledPlan:
    """**Compiled** stage: an XLA executable — calling it never re-traces.
    Carries its plan-time :class:`~repro.core.energy.CostSignature`: the
    modeled FLOPs / bytes / J-per-inference / W of one dispatch at this
    batch size, so a dispatcher can rank and power-budget candidates
    without ever measuring (DESIGN.md §9). Fused plans price their DDR
    bytes from the static arena (§10), so fusion shifts the dispatcher's
    energy ranking."""

    def __init__(self, plan: ExecutionPlan, batch_size: int, executable):
        self.plan = plan
        self.batch_size = batch_size
        self._executable = executable
        self.cost = plan.pipelined_cost_signature(batch_size)
        self.stages = plan.stage_costs(batch_size)

    @property
    def n_traces(self) -> int:
        return self.plan.n_traces

    def __call__(self, inputs: Dict[str, jax.Array], rngs: jax.Array
                 ) -> Dict[str, jax.Array]:
        # the weight arena is read LIVE on every call: SEU injection and
        # re-pack recovery swap entries without touching the executable
        return self._executable(inputs, rngs, self.plan.weight_arena)


class EagerPlan:
    """The cpu-backend stage: the same batched program, run op-by-op with
    jit disabled (the paper's ARM-CPU '1x' baseline analog)."""

    def __init__(self, plan: ExecutionPlan, batch_size: int):
        self.plan = plan
        self.batch_size = batch_size
        self._fn = plan.batched_fn()
        self.cost = plan.pipelined_cost_signature(batch_size, backend="cpu")
        self.stages = plan.stage_costs(batch_size, backend="cpu")

    @property
    def n_traces(self) -> int:
        return self.plan.n_traces

    def __call__(self, inputs: Dict[str, jax.Array], rngs: jax.Array
                 ) -> Dict[str, jax.Array]:
        with jax.disable_jit():
            return self._fn(inputs, rngs, self.plan.weight_arena)
