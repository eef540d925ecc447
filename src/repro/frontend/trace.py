"""Jaxpr front-end: trace any ``jax.jit``-able callable into the op
graph (DESIGN.md §14).

    traced = trace(fn, {"image": (128, 256, 3)}, name="my_net")
    engine = Engine(traced.graph, traced.params)

``fn`` takes one dict of **batched** arrays and returns a dict of
batched arrays; the returned keys become the graph's output node names
(the golden-digest contract keys results by output name, so the user —
not the tracer — owns those names). Tracing happens at a fixed batch of
2, which disambiguates the batch dim from size-1 tensor dims; per-sample
graph shapes are the traced avals minus the leading dim.

The walk is a straightforward abstract interpretation of the
``ClosedJaxpr``: constvars/literals become ``ConstVal``s, call-like
primitives (jit, custom_jvp/vjp) are inlined, eqns whose inputs are all
constants are eagerly evaluated, and everything else dispatches through
the translator registry (translators.py). Node specs are staged so
peepholes can rewrite them (bias folding, sum-pool -> avgpool); the
``Graph`` is built at the end, where every node's inferred shape is
cross-checked against the traced aval — a translation bug dies here,
named, instead of surfacing as wrong numerics downstream.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import tree_util
from jax.extend import core as jex_core

from repro.core.opgraph import Graph
from repro.frontend.ir import ConstVal, NodeSpec, Ref, \
    UnsupportedPrimitiveError
from repro.frontend.translators import CONST_LAZY, INLINE_PRIMS, \
    TRANSLATORS

# fixed trace batch: >1 so the batch dim can't be mistaken for a size-1
# tensor dim when reshapes are classified
TRACE_BATCH = 2


@dataclasses.dataclass
class TracedModel:
    graph: Graph
    params: Dict[str, Dict[str, jax.Array]]
    out_names: Tuple[str, ...]


class TraceState:
    """Mutable walk state: staged node specs + var-use counts (the
    sole-consumer guard peepholes need) + the current naming hint."""

    def __init__(self, batch: int):
        self.batch = batch
        self.specs: List[NodeSpec] = []
        self.hint: Optional[str] = None
        self._uses: Dict[Any, int] = {}
        self._cur_invals: List[Any] = []

    # -- used by translators ------------------------------------------------

    def emit(self, op: str, inputs: List[Ref], attrs: Dict[str, Any],
             batched_shape: Tuple[int, ...],
             params: Optional[Dict[str, Any]] = None,
             hint: Optional[str] = None) -> Ref:
        spec = NodeSpec(len(self.specs), op, [r.sid for r in inputs],
                        dict(attrs), tuple(batched_shape),
                        params=dict(params or {}),
                        hint=hint or self.hint)
        self.specs.append(spec)
        return Ref(spec.sid)

    def spec(self, ref: Ref) -> NodeSpec:
        return self.specs[ref.sid]

    def reads_of(self, eqn, ref: Ref) -> int:
        """How many times the jaxpr reads the var that produced ``ref``
        (eqn operands + jaxpr outputs). Unknown -> 2, so peepholes that
        require a sole consumer conservatively refuse to fire."""
        for atom, val in zip(eqn.invars, self._cur_invals):
            if val is ref and isinstance(atom, jex_core.Var):
                return self._uses.get(atom, 2)
        return 2

    def as_ref(self, eqn, val, per_sample_rank: int) -> Ref:
        """A Ref for any value: Refs pass through; ConstVals become
        ``const`` nodes whose value is reshaped to ``per_sample_rank``
        (size-1 leading dims) so the batched impls broadcast them
        against the other operand."""
        if isinstance(val, Ref):
            return val
        v = np.asarray(val.value, np.float32)
        if val.bdims is not None:
            if any(d == 0 for d in val.bdims):
                raise UnsupportedPrimitiveError(
                    f"eqn `{eqn}`: constant broadcast into the batch "
                    "dimension has no graph form")
            shape = [1] * per_sample_rank
            for vd, d in enumerate(val.bdims):
                shape[d - 1] = v.shape[vd]
            v = v.reshape(shape)
        else:
            if v.ndim > per_sample_rank:
                raise UnsupportedPrimitiveError(
                    f"eqn `{eqn}`: rank-{v.ndim} constant does not fit "
                    f"a rank-{per_sample_rank} per-sample operand")
            v = v.reshape((1,) * (per_sample_rank - v.ndim) + v.shape)
        return self.emit("const", [], {"value": v},
                         (self.batch,) + v.shape)

    # -- used by the walker -------------------------------------------------

    def count_uses(self, jaxpr) -> None:
        for eqn in jaxpr.eqns:
            for a in eqn.invars:
                if isinstance(a, jex_core.Var):
                    self._uses[a] = self._uses.get(a, 0) + 1
        for a in jaxpr.outvars:
            if isinstance(a, jex_core.Var):
                self._uses[a] = self._uses.get(a, 0) + 1


def _sub_jaxpr(eqn):
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        sub = eqn.params.get(key)
        if sub is None:
            continue
        if hasattr(sub, "jaxpr"):            # ClosedJaxpr
            return sub.jaxpr, sub.consts
        return sub, []
    raise UnsupportedPrimitiveError(
        f"call-like primitive '{eqn.primitive.name}' carries no "
        f"inlineable jaxpr (eqn `{eqn}`)")


def _walk(state: TraceState, jaxpr, consts, invals,
          extra_uses: Optional[List[int]] = None) -> List[Any]:
    env: Dict[Any, Any] = {}
    for v, c in zip(jaxpr.constvars, consts):
        env[v] = ConstVal(c)
    for v, val in zip(jaxpr.invars, invals):
        env[v] = val
    state.count_uses(jaxpr)
    if extra_uses:
        # readers outside this (inlined) jaxpr still count against the
        # sole-consumer peephole guards
        for v, e in zip(jaxpr.invars, extra_uses):
            if e:
                state._uses[v] = state._uses.get(v, 0) + e

    def read(atom):
        if isinstance(atom, jex_core.Literal):
            return ConstVal(np.asarray(atom.val))
        return env[atom]

    for eqn in jaxpr.eqns:
        vals = [read(a) for a in eqn.invars]
        pname = eqn.primitive.name
        if pname in INLINE_PRIMS:
            sub, sub_consts = _sub_jaxpr(eqn)
            extra = [max(state._uses.get(a, 1) - 1, 0)
                     if isinstance(a, jex_core.Var) else 0
                     for a in eqn.invars]
            prev_hint, hint = state.hint, eqn.params.get("name")
            if isinstance(hint, str) and hint:
                state.hint = hint
            outs = _walk(state, sub, sub_consts, vals, extra)
            state.hint = prev_hint
        elif pname in CONST_LAZY and pname in TRANSLATORS:
            state._cur_invals = vals
            outs = TRANSLATORS[pname](state, eqn, vals)
        elif all(isinstance(v, ConstVal) for v in vals):
            # pure trace-time computation: evaluate eagerly
            if any(v.bdims is not None for v in vals):
                raise UnsupportedPrimitiveError(
                    f"eqn `{eqn}`: constant math on a pending broadcast "
                    "is not supported")
            res = eqn.primitive.bind(
                *[jnp.asarray(v.value) for v in vals], **eqn.params)
            if not eqn.primitive.multiple_results:
                res = [res]
            outs = [ConstVal(np.asarray(r)) for r in res]
        elif pname in TRANSLATORS:
            state._cur_invals = vals
            outs = TRANSLATORS[pname](state, eqn, vals)
        else:
            raise UnsupportedPrimitiveError(
                f"no translator registered for primitive '{pname}' "
                f"(eqn `{eqn}`); add one with "
                "repro.frontend.translators.register")
        for var, val in zip(eqn.outvars, outs):
            env[var] = val
    return [read(v) for v in jaxpr.outvars]


def _finalize(state: TraceState, name: str, input_names: List[str],
              shapes: Dict[str, Tuple[int, ...]], outvals: List[Any],
              out_names: List[str]) -> TracedModel:
    out_by_sid: Dict[int, str] = {}
    for val, oname in zip(outvals, out_names):
        if not isinstance(val, Ref):
            raise UnsupportedPrimitiveError(
                f"output {oname!r} is a trace-time constant, not a "
                "traced tensor")
        if val.sid in out_by_sid:
            raise ValueError(
                f"outputs {out_by_sid[val.sid]!r} and {oname!r} are the "
                "same traced tensor; each output needs its own node")
        if state.specs[val.sid].op == "input" and \
                state.specs[val.sid].hint != oname:
            raise ValueError(
                f"output {oname!r} is the untouched input "
                f"{state.specs[val.sid].hint!r}")
        out_by_sid[val.sid] = oname

    g = Graph(name)
    params: Dict[str, Dict[str, jax.Array]] = {}
    used = set(input_names) | set(out_names)
    for k in input_names:
        g.input(k, shapes[k])
    for spec in state.specs:
        if spec.op == "input":
            spec.name = spec.hint
            continue
        if spec.op.startswith("_sum_pool"):
            raise UnsupportedPrimitiveError(
                "reduce_window_sum without a trailing div-by-window-size "
                "has no graph form (expected an average pool)")
        node_name = out_by_sid.get(spec.sid)
        if node_name is None:
            base = spec.hint or spec.op
            i = len(g.order)
            node_name = f"{base}_{i}"
            while node_name in used or node_name in g.nodes:
                i += 1
                node_name = f"{base}_{i}"
        used.add(node_name)
        in_names = [state.specs[s].name for s in spec.inputs]
        g.add(spec.op, in_names, name=node_name, **spec.attrs)
        spec.name = node_name
        expect = tuple(spec.batched_shape[1:])
        if g.nodes[node_name].out_shape != expect:
            raise AssertionError(
                f"tracer bug at node {node_name!r} ({spec.op}): graph "
                f"inferred {g.nodes[node_name].out_shape} but the jaxpr "
                f"traced per-sample {expect}")
        if spec.params:
            params[node_name] = {k: jnp.asarray(v, jnp.float32)
                                 for k, v in spec.params.items()}
    g.mark_output(*out_names)
    return TracedModel(g, params, tuple(out_names))


def trace(fn: Callable, example_inputs: Dict[str, Any], *,
          name: str = "traced") -> TracedModel:
    """Trace ``fn`` (dict of batched arrays -> dict of batched arrays)
    into a ``TracedModel``. ``example_inputs`` maps input names to
    per-sample shapes (tuples) or per-sample example arrays."""
    shapes: Dict[str, Tuple[int, ...]] = {}
    for k, v in example_inputs.items():
        if isinstance(v, (tuple, list)) and \
                all(isinstance(d, (int, np.integer)) for d in v):
            shapes[k] = tuple(int(d) for d in v)
        else:
            shapes[k] = tuple(np.shape(v))
    batched = {k: jax.ShapeDtypeStruct((TRACE_BATCH,) + s, jnp.float32)
               for k, s in shapes.items()}
    closed, out_struct = jax.make_jaxpr(fn, return_shape=True)(batched)

    leaves = tree_util.tree_flatten_with_path(out_struct)[0]
    out_names: List[str] = []
    for path, _leaf in leaves:
        if len(path) != 1 or not isinstance(path[0], tree_util.DictKey):
            raise TypeError(
                "traced function must return a flat dict of named "
                f"output arrays, got {out_struct!r}")
        out_names.append(str(path[0].key))

    state = TraceState(TRACE_BATCH)
    input_names = sorted(shapes)       # dict flatten order == invars order
    invals = []
    for k in input_names:
        spec = NodeSpec(len(state.specs), "input", [], {},
                        (TRACE_BATCH,) + shapes[k], hint=k)
        state.specs.append(spec)
        invals.append(Ref(spec.sid))
    outvals = _walk(state, closed.jaxpr, closed.consts, invals)
    return _finalize(state, name, input_names, shapes, outvals, out_names)
