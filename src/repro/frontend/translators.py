"""Per-primitive jaxpr -> op-graph translator registry.

Each translator maps one jaxpr eqn onto zero or more ``NodeSpec``s via
the ``TraceState`` passed in, and returns the output values (``Ref`` or
``ConstVal``) for the eqn's outvars. Registering a new primitive is one
decorated function (DESIGN.md §14):

    @register("my_primitive")
    def _my_primitive(state, eqn, invals):
        (x,) = invals
        return [state.emit("my_op", [x], {}, eqn.outvars[0].aval.shape)]

Translators enforce the *exact* parameterizations the graph ops model —
anything else raises ``UnsupportedPrimitiveError`` naming the eqn, never
a bare KeyError. Three peepholes keep traced graphs structurally
identical to hand-built ones (the bit-exactness contract,
tests/test_frontend.py):

* ``conv/dense + add(broadcast(const))`` folds into the node's bias
  (sole-consumer guarded) — biases are node params, not add nodes.
* ``reduce_window_sum`` stages a pending ``_sum_poolNd`` spec that the
  following ``div`` by ``k**nd`` rewrites to ``avgpoolNd`` — the same
  sum-then-divide the batched impl executes, so the fold is bit-exact.
* ``gt`` + ``convert_element_type[f32]`` collapses onto the ``greater``
  node, whose impl already emits f32.
"""
from __future__ import annotations

from typing import Callable, Dict, List

import numpy as np

from repro.frontend.ir import ConstVal, NodeSpec, Ref, \
    UnsupportedPrimitiveError

TRANSLATORS: Dict[str, Callable] = {}

# call-like primitives the walker inlines instead of translating
INLINE_PRIMS = ("jit", "custom_jvp_call", "custom_vjp_call",
                "closed_call", "call")

# primitives whose translator must run even on all-constant inputs:
# eagerly materializing a broadcast bakes in target dims and loses the
# original per-channel vector the bias-fold peephole matches on
CONST_LAZY = ("broadcast_in_dim",)


def register(name: str):
    def deco(fn):
        TRANSLATORS[name] = fn
        return fn
    return deco


def _fail(eqn, why: str) -> None:
    raise UnsupportedPrimitiveError(
        f"cannot translate eqn `{eqn}`: {why}")


def _the_ref(eqn, val, what: str) -> Ref:
    if not isinstance(val, Ref):
        _fail(eqn, f"{what} must be a traced tensor, got a trace-time "
                   "constant")
    return val


def _const_scalar(val) -> float:
    """Extract a python scalar from a size-1 ConstVal, else None."""
    if not isinstance(val, ConstVal) or val.bdims is not None:
        return None
    v = np.asarray(val.value)
    if v.size != 1:
        return None
    return float(v.reshape(()))


def _out_shape(eqn) -> tuple:
    return tuple(eqn.outvars[0].aval.shape)


# ---------------------------------------------------------------------------
# conv / dense
# ---------------------------------------------------------------------------

# channel-last dimension_numbers for 2-D (NHWC/HWIO/NHWC) and 3-D
# (NDHWC/DHWIO/NDHWC) convs — the only layouts the graph models
_CONV_SPECS = {
    2: ((0, 3, 1, 2), (3, 2, 0, 1), (0, 3, 1, 2), "conv2d"),
    3: ((0, 4, 1, 2, 3), (4, 3, 0, 1, 2), (0, 4, 1, 2, 3), "conv3d"),
}


def _same_pads(size: int, k: int, s: int) -> tuple:
    out = -(-size // s)
    total = max((out - 1) * s + k - size, 0)
    return (total // 2, total - total // 2)


@register("conv_general_dilated")
def _conv(state, eqn, invals):
    x, w = invals
    x = _the_ref(eqn, x, "conv input")
    if not isinstance(w, ConstVal) or w.bdims is not None:
        _fail(eqn, "conv weights must be a trace-time constant")
    p = eqn.params
    dn = p["dimension_numbers"]
    nd = len(dn.lhs_spec) - 2
    spec = _CONV_SPECS.get(nd)
    if spec is None or (dn.lhs_spec, dn.rhs_spec, dn.out_spec) != spec[:3]:
        _fail(eqn, f"only channel-last layouts are supported, got "
                   f"dimension_numbers={dn}")
    op = spec[3]
    if any(d != 1 for d in p["lhs_dilation"] + p["rhs_dilation"]):
        _fail(eqn, "dilated convolutions are not supported")
    if p.get("batch_group_count", 1) != 1:
        _fail(eqn, "batch_group_count != 1 is not supported")
    groups = p.get("feature_group_count", 1)
    if op == "conv3d" and groups != 1:
        _fail(eqn, "grouped conv3d is not supported")
    strides = tuple(p["window_strides"])
    if len(set(strides)) != 1:
        _fail(eqn, f"anisotropic strides {strides} are not supported")
    stride = strides[0]
    wv = np.asarray(w.value)
    kernel = tuple(wv.shape[:nd])
    features = int(wv.shape[-1])
    spatial = state.spec(x).batched_shape[1:1 + nd]
    pads = tuple(tuple(pr) for pr in p["padding"])
    if all(pr == (0, 0) for pr in pads):
        padding = "VALID"
    elif pads == tuple(_same_pads(s, k, stride)
                       for s, k in zip(spatial, kernel)):
        padding = "SAME"
    else:
        _fail(eqn, f"explicit padding {pads} is neither SAME nor VALID "
                   f"for input {spatial}, kernel {kernel}, "
                   f"stride {stride}")
    attrs = {"kernel": kernel, "features": features, "stride": stride,
             "padding": padding}
    if groups != 1:
        attrs["groups"] = groups
    ref = state.emit(op, [x], attrs, _out_shape(eqn),
                     params={"w": w.value,
                             "b": np.zeros((features,), np.float32)})
    return [ref]


@register("dot_general")
def _dot_general(state, eqn, invals):
    x, w = invals
    x = _the_ref(eqn, x, "dot_general lhs")
    if not isinstance(w, ConstVal) or w.bdims is not None:
        _fail(eqn, "dot_general rhs (weights) must be a trace-time "
                   "constant")
    dn = eqn.params["dimension_numbers"]
    contract, batch = dn
    if (tuple(contract[0]), tuple(contract[1])) != ((1,), (0,)) or \
            any(tuple(b) for b in batch):
        _fail(eqn, f"only [batch, k] @ [k, n] matmuls are supported, got "
                   f"dimension_numbers={dn}")
    if len(state.spec(x).batched_shape) != 2:
        _fail(eqn, "dot_general lhs must be rank-2 (flatten first)")
    wv = np.asarray(w.value)
    if wv.ndim != 2:
        _fail(eqn, f"dense weights must be rank-2, got {wv.shape}")
    ref = state.emit("dense", [x],
                     {"features": int(wv.shape[1]), "bias": False},
                     _out_shape(eqn), params={"w": w.value})
    return [ref]


# ---------------------------------------------------------------------------
# elementwise binary (+ bias folding)
# ---------------------------------------------------------------------------


def _try_bias_fold(state, eqn, ref: Ref, cv: ConstVal):
    """Fold `conv/dense(x) + broadcast(b)` into the producer's bias.
    Guarded: the pre-bias tensor must have exactly one reader (this add)
    and the producer must not already carry a folded bias."""
    spec = state.spec(ref)
    if spec.op not in ("conv2d", "conv3d", "dense") or spec.bias_folded:
        return False
    if state.reads_of(eqn, ref) != 1:
        return False
    v = np.asarray(cv.value)
    if v.ndim != 1 or v.shape[0] != spec.attrs["features"]:
        return False
    rank = len(spec.batched_shape)
    if cv.bdims is not None:
        # the broadcast must place the vector on the channel (last)
        # axis; jnp ranks biases up to (1, .., 1, c), so accept any
        # target whose dims are 1 or match the producer's aval
        if tuple(cv.bdims) != (rank - 1,) or len(cv.bshape) != rank or \
                any(d not in (1, s) for d, s in
                    zip(cv.bshape, spec.batched_shape)):
            return False
    elif rank != 2:        # unbroadcast (n,) only matches a [batch, n] lhs
        return False
    spec.params["b"] = v.astype(np.float32)
    if spec.op == "dense":
        spec.attrs["bias"] = True
    spec.bias_folded = True
    return True


def _binary(graph_op: str, commutative: bool):
    def t(state, eqn, invals):
        a, b = invals
        if graph_op == "add":
            for ref, cv in ((a, b), (b, a)):
                if isinstance(ref, Ref) and isinstance(cv, ConstVal) \
                        and _try_bias_fold(state, eqn, ref, cv):
                    return [ref]
        if commutative and isinstance(b, Ref) and not isinstance(a, Ref):
            a, b = b, a
        a = _the_ref(
            eqn, a, f"{graph_op} lhs (constant-first `{graph_op}` has no "
                    "graph form)")
        out = _out_shape(eqn)
        if out != state.spec(a).batched_shape:
            _fail(eqn, f"broadcasting {graph_op} changes the lhs shape "
                       f"{state.spec(a).batched_shape} -> {out}; the "
                       f"graph `{graph_op}` op is shape-preserving")
        bref = state.as_ref(eqn, b, per_sample_rank=len(out) - 1)
        return [state.emit(graph_op, [a, bref], {}, out)]
    return t


register("add")(_binary("add", commutative=True))
register("mul")(_binary("mul", commutative=True))
register("sub")(_binary("sub", commutative=False))


@register("div")
def _div(state, eqn, invals):
    x, d = invals
    x = _the_ref(eqn, x, "div lhs")
    scalar = _const_scalar(d)
    spec = state.spec(x)
    # the avgpool peephole: reduce_window_sum staged a pending spec;
    # dividing its sole reader by k**nd is exactly the batched avgpool
    # impl (sum-then-divide), so rewrite in place
    if spec.op.startswith("_sum_pool") and scalar is not None:
        nd = int(spec.op[len("_sum_pool")])
        if scalar == float(spec.attrs["kernel"] ** nd) and \
                state.reads_of(eqn, x) == 1:
            spec.op = f"avgpool{nd}d"
            return [x]
    _fail(eqn, "div is only supported as the normalizer of a "
               "sum-window average pool (reduce_window_sum / k**nd)")


@register("max")
def _max(state, eqn, invals):
    a, b = invals
    if isinstance(b, Ref) and not isinstance(a, Ref):
        a, b = b, a
    scalar = _const_scalar(b)
    if not isinstance(a, Ref) or scalar != 0.0:
        _fail(eqn, "only max(x, 0) — ReLU — is supported")
    return [state.emit("relu", [a], {}, _out_shape(eqn))]


@register("gt")
def _gt(state, eqn, invals):
    x, t = invals
    x = _the_ref(eqn, x, "gt lhs")
    scalar = _const_scalar(t)
    if scalar is None:
        _fail(eqn, "gt threshold must be a scalar trace-time constant")
    return [state.emit("greater", [x], {"threshold": scalar},
                       _out_shape(eqn))]


# ---------------------------------------------------------------------------
# elementwise unary
# ---------------------------------------------------------------------------


def _unary(graph_op: str):
    def t(state, eqn, invals):
        x = _the_ref(eqn, invals[0], f"{graph_op} input")
        return [state.emit(graph_op, [x], {}, _out_shape(eqn))]
    return t


register("logistic")(_unary("sigmoid"))
register("tanh")(_unary("tanh"))
register("exp")(_unary("exp"))


@register("convert_element_type")
def _convert(state, eqn, invals):
    # dtype is an execution-plan concern (impls cast; `greater` already
    # emits f32) — a convert on a traced tensor is a graph no-op
    return [_the_ref(eqn, invals[0], "convert_element_type input")]


# ---------------------------------------------------------------------------
# shape ops
# ---------------------------------------------------------------------------


@register("reshape")
def _reshape(state, eqn, invals):
    x = _the_ref(eqn, invals[0], "reshape input")
    spec = state.spec(x)
    new = tuple(eqn.params["new_sizes"])
    if eqn.params.get("dimensions") is not None:
        _fail(eqn, "reshape with dimension permutation is not supported")
    if new == spec.batched_shape:
        return [x]
    per_sample = spec.batched_shape[1:]
    if new == (spec.batched_shape[0], int(np.prod(per_sample))):
        return [state.emit("flatten", [x], {}, new)]
    _fail(eqn, f"only batch-preserving flatten reshapes are supported "
               f"({spec.batched_shape} -> {new})")


@register("broadcast_in_dim")
def _broadcast(state, eqn, invals):
    (v,) = invals
    shape = tuple(eqn.params["shape"])
    bdims = tuple(eqn.params["broadcast_dimensions"])
    if isinstance(v, ConstVal):
        if v.bdims is not None:
            _fail(eqn, "chained broadcasts of one constant are not "
                       "supported")
        return [ConstVal(v.value, bdims=bdims, bshape=shape)]
    if shape == state.spec(v).batched_shape:
        return [v]
    _fail(eqn, "broadcasting a traced tensor to a new shape has no "
               "graph form")


@register("concatenate")
def _concat(state, eqn, invals):
    dim = int(eqn.params["dimension"])
    if dim == 0:
        _fail(eqn, "concatenating along the batch dimension has no "
                   "graph form")
    refs = []
    rank = None
    for v in invals:
        if isinstance(v, Ref):
            rank = len(state.spec(v).batched_shape)
            break
    if rank is None:
        _fail(eqn, "concatenate needs at least one traced operand")
    for v in invals:
        refs.append(state.as_ref(eqn, v, per_sample_rank=rank - 1))
    return [state.emit("concat", refs, {"axis": dim - 1},
                       _out_shape(eqn))]


# ---------------------------------------------------------------------------
# pooling / reductions
# ---------------------------------------------------------------------------


def _window_pool(state, eqn, invals, kind: str):
    x = _the_ref(eqn, invals[0], "pool input")
    p = eqn.params
    rank = len(state.spec(x).batched_shape)
    nd = rank - 2
    if nd not in (2, 3):
        _fail(eqn, f"only 2-D/3-D channel-last pooling is supported "
                   f"(input rank {rank})")
    window = tuple(p["window_dimensions"])
    strides = tuple(p["window_strides"])
    if window[0] != 1 or window[-1] != 1 or strides[0] != 1 or \
            strides[-1] != 1:
        _fail(eqn, f"pool window {window} / strides {strides} must not "
                   "span batch or channel dims")
    ks, ss = set(window[1:-1]), set(strides[1:-1])
    if len(ks) != 1 or len(ss) != 1:
        _fail(eqn, f"anisotropic pool window {window} / strides "
                   f"{strides} are not supported")
    if any(tuple(pr) != (0, 0) for pr in p["padding"]):
        _fail(eqn, "padded pooling is not supported (graph pools are "
                   "VALID)")
    if any(d != 1 for d in p.get("base_dilation", (1,) * rank)
           + p.get("window_dilation", (1,) * rank)):
        _fail(eqn, "dilated pooling is not supported")
    k, s = ks.pop(), ss.pop()
    attrs = {"kernel": int(k)}
    if s != k:
        attrs["stride"] = int(s)
    op = f"maxpool{nd}d" if kind == "max" else f"_sum_pool{nd}d"
    return [state.emit(op, [x], attrs, _out_shape(eqn))]


@register("reduce_window_max")
def _reduce_window_max(state, eqn, invals):
    return _window_pool(state, eqn, invals, "max")


@register("reduce_window_sum")
def _reduce_window_sum(state, eqn, invals):
    # staged: only valid once the following div rewrites it to avgpool
    # (trace.finalize rejects any leftover _sum_pool spec)
    return _window_pool(state, eqn, invals, "sum")


@register("reduce_max")
def _reduce_max(state, eqn, invals):
    x = _the_ref(eqn, invals[0], "reduce_max input")
    shape = state.spec(x).batched_shape
    axes = tuple(eqn.params["axes"])
    if len(shape) != 4 or axes != (1, 2):
        _fail(eqn, "only global spatial reduce_max over a [batch, h, w, "
                   "c] tensor is supported")
    h, w = shape[1], shape[2]
    if h != w:
        _fail(eqn, f"global reduce_max needs square spatial dims, got "
                   f"{(h, w)}")
    pooled = state.emit("maxpool2d", [x], {"kernel": int(h)},
                        (shape[0], 1, 1, shape[3]))
    return [state.emit("flatten", [pooled], {}, _out_shape(eqn))]


@register("argmax")
def _argmax(state, eqn, invals):
    x = _the_ref(eqn, invals[0], "argmax input")
    shape = state.spec(x).batched_shape
    if len(shape) != 2 or tuple(eqn.params["axes"]) != (1,):
        _fail(eqn, "only argmax over the feature axis of a [batch, n] "
                   "tensor is supported")
    return [state.emit("argmax", [x], {}, _out_shape(eqn))]


# ---------------------------------------------------------------------------
# custom front-end primitives
# ---------------------------------------------------------------------------


@register("sample_normal")
def _sample_normal(state, eqn, invals):
    mu = _the_ref(eqn, invals[0], "sample_normal mu")
    logvar = _the_ref(eqn, invals[1], "sample_normal logvar")
    return [state.emit("sample_normal", [mu, logvar], {},
                       _out_shape(eqn))]
