"""Logical-axis sharding: model code names axes, the mesh maps them.

Model code never mentions mesh axes directly. Every tensor dimension gets a
*logical* name ('batch', 'seq', 'heads', 'ffn', ...); a rule table maps
logical names to mesh axes; and :func:`spec_for` resolves the mapping with
a divisibility fallback (a dim that cannot be evenly split over the mapped
mesh axes is replicated instead — this is what makes decode shapes with
seq=1 or batch=1 'just work' on the production mesh).

The active (mesh, rules) pair is installed with :func:`use_mesh`, a context
manager set up by the launcher / dry-run; when no context is active,
:func:`constrain` is a no-op, so unit tests on one CPU device run the same
model code unchanged.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# ---------------------------------------------------------------------------
# Rule tables
# ---------------------------------------------------------------------------

# Logical axis -> tuple of mesh axes (tried in order, greedily).
# 'data' doubles as the FSDP axis for weights; 'model' is the TP axis;
# 'pod' is the cross-pod DP axis.
SINGLE_POD_RULES = {
    # activations
    "batch": ("data",),
    "seq": ("model",),            # sequence parallelism between blocks
    "embed": (),                  # residual feature dim stays unsharded
    # attention
    "heads": ("model",),
    "kv_heads": ("model",),
    "head_dim": (),
    # mlp / experts
    "ffn": ("model",),
    "expert": ("model",),
    "expert_ffn": ("data",),      # second-level expert sharding (256-way EP)
    "expert_cap": ("data",),      # dispatch-buffer capacity dim
    # embeddings / head
    "vocab": ("model",),
    "fsdp": ("data",),            # ZeRO-style weight/optimizer sharding
    # ssm
    "ssm_heads": ("model",),
    "ssm_state": (),
    "conv_dim": ("model",),
}

MULTI_POD_RULES = dict(SINGLE_POD_RULES)
MULTI_POD_RULES.update({
    "batch": ("pod", "data"),
    "fsdp": ("data",),            # keep FSDP intra-pod; pods replicate weights
})


def rules_for(mesh: Mesh) -> dict:
    return MULTI_POD_RULES if "pod" in mesh.axis_names else SINGLE_POD_RULES


def serving_rules(mesh: Mesh) -> dict:
    """Inference sharding: ZeRO/FSDP weight sharding is wrong for decode —
    it re-all-gathers every weight every step (measured 9 GB/device/step on
    yi-34b decode_32k; same pathology on the experts' second-level
    'expert_ffn' axis for llama4-scout). Serving replicates weights over
    the data axis and keeps TP/EP over 'model' (§Perf B1'). NB: only when
    the replicated weights fit HBM — llama4-maverick's 403B routed experts
    do not; its decode cell keeps the sharded layout (EXPERIMENTS.md
    §Perf fleet notes)."""
    rules = dict(rules_for(mesh))
    rules["fsdp"] = ()
    rules["expert_ffn"] = ()
    return rules


# ---------------------------------------------------------------------------
# Resolution
# ---------------------------------------------------------------------------


def _axis_size(mesh: Mesh, names: Sequence[str]) -> int:
    size = 1
    for n in names:
        size *= mesh.shape[n]
    return size


def spec_for(
    shape: Sequence[int],
    logical: Sequence[Optional[str]],
    mesh: Mesh,
    rules: Optional[dict] = None,
) -> P:
    """Resolve logical names to a PartitionSpec with divisibility fallback.

    For each dim, the mapped mesh-axis tuple is trimmed from the right until
    the dim size divides the product of the remaining axes (so 'batch' ->
    ('pod','data') falls back to ('pod',) and then to replication). Mesh
    axes already consumed by an earlier dim are skipped — PartitionSpec
    forbids reuse.
    """
    rules = rules or rules_for(mesh)
    if len(shape) != len(logical):
        raise ValueError(f"shape {shape} vs logical {logical} rank mismatch")
    used: set = set()
    out = []
    for dim, name in zip(shape, logical):
        if name is None:
            out.append(None)
            continue
        axes = tuple(a for a in rules.get(name, ()) if a not in used)
        while axes and (dim % _axis_size(mesh, axes) != 0):
            axes = axes[:-1]
        if not axes:
            out.append(None)
        elif len(axes) == 1:
            out.append(axes[0])
            used.add(axes[0])
        else:
            out.append(axes)
            used.update(axes)
    return P(*out)


def sharding_for(shape, logical, mesh, rules=None) -> NamedSharding:
    return NamedSharding(mesh, spec_for(shape, logical, mesh, rules))


# ---------------------------------------------------------------------------
# Context
# ---------------------------------------------------------------------------

_state = threading.local()


def current_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


def current_rules() -> Optional[dict]:
    return getattr(_state, "rules", None)


@contextlib.contextmanager
def use_mesh(mesh: Mesh, rules: Optional[dict] = None):
    """Install (mesh, rules) so :func:`constrain` becomes active."""
    prev = (current_mesh(), current_rules())
    _state.mesh = mesh
    _state.rules = rules or rules_for(mesh)
    try:
        with mesh:
            yield mesh
    finally:
        _state.mesh, _state.rules = prev


def constrain(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """Apply a sharding constraint by logical axis names (no-op w/o mesh)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = spec_for(x.shape, logical, mesh, current_rules())
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Pytree helpers (params <-> shardings)
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# Explicit sequence-parallel collectives (§Perf iteration A3)
#
# Relying on the SPMD partitioner for the SP<->TP transitions leaves two
# costs on the table (measured on llama4-scout prefill_32k):
#   * the partitioner's all-reduce/all-gather get promoted/elided to f32
#     (2x wire bytes vs the bf16 values), and
#   * TP output projections stay all-reduce (+dynamic-slice) instead of
#     reduce-scatter (another 2x on the wire).
# These helpers pin both: bf16 all_gather on the way in, einsum +
# psum_scatter fused in one shard_map on the way out — Megatron-SP,
# explicitly. They fall back to plain constraints whenever the mesh/shape
# cannot support them (decode s=1, unit tests without a mesh, tp=1).
# ---------------------------------------------------------------------------


def _sp_ready(mesh, seq: int, *dims_mod_model: int) -> bool:
    if mesh is None or "model" not in mesh.axis_names:
        return False
    tp = mesh.shape["model"]
    if tp == 1 or seq % tp:
        return False
    return all(d % tp == 0 for d in dims_mod_model)


def sp_gather_seq(x: jax.Array, batch_logical: str = "batch") -> jax.Array:
    """[B, s/tp, D] seq-sharded -> [B, S, D] gathered, explicit bf16 wire."""
    mesh = current_mesh()
    if not _sp_ready(mesh, x.shape[1]):
        return constrain(x, batch_logical, None, None) if mesh is not None else x
    rules = current_rules()
    in_spec = spec_for(x.shape, (batch_logical, "seq", None), mesh, rules)
    out_spec = spec_for(x.shape, (batch_logical, None, None), mesh, rules)
    if "model" not in jax.tree.leaves(tuple(in_spec)):
        return constrain(x, batch_logical, None, None)

    def f(xb):
        # bitcast bf16 -> u16 around the gather pins the wire dtype: the
        # CPU backend otherwise upcasts bf16 math to f32 and hoists the
        # convert across the collective, doubling the *reported* (and, on
        # CPU, actual) wire bytes. On TPU this is a free bitcast.
        if xb.dtype == jnp.bfloat16:
            g = jax.lax.all_gather(
                jax.lax.bitcast_convert_type(xb, jnp.uint16),
                "model", axis=1, tiled=True)
            return jax.lax.bitcast_convert_type(g, jnp.bfloat16)
        return jax.lax.all_gather(xb, "model", axis=1, tiled=True)

    return jax.shard_map(f, mesh=mesh, in_specs=(in_spec,),
                         out_specs=out_spec, check_vma=False)(x)


def tp_proj_scatter(inp: jax.Array, w: jax.Array, subscripts: str,
                    inp_logical: Tuple, w_sharded_dim: int = 0) -> jax.Array:
    """``einsum(subscripts, inp, w)`` whose contraction runs over the
    model-sharded dim of ``w``; the partial result is psum_scatter'd onto
    the seq dim (axis 1) in ONE shard_map — reduce-scatter on the wire.

    inp: [B, S, ...] with the contracted dim model-sharded; w's
    ``w_sharded_dim`` is viewed P('model') (other dims replicated — jit
    gathers them, cheap for weight matrices)."""
    mesh = current_mesh()
    contracted = inp.shape[-1] if inp.ndim == 3 else inp.shape[2]
    if not _sp_ready(mesh, inp.shape[1], contracted):
        y = jnp.einsum(subscripts, inp, w)
        return constrain(y, "batch", "seq", None) if mesh is not None else y
    rules = current_rules()
    in_spec = spec_for(inp.shape, inp_logical, mesh, rules)
    w_spec = P(*[("model" if i == w_sharded_dim else None)
                 for i in range(w.ndim)])
    out_shape = jax.eval_shape(lambda a, b: jnp.einsum(subscripts, a, b),
                               inp, w).shape
    y_spec = spec_for(out_shape, ("batch", "seq", None), mesh, rules)

    def f(i_blk, w_blk):
        y = jnp.einsum(subscripts, i_blk, w_blk)
        return jax.lax.psum_scatter(y, "model", scatter_dimension=1,
                                    tiled=True)

    return jax.shard_map(f, mesh=mesh, in_specs=(in_spec, w_spec),
                         out_specs=y_spec, check_vma=False)(inp, w)


def is_logical_leaf(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x)


def tree_shardings(tree_shapes, tree_logical, mesh, rules=None):
    """Map matching pytrees of shapes (or ShapeDtypeStructs) and logical-axis
    tuples to a pytree of NamedShardings.

    Traverses the *logical* tree (whose leaves are axis-name tuples) so the
    shape tree's array/ShapeDtypeStruct leaves line up 1:1.
    """
    rules = rules or rules_for(mesh)

    def one(names, shape_like):
        shape = getattr(shape_like, "shape", shape_like)
        return sharding_for(shape, names, mesh, rules)

    return jax.tree.map(one, tree_logical, tree_shapes, is_leaf=is_logical_leaf)


def pad_to_multiple(n: int, m: int) -> int:
    return int(np.ceil(n / m) * m)
