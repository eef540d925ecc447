"""Pallas TPU kernel: fused symmetric INT8 quantization (PTQ inner loop).

Two-phase: scales come from an XLA reduction (absmax is bandwidth-bound and
XLA already emits an optimal reduce); the Pallas kernel fuses
scale-broadcast + round + clip + cast in one pass so the fp32 tensor is
read exactly once and only int8 is written back — the 4x HBM-write saving
is the point (cf. the paper's PTQ step, where quantization cost is amortized
offline but on-line requantization of activations is per-inference).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _block(n: int, target: int) -> int:
    """The whole dim when it fits in one block, else ``target``: Mosaic
    takes a block dim that is either full or tile-aligned (the row target
    a multiple of int8's 32-row tile, the column target of 128 lanes)."""
    return n if n <= target else target


def _kernel(x_ref, s_ref, q_ref):
    x = x_ref[...].astype(jnp.float32)
    inv = 1.0 / s_ref[...]                       # [bn] per-channel
    q = jnp.round(x * inv[None, :])
    q_ref[...] = jnp.clip(q, -127, 127).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def quantize_apply(
    x: jax.Array,                   # [M, N] float
    scale: jax.Array,               # [N] f32 per-channel (axis=0 reduced)
    *,
    bm: int = 256,
    bn: int = 256,
    interpret: bool = False,
) -> jax.Array:
    assert bm % 32 == 0 and bn % 128 == 0, (bm, bn)
    m, n = x.shape
    bm, bn = _block(m, bm), _block(n, bn)
    # a ragged dim pads up to whole blocks: zero rows/columns, unit scales
    mp, np_ = -(-m // bm) * bm, -(-n // bn) * bn
    if (mp, np_) != (m, n):
        x = jnp.pad(x, ((0, mp - m), (0, np_ - n)))
        scale = jnp.pad(scale, (0, np_ - n), constant_values=1.0)
    q = pl.pallas_call(
        _kernel,
        grid=(mp // bm, np_ // bn),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bn,), lambda i, j: (j,)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x, scale)
    return q[:m, :n] if (mp, np_) != (m, n) else q


def quantize(x: jax.Array, axis: Optional[int] = 0,
             interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-channel (or per-tensor) INT8. Returns (q, scale)."""
    xf = x.astype(jnp.float32)
    if axis is None:
        scale = jnp.max(jnp.abs(xf)) / 127.0 + 1e-12
        q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
        return q, scale
    assert x.ndim == 2 and axis == 0, "kernel path: 2-D, per-column scales"
    scale = jnp.max(jnp.abs(xf), axis=0) / 127.0 + 1e-12
    return quantize_apply(xf, scale, interpret=interpret), scale
