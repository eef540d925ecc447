"""Pallas TPU kernel: INT8 x INT8 -> INT32 matmul with fused dequant epilogue.

This is the DPU analog (DESIGN.md §6): the AMD DPU's entire value
proposition is INT8 MACs with weights resident on-chip; on TPU the MXU
runs int8 x int8 -> int32 natively at 2x bf16 throughput, and "on-chip
residency" means the weight tile lives in VMEM across the K loop.

Tiling: grid (M/bm, N/bn, K/bk), K innermost ('arbitrary' semantics) so the
int32 VMEM accumulator carries across K steps; per-row activation scales
and per-output-channel weight scales + bias + ReLU fuse into the epilogue,
so quantized inference is ONE kernel per layer — the paper's observation
that accelerator speedup comes from avoiding per-layer round-trips
(cf. Fig 11: input staging dominating compute for small HLS models).

Block defaults are MXU-aligned (128x128); VMEM working set at defaults is
bm*bk + bk*bn (int8) + bm*bn (int32) = 16KB + 16KB + 64KB << 16MB VMEM.
Dims that don't divide the tile are zero-padded up to aligned tiles (exact
for integer matmul) rather than shrinking blocks to tiny divisors.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.epilogue import (apply_epilogue, normalize_act,
                                    out_dtype_for)


def _kernel(x_ref, w_ref, xs_ref, ws_ref, b_ref, o_ref, acc_ref, *,
            n_k: int, act, requant_scale, has_bias: bool):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(pl.program_id(2) == n_k - 1)
    def _epilogue():
        out = acc_ref[...].astype(jnp.float32)
        out = out * xs_ref[...][:, None] * ws_ref[...][None, :]
        if has_bias:
            out = out + b_ref[...][None, :]
        out = apply_epilogue(out, act, requant_scale)
        o_ref[...] = out.astype(o_ref.dtype)


def _aligned_block(dim: int, target: int) -> int:
    """MXU-aligned block size: full ``target`` tiles when the dim is big
    enough, otherwise the dim rounded up to a multiple of 8 sublanes.
    Never a tiny divisor — callers pad instead (zero padding is exact for
    integer matmul)."""
    if dim >= target:
        return target
    return -(-dim // 8) * 8


def heuristic_blocks(m: int, k: int, n: int,
                     bm: int = 128, bn: int = 128, bk: int = 128):
    """The default (pre-autotune) block choice for an [M, K] x [K, N]
    int8 matmul — THE definition the autotuner's default candidate and
    the kernel's untuned path share, so ``autotune=False`` reproduces
    these blocks bit-for-bit."""
    return (min(bm, _aligned_block(m, bm)),
            min(bn, _aligned_block(n, bn)),
            min(bk, _aligned_block(k, bk)))


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "relu", "act", "requant_scale",
                     "out_dtype", "prepacked", "n_out", "interpret"))
def int8_matmul(
    x_q: jax.Array,                 # [M, K] int8
    w_q: jax.Array,                 # [K, N] int8 (tile-aligned if prepacked)
    x_scale: jax.Array,             # [M] f32 per-row
    w_scale: jax.Array,             # [N] f32 per-output-channel
    bias: Optional[jax.Array] = None,   # [N] f32
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    relu: bool = False,
    act: Optional[str] = None,      # 'relu' | 'sigmoid' epilogue
    requant_scale: Optional[float] = None,  # int8 output at this scale
    out_dtype=jnp.float32,
    prepacked: bool = False,        # w/w_scale/bias arrive tile-aligned
    n_out: Optional[int] = None,    # logical N when prepacked
    interpret: bool = False,
) -> jax.Array:
    act = normalize_act(relu, act)
    out_dtype = out_dtype_for(requant_scale, out_dtype)
    m, k = x_q.shape
    k2, n = w_q.shape
    if prepacked:
        # the weight arena already padded w (zeros), w_scale (1.0) and
        # bias (0.0) out to whole (bk, bn) tiles at plan time — only the
        # per-call activation still needs staging. bk/bn are the packed
        # layout and must divide the packed dims exactly.
        kp, np_ = k2, n
        n = np_ if n_out is None else n_out
        assert kp % bk == 0 and np_ % bn == 0, (kp, np_, bk, bn)
        assert k <= kp, (k, kp)
        bm = min(bm, _aligned_block(m, bm))
        mp = -(-m // bm) * bm
        if (mp, kp) != (m, k):
            x_q = jnp.pad(x_q, ((0, mp - m), (0, kp - k)))
            x_scale = jnp.pad(x_scale, (0, mp - m), constant_values=1.0)
    else:
        assert k == k2, (k, k2)
        bm, bn, bk = heuristic_blocks(m, k, n, bm, bn, bk)
        # pad every dim up to a whole number of aligned tiles; padded K
        # contributes exact zeros, padded M/N rows/cols are sliced below
        mp, kp, np_ = -(-m // bm) * bm, -(-k // bk) * bk, -(-n // bn) * bn
        if (mp, kp, np_) != (m, k, n):
            x_q = jnp.pad(x_q, ((0, mp - m), (0, kp - k)))
            w_q = jnp.pad(w_q, ((0, kp - k), (0, np_ - n)))
            x_scale = jnp.pad(x_scale, (0, mp - m), constant_values=1.0)
            w_scale = jnp.pad(w_scale, (0, np_ - n), constant_values=1.0)
            if bias is not None:
                bias = jnp.pad(bias, (0, np_ - n))
    n_k = kp // bk
    has_bias = bias is not None
    if bias is None:
        bias = jnp.zeros((np_,), jnp.float32)

    out = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k, act=act,
                          requant_scale=requant_scale, has_bias=has_bias),
        grid=(mp // bm, np_ // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, h: (i, h)),
            pl.BlockSpec((bk, bn), lambda i, j, h: (h, j)),
            pl.BlockSpec((bm,), lambda i, j, h: (i,)),
            pl.BlockSpec((bn,), lambda i, j, h: (j,)),
            pl.BlockSpec((bn,), lambda i, j, h: (j,)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, h: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x_q, w_q, x_scale, w_scale, bias)
    if (mp, np_) != (m, n):
        out = out[:m, :n]
    return out
