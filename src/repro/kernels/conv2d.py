"""Pallas TPU kernel: NHWC int8 conv2d as shift-and-matmul (the DPU conv
engine).

TPU adaptation of the DPU's convolution array: instead of a systolic
line-buffer (FPGA idiom), each grid step loads the input row window that
feeds ``rows`` output rows into VMEM and accumulates, per output row,
KH*KW shifted [W_out, Cin] x [Cin, Cout] int8 matmuls in int32 on the
MXU — im2col without ever materializing the patch matrix in HBM.
Dequant, bias, activation and the optional int8 requant fuse into the
epilogue; the grid blocks ``rows_per_block`` output rows per step so
small feature maps don't drown in grid overhead.

Input staging (:func:`pad_input`, done once per batch and at plan time
for the prepacked plans) applies the SAME/VALID pad and, for stride
s > 1, splits the columns into s phases stored one after another along
W: padded column ``j*s + p`` lands at ``p*W_pad/s + j``. Tap ``c`` of
every output column then reads one contiguous run of phase ``c % s``,
so the kernel never takes a strided slice of a vector (Mosaic lowers
only unit-stride sublane slices). Rows stay in order; the row stride is
a plain dynamic index on the untiled H axis.

The input block is one row window of ``(rows - 1) * stride + KH`` rows,
element-indexed over H so neighbouring windows share their halo rows
without an HBM copy. The whole image never sits in VMEM: CNet's
256x256x2 int8 input alone, padded to 128 lanes and double-buffered,
exceeds the scoped VMEM limit. :func:`conv_vmem_bytes` estimates a
configuration's working set for the autotuner's candidate filter.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.epilogue import (apply_epilogue, normalize_act,
                                    out_dtype_for, pad_channel_params)

DEFAULT_CONV_ROWS = 8           # output rows per grid step, untuned
SCOPED_VMEM_BYTES = 16 * 2 ** 20       # TPU v5e default scoped VMEM limit
# headroom for the compiler's own scratch: the autotuner offers no
# blocking whose estimate exceeds this budget
VMEM_BUDGET_BYTES = SCOPED_VMEM_BYTES * 3 // 4


class ConvGeom(NamedTuple):
    """Plan-time conv pad geometry: everything the SAME/VALID staging and
    the grid blocking need, derived purely from static shapes — so a
    lowering computes it ONCE (cached) and steady-state serving never
    re-derives pad amounts for identically-shaped batches."""
    h_out: int
    w_out: int
    rows: int                       # output rows per grid step
    n_row_blocks: int
    pad_top: int
    pad_bottom: int                 # includes row-block coverage padding
    pad_left: int
    pad_right: int                  # includes rounding W_pad to the stride
    h_pad: int                      # padded input dims the kernel expects
    w_pad: int
    stride: int
    window: int                     # input rows one grid step reads


@functools.lru_cache(maxsize=None)
def conv_geometry(h: int, wd: int, kh: int, kw: int, stride: int,
                  padding: str, rows_per_block: int = 1) -> ConvGeom:
    """SAME/VALID geometry shared by the kernel and the plan's input
    staging: the image is extended so every row window the grid touches
    — including rows padded out to a whole number of ``rows_per_block``
    blocks — is in range, and W_pad is a whole number of stride phases.
    Zero padding is exact for int8 accumulation. Pure function of static
    shapes, memoized."""
    if padding == "SAME":
        h_out = -(-h // stride)
        w_out = -(-wd // stride)
        pad_h = max((h_out - 1) * stride + kh - h, 0)
        pad_w = max((w_out - 1) * stride + kw - wd, 0)
        top, left = pad_h // 2, pad_w // 2
        bottom, right = pad_h - top, pad_w - left
    elif padding == "VALID":
        h_out = (h - kh) // stride + 1
        w_out = (wd - kw) // stride + 1
        top = bottom = left = right = 0
    else:
        raise ValueError(padding)
    rows = min(rows_per_block, h_out)
    n_row_blocks = -(-h_out // rows)
    need_h = (n_row_blocks * rows - 1) * stride + kh
    need_w = (w_out - 1) * stride + kw
    bottom += max(need_h - (h + top + bottom), 0)
    right += max(need_w - (wd + left + right), 0)
    right += -(wd + left + right) % stride
    return ConvGeom(h_out, w_out, rows, n_row_blocks, top, bottom, left,
                    right, h + top + bottom, wd + left + right, stride,
                    (rows - 1) * stride + kh)


def pad_input(x: jax.Array, g: ConvGeom) -> jax.Array:
    """Apply a plan-time :class:`ConvGeom` to one [B, H, W, C] batch —
    the single input-staging step (pad, then the stride-phase column
    split) both the kernels and the prepacked plan path share."""
    if (g.pad_top, g.pad_bottom, g.pad_left, g.pad_right) != (0, 0, 0, 0):
        x = jnp.pad(x, ((0, 0), (g.pad_top, g.pad_bottom),
                        (g.pad_left, g.pad_right), (0, 0)))
    if g.stride > 1:
        b, hp, wp, c = x.shape
        x = x.reshape(b, hp, wp // g.stride, g.stride, c)
        x = x.swapaxes(2, 3).reshape(b, hp, wp, c)
    return x


def block_bytes(shape: Tuple[int, ...], itemsize: int) -> int:
    """Bytes one VMEM buffer of ``shape`` occupies in Mosaic's tiled
    layout: the last dim padded to 128 lanes, the second-to-last to the
    dtype's sublane tile (8 rows of 32-bit words, so 32 for int8)."""
    shape = (1,) * max(2 - len(shape), 0) + tuple(shape)
    sublanes = 8 * 4 // itemsize
    lead = math.prod(shape[:-2])
    return (lead * -(-shape[-2] // sublanes) * sublanes
            * -(-shape[-1] // 128) * 128 * itemsize)


def conv_vmem_bytes(g: ConvGeom, kh: int, kw: int, cin: int, bc: int,
                    out_itemsize: int = 4) -> int:
    """Scoped-VMEM working set of one conv grid step: the double-buffered
    int8 row window, weight slice, scale/bias and output tile, plus the
    int32 row accumulator."""
    blocks = (block_bytes((g.window, g.w_pad, cin), 1)
              + block_bytes((kh, kw, cin, bc), 1)
              + 2 * block_bytes((bc,), 4)
              + block_bytes((g.rows, g.w_out, bc), out_itemsize))
    return 2 * blocks + block_bytes((g.w_out, bc), 4)


def _x_spec(g: ConvGeom, cin: int) -> pl.BlockSpec:
    """The row-window input block of grid step (sample, row block, ...):
    element offsets on every axis, overlapping windows on H."""
    return pl.BlockSpec(
        (pl.Element(1), pl.Element(g.window), pl.Element(g.w_pad),
         pl.Element(cin)),
        lambda bi, ri, *_: (bi, ri * g.rows * g.stride, 0, 0))


def _row_acc(x_ref, w_ref, rr, *, w_out: int, stride: int):
    """int32 accumulator of output row ``rr`` of the window: KH*KW
    shifted [w_out, Cin] x [Cin, Cout] int8 matmuls over phase-major
    input rows."""
    kh, kw, _, cout = w_ref.shape
    phase_w = x_ref.shape[2] // stride
    acc = jnp.zeros((w_out, cout), jnp.int32)
    for r in range(kh):
        row = x_ref[0, rr * stride + r]                  # [W_pad, Cin]
        for c in range(kw):
            start = (c % stride) * phase_w + c // stride
            acc += jax.lax.dot_general(
                row[start:start + w_out], w_ref[r, c],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)
    return acc


def _kernel_int8(x_ref, w_ref, ws_ref, b_ref, o_ref, *, w_out: int,
                 stride: int, x_scale: float, act, requant_scale,
                 has_bias: bool):
    # x_ref block: [1, window, W_pad, Cin] int8; o_ref block: [1, rows,
    # W_out, Cout] f32 — or int8 when the fused epilogue re-quantizes for
    # the next layer (requant_scale set).
    dequant = ws_ref[...] * jnp.float32(x_scale)         # [Cout]

    def row(rr, carry):
        acc = _row_acc(x_ref, w_ref, rr, w_out=w_out, stride=stride)
        out = acc.astype(jnp.float32) * dequant[None, :]
        if has_bias:
            out = out + b_ref[...][None, :]
        out = apply_epilogue(out, act, requant_scale)
        o_ref[0, rr] = out.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, o_ref.shape[1], row, 0)


@functools.partial(jax.jit, static_argnames=(
    "x_scale", "stride", "padding", "relu", "act", "requant_scale",
    "rows_per_block", "cout_per_block", "cout", "pre_padded", "in_hw",
    "interpret"))
def conv2d_int8(
    x_q: jax.Array,                 # [B, H, W, Cin] int8
    w_q: jax.Array,                 # [KH, KW, Cin, Cout(_pad)] int8
    w_scale: jax.Array,             # [Cout(_pad)] f32 per-output-channel
    bias: Optional[jax.Array] = None,   # [Cout(_pad)] f32
    *,
    x_scale: float = 1.0,           # static per-tensor activation scale
    stride: int = 1,
    padding: str = "SAME",
    relu: bool = False,
    act: Optional[str] = None,      # 'relu' | 'sigmoid' epilogue
    requant_scale: Optional[float] = None,  # int8 output at this scale
    rows_per_block: int = DEFAULT_CONV_ROWS,
    cout_per_block: int = 0,        # 0 = no channel tiling (whole Cout)
    cout: Optional[int] = None,     # logical Cout when w arrives padded
    pre_padded: bool = False,       # x already staged per conv_geometry
    in_hw: Optional[Tuple[int, int]] = None,  # logical (H, W), pre_padded
    interpret: bool = False,
) -> jax.Array:
    """Quantized conv: ``deq(conv_int32(x_q, w_q))`` with fused epilogue.

    ``x_scale`` is folded at plan time (PTQ calibration absmax / 127), so
    the whole layer is one kernel launch — no per-sample HBM im2col and no
    dynamic scale reduction on the critical path. With ``requant_scale``
    the epilogue re-quantizes the result to int8 for the next quantized
    layer (the graph compiler's producer->consumer fusion): the fp32
    activation never leaves the kernel.

    Tiling/prepack hooks (DESIGN.md §11 — all bit-exact vs the default
    path, since channel blocks are independent and zero pad rows/channels
    are sliced off):

    * ``cout_per_block`` tiles the output-channel dim: the grid gains a
      channel-block axis and each step holds only a [KH, KW, Cin, bc]
      weight slice in VMEM. Cout is zero-padded up to whole blocks
      (neutral scale/bias on pad channels; prepacked callers arrive
      aligned, with the logical ``cout`` passed separately).
    * ``pre_padded`` skips the kernel's own input staging: the caller
      already applied :func:`conv_geometry`/:func:`pad_input` at plan
      time (the prepacked plans' staging step) and passes the logical
      ``in_hw`` so the geometry can be re-derived from the cache.
    """
    act = normalize_act(relu, act)
    b, _, _, cin = x_q.shape
    kh, kw, _, cout_pad = w_q.shape
    cout = cout_pad if cout is None else cout
    if pre_padded:
        if in_hw is None:
            raise ValueError("pre_padded=True needs in_hw=(H, W)")
        g = conv_geometry(in_hw[0], in_hw[1], kh, kw, stride, padding,
                          rows_per_block)
        if x_q.shape[1:3] != (g.h_pad, g.w_pad):
            raise ValueError(
                f"pre-padded input {x_q.shape} does not match geometry "
                f"({g.h_pad}, {g.w_pad})")
    else:
        g = conv_geometry(x_q.shape[1], x_q.shape[2], kh, kw, stride,
                          padding, rows_per_block)
        x_q = pad_input(x_q, g)
    h_out, w_out = g.h_out, g.w_out
    rows, n_row_blocks = g.rows, g.n_row_blocks
    h_out_pad = n_row_blocks * rows
    bc = cout_per_block or cout_pad
    if cout_pad % bc:
        pad_c = -(-cout_pad // bc) * bc - cout_pad
        w_q = jnp.pad(w_q, ((0, 0), (0, 0), (0, 0), (0, pad_c)))
        w_scale, bias = pad_channel_params(w_scale, bias, pad_c)
        cout_pad += pad_c
    has_bias = bias is not None
    if bias is None:
        bias = jnp.zeros((cout_pad,), jnp.float32)

    kernel = functools.partial(
        _kernel_int8, w_out=w_out, stride=stride, x_scale=float(x_scale),
        act=act, requant_scale=requant_scale, has_bias=has_bias)
    out = pl.pallas_call(
        kernel,
        grid=(b, n_row_blocks, cout_pad // bc),
        in_specs=[
            _x_spec(g, cin),
            pl.BlockSpec((kh, kw, cin, bc),
                         lambda bi, ri, ci: (0, 0, 0, ci)),
            pl.BlockSpec((bc,), lambda bi, ri, ci: (ci,)),
            pl.BlockSpec((bc,), lambda bi, ri, ci: (ci,)),
        ],
        out_specs=pl.BlockSpec((1, rows, w_out, bc),
                               lambda bi, ri, ci: (bi, ri, 0, ci)),
        out_shape=jax.ShapeDtypeStruct((b, h_out_pad, w_out, cout_pad),
                                       out_dtype_for(requant_scale)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(x_q, w_q, w_scale, bias)
    if h_out_pad != h_out or cout_pad != cout:
        out = out[:, :h_out, :, :cout]
    return out
