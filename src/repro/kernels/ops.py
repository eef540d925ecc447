"""Public jit'd kernel API.

Every call site goes through here, and here alone is decided how a
Pallas kernel runs: compiled by Mosaic when the computation is placed on
a TPU, in the Pallas interpreter when it is placed on the CPU (the
tests, and the host-side reference a chip run compares against). Any
other platform raises: a kernel never quietly runs emulated on a machine
whose accelerator failed to come up. The platform is that of the default
device (``jax.default_device``) when one is set, else of the default
backend.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import conv2d as _conv2d
from repro.kernels import flash_attention as _flash
from repro.kernels import int8_matmul as _int8mm
from repro.kernels import quantize as _quant
from repro.kernels import ssd as _ssd


def platform() -> str:
    """The platform the next traced computation is placed on."""
    device = jax.config.jax_default_device
    if device is None:
        return jax.default_backend()
    return device if isinstance(device, str) else device.platform


def on_tpu() -> bool:
    return platform() == "tpu"


def _interp() -> bool:
    p = platform()
    if p not in ("cpu", "tpu"):
        raise RuntimeError(
            f"Pallas kernels run compiled on 'tpu' or interpreted on "
            f"'cpu'; the computation is placed on {p!r}")
    return p == "cpu"


def int8_matmul(x_q, w_q, x_scale, w_scale, bias=None, *, relu=False,
                act=None, requant_scale=None, out_dtype=jnp.float32,
                prepacked=False, n_out=None, **tiles):
    return _int8mm.int8_matmul(x_q, w_q, x_scale, w_scale, bias, relu=relu,
                               act=act, requant_scale=requant_scale,
                               out_dtype=out_dtype, prepacked=prepacked,
                               n_out=n_out, interpret=_interp(),
                               **tiles)


def conv2d_int8(x_q, w_q, w_scale, bias=None, *, x_scale=1.0, stride=1,
                padding="SAME", relu=False, act=None, requant_scale=None,
                rows_per_block=8, cout_per_block=0, cout=None,
                pre_padded=False, in_hw=None):
    return _conv2d.conv2d_int8(x_q, w_q, w_scale, bias, x_scale=x_scale,
                               stride=stride, padding=padding, relu=relu,
                               act=act, requant_scale=requant_scale,
                               rows_per_block=rows_per_block,
                               cout_per_block=cout_per_block, cout=cout,
                               pre_padded=pre_padded, in_hw=in_hw,
                               interpret=_interp())


def flash_attention(q, k, v, *, causal=True, bq=256, bk=256):
    return _flash.flash_attention(q, k, v, causal=causal, bq=bq, bk=bk,
                                  interpret=_interp())


def ssd(x, B_, C_, dt, A, init_state=None, *, chunk: int = 256):
    return _ssd.ssd(x, B_, C_, dt, A, init_state, chunk=chunk,
                    interpret=_interp())


def quantize(x, axis: Optional[int] = 0):
    return _quant.quantize(x, axis=axis, interpret=_interp())


def dequantize(q, scale, axis: Optional[int] = 0, dtype=jnp.float32):
    from repro.kernels.ref import dequantize_ref
    return dequantize_ref(q, scale, axis=axis, dtype=dtype)
