"""Plain float32 references of the served models, independent of the
program: each module builds its model's weights and inputs from a key
and computes the forward pass in straightforward ``jax.numpy`` at
``highest`` matmul precision. Sizes come from the configuration file.

A module defines ``layers(cfg)`` (the conv and dense layers with their
shapes, for the work counts), ``init(cfg, key)``, ``inputs(cfg, key, n)``
and ``forward(cfg, params, batch, quant=None)``. ``quant`` turns the
forward pass into the lower-precision control: ``Quant(bits, absmax)``
rounds every conv/dense weight per output channel and every conv/dense
input per tensor (at its calibration absmax) to ``bits``-bit integers.
"""
from __future__ import annotations

import importlib
from typing import Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


class Quant(NamedTuple):
    bits: int
    absmax: Dict[str, float]        # layer name -> input absmax


class Layer(NamedTuple):
    """One conv or dense layer at one sample: input [H, W, Cin] (dense:
    H = W = 1, Cin = K), output [H_out, W_out, Cout]."""
    name: str
    kind: str                       # 'conv' | 'dense'
    h: int
    w: int
    cin: int
    cout: int
    k: int                          # kernel size (1 for dense)
    stride: int
    h_out: int
    w_out: int
    out_is_model_output: bool


def module(cfg: Dict):
    return importlib.import_module(f"bench.reference.{cfg['reference']}")


def init_params(layers: List[Layer], key: jax.Array
                ) -> Dict[str, Dict[str, jax.Array]]:
    """He-normal conv and LeCun-normal dense weights, small random
    biases, float32."""
    params = {}
    for layer in layers:
        key, kw, kb = jax.random.split(key, 3)
        if layer.kind == "conv":
            fan_in = layer.k * layer.k * layer.cin
            shape = (layer.k, layer.k, layer.cin, layer.cout)
            std = (2.0 / fan_in) ** 0.5
        else:
            shape, std = (layer.cin, layer.cout), (1.0 / layer.cin) ** 0.5
        params[layer.name] = {
            "w": jax.random.normal(kw, shape, jnp.float32) * std,
            "b": jax.random.normal(kb, (layer.cout,), jnp.float32) * 0.01}
    return params


def _qmax(bits: int) -> float:
    return float(2 ** (bits - 1) - 1)


def quant_weight(w: jax.Array, bits: int) -> jax.Array:
    """Symmetric per-output-channel (last axis) rounding to ``bits``."""
    w2 = w.reshape(-1, w.shape[-1])
    scale = jnp.max(jnp.abs(w2), axis=0) / _qmax(bits) + 1e-12
    return (jnp.clip(jnp.round(w2 / scale), -_qmax(bits), _qmax(bits))
            * scale).reshape(w.shape)


def quant_act(x: jax.Array, absmax: float, bits: int) -> jax.Array:
    """Symmetric per-tensor rounding to ``bits`` at a static absmax."""
    scale = absmax / _qmax(bits) + 1e-12
    return jnp.clip(jnp.round(x / scale), -_qmax(bits), _qmax(bits)) * scale


def layer_io(name: str, x: jax.Array, w: jax.Array,
             quant: Optional[Quant], record: Optional[Dict]):
    """The input and weight one conv/dense layer computes with: as given,
    or rounded for the control; ``record`` collects input absmax."""
    if record is not None:
        record[name] = max(record.get(name, 0.0),
                           float(jnp.max(jnp.abs(x))))
    if quant is None:
        return x, w
    return (quant_act(x, quant.absmax[name], quant.bits),
            quant_weight(w, quant.bits))


def conv(x: jax.Array, w: jax.Array, b: jax.Array, stride: int
         ) -> jax.Array:
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST) + b


def dense(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.matmul(x, w, precision=HIGHEST) + b


def same_out(n: int, stride: int) -> int:
    return -(-n // stride)
