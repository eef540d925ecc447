"""Operations and bytes of the served layers, from the configuration's
shapes alone, so that a roofline share means the same whatever kernel
implements a layer.

Per call of a conv or dense layer over ``n`` real (non-padding) samples:

* ops = 2 x MAC of the layer's logical shapes (SAME convolution over the
  whole output, no lane or batch padding) x ``n``;
* bytes = the int8 input and output of each sample, the int8 weights,
  and a float32 scale and bias per output channel; an output that is a
  model output is float32 (it leaves the model), every other output
  feeds an int8 layer and counts one byte an element.

The least time of a call on a chip is the larger of ops over its int8
peak and bytes over its HBM bandwidth.
"""
from __future__ import annotations

import json
import os
from typing import Dict, List

from bench.reference import Layer

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {PEAKS_FILE}")
    return table[device_kind]


def macs(layer: Layer) -> int:
    """Multiply-accumulates of one sample."""
    return (layer.h_out * layer.w_out * layer.cout
            * layer.k * layer.k * layer.cin)


def ops(layer: Layer, n: int) -> float:
    return 2.0 * macs(layer) * n


def bytes_moved(layer: Layer, n: int) -> float:
    out_item = 4 if layer.out_is_model_output else 1
    per_sample = (layer.h * layer.w * layer.cin
                  + layer.h_out * layer.w_out * layer.cout * out_item)
    weights = layer.k * layer.k * layer.cin * layer.cout + 8 * layer.cout
    return float(per_sample * n + weights)


def least_seconds(layer: Layer, n: int, peak: Dict[str, float]) -> float:
    return max(ops(layer, n) / peak["int8_ops_per_s"],
               bytes_moved(layer, n) / peak["hbm_bytes_per_s"])


def model_ops(layers: List[Layer]) -> float:
    """2 x MAC of one sample over every conv and dense layer."""
    return sum(ops(layer, 1) for layer in layers)
