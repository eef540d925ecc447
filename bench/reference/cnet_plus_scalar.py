"""CNet+scalar (X-ray flux regression from a 2-channel solar image plus
the preceding background flux; the paper's Table I row, ReLU as served
on the accelerator): three 3x3 SAME conv + ReLU + 2x2 max-pool stages,
flatten, the scalar appended, a ReLU dense layer and a one-unit head."""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from bench.reference import (Layer, Quant, conv, dense, init_params,
                             layer_io)


def layers(cfg: Dict) -> List[Layer]:
    h, w, c = cfg["input_shape"]
    k, out = cfg["kernel"], []
    for i, f in enumerate(cfg["channels"]):
        out.append(Layer(f"conv{i}", "conv", h, w, c, f, k, 1, h, w, False))
        h, w, c = h // cfg["pool"], w // cfg["pool"], f
    fin = h * w * c + 1
    out.append(Layer("fc1", "dense", 1, 1, fin, cfg["dense"], 1, 1, 1, 1,
                     False))
    out.append(Layer("head", "dense", 1, 1, cfg["dense"], 1, 1, 1, 1, 1,
                     True))
    return out


def init(cfg: Dict, key: jax.Array) -> Dict[str, Dict[str, jax.Array]]:
    return init_params(layers(cfg), key)


def _one_input(cfg: Dict, key: jax.Array) -> Dict[str, jax.Array]:
    """A full-disk image pair: an HMI-like signed magnetogram and an
    AIA-like limb-brightened disk, plus a background flux level."""
    k1, k2, k3 = jax.random.split(key, 3)
    h, w, _ = cfg["input_shape"]
    yy, xx = jnp.mgrid[0:h, 0:w]
    r2 = ((yy - h / 2) / (h / 2)) ** 2 + ((xx - w / 2) / (w / 2)) ** 2
    disk = (r2 < 0.9).astype(jnp.float32)
    hmi = disk * jax.random.normal(k1, (h, w)) * 0.3
    aia = disk * jnp.exp(-3.0 * r2) + 0.02 * jax.random.normal(k2, (h, w))
    return {"image": jnp.stack([hmi, aia], axis=-1),
            "background_flux": jax.random.uniform(k3, (1,), jnp.float32,
                                                  1.0, 5.0)}


def inputs(cfg: Dict, key: jax.Array, n: int) -> Dict[str, jax.Array]:
    return jax.vmap(lambda k: _one_input(cfg, k))(jax.random.split(key, n))


def forward(cfg: Dict, params: Dict, batch: Dict[str, jax.Array],
            quant: Optional[Quant] = None, record: Optional[Dict] = None
            ) -> Dict[str, jax.Array]:
    x = batch["image"]
    p = cfg["pool"]
    for i in range(len(cfg["channels"])):
        name = f"conv{i}"
        xi, wi = layer_io(name, x, params[name]["w"], quant, record)
        x = jax.nn.relu(conv(xi, wi, params[name]["b"], 1))
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, p, p, 1),
                                  (1, p, p, 1), "VALID")
    x = jnp.concatenate([x.reshape(x.shape[0], -1),
                         batch["background_flux"]], axis=1)
    xi, wi = layer_io("fc1", x, params["fc1"]["w"], quant, record)
    x = jax.nn.relu(dense(xi, wi, params["fc1"]["b"]))
    xi, wi = layer_io("head", x, params["head"]["w"], quant, record)
    return {"head": dense(xi, wi, params["head"]["b"])}
