"""VAE encoder (SHARP magnetogram tile -> 6-element latent; the paper's
Table I row): five 3x3 stride-2 SAME conv + ReLU stages, flatten, and
the mu / logvar dense heads. The reparameterised ``sample`` is a random
draw with keys of the server's own and is checked only for finiteness."""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from bench.reference import (Layer, Quant, conv, dense, init_params,
                             layer_io, same_out)


def layers(cfg: Dict) -> List[Layer]:
    h, w, c = cfg["input_shape"]
    k, s, out = cfg["kernel"], cfg["stride"], []
    for i, f in enumerate(cfg["channels"]):
        ho, wo = same_out(h, s), same_out(w, s)
        out.append(Layer(f"conv{i}", "conv", h, w, c, f, k, s, ho, wo,
                         False))
        h, w, c = ho, wo, f
    for name in ("mu", "logvar"):
        out.append(Layer(name, "dense", 1, 1, h * w * c, cfg["latent"], 1,
                         1, 1, 1, True))
    return out


def init(cfg: Dict, key: jax.Array) -> Dict[str, Dict[str, jax.Array]]:
    return init_params(layers(cfg), key)


def _one_input(cfg: Dict, key: jax.Array) -> Dict[str, jax.Array]:
    """An active-region tile: a bipolar sunspot pair at a random offset
    and strength on a noisy background (magnetogram, |B|, B/2)."""
    k1, k2, k3 = jax.random.split(key, 3)
    h, w, _ = cfg["input_shape"]
    yy, xx = jnp.mgrid[0:h, 0:w]
    dy, dx = jax.random.uniform(k2, (2,), jnp.float32, -10.0, 10.0)
    amp = jax.random.uniform(k3, (), jnp.float32, 0.5, 1.5)
    cy, cx = h // 2 + dy, w // 2 + dx
    pos = jnp.exp(-(((yy - cy) / 12.0) ** 2 + ((xx - cx + 30) / 18.0) ** 2))
    neg = -jnp.exp(-(((yy - cy) / 15.0) ** 2
                     + ((xx - cx - 30) / 20.0) ** 2))
    field = amp * (pos + neg) + 0.05 * jax.random.normal(k1, (h, w))
    return {"image": jnp.stack([field, jnp.abs(field), 0.5 * field],
                               axis=-1)}


def inputs(cfg: Dict, key: jax.Array, n: int) -> Dict[str, jax.Array]:
    return jax.vmap(lambda k: _one_input(cfg, k))(jax.random.split(key, n))


def forward(cfg: Dict, params: Dict, batch: Dict[str, jax.Array],
            quant: Optional[Quant] = None, record: Optional[Dict] = None
            ) -> Dict[str, jax.Array]:
    x = batch["image"]
    for i in range(len(cfg["channels"])):
        name = f"conv{i}"
        xi, wi = layer_io(name, x, params[name]["w"], quant, record)
        x = jax.nn.relu(conv(xi, wi, params[name]["b"], cfg["stride"]))
    x = x.reshape(x.shape[0], -1)
    out = {}
    for name in ("mu", "logvar"):
        xi, wi = layer_io(name, x, params[name]["w"], quant, record)
        out[name] = dense(xi, wi, params[name]["b"])
    return out
