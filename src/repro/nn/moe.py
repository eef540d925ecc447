"""Mixture-of-experts FFN (llama4-style top-1 routing + shared expert).

Dispatch is scatter-based: tokens are written into a per-expert capacity
buffer ``[E, C, D]`` (overflow dropped, standard capacity-factor semantics),
expert SwiGLU runs as one batched einsum over the buffer, and results are
gathered back. The buffer is the *only* E-indexed activation, sharded
``('expert' -> model, 'expert_cap' -> data)``, so expert weights reach
256-way sharding on the production mesh (maverick's 128 x 3 x 5120 x 8192
routed params would not fit 16-way).

The router (data-dependent top-k + scatter) is a *flexible-path* op in the
paper's operator-coverage sense — see core/inspector.py; the expert matmuls
themselves are accelerator ops.

NB: capacity-based dispatch couples sequences within a global batch — a
routing change in one row can evict another row's token from a full expert
buffer (overflow is dropped to the residual). This is the standard
Switch/GShard semantics; causality holds *within* each sequence.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.nn.dims import Dims
from repro.nn.params import ParamSpec
from repro.parallel.sharding import constrain, current_mesh, current_rules, spec_for


def moe_spec(cfg: ArchConfig, dims: Dims) -> dict:
    m = cfg.moe
    d, f, e = dims.d_model, dims.d_ff, m.num_experts
    # a2a dispatch needs F-complete expert weights per model shard (tokens
    # a2a'd to the shard contract the full F); scatter dispatch second-level
    # shards F over the data axis.
    ffn_axis = None if m.ep_impl == "a2a" else "expert_ffn"
    spec = {
        "router": ParamSpec((d, e), ("fsdp", None), scale=0.006),
        "w_gate": ParamSpec((e, d, f), ("expert", None, ffn_axis)),
        "w_up": ParamSpec((e, d, f), ("expert", None, ffn_axis)),
        "w_down": ParamSpec((e, f, d), ("expert", ffn_axis, None)),
    }
    if m.num_shared_experts:
        fs = f * m.num_shared_experts
        spec["shared"] = {
            "w_gate": ParamSpec((d, fs), ("fsdp", "ffn")),
            "w_up": ParamSpec((d, fs), ("fsdp", "ffn")),
            "w_down": ParamSpec((fs, d), ("ffn", "fsdp")),
        }
    return spec


def _capacity(tokens: int, cfg: ArchConfig) -> int:
    m = cfg.moe
    cap = int(tokens * m.top_k * m.capacity_factor / m.num_experts)
    return max(8, (cap + 7) // 8 * 8)


def moe_ffn(params: dict, x: jax.Array, cfg: ArchConfig, dims: Dims) -> jax.Array:
    """x: [B, S, D] -> [B, S, D]. Top-1 routed + shared expert."""
    mesh = current_mesh()
    if (cfg.moe.ep_impl == "a2a" and mesh is not None
            and "model" in mesh.axis_names
            and cfg.moe.num_experts % mesh.shape["model"] == 0):
        y = _moe_routed_a2a(params, x, cfg, mesh)
        return y + _shared_expert(params, x, cfg)
    return _moe_ffn_scatter(params, x, cfg, dims)


def _shared_expert(params: dict, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    if not cfg.moe.num_shared_experts:
        return jnp.zeros_like(x)
    sp = params["shared"]
    hg = jnp.einsum("bsd,df->bsf", x, sp["w_gate"])
    hu = jnp.einsum("bsd,df->bsf", x, sp["w_up"])
    hs = jax.nn.silu(hg.astype(jnp.float32)).astype(x.dtype) * hu
    return jnp.einsum("bsf,fd->bsd", hs, sp["w_down"])


def _moe_routed_a2a(params: dict, x: jax.Array, cfg: ArchConfig, mesh
                    ) -> jax.Array:
    """Expert parallelism with explicit all_to_all over the 'model' axis.

    §Perf iteration A1. Tokens move (2 x T_local x D bf16 per layer over
    the EP axis) instead of expert capacity buffers being all-reduced —
    the baseline scatter dispatch measured ~114 GB/device/layer of
    all-reduce on llama4-scout prefill_32k; this moves ~0.1 GB.

    Per-device plan (inside shard_map):
      1. route local tokens (router replicated — 160 KB),
      2. pack per-destination-shard send buffers [tp, cap, D] by cumsum
         position (overflow past per-pair capacity dropped, standard
         capacity-factor semantics applied per (src, dst) pair),
      3. all_to_all tokens + local-expert indices,
      4. per-local-expert capacity scatter (LOCAL — no collectives),
         batched expert SwiGLU,
      5. all_to_all results back, unpack to token order, gate at source.
    """
    m = cfg.moe
    tp = mesh.shape["model"]
    e_per = m.num_experts // tp
    rules = current_rules()
    x_spec = spec_for(x.shape, ("batch", "seq", None), mesh, rules)
    wg = params["w_gate"]
    w_spec = spec_for(wg.shape, ("expert", None, None), mesh, rules)
    wd_spec = spec_for(params["w_down"].shape, ("expert", None, None), mesh,
                       rules)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(x_spec, P(), w_spec, w_spec, wd_spec),
        out_specs=x_spec, check_vma=False)
    def routed(x_blk, router, w_gate, w_up, w_down):
        bl, sl, d = x_blk.shape
        tl = bl * sl
        xf = x_blk.reshape(tl, d)
        logits = (xf @ router).astype(jnp.float32)              # [tl, E]
        eidx = jnp.argmax(logits, axis=-1)                      # global expert
        gate = jax.nn.sigmoid(jnp.max(logits, axis=-1))
        dest = eidx // e_per                                    # model shard
        e_loc = (eidx % e_per).astype(jnp.int32)

        cap = max(8, -(-int(tl * m.top_k * m.capacity_factor) // tp) // 8 * 8)
        dest_1h = jax.nn.one_hot(dest, tp, dtype=jnp.int32)     # [tl, tp]
        pos = jnp.take_along_axis(jnp.cumsum(dest_1h, axis=0) - 1,
                                  dest[:, None], axis=1)[:, 0]
        keep = pos < cap
        pos_c = jnp.where(keep, pos, cap - 1)

        send = jnp.zeros((tp, cap, d), x_blk.dtype)
        send = send.at[dest, pos_c].add(
            jnp.where(keep[:, None], xf, 0).astype(x_blk.dtype))
        send_e = jnp.full((tp, cap), e_per, jnp.int32)          # pad -> dummy
        send_e = send_e.at[dest, pos_c].min(
            jnp.where(keep, e_loc, e_per).astype(jnp.int32))

        recv = jax.lax.all_to_all(send, "model", 0, 0, tiled=False)
        recv_e = jax.lax.all_to_all(send_e, "model", 0, 0, tiled=False)
        rt = tp * cap
        tok_in = recv.reshape(rt, d)
        e_in = recv_e.reshape(rt)

        if e_per == 1:
            valid = (e_in == 0)[:, None].astype(tok_in.dtype)
            h = (tok_in * valid) @ w_gate[0]
            u = (tok_in * valid) @ w_up[0]
            h = jax.nn.silu(h.astype(jnp.float32)).astype(tok_in.dtype) * u
            y_r = h @ w_down[0]
        else:
            # LOCAL capacity scatter over my e_per experts (+1 dummy slot)
            cap2 = max(8, -(-rt // e_per) // 8 * 8)
            oh = jax.nn.one_hot(e_in, e_per + 1, dtype=jnp.int32)
            pos2 = jnp.take_along_axis(jnp.cumsum(oh, axis=0) - 1,
                                       e_in[:, None], axis=1)[:, 0]
            keep2 = (pos2 < cap2) & (e_in < e_per)
            pos2_c = jnp.where(keep2, pos2, cap2 - 1)
            e_c = jnp.where(keep2, e_in, 0)
            buf = jnp.zeros((e_per, cap2, d), tok_in.dtype)
            buf = buf.at[e_c, pos2_c].add(
                jnp.where(keep2[:, None], tok_in, 0).astype(tok_in.dtype))
            h = jnp.einsum("ecd,edf->ecf", buf, w_gate)
            u = jnp.einsum("ecd,edf->ecf", buf, w_up)
            h = jax.nn.silu(h.astype(jnp.float32)).astype(buf.dtype) * u
            out_buf = jnp.einsum("ecf,efd->ecd", h, w_down)
            y_r = out_buf[e_c, pos2_c] * keep2[:, None].astype(out_buf.dtype)

        y_back = jax.lax.all_to_all(y_r.reshape(tp, cap, d), "model", 0, 0,
                                    tiled=False)
        y_tok = y_back[dest, pos_c]                             # [tl, D]
        y_tok = y_tok * (keep.astype(jnp.float32) * gate
                         )[:, None].astype(y_tok.dtype)
        return y_tok.reshape(bl, sl, d)

    return routed(x, params["router"], params["w_gate"], params["w_up"],
                  params["w_down"])


def _moe_ffn_scatter(params: dict, x: jax.Array, cfg: ArchConfig,
                     dims: Dims) -> jax.Array:
    """Baseline: sharded capacity-buffer scatter (XLA SPMD dispatch)."""
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    e = m.num_experts
    cap = _capacity(t, cfg)

    xf = x.reshape(t, d)
    logits = (xf @ params["router"]).astype(jnp.float32)        # [T, E]
    # llama4 routes with sigmoid gates on the top-1 expert
    eidx = jnp.argmax(logits, axis=-1)                          # [T]
    gate = jax.nn.sigmoid(jnp.max(logits, axis=-1))             # [T]

    onehot = jax.nn.one_hot(eidx, e, dtype=jnp.int32)           # [T, E]
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1,
                              eidx[:, None], axis=1)[:, 0]      # [T]
    keep = pos < cap
    pos_c = jnp.where(keep, pos, cap - 1)

    buf = jnp.zeros((e, cap, d), x.dtype)
    buf = buf.at[eidx, pos_c].add(jnp.where(keep[:, None], xf, 0))
    buf = constrain(buf, "expert", "expert_cap", None)

    h = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
    h = jax.nn.silu(h.astype(jnp.float32)).astype(x.dtype) * u
    h = constrain(h, "expert", "expert_cap", "expert_ffn")
    out_buf = jnp.einsum("ecf,efd->ecd", h, params["w_down"])
    out_buf = constrain(out_buf, "expert", "expert_cap", None)

    y = out_buf[eidx, pos_c]                                    # [T, D]
    y = y * (keep.astype(jnp.float32) * gate)[:, None].astype(x.dtype)
    y = y.reshape(b, s, d)

    if m.num_shared_experts:
        sp = params["shared"]
        hg = jnp.einsum("bsd,df->bsf", x, sp["w_gate"])
        hu = jnp.einsum("bsd,df->bsf", x, sp["w_up"])
        hs = jax.nn.silu(hg.astype(jnp.float32)).astype(x.dtype) * hu
        y = y + jnp.einsum("bsf,fd->bsd", hs, sp["w_down"])
    return y


def aux_load_balance_loss(logits: jax.Array, eidx: jax.Array, e: int) -> jax.Array:
    """Switch-style load-balance auxiliary (exposed for the training loop)."""
    probs = jax.nn.softmax(logits, axis=-1)
    frac = jnp.mean(jax.nn.one_hot(eidx, e, dtype=jnp.float32), axis=0)
    imp = jnp.mean(probs, axis=0)
    return e * jnp.sum(frac * imp)
