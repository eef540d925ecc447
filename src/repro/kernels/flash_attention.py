"""Pallas TPU kernel: blockwise online-softmax (flash) causal attention.

The beyond-paper kernel (DESIGN.md §6): the 32k-prefill cells would need a
materialized [S,S] score matrix (17 GB/device for yi-34b) without it.

Grid (B, Hq, Sq/bq, Sk/bk); the KV dim is innermost/'arbitrary' so the
running max m, normalizer l, and output accumulator persist in VMEM across
KV steps. GQA is handled in the BlockSpec index maps — KV blocks are
indexed by ``h // group`` so grouped query heads share the same KV stream
without materializing repeated K/V (which is exactly what a DMA engine
should never copy twice).

Causality is exploited two ways: fully-masked KV blocks short-circuit via
``pl.when`` (no MXU work), and the diagonal block applies the triangle mask.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.0e38


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, bq: int, bk: int, n_k: int, causal: bool,
            kv_len: int):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    i = pl.program_id(2)
    q_start = i * bq
    k_start = j * bk
    padded_kv = kv_len < n_k * bk           # static: ragged Sk was padded

    # skip blocks strictly above the causal diagonal — and blocks that
    # lie entirely in the ragged-length KV padding
    run = (not causal) or (k_start <= q_start + bq - 1)
    if padded_kv:
        run = jnp.logical_and(run, k_start < kv_len)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)              # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)              # [bk, hd]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal or padded_kv:
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        if padded_kv:
            s = jnp.where(kpos < kv_len, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(
    q: jax.Array,                   # [B, Sq, Hq, hd]
    k: jax.Array,                   # [B, Sk, Hkv, hd]
    v: jax.Array,                   # [B, Sk, Hkv, hd]
    *,
    causal: bool = True,
    bq: int = 256,
    bk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    b, sq, hq, hd = q.shape
    _, sk, hkv, _ = k.shape
    g = hq // hkv
    bq, bk = min(bq, sq), min(bk, sk)
    # ragged sequence lengths: pad up to the block grid. Padded KV columns
    # are masked to NEG_INF inside the kernel (so they never contribute);
    # padded query rows compute garbage that is sliced off below.
    pad_q, pad_k = (-sq) % bq, (-sk) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    sq_p, sk_p = sq + pad_q, sk + pad_k
    n_k = sk_p // bk
    scale = hd ** -0.5

    qt = q.transpose(0, 2, 1, 3)                         # [B, Hq, Sq, hd]
    kt = k.transpose(0, 2, 1, 3)                         # [B, Hkv, Sk, hd]
    vt = v.transpose(0, 2, 1, 3)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, bq=bq, bk=bk, n_k=n_k,
                          causal=causal, kv_len=sk),
        grid=(b, hq, sq_p // bq, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b_, h, i, j: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b_, h, i, j: (b_, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b_, h, i, j: (b_, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq_p, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    out = out.transpose(0, 2, 1, 3)
    return out[:, :sq] if pad_q else out
