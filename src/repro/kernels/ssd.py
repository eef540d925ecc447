"""Pallas TPU kernel: Mamba2 SSD (state-space duality) chunked scan.

The SSM mixer is this framework's "flexible-path" op (DESIGN.md §4 — the
analog of the paper's MMS 3-D convs that the DPU cannot run), but its
inner chunk math is pure MXU work. This kernel keeps the running state
[P, N] resident in VMEM across the chunk dimension — the HBM-traffic
profile the pure-XLA version cannot achieve (it round-trips chunk states
and materializes the [Q, Q] decay masks in HBM).

Grid (B, H, S/Q), chunk index innermost with 'arbitrary' semantics:
    per step (all f32 in VMEM):
      a   = dt * A[h]                cum = cumsum(a)
      L   = tril(exp(cum_i - cum_j))             [Q, Q]
      M   = (C @ B^T) * L * dt_j                 [Q, Q]
      y   = M @ x  +  exp(cum)_i * (C @ state^T) [Q, P]
      state = exp(cum_Q) * state + ((suffix*dt) . x)^T B
the state scratch carries across chunk steps; the final state is emitted
on the last step (prefill hands it to the decode recurrence).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, init_ref,
            y_ref, final_ref, state_ref, *, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = init_ref[0, 0].astype(jnp.float32)      # [P, N]

    q = x_ref.shape[1]
    x = x_ref[0, :, 0, :].astype(jnp.float32)                    # [Q, P]
    B = b_ref[0].astype(jnp.float32)                             # [Q, N]
    C = c_ref[0].astype(jnp.float32)                             # [Q, N]
    dt = dt_ref[0, :, 0].astype(jnp.float32)                     # [Q]
    A = a_ref[0]                                                 # scalar

    a = dt * A
    cum = jnp.cumsum(a)                                          # [Q]

    # intra-chunk: decay-masked "attention" over the chunk
    li = cum[:, None] - cum[None, :]
    iota_i = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    iota_j = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    L = jnp.where(iota_i >= iota_j, jnp.exp(li), 0.0)            # [Q, Q]
    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    M = scores * L * dt[None, :]                                 # [Q, Q]
    y = jax.lax.dot_general(M, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [Q, P]

    # inter-chunk: contribution of the state entering this chunk
    state = state_ref[...]                                       # [P, N]
    y_in = jax.lax.dot_general(C, state, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)  # [Q, P]
    y = y + jnp.exp(cum)[:, None] * y_in
    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)

    # state update: decay past the chunk + this chunk's outer products
    suffix = jnp.exp(cum[q - 1] - cum) * dt                      # [Q]
    s_new = jax.lax.dot_general(x * suffix[:, None], B,
                                (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [P, N]
    state_ref[...] = state * jnp.exp(cum[q - 1]) + s_new

    @pl.when(ci == n_chunks - 1)
    def _emit():
        final_ref[0, 0] = state_ref[...].astype(final_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "interpret"))
def ssd(x, B_, C_, dt, A, init_state=None, *, chunk: int = 256,
        interpret: bool = False):
    """Chunked SSD scan. x [B,S,H,P], B_/C_ [B,S,N], dt [B,S,H] (already
    softplus'd, f32), A [H] (negative, f32), init_state [B,H,P,N] or None.
    Returns (y [B,S,H,P], final_state [B,H,P,N])."""
    b, s, h, p = x.shape
    n = B_.shape[-1]
    if s % chunk:
        chunk = next(c for c in range(min(chunk, s), 0, -1) if s % c == 0)
    nc = s // chunk
    if init_state is None:
        init_state = jnp.zeros((b, h, p, n), jnp.float32)

    grid = (b, h, nc)
    out_shapes = (
        jax.ShapeDtypeStruct(x.shape, x.dtype),
        jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
    )
    return pl.pallas_call(
        functools.partial(_kernel, n_chunks=nc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, p), lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bi, hi, ci: (bi, ci, hi)),
            pl.BlockSpec((1,), lambda bi, hi, ci: (hi,)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, chunk, 1, p), lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ),
        out_shape=out_shapes,
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, B_, C_, dt, A.astype(jnp.float32), init_state)
