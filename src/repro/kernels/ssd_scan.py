"""Mamba2 SSD (state-space duality) scan as a Pallas TPU kernel.

Grid: (batch, head, chunks); the chunk dim is sequential ("arbitrary")
-- the inter-chunk state [P, N] lives in VMEM scratch and carries the
recurrence, while the intra-chunk work is dense MXU matmuls:

    scores = (C B^T) * L          [cl, cl]   (L = exp(segment sums))
    y_diag = scores @ (x * dt)    [cl, P]
    y_off  = (C * exp(cum)) @ state^T
    state' = exp(cum[-1]) * state + ((x*dt*decay_end)^T @ B)

This is the hardware-adaptation of Mamba2's CUDA kernel: the chunked
dual form maps the sequential scan onto systolic matmuls with one
[P, N] VMEM-resident carry per (batch, head) -- no HBM roundtrip for
the state inside a sequence.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, s_ref, state_ref, *,
            chunk):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, :, 0, :].astype(jnp.float32)        # [cl, P]
    dt = dt_ref[0, :, 0].astype(jnp.float32)         # [cl]
    A = a_ref[0]                                     # scalar (this head)
    B = b_ref[0, :, :].astype(jnp.float32)           # [cl, N]
    C = c_ref[0, :, :].astype(jnp.float32)           # [cl, N]

    dA = dt * A                                      # [cl] (<= 0)
    cum = jnp.cumsum(dA)                             # [cl]
    seg = cum[:, None] - cum[None, :]                # [cl, cl]
    tri = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    L = jnp.exp(jnp.where(tri, seg, -jnp.inf))       # [cl, cl]

    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ()))) * L
    xdt = x * dt[:, None]                            # [cl, P]
    y_diag = jax.lax.dot(scores, xdt)                # [cl, P]

    state = state_ref[...]                           # [P, N]
    y_off = jax.lax.dot_general(
        C * jnp.exp(cum)[:, None], state, (((1,), (1,)), ((), ())))
    y_ref[0, :, 0, :] = (y_diag + y_off).astype(y_ref.dtype)

    decay_end = jnp.exp(cum[-1] - cum)               # [cl]
    new_state = (jnp.exp(cum[-1]) * state
                 + jax.lax.dot_general(xdt * decay_end[:, None], B,
                                       (((0,), (0,)), ((), ()))))
    state_ref[...] = new_state

    @pl.when(ci == nc - 1)
    def _finish():
        s_ref[0, 0, :, :] = new_state.astype(s_ref.dtype)


def ssd_scan(x, dt, A, B, C, *, chunk=128, interpret=False):
    """x: [b,S,H,P]; dt: [b,S,H]; A: [H]; B,C: [b,S,N].

    Returns (y [b,S,H,P], final_state [b,H,P,N]); f32 accumulation.
    """
    b, S, H, P = x.shape
    N = B.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk
    grid = (b, H, nc)

    kernel = functools.partial(_kernel, chunk=chunk)
    y, s_final = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda bi, h, c: (bi, c, h, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bi, h, c: (bi, c, h)),
            pl.BlockSpec((1,), lambda bi, h, c: (h,)),
            pl.BlockSpec((1, chunk, N), lambda bi, h, c: (bi, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda bi, h, c: (bi, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda bi, h, c: (bi, c, h, 0)),
            pl.BlockSpec((1, 1, P, N), lambda bi, h, c: (bi, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, S, H, P), jnp.float32),
            jax.ShapeDtypeStruct((b, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, A, B, C)
    return y, s_final
