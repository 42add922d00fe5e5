"""Flash attention (GQA + causal + sliding window) as a Pallas TPU kernel.

Grid: (batch, q_head, q_blocks, kv_blocks); the last dim is sequential
("arbitrary") -- online-softmax running stats (m, l, acc) live in VMEM
scratch and persist across kv blocks; the normalized output is written
once at the final kv block. GQA is handled in the index maps: head h
reads KV head h // G, so no K/V replication ever materializes.

Block shapes: q/o tiles are (block_q, head_dim), k/v tiles are
(block_kv, head_dim) -- head_dim is the lane dim (pad to 128 on real
TPU), block_q the sublane dim. S = q @ k.T and acc += p @ v are MXU
contractions over head_dim / block_kv.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, block_q, block_kv, causal, window):
    i = pl.program_id(2)
    j = pl.program_id(3)
    nkv = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, :, 0, :].astype(jnp.float32) * scale      # [bq, dh]
    k = k_ref[0, :, 0, :].astype(jnp.float32)              # [bk, dh]
    v = v_ref[0, :, 0, :].astype(jnp.float32)              # [bk, dh]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [bq, bk]

    qpos = i * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_kv), 0)
    kpos = j * block_kv + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_kv), 1)
    mask = jnp.ones((block_q, block_kv), bool)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
    acc_ref[...] = (acc_ref[...] * corr[:, None]
                    + jax.lax.dot(p.astype(v.dtype), v))
    m_ref[...] = m_new

    @pl.when(j == nkv - 1)
    def _finish():
        den = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, :, 0, :] = (acc_ref[...] / den[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, window=None,
                    block_q=128, block_kv=128, interpret=False):
    """q: [B,Sq,H,dh]; k,v: [B,Skv,KV,dh] -> [B,Sq,H,dh]."""
    B, Sq, H, dh = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    assert H % KV == 0, "GQA requires H % KV == 0"
    G = H // KV
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    assert Sq % block_q == 0 and Skv % block_kv == 0
    grid = (B, H, Sq // block_q, Skv // block_kv)
    scale = 1.0 / np.sqrt(dh)

    kernel = functools.partial(
        _kernel, scale=scale, block_q=block_q, block_kv=block_kv,
        causal=causal, window=window)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, 1, dh),
                         lambda b, h, i, j: (b, i, h, 0)),
            pl.BlockSpec((1, block_kv, 1, dh),
                         lambda b, h, i, j: (b, j, h // G, 0)),
            pl.BlockSpec((1, block_kv, 1, dh),
                         lambda b, h, i, j: (b, j, h // G, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, 1, dh),
                               lambda b, h, i, j: (b, i, h, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),      # m
            pltpu.VMEM((block_q,), jnp.float32),      # l
            pltpu.VMEM((block_q, dh), jnp.float32),   # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
