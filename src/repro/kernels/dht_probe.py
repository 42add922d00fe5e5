"""Batched hashtable insert/lookup as a Pallas TPU kernel -- the TPU
adaptation of the paper's §5.3 DHT hot loop.

The paper's insert is "CAS your slot; losers go to the overflow heap".
A TPU has no remote CAS, so the contention-resolution is re-thought for
the VPU/MXU (DESIGN.md §2.2): keys are routed (host/jnp side) to table
*blocks*; inside one VMEM block every conflict is resolved densely:

  * one-hot slot matrix      O[i, s] = (slot_i == s)          [KB, TB]
  * incumbent gather         inc_i   = sum_s O[i, s] * tk[s]  (matmul)
  * first-arrival winners    win_i   = no earlier lane with slot_i
  * claims become the table  tk'     = claimed ? O^T (win * key) : tk

i.e. the atomic CAS becomes a *winner-resolution one-hot contraction*
-- no scatter, no serialization, pure dense ops. Lane order plays the
role of the paper's arrival order; losers get status=overflow exactly
like the paper's overflow-heap path (handled by ops.py in jnp).

Status codes match ref.dht_insert_ref: 0 insert, 1 update, 2 overflow,
3 padding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

EMPTY = -1


def _flip(v, n):
    """[1, n] <-> [n, 1] by a masked reduction over the n x n identity
    (Mosaic has no relayout of a lane vector into sublanes)."""
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    axis = 1 if v.shape[0] == 1 else 0
    return jnp.sum(jnp.where(eye, v, 0), axis=axis, keepdims=True)


def _onehot(keys, KB, TB):
    """Keys as a [KB, 1] column -> (valid [KB, 1], one-hot [KB, TB])."""
    valid = keys != EMPTY
    slot = jnp.where(valid, keys % TB, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (KB, TB), 1)
    return valid, (slot == lane) & valid


def _insert_kernel(tk_ref, tv_ref, keys_ref, vals_ref,
                   tk_out, tv_out, status_out, *, KB, TB):
    tk = tk_ref[0]                                     # [1, TB]
    tv = tv_ref[0]
    keys = _flip(keys_ref[0], KB)                      # [KB, 1]
    vals = _flip(vals_ref[0], KB)
    valid, onehot = _onehot(keys, KB, TB)              # [KB, TB]

    # Incumbent key at each lane's slot (one-hot "gather").
    inc_k = jnp.sum(jnp.where(onehot, tk, 0), axis=1, keepdims=True)
    occupied = jnp.sum(jnp.where(onehot & (tk != EMPTY), 1, 0), axis=1,
                       keepdims=True) > 0

    # First arrival per slot: no earlier lane contends for my slot.
    lane_i = jax.lax.broadcasted_iota(jnp.int32, (KB, TB), 0)
    first = jnp.min(jnp.where(onehot, lane_i, KB), axis=0, keepdims=True)
    first_i = jnp.sum(jnp.where(onehot, first, 0), axis=1, keepdims=True)
    earlier = first_i < jax.lax.broadcasted_iota(jnp.int32, (KB, 1), 0)

    update = valid & occupied & (inc_k == keys)
    insert = valid & ~occupied & ~earlier
    status = jnp.where(~valid, 3,
                       jnp.where(insert, 0,
                                 jnp.where(update, 1, 2)))

    # Claims: winners' one-hot columns fold into the table (no scatter).
    win_oh = onehot & insert                           # [KB, TB]
    claimed = jnp.sum(jnp.where(win_oh, 1, 0), axis=0, keepdims=True) > 0
    claim_k = jnp.sum(jnp.where(win_oh, keys, 0), axis=0, keepdims=True)
    claim_v = jnp.sum(jnp.where(win_oh, vals, 0), axis=0, keepdims=True)
    upd_oh = onehot & update
    updated = jnp.sum(jnp.where(upd_oh, 1, 0), axis=0, keepdims=True) > 0
    upd_v = jnp.sum(jnp.where(upd_oh, vals, 0), axis=0, keepdims=True)

    tk_out[0] = jnp.where(claimed, claim_k, tk)
    tv_out[0] = jnp.where(claimed, claim_v, jnp.where(updated, upd_v, tv))
    status_out[0] = _flip(status, KB)


def _lookup_kernel(tk_ref, tv_ref, keys_ref, val_out, hit_out, *, KB, TB):
    tk = tk_ref[0]
    tv = tv_ref[0]
    keys = _flip(keys_ref[0], KB)
    valid, onehot = _onehot(keys, KB, TB)
    inc_k = jnp.sum(jnp.where(onehot, tk, 0), axis=1, keepdims=True)
    inc_v = jnp.sum(jnp.where(onehot, tv, 0), axis=1, keepdims=True)
    hit = valid & (inc_k == keys)
    val_out[0] = _flip(jnp.where(hit, inc_v, EMPTY), KB)
    hit_out[0] = _flip(jnp.where(hit, 1, 0), KB)


def _rows(n):
    """BlockSpec of one [1, n] row of an [nb, 1, n] array: the block's
    last two dims equal the array's, as the TPU tiling requires."""
    return pl.BlockSpec((1, 1, n), lambda b: (b, 0, 0))


def dht_insert(table_keys, table_vals, keys, vals, *, interpret=False):
    """Blocked insert. table_*: [nb, TB]; keys/vals: [nb, KB] routed
    (EMPTY-padded). Returns (table_keys', table_vals', status [nb, KB]).
    """
    nb, TB = table_keys.shape
    KB = keys.shape[1]
    kernel = functools.partial(_insert_kernel, KB=KB, TB=TB)
    tk, tv, status = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[_rows(TB), _rows(TB), _rows(KB), _rows(KB)],
        out_specs=[_rows(TB), _rows(TB), _rows(KB)],
        out_shape=[jax.ShapeDtypeStruct((nb, 1, TB), jnp.int32),
                   jax.ShapeDtypeStruct((nb, 1, TB), jnp.int32),
                   jax.ShapeDtypeStruct((nb, 1, KB), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*(x[:, None, :] for x in (table_keys, table_vals, keys, vals)))
    return tk[:, 0], tv[:, 0], status[:, 0]


def dht_lookup(table_keys, table_vals, keys, *, interpret=False):
    """Blocked lookup. Returns (vals [nb, KB], hit [nb, KB] bool)."""
    nb, TB = table_keys.shape
    KB = keys.shape[1]
    kernel = functools.partial(_lookup_kernel, KB=KB, TB=TB)
    vals, hit = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[_rows(TB), _rows(TB), _rows(KB)],
        out_specs=[_rows(KB), _rows(KB)],
        out_shape=[jax.ShapeDtypeStruct((nb, 1, KB), jnp.int32),
                   jax.ShapeDtypeStruct((nb, 1, KB), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*(x[:, None, :] for x in (table_keys, table_vals, keys)))
    return vals[:, 0], hit[:, 0] != 0
