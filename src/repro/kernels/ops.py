"""jit'd public wrappers around the Pallas kernels.

The kernels compile for the TPU (Mosaic). `interpret=True` runs their
bodies through the Pallas interpreter instead, which is how they run on
a CPU; the caller always chooses.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import dht_probe, flash_attention as fa, ssd_scan as ssd

EMPTY = jnp.int32(-1)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_kv", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=None, block_q=128,
                    block_kv=128, interpret=False):
    return fa.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=block_q, block_kv=block_kv,
                              interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B, C, *, chunk=128, interpret=False):
    return ssd.ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=interpret)


# ----------------------------------------------------------- DHT routing
def route_keys(keys, vals, nb: int, TB: int, KB: int):
    """Route keys to table blocks: block = (k // TB) % nb, slot = k % TB.

    Returns (keys_routed [nb, KB], vals_routed [nb, KB], idx [K] position
    of each input key in the routed layout, or -1 if the bucket
    overflowed KB -- those keys take the overflow-heap path directly).
    """
    K = keys.shape[0]
    bid = (keys // TB) % nb
    # Rank of each key inside its bucket (stable order = arrival order).
    onehot = jax.nn.one_hot(bid, nb, dtype=jnp.int32)          # [K, nb]
    rank = (jnp.cumsum(onehot, axis=0) - onehot)               # exclusive
    rank = jnp.take_along_axis(rank, bid[:, None], axis=1)[:, 0]
    ok = rank < KB
    flat = jnp.where(ok, bid * KB + rank, nb * KB)             # drop slot
    keys_r = jnp.full((nb * KB + 1,), EMPTY, jnp.int32).at[flat].set(keys)
    vals_r = jnp.full((nb * KB + 1,), EMPTY, jnp.int32).at[flat].set(vals)
    idx = jnp.where(ok, flat, -1)
    return (keys_r[:-1].reshape(nb, KB), vals_r[:-1].reshape(nb, KB), idx)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dht_insert(table_keys, table_vals, keys, vals, *, interpret=False):
    """Insert a key batch into the blocked table.

    table_*: [nb, TB]; keys/vals: [K] (distinct keys). Returns
    (table_keys', table_vals', status [K]) with status 0=insert,
    1=update, 2=overflow (incl. bucket-capacity overflow).
    """
    nb, TB = table_keys.shape
    KB = min(max(int(keys.shape[0]), 8), 512)
    keys_r, vals_r, idx = route_keys(keys, vals, nb, TB, KB)
    tk, tv, status_r = dht_probe.dht_insert(table_keys, table_vals,
                                            keys_r, vals_r,
                                            interpret=interpret)
    status = jnp.where(idx >= 0, status_r.reshape(-1)[jnp.maximum(idx, 0)],
                       2)
    return tk, tv, status


@functools.partial(jax.jit, static_argnames=("interpret",))
def dht_lookup(table_keys, table_vals, keys, *, interpret=False):
    nb, TB = table_keys.shape
    KB = min(max(int(keys.shape[0]), 8), 512)
    keys_r, _, idx = route_keys(keys, keys, nb, TB, KB)
    vals_r, hit_r = dht_probe.dht_lookup(table_keys, table_vals, keys_r,
                                         interpret=interpret)
    vals = jnp.where(idx >= 0, vals_r.reshape(-1)[jnp.maximum(idx, 0)],
                     EMPTY)
    hit = jnp.where(idx >= 0, hit_r.reshape(-1)[jnp.maximum(idx, 0)], False)
    return vals, hit
