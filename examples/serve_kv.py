"""Serving example: batched decode with the versioned parameter store
(the paper's DC transplant) and the DHT as the request-metadata store
-- the KV-store usage the paper targets (§5.3).

Requests arrive as (request_id, prompt token); the Batcher groups them,
decode steps run against a shared cache, the BatchedDHT maps
request_id -> slot so results can be claimed out of order, and a
background weight swap exercises the reader/writer protocol.

    PYTHONPATH=src python examples/serve_kv.py
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.dht import BatchedDHT
from repro.models import lm
from repro.serve import VersionedStore, build_decode_step

ARCH = "qwen2-0.5b"
BATCH = 8
DECODE_STEPS = 24
SWAP_AT = 12


def main():
    cfg = get_smoke_config(ARCH)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    store = VersionedStore(params, n_workers=BATCH, T_DC=4)
    decode = jax.jit(build_decode_step(cfg))

    # Request-metadata DHT: request_id -> batch slot. The probe kernel
    # compiles for the TPU; anywhere else it runs in the Pallas
    # interpreter.
    interpret = jax.default_backend() != "tpu"
    print(f"DHT kernel: interpret={interpret} "
          f"(backend {jax.default_backend()})")
    dht = BatchedDHT(nb=4, TB=64, heap=256, interpret=interpret)
    meta = dht.init()
    req_ids = jnp.asarray(np.random.RandomState(0)
                          .permutation(10_000)[:BATCH] + 1, jnp.int32)
    meta, _ = dht.insert(meta, req_ids, jnp.arange(BATCH, dtype=jnp.int32))

    cache = lm.make_cache(cfg, BATCH, DECODE_STEPS + 4)
    tok = jnp.asarray(np.random.RandomState(1)
                      .randint(0, cfg.vocab, (BATCH, 1)), jnp.int32)

    generated = []
    swapper = None
    for step in range(DECODE_STEPS):
        if step == SWAP_AT:
            # Weight swap from a background thread while readers decode.
            new_params = jax.tree.map(lambda x: x * 1.0, store._params)
            swapper = threading.Thread(target=store.swap,
                                       args=(new_params,))
            swapper.start()
        with store.reader_view(step % BATCH) as (p, ver):
            tok, cache = decode(p, tok, cache)
        generated.append(tok)
    if swapper:
        swapper.join()

    out = jnp.concatenate(generated, axis=1)
    # Claim results via the metadata DHT.
    slots, found = dht.lookup(meta, req_ids)
    assert bool(jnp.all(found))
    for i in range(min(4, BATCH)):
        rid, slot = int(req_ids[i]), int(slots[i])
        print(f"request {rid:5d} (slot {slot}): "
              f"tokens {out[slot, :8].tolist()}")
    print(f"served {BATCH} requests x {DECODE_STEPS} tokens; "
          f"store version now v{store.version} (swapped mid-stream)")


if __name__ == "__main__":
    main()
