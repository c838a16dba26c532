"""Serve a small model with batched requests: prefill + decode loop with a
continuous-batching-style slot manager (finished sequences are replaced by
queued requests between decode steps).

**Legacy (LM-zoo era).** The repo's serving path is now the simulation
fleet — ``PYTHONPATH=src python -m repro.fleet --scenario sedov
--requests 64`` — which applies the same continuous-batching idea to whole
simulation requests (see ``examples/fleet_serve.py``). This example stays
as a model-zoo exercise.

Run:  PYTHONPATH=src python examples/serve_lm.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import dataclasses
import time

import numpy as np


def main():
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serve.serve_step import decode_step, prefill

    cfg = dataclasses.replace(get_config("granite-8b", reduced=True),
                              dtype=jnp.float32)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)

    B, S0, MAXLEN = 4, 16, 64
    n_requests = 12
    rng = np.random.default_rng(0)
    queue = [rng.integers(0, cfg.vocab, S0).astype(np.int32)
             for _ in range(n_requests)]

    # fill the first batch
    active = [queue.pop(0) for _ in range(B)]
    prompts = jnp.asarray(np.stack(active))
    logits, caches, rolling = prefill(params, cfg, prompts,
                                      cache_len=MAXLEN)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    lengths = [S0] * B
    done = 0
    t0 = time.perf_counter()
    decoded = 0
    pos = jnp.asarray(S0, jnp.int32)
    # simple continuous batching: sequences "finish" at a random target
    targets = [int(rng.integers(S0 + 8, MAXLEN - 1)) for _ in range(B)]
    while done < n_requests and int(pos) < MAXLEN - 1:
        logits, caches = decode_step(params, cfg, tok, caches, pos,
                                     rolling=rolling)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        decoded += B
        pos = pos + 1
        for b in range(B):
            lengths[b] += 1
            if lengths[b] >= targets[b]:
                done += 1
                if queue:
                    # slot reuse: in a full serving stack the slot would be
                    # re-prefilled; here we just restart its counter
                    queue.pop(0)
                    lengths[b] = S0
                    targets[b] = int(rng.integers(S0 + 8, MAXLEN - 1))
    dt = time.perf_counter() - t0
    print(f"served {done}/{n_requests} requests, {decoded} tokens in "
          f"{dt*1e3:.0f} ms ({decoded/max(dt,1e-9):.0f} tok/s)")


if __name__ == "__main__":
    main()
