"""Batched greedy decoding with the serving step (reduced configs).

    PYTHONPATH=src python examples/serve_decode.py [arch]

Builds a reduced model, prefills a short prompt through the teacher-forcing
path, then decodes 32 tokens per sequence with the cached serve step —
the same ``decode_step`` the multi-pod dry-run lowers at
(arch × decode_32k × 512 devices).
"""

import os
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.compile_cache import use_checkout_cache

use_checkout_cache(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from repro.configs import get_config
from repro.models import decode_step, init_cache, init_params


def main(arch: str = "mamba2-1.3b"):
    cfg = get_config(arch).reduced()
    B, prompt_len, gen_len = 4, 8, 32
    S_ctx = prompt_len + gen_len
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    prompt = jax.random.randint(key, (B, prompt_len), 0, cfg.vocab)

    cache = init_cache(cfg, B, S_ctx)
    step = jax.jit(lambda p, c, t, pos: decode_step(p, c, t, pos, cfg))

    # prefill via repeated decode (correct for every cache flavour)
    tok = prompt[:, :1]
    t0 = time.perf_counter()
    for t in range(prompt_len):
        logits, cache = step(params, cache, prompt[:, t:t + 1], jnp.int32(t))
    out = []
    tok = jnp.argmax(logits[:, : cfg.vocab], axis=-1)[:, None]
    for t in range(prompt_len, S_ctx):
        out.append(np.asarray(tok)[:, 0])
        logits, cache = step(params, cache, tok, jnp.int32(t))
        tok = jnp.argmax(logits[:, : cfg.vocab], axis=-1)[:, None]
    wall = time.perf_counter() - t0

    gen = np.stack(out, axis=1)
    print(f"arch={arch} ({cfg.family}); generated {gen.shape} tokens "
          f"in {wall:.2f}s ({B * gen_len / wall:.0f} tok/s incl. compile)")
    for b in range(B):
        print(f"  seq{b}: {gen[b][:16].tolist()} ...")


if __name__ == "__main__":
    main(*(sys.argv[1:] or []))
