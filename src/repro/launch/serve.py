"""Serving launcher: batched greedy decoding with a KV cache on the local
devices (reduced config), or production-mesh lowering via dryrun for the
decode shapes.

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-1.3b \
      --batch 4 --prompt-len 32 --gen 32
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_config
from repro.models import model_zoo
from repro.models.common import init_params
from repro.train.train_step import make_serve_step


def prefill_prompt(cfg, params, caches, tokens):
    """Chunked prefill: the whole prompt in one cached pass (every family,
    incl. SSM state seeding and MLA latent caches)."""
    logits, caches = jax.jit(
        lambda p, c, t: model_zoo.prefill(p, cfg, {"tokens": t}, c)
    )(params, caches, tokens)
    nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    return nxt, caches


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default="mamba2-1.3b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    from repro.launch.jitcache import enable_persistent_cache
    enable_persistent_cache()

    cfg = get_config(args.arch).reduced()
    key = jax.random.PRNGKey(args.seed)
    params = init_params(model_zoo.param_defs(cfg), key, jnp.float32)
    cache_len = args.prompt_len + args.gen
    caches = init_params(model_zoo.cache_defs(cfg, args.batch, cache_len),
                         key, jnp.float32)
    prompt = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                cfg.vocab_size)

    t0 = time.time()
    nxt, caches = prefill_prompt(cfg, params, caches, prompt)
    t_prefill = time.time() - t0

    step = jax.jit(make_serve_step(cfg))
    out = [nxt]
    t0 = time.time()
    for g in range(args.gen - 1):
        nxt, caches = step(params, caches, nxt,
                           jnp.int32(args.prompt_len + g))
        out.append(nxt)
    t_gen = time.time() - t0
    gen = np.concatenate([np.asarray(o) for o in out], axis=1)
    print(json.dumps({
        "arch": args.arch, "batch": args.batch,
        "prefill_s": round(t_prefill, 3), "gen_s": round(t_gen, 3),
        "tok_per_s": round(args.batch * (args.gen - 1) / max(t_gen, 1e-9), 1),
        "sample": gen[0][:16].tolist(),
    }, indent=1))


if __name__ == "__main__":
    main()
