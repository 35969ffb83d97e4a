"""Serving launcher: batched prefill + decode loop on a reduced config.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
      --batch 4 --prompt-len 64 --gen 32

Demonstrates the inference path of the framework (continuous batched decode
with a static KV cache); the production-shape serving steps are exercised by
the dry-run (prefill_32k / decode_32k / long_500k cells).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.launch.train import reduced_lm_config
from repro.models import transformer as tfm


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg, family = get_config(args.arch)
    assert family == "lm"
    cfg = reduced_lm_config(cfg)
    key = jax.random.PRNGKey(args.seed)
    params = tfm.init_lm(key, cfg)
    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                 cfg.vocab)
    max_len = args.prompt_len + args.gen

    prefill = jax.jit(lambda p, t: tfm.prefill(p, t, cfg, max_len=max_len))
    decode = jax.jit(lambda p, c, t: tfm.decode_step(p, c, t, cfg),
                     donate_argnums=(1,))

    t0 = time.time()
    logits, cache = prefill(params, prompts)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    out = [tok]
    for _ in range(args.gen - 1):
        logits, cache = decode(params, cache, tok)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(tok)
    dt = time.time() - t0
    gen = jnp.stack(out, axis=1)
    print(f"generated {gen.shape} tokens in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s)")
    print("sample:", gen[0, :16].tolist())
    return gen


if __name__ == "__main__":
    main()
