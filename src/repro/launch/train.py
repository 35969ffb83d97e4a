"""Training launcher (runs REAL steps — used by examples and the e2e test;
the production mesh path is exercised by dryrun.py).

  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
      --steps 50 --batch 8 --seq 256 --mesh 1x1 --ckpt /tmp/ckpt

Fault tolerance: auto-resume from the newest snapshot; `--fail-at N`
simulates a crash at step N (the e2e test restarts and checks bit-identical
continuation).  `--grad-compression` turns on int8 error-feedback gradient
all-reduce across the data axis.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint.manager import CheckpointManager
from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.data.tokens import TokenStream
from repro.dist import sharding as shd
from repro.launch.mesh import make_mesh, mesh_axes
from repro.models import transformer as tfm
from repro.optim import compression
from repro.optim.adamw import AdamW, cosine_warmup


def reduced_lm_config(cfg, layers=4, d_model=128, n_heads=4, n_kv=2,
                      d_head=32, d_ff=256, vocab=1024):
    """Shrink an assigned config to a trainable-on-CPU size, keeping its
    family structure (MoE stays MoE, activation stays)."""
    moe = cfg.moe
    if moe is not None:
        moe = dataclasses.replace(moe, n_experts=min(moe.n_experts, 8),
                                  d_ff_expert=d_ff)
    return dataclasses.replace(
        cfg, n_layers=layers, d_model=d_model, n_heads=n_heads, n_kv=n_kv,
        d_head=d_head, d_ff=d_ff, vocab=vocab, moe=moe, dtype="float32",
        q_chunk=64, kv_chunk=64, remat_block=1)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--mesh", default="1x1", help="DxM, e.g. 2x4")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--fail-at", type=int, default=-1)
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--full-size", action="store_true",
                    help="use the arch's real config (needs real hardware)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg, family = get_config(args.arch)
    assert family == "lm", "train.py drives LM archs; see examples/ for others"
    if not args.full_size:
        cfg = reduced_lm_config(cfg)

    d, m = (int(x) for x in args.mesh.split("x"))
    use_mesh = d * m > 1
    if use_mesh:
        mesh = make_mesh((d, m), ("data", "model"))
        ax = mesh_axes(mesh)
        ctx = tfm.DistCtx(mesh=mesh, dp=ax["dp"], tp=ax["tp"])
        pspecs = shd.lm_param_specs(cfg, ax["dp"], ax["tp"])
        pshard = shd.to_shardings(mesh, pspecs)
        bshard = {k: NamedSharding(mesh, v)
                  for k, v in shd.lm_batch_specs(ax["dp"]).items()}
    else:
        mesh, ctx, pshard, bshard = None, tfm.LOCAL_CTX, None, None

    opt = AdamW(lr=args.lr, schedule=cosine_warmup(10, args.steps))
    key = jax.random.PRNGKey(args.seed)
    params = tfm.init_lm(key, cfg)
    opt_state = opt.init(params)
    if use_mesh:
        params = jax.device_put(params, pshard)
        oshard = jax.tree.map(lambda s: s,
                              shd.opt_specs(pspecs))
        oshard = jax.tree.map(lambda sp: NamedSharding(mesh, sp), oshard)
        opt_state = jax.device_put(opt_state, oshard)

    err_state = compression.init_error(params) if args.grad_compression else None

    def train_step(params, opt_state, err, batch):
        (loss, parts), grads = jax.value_and_grad(
            tfm.lm_loss, has_aux=True)(params, batch, cfg, ctx)
        if err is not None:
            # int8 error-feedback compression of the gradient signal
            q, scales, err = compression.compress(grads, err)
            grads = compression.decompress(q, scales)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, err, loss

    jitted = jax.jit(train_step, donate_argnums=(0, 1, 2))

    stream = TokenStream(cfg.vocab, args.batch, args.seq, seed=args.seed)
    ckpt = CheckpointManager(args.ckpt) if args.ckpt else None
    start = 0
    if ckpt and ckpt.latest_step() is not None:
        (params, opt_state), start = ckpt.restore((params, opt_state))
        if use_mesh:
            params = jax.device_put(params, pshard)
            opt_state = jax.device_put(opt_state, oshard)
        print(f"resumed from step {start}")

    t0 = time.time()
    for step in range(start, args.steps):
        if step == args.fail_at:
            print(f"simulated failure at step {step}")
            raise SystemExit(42)
        hb = stream.batch_at(step)
        batch = {k: jnp.asarray(v) for k, v in hb.items()}
        if use_mesh:
            batch = {k: jax.device_put(v, bshard[k]) for k, v in batch.items()}
        params, opt_state, err_state, loss = jitted(params, opt_state,
                                                    err_state, batch)
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss {float(loss):.4f} "
                  f"({(time.time()-t0):.1f}s)", flush=True)
        if ckpt and (step + 1) % args.ckpt_every == 0:
            ckpt.save(step + 1, (params, opt_state))
    if ckpt:
        ckpt.save(args.steps, (params, opt_state))
        ckpt.wait()
    print(f"final loss {float(loss):.4f}")
    return float(loss)


if __name__ == "__main__":
    main()
