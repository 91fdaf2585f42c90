"""CLI train driver.

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b --smoke \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

--smoke uses the reduced same-family config (CPU-runnable); without it the
full config is built (requires a real pod). Checkpoints every --ckpt-every
steps (async), resumes automatically, logs loss/grad-norm/step-time.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.backend import enable_compilation_cache
from repro.configs import ARCHS, reduce_config
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.module import init_sharded
from repro.models.zoo import build_param_specs
from repro.train import checkpoint as ckpt
from repro.train.data import DataConfig, TokenStream
from repro.train.fault_tolerance import resume_or_init
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import (TrainStepConfig, make_train_step,
                                    train_state_specs)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b", choices=list(ARCHS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    args = ap.parse_args(argv)
    enable_compilation_cache()

    cfg = ARCHS[args.arch]
    if args.smoke:
        cfg = reduce_config(cfg, n_layers=args.layers, d_model=args.d_model,
                            n_heads=max(4, args.d_model // 64),
                            d_ff=args.d_model * 3, vocab=2048)
    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh())
    print(f"arch={cfg.name} mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")

    step_cfg = TrainStepConfig(
        microbatches=args.microbatches, remat=True,
        grad_compress=args.grad_compress,
        opt=AdamWConfig(lr=args.lr, total_steps=args.steps,
                        warmup_steps=min(20, args.steps // 5)))
    pspecs = build_param_specs(cfg)
    opt_specs = train_state_specs(pspecs, step_cfg)

    data = TokenStream(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                  global_batch=args.batch, seed=args.seed))

    def init_all():
        key = jax.random.PRNGKey(args.seed)
        return {"params": init_sharded(pspecs, key, mesh),
                "opt": init_sharded(opt_specs, key, mesh)}

    start = 0
    if args.ckpt_dir:
        state, start = resume_or_init(args.ckpt_dir, init_all,
                                      like_tree=None, shardings=None)
        if start:
            print(f"resumed from step {start}")
            tmpl = init_all()
            state = ckpt.restore(
                args.ckpt_dir, start, like_tree=tmpl,
                shardings=jax.tree.map(lambda a: a.sharding, tmpl))
    else:
        state = init_all()

    train_step = jax.jit(make_train_step(cfg, mesh, step_cfg),
                         donate_argnums=(0, 1))
    params, opt = state["params"], state["opt"]
    with jax.set_mesh(mesh):
        t_last = time.perf_counter()
        for step in range(start, args.steps):
            batch = {k: jnp.asarray(v) for k, v in
                     data.global_batch(step).items()}
            params, opt, metrics = train_step(params, opt, batch)
            if step % 10 == 0 or step == args.steps - 1:
                loss = float(metrics["loss"])
                dt = time.perf_counter() - t_last
                t_last = time.perf_counter()
                print(f"step {step:5d}  loss {loss:.4f}  "
                      f"gnorm {float(metrics['grad_norm']):.3f}  "
                      f"lr {float(metrics['lr']):.2e}  ({dt:.2f}s/10steps)",
                      flush=True)
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                ckpt.save(args.ckpt_dir, step + 1,
                          {"params": params, "opt": opt}, blocking=False)
    if args.ckpt_dir:
        ckpt.save(args.ckpt_dir, args.steps, {"params": params, "opt": opt})
        ckpt.wait_for_async()
    print("done")
    return params


if __name__ == "__main__":
    main()
