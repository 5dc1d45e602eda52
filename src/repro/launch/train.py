"""End-to-end training driver.

  PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
      --reduced --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Wires together: config registry -> model -> sharded train step -> token
pipeline -> checkpoint manager -> watchdog -> (optional) OCC data curation.
On this CPU container use --reduced; on a pod the full config + production
mesh engage via --mesh single|multi.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import TrainConfig, get_arch, reduced
from repro.data.tokens import TokenPipeline
from repro.distributed.fault import StepWatchdog
from repro.distributed.shardings import shard_ctx
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.training.step import make_train_step, train_state_init


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--mesh", choices=["none", "single", "multi"], default="none")
    ap.add_argument("--dtype", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args(argv)
    use_compile_cache()

    arch = get_arch(args.arch)
    if args.reduced:
        arch = reduced(arch)
    if args.dtype:
        arch = arch.replace(dtype=args.dtype)
    elif jax.default_backend() == "cpu":
        arch = arch.replace(dtype="float32")

    mesh = None
    if args.mesh != "none":
        mesh = make_production_mesh(multi_pod=args.mesh == "multi")

    tcfg = TrainConfig(learning_rate=args.lr, warmup_steps=max(2, args.steps // 10),
                       total_steps=args.steps, microbatches=args.microbatches,
                       seed=args.seed)
    model = build_model(arch)
    pipe = TokenPipeline(arch.vocab, args.batch, args.seq, seed=args.seed)
    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    watchdog = StepWatchdog()

    with shard_ctx(mesh):
        params = model.init(jax.random.key(args.seed))
        state = train_state_init(params, tcfg)
        start_step = 0
        if ckpt and args.resume and ckpt.latest_step() is not None:
            start_step, state = ckpt.restore(state)
            print(f"resumed from step {start_step}")
        step_fn = jax.jit(make_train_step(model, tcfg), donate_argnums=(0,))

        n_params = model.param_count(params)
        print(f"arch={arch.name} params={n_params:,} steps={args.steps} "
              f"batch={args.batch} seq={args.seq}")
        t_start = time.time()
        for step in range(start_step, args.steps):
            hb = pipe.batch_at(step)
            batch = {k: jnp.asarray(v) for k, v in hb.items()}
            if arch.frontend:
                rng = np.random.default_rng([args.seed, step])
                batch["frontend"] = jnp.asarray(rng.normal(
                    size=(args.batch, arch.frontend_len, arch.frontend_dim)
                ).astype(np.float32))
            t0 = time.time()
            state, metrics = step_fn(state, batch)
            loss = float(metrics["loss"])
            dt = time.time() - t0
            ev = watchdog.observe(step, dt)
            if ev:
                print(f"[straggler] step {step}: {dt:.2f}s vs ewma {ev.ewma:.2f}s")
            if step % args.log_every == 0 or step == args.steps - 1:
                toks = args.batch * args.seq
                print(f"step {step:5d} loss {loss:8.4f} "
                      f"gnorm {float(metrics['grad_norm']):7.3f} "
                      f"lr {float(metrics['lr']):.2e} {dt:6.2f}s "
                      f"({toks / max(dt, 1e-9):,.0f} tok/s)")
            if ckpt and (step + 1) % args.ckpt_every == 0:
                ckpt.save(step + 1, state)
        if ckpt:
            ckpt.save(args.steps, state)
            ckpt.wait()
        print(f"done in {time.time() - t_start:.1f}s; final loss {loss:.4f}")
        return loss


if __name__ == "__main__":
    main()
