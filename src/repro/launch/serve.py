"""Batched serving driver.

  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b --reduced \
      --requests 8 --prompt-len 16 --max-new 16

Runs the slot-based ServeEngine (prefill + decode loop + slot recycling)
and reports per-token latency and throughput.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_arch, reduced
from repro.distributed.shardings import shard_ctx
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.serving.engine import Request, ServeEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--mesh", choices=["none", "single", "multi"], default="none")
    ap.add_argument("--decode-mode", choices=["tp", "cp"], default="tp")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()

    arch = get_arch(args.arch)
    if args.reduced:
        arch = reduced(arch)
    if jax.default_backend() == "cpu":
        arch = arch.replace(dtype="float32")
    mesh = None
    if args.mesh != "none":
        mesh = make_production_mesh(multi_pod=args.mesh == "multi")

    rng = np.random.default_rng(args.seed)
    with shard_ctx(mesh):
        model = build_model(arch)
        params = model.init(jax.random.key(args.seed))
        engine = ServeEngine(model, params, n_slots=args.slots,
                             cache_len=args.cache_len,
                             decode_mode=args.decode_mode)
        reqs = [Request(uid=i,
                        prompt=rng.integers(0, arch.vocab, args.prompt_len),
                        max_new=args.max_new)
                for i in range(args.requests)]
        t0 = time.time()
        done = engine.run(reqs)
        dt = time.time() - t0
        total_new = sum(len(r.out) for r in done)
        print(f"served {len(done)} requests, {total_new} new tokens "
              f"in {dt:.2f}s ({total_new / max(dt, 1e-9):.1f} tok/s, "
              f"{args.slots} slots)")
        for r in done[:4]:
            print(f"  req {r.uid}: out[:8]={r.out[:8]}")
        return done


if __name__ == "__main__":
    main()
