import os
os.environ["XLA_FLAGS"] = os.environ.get(
    "REPRO_DRYRUN_XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above run before ANY other import (jax locks the device count
on first init).  Usage:

  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] --out results/dryrun

Each cell emits a JSON with memory_analysis, cost_analysis, collective-byte
breakdown (parsed from post-SPMD HLO), sharding decisions, and the roofline
terms.  A failure here (sharding mismatch, OOM at compile, unsupported
collective) is a bug in the system.
"""
import argparse   # noqa: E402
import json       # noqa: E402
import time       # noqa: E402
import traceback  # noqa: E402

import jax        # noqa: E402

from repro.configs import ARCHS, SHAPES, TrainConfig, get_arch, supports_shape  # noqa: E402
from repro.distributed.shardings import shard_ctx                   # noqa: E402
from repro.launch.mesh import make_production_mesh                  # noqa: E402
from repro.launch.specs import plan_cell                            # noqa: E402
from repro import roofline                                          # noqa: E402
from repro.models.model import Model                                # noqa: E402


def run_cell(arch_name: str, shape_name: str, multi_pod: bool,
             variant: dict | None = None, out_dir: str | None = None) -> dict:
    arch = get_arch(arch_name)
    shape = SHAPES[shape_name]
    ok, why = supports_shape(arch, shape)
    label = f"{arch_name} x {shape_name} x {'2x16x16' if multi_pod else '16x16'}"
    if not ok:
        rec = {"cell": label, "status": "skipped", "reason": why,
               "arch": arch_name, "shape": shape_name, "multi_pod": multi_pod}
        _emit(rec, out_dir, arch_name, shape_name, multi_pod, variant)
        print(f"[skip] {label}: {why}")
        return rec

    variant = variant or {}
    if variant:
        arch = arch.replace(**{k: v for k, v in variant.items()
                               if k in arch.__dataclass_fields__ and k != "moe"})
        if "moe_impl" in variant and arch.moe is not None:
            import dataclasses
            arch = arch.replace(
                moe=dataclasses.replace(arch.moe, impl=variant["moe_impl"]))
        if "capacity_factor" in variant and arch.moe is not None:
            import dataclasses
            arch = arch.replace(moe=dataclasses.replace(
                arch.moe, capacity_factor=variant["capacity_factor"]))

    if "mesh_shape" in variant:   # §Perf lever: same chips, different split
        shp = tuple(variant["mesh_shape"])
        axes = ("data", "model") if len(shp) == 2 else ("pod", "data", "model")
        mesh = jax.make_mesh(
            shp, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    t0 = time.time()
    # Production defaults: sequence-parallel activation storage on (see
    # EXPERIMENTS.md §Perf — 5x saved-residual memory win); variants override.
    ctx_kw = {"seq_shard_acts": True}
    ctx_kw.update({k: v for k, v in variant.items()
                   if k in ("seq_shard_acts", "zero3", "force_decode_mode")})
    tcfg = TrainConfig(microbatches=int(variant.get("microbatches", 1)))
    with shard_ctx(mesh, **ctx_kw):
        with mesh:
            plan = plan_cell(arch, shape, mesh, tcfg)
            lowered = plan.lower()
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            print(mem)    # proves it fits
            print({k: v for k, v in cost.items()
                   if k in ("flops", "bytes accessed", "optimal_seconds")})

            hlo = compiled.as_text()

    model = Model(arch)
    n_params = model.param_count()
    n_active = roofline.active_params(arch, n_params)
    from repro.models.transformer import segments_for as _segs
    # per-depth trip counts: [microbatch scan, layer scan] (dense: n_layers;
    # hybrid archs unroll segments in python so each body runs `count` times)
    seg_mult = max(c for _, c, _ in _segs(arch))
    trips = ([tcfg.microbatches] if tcfg.microbatches > 1 else []) + [seg_mult]
    mult = seg_mult * max(1, tcfg.microbatches)
    coll = roofline.parse_collectives_nested(hlo, trips)
    coll_raw = roofline.parse_collectives(hlo, loop_multiplier=1)

    # Roofline terms from the analytic model (cost_analysis undercounts
    # rolled scan bodies — see roofline.py; HLO raw numbers recorded below).
    from repro.models.transformer import segments_for
    segs = segments_for(arch)
    ana_f = roofline.analytic_flops(arch, shape, segs)
    ana_b = roofline.analytic_bytes(arch, shape, segs, dict(mesh.shape), n_params)
    flops_dev = ana_f["step_total"] / n_chips
    bytes_dev = ana_b["total"]
    terms = roofline.roofline_terms(flops_dev, bytes_dev, coll.total_bytes)
    mf = roofline.model_flops(arch, shape, n_params, n_active)

    rec = {
        "cell": label, "status": "ok",
        "arch": arch_name, "shape": shape_name, "multi_pod": multi_pod,
        "variant": variant, "meta": plan.meta,
        "n_chips": n_chips, "n_params": n_params, "n_active": n_active,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        },
        "cost": {
            "flops_per_dev": flops_dev, "bytes_per_dev": bytes_dev,
            "hlo_flops_raw": float(cost.get("flops", 0.0)),
            "hlo_bytes_raw": float(cost.get("bytes accessed", 0.0)),
            "analytic_flops": ana_f, "analytic_bytes": ana_b,
        },
        "collectives": {
            "bytes_by_kind_scaled": coll.bytes_by_kind,
            "bytes_by_kind_raw": coll_raw.bytes_by_kind,
            "count_by_kind": coll.count_by_kind,
            "total_bytes_scaled": coll.total_bytes,
            "loop_multiplier": mult,
        },
        "roofline": terms,
        "model_flops_global": mf,
        "model_flops_per_dev": mf / n_chips,
        "useful_flops_ratio": (mf / n_chips) / flops_dev if flops_dev else None,
        "timings": {"lower_s": t_lower, "compile_s": t_compile},
        "hlo_bytes": len(hlo),
    }
    _emit(rec, out_dir, arch_name, shape_name, multi_pod, variant)
    print(f"[ok] {label}: dominant={terms['dominant']} "
          f"compute={terms['compute_s']:.4f}s memory={terms['memory_s']:.4f}s "
          f"collective={terms['collective_s']:.4f}s "
          f"useful={rec['useful_flops_ratio'] and round(rec['useful_flops_ratio'],3)} "
          f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
    return rec


def _emit(rec, out_dir, arch_name, shape_name, multi_pod, variant):
    if not out_dir:
        return
    os.makedirs(out_dir, exist_ok=True)
    vtag = ("__" + "_".join(f"{k}-{v}" for k, v in sorted(variant.items()))) \
        if variant else ""
    fname = f"{arch_name}__{shape_name}__{'mp' if multi_pod else 'sp'}{vtag}.json"
    with open(os.path.join(out_dir, fname.replace('/', '-')), "w") as f:
        json.dump(rec, f, indent=1, default=str)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--variant", default=None,
                    help="JSON dict of ArchConfig / ShardCtx overrides")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    variant = json.loads(args.variant) if args.variant else None
    cells: list[tuple[str, str, bool]] = []
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    if args.all:
        for a in ARCHS:
            for s in SHAPES:
                for mp in meshes:
                    cells.append((a, s, mp))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        for mp in meshes:
            cells.append((args.arch, args.shape, mp))

    failures = []
    for a, s, mp in cells:
        if args.skip_existing and args.out:
            vtag = ("__" + "_".join(f"{k}-{v}" for k, v in sorted((variant or {}).items())))\
                if variant else ""
            f = os.path.join(args.out,
                             f"{a}__{s}__{'mp' if mp else 'sp'}{vtag}.json")
            if os.path.exists(f):
                print(f"[cached] {a} x {s} x {'mp' if mp else 'sp'}")
                continue
        try:
            run_cell(a, s, mp, variant, args.out)
        except Exception as e:
            failures.append((a, s, mp, repr(e)))
            print(f"[FAIL] {a} x {s} x {'mp' if mp else 'sp'}: {e}")
            traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("\nall cells OK")


if __name__ == "__main__":
    main()
