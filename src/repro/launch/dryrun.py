"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes with ShapeDtypeStruct inputs (no allocation).

    PYTHONPATH=src python -m repro.launch.dryrun --arch minitron-8b \
        --shape train_4k --mesh multi --strategy hier
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
        --out results/dryrun

Per cell, records memory_analysis, cost_analysis, and the trip-count-aware
HLO cost model (FLOPs / HBM bytes / per-axis collective link bytes) that
feeds EXPERIMENTS.md §Dry-run and §Roofline.  Failures here are bugs in the
sharding config, not in the models.

Tiers: ``--tier full`` forces 512 host devices (the production meshes; too
heavy for CI, opt-in), ``--tier reduced`` forces 16 devices on the same
axis layout — the CI tier.  ``--smoke`` swaps in the reduced model configs
so a reduced-tier cell compiles in seconds.  The device count is pinned via
XLA_FLAGS *before* jax is imported, so this module must not import jax at
module scope.
"""

import argparse
import json
import os
import time
import traceback

TIER_DEVICES = {"full": 512, "reduced": 16}


def _force_devices(tier: str) -> int:
    """Pin the host device count for ``tier``; must run before jax imports."""
    n = TIER_DEVICES[tier]
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    return n


def run_cell(arch: str, shape_name: str, mesh_kind: str, strategy: str,
             density: float = 0.10, microbatches: int = 8,
             tier: str = "full", smoke: bool = False) -> dict:
    import jax.numpy as jnp
    from ..configs.base import SHAPES
    from ..configs.registry import get_config, get_smoke_config
    from ..dist.collectives import SyncConfig
    from ..launch.hlo_cost import analyze_hlo
    from ..launch.mesh import make_production_mesh
    from ..train.train_step import (
        TrainConfig,
        abstract_cache,
        abstract_opt_state,
        abstract_params,
        abstract_residuals,
        build_serve_step,
        build_train_step,
        input_specs,
    )

    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"),
                                reduced=(tier == "reduced"))
    mesh_shape = dict(mesh.shape)

    # lean dtype policy for the very large models (fits the HBM budget)
    lean = cfg.name in ("deepseek-v3-671b", "llama-3.2-vision-90b")
    tcfg = TrainConfig(
        sync=SyncConfig(strategy=strategy, density=density),
        param_dtype=jnp.bfloat16 if lean else jnp.float32,
        microbatches=microbatches if shape.kind == "train" else 1,
    )

    rec: dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "mesh_shape": mesh_shape, "strategy": strategy, "density": density,
        "tier": tier, "smoke": smoke,
        "kind": shape.kind, "param_dtype": str(tcfg.param_dtype.__name__),
        "microbatches": tcfg.microbatches,
    }
    t0 = time.perf_counter()
    batch = input_specs(cfg, shape)
    if shape.kind == "train":
        make_jit, _ = build_train_step(cfg, mesh, tcfg)
        step = make_jit(batch)
        lowered = step.lower(
            abstract_params(cfg, tcfg.param_dtype),
            abstract_opt_state(cfg, tcfg),
            abstract_residuals(cfg, tcfg, mesh_shape.get("pod", 1)),
            batch,
        )
    elif shape.kind == "prefill":
        make_jit, _ = build_serve_step(cfg, mesh, tcfg, kind="prefill")
        step = make_jit(batch)
        lowered = step.lower(abstract_params(cfg, tcfg.param_dtype), batch)
    else:  # decode
        make_jit, _ = build_serve_step(cfg, mesh, tcfg, kind="decode")
        cache = abstract_cache(cfg, shape)
        step = make_jit(cache, batch)
        lowered = step.lower(abstract_params(cfg, tcfg.param_dtype), cache, batch)
    rec["lower_s"] = round(time.perf_counter() - t0, 2)

    t0 = time.perf_counter()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.perf_counter() - t0, 2)

    ma = compiled.memory_analysis()
    rec["memory"] = {
        "argument_gb": ma.argument_size_in_bytes / 1e9,
        "output_gb": ma.output_size_in_bytes / 1e9,
        "temp_gb": ma.temp_size_in_bytes / 1e9,
        "alias_gb": ma.alias_size_in_bytes / 1e9,
        # donated args alias outputs; peak live ≈ args + temp
        "peak_gb": (ma.argument_size_in_bytes + ma.temp_size_in_bytes) / 1e9,
    }
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):  # old jax: one dict per computation
        ca = ca[0] if ca else {}
    rec["cost_analysis"] = {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
    }
    t0 = time.perf_counter()
    hlo = analyze_hlo(compiled.as_text(), mesh_shape)
    rec["hlo"] = hlo.to_json()
    # compact per-axis summary
    by_axes: dict[str, float] = {}
    for c in hlo.collectives:
        key = "+".join(c["axes"]) or "replica"
        by_axes[key] = by_axes.get(key, 0.0) + c["link_bytes"]
    rec["collective_link_bytes_by_axes"] = by_axes
    rec["analyze_s"] = round(time.perf_counter() - t0, 2)
    return rec


def main():
    from ..configs.base import SHAPES
    from ..configs.registry import ARCHS, cells

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--strategy", default="hier",
                    help="registered device_sync strategy (flat/hier/geococo/"
                         "...); validated against the registry at build time")
    ap.add_argument("--density", type=float, default=0.10)
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--tier", default="full", choices=list(TIER_DEVICES),
                    help="full = 512-device production meshes (opt-in, "
                         "heavy); reduced = 16-device CI tier")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced model configs (CI-speed compiles)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()
    n_dev = _force_devices(args.tier)
    print(f"[tier] {args.tier}: {n_dev} forced host devices"
          + (" (smoke configs)" if args.smoke else ""))

    if args.all:
        todo = cells()
    else:
        if args.arch is None:
            raise SystemExit("need --arch or --all")
        archs = [args.arch]
        todo = [
            (a, s) for a, s in cells(tuple(archs))
            if args.shape is None or s.name == args.shape
        ]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    os.makedirs(args.out, exist_ok=True)
    n_fail = 0
    for arch, shape in todo:
        for mesh_kind in meshes:
            tag = f"{arch}__{shape.name}__{mesh_kind}__{args.strategy}"
            if args.tier != "full":
                tag += f"__{args.tier}"
            path = os.path.join(args.out, tag + ".json")
            if args.skip_existing and os.path.exists(path):
                print(f"[skip] {tag}")
                continue
            print(f"[cell] {tag} ...", flush=True)
            try:
                rec = run_cell(arch, shape.name, mesh_kind, args.strategy,
                               args.density, args.microbatches,
                               tier=args.tier, smoke=args.smoke)
                rec["status"] = "ok"
                print(
                    f"    ok: compile {rec['compile_s']}s  "
                    f"peak {rec['memory']['peak_gb']:.1f} GB/dev  "
                    f"flops {rec['hlo']['flops']:.3e}  "
                    f"coll {rec['collective_link_bytes_by_axes']}", flush=True,
                )
            except Exception as e:
                n_fail += 1
                rec = {
                    "arch": arch, "shape": shape.name, "mesh": mesh_kind,
                    "strategy": args.strategy, "status": "fail",
                    "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-3000:],
                }
                print(f"    FAIL: {type(e).__name__}: {str(e)[:300]}", flush=True)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
    print(f"done; {n_fail} failures")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
