"""Training entry point.

    PYTHONPATH=src python -m repro.launch.train --arch granite-moe-3b-a800m \
        --sync geococo --steps 100

The mesh defaults to ``default_mesh_shape(jax.device_count())``.  Under
``JAX_PLATFORMS=cpu`` the host is split into as many virtual devices as
``--mesh`` asks for (8 without ``--mesh``); use ``--smoke`` (reduced
config) there.
"""

from __future__ import annotations

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mesh", default=None,
                    help="pod,data,model sizes (product = device count); "
                         "default: derived from the device count")
    ap.add_argument("--sync", default="hier",
                    help="registered device_sync strategy (flat/hier/geococo/"
                         "...); validated against the registry once jax is up")
    ap.add_argument("--density", type=float, default=0.10)
    ap.add_argument("--control", action="store_true",
                    help="attach a repro.control ControlPlane: a monitored "
                         "inter-pod latency trace drives relay_psum ring "
                         "order + replans through typed network events")
    ap.add_argument("--control-noise", type=float, default=0.10,
                    help="probe noise sigma for the monitored view")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import math

    from .compile_cache import enable_compile_cache
    from .mesh import default_mesh, make_mesh, split_cpu_host

    enable_compile_cache()
    shape = tuple(int(x) for x in args.mesh.split(",")) if args.mesh else None
    split_cpu_host(math.prod(shape) if shape else 8)

    from ..configs.registry import get_config, get_smoke_config
    from ..data.pipeline import DataConfig
    from ..dist.collectives import SyncConfig
    from ..optim.adamw import AdamWConfig
    from ..train.train_step import TrainConfig
    from ..train.trainer import Trainer, TrainerConfig

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if shape:
        mesh = make_mesh(shape, ("pod", "data", "model")[-len(shape):])
    else:
        mesh = default_mesh()
    tcfg = TrainConfig(
        sync=SyncConfig(strategy=args.sync, density=args.density,
                        chunk=2048, min_leaf_size=4096),
        optim=AdamWConfig(lr=args.lr, total_steps=args.steps,
                          warmup_steps=max(args.steps // 20, 5)),
    )
    run_cfg = TrainerConfig(
        steps=args.steps, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, seed=args.seed,
    )
    data_cfg = DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq_len,
        global_batch=args.global_batch, seed=args.seed,
    )
    control = None
    n_pods = dict(mesh.shape).get("pod", 1)
    if args.control and n_pods > 1:
        import numpy as np

        from ..control import ControlPlane, MonitorView, TraceView
        from ..core.latency import aws_latency_matrix, jitter_trace

        # inter-pod WAN: the first n_pods AWS-style regions under jitter,
        # observed through full-mesh EWMA probing (not ground truth)
        base = aws_latency_matrix()[:n_pods, :n_pods]
        trace = jitter_trace(base, max(args.steps, 2),
                             np.random.default_rng(args.seed))
        view = MonitorView(TraceView(trace), noise=args.control_noise,
                           rng=np.random.default_rng(args.seed + 1))
        control = ControlPlane(view)
    trainer = Trainer(cfg, mesh, tcfg, run_cfg, data_cfg, control=control)
    if trainer.maybe_resume():
        print(f"resumed from step {trainer.step_idx}")
    hist = trainer.run()
    print(
        f"done: loss {hist[0]['loss']:.4f} -> {hist[-1]['loss']:.4f} "
        f"over {len(hist)} steps"
    )
    if control is not None:
        print(
            f"control plane: {control.round} rounds, "
            f"{control.replan_count} replans, relay order "
            f"{control.relay_order}, events {control.event_counts()}, "
            f"probe traffic {control.probe_bytes} B; "
            f"step rebuilds {trainer.sync_rebuilds}"
        )


if __name__ == "__main__":
    main()
