"""End-to-end driver: train a ~100M-parameter model with GeoCoCo sync.

    PYTHONPATH=src python examples/train_100m.py                  # ~100M, 300 steps
    PYTHONPATH=src python examples/train_100m.py --small --steps 40   # CI-sized

Runs on a (pod, data, model) mesh over every device JAX sees -- (2, 2, 2)
on the 8 virtual devices ``JAX_PLATFORMS=cpu`` gets: FSDP+TP inside each
pod (GSPMD) and GeoCoCo's filtered top-k exchange across the pod
(WAN-analogue) boundary, with periodic checkpointing.
"""

import argparse

from repro.launch.mesh import split_cpu_host

split_cpu_host()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--small", action="store_true",
                    help="~20M params / short seq for CI")
    ap.add_argument("--sync", default="geococo",
                    choices=["flat", "hier", "geococo"])
    ap.add_argument("--ckpt-dir", default="/tmp/geococo_train_100m")
    args = ap.parse_args()

    import jax

    from repro.configs.base import Block, ModelConfig
    from repro.data.pipeline import DataConfig
    from repro.dist.collectives import SyncConfig
    from repro.launch.mesh import default_mesh
    from repro.models.model import param_count
    from repro.optim.adamw import AdamWConfig
    from repro.train.train_step import TrainConfig
    from repro.train.trainer import Trainer, TrainerConfig

    if args.small:
        cfg = ModelConfig(
            name="demo-20m", family="dense", n_layers=4, d_model=256,
            n_heads=8, n_kv_heads=4, d_ff=1024, vocab_size=32_000,
            blocks_pattern=(Block("attn", "dense"),),
        )
        seq, gb = 128, 8
    else:
        # ~100M-parameter llama-style model
        cfg = ModelConfig(
            name="demo-100m", family="dense", n_layers=8, d_model=640,
            n_heads=10, n_kv_heads=5, d_ff=2560, vocab_size=32_000,
            blocks_pattern=(Block("attn", "dense"),),
        )
        seq, gb = 256, 8

    print(f"model {cfg.name}: {param_count(cfg)/1e6:.1f}M params; "
          f"devices {jax.device_count()}, sync={args.sync}")
    mesh = default_mesh()
    tcfg = TrainConfig(
        sync=SyncConfig(strategy=args.sync, density=0.10, chunk=2048,
                        min_leaf_size=16_384),
        optim=AdamWConfig(lr=6e-4, total_steps=args.steps, warmup_steps=20),
    )
    run_cfg = TrainerConfig(
        steps=args.steps, ckpt_dir=args.ckpt_dir, ckpt_every=100,
        log_every=10, seed=0,
    )
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                          global_batch=gb, seed=0)
    trainer = Trainer(cfg, mesh, tcfg, run_cfg, data_cfg)
    if trainer.maybe_resume():
        print(f"resumed from checkpoint at step {trainer.step_idx}")
    hist = trainer.run()
    first, last = hist[0]["loss"], hist[-1]["loss"]
    print(f"\nloss {first:.4f} -> {last:.4f} over {len(hist)} steps "
          f"({(1 - last / first):+.1%})")
    assert last < first, "training must reduce the loss"


if __name__ == "__main__":
    main()
