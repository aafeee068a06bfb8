"""Batched serving example: prefill + decode with KV caches on a mesh.

    PYTHONPATH=src python examples/serve_decode.py [--arch rwkv6-7b-smoke]

Serves a reduced-config model on a mesh over every device JAX sees (8
virtual devices under ``JAX_PLATFORMS=cpu``): batch prefill of
mixed prompts, then greedy decode steps, exercising the serve path the
decode_32k / long_500k dry-run cells compile at full scale (KV/ring/state
caches included).
"""

import argparse

from repro.launch.mesh import split_cpu_host

split_cpu_host()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minitron-8b")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--gen-len", type=int, default=16)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.registry import get_smoke_config
    from repro.launch.mesh import default_mesh
    from repro.models.model import forward, init_cache, init_params
    from repro.train.train_step import TrainConfig, build_serve_step

    cfg = get_smoke_config(args.arch)
    mesh = default_mesh()
    tcfg = TrainConfig()
    rng = np.random.default_rng(0)

    params = init_params(cfg, jax.random.PRNGKey(0))
    max_len = args.prompt_len + args.gen_len
    cache = init_cache(cfg, args.batch, max_len, dtype=jnp.float32)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)), jnp.int32
    )

    # prefill (uses the cached forward so decode can continue)
    logits, cache = forward(cfg, params, {"tokens": prompts}, cache=cache,
                            compute_dtype=jnp.float32)
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    print(f"arch={cfg.name}: prefilled {args.batch} x {args.prompt_len} tokens")

    # jitted decode step on the mesh
    make_jit, _ = build_serve_step(cfg, mesh, tcfg, kind="decode")
    batch0 = {"tokens": tok[:, None]}
    if cfg.n_img_tokens:
        batch0["img"] = jnp.asarray(
            rng.normal(size=(args.batch, cfg.n_img_tokens, cfg.d_model)),
            jnp.float32,
        )
    step = make_jit(jax.tree.map(lambda x: x, cache), batch0)

    outs = [tok]
    for t in range(args.gen_len - 1):
        batch_t = dict(batch0, tokens=outs[-1][:, None])
        tok, cache = step(params, cache, batch_t)
        outs.append(tok)
    gen = np.stack([np.asarray(t) for t in outs], axis=1)
    print(f"decoded {gen.shape[1]} steps; sample row: {gen[0].tolist()}")
    assert np.isfinite(gen).all()
    print("serving path OK (prefill -> jitted sharded decode with cache)")


if __name__ == "__main__":
    main()
