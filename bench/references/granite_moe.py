"""Plain float32 reference of a granite-moe training step.

Written from the model's published description, in ``jax.numpy`` at
``precision="highest"``, one sequence at a time: RMSNorm, grouped-query
attention with rotary positions, and a mixture of experts whose top-k
routing keeps, per expert, the first ``capacity`` assignments in
(sequence, position, k) order across the whole batch.  Every expert is
applied to every token and weighted by its gate, so no dispatch buffer
stands between the reference and the equations.  Granite's embedding,
residual, attention and logits multipliers are applied as the
configuration states them.  The loss is the mean next-token cross-entropy;
AdamW with global-norm clipping, linear warm-up and a cosine schedule
follows it.

Parameters are made here from the seed by the same recipe of
``jax.random`` splits as the trainer's initializer and held in the same
tree layout, so that the two can be compared leaf by leaf.

``mode="fp8"`` is the control, the usual float8 training recipe: every
matmul operand is rounded to float8 e4m3 and every gradient entering a
matmul of the backward pass to float8 e5m2, each with a per-tensor scale.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return dict(d=d, h=h, kv=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim") or d // h, f=cfg["intermediate_size"],
                e=cfg["num_local_experts"], k=cfg["num_experts_per_tok"],
                v=cfg["vocab_size"], layers=cfg["num_hidden_layers"])


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def init_params(cfg: dict, key) -> dict:
    """Parameters from a PRNG key, float32, layers stacked on a leading axis."""
    n = dims(cfg)
    d, f, e = n["d"], n["f"], n["e"]
    normal = lambda key, shape, scale: jax.random.normal(key, shape, jnp.float32) * scale
    k_embed, _k_head, _k_pre, k_scan, _k_suf = jax.random.split(key, 5)

    def layer(key):
        (block_key,) = jax.random.split(key, 1)
        k_attn, k_moe, _, _ = jax.random.split(block_key, 4)
        kq, kk, kv, ko = jax.random.split(k_attn, 4)
        kr, ki, kg, kw, _ = jax.random.split(k_moe, 5)
        s_in, s_out = 1.0 / math.sqrt(d), 0.02 / math.sqrt(2)
        return {
            "norm1": {"g": jnp.ones((d,), jnp.float32)},
            "mixer": {
                "wq": {"w": normal(kq, (d, n["h"] * n["hd"]), s_in)},
                "wk": {"w": normal(kk, (d, n["kv"] * n["hd"]), s_in)},
                "wv": {"w": normal(kv, (d, n["kv"] * n["hd"]), s_in)},
                "wo": {"w": normal(ko, (n["h"] * n["hd"], d), s_out)},
            },
            "norm2": {"g": jnp.ones((d,), jnp.float32)},
            "ffn": {
                "router": {"w": normal(kr, (d, e), 0.02)},
                "wi": normal(ki, (e, d, f), s_in),
                "wg": normal(kg, (e, d, f), s_in),
                "wo": normal(kw, (e, f, d), s_out),
            },
        }

    layers = [layer(k) for k in jax.random.split(k_scan, n["layers"])]
    return {
        "embed": {"table": normal(k_embed, (n["v"], d), 0.02)},
        "prefix": (),
        "scan": (jax.tree.map(lambda *xs: jnp.stack(xs), *layers),),
        "suffix": (),
        "final_norm": {"g": jnp.ones((d,), jnp.float32)},
    }


# ---------------------------------------------------------------------------
# forward, one sequence
# ---------------------------------------------------------------------------


def _to_fp8(x, dtype):
    """``x`` rounded to the float8 ``dtype`` under a per-tensor scale."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(jnp.finfo(dtype).max)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8_operand(x):
    return _to_fp8(x, jnp.float8_e4m3fn)


_fp8_operand.defvjp(lambda x: (_fp8_operand(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _fp8_cotangent(y):
    return y


_fp8_cotangent.defvjp(lambda y: (y, None),
                      lambda _, g: (_to_fp8(g, jnp.float8_e5m2),))


def _ein(mode: str, spec: str, a, b):
    """A matmul at ``precision="highest"``; under ``mode="fp8"`` its operands
    are float8 e4m3 and, in the backward pass, the incoming gradient is
    float8 e5m2, each under a per-tensor scale, with float32 accumulation."""
    if mode == "fp8":
        return _fp8_cotangent(jnp.einsum(spec, _fp8_operand(a), _fp8_operand(b),
                                         precision=HIGHEST))
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    s, _, hd = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _attention(cfg, mode, p, x, q_block):
    n = dims(cfg)
    s = x.shape[0]
    q = _ein(mode, "sd,dn->sn", x, p["wq"]["w"]).reshape(s, n["h"], n["hd"])
    k = _ein(mode, "sd,dn->sn", x, p["wk"]["w"]).reshape(s, n["kv"], n["hd"])
    v = _ein(mode, "sd,dn->sn", x, p["wv"]["w"]).reshape(s, n["kv"], n["hd"])
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    group = n["h"] // n["kv"]             # query head i reads key head i // group
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scale = cfg["attention_multiplier"]
    nb = s // q_block

    @jax.checkpoint
    def block(args):
        i, qb = args
        scores = _ein(mode, "qhd,khd->hqk", qb, k) * scale
        qpos = i * q_block + jnp.arange(q_block)
        mask = qpos[:, None] >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
        return _ein(mode, "hqk,khd->qhd", probs, v)

    out = jax.lax.map(block, (jnp.arange(nb), q.reshape(nb, q_block, n["h"], n["hd"])))
    return _ein(mode, "sn,nd->sd", out.reshape(s, n["h"] * n["hd"]), p["wo"]["w"])


def _moe(cfg, mode, p, x, offsets, capacity):
    """Routed experts for one sequence.  ``offsets[e]`` counts the
    assignments to expert ``e`` in the sequences before this one."""
    n = dims(cfg)
    t = x.shape[0]
    probs = jax.nn.softmax(_ein(mode, "td,de->te", x, p["router"]["w"]), axis=-1)
    gate, idx = jax.lax.top_k(probs, n["k"])
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(idx, n["e"], dtype=jnp.int32)          # (t, k, e)
    flat = onehot.reshape(t * n["k"], n["e"])
    rank = (jnp.cumsum(flat, axis=0) * flat).max(-1).reshape(t, n["k"])
    keep = offsets[idx] + rank - 1 < capacity
    weight = jnp.einsum("tke,tk->te", onehot.astype(jnp.float32), gate * keep)
    hi = _ein(mode, "td,edf->tef", x, p["wi"])
    hg = _ein(mode, "td,edf->tef", x, p["wg"])
    act = jax.nn.silu(hg) * hi * weight[..., None]
    return _ein(mode, "tef,efd->td", act, p["wo"]), flat.sum(0)


def row_loss(cfg: dict, mode: str, capacity: int, q_block: int,
             params, tokens, labels, offsets):
    """(mean next-token loss of one sequence, assignments per layer and
    expert).  ``offsets`` is (layers, experts)."""
    eps = cfg["rms_norm_eps"]
    res = cfg["residual_multiplier"]
    x = params["embed"]["table"][tokens] * cfg["embedding_multiplier"]

    @jax.checkpoint
    def layer(x, args):
        p, off = args
        x = x + res * _attention(cfg, mode, p["mixer"], _rms(x, p["norm1"]["g"], eps), q_block)
        y, counts = _moe(cfg, mode, p["ffn"], _rms(x, p["norm2"]["g"], eps), off, capacity)
        return x + res * y, counts

    x, counts = jax.lax.scan(layer, x, (params["scan"][0], offsets))
    x = _rms(x, params["final_norm"]["g"], eps)
    logits = _ein(mode, "sd,vd->sv", x, params["embed"]["table"]) / cfg["logits_scaling"]
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, labels[:, None], axis=-1)[:, 0]
    return nll.mean(), counts


# ---------------------------------------------------------------------------
# training steps
# ---------------------------------------------------------------------------


def leaf_names(tree) -> list[str]:
    return [jax.tree_util.keystr(k) for k, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _lr(opt: dict, step):
    warm = jnp.minimum(step / max(opt["warmup_steps"], 1), 1.0)
    frac = jnp.clip((step - opt["warmup_steps"])
                    / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0, 1.0)
    return opt["lr"] * warm * 0.5 * (1.0 + jnp.cos(jnp.pi * frac))


class Reference:
    """The reference for one configuration, optimizer and batch shape.

    ``mode`` is ``"f32"`` for the reference and ``"fp8"`` for the control.
    ``capacity_rows`` is the number of sequences the expert capacity is
    reckoned over: the whole batch of the step being followed."""

    def __init__(self, cfg: dict, opt: dict, seq_len: int, capacity_rows: int,
                 mode: str = "f32", q_block: int = 512):
        n = dims(cfg)
        self.cfg, self.opt, self.n = cfg, opt, n
        capacity = max(1, int(cfg["capacity_factor"] * n["k"] * capacity_rows
                              * seq_len / n["e"]))
        q_block = min(q_block, seq_len)
        self._init = jax.jit(partial(init_params, cfg))
        self._grad = jax.jit(jax.value_and_grad(
            partial(row_loss, cfg, mode, capacity, q_block), has_aux=True))
        self._add = jax.jit(lambda a, g: jax.tree.map(jnp.add, a, g), donate_argnums=0)
        self._adamw = jax.jit(self._adamw_step, donate_argnums=(0, 2, 3))
        self._change = jax.jit(lambda a, b: [jnp.linalg.norm((x - y).ravel()) for x, y
                                             in zip(jax.tree.leaves(a), jax.tree.leaves(b))])

    def _adamw_step(self, params, grads, m, v, step, rows):
        opt = self.opt
        grads = jax.tree.map(lambda g: g / rows, grads)
        gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        clip = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-9))
        g = jax.tree.map(lambda x: x * clip, grads)
        m = jax.tree.map(lambda m_, g_: opt["b1"] * m_ + (1 - opt["b1"]) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: opt["b2"] * v_ + (1 - opt["b2"]) * g_ * g_, v, g)
        stepf = step.astype(jnp.float32)
        b1c, b2c = 1 - opt["b1"] ** stepf, 1 - opt["b2"] ** stepf
        lr = _lr(opt, stepf)
        params = jax.tree.map(
            lambda p, m_, v_: p - lr * ((m_ / b1c) / (jnp.sqrt(v_ / b2c) + opt["eps"])
                                        + opt["weight_decay"] * p),
            params, m, v)
        return params, m, v, [jnp.linalg.norm(x.ravel()) for x in jax.tree.leaves(g)]

    def train(self, seed: int, batches: list[dict], rows: int | None = None) -> dict:
        """Follow one step per batch from the seed's parameters.

        Returns the loss of each step before its update, the norm of each
        leaf's clipped first gradient and the norm of each leaf's change
        over all the steps.  ``rows`` keeps the first ``rows`` sequences of
        each batch: the loss and gradient are then their mean."""
        n = self.n
        rows = batches[0]["tokens"].shape[0] if rows is None else rows
        with jax.default_matmul_precision("highest"):
            params = self._init(jax.random.PRNGKey(seed))
            p0 = jax.tree.map(jnp.copy, params)
            m = jax.tree.map(jnp.zeros_like, params)
            v = jax.tree.map(jnp.zeros_like, params)
            losses, grad_norms = [], None
            for step, bt in enumerate(batches, start=1):
                offsets = jnp.zeros((n["layers"], n["e"]), jnp.int32)
                acc, total = None, 0.0
                for r in range(rows):
                    (loss, counts), g = self._grad(
                        params, jnp.asarray(bt["tokens"][r]),
                        jnp.asarray(bt["labels"][r]), offsets)
                    offsets = offsets + counts
                    acc = g if acc is None else self._add(acc, g)
                    total += float(loss)
                losses.append(total / rows)
                params, m, v, norms = self._adamw(
                    params, acc, m, v, jnp.int32(step), jnp.float32(rows))
                if grad_norms is None:
                    grad_norms = [float(x) for x in norms]
            change_norms = [float(x) for x in self._change(params, p0)]
        names = leaf_names(params)
        return {"losses": losses,
                "grad_norms": dict(zip(names, grad_norms)),
                "change_norms": dict(zip(names, change_norms))}
