"""Plain float32 reference of a DeepSeek-V3-block training step.

Written from the architecture's published description (arXiv 2412.19437,
``model_type`` deepseek_v3), in ``jax.numpy`` at ``precision="highest"``,
one sequence at a time:

* RMSNorm; multi-head latent attention: queries ``x W_q`` (or through a
  normed latent where ``q_lora_rank`` is set), a normed key-value latent
  ``c = RMSNorm(x W_dkv)``, ``k = [c W_uk | RoPE(x W_kr)]`` (one rope key
  shared by the heads), ``v = c W_uv``, causal softmax at 1/sqrt(qk width);
* the leading dense layers' SwiGLU;
* a mixture of experts whose router is ``router_width`` wide: sigmoid
  scores, the top-k of scores plus the selection bias, gates from the
  unbiased scores normalised over the k and scaled by
  ``routed_scaling_factor``; per expert the first ``capacity`` assignments
  in (sequence, position, k) order over all ``router_width`` experts are
  kept.  The ``n_routed_experts`` held here (the first of the router's) are
  each applied to every token and weighted by its kept gate, so no
  dispatch buffer stands between the reference and the equations; the
  shared experts are applied to every token;
* the untied head over the vocabulary as configured.

The loss is the mean next-token cross-entropy plus each MoE layer's
sequence-wise balance term ``alpha * sum_i f_i P_i`` (``f_i`` from the
experts each token is routed to, ``P_i`` the mean of its normalised
score).  AdamW with global-norm clipping, linear warm-up and a cosine
schedule follows, leaving the selection biases alone; each bias then moves
by ``bias_update_speed * sign(mean load - load)`` with the batch's loads
before capacity.

Parameters are made here from the seed by the same recipe of
``jax.random`` splits as the trainer's initializer and held in the same
tree layout, bias included, so that the two can be compared leaf by leaf.

``mode="fp8"`` is the control, the usual float8 training recipe: every
matmul operand is rounded to float8 e4m3 and every gradient entering a
matmul of the backward pass to float8 e5m2, each with a per-tensor scale.
``omit`` leaves parts out, as a program that lost them would: ``"shared"``
(the shared experts), ``"balance"`` (the balance term), ``"bias_rule"``
(the bias never moves).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def dims(cfg: dict) -> dict:
    dense = cfg["first_k_dense_replace"]
    return dict(d=cfg["hidden_size"], h=cfg["num_attention_heads"],
                nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
                vd=cfg["v_head_dim"], r_kv=cfg["kv_lora_rank"], r_q=cfg["q_lora_rank"],
                f_dense=cfg["intermediate_size"], f=cfg["moe_intermediate_size"],
                held=cfg["n_routed_experts"], e=cfg["router_width"],
                k=cfg["num_experts_per_tok"], shared=cfg["n_shared_experts"],
                v=cfg["vocab_size"], dense=dense,
                moe_layers=cfg["num_hidden_layers"] - dense)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def init_params(cfg: dict, key) -> dict:
    """Parameters from a PRNG key, float32, the MoE layers stacked on a
    leading axis."""
    n = dims(cfg)
    d, h = n["d"], n["h"]
    qk = n["nope"] + n["rope"]
    normal = lambda key, shape, scale: jax.random.normal(key, shape, jnp.float32) * scale
    dense = lambda key, a, b, scale=None: {"w": normal(key, (a, b), scale or 1 / math.sqrt(a))}
    ones = lambda m: {"g": jnp.ones((m,), jnp.float32)}
    s_out = 0.02 / math.sqrt(2)
    k_embed, k_head, k_pre, k_scan, _k_suf = jax.random.split(key, 5)

    def attention(key):
        ks = jax.random.split(key, 7)
        if n["r_q"]:
            q = {"wdq": dense(ks[0], d, n["r_q"]), "q_norm": ones(n["r_q"]),
                 "wuq": dense(ks[1], n["r_q"], h * qk)}
        else:
            q = {"wq": dense(ks[0], d, h * qk)}
        return {**q, "wdkv": dense(ks[2], d, n["r_kv"]), "kv_norm": ones(n["r_kv"]),
                "wuk": dense(ks[3], n["r_kv"], h * n["nope"]),
                "wuv": dense(ks[4], n["r_kv"], h * n["vd"]),
                "wkr": dense(ks[5], d, n["rope"]),
                "wo": dense(ks[6], h * n["vd"], d, s_out)}

    def swiglu(key, f):
        k1, k2, k3 = jax.random.split(key, 3)
        return {"wi": dense(k1, d, f), "wg": dense(k2, d, f), "wo": dense(k3, f, d, s_out)}

    def experts(key):
        kr, ki, kg, ko, ks = jax.random.split(key, 5)
        e, f = n["held"], n["f"]
        return {"router": dense(kr, d, n["e"], 0.02),
                "wi": normal(ki, (e, d, f), 1 / math.sqrt(d)),
                "wg": normal(kg, (e, d, f), 1 / math.sqrt(d)),
                "wo": normal(ko, (e, f, d), s_out),
                "bias": jnp.zeros((n["e"],), jnp.float32),
                "shared": swiglu(ks, n["shared"] * f)}

    def layer(key, moe):
        k_attn, k_ffn, _, _ = jax.random.split(key, 4)
        return {"norm1": ones(d), "mixer": attention(k_attn), "norm2": ones(d),
                "ffn": experts(k_ffn) if moe else swiglu(k_ffn, n["f_dense"])}

    prefix = tuple(layer(k, False)
                   for k in jax.random.split(k_pre, max(n["dense"], 1))[: n["dense"]])
    scan = [layer(jax.random.split(k, 1)[0], True)
            for k in jax.random.split(k_scan, n["moe_layers"])]
    return {
        "embed": {"table": normal(k_embed, (n["v"], d), 0.02)},
        "prefix": prefix,
        "scan": (jax.tree.map(lambda *xs: jnp.stack(xs), *scan),),
        "suffix": (),
        "final_norm": ones(d),
        "lm_head": dense(k_head, d, n["v"], 0.02),
    }


def is_bias(name: str) -> bool:
    return name.endswith("['ffn']['bias']")


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
    """Rotary positions on (s, heads, dim), rotating the two halves."""
    s, _, hd = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _attention(cfg, mode, p, x, q_block):
    n = dims(cfg)
    s, h = x.shape[0], n["h"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    lin = lambda a, w: _ein(mode, "sd,dn->sn", a, w["w"])
    if n["r_q"]:
        q = lin(_rms(lin(x, p["wdq"]), p["q_norm"]["g"], eps), p["wuq"])
    else:
        q = lin(x, p["wq"])
    q = q.reshape(s, h, n["nope"] + n["rope"])
    c = _rms(lin(x, p["wdkv"]), p["kv_norm"]["g"], eps)
    k_nope = lin(c, p["wuk"]).reshape(s, h, n["nope"])
    v = lin(c, p["wuv"]).reshape(s, h, n["vd"])
    k_rope = _rope(lin(x, p["wkr"])[:, None, :], theta)
    q = jnp.concatenate([q[..., : n["nope"]], _rope(q[..., n["nope"]:], theta)], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (s, h, n["rope"]))], axis=-1)
    scale = 1.0 / math.sqrt(n["nope"] + n["rope"])
    nb = s // q_block

    @jax.checkpoint
    def block(args):
        i, qb = args
        scores = _ein(mode, "qhd,khd->hqk", qb, k) * scale
        qpos = i * q_block + jnp.arange(q_block)
        mask = qpos[:, None] >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
        return _ein(mode, "hqk,khd->qhd", probs, v)

    out = jax.lax.map(block, (jnp.arange(nb), q.reshape(nb, q_block, h, -1)))
    return lin(out.reshape(s, h * n["vd"]), p["wo"])


def _swiglu(mode, p, x):
    lin = lambda a, w: _ein(mode, "sd,dn->sn", a, w["w"])
    return lin(jax.nn.silu(lin(x, p["wg"])) * lin(x, p["wi"]), p["wo"])


def _moe(cfg, mode, omit, p, x, offsets, capacity):
    """Routed and shared experts for one sequence.  ``offsets[e]`` counts
    the assignments to expert ``e`` in the sequences before this one.
    Returns (output, assignments per expert, balance term)."""
    n = dims(cfg)
    t, e, k = x.shape[0], n["e"], n["k"]
    scores = jax.nn.sigmoid(_ein(mode, "td,de->te", x, p["router"]["w"]))
    _, idx = jax.lax.top_k(scores + p["bias"], k)
    gate = jnp.take_along_axis(scores, idx, axis=-1)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    gate = gate * cfg["routed_scaling_factor"]
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)                # (t, k, e)
    flat = onehot.reshape(t * k, e)
    rank = (jnp.cumsum(flat, axis=0) * flat).max(-1).reshape(t, k)
    keep = offsets[idx] + rank - 1 < capacity
    # the held experts are the router's first n_routed_experts
    weight = jnp.einsum("tke,tk->te", onehot[..., : n["held"]].astype(jnp.float32),
                        gate * keep)
    hi = _ein(mode, "td,edf->tef", x, p["wi"])
    hg = _ein(mode, "td,edf->tef", x, p["wg"])
    act = jax.nn.silu(hg) * hi * weight[..., None]
    out = _ein(mode, "tef,efd->td", act, p["wo"])
    if "shared" not in omit:
        out = out + _swiglu(mode, p["shared"], x)
    counts = flat.sum(0)
    f = counts.astype(jnp.float32) * (e / (k * t))
    share = (scores / scores.sum(-1, keepdims=True)).mean(0)
    alpha = 0.0 if "balance" in omit or not cfg["seq_aux"] else cfg["aux_loss_alpha"]
    return out, counts, alpha * jnp.sum(f * share)


def row_loss(cfg: dict, mode: str, omit: frozenset, capacity: int, q_block: int,
             params, tokens, labels, offsets):
    """(loss of one sequence: mean next-token loss plus its balance terms,
    assignments per MoE layer and expert).  ``offsets`` is (layers,
    router width)."""
    eps = cfg["rms_norm_eps"]
    x = params["embed"]["table"][tokens]

    def attend(p, x):
        return x + _attention(cfg, mode, p["mixer"], _rms(x, p["norm1"]["g"], eps), q_block)

    @jax.checkpoint
    def dense_layer(p, x):
        x = attend(p, x)
        return x + _swiglu(mode, p["ffn"], _rms(x, p["norm2"]["g"], eps))

    @jax.checkpoint
    def moe_layer(x, args):
        p, off = args
        x = attend(p, x)
        y, counts, balance = _moe(cfg, mode, omit, p["ffn"], _rms(x, p["norm2"]["g"], eps),
                                  off, capacity)
        return x + y, (counts, balance)

    for p in params["prefix"]:
        x = dense_layer(p, x)
    x, (counts, balance) = jax.lax.scan(moe_layer, x, (params["scan"][0], offsets))
    x = _rms(x, params["final_norm"]["g"], eps)
    logits = _ein(mode, "sd,dv->sv", x, params["lm_head"]["w"])
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, labels[:, None], axis=-1)[:, 0]
    return nll.mean() + balance.sum(), counts


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

    ``mode`` is ``"f32"`` for the reference and ``"fp8"`` for the control;
    ``omit`` names parts left out (see the module's docstring).
    ``capacity_rows`` is the number of sequences the expert capacity is
    reckoned over: the whole batch of the step being followed."""

    def __init__(self, cfg: dict, opt: dict, seq_len: int, capacity_rows: int,
                 mode: str = "f32", q_block: int = 512, omit=frozenset()):
        n = dims(cfg)
        self.cfg, self.opt, self.n = cfg, opt, n
        self.omit = frozenset(omit)
        capacity = max(1, int(cfg["capacity_factor"] * n["k"] * capacity_rows
                              * seq_len / n["e"]))
        q_block = min(q_block, seq_len)
        grad = jax.value_and_grad(partial(row_loss, cfg, mode, self.omit, capacity, q_block),
                                  has_aux=True)

        def grad_add(params, acc, tokens, labels, offsets):
            (loss, counts), g = grad(params, tokens, labels, offsets)
            return loss, counts, jax.tree.map(jnp.add, acc, g)

        self._init = jax.jit(partial(init_params, cfg))
        self._grad_add = jax.jit(grad_add, donate_argnums=1)
        self._adamw = jax.jit(self._adamw_step, donate_argnums=(0, 2, 3))
        self._change = jax.jit(lambda a, b: [jnp.linalg.norm((x - y).ravel()) for x, y
                                             in zip(jax.tree.leaves(a), jax.tree.leaves(b))])

    def _adamw_step(self, params, grads, m, v, step, rows, loads):
        opt = self.opt
        gamma = 0.0 if "bias_rule" in self.omit else self.cfg["bias_update_speed"]
        grads = jax.tree.map(lambda g: g / rows, grads)
        gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        clip = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-9))
        g = jax.tree.map(lambda x: x * clip, grads)
        stepf = step.astype(jnp.float32)
        b1c, b2c = 1 - opt["b1"] ** stepf, 1 - opt["b2"] ** stepf
        lr = _lr(opt, stepf)

        def update(path, p_, g_, m_, v_):
            if is_bias(jax.tree_util.keystr(path)):
                # the selection bias: untouched by AdamW, moved by the loads
                return (p_ + gamma * jnp.sign(loads.mean(-1, keepdims=True) - loads), m_, v_)
            m_ = opt["b1"] * m_ + (1 - opt["b1"]) * g_
            v_ = opt["b2"] * v_ + (1 - opt["b2"]) * g_ * g_
            p_ = p_ - lr * ((m_ / b1c) / (jnp.sqrt(v_ / b2c) + opt["eps"])
                            + opt["weight_decay"] * p_)
            return p_, m_, v_

        out = jax.tree_util.tree_map_with_path(update, params, g, m, v)
        pick = lambda i: jax.tree.map(lambda _, o: o[i], params, out)
        return pick(0), pick(1), pick(2), [jnp.linalg.norm(x.ravel())
                                           for x in jax.tree.leaves(g)]

    def train(self, seed: int, batches: list[dict], rows: int | None = None) -> dict:
        """Follow one step per batch from the seed's parameters.

        Returns the loss of each step before its update, the norm of each
        leaf's clipped first gradient, the norm of each leaf's change over
        all the steps and each selection bias after them.  ``rows`` keeps
        the first ``rows`` sequences of each batch: the loss and gradient
        are then their mean."""
        n = self.n
        rows = batches[0]["tokens"].shape[0] if rows is None else rows
        with jax.default_matmul_precision("highest"):
            params = self._init(jax.random.PRNGKey(seed))
            # the first parameters and Adam's moments wait on the host: the
            # device holds the parameters, the gradient sum and one row's work
            p0 = jax.device_get(params)
            m = jax.tree.map(np.zeros_like, p0)
            v = jax.tree.map(np.zeros_like, p0)
            losses, grad_norms = [], None
            for step, bt in enumerate(batches, start=1):
                offsets = jnp.zeros((n["moe_layers"], n["e"]), jnp.int32)
                acc, total = jax.tree.map(jnp.zeros_like, params), 0.0
                for r in range(rows):
                    loss, counts, acc = self._grad_add(
                        params, acc, jnp.asarray(bt["tokens"][r]),
                        jnp.asarray(bt["labels"][r]), offsets)
                    offsets = offsets + counts
                    total += float(loss)
                losses.append(total / rows)
                params, m, v, norms = self._adamw(
                    params, acc, jax.device_put(m), jax.device_put(v), jnp.int32(step),
                    jnp.float32(rows), offsets.astype(jnp.float32))
                del acc
                m, v = jax.device_get((m, v))
                if grad_norms is None:
                    grad_norms = [float(x) for x in norms]
            del m, v
            change_norms = [float(x) for x in self._change(params, p0)]
        names = leaf_names(params)
        return {"losses": losses,
                "grad_norms": dict(zip(names, grad_norms)),
                "change_norms": dict(zip(names, change_norms)),
                "biases": {k: np.asarray(x) for k, x in zip(names, jax.tree.leaves(params))
                           if is_bias(k)}}
