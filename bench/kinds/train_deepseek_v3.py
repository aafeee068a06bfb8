"""A training cell of a DeepSeek-V3-block model (``model_type`` deepseek_v3).

It is ``train.py``'s cell: the same set-up through ``Trainer.run``, window,
host spans and check against the plain reference, run from a module
instance of ``train.py`` of its own (``harness.load_module``), in which
``model_config`` maps a deepseek_v3 config file to the program's
``ModelConfig``, ``flops`` counts this block's model FLOPs, and
``numbers`` adds ``bias_gap`` to the compared numbers.  ``train`` is that
instance.
"""

from __future__ import annotations

import dataclasses
import math
import types

from bench import harness


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a deepseek_v3 config file: the
    leading dense layers, then MLA + MoE layers holding ``n_routed_experts``
    of a router ``router_width`` wide."""
    from repro.configs.base import Block, MLAConfig, MoEConfig
    from repro.configs.registry import get_config

    prog = cfg["program"]
    unmodelled = {"n_group": 1, "topk_group": 1, "moe_layer_freq": 1,
                  "num_nextn_predict_layers": 0, "hidden_act": "silu",
                  "attention_bias": False, "norm_topk_prob": True,
                  "topk_method": "noaux_tc", "scoring_func": "sigmoid",
                  "model_type": "deepseek_v3"}
    for key, value in unmodelled.items():
        if cfg[key] != value:
            raise ValueError(f"the program models {key} = {value!r} only, "
                             f"the config states {cfg[key]!r}")
    dense = cfg["first_k_dense_replace"]
    width = cfg["moe_intermediate_size"]
    return dataclasses.replace(
        get_config(prog["arch"]),
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        norm_eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=cfg["tie_word_embeddings"], remat=prog["remat"],
        blocks_prefix=(Block("mla", "dense"),) * dense,
        blocks_pattern=(Block("mla", "moe"),),
        moe=MoEConfig(n_experts=cfg["router_width"], top_k=cfg["num_experts_per_tok"],
                      d_expert=width, n_shared=cfg["n_shared_experts"], d_shared=width,
                      capacity_factor=cfg["capacity_factor"], scoring="sigmoid",
                      routed_scale=cfg["routed_scaling_factor"],
                      n_held=cfg["n_routed_experts"],
                      balance_alpha=cfg["aux_loss_alpha"] if cfg["seq_aux"] else 0.0,
                      bias_gamma=cfg["bias_update_speed"]),
        mla=MLAConfig(q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
                      qk_nope_head_dim=cfg["qk_nope_head_dim"],
                      qk_rope_head_dim=cfg["qk_rope_head_dim"],
                      v_head_dim=cfg["v_head_dim"]),
    )


def active_matmul_params(cfg: dict) -> float:
    """Matmul parameters one token passes through in one forward pass: MLA's
    projections, the dense layers' SwiGLU, each MoE layer's router, shared
    experts and routed experts at the share held here (top-k x held /
    router width assignments a token), and the unembedding."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    r_kv, v = cfg["kv_lora_rank"], cfg["v_head_dim"]
    q = d * h * qk if not cfg["q_lora_rank"] else (
        d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * h * qk)
    mla = q + d * (r_kv + cfg["qk_rope_head_dim"]) + r_kv * h * (
        cfg["qk_nope_head_dim"] + v) + h * v * d
    dense = cfg["first_k_dense_replace"]
    moe_layers = cfg["num_hidden_layers"] - dense
    expert = 3 * d * cfg["moe_intermediate_size"]
    routed = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / cfg["router_width"]
    moe = d * cfg["router_width"] + (cfg["n_shared_experts"] + routed) * expert
    return (cfg["num_hidden_layers"] * mla + dense * 3 * d * cfg["intermediate_size"]
            + moe_layers * moe + d * cfg["vocab_size"])


def attention_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Causal attention, forward and backward, per token: scores at the
    query-key width, the weighted sum at the value width, over
    (seq_len + 1) / 2 keys on average."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    forward = 2 * cfg["num_attention_heads"] * (qk + cfg["v_head_dim"]) * (seq_len + 1) / 2
    return 3 * forward * cfg["num_hidden_layers"]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    return 6.0 * active_matmul_params(cfg) + attention_flops_per_token(cfg, seq_len)


def is_bias(leaf: str) -> bool:
    return leaf.endswith("['ffn']['bias']")


def numbers(side: dict, ref: dict) -> dict[str, float]:
    """``train.py``'s compared numbers and ``bias_gap``: the worst MoE
    block's |norm of its selection bias's change over the checked steps -
    the reference's| / the reference's.  The bias has no gradient and moves
    by its own rule, so ``update_gap``, which leaves out the leaves without
    a gradient, does not see it."""
    out = _train_numbers(side, ref)
    ours, theirs = side["change_norms"], ref["change_norms"]
    if set(ours) != set(theirs):
        return {**out, "bias_gap": math.inf}
    return {**out, "bias_gap": max(abs(ours[k] - theirs[k]) / max(theirs[k], 1e-30)
                                   for k in theirs if is_bias(k))}


train = harness.load_module(harness.BENCH_DIR / "kinds" / "train.py")
_train_numbers = train.numbers
train.model_config = model_config
train.flops = types.SimpleNamespace(train_flops_per_token=train_flops_per_token)
train.numbers = numbers
run = train.run
