"""Model FLOPs that one training token requires, from a configuration's shapes.

Counted once per token, forward and backward (x 3 of the forward):

* 2 x the active matmul parameters: query, key, value and output
  projections, the router, the ``num_experts_per_tok`` routed experts (three
  matrices each) or a dense SwiGLU, and the unembedding;
* causal attention: the scores and the weighted sum over the keys at or
  before each position, so (seq_len + 1) / 2 keys on average.

Capacity padding, recomputation under remat and every elementwise
operation are left out: this is the work the model requires, whichever
kernel does it.
"""

from __future__ import annotations


def active_matmul_params(cfg: dict) -> int:
    """Matmul parameters one token passes through in one forward pass."""
    d = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // heads
    attn = d * heads * hd * 2 + d * kv * hd * 2
    if cfg.get("num_local_experts"):
        ffn = (d * cfg["num_local_experts"]
               + cfg["num_experts_per_tok"] * 3 * d * cfg["intermediate_size"])
    else:
        ffn = 3 * d * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (attn + ffn) + d * cfg["vocab_size"]


def attention_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Causal attention, forward and backward, per token."""
    d = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // heads
    forward = 2 * 2 * heads * hd * (seq_len + 1) / 2
    return 3 * forward * cfg["num_hidden_layers"]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    return 6.0 * active_matmul_params(cfg) + attention_flops_per_token(cfg, seq_len)
