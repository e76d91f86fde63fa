"""Operations and least bytes of the work the model defines, from its shapes.

Counted whatever implements it: ternary weights at 8 bits per 5 weights
(the packed base-3 format), the embedding and head at their stored dtype
(float32), the live KV read and the KV written at the cache's dtype
(bfloat16).  A multiply-add is two operations.  The int8 peak is the one to
set these operations against: the model's linears are W1.58A8.
"""

from __future__ import annotations

F32, BF16 = 4, 2


def linear_weights(cfg: dict) -> int:
    """Ternary weights of all decoder linears."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    per_layer = d * (q + 2 * kv) + q * d + 3 * d * ff
    return cfg["num_hidden_layers"] * per_layer


def packed_weight_bytes(cfg: dict) -> float:
    """One byte holds ``pack_group`` (5) ternary weights."""
    return linear_weights(cfg) / cfg["pack_group"]


def head_bytes(cfg: dict) -> int:
    return cfg["vocab_size"] * cfg["hidden_size"] * F32


def norm_bytes(cfg: dict) -> int:
    return (2 * cfg["num_hidden_layers"] + 1) * cfg["hidden_size"] * F32


def weight_pass_bytes(cfg: dict) -> float:
    """Bytes one pass over the weights must read: every decode tick."""
    return packed_weight_bytes(cfg) + head_bytes(cfg) + norm_bytes(cfg)


def kv_bytes_per_token(cfg: dict) -> int:
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return cfg["num_hidden_layers"] * 2 * kv * BF16


def attention_ops(cfg: dict, context: int) -> int:
    """QK and PV of one query over ``context`` keys, all layers."""
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    return cfg["num_hidden_layers"] * 4 * context * q


def token_ops(cfg: dict, context: int, head: bool = True) -> int:
    """One token through the model, attending ``context`` positions."""
    ops = 2 * linear_weights(cfg) + attention_ops(cfg, context)
    if head:
        ops += 2 * cfg["hidden_size"] * cfg["vocab_size"]
    return ops


def decode_token(cfg: dict, context: int) -> tuple:
    """(ops, bytes besides the weights) of one decoded token whose query
    attends ``context`` positions: it reads their KV and writes its own."""
    kv = kv_bytes_per_token(cfg)
    return token_ops(cfg, context), context * kv + kv


def prompt_ops(cfg: dict, length: int) -> int:
    """A whole prompt: every token through the decoder, attending its
    causal prefix, and the head once, for the last token's logits."""
    ops = length * 2 * linear_weights(cfg)
    ops += attention_ops(cfg, length * (length + 1) // 2)
    return ops + 2 * cfg["hidden_size"] * cfg["vocab_size"]
