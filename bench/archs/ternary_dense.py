"""The dense pre-norm decoder with W1.58A8 linears that
``bench/references/ternary_dense.py`` describes: per layer ternary q/k/v/o
projections with rotary attention over grouped KV heads, and a ternary
SwiGLU MLP of gate, up and down; a final norm and a float32 head.

Its counts follow ``bench/counts.py``'s conventions: ternary weights at 8
bits per ``pack_group`` (5) weights, the embedding, head and norm gains at
float32, the KV cache at bfloat16, a multiply-add as two operations.  Per
sequence it keeps only the KV cache.
"""

from __future__ import annotations

from bench.counts import BF16, F32
from repro.configs.base import ModelConfig


def model_config(cfg: dict) -> ModelConfig:
    """The program's config object for a ``bench/configs`` file."""
    return ModelConfig(
        name=cfg["name"], family="dense", block_kind="attn",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        group_size=cfg["pack_group"])


def fan_in(cfg: dict) -> dict:
    d, q = cfg["hidden_size"], cfg["num_attention_heads"] * cfg["head_dim"]
    return {"q": d, "k": d, "v": d, "o": q, "gate": d, "up": d,
            "down": cfg["intermediate_size"]}


# -- counts ------------------------------------------------------------------

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
