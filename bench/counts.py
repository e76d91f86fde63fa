"""Operations and least bytes of the work the model defines, from its shapes.

Counted whatever implements it: ternary weights at 8 bits per 5 weights
(the packed base-3 format), the embedding and head at their stored dtype
(float32), the live KV read and the KV written at the cache's dtype
(bfloat16).  A multiply-add is two operations.  The int8 peak is the one to
set these operations against: the model's linears are W1.58A8.

Each count is the configuration's architecture's (``bench/archs``, found
by the configuration's ``reference``), which fills in this contract:

* ``weight_pass_bytes(cfg)``: the bytes one pass over the weights must
  read, which every decode tick makes;
* ``decode_token(cfg, context)``: ``(operations, bytes besides the
  weights)`` of one decoded token at ``context`` positions.  The bytes are
  all the per-sequence state the token must read and write: the live KV it
  attends and its own KV written, and any recurrent state the architecture
  carries from token to token;
* ``prompt_ops(cfg, length)``: the operations of a whole prompt of
  ``length`` tokens, with the head once, for the last token's logits.
"""

from __future__ import annotations

from bench.archs import arch

F32, BF16 = 4, 2


def weight_pass_bytes(cfg: dict) -> float:
    return arch(cfg).weight_pass_bytes(cfg)


def decode_token(cfg: dict, context: int) -> tuple:
    return arch(cfg).decode_token(cfg, context)


def prompt_ops(cfg: dict, length: int) -> int:
    return arch(cfg).prompt_ops(cfg, length)
