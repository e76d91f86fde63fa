"""The fused decode block's share of its roofline over the traced window.

The least time of the ticks the block ran: the larger of the model's
operations for the tokens decoded over the int8 peak, and the bytes they
must move over the HBM bandwidth (one pass over the weights per tick, each
token's live KV read and its own KV written), divided by the block's device
time.  The tokens are those the host received while the trace ran."""

from bench import counts
from bench.metrics import module


def read(run):
    m = module(run, "_decode_block_dev")
    ctxs = run["decode_contexts"]
    if m is None or not ctxs:
        return None
    cfg, pk = run["cfg"], run["peaks"]
    ticks = m["count"] * run["engine"]["decode_block"]
    ops = bytes_ = 0.0
    for c in ctxs:
        o, b = counts.decode_token(cfg, c)
        ops += o
        bytes_ += b
    bytes_ += ticks * counts.weight_pass_bytes(cfg)
    least = max(ops / pk["int8_ops"], bytes_ / pk["hbm_bytes_s"])
    return 100.0 * least / m["seconds"]
