"""Device time of one decode tick: the device seconds of the fused decode
block program (``_decode_block_dev``) over the ticks it ran."""

from bench.metrics import module


def read(run):
    m = module(run, "_decode_block_dev")
    if m is None:
        return None
    return 1e3 * m["seconds"] / (m["count"] * run["engine"]["decode_block"])
