"""Device time per decode tick of attention: the self time of the ops
under the ``attention`` scope (rope, the KV write and the attention
kernel) in ``_decode_block_dev``, over its ticks.  Reads
``bench.trace_scopes``' reduction."""

from bench.trace_scopes import decode_block_ms


def read(run):
    return decode_block_ms(run, ("attention",))
