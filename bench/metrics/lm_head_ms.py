"""Device time per decode tick of the head: the self time of the ops under
the ``lm_head`` scope (final norm and the vocabulary projection) in
``_decode_block_dev``, over its ticks.  Reads ``bench.trace_scopes``'
reduction."""

from bench.trace_scopes import decode_block_ms


def read(run):
    return decode_block_ms(run, ("lm_head",))
