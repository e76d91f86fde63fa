"""Device time per fused decode block of the weight expansion that opens
it: the self time of the ops under the ``predecode`` scope
(``transformer.predecode_packed``) in ``_decode_block_dev``, over its
executions.  Reads ``bench.trace_scopes``' reduction."""

from bench.trace_scopes import decode_block_ms


def read(run):
    return decode_block_ms(run, ("predecode",), per_tick=False)
