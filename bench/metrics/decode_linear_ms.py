"""Device time per decode tick of the ternary linears: the self time of
the ops under the ``qkv``, ``o_proj``, ``gate_up`` and ``down`` scopes in
``_decode_block_dev``, over its ticks.  Reads ``bench.trace_scopes``'
reduction."""

from bench.trace_scopes import decode_block_ms


def read(run):
    return decode_block_ms(run, ("qkv", "o_proj", "gate_up", "down"))
