"""Device time of one admission wave: the device seconds of the
``_prefill_chunks`` program in the trace over the waves it ran."""

from bench.metrics import module


def read(run):
    m = module(run, "_prefill_chunks")
    if m is None:
        return None
    return 1e3 * m["seconds"] / m["count"]
