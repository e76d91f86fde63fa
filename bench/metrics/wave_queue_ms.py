"""How long an admission wave sat in the device queue: the mean, over the
waves that began in the traced window, of the wave program's device start
less the end of the ``engine.wave`` host span that dispatched it.  Reads
``bench.trace_scopes``' reduction (``waves``)."""


def read(run):
    waves = run["summary"].get("waves")
    if not waves:
        return None
    return 1e3 * sum(w["queue_s"] for w in waves) / len(waves)
