"""Share of decode lanes that produced a token, over the traced window:
decode tokens / (decode blocks x ticks per block x slots), from the
engine's own counters."""


def read(run):
    st, eng = run["stats"], run["engine"]
    lanes = st["decode_blocks"] * eng["decode_block"] * eng["batch_slots"]
    if lanes <= 0:
        return None
    return 100.0 * st["decode_tokens"] / lanes
