"""The whole step's share of the chip's int8 peak over the traced window:
the model's operations for the prompts whose first token arrived and the
tokens decoded while the trace ran, over (traced window x int8 peak)."""

from bench import counts


def read(run):
    cfg, s = run["cfg"], run["summary"]
    if s["window_s"] <= 0:
        return None
    ops = sum(counts.decode_token(cfg, c)[0] for c in run["decode_contexts"])
    ops += sum(counts.prompt_ops(cfg, n) for n in run["prompt_lengths"])
    if ops == 0:
        return None
    return 100.0 * ops / (s["window_s"] * run["peaks"]["int8_ops"])
