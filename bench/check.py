"""The comparison that decides ``correct``.

Once the window has closed and every request sent in it has finished, a
sample of the finished requests, drawn from the seed and always holding the
one with the longest sequence, is run through the configuration's plain
reference: one whole forward over each prompt and the tokens served for it.
Every request is greedy, so each served token should be the reference's
best up to rounding.  The number compared is the widest gap by which a
served token's logit lies below the reference's best at its position.
Besides it: every request sent in the window finished with all its tokens,
each a valid id, streamed to the client exactly as the engine recorded it;
and the sample holds enough tokens to mean something.
"""

from __future__ import annotations

import importlib

import numpy as np

from repro.serving import RequestStatus


def reference_module(cfg: dict):
    return importlib.import_module(f"bench.references.{cfg['reference']}")


def sample(served: list, n: int, seed: int) -> list:
    """n finished requests drawn from the seed, the longest among them."""
    done = [s for s in served if s.complete]
    if not done:
        return []
    longest = max(done, key=lambda s: (len(s.prompt) + len(s.tokens),
                                       s.index))
    rest = [s for s in done if s is not longest]
    rng = np.random.Generator(np.random.PCG64([seed, 0xC0FFEE]))
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def delivery_faults(served: list, vocab: int) -> int:
    """Requests that did not finish whole and valid."""
    bad = 0
    for s in served:
        r = s.request
        out = [] if r.output is None else list(r.output)
        if (r.status != RequestStatus.OK or not s.complete
                or out != s.tokens or len(out) != s.max_new
                or any(not 0 <= t < vocab for t in out)):
            bad += 1
    return bad


def readings(cfg: dict, weights, picked: list, seq_len: int, n_out: int,
             controls: bool = False) -> dict:
    """The widest gap over the picked requests' served tokens; with
    ``controls``, also the widest gap of each of the reference's controls:
    that of the tokens the control puts first at the same positions."""
    ref_mod = reference_module(cfg)
    ref = ref_mod.Reference(cfg, weights, seq_len, n_out)
    gaps = []
    ctl = {name: [] for name in (ref_mod.CONTROLS if controls else ())}
    for s in picked:
        logits = ref.logits(s.prompt, s.tokens)
        gaps.append(ref_mod.gaps(logits, s.tokens))
        for name in ctl:
            low = ref.logits(s.prompt, s.tokens, **ref_mod.CONTROLS[name])
            ctl[name].append(ref_mod.gaps(logits, low.argmax(axis=-1)))

    def widest(g):
        return float(np.max(np.concatenate(g))) if g else float("inf")

    return {"max_logit_gap": widest(gaps),
            "tokens_compared": int(sum(len(g) for g in gaps)),
            "controls": {name: widest(g) for name, g in ctl.items()}}


def check(cfg: dict, weights, served: list, cell: dict, traffic,
          seed: int, controls: bool = False) -> tuple:
    """``(checks, control_checks)``.  ``checks`` holds each number compared
    with its limit, ``{name: {"value", "limit"}}``, an upper limit except
    for ``tokens_compared``, a lower one.  With ``controls``,
    ``control_checks`` holds for each of the reference's controls the same
    checks with its tokens standing in for the served ones, to be judged
    by ``passed`` as a run is; otherwise it is empty."""
    picked = sample(served, cell["check"]["sample"], seed)
    r = readings(cfg, weights, picked, cell["engine"]["max_seq"],
                 traffic.longest[1], controls=controls)
    checks = {
        "max_logit_gap": {"value": r["max_logit_gap"],
                          "limit": cell["limits"]["max_logit_gap"]},
        "failed_requests": {"value": delivery_faults(served,
                                                     cfg["vocab_size"]),
                            "limit": 0},
        "tokens_compared": {"value": r["tokens_compared"],
                            "limit": cell["check"]["min_tokens"]},
    }
    stand_ins = {name: {**checks, "max_logit_gap": {
        **checks["max_logit_gap"], "value": gap}}
        for name, gap in r["controls"].items()}
    return checks, stand_ins


def passed(checks: dict) -> bool:
    ok = True
    for name, c in checks.items():
        if name == "tokens_compared":
            ok &= c["value"] >= c["limit"]
        else:
            ok &= c["value"] <= c["limit"]
    return bool(ok)
