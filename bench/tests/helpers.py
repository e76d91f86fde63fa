"""A cell at test size, for running the harness on the CPU."""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

CLOSED = {"loop": "closed", "clients": 2,
          "prompt": {"dist": "uniform", "lo": 8, "hi": 40},
          "output": {"dist": "uniform", "lo": 4, "hi": 12}, "block": 8}
OPEN = {"loop": "open", "rate_per_s": 20.0,
        "prompt": {"dist": "lognormal", "median": 16, "sigma": 0.8,
                   "lo": 4, "hi": 48},
        "output": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                   "lo": 2, "hi": 14}, "block": 8}
CONTIGUOUS = {"batch_slots": 2, "max_seq": 64, "paged": False,
              "prefill_chunk": 16, "decode_block": 4}
PAGED = {"batch_slots": 4, "max_seq": 64, "paged": True, "page_size": 8,
         "prefill_chunk": 16, "decode_block": 4}
# the limit of max_logit_gap for the tiny model on the CPU, set as the
# benchmark's limits are: sound runs read at most 0.038 over 16 seeds (the
# bfloat16 KV cache flips an int8 step now and then), the int4 control at
# least 0.37
TINY_LIMIT = 0.12


def tiny_cfg() -> dict:
    with open(os.path.join(HERE, "tiny.json")) as f:
        return json.load(f)


def tiny_spec(mix=CLOSED, engine=CONTIGUOUS) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    return {"workload": {"chips": 1}, "cfg": tiny_cfg(), "mix": dict(mix),
            "cell": {"engine": dict(engine),
                     "check": {"sample": 3, "min_tokens": 8},
                     "limits": {"max_logit_gap": TINY_LIMIT}},
            "end_to_end": bm["end_to_end"], "per_layer": bm["per_layer"],
            "peaks": {"int8_ops": 1e12, "hbm_bytes_s": 1e11}}
