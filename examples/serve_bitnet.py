"""Serve a packed ternary model with the device-resident serving loop —
the paper's end-to-end inference story (prefill AND decode first-class,
overlapped rather than serialized).

Six requests with mixed prompt lengths share 3 decode slots.  Admission is
chunked and batched: every pending prompt advances one in-place chunk per
wave, interleaved with fused 4-tick decode blocks, so in-flight lanes never
stall for more than one chunk + one block dispatch.  Decode sampling, cache
writes and done-masking all stay on device; the host syncs once per block.

Run:  PYTHONPATH=src python examples/serve_bitnet.py
"""

import time

import jax
import numpy as np

from repro.configs import get_config
from repro.models import transformer
from repro.serving import Request, ServingEngine

cfg = get_config("bitnet-0.73b").reduced(
    n_layers=2, d_model=128, n_heads=4, d_ff=256, vocab_size=256)
params = transformer.init_params(cfg, jax.random.PRNGKey(0))
packed = transformer.pack_params(cfg, params)

rng = np.random.default_rng(0)
# mixed generation lengths stagger completions, so freed slots are refilled
# while the others are still decoding (genuine mid-flight admission)
requests = [
    Request(prompt=rng.integers(0, cfg.vocab_size, size=plen),
            max_new_tokens=gen)
    for plen, gen in ((8, 16), (24, 6), (16, 12), (40, 16), (12, 8),
                      (32, 14))
]
engine = ServingEngine(cfg, packed, max_seq=64, batch_slots=3,
                       prefill_chunk=16, decode_block=4)
t0 = time.perf_counter()
engine.run(requests)
wall = time.perf_counter() - t0

total = sum(len(r.output) for r in requests)
st = engine.stats
host = st["host_s"]
print(f"served {len(requests)} requests / {total} new tokens "
      f"in {wall:.2f}s -> {total/wall:.1f} tok/s aggregate; host "
      f"{host['block'] + host['readback']:.2f}s dispatching and reading "
      f"back decode blocks, longest beat {st['beat_max_s']*1e3:.0f}ms "
      f"(mostly {st['beat_max_stage']})")
print(f"decode blocks {st['decode_blocks']} ({st['decode_steps']} fused "
      f"ticks), prefill waves {st['prefill_chunks']}, admissions "
      f"{st['admissions']} ({st['mid_flight_admissions']} mid-flight), "
      f"max {st['max_chunks_between_decode_blocks']} wave(s) between blocks")
print(f"TTFT p50 {st['ttft_p50_s']*1e3:.0f}ms  p95 {st['ttft_p95_s']*1e3:.0f}ms")
for i, r in enumerate(requests):
    print(f"  req{i}: prompt {len(r.prompt):3d} toks, "
          f"TTFT {r.ttft_s*1e3:6.1f}ms, out {r.output[:8].tolist()}...")
assert engine.stats["mid_flight_admissions"] > 0
assert engine.stats["max_chunks_between_decode_blocks"] <= 1
print("serve_bitnet OK")
