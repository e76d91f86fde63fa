"""Serving engine tests: token-level continuous batching correctness.

The load-bearing claim: a ragged batch of prompts decoded with the per-slot
length vector is *token-identical* to decoding each request alone — i.e. the
right-padded prefill tail and other slots' cache rows are invisible to every
request (no edge-padding pollution), and mid-flight admission into a freed
slot does not disturb in-flight slots.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import attention, transformer
from repro.models.layers import Ctx
from repro.serving import Request, ServingEngine
from repro.serving.engine import STAGES


@pytest.fixture(scope="module")
def served_model():
    cfg = get_config("qwen1.5-0.5b").reduced()
    params = transformer.init_params(cfg, jax.random.PRNGKey(1))
    packed = transformer.pack_params(cfg, params)
    ctx = Ctx(mode="packed", group_size=cfg.group_size,
              attn_q_chunk=128, attn_kv_chunk=128)
    return cfg, packed, ctx


def reference_decode(cfg, packed, ctx, prompt, max_new, max_seq):
    """Unbatched greedy prefill + decode loop (the oracle)."""
    cache = transformer.init_cache(cfg, 1, max_seq, jnp.bfloat16)
    logits, cache = transformer.prefill_step(
        cfg, packed, jnp.asarray(np.asarray(prompt, np.int32)[None]), ctx,
        cache)
    toks = [int(jnp.argmax(logits, -1)[0])]
    pos = len(prompt)
    for _ in range(max_new - 1):
        logits, cache = transformer.decode_step(
            cfg, packed, jnp.asarray([[toks[-1]]], jnp.int32), ctx, cache,
            jnp.asarray(pos, jnp.int32))
        toks.append(int(jnp.argmax(logits, -1)[0]))
        pos += 1
    return toks


def test_ragged_batch_matches_unbatched(served_model):
    """Three ragged prompts in one 3-slot batch == each decoded alone."""
    cfg, packed, ctx = served_model
    max_seq = 32
    prompts = [np.asarray([1, 2, 3, 4, 5], np.int32),
               np.asarray([9, 8, 7], np.int32),
               np.asarray([4, 4, 2, 1, 1, 3, 2, 5, 6], np.int32)]
    eng = ServingEngine(cfg, packed, max_seq=max_seq, batch_slots=3, ctx=ctx)
    reqs = [Request(prompt=p, max_new_tokens=6) for p in prompts]
    eng.run(reqs)
    for r, p in zip(reqs, prompts):
        ref = reference_decode(cfg, packed, ctx, p, 6, max_seq)
        np.testing.assert_array_equal(r.output, np.asarray(ref, np.int32))
    # all three fit the initial wave: no slot was refilled mid-flight
    assert eng.stats["mid_flight_admissions"] == 0


def test_per_request_ttft_recorded(served_model):
    cfg, packed, ctx = served_model
    eng = ServingEngine(cfg, packed, max_seq=24, batch_slots=2, ctx=ctx)
    reqs = [Request(prompt=np.arange(1, 5, dtype=np.int32) * (i + 1) % 32,
                    max_new_tokens=3) for i in range(4)]
    eng.run(reqs)
    for r in reqs:
        assert r.done and r.ttft_s is not None and r.ttft_s > 0
    # requests 2/3 waited for a freed slot: their TTFT includes the queue
    # delay, so it exceeds the fastest first-wave TTFT
    assert max(reqs[2].ttft_s, reqs[3].ttft_s) > min(reqs[0].ttft_s,
                                                     reqs[1].ttft_s)
    assert eng.stats["ttft_s"] == [r.ttft_s for r in reqs]


def test_mid_flight_admission_completes_correctly(served_model):
    """A request admitted into a freed slot while the other slot is still
    decoding must match its unbatched reference."""
    cfg, packed, ctx = served_model
    max_seq = 32
    short = np.asarray([3, 1, 4], np.int32)       # finishes first
    long_ = np.asarray([2, 7, 1, 8, 2, 8], np.int32)
    late = np.asarray([1, 6, 1, 8, 0], np.int32)  # admitted mid-flight
    eng = ServingEngine(cfg, packed, max_seq=max_seq, batch_slots=2, ctx=ctx)
    reqs = [Request(prompt=short, max_new_tokens=2),
            Request(prompt=long_, max_new_tokens=10),
            Request(prompt=late, max_new_tokens=4)]
    eng.run(reqs)
    assert eng.stats["mid_flight_admissions"] >= 1
    for r, p in zip(reqs, (short, long_, late)):
        ref = reference_decode(cfg, packed, ctx, p, r.max_new_tokens,
                               max_seq)
        np.testing.assert_array_equal(r.output, np.asarray(ref, np.int32))


def test_serving_engine_end_to_end(served_model):
    """Mixed max_new_tokens across more requests than slots: everything
    completes with the right lengths and in-vocab tokens."""
    cfg, packed, ctx = served_model
    eng = ServingEngine(cfg, packed, max_seq=64, batch_slots=2, ctx=ctx)
    reqs = [Request(prompt=np.arange(5) % cfg.vocab_size, max_new_tokens=4),
            Request(prompt=np.arange(9) % cfg.vocab_size, max_new_tokens=6),
            Request(prompt=np.arange(3) % cfg.vocab_size, max_new_tokens=4)]
    eng.run(reqs)
    for r in reqs:
        assert r.done and r.ttft_s is not None
        assert len(r.output) == r.max_new_tokens
        assert (r.output >= 0).all() and (r.output < cfg.vocab_size).all()


def test_prompt_longer_than_max_seq_rejected(served_model):
    """Invalid requests are REJECTED on the request object at admission
    time (never raising out of run(), which would abandon in-flight
    lanes) and never touch a slot or the device."""
    from repro.serving import RequestStatus
    cfg, packed, ctx = served_model
    eng = ServingEngine(cfg, packed, max_seq=8, batch_slots=1, ctx=ctx)
    (r,) = eng.run([Request(prompt=np.arange(9, dtype=np.int32))])
    assert r.done and r.status == RequestStatus.REJECTED
    assert "max_seq" in r.error and len(r.output) == 0
    assert eng.stats["requests_rejected"] == 1
    assert eng.stats["admissions"] == 0
    (r,) = eng.run([Request(prompt=np.arange(3, dtype=np.int32),
                            max_new_tokens=0)])
    assert r.status == RequestStatus.REJECTED
    assert "max_new_tokens" in r.error


# ---------------------------------------------------------------------------
# Fused multi-tick decode + chunked in-place prefill (device-resident loop)
# ---------------------------------------------------------------------------

def _mixed_requests(vocab):
    prompts = [np.asarray([1, 2, 3, 4, 5], np.int32),
               np.asarray([9, 8, 7], np.int32),
               np.asarray([4, 4, 2, 1, 1, 3, 2, 5, 6, 1, 7, 2, 3], np.int32),
               np.asarray([5, 1], np.int32)]
    news = [6, 3, 7, 5]
    return prompts, [Request(prompt=p, max_new_tokens=n)
                     for p, n in zip(prompts, news)]


def test_fused_block_matches_single_tick_and_unbatched(served_model):
    """Chunked prefill + fused-scan greedy decode is token-identical to the
    single-tick whole-prompt configuration (PR-1 semantics: decode_block=1,
    one prefill call per prompt) and to the unbatched oracle."""
    cfg, packed, ctx = served_model
    max_seq = 32
    prompts, reqs_fused = _mixed_requests(cfg.vocab_size)
    eng_fused = ServingEngine(cfg, packed, max_seq=max_seq, batch_slots=3,
                              ctx=ctx, prefill_chunk=4, decode_block=8)
    eng_fused.run(reqs_fused)
    _, reqs_tick = _mixed_requests(cfg.vocab_size)
    eng_tick = ServingEngine(cfg, packed, max_seq=max_seq, batch_slots=3,
                             ctx=ctx, prefill_chunk=max_seq, decode_block=1)
    eng_tick.run(reqs_tick)
    for rf, rt, p in zip(reqs_fused, reqs_tick, prompts):
        ref = reference_decode(cfg, packed, ctx, p, rf.max_new_tokens,
                               max_seq)
        np.testing.assert_array_equal(rf.output, np.asarray(ref, np.int32))
        np.testing.assert_array_equal(rt.output, rf.output)


def test_chunked_prefill_compiles_o1_shapes(served_model):
    """10 distinct prompt lengths hit ONE compiled prefill program (the
    PR-1 engine compiled one per length bucket) and one decode program."""
    cfg, packed, ctx = served_model
    eng = ServingEngine(cfg, packed, max_seq=32, batch_slots=2, ctx=ctx,
                        prefill_chunk=4, decode_block=4)
    reqs = [Request(prompt=np.arange(1, plen + 1, dtype=np.int32) % 32,
                    max_new_tokens=2) for plen in range(3, 13)]
    eng.run(reqs)
    assert len({len(r.prompt) for r in reqs}) == 10
    shapes = eng.compiled_shapes()
    if shapes["prefill_chunk"] is None:
        pytest.skip("jit cache introspection unavailable on this jax")
    assert shapes["prefill_chunk"] == 1
    assert shapes["decode_block"] == 1


def test_long_prompt_interleaves_with_decode(served_model):
    """A long prompt admitted mid-flight stalls in-flight lanes for at most
    one prefill chunk between consecutive decode blocks."""
    cfg, packed, ctx = served_model
    eng = ServingEngine(cfg, packed, max_seq=32, batch_slots=2, ctx=ctx,
                        prefill_chunk=4, decode_block=4)
    first = Request(prompt=np.asarray([3, 1, 4], np.int32),
                    max_new_tokens=24)              # stays in flight
    short = Request(prompt=np.asarray([7, 2], np.int32),
                    max_new_tokens=2)               # frees its slot fast
    long_ = Request(prompt=np.arange(1, 21, dtype=np.int32),  # 5 chunks,
                    max_new_tokens=4)               # admitted mid-flight
    eng.run([first, short, long_])
    st = eng.stats
    assert st["mid_flight_admissions"] >= 1
    assert st["prefill_chunks"] >= 6  # 1 wave (first+short) + 5 (long_)
    # the interleave bound: never more than one admission wave between
    # decode blocks, no matter how long the admitted prompt is
    assert st["max_chunks_between_decode_blocks"] == 1
    # and the outputs are still exact
    for r in (first, short, long_):
        ref = reference_decode(cfg, packed, ctx, r.prompt, r.max_new_tokens,
                               32)
        np.testing.assert_array_equal(r.output, np.asarray(ref, np.int32))


def test_shifted_final_chunk_non_divisible_chunk_size(served_model):
    """A chunk size that does not divide max_seq works: the final chunk is
    shifted back to end exactly at the cache row end, and greedy outputs
    still match the unbatched oracle."""
    cfg, packed, ctx = served_model
    max_seq = 30                       # 30 % 7 != 0
    eng = ServingEngine(cfg, packed, max_seq=max_seq, batch_slots=2, ctx=ctx,
                        prefill_chunk=7, decode_block=4)
    assert eng.prefill_chunk == 7
    prompts = [np.arange(2, 27, dtype=np.int32) % 32,   # 25 toks: 4 chunks,
               np.asarray([5, 3, 1], np.int32)]         # last one shifted
    reqs = [Request(prompt=p, max_new_tokens=4) for p in prompts]
    eng.run(reqs)
    for r, p in zip(reqs, prompts):
        ref = reference_decode(cfg, packed, ctx, p, 4, max_seq)
        np.testing.assert_array_equal(r.output, np.asarray(ref, np.int32))


def test_parked_write_never_clobbers_live_token(served_model):
    """The inactive-lane parking contract: a lane that fills its cache row
    (``cache_len == max_seq``) goes inactive mid-block and its remaining
    ticks park writes at the clamped row tail ``max_seq - 1`` — ON TOP of
    its own last live token.  That is only safe because the lane is retired
    at block end, before any dispatch could attend the clobbered entry (the
    engine asserts this after every block).  Exercise exactly that window —
    a row-filling request with ticks to spare inside its block, then a
    reused slot — and require token-identical outputs throughout."""
    cfg, packed, ctx = served_model
    max_seq = 12
    filler = Request(prompt=np.asarray([3, 1, 4, 1, 5], np.int32),
                     max_new_tokens=20)   # caps at cache_len == max_seq
    #                                       after 8 tokens, 7 ticks into an
    #                                       8-tick block: the final tick
    #                                       parks at max_seq - 1
    reused = Request(prompt=np.asarray([2, 7, 1], np.int32),
                     max_new_tokens=4)    # admitted into the freed slot
    eng = ServingEngine(cfg, packed, max_seq=max_seq, batch_slots=1, ctx=ctx,
                        prefill_chunk=4, decode_block=8)
    eng.run([filler, reused])
    assert len(filler.output) == max_seq - len(filler.prompt) + 1
    ref = reference_decode(cfg, packed, ctx, filler.prompt,
                           len(filler.output), max_seq)
    np.testing.assert_array_equal(filler.output, np.asarray(ref, np.int32))
    ref2 = reference_decode(cfg, packed, ctx, reused.prompt, 4, max_seq)
    np.testing.assert_array_equal(reused.output, np.asarray(ref2, np.int32))


def test_sampling_reproducible_across_slots_and_schedules(served_model):
    """A sampled request's output depends only on its seed (keys are
    fold_in(PRNGKey(seed), emitted index)), not on which slot or tick
    schedule the scheduler picked."""
    cfg, packed, ctx = served_model

    def probe():
        return Request(prompt=np.asarray([2, 7, 1, 8], np.int32),
                       max_new_tokens=8, temperature=0.9, seed=123)

    def filler(n):
        return Request(prompt=np.asarray([5, 3, 1], np.int32) * n % 32,
                       max_new_tokens=n + 3)

    eng = ServingEngine(cfg, packed, max_seq=32, batch_slots=2, ctx=ctx,
                        prefill_chunk=4, decode_block=4)
    a = probe()
    eng.run([a, filler(1), filler(2)])        # probe admitted first (slot 0)
    eng2 = ServingEngine(cfg, packed, max_seq=32, batch_slots=2, ctx=ctx,
                         prefill_chunk=4, decode_block=4, seed=99)
    b = probe()
    eng2.run([filler(1), filler(2), b])       # probe admitted last (refill)
    np.testing.assert_array_equal(a.output, b.output)
    # a different seed decodes a different trajectory (temperature > 0)
    eng3 = ServingEngine(cfg, packed, max_seq=32, batch_slots=2, ctx=ctx,
                         prefill_chunk=4, decode_block=4)
    c = probe()
    c.seed = 124
    eng3.run([c])
    assert not np.array_equal(a.output, c.output)


def test_stats_decode_only_throughput_and_ttft_percentiles(served_model):
    cfg, packed, ctx = served_model
    eng = ServingEngine(cfg, packed, max_seq=32, batch_slots=2, ctx=ctx,
                        prefill_chunk=4, decode_block=4)
    reqs = [Request(prompt=np.arange(1, 6, dtype=np.int32) * (i + 1) % 32,
                    max_new_tokens=5) for i in range(4)]
    eng.run(reqs)
    st = eng.stats
    assert st["decode_tokens"] == st["total_new_tokens"] - st["admissions"]
    # the host seconds of each beat stage: the decode blocks' dispatch and
    # readback took time, and the stages never overlap, so together they
    # fit inside the window
    host = st["host_s"]
    assert set(host) == set(STAGES)
    assert host["block"] > 0 and host["readback"] > 0
    assert sum(host.values()) <= st["wall_s"]
    assert 0 < st["beat_max_s"] <= st["wall_s"]
    assert st["beat_max_stage"] in STAGES
    assert st["ttft_p50_s"] <= st["ttft_p95_s"]
    assert st["ttft_p95_s"] <= max(st["ttft_s"])


# ---------------------------------------------------------------------------
# The ragged primitives under the engine
# ---------------------------------------------------------------------------

def test_prefill_lengths_gather_matches_exact_prefill(served_model):
    """Right-padded prefill with lengths == exact-length prefill logits."""
    cfg, packed, ctx = served_model
    prompt = np.asarray([5, 4, 3, 2, 1], np.int32)
    cache = transformer.init_cache(cfg, 1, 16, jnp.bfloat16)
    exact, _ = transformer.prefill_step(cfg, packed,
                                        jnp.asarray(prompt[None]), ctx,
                                        cache)
    padded = np.zeros((1, 8), np.int32)
    padded[0, :5] = prompt
    cache = transformer.init_cache(cfg, 1, 16, jnp.bfloat16)
    via_len, _ = transformer.prefill_step(cfg, packed, jnp.asarray(padded),
                                          ctx, cache,
                                          lengths=jnp.asarray([5], jnp.int32))
    np.testing.assert_allclose(np.asarray(exact), np.asarray(via_len),
                               atol=1e-5, rtol=1e-5)


def test_prefill_chunk_matches_monolithic_prefill(served_model):
    """Chunked in-place prefill (3 chunks into shared-cache row 1) produces
    the same last-token logits and the same KV row as one whole-prompt
    prefill (f32 cache: no chunk-boundary rounding)."""
    cfg, packed, ctx = served_model
    max_seq, slots, chunk = 16, 3, 4
    prompt = np.asarray([5, 4, 3, 2, 1, 6, 7, 8, 9, 2], np.int32)  # 10 toks
    plen = len(prompt)
    exact_cache = transformer.init_cache(cfg, 1, max_seq, jnp.float32)
    exact, exact_cache = transformer.prefill_step(
        cfg, packed, jnp.asarray(prompt[None]), ctx, exact_cache)
    cache = transformer.init_cache(cfg, slots, max_seq, jnp.float32)
    slot = 1
    logits = None
    for lo in range(0, plen, chunk):
        toks = np.zeros((slots, chunk), np.int32)
        seg = prompt[lo:lo + chunk]
        toks[slot, :len(seg)] = seg
        logits, cache = transformer.prefill_chunk(
            cfg, packed, jnp.asarray(toks), ctx, cache,
            offsets=np.asarray([0, lo, 0], np.int32),
            admit_mask=np.asarray([False, True, False]),
            last_index=np.asarray(
                [0, min(plen - 1 - lo, chunk - 1), 0], np.int32))
    np.testing.assert_allclose(np.asarray(logits)[slot], np.asarray(exact)[0],
                               atol=1e-4, rtol=1e-4)
    # the written KV prefix of row `slot` matches the monolithic cache
    np.testing.assert_allclose(
        np.asarray(cache["k"][:, slot, :plen]),
        np.asarray(exact_cache["k"][:, 0, :plen]), atol=1e-4, rtol=1e-4)
    # other rows untouched
    assert not np.asarray(cache["k"][:, 0]).any()
    assert not np.asarray(cache["k"][:, 2]).any()


def test_decode_attention_per_slot_lengths():
    """XLA + Pallas decode attention with a (b,) length vector both match
    the oracle, and row i ignores cache positions >= lengths[i]."""
    from repro.kernels.decode_attention import ops, ref
    b, h, kv_h, s, d = 3, 4, 2, 32, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b, h, 1, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, kv_h, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, kv_h, s, d), jnp.float32)
    lens = jnp.asarray([5, 17, 32], jnp.int32)
    expect = ref.decode_attention_ref(q, k, v, lens)
    got_xla = attention.decode_attention_xla(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(got_xla), np.asarray(expect),
                               atol=1e-5, rtol=1e-5)
    got_pl = ops.decode_attention(q, k, v, lens, bkv=8)
    np.testing.assert_allclose(np.asarray(got_pl), np.asarray(expect),
                               atol=1e-5, rtol=1e-5)
    # stale-tail invariance: garbage beyond each row's length is invisible
    noise = jax.random.normal(ks[3], (b, kv_h, s, d), jnp.float32) * 100
    stale = jnp.arange(s)[None, None, :, None] >= lens[:, None, None, None]
    got_noisy = attention.decode_attention_xla(
        q, jnp.where(stale, noise, k), jnp.where(stale, noise, v), lens)
    np.testing.assert_allclose(np.asarray(got_noisy), np.asarray(expect),
                               atol=1e-5, rtol=1e-5)


def test_update_kv_cache_per_slot_positions():
    """Vector positions write each row at its own offset."""
    b, s, hh, d = 2, 8, 1, 4
    kc = jnp.zeros((b, s, hh, d))
    vc = jnp.zeros((b, s, hh, d))
    k_new = jnp.ones((b, 1, hh, d))
    v_new = 2 * jnp.ones((b, 1, hh, d))
    pos = jnp.asarray([2, 5], jnp.int32)
    kc2, vc2 = attention.update_kv_cache(kc, vc, k_new, v_new, pos)
    kc2, vc2 = np.array(kc2), np.array(vc2)
    assert (kc2[0, 2] == 1).all() and (kc2[1, 5] == 1).all()
    assert (vc2[0, 2] == 2).all() and (vc2[1, 5] == 2).all()
    kc2[0, 2] = kc2[1, 5] = vc2[0, 2] = vc2[1, 5] = 0
    assert (kc2 == 0).all() and (vc2 == 0).all()


def test_weights_expanded_once_and_contracted_as_int8(served_model,
                                                      monkeypatch):
    """The ternary codes are expanded once, when the engine is built, and
    not by any dispatch: the admission wave and the decode block carry no
    ``predecode`` scope and contract every linear on int8 operands."""
    cfg, packed, ctx = served_model
    calls = []
    expand = transformer.predecode_packed
    monkeypatch.setattr(transformer, "predecode_packed",
                        lambda *a, **k: calls.append(1) or expand(*a, **k))
    eng = ServingEngine(cfg, packed, max_seq=32, batch_slots=2,
                        decode_block=4, prefill_chunk=4, ctx=ctx)
    for i in range(3):
        eng.submit(Request(prompt=np.arange(3 + 2 * i) % cfg.vocab_size,
                           max_new_tokens=7))
    steps = 0
    while eng.step().remaining:
        steps += 1
    assert steps > 3
    assert eng.stats["prefill_chunks"] > 1 and eng.stats["decode_blocks"] > 1
    assert len(calls) == 1

    n = eng.slots
    i32 = jax.ShapeDtypeStruct((n,), jnp.int32)
    wave = eng._prefill_chunks.lower(
        eng.params, jax.ShapeDtypeStruct((n, eng.prefill_chunk), jnp.int32),
        eng._cache, eng._bt_device(), i32,
        jax.ShapeDtypeStruct((n,), jnp.bool_), i32, i32,
        jax.ShapeDtypeStruct((n,), jnp.float32), i32)
    block = eng._decode_block_dev.lower(eng.params, eng._state, eng._cache,
                                        eng._bt_device(), eng._no_nan)
    weights = [w.shape[1:] for path, w in
               jax.tree_util.tree_flatten_with_path(eng.params["layers"])[0]
               if path[-1].key == "wt"]
    assert len(weights) == 4  # qkv, o, gate|up, down
    for lowered in (wave, block):
        text = lowered.as_text(debug_info=True)
        assert "/predecode/" not in text
        for rows, cols in weights:
            assert f"tensor<{rows}x{cols}xi8>) -> " in text
            assert f"tensor<{rows}x{cols}xf32>" not in text
