"""Serving engine: device-resident decode hot loop + chunked in-place prefill.

The paper's system-level claim — prefill and decode are different machines
and both must be first-class, *overlapped* rather than serialized (§3.4
streaming dataflow) — is the organizing principle.  PR 1's token-level
continuous batching paid one jit dispatch + full host sync per decoded token
and froze every in-flight lane while a whole prompt prefilled; this engine
keeps the whole serving tick on device:

  * **fused multi-tick decode** — ``_decode_block`` is one jit'd
    ``lax.scan`` over ``decode_block`` single-token ticks.  Per-slot
    sampling (greedy + temperature via per-request PRNG keys), KV-cache
    writes, ``cache_len``/``emitted`` bookkeeping and done-masking all stay
    on device; the host gets back a ``(slots, decode_block)`` token block
    plus emit masks once per block instead of once per token.  The packed
    ternary weights are expanded once, when the engine is built
    (``transformer.predecode_packed``), and stay resident as int8: every
    block and wave contracts int8 activations against them (int8 x int8 ->
    int32) and re-derives nothing.  Lanes that finish mid-block emit pad
    tokens (0) under the mask so the scan shape stays static; their cache
    writes are parked at the row tail (position ``max_seq - 1``), which is
    either masked by the live length or overwritten before it is ever
    attended.
  * **chunked in-place prefill, batched across slots** — admission runs
    ``prefill_chunk``-sized *wave* dispatches (``transformer.prefill_chunk``)
    in which EVERY pending admission writes its chunk's KV straight into its
    own shared-cache row at its own offset (masked rows untouched) and
    attends its already-written ``[0, offset)`` prefix.  Chunk size is the
    only shape involved, so the prefill jit cache holds exactly one entry
    for any mix of prompt lengths (PR 1 compiled one program per
    prompt-length bucket and copied a donor cache per admission).
  * **bounded interleaving** — the host loop alternates one admission wave
    with one decode block, so admissions — however many, however long —
    never stall in-flight lanes for more than one chunk + one block
    dispatch (``stats["max_chunks_between_decode_blocks"]`` records the
    bound).
  * **device-resident scheduling** (``device_sched=True``, the default) —
    the per-block scheduler state (``last_token``, ``cache_len``,
    ``emitted``, active mask, per-slot ``max_new``/``temps``/``seeds``)
    lives in a device pytree threaded block-to-block through the fused
    decode jit, so block N+1 dispatches immediately after block N with
    ZERO device->host round-trips in steady state; the host fetches each
    block's tokens ONE BLOCK BEHIND (block N is read back while block N+1
    runs) and only mirrors the state for admission/retirement decisions.
    Because the host view lags by at most one block, a lane that finishes
    on device may tick through one extra fully masked block before the
    host retires it — those ticks emit nothing and their writes are
    parked, so outputs are token-identical to the host-driven engine
    (``device_sched=False``, which syncs every block before the next
    dispatch).  ``stats["host_block_syncs"]`` counts block readbacks a
    subsequent dispatch had to wait for (every block in host mode; only
    retire/admit-triggering blocks in device mode) and
    ``stats["steady_state_syncs_per_block"]`` is that count over blocks
    dispatched with no admission/retire/prefill since the previous block
    — exactly 0.0 device-resident, 1.0 host-driven.

**Paged KV cache** (``paged=True``): instead of one contiguous ``max_seq``
cache row per slot, the engine owns a global pool of fixed-size KV pages
(``page_size`` tokens each; page 0 is the reserved *null page*) plus a
per-slot block table.  A host-side free-list allocator hands pages out
lazily — at admission a slot holds only the pages its written prompt prefix
needs, and decode grows the table page-by-page — so KV memory scales with
*live tokens*, not ``slots x max_seq``.  The block table keeps its full
static width (one compiled program; dead columns are null entries the
Pallas kernels skip without issuing work — slicing the width was measured
to cost more in recompiles than it saves in gather).  Admission is gated
by worst-case
reservation (``ceil(min(prompt + max_new, max_seq) / page_size)`` pages per
request, FIFO): a request is only admitted when the sum of active
reservations still fits the pool, which guarantees lazy growth can never
fail mid-decode while letting many short requests share a pool that could
not hold them contiguously.  Retiring a slot returns its pages to the free
list and zeroes its block-table row; recycled pages carry stale KV, which is
invisible because a new owner's prefill rewrites every position below its
live length and attention masks the rest.  The contiguous path's
inactive-lane tail parking simplifies: inactive lanes park at flat address
``max_seq``, which the block table resolves to the null page (or to the
final page's never-live slack row), so no live token can ever be clobbered
regardless of what the lane's pages hold.
Device-side layout and kernels live in ``transformer.init_paged_cache``,
``attention.paged_*`` and the paged Pallas kernels in
``kernels/decode_attention`` / ``kernels/flash_prefill``.

**Paged prefix sharing** (``enable_prefix_sharing=True``, paged mode only):
templated workloads repeat long prompt prefixes, and a prefix's KV depends
only on the prefix tokens and their absolute positions — so slots whose
prompts share a prefix can read the *same* pages.  A host-side radix trie
(``_PrefixIndex``, one node per fully written prompt page) maps an admitted
prompt to its longest cached prefix; the engine grants those pages by
aliasing block-table entries and bumping per-page refcounts, and chunked
prefill starts at the first divergent token instead of 0.  When the share
base lands mid-page, the boundary page is copy-on-write split: the slot
gets a freshly allocated device copy and writes into the copy's tail.
Admissions whose prompt prefix is being prefilled by a PENDING admission
right now are held back until that donor completes (it registers its pages
at completion) rather than prefilling the prefix twice.  Completed
admissions register their full prompt pages in the trie, which takes one
pool reference per page so cached prefixes outlive their slot; under
capacity pressure (or the ``prefix_cache_pages`` cap) LRU trie leaves are
evicted, freeing pages nobody else reads.

Sharing invariants (load-bearing; the property tests in
``tests/test_prefix_sharing.py`` exercise them):

  * the null page 0 is never shared — the allocator never hands it out,
    so it can never enter a grant or the index;
  * a slot's writable frontier page always has refcount 1: granted pages
    cover ``[0, base)`` with ``base`` page-interior only via the CoW copy
    (exclusively owned), and registered pages are full prompt pages that
    the slot never writes again — decode appends land at ``>= plen`` and
    the inactive-lane park at flat ``max_seq`` resolves to the null page
    or the final page's slack row, neither of which is ever registrable
    (a registered page j satisfies ``(j+1)*page_size <= plen <= max_seq``);
  * the share base is a ``prefill_chunk`` multiple, ``<= plen - 1`` and
    ``<= max_seq - prefill_chunk`` — the sharer's own chunk schedule is
    identical to the non-sharing engine's (greedy outputs are therefore
    bit-identical, not merely argmax-stable), the shifted final chunk can
    never rewrite a shared position, and positions a donor's own shifted
    final chunk rewrote are never granted;
  * admission is gated by ``reservations + legacy shared pages <= pool``:
    a reservation counts only pages the slot may still *allocate* (its
    worst case minus granted aliases; the CoW page is an allocation), and
    pages kept alive by sharers after their allocator retired are added to
    the gate — so lazy growth still can never fail mid-flight, while a
    request that only fits because of shared pages admits instead of
    deferring.  Index-only pages are invisible to the gate: they are
    reclaimed on demand by LRU eviction when allocation runs dry.

**Resident lifecycle** (``submit()`` / ``step()`` / ``drain()`` /
``close()``): the engine is a long-lived object, not a batch function.
``submit(request)`` may be called at ANY point in the serving lifecycle —
mid-decode, mid-degrade, mid-retry-backoff — and runs the admission-time
policy per arrival: validation (an unservable request is stamped REJECTED
immediately), default seed assignment off the engine-lifetime arrival
counter, and clock stamping (``deadline_s`` and TTFT measure from this
moment, never from a window boundary).  ``step()`` advances exactly one
scheduler beat::

    police -> breaker ticks -> retry pump -> promote probe ->
    admission wave -> fused decode block -> one-block-behind drain

and returns a :class:`StepOutcome`; when retry backoff is the only
remaining work it carries ``idle_until`` so the caller sleeps instead of
polling.  ``drain()`` steps until every submitted request is terminal and
finalizes the stats window; ``close()`` drains and refuses further
submissions.  Batch ``run()`` is a thin wrapper — reset the stats window,
submit all, drain — so batch and incremental submission execute the EXACT
same scheduler loop with identical tokens, and every serving mode/test
pins the resident path.  All serving state (lanes, pools, block tables,
prefix cache, retry queue, both breakers, the arrival counter) lives for
the ENGINE lifetime and persists across windows; ``stats`` is a per-window
view (``reset_stats()`` opens a window) while ``lifetime`` accumulates
across windows.  Per-token streaming: an optional ``on_token(request,
token)`` callback fires at readback, in emit order, once per committed
token — after the integrity guards (a poisoned block's discarded tokens
never fire) and never re-firing a retry replay's carried tokens, so the
streamed sequence always equals the request's final ``output``.

**Multi-device serving** (``mesh=``, with ``shard_slots=``/``shard_kv=``):
the engine accepts a 2-axis ``('data', 'model')`` mesh and runs every
fused dispatch — prefill wave, host-driven block, device-resident block,
CoW page copy — as one ``shard_map`` over it (``check_vma=False``).
``shard_slots`` splits the slot batch over 'data': every scheduler-pytree
leaf, per-slot operand and decode-block output is sharded ``P('data')``
on its slot axis, the contiguous cache genuinely shards its slot row
axis, and the slot count is padded up to a 'data' multiple (padded lanes
are permanently disabled).  Paged pools are *replicated-but-divergent*:
each data shard writes only its own slots' pages into its replica and
the pools are never read back to the host, which is why every
pool-touching function must go through the engine's shard_maps (a plain
jit could reshard — i.e. consolidate — the replicas) and why prefix
sharing is namespaced per data shard (a page registered by another
shard's slot holds garbage locally; trie keys carry the shard id).
``shard_kv`` splits flash-decode attention over 'model' by routing the
canonical ``kv_splits`` K-chunks of the split-K decode kernel across the
axis ranks, combining per-rank partial softmaxes with an ordered
``all_gather`` — bitwise identical to the single-device engine running
the same ``kv_splits`` (see ``kernels/decode_attention/ops``).  All
host/device ownership transitions below survive sharding unchanged:
the host mirror stays the global (all-shard) view, and row-granular
patches (``_set_bt_row``/``_kill_lane``/``_admit_lanes``) apply to the
sharded arrays through GSPMD without consolidating them.

Slot state machine — who owns what.  Each decode lane is mirrored twice:
a device row in the resident ``SchedulerState`` pytree (``last_token``,
``cache_len``, ``emitted``, ``active``, ``max_new``, ``temps``, ``seeds``
— everything a decode tick reads or writes) and a host ``_Slot`` (the
request object, accumulated output tokens, and a ``cache_len`` mirror —
everything admission, retirement and the page allocator need).  The
device copy is authoritative during decode and is threaded block-to-block
without readback; the host copy trails it by at most one block and is the
only place FREE/ACTIVE transitions are decided.  Bracketed steps are
paged-mode only; ``{host}``/``{device}`` marks where each step runs.
Under a mesh the ``{device}`` column reads ``{sharded}``: the step
executes once per mesh device over that device's slot shard (admission
chunks, first-token sampling, lane merge, decode blocks, self-
deactivation, the force-deactivate patch and block-table row updates all
shard their slot axis over 'data'; KV attention additionally splits over
'model' with ``shard_kv``), while every ``{host}`` decision — admission,
retirement, page grants, CoW, retry replay, degrade and re-promotion —
stays global, made once against the all-shard host mirror:

    ARRIVED --submit() {host}: seed assigned off the engine-lifetime
           arrival counter, deadline/TTFT clocks stamped--> QUEUED
    ARRIVED --validation fails at submit() {host}--> DONE(REJECTED)
           [never enters the queue, never touches a slot, a page, or
            the device]
    QUEUED --cancel()/deadline sweep {host}--> DONE(CANCELLED | TIMEOUT)
    FREE --[reserve worst-case pages {host};
            device_sched: pre-grant the full reservation {host}]--
         admit(chunk* {device} [+ host mode: grow pages over the written
               prefix], first token sampled {device}, lane merged into the
               resident state {device})--> ACTIVE
    PENDING --alloc fault during grant/pre-grant/chunk growth {host}--
            > DONE(FAILED)  [granted aliases + reservation roll back
              refcount-exact; the wave row stays masked; other pending
              admissions advance untouched]
    PENDING --cancel()/deadline sweep {host}--> DONE(CANCELLED | TIMEOUT)
    ACTIVE --decode block {device}: emitted += k, cache_len += k, done
             mask maintained on device [host mode only: grow pages to
             cover the block's appends {host}]--> ACTIVE
    ACTIVE --emitted == max_new_tokens or cache_len == max_seq:
           the lane deactivates ITSELF on device; the host observes this
           one block later in the readback--> FREE {host}
           [pages + reservation returned, block-table row cleared
            device-side via a row-granular update], request DONE(OK —
           or DEGRADED when the engine has fallen back, see below)
    ACTIVE --integrity guard {host, reading the device's in-block
             non-finite latch or the token-range check}--> FREE {host},
           request DONE(FAILED)  [tokens before the poisoned block kept;
            pages roll back; prefix registrations withdrawn; the lane is
            force-deactivated in the resident state {device} so later
            blocks tick it fully masked — every other lane unaffected]
    ACTIVE --cancel()/deadline sweep at a block boundary {host}--> FREE,
           request DONE(CANCELLED | TIMEOUT)  [tokens so far kept; KV
            valid, so prefix registrations STAY]
    DONE(FAILED | TIMEOUT*) --budgeted RETRY {host}: retries < budget and
           the retry breaker not open (*TIMEOUT only with
           ``retry_timeouts``)--> RETRY-WAIT  [the terminal stamp is
           withdrawn; pages already rolled back refcount-exact through
           the shared release path; the withdrawn attempt's error joins
           ``retry_errors``]
    RETRY-WAIT --seeded-deterministic exponential backoff elapses
           {host}--> QUEUED  [admission replays prompt + tokens-so-far
           as the new prefill, so the KV is rebuilt and greedy output
           continues token-identically to an uninterrupted run; prefix
           sharing makes the replay cheap when the prompt's pages are
           still cached; the deadline budget restarts per attempt]
    (engine degraded, ``repromote``) --device breaker half-open after its
           cooldown {host}--> PROBE: one canary dispatch {device} through
           the real dispatch seams (injector hook, watchdog) but NEVER
           the real fused block (its donated state/cache must survive a
           failing probe)
    PROBE --success--> PROMOTE {host->device}: resident state pytree +
           block table rebuilt/re-uploaded from the host mirror, live
           lanes topped up to their full page reservation, scheduling
           handed back to the device; ``steady_state_syncs_per_block``
           returns to 0.0 and completions are stamped OK again
    PROBE --failure--> breaker re-opens with doubled cooldown {host}
           [persistent faults converge to stable host-driven service
            with exponentially rarer, bounded probing]

Engine-level degradation (device-resident mode only): a dispatch that
still fails after ``dispatch_retries`` re-issues, or a fused block that
exceeds ``block_deadline_s`` (serving watchdog, non-process-killing),
means the device scheduler itself can no longer be trusted.  The engine
then *reconciles* — drains every in-flight readback, after which the
host mirror is exact (each device transition is a pure function of the
drained blocks) — drops the resident state, and finishes the run on the
``device_sched=False`` host-driven path.  Surviving requests complete
with token-identical greedy output, stamped DEGRADED; with ``repromote``
(the default) the engine probes device health per the PROBE/PROMOTE
transitions above and returns to device-resident scheduling mid-run once
the cause clears; the next ``run()`` starts device-resident regardless.
On the host path the same two triggers have no lower service level to
fall to: a watchdog trip is only counted (the block did complete), a
persistently failing dispatch retires the live batch FAILED and keeps
serving the queue (feeding the retry path when a budget is set).

With ``device_sched=False`` the device pytree is not built: the host
arrays are rebuilt and uploaded per block (the pre-PR behaviour), which
is the reference the equivalence tests compare against — and the
degradation target above.

Sampling is reproducible per request: each slot's PRNG key is
``fold_in(PRNGKey(request.seed), emitted_index)``, so a request's output
depends only on its seed and its own logits — never on which slot or tick
order the scheduler happened to pick.  ``request.seed`` defaults to a
deterministic function of the engine seed and the engine-lifetime ARRIVAL
counter (not the position within one ``run()``'s request list), so the
same request stream split across any mix of ``submit()`` and ``run()``
calls samples identically to a single batch.

Recurrent kinds (SSM / xLSTM) cannot resume prefill chunk-to-chunk (their
state integrates every token), so they fall back to PR 1's whole-prompt
donor prefill + adopt — the fused decode block works for them unchanged.

``engine.stats`` is a per-WINDOW view (one ``run()``, or whatever span
the caller delimits with ``reset_stats()``/``drain()``): aggregate
throughput, TTFT p50/p95 measured from each request's arrival,
scheduler-beat and idle-sleep counts, the host seconds of each beat stage
(``host_s``) with the window's longest beat (``beat_max_s``) and the stage
that held most of it (``beat_max_stage``), and admission / interleave
counters; paged mode adds KV pool
gauges (page size, pool size, pages-in-use peak, pool utilization,
live-token peak, reservation peak, page-starved admission deferrals).
``engine.lifetime`` accumulates across windows (arrivals, windows, status
counters, faults, retries, decode totals) and is never clobbered by a new
``run()``.  Robustness gauges are present in every
mode: one ``requests_*`` counter per terminal status (recounted from the
window's request objects at finalize, so counters and statuses can never
disagree),
``degraded_blocks`` / ``sched_fallbacks`` / ``watchdog_trips`` /
``integrity_faults`` / ``faults_injected``, and recovery gauges
(``requests_retried`` / ``retries_total`` / ``retry_backoff_s`` /
``retries_denied_breaker`` / ``repromotions`` / ``canary_probes`` /
``breaker_state`` / ``retry_breaker_state``).  ``ServingEngine.audit()``
re-derives the page-pool refcounts from the block tables and prefix trie
and raises :class:`AuditError` on any leak / double-free / null-page
violation (``audit_on_retire=True`` runs it after every fault-path
retirement).
"""

from __future__ import annotations

import dataclasses
import enum
import functools
import time
from collections import deque
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.kernels.decode_attention.ops import validate_num_splits
from repro.models import transformer
from repro.models.layers import Ctx
from repro.runtime import sharding as shardlib
from repro.runtime.fault import (CircuitBreaker, Watchdog, backoff_delay,
                                 with_retries)
from repro.serving.faultinject import FaultInjector, InjectedFault

_SEED_MOD = 2 ** 31 - 1

# The host stages of a scheduler beat, in beat order.  Each is a host span
# ``engine.<stage>`` in the profiler's trace and a sum in
# ``stats["host_s"]`` (see ``ServingEngine._stage``).
STAGES = ("police", "admit", "wave", "first_sync", "block", "readback")


class RequestStatus(enum.Enum):
    """Terminal disposition of a served request (set exactly once, when
    ``done`` flips True).  The taxonomy is the per-request blast-radius
    contract: anything short of OK names which containment path retired
    the lane, and every one of them leaves the other lanes untouched."""

    OK = "ok"               # completed normally
    REJECTED = "rejected"   # failed admission-time validation; never ran
    TIMEOUT = "timeout"     # deadline_s expired (queued or mid-flight)
    CANCELLED = "cancelled"  # cancel(request) observed at a block boundary
    FAILED = "failed"       # runtime fault confined to this lane (NaN/inf
    #                         logits, corrupt readback, page-alloc fault)
    DEGRADED = "degraded"   # finished with correct tokens, but after the
    #                         engine fell back to the host-driven path


class AuditError(RuntimeError):
    """A page-pool / prefix-trie / block-table invariant is violated
    (``ServingEngine.audit``)."""


# stats key charged per terminal status; all six keys are always present
# in ``engine.stats`` (and recounted from request objects at run end, so
# the counters and the statuses can never disagree)
_STATUS_COUNTERS = {
    RequestStatus.OK: "requests_completed",
    RequestStatus.REJECTED: "requests_rejected",
    RequestStatus.TIMEOUT: "requests_timed_out",
    RequestStatus.CANCELLED: "requests_cancelled",
    RequestStatus.FAILED: "requests_failed",
    RequestStatus.DEGRADED: "requests_degraded",
}


@dataclasses.dataclass
class StepOutcome:
    """What one scheduler beat (``ServingEngine.step``) accomplished.

    ``worked`` is False only when the engine had nothing anywhere (every
    pool empty) — the beat was a no-op.  ``remaining`` counts requests the
    engine still owes a terminal status (queued + pending admission + live
    lanes + retry-wait; the one-block-behind readback can make a lane look
    live one block after it finished on device).  ``idle_until`` is a
    ``time.perf_counter()`` timestamp: when set, no beat can make progress
    before then (the only work left is retry-wait backoff) — callers
    should sleep toward it instead of spinning ``step()``; ``None`` means
    either more work is dispatchable right now or the engine is empty."""

    worked: bool
    remaining: int
    idle_until: Optional[float] = None


@dataclasses.dataclass(eq=False)  # identity eq: the prompt array makes
class Request:                     # field-wise __eq__ ambiguous, and queue
    # membership (cancel/deadline removal) must match THIS object anyway
    prompt: np.ndarray                 # (prompt_len,) int32 token ids
    max_new_tokens: int = 16
    temperature: float = 0.0           # 0 = greedy
    seed: Optional[int] = None         # sampling seed; engine assigns a
    #                                    deterministic default if None
    deadline_s: Optional[float] = None  # wall-clock budget from submit()
    #                                     (arrival), NOT from run() start —
    #                                     a late arrival never burns budget
    #                                     it was not yet queued for; checked
    #                                     at block/wave boundaries ->
    #                                     TIMEOUT.  A retried attempt's
    #                                     budget restarts when the retry is
    #                                     scheduled (per-attempt deadline,
    #                                     or every retry of a TIMEOUT would
    #                                     be stillborn)
    max_retries: Optional[int] = None  # per-request override of the
    #                                    engine-level retry budget
    # filled by the engine:
    output: Optional[np.ndarray] = None
    ttft_s: Optional[float] = None     # time from submit() (arrival) to
    #                                    first token, incl. queueing
    done: bool = False
    status: Optional[RequestStatus] = None
    error: Optional[str] = None        # human-readable cause for non-OK
    cancelled: bool = False            # set via ServingEngine.cancel()
    attempts: int = 0                  # admissions started (1 = no retry)
    retries: int = 0                   # re-queues granted by the engine
    retry_errors: List[str] = dataclasses.field(default_factory=list)
    #                                    error history of withdrawn attempts
    #                                    (the final error stays in ``error``)


class _Slot:
    """Host-side state for one decode lane of the shared cache."""

    __slots__ = ("request", "tokens", "cache_len", "last_token")

    def __init__(self):
        self.request: Optional[Request] = None
        self.tokens: List[int] = []
        self.cache_len: int = 0
        self.last_token: int = 0

    @property
    def active(self) -> bool:
        return self.request is not None

    def free(self, status: RequestStatus = RequestStatus.OK,
             error: Optional[str] = None) -> None:
        r = self.request
        r.output = np.asarray(self.tokens, np.int32)
        r.done = True
        r.status = status
        if error is not None:
            r.error = error
        self.request = None
        self.tokens = []
        self.cache_len = 0
        self.last_token = 0


class _PagePool:
    """Host-side refcounted allocator over the global KV page pool.

    Page 0 is the reserved null page: it is never handed out, dead
    block-table entries point at it, and every device-side write without a
    live target is routed into it.  Pages are refcounted objects: ``alloc``
    hands them out at refcount 1, prefix sharing adds one reference per
    aliasing reader (a slot's block-table entry or the prefix index) via
    ``incref``, and ``decref`` returns a page to the free list only when
    its last reader drops — so ``used_pages`` counts every page exactly
    once no matter how many readers alias it.  Dropping a reference the
    caller does not hold (double free) and referencing a free page both
    fail fast.  The free list is LIFO so recently retired (cache-hot)
    pages are reused first."""

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError("page pool needs >= 2 pages (one is the "
                             "reserved null page)")
        self.num_pages = num_pages
        self._free = list(range(num_pages - 1, 0, -1))
        self._refs: dict = {}  # page id -> refcount >= 1 (absent = free)

    @property
    def usable(self) -> int:
        return self.num_pages - 1

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        """Unique pages in use — a page aliased by N readers counts once
        (pool utilization must not be inflated by sharing)."""
        return self.usable - len(self._free)

    @property
    def shared_pages(self) -> int:
        """Pages currently aliased by more than one reader."""
        return sum(1 for c in self._refs.values() if c >= 2)

    def refcount(self, page: int) -> int:
        return self._refs.get(page, 0)

    def alloc(self, n: int) -> List[int]:
        if n > len(self._free):
            raise RuntimeError(
                f"KV page pool exhausted: asked {n}, have {len(self._free)} "
                "(reservation-gated admission should make this unreachable)")
        out = [self._free.pop() for _ in range(n)]
        for p in out:
            self._refs[p] = 1
        return out

    def incref(self, page: int) -> None:
        if page not in self._refs:
            raise RuntimeError(f"incref of free page {page}")
        self._refs[page] += 1

    def decref(self, page: int) -> bool:
        """Drop one reference; the page is freed only when the last reader
        drops (returns True then).  A page with live readers is never
        returned to the free list."""
        c = self._refs.get(page)
        if c is None:
            raise RuntimeError(f"double free of page {page}")
        if c == 1:
            del self._refs[page]
            self._free.append(page)
            return True
        self._refs[page] = c - 1
        return False

    def free(self, pages: List[int]) -> None:
        for p in pages:
            self.decref(p)


class _PrefixNode:
    """One fully written KV page of a cached prompt prefix: ``key`` is the
    page's ``page_size`` token ids, ``page`` the pool page holding that
    span's KV.  A root-to-node path spells a cached prefix."""

    __slots__ = ("key", "page", "parent", "children", "last_use")

    def __init__(self, key, page, parent):
        self.key = key
        self.page = page
        self.parent = parent
        self.children: dict = {}
        self.last_use = 0


class _PrefixIndex:
    """Host-side radix trie over cached prompt prefixes, page granularity.

    Each node is one *fully written* prompt page; the engine takes one pool
    reference per node, so cached pages outlive the slot that wrote them.
    Partial trailing prompt pages are never indexed (their tail rows are
    stale — and that exclusion is also what keeps decode appends and parked
    writes out of every indexed page).  Eviction removes least-recently-used
    leaves, so a cached prefix disappears tail-first; interior nodes become
    leaves as their children go."""

    def __init__(self, page_size: int):
        self.page_size = page_size
        self.root = _PrefixNode(None, None, None)
        self._clock = 0
        self.n_pages = 0  # live node count == pages the index references

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def lookup(self, prompt, ns: int = 0) -> tuple:
        """Longest cached prefix of ``prompt``: the chain of matched
        full-page nodes plus, when the next page diverges mid-page, the
        best partially matching child and its common-token count (the
        copy-on-write donor).  Touches matched nodes for LRU.

        ``ns`` is the sharing namespace (the slot batch's data shard under
        multi-device serving): node keys are ``(ns,) + page tokens``, so a
        prompt only ever matches pages registered by its OWN shard —
        paged pools are replicated-but-divergent, and a page written on
        another data-shard device holds garbage here."""
        ps = self.page_size
        now = self._tick()
        node, chain = self.root, []
        n_full = len(prompt) // ps
        while len(chain) < n_full:
            j = len(chain)
            key = (ns,) + tuple(int(t) for t in prompt[j * ps:(j + 1) * ps])
            child = node.children.get(key)
            if child is None:
                break
            child.last_use = now
            chain.append(child)
            node = child
        rest = [int(t) for t in prompt[len(chain) * ps:]]
        boundary, blcp = None, 0
        for key, child in node.children.items():
            if key[0] != ns:
                continue
            lcp = 0
            for a, b in zip(key[1:], rest):
                if a != b:
                    break
                lcp += 1
            if lcp > blcp:
                boundary, blcp = child, lcp
        if boundary is not None:
            boundary.last_use = now
        return chain, boundary, blcp

    def insert(self, prompt, pages, ns: int = 0) -> list:
        """Index ``pages[j]`` as the KV of prompt page j under namespace
        ``ns`` (see ``lookup``).  Returns the NEW
        nodes — the caller takes one pool reference per new node.  Groups
        whose token content is already cached keep the original page (two
        slots that prefilled the same prefix independently dedup to the
        first registrant; the second's pages stay private to it)."""
        ps = self.page_size
        now = self._tick()
        node, new = self.root, []
        for j in range(len(pages)):
            key = (ns,) + tuple(int(t) for t in prompt[j * ps:(j + 1) * ps])
            child = node.children.get(key)
            if child is None:
                child = _PrefixNode(key, pages[j], node)
                node.children[key] = child
                new.append(child)
                self.n_pages += 1
            child.last_use = now
            node = child
        return new

    def evict_coldest(self, evictable, force: bool = False):
        """Remove the least-recently-used leaf whose page satisfies
        ``evictable(page)`` and return its page id (None when no candidate).
        With ``force``, fall back to the coldest leaf regardless — dropping
        the index reference of a still-pinned page frees no memory now but
        unblocks its (index-only) ancestors for the next round, which is
        what guarantees capacity-pressure eviction always makes progress.

        The scan is O(nodes) per eviction — fine at current pool scales
        (the index can never outgrow the page pool); switch to an
        LRU-ordered leaf set if pools reach thousands of pages."""
        for pred in ((evictable, lambda p: True) if force else (evictable,)):
            best = None
            stack = [self.root]
            while stack:
                node = stack.pop()
                stack.extend(node.children.values())
                if (node is not self.root and not node.children
                        and pred(node.page)
                        and (best is None or node.last_use < best.last_use)):
                    best = node
            if best is not None:
                del best.parent.children[best.key]
                self.n_pages -= 1
                return best.page
        return None


class ServingEngine:
    def __init__(self, cfg: ModelConfig, packed_params, *, max_seq: int,
                 batch_slots: int = 4, ctx: Optional[Ctx] = None,
                 seed: int = 0, prefill_chunk: int = 32,
                 decode_block: int = 8, cache_dtype=jnp.bfloat16,
                 paged: bool = False, page_size: int = 16,
                 kv_pages: Optional[int] = None,
                 enable_prefix_sharing: bool = False,
                 prefix_cache_pages: Optional[int] = None,
                 device_sched: bool = True,
                 kv_quant: bool = False,
                 mesh=None,
                 shard_slots: bool = True,
                 shard_kv: bool = False,
                 kv_splits: Optional[int] = None,
                 block_deadline_s: Optional[float] = None,
                 dispatch_retries: int = 2,
                 dispatch_backoff_s: float = 0.0,
                 max_retries: int = 0,
                 retry_timeouts: bool = False,
                 retry_backoff_s: float = 0.02,
                 repromote: bool = True,
                 probe_cooldown_blocks: int = 2,
                 retry_breaker_threshold: int = 4,
                 retry_breaker_window: int = 16,
                 retry_breaker_cooldown: int = 8,
                 fault_injector: Optional[FaultInjector] = None,
                 audit_on_retire: bool = False,
                 on_block: Optional[Callable] = None,
                 on_token: Optional[Callable] = None):
        self.cfg = cfg
        self.params = packed_params
        self.max_seq = max_seq
        self.slots = batch_slots
        self.decode_block = max(1, decode_block)
        self.paged = bool(paged)
        self.device_sched = bool(device_sched)
        self.kv_quant = bool(kv_quant)
        # -- multi-device serving -------------------------------------------
        # mesh axes: 'data' shards the decode slot batch (each device owns
        # slots/dd lanes of every fused dispatch), 'model' shards
        # flash-decode attention over the KV sequence (canonical split-K
        # partials + an on-mesh partial-softmax combine).  mesh=None is the
        # byte-identical single-device engine.
        self.mesh = mesh
        if mesh is not None:
            if tuple(mesh.axis_names) != ("data", "model"):
                raise ValueError(
                    "ServingEngine mesh must have axis_names "
                    f"('data', 'model'); got {tuple(mesh.axis_names)}")
            if cfg.block_kind != "attn":
                raise ValueError(
                    "multi-device serving requires block_kind='attn' "
                    "(recurrent kinds keep the single-device engine); got "
                    f"{cfg.block_kind!r}")
        dd = int(mesh.shape["data"]) if mesh is not None else 1
        mm = int(mesh.shape["model"]) if mesh is not None else 1
        self.shard_slots = bool(shard_slots) and dd > 1
        self.shard_kv = bool(shard_kv) and mm > 1
        self.requested_slots = batch_slots
        if self.shard_slots and batch_slots % dd:
            # pad the slot axis up to a data-axis multiple; padded lanes
            # are permanently disabled (admission only ever assigns
            # slots[:batch_slots]), so the engine's request-facing
            # semantics are those of the requested slot count
            self.slots = -(-batch_slots // dd) * dd
        self._usable_slots = batch_slots
        self.mesh_shape = (dd, mm)
        self.slots_per_device = (self.slots // dd if self.shard_slots
                                 else self.slots)
        if kv_splits is None:
            self.kv_splits = mm if self.shard_kv else 0
        else:
            self.kv_splits = int(kv_splits)
            if self.kv_splits < 1:
                raise ValueError("kv_splits must be >= 1 when set")
        if self.shard_kv:
            # the split count must tile evenly over the model axis (each
            # rank owns kv_splits/mm canonical K-chunks)
            validate_num_splits(self.kv_splits, mm)
        if self.kv_quant and cfg.block_kind != "attn":
            raise ValueError(
                "kv_quant=True (int8 KV + per-(token, head) scales) requires "
                f"block_kind='attn'; got {cfg.block_kind!r}")
        if self.paged:
            if cfg.block_kind != "attn":
                raise ValueError(
                    "paged KV cache requires block_kind='attn' (recurrent "
                    f"kinds keep O(1) state per slot); got {cfg.block_kind!r}")
            self.page_size = max(1, min(int(page_size), max_seq))
            self.pages_per_slot = -(-max_seq // self.page_size)
            # default pool: full provisioning (every slot can reach max_seq)
            # + the null page; pass a smaller kv_pages to trade capacity for
            # memory — admission then defers when reservations would overflow
            self.kv_pages = (int(kv_pages) if kv_pages is not None
                             else batch_slots * self.pages_per_slot + 1)
        else:
            self.page_size = None
            self.pages_per_slot = 0
            self.kv_pages = 0
        self.enable_prefix_sharing = bool(enable_prefix_sharing)
        if self.enable_prefix_sharing and not self.paged:
            raise ValueError("enable_prefix_sharing requires paged=True "
                             "(prefix reuse aliases KV pool pages through "
                             "the block table)")
        if prefix_cache_pages is not None and int(prefix_cache_pages) < 0:
            raise ValueError("prefix_cache_pages must be >= 0 (or None for "
                             "unbounded caching under pool pressure)")
        self.prefix_cache_pages = (None if prefix_cache_pages is None
                                   else int(prefix_cache_pages))
        self._prefix = None  # built per run() when sharing is enabled
        # any chunk size <= max_seq works: a final chunk that would run past
        # the end of its cache row is shifted back to end exactly at
        # max_seq (its leading overlap rewrites positions the previous
        # chunk already covered — same tokens, same absolute positions)
        self.prefill_chunk = max(1, min(prefill_chunk, max_seq))
        self._chunked = cfg.block_kind == "attn"
        self.cache_dtype = cache_dtype
        self.ctx = ctx or Ctx(mode="packed", group_size=cfg.group_size,
                              attn_q_chunk=128, attn_kv_chunk=128)
        if self.kv_splits:
            # canonical K-chunk split-K decode attention — the only
            # formulation with the cross-shard bitwise contract (see
            # kernels/decode_attention/ops.splitk_partials); kv_shard_axis
            # routes the chunks across the mesh's 'model' ranks
            self.ctx = dataclasses.replace(
                self.ctx, kv_splits=self.kv_splits,
                kv_shard_axis="model" if self.shard_kv else None,
                kv_shard_size=mm if self.shard_kv else 1)
        self.seed = seed
        self.stats: dict = {}
        # -- robustness layer ---------------------------------------------
        # block_deadline_s bounds ONE fused-block dispatch + its gating
        # readback (serving watchdog, non-process-killing: a trip is an
        # integrity event, not an abort); dispatch_retries re-issues a
        # dispatch that failed host-side BEFORE the jit call (no donated
        # buffer lost); on_block(engine, block_ordinal) runs after every
        # block's bookkeeping (monitoring / deterministic cancel seam).
        self.block_deadline_s = block_deadline_s
        self.dispatch_retries = max(0, int(dispatch_retries))
        self.dispatch_backoff_s = float(dispatch_backoff_s)
        self.fault_injector = fault_injector
        self.audit_on_retire = bool(audit_on_retire)
        self.on_block = on_block
        # streaming seam: on_token(request, token) fires host-side at the
        # moment each token is read back (first token at admission
        # completion, decode tokens at block readback — one block behind
        # the device in device-resident mode).  Tokens arrive in emit
        # order, once each; a retry's replayed (carried) tokens are NOT
        # re-fired (the failed attempt already delivered them).
        self.on_token = on_token
        # -- recovery layer -----------------------------------------------
        # max_retries budgets request re-queues after a FAILED (and, with
        # retry_timeouts, TIMEOUT) retirement: the re-queued attempt replays
        # prompt + tokens-emitted-so-far as its prefill, so greedy output is
        # token-identical to an uninterrupted run.  retry_backoff_s seeds a
        # deterministic exponential backoff before re-admission.  repromote
        # lets a degraded run probe device health with a canary dispatch and
        # return to device-resident scheduling once the cause clears; both
        # paths are gated by circuit breakers so a persistent fault
        # converges to stable host-driven service instead of thrashing.
        self.max_retries = max(0, int(max_retries))
        self.retry_timeouts = bool(retry_timeouts)
        self.retry_backoff_s = float(retry_backoff_s)
        self.repromote = bool(repromote)
        self.probe_cooldown_blocks = max(1, int(probe_cooldown_blocks))
        self.retry_breaker_threshold = max(1, int(retry_breaker_threshold))
        self.retry_breaker_window = max(1, int(retry_breaker_window))
        self.retry_breaker_cooldown = max(1, int(retry_breaker_cooldown))

        cfg_, ctx_ = self.cfg, self.ctx
        max_seq_, block_ = self.max_seq, self.decode_block
        paged_ = self.paged
        # contiguous mode passes this inert placeholder for the block-table
        # argument (the traced value is unused and DCE'd)
        self._no_bt = jnp.zeros((1, 1), jnp.int32)

        def _sample(logits, seeds, emitted, temps):
            """Per-slot sampling: greedy, or categorical keyed by
            fold_in(PRNGKey(request seed), emitted-token index) — the output
            depends only on the request, never on slot or tick order.  The
            PRNG work is skipped entirely (lax.cond) when the whole batch is
            greedy."""
            with jax.named_scope("sample"):
                greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

                def with_temperature(_):
                    def one(seed, idx, row, t):
                        key = jax.random.fold_in(jax.random.PRNGKey(seed),
                                                 idx)
                        return jax.random.categorical(
                            key,
                            row.astype(jnp.float32) / jnp.maximum(t, 1e-6))
                    sampled = jax.vmap(one)(seeds, emitted, logits,
                                            temps).astype(jnp.int32)
                    return jnp.where(temps > 0.0, sampled, greedy)

                return jax.lax.cond(jnp.any(temps > 0.0), with_temperature,
                                    lambda _: greedy, None)

        def _prefill_chunks(params, tokens, cache, bt, offsets, admit_mask,
                            last_idx, seeds, temps, emit_idx):
            """One admission wave: a (slots, C) chunk batch written in place
            at per-row offsets; rows not admitting are masked.  First tokens
            for rows whose prompt ends in this chunk are sampled on device
            at per-row emitted index ``emit_idx`` — 0 for a fresh admission,
            the carried-token count for a retry replay (so temperature
            sampling folds in the same per-token key an uninterrupted run
            would have used).  In paged mode ``bt`` is the (slots,
            pages_per_slot) block table and the chunk KV is scattered into
            the page pool."""
            logits, cache = transformer.prefill_chunk(
                cfg_, params, tokens, ctx_, cache, offsets=offsets,
                admit_mask=admit_mask, last_index=last_idx,
                page_table=bt if paged_ else None)
            first = _sample(logits, seeds, emit_idx, temps)
            return first, cache

        def _make_tick(params, bt, max_new, temps, seeds, nan_mask):
            """The single decode tick shared by the host-driven and the
            device-resident block: one decode_step + sample + bookkeeping
            over the (tokens, cache, cache_len, emitted, active, bad)
            carry.  ``bad`` is the in-block integrity flag: a lane whose
            logits go non-finite on any tick is latched bad for the block
            and reported in the same readback as its tokens (one extra
            (slots,) bool per block, no additional sync).  ``nan_mask`` is
            the fault-injection seam — all-False in production, where the
            ``jnp.where`` select is an exact identity."""

            def tick(carry, _):
                tokens, cache, cache_len, emitted, active, bad = carry
                # park inactive lanes' cache write at flat address max_seq.
                # An inactive lane is not necessarily empty: a mid-admission
                # lane already holds written prompt KV that a cache_len-0
                # write would clobber.  Contiguous mode clamps the park to
                # row position max_seq - 1 (masked by the live length or
                # never attended again — see the host-side assert).  Paged
                # mode resolves max_seq through the block table to a page
                # that can never hold a live token: past the table entirely
                # (routed to the null page) or, when page_size does not
                # divide max_seq, the final page's slack row past position
                # max_seq - 1.
                step_len = jnp.where(active, cache_len, max_seq_)
                logits, cache = transformer.decode_step(
                    cfg_, params, tokens[:, None], ctx_, cache, step_len,
                    page_table=bt if paged_ else None)
                logits = jnp.where(nan_mask[:, None], jnp.nan, logits)
                # integrity guard: latch lanes whose logits went non-finite
                # (NaN/inf anywhere in the row poisons the sample)
                bad = jnp.logical_or(bad, jnp.logical_and(
                    active,
                    jnp.logical_not(jnp.all(jnp.isfinite(
                        logits.astype(jnp.float32)), axis=-1))))
                nxt = _sample(logits, seeds, emitted, temps)
                out = jnp.where(active, nxt, 0)
                tokens = jnp.where(active, nxt, tokens)
                cache_len = jnp.where(active, cache_len + 1, cache_len)
                emitted = jnp.where(active, emitted + 1, emitted)
                done = jnp.logical_or(emitted >= max_new,
                                      cache_len >= max_seq_)
                new_active = jnp.logical_and(active, jnp.logical_not(done))
                return ((tokens, cache, cache_len, emitted, new_active, bad),
                        (out, active))

            return tick

        def _decode_block(params, tokens, cache, bt, cache_len, emitted,
                          max_new, active, temps, seeds, nan_mask):
            """Fused multi-tick decode: scan `decode_block` ticks on device.

            Finished lanes keep ticking under a mask (static scan shape):
            they emit pad token 0 and their bookkeeping freezes.  Their KV
            write is parked at flat address ``max_seq``: contiguous mode
            clamps that to the row tail (position ``max_seq - 1``), where it
            is either masked by the live length or, for a lane that filled
            its row, never attended again before the slot is retired
            (asserted host-side); paged mode resolves it through the block
            table to a location no live token can occupy — the null page, or
            the final page's slack row when page_size does not divide
            max_seq.
            """
            tick = _make_tick(params, bt, max_new, temps, seeds, nan_mask)
            carry = (tokens, cache, cache_len, emitted, active,
                     jnp.zeros_like(active))
            (tokens, cache, cache_len, emitted, active, bad), (blk, mask) = \
                jax.lax.scan(tick, carry, None, length=block_)
            return blk.T, mask.T, bad, cache  # (slots, decode_block) each

        def _decode_block_dev(params, state, cache, bt, nan_mask):
            """Device-resident fused decode block: the whole per-slot
            scheduler carry (``last_token``/``cache_len``/``emitted``/
            ``active`` plus the per-request sampling constants) lives in
            the donated ``state`` pytree and is threaded block-to-block on
            device — dispatching block N+1 needs no host value from block
            N, so the host never sits between blocks in steady state."""
            tick = _make_tick(params, bt, state["max_new"], state["temps"],
                              state["seeds"], nan_mask)
            carry = (state["last_token"], cache, state["cache_len"],
                     state["emitted"], state["active"],
                     jnp.zeros_like(state["active"]))
            (tokens, cache, cache_len, emitted, active, bad), (blk, mask) = \
                jax.lax.scan(tick, carry, None, length=block_)
            state = dict(state, last_token=tokens, cache_len=cache_len,
                         emitted=emitted, active=active)
            return state, blk.T, mask.T, bad, cache

        @functools.partial(jax.jit, donate_argnums=(0,))
        def _admit_lanes(state, first, upd, activate, cache_len, emit0,
                         max_new, temps, seeds):
            """Merge completed admissions into the device scheduler state:
            rows under ``upd`` take the wave's on-device first token as
            ``last_token`` (the token never visits the host on its way into
            decode), reset their counters (``emit0`` is 1 for a fresh
            admission, carried + 1 for a retry replay), and activate —
            unless the request already finished at prefill (``activate``
            false)."""
            sel = lambda new, old: jnp.where(upd, new, old)
            return {
                "last_token": sel(first, state["last_token"]),
                "cache_len": sel(cache_len, state["cache_len"]),
                "emitted": sel(emit0, state["emitted"]),
                "active": jnp.where(upd, activate, state["active"]),
                "max_new": sel(max_new, state["max_new"]),
                "temps": jnp.where(upd, temps, state["temps"]),
                "seeds": sel(seeds, state["seeds"]),
            }

        @functools.partial(jax.jit, donate_argnums=(0,))
        def _set_bt_row(bt, i, row):
            """In-place update of one block-table row on device (slot grant/
            growth installs its pages; retirement clears to the null page).
            Row-granular so the resident table is never re-uploaded whole."""
            return jax.lax.dynamic_update_slice(
                bt, row[None].astype(bt.dtype), (i, 0))

        @functools.partial(jax.jit, donate_argnums=(0,))
        def _kill_lane(state, i):
            """Force-deactivate lane i in the resident scheduler state —
            the device half of a host-initiated retirement (timeout,
            cancellation, integrity failure).  In the normal flow lanes
            deactivate THEMSELVES; this is the only transition the host
            pushes onto the device mid-run, and it is a single scalar
            update so it composes with in-flight blocks like a
            block-table row patch does."""
            return dict(state, active=state["active"].at[i].set(False))

        # legacy whole-prompt admission (recurrent kinds: SSM/xLSTM state
        # cannot resume chunk-to-chunk) — donor prefill + adopt, PR 1 style
        @jax.jit
        def _prefill_full(params, tokens, cache, lengths):
            return transformer.prefill_step(cfg_, params, tokens, ctx_,
                                            cache, lengths=lengths)

        def _cow_copy_page(cache, src, dst):
            """Copy-on-write split: duplicate pool page ``src`` onto the
            freshly allocated ``dst`` (all layers, K and V planes) so the
            new owner can write into the page tail without disturbing the
            donor's readers.  src/dst are traced — one compiled program."""
            return transformer.copy_paged_page(cache, src, dst)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def _adopt(cache, one_cache, slot):
            def write(full, new):
                start = (0, slot) + (0,) * (full.ndim - 2)
                return jax.lax.dynamic_update_slice(
                    full, new.astype(full.dtype), start)
            return jax.tree_util.tree_map(write, cache, one_cache)

        self._sample_tokens = jax.jit(_sample)
        if mesh is None:
            self._prefill_chunks = jax.jit(_prefill_chunks,
                                           donate_argnums=(2,))
            self._decode_block = jax.jit(_decode_block, donate_argnums=(2,))
            self._decode_block_dev = jax.jit(_decode_block_dev,
                                             donate_argnums=(1, 2))
            self._cow_copy_page = jax.jit(_cow_copy_page,
                                          donate_argnums=(0,))
            self._shardings = None
        else:
            # shard_map the three fused dispatches (and the CoW page copy)
            # over the mesh.  Scheduler-pytree leaves, per-slot operands and
            # decode-block outputs shard their slot axis over 'data'; the
            # contiguous cache genuinely shards its slot row axis; paged
            # pools are replicated-but-DIVERGENT (each data shard writes
            # only its own slots' pages and the pools are never read back),
            # so every function that touches them must run under shard_map
            # with the replication check disabled — a plain jit could let
            # GSPMD reshard (consolidate) them, mixing replicas.
            specs = shardlib.serving_specs(
                mesh, slots=self.slots, paged=self.paged,
                kv_quant=self.kv_quant, shard_slots=self.shard_slots)
            st, cs, bts = specs["state"], specs["cache"], specs["bt"]
            blks, toks = specs["blk"], specs["tokens"]
            rep = P()
            smap = functools.partial(jax.shard_map, mesh=mesh,
                                     check_vma=False)
            self._prefill_chunks = jax.jit(smap(
                _prefill_chunks,
                in_specs=(rep, toks, cs, bts, st, st, st, st, st, st),
                out_specs=(st, cs)), donate_argnums=(2,))
            self._decode_block = jax.jit(smap(
                _decode_block,
                in_specs=(rep, st, cs, bts, st, st, st, st, st, st, st),
                out_specs=(blks, blks, st, cs)), donate_argnums=(2,))
            self._decode_block_dev = jax.jit(smap(
                _decode_block_dev,
                in_specs=(rep, st, cs, bts, st),
                out_specs=(st, blks, blks, st, cs)), donate_argnums=(1, 2))
            self._cow_copy_page = jax.jit(smap(
                _cow_copy_page, in_specs=(cs, rep, rep), out_specs=cs),
                donate_argnums=(0,))
            self._shardings = shardlib.serving_shardings(
                mesh, {"state": st, "bt": bts, "cache": cs})
            # replicate the weights over the mesh once: left on one device
            # they would be re-broadcast to every shard by each dispatch
            self.params = jax.device_put(packed_params,
                                         NamedSharding(mesh, rep))
        # expand the ternary codes once, in one program, and keep the int8
        # matrices resident: nothing about them changes between dispatches.
        # Only the stacked layers are expanded; the embedding and norms stay
        # the caller's arrays rather than copies.
        def expand(layers):
            return transformer.predecode_packed(
                cfg_, {"layers": layers})["layers"]

        placed = ({} if mesh is None
                  else {"out_shardings": NamedSharding(mesh, P())})
        self.params = dict(self.params, layers=jax.jit(expand, **placed)(
            self.params["layers"]))
        self._admit_lanes = _admit_lanes
        self._set_bt_row = _set_bt_row
        self._kill_lane = _kill_lane
        self._prefill_full = _prefill_full
        self._adopt = _adopt
        # production NaN-injection mask: all-False, allocated once (the
        # in-block jnp.where select is then an exact identity)
        self._no_nan = jnp.zeros((self.slots,), jnp.bool_)
        if self._shardings is not None:
            self._no_nan = jax.device_put(self._no_nan,
                                          self._shardings["state"])
        # canary probe: a tiny dedicated jit (NOT the real fused block —
        # that donates the live state and cache, which a failing probe
        # must never put at risk).  It exercises the same dispatch seam
        # (fi.on_dispatch, the watchdog deadline) the real block does, so
        # a wedged device fails the probe and a recovered one passes it.
        self._canary_jit = jax.jit(lambda x: (x * 2 + 1).sum())
        self._canary_arg = jnp.arange(8, dtype=jnp.int32)

        # -- resident lifecycle --------------------------------------------
        # Engine-LIFETIME counters: monotone across windows, never reset by
        # run()/reset_stats().  Window (per-run) stats live in self.stats.
        self.lifetime = {
            "arrivals": 0, "windows": 0, "faults_injected": 0,
            "admissions": 0, "decode_blocks": 0, "decode_tokens": 0,
            "total_new_tokens": 0, "requests_retried": 0, "retries_total": 0,
        }
        self.lifetime.update({k: 0 for k in _STATUS_COUNTERS.values()})
        self._closed = False
        self._span = None  # the beat stage's open host span
        self._span_stage, self._span_t0 = None, 0.0
        self._reset_engine_state()
        self.reset_stats()

    def compiled_shapes(self) -> dict:
        """Live jit-cache entry counts (the O(1)-compile invariant; holds
        for paged mode too — the block table has one static width).

        Values are None when the private jit cache introspection is
        unavailable (it is not public JAX API and has drifted before)."""
        def size(fn):
            try:
                return fn._cache_size()
            except AttributeError:
                return None
        return {"prefill_chunk": size(self._prefill_chunks),
                "decode_block": size(self._decode_block_dev
                                     if self.device_sched
                                     else self._decode_block)}

    # -- paged-pool bookkeeping (host side) --------------------------------

    def worst_case_pages(self, req: Request) -> int:
        """Pages this request can ever need (its admission reservation):
        the row stores at most min(prompt + max_new - 1, max_seq) KV
        entries — the final emitted token's KV is never written (a lane is
        done the tick it appears).  Public so benchmarks/schedulers share
        the engine's reservation formula instead of re-deriving it."""
        if not self.paged:
            raise ValueError("worst_case_pages is only meaningful on a "
                             "paged engine (paged=True)")
        total = min(len(req.prompt) + req.max_new_tokens - 1, self.max_seq)
        return -(-total // self.page_size)

    def _alloc_pages(self, n: int) -> List[int]:
        """Pool alloc with capacity-pressure eviction: when the free list
        cannot cover ``n``, LRU cached-prefix leaves are evicted first
        (pages nobody else reads free immediately; still-pinned leaves
        merely drop their index reference, unblocking index-only ancestors
        for the next round).  The admission gate guarantees this always
        finds enough pages (see the prefix-sharing invariants in the class
        docstring)."""
        if self.fault_injector is not None:
            # injection seam: a scheduled alloc fault raises BEFORE any
            # eviction or pool mutation, so the abort path rolls back from
            # a consistent state
            self.fault_injector.on_alloc()
        if self._prefix is not None:
            while self._pool.free_pages < n and self._evict_one_prefix():
                pass
        out = self._pool.alloc(n)
        st = self.stats
        st["kv_pages_peak"] = max(st["kv_pages_peak"], self._pool.used_pages)
        return out

    def _own_page(self, i: int, pid: int, j: int) -> None:
        """Install a freshly allocated page (refcount 1: this slot alone —
        the writable-frontier invariant) at block-table position j of
        slot i.  Callers batch the device-row push (``_push_bt_row``) after
        all of a slot's installs."""
        self._bt[i, j] = pid
        self._slot_pages[i].append(pid)
        self._page_slot_refs[pid] = self._page_slot_refs.get(pid, 0) + 1
        self._backed.add(pid)

    def _push_bt_row(self, i: int) -> None:
        """Mirror slot i's host block-table row into the resident device
        table as a row-granular dynamic update.  Before the first dispatch
        (``_bt_dev`` still None) there is nothing to patch — the lazy full
        upload in ``_bt_device`` picks the row up.  This replaces the old
        whole-table invalidate/re-upload on every grow/grant/retire."""
        if self._bt_dev is not None:
            self._bt_dev = self._set_bt_row(
                self._bt_dev, jnp.asarray(i, jnp.int32),
                jnp.asarray(self._bt[i]))

    def _grow_pages(self, i: int, upto_tokens: int) -> None:
        """Extend slot i's page list to cover flat positions
        [0, upto_tokens).  Pre-granted shared pages count toward coverage;
        growth never exceeds the slot's admission reservation (which
        excludes them), so the pool can't run dry mid-flight.  Host-driven
        scheduling grows lazily (prefill frontier / next decode block);
        device-resident scheduling pre-grants the whole reservation at
        admission, making every later call a no-op."""
        need = -(-upto_tokens // self.page_size)
        pages = self._slot_pages[i]
        if need <= len(pages):
            return
        new = self._alloc_pages(need - len(pages))
        for j, pid in enumerate(new, start=len(pages)):
            self._own_page(i, pid, j)
        self._push_bt_row(i)

    def _pinned_unreserved(self) -> int:
        """Unique pages kept alive by slot references but not covered by
        any active slot's reservation: their allocating slot retired while
        sharers (and possibly the index) still read them.  The admission
        gate adds this to the reservation sum so legacy shared pages can
        never starve lazy growth."""
        return sum(1 for p in self._page_slot_refs
                   if p not in self._backed)

    def _release_slot_pages(self, i: int) -> None:
        """Return slot i's KV bookkeeping to the pool: drop one reference
        per page it reads (shared prefix pages survive while the index or
        other slots still read them; exclusively owned pages return to the
        free list), return its reservation, and zero its block-table row
        so later writes by the dead lane land in the null page.  The
        device table gets a row-granular clear (not a full re-upload):
        retirement is a single dynamic-update-slice on the resident array,
        so it composes with in-flight decode blocks under the
        device-resident scheduler (ordering by data dependence through the
        threaded cache/table).  Shared by every retirement path — normal
        completion, admission abort, and fault/timeout/cancel retirement —
        so the refcount discipline is identical no matter why a lane
        dies."""
        self._sched_epoch += 1
        if not self.paged:
            return
        # detach the slot's bookkeeping before dropping any reference,
        # so the pool and block tables always agree
        pages, self._slot_pages[i] = self._slot_pages[i], []
        shared_n = self._slot_shared_n[i]
        self._slot_shared_n[i] = 0
        if self._prefix is not None:
            # registrations outlive a normally retired slot (the index
            # holds its own refs); forget the provenance so a fault in the
            # slot's NEXT occupant cannot withdraw them
            self._slot_reg_nodes[i] = []
        self._reserved_total -= self._slot_reserved[i]
        self._slot_reserved[i] = 0
        self._bt[i, :] = 0
        self._push_bt_row(i)
        for j, p in enumerate(pages):
            if j >= shared_n:
                self._backed.discard(p)
            self._page_slot_refs[p] -= 1
            if not self._page_slot_refs[p]:
                del self._page_slot_refs[p]
            self._pool.decref(p)
        if self._prefix is not None and self.prefix_cache_pages is not None:
            # pages this slot pinned may have just become index-only
            self._enforce_prefix_cap()

    def _free_slot(self, slots, i: int,
                   status: RequestStatus = RequestStatus.OK,
                   error: Optional[str] = None) -> None:
        """Retire slot i: emit its output (with ``status``) and release its
        pages/reservation via ``_release_slot_pages``.  An OK completion
        after the engine degraded to the host-driven path is stamped
        DEGRADED instead (correct tokens, reduced service level)."""
        if status is RequestStatus.OK and self._degraded:
            status = RequestStatus.DEGRADED
            self.stats["requests_degraded"] += 1
        req = slots[i].request
        self._release_slot_pages(i)
        slots[i].free(status, error)
        if req.retries and status in (RequestStatus.OK,
                                      RequestStatus.DEGRADED):
            # a retried request completing is the retry breaker's success
            # signal: transient faults really are clearing
            self._retry_breaker.record_success()

    def _fault_retire(self, slots, i: int, status: RequestStatus,
                      error: str, rollback_prefix: bool = False) -> None:
        """Retire slot i mid-flight on a containment event (integrity
        failure, timeout, cancellation): the request keeps its tokens so
        far, is stamped ``status``/``error``, its pages and reservation
        roll back refcount-exact, and — under the device-resident
        scheduler — the lane is force-deactivated in the resident state so
        later blocks tick it fully masked.  With ``rollback_prefix`` the
        pages this slot registered in the prefix trie are withdrawn too
        (a faulted lane's KV must not be granted to future admissions)."""
        st = self.stats
        if rollback_prefix:
            self._unregister_prefix(i)
        if self._dev_active and self._state is not None:
            self._state = self._kill_lane(self._state,
                                          jnp.asarray(i, jnp.int32))
        req = slots[i].request
        self._free_slot(slots, i, status, error)
        st[_STATUS_COUNTERS[status]] += 1
        self._maybe_retry(req)
        if self.audit_on_retire:
            self.audit()

    def _abort_admission(self, pending: dict, i: int, status: RequestStatus,
                         error: str) -> None:
        """Abort a PENDING admission (its lane never activated): the
        request retires with no output, granted/owned pages and the
        reservation roll back, and the slot returns to FREE.  Partially
        prefilled KV in the released pages is stale-by-construction: a
        recycled page's next owner rewrites every position below its live
        length and attention masks the rest."""
        admit = pending.pop(i)
        req = admit["req"]
        # a replayed admission keeps the carried tokens of its withdrawn
        # attempt (an abort loses this attempt's prefill, not the request's
        # committed progress); a fresh admission has none
        req.output = np.asarray(self._carried(req), np.int32)
        req.done = True
        req.status = status
        req.error = error
        self._release_slot_pages(i)
        self.stats[_STATUS_COUNTERS[status]] += 1
        self._maybe_retry(req)
        if self.audit_on_retire:
            self.audit()

    def _reject_started_head(self, queue, i: int, error: str) -> None:
        """A fault between reservation and admission start (prefix-grant
        CoW allocation): the queue head retires FAILED, and whatever the
        slot already holds — aliased grant pages, the reservation — rolls
        back through the shared release path."""
        req = queue.popleft()
        req.output = np.asarray(self._carried(req), np.int32)
        req.done = True
        req.status = RequestStatus.FAILED
        req.error = error
        self._release_slot_pages(i)
        self.stats[_STATUS_COUNTERS[RequestStatus.FAILED]] += 1
        self._maybe_retry(req)
        if self.audit_on_retire:
            self.audit()

    # -- budgeted retry with progress replay (host side) -------------------

    def _carried(self, req: Request) -> list:
        """Tokens a withdrawn attempt already committed (empty for a fresh
        request).  A retry replays them as prompt suffix, so the new
        attempt's first sampled token continues exactly where the failed
        one stopped."""
        return getattr(req, "_replay_tokens", None) or []

    def _eff_prompt(self, req: Request):
        """The prefill the CURRENT attempt runs: the raw prompt, or — for a
        retry — ``prompt + tokens emitted so far``.  Every admission-side
        consumer (validation, prefix lookup/registration, chunk waves) uses
        this view; ``req.prompt`` stays the user's original request.  The
        worst-case page reservation is invariant under replay:
        ``eff_plen + remaining - 1 == plen + max_new - 1``."""
        p = getattr(req, "_replay_prompt", None)
        return p if p is not None else req.prompt

    def _retry_budget(self, req: Request) -> int:
        return (int(req.max_retries) if req.max_retries is not None
                else self.max_retries)

    def _maybe_retry(self, req: Request) -> None:
        """Budgeted retry: called right after ``req`` was stamped with a
        terminal status.  If the status is retryable (FAILED; TIMEOUT too
        with ``retry_timeouts``), budget remains, and the retry circuit
        breaker is not open, the stamp is withdrawn and the request waits
        out a seeded-deterministic exponential backoff before re-entering
        admission with its progress replayed (``_eff_prompt``).  The pages
        the failed attempt held were already rolled back by the shared
        release path, so the retry allocates from a clean slate — and
        prefix sharing makes the replayed prefill cheap when the prompt's
        pages are still cached."""
        status = req.status
        if status not in (RequestStatus.FAILED, RequestStatus.TIMEOUT):
            return
        if status is RequestStatus.TIMEOUT and not self.retry_timeouts:
            return
        if self._retry_budget(req) <= 0:
            return
        # every retryable failure is breaker evidence, whether or not this
        # particular request has budget left
        self._retry_breaker.record_failure()
        if req.retries >= self._retry_budget(req):
            return
        if not self._retry_breaker.allow():
            self.stats["retries_denied_breaker"] += 1
            return
        st = self.stats
        st[_STATUS_COUNTERS[status]] -= 1  # the stamp is withdrawn
        tokens = req.output.tolist() if req.output is not None else []
        req.retry_errors.append(
            f"attempt {req.attempts} [{status.value}]: {req.error}")
        req.done = False
        req.status = None
        req.error = None
        req.output = None
        req.retries += 1
        st["retries_total"] += 1
        req._replay_tokens = tokens
        req._replay_prompt = (np.concatenate(
            [np.asarray(req.prompt, np.int32),
             np.asarray(tokens, np.int32)]) if tokens
            else np.asarray(req.prompt, np.int32))
        delay = backoff_delay(self.retry_backoff_s, req.retries - 1,
                              seed=self.seed * 1000003 + req.seed)
        st["retry_backoff_s"] += delay
        now = time.perf_counter()
        # per-attempt deadline: the budget restarts when the retry rejoins
        # the queue (measuring it from run start would make every retried
        # TIMEOUT stillborn)
        req._deadline_t0 = now + delay
        self._retryq.append({"req": req, "not_before": now + delay})

    def _pump_retries(self, queue) -> None:
        """Move retry-wait requests whose backoff elapsed to the admission
        queue tail (FIFO with fresh arrivals)."""
        if not self._retryq:
            return
        now = time.perf_counter()
        ready = [e for e in self._retryq if e["not_before"] <= now]
        if not ready:
            return
        self._retryq = [e for e in self._retryq if e["not_before"] > now]
        for e in ready:
            queue.append(e["req"])

    def _unregister_prefix(self, i: int) -> None:
        """Withdraw the prefix-trie nodes slot i registered (deepest
        first).  A node another prompt has since extended under stays —
        its page was fully written before the fault window — but every
        leaf this slot contributed drops its index reference."""
        if self._prefix is None:
            return
        nodes = self._slot_reg_nodes[i]
        self._slot_reg_nodes[i] = []
        for node in reversed(nodes):
            if node.children or node.parent is None:
                continue
            if node.parent.children.get(node.key) is not node:
                continue  # already evicted
            del node.parent.children[node.key]
            self._prefix.n_pages -= 1
            self._pool.decref(node.page)

    def _reject(self, req: Request, error: str) -> None:
        """Admission-time validation failure: the request never touches a
        slot, a page, or the device — it is reported on the request object
        (REJECTED) instead of raising out of ``run()`` and orphaning every
        in-flight lane."""
        req.output = np.zeros((0,), np.int32)
        req.done = True
        req.status = RequestStatus.REJECTED
        req.error = error
        self.stats["requests_rejected"] += 1

    def _validate(self, req: Request) -> Optional[str]:
        """Admission gate: return the rejection reason, or None when the
        request is servable.  Order matters — shape checks before content
        checks (an empty prompt has no min/max).  Checks run against the
        effective prompt (prompt + carried tokens for a retry replay) —
        a replay can never fail a check its first attempt passed: its
        length stays <= the original worst case and its tokens are
        engine-emitted, hence in-vocab."""
        prompt = self._eff_prompt(req)
        if len(prompt) < 1:
            return "prompt must have at least one token"
        if len(prompt) > self.max_seq:
            return (f"prompt length {len(prompt)} > max_seq "
                    f"{self.max_seq}")
        if req.max_new_tokens < 1:  # prefill always emits a first token
            return "max_new_tokens must be >= 1"
        if self.cfg.frontend == "token" and (
                int(np.min(prompt)) < 0
                or int(np.max(prompt)) >= self.cfg.vocab_size):
            # out-of-vocab ids make jnp.take fill NaN embeddings; the
            # lane's KV writes (including null-page parks) then poison
            # OTHER lanes through masked-position 0*NaN — reject at
            # admission instead of corrupting outputs schedule-dependently
            return (f"prompt token ids must be in "
                    f"[0, {self.cfg.vocab_size})")
        if self.paged and self.worst_case_pages(req) > self._pool.usable:
            return (f"request needs {self.worst_case_pages(req)} KV pages "
                    f"worst-case but the pool only has "
                    f"{self._pool.usable}; raise kv_pages or shrink the "
                    "request")
        return None

    def cancel(self, req: Request) -> None:
        """Request cancellation: observed at the next block/wave boundary.
        Queued requests retire without running; pending admissions abort;
        live lanes keep their tokens so far.  Status CANCELLED."""
        req.cancelled = True

    def _expired(self, req: Request) -> bool:
        if req.deadline_s is None:
            return False
        # every request measures its budget from its own ``_deadline_t0``:
        # stamped at submit() (arrival) for a fresh request, restamped at
        # ``not_before`` when a retry is scheduled.  Nothing is measured
        # from run()/window start — a late arrival never burns budget it
        # was not yet queued for.
        start = getattr(req, "_deadline_t0", None)
        if start is None:
            start = self._window_t0
        return time.perf_counter() - start > req.deadline_s

    def _police(self, slots, pending: dict, queue) -> None:
        """Block-boundary sweep of the cancellation and deadline
        contracts over all four request pools (queued, retry-wait, pending
        admission, live lane).  Runs host-side only — no device sync; a
        live lane's force-deactivation is a scalar device update."""
        for r in list(queue):
            why = (RequestStatus.CANCELLED if r.cancelled else
                   RequestStatus.TIMEOUT if self._expired(r) else None)
            if why is not None:
                queue.remove(r)
                r.output = np.asarray(self._carried(r), np.int32)
                r.done = True
                r.status = why
                r.error = ("cancelled before admission"
                           if why is RequestStatus.CANCELLED
                           else f"deadline_s={r.deadline_s} expired in queue")
                self.stats[_STATUS_COUNTERS[why]] += 1
                self._maybe_retry(r)
        for e in list(self._retryq):
            r = e["req"]
            # a deadline cannot expire while waiting out backoff (the
            # per-attempt clock starts at not_before), but cancellation is
            # observed here like in every other pool
            if r.cancelled:
                self._retryq.remove(e)
                r.output = np.asarray(self._carried(r), np.int32)
                r.done = True
                r.status = RequestStatus.CANCELLED
                r.error = "cancelled while waiting to retry"
                self.stats[_STATUS_COUNTERS[RequestStatus.CANCELLED]] += 1
        for i in list(pending):
            r = pending[i]["req"]
            if r.cancelled:
                self._abort_admission(pending, i, RequestStatus.CANCELLED,
                                      "cancelled during admission")
            elif self._expired(r):
                self._abort_admission(
                    pending, i, RequestStatus.TIMEOUT,
                    f"deadline_s={r.deadline_s} expired during admission")
        for i, s in enumerate(slots):
            if not s.active:
                continue
            r = s.request
            if r.cancelled:
                self._fault_retire(slots, i, RequestStatus.CANCELLED,
                                   "cancelled mid-decode")
            elif self._expired(r):
                self._fault_retire(
                    slots, i, RequestStatus.TIMEOUT,
                    f"deadline_s={r.deadline_s} expired mid-decode")

    # -- prefix sharing (host side) ----------------------------------------

    def _slot_shard(self, i: int) -> int:
        """Data-shard owning slot ``i`` (0 when slots are unsharded) —
        the prefix-sharing namespace: under slot sharding each device only
        writes its own slots' pages into its (divergent) pool replica, so
        a grant is only valid between slots on the same shard."""
        return i // self.slots_per_device if self.shard_slots else 0

    def _prefix_lookup(self, prompt, ns: int = 0) -> dict:
        """Map a prompt (the admission's *effective* prompt — for a retry
        replay that is prompt + carried tokens, whose pages the failed
        attempt may have registered before dying, making the replay
        nearly free) to its longest cached prefix, clamped to the
        engine's sharing granularity.  The share base is

          * a multiple of ``prefill_chunk`` — the sharer's own chunk
            schedule (and therefore its arithmetic) is then identical to
            the non-sharing engine's, so outputs are bit-identical, and
            the clamp below keeps every shared position out of reach of
            the shifted final chunk;
          * at most ``max_seq - prefill_chunk`` — a shifted final chunk
            can then never rewrite a shared position (and positions a
            donor's own shifted chunk rewrote are never granted);
          * at most ``plen - 1`` — the last prompt token always runs
            through prefill (its logits produce the first sampled token).

        Returns the full pages to alias plus, when the base lands
        mid-page, the donor page to copy-on-write split."""
        chain, boundary, blcp = self._prefix.lookup(prompt, ns)
        ps, c = self.page_size, self.prefill_chunk
        base = min(len(chain) * ps + blcp, len(prompt) - 1,
                   self.max_seq - c)
        base -= base % c
        n_full, cow = divmod(base, ps)
        cow_src = None
        if cow:
            cow_src = (chain[n_full].page if n_full < len(chain)
                       else boundary.page)
        return {"base": base, "pages": [n.page for n in chain[:n_full]],
                "cow_src": cow_src}

    def _held_for_pending_prefix(self, req: Request, pending: dict,
                                 have: int, ns: int = 0) -> bool:
        """Prefix-aware admission holdback: when the queue head would share
        more full pages with a PENDING admission's prompt than the index
        can grant right now (``have``, the head's current lookup base),
        wait for that donor to finish (it registers its pages on
        completion) instead of prefilling the common prefix twice.  Donors
        always finish in finitely many waves, so the head is never held
        forever.  Only same-shard donors (``ns``) count: a page another
        data shard is about to register could never be granted here."""
        if self._prefix is None or not pending:
            return False
        prompt = self._eff_prompt(req)
        ps, c = self.page_size, self.prefill_chunk
        for admit in pending.values():
            if self._slot_shard(admit["slot"]) != ns:
                continue
            donor = admit["prompt"]
            lcp = 0
            for a, b in zip(donor, prompt):
                if int(a) != int(b):
                    break
                lcp += 1
            # the donor will index floor(donor_plen / ps) full pages; apply
            # the same clamps _prefix_lookup would
            pot = min((lcp // ps) * ps, (len(donor) // ps) * ps,
                      len(prompt) - 1, self.max_seq - c)
            pot -= pot % c
            if pot >= ps and pot > have:
                return True
        return False

    def _grant_prefix(self, cache, i: int, grant: dict):
        """Alias the granted prefix pages into slot i's block table (one
        pool reference per aliased page) and, when the base lands mid-page,
        allocate + device-copy the boundary page (CoW split) so the slot's
        writable frontier is exclusively owned.  Aliased pages are
        referenced BEFORE any allocation so capacity-pressure eviction can
        never reclaim them in between."""
        st = self.stats
        for j, p in enumerate(grant["pages"]):
            self._pool.incref(p)
            self._page_slot_refs[p] = self._page_slot_refs.get(p, 0) + 1
            self._slot_pages[i].append(p)
            self._bt[i, j] = p
        self._slot_shared_n[i] = len(grant["pages"])
        if grant["cow_src"] is not None:
            # pin the donor page across the allocation AND the copy:
            # _alloc_pages may force-evict LRU leaves, and an index-only
            # cow_src could otherwise be freed and handed straight back
            # as dst (or freed before the device copy reads it)
            self._pool.incref(grant["cow_src"])
            try:
                (dst,) = self._alloc_pages(1)
                self._own_page(i, dst, len(grant["pages"]))
                cache = self._cow_copy_page(
                    cache, jnp.asarray(grant["cow_src"], jnp.int32),
                    jnp.asarray(dst, jnp.int32))
            finally:
                self._pool.decref(grant["cow_src"])
            st["kv_cow_splits"] += 1
        self._push_bt_row(i)
        st["prefix_hits"] += 1
        st["prefill_tokens_skipped"] += grant["base"]
        st["kv_pages_shared"] += len(grant["pages"])
        st["kv_pages_shared_peak"] = max(st["kv_pages_shared_peak"],
                                         self._pool.shared_pages)
        return cache

    def _register_prefix(self, i: int, prompt, plen: int) -> None:
        """Index the admitting slot's fully written prompt pages so later
        admissions can alias them.  Only pages entirely covered by the
        prompt (the admission's effective prompt — for a replay, prompt +
        carried tokens, all fully written by its waves) are indexed —
        partial tails are stale, and the exclusion is what keeps decode
        appends and parked writes out of every indexed page.  New nodes
        take one pool reference each: the cached prefix outlives the
        slot."""
        m = plen // self.page_size
        if not m:
            return
        new = self._prefix.insert(prompt, self._slot_pages[i][:m],
                                  ns=self._slot_shard(i))
        for node in new:
            self._pool.incref(node.page)
        # remember what this slot contributed so a later fault in the SAME
        # occupancy can withdraw exactly these registrations and no others
        self._slot_reg_nodes[i] = new
        if new and self.prefix_cache_pages is not None:
            self._enforce_prefix_cap()

    def _evict_one_prefix(self) -> bool:
        page = self._prefix.evict_coldest(
            lambda p: self._pool.refcount(p) == 1, force=True)
        if page is None:
            return False
        self._pool.decref(page)  # frees it iff the index was the last reader
        self.stats["prefix_evictions"] += 1
        return True

    def _enforce_prefix_cap(self) -> None:
        """Best-effort bound on pages the index keeps alive beyond live
        slots (the ``prefix_cache_pages`` knob); pages still pinned by
        slots can block a full sweep, so the loop stops when eviction
        makes no progress."""
        while self._index_only_pages() > self.prefix_cache_pages:
            if not self._evict_one_prefix():
                break

    def _index_only_pages(self) -> int:
        n = 0
        stack = [self._prefix.root]
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            if node.page is not None and self._pool.refcount(node.page) == 1:
                n += 1
        return n

    def _bt_device(self):
        """Device block table at its full static width (pages_per_slot),
        uploaded in full exactly once per run (lazily, at the first
        dispatch); every later change — page grant, growth, retirement —
        is a row-granular device-side update via ``_set_bt_row``, so
        steady-state decode re-uses the resident array with no transfer
        and no full re-upload ever happens again.

        The width is deliberately NOT sliced to the live high-water page
        count: every distinct width would recompile the fused decode block
        and the prefill wave (measured: compile time dwarfs the gather
        savings).  Dead columns are null-page entries, for which the Pallas
        kernels issue no compute; only the XLA gather fallback pays for
        them."""
        if not self.paged:
            return self._no_bt
        if self._bt_dev is None:
            if self._shardings is not None:
                self._bt_dev = jax.device_put(self._bt,
                                              self._shardings["bt"])
            else:
                self._bt_dev = jnp.asarray(self._bt)
        return self._bt_dev

    # -- admission (chunked, in-place, batched across slots) ---------------

    def _start_admission(self, slot_idx: int, req: Request,
                         base: int = 0) -> dict:
        prompt = self._eff_prompt(req)  # prompt + carried for a replay
        carried = self._carried(req)
        plen = len(prompt)  # <= max_seq, validated up front in run()
        req.attempts += 1
        if self._chunked:
            # chunked prefill covers [base, plen): the shared prefix
            # [0, base) is already in granted pages and is skipped
            n_chunks = -(-(plen - base) // self.prefill_chunk)
        else:
            n_chunks = 1
        return {"slot": slot_idx, "req": req, "prompt": prompt,
                "carried": carried, "plen": plen, "next": 0,
                "n_chunks": n_chunks, "base": base}

    def _first_token(self, logits, req: Request, emit_idx: int = 0) -> int:
        return int(np.asarray(self._sample_tokens(
            logits, jnp.asarray([req.seed], jnp.int32),
            jnp.asarray([emit_idx], jnp.int32),
            jnp.asarray([req.temperature], jnp.float32)))[0])

    def _finish_admission(self, slots, admit, tok: int):
        req, i = admit["req"], admit["slot"]
        if req.ttft_s is None:  # a retry keeps its first attempt's TTFT
            # measured from the request's ARRIVAL (submit time), not from
            # run()/window start — the number a continuously arriving
            # client actually observes
            req.ttft_s = time.perf_counter() - getattr(
                req, "_arrival_t", self._window_t0)
        s = slots[i]
        s.request = req
        # a replay's lane resumes mid-output: the carried tokens are
        # already committed, the wave's sampled token is the next one
        s.tokens = list(admit["carried"]) + [tok]
        s.cache_len = admit["plen"]
        s.last_token = tok
        if self.on_token is not None:
            # stream only the NEW token: a replay's carried tokens were
            # already delivered by the attempt that emitted them
            self.on_token(req, int(tok))
        self.stats["admissions"] += 1
        if self._prefix is not None:
            # the prompt's full pages are now all written: make them
            # reusable (before any potential immediate retirement, so a
            # prefill-only request still seeds the cache)
            self._register_prefix(i, admit["prompt"], admit["plen"])
        # request finished at prefill (budget or cache exhausted)
        if len(s.tokens) >= req.max_new_tokens or s.cache_len >= self.max_seq:
            self._free_slot(slots, i)

    def _prefill_wave(self, cache, pending, slots):
        """Dispatch one admission wave: advance EVERY pending admission by
        one chunk in a single batched jit call (rows of lanes that are
        decoding or idle are masked).  In-flight lanes therefore stall for
        at most this one dispatch between decode blocks, no matter how many
        prompts are being admitted or how long they are."""
        self.stats["prefill_chunks"] += 1
        self._sched_epoch += 1  # a wave mutates device-visible inputs
        self._stage("wave")
        if not self._chunked:  # recurrent: whole prompt, donor + adopt,
            i = next(iter(pending))  # one admission per wave
            admit = pending.pop(i)
            req, plen = admit["req"], admit["plen"]
            toks = np.asarray(admit["prompt"], np.int32)[None]
            one_cache = transformer.init_cache(self.cfg, 1, plen,
                                               self.cache_dtype)
            self._tag("requests", (req,))
            logits, one_cache = self._prefill_full(
                self.params, jnp.asarray(toks), one_cache,
                jnp.asarray([plen], jnp.int32))
            self._stage("first_sync")
            self._tag("requests", (req,))
            tok = self._first_token(logits, req, len(admit["carried"]))
            cache = self._adopt(cache, one_cache, jnp.asarray(i, jnp.int32))
            if self._dev_active:
                self._merge_admissions(
                    [(i, admit)],
                    jnp.zeros((self.slots,), jnp.int32).at[i].set(tok),
                    np.asarray([req.seed if i == j else 0
                                for j in range(self.slots)], np.int32),
                    np.asarray([req.temperature if i == j else 0.0
                                for j in range(self.slots)], np.float32))
            self._finish_admission(slots, admit, tok)
            self._stage()
            return cache
        n, c = self.slots, self.prefill_chunk
        toks = np.zeros((n, c), np.int32)
        offs = np.zeros((n,), np.int32)
        mask = np.zeros((n,), bool)
        last = np.zeros((n,), np.int32)
        seeds = np.zeros((n,), np.int32)
        temps = np.zeros((n,), np.float32)
        emit0 = np.zeros((n,), np.int32)
        completing = []
        for i in list(pending):
            admit = pending[i]
            req, plen = admit["req"], admit["plen"]
            # shifted final chunk: never write past the cache row end.  A
            # shared-prefix admission starts at its base; the shift can
            # never cross below it (base <= max_seq - c by the lookup
            # clamp), so shared pages are never rewritten.
            lo = min(admit["base"] + admit["next"] * c, self.max_seq - c)
            if self.paged:
                # cover the chunk's live span [0, min(lo + C, plen));
                # shifted-chunk slack writes past the prompt land either in
                # the owned final page's masked tail (positions >= the live
                # length) or, past the allocation, in the null page.
                # Growth runs BEFORE the row is marked in the wave, so an
                # allocation fault aborts only this admission and its row
                # stays masked out of the dispatch.
                try:
                    self._grow_pages(i, min(lo + c, plen))
                except InjectedFault as e:
                    self._abort_admission(
                        pending, i, RequestStatus.FAILED,
                        f"KV page allocation failed during admission: {e}")
                    continue
            seg = admit["prompt"][lo:lo + c]
            toks[i, :len(seg)] = seg
            offs[i] = lo
            mask[i] = True
            last[i] = max(0, min(plen - 1 - lo, c - 1))
            seeds[i] = req.seed
            temps[i] = req.temperature
            emit0[i] = len(admit["carried"])  # replay: resume the emit index
            admit["next"] += 1
            if admit["next"] >= admit["n_chunks"]:
                completing.append(i)
        if not mask.any():
            self._stage()
            return cache  # every admission aborted this wave
        # the rows still pending are the ones this wave dispatches
        self._tag("requests", (a["req"] for a in pending.values()))
        first, cache = self._prefill_chunks(
            self.params, jnp.asarray(toks), cache, self._bt_device(),
            jnp.asarray(offs), jnp.asarray(mask), jnp.asarray(last),
            jnp.asarray(seeds), jnp.asarray(temps), jnp.asarray(emit0))
        self._stage()  # the wave ends when its dispatch returns
        if completing:
            self._stage("first_sync")
            self._tag("requests", (pending[i]["req"] for i in completing))
            if self._dev_active:
                # activate the lanes on device BEFORE the host sync: the
                # wave's on-device first tokens flow straight into the
                # resident scheduler state, so the readback below is pure
                # bookkeeping (ttft, output buffers, prefix registration)
                self._merge_admissions(
                    [(i, pending[i]) for i in completing], first,
                    seeds, temps)
            ft = np.asarray(first)  # sync only when an admission completes
            for i in completing:
                self._finish_admission(slots, pending.pop(i), int(ft[i]))
            self._stage()
        return cache

    def _merge_admissions(self, admits, first, seeds, temps) -> None:
        """Fold completed admissions into the device scheduler state.
        ``first`` stays a device array (the sampled first tokens never
        bounce through the host on their way into decode).  Lanes whose
        request already finished at prefill (max_new == 1 or a full row)
        are merged inactive — the scan tick emits before checking done, so
        activating them would emit one spurious token."""
        n = self.slots
        upd = np.zeros((n,), bool)
        activate = np.zeros((n,), bool)
        clens = np.zeros((n,), np.int32)
        emit0 = np.zeros((n,), np.int32)
        mnew = np.zeros((n,), np.int32)
        for i, admit in admits:
            req, plen = admit["req"], admit["plen"]
            k = len(admit["carried"])  # replay resumes mid-output
            upd[i] = True
            clens[i] = plen
            emit0[i] = k + 1
            mnew[i] = req.max_new_tokens
            activate[i] = not (req.max_new_tokens <= k + 1
                               or plen >= self.max_seq)
        self._state = self._admit_lanes(
            self._state, first, jnp.asarray(upd), jnp.asarray(activate),
            jnp.asarray(clens), jnp.asarray(emit0), jnp.asarray(mnew),
            jnp.asarray(temps), jnp.asarray(seeds))

    # -- decode (fused multi-tick block) -----------------------------------

    def _note_dispatch(self) -> None:
        """Classify this decode dispatch for the sync counters: an interval
        with no admission/retire/prefill since the previous dispatch is a
        steady-state block, and it is charged with whatever dispatch-gating
        host syncs happened in that interval (host-driven: exactly the
        previous block's readback; device-resident: none by construction —
        a drain that retires a lane bumps the epoch, making the enclosing
        interval non-steady)."""
        st = self.stats
        steady = (self._last_dispatch_epoch is not None
                  and self._sched_epoch == self._last_dispatch_epoch)
        if steady:
            st["steady_state_blocks"] += 1
            self._steady_syncs += self._syncs_since_dispatch
        self._syncs_since_dispatch = 0
        self._last_dispatch_epoch = self._sched_epoch

    def _nan_mask_for_block(self):
        """Fault-injection seam: the NaN lane mask for the block about to
        dispatch (keyed on the engine's decode-block ordinal).  Returns the
        cached all-False mask when nothing is scheduled — zero allocation,
        and the in-block select is an exact identity."""
        fi = self.fault_injector
        if fi is not None:
            m = fi.nan_mask(self.stats["decode_blocks"] - 1, self.slots)
            if m is not None:
                return jnp.asarray(m)
        return self._no_nan

    def _run_decode_block(self, cache, slots):
        st = self.stats
        self._stage("block")  # ends when the block's dispatch returns
        if self.paged:
            if not self._dev_active:
                # host-driven: grow each live lane's page list to cover
                # every append this block can make — bounded by the lane's
                # remaining budget, so it never exceeds the admission
                # reservation.  (Device-resident lanes pre-granted their
                # whole reservation at admission; nothing to do.)  A
                # growth fault retires only the lane that hit it.
                for i, s in enumerate(slots):
                    if s.active:
                        remaining = s.request.max_new_tokens - len(s.tokens)
                        upto = min(s.cache_len
                                   + min(self.decode_block, remaining),
                                   self.max_seq)
                        try:
                            self._grow_pages(i, upto)
                        except InjectedFault as e:
                            self._fault_retire(
                                slots, i, RequestStatus.FAILED,
                                f"KV page growth failed mid-decode: {e}")
            live = sum(s.cache_len for s in slots if s.active)
            st["kv_live_tokens_peak"] = max(st["kv_live_tokens_peak"], live)
        if not any(s.active for s in slots):
            self._stage()
            return cache  # growth faults may have emptied the batch
        self._note_dispatch()
        st["decode_blocks"] += 1
        st["decode_steps"] += self.decode_block
        if self._degraded:
            st["degraded_blocks"] += 1
        nan_mask = self._nan_mask_for_block()
        wd = (Watchdog(self.block_deadline_s)
              if self.block_deadline_s is not None else None)
        try:
            if wd is None:
                cache = self._dispatch_block(cache, slots, nan_mask)
            else:
                # serving watchdog, non-process-killing: bound ONE fused
                # block dispatch + its gating readback; a trip is recorded
                # and (device mode) degrades rather than aborting
                with wd:
                    cache = self._dispatch_block(cache, slots, nan_mask)
                if wd.fired:
                    st["watchdog_trips"] += 1
                    if self._dev_active:
                        self._degrade(
                            slots, "watchdog: fused-block dispatch "
                            f"exceeded block_deadline_s="
                            f"{self.block_deadline_s}")
        except InjectedFault as e:
            # a dispatch that still fails after the retry budget: the
            # device scheduler is wedged.  Device mode reconciles and
            # falls back to the host-driven path; the host path (already
            # the lowest service level) fails the live batch and keeps
            # serving the queue.
            if self._dev_active:
                self._degrade(slots, f"dispatch fault: {e}")
            else:
                for i, s in enumerate(slots):
                    if s.active:
                        self._fault_retire(
                            slots, i, RequestStatus.FAILED,
                            f"decode dispatch failed on host path: {e}")
        return cache

    def _dispatch_block(self, cache, slots, nan_mask):
        """Issue one fused decode block (device-resident or host-driven),
        with the injector's dispatch seam and ``with_retries`` wrapping
        the host-side call.  Retries are legal because the seam fires
        BEFORE the jit call — no donated buffer has been consumed when a
        retryable fault raises."""
        fi = self.fault_injector
        if self._dev_active:
            def dispatch():
                if fi is not None:
                    fi.on_dispatch(device=True)
                # dispatch from the device-resident carry: no host array
                # is built and nothing from the previous block is awaited
                # — block N+1 enters the stream while block N may still
                # be running
                return self._decode_block_dev(
                    self.params, self._state, cache, self._bt_device(),
                    nan_mask)
            self._state, blk, mask, bad, cache = with_retries(
                dispatch, max_retries=self.dispatch_retries,
                retry_on=(InjectedFault,),
                backoff_s=self.dispatch_backoff_s, seed=self.seed)()
            self._stage()
            self._inflight.append((blk, mask, bad))
            # fetch one block behind: drain block N while block N+1 runs
            self._drain_blocks(slots, depth=1)
            return cache
        reqs = [s.request for s in slots]

        def dispatch():
            if fi is not None:
                fi.on_dispatch(device=False)
            return self._decode_block(
                self.params,
                jnp.asarray([s.last_token for s in slots], jnp.int32),
                cache,
                self._bt_device(),
                jnp.asarray([s.cache_len for s in slots], jnp.int32),
                jnp.asarray([len(s.tokens) for s in slots], jnp.int32),
                jnp.asarray([r.max_new_tokens if r else 0 for r in reqs],
                            jnp.int32),
                jnp.asarray([s.active for s in slots], jnp.bool_),
                jnp.asarray([r.temperature if r else 0.0 for r in reqs],
                            jnp.float32),
                jnp.asarray([r.seed if r else 0 for r in reqs], jnp.int32),
                nan_mask)
        blk, mask, bad, cache = with_retries(
            dispatch, max_retries=self.dispatch_retries,
            retry_on=(InjectedFault,),
            backoff_s=self.dispatch_backoff_s, seed=self.seed)()
        self._stage("readback")
        self._tag("retired",
                  self._process_block(slots, blk, mask, bad, gating=True))
        self._stage()
        return cache

    def _degrade(self, slots, reason: str) -> None:
        """Graceful degradation: reconcile the (at most one block behind)
        host mirror by draining everything in flight, drop the resident
        device state — after a full drain the mirror is exact, because
        every device-side transition is a pure function of the drained
        readbacks — and finish the run on the host-driven reference path.
        Surviving requests complete with correct (token-identical greedy)
        outputs and status DEGRADED — unless ``repromote`` later promotes
        the run back to device-resident scheduling (see ``_try_promote``),
        after which completions are OK again."""
        self.stats["sched_fallbacks"] += 1
        self._drain_blocks(slots, depth=0)
        self._state = None
        self._degraded = True
        self._dev_active = False
        self._sched_epoch += 1  # the fallback is a scheduler event
        # trips the device breaker (threshold 1): re-promotion waits out
        # the probe cooldown, then goes through a half-open canary probe
        self._dev_breaker.record_failure()

    # -- mid-run re-promotion (degraded -> device-resident) ----------------

    def _canary_probe(self) -> bool:
        """Probe device health with a tiny dedicated dispatch through the
        same seams a real fused block runs behind (the injector's dispatch
        hook, the serving watchdog) — never the real block, whose donated
        state/cache a failing probe would destroy.  True = device answered
        within deadline."""
        st = self.stats
        st["canary_probes"] += 1
        fi = self.fault_injector

        def probe():
            if fi is not None:
                fi.on_dispatch(device=True)
            return self._canary_jit(self._canary_arg)

        wd = (Watchdog(self.block_deadline_s)
              if self.block_deadline_s is not None else None)
        try:
            if wd is None:
                jax.block_until_ready(probe())
            else:
                with wd:
                    jax.block_until_ready(probe())
                if wd.fired:
                    st["watchdog_trips"] += 1
                    return False
        except InjectedFault:
            return False
        return True

    def _try_promote(self, slots) -> None:
        """Half-open trial of the device breaker: once the cooldown after
        a degrade has passed, send one canary; on success promote the run
        back to device-resident scheduling, on failure re-open the breaker
        with a doubled cooldown (bounded probing under a persistent
        fault)."""
        br = self._dev_breaker
        if not br.allow():
            return
        if self._canary_probe():
            br.record_success()
            self._promote(slots)
        else:
            br.record_failure()

    def _promote(self, slots) -> None:
        """Mid-run re-promotion: rebuild the resident scheduler pytree from
        the host mirror (exact — the host path is authoritative while
        degraded), re-upload the block table, and hand scheduling back to
        the device.  Post-promotion completions are stamped OK again, and
        the steady-state sync gauge restarts from zero so it measures the
        CURRENT scheduling regime (0.0 once the device is back in charge),
        not the host-driven interlude."""
        st = self.stats
        if self.paged:
            # device-resident decode never allocates: top up every live
            # lane to its full worst-case coverage before handing it back
            # (its admission reservation still covers this; a no-op for
            # lanes the host path already grew fully)
            for i, s in enumerate(slots):
                if not s.active:
                    continue
                upto = min(s.cache_len + (s.request.max_new_tokens
                                          - len(s.tokens)), self.max_seq)
                try:
                    self._grow_pages(i, upto)
                except InjectedFault as e:
                    self._fault_retire(
                        slots, i, RequestStatus.FAILED,
                        f"KV page allocation failed at re-promotion: {e}")
        reqs = [s.request for s in slots]
        self._state = {
            "last_token": jnp.asarray([s.last_token for s in slots],
                                      jnp.int32),
            "cache_len": jnp.asarray([s.cache_len for s in slots],
                                     jnp.int32),
            "emitted": jnp.asarray([len(s.tokens) for s in slots],
                                   jnp.int32),
            "active": jnp.asarray([s.active for s in slots], jnp.bool_),
            "max_new": jnp.asarray([r.max_new_tokens if r else 0
                                    for r in reqs], jnp.int32),
            "temps": jnp.asarray([r.temperature if r else 0.0
                                  for r in reqs], jnp.float32),
            "seeds": jnp.asarray([r.seed if r else 0 for r in reqs],
                                 jnp.int32),
        }
        if self._shardings is not None:
            self._state = jax.device_put(self._state,
                                         self._shardings["state"])
        if self.paged:
            self._bt_dev = None  # full re-upload from the host mirror at
            #                      the next dispatch (lazy, like run start)
        self._dev_active = True
        self._degraded = False
        self._sched_epoch += 1  # promotion is a scheduler event
        st["repromotions"] += 1
        st["steady_state_blocks"] = 0
        self._steady_syncs = 0
        self._last_dispatch_epoch = None
        if self.audit_on_retire:
            self.audit()

    def _drain_blocks(self, slots, depth: int = 0) -> None:
        """Read back queued decode blocks down to ``depth`` still in
        flight (depth=1 is the steady-state one-block-behind pipeline;
        depth=0 the final drain)."""
        if len(self._inflight) <= depth:
            return
        self._stage("readback")
        retired = []
        while len(self._inflight) > depth:
            blk, mask, bad = self._inflight.popleft()
            retired += self._process_block(slots, blk, mask, bad,
                                           gating=False)
        self._tag("retired", retired)
        self._stage()

    def _process_block(self, slots, blk, mask, bad, *,
                       gating: bool) -> List[Request]:
        """Fold one decode block's readback into the host mirror: run the
        output-integrity guards, extend outputs, advance lengths, retire
        finished lanes.  ``gating`` marks a readback the next dispatch
        waits on (every block in host-driven mode); in device-resident
        mode a readback only becomes a gating sync when it triggers
        retirement — that is the moment host state re-enters the device
        scheduler (row clear, freed reservation).

        Integrity guards, per lane: ``bad[i]`` (device-side non-finite
        logits latch, read back with the tokens — no extra sync) and a
        host-side token-range check (catches readback/interconnect
        corruption the device could not see).  A flagged lane retires
        FAILED with the tokens it had before this block; its prefix
        registrations are withdrawn; every other lane is untouched.
        Returns the requests the fold retired."""
        blk = np.asarray(blk)
        mask = np.asarray(mask)
        bad = np.asarray(bad)
        fi = self.fault_injector
        if fi is not None:
            blk = fi.on_readback(blk, mask,
                                 bad_token=self.cfg.vocab_size + 7)
        st = self.stats
        st["decode_tokens"] += int(mask.sum())
        retired = []
        live_after = 0  # post-append live tokens, counted before any free
        for i, s in enumerate(slots):
            if not s.active:
                continue
            if bad[i]:
                st["integrity_faults"] += 1
                retired.append(s.request)
                self._fault_retire(
                    slots, i, RequestStatus.FAILED,
                    "non-finite logits in fused block (lane isolated; "
                    "the block's tokens for this lane are discarded)",
                    rollback_prefix=True)
                continue
            new_arr = blk[i][mask[i]]
            if new_arr.size and (int(new_arr.min()) < 0 or
                                 int(new_arr.max()) >= self.cfg.vocab_size):
                st["integrity_faults"] += 1
                retired.append(s.request)
                self._fault_retire(
                    slots, i, RequestStatus.FAILED,
                    "emitted token id out of range (corrupt readback; "
                    "lane isolated)", rollback_prefix=True)
                continue
            new = new_arr.tolist()
            s.tokens.extend(int(t) for t in new)
            s.cache_len += len(new)
            live_after += s.cache_len
            if new:
                s.last_token = int(new[-1])
                if self.on_token is not None:
                    # stream in emit order AFTER the integrity guards: a
                    # poisoned block's tokens are discarded above, so a
                    # streamed token is never withdrawn
                    for t in new:
                        self.on_token(s.request, int(t))
            if (len(s.tokens) >= s.request.max_new_tokens
                    or s.cache_len >= self.max_seq):
                retired.append(s.request)
                self._free_slot(slots, i)
        if self.paged:
            # the gauge at block entry misses the block's own appends; this
            # post-append sample makes the live-token peak exact
            st["kv_live_tokens_peak"] = max(st["kv_live_tokens_peak"],
                                            live_after)
        if gating or retired:
            st["host_block_syncs"] += 1
            self._syncs_since_dispatch += 1
        # the parked-write contract: the in-block park of a lane that filled
        # its row (contiguous: clamped to max_seq - 1, clobbering its own
        # last KV entry) is only safe because such a lane is retired HERE,
        # before the host could attend that row again with a NEW request.
        # A still-active lane at cache_len >= max_seq would read its own
        # clobbered tail — fail fast (a RuntimeError, not an assert: this
        # must survive -O)
        if any(s.cache_len >= self.max_seq for s in slots if s.active):
            raise RuntimeError(
                "active lane at cache_len >= max_seq: parked decode writes "
                "could clobber a live token")
        return retired

    # -- main loop ---------------------------------------------------------

    def audit(self) -> dict:
        """Verify the page-pool / prefix-trie / block-table invariants and
        return a summary gauge dict; raise :class:`AuditError` on the first
        violation.  This is the refcount oracle from the property tests
        promoted into the engine: every page is either free or referenced
        (no leaks), never both (no double-free), the null page never enters
        the allocator or a slot (never shared), each slot's host block
        table mirrors its page list exactly, and the pool's refcounts equal
        the sum of slot references + prefix-index references recomputed
        from scratch.  Callable between requests or right after a
        fault-path retirement (``audit_on_retire=True`` does so
        automatically); it reads only host state — no device sync."""
        if not self.paged or not hasattr(self, "_pool"):
            return {"ok": True, "paged": False}
        pool = self._pool

        def fail(msg):
            raise AuditError(f"serving audit failed: {msg}")

        free, live = pool._free, pool._refs
        if len(set(free)) != len(free):
            fail("duplicate entries in the free list (double free)")
        if 0 in live or 0 in free:
            fail("null page entered the allocator")
        if set(free) & set(live):
            fail("page both free and referenced")
        if set(free) | set(live) != set(range(1, pool.num_pages)):
            fail("pages leaked: neither free nor referenced")
        if any(c < 1 for c in live.values()):
            fail("nonpositive refcount on a live page")
        # oracle recount: expected refcount = per-slot block-table
        # references + prefix-index references, rebuilt from scratch
        expected: dict = {}
        for i, pages in enumerate(self._slot_pages):
            row = self._bt[i]
            for j, p in enumerate(pages):
                if p == 0:
                    fail(f"slot {i} owns the null page")
                if int(row[j]) != p:
                    fail(f"block-table row {i} diverged from the slot's "
                         f"page list at column {j}")
                expected[p] = expected.get(p, 0) + 1
            if any(int(x) != 0 for x in row[len(pages):]):
                fail(f"block-table row {i} has live entries past the "
                     "slot's page list")
        if expected != self._page_slot_refs:
            fail("slot page-reference map diverged from the block tables")
        n_index = 0
        if self._prefix is not None:
            stack = [self._prefix.root]
            while stack:
                node = stack.pop()
                stack.extend(node.children.values())
                if node.page is not None:
                    n_index += 1
                    if node.page == 0:
                        fail("null page registered in the prefix index")
                    expected[node.page] = expected.get(node.page, 0) + 1
            if n_index != self._prefix.n_pages:
                fail("prefix-index page count diverged from its nodes")
        if expected != live:
            fail("pool refcounts diverged from the block-table + "
                 "prefix-index oracle")
        if sum(self._slot_reserved) != self._reserved_total:
            fail("reservation sum diverged from per-slot reservations")
        if not set(self._backed) <= set(live):
            fail("reservation-backed page is not referenced")
        return {"ok": True, "paged": True,
                "used_pages": pool.used_pages,
                "free_pages": pool.free_pages,
                "shared_pages": pool.shared_pages,
                "index_pages": n_index}

    # -- resident lifecycle ------------------------------------------------

    def _reset_engine_state(self) -> None:
        """(Re)build the ENGINE-LIFETIME serving state: decode lanes, the
        request pools (queue / pending admission / retry-wait), the KV
        page pool + block tables + prefix index, the device scheduler
        pytree, both circuit breakers, and the arrival counter.  Called
        once from ``__init__``; calling it again abandons every in-flight
        request and drops all cached prefixes — it is the hard-reset
        escape hatch, NOT part of the normal submit/step/drain lifecycle
        (``run()`` does not call it: pools, breakers and the prefix cache
        deliberately persist across windows on a shared engine)."""
        # sync-counter scaffolding: the scheduler epoch advances on every
        # host event that feeds the device scheduler (admission wave,
        # retirement); a decode block dispatched with the epoch unchanged
        # since the previous dispatch ran in steady state
        self._sched_epoch = 0
        self._inflight: deque = deque()  # dispatched, not yet read back
        # robustness scaffolding: _dev_active is the LIVE scheduler mode
        # (flips False when the engine degrades; self.device_sched is the
        # configured mode and never changes); _degraded stamps every later
        # OK completion DEGRADED
        self._dev_active = bool(self.device_sched)
        self._degraded = False
        self._state = None
        # recovery scaffolding: the retry-wait pool plus the two circuit
        # breakers.  The device breaker trips on the FIRST degrade
        # (threshold 1 — degrading is already the containment action) and
        # its cooldown paces canary probes; the retry breaker trips when
        # retryable failures cluster, converting retry storms into
        # fail-fast terminal statuses.  Ticks advance once per scheduler
        # beat (step() with work), not wall time, so recovery pacing is
        # deterministic under test.  Both breakers live for the ENGINE
        # lifetime: a persistent fault's accumulated (doubled) probe
        # cooldown is real evidence about the device and survives window
        # boundaries instead of being forgotten at every run().
        self._retryq: List[dict] = []
        self._dev_breaker = CircuitBreaker(
            threshold=1, window=1, cooldown=self.probe_cooldown_blocks)
        self._retry_breaker = CircuitBreaker(
            threshold=self.retry_breaker_threshold,
            window=self.retry_breaker_window,
            cooldown=self.retry_breaker_cooldown)
        if self.device_sched:
            self._state = self._zero_sched_state()
        if self.paged:
            self._pool = _PagePool(self.kv_pages)
            self._prefix = (_PrefixIndex(self.page_size)
                            if self.enable_prefix_sharing else None)
            self._bt = np.zeros((self.slots, self.pages_per_slot), np.int32)
            self._bt_dev = None  # cached device copy of self._bt
            self._slot_pages: List[List[int]] = [[] for _ in
                                                 range(self.slots)]
            self._slot_shared_n = [0] * self.slots
            self._page_slot_refs: dict = {}  # page -> live slot references
            self._backed: set = set()  # pages inside an active reservation
            self._slot_reserved = [0] * self.slots
            self._reserved_total = 0
        self._slot_reg_nodes: List[list] = [[] for _ in range(self.slots)]
        self._lanes = [_Slot() for _ in range(self.slots)]
        self._queue: deque = deque()  # submitted, waiting for a slot
        self._pending: dict = {}      # slot index -> in-progress admission
        self._cache = None            # KV cache; built at the first beat
        self._arrivals = 0            # engine-lifetime monotonic counter:
        #                               default seeds and batch/incremental
        #                               token identity key on it
        self._chunks_since_block = 0
        self._deferred_head = None  # queue head already counted as deferred
        self._held_head = None      # queue head already counted as held

    def _zero_sched_state(self) -> dict:
        z = lambda dt: jnp.zeros((self.slots,), dt)
        state = {"last_token": z(jnp.int32), "cache_len": z(jnp.int32),
                 "emitted": z(jnp.int32), "active": z(jnp.bool_),
                 "max_new": z(jnp.int32), "temps": z(jnp.float32),
                 "seeds": z(jnp.int32)}
        if self._shardings is not None:
            state = jax.device_put(state, self._shardings["state"])
        return state

    def reset_stats(self) -> None:
        """Open a fresh stats WINDOW: rebuild ``self.stats`` (every gauge
        key present, every mode) and restart the window clock.  The
        engine-lifetime counters in ``self.lifetime`` — and all serving
        state: pools, prefix cache, breakers, in-flight work — are
        untouched.  ``run()`` calls this at entry (each batch is its own
        window); continuous callers may call it after a ``drain()`` to
        delimit reporting windows.  Requests submitted before the reset
        but not yet terminal leave the window's books — reset between
        drains, not mid-flight."""
        self.stats = {"admissions": 0, "mid_flight_admissions": 0,
                      "prefill_chunks": 0, "decode_steps": 0,
                      "decode_blocks": 0, "decode_tokens": 0,
                      "max_chunks_between_decode_blocks": 0,
                      "host_block_syncs": 0, "steady_state_blocks": 0,
                      # beat accounting (the busy-spin regression guard: a
                      # pure retry-backoff window costs ONE sleep, not a
                      # capped-sleep poll loop)
                      "scheduler_beats": 0, "idle_sleeps": 0,
                      "idle_wait_s": 0.0,
                      # host seconds per beat stage, and the longest beat
                      # with the stage that held most of it: they name a
                      # host stall in a run that takes no trace
                      "host_s": dict.fromkeys(STAGES, 0.0),
                      "beat_max_s": 0.0, "beat_max_stage": None,
                      # robustness gauges — always present, every mode
                      "requests_completed": 0, "requests_rejected": 0,
                      "requests_failed": 0, "requests_timed_out": 0,
                      "requests_cancelled": 0, "requests_degraded": 0,
                      "degraded_blocks": 0, "faults_injected": 0,
                      "watchdog_trips": 0, "sched_fallbacks": 0,
                      "integrity_faults": 0,
                      # recovery gauges — always present, every mode (the
                      # breaker states report the persistent breakers)
                      "requests_retried": 0, "retries_total": 0,
                      "retry_backoff_s": 0.0, "retries_denied_breaker": 0,
                      "repromotions": 0, "canary_probes": 0,
                      "breaker_state": self._dev_breaker.state,
                      "retry_breaker_state": self._retry_breaker.state}
        if self.paged:
            self.stats.update({"kv_pages_peak": 0, "kv_live_tokens_peak": 0,
                               "kv_reserved_pages_peak": 0,
                               "admissions_deferred_pages": 0,
                               # prefix-sharing gauges (always present in
                               # paged mode; zero when sharing is off)
                               "prefix_hits": 0,
                               "prefill_tokens_skipped": 0,
                               "kv_pages_shared": 0,
                               "kv_pages_shared_peak": 0,
                               "kv_cow_splits": 0,
                               "prefix_evictions": 0,
                               "admissions_held_for_prefix": 0})
        # steady-state classification restarts per window: the first block
        # of a window is never charged as steady
        self._last_dispatch_epoch = None
        self._syncs_since_dispatch = 0
        self._steady_syncs = 0
        self._window_requests: List[Request] = []
        self._window_t0 = time.perf_counter()
        self._window_contrib: Optional[dict] = None
        fi = self.fault_injector
        self._fi_events0 = len(fi.events) if fi is not None else 0

    def _ensure_cache(self) -> None:
        if self._cache is not None:
            return
        if self.paged:
            self._cache = transformer.init_paged_cache(
                self.cfg, self.kv_pages, self.page_size, self.cache_dtype,
                kv_quant=self.kv_quant)
        else:
            self._cache = transformer.init_cache(
                self.cfg, self.slots, self.max_seq, self.cache_dtype,
                kv_quant=self.kv_quant)
        if self._shardings is not None:
            # a fresh all-zero cache really is replicated, so the paged
            # pools start consistent; per-shard divergence only accrues
            # through the shard_map'd dispatches that follow
            self._cache = jax.device_put(self._cache,
                                         self._shardings["cache"])

    def _restore_device_residency(self) -> None:
        """Hand scheduling back to the device at a window boundary after a
        degraded window: with the engine fully drained (no live lane,
        nothing pending or in flight) a zeroed resident pytree is exact,
        so no canary is needed — the documented "the next run() starts
        device-resident regardless" contract.  The device breaker is NOT
        reset: a persistent fault's accumulated cooldown keeps pacing any
        mid-window re-promotion probes across windows."""
        if not self.device_sched or self._dev_active:
            return
        if (self._pending or self._inflight
                or any(s.active for s in self._lanes)):
            return  # mid-flight: only the canary/promote path may restore
        self._state = self._zero_sched_state()
        self._dev_active = True
        self._degraded = False
        self._sched_epoch += 1

    def submit(self, req: Request) -> Request:
        """Enqueue one request on the RESIDENT engine — at any time, from
        any point in the serving lifecycle (mid-decode, mid-degrade,
        mid-retry-backoff).  Admission-time policy that used to run at
        ``run()`` start runs HERE, per arrival:

          * validation (``_validate``) — an unservable request is stamped
            REJECTED immediately and never enters the queue;
          * default seed assignment — keyed on the engine-lifetime arrival
            counter, so the same request stream split across any number of
            ``submit()`` calls samples identically to one batch ``run()``;
          * clock stamping — the ``deadline_s`` budget and TTFT both
            measure from THIS moment (arrival), never from a window start.

        Returns the request (already terminal if it was rejected).
        ``submit()`` dispatches nothing — the caller advances the engine
        with ``step()``/``drain()``."""
        if self._closed:
            raise RuntimeError("submit() on a closed ServingEngine")
        now = time.perf_counter()
        # deterministic per-request default; normalize to int32 range
        req.seed = ((self.seed * 1000003 + self._arrivals)
                    if req.seed is None else int(req.seed)) % _SEED_MOD
        req._ordinal = self._arrivals  # names the request's host spans
        self._arrivals += 1
        self.lifetime["arrivals"] += 1
        req._arrival_t = now
        req._deadline_t0 = now
        self._window_requests.append(req)
        err = self._validate(req)
        if err is not None:
            self._reject(req, err)
            return req
        self._queue.append(req)
        return req

    @property
    def has_work(self) -> bool:
        """Whether any pool still owes progress: queued, pending
        admission, live lane (the host view may lag one readback behind),
        in-flight block, or retry-wait."""
        return bool(self._queue or self._pending or self._inflight
                    or self._retryq
                    or any(s.active for s in self._lanes))

    def step(self) -> StepOutcome:
        """Advance the resident scheduler by exactly ONE beat:

            police -> breaker ticks -> retry pump -> promote probe ->
            admission wave -> fused decode block -> one-block-behind drain

        (each stage runs only when it has work; an empty engine no-ops).
        One beat dispatches at most one admission wave and one decode
        block, so in-flight lanes never stall for more than one chunk +
        one block no matter the arrival pattern.  Drive the engine by
        looping ``step()`` — honoring ``StepOutcome.idle_until`` by
        sleeping instead of re-calling immediately — or use
        ``drain()``/``run()``, which do exactly that.

        Each stage runs as a host span ``engine.<stage>`` (``STAGES``);
        the beat's seconds go to ``stats["beat_max_s"]`` when it is the
        window's longest."""
        if not self.has_work:
            return StepOutcome(worked=False, remaining=0)
        host = self.stats["host_s"]
        before, t0 = dict(host), time.perf_counter()
        try:
            return self._run_beat()
        finally:
            self._stage()
            beat = time.perf_counter() - t0
            if beat > self.stats["beat_max_s"]:
                self.stats["beat_max_s"] = beat
                self.stats["beat_max_stage"] = max(
                    STAGES, key=lambda k: host[k] - before[k])

    def _stage(self, name: Optional[str] = None) -> None:
        """End the beat's open stage and, given ``name``, open the next:
        a host span ``engine.<name>`` in the profiler's trace, whose
        ``perf_counter`` seconds are added to ``stats["host_s"][name]``
        when it ends.  The stages of one beat follow each other and never
        nest."""
        now = time.perf_counter()
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
            self.stats["host_s"][self._span_stage] += now - self._span_t0
        if name is not None:
            self._span = jax.profiler.TraceAnnotation("engine." + name)
            self._span.__enter__()
            self._span_stage, self._span_t0 = name, now

    def _tag(self, key: str, requests) -> None:
        """Put the arrival ordinals of ``requests`` on the open stage's
        span as metadata ``key`` (space-separated), so the spans of one
        request share its ordinal.  Only while a trace is being taken."""
        if self._span is not None and self._span.is_enabled():
            ords = " ".join(str(r._ordinal) for r in requests)
            if ords:
                self._span.set_metadata(**{key: ords})

    def _run_beat(self) -> StepOutcome:
        slots, pending, queue = self._lanes, self._pending, self._queue
        self._ensure_cache()
        self.stats["scheduler_beats"] += 1
        self._stage("police")
        # cancellation + deadline sweep over every request pool, once
        # per block boundary (host-side only, no device sync)
        self._police(slots, pending, queue)
        # one breaker tick per scheduler beat (deterministic pacing)
        self._dev_breaker.tick()
        self._retry_breaker.tick()
        # retry-wait requests whose backoff elapsed rejoin the queue
        self._pump_retries(queue)
        # degraded + repromote: once the device breaker's cooldown has
        # passed, probe with a canary and promote back to
        # device-resident scheduling if the device answers
        if (self.device_sched and self.repromote and not self._dev_active
                and (queue or pending
                     or any(s.active for s in slots))):
            self._try_promote(slots)
        # wave-assign every free slot a queued request; all pending
        # admissions advance together, one chunk per wave dispatch.
        # mid-flight = an admission that starts while other lanes are
        # live decoding.  Paged mode admits FIFO under worst-case page
        # reservation (discounted by granted shared pages): the
        # reservation sum plus legacy shared pages never exceeds the
        # pool, so lazy page growth can't fail mid-flight.
        # padded lanes (slot-axis rounding under data sharding) sit past
        # _usable_slots and are never assigned — they tick fully masked
        self._stage("admit" if queue else None)
        for i, s in enumerate(slots[:self._usable_slots]):
            if not queue:
                break
            if not s.active and i not in pending:
                # pop invalid heads first: a rejection frees the head
                # position for the next queued request immediately
                # (submit() already validated fresh arrivals; this keeps
                # the gate airtight for anything re-queued internally)
                while queue:
                    err = self._validate(queue[0])
                    if err is None:
                        break
                    self._reject(queue.popleft(), err)
                if not queue:
                    break
                head = queue[0]
                grant = None
                if self.paged:
                    ns = self._slot_shard(i)
                    if self._prefix is not None:
                        grant = self._prefix_lookup(
                            self._eff_prompt(head), ns)
                    if self._held_for_pending_prefix(
                            head, pending,
                            grant["base"] if grant else 0, ns):
                        # a pending admission is prefilling this head's
                        # prefix right now: wait for it to register its
                        # pages rather than prefill the prefix twice
                        # (counted once per held head, like deferrals)
                        if head is not self._held_head:
                            self.stats["admissions_held_for_prefix"] += 1
                            self._held_head = head
                        break
                    worst = self.worst_case_pages(head)
                    # reservation = pages this slot may ALLOCATE:
                    # aliased prefix pages are discounted (they already
                    # exist); the CoW boundary page is not (it is a
                    # fresh allocation the reservation must cover)
                    reserve = worst - (len(grant["pages"]) if grant
                                       else 0)
                    # granting converts index-only pages (evictable)
                    # into slot-pinned ones — account for them like
                    # legacy shared pages
                    newly_pinned = (sum(
                        1 for p in grant["pages"]
                        if p not in self._page_slot_refs)
                        if grant else 0)
                    if (self._reserved_total + self._pinned_unreserved()
                            + newly_pinned + reserve
                            > self._pool.usable):
                        # count deferral EPISODES (once per starved
                        # queue head), not loop iterations spent waiting
                        if head is not self._deferred_head:
                            self.stats["admissions_deferred_pages"] += 1
                            self._deferred_head = head
                        break  # page-starved: retry after lanes retire
                    self._slot_reserved[i] = reserve
                    self._reserved_total += reserve
                    self.stats["kv_reserved_pages_peak"] = max(
                        self.stats["kv_reserved_pages_peak"],
                        self._reserved_total)
                    if grant is not None and grant["base"]:
                        try:
                            self._cache = self._grant_prefix(
                                self._cache, i, grant)
                        except InjectedFault as e:
                            # CoW boundary allocation failed: the head
                            # retires FAILED; aliased pages + the
                            # reservation roll back refcount-exact
                            self._reject_started_head(
                                queue, i,
                                "KV page allocation failed during "
                                f"prefix grant: {e}")
                            continue
                pending[i] = self._start_admission(
                    i, queue.popleft(),
                    base=grant["base"] if grant else 0)
                if self.paged and self._dev_active:
                    # pre-grant the lane's whole worst-case reservation
                    # up front (the admission gate already reserved it,
                    # so schedulability is unchanged) — decode then
                    # never allocates, which is what lets block N+1
                    # dispatch without consulting the host allocator
                    req = pending[i]["req"]
                    try:
                        self._grow_pages(i, min(
                            len(req.prompt) + req.max_new_tokens - 1,
                            self.max_seq))
                    except InjectedFault as e:
                        self._abort_admission(
                            pending, i, RequestStatus.FAILED,
                            "KV page allocation failed at admission "
                            f"pre-grant: {e}")
                        continue
                if any(o.active for o in slots):
                    self.stats["mid_flight_admissions"] += 1
        self._stage()
        # one batched prefill wave — in-flight lanes stall for at most
        # this one dispatch before the next decode block runs
        if pending:
            others_active = any(s.active for s in slots)
            self._cache = self._prefill_wave(self._cache, pending, slots)
            if others_active:
                self._chunks_since_block += 1
                self.stats["max_chunks_between_decode_blocks"] = max(
                    self.stats["max_chunks_between_decode_blocks"],
                    self._chunks_since_block)
        # one fused decode block for every live lane.  Under the
        # device-resident scheduler the host view can lag one block
        # behind the device (a lane that finished on device still looks
        # active here) — the extra dispatch ticks fully masked, and the
        # drain inside _run_decode_block refreshes the view.
        if any(s.active for s in slots):
            self._cache = self._run_decode_block(self._cache, slots)
            self._chunks_since_block = 0
            if self.on_block is not None:
                # test/ops hook at the block boundary (e.g. issue a
                # cancel() deterministically at block k)
                self.on_block(self, self.stats["decode_blocks"])
        elif self._inflight:
            # nothing left to dispatch: read back the trailing blocks
            self._drain_blocks(slots, depth=0)
        idle_until = None
        if (self._retryq and not queue and not pending
                and not self._inflight
                and not any(s.active for s in slots)):
            # the only work left is waiting out retry backoff: surface
            # the earliest expiry so the caller SLEEPS toward it instead
            # of spinning the beat loop (the batch-mode busy-spin bug)
            idle_until = min(e["not_before"] for e in self._retryq)
        remaining = (len(queue) + len(pending) + len(self._retryq)
                     + sum(1 for s in slots if s.active))
        return StepOutcome(worked=True, remaining=remaining,
                           idle_until=idle_until)

    def drain(self) -> dict:
        """Step until every submitted request is terminal — sleeping (one
        ``time.sleep`` per backoff window, counted in
        ``stats["idle_sleeps"]``), never spinning — then finalize the
        stats window.  Returns ``self.stats``.  Idempotent: draining an
        idle engine just re-finalizes the current window."""
        while self.has_work:
            out = self.step()
            if out.idle_until is not None:
                wait = out.idle_until - time.perf_counter()
                if wait > 0:
                    self.stats["idle_sleeps"] += 1
                    self.stats["idle_wait_s"] += wait
                    time.sleep(wait)
        self._finalize_window()
        return self.stats

    def close(self) -> None:
        """Finish all in-flight work and retire the engine: ``drain()``,
        then refuse further ``submit()`` calls.  ``step()``/``drain()``
        stay callable (and no-op) so shutdown races are harmless."""
        self.drain()
        self._closed = True

    def _finalize_window(self) -> None:
        """Close out the stats window over the requests submitted since
        the last ``reset_stats()``: wall clock, throughput, TTFT
        percentiles, the authoritative status recount, paged-pool gauges —
        then fold the window's contribution into the engine-lifetime
        counters (``self.lifetime``) exactly once (re-finalizing replaces
        the previous contribution instead of double-counting), which is
        what lets two consecutive ``run()``s on a shared engine account
        faults and statuses additively instead of clobbering them."""
        requests = self._window_requests
        wall = time.perf_counter() - self._window_t0
        total = sum(len(r.output) for r in requests if r.output is not None)
        ttfts = [r.ttft_s for r in requests if r.ttft_s is not None]
        st = self.stats
        # authoritative, attempts-aware status recount from the request
        # objects themselves (the incremental counters can only agree,
        # but recounting makes the invariant structural: sum(status
        # counters) == len(window requests)).  A re-queued request counts
        # exactly once, under its FINAL status — the withdrawn attempts
        # live in the retry gauges (requests_retried / retries_total /
        # per-request attempts + retry_errors), never in the status
        # counters.
        counts = {s: 0 for s in RequestStatus}
        for r in requests:
            if r.status is not None:
                counts[r.status] += 1
        for s_, key in _STATUS_COUNTERS.items():
            st[key] = counts[s_]
        st["requests_retried"] = sum(1 for r in requests if r.retries)
        st["retries_total"] = sum(r.retries for r in requests)
        st["breaker_state"] = self._dev_breaker.state
        st["retry_breaker_state"] = self._retry_breaker.state
        fi = self.fault_injector
        if fi is not None:
            st["faults_injected"] = max(0, len(fi.events) - self._fi_events0)
        st.update({
            "wall_s": wall,
            "total_new_tokens": total,
            "tokens_per_s": total / wall if wall > 0 else float("inf"),
            # per-request TTFT, measured from each request's ARRIVAL
            # (submit time) — under batch run() arrival coincides with the
            # window start, so the batch semantics are unchanged
            "ttft_s": ttfts,
            "ttft_p50_s": (float(np.percentile(ttfts, 50)) if ttfts
                           else None),
            "ttft_p95_s": (float(np.percentile(ttfts, 95)) if ttfts
                           else None),
            # dispatch-gating host syncs charged to steady-state blocks:
            # exactly 1.0 host-driven (every block round-trips before the
            # next dispatch), exactly 0.0 device-resident (the carry is
            # threaded on device; drains that retire a lane end the steady
            # interval and are charged to the non-steady block that follows)
            "steady_state_syncs_per_block": (
                self._steady_syncs / st["steady_state_blocks"]
                if st["steady_state_blocks"] else 0.0),
            "host_syncs_per_block": (
                st["host_block_syncs"] / st["decode_blocks"]
                if st["decode_blocks"] else 0.0),
        })
        if self.paged:
            usable = self._pool.usable
            st.update({
                "kv_page_size": self.page_size,
                "kv_pool_pages": usable,
                "kv_pool_tokens": usable * self.page_size,
                "kv_pool_util_peak": (st["kv_pages_peak"] / usable
                                      if usable else 0.0),
                # after drain only the prefix cache still holds pages (0
                # without sharing); each is counted once however many
                # readers it had
                "kv_pages_in_use": self._pool.used_pages,
                "kv_prefix_cached_pages": (self._prefix.n_pages
                                           if self._prefix else 0),
                "prefix_hit_rate": (st["prefix_hits"] / st["admissions"]
                                    if st["admissions"] else 0.0),
            })
        # engine-lifetime accounting: replace this window's previous
        # contribution (if it was already finalized) with the fresh one
        contrib = {"windows": 1, "total_new_tokens": total}
        for key in _STATUS_COUNTERS.values():
            contrib[key] = st[key]
        for key in ("faults_injected", "admissions", "decode_blocks",
                    "decode_tokens", "requests_retried", "retries_total"):
            contrib[key] = st[key]
        prev = self._window_contrib or {}
        for k, v in contrib.items():
            self.lifetime[k] += v - prev.get(k, 0)
        self._window_contrib = contrib

    def run(self, requests: List[Request]) -> List[Request]:
        """Serve a batch: chunked admission interleaved with fused decode
        blocks (token-level continuous batching).  A thin wrapper over the
        resident lifecycle — reset the stats window, ``submit()`` every
        request, ``drain()`` — so batch and incremental submission run the
        EXACT same scheduler loop and produce identical tokens (default
        seeds key on the engine-lifetime arrival counter, deadline/TTFT
        clocks on per-request arrival).  Serving state (KV pool, prefix
        cache, breakers, retry queue) persists across ``run()``s on a
        shared engine; a window that ended degraded starts the next run
        device-resident again (the device breaker keeps its cooldown)."""
        self.reset_stats()
        self._restore_device_residency()
        fi = self.fault_injector
        if fi is not None:
            # per-run ordinal addressing (fail the Nth alloc of THIS run);
            # fi.events persists, so lifetime fault accounting still sums
            fi.reset_run()
        for r in requests:
            self.submit(r)
        self.drain()
        return requests
