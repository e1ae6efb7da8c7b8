"""Continuous batching for ``generate()`` serving.

The reference's protocol is unary request/response; its only batching is
the client's (SURVEY §7 hard part (b): "dynamic micro-batching +
continuous batching for generate() under a protocol designed for unary
calls"). This scheduler closes that gap the TPU way:

* A fixed pool of ``slots`` decode lanes and a fixed cache length — every
  device computation has STATIC shapes, so XLA compiles exactly three
  executables (prefill per bucket, slot-insert, fused decode+sample) and
  the MXU never waits on a recompile.
* New requests are admitted into free slots **while older requests are
  mid-decode**: prefill runs as its own batched forward (bucketed prompt
  lengths), its K/V is spliced into the shared cache with a
  ``dynamic_update_slice``, and the next fused step decodes old + new
  lanes together (``DecoderLM.decode_step_ragged`` — per-row positions).
* Sampling is fused into the decode executable (greedy/temperature per
  lane), and bursts of up to ``steps_per_poll`` decode steps run as ONE
  device call (``lax.scan`` over the fused step), so the host syncs once
  per burst — not once per token. Dispatch/sync latency is the decode
  bottleneck off-device; this amortises it k-fold.
* Bursts are **software-pipelined** (``pipeline_depth``, default 2): burst
  N+1 is dispatched (its token copy started, ``copy_to_host_async``)
  before burst N is read, so ONE burst is queued behind the running one.
  That covers a host turn (read, credit, admit, dispatch) shorter than a
  burst; a second queued burst hides nothing more and adds a burst period
  to every token's delivery. Decode state lives on device, so the host
  only *observes* tokens late: each dispatch snapshots which request held
  each lane and a burst's tokens are credited strictly to that snapshot
  (a lane that finished mid-pipeline decodes a few ignored tokens).
* The KV cache is held as per-layer arrays and updated IN PLACE: only the
  one-position write touches HBM per step (a scatter, or the decode
  kernel's own copy where ``reads_ragged`` holds; a stacked cache threaded
  through the layer scan made XLA rewrite every byte of it every step).
  The attention READ follows the live prefix, not the allocated cache:
  where ``ops.decode_attention.reads_ragged`` holds each lane's own
  length bounds it, elsewhere a static bucket covering the deepest
  lane's position (host-tracked, no sync).
* **Chunked prefill interleave** (``prefill_chunk``): a long-prompt
  admission no longer stalls every decode lane for a full prompt-length
  forward. The prompt is split into ``prefill_chunk``-token slices
  executed BETWEEN decode polls (``DecoderLM.prefill_chunk`` extends a
  staging slab without re-reading the prefix — the slab lives OUTSIDE
  the decode cache, so in-flight bursts never see a half-built prompt
  and the decode executables stay bit-identical to the whole-prompt
  path); only the final slice samples the first token, and the finished
  slab goes through the ordinary lane insert. Decode keeps its burst
  cadence while long prompts trickle in.
* With a mesh, params/cache shard over the ``model`` axis (KV heads) and
  optionally the ``seq`` axis (cache length) — long prompts span ICI.

No reference counterpart (category: new TPU-native capability; BASELINE
config 5 "Llama-2-7B generate() with engine-side dynamic batching").
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import logging
import queue
import threading
import time
from concurrent.futures import Future
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..analysis.roles import caller_thread, scheduler_only
from ..tracing import (
    HostClock, PhaseClock, compile_report, compiles_since, get_tracer, wall_us,
)

logger = logging.getLogger(__name__)


class PromptTooLong(ValueError):
    """The request cannot fit the serving cache: the prompt exceeds
    every prefill bucket and ``max_seq``. Carries a wire status so the
    engine answers a typed **413** (REST) / ``INVALID_ARGUMENT`` (gRPC)
    instead of a 500 traceback — the client sent an unservable request;
    retrying it unchanged can never succeed."""

    status = 413


class BudgetExceeded(PromptTooLong):
    """``prompt_len + max_new_tokens > max_seq``: the generation would
    outgrow the decode cache. Rejected **at submit/admit time** with the
    same 413-class status as :class:`PromptTooLong` — before this check
    the overrun was silently clamped (a client asking for 512 tokens got
    40 with no signal) and anything slipping past surfaced deep in the
    scheduler as a shape error. Size ``max_seq`` to prompt + budget, or
    lower ``max_new_tokens``."""


class RetuneError(ValueError):
    """A :meth:`ContinuousBatcher.retune` request named a knob value
    outside the boot-time compile census (or an unknown/ill-typed knob).
    Typed and raised synchronously on the caller thread BEFORE anything
    is staged: a config the warm() pass did not precompile would stall
    the scheduler tens of seconds mid-traffic, so the planner's
    out-of-census proposals are refused here, never half-applied."""


class BatcherDead(RuntimeError):
    """The continuous batcher's scheduler loop is not serving: it died
    (in-flight work at crash time), its crash-loop budget is exhausted
    (latched dead until the reconciler replaces the member), or it was
    closed. Carries the 503 wire status plus ``retry_after_s`` so the
    engine maps it to ``503 + Retry-After`` exactly like PR 2's shed
    path maps :class:`~..resilience.ShedError` to 429 — clients back
    off and retry (another replica, or this one once its supervised
    restart lands)."""

    status = 503

    def __init__(self, info: str, retry_after_s: float = 1.0):
        super().__init__(info)
        self.info = info
        self.retry_after_s = float(retry_after_s)


def _is_ready(array) -> bool:
    """``array.is_ready()``; one without the method (a test double's, a
    numpy array) is ready as it stands."""
    ready = getattr(array, "is_ready", None)
    return ready is None or ready()


# the scheduler's poll, phase by phase (PhaseClock adds "other"): each a
# ``batcher.<phase>`` span in the profiler's host plane and seconds in
# ``stats["loop_<phase>_s"]``
LOOP_PHASES = ("admit", "chunks", "dispatch", "read_wait", "credit", "idle")


@dataclasses.dataclass
class FrontStamps:
    """What the front stamps on a request (monotonic seconds, 0.0 = not
    reached). Written by the front's thread alone, possibly after the
    scheduler resolved the request, so the timeline ring keeps this
    object by reference rather than a copy of its values."""

    # the route was entered, before the body was parsed
    received_t: float = 0.0
    # the first token chunk / the done event was handed to the connection
    first_write_t: float = 0.0
    done_write_t: float = 0.0


@dataclasses.dataclass
class GenRequest:
    tokens: List[int]
    max_new_tokens: int = 32
    temperature: float = 0.0
    eos_id: Optional[int] = None
    seed: int = 0
    future: Future = dataclasses.field(default_factory=Future)
    # streaming: called from the scheduler thread with each newly credited
    # span of tokens (must be cheap + non-blocking; exceptions are logged,
    # never propagated into the decode loop)
    on_tokens: Optional[object] = None
    # prompt tokens served from the radix prefix cache at admit (0 = full
    # prefill); surfaced per-request so responses/graph nodes can report
    # cache effectiveness
    cache_hit_tokens: int = 0
    # -- lifecycle timeline (monotonic seconds; 0.0 = not reached) --------
    # stamped by the scheduler as the request crosses each phase boundary;
    # feed both the SLO histograms (queue wait / TTFT / TPOT) and — when a
    # sampled trace context rode in on ``trace`` — the retroactive
    # per-request timeline spans. Plain float stores: no allocation, no
    # lock, written by one thread at a time per field.
    submit_t: float = 0.0
    admit_t: float = 0.0
    # lane activation: the moment the prompt K/V landed in the decode
    # cache (post-insert) — for chunked admissions this is many polls
    # after admit_t, so decode residency must anchor here, not at admit
    decode_start_t: float = 0.0
    # the prefill's dispatch returned and the insert's begins: admit_t to
    # here is the host's time in the prefill's dispatch, here to
    # decode_start_t in the insert's (a chunked admission: the last chunk's)
    insert_t: float = 0.0
    # the loop's poll (``_poll_count``) that activated the lane: the
    # flight recorder's row with this ``poll`` names the request among its
    # ``admitted_ids``
    admit_poll: int = 0
    # the first burst whose snapshot held this lane with its prefill
    # token still pending was dispatched: that burst carries the first
    # token to the host
    first_dispatch_t: float = 0.0
    first_tok_t: float = 0.0
    done_t: float = 0.0
    front: FrontStamps = dataclasses.field(default_factory=FrontStamps)
    # process-wide sequence number: names the request in the timeline ring
    rid: int = dataclasses.field(default_factory=itertools.count(1).__next__)
    # wall-clock anchor of submit_t (epoch microseconds) so retroactive
    # spans can place monotonic intervals on the Jaeger timeline
    submit_wall_us: int = 0
    # (trace_id, parent_span_id) captured from the submitting thread's
    # active span; None when tracing is off or the request is unsampled
    trace: Optional[Tuple[str, str]] = None
    # disaggregated serving: a remote admit carries its prefill-side slab
    # here ({"slab" dev arrays, "first", "key", "covered", "nbytes",
    # "version"}) and skips local prefill entirely — the wave-routing
    # loop routes it to _admit_remote_lane (see admit_remote)
    remote: Optional[Dict[str, Any]] = None
    # decode-lane preemption checkpoint ({"emitted": [...], "key":
    # [hi, lo]}): set when a pressure reclaim evicted this request from
    # its lane mid-decode. The K/V is NOT checkpointed — resume
    # recomputes it with a prefill over prompt+generated-so-far and
    # continues the exact sampling stream from the checkpointed
    # post-split RNG lane key (see _admit_resume). None = never
    # preempted, or preempted before any token was credited (a plain
    # re-admit reproduces the identical stream from the seed alone).
    resume: Optional[Dict[str, Any]] = None
    # absolute deadline (monotonic seconds) when the submit carried a
    # budget — the preemption victim policy reads it (a lane that must
    # answer soon is preempted only after every deadline-free lane)
    deadline_t: Optional[float] = None
    # multi-tenant serving (serving/weightpager.py): owning tenant id
    # (None = single-tenant back-compat) and SLO class ("strict" |
    # "standard" | "best_effort"). The victim policy protects a strict
    # tenant's last live lane; _resolve splits the SLO samples per
    # tenant so the scheduler's starvation score sees per-tenant TTFT
    tenant: Optional[str] = None
    slo: str = "standard"

    def emit_span(self, operation: str, start_t: float, end_t: float,
                  tags: Optional[Dict[str, Any]] = None) -> None:
        """Retroactive timeline span, parented under the trace context
        captured at submit(). No-op (one attribute check) for untraced
        requests. Monotonic interval endpoints are placed on the wall
        clock via the request's submit anchor."""
        if self.trace is None:
            return
        start_us = self.submit_wall_us + int((start_t - self.submit_t) * 1e6)
        get_tracer().record_span(
            operation, self.trace[0], self.trace[1], start_us,
            int((end_t - start_t) * 1e6), tags=tags,
        )


@dataclasses.dataclass
class _ChunkJob:
    """A long-prompt admission mid-chunked-prefill: the slot is reserved
    but not yet decoding; one chunk advances per scheduler poll. The
    prompt K/V accumulate in a STAGING slab (cache_one layout) outside
    the decode cache, spliced into the lane only when complete."""

    request: GenRequest
    slot: int
    next_start: int  # absolute position of the next chunk's first token
    slab: Any  # {"k","v"} stacked [L, 1, KV, bucket, Dh]
    bucket: int
    # prompt tokens already covered by a spliced prefix-cache slab
    # (chunking then starts at the splice point)
    hit_tokens: int = 0
    # preemption recompute-resume: (emitted tokens, checkpointed lane
    # key) — the final chunk then inserts the checkpointed continuation
    # state instead of its own sample and replays the emitted K/V
    resume: Optional[Tuple[List[int], Any]] = None


@dataclasses.dataclass
class _SwapJob:
    """A requested live weight swap: the new params are already cast,
    device-resident and (when meshed) sharded — double-buffered next to
    the serving set. The scheduler flips the pointer at a poll boundary
    once every in-flight lane (decode, chunked prefill, pipelined burst)
    has finished on the OLD version; until then admissions hold so the
    drain converges."""

    params: Any
    version: Any
    future: Future = dataclasses.field(default_factory=Future)
    # lanes in flight when the scheduler first observed the request
    # (flight-recorder attribution), and polls spent draining them
    drain_lanes: Optional[int] = None
    waited_polls: int = 0
    # double-buffered param bytes — the pressure ledger's "swap"
    # component while the drain holds both versions resident
    nbytes: int = 0
    # when the swap was staged (monotonic): the straggler bound
    # (swap_drain_ms) is measured from here, so one long generation
    # cannot stall the flip indefinitely
    staged_t: float = 0.0


@dataclasses.dataclass
class _DrainJob:
    """A requested graceful drain: the scheduler checkpoints every live
    lane at the next poll boundary (reusing the preemption machinery),
    collects chunked admissions, the resume queue, and queued-not-
    admitted requests, and resolves the future with the full list of
    :class:`GenRequest` — each carrying its host-side checkpoint in
    ``resume`` — for the caller to hand to a peer."""

    future: Future = dataclasses.field(default_factory=Future)


@dataclasses.dataclass
class _RetuneJob:
    """A validated live knob retune (autonomic planner actuation): the
    scheduler applies it at the next poll boundary — the same staging
    discipline as :class:`_SwapJob`/:class:`_DrainJob`, so a knob flip
    can never tear a live burst (the loop snapshots ``_fused_k`` once
    per poll) or race a chunked prefill (a ``prefill_chunk`` change
    waits until the in-flight chunk jobs drain). ``knobs`` holds the
    canonicalized target values; validation already happened on the
    caller thread (:class:`RetuneError` on refusal)."""

    knobs: Dict[str, Any]
    origin: str = "planner"
    future: Future = dataclasses.field(default_factory=Future)
    # polls spent deferring (chunked prefills in flight while the job
    # changes prefill_chunk) — flight-recorder attribution
    waited_polls: int = 0


@dataclasses.dataclass
class _Slot:
    request: GenRequest
    emitted: List[int] = dataclasses.field(default_factory=list)
    # the prefill's first sampled token stays ON DEVICE at admit (reading
    # it would cost a host sync per admission); the next burst's [0] row
    # carries it to the host instead
    first_pending: bool = True
    # tokens covered by bursts DISPATCHED so far (not yet necessarily
    # observed). When the request has no eos, completion is predictable:
    # dispatched >= max_new_tokens means the in-flight bursts already
    # cover the whole budget and the lane can be re-admitted NOW instead
    # of pipeline_depth bursts later (see the pre-free block in _loop)
    dispatched: int = 0
    # crediting fence: set once the request's output is complete (budget
    # or eos) so rows from later in-flight bursts — overshoot decode, or
    # rows that now belong to the lane's next occupant — are never
    # appended or streamed to a finished request
    credit_done: bool = False
    # generation by blocks, a traced request: when the lane's last block
    # went to the client (the start of the next ``gen.block`` span)
    block_t: float = 0.0


def _positions_streamed(pos: int, k: int, bucket: int, block: int) -> int:
    """Positions the ragged decode kernel streams for one lane over ``k``
    steps, the first of which writes at ``pos``: the j-th reads ``pos + j``
    keys, never more than the bucket (the entry clamps a lane's length to
    it), rounded up to the kernel's ``block``: the walk copies whole
    blocks, past a bucket that is no multiple of the block too."""
    return sum(
        -(-min(pos + j, bucket) // block) * block for j in range(1, k + 1)
    )


def _positions_windowed(pos: int, k: int, window: int, block: int) -> tuple:
    """``_positions_streamed`` for a layer with a window: ``(streamed,
    seen, live)`` over the ``k`` steps. The j-th step's lane holds ``pos +
    j`` keys (live), must see the last ``window`` of them (seen) and
    streams the blocks from the one that holds the window's start."""
    streamed = seen = live = 0
    for j in range(1, k + 1):
        n = pos + j
        start = max(0, n - window)
        streamed += (-(-n // block) - start // block) * block
        seen += n - start
        live += n
    return streamed, seen, live


class _BurstExecutable:
    """A jitted function of the batcher that runs the family's decode step
    (a burst, the replay). Called with the batcher's ``params`` (by the
    scheduler, ``warm()``, a comparison with a reference that drives the
    batcher's own executables) it runs on the tree derived from them
    (``_derive_burst_params``): no caller knows the burst's layout. Any
    other tree goes through as given, and what else is asked of it
    (``lower``, ``_cache_size``) is the jitted function's."""

    def __init__(self, batcher: "ContinuousBatcher", jitted):
        self._batcher = batcher
        self._jitted = jitted

    def __call__(self, params, *args):
        b = self._batcher
        return self._jitted(
            b._burst_params if params is b.params else params, *args)

    def __getattr__(self, name):
        return getattr(self._jitted, name)


class ContinuousBatcher:
    """Slot-based continuous batching scheduler over a decoder family
    (``models/family.py:DecoderFamily``: everything it asks of a model).

    ``submit()`` is thread-safe and returns a Future resolving to the
    generated token list. A single scheduler thread owns the device loop.
    """

    # floor for attn_bucket: cache reads must stay MXU/VPU-tileable on
    # TPU. Tests lower it (via the class attribute) to cross buckets at
    # tiny cache lengths on CPU.
    MIN_ATTN_BUCKET = 64

    def __init__(
        self,
        model,
        params,
        slots: int = 8,
        max_seq: Optional[int] = None,
        mesh=None,
        shard_cache_seq: bool = False,
        prefill_buckets: Sequence[int] = (32, 128, 512, 1024, 1792),
        steps_per_poll: int = 8,
        pipeline_depth: int = 2,
        attn_bucket: int = 128,
        fused_steps_per_dispatch: int = 0,
        draft_model=None,
        draft_params=None,
        speculate_tokens: int = 4,
        prefix_cache_hbm_bytes: int = 0,
        prefix_cache_min_tokens: int = 16,
        admit_queue_limit: int = 0,
        prefill_chunk: int = 0,
        flight_recorder_capacity: int = 4096,
        restart_budget: int = 3,
        restart_backoff_s: float = 0.5,
        hbm_ledger_bytes: int = 0,
        pressure_high: float = 0.90,
        pressure_low: float = 0.75,
        host_kv_tier_bytes: int = 0,
        kv_tier_min_tokens: int = 0,
        kv_tier_promote_min_tokens: int = 0,
        swap_drain_ms: int = 0,
        swap_resume_policy: str = "resume",
        profiler=None,
    ):
        import jax
        import jax.numpy as jnp
        from jax import lax

        self.model = model
        # a family refuses, typed and at load, the features it has no
        # path for (DecoderFamily.serving_refuses) rather than computing
        # something else under them
        self._check_family_serves(
            speculation=draft_model is not None,
            mesh=mesh is not None,
            kv_tier=int(host_kv_tier_bytes) > 0,
            prefix_cache=int(prefix_cache_hbm_bytes) > 0,
            chunked_prefill=int(prefill_chunk) > 0,
            preemption=int(hbm_ledger_bytes) > 0 or int(swap_drain_ms) > 0,
            fused=int(fused_steps_per_dispatch) > 0,
        )
        self.slots = int(slots)
        self.max_seq = int(max_seq or model.cfg.max_seq)
        # positions a lane's decode step covers: 1, or the block of a
        # family that generates by blocks (model.block_tokens()), whose
        # bursts are passes over each live lane's block (_block_burst_fn)
        self._block_w = int(model.block_tokens())
        if self.max_seq % self._block_w:
            raise ValueError(
                f"max_seq {self.max_seq} is no multiple of the family's "
                f"block of {self._block_w} positions")
        self.mesh = mesh
        self.steps_per_poll = int(steps_per_poll)
        # burst length actually dispatched: pow2 floor of steps_per_poll —
        # computed ONCE so warm() and the loop can never disagree on which
        # burst executable exists. The floor is surfaced (not silent): it
        # rides server stats as ``steps_per_poll_effective`` and logs once
        # here, so an operator who configured 12 can see they got 8.
        k = max(1, self.steps_per_poll)
        while k & (k - 1):
            k &= k - 1
        self._k = k
        if k != self.steps_per_poll:
            logger.info(
                "steps_per_poll=%d rounded down to the pow2 burst length "
                "%d (see steps_per_poll_effective in server stats)",
                self.steps_per_poll, k,
            )
        # fused multi-step decode: one dispatch runs up to this many
        # decode steps with ON-DEVICE stop-token detection and per-lane
        # done masks (0 = off — the step-at-a-time burst path, exactly
        # the pre-fused code). pow2-floored like steps_per_poll so one
        # executable exists per (K, attn bucket).
        self.fused_steps_per_dispatch = max(0, int(fused_steps_per_dispatch))
        fk = self.fused_steps_per_dispatch
        while fk & (fk - 1):
            fk &= fk - 1
        self._fused_k = fk
        # True while the device-resident per-lane stop/budget registers
        # match the host's view; membership changes and mode flips clear
        # it so the next fused dispatch re-uploads (never per burst)
        self._fused_sync = False
        # bursts in flight before the host blocks on the oldest: 1 = fully
        # synchronous (dispatch, read, ...), 2 = one queued behind the running
        self.pipeline_depth = max(1, int(pipeline_depth))
        # attention-read bucket granularity: the per-burst cache read is
        # rounded up to a multiple of this. Smaller = tighter KV reads at
        # deep prefixes but more burst executables (one per bucket); 64
        # is the practical TPU floor (the read must stay MXU/VPU-
        # tileable), enforced via the MIN_ATTN_BUCKET class attribute so
        # production configs keep the historical clamp while CPU tests
        # lower it to cross buckets at tiny cache lengths
        self.attn_bucket = max(type(self).MIN_ATTN_BUCKET, int(attn_bucket))
        # chunked prefill: prompt tokens per interleaved prefill slice
        # (0 = off; prompts whose bucket fits one chunk never chunk)
        self.prefill_chunk = max(0, int(prefill_chunk))
        # speculative decoding: a cheap draft proposes `speculate_tokens`
        # tokens per round and ONE target chunk forward verifies them.
        # Exact for any draft: greedy lanes emit the target's argmax
        # decode; temperature lanes use speculative SAMPLING (accept with
        # min(1, p/q), resample the residual on rejection) whose output
        # distribution equals sampling the target. The draft only sets
        # how many target forwards each token costs.
        self.draft_model = draft_model
        self.speculate_tokens = int(speculate_tokens) if draft_model is not None else 0
        # the family says which of them its prefill takes (all, but for
        # one whose prefill is laid out in windows) and which lengths past
        # them a long prompt pads to instead of ``max_seq``
        own = sorted(b for b in prefill_buckets if b <= self.max_seq)
        self.prefill_buckets = tuple(
            model.prefill_lengths(own, self.max_seq)) or (self.max_seq,)
        # the lengths the family added: they take the prompts a call the
        # family gives an added length (``_rows_ok``), the configured
        # buckets and ``max_seq`` what they always took
        self._added_lengths = frozenset(self.prefill_buckets) - {
            *own, self.max_seq}
        # and how many prompts a turn admits before the next burst (every
        # free lane, but for a family whose prefill holds the device long)
        self._admit_cap = int(model.admissions_per_turn()) or self.slots

        self._queue: "queue.Queue[GenRequest]" = queue.Queue()
        # -- admit-queue load shedding (shed-before-work) -----------------
        # hard cap on queued-not-admitted requests (0 = uncapped), plus a
        # deadline-aware shed: completion timestamps of finished requests
        # give an observed service rate, and a submit whose expected queue
        # wait (depth / rate) already exceeds its remaining deadline is
        # rejected NOW — before its prefill occupies the device for a
        # response nobody will wait for
        self.admit_queue_limit = max(0, int(admit_queue_limit))
        self._finish_times = collections.deque(maxlen=32)
        self._active: Dict[int, _Slot] = {}
        # device copies of the lane masks; re-uploaded only when lane
        # membership changes (every host->device transfer pays the
        # dispatch-latency tax, so the steady-state loop must not upload
        # anything per burst)
        self._masks_dirty = True
        self._active_dev = None
        self._temps_dev = None
        self._any_stoch = False
        # host mirror of each lane's device position (prompt length at
        # admit, +k per dispatched burst) — lets the scheduler pick the
        # attention-read bucket WITHOUT a device sync
        self._pos_host: Dict[int, int] = {}
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._thread_lock = threading.Lock()
        self._started = threading.Event()
        # -- scheduler supervision (crash-loop restart) -------------------
        # a loop death no longer poisons the batcher forever: the
        # supervisor fails in-flight work with a typed BatcherDead,
        # rebuilds the device state (the donated cache buffers are gone),
        # re-warms, and resumes — bounded by ``restart_budget`` restarts
        # with exponential backoff from ``restart_backoff_s``. Exhausting
        # the budget latches ``health = "dead"`` (readiness goes red so
        # the reconciler replaces the member). ``health`` is a plain str
        # written by one thread at a time: "serving" | "restarting" |
        # "dead" | "closed".
        self.health = "serving"
        self.restart_budget = max(0, int(restart_budget))
        self.restart_backoff_s = max(0.0, float(restart_backoff_s))
        # the budget counts crashes in quick succession (a crash LOOP):
        # after this long without a death, the counter resets — a
        # once-a-day transient must never slowly latch a healthy member
        self.restart_window_s = 300.0
        self._restarts = 0
        self._last_crash_t = 0.0
        # chaos hook: called at the top of every scheduler poll with the
        # running poll count; raising kills the loop, exercising the REAL
        # crash-recovery path (resilience.faults wires it from the
        # SELDON_FAULTS scheduler section; tests set it directly)
        self.fault_hook: Optional[Any] = None
        # multi-tenant hook: called at the top of every poll with the
        # poll count (after the chaos hook). The TenantScheduler wires
        # its wake-up here — bookkeeping only, it must never block or
        # call caller-role batcher methods (serving/weightpager.py)
        self.tenant_hook: Optional[Any] = None
        # the WeightPager whose resident checkpoint the pressure ledger
        # bills as its "pager" component (set by the serving component
        # when multi-tenancy is on; None keeps the ledger unchanged)
        self.tenant_pager: Optional[Any] = None
        self._poll_count = 0
        # WORKING polls only (lanes live, chunked jobs pending, bursts
        # in flight, or queued work): the pressure hook's clock, so a
        # SELDON_FAULTS shrink window lands relative to traffic instead
        # of firing during idle churn
        self._work_poll_count = 0
        # warm() records its arguments here so a crash-restart re-runs
        # the same precompile before resuming admissions
        self._warm_args: Optional[Dict[str, Any]] = None
        # -- radix prefix KV cache (cross-request prompt reuse) -----------
        # device K/V slabs of completed requests' prompts, indexed by a
        # radix tree over token IDs; an admit whose prompt shares a cached
        # prefix splices the slab and prefills only the suffix. Budgeted
        # in HBM bytes (0 = off), LRU-evicted at radix-node granularity.
        self.prefix_cache_min_tokens = max(1, int(prefix_cache_min_tokens))
        self._prefix_cache_budget = int(prefix_cache_hbm_bytes)
        self._prefix_index = None
        if self._prefix_cache_budget > 0:
            from .prefix_cache import RadixPrefixIndex

            self._prefix_index = RadixPrefixIndex(self._prefix_cache_budget)
        # -- tiered KV memory: host-RAM spill tier (serving/kvtier.py) ----
        # SKV1-serialized slabs in pinned host RAM under their own byte
        # budget (0 = off): the reclaim ladder DEMOTES prefix slabs here
        # instead of destroying them (promote = device_put + splice on a
        # later match, locally or from a peer's tier over the KV
        # transport), and preempted lanes checkpoint their exact cache
        # columns for copy-back resume (recompute+replay stays the
        # fallback when the tier evicted the entry).
        self.host_kv_tier_bytes = max(0, int(host_kv_tier_bytes))
        # demote threshold: prefixes shorter than this never enter the
        # tier (defaults to prefix_cache_min_tokens); promote threshold:
        # tier matches shallower than this are not worth the PCIe copy
        # (defaults to the demote threshold)
        self.kv_tier_min_tokens = (
            int(kv_tier_min_tokens) or self.prefix_cache_min_tokens
        )
        self.kv_tier_promote_min_tokens = (
            int(kv_tier_promote_min_tokens) or self.kv_tier_min_tokens
        )
        self._kv_tier = None
        if self.host_kv_tier_bytes > 0:
            from .kvtier import HostKVTier

            self._kv_tier = HostKVTier(
                self.host_kv_tier_bytes,
                min_tokens=self.kv_tier_min_tokens,
                version=self.weight_version
                if hasattr(self, "weight_version") else 0,
            )
        # checkpoint-entry keys are per-batcher sequence numbers
        self._tier_ck_seq = 0
        # spec_rounds / spec_emitted feed the acceptance-rate gauge:
        # emitted/rounds ranges 1 (nothing accepted) .. gamma+1 (all).
        # prefill_steps/prefill_tokens split device prefill work out from
        # decode steps (the prefix cache's win shows up as prefill_tokens
        # dropping while prefix_tokens_saved climbs); prefill_tokens counts
        # the padded rows computed, prefill_prompt_tokens those of them
        # that held a token of a prompt: their ratio is what padding costs
        # burst_reads/burst_read_bytes: modeled HBM read traffic of
        # dispatched decode bursts — params once per step plus each
        # lane-row's bucketed KV read (spec rounds are excluded: a round
        # reads the draft's blocks gamma times beside one verify pass).
        # lane_steps = sum over dispatched bursts of k x rows (steps x
        # slots) — the occupancy denominator.
        self.stats = {
            "admitted": 0, "finished": 0, "cancelled": 0, "steps": 0,
            "lane_steps": 0,
            "tokens": 0, "spec_rounds": 0, "spec_emitted": 0,
            "prefill_steps": 0, "prefill_tokens": 0, "prefill_chunks": 0,
            "prefill_prompt_tokens": 0,
            "prefix_hits": 0, "prefix_misses": 0, "prefix_evicted": 0,
            "prefix_tokens_saved": 0, "prefix_cache_bytes": 0,
            "shed": 0,
            "burst_reads": 0, "burst_read_bytes": 0,
            # how far the ragged decode read engages: positions of K and V
            # the kernel streams per dispatched burst (each lane's
            # length rounded up to the kernel's block, step by step), and
            # what the bucket's dots read of the same burst (rows x
            # attn_len x steps). Their ratio is the share of the old read
            # still made
            "kv_positions_read": 0, "kv_positions_bucket": 0,
            # the same arithmetic for the model's layers with a window
            # (model.attention_kinds()): positions such a layer streams
            # (from the block that holds the window's start), the
            # positions it must see (min(len, window)), and every live
            # position of those lanes. 0 in a model with no such layer
            "kv_positions_read_window": 0, "kv_positions_seen_window": 0,
            "kv_positions_live_window": 0,
            # the decode write: K and V rows a dispatched burst lands for
            # its live lanes (lanes x steps x layers x 2), and how many of
            # them the read's kernel lands itself (all where
            # _ragged_read, none where the scatter writes them)
            "kv_rows_written": 0, "kv_rows_written_in_kernel": 0,
            # disaggregated serving: slabs/bytes shipped out (prefill
            # role), slabs/bytes admitted in (decode role), and transfer
            # bytes the decode-side radix cache deduplicated away
            "kv_exports": 0, "kv_export_bytes": 0,
            "kv_imports": 0, "kv_import_bytes": 0,
            "kv_transfer_bytes_saved": 0,
            # fault tolerance: supervised scheduler restarts that landed,
            # prefill-peer ejections/readmissions (decode role — bumped by
            # the server's failover transport), and remote prefills served
            # LOCALLY because the entire prefill pool was ejected
            "batcher_restarts": 0,
            "peer_ejections": 0, "peer_readmissions": 0,
            "degraded_local_prefill": 0,
            # HBM pressure: decode lanes preempted (checkpoint-to-host +
            # requeue), recompute-resumes that landed, admissions shed /
            # remote admits refused while over the high watermark, and
            # prefix slabs the reclaim ladder evicted (a subset of
            # prefix_evicted — the pressure-attributed share)
            "preemptions": 0, "preempt_resumes": 0,
            "pressure_sheds": 0, "pressure_refused": 0,
            "pressure_prefix_evictions": 0,
            # tiered KV memory (host-RAM spill tier): slabs demoted to
            # host RAM (prefix demotions + lane checkpoints + export
            # publishes), tier lookups that found an entry, entries
            # promoted back to device (device_put: local prefix match,
            # peer pull, checkpoint copy-back), entries LRU-evicted or
            # CRC-dropped, the tier's live byte level, and resumes that
            # EXPECTED a tier checkpoint but fell back to recompute +
            # teacher-forced replay (the tier evicted/refused it)
            "kv_tier_demotions": 0, "kv_tier_promotions": 0,
            "kv_tier_hits": 0, "kv_tier_evictions": 0,
            "kv_tier_bytes": 0, "kv_tier_replay_fallbacks": 0,
            # fused multi-step decode: device steps run inside stop-aware
            # fused bursts, and the dispatches that carried them — the
            # dispatch-floor win IS fused_steps / fused_dispatches
            # climbing while the host poll rate stays flat
            "fused_steps": 0, "fused_dispatches": 0,
            # operator note, not a counter: the pow2-floored burst length
            # actually dispatched (== steps_per_poll unless it was
            # silently-no-longer rounded down)
            "steps_per_poll_effective": k,
        }
        # export_prefill runs on caller threads (the prefill transport's
        # handlers), concurrently with each other; its stat updates take
        # this lock so counters can't lose increments
        self._export_lock = threading.Lock()
        # SLO instrumentation: queue-wait / TTFT / TPOT samples of
        # COMPLETED requests. ``slo_pending`` is the drain queue the
        # serving component ships as Meta.metrics TIMERs (drop-oldest
        # under pressure — telemetry must never grow unbounded);
        # ``slo_recent`` is a reservoir diagnostics read for percentiles.
        # The count and the TTFT sum ride in ``stats`` so a window-diffed
        # snapshot has the mean.
        self.slo_pending: "collections.deque" = collections.deque(maxlen=4096)
        self.slo_recent: "collections.deque" = collections.deque(maxlen=2048)
        self.stats.update({"slo_samples": 0, "ttft_s_sum": 0.0})
        # request timeline: one tuple per completed request beside its
        # SLO triple — (rid, prompt length, padded bucket, tokens emitted,
        # cache_hit_tokens, submit_t, admit_t, decode_start_t,
        # first_dispatch_t, first_tok_t, done_t, insert_t, admit_poll,
        # FrontStamps). Always on;
        # nothing per token. :meth:`capture_requests` names the fields.
        self.timeline_recent: "collections.deque" = collections.deque(maxlen=2048)
        # the scheduler loop's own time: working polls, bursts read back (and
        # those the device had finished first), dispatch-to-read seconds summed
        self.stats.update({
            "polls": 0, "bursts": 0, "bursts_read_late": 0,
            "burst_read_lag_s_sum": 0.0,
            # XLA compiles since the generate unit said ready (stage
            # ``serve`` of tracing.CompileLog), as the poll rows took them
            # along: backend compiles, and the seconds of every kind
            "compiles_after_ready": 0, "compile_after_ready_s": 0.0,
        })
        self._clock = PhaseClock(self.stats, "batcher", "loop", LOOP_PHASES)
        # per-tenant splits of the same samples (multi-tenant serving):
        # keyed lazily by tenant id at _resolve time so the single-tenant
        # path allocates nothing. tenant_slo counts a tenant's finished
        # requests; the pending deques drain as tenant-tagged TIMERs; the
        # recent reservoirs feed the TenantScheduler's TTFT feedback.
        self.tenant_slo: Dict[str, Dict[str, float]] = {}
        self.tenant_slo_pending: Dict[str, "collections.deque"] = {}
        self.tenant_slo_recent: Dict[str, "collections.deque"] = {}
        # scheduler flight recorder: one structured record per poll (batch
        # composition, the burst's plan, chunk interleave, shed events, and
        # the loop's clock over the poll: see _loop), bounded +
        # drop-oldest, cheap enough to leave on (0 = off)
        from .flightrecorder import FlightRecorder

        self.flight: Optional[FlightRecorder] = (
            FlightRecorder(flight_recorder_capacity)
            if int(flight_recorder_capacity) > 0
            else None
        )
        # what the next poll record takes along: the bursts read and the
        # requests admitted since the last one
        self._row_bursts: List[Dict[str, Any]] = []
        self._row_admitted: List[int] = []
        # and, with the ring on, the host's account of the scheduler
        # thread over the record's stretch (its heartbeat lives as long
        # as that thread: _run) and the compile log's events since
        self._host: Optional[HostClock] = (
            HostClock() if self.flight is not None else None
        )
        self._compile_cursor = compiles_since()[0]
        # the last chunk dispatched that was not a job's last, as (an
        # output of it, self._cur_tok then): see _device_drained
        self._chunk_newest: Optional[Tuple[Any, Any]] = None
        # device-time ledger (serving/profiler.py): every warmed-
        # executable dispatch below runs inside ``self._prof.measure``.
        # A disabled ledger's measure() is a shared no-op — the hooks
        # cost one attribute check — and the hooks never touch the
        # dispatched computation, so profiler on vs off is byte-
        # identical and compiles nothing new (tests/test_profiler.py
        # pins both).
        from .profiler import DeviceTimeLedger

        self._prof = (
            profiler if profiler is not None else DeviceTimeLedger()
        )
        # test/debug hook: set to a list and every dispatched decode
        # burst appends {"lanes", "attn_len", "need"} — the scheduler-
        # level record of the bucket each burst and each lane was given
        self.trace_groups: Optional[List[Dict[str, Any]]] = None
        # -- HBM pressure: unified ledger + watermark controller ----------
        # live decode footprint + staging slabs + prefix cache + pending
        # swap double buffer against hbm_ledger_bytes (0 = off: the hot
        # loop never consults it). Over the HIGH watermark the reclaim
        # ladder runs each poll (evict prefixes -> cancel speculation ->
        # preempt lanes -> shed admissions) until usage drops to LOW.
        from .pressure import PressureController

        self._pressure = PressureController(
            hbm_ledger_bytes, high=pressure_high, low=pressure_low
        )
        # chaos hook: called each poll with the poll count; a returned
        # int re-budgets the ledger (-1 restores the boot budget) — the
        # SELDON_FAULTS "pressure" section wires it (resilience.faults)
        self.pressure_hook: Optional[Any] = None
        # preempted requests awaiting recompute-resume: drained BEFORE
        # the admit queue so a victim re-acquires a lane ahead of newer
        # work (its recompute is the price already paid once)
        self._resume_queue: "collections.deque" = collections.deque()
        # reclaim rung 2: speculation cancelled under pressure (draft
        # cache freed; plain bursts decode — greedy streams identical by
        # the spec-exactness contract). Restored when pressure clears.
        self._spec_suppressed = False
        # chunked-prefill jobs in flight, keyed by reserved slot
        self._chunked: Dict[int, _ChunkJob] = {}
        # -- live weight hot-swap -----------------------------------------
        # request_weight_swap stages a double-buffered _SwapJob here; the
        # scheduler loop executes it at a poll boundary once all lanes
        # drained. weight_version keys the prefix cache (old-weights K/V
        # can never splice into a new-weights prefill) and rides flight-
        # recorder swap events.
        self.weight_version: Any = 0
        self.stats["weight_swaps"] = 0
        self._swap_lock = threading.Lock()
        self._pending_swap: Optional[_SwapJob] = None
        self._swap_seq = 0
        # hot-swap straggler bound: after this long draining, in-flight
        # lanes are preempt-checkpointed so one long generation cannot
        # stall a weight flip indefinitely (0 = wait forever, the
        # pre-existing behavior). Policy for the checkpointed
        # stragglers: "resume" re-queues them to continue on the NEW
        # weights (their prefix replays under the new version — a
        # deliberate, documented identity trade); "fail" refuses them
        # typed (WeightVersionMismatch, 409-class) so the client
        # re-submits under the new version knowingly.
        self.swap_drain_ms = max(0, int(swap_drain_ms))
        if swap_resume_policy not in ("resume", "fail"):
            raise ValueError(
                f"swap_resume_policy must be resume|fail, got "
                f"{swap_resume_policy!r}"
            )
        self.swap_resume_policy = swap_resume_policy
        # -- graceful drain / live-lane migration -------------------------
        # drain() stages a _DrainJob; the scheduler checkpoints every
        # live lane at a poll boundary and hands the host-side
        # checkpoints back for migration to a peer (serving/migration.py)
        self._pending_drain: Optional[_DrainJob] = None
        self._drain_lock = threading.Lock()
        self.stats.update({
            # drains completed, checkpoints exported to a peer,
            # checkpoints successfully migrated (peer accepted), resumes
            # admitted FROM a wire checkpoint/resume token, and lanes
            # preempt-checkpointed by the hot-swap straggler bound
            "drains": 0, "checkpoint_exports": 0, "migrations": 0,
            "migrated_resumes": 0, "swap_preemptions": 0,
        })
        # -- planner retune (autonomic serving planner) -------------------
        # retune() stages a validated _RetuneJob; the scheduler applies
        # it at a poll boundary. The census snapshot records which
        # executables warm() will compile — derived from the SAME boot
        # knobs warm() reads — so a later retune can be checked against
        # what actually exists instead of stalling the loop on a compile.
        self._retune_lock = threading.Lock()
        self._pending_retune: Optional[_RetuneJob] = None
        _census_fks: List[int] = []
        if self._fused_k > 0:
            _cfk = self._fused_k
            _clo = min(self._k, self._fused_k)
            while _cfk >= _clo:
                _census_fks.append(_cfk)
                _cfk //= 2
        self._retune_census: Dict[str, Any] = {
            # fused Ks warm() compiles: pow2s in [min(k, fused), fused]
            "fused_ks": tuple(sorted(_census_fks)),
            # chunk executables exist only for the boot chunk size
            "prefill_chunk": self.prefill_chunk,
            # warm()'s attention-bucket overhang covered this depth
            "pipeline_depth": self.pipeline_depth,
        }
        self.stats["planner_retunes"] = 0

        # -- device state ----------------------------------------------------
        # The persistent KV cache lives UNSTACKED: per-layer [S, KV, T, Dh]
        # arrays. A stacked [L, ...] cache threaded through the layer scan
        # as xs/ys makes XLA rewrite every layer's cache every step (cost
        # scales with total cache bytes); per-layer arrays carried through
        # the burst scan update in place — only the one-position write
        # touches HBM (see DecoderLM.decode_step_ragged_list).
        def cache_sharding_for(kv_heads: int):
            """Per-layer cache [S, KV, T, Dh]: KV heads over `model` (tp),
            cache length over `seq` (long context spans ICI). KV head
            counts that don't divide the model axis (GQA targets, thin
            drafts) replicate the KV dim instead of failing device_put.
            The layout itself lives on the model (DecoderLM.cache_sharding)
            so it stays next to param_sharding; this closure only binds
            the mesh + seq knob for the supervisor's crash-restart."""
            if mesh is None:
                return None
            return model.cache_sharding(
                mesh, kv_heads=kv_heads, shard_seq=shard_cache_seq
            )

        def unstack_cache(owner, sharding):
            """The cache the bursts carry, as the model lays it out
            (``cache_layers``): a dict of kinds, each a list over the
            layers that have that kind of one array whose first axis is
            the lane."""
            out = owner.cache_layers(self.slots, self.max_seq)
            if sharding is not None:
                out = jax.tree_util.tree_map(
                    lambda a: jax.device_put(a, sharding), out
                )
            return out

        cast_memo: Dict[int, Any] = {}

        def serving_cast(model_, p):
            """Store float params in the model's COMPUTE dtype. The forward
            casts every param to compute dtype at use, so pre-casting is
            numerically identical — but decode is HBM-bound and fp32
            storage would double both the footprint and the bytes every
            fused step reads (a 1.3B model: 5.4GB/step vs 2.7GB).

            Identity-memoised so leaves the early-exit draft SHARES with
            the target (embed/unembed/ln_f in generateserver's self-draft)
            stay one device array instead of casting into two copies
            (~262MB duplicated at the flagship config otherwise)."""
            dt = jnp.dtype(model_.compute_dtype)
            if dt == jnp.float32:
                return p

            def cast(a):
                if not (hasattr(a, "dtype") and a.dtype == jnp.float32):
                    return a
                key = id(a)
                if key not in cast_memo:
                    cast_memo[key] = a.astype(dt)
                return cast_memo[key]

            return jax.tree_util.tree_map(cast, p)

        params = serving_cast(model, params)
        if mesh is not None:
            # arm sharded-STORAGE / replicated-COMPUTE serving BEFORE
            # any executable traces: every entry gathers params/cache
            # to full replication (exact all-gather, no arithmetic) so
            # the math is the byte-identical 1-device program, and
            # every exit re-shards cache writes (models/llm.py)
            model.set_serving_mesh(mesh, shard_seq=shard_cache_seq)
            params = jax.device_put(params, model.param_sharding(mesh, params))
        self.params = params
        # the cast memo pins the boot params' cast leaves; a weight swap
        # clears it so the OLD buffer actually frees once the flip lands
        self._cast_memo = cast_memo
        # kept as closures for the supervisor: a crash-restart reallocates
        # the donated cache (and lane registers) through the same path the
        # constructor used, params untouched
        self._cache_sharding_for = cache_sharding_for
        self._unstack_cache = unstack_cache
        # staging/transfer slab layout [L, 1, KV, bucket, Dh]: every
        # host->device slab upload (remote admit, tier promote, copy-back
        # resume, fresh chunked-prefill slab) lands pre-sharded through
        # _upload_slab so the insert/splice executables never reshard
        self._slab_sharding = (
            model.slab_sharding(mesh) if mesh is not None else None
        )
        # per-shard split factors for the pressure ledger: how many ways
        # the persistent cache's bytes divide across chips (model axis,
        # plus seq when the cache length is sharded) — 1 when unmeshed
        # or when indivisible KV heads forced replication
        self._kv_model_shard = 1
        self._kv_seq_shard = 1
        if mesh is not None:
            mshape = dict(mesh.shape)
            tp = int(mshape.get("model", 1))
            kvh = int(model.cfg.n_kv_heads)
            if tp > 1 and kvh and kvh % tp == 0:
                self._kv_model_shard = tp
            sq = int(mshape.get("seq", 1))
            if shard_cache_seq and sq > 1:
                self._kv_seq_shard = sq
        self._kv_shard = self._kv_model_shard * self._kv_seq_shard
        self._draft_params = None
        self._draft_cache = None
        if self.speculate_tokens > 0:
            dp = serving_cast(draft_model, draft_params)
            if mesh is not None:
                draft_model.set_serving_mesh(mesh)
                dp = jax.device_put(dp, draft_model.param_sharding(mesh, dp))
            self._draft_params = dp
        self._alloc_device_state()
        # after the cache is there, so that the load's transient peak is
        # params and cache as it always was
        self._derive_burst_params()

        # -- executables -----------------------------------------------------

        def sample_next(keys, logits, temps):
            """The ONE per-lane greedy/seeded next-token sampler: split
            each lane's key, draw categorical at temps>0 else argmax.
            Every batched decode path (step-at-a-time burst, fused
            masked step, batched prefill firsts) calls THIS — the
            byte-identity contract across those paths rests on them
            sharing the sampling math, so any change lands everywhere
            by construction."""
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            split = jax.vmap(jax.random.split)(keys)  # [S, 2, key]
            keys, subs = split[:, 0], split[:, 1]
            sampled = jax.vmap(
                lambda k, lg, t: jax.random.categorical(k, lg / jnp.maximum(t, 1e-6))
            )(subs, logits, temps).astype(jnp.int32)
            return keys, jnp.where(temps > 0, sampled, greedy)

        # counters a model's decode step returns after its caches (an
        # int32 vector, model.step_counter_names): summed over a burst's
        # steps on the device, they ride home as the burst's last array
        # and _read_burst adds them into stats. A model with none (the
        # llama block) returns none and its bursts return what they
        # always did.
        self._step_counters = tuple(model.step_counter_names)
        # counters a model's prefill returns after its slab
        # (model.prefill_counter_names, prefill_counted): the insert that
        # follows a prefill adds them to a vector on the device
        # (_prefill_counts), which the next burst dispatched takes along
        # for _read_burst: no program and no wait of their own. A model
        # with none keeps the inserts it always had.
        self._prefill_counters = tuple(model.prefill_counter_names)
        for name in self._step_counters + self._prefill_counters:
            self.stats.setdefault(name, 0)
        # the step counters whose writes the decode kernel lands itself
        # (model.step_counters_in_kernel, as kv_rows_written_in_kernel
        # shadows kv_rows_written): _read_burst adds each into a stats key
        # of its own. A family that names none gets no key.
        self._counters_in_kernel = model.step_counters_in_kernel(
            self._cache, mesh)
        for name in self._counters_in_kernel:
            self.stats.setdefault(name, 0)
        run_prefill = (model.prefill_counted if self._prefill_counters
                       else model.prefill)

        def fused_step(params, cache, cur_tok, pos, active, temps, keys, attn_len):
            logits, cache, *counts = model.decode_step_cache(
                params, cache, cur_tok[:, None], pos, attn_len=attn_len,
                lens=jnp.where(active, pos + 1, 0),
            )
            keys, nxt = sample_next(keys, logits, temps)
            nxt = jnp.where(active, nxt, 0)
            pos = jnp.where(active, pos + 1, pos)
            return (nxt, pos, cache, keys, *counts)

        def at_lane(layer, rows, slot):
            """``rows`` [m, ...] written into ``layer`` [S, ...] from lane
            ``slot`` on, from 0 along every other axis: a prompt's keys
            fill [slot, :, :bucket], a lane's state its whole row."""
            return lax.dynamic_update_slice(
                layer, rows, (slot,) + (0,) * (layer.ndim - 1))

        def summed(counted):
            """What an insert is given after its registers: nothing, or
            the prefill counters so far and this prefill's -> nothing, or
            [their sum], which the insert returns last."""
            return [counted[0] + counted[1]] if counted else []

        def insert(cache, cache_one, slot, first_tok, first_pos, lane_key,
                   cur_tok, pos, keys, *counted):
            # cache_one is the prefill's stacked [L, 1, KV, Tb, Dh] slab;
            # each layer's slice lands in that layer's cache at `slot`
            new = {
                name: [
                    at_lane(layer, cache_one[name][l], slot)
                    for l, layer in enumerate(layers)
                ]
                for name, layers in cache.items()
            }
            cur_tok = cur_tok.at[slot].set(first_tok)
            pos = pos.at[slot].set(first_pos)
            keys = keys.at[slot].set(lane_key)
            return (new, cur_tok, pos, keys, *summed(counted))

        def prefill_one(params, prompt, last_index, seed, temp):
            # cache_one spans only the prompt bucket — decode writes extend
            # it in place, so inserting a full max_seq slab per admission
            # would just copy zeros over HBM
            logits, cache_one, *counts = run_prefill(
                params, prompt, prompt.shape[1], last_index=last_index
            )
            key = jax.random.PRNGKey(seed)
            key, sub = jax.random.split(key)
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            sampled = jax.random.categorical(
                sub, logits / jnp.maximum(temp, 1e-6), axis=-1
            ).astype(jnp.int32)
            first = jnp.where(temp > 0, sampled, greedy)
            return (first, cache_one, key, *counts)

        def prefill_many(params, prompts, last_index, seeds, temps):
            # m admissions share ONE forward: the prompt matmuls go from
            # [Tb, d] to [m*Tb, d] rows, so the MXU amortises what m
            # separate [1, Tb] prefills would each pay — at 20-30 admits/s
            # the per-admission forward is the throughput tier's largest
            # non-decode device cost. m is a small static bucket (2/4/8),
            # so at most 3 extra executables exist per prompt bucket.
            logits, slab, *counts = run_prefill(
                params, prompts, prompts.shape[1], last_index=last_index
            )
            keys = jax.vmap(jax.random.PRNGKey)(seeds)
            keys, firsts = sample_next(keys, logits, temps)
            return (firsts, slab, keys, *counts)

        def insert_many(cache, slab, slot_ix, firsts, first_pos, lane_keys,
                       cur_tok, pos, keys, *counted):
            # slab is the batched prefill's [L, m, KV, Tb, Dh] stack; each
            # row i lands in its lane slot_ix[i] (traced start indices —
            # one executable per (m, bucket), not per slot assignment)
            m = firsts.shape[0]
            new = {name: list(layers) for name, layers in cache.items()}
            for i in range(m):
                for name, layers in new.items():
                    for l in range(len(layers)):
                        layers[l] = at_lane(
                            layers[l], slab[name][l, i:i + 1], slot_ix[i])
            cur_tok = cur_tok.at[slot_ix].set(firsts)
            pos = pos.at[slot_ix].set(first_pos)
            keys = keys.at[slot_ix].set(lane_keys)
            return (new, cur_tok, pos, keys, *summed(counted))

        def fused_burst(params, cache, cur_tok, pos, active, temps, keys, k, attn_len):
            """k fused decode steps as one executable; returns [k, slots]
            tokens so the host syncs once per burst. ``attn_len`` (static)
            bounds the cache read — the scheduler picks a bucket >= every
            lane's end-of-burst position, so one executable exists per
            (k, bucket) pair and the read narrows to live prefix; None
            where each lane's own length bounds it (``_ragged_read``):
            one executable per k."""

            def body(carry, _):
                cache, cur_tok, pos, keys = carry
                nxt, pos, cache, keys, *counts = fused_step(
                    params, cache, cur_tok, pos, active, temps, keys, attn_len
                )
                return (cache, nxt, pos, keys), (nxt, *counts)

            (cache, cur_tok_out, pos, keys), (toks, *counts) = lax.scan(
                body, (cache, cur_tok, pos, keys), None, length=k
            )
            # row 0 = the tokens the burst STARTED from (deferred prefill
            # firsts ride home with the burst's one sync)
            toks = jnp.concatenate([cur_tok[None, :], toks], axis=0)
            return (toks, cur_tok_out, pos, cache, keys,
                    *(c.sum(axis=0) for c in counts))

        # -- stop-aware fused multi-step decode ------------------------------
        def fused_masked_step(params, cache, cur_tok, pos, alive, temps,
                              keys, attn_len, park):
            """One decode step under a per-lane ``alive`` mask: finished
            lanes' K/V writes park OUT OF BOUNDS at ``park`` (dropped, by
            JAX scatter semantics and by the decode kernel alike — the
            lane's cache freezes) and their
            token/position carry unchanged, so a lane that hit its stop
            keeps its stop token in ``cur_tok`` for the next burst's
            done0 check. For alive lanes the matmuls, mask bound, key
            split, and sampling are exactly ``fused_step``'s — the
            byte-identity contract vs the step-at-a-time path rests on
            that. Keys split for EVERY lane each step (as fused_step
            does): a frozen lane's key is dead state its next occupant's
            insert overwrites."""
            wpos = jnp.where(alive, pos, park)
            logits, cache, *counts = model.decode_step_cache(
                params, cache, cur_tok[:, None], pos, attn_len=attn_len,
                write_pos=wpos, lens=jnp.where(alive, pos + 1, 0),
            )
            keys, nxt = sample_next(keys, logits, temps)
            cur_tok = jnp.where(alive, nxt, cur_tok)
            pos = jnp.where(alive, pos + 1, pos)
            return (cur_tok, pos, cache, keys, *counts)

        def fused_stop_burst(params, cache, cur_tok, pos, active, temps,
                             keys, stops, budgets, k, attn_len):
            """k decode steps with ON-DEVICE stop-token detection and
            per-lane done masks: a lane freezes the moment it emits its
            stop token or exhausts its remaining budget — its writes park
            OOB, its registers stop advancing — while the other lanes
            keep decoding. One dispatch can therefore run far past the
            step-at-a-time burst length without decoding garbage past a
            stop. Returns ``([k+1, S]`` tokens with row 0 = the start
            tokens, per-lane emitted ``counts``, a ``done`` bitmap, and
            the updated lane registers) — the host syncs once per poll
            and reads nothing else. ``stops`` is -1 for lanes without an
            eos (tokens are >= 0, so it never matches); ``budgets`` is
            each lane's remaining allowance AFTER its current token
            (decremented on device, re-uploaded only on membership
            changes)."""
            # static: an index the write of every kind drops (past the
            # positions' axis; the family says where its kinds end)
            park = model.park_index(cache)

            def body(carry, _):
                cache, cur, p, kk, budget, done = carry
                alive = active & ~done
                cur, p, cache, kk, *counts = fused_masked_step(
                    params, cache, cur, p, alive, temps, kk, attn_len, park
                )
                budget = budget - alive.astype(jnp.int32)
                done = done | (alive & ((cur == stops) | (budget <= 0)))
                return (cache, cur, p, kk, budget, done), (
                    jnp.where(alive, cur, 0), alive, *counts,
                )

            # a lane can arrive already-done: its stop token was emitted
            # in an earlier burst the host has not read yet (pipeline
            # lag), or its budget was fully covered — either way it runs
            # zero steps here instead of overshoot-decoding
            done0 = ~active | (budgets <= 0) | (cur_tok == stops)
            (cache, cur, pos, keys, budgets, done), (toks, alive_rows, *extra) = (
                lax.scan(
                    body,
                    (cache, cur_tok, pos, keys, budgets, done0),
                    None, length=k,
                )
            )
            counts = alive_rows.astype(jnp.int32).sum(axis=0)
            toks = jnp.concatenate([cur_tok[None, :], toks], axis=0)
            return (toks, counts, done, cur, pos, cache, keys,
                    budgets, *(c.sum(axis=0) for c in extra))

        # -- generation by blocks ---------------------------------------------
        def fused_burst_blocks(params, cache, regs, pos, active, temps, keys,
                               k, attn_len, any_stoch):
            """k passes as one executable, for a family whose step is a pass
            over a block of W positions a lane (``model.block_tokens()``).
            ``pos`` [S] is each lane's block's first position; ``regs`` the
            lanes' block registers: ``tok`` / ``masked`` [S, W] the block's
            tokens and which of them are not filled in yet, ``n_pass`` the
            denoising passes it has had, ``skip`` how many of its first
            positions are a prompt's tail (emitted to nobody), ``budget``
            the positions the lane has still to commit (its first block's
            tail and what is left of ``max_new_tokens``), ``stops`` its
            eos or -1. Lanes are in different phases of one forward: a lane
            whose block has a masked position has ``model.block_unmask``
            fill some in; one whose block has none was on its COMMIT pass:
            its rows stay in the cache, its tokens are the pass's output
            row, and it moves on to the next block, all ``[MASK]``. A lane
            whose budget is spent (or that emitted its eos) reads and
            writes nothing more. Returns ``(toks [k, S, W], counts [k, S],
            bits [k, S], regs, pos, cache, keys, *counters)``, the first two
            in the speculative burst's shape: a pass that committed a block
            gives its ``counts`` tokens for the client (W less the first
            block's skip) first in its row of ``toks``, the row turned by
            the skip; any other pass gives 0 and the block as it found it.
            ``bits`` is the block's mask bits as the pass found them (bit i:
            position i), for whoever follows the passes (the comparison
            with the reference; the scheduler does not read it)."""
            W = self._block_w
            mask_id = jnp.int32(model.cfg.mask_token_id)
            col = jnp.arange(W, dtype=jnp.int32)[None, :]

            def body(carry, _):
                cache, regs, pos, keys = carry
                tok, masked = regs["tok"], regs["masked"]
                alive = active & (regs["budget"] > 0)
                logits, cache, counts = model.decode_block_cache(
                    params, cache, tok, pos, masked=masked, attn_len=attn_len,
                    lens=jnp.where(alive, pos + W, 0))
                commit = alive & ~masked.any(axis=-1)
                new_tok, new_masked, keys, unmasked = model.block_unmask(
                    logits, tok, masked, regs["n_pass"], alive, temps, keys,
                    any_stoch)
                sent = col >= regs["skip"][:, None]
                stopped = commit & (
                    (tok == regs["stops"][:, None]) & sent).any(axis=-1)
                count = jnp.where(commit, W - regs["skip"], 0)
                turned = jnp.take_along_axis(
                    tok, (col + (W - count)[:, None]) % W, axis=1)
                bits = (masked << col).sum(axis=-1)
                regs = {
                    "tok": jnp.where(commit[:, None], mask_id, new_tok),
                    "masked": commit[:, None] | new_masked,
                    "n_pass": jnp.where(
                        commit, 0, regs["n_pass"] + alive.astype(jnp.int32)),
                    "skip": jnp.where(commit, 0, regs["skip"]),
                    "budget": jnp.where(
                        stopped, 0,
                        regs["budget"] - jnp.where(commit, W, 0)),
                    "stops": regs["stops"],
                }
                pos = jnp.where(commit, pos + W, pos)
                return (cache, regs, pos, keys), (turned, count, bits,
                                                  counts + unmasked)

            (cache, regs, pos, keys), (toks, emitted, bits, counts) = lax.scan(
                body, (cache, regs, pos, keys), None, length=k)
            return (toks, emitted, bits, regs, pos, cache, keys,
                    counts.sum(axis=0))

        def block_insert(regs, slot_ix, tok, masked, skip, budget, stops):
            """The block registers of m lanes an insert has just filled:
            their first block (the prompt's tail, then ``[MASK]``s)."""
            new = {"tok": tok, "masked": masked, "skip": skip,
                   "budget": budget, "stops": stops,
                   "n_pass": jnp.zeros_like(skip)}
            return {name: regs[name].at[slot_ix].set(new[name])
                    for name in regs}

        # the burst's executable is ``jit_fused_burst`` whatever its step is:
        # what reads a trace by that name reads a pass as a step
        fused_burst_blocks.__name__ = fused_burst.__name__
        self._block_burst_fn = jax.jit(
            fused_burst_blocks, donate_argnums=(1, 2),
            static_argnums=(7, 8, 9))
        self._block_insert_fn = jax.jit(block_insert, donate_argnums=(0,))

        # -- prefix-cache executables ---------------------------------------
        def prefix_prefill(params, slab, suffix, start_pos, last_index, seed, temp):
            # suffix-only prefill over the cached prefix slab: the model's
            # prefix-splice op + the same first-token sampling prefill_one
            # does. One executable per (slab bucket, suffix bucket) pair —
            # start_pos/last_index are traced
            logits, suffix_slab = model.prefill_with_prefix(
                params, slab, suffix, start_pos, last_index=last_index
            )
            key = jax.random.PRNGKey(seed)
            key, sub = jax.random.split(key)
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            sampled = jax.random.categorical(
                sub, logits / jnp.maximum(temp, 1e-6), axis=-1
            ).astype(jnp.int32)
            first = jnp.where(temp > 0, sampled, greedy)
            return first, suffix_slab, key

        def insert_prefix(cache, slab, suffix_slab, slot, start_pos,
                          first_tok, first_pos, lane_key, cur_tok, pos, keys):
            # splice the donor prefix slab at the lane's origin, then the
            # freshly prefilled suffix at start_pos (both traced starts —
            # donor residue past the real prompt end is decode-overwritten
            # before it can become readable, the standard residue invariant)
            new = {}
            for name in ("k", "v"):
                layers = []
                for l, layer in enumerate(cache[name]):
                    layer = lax.dynamic_update_slice(
                        layer, slab[name][l], (slot, 0, 0, 0)
                    )
                    layer = lax.dynamic_update_slice(
                        layer, suffix_slab[name][l], (slot, 0, start_pos, 0)
                    )
                    layers.append(layer)
                new[name] = layers
            cur_tok = cur_tok.at[slot].set(first_tok)
            pos = pos.at[slot].set(first_pos)
            keys = keys.at[slot].set(lane_key)
            return new, cur_tok, pos, keys

        def extract_prefix(cache, slot, bucket):
            # copy one lane's prompt-prefix K/V out as a stacked cache_one
            # slab [L, 1, KV, bucket, Dh] — the publishable unit. A copy,
            # not a view: it must outlive the donated cache's churn
            return {
                name: jnp.stack(
                    [
                        lax.dynamic_slice(
                            layer, (slot, 0, 0, 0),
                            (1, layer.shape[1], bucket, layer.shape[3]),
                        )
                        for layer in cache[name]
                    ]
                )
                for name in ("k", "v")
            }

        # -- preemption recompute-resume: teacher-forced decode replay -------
        def replay_burst(params, cache, lane_ix, toks, act, start_pos,
                         attn_len):
            """Rebuild the K/V of already-emitted tokens for ONE gathered
            lane by replaying them through the SAME fused decode step
            that wrote them originally. A prefill over prompt+generated
            would recompute those positions with different matmul shapes
            — visibly different K/V at bf16, enough to flip a near-tied
            argmax downstream — so byte-identical resume REQUIRES the
            decode op. ``toks``/``act`` are a fixed-length (k) forced
            chunk (pads inactive: their writes land at the unadvanced
            position the lane's next real step overwrites before any
            read). One executable per (k, attn_len) pair; the gathered
            [1]-lane execution is bitwise equal to the full-batch row
            (tests/test_pressure.py holds resume to byte identity)."""
            g_ks = [layer[lane_ix, :, :attn_len, :] for layer in cache["k"]]
            g_vs = [layer[lane_ix, :, :attn_len, :] for layer in cache["v"]]
            pos0 = jnp.full((1,), start_pos, jnp.int32)

            def body(carry, x):
                ks, vs, pos = carry
                tok, a = x
                _logits, ks, vs, *_ = model.decode_step_ragged_list(
                    params, ks, vs, tok[None, None], pos, attn_len=None
                )
                pos = jnp.where(a, pos + 1, pos)
                return (ks, vs, pos), None

            (g_ks, g_vs, _pos), _ = lax.scan(
                body, (g_ks, g_vs, pos0), (toks, act)
            )
            new = {
                "k": [
                    layer.at[lane_ix, :, :attn_len, :].set(g)
                    for layer, g in zip(cache["k"], g_ks)
                ],
                "v": [
                    layer.at[lane_ix, :, :attn_len, :].set(g)
                    for layer, g in zip(cache["v"], g_vs)
                ],
            }
            return new

        self._replay_fn = jax.jit(
            replay_burst, donate_argnums=(1,), static_argnums=(6,)
        )

        # -- chunked prefill (interleaved with decode polls) -----------------
        def chunk_prefill_step(params, slab, tokens, start_pos, last_index,
                               seed, temp, attn_len, is_last):
            """One prompt chunk into a STAGING slab (cache_one layout,
            outside the decode cache — in-flight bursts can never touch a
            half-built prompt, and the decode executables stay bit-exact
            vs the whole-prompt path). The FINAL chunk (static
            ``is_last``) additionally samples the first token exactly
            like prefill_one — same PRNG derivation, so chunked and
            unchunked admits emit identical streams; the finished slab
            then goes through the ORDINARY lane insert."""
            logits, slab = model.prefill_chunk(
                params, slab, tokens, start_pos, attn_len,
                last_index=last_index, want_logits=is_last,
            )
            if not is_last:
                zero = jnp.zeros((), jnp.int32)
                return slab, zero, jax.random.PRNGKey(0)
            key = jax.random.PRNGKey(seed)
            key, sub = jax.random.split(key)
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            sampled = jax.random.categorical(
                sub, logits / jnp.maximum(temp, 1e-6), axis=-1
            ).astype(jnp.int32)
            first = jnp.where(temp > 0, sampled, greedy)
            return slab, first[0], key

        def splice_slab(slab, donor):
            # prefix-cache hit under chunking: the donor's K/V land at the
            # head of the staging slab, chunking resumes at the match
            # point (donor bucket <= prompt bucket per _prefix_match)
            return {
                name: lax.dynamic_update_slice(
                    slab[name], donor[name], (0, 0, 0, 0, 0)
                )
                for name in ("k", "v")
            }

        self._burst_fn = jax.jit(
            fused_burst, donate_argnums=(1,), static_argnums=(7, 8)
        )
        self._fused_burst_fn = jax.jit(
            fused_stop_burst, donate_argnums=(1,), static_argnums=(9, 10)
        )
        self._chunk_fn = jax.jit(
            chunk_prefill_step, donate_argnums=(1,), static_argnums=(7, 8)
        )
        self._splice_fn = jax.jit(splice_slab, donate_argnums=(0,))
        # bytes per cached position, all layers (K and V, or whatever rows
        # the family caches: the model sizes them): the unit of the modeled
        # burst read and of the pressure ledger
        self._kv_key_bytes = model.cache_position_bytes(self._cache)
        # positions a lane holds -> bytes of the cache they occupy (the
        # same product for a family of one row a position; one whose kinds
        # differ in length prices them itself)
        self._lane_bytes = model.lane_cache_bytes(self._cache)
        # the arrays a decode step writes one row each of
        position_layers = model.position_layers(self._cache)
        self._position_layers = len(position_layers)
        # the ragged decode read's granule (stats["kv_positions_read"]):
        # the kernel's own rule, by the bytes a block of the cache holds
        from ..ops.decode_attention import walk_block

        self._kv_read_block = walk_block(
            model.cfg.n_kv_heads, model.cfg.head_dim,
            position_layers[0].dtype, self.max_seq)
        # the windows of the model's layers that have one and hold a row a
        # position (the kinds come from the model; the llama block has none)
        self._kv_windows = tuple(model.row_cache_windows())
        # whether the decode step's read takes each lane's own length on
        # the platform the bursts are lowered for (the cache's devices).
        # Where it does, the bucket bounds nothing in _burst_fn and
        # _fused_burst_fn: they are warmed and dispatched with
        # attn_len=None, one executable per K and none per bucket. The
        # host's arithmetic (need, the counters, the variant names) keeps
        # the bucket either way.
        self._ragged_read = model.burst_reads_ragged(self._cache, mesh)
        # the draft cache's per-token K/V price (speculation only): the
        # pressure ledger charges live lanes for BOTH caches while the
        # draft is resident, and stops when rung 2 frees it
        self._draft_kv_key_bytes = (
            2 * sum(
                layer.dtype.itemsize * layer.shape[1] * layer.shape[3]
                for layer in self._draft_cache["k"]
            )
            if self.speculate_tokens > 0
            else 0
        )
        self._param_bytes = sum(
            leaf.nbytes
            for leaf in jax.tree_util.tree_leaves(self.params)
            if hasattr(leaf, "nbytes")
        )

        # per-chip param footprint under the mesh layout: each leaf's
        # shard shape is pure sharding metadata (no device sync), so this
        # is exact even for the mixed partitioned/replicated TP layout.
        # Equal to _param_bytes when unmeshed/fully replicated.
        def _leaf_shard_bytes(leaf):
            sh = getattr(leaf, "sharding", None)
            if sh is None or not hasattr(sh, "shard_shape"):
                return leaf.nbytes
            n = leaf.dtype.itemsize
            for d in sh.shard_shape(leaf.shape):
                n *= d
            return n

        self._param_shard_bytes = sum(
            _leaf_shard_bytes(leaf)
            for leaf in jax.tree_util.tree_leaves(self.params)
            if hasattr(leaf, "nbytes")
        )
        self._insert_fn = jax.jit(insert, donate_argnums=(0,))
        self._prefill_fn = jax.jit(prefill_one)
        self._prefill_many_fn = jax.jit(prefill_many)
        self._insert_many_fn = jax.jit(insert_many, donate_argnums=(0,))
        self._prefix_prefill_fn = jax.jit(prefix_prefill)
        self._insert_prefix_fn = jax.jit(insert_prefix, donate_argnums=(0,))
        self._extract_fn = jax.jit(extract_prefix, static_argnums=(2,))

        # -- speculative executables (exact; see spec_round docstring) ------
        self._spec_burst_fn = None
        self._draft_prefill_fn = None
        self._draft_insert_fn = None
        if self.speculate_tokens > 0:
            gamma = self.speculate_tokens
            draft = draft_model

            def _lane_split(keys):
                split = jax.vmap(jax.random.split)(keys)
                return split[:, 0], split[:, 1]

            def spec_round(
                params, dparams, ks, vs, dks, dvs, cur_tok, pos, active,
                temps, keys, attn_len, any_stoch,
            ):
                """One speculation round: the draft proposes gamma tokens,
                ONE target chunk forward verifies, the accepted prefix + a
                correction/bonus token are emitted.

                Exactness per lane (Leviathan et al. speculative sampling):
                  * temp == 0 — draft argmax, accept while it equals the
                    target argmax: output IS the target's greedy decode.
                  * temp > 0 — draft SAMPLES from q, accept d_i with prob
                    min(1, p(d_i)/q(d_i)); on first rejection resample from
                    norm(max(p-q, 0)); on full acceptance sample the bonus
                    from p. The emitted distribution provably equals
                    sampling from the target — for ANY draft.
                Returns per-lane emitted tokens [S, gamma+1] and counts [S].
                """
                safe_t = jnp.maximum(temps, 1e-6)[:, None]  # [S,1]
                stoch = (temps > 0)
                dtok, dpos = cur_tok, pos
                drafts, q_rows = [], []
                for _ in range(gamma):
                    dlogits, dks, dvs = draft.decode_step_ragged_list(
                        dparams, dks, dvs, dtok[:, None], dpos, attn_len=attn_len
                    )
                    greedy = jnp.argmax(dlogits, -1).astype(jnp.int32)
                    if any_stoch:
                        keys, subs = _lane_split(keys)
                        q_rows.append(jax.nn.softmax(dlogits / safe_t, axis=-1))
                        sampled = jax.vmap(jax.random.categorical)(
                            subs, dlogits / safe_t
                        ).astype(jnp.int32)
                        dtok = jnp.where(stoch, sampled, greedy)
                    else:
                        dtok = greedy
                    dtok = jnp.where(active, dtok, 0)
                    drafts.append(dtok)
                    dpos = jnp.where(active, dpos + 1, dpos)
                drafts_arr = jnp.stack(drafts, axis=1)  # [S, gamma]
                window = jnp.concatenate([cur_tok[:, None], drafts_arr], axis=1)
                tlogits, ks, vs = model.decode_chunk_ragged_list(
                    params, ks, vs, window, pos, attn_len=attn_len
                )
                t_greedy = jnp.argmax(tlogits, -1).astype(jnp.int32)  # [S,g+1]
                acc_greedy = drafts_arr == t_greedy[:, :gamma]

                if any_stoch:
                    q_full = jnp.stack(q_rows, axis=1)  # [S, gamma, V]
                    p = jax.nn.softmax(tlogits / safe_t[..., None], axis=-1)
                    # acceptance: p_{i-1}(d_i)/q_{i-1}(d_i) vs lane uniforms
                    p_sel = jnp.take_along_axis(
                        p[:, :gamma, :], drafts_arr[..., None], axis=2
                    )[..., 0]  # [S, gamma]
                    q_sel = jnp.take_along_axis(
                        q_full, drafts_arr[..., None], axis=2
                    )[..., 0]
                    keys, subs = _lane_split(keys)
                    u = jax.vmap(lambda kk: jax.random.uniform(kk, (gamma,)))(subs)
                    acc_stoch = u < jnp.minimum(
                        p_sel / jnp.maximum(q_sel, 1e-20), 1.0
                    )
                    acc = jnp.where(stoch[:, None], acc_stoch, acc_greedy)
                else:
                    acc = acc_greedy
                accepted = jnp.cumprod(acc.astype(jnp.int32), axis=1).sum(axis=1)

                # correction/bonus token at window index `accepted`
                corr_greedy = jnp.take_along_axis(
                    t_greedy, accepted[:, None], axis=1
                )[:, 0]
                if any_stoch:
                    p_at_a = jnp.take_along_axis(
                        p, accepted[:, None, None], axis=1
                    )[:, 0]  # [S, V]
                    a_clamp = jnp.minimum(accepted, gamma - 1)
                    q_at_a = jnp.take_along_axis(
                        q_full, a_clamp[:, None, None], axis=1
                    )[:, 0]
                    resid = jnp.maximum(p_at_a - q_at_a, 0.0)
                    resid_sum = resid.sum(-1, keepdims=True)
                    # numerically-empty residual (p <= q everywhere) -> p
                    resid = jnp.where(resid_sum > 1e-12, resid, p_at_a)
                    dist = jnp.where((accepted == gamma)[:, None], p_at_a, resid)
                    keys, subs = _lane_split(keys)
                    corr_sample = jax.vmap(jax.random.categorical)(
                        subs, jnp.log(dist + 1e-30)
                    ).astype(jnp.int32)
                    correction = jnp.where(stoch, corr_sample, corr_greedy)
                else:
                    correction = corr_greedy

                cols = jnp.arange(gamma + 1, dtype=jnp.int32)[None, :]
                drafts_padded = jnp.concatenate(
                    [drafts_arr, jnp.zeros((self.slots, 1), jnp.int32)], axis=1
                )
                out = jnp.where(cols < accepted[:, None], drafts_padded, 0)
                out = jnp.where(cols == accepted[:, None], correction[:, None], out)
                count = jnp.where(active, accepted + 1, 0)
                out = jnp.where(active[:, None], out, 0)
                cur_tok = jnp.where(active, correction, cur_tok)
                pos = jnp.where(active, pos + accepted + 1, pos)
                return ks, vs, dks, dvs, cur_tok, pos, keys, out, count

            def spec_burst(
                params, dparams, caches, cur_tok, pos, active, temps, keys,
                k, attn_len, any_stoch,
            ):
                """k speculation rounds as one executable. ``any_stoch``
                (static) compiles the greedy-only variant without the
                q/p softmaxes + sampling when every lane is greedy. Returns
                (start_tok [S], toks [k, S, gamma+1], counts [k, S], ...)."""

                def body(carry, _):
                    ks, vs, dks, dvs, cur_tok, pos, keys = carry
                    ks, vs, dks, dvs, cur_tok, pos, keys, out, count = spec_round(
                        params, dparams, ks, vs, dks, dvs, cur_tok, pos,
                        active, temps, keys, attn_len, any_stoch,
                    )
                    return (ks, vs, dks, dvs, cur_tok, pos, keys), (out, count)

                start_tok = cur_tok
                (ks, vs, dks, dvs, cur_tok, pos, keys), (toks, counts) = lax.scan(
                    body,
                    (caches["k"], caches["v"], caches["dk"], caches["dv"],
                     cur_tok, pos, keys),
                    None,
                    length=k,
                )
                new_caches = {"k": ks, "v": vs, "dk": dks, "dv": dvs}
                return start_tok, toks, counts, cur_tok, pos, keys, new_caches

            self._spec_burst_fn = jax.jit(
                spec_burst, donate_argnums=(2,), static_argnums=(8, 9, 10)
            )

            def draft_prefill(dparams, prompt, last_index):
                # the draft only needs its K/V prefix; its own next-token
                # guess is irrelevant (the first emitted token comes from
                # the TARGET prefill, and round drafting restarts from it)
                _logits, cache_one = draft.prefill(
                    dparams, prompt, prompt.shape[1], last_index=last_index
                )
                return cache_one

            def draft_insert(dcache, cache_one, slot):
                return {
                    name: [
                        lax.dynamic_update_slice(
                            layer, cache_one[src][l], (slot, 0, 0, 0)
                        )
                        for l, layer in enumerate(dcache[name])
                    ]
                    for name, src in (("k", "k"), ("v", "v"))
                }

            self._draft_prefill_fn = jax.jit(draft_prefill)
            self._draft_insert_fn = jax.jit(draft_insert, donate_argnums=(0,))

        # the executables that run the family's decode step take the params
        # in the layout that step consumes (_derive_burst_params)
        for name in ("_burst_fn", "_fused_burst_fn", "_block_burst_fn",
                     "_spec_burst_fn", "_replay_fn"):
            if getattr(self, name) is not None:
                setattr(self, name, _BurstExecutable(self, getattr(self, name)))

    # -- public api ----------------------------------------------------------

    def observed_rate(self) -> Optional[float]:
        """Finished requests per second over the recent completion window
        (None until two completions exist — never shed blind)."""
        times = list(self._finish_times)
        if len(times) < 2:
            return None
        span = times[-1] - times[0]
        if span <= 0:
            return None
        return (len(times) - 1) / span

    def slo_summary(self) -> Optional[Dict[str, Any]]:
        """Percentile summary (ms) of the recent completed-request SLO
        reservoir: queue wait, TTFT, TPOT. None until a request completes."""
        samples = list(self.slo_recent)
        if not samples:
            return None

        def pct(vals: List[float]) -> Dict[str, float]:
            vals = sorted(vals)
            n = len(vals)
            return {
                "p50_ms": round(vals[n // 2] * 1e3, 3),
                "p99_ms": round(vals[min(n - 1, int(n * 0.99))] * 1e3, 3),
                "mean_ms": round(sum(vals) / n * 1e3, 3),
            }

        # single-token completions carry tpot=None (no inter-token
        # interval exists) — excluded here exactly as the TIMER export
        # excludes them, so /prometheus and this summary agree
        tpots = [s[2] for s in samples if s[2] is not None]
        return {
            "samples": len(samples),
            "queue_wait_ms": pct([s[0] for s in samples]),
            "ttft_ms": pct([s[1] for s in samples]),
            "tpot_ms": pct(tpots) if tpots else None,
        }

    def capture_counters(self) -> Dict[str, Dict[str, float]]:
        """Running totals for ``tracing.start_capture`` / ``stop_capture``
        to difference: ``loop`` is the scheduler thread's time by phase
        (the phase in progress included, so the phases sum to
        ``wall_s``) with the poll and burst counts; ``counters`` is
        every numeric entry of ``stats``."""
        loop = self._clock.read()
        for k in ("polls", "bursts", "burst_read_lag_s_sum"):
            loop[k] = self.stats[k]
        return {"loop": loop, "counters": dict(self.stats)}

    def capture_started(self) -> None:
        """A profiler records from here on: the loop's phase in progress
        gets a span in it (``PhaseClock.reenter``)."""
        self._clock.reenter()

    TIMELINE_FIELDS = (
        "id", "prompt_len", "bucket", "tokens", "cache_hit_tokens",
        "submit_t", "admit_t", "decode_start_t", "first_dispatch_t",
        "first_tok_t", "done_t", "insert_t", "admit_poll",
    )

    def capture_requests(self) -> List[Dict[str, Any]]:
        """The timeline ring as dicts, oldest first: ``TIMELINE_FIELDS``
        and the front's stamps as they stand now (absolute monotonic
        seconds, 0.0 = not reached)."""
        out = []
        for entry in list(self.timeline_recent):
            row = dict(zip(self.TIMELINE_FIELDS, entry))
            row.update(dataclasses.asdict(entry[-1]))
            out.append(row)
        return out

    def capture_polls(self) -> List[Dict[str, Any]]:
        """The flight recorder's rows as they stand, oldest first, every
        type (``t`` absolute monotonic seconds); ``[]`` with the ring off."""
        return self.flight.snapshot() if self.flight is not None else []

    def capture_compiles(self) -> Dict[str, Any]:
        """The process's compile log as it stands
        (``tracing.CompileLog.report``: absolute, by stage and by name)."""
        return compile_report()

    @caller_thread
    def _shed_check(
        self, deadline_s: Optional[float], remote: bool = False
    ) -> None:
        """Admit-queue shedding, BEFORE the request costs any device work:
        the HBM-pressure admission watermark, an explicit queue cap, and
        the deadline-aware rule (expected queue wait = depth / observed
        completion rate > remaining budget).

        The pressure rung is the ladder's last resort — it only fires
        while the ledger is latched over the high watermark. ``remote``
        selects the typed refusal: a local submit sheds with the PR 2
        :class:`~..resilience.ShedError` (429 + Retry-After); a remote
        admit refuses with :class:`~.pressure.PressureRefused` (503 +
        Retry-After) so a decode pool under pressure pushes back to its
        prefill peers BEFORE a slab crosses the wire, instead of
        half-admitting it."""
        pc = self._pressure
        if pc.budget_bytes > 0 and pc.active:
            after = pc.retry_after_s()
            if remote:
                from .pressure import PressureRefused

                self.stats["pressure_refused"] += 1
                self._note_shed("pressure", self._queue.qsize(), None)
                raise PressureRefused(
                    f"decode pool over its HBM ledger high watermark "
                    f"({pc.used} of {pc.budget_bytes} bytes); refusing "
                    "remote admits until reclaim reaches the low "
                    "watermark",
                    retry_after_s=after,
                )
            from ..resilience import ShedError

            self.stats["shed"] += 1
            self.stats["pressure_sheds"] += 1
            self._note_shed("pressure", self._queue.qsize(),
                            self.observed_rate())
            raise ShedError(
                f"HBM ledger over its high watermark ({pc.used} of "
                f"{pc.budget_bytes} bytes) — admissions shed until the "
                "reclaim ladder reaches the low watermark",
                retry_after_s=after,
            )
        depth = self._queue.qsize()
        if self.admit_queue_limit and depth >= self.admit_queue_limit:
            from ..resilience import ShedError

            rate = self.observed_rate()
            self.stats["shed"] += 1
            self._note_shed("queue_full", depth, rate)
            raise ShedError(
                f"admit queue full ({depth} >= {self.admit_queue_limit})",
                retry_after_s=(depth / rate) if rate else 1.0,
            )
        if deadline_s is None or depth == 0:
            return
        rate = self.observed_rate()
        if rate is None:
            return
        est_wait = depth / rate
        if est_wait > deadline_s:
            from ..resilience import ShedError

            self.stats["shed"] += 1
            self._note_shed("deadline", depth, rate)
            raise ShedError(
                f"deadline {deadline_s * 1000:.0f}ms below estimated queue "
                f"wait {est_wait * 1000:.0f}ms ({depth} queued at "
                f"{rate:.2f} req/s) — shed before work",
                retry_after_s=est_wait,
            )

    @caller_thread
    def _note_shed(self, reason: str, depth: int, rate: Optional[float]) -> None:
        """Flight-recorder + trace breadcrumbs for a shed decision (runs on
        the SUBMITTING thread, where the request's span is still active)."""
        if self.flight is not None and self.flight.enabled:
            self.flight.record({
                "type": "shed", "reason": reason, "queue": depth,
                "rate_per_s": round(rate, 3) if rate else None,
            })
        from ..tracing import get_tracer

        tracer = get_tracer()
        if tracer.enabled:
            parent = tracer.active_span()
            if parent is not None and parent.trace_id != "0":
                # monotonic-anchored timestamp: a raw time.time() here
                # could disorder the shed breadcrumb against the sibling
                # spans' anchored clocks under an NTP step
                tracer.record_span(
                    "gen.shed", parent.trace_id, parent.span_id,
                    wall_us(), 0,
                    tags={"reason": reason, "queue_depth": depth},
                )

    def _dead_error(self) -> BatcherDead:
        """The typed refusal every entrypoint raises once the scheduler
        is gone — BatcherDead carries retry_after_s so the engine answers
        503 + Retry-After instead of an opaque 500."""
        if self.health == "closed":
            return BatcherDead("batcher is closed", retry_after_s=1.0)
        if self.health == "dead":
            return BatcherDead(
                "continuous batcher died and exhausted its crash-loop "
                "budget; this member stays unready until the control "
                "plane replaces it",
                retry_after_s=5.0,
            )
        return BatcherDead(
            "continuous batcher died; see server log", retry_after_s=5.0
        )

    def _check_alive(self) -> None:
        # the health latch is checked alongside _stop: _crash_recover
        # writes health="dead" a few instructions before it sets _stop,
        # and an entrypoint landing in that window must still refuse
        # (drain() in particular must never overwrite the dead latch)
        if self._stop.is_set() or self.health in ("dead", "closed"):
            raise self._dead_error()
        if self.health == "draining":
            # a draining member refuses new work typed (503 +
            # Retry-After) so the gateway/engine routes the retry at a
            # peer; in-flight work is being checkpointed and handed
            # over, not dropped
            raise BatcherDead(
                "batcher is draining for migration; retry another member",
                retry_after_s=1.0,
            )

    def _check_budget(self, prompt_len: int, max_new_tokens) -> None:
        """Reject ``prompt_len + max_new_tokens > max_seq`` at the
        boundary with a typed :class:`BudgetExceeded` (413-class).
        Historically the overrun was silently clamped to the remaining
        headroom — a client asking for 512 tokens got 40 with no signal
        — and anything slipping past surfaced deep in the scheduler as
        an opaque shape error."""
        m = int(max_new_tokens)
        if prompt_len + m > self.max_seq:
            raise BudgetExceeded(
                f"prompt of {prompt_len} + max_new_tokens {m} exceeds "
                f"max_seq {self.max_seq}; raise max_seq or lower the "
                "generation budget"
            )

    @caller_thread
    def submit(
        self,
        tokens: Sequence[int],
        max_new_tokens: int = 32,
        temperature: float = 0.0,
        eos_id: Optional[int] = None,
        seed: int = 0,
        on_tokens=None,
        deadline_s: Optional[float] = None,
        tenant: Optional[str] = None,
        slo: str = "standard",
    ) -> Future:
        self._check_alive()
        if not len(tokens):
            raise ValueError("empty prompt")
        if len(tokens) >= self.max_seq:
            raise PromptTooLong(
                f"prompt of {len(tokens)} exceeds max_seq {self.max_seq}"
            )
        self._check_budget(len(tokens), max_new_tokens)
        self._shed_check(deadline_s)
        req = GenRequest(
            tokens=list(map(int, tokens)),
            max_new_tokens=int(max_new_tokens),
            temperature=float(temperature),
            eos_id=eos_id,
            seed=int(seed),
            on_tokens=on_tokens,
            tenant=tenant,
            slo=str(slo or "standard"),
        )
        req.submit_t = time.monotonic()
        if deadline_s is not None:
            req.deadline_t = req.submit_t + float(deadline_s)
        req.submit_wall_us = wall_us(req.submit_t)
        # capture the submitting thread's sampled trace context so the
        # scheduler thread can parent this request's timeline spans under
        # the serving span (the engine's graph-hop span, propagated into
        # this thread by InProcessClient's context copy). The unsampled
        # sentinel carries trace_id "0" and is skipped — a dropped
        # request must not grow retroactive span fragments.
        from ..tracing import get_tracer

        tracer = get_tracer()
        if tracer.enabled:
            parent = tracer.active_span()
            if parent is not None and parent.trace_id != "0":
                req.trace = (parent.trace_id, parent.span_id)
        # callers read per-request admit metadata (cache_hit_tokens) off
        # the future after it resolves
        req.future.gen_request = req
        self._queue.put(req)
        if self._stop.is_set():
            # the loop died between the entry check and the put: its drain
            # already ran, so nothing will ever pop this request — fail the
            # stranded queue here instead of leaving the future unresolved
            self._drain_queue(self._dead_error())
            return req.future
        self.start()
        return req.future

    @caller_thread
    def generate(self, tokens, **kw) -> List[int]:
        """Blocking convenience: submit and wait for the generated ids."""
        return self.submit(tokens, **kw).result()

    # -- disaggregated serving (prefill/decode pools, KV-slab handoff) -----

    @property
    def _slab_token_bytes(self) -> int:
        """K+V bytes one prompt position occupies across every layer —
        the per-token unit the transfer-dedup accounting is priced in."""
        return self._kv_key_bytes

    @caller_thread
    def export_prefill(
        self,
        tokens: Sequence[int],
        max_new_tokens: int = 32,
        temperature: float = 0.0,
        eos_id: Optional[int] = None,
        seed: int = 0,
        covered_len: int = 0,
    ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        """PREFILL-side half of disaggregation: run the prompt forward
        and return ``(meta, slab)`` — the host-side ``cache_one`` K/V
        stack plus everything a decode pool needs to splice it as a
        remote lane insert (first sampled token, post-split RNG lane
        key, weight version, sampling params).

        Reuses PR 3's staging-slab path: with ``prefill_chunk`` set and
        a multi-chunk bucket the slab is built chunk by chunk exactly
        like an interleaved admission (no decode lanes are touched —
        this method never requires the scheduler loop, which a
        prefill-role server does not run); otherwise the ordinary
        bucketed whole-prompt prefill produces it in one forward. The
        first token is sampled on THIS side with the same PRNG
        derivation an admission uses, so disaggregated greedy output is
        byte-identical to unified serving.

        ``covered_len`` > 0 (the decode side's radix prefix cache
        already holds that many leading tokens) slices the transfer down
        to the suffix columns — the K/V is still computed here (a full
        prefill is the only way to produce correct suffix K/V without
        the donor slab), but only ``bucket - covered_len`` positions
        cross the wire and ``kv_transfer_bytes_saved`` records the
        dedup."""
        import jax.numpy as jnp

        from ..tracing import device_trace
        from .disagg import prompt_hash

        self._check_alive()
        self._check_family_serves(migration=True)
        n = len(tokens)
        if not n:
            raise ValueError("empty prompt")
        if n >= self.max_seq:
            raise PromptTooLong(
                f"prompt of {n} exceeds max_seq {self.max_seq}"
            )
        self._check_budget(n, max_new_tokens)
        tokens = [int(t) for t in tokens]
        bucket = self._bucket(n)
        covered = max(0, min(int(covered_len), n - 1))
        C = self.prefill_chunk
        chunks = held = 0
        if C and bucket > C:
            # the staging path: one _chunk_fn slice at a time, same
            # offsets/slide-back as _advance_chunks, final slice samples
            slab = self._new_slab(bucket)
            first = key = None
            start = 0
            while True:
                is_last = start + C >= n
                s = max(0, min(start, bucket - C)) if is_last else start
                end = min(s + C, n)
                buf = np.zeros((1, C), np.int32)
                buf[0, : end - s] = tokens[s:end]
                attn_len = min(bucket, self._attn_need(s + C))
                with self._prof.measure(
                    "chunk_prefill", variant=f"b{bucket}",
                    bytes_read=self._param_bytes + C * self._kv_key_bytes,
                    tokens=C,
                ) as _m, device_trace("gen.prefill_chunk"):
                    slab, first, key = self._chunk_fn(
                        self.params, slab, jnp.asarray(buf),
                        jnp.int32(s), jnp.int32(n - 1 - s),
                        jnp.int32(seed), jnp.float32(temperature),
                        attn_len, is_last,
                    )
                    _m.sync(slab)
                chunks += 1
                held += end - s
                if is_last:
                    break
                start = end
            cache_one, first_tok = slab, first
        else:
            prompt = np.zeros((1, bucket), np.int32)
            prompt[0, :n] = tokens
            with self._prof.measure(
                "prefill", variant=f"p{bucket}",
                bytes_read=self._param_bytes + self._lane_bytes(bucket),
                tokens=bucket,
            ) as _m, device_trace("gen.prefill"):
                first, cache_one, key, *_ = self._prefill_fn(
                    self.params, jnp.asarray(prompt),
                    jnp.asarray([n - 1], jnp.int32),
                    jnp.int32(seed), jnp.float32(temperature),
                )
                _m.sync(cache_one)
            first_tok = first[0]
        # host pull IS the export (the slab must cross a transport);
        # suffix-only when the decode side already holds the prefix
        k = np.asarray(cache_one["k"])
        v = np.asarray(cache_one["v"])
        if self._kv_tier is not None:
            # the FULL prompt slab is already host-side here — publishing
            # it into the tier costs one SKV1 encode and zero device
            # work, and makes this member's KV port answer peer
            # prefix-lookups for the prompt (cluster-wide sharing)
            if self._kv_tier.put_prefix(tokens, {"k": k, "v": v},
                                        self.weight_version):
                if self.flight is not None and self.flight.enabled:
                    self.flight.record({
                        "type": "kv_demote", "kind": "prefix",
                        "source": "export",
                        "tokens": n,
                        "phash": prompt_hash(tokens)[:8],
                        "bytes": int(k.nbytes) + int(v.nbytes),
                    })
        if covered:
            k = k[:, :, :, covered:, :]
            v = v[:, :, :, covered:, :]
        meta = {
            "tokens": tokens,
            "prompt_hash": prompt_hash(tokens),
            "n_tokens": n,
            "bucket": bucket,
            "covered_len": covered,
            "layout": "cache_one",
            "first_token": int(np.asarray(first_tok)),
            "rng_key": np.asarray(key).astype(np.uint32).tolist(),
            "weight_version": self.weight_version,
            "max_new_tokens": int(max_new_tokens),
            "temperature": float(temperature),
            "eos_id": eos_id,
            "seed": int(seed),
        }
        nbytes = int(k.nbytes) + int(v.nbytes)
        with self._export_lock:
            self.stats["kv_exports"] += 1
            self.stats["kv_export_bytes"] += nbytes
            self.stats["prefill_steps"] += max(1, chunks)
            self.stats["prefill_tokens"] += chunks * C if chunks else bucket
            self.stats["prefill_prompt_tokens"] += held if chunks else n
            self.stats["prefill_chunks"] += chunks
            # kv_transfer_bytes_saved is counted on the DECODE side only
            # (the pool whose radix cache made the dedup decision): the
            # exported series is direction-less, so counting the same
            # covered tokens here too would double the cluster-wide sum
        if self.flight is not None and self.flight.enabled:
            self.flight.record({
                "type": "kv_export",
                "tokens": n,
                "bucket": bucket,
                "covered_len": covered,
                "bytes": nbytes,
                "chunks": chunks,
                "weight_version": self.weight_version,
            })
        return meta, {"k": k, "v": v}

    @caller_thread
    def remote_covered_len(self, tokens: Sequence[int]) -> int:
        """DECODE-side consult before requesting a remote prefill: the
        longest locally cached prefix usable as the transfer-dedup base
        (0 = ask for the full slab). Applies the same usability caps as
        a local prefix-cache admit, so a nonzero answer is one
        admit_remote can actually splice."""
        if self._prefix_index is None:
            return 0
        tokens = [int(t) for t in tokens]
        n = len(tokens)
        m, slab = self._prefix_index.match(tokens)
        m = min(m, n - 1)
        if slab is None or m < self.prefix_cache_min_tokens:
            return 0
        if slab["k"].shape[3] > self._bucket(n):
            return 0  # donor wider than the prompt bucket: not a win
        return m

    @caller_thread
    def admit_remote(
        self,
        slab: Dict[str, Any],
        meta: Dict[str, Any],
        on_tokens=None,
        deadline_s: Optional[float] = None,
    ) -> Future:
        """DECODE-side half of disaggregation: validate a shipped slab
        message, upload it, and queue it as a remote lane insert —
        spliced by the scheduler thread through the SAME insert
        executables an ordinary admission uses, so decode after a remote
        admit is byte-identical to unified serving.

        Rejections are typed and happen BEFORE any lane state exists:
        weight-version mismatch (a hot-swap landed between prefill and
        admit) raises :class:`~.disagg.WeightVersionMismatch`; a
        shape/dtype/layout mismatch raises :class:`~.disagg.DisaggError`;
        a suffix-only slab whose local donor prefix was evicted raises
        :class:`~.disagg.PrefixGone` at insert time (the caller retries
        with ``covered_len=0``). Returns the request Future, exactly
        like :meth:`submit`."""
        import jax.numpy as jnp

        from .disagg import DisaggError, PrefixGone, WeightVersionMismatch
        from .disagg import prompt_hash as _phash

        self._check_alive()
        self._check_family_serves(migration=True)
        if self.speculate_tokens > 0:
            raise DisaggError(
                "remote admits are not supported with speculative "
                "decoding (the draft cache has no prefix for the lane)"
            )
        tokens = [int(t) for t in meta.get("tokens") or []]
        if not tokens:
            raise DisaggError("slab meta carries no prompt tokens")
        n = len(tokens)
        if n >= self.max_seq:
            raise DisaggError(
                f"remote prompt of {n} exceeds max_seq {self.max_seq}"
            )
        self._check_budget(n, meta.get("max_new_tokens", 32))
        if meta.get("prompt_hash") and meta["prompt_hash"] != _phash(tokens):
            raise DisaggError("slab prompt hash mismatch — corrupt meta")
        if meta.get("layout", "cache_one") != "cache_one":
            raise DisaggError(
                f"unsupported slab layout {meta.get('layout')!r}"
            )
        if meta.get("weight_version") != self.weight_version:
            raise WeightVersionMismatch(
                f"slab prefilled under weight_version "
                f"{meta.get('weight_version')!r} but this decode pool "
                f"serves {self.weight_version!r}"
            )
        covered = max(0, int(meta.get("covered_len", 0)))
        if covered and self._prefix_index is None:
            raise PrefixGone(
                "suffix-only slab but this decode pool runs no prefix "
                "cache — re-request with covered_len=0"
            )
        self._shed_check(deadline_s, remote=True)
        cfg = self.model.cfg
        k = np.asarray(slab["k"])
        v = np.asarray(slab["v"])
        bucket = self._bucket(n)
        want = (cfg.n_layers, 1, cfg.n_kv_heads, bucket - covered,
                cfg.head_dim)
        if tuple(k.shape) != want or tuple(v.shape) != want:
            raise DisaggError(
                f"slab shape {tuple(k.shape)} does not match the serving "
                f"model's {want} (prompt {n} -> bucket {bucket}, "
                f"covered {covered})"
            )
        dt = jnp.dtype(self.model.compute_dtype)
        if str(k.dtype) != str(dt):
            raise DisaggError(
                f"slab dtype {k.dtype} vs serving compute dtype {dt} — "
                "prefill and decode pools must share a dtype"
            )
        if meta.get("first_token") is None:
            raise DisaggError("slab meta carries no first_token")
        key_arr = np.asarray(meta.get("rng_key", [0, 0]), np.uint32)
        req = GenRequest(
            tokens=tokens,
            max_new_tokens=int(meta.get("max_new_tokens", 32)),
            temperature=float(meta.get("temperature", 0.0)),
            eos_id=meta.get("eos_id"),
            seed=int(meta.get("seed", 0)),
            on_tokens=on_tokens,
        )
        req.submit_t = time.monotonic()
        if deadline_s is not None:
            req.deadline_t = req.submit_t + float(deadline_s)
        req.submit_wall_us = wall_us(req.submit_t)
        req.cache_hit_tokens = covered
        from ..tracing import get_tracer

        tracer = get_tracer()
        if tracer.enabled:
            parent = tracer.active_span()
            if parent is not None and parent.trace_id != "0":
                req.trace = (parent.trace_id, parent.span_id)
        # device upload happens HERE, on the caller thread: the H2D copy
        # overlaps whatever burst the scheduler is running (pre-sharded
        # under a mesh — wire bytes stay layout-independent, the shards
        # form on upload)
        req.remote = {
            "slab": self._upload_slab({"k": k, "v": v}),
            "first": int(meta["first_token"]),
            "key": jnp.asarray(key_arr),
            "covered": covered,
            "nbytes": int(k.nbytes) + int(v.nbytes),
            "version": meta.get("weight_version"),
        }
        req.future.gen_request = req
        self._queue.put(req)
        if self._stop.is_set():
            self._drain_queue(self._dead_error())
            return req.future
        self.start()
        return req.future

    # -- live-lane migration (graceful drain + wire-checkpoint resume) -----

    @caller_thread
    def drain(self, timeout_s: float = 30.0) -> List[GenRequest]:
        """Graceful drain: checkpoint every live lane at the next poll
        boundary (the same preemption machinery PR 9 built — emitted
        tokens + post-split RNG lane key + sampling params, NOT the
        K/V), stop admissions (``health = "draining"``, new submits
        refuse typed 503), and return EVERY request this batcher still
        owes an answer for: checkpointed lanes (``req.resume`` set),
        mid-chunked-prefill admissions (requeued whole), the preemption
        resume queue, and queued-not-admitted requests. The caller
        (``GenerateServer.drain_to``) hands them to a peer via the SGC1
        codec; their futures stay pending until the peer answers —
        rolling maintenance drops zero requests.

        A dead/closed member has nothing drainable (its queued futures
        were already failed typed by the supervisor's drain), so the
        entry check's :class:`BatcherDead` propagates. A drain that
        outruns ``timeout_s`` is CANCELLED, not stranded: the scheduler
        observes the cancellation, keeps (or re-queues) the work, and
        restores ``health = "serving"`` so the member resumes normal
        service instead of latching draining forever."""
        self._check_alive()
        with self._drain_lock:
            if self._pending_drain is not None:
                raise RuntimeError("a drain is already in progress")
            # re-check under the lock: the supervisor writes the dead
            # latch without it, and overwriting "dead" with "draining"
            # would misreport a terminally dead member as mid-drain
            if self._stop.is_set() or self.health in ("dead", "closed"):
                raise self._dead_error()
            # refuse new admissions NOW (caller threads see it before
            # the scheduler reaches the poll boundary) — a request
            # admitted after this line would miss the checkpoint sweep
            self.health = "draining"
            job = _DrainJob()
            self._pending_drain = job
        self.start()
        from concurrent.futures import TimeoutError as _FuturesTimeout

        try:
            return job.future.result(timeout=timeout_s)
        except _FuturesTimeout:
            if not job.future.cancel():
                # the scheduler is resolving the drain RIGHT NOW (the
                # future is running/done): take the result after a
                # short grace instead of abandoning checkpointed work
                return job.future.result(timeout=5.0)
            # cancelled before the scheduler started it: the next poll
            # clears the latch and resumes admissions (_do_drain's
            # set_running_or_notify_cancel branch)
            raise RuntimeError(
                f"drain did not complete within {timeout_s}s; cancelled "
                "— admissions resume on the next poll"
            )

    def _check_family_serves(self, **asked: bool) -> None:
        """A request that needs what the model's family has no path for
        (``DecoderFamily.serving_refuses``) is refused typed where it comes
        in, as the constructor refuses a setting."""
        self.model.check_serves(**asked)

    @caller_thread
    def submit_checkpoint(self, ck: Dict[str, Any], on_tokens=None) -> Future:
        """Admit a wire checkpoint (an SGC1 dict — a drained peer's
        lane, or a client resume token) and continue the generation
        exactly where it stopped: the scheduler resumes it through
        :meth:`_admit_resume` (prompt K/V recompute + teacher-forced
        replay of the emitted tokens), so greedy AND seeded-sampling
        output is byte-identical to an uninterrupted run and crediting
        continues after the checkpoint (already-delivered stream spans
        are never re-sent).

        Typed refusals, all BEFORE any lane state exists: a checkpoint
        from another ``weight_version`` raises
        :class:`~.disagg.WeightVersionMismatch` (its emitted prefix is
        not reproducible under these weights); over-long prompts and
        budget overruns raise the same 413-class errors ``submit``
        does. The checkpoint's cumulative wait anchor re-bases
        ``submit_t`` so queue-wait telemetry spans both members."""
        from .disagg import WeightVersionMismatch

        self._check_alive()
        self._check_family_serves(preemption=True)
        wv = ck.get("weight_version")
        if wv is not None and wv != self.weight_version:
            raise WeightVersionMismatch(
                f"checkpoint was taken under weight_version {wv!r} but "
                f"this member serves {self.weight_version!r} — its "
                "emitted prefix is not reproducible here"
            )
        tokens = [int(t) for t in ck.get("prompt") or []]
        if not tokens:
            raise ValueError("checkpoint carries no prompt tokens")
        if len(tokens) >= self.max_seq:
            raise PromptTooLong(
                f"checkpoint prompt of {len(tokens)} exceeds max_seq "
                f"{self.max_seq}"
            )
        mnt = int(ck.get("max_new_tokens", 32))
        self._check_budget(len(tokens), mnt)
        emitted = [int(t) for t in ck.get("emitted") or []]
        if len(emitted) > mnt:
            raise ValueError(
                f"checkpoint emitted {len(emitted)} tokens past its "
                f"max_new_tokens {mnt}"
            )
        req = GenRequest(
            tokens=tokens,
            max_new_tokens=mnt,
            temperature=float(ck.get("temperature", 0.0)),
            eos_id=ck.get("eos_id"),
            seed=int(ck.get("seed", 0)),
            on_tokens=on_tokens,
        )
        now = time.monotonic()
        # cumulative queue-wait anchor: the time the request already
        # waited on the source member rides the checkpoint, so the
        # queue-wait histogram sees source wait + local wait instead of
        # restarting the clock at migration
        wait_s = max(0.0, float(ck.get("wait_s") or 0.0))
        req.submit_t = now - wait_s
        req.submit_wall_us = (
            int(ck.get("submit_wall_us") or 0) or wall_us(req.submit_t)
        )
        dl = ck.get("deadline_s")
        if dl is not None:
            req.deadline_t = now + max(0.0, float(dl))
        if emitted and (
            len(emitted) >= mnt
            or (req.eos_id is not None and emitted[-1] == req.eos_id)
        ):
            # the checkpoint is already COMPLETE (a final-state resume
            # token): nothing is left to decode, so answer host-side
            # without occupying a lane — re-admitting it would append
            # one overshoot token before the done check could fire
            req.future.gen_request = req
            req.future.set_result(tokens + emitted)
            with self._export_lock:
                self.stats["migrated_resumes"] += 1
            return req.future
        if emitted:
            key = ck.get("rng_key")
            if key is None:
                # crash tokens ship keyless (reading the lane key per
                # span would cost a host sync per span): re-derive it
                # from the deterministic split chain
                from .migration import derive_lane_key

                key = derive_lane_key(req.seed, len(emitted))
            req.resume = {
                "emitted": emitted, "key": [int(k) for k in key],
            }
        with self._export_lock:
            self.stats["migrated_resumes"] += 1
        if self.flight is not None and self.flight.enabled:
            self.flight.record({
                "type": "migrated_resume",
                "tokens": len(tokens),
                "emitted": len(emitted),
                "weight_version": self.weight_version,
            })
        req.future.gen_request = req
        self._queue.put(req)
        if self._stop.is_set():
            self._drain_queue(self._dead_error())
            return req.future
        self.start()
        return req.future

    @caller_thread
    def request_weight_swap(self, params, version=None) -> Future:
        """Stage a live weight hot-swap; returns a Future resolving to
        the new weight version once the scheduler flips.

        Thread-safe, callable under traffic. The new params are cast to
        the serving compute dtype, validated leaf-for-leaf against the
        served set (same tree / shapes / dtypes — the jitted executables
        are specialized on them, so an incompatible checkpoint is
        REJECTED here instead of retracing mid-traffic), device-put
        (sharded when meshed) — i.e. double-buffered next to the live
        weights, the upload overlapping serving. The scheduler then:

        * stops admitting new requests (queued submits wait),
        * lets every in-flight lane — decode, chunked prefill, pipelined
          burst — finish on the OLD version,
        * flips the param pointer at the next poll boundary, bumps
          ``weight_version``, purges the prefix cache (its slabs are
          keyed by weight version — stale K/V can never splice into a
          new-weights prefill), records a flight-recorder
          ``weight_swap`` event, and resumes admissions on the new
          weights.
        """
        import jax
        import jax.numpy as jnp

        self._check_alive()
        if self.speculate_tokens > 0:
            raise RuntimeError(
                "weight hot-swap is not supported with speculative decoding "
                "(the draft shares or derives from the served params)"
            )
        dt = jnp.dtype(self.model.compute_dtype)
        if dt != jnp.float32:
            with self._prof.measure(
                "swap_cast", variant=str(dt),
                bytes_read=self._param_bytes,
            ) as _m:
                params = jax.tree_util.tree_map(
                    lambda a: a.astype(dt)
                    if hasattr(a, "dtype") and a.dtype == jnp.float32
                    else a,
                    params,
                )
                _m.sync(params)
        ok, why = self.model.params_swappable(self.params, params)
        if not ok:
            raise ValueError(f"weight hot-swap rejected: {why}")
        if self.mesh is not None:
            params = jax.device_put(
                params, self.model.param_sharding(self.mesh, params)
            )
        with self._swap_lock:
            if self._pending_swap is not None:
                raise RuntimeError("a weight swap is already pending")
            if version is None:
                self._swap_seq += 1
                if self._swap_seq == self.weight_version:
                    self._swap_seq += 1
                version = self._swap_seq
            elif version == self.weight_version:
                # a flip that keeps the version number would leave the
                # version-keyed prefix cache holding OLD-weights K/V that
                # still matches — the exact splice the keying exists to
                # prevent
                raise ValueError(
                    f"weight swap version {version!r} is already the "
                    "served version; pick a new version id"
                )
            job = _SwapJob(
                params=params,
                version=version,
                nbytes=sum(
                    leaf.nbytes
                    for leaf in jax.tree_util.tree_leaves(params)
                    if hasattr(leaf, "nbytes")
                ),
                staged_t=time.monotonic(),
            )
            self._pending_swap = job
        # the loop must be alive to execute the swap, traffic or not
        self.start()
        return job.future

    def swap_pending(self) -> bool:
        """Whether a staged weight swap is awaiting its drain — callers
        about to pay a full checkpoint load (GenerateServer.hot_swap) can
        fail fast instead of discovering the conflict afterwards. The
        authoritative check stays inside request_weight_swap."""
        return self._pending_swap is not None

    @caller_thread
    def cancel_weight_swap(self) -> bool:
        """Abort a staged-but-not-yet-executed weight swap, resuming
        admissions on the next poll. The escape hatch for a drain that
        cannot converge (e.g. a stalled streaming consumer holding a
        lane open with no deadline): without it the staged job would
        hold every admission until close(). Returns True when a pending
        swap was cancelled; False when none was pending (including a
        swap that already flipped)."""
        with self._swap_lock:
            swap, self._pending_swap = self._pending_swap, None
        if swap is None:
            return False
        if not swap.future.done():
            swap.future.set_exception(
                RuntimeError("weight swap cancelled before the flip")
            )
        return True

    # knobs retune() accepts; everything else (slots, steps_per_poll,
    # speculate_tokens, cache geometry) would invalidate compiled
    # executables or reallocate device state and is refused typed
    RETUNABLE_KNOBS = (
        "fused_steps_per_dispatch", "prefill_chunk", "pipeline_depth",
        "admit_queue_limit", "pressure_high", "pressure_low",
    )

    def retune_census(self) -> Dict[str, Any]:
        """The boot-time compile census a retune is validated against:
        which fused Ks warm() compiled, the one chunk size with
        precompiled executables, and the warmed pipeline depth. The
        planner reads this to prune its search space to configs this
        member can actually flip to."""
        return dict(self._retune_census)

    def serving_config(self) -> Dict[str, Any]:
        """The CURRENT values of the profile-grid config axes
        (planning/artifact.py CONFIG_KEYS) — unlike the boot census
        these move with every applied retune. The planner diffs the
        cost model's pick against this to decide whether a retune is
        even needed."""
        return {
            "slots": int(self.slots),
            "prefill_chunk": int(self.prefill_chunk or 0),
            "fused_steps_per_dispatch": int(
                self.fused_steps_per_dispatch or 0
            ),
            "kv_tier_bytes": int(
                getattr(self._kv_tier, "budget_bytes", 0) or 0
            ),
        }

    @caller_thread
    def retune(self, origin: str = "planner", **knobs) -> Future:
        """Stage a live retune of scheduler knobs; returns a Future
        resolving to ``{knob: [old, new]}`` for the knobs that actually
        changed once the scheduler applies the job at a poll boundary.

        Thread-safe, callable under traffic — the autonomic planner's
        ONE actuation path into the hot loop. Same staging discipline as
        swap/drain: nothing changes on the caller thread; the scheduler
        applies every knob together at the top of a poll, where no burst
        is mid-dispatch (the loop snapshots ``_fused_k`` once per poll)
        and — for a ``prefill_chunk`` change — only once in-flight
        chunked prefills have drained. Byte identity is preserved by
        construction: every retunable knob already carries an
        on-vs-off/byte-identity contract (fused decode, chunked
        prefill, pressure, admission caps), so a mid-run retune
        produces the same tokens as booting with the new values.

        Validation is synchronous and typed (:class:`RetuneError`):
        a value outside the boot compile census — a fused K warm() never
        compiled, a chunk size with no precompiled chunk executables, a
        pipeline deepening past the warmed attention overhang — is
        refused HERE, before staging, so the scheduler can never be
        asked to compile mid-traffic.
        """
        self._check_alive()
        if not knobs:
            raise RetuneError("retune called with no knobs")
        unknown = set(knobs) - set(self.RETUNABLE_KNOBS)
        if unknown:
            raise RetuneError(
                f"unknown/unretunable knob(s) {sorted(unknown)}; "
                f"retunable: {list(self.RETUNABLE_KNOBS)}"
            )
        census = self._retune_census
        target: Dict[str, Any] = {}

        def _int(name, lo=0):
            try:
                v = int(knobs[name])
            except (TypeError, ValueError):
                raise RetuneError(
                    f"{name} must be an int, got {knobs[name]!r}"
                ) from None
            if v < lo:
                raise RetuneError(f"{name} must be >= {lo}, got {v}")
            return v

        if "fused_steps_per_dispatch" in knobs:
            raw = _int("fused_steps_per_dispatch")
            fk = raw
            while fk & (fk - 1):
                fk &= fk - 1
            if fk > 0 and self._spec_burst_fn is not None:
                raise RetuneError(
                    "fused decode cannot be enabled under speculative "
                    "decoding (no fused executables exist in spec mode)"
                )
            if fk > 0 and fk not in census["fused_ks"]:
                raise RetuneError(
                    f"fused_steps_per_dispatch={raw} (pow2 floor {fk}) "
                    f"is outside the boot compile census "
                    f"{list(census['fused_ks'])}; only warmed Ks (or 0) "
                    "can be retuned to"
                )
            target["fused_steps_per_dispatch"] = (raw, fk)
        if "prefill_chunk" in knobs:
            pc = _int("prefill_chunk")
            if pc not in (0, census["prefill_chunk"]):
                raise RetuneError(
                    f"prefill_chunk={pc} has no precompiled chunk "
                    f"executables; census allows 0 or "
                    f"{census['prefill_chunk']}"
                )
            target["prefill_chunk"] = pc
        if "pipeline_depth" in knobs:
            pd = _int("pipeline_depth", lo=1)
            if pd > census["pipeline_depth"]:
                raise RetuneError(
                    f"pipeline_depth={pd} exceeds the warmed depth "
                    f"{census['pipeline_depth']} (warm()'s attention "
                    "overhang only covered the boot depth)"
                )
            target["pipeline_depth"] = pd
        if "admit_queue_limit" in knobs:
            target["admit_queue_limit"] = _int("admit_queue_limit")
        if "pressure_high" in knobs or "pressure_low" in knobs:
            try:
                high = float(knobs.get(
                    "pressure_high", self._pressure.high_frac
                ))
                low = float(knobs.get(
                    "pressure_low", self._pressure.low_frac
                ))
            except (TypeError, ValueError):
                raise RetuneError(
                    "pressure watermarks must be floats"
                ) from None
            if not (0.0 < high <= 1.0):
                raise RetuneError(
                    f"pressure_high {high} not in (0, 1]"
                )
            if not (0.0 < low <= high):
                raise RetuneError(
                    f"pressure_low {low} must be in (0, high={high}]"
                )
            target["pressure_high"] = high
            target["pressure_low"] = low
        with self._retune_lock:
            if self._pending_retune is not None:
                raise RetuneError("a retune is already pending")
            job = _RetuneJob(knobs=target, origin=str(origin))
            self._pending_retune = job
        # the loop must be alive to apply the job, traffic or not
        self.start()
        return job.future

    @scheduler_only
    def _do_retune(self, job: _RetuneJob) -> None:
        """Apply a staged retune (scheduler thread, poll boundary). Runs
        under ``_retune_lock`` for the same cancel-vs-apply atomicity as
        :meth:`_do_swap`. A job that changes ``prefill_chunk`` DEFERS
        while chunked prefills are in flight — their staged slabs and
        offsets were planned at the old chunk size."""
        with self._retune_lock:
            if self._pending_retune is not job:
                return
            new_pc = job.knobs.get("prefill_chunk")
            if (
                new_pc is not None
                and new_pc != self.prefill_chunk
                and self._chunked
            ):
                job.waited_polls += 1
                return
            changed: Dict[str, List[Any]] = {}

            def _apply(name, old, new, setter):
                if old != new:
                    changed[name] = [old, new]
                setter(new)

            for name, val in job.knobs.items():
                if name == "fused_steps_per_dispatch":
                    raw, fk = val
                    if self._fused_k != fk:
                        changed[name] = [self._fused_k, fk]
                        # device stop/budget registers re-upload before
                        # the next fused dispatch
                        self._fused_sync = False
                    self.fused_steps_per_dispatch = raw
                    self._fused_k = fk
                elif name == "prefill_chunk":
                    _apply(
                        name, self.prefill_chunk, val,
                        lambda v: setattr(self, "prefill_chunk", v),
                    )
                elif name == "pipeline_depth":
                    _apply(
                        name, self.pipeline_depth, val,
                        lambda v: setattr(self, "pipeline_depth", v),
                    )
                elif name == "admit_queue_limit":
                    _apply(
                        name, self.admit_queue_limit, val,
                        lambda v: setattr(self, "admit_queue_limit", v),
                    )
                elif name == "pressure_high":
                    _apply(
                        name, self._pressure.high_frac, val,
                        lambda v: setattr(self._pressure, "high_frac", v),
                    )
                elif name == "pressure_low":
                    _apply(
                        name, self._pressure.low_frac, val,
                        lambda v: setattr(self._pressure, "low_frac", v),
                    )
            self.stats["planner_retunes"] += 1
            if self.flight is not None and self.flight.enabled:
                self.flight.record({
                    "type": "planner_retune",
                    "origin": job.origin,
                    "changed": changed,
                    "waited_polls": job.waited_polls,
                })
            self._pending_retune = None
        if changed:
            logger.info(
                "planner retune (%s): %s (deferred %d polls)",
                job.origin,
                ", ".join(
                    f"{k} {o!r}->{n!r}" for k, (o, n) in changed.items()
                ),
                job.waited_polls,
            )
        if not job.future.done():
            job.future.set_result(changed)

    @caller_thread
    def cancel_retune(self) -> bool:
        """Abort a staged-but-not-yet-applied retune (e.g. a planner
        tick superseded by a newer decision before the poll boundary).
        Returns True when a pending job was cancelled."""
        with self._retune_lock:
            job, self._pending_retune = self._pending_retune, None
        if job is None:
            return False
        if not job.future.done():
            job.future.set_exception(
                RetuneError("retune cancelled before the poll boundary")
            )
        return True

    @scheduler_only
    def _do_swap(self, swap: _SwapJob) -> None:
        """Execute a drained swap (scheduler thread, poll boundary).

        The whole flip runs under ``_swap_lock`` so ``cancel_weight_swap``
        either lands BEFORE (pops the job — we see the mismatch and skip)
        or AFTER (pending is already None — cancel returns False); it can
        never fail the future of a swap that actually flipped. The flip
        is host-side pointer work, so the hold is short.
        """
        with self._swap_lock:
            if self._pending_swap is not swap:
                return  # cancelled between the drain check and here
            old_v = self.weight_version
            self.params = swap.params
            self._derive_burst_params()
            self.weight_version = swap.version
            # drop the boot-cast memo so the old buffer's last pin dies
            # with the pointer flip (double-buffering ends here)
            self._cast_memo.clear()
            if self._prefix_index is not None:
                purged = self._prefix_index.set_version(swap.version)
                self.stats["prefix_evicted"] += purged
                self.stats["prefix_cache_bytes"] = self._prefix_index.total_bytes
            if self._kv_tier is not None:
                # the tier's entries are OLD-weights K/V too: purge on
                # the same version key (a swap straggler's checkpoint
                # then replays on the new weights instead of splicing
                # stale cache — correct by construction)
                self._kv_tier.set_version(swap.version)
            self.stats["weight_swaps"] += 1
            if self.flight is not None and self.flight.enabled:
                self.flight.record({
                    "type": "weight_swap",
                    "old_version": old_v,
                    "new_version": swap.version,
                    "drained_lanes": swap.drain_lanes or 0,
                    "waited_polls": swap.waited_polls,
                })
            self._pending_swap = None
        logger.info(
            "weight swap %r -> %r (drained %d lanes over %d polls)",
            old_v, swap.version, swap.drain_lanes or 0, swap.waited_polls,
        )
        if not swap.future.done():
            swap.future.set_result(swap.version)

    @scheduler_only
    def _swap_preempt_stragglers(self, pending) -> None:
        """Hot-swap straggler bound: the drain has run past
        ``swap_drain_ms``, so preempt-checkpoint every in-flight lane
        (and chunked admission) instead of holding the flip hostage to
        one long generation. Policy ``"resume"`` requeues them — they
        resume AFTER the flip, on the NEW weights (an explicit identity
        trade the knob documents); ``"fail"`` refuses them typed
        (WeightVersionMismatch, 409-class) so the client re-submits
        under the new version knowingly."""
        self._drain_pending(pending)
        victims: List[GenRequest] = []
        for slot in sorted(self._chunked):
            victims.append(self._chunked.pop(slot).request)
        for slot in sorted(self._active):
            _s, req = self._checkpoint_lane(slot)
            victims.append(req)
        if not victims:
            return
        self.stats["swap_preemptions"] += len(victims)
        if self.flight is not None and self.flight.enabled:
            self.flight.record({
                "type": "swap_straggler_preempt",
                "lanes": len(victims),
                "policy": self.swap_resume_policy,
                "swap_drain_ms": self.swap_drain_ms,
            })
        logger.warning(
            "weight swap straggler bound hit after %dms: %d in-flight "
            "lane(s) preempt-checkpointed (policy=%s)",
            self.swap_drain_ms, len(victims), self.swap_resume_policy,
        )
        if self.swap_resume_policy == "fail":
            from .disagg import WeightVersionMismatch

            for req in victims:
                if req.resume is None:
                    # zero tokens emitted (chunked admission / fresh
                    # lane): there is no old-weights prefix to betray —
                    # a plain re-admit under the new weights reproduces
                    # its stream from the seed alone, so failing it
                    # would be a needless 409
                    self._resume_queue.append(req)
                elif not req.future.done():
                    req.future.set_exception(WeightVersionMismatch(
                        "generation preempted by a weight swap after "
                        f"swap_drain_ms={self.swap_drain_ms} and "
                        "swap_resume_policy=fail forbids resuming its "
                        "emitted prefix under the new weights; re-submit"
                    ))
        else:
            for req in victims:
                self._resume_queue.append(req)

    @scheduler_only
    def _do_drain(self, job: _DrainJob, pending) -> None:
        """Execute a staged graceful drain at this poll boundary:
        flush the pipeline (checkpoints must see exact host state),
        checkpoint every live lane, collect chunked admissions whole,
        then sweep the resume queue and the admit queue. Admissions are
        already refused (``health == "draining"`` flipped on the caller
        thread), so the collected list is complete. A job whose caller
        timed out and cancelled is aborted BEFORE any lane is touched —
        the latch clears and the member resumes serving with its work
        intact."""
        if not job.future.set_running_or_notify_cancel():
            # the drain() caller gave up (timeout): nothing was
            # checkpointed yet, so just un-latch and keep serving
            with self._drain_lock:
                if self._pending_drain is job:
                    self._pending_drain = None
            self.health = "serving"
            logger.warning(
                "graceful drain cancelled by its caller before the poll "
                "boundary; admissions resumed"
            )
            return
        # re-assert the latch: a supervised restart between staging and
        # this poll rewrote health back to "serving" — the member must
        # refuse new work from here on, or post-drain admissions would
        # be stranded when the caller tears it down
        self.health = "draining"
        try:
            self._drain_pending(pending)
            drained: List[GenRequest] = []
            n_lanes = n_ck = 0
            for slot in sorted(self._chunked):
                drained.append(self._chunked.pop(slot).request)
            n_chunked = len(drained)
            for slot in sorted(self._active):
                _s, req = self._checkpoint_lane(slot)
                n_lanes += 1
                if req.resume is not None:
                    n_ck += 1
                drained.append(req)
            while self._resume_queue:
                drained.append(self._resume_queue.popleft())
            while True:
                try:
                    drained.append(self._queue.get_nowait())
                except queue.Empty:
                    break
            drained = [r for r in drained if not r.future.cancelled()]
            self.stats["drains"] += 1
            if self.flight is not None and self.flight.enabled:
                self.flight.record({
                    "type": "drain",
                    "lanes": n_lanes,
                    "checkpoints": n_ck,
                    "chunked": n_chunked,
                    "handed": len(drained),
                })
            logger.info(
                "graceful drain: %d lane(s) checkpointed (%d with "
                "emitted tokens), %d chunked, %d total requests handed "
                "to migration", n_lanes, n_ck, n_chunked, len(drained),
            )
            with self._drain_lock:
                self._pending_drain = None
            if not job.future.done():
                job.future.set_result(drained)
        except Exception as e:  # noqa: BLE001 - the drain caller must wake
            with self._drain_lock:
                self._pending_drain = None
            if not job.future.done():
                job.future.set_exception(e)

    def _fail_pending_drain(self, err: Exception) -> None:
        with self._drain_lock:
            job, self._pending_drain = self._pending_drain, None
        if job is not None and not job.future.done():
            job.future.set_exception(err)

    @scheduler_only
    def _derive_burst_params(self) -> None:
        """``self.params`` as the burst executables take them
        (``model.burst_params``: the weights whose layout the family's
        compiled burst consumes, transposed once here and not at the top of
        every burst; ``self.params`` itself where the family states none).
        The tree held before goes first, so a swap holds one derived copy
        at a time. ``stats["burst_params_relaid_bytes"]``: the bytes of the
        leaves held beside the stored ones."""
        import jax

        self._burst_params = None
        self._burst_params = self.model.burst_params(self.params)
        stored = {id(leaf) for leaf in jax.tree_util.tree_leaves(self.params)}
        self.stats["burst_params_relaid_bytes"] = sum(
            leaf.nbytes
            for leaf in jax.tree_util.tree_leaves(self._burst_params)
            if id(leaf) not in stored)

    @scheduler_only
    def _alloc_device_state(self) -> None:
        """(Re)allocate everything the scheduler loop mutates on device:
        the unstacked per-layer KV cache (and the draft's), the per-lane
        token/position registers, and the per-lane PRNG streams (each
        request's sampling is seeded by ITS seed, folded in at admit, so
        results are reproducible no matter which other requests share the
        decode batch). Called by the constructor and by the supervisor
        after a loop death — the donating burst executables consumed the
        old buffers, so a restarted loop must never touch them."""
        import jax
        import jax.numpy as jnp

        self._cache = self._unstack_cache(
            self.model, self._cache_sharding_for(self.model.cfg.n_kv_heads)
        )
        if self.speculate_tokens > 0:
            self._draft_cache = self._unstack_cache(
                self.draft_model,
                self._cache_sharding_for(self.draft_model.cfg.n_kv_heads),
            )
        self._cur_tok = jnp.zeros((self.slots,), jnp.int32)
        self._pos = jnp.zeros((self.slots,), jnp.int32)
        self._keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(self.slots))
        # the prefill counters no burst has taken home yet: [] for a model
        # that names none, else [an int32 vector], which is these zeros
        # again (no insert consumes them) once a burst has taken it
        n = len(self.model.prefill_counter_names)
        self._no_prefill_counts = [jnp.zeros((n,), jnp.int32)] if n else []
        self._prefill_counts = self._no_prefill_counts
        # per-lane stop tokens (-1 = no eos, never matches) and remaining
        # token budgets for the stop-aware fused burst; the device
        # decrements its own budget copy per step, the host re-uploads
        # only on membership changes (_fused_sync)
        self._stops_dev = jnp.full((self.slots,), -1, jnp.int32)
        self._budget_dev = jnp.zeros((self.slots,), jnp.int32)
        self._fused_sync = False
        self._block_regs = self._fresh_block_regs()

    def _fresh_block_regs(self):
        """The lanes' block registers with no lane in them (a budget of 0:
        nothing runs), for a family that generates by blocks
        (``fused_burst_blocks``); None for every other."""
        import jax.numpy as jnp

        if self._block_w == 1:
            return None
        S, W = self.slots, self._block_w
        # an array each: the burst donates them
        return {"tok": jnp.zeros((S, W), jnp.int32),
                "masked": jnp.ones((S, W), bool),
                **{name: jnp.full((S,), fill, jnp.int32) for name, fill in (
                    ("n_pass", 0), ("skip", 0), ("budget", 0), ("stops", -1))}}

    @scheduler_only
    def _rebuild(self) -> None:
        """Crash recovery (scheduler thread): fresh device state + a
        reset prefix index (its slabs referenced the invalidated cache
        stream's world — correctness never depends on the cache, so the
        safe reset only costs re-warming it), then the recorded ``warm()``
        re-precompile so the restarted loop serves its first admission
        without an XLA stall. Host-side lane bookkeeping is cleared by
        the caller's in-flight sweep before this runs."""
        self._active.clear()
        self._chunked.clear()
        self._pos_host.clear()
        self._masks_dirty = True
        self._active_dev = None
        self._temps_dev = None
        # _alloc_device_state rebuilds the draft cache, so a suppression
        # that was live at crash time is simply over; preempted requests
        # in the resume queue survive (their checkpoints are host-side)
        self._spec_suppressed = False
        self._alloc_device_state()
        if self._prefix_index is not None:
            from .prefix_cache import RadixPrefixIndex

            self._prefix_index = RadixPrefixIndex(self._prefix_cache_budget)
            self._prefix_index.set_version(self.weight_version)
            self.stats["prefix_cache_bytes"] = 0
        if self._warm_args is not None:
            self.warm(**self._warm_args)

    @caller_thread
    def start(self) -> None:
        if self._stop.is_set():
            raise BatcherDead(
                "batcher is closed" if self.health == "closed"
                else "continuous batcher is dead; see server log",
                retry_after_s=5.0,
            )
        with self._thread_lock:
            # check-then-act under a lock: two racing submits must not spawn
            # two scheduler threads over the same donated device state
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._run, name="continuous-batcher", daemon=True
                )
                self._thread.start()
        self._started.wait()

    def warm(
        self,
        prompt_lens: Sequence[int] = (),
        max_new_tokens: int = 0,
        batch_sizes: Sequence[int] = (1, 4, 8),
    ) -> None:
        """Pre-compile every executable the serving loop will need for the
        given traffic shape, BEFORE traffic arrives.

        jit executables compile lazily, so without this the first
        admission wave compiles the batched prefill, and every new
        attention-read bucket a deepening prefix crosses compiles a new
        burst — tens of seconds of stall landing mid-traffic. Warm runs
        each variant once on dummy inputs (donating executables get a
        throwaway same-shape cache) while the scheduler is idle; it must
        be called before the first submit() (the wrapper's
        warmup-before-listen phase).

        Mirrors the reference's model-warmup-before-ready pattern
        (readiness gating); compile-stall avoidance is the TPU-specific
        reason it is load-bearing here.
        """
        import jax
        import jax.numpy as jnp

        # remember the traffic shape so a supervised crash-restart can
        # re-run the exact same precompile before resuming admissions
        self._warm_args = {
            "prompt_lens": tuple(prompt_lens),
            "max_new_tokens": int(max_new_tokens),
            "batch_sizes": tuple(batch_sizes),
        }
        # clamp declared warmup lens to the cache length: an oversized
        # config entry warms the max_seq bucket rather than failing load()
        # with _bucket's too-long-REQUEST error (submit() still rejects
        # real prompts at the boundary)
        buckets = sorted({self._bucket(min(p, self.max_seq)) for p in prompt_lens})
        if not buckets:
            buckets = [self.prefill_buckets[0]]
        k = self._k
        # per-poll worst-case advance: spec rounds emit up to gamma+1
        # tokens each; a fused dispatch advances up to fused_steps (its
        # adaptive K never exceeds that)
        adv = max(
            k * (self.speculate_tokens + 1 if self._spec_burst_fn else 1),
            self._fused_k,
        )
        # attention buckets a run at these prompt lengths can touch: from
        # the shallowest first-burst prefix to the deepest end-of-budget.
        # eos-bearing lanes outlive their budget until the host OBSERVES
        # the stop — up to pipeline_depth-1 bursts of extra _pos_host
        # advance — so cover that overhang too
        lo = min(prompt_lens) if prompt_lens else 1
        hi = (
            (max(prompt_lens) if prompt_lens else 1)
            + max_new_tokens
            + adv * (1 + max(0, self.pipeline_depth - 1))
        )
        ab = self.attn_bucket
        attn_lens = sorted(
            {
                min(self.max_seq, -(-p // ab) * ab)
                for p in range(lo + adv, hi + 1, ab)
            }
            | {min(self.max_seq, -(-(hi) // ab) * ab)}
        )
        # Warm runs the donating executables against the LIVE cache and
        # threads the returned state back in, instead of allocating a
        # cache-sized throwaway per variant (at slots=32 / 1.26B that dummy
        # was a whole extra 3.2 GB of HBM at the peak — the difference
        # between the flagship throughput config fitting or OOMing). Safe
        # because lanes already tolerate residue: every readable position
        # of a lane is rewritten by its current occupant's insert + decode
        # steps before the mask can admit it (the same invariant that lets
        # lanes be reused across requests without scrubbing).
        for bucket in buckets:
            for m in batch_sizes:
                if m > 1 and self.speculate_tokens > 0:
                    continue  # spec mode admits singly
                if m > self.slots:
                    continue  # a wave can never exceed the lane pool
                if m == 8 and not self._chunk8_ok(bucket):
                    continue  # slab would not fit; admission won't use it
                if not self._rows_ok(m, bucket):
                    continue  # the family's prefill takes fewer a call
                prompts = jnp.zeros((m, bucket), jnp.int32)
                last = jnp.zeros((m,), jnp.int32)
                if m == 1:
                    first, cache_one, lane_key, *counts = self._prefill_fn(
                        self.params, prompts, last, jnp.int32(0), jnp.float32(0.0)
                    )
                    self._cache, self._cur_tok, self._pos, self._keys, *_ = (
                        self._insert_fn(
                            self._cache, cache_one, 0, first[0], 1, lane_key,
                            self._cur_tok, self._pos, self._keys,
                            *self._prefill_counts, *counts,
                        )
                    )
                else:
                    firsts, slab, lane_keys, *counts = self._prefill_many_fn(
                        self.params, prompts, last,
                        jnp.zeros((m,), jnp.int32), jnp.zeros((m,), jnp.float32),
                    )
                    self._cache, self._cur_tok, self._pos, self._keys, *_ = (
                        self._insert_many_fn(
                            self._cache, slab, jnp.arange(m, dtype=jnp.int32),
                            firsts, last + 1, lane_keys,
                            self._cur_tok, self._pos, self._keys,
                            *self._prefill_counts, *counts,
                        )
                    )
                # block so only one warm call is in flight at a time
                self._cache_leaf().block_until_ready()  # seldon-lint: disable=host-sync-hot-path (warm precompile: intentional sync while the loop is idle)
                if self.speculate_tokens > 0:
                    dslab = self._draft_prefill_fn(
                        self._draft_params, prompts, last
                    )
                    self._draft_cache = self._draft_insert_fn(
                        self._draft_cache, dslab, 0
                    )
        if self.prefill_chunk > 0:
            # chunked-prefill executables: one per (bucket, chunk offset,
            # is_last) the declared prompt shapes can touch. A shorter
            # real prompt in the same bucket takes its final chunk at an
            # earlier offset, so BOTH variants compile at every offset.
            C = self.prefill_chunk
            for bucket in buckets:
                if bucket <= C:
                    continue
                slab = self._new_slab(bucket)
                for start in range(0, bucket, C):
                    start = min(start, bucket - C)
                    attn_len = min(bucket, self._attn_need(start + C))
                    for is_last in (False, True):
                        buf = jnp.zeros((1, C), jnp.int32)
                        slab, _first, _key = self._chunk_fn(
                            self.params, slab, buf,
                            jnp.int32(start), jnp.int32(C - 1),
                            jnp.int32(0), jnp.float32(0.0),
                            attn_len, is_last,
                        )
                        slab["k"].block_until_ready()  # seldon-lint: disable=host-sync-hot-path (warm precompile: intentional sync while the loop is idle)
                del slab
        if self._prefix_index is not None:
            # prefix-cache executables: extract per donor bucket, and the
            # suffix prefill + splice per (donor, suffix<=donor) bucket
            # pair — the shapes hit traffic takes (a longer-than-donor
            # suffix compiles on first use; it is the rare shape)
            for d in buckets:
                slab = self._extract_fn(self._cache, 0, d)
                if self.prefill_chunk > 0:
                    # chunked-hit splice executables: donor slab into a
                    # fresh staging slab, one per (donor, prompt bucket)
                    # pair the declared shapes can take — compiled here,
                    # never inline on the scheduler thread
                    for b in buckets:
                        if b >= d and b > self.prefill_chunk:
                            out = self._splice_fn(self._new_slab(b), slab)
                            out["k"].block_until_ready()  # seldon-lint: disable=host-sync-hot-path (warm precompile: intentional sync while the loop is idle)
                for s_b in buckets:
                    if s_b > d:
                        continue
                    suffix = jnp.zeros((1, s_b), jnp.int32)
                    first, suffix_slab, lane_key = self._prefix_prefill_fn(
                        self.params, slab, suffix, jnp.int32(1),
                        jnp.zeros((1,), jnp.int32),
                        jnp.int32(0), jnp.float32(0.0),
                    )
                    self._cache, self._cur_tok, self._pos, self._keys = (
                        self._insert_prefix_fn(
                            self._cache, slab, suffix_slab, 0, jnp.int32(1),
                            first[0], 2, lane_key,
                            self._cur_tok, self._pos, self._keys,
                        )
                    )
                    self._cache_leaf().block_until_ready()  # seldon-lint: disable=host-sync-hot-path (warm precompile: intentional sync while the loop is idle)
        if self._kv_tier is not None:
            # tier spill / copy-back executables: a rung-3 preemption
            # extracts the victim lane's cache columns at its ATTENTION
            # width (_attn_need(pos)) and the copy-back resume inserts a
            # slab of that same width — widths the prefix-cache warm
            # above (prompt buckets) never touches. Compile every width
            # a lane can spill at so the first preemption and the first
            # resume never compile inline on the scheduler thread.
            tier_widths = sorted({
                self._attn_need(p) for p in range(max(1, lo), hi + 1)
            })
            for w in tier_widths:
                slab = self._extract_fn(self._cache, 0, w)
                self._cache, self._cur_tok, self._pos, self._keys = (
                    self._insert_fn(
                        self._cache, slab, 0, jnp.int32(0), w,
                        jax.random.PRNGKey(0),
                        self._cur_tok, self._pos, self._keys,
                    )
                )
                self._cache_leaf().block_until_ready()  # seldon-lint: disable=host-sync-hot-path (warm precompile: intentional sync while the loop is idle)
            # census line, PR 13 style: a width-count jump between runs
            # means a config change grew the tier's compile surface
            logger.info(
                "warm: kv-tier extract/insert compile census: %d width "
                "variant(s) (%s)", len(tier_widths), tier_widths,
            )
        active = jnp.zeros((self.slots,), bool)
        temps = jnp.zeros((self.slots,), jnp.float32)
        # the plain and the stop-aware burst: one variant per bucket, or
        # one in all where the read bounds itself (_ragged_read)
        burst_lens = [None] if self._ragged_read else attn_lens
        if self._spec_burst_fn is not None:
            for attn_len in attn_lens:
                caches = {
                    "k": self._cache["k"], "v": self._cache["v"],
                    "dk": self._draft_cache["k"], "dv": self._draft_cache["v"],
                }
                # greedy variant only: temperature lanes compile their own
                # (rare) variant on first use
                (
                    _start, _toks, _counts, self._cur_tok, self._pos,
                    self._keys, nc,
                ) = self._spec_burst_fn(
                    self.params, self._draft_params, caches,
                    self._cur_tok, self._pos, active, temps,
                    self._keys, k, attn_len, False,
                )
                self._cache = {"k": nc["k"], "v": nc["v"]}
                self._draft_cache = {"k": nc["dk"], "v": nc["dv"]}
                self._cache_leaf().block_until_ready()  # seldon-lint: disable=host-sync-hot-path (warm precompile: intentional sync while the loop is idle)
        elif self._block_w > 1:
            # generation by blocks: the burst of passes and the registers'
            # insert at each wave size; the greedy variant only, as the
            # speculative burst (a lane with a temperature compiles its own)
            for m in batch_sizes:
                if m <= self.slots:
                    self._block_admit(
                        list(range(m)),
                        [GenRequest(tokens=[0], max_new_tokens=1)] * m)
            for attn_len in burst_lens:
                (toks, _n, _bits, self._block_regs, self._pos, self._cache,
                 self._keys, *_) = self._block_burst_fn(
                    self.params, self._cache, self._block_regs, self._pos,
                    active, temps, self._keys, k, attn_len, False)
                toks.block_until_ready()  # seldon-lint: disable=host-sync-hot-path (warm precompile: intentional sync while the loop is idle)
            logger.info(
                "warm: block burst compile census: %d variant(s) "
                "(k=%d x attn=%s)", len(burst_lens), k, burst_lens,
            )
        else:
            for attn_len in burst_lens:
                toks, self._cur_tok, self._pos, self._cache, self._keys, *_ = (
                    self._burst_fn(
                        self.params, self._cache, self._cur_tok, self._pos,
                        active, temps, self._keys, k, attn_len,
                    )
                )
                toks.block_until_ready()  # seldon-lint: disable=host-sync-hot-path (warm precompile: intentional sync while the loop is idle)
            logger.info(
                "warm: decode burst compile census: %d variant(s) "
                "(k=%d x attn=%s)", len(burst_lens), k, burst_lens,
            )
        if self._fused_k > 0 and self._spec_burst_fn is None:
            # stop-aware fused variants: every (K, attn bucket) the
            # adaptive-K plan can reach — K is a pow2 in
            # [min(steps_per_poll, fused), fused] (see _fused_plan), so
            # the shrink can never ask for an executable this loop did
            # not build. The one-line census below is the CI-visible
            # retrace-hazard guard: a variant-count jump between runs
            # means a config change grew the compile surface.
            fks: List[int] = []
            fk = self._fused_k
            lo_k = min(self._k, self._fused_k)
            while fk >= lo_k:
                fks.append(fk)
                fk //= 2
            fks = sorted(fks)
            stops0 = jnp.full((self.slots,), -1, jnp.int32)
            budget0 = jnp.zeros((self.slots,), jnp.int32)
            for attn_len in burst_lens:
                for fk in fks:
                    (
                        toks, _counts, _done, self._cur_tok, self._pos,
                        self._cache, self._keys, budget0, *_,
                    ) = self._fused_burst_fn(
                        self.params, self._cache, self._cur_tok, self._pos,
                        active, temps, self._keys, stops0, budget0, fk,
                        attn_len,
                    )
                    toks.block_until_ready()  # seldon-lint: disable=host-sync-hot-path (warm precompile: intentional sync while the loop is idle)
            logger.info(
                "warm: fused decode compile census: %d variant(s) "
                "(k=%s x attn=%s)",
                len(burst_lens) * len(fks), fks, burst_lens,
            )
        if self.mesh is not None:
            # sharded-serving census, same PR-13 contract as the fused
            # line: every executable above just compiled against the
            # MESH layouts, so a partitioned-leaf or per-shard-byte jump
            # between runs means a layout change moved bytes across
            # chips. One designed sync makes the census report compiled
            # executables, not queued ones.
            self._cache_leaf().block_until_ready()  # seldon-lint: disable=host-sync-hot-path (sharded warm census: intentional sync while the loop is idle so the census reports compiled sharded executables)
            leaves = [
                leaf for leaf in jax.tree_util.tree_leaves(self.params)
                if hasattr(leaf, "sharding")
            ]
            partitioned = sum(
                1 for leaf in leaves
                if not leaf.sharding.is_fully_replicated
            )
            logger.info(
                "warm: sharded serving census: mesh=%s devices=%d "
                "partitioned_params=%d/%d param_shard_bytes=%d kv_shard=%d",
                dict(self.mesh.shape), self.mesh.devices.size,
                partitioned, len(leaves), self._param_shard_bytes,
                self._kv_shard,
            )
        # warm left garbage in cur_tok/pos; reset the host-visible lane
        # state so the first admissions start from a clean slate (the
        # device cache needs no scrub — see residue invariant above)
        self._cur_tok = jnp.zeros((self.slots,), jnp.int32)
        self._pos = jnp.zeros((self.slots,), jnp.int32)
        self._keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(self.slots))
        self._stops_dev = jnp.full((self.slots,), -1, jnp.int32)
        self._budget_dev = jnp.zeros((self.slots,), jnp.int32)
        self._fused_sync = False
        self._block_regs = self._fresh_block_regs()

    @caller_thread
    def close(self) -> None:
        if self.health != "dead":
            # a dead batcher stays "dead" (its unready latch is the
            # reconciler's replace signal); a serving/restarting one
            # records the deliberate shutdown
            self.health = "closed"
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
        self._drain_queue(self._dead_error())
        self._fail_pending_swap(self._dead_error())
        self._fail_pending_drain(self._dead_error())

    def _fail_pending_swap(self, err: Exception) -> None:
        with self._swap_lock:
            swap, self._pending_swap = self._pending_swap, None
        if swap is not None and not swap.future.done():
            swap.future.set_exception(err)

    def _release_tier_ckpt(self, req: GenRequest) -> None:
        """Release a request's host-tier checkpoint (if any): the
        request was cancelled, failed, or migrated away, so the entry
        would otherwise pin tier budget forever — prefix demotions can
        never evict checkpoints. Callable from any thread (the tier is
        lock-protected; ``pop`` makes the release idempotent)."""
        ck = req.resume
        if ck is None or self._kv_tier is None:
            return
        key = ck.pop("tier", None)
        if key is not None:
            self._kv_tier.drop_ckpt(key)

    def _drain_queue(self, err: Exception) -> None:
        while self._resume_queue:
            try:
                req = self._resume_queue.popleft()
            except IndexError:  # raced another drainer
                break
            self._release_tier_ckpt(req)
            if not req.future.done():
                req.future.set_exception(err)
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                return
            if not req.future.done():
                req.future.set_exception(err)

    # -- scheduler loop --------------------------------------------------------

    def _cache_leaf(self):
        """One array of the serving cache, whatever kinds it holds: what a
        warm-up call blocks on."""
        import jax

        return jax.tree_util.tree_leaves(self._cache)[0]

    def _chunk8_ok(self, bucket: int) -> bool:
        """m=8 batched prefill is admitted when its slab stays small (the
        slab is a transient allocation on top of params + cache, [L, 8,
        KV, bucket, Dh] x2 for a K/V family: the model sizes it; 4 GB
        keeps flagship configs comfortably inside HBM)."""
        return (self._rows_ok(8, bucket)
                and self.model.prefill_slab_bytes(8, bucket) <= 4 << 30)

    def _rows_ok(self, m: int, bucket: int) -> bool:
        """Whether the family's prefill in ``bucket`` takes ``m`` prompts a
        call (one alone always)."""
        return m == 1 or m <= self.model.prefill_rows_max(
            bucket, added=bucket in self._added_lengths)

    def _bucket(self, n: int) -> int:
        for b in self.prefill_buckets:
            if n <= b:
                return b
        if n <= self.max_seq:
            return self.max_seq
        # a too-long request must fail HERE with a clear, TYPED message
        # (413 / INVALID_ARGUMENT at the engine), not as an opaque
        # downstream broadcast/shape error when the prompt is packed
        # into a bucket-sized array it cannot fit
        raise PromptTooLong(
            f"request of {n} tokens exceeds the largest prefill bucket "
            f"({self.prefill_buckets[-1]}) and max_seq ({self.max_seq}); "
            "raise max_seq or shorten the prompt"
        )

    def _attn_need(self, hi: int) -> int:
        """Smallest attn_bucket multiple covering position ``hi`` (clamped
        to the cache length)."""
        ab = self.attn_bucket
        return min(self.max_seq, -(-hi // ab) * ab)

    @scheduler_only
    def _emit_span(self, req: GenRequest, operation: str, start_t: float,
                   end_t: float, tags: Optional[Dict[str, Any]] = None) -> None:
        """:meth:`GenRequest.emit_span` from the scheduler's side."""
        req.emit_span(operation, start_t, end_t, tags)

    @scheduler_only
    def _fused_plan(self, k_max=None):
        """Adaptive K for the stop-aware fused burst: ``(k, reason)``.

        Start from ``fused_steps_per_dispatch`` and shrink — never below
        the configured ``steps_per_poll`` burst (``self._k``), so the
        shrink can't reintroduce the tiny-burst-per-completion pathology
        the fixed-k design was built to avoid:

        * **stop_budget** — to the nearest lane's remaining token budget
          (pow2-floored): steps past the closest stop are wasted device
          work the done mask would only discard;
        * **pressure** — to ``steps_per_poll`` while the HBM ledger is
          latched: the reclaim ladder (and its preemption checkpoints)
          only runs between dispatches, so boundaries must come at the
          pre-fused cadence;
        * **poll_boundary** — to ``steps_per_poll`` while a weight swap
          or graceful drain is staged: both act at poll boundaries, and
          a K-step burst would stall the flip/checkpoint by K steps.

        The result is always a pow2 <= fused_steps_per_dispatch, so one
        precompiled executable exists per (K, attn bucket) and the shrink
        can never trigger an inline XLA compile.

        ``k_max``: the caller's snapshot of ``self._fused_k`` — the loop
        passes the same value that decided ``use_fused`` this poll, so a
        concurrent toggle (``retune`` flips the knob on a live server)
        can never tear between the mode decision and the plan and yield
        an unwarmed K."""
        if k_max is None:
            k_max = self._fused_k
        k, reason = k_max, None
        floor = min(self._k, k_max)
        rem = [
            r for r in (
                s.request.max_new_tokens - s.dispatched
                - (1 if s.first_pending else 0)
                for s in self._active.values()
            ) if r > 0
        ]
        if rem:
            tight = 1
            nearest = min(rem)
            while tight * 2 <= nearest:
                tight *= 2
            tight = max(tight, floor)
            if tight < k:
                k, reason = tight, "stop_budget"
        if (
            self._pressure.budget_bytes > 0 and self._pressure.active
            and floor < k
        ):
            k, reason = floor, "pressure"
        # unlocked reads, same discipline as the loop's swap sighting: a
        # one-poll-late shrink is harmless
        if (
            (self._pending_swap is not None or self._pending_drain is not None)
            and floor < k
        ):
            k, reason = floor, "poll_boundary"
        return max(1, min(k, k_max)), reason

    @scheduler_only
    def _draft_admit(self, slot: int, req: GenRequest) -> None:
        """Give the draft its prompt K/V prefix (speculation only). Draft
        prefixes are RE-DERIVED from the full prompt, never cached or
        chunked — the draft forward is cheap by construction."""
        self._draft_admit_tokens(slot, req.tokens)

    @scheduler_only
    def _draft_admit_tokens(self, slot: int, tokens: List[int]) -> None:
        """Draft prefill over an arbitrary token sequence — the prompt
        at admit, or prompt+generated-so-far when a preempted lane
        resumes (or rung 2's cancelled speculation re-enables): the
        draft's K/V is a pure function of the tokens, so re-derivation
        lands it in exactly the state incremental drafting left it."""
        import jax.numpy as jnp

        n = len(tokens)
        prompt = np.zeros((1, self._bucket(n)), np.int32)
        prompt[0, :n] = tokens
        dcache_one = self._draft_prefill_fn(
            self._draft_params, jnp.asarray(prompt),
            jnp.asarray([n - 1], jnp.int32),
        )
        self._draft_cache = self._draft_insert_fn(
            self._draft_cache, dcache_one, slot
        )

    def _new_slab(self, bucket: int):
        """Fresh staging slab in the cache_one layout the lane insert
        consumes: ``{"k","v"}`` of ``[L, 1, KV, bucket, Dh]`` — allocated
        pre-sharded under a mesh so chunked prefill writes shards in
        place instead of resharding on the first chunk."""
        import jax
        import jax.numpy as jnp

        cfg = self.model.cfg
        shape = (cfg.n_layers, 1, cfg.n_kv_heads, bucket, cfg.head_dim)
        dt = jnp.dtype(self.model.compute_dtype)
        slab = {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
        if self._slab_sharding is not None:
            slab = {
                name: jax.device_put(a, self._slab_sharding)
                for name, a in slab.items()
            }
        return slab

    def _upload_slab(self, host: Dict[str, Any]) -> Dict[str, Any]:
        """Host->device K/V slab upload (``[L, 1, KV, T, Dh]``) honoring
        the mesh slab layout. Every wire/tier slab arrives as contiguous
        host bytes (SKV1 and the host tier are layout-independent by
        contract); under a mesh the upload scatters each chip's KV-head
        shard directly so the downstream insert/splice executables see
        the same layout the persistent cache uses. Unmeshed this is the
        plain ``jnp.asarray`` H2D copy it always was."""
        import jax
        import jax.numpy as jnp

        if self._slab_sharding is None:
            return {"k": jnp.asarray(host["k"]), "v": jnp.asarray(host["v"])}
        return {
            "k": jax.device_put(host["k"], self._slab_sharding),
            "v": jax.device_put(host["v"], self._slab_sharding),
        }

    @scheduler_only
    def _count_admitted(self, *reqs: GenRequest) -> None:
        """``reqs``' lanes are live from this poll on: the count, each
        request's ``admit_poll``, and their ids for the poll's record."""
        self.stats["admitted"] += len(reqs)
        for req in reqs:
            req.admit_poll = self._poll_count
        if self.flight is not None and self.flight.enabled:
            self._row_admitted.extend(req.rid for req in reqs)

    @scheduler_only
    def _start_chunked(self, slot: int, req: GenRequest, hit=None,
                       resume=None) -> None:
        """Reserve ``slot`` and queue the prompt for interleaved chunked
        prefill. On a prefix-cache hit the donor slab lands at the head
        of the staging slab and chunking starts at the splice point —
        rounded DOWN to the chunk grid: chunk offsets must stay at
        multiples of ``prefill_chunk`` so every (offset, attn_len)
        executable is one warm() precompiled (an off-grid start would
        jit-compile inline on the scheduler thread, stalling every decode
        lane mid-serving). The [aligned, match) overlap is recomputed and
        overwrites the donor splice with the same tokens at the same
        absolute positions — idempotent, at most one chunk's extra work."""
        bucket = self._bucket(len(req.tokens))
        t_admit = time.monotonic()
        req.admit_t = t_admit
        slab = self._new_slab(bucket)
        start = 0
        if hit is not None:
            # a real radix hit, even when alignment leaves nothing to
            # splice (match < one chunk): counted as a hit with its true
            # (aligned) savings so cache telemetry stays honest under
            # chunking
            m, donor = hit
            start = (m // self.prefill_chunk) * self.prefill_chunk
            if start > 0:
                with self._prof.measure(
                    "splice", variant=f"b{bucket}",
                    tenant=req.tenant or "",
                    bytes_read=start * self._kv_key_bytes,
                    tokens=start,
                ) as _m:
                    slab = self._splice_fn(slab, donor)
                    _m.sync(slab)
            req.cache_hit_tokens = start
            self.stats["prefix_hits"] += 1
            self.stats["prefix_tokens_saved"] += start
        elif self._prefix_index is not None:
            self.stats["prefix_misses"] += 1
        self._chunked[slot] = _ChunkJob(
            request=req, slot=slot, next_start=start, slab=slab,
            bucket=bucket, hit_tokens=start, resume=resume,
        )
        self._emit_span(
            req, "gen.queue_wait", req.submit_t, t_admit,
            tags={"lane": slot, "chunked": True,
                  "cache_hit_tokens": req.cache_hit_tokens},
        )

    @scheduler_only
    def _advance_chunks(self) -> None:
        """Run ONE prefill chunk for every pending chunked admission (the
        interleave: a chunk per job per decode poll). The final chunk
        samples the first token on device and the finished slab goes
        through the ORDINARY lane insert, so activation is exactly a
        whole-prompt admit (same deferred-first mechanics, same insert
        executable, bit-identical decode from there on)."""
        import jax.numpy as jnp

        C = self.prefill_chunk
        for slot in list(self._chunked):
            job = self._chunked[slot]
            req = job.request
            if req.future.cancelled():
                del self._chunked[slot]
                self.stats["cancelled"] += 1
                continue
            n = len(req.tokens)
            start = job.next_start
            is_last = start + C >= n
            if is_last:
                # the padded chunk must stay inside the slab; sliding the
                # start back re-writes identical K/V (same tokens, same
                # absolute positions) — idempotent by construction
                start = max(0, min(start, job.bucket - C))
            end = min(start + C, n)
            buf = np.zeros((1, C), np.int32)
            buf[0, : end - start] = req.tokens[start:end]
            attn_len = min(job.bucket, self._attn_need(start + C))
            t_chunk = time.monotonic()
            try:
                from ..tracing import device_trace

                with self._prof.measure(
                    "chunk_prefill", variant=f"b{job.bucket}",
                    tenant=req.tenant or "",
                    bytes_read=self._param_bytes + C * self._kv_key_bytes,
                    tokens=C,
                ) as _m, device_trace("gen.prefill_chunk"):
                    job.slab, first, lane_key = self._chunk_fn(
                        self.params, job.slab, jnp.asarray(buf),
                        jnp.int32(start), jnp.int32(n - 1 - start),
                        jnp.int32(req.seed), jnp.float32(req.temperature),
                        attn_len, is_last,
                    )
                    _m.sync(job.slab)
                if is_last:
                    req.insert_t = time.monotonic()
                    if job.resume is not None:
                        # recompute-resume: the checkpointed continuation
                        # state replaces the chunk's own sample
                        import jax.numpy as _jnp

                        emitted_r, key_r = job.resume
                        first = _jnp.int32(int(emitted_r[-1]))
                        lane_key = key_r
                        insert_pos = n + len(emitted_r) - 1
                    else:
                        insert_pos = n
                    with self._prof.measure(
                        "insert", variant=f"b{job.bucket}",
                        tenant=req.tenant or "",
                        bytes_read=job.bucket * self._kv_key_bytes,
                        tokens=insert_pos,
                    ) as _m, device_trace("gen.lane_insert"):
                        self._cache, self._cur_tok, self._pos, self._keys = (
                            self._insert_fn(
                                self._cache, job.slab, slot, first,
                                insert_pos, lane_key,
                                self._cur_tok, self._pos, self._keys,
                            )
                        )
                        _m.sync(self._cur_tok)
                else:
                    # an output of the chunk's program, and the array an
                    # insert or a burst after it would have replaced
                    self._chunk_newest = (lane_key, self._cur_tok)
            except Exception as e:  # noqa: BLE001 - bad request/device state
                logger.exception("chunked prefill failed")
                del self._chunked[slot]
                if not req.future.done():
                    req.future.set_exception(e)
                continue
            self.stats["prefill_steps"] += 1
            # positions COMPUTED, incl. pad and slide-back overlap — the
            # same convention as the bucketed full prefill (which counts
            # its whole bucket): prefill_tokens is a device-work proxy,
            # not a real-prompt-token count
            self.stats["prefill_tokens"] += C
            self.stats["prefill_prompt_tokens"] += end - start
            self.stats["prefill_chunks"] += 1
            self._emit_span(
                req, "gen.prefill_chunk", t_chunk, time.monotonic(),
                tags={"lane": slot, "start": start, "tokens": C,
                      "last": is_last, "dispatch": True},
            )
            if is_last:
                del self._chunked[slot]
                if job.resume is not None:
                    # shared resume tail: replay emitted K/V, draft
                    # re-derivation, lane re-activation with crediting
                    # continuing after the checkpoint
                    self._activate_resumed(slot, req, job.resume[0])
                    continue
                if self._spec_active():
                    # (suppressed speculation skips this: the lane gets
                    # its draft prefix at _resume_speculation instead)
                    self._draft_admit(slot, req)
                req.decode_start_t = time.monotonic()
                self._active[slot] = _Slot(request=req)
                self._pos_host[slot] = n
                self._masks_dirty = True
                self._count_admitted(req)
            else:
                job.next_start = end

    @scheduler_only
    def _prefix_match(self, req: GenRequest):
        return self._prefix_match_tokens(req.tokens)

    @scheduler_only
    def _prefix_match_tokens(self, tokens: List[int]):
        """Longest usable cached prefix for this prompt: ``(m, slab)`` or
        None. Capped at n-1 (the last prompt token is always recomputed —
        its forward produces the logits the first new token samples from)
        and rejected when the suffix bucket would not fit the cache.
        Takes a raw token list so a recompute-resume (prompt + generated
        so far) can splice cached prompt prefixes exactly like a fresh
        admission."""
        if self._prefix_index is None:
            return None
        n = len(tokens)
        m, slab = self._prefix_index.match(tokens)
        m = min(m, n - 1)
        if (
            (slab is None or m < self.prefix_cache_min_tokens)
            and self._kv_tier is not None
        ):
            # device radix miss: consult the host tier — a demoted slab
            # promotes (device_put + re-insert) and serves this very
            # admission as an ordinary splice
            promoted = self._promote_tier_prefix(tokens)
            if promoted is not None:
                m, slab = promoted
                m = min(m, n - 1)
        if slab is None or m < self.prefix_cache_min_tokens:
            return None
        if m + self._bucket(n - m) > self.max_seq:
            # the traced-start suffix insert would clamp and corrupt the
            # lane; full prefill is the safe path for near-max prompts
            return None
        if slab["k"].shape[3] > self._bucket(n):
            # the hit's cost scales with the DONOR's bucket (splice bytes
            # + suffix attention over the combined cache): a short prompt
            # matching into a much longer cached prompt would pay more
            # than the full prefill it skips — not a win, decline
            return None
        return m, slab

    @scheduler_only
    def _maybe_publish(self, slot: int, s: "_Slot") -> None:
        """Publish the request's prompt K/V back into the radix pool (the
        prompt region [0, n) is fully written from admit onward and decode
        only appends, so extraction is valid at any free point). Skipped
        when an exact entry already covers the prompt — repeat-heavy
        traffic publishes each distinct prompt once."""
        idx = self._prefix_index
        if idx is None:
            return
        toks = s.request.tokens
        n = len(toks)
        if n < self.prefix_cache_min_tokens:
            return
        if idx.covered_len(toks) >= n:
            return
        _b = self._bucket(n)
        with self._prof.measure(
            "extract", variant=f"b{_b}",
            tenant=s.request.tenant or "",
            bytes_read=_b * self._kv_key_bytes, tokens=_b,
        ) as _m:
            slab = self._extract_fn(self._cache, slot, _b)
            _m.sync(slab)
        nbytes = int(slab["k"].nbytes) + int(slab["v"].nbytes)
        self.stats["prefix_evicted"] += idx.insert(toks, slab, nbytes)
        self.stats["prefix_cache_bytes"] = idx.total_bytes

    @scheduler_only
    def _admit_remote_lane(self, slot: int, req: GenRequest) -> None:
        """Splice a shipped prefill slab into ``slot`` (scheduler thread;
        the decode-side endpoint of the KV handoff). No prefill runs
        here — the slab carries the prompt K/V and the first sampled
        token; a full slab goes through the ORDINARY whole-prompt
        insert, a suffix-only slab re-matches the local radix index and
        goes through the prefix-splice insert, so decode state after a
        remote admit is bit-identical to the unified path's."""
        import jax.numpy as jnp

        from ..tracing import device_trace
        from .disagg import PrefixGone, WeightVersionMismatch

        r = req.remote
        n = len(req.tokens)
        t_admit = time.monotonic()
        req.admit_t = t_admit
        # re-validate at the poll boundary: a hot-swap that flipped while
        # this request sat in the queue makes the slab stale — the typed
        # refusal the progressive-delivery contract requires
        if r["version"] != self.weight_version:
            raise WeightVersionMismatch(
                f"weight swap landed mid-handoff: slab is "
                f"{r['version']!r}, serving {self.weight_version!r}"
            )
        covered = r["covered"]
        if covered:
            m, donor = self._prefix_index.match(req.tokens)
            if donor is None or m < covered:
                raise PrefixGone(
                    f"cached prefix covers {m} tokens but the slab "
                    f"assumes {covered} — donor evicted mid-handoff; "
                    "re-request with covered_len=0"
                )
            with self._prof.measure(
                "insert", variant=f"px{self._bucket(n)}",
                tenant=req.tenant or "",
                bytes_read=self._bucket(n) * self._kv_key_bytes, tokens=n,
            ) as _m, device_trace("gen.lane_insert"):
                self._cache, self._cur_tok, self._pos, self._keys = (
                    self._insert_prefix_fn(
                        self._cache, donor, r["slab"], slot,
                        jnp.int32(covered), jnp.int32(r["first"]), n,
                        r["key"], self._cur_tok, self._pos, self._keys,
                    )
                )
                _m.sync(self._cur_tok)
            self.stats["prefix_hits"] += 1
            self.stats["prefix_tokens_saved"] += covered
        else:
            with self._prof.measure(
                "insert", variant=f"b{self._bucket(n)}",
                tenant=req.tenant or "",
                bytes_read=self._lane_bytes(self._bucket(n)), tokens=n,
            ) as _m, device_trace("gen.lane_insert"):
                self._cache, self._cur_tok, self._pos, self._keys = (
                    self._insert_fn(
                        self._cache, r["slab"], slot, jnp.int32(r["first"]),
                        n, r["key"], self._cur_tok, self._pos, self._keys,
                    )
                )
                _m.sync(self._cur_tok)
            if self._prefix_index is not None:
                self.stats["prefix_misses"] += 1
        t_inserted = time.monotonic()
        req.insert_t = t_admit      # no prefill here: the insert is all
        req.decode_start_t = t_inserted
        self._emit_span(
            req, "gen.queue_wait", req.submit_t, t_admit,
            tags={"lane": slot, "remote": True,
                  "cache_hit_tokens": covered},
        )
        self._emit_span(
            req, "gen.lane_insert", t_admit, t_inserted,
            tags={"lane": slot, "remote": True, "dispatch": True},
        )
        self._active[slot] = _Slot(request=req)
        self._pos_host[slot] = n
        self._masks_dirty = True
        self._count_admitted(req)
        self.stats["kv_imports"] += 1
        self.stats["kv_import_bytes"] += r["nbytes"]
        if covered:
            self.stats["kv_transfer_bytes_saved"] += (
                covered * self._slab_token_bytes
            )
        if self.flight is not None and self.flight.enabled:
            self.flight.record({
                "type": "remote_insert",
                "lane": slot,
                "tokens": n,
                "covered_len": covered,
                "bytes": r["nbytes"],
                "weight_version": self.weight_version,
            })
        # the slab's device arrays are spliced; drop the reference so the
        # upload buffer frees as soon as the insert's copy completes
        req.remote = None

    # -- tiered KV memory: host-RAM spill tier (serving/kvtier.py) ---------

    def sync_kv_tier_stats(self) -> None:
        """Mirror the tier's internal counters into the batcher's stats
        surface (flight dumps, server metric deltas). Tier counters are
        written under the tier lock by scheduler AND transport threads;
        these are plain int copies, safe from any thread."""
        tier = self._kv_tier
        if tier is None:
            return
        t = tier.stats
        self.stats["kv_tier_demotions"] = t["demotions"]
        self.stats["kv_tier_hits"] = t["hits"]
        self.stats["kv_tier_evictions"] = t["evictions"]
        self.stats["kv_tier_bytes"] = tier.total_bytes

    def kv_tier_summary(self) -> Optional[Dict[str, Any]]:
        return self._kv_tier.summary() if self._kv_tier is not None else None

    @property
    def tier_promote_gate(self) -> int:
        """Effective promote threshold: a tier match below
        ``prefix_cache_min_tokens`` could never serve an admission (the
        radix-hit gate would discard it right after the PCIe copy), so
        the promote gate is the max of the two knobs."""
        return max(self.kv_tier_promote_min_tokens,
                   self.prefix_cache_min_tokens)

    @scheduler_only
    def _demote_prefix_slabs(self, victims) -> None:
        """Demote reclaim-ladder prefix victims to the host tier:
        ``device_get`` each slab at this poll boundary (the one designed
        sync of the demote path — pressure reclaim is already a
        poll-boundary event and the copy IS the feature: a PCIe pull now
        buys back a whole re-prefill later), SKV1-encode, store keyed by
        (weight_version, token path)."""
        import jax

        from .disagg import prompt_hash

        tier = self._kv_tier
        for tokens, slab, _nbytes in victims:
            # refuse BEFORE the PCIe pull, not after: a victim below the
            # demote threshold, or already covered by a stored entry,
            # would be refused by put_prefix anyway — paying two
            # device_get syncs for it mid-pressure-event is the worst
            # possible time
            if (
                len(tokens) < tier.min_tokens
                or tier.prefix_covered_len(tokens, self.weight_version)
                >= len(tokens)
            ):
                continue
            host = {
                "k": jax.device_get(slab["k"]),  # seldon-lint: disable=host-sync-hot-path (tier demote: poll-boundary PCIe pull of an evicted prefix slab — the copy replaces a future re-prefill; reclaim is latched, not steady-state)
                "v": jax.device_get(slab["v"]),  # seldon-lint: disable=host-sync-hot-path (tier demote: second half of the same poll-boundary slab pull)
            }
            if tier.put_prefix(tokens, host, self.weight_version):
                if self.flight is not None and self.flight.enabled:
                    self.flight.record({
                        "type": "kv_demote", "kind": "prefix",
                        "tokens": len(tokens),
                        "phash": prompt_hash(tokens)[:8],
                        "bytes": int(host["k"].nbytes) + int(host["v"].nbytes),
                    })

    def tier_prefix_lookup(self, tokens, min_tokens: Optional[int] = None):
        """The ONE usable-hit probe of this member's host tier, shared
        by the scheduler's promote-on-miss, the decode role's
        transfer-dedup consult, and the KV-port listener's peer lookup
        — so the gate (promote threshold, donor-bucket cap, near-max
        suffix cap) can never drift between the side that SHIPS a slab
        and the side that must splice it. Returns ``(m, meta, host)``
        with host arrays CRC-verified, or None on miss / corruption
        (entry already dropped, logged) / caps. Thread-safe: pure host
        reads under the tier lock."""
        from .disagg import DisaggError

        tier = self._kv_tier
        if tier is None:
            return None
        tokens = [int(t) for t in tokens]
        n = len(tokens)
        try:
            hit = tier.match_prefix(tokens, self.weight_version)
        except DisaggError as e:
            logger.warning("kv tier prefix entry dropped: %s", e)
            return None
        if hit is None:
            return None
        depth, meta, host = hit
        m = min(depth, n - 1)
        if m < max(int(min_tokens or 0), self.tier_promote_gate):
            return None
        if (
            host["k"].shape[3] > self._bucket(n)
            or m + self._bucket(n - m) > self.max_seq
        ):
            # same caps the device-side match applies: a donor wider
            # than the prompt bucket (or a near-max suffix insert) costs
            # more than the prefill it skips
            return None
        return m, meta, host

    @scheduler_only
    def _promote_tier_prefix(self, tokens):
        """Tier consult on a device radix miss: decode the longest
        stored host prefix (CRC-verified), ``device_put`` it, re-insert
        it into the device radix index under its ENTRY path, and return
        ``(m, device_slab)`` ready for the ordinary splice — a warm hit
        that costs a PCIe copy instead of a re-prefill. None on miss,
        corruption (entry already dropped), or when the usability caps
        say the splice would not win (see :meth:`tier_prefix_lookup`)."""
        from .disagg import prompt_hash

        idx = self._prefix_index
        if idx is None:
            return None
        hit = self.tier_prefix_lookup(tokens)
        if hit is None:
            return None
        m, meta, host = hit
        entry_tokens = [int(t) for t in meta.get("tokens") or []]
        slab_dev = self._upload_slab(host)
        nbytes = int(host["k"].nbytes) + int(host["v"].nbytes)
        self.stats["prefix_evicted"] += idx.insert(
            entry_tokens, slab_dev, nbytes
        )
        self.stats["prefix_cache_bytes"] = idx.total_bytes
        self.stats["kv_tier_promotions"] += 1
        if self.flight is not None and self.flight.enabled:
            self.flight.record({
                "type": "tier_hit", "kind": "prefix", "source": "local",
                "tokens": m, "phash": prompt_hash(entry_tokens)[:8],
            })
            self.flight.record({
                "type": "kv_promote", "kind": "prefix", "source": "local",
                "tokens": m, "bytes": nbytes,
                "phash": prompt_hash(entry_tokens)[:8],
            })
        return m, slab_dev

    @caller_thread
    def consult_tier_covered_len(self, tokens) -> int:
        """Decode-role transfer-dedup consult of this member's OWN host
        tier: a demoted prefix that matches the prompt promotes into the
        device radix index right here (caller thread — the H2D upload
        overlaps whatever burst the scheduler is running, exactly like a
        remote admit's slab), and the refreshed ``remote_covered_len``
        is returned so the prefill request ships suffix-only. 0 on
        miss/corruption/caps — the full-slab path is always right
        behind."""
        if self._prefix_index is None:
            return 0
        hit = self.tier_prefix_lookup(tokens)
        if hit is None:
            return 0
        _m, meta, host = hit
        self.promote_peer_prefix(meta, host, source="local")
        return self.remote_covered_len(tokens)

    @caller_thread
    def promote_peer_prefix(self, meta: Dict[str, Any],
                            host: Dict[str, Any],
                            source: str = "peer") -> int:
        """Insert a prefix slab pulled from a PEER's host tier into the
        LOCAL device radix index (H2D upload on this caller thread,
        exactly like a remote admit's slab upload), so the ordinary
        match/splice machinery — and the transfer-dedup consult — serve
        it from here on. Returns the entry's token count."""
        from .disagg import prompt_hash

        idx = self._prefix_index
        if idx is None:
            return 0
        entry_tokens = [int(t) for t in meta.get("tokens") or []]
        if not entry_tokens:
            return 0
        slab_dev = self._upload_slab(host)
        nbytes = int(host["k"].nbytes) + int(host["v"].nbytes)
        evicted = idx.insert(entry_tokens, slab_dev, nbytes)
        with self._export_lock:
            self.stats["prefix_evicted"] += evicted
            self.stats["prefix_cache_bytes"] = idx.total_bytes
            self.stats["kv_tier_promotions"] += 1
        if self.flight is not None and self.flight.enabled:
            self.flight.record({
                "type": "kv_promote", "kind": "prefix", "source": source,
                "tokens": len(entry_tokens), "bytes": nbytes,
                "phash": prompt_hash(entry_tokens)[:8],
            })
        return len(entry_tokens)

    @scheduler_only
    def _checkpoint_kv_to_tier(self, slot: int, req: GenRequest) -> None:
        """Ladder rung 3's spill half: copy the preempted lane's exact
        cache columns ``[0, pos)`` to the host tier (when budget allows)
        so resume is a copy-back insert instead of prompt-recompute +
        teacher-forced replay. The extract is a device-side copy; the
        pull to host is the one designed sync — the pipeline is already
        drained (preemption's own requirement), and the bytes pulled
        here are exactly the recompute the resume no longer pays."""
        import jax

        from .disagg import prompt_hash

        tier = self._kv_tier
        ck = req.resume
        if tier is None or ck is None:
            return
        emitted = ck["emitted"]
        n = len(req.tokens)
        pos = n + len(emitted) - 1
        width = self._attn_need(pos)
        with self._prof.measure(
            "extract", variant="preempt", tenant=req.tenant or "",
            bytes_read=width * self._kv_key_bytes, tokens=width,
        ) as _m:
            slab = self._extract_fn(self._cache, slot, width)
            _m.sync(slab)
        host = {
            "k": jax.device_get(slab["k"]),  # seldon-lint: disable=host-sync-hot-path (tier checkpoint: poll-boundary pull of a preempted lane's K/V — pipeline already drained; this copy replaces the resume's whole recompute+replay)
            "v": jax.device_get(slab["v"]),  # seldon-lint: disable=host-sync-hot-path (tier checkpoint: second half of the same poll-boundary lane pull)
        }
        self._tier_ck_seq += 1
        key = self._tier_ck_seq
        stored = tier.put_ckpt(
            key,
            {"pos": pos, "width": width, "prompt_tokens": n,
             "emitted": len(emitted)},
            host, version=self.weight_version,
        )
        if stored:
            ck["tier"] = key
            if self.flight is not None and self.flight.enabled:
                self.flight.record({
                    "type": "kv_demote", "kind": "ckpt", "lane": slot,
                    "tokens": pos, "phash": prompt_hash(req.tokens)[:8],
                    "bytes": int(host["k"].nbytes) + int(host["v"].nbytes),
                })
        else:
            # belt and braces: a refused checkpoint leaves no key behind
            # (the checkpoint dict is freshly built per preemption, but
            # a stale key here would make the next resume count a
            # phantom replay fallback)
            ck.pop("tier", None)

    # -- HBM pressure: ledger, reclaim ladder, decode-lane preemption ------

    def pressure_summary(self) -> Optional[Dict[str, Any]]:
        """Ledger snapshot for metrics/flight dumps; None when the
        pressure subsystem is off (budget 0). Under a mesh the snapshot
        also carries the shard factors the ledger divided by, so an
        operator reading used_bytes knows it is PER-CHIP occupancy."""
        pc = self._pressure
        if pc.budget_bytes <= 0 and not pc.stats["budget_changes"]:
            return None
        out = pc.summary()
        if self.mesh is not None:
            out["kv_shard"] = self._kv_shard
            out["param_shard_bytes"] = self._param_shard_bytes
        return out

    def _spec_active(self) -> bool:
        """Speculation is configured AND not cancelled by the pressure
        ladder's rung 2."""
        return self._spec_burst_fn is not None and not self._spec_suppressed

    def _burst_tenant(self) -> str:
        """Tenant label for a whole-batch dispatch: the single tenant
        every active lane belongs to, or "" when mixed/untenanted (the
        weight pager serves one resident tenant at a time, so decode
        bursts are single-tenant in practice; attribution degrades to
        unlabeled rather than lying when lanes ever mix)."""
        tenant = ""
        for s in self._active.values():
            t = s.request.tenant
            if t is None:
                return ""
            if not tenant:
                tenant = t
            elif t != tenant:
                return ""
        return tenant

    @scheduler_only
    def _ledger_components(self) -> Dict[str, int]:
        """The unified HBM ledger, priced the way the reclaim ladder can
        free it: live decode footprint per lane (current attention-read
        bucket x per-token K/V bytes, draft cache included while
        resident), chunked-prefill staging slabs, the radix prefix
        cache's published bytes, and a staged hot-swap's double-buffered
        params. Pure host arithmetic over at most ``slots`` entries —
        cheap enough to run every poll.

        Under a mesh every component is priced **per shard**: array
        ``.nbytes`` is the GLOBAL byte count of a sharded buffer, but the
        watermark guards a single chip's HBM, so KV components divide by
        the cache's shard factor (model axis x seq when sharded — same
        factor for staging/prefix slabs, which carry the model-axis split)
        and a staged swap scales by the param layout's per-shard fraction.
        Unmeshed, every factor is 1 and the arithmetic is unchanged."""
        draft_per_tok = 0
        if self.speculate_tokens > 0 and not self._spec_suppressed:
            draft_per_tok = self._draft_kv_key_bytes
        decode = sum(
            self._lane_bytes(need) + need * draft_per_tok
            for need in map(self._attn_need, self._pos_host.values())
        ) // self._kv_shard
        staging = sum(
            job.bucket for job in self._chunked.values()
        ) * self._kv_key_bytes // self._kv_model_shard
        prefix = (
            self._prefix_index.total_bytes
            if self._prefix_index is not None else 0
        ) // self._kv_model_shard
        swap = self._pending_swap
        swap_bytes = getattr(swap, "nbytes", 0) if swap is not None else 0
        if swap_bytes and self._param_bytes:
            swap_bytes = (
                swap_bytes * self._param_shard_bytes // self._param_bytes
            )
        # multi-tenancy: the resident tenant's checkpoint occupies HBM
        # beyond the baseline single-model params the watermark already
        # assumes — the pager reports its residency so page-ins compete
        # with KV growth in the same ledger. Scaled per shard exactly
        # like a staged swap (same param layout).
        pager_bytes = 0
        if self.tenant_pager is not None:
            pager_bytes = int(
                getattr(self.tenant_pager, "resident_hbm_bytes", 0)
            )
            if pager_bytes and self._param_bytes:
                pager_bytes = (
                    pager_bytes * self._param_shard_bytes
                    // self._param_bytes
                )
        return {
            "decode": decode, "staging": staging,
            "prefix": prefix, "swap": swap_bytes, "pager": pager_bytes,
        }

    @scheduler_only
    def _drain_pending(self, pending) -> None:
        """Read every in-flight burst NOW (oldest first). Preemption
        checkpoints must see the lane's exact host state — emitted
        tokens and the device position they imply — so the pipeline is
        flushed before any victim is chosen. Preemption is rare; one
        flushed pipeline is its cheapest cost."""
        while pending:
            self._read_burst(pending.popleft())

    @scheduler_only
    def _take_prefill_counts(self):
        """The prefill counters since the last call, as a burst's entry
        takes them along ([] for a model that names none): the device
        ran the inserts that summed them before the burst, so whoever
        reads the burst finds them ready."""
        taken, self._prefill_counts = (
            self._prefill_counts, self._no_prefill_counts)
        return taken

    @scheduler_only
    def _read_burst(self, entry) -> None:
        """Bring one in-flight burst's tokens to the host and credit them.
        ``entry`` is ``(mode, device arrays, (snapshot, ...), dispatch
        time)``; the ``np.asarray`` here is the burst's one host sync
        (phase ``read_wait``), everything after it is ``credit``. The next
        poll record takes the read along in its ``bursts``."""
        mode, arrays, rest, t_dispatch = entry
        # the device had finished this burst before the host came for it: the
        # host was the one waited for (an attribute check, no sync; an array
        # without is_ready, a test double's, is ready as the loop reads it)
        late = _is_ready(arrays[-1])
        if late:
            self.stats["bursts_read_late"] += 1
        self._clock.to("read_wait")
        host = [np.asarray(a) for a in arrays]
        t_read = self._clock.to("credit")
        self.stats["bursts"] += 1
        self.stats["burst_read_lag_s_sum"] += t_read - t_dispatch
        if self.flight is not None and self.flight.enabled:
            self._row_bursts.append({
                "dispatch_t": t_dispatch, "read_t": t_read,
                # a plain burst's tokens: its k rows under the prefill's
                "k": rest[1] if len(rest) > 1 else len(host[0]) - 1,
                "lanes": len(rest[0]), "late": late,
            })
        if self._step_counters and mode != "spec":
            # the model's own counters of the burst's steps: its last array
            counts = dict(zip(self._step_counters, map(int, host.pop())))
            for name, n in counts.items():
                self.stats[name] += n
            for name, counter in self._counters_in_kernel.items():
                if counter is not None:
                    self.stats[name] += counts[counter]
        if self._prefill_counters and mode != "spec":
            # and, before it, those of the prefills dispatched since the
            # burst before (_take_prefill_counts)
            for name, n in zip(self._prefill_counters, host.pop()):
                self.stats[name] += int(n)
        if mode == "spec":
            start_tok, toks, counts = host
            # acceptance telemetry over ALL lanes that ran rounds
            # (device-true, independent of host-side crediting cutoffs)
            self.stats["spec_rounds"] += int((counts > 0).sum())
            self.stats["spec_emitted"] += int(counts.sum())
            self._credit_rounds(
                toks, counts, rest[0],
                rest[1] * (self.speculate_tokens + 1), start_tok=start_tok)
        elif mode == "block":
            self._credit_rounds(*host, rest[0], rest[2], width=self._block_w)
        elif mode == "fused":
            self._process_fused_burst(*host, *rest)
        else:
            self._process_burst(*host, *rest)
        self._clock.to("other")

    @scheduler_only
    def _device_drained(self) -> bool:
        """Whether the device has finished everything the loop ever gave
        it: the chip runs one program at a time in dispatch order, so the
        newest array done means all done. That array is ``_cur_tok`` (every
        insert and every burst replaces it) unless a chunk that was not
        its job's last went out since. An attribute check, no sync. What
        only an option that is off by default dispatches (a prefix
        extract, a draft's admit, a replay, a swap) is not looked at."""
        newest = self._chunk_newest
        if newest is not None and newest[1] is self._cur_tok:
            return _is_ready(newest[0])
        self._chunk_newest = None
        return _is_ready(self._cur_tok)

    @scheduler_only
    def _pressure_poll(self, pending) -> None:
        """Per-poll pressure work: apply the chaos hook's re-budget,
        refresh the ledger, and — while latched over the high watermark
        — run the reclaim ladder. With ``budget == 0`` and no hook this
        is two attribute checks: the no-pressure hot loop stays clean."""
        pc = self._pressure
        if self.pressure_hook is not None:
            nb = self.pressure_hook(self._work_poll_count)
            if nb is not None:
                if int(nb) < 0:
                    pc.restore_budget()
                else:
                    pc.set_budget(int(nb))
                if self._kv_tier is not None:
                    pc.host_bytes = self._kv_tier.total_bytes
                if self.flight is not None and self.flight.enabled:
                    self.flight.record({
                        "type": "pressure_budget",
                        "budget_bytes": pc.budget_bytes,
                        "restored": int(nb) < 0,
                        "host_tier_bytes": pc.host_bytes,
                    })
        if pc.budget_bytes <= 0:
            # a restore can land back on a ZERO boot budget (pressure
            # configured purely via the chaos hook): cancelled
            # speculation must still come back, or the fault window
            # would silently disable drafting for the process lifetime
            if self._spec_suppressed:
                self._resume_speculation()
            if pc.active:
                pc.update(self._ledger_components())
            return
        if self._kv_tier is not None:
            # host-RAM occupancy rides the summary/flight surface but
            # never the HBM ledger math (host RAM is not HBM — counting
            # it would double-bill every demotion). Refreshed only on
            # the budget>0 path: the no-pressure hot loop stays the two
            # attribute checks the method contract promises
            # (metrics()/flight_dump refresh on demand).
            pc.host_bytes = self._kv_tier.total_bytes
            self.sync_kv_tier_stats()
        pc.update(self._ledger_components())
        if not pc.active:
            if self._spec_suppressed:
                self._resume_speculation()
            return
        self._reclaim(pending, pc)

    @scheduler_only
    def _reclaim(self, pending, pc) -> None:
        """The reclaim ladder, cheapest rung first, until usage drops to
        the low watermark:

        1. **evict prefixes** — pure cache, zero work lost;
        2. **cancel speculation** — free the draft cache, decode falls
           back to plain bursts (greedy streams identical by the spec
           exactness contract; skipped while any stochastic lane is
           live — seeded-sampling byte-identity outranks this rung);
        3. **preempt lanes** — checkpoint a victim to host and requeue
           it for recompute-resume, freeing its slot and cache columns
           at this poll boundary (never the last lane: one lane always
           makes forward progress, so pressure cannot livelock);
        4. **shed admissions** — implicit: the latched ``active`` flag
           holds the wave loop and sheds/refuses new submits
           (:meth:`_shed_check`) until reclaim reaches the low
           watermark."""
        idx = self._prefix_index
        if pc.active and idx is not None and idx.total_bytes > 0:
            target = max(0, idx.total_bytes - pc.overshoot_bytes())
            # rung 1 is DEMOTE, not evict, when the host tier is on:
            # victims are collected under the index lock, then pulled to
            # host + SKV1-encoded into the tier out here (the slow part
            # must not hold readers off the radix walk)
            demoted: Optional[List] = (
                [] if self._kv_tier is not None else None
            )
            evicted = idx.evict_to(target, collect=demoted)
            if evicted:
                self.stats["prefix_evicted"] += evicted
                self.stats["pressure_prefix_evictions"] += evicted
                self.stats["prefix_cache_bytes"] = idx.total_bytes
                if demoted:
                    self._demote_prefix_slabs(demoted)
                if self.flight is not None and self.flight.enabled:
                    self.flight.record({
                        "type": "pressure_reclaim",
                        "action": "evict_prefix",
                        "evicted": evicted,
                        "demoted": len(demoted) if demoted else 0,
                        "used_bytes": pc.used,
                    })
                pc.update(self._ledger_components())
        if (
            pc.active
            and self._spec_burst_fn is not None
            and not self._spec_suppressed
            and all(
                s.request.temperature == 0.0 for s in self._active.values()
            )
            and all(
                j.request.temperature == 0.0 for j in self._chunked.values()
            )
        ):
            self._drain_pending(pending)
            self._suppress_speculation()
            pc.update(self._ledger_components())
        if pc.active and len(self._active) + len(self._chunked) > 1:
            self._drain_pending(pending)
            # the drain may have finished lanes outright
            pc.update(self._ledger_components())
            while pc.active and len(self._active) + len(self._chunked) > 1:
                victim = self._pick_victim()
                if victim is None:
                    break
                kind, slot = victim
                if kind == "chunked":
                    self._preempt_chunked(slot)
                else:
                    self._preempt_lane(slot)
                pc.update(self._ledger_components())

    @scheduler_only
    def _admit_cost_bytes(self, req: GenRequest) -> int:
        """Projected END-of-generation ledger footprint of admitting
        ``req``: the attention bucket its final position will need,
        priced per token. The watermark-aware admission check uses it so
        a lane that must inevitably trip the high watermark is held at
        the head of the line instead of admitted-then-preempted (the
        thrash the hysteresis gap exists to prevent)."""
        draft_per_tok = 0
        if self.speculate_tokens > 0 and not self._spec_suppressed:
            draft_per_tok = self._draft_kv_key_bytes
        end = min(self.max_seq, len(req.tokens) + req.max_new_tokens)
        need = self._attn_need(end)
        return self._lane_bytes(need) + need * draft_per_tok

    @scheduler_only
    def _pick_victim(self):
        """Deadline/progress-aware victim choice: chunked admissions
        first (no tokens emitted yet — preemption loses zero work and
        frees a whole staging slab), then decode lanes — best-effort
        SLO class before everything else (a multi-tenant server sheds
        its cheapest tenant's work first), deadline-free lanes before
        deadline-bearing ones (a lane that must answer soon is spared
        as long as anything else can give way), most remaining
        generation budget first within each class (the lane that would
        hold its slot longest yields it; lanes close to done are left
        to finish and free themselves).

        Tenant guard (extends the never-last-lane rule): while any
        best-effort tenant still has a preemptible lane, the ONLY live
        lane of a ``strict`` tenant is never chosen — preempting it
        would zero an SLO-critical tenant's progress to make room it
        could have taken from discountable work instead. If every
        candidate is protected (e.g. all lanes are strict singletons)
        the guard stands down and the base policy applies: pressure
        relief must still be possible."""
        if self._chunked:
            slot = max(
                self._chunked, key=lambda s: self._chunked[s].bucket
            )
            return ("chunked", slot)
        if len(self._active) <= 1:
            return None
        now = time.monotonic()

        lanes_per_tenant: Dict[Optional[str], int] = {}
        has_best_effort = False
        for s in self._active.values():
            req = s.request
            if req.tenant is not None:
                lanes_per_tenant[req.tenant] = (
                    lanes_per_tenant.get(req.tenant, 0) + 1
                )
            if req.slo == "best_effort":
                has_best_effort = True

        def protected(slot: int) -> bool:
            req = self._active[slot].request
            return (
                has_best_effort
                and req.slo == "strict"
                and req.tenant is not None
                and lanes_per_tenant.get(req.tenant, 0) <= 1
            )

        candidates = [s for s in self._active if not protected(s)]
        if not candidates:
            candidates = list(self._active)

        def order(slot: int):
            s = self._active[slot]
            req = s.request
            slack = (
                req.deadline_t - now if req.deadline_t is not None else None
            )
            return (
                # best_effort sorts lowest → preempted first; the
                # default "standard" keeps the pre-tenant ordering
                # byte-identical for single-tenant servers
                0 if req.slo == "best_effort" else 1,
                0 if slack is None else 1,
                -(slack if slack is not None else 0.0),
                -(req.max_new_tokens - len(s.emitted)),
            )

        return ("lane", min(candidates, key=order))

    @scheduler_only
    def _preempt_chunked(self, slot: int) -> None:
        """Preempt a mid-chunked-prefill admission: drop the staging
        slab and requeue the request whole (no tokens were emitted, so
        a fresh admit reproduces the identical stream from the seed)."""
        job = self._chunked.pop(slot)
        req = job.request
        self.stats["preemptions"] += 1
        if self.flight is not None and self.flight.enabled:
            self.flight.record({
                "type": "preempt", "lane": slot, "kind": "chunked",
                "prompt_tokens": len(req.tokens), "emitted": 0,
            })
        self._emit_span(req, "gen.preempt", time.monotonic(),
                        time.monotonic(), tags={"lane": slot,
                                                "kind": "chunked"})
        self._resume_queue.append(req)

    @scheduler_only
    def _checkpoint_lane(self, slot: int) -> Tuple[_Slot, GenRequest]:
        """Checkpoint one decode lane to host and free it: generated
        tokens + the lane's post-split RNG key + the sampling params
        already on the request — NOT its K/V. The slot and its cache
        columns free at this poll boundary. The caller has drained the
        pipeline, so ``emitted`` and the device state agree exactly;
        the one tiny host read here (an [2] uint32 key) is the whole
        checkpoint cost. Shared by pressure preemption
        (:meth:`_preempt_lane`), the hot-swap straggler bound, and
        graceful drain (:meth:`_do_drain`)."""
        s = self._active.pop(slot)
        req = s.request
        # the lane's CURRENT key — sampling resumes mid-stream from it,
        # which is what makes seeded-sampling output byte-identical
        # checkpoint-on vs off
        key = np.asarray(self._keys[slot]).astype(np.uint32).tolist()  # seldon-lint: disable=host-sync-hot-path (preemption/drain checkpoint: one 8-byte key read at a rare reclaim point, pipeline already drained)
        self._pos_host.pop(slot, None)
        self._masks_dirty = True
        if s.emitted:
            req.resume = {"emitted": list(s.emitted), "key": key}
        return s, req

    @scheduler_only
    def _preempt_lane(self, slot: int) -> None:
        """Preempt one decode lane (pressure ladder rung 3): checkpoint
        to host via :meth:`_checkpoint_lane` and requeue for resume.
        With the host KV tier on, the lane's exact cache columns spill
        there too (budget allowing) so the resume is a copy-back insert;
        without it — or when the tier refuses/evicts — resume falls back
        to recompute + teacher-forced replay, byte-identical either
        way."""
        s, req = self._checkpoint_lane(slot)
        if self._kv_tier is not None and req.resume is not None:
            # spill the K/V BEFORE anything can reuse the slot's columns
            # (same poll, scheduler thread — nothing dispatched since
            # the drain)
            self._checkpoint_kv_to_tier(slot, req)
        self.stats["preemptions"] += 1
        if self.flight is not None and self.flight.enabled:
            self.flight.record({
                "type": "preempt", "lane": slot, "kind": "lane",
                "prompt_tokens": len(req.tokens),
                "emitted": len(s.emitted),
                "remaining": req.max_new_tokens - len(s.emitted),
            })
        self._emit_span(
            req, "gen.preempt", time.monotonic(), time.monotonic(),
            tags={"lane": slot, "emitted": len(s.emitted)},
        )
        self._resume_queue.append(req)

    @scheduler_only
    def _suppress_speculation(self) -> None:
        """Reclaim rung 2: free the draft cache and decode with plain
        bursts. Greedy lanes keep byte-identical streams (spec greedy IS
        the target argmax decode); the caller guarantees no stochastic
        lane is live. Restored by :meth:`_resume_speculation` when
        pressure clears."""
        self._spec_suppressed = True
        self._draft_cache = None
        if self.flight is not None and self.flight.enabled:
            self.flight.record({
                "type": "pressure_reclaim", "action": "cancel_speculation",
            })
        logger.warning(
            "HBM pressure: speculation cancelled (draft cache freed); "
            "plain decode bursts until the ledger clears"
        )

    @scheduler_only
    def _resume_speculation(self) -> None:
        """Pressure cleared: reallocate the draft cache and re-derive
        every live lane's draft prefix from prompt + generated-so-far
        (the draft K/V is a pure function of the tokens). Runs BEFORE
        admissions resume in the same poll, so no lane is ever admitted
        into a half-restored draft world."""
        self._draft_cache = self._unstack_cache(
            self.draft_model,
            self._cache_sharding_for(self.draft_model.cfg.n_kv_heads),
        )
        for slot, s in self._active.items():
            full = (
                s.request.tokens + s.emitted[:-1]
                if s.emitted else s.request.tokens
            )
            self._draft_admit_tokens(slot, full)
        self._spec_suppressed = False
        if self.flight is not None and self.flight.enabled:
            self.flight.record({
                "type": "pressure_reclaim", "action": "resume_speculation",
                "lanes": len(self._active),
            })

    @scheduler_only
    def _replay_emitted(self, slot: int, start_pos: int,
                        replay_toks: List[int]) -> None:
        """Teacher-forced decode replay: rebuild positions
        ``[start_pos, start_pos + len(replay_toks))`` of ``slot``'s
        cache from the already-emitted tokens, through the SAME fused
        decode step that wrote them originally (see replay_burst — a
        prefill recompute differs at bf16 and breaks byte-identity).
        Chunked to the burst length ``k`` so one executable exists per
        (k, attn_len), never per resume length."""
        import jax.numpy as jnp

        if not replay_toks:
            return
        k = self._k
        attn_len = self._attn_need(start_pos + len(replay_toks))
        lane_ix = jnp.asarray([slot], jnp.int32)
        for off in range(0, len(replay_toks), k):
            chunk = replay_toks[off:off + k]
            toks = np.zeros((k,), np.int32)
            toks[: len(chunk)] = chunk
            act = np.zeros((k,), bool)
            act[: len(chunk)] = True
            with self._prof.measure(
                "replay", variant=f"k{k}b{attn_len}", tenant="",
                bytes_read=self._param_bytes
                + len(chunk) * self._kv_key_bytes,
                tokens=len(chunk),
            ) as _m:
                self._cache = self._replay_fn(
                    self.params, self._cache, lane_ix, jnp.asarray(toks),
                    jnp.asarray(act), jnp.int32(start_pos + off), attn_len,
                )
                _m.sync(self._cache["k"])
        self.stats["steps"] += -(-len(replay_toks) // k) * k
        self.stats["lane_steps"] += -(-len(replay_toks) // k) * k

    @scheduler_only
    def _activate_resumed(self, slot: int, req: GenRequest,
                          emitted: List[int], replay: bool = True) -> None:
        """Shared tail of the plain, chunked, and tier-copy-back resume
        paths: replay the emitted tokens' K/V (``replay=False`` when a
        tier checkpoint already restored the exact cache columns),
        re-derive the draft prefix (speculation), and re-activate the
        lane with crediting continuing AFTER the checkpoint
        (already-delivered stream spans are never re-sent;
        first_pending False keeps the insert's token — emitted[-1] —
        from being credited twice)."""
        n = len(req.tokens)
        if replay:
            self._replay_emitted(slot, n, emitted[:-1])
        if self._spec_active():
            self._draft_admit_tokens(slot, req.tokens + emitted[:-1])
        s = _Slot(request=req)
        s.emitted = list(emitted)
        s.first_pending = False
        s.dispatched = len(emitted)
        self._active[slot] = s
        self._pos_host[slot] = n + len(emitted) - 1
        self._masks_dirty = True
        req.resume = None
        self.stats["preempt_resumes"] += 1
        if self.flight is not None and self.flight.enabled:
            self.flight.record({
                "type": "preempt_resume", "lane": slot,
                "prompt_tokens": n,
                "replayed_tokens": max(0, len(emitted) - 1) if replay else 0,
                "copyback": not replay,
                "emitted": len(emitted),
                "cache_hit_tokens": req.cache_hit_tokens,
            })

    @scheduler_only
    def _resume_from_tier(self, slot: int, req: GenRequest,
                          emitted: List[int], first_tok, lane_key,
                          end_pos: int, tier_key) -> bool:
        """Copy-back resume: take the lane's tier checkpoint (one-shot),
        ``device_put`` the stored cache columns, and insert them with
        the checkpointed continuation registers. True when the lane is
        live again; False sends the caller down the recompute+replay
        fallback (entry evicted, stale version, or corrupt — the tier
        already dropped a corrupt entry, typed, before any lane state
        was touched)."""
        from ..tracing import device_trace
        from .disagg import DisaggError, prompt_hash

        try:
            ent = self._kv_tier.take_ckpt(tier_key, self.weight_version)
        except DisaggError as e:
            logger.warning("kv tier checkpoint dropped: %s", e)
            return False
        if ent is None:
            return False
        meta, host = ent
        if int(meta.get("pos", -1)) != end_pos:
            # a drifted checkpoint must never splice: the registers and
            # the cache would disagree on where the lane is
            logger.warning(
                "kv tier checkpoint position %s != lane end %d — replaying",
                meta.get("pos"), end_pos,
            )
            return False
        slab_dev = self._upload_slab(host)
        with self._prof.measure(
            "insert", variant="tier", tenant=req.tenant or "",
            bytes_read=int(meta.get("width", 0)) * self._kv_key_bytes,
            tokens=end_pos,
        ) as _m, device_trace("gen.lane_insert"):
            self._cache, self._cur_tok, self._pos, self._keys = (
                self._insert_fn(
                    self._cache, slab_dev, slot, first_tok, end_pos,
                    lane_key, self._cur_tok, self._pos, self._keys,
                )
            )
            _m.sync(self._cur_tok)
        self.stats["kv_tier_promotions"] += 1
        if self.flight is not None and self.flight.enabled:
            self.flight.record({
                "type": "tier_hit", "kind": "ckpt", "source": "local",
                "lane": slot, "tokens": end_pos,
                "phash": prompt_hash(req.tokens)[:8],
            })
            self.flight.record({
                "type": "kv_promote", "kind": "ckpt", "source": "local",
                "lane": slot, "tokens": end_pos,
                "bytes": int(host["k"].nbytes) + int(host["v"].nbytes),
                "phash": prompt_hash(req.tokens)[:8],
            })
        self._activate_resumed(slot, req, emitted, replay=False)
        return True

    @scheduler_only
    def _admit_resume(self, slot: int, req: GenRequest) -> None:
        """Recompute-resume a preempted request: rebuild the PROMPT K/V
        through the ordinary admission machinery (bucketed prefill, a
        prefix-cache hit splicing naturally, or the PR 3 staging-slab
        chunked path for long prompts), insert with the checkpointed
        continuation state instead of the prefill's own sample —
        ``cur_tok`` = the last emitted token, ``pos`` = the exact device
        position the preempted lane held, ``key`` = the checkpointed
        post-split RNG key — then replay the emitted tokens' K/V with
        the decode step itself (:meth:`_replay_emitted`). Decode from
        there is the same computation the uninterrupted lane would have
        run, so greedy AND seeded-sampling outputs are byte-identical
        preempt-on vs off."""
        import jax.numpy as jnp

        from ..tracing import device_trace

        ck = req.resume
        emitted = list(ck["emitted"])
        n = len(req.tokens)
        end_pos = n + len(emitted) - 1
        first_tok = jnp.int32(int(emitted[-1]))
        lane_key = jnp.asarray(np.asarray(ck["key"], np.uint32))
        t_admit = time.monotonic()
        # the tier key is POPPED here whatever happens next: take_ckpt
        # is one-shot, so a later re-preemption must re-checkpoint under
        # a fresh key — a stale key left behind would make the next
        # resume count a phantom replay fallback
        tier_key = (
            ck.pop("tier", None) if self._kv_tier is not None else None
        )
        if tier_key is not None:
            # copy-back fast path: the preemption spilled this lane's
            # exact cache columns to the host tier — device_put them
            # back through the ordinary insert executable (cur_tok/pos/
            # key restored to the checkpointed registers) and skip BOTH
            # the prompt recompute and the teacher-forced replay. The
            # restored bytes are the bytes the lane held, so decode from
            # here is the identical computation either way.
            if self._resume_from_tier(slot, req, emitted, first_tok,
                                      lane_key, end_pos, tier_key):
                self._emit_span(
                    req, "gen.resume", t_admit, time.monotonic(),
                    tags={"lane": slot, "emitted": len(emitted),
                          "copyback": True},
                )
                return
            # the tier evicted/refused/corrupted the entry: recompute +
            # replay below is the documented fallback — count it so the
            # "spill, don't destroy" win stays measurable
            self.stats["kv_tier_replay_fallbacks"] += 1
        hit = self._prefix_match(req)
        C = self.prefill_chunk
        if C and (
            (hit is None and self._bucket(n) > C)
            or (hit is not None and n - hit[0] > C)
        ):
            # long prompt: rebuild through the SAME staging-slab chunked
            # path the original admission used (byte-identity again —
            # chunked and whole prefill K/V need not agree at bf16)
            self._start_chunked(slot, req, hit=hit,
                                resume=(emitted, lane_key))
            self._emit_span(
                req, "gen.resume", t_admit, time.monotonic(),
                tags={"lane": slot, "emitted": len(emitted),
                      "chunked": True},
            )
            return
        if hit is not None:
            m, slab = hit
            wb = self._bucket(n - m)
            suffix = np.zeros((1, wb), np.int32)
            suffix[0, : n - m] = req.tokens[m:]
            with self._prof.measure(
                "prefill", variant=f"px{wb}", tenant=req.tenant or "",
                bytes_read=self._param_bytes + wb * self._kv_key_bytes,
                tokens=wb,
            ) as _m, device_trace("gen.prefill"):
                _f, suffix_slab, _k = self._prefix_prefill_fn(
                    self.params, slab, jnp.asarray(suffix), jnp.int32(m),
                    jnp.asarray([n - 1 - m], jnp.int32),
                    jnp.int32(req.seed), jnp.float32(req.temperature),
                )
                _m.sync(suffix_slab)
            with self._prof.measure(
                "insert", variant=f"px{wb}", tenant=req.tenant or "",
                bytes_read=(m + wb) * self._kv_key_bytes, tokens=end_pos,
            ) as _m, device_trace("gen.lane_insert"):
                self._cache, self._cur_tok, self._pos, self._keys = (
                    self._insert_prefix_fn(
                        self._cache, slab, suffix_slab, slot, jnp.int32(m),
                        first_tok, end_pos, lane_key,
                        self._cur_tok, self._pos, self._keys,
                    )
                )
                _m.sync(self._cur_tok)
            req.cache_hit_tokens = m
            self.stats["prefix_hits"] += 1
            self.stats["prefix_tokens_saved"] += m
            self.stats["prefill_steps"] += 1
            self.stats["prefill_tokens"] += wb
            self.stats["prefill_prompt_tokens"] += n - m
        else:
            bucket = self._bucket(n)
            prompt = np.zeros((1, bucket), np.int32)
            prompt[0, :n] = req.tokens
            with self._prof.measure(
                "prefill", variant=f"p{bucket}", tenant=req.tenant or "",
                bytes_read=self._param_bytes + self._lane_bytes(bucket),
                tokens=bucket,
            ) as _m, device_trace("gen.prefill"):
                _f, cache_one, _k, *_ = self._prefill_fn(
                    self.params, jnp.asarray(prompt),
                    jnp.asarray([n - 1], jnp.int32),
                    jnp.int32(req.seed), jnp.float32(req.temperature),
                )
                _m.sync(cache_one)
            with self._prof.measure(
                "insert", variant=f"b{bucket}", tenant=req.tenant or "",
                bytes_read=self._lane_bytes(bucket), tokens=end_pos,
            ) as _m, device_trace("gen.lane_insert"):
                self._cache, self._cur_tok, self._pos, self._keys = (
                    self._insert_fn(
                        self._cache, cache_one, slot, first_tok, end_pos,
                        lane_key, self._cur_tok, self._pos, self._keys,
                    )
                )
                _m.sync(self._cur_tok)
            if self._prefix_index is not None:
                self.stats["prefix_misses"] += 1
            self.stats["prefill_steps"] += 1
            self.stats["prefill_tokens"] += bucket
            self.stats["prefill_prompt_tokens"] += n
        self._activate_resumed(slot, req, emitted)
        self._emit_span(
            req, "gen.resume", t_admit, time.monotonic(),
            tags={"lane": slot, "emitted": len(emitted),
                  "cache_hit_tokens": req.cache_hit_tokens},
        )

    @scheduler_only
    def _admit(self, slot: int, req: GenRequest, hit=None) -> None:
        # ``hit``: a (match_len, slab) the wave-routing loop already
        # computed — passed through so the radix walk (and its LRU touch)
        # runs once per admission, not twice
        import jax.numpy as jnp

        from ..tracing import device_trace

        n = len(req.tokens)
        t_admit = time.monotonic()
        req.admit_t = t_admit
        if hit is None:
            hit = self._prefix_match(req)
        if hit is not None:
            # cache hit: splice the donor slab, prefill ONLY the suffix
            # (same bucketed machinery, on the shorter remainder)
            m, slab = hit
            wb = self._bucket(n - m)
            suffix = np.zeros((1, wb), np.int32)
            suffix[0, : n - m] = req.tokens[m:]
            with self._prof.measure(
                "prefill", variant=f"px{wb}", tenant=req.tenant or "",
                bytes_read=self._param_bytes + wb * self._kv_key_bytes,
                tokens=wb,
            ) as _m, device_trace("gen.prefill"):
                first, suffix_slab, lane_key = self._prefix_prefill_fn(
                    self.params,
                    slab,
                    jnp.asarray(suffix),
                    jnp.int32(m),
                    jnp.asarray([n - 1 - m], jnp.int32),
                    jnp.int32(req.seed),
                    jnp.float32(req.temperature),
                )
                _m.sync(suffix_slab)
            t_insert = time.monotonic()
            with self._prof.measure(
                "insert", variant=f"px{wb}", tenant=req.tenant or "",
                bytes_read=(m + wb) * self._kv_key_bytes, tokens=n,
            ) as _m, device_trace("gen.lane_insert"):
                self._cache, self._cur_tok, self._pos, self._keys = (
                    self._insert_prefix_fn(
                        self._cache, slab, suffix_slab, slot, jnp.int32(m),
                        first[0], n, lane_key,
                        self._cur_tok, self._pos, self._keys,
                    )
                )
                _m.sync(self._cur_tok)
            req.cache_hit_tokens = m
            self.stats["prefix_hits"] += 1
            self.stats["prefix_tokens_saved"] += m
            self.stats["prefill_steps"] += 1
            self.stats["prefill_tokens"] += wb
            self.stats["prefill_prompt_tokens"] += n - m
            self._emit_span(
                req, "gen.prefill", t_admit, t_insert,
                tags={"lane": slot, "bucket": wb, "cache_hit_tokens": m,
                      "dispatch": True},
            )
        else:
            bucket = self._bucket(n)
            prompt = np.zeros((1, bucket), np.int32)
            prompt[0, :n] = req.tokens
            with self._prof.measure(
                "prefill", variant=f"p{bucket}", tenant=req.tenant or "",
                bytes_read=self._param_bytes + self._lane_bytes(bucket),
                tokens=bucket,
            ) as _m, device_trace("gen.prefill"):
                first, cache_one, lane_key, *counts = self._prefill_fn(
                    self.params,
                    jnp.asarray(prompt),
                    jnp.asarray([n - 1], jnp.int32),
                    jnp.int32(req.seed),
                    jnp.float32(req.temperature),
                )
                _m.sync(cache_one)
            t_insert = time.monotonic()
            with self._prof.measure(
                "insert", variant=f"b{bucket}", tenant=req.tenant or "",
                bytes_read=self._lane_bytes(bucket), tokens=n,
            ) as _m, device_trace("gen.lane_insert"):
                (self._cache, self._cur_tok, self._pos, self._keys,
                 *self._prefill_counts) = self._insert_fn(
                    self._cache, cache_one, slot, first[0],
                    self._lane_start(n), lane_key,
                    self._cur_tok, self._pos, self._keys,
                    *self._prefill_counts, *counts,
                )
                _m.sync(self._cur_tok)
            if self._prefix_index is not None:
                self.stats["prefix_misses"] += 1
            self.stats["prefill_steps"] += 1
            self.stats["prefill_tokens"] += bucket
            self.stats["prefill_prompt_tokens"] += n
            self._emit_span(
                req, "gen.prefill", t_admit, t_insert,
                tags={"lane": slot, "bucket": bucket, "dispatch": True},
            )
        t_inserted = time.monotonic()
        req.insert_t = t_insert
        req.decode_start_t = t_inserted
        self._emit_span(req, "gen.lane_insert", t_insert, t_inserted,
                        tags={"lane": slot, "dispatch": True})
        self._emit_span(
            req, "gen.queue_wait", req.submit_t, t_admit,
            tags={"lane": slot,
                  "cache_hit_tokens": req.cache_hit_tokens},
        )
        if self._spec_active():
            # the draft needs the prompt's K/V prefix too so its proposals
            # attend over the real context (see _draft_admit: re-derived
            # from the full prompt, never cached — the radix pool holds
            # only target K/V)
            self._draft_admit(slot, req)
        # no host read here: prefill + insert stay fully async; the first
        # token reaches the host with the next burst's sync
        self._active[slot] = _Slot(request=req)
        self._pos_host[slot] = self._lane_start(n)
        self._block_admit([slot], [req])
        self._masks_dirty = True
        self._count_admitted(req)

    def _lane_start(self, n):
        """Where a lane that holds a prompt of ``n`` tokens starts to
        decode: at ``n``, or at the first position of the block that holds
        the prompt's tail (or follows its last whole block) for a family
        that generates by blocks."""
        return n - n % self._block_w

    def _block_admit(self, slots: List[int], reqs: List[GenRequest]) -> None:
        """Generation by blocks: the first block of each lane an insert has
        just filled, into the lanes' block registers (one small dispatch
        behind the insert; nothing for every other family). The block
        starts as the prompt's tail, ``[MASK]`` elsewhere; which is which
        is the lane's mask bits, whatever ids the prompt holds."""
        W = self._block_w
        if W == 1:
            return
        import jax.numpy as jnp

        m = len(reqs)
        tok = np.full((m, W), self.model.cfg.mask_token_id, np.int32)
        masked = np.ones((m, W), bool)
        skip = np.zeros((m,), np.int32)
        budget = np.zeros((m,), np.int32)
        stops = np.full((m,), -1, np.int32)
        for i, req in enumerate(reqs):
            tail = len(req.tokens) % W
            if tail:
                tok[i, :tail] = req.tokens[-tail:]
                masked[i, :tail] = False
            skip[i] = tail
            budget[i] = tail + req.max_new_tokens
            if req.eos_id is not None:
                stops[i] = int(req.eos_id)
        self._block_regs = self._block_insert_fn(
            self._block_regs, jnp.asarray(np.asarray(slots, np.int32)),
            *(jnp.asarray(a) for a in (tok, masked, skip, budget, stops)))

    @scheduler_only
    def _admit_many(self, slots: List[int], reqs: List[GenRequest], bucket: int) -> None:
        """Admit m same-bucket requests with ONE batched prefill forward +
        ONE batched insert (see prefill_many). Only used without
        speculation — the draft cache path stays per-request."""
        import jax.numpy as jnp

        from ..tracing import device_trace

        m = len(reqs)
        t_admit = time.monotonic()
        prompts = np.zeros((m, bucket), np.int32)
        last = np.zeros((m,), np.int32)
        seeds = np.zeros((m,), np.int32)
        temps = np.zeros((m,), np.float32)
        for i, req in enumerate(reqs):
            n = len(req.tokens)
            prompts[i, :n] = req.tokens
            last[i] = n - 1
            seeds[i] = req.seed
            temps[i] = req.temperature
        _wave_tenant = ""
        if self._prof.enabled:
            _ts = {req.tenant for req in reqs}
            if len(_ts) == 1 and None not in _ts:
                _wave_tenant = _ts.pop() or ""
        with self._prof.measure(
            "prefill", variant=f"m{m}p{bucket}", tenant=_wave_tenant,
            bytes_read=self._param_bytes + m * self._lane_bytes(bucket),
            tokens=m * bucket,
        ) as _pm, device_trace("gen.prefill"):
            firsts, slab, lane_keys, *counts = self._prefill_many_fn(
                self.params, jnp.asarray(prompts), jnp.asarray(last),
                jnp.asarray(seeds), jnp.asarray(temps),
            )
            _pm.sync(slab)
        t_insert = time.monotonic()
        with self._prof.measure(
            "insert", variant=f"m{m}b{bucket}", tenant=_wave_tenant,
            bytes_read=m * self._lane_bytes(bucket), tokens=m * bucket,
        ) as _im, device_trace("gen.lane_insert"):
            (self._cache, self._cur_tok, self._pos, self._keys,
             *self._prefill_counts) = self._insert_many_fn(
                self._cache, slab, jnp.asarray(np.asarray(slots, np.int32)),
                firsts, jnp.asarray(self._lane_start(last + 1)), lane_keys,
                self._cur_tok, self._pos, self._keys,
                *self._prefill_counts, *counts,
            )
            _im.sync(self._cur_tok)
        t_inserted = time.monotonic()
        for slot, req in zip(slots, reqs):
            req.admit_t = t_admit
            req.insert_t = t_insert
            req.decode_start_t = t_inserted
            self._emit_span(
                req, "gen.queue_wait", req.submit_t, t_admit,
                tags={"lane": slot, "batched": m},
            )
            self._emit_span(
                req, "gen.prefill", t_admit, t_inserted,
                tags={"lane": slot, "bucket": bucket, "batched": m,
                      "dispatch": True},
            )
            self._active[slot] = _Slot(request=req)
            self._pos_host[slot] = self._lane_start(len(req.tokens))
        self._block_admit(slots, reqs)
        self._masks_dirty = True
        self._count_admitted(*reqs)
        self.stats["prefill_steps"] += 1
        self.stats["prefill_tokens"] += m * bucket
        self.stats["prefill_prompt_tokens"] += sum(
            len(req.tokens) for req in reqs)
        if self._prefix_index is not None:
            self.stats["prefix_misses"] += m

    @scheduler_only
    def _resolve(self, s: _Slot) -> None:
        # a trailing eos token is kept in the output, like HF generate.
        # `finished` counts requests that ran to completion; `cancelled`
        # counts abandonments (queued or mid-decode) — disjoint, so
        # finished + cancelled = all requests ever resolved
        s.credit_done = True
        req = s.request
        now = req.done_t = time.monotonic()
        if req.future.cancelled():
            self.stats["cancelled"] += 1
            if req.admit_t:
                # the lane was reclaimed mid-decode (client disconnect /
                # deadline): the timeline still shows the residency it
                # burned, attributed as a cancellation
                self._emit_span(
                    req, "gen.decode", req.decode_start_t or req.admit_t, now,
                    tags={"outcome": "cancelled", "tokens": len(s.emitted)},
                )
            return
        # SLO sample: queue wait / TTFT / TPOT of this completed request.
        # TTFT and queue wait are submit-anchored (what the client saw);
        # TPOT averages the inter-token gap over the credited stream.
        # Recorded BEFORE set_result: resolving the future wakes the
        # predict thread, whose response path drains slo_pending via
        # metrics() — the sample and the gen.decode span must already
        # exist so a request's own response carries its own triple.
        if req.submit_t:
            n_tok = len(s.emitted)
            first = req.first_tok_t or now
            queue_wait = max(0.0, (req.admit_t or now) - req.submit_t)
            ttft = max(0.0, first - req.submit_t)
            # a 1-token generation has no inter-token interval: tpot is
            # None so the reservoir percentiles, the TIMER export, and
            # the span tag all skip it the same way instead of counting
            # a meaningless 0.0 in some views but not others
            tpot = (now - first) / (n_tok - 1) if n_tok > 1 else None
            self.stats["slo_samples"] += 1
            self.stats["ttft_s_sum"] += ttft
            self.slo_pending.append((queue_wait, ttft, tpot))
            self.slo_recent.append((queue_wait, ttft, tpot))
            self.timeline_recent.append((
                req.rid, len(req.tokens), self._bucket(len(req.tokens)),
                n_tok, req.cache_hit_tokens, req.submit_t, req.admit_t,
                req.decode_start_t, req.first_dispatch_t, req.first_tok_t,
                now, req.insert_t, req.admit_poll, req.front,
            ))
            if req.tenant is not None:
                # per-tenant split of the same triple: the TenantScheduler
                # reads tenant_slo_recent as its TTFT feedback signal and
                # the server drains tenant_slo_pending into tagged TIMER
                # metrics — one sample feeds both, recorded here so a
                # tenant's own response carries its own numbers
                self.tenant_slo.setdefault(
                    req.tenant, {"finished": 0.0})["finished"] += 1
                self.tenant_slo_pending.setdefault(
                    req.tenant, collections.deque(maxlen=1024)
                ).append((queue_wait, ttft, tpot))
                self.tenant_slo_recent.setdefault(
                    req.tenant, collections.deque(maxlen=512)
                ).append((queue_wait, ttft, tpot))
            if req.admit_t:
                tags = {"outcome": "complete", "tokens": n_tok,
                        "ttft_ms": round(ttft * 1e3, 3)}
                if tpot is not None:
                    tags["tpot_ms"] = round(tpot * 1e3, 3)
                self._emit_span(
                    req, "gen.decode", req.decode_start_t or req.admit_t,
                    now, tags=tags,
                )
        if not req.future.done():
            req.future.set_result(req.tokens + s.emitted)
        self.stats["finished"] += 1
        # completion timestamp feeds the observed service rate that the
        # admit-queue shed uses for its expected-wait estimate
        self._finish_times.append(now)

    @scheduler_only
    def _finish(self, slot: int) -> None:
        s = self._active.pop(slot)
        # publish while the lane still holds this request's prompt K/V —
        # the next occupant's insert is dispatched after the extract, so
        # stream order keeps the slab coherent
        self._maybe_publish(slot, s)
        self._pos_host.pop(slot, None)
        self._masks_dirty = True
        self._resolve(s)

    @scheduler_only
    def _check_done(self) -> None:
        for slot in list(self._active):
            s = self._active[slot]
            req = s.request
            if req.future.cancelled():
                # caller gave up (client disconnect / deadline): reclaim the
                # lane instead of decoding the rest of its budget for no one
                self._finish(slot)
                continue
            if len(s.emitted) >= req.max_new_tokens or (
                req.eos_id is not None and s.emitted and s.emitted[-1] == req.eos_id
            ):
                self._finish(slot)

    @scheduler_only
    def _credit(self, s: _Slot, tokens) -> bool:
        """Append tokens to a request; True once it is done (budget/eos —
        the caller drops the rest of the burst's tokens for this lane)."""
        req = s.request
        start = len(s.emitted)
        if start == 0 and len(tokens) and req.first_tok_t == 0.0:
            # first span of credited tokens = the client-visible TTFT
            # moment (a float store per REQUEST, not per token)
            req.first_tok_t = time.monotonic()
            if req.trace is not None:
                held_from = req.decode_start_t or req.admit_t
                self._emit_span(
                    req, "gen.first_token_hold", held_from, req.first_tok_t,
                    tags={"first_dispatch_ms": round(
                        (req.first_dispatch_t - held_from) * 1e3, 3)},
                )
        done = False
        for t in tokens:
            s.emitted.append(int(t))
            self.stats["tokens"] += 1
            if len(s.emitted) >= req.max_new_tokens or (
                req.eos_id is not None and int(t) == req.eos_id
            ):
                done = True
                break
        if req.on_tokens is not None and len(s.emitted) > start:
            try:
                req.on_tokens(list(s.emitted[start:]))
            except Exception:  # noqa: BLE001 - consumer bugs can't stall decode
                logger.exception("on_tokens callback failed")
        return done

    @scheduler_only
    def _process_burst(self, host_toks, snapshot) -> None:
        """Credit one burst's tokens to the requests that occupied each lane
        AT DISPATCH TIME. Bursts execute on the device stream in dispatch
        order and any re-admission insert is dispatched after them, so the
        snapshot occupant is always the request the rows belong to — even
        when the lane was pre-freed and re-admitted before this read. A
        request whose output is already complete (``credit_done``) is
        skipped: its remaining rows are overshoot decode, dropped by
        design. ``snapshot[slot] = (s, start_row)``; the lane's column in
        the burst's token matrix is its slot. The arrays are on the host
        already (:meth:`_read_burst`)."""
        for slot, (s, start) in snapshot.items():
            if s.credit_done:
                continue
            if self._credit(s, host_toks[start:, slot]):
                if self._active.get(slot) is s:
                    self._finish(slot)
                else:
                    self._resolve(s)  # lane was pre-freed at dispatch time
        self._check_done()

    @scheduler_only
    def _process_fused_burst(self, host_toks, counts, done, snapshot,
                             k) -> None:
        """Credit one stop-aware fused burst. Per lane, exactly
        ``counts[slot]`` tokens were emitted before its on-device done
        mask froze it (stop token / budget), so — unlike
        :meth:`_process_burst` — no overshoot rows exist to drop; the
        host just credits the counted span (row 0 still carries the
        deferred prefill first token). ``done`` is the device's own
        verdict; crediting re-derives it from the tokens (``_credit``
        checks eos/budget per token), so the two can never disagree
        without the identity tests catching it. Like
        :meth:`_credit_rounds`, tightens the host position bound
        from the worst-case k advance to the lane's actual alive steps —
        a lane frozen early must not inflate the pressure ledger or the
        attention-bucket need until the host observes it."""
        for slot, (s, start) in snapshot.items():
            if self._active.get(slot) is s and slot in self._pos_host:
                self._pos_host[slot] -= k - int(counts[slot])
            if s.credit_done:
                continue
            span = host_toks[start: 1 + int(counts[slot]), slot]
            if not len(span):
                continue
            if self._credit(s, span):
                if self._active.get(slot) is s:
                    self._finish(slot)
                else:
                    self._resolve(s)  # lane was pre-freed at dispatch time
        self._check_done()

    @scheduler_only
    def _credit_rounds(self, host_toks, counts, snapshot, worst, width=0,
                       start_tok=None) -> None:
        """Credit a burst whose rounds each emit a number of tokens of their
        own a lane: speculation's rounds (accepted drafts + the target's
        correction, under the deferred first token ``start_tok``) and
        generation by blocks' passes (a commit's block, else nothing).
        ``host_toks`` [k, S, W] holds a round's tokens first in its row,
        ``counts`` [k, S] how many. Also tightens the host position bound
        from the ``worst`` the dispatch assumed to what the lane advanced:
        the tokens it emitted, or ``width`` positions a round that emitted
        any (a block: its first may hold a prompt's tail, sent to nobody).
        A block is one span of tokens to the client and one ``gen.block``
        span of its request."""
        for slot, (s, start) in snapshot.items():
            if self._active.get(slot) is not s:
                continue
            n = counts[:, slot]
            if slot in self._pos_host:
                self._pos_host[slot] -= worst - (
                    width * int((n > 0).sum()) if width else int(n.sum()))
            req = s.request
            done = start_tok is not None and start == 0 and self._credit(
                s, [int(start_tok[slot])])
            for r in np.flatnonzero(n):
                if done:
                    break
                had = len(s.emitted)
                done = self._credit(s, host_toks[r, slot, : int(n[r])])
                if width and req.trace is not None:
                    # the block's span: from the block before it (the
                    # lane's insert, for the first) to its tokens' credit
                    now = time.monotonic()
                    self._emit_span(
                        req, "gen.block", s.block_t or req.decode_start_t,
                        now, tags={"tokens": len(s.emitted) - had,
                                   "emitted": len(s.emitted)})
                    s.block_t = now
        self._check_done()

    @scheduler_only
    def _dispatch_block_burst(self, active_dev, temps_dev, pending):
        """Generation by blocks: one burst of ``_k`` passes over every live
        lane's block (``fused_burst_blocks``), dispatched and queued on
        ``pending`` as the plain burst is. A pass commits a block or
        not, so what a lane advances is known only when the burst is read
        (:meth:`_credit_rounds`): until then its host position is
        the most it can be, a commit every second pass. Returns the poll
        record's plan and the dispatch time."""
        from ..tracing import device_trace

        k, W = self._k, self._block_w
        worst = W * ((k + 1) // 2)
        # the cache read's bound: the deepest lane's last possible block
        attn_len = self._attn_need(
            max(self._pos_host[i] for i in self._active) + worst + W)
        lanes = sorted(self._active)
        snapshot = {}
        t_dispatch = time.monotonic()
        for slot in lanes:
            s = self._active[slot]
            snapshot[slot] = (s, 0)
            if s.first_pending:
                s.request.first_dispatch_t = t_dispatch
            s.first_pending = False
            self._pos_host[slot] += worst
        read_bytes = self.model.dispatch_read_bytes(
            "decode_burst", rows=self.slots, live=len(lanes), k=k,
            bucket=attn_len, param_bytes=self._param_bytes,
            kv_row_bytes=self._kv_key_bytes)
        with self._prof.measure(
            "decode_burst", variant=f"w{W}b{attn_len}",
            tenant=self._burst_tenant() if self._prof.enabled else "",
            bytes_read=read_bytes, tokens=k * self.slots * W,
        ) as _m, device_trace("gen.decode_burst"):
            (toks, counts, _bits, self._block_regs, self._pos, self._cache,
             self._keys, *extra) = self._block_burst_fn(
                self.params, self._cache, self._block_regs, self._pos,
                active_dev, temps_dev, self._keys, k,
                None if self._ragged_read else attn_len, self._any_stoch)
            _m.sync(toks)
        burst = ("block",
                 (toks, counts, *self._take_prefill_counts(), *extra),
                 (snapshot, k, worst), t_dispatch)
        self.stats["steps"] += k
        self.stats["lane_steps"] += k * self.slots
        self.stats["burst_reads"] += 1
        self.stats["burst_read_bytes"] += read_bytes
        rows = len(lanes) * k * W * self._position_layers
        self.stats["kv_rows_written"] += rows
        if self._ragged_read:
            self.stats["kv_rows_written_in_kernel"] += rows
        for t in burst[1]:
            try:
                t.copy_to_host_async()
            except AttributeError:  # non-jax (test doubles)
                pass
        pending.append(burst)
        return {"mode": "block", "k": k, "lanes": len(lanes),
                "bucket": attn_len}, t_dispatch

    def _run(self) -> None:
        """Scheduler thread entrypoint: the supervision shell around the
        poll loop. A clean ``close()`` exits; a loop death fails in-flight
        work with a typed :class:`BatcherDead` and — crash-loop budget
        permitting — rebuilds the device state and resumes, so a
        transient device/driver fault costs seconds, not a pod."""
        self._started.set()
        self._clock.start()
        if self._host is not None:
            self._host.start()
            self._host.beat.start()
        try:
            while not self._stop.is_set():
                if not self._loop():
                    return
        finally:
            self._clock.stop()
            if self._host is not None:
                self._host.beat.stop()
                self._host.stop()

    @scheduler_only
    def _fail_inflight(self, pending, err: Exception) -> None:
        """Fail every request the dead loop had in flight: active lanes,
        pre-freed lanes living only in pending-burst snapshots (without
        this sweep their callers would block forever), and chunked
        admissions holding reserved lanes but no ``_active`` entry.
        Queued-not-admitted requests are NOT drained here — their prompts
        are host-side, so they survive a supervised restart and only fail
        once the batcher latches dead."""
        for slot in list(self._active):
            s = self._active.pop(slot)
            if not s.request.future.done():
                s.request.future.set_exception(err)
        for _mode, _arrays, rest, _t in pending:
            for entry in rest[0].values():
                s = entry[0]
                if not s.request.future.done():
                    s.request.future.set_exception(err)
        for slot in list(self._chunked):
            job = self._chunked.pop(slot)
            if not job.request.future.done():
                job.request.future.set_exception(err)

    @scheduler_only
    def _crash_recover(self, pending) -> bool:
        """Supervise one loop death (scheduler thread). True = the loop
        may resume on rebuilt device state; False = the batcher is done
        for good — the crash-loop budget is exhausted (``health``
        latches ``"dead"``, readiness goes red, the reconciler replaces
        this member) or ``close()`` landed mid-backoff. A failed rebuild
        (the device may still be sick) consumes another budget slot and
        backs off again."""
        while True:
            now = time.monotonic()
            if (self._last_crash_t
                    and now - self._last_crash_t > self.restart_window_s):
                self._restarts = 0  # served healthily long enough
            self._last_crash_t = now
            self._restarts += 1
            attempt = self._restarts
            exhausted = attempt > self.restart_budget
            backoff = min(
                self.restart_backoff_s * (2 ** (attempt - 1)), 30.0
            )
            if exhausted:
                self.health = "dead"
                err = self._dead_error()
            else:
                self.health = "restarting"
                err = BatcherDead(
                    f"continuous batcher died; restarting "
                    f"(attempt {attempt}/{self.restart_budget})",
                    retry_after_s=max(backoff, 0.5),
                )
            self._fail_inflight(pending, err)
            pending = ()  # later iterations have nothing new in flight
            self._fail_pending_swap(err)
            # a drain staged when the loop died cannot complete: fail it
            # typed (the supervisor's health writes below replace the
            # "draining" latch, so a successful restart resumes service)
            self._fail_pending_drain(err)
            if self.flight is not None and self.flight.enabled:
                self.flight.record({
                    "type": "batcher_restart",
                    "attempt": attempt,
                    "budget": self.restart_budget,
                    "backoff_s": round(backoff, 3),
                    "outcome": "latched_dead" if exhausted else "restarting",
                })
            if exhausted:
                logger.error(
                    "continuous batcher crash-loop budget exhausted after "
                    "%d restarts; latching unready for replacement",
                    self.restart_budget,
                )
                self._stop.set()
                self._drain_queue(err)
                return False
            if self._stop.wait(backoff):
                self._drain_queue(self._dead_error())
                return False  # close() landed while backing off
            try:
                self._rebuild()
            except Exception:  # noqa: BLE001 - rebuild on a sick device
                logger.exception("batcher rebuild failed (attempt %d)", attempt)
                continue
            self.stats["batcher_restarts"] += 1
            self.health = "serving"
            logger.warning(
                "continuous batcher restarted (%d/%d): fresh cache, prefix "
                "index reset, executables re-warmed",
                attempt, self.restart_budget,
            )
            return True

    @scheduler_only
    def _loop(self) -> bool:
        """One supervised run of the poll loop. Returns False on a clean
        ``close()`` stop, or :meth:`_crash_recover`'s verdict after a
        loop death (True = run again on rebuilt state).

        **The poll record** (``"type": "poll"`` in the flight recorder;
        one per iteration that admitted, advanced a chunk or dispatched a
        burst) is a span of the scheduler thread's time. It is written
        once the iteration's reads are done, so its stretch is the
        iteration whole, reads included, plus every iteration before it
        that wrote none (idle, or reads only): ``t`` is where the stretch
        began (monotonic), ``phase_s`` the clock's seconds by phase over
        it (``PhaseClock.lap``: the rows lie end to end and sum to the
        clock's totals), ``host`` what the host did to the thread over the
        same stretch (``HostClock.lap``, taken where the clock is lapped:
        its seconds on a core and runnable with none, the machine's busy
        share, the latest heartbeat, collector seconds), ``compiles`` what
        XLA compiled since the generate unit said ready and the last
        record (``tracing.CompileLog``'s ``serve`` events, each with its
        name, kind, seconds and what the persistent cache said; counted
        into ``stats["compiles_after_ready"]`` and
        ``["compile_after_ready_s"]``), ``bursts`` each burst read in it (``dispatch_t``,
        ``read_t``, ``k``, ``lanes``, ``late``: the device had finished it
        first), ``dispatched_t`` when this poll's burst went out,
        ``poll`` the loop's poll number and ``admitted_ids`` the requests
        whose lanes it activated (their timeline rows carry the same
        number as ``admit_poll``), ``drained`` whether the device had
        finished everything it was ever given when this poll came to its
        first dispatch (:meth:`_device_drained`): it then sits idle until
        that dispatch lands."""
        import jax.numpy as jnp

        from ..tracing import device_trace

        # in-flight bursts, oldest first: (mode, device arrays to read,
        # (lane snapshot, ...), dispatch time) — see _read_burst
        pending: "collections.deque" = collections.deque()
        clock = self._clock
        try:
            while not self._stop.is_set():
                # chaos hook: an injected poll death here exercises the
                # REAL supervision path end to end (faults.py wires it
                # from the SELDON_FAULTS scheduler section)
                self._poll_count += 1
                if self.fault_hook is not None:
                    self.fault_hook(self._poll_count)
                # multi-tenancy: publish the poll clock to the
                # TenantScheduler so its starvation bound is measured in
                # scheduler polls, not wall time (weightpager.py)
                if self.tenant_hook is not None:
                    self.tenant_hook(self._poll_count)
                # HBM pressure: refresh the ledger and, over the high
                # watermark, run the reclaim ladder (may drain `pending`
                # and preempt lanes at this poll boundary). Two attribute
                # checks when the subsystem is off.
                if (
                    self._active or self._chunked or pending
                    or self._resume_queue or not self._queue.empty()
                ):
                    self._work_poll_count += 1
                    self.stats["polls"] += 1
                if self.pressure_hook is not None or (
                    self._pressure.budget_bytes > 0
                ):
                    self._pressure_poll(pending)
                pressure_hold = (
                    self._pressure.budget_bytes > 0 and self._pressure.active
                )
                # flight recorder: counter snapshot at poll start so the
                # poll record carries DELTAS (what this poll did), plus the
                # decode plan captured at dispatch below. One small dict
                # per working poll — never per token.
                flight = self.flight if (
                    self.flight is not None and self.flight.enabled
                ) else None
                if flight is not None:
                    f0 = (
                        self.stats["admitted"], self.stats["prefill_chunks"],
                        self.stats["prefix_hits"], self.stats["prefix_evicted"],
                    )
                poll_plan: Optional[Dict[str, Any]] = None
                # sampled before this poll's first dispatch, whichever it is
                drained: Optional[bool] = None
                # -- live weight swap: drain, then flip at a poll boundary.
                # While a swap is staged, admissions HOLD (queued submits
                # wait) so in-flight lanes — decode, chunked prefill, and
                # every pipelined burst — finish on the OLD version; the
                # flip happens only when all three are empty, so no burst
                # ever mixes weight versions.
                # unlocked read: GIL-atomic, and a one-poll-late sighting
                # of a freshly staged swap is harmless — _do_swap
                # re-validates `self._pending_swap is not swap` under the
                # lock before flipping. Keeps the no-rollout hot loop free
                # of a per-poll mutex.
                # -- graceful drain: checkpoint everything at this poll
                # boundary and hand it to the caller for migration.
                # Admissions are already refused (health flipped to
                # "draining" on the caller thread), so after this the
                # loop simply idles until close().
                dj = self._pending_drain
                if dj is not None:
                    self._do_drain(dj, pending)
                    continue
                # -- planner retune: apply staged knob changes HERE, at
                # the top of the poll, before this poll's _fused_k
                # snapshot and admissions read any knob — so one poll
                # never sees a half-applied config. Unlocked read,
                # GIL-atomic, same re-validation discipline as swap.
                rj = self._pending_retune
                if rj is not None:
                    self._do_retune(rj)
                swap = self._pending_swap
                if swap is not None:
                    if swap.drain_lanes is None:
                        swap.drain_lanes = (
                            len(self._active) + len(self._chunked)
                        )
                    if self._active or self._chunked or pending:
                        swap.waited_polls += 1
                        if (
                            self.swap_drain_ms > 0
                            and swap.staged_t
                            and time.monotonic() - swap.staged_t
                            >= self.swap_drain_ms / 1e3
                        ):
                            # straggler bound: stop waiting on long
                            # generations — checkpoint them and flip
                            self._swap_preempt_stragglers(pending)
                    if not self._active and not self._chunked and not pending:
                        self._do_swap(swap)
                        swap = None
                # admit as many queued requests as there are free slots
                # (or as the family says a turn takes) —
                # same-bucket admissions are grouped so m lanes share one
                # batched prefill forward (pow2 chunks bound executables)
                clock.to("admit")
                wave: List[GenRequest] = []
                busy = len(self._active) + len(self._chunked)
                wave_cost = 0
                while (
                    swap is None
                    and not pressure_hold
                    and busy + len(wave) < self.slots
                    and len(wave) < self._admit_cap
                ):
                    # preempted requests resume AHEAD of newer work —
                    # their recompute is a price already paid once
                    if self._resume_queue:
                        req = self._resume_queue.popleft()
                    else:
                        try:
                            req = self._queue.get_nowait()
                        except queue.Empty:
                            break
                    if req.future.cancelled():
                        self.stats["cancelled"] += 1
                        # a preempted-then-cancelled request must not
                        # leave its K/V checkpoint pinning tier budget
                        self._release_tier_ckpt(req)
                        continue  # caller gave up while queued
                    if self._pressure.budget_bytes > 0:
                        # watermark-aware admission: if this request's
                        # end-of-generation footprint would trip the high
                        # watermark, hold it at the HEAD of the line until
                        # completions/reclaim open headroom — admitting it
                        # now would only earn it a preemption. With no
                        # other lane live it always admits: one lane of
                        # forward progress can never starve.
                        cost = self._admit_cost_bytes(req)
                        if (busy + len(wave)) and (
                            self._pressure.used + wave_cost + cost
                            >= self._pressure.high_bytes
                        ):
                            self._resume_queue.appendleft(req)
                            break
                        wave_cost += cost
                    wave.append(req)
                if wave:
                    if flight is not None:
                        drained = self._device_drained()
                    free_iter = iter(
                        i for i in range(self.slots)
                        if i not in self._active and i not in self._chunked
                    )
                    chunk_size = self.prefill_chunk
                    by_bucket: Dict[int, List[GenRequest]] = {}
                    for req in wave:
                        if req.resume is not None:
                            # recompute-resume of a preempted lane:
                            # prefill over prompt+generated, continue the
                            # exact sampling stream from the checkpoint
                            slot = next(free_iter)
                            try:
                                self._admit_resume(slot, req)
                            except Exception as e:  # noqa: BLE001 - bad state
                                logger.exception("preemption resume failed")
                                self._release_tier_ckpt(req)
                                if not req.future.done():
                                    req.future.set_exception(e)
                            continue
                        if req.remote is not None:
                            # disaggregated handoff: the prompt K/V came
                            # over the wire — splice it, no local prefill
                            slot = next(free_iter)
                            try:
                                self._admit_remote_lane(slot, req)
                            except Exception as e:  # noqa: BLE001 - typed refusal
                                from .disagg import (
                                    PrefixGone,
                                    WeightVersionMismatch,
                                )

                                if isinstance(
                                    e, (PrefixGone, WeightVersionMismatch)
                                ):
                                    # expected, self-healing refusals (the
                                    # caller retries full-slab / re-prefills
                                    # under the new version): one info line,
                                    # no traceback — ERROR stays reserved
                                    # for corrupt slabs and real faults
                                    logger.info("remote admit refused: %s", e)
                                else:
                                    logger.exception("remote admit failed")
                                if not req.future.done():
                                    req.future.set_exception(e)
                            continue
                        hit = (
                            self._prefix_match(req)
                            if self._prefix_index is not None
                            else None
                        )
                        n = len(req.tokens)
                        if chunk_size and (
                            (hit is None and self._bucket(n) > chunk_size)
                            or (hit is not None and n - hit[0] > chunk_size)
                        ):
                            # long prefill: reserve the lane and trickle
                            # the prompt in between decode polls instead
                            # of stalling every lane for one forward
                            slot = next(free_iter)
                            try:
                                self._start_chunked(slot, req, hit=hit)
                            except Exception as e:  # noqa: BLE001 - bad request
                                logger.exception("chunked admit failed")
                                self._chunked.pop(slot, None)
                                if not req.future.done():
                                    req.future.set_exception(e)
                            continue
                        if hit is not None:
                            # prefix-cache hit: the suffix-only admit path
                            # (splice + short prefill) beats riding a
                            # batched FULL prefill with its bucket-mates
                            slot = next(free_iter)
                            try:
                                self._admit(slot, req, hit=hit)
                            except Exception as e:  # noqa: BLE001 - bad request
                                logger.exception("admit failed")
                                if not req.future.done():
                                    req.future.set_exception(e)
                            continue
                        by_bucket.setdefault(
                            self._bucket(len(req.tokens)), []
                        ).append(req)
                    for bucket, reqs in by_bucket.items():
                        while reqs:
                            # two batched variants exist per bucket (m=8
                            # where the slab fits, m=4) — remainders of
                            # 1-3 go through the single-admission path
                            # rather than compiling more executables.
                            # m=8 matters at LONG buckets: batched prefill
                            # roughly halves the per-request cost vs m=4
                            # (measured 39 -> 25.5 ms/req at 1792 on v5e),
                            # and prefill duty is the long tiers' largest
                            # non-decode cost
                            m = 1
                            if self.speculate_tokens == 0:
                                if len(reqs) >= 8 and self._chunk8_ok(bucket):
                                    m = 8
                                elif len(reqs) >= 4 and self._rows_ok(
                                        4, bucket):
                                    m = 4
                            chunk, reqs = reqs[:m], reqs[m:]
                            slots_ = [next(free_iter) for _ in chunk]
                            try:
                                if m == 1:
                                    self._admit(slots_[0], chunk[0])
                                else:
                                    self._admit_many(slots_, chunk, bucket)
                            except Exception as e:  # noqa: BLE001 - bad request
                                logger.exception("admit failed")
                                for req in chunk:
                                    if not req.future.done():
                                        req.future.set_exception(e)
                if (
                    not self._active and not pending and not self._chunked
                    and not (self._resume_queue and not pressure_hold)
                ):
                    clock.to("idle")
                    try:
                        req = self._queue.get(timeout=0.05)
                    except queue.Empty:
                        req = None
                    clock.to("other")
                    if req is not None:
                        self._queue.put(req)
                    continue
                if self._chunked:
                    # the interleave: one prefill chunk per pending long
                    # admission, then the decode burst below — decode
                    # lanes keep their cadence while long prompts land
                    clock.to("chunks")
                    if flight is not None and drained is None:
                        drained = self._device_drained()
                    self._advance_chunks()
                clock.to("dispatch")
                if self._active:
                    if flight is not None and drained is None:
                        drained = self._device_drained()
                    if self._masks_dirty:
                        # a NEW array each time, as ``active`` is: the
                        # CPU backend's jnp.asarray may alias the numpy
                        # buffer, and a burst still in flight reads the
                        # temperatures it was dispatched with (one array
                        # rewritten in place let a lane freed at its last
                        # burst's dispatch sample that burst greedily)
                        temps = np.zeros((self.slots,), np.float32)
                        for i, state in self._active.items():
                            temps[i] = state.request.temperature
                        active = np.zeros((self.slots,), bool)
                        for i in self._active:
                            active[i] = True
                        self._active_dev = jnp.asarray(active)
                        self._temps_dev = jnp.asarray(temps)
                        # static flag: a greedy-only burst compiles without
                        # the q/p softmax + sampling machinery
                        self._any_stoch = bool((temps > 0).any())
                        self._masks_dirty = False
                        self._fused_sync = False
                    active_dev = self._active_dev
                    temps_dev = self._temps_dev
                    # burst length. Step-at-a-time path: k is FIXED (one
                    # compiled variant) — lanes that hit max_new_tokens or
                    # eos mid-burst simply have their overshoot tokens
                    # dropped by _process_burst; clamping k to the tightest
                    # remaining budget (the pre-fused design) made staggered
                    # requests force tiny bursts on every lane, paying the
                    # sync RTT per token near each completion. Fused path:
                    # K is ADAPTIVE (never below self._k — see _fused_plan)
                    # and on-device done masks freeze lanes that stop
                    # mid-burst, so one dispatch safely covers many polls'
                    # worth of steps. Speculation keeps its own fused
                    # draft/verify rounds: the fused path degrades to it —
                    # and while the pressure ladder SUPPRESSES speculation,
                    # to the plain step-at-a-time burst (the path PR 9
                    # warmed and proved identical), never to cold fused
                    # executables.
                    fused_k = self._fused_k  # one snapshot per poll
                    use_fused = fused_k > 0 and self._spec_burst_fn is None
                    fused_reason = None
                    if use_fused:
                        k, fused_reason = self._fused_plan(fused_k)
                        if not self._fused_sync:
                            # per-lane stop tokens + remaining budgets:
                            # uploaded only when membership (or the
                            # dispatch mode) changed — the device
                            # decrements its own budget copy per step, so
                            # the steady-state fused loop uploads nothing
                            stops = np.full((self.slots,), -1, np.int32)
                            budget = np.zeros((self.slots,), np.int32)
                            for i, s in self._active.items():
                                if s.request.eos_id is not None:
                                    stops[i] = int(s.request.eos_id)
                                budget[i] = (
                                    s.request.max_new_tokens - s.dispatched
                                    - (1 if s.first_pending else 0)
                                )
                            self._stops_dev = jnp.asarray(stops)
                            self._budget_dev = jnp.asarray(budget)
                            self._fused_sync = True
                    else:
                        k = self._k
                        self._fused_sync = False
                    # per-burst worst-case position advance (spec rounds can
                    # emit up to gamma+1 tokens each)
                    adv = k * (
                        self.speculate_tokens + 1 if self._spec_active() else 1
                    )
                    # attention-read bucket: the smallest attn_bucket
                    # multiple covering every active lane's end-of-burst
                    # position (host-tracked, no sync). One executable per
                    # bucket, except where the read bounds itself
                    # (_ragged_read).
                    attn_len = self._attn_need(
                        max(self._pos_host[i] for i in self._active) + adv
                    )
                    if self._block_w > 1:
                        poll_plan, t_dispatch = self._dispatch_block_burst(
                            active_dev, temps_dev, pending)
                        if flight is None:
                            poll_plan = None
                    elif self._spec_active():
                        # snapshot BEFORE dispatch: tokens of this burst
                        # belong to these occupants, whatever the host
                        # learns later.
                        snapshot = {}
                        t_dispatch = time.monotonic()
                        for slot, s in self._active.items():
                            first = s.first_pending
                            snapshot[slot] = (s, 0 if first else 1)
                            if first:
                                s.request.first_dispatch_t = t_dispatch
                            s.first_pending = False
                            s.dispatched += k + (1 if first else 0)
                            self._pos_host[slot] += adv
                        caches = {
                            "k": self._cache["k"], "v": self._cache["v"],
                            "dk": self._draft_cache["k"],
                            "dv": self._draft_cache["v"],
                        }
                        with self._prof.measure(
                            "spec_burst",
                            variant=f"g{self.speculate_tokens}b{attn_len}",
                            tenant=self._burst_tenant()
                            if self._prof.enabled else "",
                            bytes_read=k * (
                                self._param_bytes
                                + self.slots * attn_len * self._kv_key_bytes
                            ),
                            tokens=k * self.slots,
                        ) as _m, device_trace("gen.decode_burst"):
                            (
                                start_tok, toks, counts, self._cur_tok,
                                self._pos, self._keys, nc,
                            ) = self._spec_burst_fn(
                                self.params, self._draft_params, caches,
                                self._cur_tok, self._pos, active_dev, temps_dev,
                                self._keys, k, attn_len, self._any_stoch,
                            )
                            _m.sync(toks)
                        if flight is not None:
                            poll_plan = {
                                "mode": "spec", "k": k, "attn_len": attn_len,
                                "lanes": len(self._active),
                            }
                        self._cache = {"k": nc["k"], "v": nc["v"]}
                        self._draft_cache = {"k": nc["dk"], "v": nc["dv"]}
                        self.stats["steps"] += k
                        self.stats["lane_steps"] += k * self.slots
                        for t in (start_tok, toks, counts):
                            try:
                                t.copy_to_host_async()
                            except AttributeError:
                                pass
                        pending.append((
                            "spec", (start_tok, toks, counts), (snapshot, k),
                            t_dispatch,
                        ))
                    else:
                        # one burst over all live lanes. need[slot] is
                        # the lane's OWN bucket; the burst's, attn_len,
                        # is their maximum
                        need = {
                            slot: self._attn_need(self._pos_host[slot] + adv)
                            for slot in self._active
                        }
                        lanes = sorted(need)
                        if flight is not None:
                            # ONE composition record per poll (for a fused
                            # poll the adaptive K and why it shrank) —
                            # never per step, so the recorder's host cost
                            # stays per-poll as shipped.
                            poll_plan = {
                                "mode": "fused" if use_fused else "decode",
                                "k": k,
                                "lanes": len(lanes),
                                "bucket": attn_len,
                                "distinct_buckets": len(set(need.values())),
                            }
                            if use_fused:
                                poll_plan["k_max"] = fused_k
                                if fused_reason is not None:
                                    poll_plan["shrunk_by"] = fused_reason
                        # snapshot BEFORE dispatch, as the spec arm does
                        snapshot = {}
                        t_dispatch = time.monotonic()
                        for slot in lanes:
                            s = self._active[slot]
                            first = s.first_pending
                            snapshot[slot] = (s, 0 if first else 1)
                            if first:
                                s.request.first_dispatch_t = t_dispatch
                            s.first_pending = False
                            s.dispatched += k + (1 if first else 0)
                            self._pos_host[slot] += adv
                        # the family prices its own step: the llama
                        # block every weight and every row's bucket, one
                        # with window layers or experts what the live
                        # lanes make it read
                        read_bytes = self.model.dispatch_read_bytes(
                            "decode_burst", rows=self.slots,
                            live=len(lanes), k=k, bucket=attn_len,
                            param_bytes=self._param_bytes,
                            kv_row_bytes=self._kv_key_bytes,
                        )
                        # the executable's own bound: none where the read
                        # takes each lane's length (the host's arithmetic
                        # above and below keeps the bucket either way)
                        bound = None if self._ragged_read else attn_len
                        with self._prof.measure(
                            "fused_burst" if use_fused else "decode_burst",
                            variant=f"k{k}b{attn_len}" if use_fused
                            else f"b{attn_len}",
                            tenant=self._burst_tenant()
                            if self._prof.enabled else "",
                            bytes_read=read_bytes,
                            tokens=k * self.slots,
                        ) as _m, device_trace("gen.decode_burst"):
                            if use_fused:
                                (
                                    toks, counts, done_bits,
                                    self._cur_tok, self._pos, self._cache,
                                    self._keys, self._budget_dev, *extra,
                                ) = self._fused_burst_fn(
                                    self.params, self._cache,
                                    self._cur_tok, self._pos,
                                    active_dev, temps_dev, self._keys,
                                    self._stops_dev, self._budget_dev,
                                    k, bound,
                                )
                                burst = (
                                    "fused",
                                    (toks, counts, done_bits,
                                     *self._take_prefill_counts(), *extra),
                                    (snapshot, k), t_dispatch,
                                )
                            else:
                                (
                                    toks, self._cur_tok, self._pos,
                                    self._cache, self._keys, *extra,
                                ) = self._burst_fn(
                                    self.params, self._cache,
                                    self._cur_tok, self._pos,
                                    active_dev, temps_dev, self._keys,
                                    k, bound,
                                )
                                burst = (
                                    "plain",
                                    (toks, *self._take_prefill_counts(),
                                     *extra),
                                    (snapshot,), t_dispatch,
                                )
                            _m.sync(toks)
                        self.stats["steps"] += k
                        self.stats["lane_steps"] += k * self.slots
                        self.stats["burst_reads"] += 1
                        self.stats["burst_read_bytes"] += read_bytes
                        self.stats["kv_positions_read"] += sum(
                            _positions_streamed(
                                self._pos_host[slot] - adv, k, attn_len,
                                self._kv_read_block)
                            for slot in lanes
                        )
                        self.stats["kv_positions_bucket"] += (
                            k * self.slots * attn_len
                        )
                        for window in self._kv_windows:
                            for slot in lanes:
                                streamed, seen, live = _positions_windowed(
                                    self._pos_host[slot] - adv, k, window,
                                    self._kv_read_block)
                                self.stats["kv_positions_read_window"] += streamed
                                self.stats["kv_positions_seen_window"] += seen
                                self.stats["kv_positions_live_window"] += live
                        rows = len(lanes) * k * self._position_layers
                        self.stats["kv_rows_written"] += rows
                        if self._ragged_read:
                            self.stats["kv_rows_written_in_kernel"] += rows
                        if use_fused:
                            self.stats["fused_dispatches"] += 1
                            self.stats["fused_steps"] += k
                        if self.trace_groups is not None:
                            self.trace_groups.append({
                                "lanes": tuple(lanes),
                                "attn_len": attn_len,
                                "need": need,
                            })
                        # start the device->host token copy NOW; by the
                        # time the host reads this burst (pipeline_depth
                        # dispatches later) the transfer has landed
                        for t in burst[1]:
                            try:
                                t.copy_to_host_async()
                            except AttributeError:  # non-jax (test doubles)
                                pass
                        pending.append(burst)
                        # PREDICTIVE FREE: a lane whose eos-less budget is
                        # now fully covered by dispatched bursts is done —
                        # the host needn't observe the tokens to know it.
                        # Freeing it here (instead of pipeline_depth bursts
                        # later) lets the next admission's prefill+insert
                        # queue behind the in-flight bursts, so the lane
                        # decodes a NEW request the very next burst rather
                        # than burning steps on overshoot. (Spec mode keeps
                        # the observed path: its per-round advance is
                        # data-dependent, so completion isn't predictable.)
                        freed = [
                            slot
                            for slot, s in self._active.items()
                            if s.request.eos_id is None
                            and s.dispatched >= s.request.max_new_tokens
                        ]
                        for slot in freed:
                            s = self._active.pop(slot)
                            # pre-freed lanes never reach _finish; this is
                            # the only point their prompt K/V can publish
                            # before the lane's next occupant splices over
                            self._maybe_publish(slot, s)
                            self._pos_host.pop(slot, None)
                        if freed:
                            self._masks_dirty = True
                clock.to("other")
                entry: Optional[Dict[str, Any]] = None
                if flight is not None:
                    admitted = self.stats["admitted"] - f0[0]
                    chunks = self.stats["prefill_chunks"] - f0[1]
                    hits = self.stats["prefix_hits"] - f0[2]
                    evicted = self.stats["prefix_evicted"] - f0[3]
                    if poll_plan is not None or admitted or chunks:
                        entry = {
                            "type": "poll",
                            "poll": self._poll_count,
                            "queue": self._queue.qsize(),
                            "active": len(self._active),
                            "chunked": len(self._chunked),
                            "pending_bursts": len(pending),
                            "drained": drained,
                        }
                        if admitted:
                            entry["admitted"] = admitted
                            entry["admitted_ids"] = self._row_admitted
                            self._row_admitted = []
                        if chunks:
                            entry["prefill_chunks"] = chunks
                        if hits:
                            entry["prefix_hits"] = hits
                        if evicted:
                            entry["prefix_evicted"] = evicted
                        if poll_plan is not None:
                            entry["plan"] = poll_plan
                            entry["dispatched_t"] = t_dispatch
                        if self._prof.enabled:
                            # per-poll device-time ledger deltas ride the
                            # poll record; quiet-poll leftovers roll into
                            # the next recorded poll (flush clears)
                            dt_rows = self._prof.poll_flush()
                            if dt_rows:
                                entry["device_time"] = dt_rows
                # read bursts oldest-first: always when the pipeline is full
                # (or nothing is left to dispatch) — and OPPORTUNISTICALLY
                # when a burst's token copy has already landed on the host
                # (is_ready -> np.asarray won't block). Eager reads shrink
                # the completion-observation lag for eos/temperature lanes
                # without ever stalling dispatch.
                while pending:
                    if not (len(pending) >= self.pipeline_depth or not self._active):
                        # last-initiated transfer of the oldest burst
                        # (its arrays copy in order): if IT landed,
                        # np.asarray of the earlier ones won't block either
                        if not _is_ready(pending[0][1][-1]):
                            break
                    self._read_burst(pending.popleft())
                if entry is not None:
                    # the reads are done: the record's stretch ends at the
                    # last switch of the clock (no read of its own)
                    entry["t"], entry["phase_s"] = clock.lap()
                    entry["host"] = self._host.lap()
                    self._compile_cursor, compiled = compiles_since(
                        self._compile_cursor)
                    if compiled:
                        entry["compiles"] = compiled
                        self.stats["compiles_after_ready"] += sum(
                            e["kind"] == "backend" for e in compiled)
                        self.stats["compile_after_ready_s"] += sum(
                            e["s"] for e in compiled)
                    if self._row_bursts:
                        entry["bursts"] = self._row_bursts
                        self._row_bursts = []
                    flight.record(entry)
        except Exception:  # noqa: BLE001 - every loop death is supervised
            logger.exception("continuous batcher loop died")
            return self._crash_recover(pending)
        return False  # clean stop via close()
