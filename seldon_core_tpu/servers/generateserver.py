"""Generate prepackaged server: LLM token generation with continuous
batching behind the standard unary predict protocol.

BASELINE.json config 5 ("Llama-2-7B generate() with engine-side dynamic
batching"); no reference counterpart — the reference's servers are all
unary classifiers (servers/sklearnserver/... — SURVEY §2 #32-35).

Model URI layout: same ``jax_config.json`` as jaxserver with
``"family": "llm"``; extra server params tune the scheduler::

    slots            decode lanes (default 8)
    max_seq          cache length override
    shard_cache_seq  shard the KV cache length over the mesh's `seq` axis
    mesh_shape       sharded serving: build a device mesh at load and
                     serve ONE model partitioned across it — params take
                     the TP layout (DecoderLM.param_sharding), every KV
                     slab shards its heads axis over ``model`` while the
                     lane axis stays data-parallel. ``"data=2,model=4"``
                     (strict axis=size pairs, typed MeshShapeError on
                     malformed/non-dividing shapes) or ``"auto"``
                     (factor jax.device_count() into the 2D data x model
                     serving mesh). Greedy AND seeded outputs stay
                     byte-identical to 1-device — see docs/generate.md
                     "Sharded serving". Ignored when an explicit ``mesh``
                     object is injected (the engine placement path)
    steps_per_poll   decode steps fused into one device burst (default 8;
                     pow2-floored — the value actually dispatched is
                     surfaced as ``steps_per_poll_effective`` in server
                     stats)
    fused_steps_per_dispatch
                     fused multi-step decode: one dispatch runs up to
                     this many decode steps ENTIRELY on device —
                     per-step KV append, greedy + seeded-categorical
                     sampling, stop-token detection, and per-lane done
                     masks that freeze finished lanes (0 = off, the
                     step-at-a-time burst path). K adapts per poll
                     (shrinks toward the nearest lane's stop budget and
                     to ``steps_per_poll`` under HBM pressure or a
                     staged swap/drain) and byte-identity on vs off is
                     the contract — see docs/generate.md "Fused decode"
    pipeline_depth   bursts in flight before the host reads the oldest
                     (default 2: one queued; 1 = synchronous)
    speculate_tokens speculative decoding: draft this many tokens per
                     round, verify with one target forward (0 = off).
                     Exact for any draft — greedy lanes reproduce the
                     target argmax decode, temperature lanes use
                     speculative sampling (the emitted distribution
                     equals sampling the target). Needs a draft:
    draft_layers     early-exit self-draft — the first N layers of the
                     SERVED model propose (no second checkpoint)
    draft_uri        separate draft model dir (same vocab)
    prefix_cache_hbm_bytes
                     radix prefix KV-cache budget in HBM bytes (0 = off,
                     the disable flag): completed requests publish their
                     prompt K/V; later prompts sharing a prefix splice it
                     and prefill only the suffix (LRU-evicted at radix-
                     node granularity). Responses then carry per-request
                     ``cache_hit_tokens``.
    prefix_cache_min_tokens
                     shortest prefix worth caching or reusing (default 16)
    admit_queue_limit
                     max queued-not-admitted requests before submits are
                     shed with 429 (0 = uncapped). Queued requests with a
                     deadline (meta ``deadlineMs``) are additionally shed
                     when the queue's expected wait exceeds it — see
                     docs/operate.md "Resilience"
    prefill_chunk    chunked prefill: split long-prompt prefills into
                     this many tokens per slice, interleaved between
                     decode polls (0 = off) — a 1,792-token admit no
                     longer stalls every decode lane for one
                     prompt-length forward
    flight_recorder  scheduler flight-recorder capacity: the batcher
                     keeps this many per-poll decision records in a
                     bounded drop-oldest ring, dumped at the engine's
                     ``/flightrecorder`` route (0 = off; default 4096,
                     a few minutes of polls — cheap enough to leave
                     on, see docs/operate.md "Observability")
    role             ``unified`` (default; serve prefill+decode locally,
                     byte-identical to every prior release) |
                     ``prefill`` (run prompt prefill only and export the
                     K/V slab over the KV transport — no decode lanes,
                     no scheduler loop) | ``decode`` (pull prefilled
                     slabs from ``peer`` and run decode-only lanes).
                     See docs/generate.md "Disaggregated serving"
    peer             decode role: the prefill pool's KV endpoints as a
                     ``host:port`` LIST (comma-separated string) — peers
                     are health-probed, ejected with backoff on transfer
                     failure, readmitted on probe success, and a failed
                     transfer retries once on the next healthy peer;
                     with the whole pool ejected, decode degrades to
                     LOCAL unified prefill (``degraded_local_prefill``
                     counts the regression). Tests/benches may instead
                     wire live prefill GenerateServer objects via
                     ``set_peer()`` (loopback transport — same codec,
                     in memory)
    kv_port          prefill role: TCP port the KV export listener
                     binds (0 = loopback-only, no listener)
    kv_chunk_bytes   KV transport write granularity — the sender-side
                     in-flight bound per slab stream (default 1 MiB)
    peer_eject_backoff_s
                     decode role: initial per-peer re-probe backoff
                     after a transfer failure (exponential, capped 30s;
                     default 1.0)
    restart_budget   scheduler supervision: how many times a dead
                     batcher loop may rebuild (fresh cache + re-warm)
                     before the member latches unready for replacement
                     (default 3); see docs/operate.md "Failure modes"
    restart_backoff_s
                     initial crash-restart backoff (exponential,
                     default 0.5)
    hbm_ledger_bytes HBM-pressure budget for the scheduler's unified
                     ledger (live decode footprint + staging slabs +
                     prefix cache + pending-swap double buffer; 0 =
                     off, the disable flag). Over the high watermark
                     the reclaim ladder runs: evict prefixes, cancel
                     speculation, preempt decode lanes
                     (checkpoint-to-host + recompute-resume, byte-
                     identical output), shed admissions — see
                     docs/generate.md "HBM pressure & preemption"
    pressure_high    high watermark as a fraction of the ledger budget
                     (default 0.90): crossing it latches pressure
    pressure_low     low watermark (default 0.75): reclaim runs until
                     usage drops here, then admissions resume
    host_kv_tier_bytes
                     tiered KV memory: byte budget of the pinned
                     host-RAM spill tier (0 = off, the disable flag).
                     With it on, the reclaim ladder DEMOTES prefix
                     slabs to host instead of destroying them (a later
                     match promotes: device_put + splice — a PCIe copy
                     instead of a re-prefill), preempted lanes
                     checkpoint their exact K/V for copy-back resume
                     (recompute+replay stays the fallback), prefill
                     exports publish their slabs for peers, and the KV
                     port answers peer prefix-lookups from the tier —
                     see docs/generate.md "Tiered KV memory"
    kv_tier_min_tokens
                     demote threshold: prefixes shorter than this never
                     enter the tier (0 = prefix_cache_min_tokens)
    kv_tier_promote_min_tokens
                     promote threshold: tier matches shallower than
                     this are not worth the PCIe copy (0 = the demote
                     threshold)
    kv_tier_peer_lookup
                     decode role: ask the prefill peers' host tiers for
                     a shared prefix before requesting a full prefill
                     (-1 = auto, on exactly when host_kv_tier_bytes is
                     set; 0 = off; 1 = force on — needs a local prefix
                     cache to splice the pulled slab)
    resume_tokens    live migration: attach an opaque SGC1 resume token
                     (serving/migration.py) to every streamed span (and
                     the unary response) so a member death mid-
                     generation is survivable — resubmit the token on
                     any peer serving the same weight_version and the
                     generation continues byte-identical with no span
                     re-sent (0 = off; incompatible with speculation —
                     the token's RNG re-derivation assumes plain
                     decode). See docs/generate.md "Live migration &
                     resumable streams"
    swap_drain_ms    hot-swap straggler bound: after this long draining
                     a staged weight swap, preempt-checkpoint the
                     remaining in-flight lanes so one long generation
                     cannot stall the flip (0 = wait forever)
    swap_resume_policy
                     what happens to swap-preempted stragglers:
                     ``resume`` (default) re-queues them to finish on
                     the NEW weights; ``fail`` refuses them typed
                     (WeightVersionMismatch, 409-class)

Request (jsonData)::

    {"prompt_tokens": [1, 2, ...],        # or "prompt": "text" (byte-level)
     "max_new_tokens": 32, "temperature": 0.0, "eos_id": null, "seed": 0}

Batched form: ``prompt_tokens`` may be a list of lists — each prompt is
submitted separately and rides the SAME in-flight decode batch (that is
the continuous-batching win; no padding to the longest prompt).

Response (jsonData): ``{"tokens": [[...]], "text": [...]}`` — ``text``
only for byte-level string prompts.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from concurrent.futures import TimeoutError as FuturesTimeout
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence

from ..analysis.roles import caller_thread
from ..models.family import DecoderFamily
from ..user_model import SeldonComponent
from .jaxserver import JAXServer

logger = logging.getLogger(__name__)

# the counters that say what XLA compiled after the unit said ready. When a
# shape's first call comes is not data: a comparison of two responses byte
# for byte leaves these out, as it leaves TIMERs out
COMPILE_TELEMETRY_KEYS = ("gen_compiles_after_ready",
                          "gen_compile_after_ready_s")


@dataclasses.dataclass
class StreamHandle:
    """A live token stream: iterate ``chunks``; call ``cancel()`` when the
    consumer goes away so the decode lane is reclaimed."""

    chunks: Iterable
    cancel: Callable[[], bool]
    # the scheduler's GenRequest, for the front to stamp (``front``) and
    # to hang its own timeline span on; None behind a tenant queue that
    # has not submitted yet
    request: Any = None


class GenerateServer(SeldonComponent):
    # class-level defaults so partially constructed instances (tests
    # build shells via __new__ around a bare batcher) behave as the
    # unified role with no transport endpoints
    _role = "unified"
    _kv_server = None
    _kv_client = None
    _resume_tokens = False
    _kv_tier_peer_lookup = False
    _tenant_spec = None
    tenant_pager = None
    tenant_scheduler = None
    batcher = None
    profiler = None
    slo_burn = None

    def __init__(
        self,
        model_uri: str,
        mesh=None,
        slots: int = 8,
        max_seq: Optional[int] = None,
        shard_cache_seq: bool = False,
        mesh_shape: Optional[str] = None,
        steps_per_poll: int = 8,
        fused_steps_per_dispatch: int = 0,
        pipeline_depth: int = 2,
        attn_bucket: int = 128,
        speculate_tokens: int = 0,
        draft_layers: int = 0,
        draft_uri: Optional[str] = None,
        prefix_cache_hbm_bytes: int = 0,
        prefix_cache_min_tokens: int = 16,
        admit_queue_limit: int = 0,
        prefill_chunk: int = 0,
        flight_recorder: int = 4096,
        role: str = "unified",
        peer: Optional[str] = None,
        kv_port: int = 0,
        kv_chunk_bytes: int = 1 << 20,
        peer_eject_backoff_s: float = 1.0,
        restart_budget: int = 3,
        restart_backoff_s: float = 0.5,
        hbm_ledger_bytes: int = 0,
        pressure_high: float = 0.90,
        pressure_low: float = 0.75,
        host_kv_tier_bytes: int = 0,
        kv_tier_min_tokens: int = 0,
        kv_tier_promote_min_tokens: int = 0,
        kv_tier_peer_lookup: int = -1,
        resume_tokens: int = 0,
        swap_drain_ms: int = 0,
        swap_resume_policy: str = "resume",
        warmup_prompt_lens: Optional[Sequence[int]] = None,
        warmup_max_new_tokens: int = 0,
        tenants: Optional[str] = None,
        weight_pager_host_bytes: int = 0,
        tenant_tick_ms: int = 20,
        tenant_max_wait_polls: int = 256,
        tenant_min_resident_ms: int = 50,
        profiler: int = 0,
        profiler_deep_every: int = 0,
        profiler_hbm_gb_s: float = 0.0,
        profiler_dispatch_floor_us: float = 0.0,
        slo_objectives: Optional[str] = None,
        slo_fast_window_s: float = 60.0,
        slo_slow_window_s: float = 3600.0,
        **kwargs,
    ):
        self.model_uri = model_uri
        role = str(role or "unified").lower()
        if role not in ("unified", "prefill", "decode"):
            raise ValueError(
                f"role must be unified|prefill|decode, got {role!r}"
            )
        self._role = role
        self._peer = peer or None
        self._kv_port = int(kv_port)
        self._kv_chunk_bytes = int(kv_chunk_bytes)
        self._peer_eject_backoff_s = float(peer_eject_backoff_s)
        self._restart_budget = int(restart_budget)
        self._restart_backoff_s = float(restart_backoff_s)
        self._hbm_ledger_bytes = int(hbm_ledger_bytes)
        self._pressure_high = float(pressure_high)
        self._pressure_low = float(pressure_low)
        self._host_kv_tier_bytes = int(host_kv_tier_bytes)
        self._kv_tier_min_tokens = int(kv_tier_min_tokens)
        self._kv_tier_promote_min_tokens = int(kv_tier_promote_min_tokens)
        # -1 = auto: peer prefix-lookups ride exactly the tier knob
        self._kv_tier_peer_lookup = (
            self._host_kv_tier_bytes > 0
            if int(kv_tier_peer_lookup) < 0 else bool(int(kv_tier_peer_lookup))
        )
        # typed-params env delivers booleans as strings
        self._resume_tokens = (
            resume_tokens.lower() == "true"
            if isinstance(resume_tokens, str) and not resume_tokens.isdigit()
            else bool(int(resume_tokens))
        )
        self._swap_drain_ms = int(swap_drain_ms)
        self._swap_resume_policy = str(swap_resume_policy or "resume")
        if self._resume_tokens and int(speculate_tokens) > 0:
            raise ValueError(
                "resume_tokens is not supported with speculative decoding "
                "(the token's RNG re-derivation assumes the plain decode "
                "split chain)"
            )
        self._kv_server = None   # PrefillTransportServer (prefill role)
        self._kv_client = None   # FailoverKVClient over the peer list (decode)
        self._faults = None      # FaultInjector (chaos harness), set at load
        if role != "unified" and int(speculate_tokens) > 0:
            raise ValueError(
                "disaggregated roles do not support speculative decoding "
                "(the draft cache cannot cross the KV transport)"
            )
        self._mesh = mesh
        # sharded-serving knob: parsed STRICTLY at construction (the
        # admission-time contract — a malformed shape must refuse here,
        # not as an opaque XLA failure mid-load). "auto" defers the
        # factoring to load(), when jax.device_count() is known.
        mesh_shape = (mesh_shape or "").strip() if isinstance(
            mesh_shape, str
        ) else mesh_shape
        self._mesh_shape: Optional[Any] = None
        if mesh_shape:
            if str(mesh_shape).lower() == "auto":
                self._mesh_shape = "auto"
            else:
                from ..parallel.mesh import parse_mesh_shape

                self._mesh_shape = parse_mesh_shape(str(mesh_shape))
        self._slots = int(slots)
        self._max_seq = int(max_seq) if max_seq else None
        self._shard_cache_seq = bool(shard_cache_seq) if not isinstance(
            shard_cache_seq, str
        ) else shard_cache_seq.lower() == "true"
        self._steps_per_poll = int(steps_per_poll)
        self._fused_steps_per_dispatch = int(fused_steps_per_dispatch)
        self._pipeline_depth = int(pipeline_depth)
        self._attn_bucket = int(attn_bucket)
        self._speculate_tokens = int(speculate_tokens)
        self._draft_layers = int(draft_layers)
        self._draft_uri = draft_uri
        self._prefix_cache_hbm_bytes = int(prefix_cache_hbm_bytes)
        self._prefix_cache_min_tokens = int(prefix_cache_min_tokens)
        self._admit_queue_limit = int(admit_queue_limit)
        self._prefill_chunk = int(prefill_chunk)
        self._flight_recorder = int(flight_recorder)
        # cumulative scheduler stats ship as true counters (deltas)
        # through Meta.metrics
        from ..metrics import CounterDeltas

        self._deltas = CounterDeltas()
        # parse CSV from typed-params env ("128,1792") as well as sequences
        if isinstance(warmup_prompt_lens, str):
            warmup_prompt_lens = [
                int(x) for x in warmup_prompt_lens.split(",") if x.strip()
            ]
        self._warmup_prompt_lens = list(warmup_prompt_lens or [])
        self._warmup_max_new_tokens = int(warmup_max_new_tokens)
        # multi-tenancy: `tenants` is the same strict grammar as the
        # seldon.io/tenants annotation (name=slo[@model_uri] CSV) —
        # parsed at construction so a malformed spec refuses at
        # admission, not mid-load. The pager host budget gates the
        # whole subsystem: 0 (default) = single-tenant, byte-identical
        # to the pre-tenant server.
        self._tenant_spec = None
        if tenants:
            from ..serving.weightpager import parse_tenant_spec

            self._tenant_spec = parse_tenant_spec(str(tenants))
        self._weight_pager_host_bytes = int(weight_pager_host_bytes)
        if self._tenant_spec and self._weight_pager_host_bytes <= 0:
            raise ValueError(
                "tenants configured but weight_pager_host_bytes is 0 — "
                "the pager's host-RAM staging budget must be set"
            )
        if self._tenant_spec and self._role != "unified":
            raise ValueError(
                "multi-tenant paging is not supported on disaggregated "
                "roles (the KV transport assumes one weight lineage)"
            )
        self._tenant_tick_ms = int(tenant_tick_ms)
        self._tenant_max_wait_polls = int(tenant_max_wait_polls)
        self._tenant_min_resident_ms = int(tenant_min_resident_ms)
        self.tenant_pager = None      # WeightPager, set at load
        self.tenant_scheduler = None  # TenantScheduler, set at load
        # device-time profiler (serving/profiler.py): off by default —
        # the ledger is a shared no-op then, and the identity/overhead
        # gates in tests/test_profiler.py hold it to byte-identical
        # output. The MBU / dispatch-floor denominators are knobs so
        # the live gauges use numbers MEASURED on the serving chip — 0
        # omits the gauge rather than publishing a guess.
        from ..serving.profiler import DeviceTimeLedger

        self.profiler = DeviceTimeLedger(
            enabled=bool(int(profiler)),
            deep_every=int(profiler_deep_every),
            hbm_gb_s=float(profiler_hbm_gb_s),
            dispatch_floor_us=float(profiler_dispatch_floor_us),
        )
        # SLO burn-rate engine (serving/slo_burn.py), fed by the same
        # completed-request TTFT/TPOT/queue-wait drain /metrics exports.
        # Grammar: "slo:threshold_ms:target" CSV, e.g.
        # "ttft:200:0.99,queue_wait:50:0.999" — strict parse at
        # construction, same contract as the tenants spec.
        self.slo_burn = None
        if slo_objectives:
            from ..serving.slo_burn import SloBurnEngine, SloObjective

            objs = []
            for ent in str(slo_objectives).split(","):
                ent = ent.strip()
                if not ent:
                    continue
                parts = ent.split(":")
                if len(parts) != 3:
                    raise ValueError(
                        "slo_objectives entries are slo:threshold_ms:target "
                        f"(e.g. ttft:200:0.99), got {ent!r}"
                    )
                objs.append(SloObjective(
                    parts[0].strip(), float(parts[1]) * 1e-3, float(parts[2])
                ))
            self.slo_burn = SloBurnEngine(
                objs,
                fast_window_s=float(slo_fast_window_s),
                slow_window_s=float(slo_slow_window_s),
            )
        self._extra = kwargs
        self.batcher = None
        self._model = None
        self._swap_count = 0

    @staticmethod
    def _cast_params_freeing_impl(tree, dt):
        """Cast fp32 leaves to ``dt`` IN PLACE through nested dicts,
        dropping each fp32 leaf as it is replaced. A functional tree_map
        would hold the full fp32 tree alive until rebind — at flagship
        scale that is 5 GB of HBM pinned through warmup, the difference
        between slots=32 fitting or OOMing (the batcher's serving_cast
        then sees already-cast leaves and passes through)."""
        import jax.numpy as jnp

        # iterate KEYS only: a list of items() tuples would pin every fp32
        # value for the whole loop, re-creating the double-resident peak
        # a list of layers (models/afmoe.py) is walked by index
        for key in (range(len(tree)) if isinstance(tree, list) else list(tree)):
            v = tree[key]
            if isinstance(v, (dict, list)):
                GenerateServer._cast_params_freeing_impl(v, dt)
            elif hasattr(v, "dtype") and v.dtype == jnp.float32:
                tree[key] = v.astype(dt)
            del v
        return tree

    def load(self) -> None:
        from ..serving.continuous import ContinuousBatcher
        from ..tracing import (
            compile_report, compile_stage, install_compile_log,
            register_capture_source,
        )

        t_load = time.monotonic()
        # every XLA compile from here on lands in the process's compile
        # log under its name and the stage in force: load, warm, serve
        install_compile_log()
        server = JAXServer(self.model_uri)
        apply_fn, params = server.build()
        self._model = server._model
        import jax.numpy as jnp

        if not isinstance(self._model, DecoderFamily):
            raise RuntimeError(
                f"model family {type(self._model)} "
                "does not support generate(); use family 'llm'"
            )
        dt = jnp.dtype(self._model.compute_dtype)
        if dt != jnp.float32 and isinstance(params, dict):
            params = self._cast_params_freeing_impl(params, dt)
        # before a mesh or a draft is built for a family that has no
        # path for it (typed: models.family.UnsupportedByModel)
        self._model.check_serves(
            speculation=self._speculate_tokens > 0,
            mesh=self._mesh is not None or self._mesh_shape is not None,
            kv_tier=self._host_kv_tier_bytes > 0,
            migration=self._role != "unified",
        )
        if self._mesh is None and self._mesh_shape is not None:
            # build the serving mesh from the knob: an injected mesh
            # object (the engine placement path) always wins, so a
            # reconciler-placed member never double-builds
            import jax

            from ..parallel.mesh import (
                factor_devices, make_mesh, validate_model_dims,
            )

            if self._mesh_shape == "auto":
                f = factor_devices(jax.device_count())
                # collapse to the 2D data x model serving mesh: generate
                # serving runs no pipeline axis, and the seq axis only
                # pays with shard_cache_seq (opt-in, explicit shapes)
                shape = {
                    "data": f["data"] * f["stage"] * f["seq"],
                    "model": f["model"],
                }
            else:
                shape = dict(self._mesh_shape)
            cfg = self._model.cfg
            validate_model_dims(
                shape, int(cfg.n_heads), int(cfg.d_ff),
                n_kv_heads=int(getattr(cfg, "n_kv_heads", 0) or 0),
            )
            self._mesh = make_mesh(shape)
            logger.info(
                "generateserver: sharded serving mesh %s over %d device(s)",
                shape, self._mesh.devices.size,
            )
        draft_model = None
        draft_params = None
        if self._speculate_tokens > 0:
            if self._draft_uri:
                dserver = JAXServer(self._draft_uri)
                _apply, draft_params = dserver.build()
                draft_model = dserver._model
            elif self._draft_layers > 0:
                if self._draft_layers >= self._model.cfg.n_layers:
                    raise ValueError(
                        f"draft_layers ({self._draft_layers}) must be < the "
                        f"served model's n_layers ({self._model.cfg.n_layers})"
                    )
                # early-exit self-draft: the first N layers of the served
                # model (shared embed/head/norm, blocks sliced) — no second
                # checkpoint, and the proposals improve with the model
                import jax

                from ..models.llm import DecoderLM

                draft_model = DecoderLM(**dict(
                    dataclasses.asdict(self._model.cfg),
                    n_layers=self._draft_layers))
                draft_params = {
                    **params,
                    "blocks": jax.tree_util.tree_map(
                        lambda a: a[: self._draft_layers], params["blocks"]
                    ),
                }
            else:
                raise ValueError(
                    "speculate_tokens needs draft_layers or draft_uri"
                )
        self.batcher = ContinuousBatcher(
            self._model,
            params,
            # a prefill-role server runs NO decode lanes: the slab is
            # built in staging and shipped, never inserted locally — one
            # token lane keeps the cache allocation minimal
            slots=1 if self._role == "prefill" else self._slots,
            max_seq=self._max_seq,
            mesh=self._mesh,
            shard_cache_seq=self._shard_cache_seq,
            steps_per_poll=self._steps_per_poll,
            fused_steps_per_dispatch=self._fused_steps_per_dispatch,
            pipeline_depth=self._pipeline_depth,
            attn_bucket=self._attn_bucket,
            draft_model=draft_model,
            draft_params=draft_params,
            speculate_tokens=self._speculate_tokens,
            prefix_cache_hbm_bytes=self._prefix_cache_hbm_bytes,
            prefix_cache_min_tokens=self._prefix_cache_min_tokens,
            admit_queue_limit=self._admit_queue_limit,
            prefill_chunk=self._prefill_chunk,
            flight_recorder_capacity=self._flight_recorder,
            restart_budget=self._restart_budget,
            restart_backoff_s=self._restart_backoff_s,
            hbm_ledger_bytes=self._hbm_ledger_bytes,
            pressure_high=self._pressure_high,
            pressure_low=self._pressure_low,
            host_kv_tier_bytes=self._host_kv_tier_bytes,
            kv_tier_min_tokens=self._kv_tier_min_tokens,
            kv_tier_promote_min_tokens=self._kv_tier_promote_min_tokens,
            swap_drain_ms=self._swap_drain_ms,
            swap_resume_policy=self._swap_resume_policy,
            profiler=self.profiler,
        )
        # tracing.start_capture / stop_capture report this batcher's
        # counters, loop phases and request timelines
        register_capture_source(self.batcher)
        # chaos harness (off without SELDON_FAULTS): the scheduler
        # section wires induced poll death onto the batcher's fault
        # hook, the pressure section wires mid-run ledger re-budgeting;
        # kv rules are resolved per peer when transports are built below
        from ..resilience import FaultInjector

        self._faults = FaultInjector.from_env()
        if self._faults is not None:
            hook = self._faults.scheduler_hook()
            if hook is not None:
                self.batcher.fault_hook = hook
            phook = self._faults.pressure_hook()
            if phook is not None:
                self.batcher.pressure_hook = phook
        if self._tenant_spec:
            # multi-tenancy: register EVERY tenant's checkpoint in the
            # pager's host-RAM staging tier (the resident one included —
            # its staging copy is what makes demotion a pointer flip,
            # not an HBM download), align the batcher's weight-version
            # lineage to the primary tenant's namespaced version BEFORE
            # warm() so the caches never see the un-namespaced 0, and
            # hang the SLO scheduler off the poll loop. Done before
            # warm(): the compiled executables are shape-keyed, not
            # weight-keyed, so one warm covers all tenants (the
            # scale-to-zero no-recompile property).
            self._load_tenants(params)
        # the batcher holds the serving copy; under a mesh this local is
        # the UNSHARDED tree, whole on the first chip through warm-up
        del params
        t_warm = time.monotonic()
        compile_stage("warm")
        if self._warmup_prompt_lens:
            # compile-before-listen: every prefill/insert/burst variant the
            # declared traffic shape needs is built here, so the first
            # admission wave never stalls tens of seconds on XLA
            self.batcher.warm(
                prompt_lens=self._warmup_prompt_lens,
                max_new_tokens=self._warmup_max_new_tokens,
            )
        # what compiles from here on, a request waits for
        compile_stage("serve")
        if self._role == "prefill":
            # no scheduler loop: export_prefill runs on the transport's
            # handler threads, decode lanes never activate
            if self._kv_port:
                from ..serving.disagg import PrefillTransportServer

                self._kv_server = PrefillTransportServer(
                    self, port=self._kv_port,
                    chunk_bytes=self._kv_chunk_bytes,
                )
                logger.info(
                    "generateserver: prefill role exporting KV on :%d",
                    self._kv_server.port,
                )
        else:
            self.batcher.start()
            if self.tenant_scheduler is not None:
                # the page-in driver blocks on scheduler progress
                # (request_weight_swap futures), so it only starts once
                # the poll loop is live
                self.tenant_scheduler.start()
        if self._role == "decode" and self._peer is not None:
            self._kv_client = self._build_failover(self._peer)
        # the ready line names the device it serves on: JAX left alone
        # carries on on the CPU when the accelerator fails to initialise,
        # and a captured log must show which one this was
        import jax

        devices = (
            list(self._mesh.devices.flat) if self._mesh is not None
            else jax.devices()[:1]
        )
        # of warm_s: the seconds JAX traced and lowered (what a warm
        # compile cache does not save) and those in the backend (it does);
        # the cache's hits and misses over load and warm
        stages = compile_report()["stages"]
        warm = stages.get("warm", {})
        logger.info(
            "generateserver: %s ready (role=%s, slots=%d, max_seq=%d) "
            "platform=%s device_kind=%r visible_devices=%d serving_devices=%d "
            "bytes_in_use=%s load_s=%.1f warm_s=%.1f warm_trace_lower_s=%.1f "
            "warm_compile_s=%.1f cache_hits=%d cache_misses=%d",
            self.model_uri, self._role, self._slots, self.batcher.max_seq,
            devices[0].platform, devices[0].device_kind, jax.device_count(),
            len(devices),
            # None where the backend keeps no memory stats (CPU)
            [(d.memory_stats() or {}).get("bytes_in_use") for d in devices],
            t_warm - t_load, time.monotonic() - t_warm,
            warm.get("trace_s", 0.0) + warm.get("lower_s", 0.0),
            warm.get("backend_s", 0.0),
            *(sum(stages.get(s, {}).get(k, 0) for s in ("load", "warm"))
              for k in ("cache_hits", "cache_misses")),
        )

    def _load_tenants(self, primary_params) -> None:
        """Stage every declared tenant's checkpoint and align the
        batcher's weight-version lineage to the primary tenant's
        namespaced version. Secondary checkpoints load through the
        hot-swap discipline: same architecture required (one warmed
        executable set serves all tenants — THE scale-to-zero
        property), cast to the serving dtype before staging so page-in
        is decode+upload, never a cast."""
        import jax.numpy as jnp

        from ..serving.weightpager import TenantScheduler, WeightPager

        pager = WeightPager(self._weight_pager_host_bytes)
        primary, primary_slo, primary_uri = self._tenant_spec[0]
        if primary_uri and primary_uri != self.model_uri:
            raise ValueError(
                f"primary tenant {primary!r} declares model uri "
                f"{primary_uri!r} but the server loads {self.model_uri!r} "
                "— the first tenant boots resident on the served model"
            )
        v0 = pager.put(primary, primary_params, primary_slo)
        pager.mark_resident(primary)
        dt = jnp.dtype(self._model.compute_dtype)
        for name, slo, uri in self._tenant_spec[1:]:
            server = JAXServer(uri or self.model_uri)
            _apply, params = server.build()
            other = server._model
            if not isinstance(other, DecoderFamily):
                raise ValueError(
                    f"tenant {name!r} checkpoint at {uri!r} is not an "
                    "llm-family model dir"
                )
            changed = self._model.config_differs(other)
            if changed:
                raise ValueError(
                    f"tenant {name!r} checkpoint architecture differs "
                    f"from the served model ({', '.join(changed)}); "
                    "paged tenants share one executable set"
                )
            if dt != jnp.float32 and isinstance(params, dict):
                params = self._cast_params_freeing_impl(params, dt)
            pager.put(name, params, slo)
        b = self.batcher
        # lineage alignment BEFORE warm()/start(): caches are empty, so
        # adopting the namespaced version purges nothing, and the first
        # real page-in retains this tenant's slabs by namespace
        b.weight_version = v0
        if b._prefix_index is not None:
            b._prefix_index.set_version(v0)
        if b._kv_tier is not None:
            b._kv_tier.set_version(v0)
        b.tenant_pager = pager
        self.tenant_pager = pager
        self.tenant_scheduler = TenantScheduler(
            b, pager,
            {name: slo for name, slo, _uri in self._tenant_spec},
            tick_s=self._tenant_tick_ms / 1e3,
            max_wait_polls=self._tenant_max_wait_polls,
            min_resident_s=self._tenant_min_resident_ms / 1e3,
        )
        logger.info(
            "generateserver: multi-tenant paging over %d tenant(s), "
            "%d host-staging bytes, resident=%s",
            len(self._tenant_spec), self._weight_pager_host_bytes, primary,
        )

    # -- byte-level text fallback (no tokenizer shipped in-image) ----------

    def _encode(self, text: str) -> List[int]:
        return list(text.encode("utf-8"))

    def _decode(self, tokens: Iterable[int]) -> str:
        return bytes(t for t in tokens if 0 <= t < 256).decode("utf-8", "replace")

    def _parse_prompts(self, body: Dict[str, Any]):
        """ONE wire-schema parser for the unary and streaming paths:
        returns (token_lists, text_mode, sampling_kwargs)."""
        if "prompt" in body and "prompt_tokens" not in body:
            prompts = body["prompt"]
            prompts = [prompts] if isinstance(prompts, str) else list(prompts)
            token_lists = [self._encode(p) for p in prompts]
            text_mode = True
        else:
            pt = body.get("prompt_tokens")
            if not pt:
                raise ValueError("need prompt_tokens or prompt")
            token_lists = (
                [list(p) for p in pt] if isinstance(pt[0], (list, tuple)) else [list(pt)]
            )
            text_mode = False
        kw = dict(
            max_new_tokens=int(body.get("max_new_tokens", 32)),
            temperature=float(body.get("temperature", 0.0)),
            eos_id=body.get("eos_id"),
            seed=int(body.get("seed", 0)),
        )
        return token_lists, text_mode, kw

    # -- disaggregated serving (prefill/decode pools) ----------------------

    def _note_peer_event(self, kind: str, addr: str, reason: str = "") -> None:
        """Counter + flight-record hook for the failover transport's
        eject/readmit decisions — the observable half of the peer
        failover contract (seldon_engine_peer_ejections, ``peer_ejected``
        flight records)."""
        b = self.batcher
        if b is None:
            return
        key = "peer_ejections" if kind == "peer_ejected" else "peer_readmissions"
        with b._export_lock:
            b.stats[key] += 1
        if b.flight is not None and b.flight.enabled:
            rec = {"type": kind, "peer": addr}
            if reason:
                rec["reason"] = reason
            b.flight.record(rec)

    def _build_failover(self, peers):
        """Decode role: the peer LIST (comma-separated ``host:port``
        string, a single live server object, or a sequence of either)
        becomes one FailoverKVClient with this server's ejection
        telemetry and per-peer chaos faults wired in."""
        from ..serving.disagg import make_failover

        injector = self._faults
        return make_failover(
            peers,
            chunk_bytes=self._kv_chunk_bytes,
            fault_for=(
                injector.kv_faults_for if injector is not None else None
            ),
            eject_backoff_s=self._peer_eject_backoff_s,
            on_eject=lambda addr, reason: self._note_peer_event(
                "peer_ejected", addr, reason
            ),
            on_readmit=lambda addr: self._note_peer_event(
                "peer_readmitted", addr
            ),
        )

    def set_peer(self, prefill_server) -> None:
        """Wire a decode-role server to its prefill peer(s): a live
        GenerateServer/handler object (loopback transport — the slab
        still round-trips the full wire codec in memory), a
        ``host:port`` string (TCP; comma-separated for a list), or a
        sequence of either. Always wrapped in the failover layer, so
        single-peer and multi-peer decode pools share one ejection/
        degradation contract."""
        if self._role != "decode":
            raise RuntimeError(f"set_peer on a {self._role}-role server")
        self._kv_client = self._build_failover(prefill_server)

    def kv_ping(self) -> bool:
        """Loopback health probe target (the in-process twin of the TCP
        listener's ``{"ping": true}`` frame): True while this server's
        batcher can still serve prefill exports."""
        return self.batcher is not None and self.batcher.health == "serving"

    @caller_thread
    def prefill_export(self, request: Dict[str, Any]):
        """PREFILL-side transport handler: run the prompt forward and
        return ``(meta, slab)`` for the wire codec. Called by the
        loopback transport directly and by PrefillTransportServer per
        TCP connection. A ``prefix_lookup`` request is answered from
        the HOST KV TIER instead — no device work at all: the longest
        stored prefix's slab (CRC-verified on read) goes back over the
        same codec, or a typed :class:`~..serving.disagg.TierMiss`
        frame that the failover layer passes through without ejecting
        (a cold tier is not a dead pool)."""
        if self.batcher is None:
            self.load()
        if request.get("prefix_lookup"):
            return self._tier_lookup(request)
        toks = request.get("tokens")
        if not toks:
            raise ValueError("prefill request needs tokens")
        return self.batcher.export_prefill(
            [int(t) for t in toks],
            max_new_tokens=int(request.get("max_new_tokens", 32)),
            temperature=float(request.get("temperature", 0.0)),
            eos_id=request.get("eos_id"),
            seed=int(request.get("seed", 0)),
            covered_len=int(request.get("covered_len", 0)),
        )

    @caller_thread
    def _tier_lookup(self, request: Dict[str, Any]):
        """Answer a peer's prefix-lookup from the local host KV tier:
        ``(meta, slab)`` covering the ENTRY's full token path (the
        puller re-inserts it into its own radix index and lets the
        ordinary match serve the common depth). Runs on transport
        handler threads — the tier is host bytes under its own lock, so
        this never touches the device or the scheduler."""
        from ..serving.disagg import TierMiss

        b = self.batcher
        tier = b._kv_tier
        toks = [int(t) for t in request.get("tokens") or []]
        if tier is None or not toks:
            raise TierMiss("no host KV tier on this member")
        want_version = request.get("weight_version")
        if want_version != b.weight_version:
            raise TierMiss(
                f"tier serves weight_version {b.weight_version!r}, "
                f"peer asked for {want_version!r}"
            )
        # the SHARED usable-hit probe (ContinuousBatcher.tier_prefix_
        # lookup): the same promote-gate + donor-width/near-max caps the
        # puller applies locally run HERE, before the transfer is paid
        # (pool members share one model config, so bucket geometry
        # agrees) — a corrupt entry is dropped typed inside the probe
        # and answers a MISS frame, never a generic error that would
        # eject a healthy listener
        hit = b.tier_prefix_lookup(
            toks, min_tokens=int(request.get("min_tokens", 0))
        )
        if hit is None:
            raise TierMiss(
                "no usable stored prefix for this prompt (miss, below "
                "the promote gate, or not a win at this prompt's bucket)"
            )
        depth, meta, slab = hit
        with b._export_lock:
            # the peer-serving hit is a TIER hit on THIS member (its RAM
            # saved the peer a prefill); the puller counts the promotion
            b.stats["kv_tier_hits"] = tier.stats["hits"]
        if b.flight is not None and b.flight.enabled:
            from ..serving.disagg import prompt_hash

            b.flight.record({
                "type": "tier_hit", "kind": "prefix", "source": "peer",
                "tokens": depth,
                "phash": prompt_hash(meta.get("tokens") or [])[:8],
            })
        out_meta = {
            "kind": "tier_prefix",
            "tokens": meta.get("tokens"),
            "weight_version": b.weight_version,
            "tier_depth": depth,
        }
        return out_meta, slab

    @caller_thread
    def _peer_prefix_pull(self, toks, deadline_s) -> int:
        """Decode-role tier sharing: on a LOCAL radix miss, ask the
        prefill peers' host tiers for a shared prefix and promote the
        answer into the local radix index. Returns the new
        ``remote_covered_len`` (0 when nothing was pulled). Misses and
        transport trouble are non-events — the ordinary full-prefill
        path is always right behind."""
        from ..serving.disagg import DisaggError, TierMiss

        b = self.batcher
        try:
            meta, slab = self._kv_client.prefill({
                "prefix_lookup": True,
                "tokens": [int(t) for t in toks],
                "weight_version": b.weight_version,
                "min_tokens": b.tier_promote_gate,
            }, deadline_s=deadline_s)
        except TierMiss:
            return 0
        except DisaggError:
            # peer trouble is the failover layer's business (it already
            # ejected/rotated as needed); the lookup is opportunistic
            return 0
        if meta.get("weight_version") != b.weight_version:
            return 0
        b.promote_peer_prefix(meta, slab)
        return b.remote_covered_len(toks)

    @caller_thread
    def _remote_submit(self, toks, kw, deadline_s, covered=None,
                       on_tokens=None):
        """Decode-role submit: consult the local radix cache for the
        transfer-dedup base, pull the (suffix-only when possible) slab
        from the prefill pool under a ``gen.kv_transfer`` span, and
        queue it as a remote lane insert. With the ENTIRE prefill pool
        ejected, degrade gracefully to local unified prefill — the
        batcher owns the full prefill path and its warmed executables,
        so greedy output stays byte-identical while
        ``degraded_local_prefill`` makes the regression visible."""
        from ..serving.disagg import AllPeersDown
        from ..tracing import get_tracer

        if self._kv_client is None:
            raise RuntimeError(
                "decode role has no prefill peer (set `peer` or call "
                "set_peer())"
            )
        # bounds-check BEFORE the handoff: over the TCP transport a
        # prefill-side PromptTooLong/BudgetExceeded comes back as a
        # generic error frame the failover layer reads as peer death —
        # one unservable request must never eject healthy prefill peers
        from ..serving.continuous import PromptTooLong

        n = len(toks)
        if n >= self.batcher.max_seq:
            raise PromptTooLong(
                f"prompt of {n} exceeds max_seq {self.batcher.max_seq}"
            )
        self.batcher._check_budget(n, kw.get("max_new_tokens", 32))
        # shed BEFORE the handoff costs anything: an overloaded decode
        # pool must not amplify load onto the prefill pool and the wire
        # only to reject the slab on arrival (admit_remote re-checks,
        # but by then the transfer is paid). remote=True makes an
        # HBM-pressure refusal the typed PressureRefused (503 +
        # Retry-After) — the decode pool pushes back to its prefill
        # peers instead of half-admitting slabs.
        self.batcher._shed_check(deadline_s, remote=True)
        if covered is None:
            covered = self.batcher.remote_covered_len(toks)
            if covered == 0 and self.batcher._kv_tier is not None:
                # a demoted prefix in this member's OWN tier promotes
                # back before asking anyone else
                covered = self.batcher.consult_tier_covered_len(toks)
            if (
                covered == 0
                and self._kv_tier_peer_lookup
                and self.batcher._prefix_index is not None
                and len(toks) >= self.batcher.tier_promote_gate
            ):
                # cluster-wide prefix sharing: a local radix miss asks
                # the prefill peers' host tiers before paying a full
                # prefill + full-slab transfer (the pulled slab promotes
                # into the local radix index, so the suffix-only
                # request below dedups the wire bytes too)
                covered = self._peer_prefix_pull(toks, deadline_s)
        request = {
            "tokens": [int(t) for t in toks],
            "covered_len": int(covered),
            **kw,
        }
        try:
            with get_tracer().span(
                "gen.kv_transfer",
                tags={"covered_len": int(covered), "tokens": len(toks),
                      "transport": self._kv_client.name},
            ):
                meta, slab = self._kv_client.prefill(
                    request, deadline_s=deadline_s
                )
        except AllPeersDown as e:
            return self._local_prefill_fallback(
                toks, kw, deadline_s, on_tokens, str(e)
            )
        return self.batcher.admit_remote(
            slab, meta, on_tokens=on_tokens, deadline_s=deadline_s
        )

    @caller_thread
    def _local_prefill_fallback(self, toks, kw, deadline_s, on_tokens,
                                reason: str):
        """The whole prefill pool is ejected: serve the prompt with a
        LOCAL unified prefill instead of failing the request. Counted
        (``degraded_local_prefill``) and flight-recorded so the
        regression is visible on dashboards while the failover layer
        keeps probing the pool back in."""
        b = self.batcher
        with b._export_lock:
            b.stats["degraded_local_prefill"] += 1
        if b.flight is not None and b.flight.enabled:
            b.flight.record({
                "type": "degraded_local_prefill",
                "tokens": len(toks),
                "reason": reason,
            })
        logger.warning(
            "prefill pool fully ejected (%s); serving %d-token prompt "
            "with local unified prefill", reason, len(toks),
        )
        return b.submit(toks, deadline_s=deadline_s, on_tokens=on_tokens,
                        **kw)

    # -- live-lane migration (graceful drain + resume tokens) --------------

    @caller_thread
    def resume_checkpoint(self, ck, on_tokens=None):
        """Admit one generate checkpoint — an SGC1 dict, a base64 resume
        token, or raw SGC1 bytes — and continue the generation exactly
        where it stopped (byte-identical, spans never re-sent). The
        decode-side entry point of a drain handoff and of a client's
        crash-resume retry; the engine's ``POST /drain`` import mode
        lands here per checkpoint."""
        from ..serving.migration import decode_checkpoint, parse_token

        if self.batcher is None:
            self.load()
        if isinstance(ck, str):
            ck = parse_token(ck)
        elif isinstance(ck, (bytes, bytearray)):
            ck = decode_checkpoint(bytes(ck))
        return self.batcher.submit_checkpoint(ck, on_tokens=on_tokens)

    def _settle_migrated(self, req, peer_future) -> None:
        """Done-callback chaining a migrated request's peer future back
        into the ORIGINAL future the local client thread is waiting on:
        the connection that carried the request never sees the drain."""
        if req.future.done():
            return
        try:
            req.future.set_result(peer_future.result())
        except Exception as e:  # noqa: BLE001 - relay the typed failure
            req.future.set_exception(e)

    @caller_thread
    def drain_to(self, peer, timeout_s: float = 60.0) -> Dict[str, Any]:
        """Graceful drain: checkpoint every in-flight generation at a
        poll boundary (``ContinuousBatcher.drain`` — the member flips to
        the ``"draining"`` health state and refuses new work typed) and
        hand the checkpoints plus queued requests to ``peer``:

        * a live server object exposing ``resume_checkpoint`` —
          loopback: per-request futures chain back into the original
          waiters and streamed spans keep flowing through the original
          ``on_tokens`` consumer, so clients observe nothing;
        * a ``"host:port"`` string — the peer ENGINE's ``POST /drain``
          route over TCP (``serving.migration.post_drain``): the final
          token lists come back positionally, stream consumers get the
          post-checkpoint tail as one span (never a re-send).

        Every request completes byte-identical to an uninterrupted run
        (greedy and seeded sampling — the SGC1 checkpoint carries the
        exact post-split RNG lane key). Returns a summary dict; failed
        handoffs fail their original futures typed rather than hanging
        them."""
        from ..serving import migration

        if self.batcher is None:
            self.load()
        b = self.batcher
        drained = b.drain(timeout_s=timeout_s)
        cks = [migration.checkpoint_of(req, b.weight_version)
               for req in drained]
        for req in drained:
            # the work leaves this member (peer resume, or typed failure
            # below): its host-tier K/V checkpoint would otherwise pin
            # tier budget forever
            b._release_tier_ckpt(req)
        with b._export_lock:
            b.stats["checkpoint_exports"] += len(cks)
        if b.flight is not None and b.flight.enabled:
            for ck in cks:
                b.flight.record({
                    "type": "checkpoint_export",
                    "tokens": len(ck["prompt"]),
                    "emitted": len(ck["emitted"]),
                    "weight_version": b.weight_version,
                })
        handed = failed = 0
        if hasattr(peer, "resume_checkpoint"):
            for req, ck in zip(drained, cks):
                try:
                    pf = peer.resume_checkpoint(ck, on_tokens=req.on_tokens)
                except Exception as e:  # noqa: BLE001 - typed refusal
                    failed += 1
                    if not req.future.done():
                        req.future.set_exception(e)
                    continue
                handed += 1
                pf.add_done_callback(
                    lambda f, req=req: self._settle_migrated(req, f)
                )
        else:
            try:
                results = migration.post_drain(
                    str(peer), cks, timeout_s=timeout_s
                )
            except Exception as e:  # noqa: BLE001 - typed refusal
                for req in drained:
                    failed += 1
                    if not req.future.done():
                        req.future.set_exception(e)
                results = None
            if results is not None:
                for req, ck, res in zip(drained, cks, results):
                    handed += 1
                    if req.on_tokens is not None:
                        # the post-checkpoint tail as one span: spans at
                        # or before stream_pos were already delivered
                        tail = list(res)[
                            len(ck["prompt"]) + ck["stream_pos"]:
                        ]
                        if tail:
                            try:
                                req.on_tokens(tail)
                            except Exception:  # noqa: BLE001 - consumer bug
                                logger.exception("on_tokens relay failed")
                    if not req.future.done():
                        req.future.set_result(list(res))
        with b._export_lock:
            b.stats["migrations"] += handed
        if b.flight is not None and b.flight.enabled and handed:
            b.flight.record({
                "type": "migrated_resume",
                "peer": getattr(peer, "model_uri", None) or str(peer),
                "handed": handed,
            })
        logger.info(
            "drain_to: %d checkpoint(s) exported, %d handed to the "
            "peer, %d failed typed", len(cks), handed, failed,
        )
        return {
            "drained": len(drained),
            "checkpoints": len(cks),
            "handed": handed,
            "failed": failed,
        }

    def _make_resume_token(self, req, prompt, delivered, kw,
                           text_mode=False) -> str:
        """Opaque resume token for a live generation: the SGC1 payload
        over prompt + delivered-so-far, keyless (the resume side
        re-derives the lane key from seed + emitted count) so refreshing
        it per span costs zero device syncs. ``text_mode`` rides the
        checkpoint so a resumed strData stream keeps decoding ``text``
        fields."""
        import time as _time

        from ..serving.migration import checkpoint_token

        gr = getattr(req, "gen_request", None) or req
        now = _time.monotonic()
        return checkpoint_token({
            "v": 1,
            "prompt": [int(t) for t in prompt],
            "emitted": [int(t) for t in delivered],
            "rng_key": None,
            "text_mode": bool(text_mode),
            "max_new_tokens": int(kw.get("max_new_tokens", 32)),
            "temperature": float(kw.get("temperature", 0.0)),
            "eos_id": kw.get("eos_id"),
            "seed": int(kw.get("seed", 0)),
            "weight_version": self.batcher.weight_version,
            "wait_s": round(max(0.0, now - gr.submit_t), 6)
            if getattr(gr, "submit_t", 0.0) else 0.0,
            "submit_wall_us": int(getattr(gr, "submit_wall_us", 0) or 0),
            "deadline_s": (
                max(0.0, gr.deadline_t - now)
                if getattr(gr, "deadline_t", None) is not None else None
            ),
            "stream_pos": len(delivered),
        })

    @caller_thread
    def _collect_results(self, futures, token_lists, kw, deadline_s,
                         expires_at, retry_prefix_gone=False):
        """Await every request future under the remaining deadline budget
        — ONE implementation for the unified and decode-role paths so
        the deadline/cancellation semantics cannot drift apart.

        All-or-nothing: any failure (or budget exhaustion) cancels the
        sibling futures, reclaiming queued slots and mid-decode lanes,
        before the error surfaces. Waits never exceed the request's own
        budget (600s safety fallback without one) — an abandoned wait
        would pin this worker thread and its decode lane.
        ``retry_prefix_gone`` adds the decode-role contract: a
        suffix-only handoff whose radix donor was evicted before the
        splice re-requests the FULL slab once — correctness never
        depends on the cache."""
        import time as _time

        from ..resilience import DeadlineExceeded

        def remaining() -> float:
            if expires_at is None:
                return 600.0
            return max(0.001, expires_at - _time.monotonic())

        try:
            results = []
            for i, f in enumerate(futures):
                try:
                    results.append(f.result(timeout=remaining()))
                except Exception as e:
                    if retry_prefix_gone:
                        from ..serving.disagg import PrefixGone

                        if isinstance(e, PrefixGone):
                            f2 = self._remote_submit(
                                token_lists[i], kw, deadline_s, covered=0
                            )
                            futures[i] = f2
                            results.append(f2.result(timeout=remaining()))
                            continue
                    raise
        except FuturesTimeout:
            for f in futures:
                f.cancel()
            if deadline_s is None:
                raise  # the 600s safety fallback fired, not a budget
            raise DeadlineExceeded(
                f"generate ran past its {deadline_s * 1000:.0f}ms budget"
            )
        except Exception:
            for f in futures:
                f.cancel()
            raise
        return results

    @caller_thread
    def _predict_disagg(self, token_lists, kw, deadline_s, expires_at):
        """Decode-role submit loop: prefill at the peer pool, slab over
        the KV transport, then the shared all-or-nothing collection.

        Multi-prompt requests dispatch their transfers CONCURRENTLY —
        sequential round trips would make prompt N's TTFT pay N-1 whole
        prefill+transfer latencies, and the prefill listener's bounded
        handler pool exists precisely to serve them in parallel."""
        from concurrent.futures import ThreadPoolExecutor

        if len(token_lists) == 1:
            futures = [self._remote_submit(token_lists[0], kw, deadline_s)]
        else:
            with ThreadPoolExecutor(
                max_workers=min(8, len(token_lists)),
                thread_name_prefix="kv-transfer",
            ) as pool:
                submits = [
                    pool.submit(self._remote_submit, toks, kw, deadline_s)
                    for toks in token_lists
                ]
            # the with-block joined the pool: every transfer has finished,
            # one way or the other. All-or-nothing: any failure cancels
            # EVERY sibling whose slab landed (sweeping `submits`, not a
            # partial collection list, so no admitted lane can leak).
            err = next(
                (sf.exception() for sf in submits if sf.exception()), None
            )
            if err is not None:
                for sf in submits:
                    if sf.exception() is None:
                        sf.result().cancel()
                raise err
            # in submission order, so responses stay positional
            futures = [sf.result() for sf in submits]
        results = self._collect_results(
            futures, token_lists, kw, deadline_s, expires_at,
            retry_prefix_gone=True,
        )
        return futures, results

    def close(self) -> None:
        """Stop the KV transport endpoints and the scheduler."""
        if self.tenant_scheduler is not None:
            # before the batcher: the driver blocks on swap futures the
            # poll loop resolves, and stop() fails queued work typed
            self.tenant_scheduler.stop()
            self.tenant_scheduler = None
        if self._kv_server is not None:
            self._kv_server.close()
            self._kv_server = None
        if self._kv_client is not None:
            self._kv_client.close()
            self._kv_client = None
        if self.batcher is not None:
            self.batcher.close()

    @caller_thread
    def predict(self, X, names, meta=None):
        received_t = time.monotonic()
        if self.batcher is None:
            self.load()
        if self._role == "prefill":
            raise RuntimeError(
                "this unit is a prefill-role pool member: it serves the "
                "KV transport only — route generate requests at the "
                "decode pool"
            )
        body = X if isinstance(X, dict) else None
        if body is None:
            if isinstance(X, str):
                body = {"prompt": X}
            else:
                raise ValueError(
                    "generate expects jsonData {prompt_tokens|prompt, ...} or strData"
                )
        # remaining deadline budget rides the request meta (stamped per
        # hop by the graph executor): the batcher sheds the submit when
        # its admit queue cannot meet it (ShedError -> engine 429)
        from ..resilience import deadline_s_from_meta

        deadline_s = deadline_s_from_meta(meta)
        import time as _time

        expires_at = (
            _time.monotonic() + deadline_s if deadline_s is not None else None
        )
        if body.get("resume_token"):
            # crash-resume retry: the opaque SGC1 token continues the
            # generation exactly where the dead member stopped —
            # byte-identical, wait telemetry cumulative
            from ..serving.migration import parse_token

            ck = parse_token(str(body["resume_token"]))
            fut = self.batcher.submit_checkpoint(ck)
            gr = getattr(fut, "gen_request", None)
            prompt = list(gr.tokens) if gr is not None else []
            results = self._collect_results(
                [fut], [prompt], {}, deadline_s, expires_at
            )
            out: Dict[str, Any] = {"tokens": results}
            if ck.get("text_mode"):
                out["text"] = [self._decode(results[0][len(prompt):])]
            if self._resume_tokens and gr is not None:
                out["resume_tokens"] = [self._make_resume_token(
                    fut, prompt, results[0][len(prompt):],
                    {"max_new_tokens": gr.max_new_tokens,
                     "temperature": gr.temperature,
                     "eos_id": gr.eos_id, "seed": gr.seed},
                    text_mode=bool(ck.get("text_mode")),
                )]
            return out
        token_lists, text_mode, kw = self._parse_prompts(body)
        if self._role == "decode":
            # disaggregated path: prefill happens at the peer pool, the
            # slab crosses the KV transport, decode runs here
            futures, results = self._predict_disagg(
                token_lists, kw, deadline_s, expires_at
            )
            return self._build_response(
                futures, results, token_lists, text_mode, kw=kw
            )
        submit = self.batcher.submit
        skw = dict(kw)
        if self.tenant_scheduler is not None:
            # multi-tenant routing: the scheduler passes the resident
            # tenant's work straight through and queues everyone else
            # for a page-in; the id arrives in the message meta (engine
            # stamps the Seldon-Tenant header) or the body (direct use)
            from ..serving.weightpager import tenant_from_meta

            submit = self.tenant_scheduler.submit
            skw["tenant"] = body.get("tenant") or tenant_from_meta(meta)
        futures = []
        try:
            for toks in token_lists:
                futures.append(
                    submit(toks, deadline_s=deadline_s, **skw)
                )
        except Exception:
            # a multi-prompt request is all-or-nothing: whatever failed a
            # later submit (shed 429, over-long prompt 400, closed
            # batcher), cancel the prompts already queued so the error
            # never leaves orphaned device work decoding for a response
            # nobody will collect
            for f in futures:
                f.cancel()
            raise
        for f in futures:
            gr = getattr(f, "gen_request", None)
            if gr is not None:
                gr.front.received_t = received_t
        results = self._collect_results(
            futures, token_lists, kw, deadline_s, expires_at
        )
        return self._build_response(
            futures, results, token_lists, text_mode, kw=kw
        )

    def _build_response(self, futures, results, token_lists, text_mode,
                        kw=None):
        out: Dict[str, Any] = {"tokens": results}
        if text_mode:
            out["text"] = [
                self._decode(r[len(p):]) for r, p in zip(results, token_lists)
            ]
        if self._resume_tokens and kw is not None:
            out["resume_tokens"] = [
                self._make_resume_token(f, p, r[len(p):], kw,
                                        text_mode=text_mode)
                for f, r, p in zip(futures, results, token_lists)
            ]
        if self.batcher._prefix_index is not None:
            # per-request prompt tokens served from the prefix cache, in
            # request order — graph nodes and the engine report it. For a
            # decode pool the hit doubles as the transfer-dedup count:
            # those tokens' K/V never crossed the wire
            out["cache_hit_tokens"] = [
                int(getattr(getattr(f, "gen_request", None),
                            "cache_hit_tokens", 0))
                for f in futures
            ]
        return out

    @caller_thread
    def stream(self, body: Dict[str, Any]) -> "StreamHandle":
        """Streaming generate: validates and SUBMITS eagerly (malformed
        bodies and closed batchers raise HERE, before any response bytes
        exist), then returns a :class:`StreamHandle` whose ``chunks``
        iterator yields ``{"tokens": [...]}`` per credited span and a
        final ``{"done": true, "tokens": [prompt+generated]}``.
        ``handle.cancel()`` (client disconnect) releases the decode lane.
        One prompt per stream; batch prompts belong to unary predict."""
        import queue as _queue

        if self.batcher is None:
            self.load()
        if self._role == "prefill":
            raise RuntimeError(
                "prefill-role pool members serve the KV transport only"
            )
        q: "_queue.Queue" = _queue.Queue()
        if body.get("resume_token"):
            # crash-resume of an interrupted stream: continue from the
            # token's checkpoint — only NEW spans are yielded (crediting
            # resumes after the checkpoint), so no span is ever re-sent
            from ..serving.migration import parse_token

            ck = parse_token(str(body["resume_token"]))
            text_mode = bool(ck.get("text_mode"))
            toks = [int(t) for t in ck["prompt"]]
            kw = dict(
                max_new_tokens=int(ck.get("max_new_tokens", 32)),
                temperature=float(ck.get("temperature", 0.0)),
                eos_id=ck.get("eos_id"),
                seed=int(ck.get("seed", 0)),
            )
            resume_base = [int(t) for t in ck.get("emitted") or []]
            fut = self.batcher.submit_checkpoint(ck, on_tokens=q.put)
        else:
            token_lists, text_mode, kw = self._parse_prompts(body)
            if len(token_lists) != 1:
                raise ValueError("stream takes ONE prompt")
            toks = token_lists[0]
            resume_base = []
            if self._role == "decode":
                # streamed disaggregated generate: the slab handoff
                # happens before the first byte goes out, then tokens
                # stream as spans land exactly like the unary path.
                # Always the FULL slab (covered=0): the unary path's
                # PrefixGone retry cannot be replayed once response
                # bytes exist, so streaming trades the transfer dedup
                # for a handoff that can never lose its donor mid-stream
                fut = self._remote_submit(toks, kw, None, covered=0,
                                          on_tokens=q.put)
            elif self.tenant_scheduler is not None:
                from ..serving.weightpager import tenant_from_meta

                fut = self.tenant_scheduler.submit(
                    toks, tenant=body.get("tenant")
                    or tenant_from_meta(body.get("meta")),
                    on_tokens=q.put, **kw,
                )
            else:
                fut = self.batcher.submit(toks, on_tokens=q.put, **kw)
        fut.add_done_callback(lambda _f: q.put(None))

        def chunks():
            # delivered-so-far accumulator: the per-span resume token is
            # the SGC1 checkpoint over prompt + delivered (keyless — the
            # resume side re-derives the lane key), refreshed per span
            delivered = list(resume_base)
            while True:
                item = q.get()
                if item is None:
                    break
                delivered.extend(int(t) for t in item)
                chunk: Dict[str, Any] = {"tokens": item}
                if text_mode:
                    chunk["text"] = self._decode(item)
                if self._resume_tokens:
                    chunk["resume_token"] = self._make_resume_token(
                        fut, toks, delivered, kw, text_mode=text_mode
                    )
                yield chunk
            result = fut.result(timeout=600.0)
            final: Dict[str, Any] = {"done": True, "tokens": result}
            if text_mode:
                final["text"] = self._decode(result[len(toks):])
            if self.batcher._prefix_index is not None:
                final["cache_hit_tokens"] = int(
                    getattr(getattr(fut, "gen_request", None),
                            "cache_hit_tokens", 0)
                )
            yield final

        return StreamHandle(chunks=chunks(), cancel=fut.cancel,
                            request=getattr(fut, "gen_request", None))

    @caller_thread
    def hot_swap(self, model_uri: str, wait_s: float = 30.0) -> Dict[str, Any]:
        """Live weight hot-swap: load a new checkpoint and replace the
        served weights WITHOUT restarting the process or dropping a
        request (the progressive-delivery path — the engine's
        ``/weights/swap`` route lands here).

        The new checkpoint must be the SAME architecture (the decode
        executables are shape-specialized); its params are cast to the
        serving dtype and handed to the batcher's double-buffered
        ``request_weight_swap`` — new-weight upload overlaps old-weight
        serving, in-flight lanes finish on the old version, the flip
        happens at a scheduler poll boundary, and the prefix cache is
        re-keyed so old-weights K/V can never serve a new-weights
        prefill. Waits up to ``wait_s`` for the flip; a swap still
        draining after that returns ``swapped: false`` and lands on its
        own."""
        if self.batcher is None:
            self.load()
        if self.batcher.swap_pending():
            # fail BEFORE the checkpoint load: the conflict is knowable
            # now, and a large model's read+cast+upload takes minutes
            raise RuntimeError("a weight swap is already pending")
        server = JAXServer(model_uri)
        _apply, params = server.build()
        new_model = server._model
        if not isinstance(new_model, DecoderFamily):
            raise ValueError(
                f"hot-swap checkpoint at {model_uri!r} is not an llm-family "
                "model dir"
            )
        changed = self._model.config_differs(new_model)
        if changed:
            raise ValueError(
                f"hot-swap checkpoint architecture differs from the served "
                f"model ({', '.join(changed)}); same-shape checkpoints only"
            )
        import jax.numpy as jnp

        dt = jnp.dtype(self._model.compute_dtype)
        if dt != jnp.float32 and isinstance(params, dict):
            params = self._cast_params_freeing_impl(params, dt)
        self._swap_count += 1
        version = f"v{self._swap_count}"
        fut = self.batcher.request_weight_swap(params, version=version)
        swapped = True
        try:
            fut.result(timeout=max(0.001, float(wait_s)))
        except FuturesTimeout:
            swapped = False  # still draining; the flip lands on its own
        return {
            "version": version,
            "swapped": swapped,
            "model_uri": model_uri,
            "weight_version": self.batcher.weight_version,
        }

    def cancel_hot_swap(self) -> Dict[str, Any]:
        """Abort a staged swap whose drain isn't converging (admissions
        resume on the next poll); see ContinuousBatcher.cancel_weight_swap."""
        cancelled = (
            self.batcher.cancel_weight_swap()
            if self.batcher is not None else False
        )
        return {
            "cancelled": cancelled,
            "weight_version":
                self.batcher.weight_version if self.batcher else None,
        }

    def retune(self, knobs: Dict[str, Any], origin: str = "planner",
               wait_s: float = 10.0) -> Dict[str, Any]:
        """Actuate a live scheduler retune through the safe path (the
        engine's ``POST /retune`` route and the reconciler's planner
        tick both land here): stage via ContinuousBatcher.retune() —
        synchronous typed validation against the boot compile census —
        then wait for the scheduler to apply it at a poll boundary.
        Returns ``{"changed": {knob: [old, new]}, "census": {...}}``;
        RetuneError propagates to the caller (the route maps it to a
        409-class refusal, the same contract as out-of-census configs)."""
        from ..serving.continuous import RetuneError

        if self.batcher is None:
            raise RuntimeError("retune before load(): no batcher")
        if not isinstance(knobs, dict):
            raise RetuneError(
                f"knobs must be an object, got {type(knobs).__name__}"
            )
        fut = self.batcher.retune(origin=str(origin), **knobs)
        changed = fut.result(timeout=wait_s)
        return {
            "changed": changed,
            "census": self.batcher.retune_census(),
            "origin": str(origin),
        }

    def retune_census(self) -> Optional[Dict[str, Any]]:
        """The loaded batcher's boot compile census (None before load)
        — the planner prunes its profile walk to in-census configs."""
        return (
            self.batcher.retune_census()
            if self.batcher is not None else None
        )

    def serving_config(self) -> Optional[Dict[str, Any]]:
        """The batcher's CURRENT profile-axis knob values (None before
        load) — ships in the /fleet payload so the reconciler's planner
        tick can diff the cost model's pick against what is serving."""
        return (
            self.batcher.serving_config()
            if self.batcher is not None else None
        )

    def tags(self) -> Dict:
        return {"server": "generateserver"}

    def health_status(self):
        """Readiness hook (InProcessClient.ready -> GraphExecutor.ready
        -> the engine's /ready): a batcher that is mid-crash-restart or
        latched dead flips this unit — and with it the engine — unready,
        so the gateway routes around the member and, once the crash-loop
        budget is exhausted, the reconciler replaces it. A server that
        has not loaded yet keeps the default lenient readiness."""
        b = self.batcher
        if b is not None and b.health != "serving":
            raise RuntimeError(f"continuous batcher is {b.health}")
        return "ok"

    def flight_dump(self, limit: Optional[int] = None) -> Optional[Dict[str, Any]]:
        """Scheduler flight-recorder export (the ``/flightrecorder`` route's
        payload): the per-poll decision ring plus the SLO reservoir summary
        and a scheduler-stat snapshot, so one dump is enough to attribute a
        tail-latency regression. None when the recorder is off/not loaded."""
        if self.batcher is None or self.batcher.flight is None:
            return None
        if self.batcher._kv_tier is not None:
            self.batcher.sync_kv_tier_stats()
        out = self.batcher.flight.dump(limit)
        out["slo"] = self.batcher.slo_summary()
        out["stats"] = {k: v for k, v in self.batcher.stats.items()}
        out["weight_version"] = self.batcher.weight_version
        pressure = self.batcher.pressure_summary()
        if pressure is not None:
            out["pressure"] = pressure
        tier = self.batcher.kv_tier_summary()
        if tier is not None:
            out["kv_tier"] = tier
        if self.tenant_pager is not None:
            out["weight_pager"] = self.tenant_pager.summary()
        if self.tenant_scheduler is not None:
            out["tenant_scheduler"] = self.tenant_scheduler.summary()
        if self.profiler is not None and self.profiler.enabled:
            out["profiler"] = self.profiler.summary()
        if self.slo_burn is not None:
            out["slo_burn"] = self.slo_burn.summary()
        return out

    def metrics(self) -> List[Dict]:
        """Meta.metrics hook: every cumulative scheduler total ships as a
        COUNTER **delta** through one CounterDeltas instance (the engine
        sink sums counter values per response — see metrics.CounterDeltas
        for the contract), SLO samples ship as per-completion TIMERs the
        engine folds into TTFT/TPOT/queue-wait histograms, and only true
        levels (cache bytes, occupancy, acceptance) ship as GAUGEs."""
        if self.batcher is None:
            return []
        s = self.batcher.stats
        delta = self._deltas.counter
        out = [
            delta("gen_tokens", s["tokens"]),
            delta("gen_steps", s["steps"]),
            delta("gen_finished", s["finished"]),
            delta("gen_admitted", s["admitted"]),
            # prefill-vs-decode split: per-node cache wins show up as
            # prefill step/token counters flattening while decode keeps pace
            delta("gen_prefill_steps", s["prefill_steps"]),
            delta("gen_prefill_tokens", s["prefill_tokens"]),
            delta("gen_decode_steps", s["steps"]),
            # per-burst modeled HBM read traffic (params + bucketed KV per
            # dispatched burst)
            delta("gen_burst_reads", s["burst_reads"]),
            delta("gen_burst_read_bytes", s["burst_read_bytes"]),
        ]
        if s.get("compiles_after_ready"):
            # XLA compiled while serving: a request waited for an
            # executable warm() was not told of (declare the length)
            out.extend(delta(key, s[key[len("gen_"):]])
                       for key in COMPILE_TELEMETRY_KEYS)
        if s.get("prefill_chunks"):
            out.append(delta("gen_prefill_chunks", s["prefill_chunks"]))
        if s.get("fused_dispatches"):
            # fused multi-step decode: device steps per dispatched fused
            # burst — engine_metrics maps these to the first-class
            # seldon_engine_fused_{steps,dispatches} series; their ratio
            # is the realized K (the dispatch-floor win)
            out.extend([
                delta("gen_fused_steps", s["fused_steps"]),
                delta("gen_fused_dispatches", s["fused_dispatches"]),
            ])
        if s.get("shed"):
            out.append(delta("gen_shed_total", s["shed"]))
        if s.get("weight_swaps"):
            out.append(delta("gen_weight_swaps", s["weight_swaps"]))
        if s.get("planner_retunes"):
            # autonomic planner actuations that landed at a poll
            # boundary — engine_metrics maps this to the first-class
            # seldon_engine_planner_retunes series (rate > a few per
            # minute = the planner is thrashing; flight_report renders
            # the matching planner_retune records with a DIAGNOSIS)
            out.append(delta("gen_planner_retunes", s["planner_retunes"]))
        # fault-tolerance counters + the first-class health gauge: the
        # engine sink maps the counters to seldon_engine_batcher_restarts
        # / _peer_ejections / _degraded_local_prefill (engine_metrics
        # _RECOVERY) so a chaotic run is diagnosable off /metrics alone
        out.append({
            "type": "GAUGE", "key": "gen_batcher_healthy",
            "value": 1.0 if self.batcher.health == "serving" else 0.0,
        })
        if self.batcher.mesh is not None:
            # sharded serving: mesh shape + the per-chip footprint levels
            # (engine_metrics maps these to the first-class
            # seldon_engine_mesh_* gauges) — param_shard_bytes vs the
            # global param bytes is the >1-chip-model headroom proof
            mshape = dict(self.batcher.mesh.shape)
            out.extend([
                {"type": "GAUGE", "key": "gen_mesh_devices",
                 "value": float(self.batcher.mesh.devices.size)},
                {"type": "GAUGE", "key": "gen_mesh_data",
                 "value": float(mshape.get("data", 1))},
                {"type": "GAUGE", "key": "gen_mesh_model",
                 "value": float(mshape.get("model", 1))},
                {"type": "GAUGE", "key": "gen_mesh_param_shard_bytes",
                 "value": float(self.batcher._param_shard_bytes)},
                {"type": "GAUGE", "key": "gen_mesh_kv_shard",
                 "value": float(self.batcher._kv_shard)},
            ])
        if s.get("batcher_restarts"):
            out.append(delta("gen_batcher_restarts", s["batcher_restarts"]))
        if s.get("peer_ejections"):
            out.append(delta("gen_peer_ejections", s["peer_ejections"]))
        if s.get("peer_readmissions"):
            out.append(delta("gen_peer_readmissions",
                             s["peer_readmissions"]))
        if s.get("degraded_local_prefill"):
            out.append(delta("gen_degraded_local_prefill",
                             s["degraded_local_prefill"]))
        # live migration: graceful drains, checkpoints exported/handed
        # to a peer, resumes admitted from wire checkpoints or resume
        # tokens, and hot-swap straggler preemptions — engine_metrics
        # maps these to seldon_engine_drains_total /
        # seldon_engine_migrations_total and friends
        if s.get("drains"):
            out.append(delta("gen_drains", s["drains"]))
        if s.get("checkpoint_exports"):
            out.append(delta("gen_checkpoint_exports",
                             s["checkpoint_exports"]))
        if s.get("migrations"):
            out.append(delta("gen_migrations", s["migrations"]))
        if s.get("migrated_resumes"):
            out.append(delta("gen_migrated_resumes",
                             s["migrated_resumes"]))
        if s.get("swap_preemptions"):
            out.append(delta("gen_swap_preemptions",
                             s["swap_preemptions"]))
        # HBM pressure: preemption/resume/shed counters plus the ledger
        # gauges — engine_metrics maps them to the first-class
        # seldon_engine_pressure_* / seldon_engine_preemptions series so
        # an overload window is diagnosable straight off /metrics
        if s.get("preemptions"):
            out.append(delta("gen_preemptions", s["preemptions"]))
        if s.get("preempt_resumes"):
            out.append(delta("gen_preempt_resumes", s["preempt_resumes"]))
        if s.get("pressure_sheds"):
            out.append(delta("gen_pressure_sheds", s["pressure_sheds"]))
        if s.get("pressure_refused"):
            out.append(delta("gen_pressure_refused", s["pressure_refused"]))
        if s.get("pressure_prefix_evictions"):
            out.append(delta("gen_pressure_prefix_evictions",
                             s["pressure_prefix_evictions"]))
        # tiered KV memory: demote/promote/hit/evict counters plus the
        # tier's live byte level — engine_metrics maps them to the
        # first-class seldon_engine_kv_tier_* series (host RAM, NOT the
        # HBM pressure gauges)
        if self.batcher._kv_tier is not None:
            self.batcher.sync_kv_tier_stats()
            out.extend([
                delta("gen_kv_tier_demotions", s["kv_tier_demotions"]),
                delta("gen_kv_tier_promotions", s["kv_tier_promotions"]),
                delta("gen_kv_tier_hits", s["kv_tier_hits"]),
                delta("gen_kv_tier_evictions", s["kv_tier_evictions"]),
                delta("gen_kv_tier_replay_fallbacks",
                      s["kv_tier_replay_fallbacks"]),
                {"type": "GAUGE", "key": "gen_kv_tier_bytes",
                 "value": float(s["kv_tier_bytes"])},
            ])
        pressure = self.batcher.pressure_summary()
        if pressure is not None:
            out.extend([
                {"type": "GAUGE", "key": "gen_pressure_used_bytes",
                 "value": float(pressure["used_bytes"])},
                {"type": "GAUGE", "key": "gen_pressure_budget_bytes",
                 "value": float(pressure["budget_bytes"])},
                {"type": "GAUGE", "key": "gen_pressure_active",
                 "value": 1.0 if pressure["active"] else 0.0},
            ])
        if s.get("kv_exports") or s.get("kv_imports"):
            # disaggregated serving: slab/byte counters per direction plus
            # the transfer-dedup savings — engine_metrics maps these to
            # the first-class seldon_engine_kv_transfer_* series
            out.extend([
                delta("gen_kv_export_slabs", s["kv_exports"]),
                delta("gen_kv_export_bytes", s["kv_export_bytes"]),
                delta("gen_kv_import_slabs", s["kv_imports"]),
                delta("gen_kv_import_bytes", s["kv_import_bytes"]),
                delta("gen_kv_transfer_bytes_saved",
                      s["kv_transfer_bytes_saved"]),
            ])
        if self.batcher._prefix_index is not None:
            out.extend([
                delta("prefix_cache_hits", s["prefix_hits"]),
                delta("prefix_cache_misses", s["prefix_misses"]),
                delta("prefix_cache_evictions", s["prefix_evicted"]),
                delta("prefix_tokens_saved", s["prefix_tokens_saved"]),
                {"type": "GAUGE", "key": "prefix_cache_bytes",
                 "value": float(s["prefix_cache_bytes"])},
            ])
        if s.get("spec_rounds"):
            out.append(
                {
                    "type": "GAUGE",
                    "key": "gen_spec_tokens_per_round",
                    # 1.0 = nothing accepted, gamma+1 = every draft accepted
                    "value": round(s["spec_emitted"] / s["spec_rounds"], 4),
                }
            )
        # SLO samples: one TIMER triple per request completed since the
        # last export (drained, bounded by the pending ring). The engine
        # sink turns TIMER ms into seconds histograms per graph node —
        # TTFT/TPOT/queue-wait become first-class series there
        # (engine_metrics._SLO_TIMERS).
        pending = self.batcher.slo_pending
        while pending:
            try:
                queue_wait, ttft, tpot = pending.popleft()
            except IndexError:  # raced another exporter thread
                break
            out.append({"type": "TIMER", "key": "gen_queue_wait_ms",
                        "value": round(queue_wait * 1e3, 4)})
            out.append({"type": "TIMER", "key": "gen_ttft_ms",
                        "value": round(ttft * 1e3, 4)})
            if tpot is not None:
                out.append({"type": "TIMER", "key": "gen_tpot_ms",
                            "value": round(tpot * 1e3, 4)})
            if self.slo_burn is not None:
                # the burn engine rides the SAME drain: one sample feed,
                # two consumers (histograms + error budgets)
                self.slo_burn.observe("queue_wait", queue_wait)
                self.slo_burn.observe("ttft", ttft)
                self.slo_burn.observe("tpot", tpot)
        if self.tenant_pager is not None:
            # multi-tenant serving: pager counters/levels plus PER-TENANT
            # request counters and SLO timer triples, each tagged with
            # its tenant id — engine_metrics maps them to the
            # seldon_engine_tenant_* / seldon_engine_weight_pager_*
            # series, and the tag becomes a label so one /metrics scrape
            # separates every tenant's histograms
            p = self.tenant_pager.stats
            out.extend([
                delta("gen_weight_page_ins", p["page_ins"]),
                delta("gen_weight_page_outs", p["page_outs"]),
                delta("gen_weight_pager_evictions", p["evictions"]),
                delta("gen_weight_pager_refused", p["refused"]),
                {"type": "GAUGE", "key": "gen_weight_pager_host_bytes",
                 "value": float(self.tenant_pager.host_bytes)},
                {"type": "GAUGE", "key": "gen_weight_pager_resident_bytes",
                 "value": float(self.tenant_pager.resident_hbm_bytes)},
                {"type": "GAUGE", "key": "gen_tenants_registered",
                 "value": float(len(self.tenant_pager.tenants()))},
            ])
            if self.tenant_scheduler is not None:
                out.append(delta(
                    "gen_tenant_switches",
                    self.tenant_scheduler.stats["switches"],
                ))
            for t, sums in list(self.batcher.tenant_slo.items()):
                out.append(delta("gen_tenant_requests", sums["finished"],
                                 tags={"tenant": t}))
            for t, tp in list(self.batcher.tenant_slo_pending.items()):
                while tp:
                    try:
                        queue_wait, ttft, tpot = tp.popleft()
                    except IndexError:  # raced another exporter thread
                        break
                    if self.slo_burn is not None:
                        self.slo_burn.observe("queue_wait", queue_wait, t)
                        self.slo_burn.observe("ttft", ttft, t)
                        self.slo_burn.observe("tpot", tpot, t)
                    tags = {"tenant": t}
                    out.append({"type": "TIMER",
                                "key": "gen_tenant_queue_wait_ms",
                                "value": round(queue_wait * 1e3, 4),
                                "tags": tags})
                    out.append({"type": "TIMER", "key": "gen_tenant_ttft_ms",
                                "value": round(ttft * 1e3, 4),
                                "tags": tags})
                    if tpot is not None:
                        out.append({"type": "TIMER",
                                    "key": "gen_tenant_tpot_ms",
                                    "value": round(tpot * 1e3, 4),
                                    "tags": tags})
        if self.profiler is not None and self.profiler.enabled:
            # device-time ledger: cumulative per-(kind, variant, tenant)
            # buckets ship as COUNTER deltas — engine_metrics maps them
            # to the seldon_engine_device_* series with the attribution
            # as labels — plus the live gauges the sliding window backs
            for (kind, variant, tenant), (secs, n, nbytes, _toks) in sorted(
                self.profiler.buckets().items()
            ):
                tags = {"kind": kind, "variant": variant}
                if tenant:
                    tags["tenant"] = tenant
                out.append(delta(
                    "gen_device_time_ms",
                    round(secs * 1e3, 3), tags=tags,
                ))
                out.append(delta("gen_device_dispatches", n, tags=tags))
                out.append(delta("gen_device_bytes", nbytes, tags=tags))
            live = self.profiler.gauges()
            for key, name in (("device_busy_frac", "gen_device_busy_frac"),
                              ("mbu_pct", "gen_mbu_pct"),
                              ("dispatch_floor_pct",
                               "gen_dispatch_floor_pct")):
                val = live.get(key)
                if val is not None:
                    out.append({"type": "GAUGE", "key": name,
                                "value": float(val)})
        if self.slo_burn is not None:
            # burn-rate verdicts: per-(tenant, slo) gauges + a severity
            # counter — the fleet scrape and the reconciler's scale
            # signals read the same feed via slo_verdicts()
            for v in self.slo_burn.verdicts():
                tags = {"slo": v["slo"], "window": "fast"}
                if v["tenant"]:
                    tags["tenant"] = v["tenant"]
                out.append({"type": "GAUGE", "key": "gen_slo_burn_rate",
                            "value": v["fast_burn"], "tags": dict(tags)})
                tags["window"] = "slow"
                out.append({"type": "GAUGE", "key": "gen_slo_burn_rate",
                            "value": v["slow_burn"], "tags": dict(tags)})
                del tags["window"]
                out.append({"type": "GAUGE",
                            "key": "gen_slo_budget_remaining",
                            "value": v["budget_remaining"],
                            "tags": dict(tags)})
            for (t, slo, sev), n in sorted(
                self.slo_burn.verdict_counts().items()
            ):
                tags = {"slo": slo, "severity": sev}
                if t:
                    tags["tenant"] = t
                out.append(delta("gen_slo_verdicts", n, tags=tags))
        return out
