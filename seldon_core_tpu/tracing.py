"""Distributed tracing: per-hop spans, Jaeger agent export, JAX hooks.

Parity with the reference's Jaeger/OpenTracing wiring (reference: engine
TracingProvider + span re-activation across async graph hops
PredictiveUnitBean.java:85-118, outbound header injection
InternalPredictionService.java:141-144, Python wrapper jaeger setup
python/seldon_core/microservice.py:116-151). The image has no jaeger
client, so the agent protocol is implemented directly: finished spans are
pushed to the Jaeger agent over UDP in thrift-compact ``emitBatch``
datagrams (``JAEGER_AGENT_HOST``/``JAEGER_AGENT_PORT`` env, the
reference's exact knobs), with per-request probabilistic sampling
(``JAEGER_SAMPLER_TYPE``/``JAEGER_SAMPLER_PARAM``). Spans are also kept
in-process and served in Jaeger HTTP-API JSON shape at the engine's
``/traces`` route; propagation uses the ``uber-trace-id`` header format
so traces stitch across engine → microservice process hops.

TPU deltas: ``device_trace`` wraps ``jax.profiler.TraceAnnotation`` so a
span's name shows up inside XLA device profiles; :class:`PhaseClock`
partitions one thread's time into such spans and always-on counters;
:class:`HostClock` (with its :class:`Heartbeat`) is that thread's account
of what the host did to it meanwhile, and :class:`CompileLog` the
process's log of every XLA compile by name and stage; and
``start_capture``/``stop_capture`` are the one control that brackets a
window in the running process — the JAX profiler if asked for, and a
report of what the registered sources counted and stamped meanwhile.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import gc
import os
import random
import threading
import time
from collections import deque
import weakref
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

TRACE_HEADER = "uber-trace-id"  # trace_id:span_id:parent_span_id:flags
BAGGAGE_PREFIX = "uberctx-"

# Monotonic->wall anchor, sampled ONCE at import: every span/flight-
# recorder timestamp is derived as anchor + monotonic offset, so an NTP
# step mid-flight can never disorder spans within a trace or corrupt
# the intervals between recorder entries. time.time() appears only here
# (the seldon-lint wall-clock rule allows *WALL* anchor assignments).
_WALL_ANCHOR_US = int(time.time() * 1e6)
_MONO_ANCHOR = time.monotonic()


def wall_us(monotonic_t: Optional[float] = None) -> int:
    """Wall-clock microseconds for event timestamps, derived from the
    monotonic clock via the process-lifetime anchor. Pass a stored
    ``time.monotonic()`` reading to place a past event; default is
    now.

    Deliberate tradeoff: a wall-clock step AFTER process start (late
    NTP sync) leaves this process's timestamps offset from other
    hosts' by the step size for the process lifetime — cross-process
    span alignment degrades by that constant, but intra-process span
    ordering and every recorded interval stay exact, which is what
    deadline math and flight-recorder diffing depend on. Run serving
    hosts with time synced before process start (standard fleet
    practice) and the offset is bounded by normal NTP slew."""
    m = time.monotonic() if monotonic_t is None else monotonic_t
    return _WALL_ANCHOR_US + int((m - _MONO_ANCHOR) * 1e6)

_current_span: contextvars.ContextVar[Optional["Span"]] = contextvars.ContextVar(
    "seldon_tpu_span", default=None
)


def _rand_id() -> str:
    return f"{random.getrandbits(64):016x}"


@dataclass
class Span:
    operation: str
    trace_id: str
    span_id: str
    parent_id: Optional[str] = None
    start_us: int = 0
    duration_us: int = 0
    tags: Dict[str, Any] = field(default_factory=dict)
    logs: List[Dict[str, Any]] = field(default_factory=list)
    # uber-trace-id flags byte; bit 0 is the SAMPLED bit. Locally created
    # spans only exist when sampled, so 1 is the default — extracted
    # remote stubs carry whatever the upstream hop decided.
    flags: int = 1

    def set_tag(self, key: str, value: Any) -> "Span":
        self.tags[key] = value
        return self

    def log(self, **fields) -> None:
        self.logs.append({"timestamp": wall_us(), "fields": fields})

    def context_header(self) -> str:
        return f"{self.trace_id}:{self.span_id}:{self.parent_id or '0'}:{self.flags:x}"


class Tracer:
    """In-process span collector with contextvar activation and optional
    UDP push to a Jaeger agent."""

    def __init__(self, service_name: str = "seldon-tpu", max_spans: int = 4096,
                 enabled: bool = True, exporter: Optional["JaegerUdpExporter"] = None,
                 sample_rate: float = 1.0):
        self.service_name = service_name
        self.enabled = enabled
        self.exporter = exporter
        self.sample_rate = float(sample_rate)
        self._spans: deque = deque(maxlen=max_spans)
        self._pending: List[Span] = []  # awaiting export
        self._lock = threading.Lock()
        self._flusher: Optional[threading.Thread] = None
        self._closed = threading.Event()
        if exporter is not None:
            self._flusher = threading.Thread(
                target=self._flush_loop, daemon=True, name="jaeger-flush"
            )
            self._flusher.start()

    # -- span lifecycle -----------------------------------------------------

    @contextlib.contextmanager
    def span(self, operation: str, tags: Optional[Dict[str, Any]] = None,
             headers: Optional[Dict[str, str]] = None):
        """Open a span as a child of (priority order) the extracted header
        context or the currently active span; activate it for the body."""
        if not self.enabled:
            yield _NOOP_SPAN
            return
        parent = self.extract(headers) if headers and TRACE_HEADER in headers else _current_span.get()
        if parent is _UNSAMPLED:
            # inside an unsampled request — locally decided OR told so by
            # the upstream hop's flags — children must not re-roll the
            # dice (they would export orphan fragments of dropped traces).
            # Pin the context so nested spans and inject() see the
            # decision even when it arrived via an extracted header.
            token = _current_span.set(_UNSAMPLED)
            try:
                yield _NOOP_SPAN
            finally:
                _current_span.reset(token)
            return
        if parent is None and self.sample_rate < 1.0:
            # per-request head sampling: the ROOT decides; the decision is
            # pinned in the context so every nested span inherits it
            if random.random() >= self.sample_rate:
                token = _current_span.set(_UNSAMPLED)
                try:
                    yield _NOOP_SPAN
                finally:
                    _current_span.reset(token)
                return
        s = Span(
            operation=operation,
            trace_id=parent.trace_id if parent else _rand_id(),
            span_id=_rand_id(),
            parent_id=parent.span_id if parent else None,
            start_us=wall_us(),
            tags=dict(tags or {}),
            # inherit the parent's flags byte so upstream bits beyond
            # SAMPLED (e.g. Jaeger's DEBUG 0x2) survive the hop instead
            # of resetting to the local default at the first child
            flags=parent.flags if parent is not None else 1,
        )
        token = _current_span.set(s)
        t0 = time.perf_counter()
        try:
            yield s
        except Exception as e:
            s.set_tag("error", True)
            s.log(event="error", message=str(e))
            raise
        finally:
            s.duration_us = int((time.perf_counter() - t0) * 1e6)
            _current_span.reset(token)
            with self._lock:
                self._spans.append(s)
                if self.exporter is not None:
                    self._pending.append(s)
                    do_flush = len(self._pending) >= 64
            if self.exporter is not None and do_flush:
                self.flush()

    def flush(self) -> int:
        """Push pending spans to the agent now; returns spans exported."""
        if self.exporter is None:
            return 0
        with self._lock:
            batch, self._pending = self._pending, []
        if batch:
            try:
                self.exporter.emit(self.service_name, batch)
            except OSError:  # agent away: tracing must never break serving
                pass
        return len(batch)

    def _flush_loop(self) -> None:
        while not self._closed.wait(0.5):
            self.flush()

    def close(self) -> None:
        """Stop the flusher thread and export what's left. init_tracer
        closes any replaced tracer, so re-init cannot leak threads."""
        self._closed.set()
        self.flush()
        if self.exporter is not None:
            try:
                self.exporter._sock.close()
            except OSError:
                pass

    def active_span(self) -> Optional[Span]:
        return _current_span.get()

    def record_span(
        self,
        operation: str,
        trace_id: str,
        parent_id: Optional[str],
        start_us: int,
        duration_us: int,
        tags: Optional[Dict[str, Any]] = None,
    ) -> Optional[Span]:
        """Append an already-finished span with explicit timing/parentage.

        The generation scheduler runs on its own thread and learns phase
        boundaries retroactively (a request's queue wait is only known at
        admit, its decode residency at completion), so it cannot use the
        context-manager span() — it records finished spans against the
        trace context captured at submit(). Sampling was already decided
        by that context's root: a request without a sampled parent never
        reaches here (the caller holds no trace ids for it)."""
        if not self.enabled:
            return None
        s = Span(
            operation=operation,
            trace_id=trace_id,
            span_id=_rand_id(),
            parent_id=parent_id,
            start_us=int(start_us),
            duration_us=max(0, int(duration_us)),
            tags=dict(tags or {}),
        )
        with self._lock:
            self._spans.append(s)
            do_flush = False
            if self.exporter is not None:
                self._pending.append(s)
                do_flush = len(self._pending) >= 64
        if do_flush:
            self.flush()
        return s

    # -- propagation --------------------------------------------------------

    def inject(self, headers: Dict[str, str]) -> Dict[str, str]:
        s = _current_span.get()
        if not self.enabled or s is None:
            return headers
        if s is _UNSAMPLED:
            # the root dropped this request: tell the next hop so IT does
            # not re-sample and export orphan fragments of a dead trace.
            # Only the flags byte carries information across the hop, but
            # the ids must still be valid non-zero values — standard
            # jaeger clients treat a zero trace id as a corrupted context
            # and would fall back to starting a fresh sampled root.
            headers[TRACE_HEADER] = f"{_rand_id()}:{_rand_id()}:0:0"
        else:
            headers[TRACE_HEADER] = s.context_header()
        return headers

    @staticmethod
    def extract(headers: Dict[str, str]) -> Optional[Span]:
        """Parse an incoming uber-trace-id into a remote parent stub.

        The flags field's sampled bit is honored: a header whose upstream
        hop decided NOT to sample yields the pinned-unsampled sentinel, so
        this hop's spans no-op instead of re-rolling the sampling dice on
        a request the root already dropped."""
        raw = headers.get(TRACE_HEADER) or headers.get(TRACE_HEADER.title())
        if not raw:
            return None
        parts = raw.split(":")
        if len(parts) != 4:
            return None
        try:
            flags = int(parts[3], 16)
        except ValueError:
            return None
        if not flags & 1:
            return _UNSAMPLED
        return Span(operation="<remote>", trace_id=parts[0], span_id=parts[1],
                    parent_id=None if parts[2] == "0" else parts[2],
                    flags=flags)

    # -- export -------------------------------------------------------------

    def finished_spans(self) -> List[Span]:
        with self._lock:
            return list(self._spans)

    def reset(self) -> None:
        with self._lock:
            self._spans.clear()

    def export_jaeger(
        self,
        operation: Optional[str] = None,
        limit: Optional[int] = None,
        since_us: Optional[int] = None,
    ) -> Dict[str, Any]:
        """Jaeger HTTP API JSON shape: {"data": [{traceID, spans, processes}]}.

        Filters (all optional, served as ``/traces`` query params so a
        4096-span buffer is inspectable without dumping it whole):
        ``operation`` keeps spans whose operation name contains the
        substring, ``since_us`` keeps spans starting at/after the epoch
        microsecond, ``limit`` keeps only the N most recent matching
        spans (finish order)."""
        spans = self.finished_spans()
        if operation:
            spans = [s for s in spans if operation in s.operation]
        if since_us is not None:
            spans = [s for s in spans if s.start_us >= since_us]
        if limit is not None and limit >= 0:
            spans = spans[-limit:] if limit else []
        by_trace: Dict[str, List[Span]] = {}
        for s in spans:
            by_trace.setdefault(s.trace_id, []).append(s)
        data = []
        for trace_id, spans in by_trace.items():
            data.append(
                {
                    "traceID": trace_id,
                    "spans": [
                        {
                            "traceID": s.trace_id,
                            "spanID": s.span_id,
                            "operationName": s.operation,
                            "references": (
                                [{"refType": "CHILD_OF", "traceID": s.trace_id,
                                  "spanID": s.parent_id}] if s.parent_id else []
                            ),
                            "startTime": s.start_us,
                            "duration": s.duration_us,
                            "tags": [
                                {"key": k, "type": "string", "value": str(v)}
                                for k, v in s.tags.items()
                            ],
                            "logs": s.logs,
                            "processID": "p1",
                        }
                        for s in spans
                    ],
                    "processes": {"p1": {"serviceName": self.service_name, "tags": []}},
                }
            )
        return {"data": data}


class JaegerUdpExporter:
    """Jaeger agent client: thrift-compact ``Agent.emitBatch`` oneway
    messages over UDP :6831 — the exact wire protocol jaeger-client's
    UDPSender speaks, implemented directly (no thrift dependency in the
    image). Batches are split to fit the agent's 65KB datagram limit."""

    # thrift compact type nibbles
    _T_BOOL_TRUE, _T_BOOL_FALSE = 1, 2
    _T_I32, _T_I64, _T_DOUBLE, _T_STR, _T_LIST, _T_STRUCT = 5, 6, 7, 8, 9, 12

    def __init__(self, host: str, port: int = 6831, max_packet: int = 65000):
        import socket

        self.addr = (host, int(port))
        self.max_packet = max_packet
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)

    # -- thrift compact primitives ------------------------------------------

    @staticmethod
    def _varint(n: int) -> bytes:
        out = bytearray()
        while True:
            if n < 0x80:
                out.append(n)
                return bytes(out)
            out.append((n & 0x7F) | 0x80)
            n >>= 7

    @classmethod
    def _zigzag(cls, n: int, bits: int = 64) -> bytes:
        return cls._varint(((n << 1) ^ (n >> (bits - 1))) & ((1 << bits) - 1))

    @classmethod
    def _field(cls, out: bytearray, last_id: int, fid: int, ftype: int) -> int:
        delta = fid - last_id
        if 0 < delta <= 15:
            out.append((delta << 4) | ftype)
        else:
            out.append(ftype)
            out += cls._zigzag(fid, 16)
        return fid

    @classmethod
    def _string(cls, s: str) -> bytes:
        b = s.encode("utf-8")
        return cls._varint(len(b)) + b

    @classmethod
    def _list_header(cls, size: int, etype: int) -> bytes:
        if size < 15:
            return bytes([(size << 4) | etype])
        return bytes([0xF0 | etype]) + cls._varint(size)

    @staticmethod
    def _i64_of_hex(h: str) -> int:
        v = int(h, 16) & 0xFFFFFFFFFFFFFFFF
        return v - (1 << 64) if v >= (1 << 63) else v

    # -- jaeger.thrift structs ----------------------------------------------

    def _tag(self, key: str, value: Any) -> bytes:
        out = bytearray()
        last = self._field(out, 0, 1, self._T_STR)          # key
        out += self._string(key)
        last = self._field(out, last, 2, self._T_I32)       # vType = STRING(0)
        out += self._zigzag(0, 32)
        last = self._field(out, last, 3, self._T_STR)       # vStr
        out += self._string(str(value))
        out.append(0)  # stop
        return bytes(out)

    def _span(self, s: Span) -> bytes:
        out = bytearray()
        last = self._field(out, 0, 1, self._T_I64)          # traceIdLow
        out += self._zigzag(self._i64_of_hex(s.trace_id))
        last = self._field(out, last, 2, self._T_I64)       # traceIdHigh
        out += self._zigzag(0)
        last = self._field(out, last, 3, self._T_I64)       # spanId
        out += self._zigzag(self._i64_of_hex(s.span_id))
        last = self._field(out, last, 4, self._T_I64)       # parentSpanId
        out += self._zigzag(self._i64_of_hex(s.parent_id) if s.parent_id else 0)
        last = self._field(out, last, 5, self._T_STR)       # operationName
        out += self._string(s.operation)
        last = self._field(out, last, 7, self._T_I32)       # flags = sampled
        out += self._zigzag(1, 32)
        last = self._field(out, last, 8, self._T_I64)       # startTime us
        out += self._zigzag(s.start_us)
        last = self._field(out, last, 9, self._T_I64)       # duration us
        out += self._zigzag(s.duration_us)
        if s.tags:
            last = self._field(out, last, 10, self._T_LIST)  # tags
            out += self._list_header(len(s.tags), self._T_STRUCT)
            for k, v in s.tags.items():
                out += self._tag(k, v)
        out.append(0)  # stop
        return bytes(out)

    def _batch(self, service_name: str, spans: List[Span]) -> bytes:
        process = bytearray()
        plast = self._field(process, 0, 1, self._T_STR)
        process += self._string(service_name)
        process.append(0)

        batch = bytearray()
        blast = self._field(batch, 0, 1, self._T_STRUCT)    # process
        batch += process
        blast = self._field(batch, blast, 2, self._T_LIST)  # spans
        batch += self._list_header(len(spans), self._T_STRUCT)
        for s in spans:
            batch += self._span(s)
        batch.append(0)

        # message: protocol 0x82, ONEWAY(4)<<5 | version 1, seqid, name,
        # then the args struct {1: Batch}
        msg = bytearray(b"\x82\x81")
        msg += self._varint(0)                               # seqid
        msg += self._string("emitBatch")
        alast = self._field(msg, 0, 1, self._T_STRUCT)
        msg += batch
        msg.append(0)
        return bytes(msg)

    def emit(self, service_name: str, spans: List[Span]) -> None:
        # split so each datagram stays under the agent's packet limit
        chunk: List[Span] = []
        size = 0
        for s in spans:
            est = 128 + len(s.operation) + sum(
                len(str(k)) + len(str(v)) + 16 for k, v in s.tags.items()
            )
            if chunk and size + est > self.max_packet:
                self._sock.sendto(self._batch(service_name, chunk), self.addr)
                chunk, size = [], 0
            chunk.append(s)
            size += est
        if chunk:
            self._sock.sendto(self._batch(service_name, chunk), self.addr)


class _NoopSpan(Span):
    def __init__(self):
        super().__init__("noop", "0", "0")

    def set_tag(self, key, value):
        return self

    def log(self, **fields):
        pass


_NOOP_SPAN = _NoopSpan()
# context marker for "this request lost the sampling coin flip": children
# and injected headers must follow the root's decision, not re-roll
_UNSAMPLED = _NoopSpan()

# -- global tracer (the reference reads JAEGER_* env in both wrapper and
# engine; TRACING=1 gates setup — microservice.py:116-151) ------------------

_GLOBAL: Optional[Tracer] = None


def init_tracer(service_name: Optional[str] = None, enabled: Optional[bool] = None) -> Tracer:
    """Env parity with the reference's jaeger setup (microservice.py:116-151):
    TRACING gates it, JAEGER_AGENT_HOST/PORT select the UDP agent,
    JAEGER_SAMPLER_TYPE const|probabilistic + JAEGER_SAMPLER_PARAM set the
    per-request head-sampling rate."""
    global _GLOBAL
    if _GLOBAL is not None:
        _GLOBAL.close()
    if enabled is None:
        enabled = os.environ.get("TRACING", "0") not in ("0", "false", "")
    exporter = None
    agent_host = os.environ.get("JAEGER_AGENT_HOST", "")
    if enabled and agent_host:
        exporter = JaegerUdpExporter(
            agent_host, int(os.environ.get("JAEGER_AGENT_PORT", "6831"))
        )
    sampler_type = os.environ.get("JAEGER_SAMPLER_TYPE", "const")
    try:
        param = float(os.environ.get("JAEGER_SAMPLER_PARAM", "1"))
    except ValueError:
        param = 1.0
    sample_rate = param if sampler_type == "probabilistic" else (
        1.0 if param else 0.0
    )
    _GLOBAL = Tracer(
        service_name or os.environ.get("JAEGER_SERVICE_NAME", "seldon-tpu"),
        enabled=enabled,
        exporter=exporter,
        sample_rate=sample_rate,
    )
    return _GLOBAL


def get_tracer() -> Tracer:
    global _GLOBAL
    if _GLOBAL is None:
        _GLOBAL = init_tracer()
    return _GLOBAL


# -- TPU device tracing -----------------------------------------------------


@functools.cache
def _annotation_type():
    """``jax.profiler.TraceAnnotation``, resolved once per process."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:  # pragma: no cover
        return contextlib.nullcontext
    return TraceAnnotation


def device_trace(name: str):
    """Annotate the enclosed device work so it shows up named inside XLA
    profiles (TPU equivalent of the reference's span around the model call)."""
    return _annotation_type()(name)


class PhaseClock:
    """One thread's wall time, partitioned into named phases.

    The owning thread calls :meth:`to` at each phase boundary. Every phase
    is (a) a ``TraceAnnotation`` named ``<prefix>.<phase>``, so it lands in
    the profiler's host plane on the clock the device ops are on, and (b)
    seconds added to ``counters["<key>_<phase>_s"]``. Time is never
    unaccounted: between :meth:`start` and :meth:`stop` the thread is in
    exactly one phase. One ``time.monotonic()`` read and one annotation
    per switch; no lock, no allocation beyond the annotation itself.
    :meth:`read` may be called from any thread; :meth:`lap` is the
    owner's."""

    def __init__(self, counters: Dict[str, Any], prefix: str, key: str,
                 phases: Sequence[str], rest: str = "other"):
        self._counters = counters
        self._rest = rest
        self._key = {p: f"{key}_{p}_s" for p in (*phases, rest)}
        self._span_name = {p: f"{prefix}.{p}" for p in self._key}
        for k in self._key.values():
            counters.setdefault(k, 0.0)
        self._at: Optional[tuple] = None    # (phase, since) while running
        self._span = None
        self._edge = None                   # see reenter()
        self._seq = 0                       # odd while a switch is half done
        self._t_start = self._t_stop = 0.0
        self._lap_t = 0.0                   # where the last lap ended
        self._lap_totals = self._totals()

    def _totals(self) -> list:
        return [self._counters[k] for k in self._key.values()]

    def start(self) -> None:
        now = time.monotonic()
        # a later start takes up where the accounted time left off
        self._t_start = now - sum(self._totals())
        self._at = (self._rest, now)
        # laps start over: what a stopped loop left unlapped is dropped
        self._lap_t = now
        self._lap_totals = self._totals()
        self._span = device_trace(self._span_name[self._rest])
        self._span.__enter__()

    def to(self, phase: str) -> float:
        """Enter ``phase``; returns the moment of the switch."""
        now = time.monotonic()
        if self._at is None:    # not started: nothing to account to
            return now
        was, since = self._at
        self._seq += 1
        self._counters[self._key[was]] += now - since
        self._at = (phase, now)
        self._seq += 1
        self._span.__exit__(None, None, None)
        self._span = device_trace(self._span_name[phase])
        self._span.__enter__()
        if self._edge is not None:
            edge, self._edge = self._edge, None
            edge.__exit__(None, None, None)
        return now

    def reenter(self) -> None:
        """Any thread, once a profiler records: the span of the phase in
        progress was entered before that and is never recorded (a
        ``read_wait`` can be most of a second), so a second one of its
        name runs from here to the owner's next switch. Should that switch
        come first, this span outlives the phase it names; the next
        phase's own span then lies inside it and is the shorter, so it
        still names what happens there."""
        at = self._at
        if at is not None:
            edge = device_trace(self._span_name[at[0]])
            edge.__enter__()
            self._edge = edge

    def stop(self) -> None:
        if self._at is None:
            return
        self._t_stop = self.to(self._rest)
        self._span.__exit__(None, None, None)
        self._span = None
        self._at = None

    def lap(self) -> tuple:
        """Owner thread only: ``(t, {phase: seconds})`` of the stretch from
        where the last lap ended (``t``, monotonic) to the last switch,
        phases with no time left out. No clock read: the phase in
        progress goes to the next lap, so laps lie end to end, ``t`` plus
        a lap's seconds is the next lap's ``t``, and the laps sum to the
        counters."""
        t, was = self._lap_t, self._lap_totals
        now = self._lap_totals = self._totals()
        if self._at is not None:
            self._lap_t = self._at[1]
        return t, {p: b - a for p, a, b in zip(self._key, was, now) if b > a}

    def read(self) -> Dict[str, float]:
        """``{<phase>_s..., wall_s}`` up to now, the phase in progress
        included, so the phases sum to ``wall_s``."""
        while True:
            seq = self._seq
            at = self._at
            now = time.monotonic()
            out = {f"{p}_s": self._counters[k] for p, k in self._key.items()}
            if seq % 2 == 0 and self._seq == seq:
                break
        if at is not None:
            out[f"{at[0]}_s"] += now - at[1]
        out["wall_s"] = (now if at is not None else self._t_stop) - self._t_start
        return out



class Heartbeat:
    """A daemon thread that sleeps to every multiple of ``period`` seconds
    and keeps the most any beat came late since it was last taken.

    A beat needs the interpreter lock to note anything, so it tells the two
    readings of a wait with no CPU and no run-queue time apart: beats on
    time, the process was alive and its owner's thread alone was held (the
    runtime, the driver, the device); beats late by the wait, the whole
    process stood (the lock held, frozen, paged out). ``on_beat``, where
    set, is called after every beat, on this thread (a ``HostClock``
    samples ``/proc`` there). ``clock`` and ``sleep`` are the tests' to
    replace; the thread's own sleep is a wait on its stop."""

    def __init__(self, period: float = 0.05, clock=time.monotonic, sleep=None):
        self.on_beat = None
        self._period = period
        self._clock = clock
        self._stopped = threading.Event()
        self._sleep = sleep if sleep is not None else self._stopped.wait
        self._lock = threading.Lock()
        self._late = 0.0
        self._due: Optional[float] = None   # the beat slept towards
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return
            self._stopped.clear()
            self._thread = threading.Thread(
                target=self._run, name="host-heartbeat", daemon=True)
            self._thread.start()

    def stop(self) -> None:
        self._stopped.set()
        with self._lock:
            thread, self._thread = self._thread, None
        if thread is not None:
            thread.join(timeout=1.0)
        self._due = None

    def _run(self) -> None:
        while not self._stopped.is_set():
            self.beat()

    def beat(self) -> None:
        """One beat: sleep to the next multiple of the period, note how
        late the wake-up came (a stop wakes it early: nothing to note)."""
        now = self._clock()
        self._due = due = (now // self._period + 1) * self._period
        self._sleep(due - now)
        late = self._clock() - due
        with self._lock:
            if late > self._late:
                self._late = late
        if self.on_beat is not None:
            self.on_beat()

    def take(self, now: float) -> Optional[float]:
        """The most a beat came late since the last take, in seconds; a
        beat overdue at ``now`` and not yet noted counts as late as it is
        by then (the taker may run before the beat it waited with). None
        before the first beat and after a stop."""
        due = self._due
        if due is None:
            return None
        with self._lock:
            late, self._late = self._late, 0.0
        return max(late, now - due, 0.0)


# collector seconds, process-wide (a collection stops every Python thread):
# [summed over finished collections, start of the one in progress]
_GC_S = [0.0, 0.0]


def _gc_note(phase: str, info: Dict[str, Any]) -> None:
    if phase == "start":
        _GC_S[1] = time.monotonic()
    else:
        _GC_S[0] += time.monotonic() - _GC_S[1]


class HostClock:
    """What the host did to one thread, stretch by stretch: beside a
    :class:`PhaseClock` (where the thread's time went by the program's own
    phases) this says whether the thread ran at all.

    The owning thread calls :meth:`start`, then :meth:`lap` where it laps
    its ``PhaseClock``; a lap is the differences since the lap before, so
    laps lie end to end:

    ``cpu_s``        the thread's seconds on a core (its CPU-time clock,
                     ``time.thread_time``)
    ``runq_s``       its seconds runnable but waiting for a core
                     (``/proc/thread-self/schedstat``, field 2)
    ``busy_share``   1 - (idle + iowait) / total of the machine's CPU time
                     (``/proc/stat``'s first line)
    ``beat_late_s``  the most a :class:`Heartbeat` of the stretch came late
    ``gc_s``         collector seconds, process-wide; left out at 0

    A lap costs its thread two clock reads and no system call that gives
    the interpreter lock away: the two files are read by the heartbeat's
    thread at every beat (:meth:`sample`: two ``pread`` on descriptors held
    open), and a lap takes the newest sample, so ``runq_s`` and
    ``busy_share`` are those of a stretch up to a beat older than the
    lap's. A file ``/proc`` does not give, gives in another form or leaves
    at zero (a sandbox kernel's ``/proc/stat`` accounts nothing) is not
    read again and its field is left out: no fallback, no guess."""

    def __init__(self, beat: Optional[Heartbeat] = None,
                 schedstat: str = "/proc/thread-self/schedstat",
                 stat: str = "/proc/stat", clock=time.monotonic,
                 cpu_clock=time.thread_time):
        self.beat = beat if beat is not None else Heartbeat()
        self._paths = (schedstat, stat)
        self._fds: List[Optional[int]] = [None, None]
        self._clock, self._cpu_clock = clock, cpu_clock
        self._sampled: tuple = (None, None)     # (runq ns, (total, idle) ticks)
        self._was: tuple = (0.0, None, None, 0.0)

    def start(self) -> None:
        """Owner thread: ``thread-self`` names the thread that opens it
        (the descriptor then reads that thread's file from any thread)."""
        self.stop()
        for i, path in enumerate(self._paths):
            try:
                self._fds[i] = os.open(path, os.O_RDONLY)
            except OSError:
                self._fds[i] = None
        if _gc_note not in gc.callbacks:
            gc.callbacks.append(_gc_note)
        self.sample()
        if self._sampled[1] is not None and self._sampled[1][0] == 0:
            self._close(1)              # a /proc/stat that counts nothing
        self.beat.on_beat = self.sample if any(
            fd is not None for fd in self._fds) else None
        self._was = (self._cpu_clock(), *self._sampled, _GC_S[0])

    def stop(self) -> None:
        self.beat.on_beat = None
        for i in range(len(self._fds)):
            self._close(i)

    def _close(self, i: int) -> None:
        fd, self._fds[i] = self._fds[i], None
        if fd is not None:
            os.close(fd)

    def sample(self) -> None:
        """Reads the two files as they stand now: the heartbeat's thread
        at every beat, and :meth:`start`."""
        runq = stat = None
        sched_fd, stat_fd = self._fds
        try:
            if sched_fd is not None:
                runq = int(os.pread(sched_fd, 128, 0).split()[1])
        except (OSError, ValueError, IndexError):
            pass
        try:
            if stat_fd is not None:
                line = os.pread(stat_fd, 512, 0).split(b"\n", 1)[0].split()
                # user nice system idle iowait irq softirq steal (guest
                # time is inside user and nice)
                ticks = [int(x) for x in line[1:9]]
                if line[0] == b"cpu" and len(ticks) >= 5:
                    stat = (sum(ticks), ticks[3] + ticks[4])
        except (OSError, ValueError, IndexError):
            pass
        self._sampled = (runq, stat)

    def lap(self) -> Dict[str, float]:
        """Owner thread only: the fields over the stretch since the last
        lap (or the start)."""
        was = self._was
        now = self._was = (self._cpu_clock(), *self._sampled, _GC_S[0])
        out: Dict[str, float] = {"cpu_s": now[0] - was[0]}
        if was[1] is not None and now[1] is not None:
            out["runq_s"] = (now[1] - was[1]) * 1e-9
        if was[2] is not None and now[2] is not None:
            total = now[2][0] - was[2][0]
            if total > 0:   # no tick of the machine's between the samples
                out["busy_share"] = 1.0 - (now[2][1] - was[2][1]) / total
        late = self.beat.take(self._clock())
        if late is not None:
            out["beat_late_s"] = late
        if now[3] > was[3]:
            out["gc_s"] = now[3] - was[3]
        return out


class CompileLog:
    """Every XLA compile of the process, by name and by stage, from
    ``jax.monitoring``'s own events: the seconds JAX spent tracing a
    function, lowering it and in the backend (the persistent cache's
    retrieval included: it is inside that event), and whether the cache
    held it. A name is XLA's for the module, ``jit_<function>``: what the
    device trace and the compile log call it.

    :meth:`install` registers the listeners once a process; :meth:`stage`
    stamps what follows (``load``, ``warm``, ``serve``). Kept: per stage
    and per ``(stage, name)`` the totals ``n`` (backend compiles),
    ``trace_s``, ``lower_s``, ``backend_s``, ``cache_hits``,
    ``cache_misses``; and a ring of the last ``ring`` events of stage
    ``serve``, ``{t, name, kind, s, cache}``, ``t`` monotonic at the
    event's end, ``serve_total`` counting them all (a reader's cursor:
    :meth:`since`). Trace events nest (the ``jnp`` functions a traced
    function calls fire their own), so a trace is counted once its name
    goes on to lower, under that name, and the rest are dropped."""

    KINDS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
        "/jax/core/compile/backend_compile_duration": "backend",
    }
    CACHE = {
        "/jax/compilation_cache/cache_hits": "hit",
        "/jax/compilation_cache/cache_misses": "miss",
    }

    def __init__(self, ring: int = 256):
        self._lock = threading.Lock()
        self._installed = False
        self._stage: Optional[str] = None
        self._stages: Dict[str, Dict[str, float]] = {}
        self._names: Dict[tuple, Dict[str, float]] = {}
        self._serve: deque = deque(maxlen=ring)
        self.serve_total = 0
        # the compiling thread's own: traces waiting for their lowering,
        # and what the cache said inside the backend event in progress
        self._local = threading.local()

    def install(self) -> None:
        """Registers the listeners (once, however often called) and opens
        stage ``load``. Before anything is jitted: an executable compiled
        earlier is in no count."""
        import jax.monitoring

        with self._lock:
            if not self._installed:
                jax.monitoring.register_event_duration_secs_listener(
                    self._on_duration)
                jax.monitoring.register_event_listener(self._on_event)
                self._installed = True
        self.stage("load")

    def stage(self, name: str) -> None:
        self._stage = name

    def _on_event(self, event: str, **kwargs) -> None:
        said = self.CACHE.get(event)
        if said is not None:
            self._local.cache = said

    def _on_duration(self, event: str, secs: float, **kwargs) -> None:
        kind = self.KINDS.get(event)
        if kind is None:
            return
        now = time.monotonic()
        name = str(kwargs.get("fun_name", "?"))
        local = self._local.__dict__
        if kind == "trace":
            local.setdefault("traced", {})[name] = secs
            return
        events = [{"t": now, "name": name.replace("(", "_").replace(")", ""),
                   "kind": kind, "s": secs,
                   "cache": local.pop("cache", None) if kind == "backend" else None}]
        if kind == "lower":
            traced = local.pop("traced", {})
            inner = name[name.find("(") + 1:-1] if name.endswith(")") else name
            if inner in traced:     # it ended where the lowering began
                events.insert(0, dict(events[0], kind="trace", s=traced[inner],
                                      t=now - secs))
        with self._lock:
            stage = self._stage
            for totals in (self._stages.setdefault(stage, self._zero()),
                           self._names.setdefault((stage, events[0]["name"]),
                                                  self._zero())):
                for e in events:
                    totals[e["kind"] + "_s"] += e["s"]
                    if e["kind"] == "backend":
                        totals["n"] += 1
                        if e["cache"] is not None:
                            totals["cache_hits" if e["cache"] == "hit"
                                   else "cache_misses"] += 1
            if stage == "serve":
                self._serve.extend(events)
                self.serve_total += len(events)

    @staticmethod
    def _zero() -> Dict[str, float]:
        return {"n": 0, "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
                "cache_hits": 0, "cache_misses": 0}

    def since(self, cursor: Optional[int] = None) -> tuple:
        """``(serve_total, the serve events past the first ``cursor``)``,
        oldest first, as far back as the ring holds (none without a
        cursor: the first call's); the events are the ring's own dicts.
        One comparison where nothing compiled."""
        if cursor is None or cursor == self.serve_total:
            return self.serve_total, ()
        with self._lock:
            new = min(self.serve_total - cursor, len(self._serve))
            return self.serve_total, list(self._serve)[len(self._serve) - new:]

    def report(self, top: int = 20) -> Dict[str, Any]:
        """Absolute, not differenced: ``stages`` (each stage's totals),
        ``executables`` (the ``top`` largest by seconds, with their stage
        and name) and ``serve_events`` (the ring)."""
        with self._lock:
            stages = {s: dict(v) for s, v in self._stages.items()}
            names = [dict(v, stage=s, name=n)
                     for (s, n), v in self._names.items()]
            events = list(self._serve)
        names.sort(key=lambda v: -(v["trace_s"] + v["lower_s"] + v["backend_s"]))
        return {"stages": stages, "executables": names[:top],
                "serve_events": events}


# a host span of this name, the reading after it, opens every profiled
# capture: ``time.monotonic()`` as the span began
CAPTURE_CLOCK_SPAN = "capture.clock monotonic_s="


class CaptureError(RuntimeError):
    """``start_capture`` while a capture runs, or ``stop_capture``
    without one."""


class CaptureControl:
    """Starts and stops a capture in the running process: the JAX
    profiler (when a ``logdir`` is given) and the program's own counters
    and request stamps. The *source* is the object with

    ``capture_counters() -> {group: {name: number}}``  running totals
    ``capture_requests() -> [dict]``                   recent request timelines
    ``capture_polls() -> [dict]``                      the flight recorder's rows
                                                       (optional: ``[]`` without)
    ``capture_compiles() -> dict``                     the compile log's report
                                                       (optional: left out without)
    ``capture_started()``                              the profiler records now

    held weakly, so a closed server drops out. A capture is a call, not a
    configuration: nothing here is read from the environment."""

    def __init__(self):
        self._source = lambda: None
        self._lock = threading.Lock()
        self._running: Optional[Dict[str, Any]] = None

    def register(self, source) -> None:
        self._source = weakref.ref(source)

    def start(self, logdir: Optional[str] = None) -> Dict[str, float]:
        with self._lock:
            if self._running is not None:
                raise CaptureError("a capture is already running")
            t0 = time.monotonic()
            # held strongly until the stop, so both readings are of the
            # same source
            source = self._source()
            before = source.capture_counters() if source is not None else {}
            if logdir is not None:
                import jax.profiler

                # device ops and the spans the program names itself; the
                # Python tracer's frames only slow the host down
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(logdir, profiler_options=options)
                # the trace counts from its session's start, which no
                # caller sees: a span named by the monotonic reading it
                # begins at places monotonic stamps on the trace itself
                with device_trace(
                        f"{CAPTURE_CLOCK_SPAN}{time.monotonic():.9f}"):
                    pass
                if source is not None:
                    source.capture_started()
            self._running = {
                "t0": t0, "source": source, "before": before,
                "profiling": logdir is not None,
            }
            return {"t": t0}

    def stop(self) -> Dict[str, Any]:
        """Ends the capture and returns its report: ``t0``/``t1``
        (monotonic; the ``CAPTURE_CLOCK_SPAN`` in a profiled capture's
        trace places them, and every other monotonic stamp, on the trace),
        each counter group of the source as differences over the capture
        (the batcher's ``loop`` and ``counters``), and ``requests`` and ``polls``
        — every timeline and every flight-recorder row the source still
        holds, stamps absolute monotonic, so a reader selects by window —
        and ``compiles``, the compile log's report (:meth:`CompileLog.report`:
        absolute too)."""
        with self._lock:
            run = self._running
            if run is None:
                raise CaptureError("no capture is running")
            self._running = None
            source = run["source"]
            t1 = time.monotonic()
            try:
                after = source.capture_counters() if source is not None else {}
                requests = source.capture_requests() if source is not None else []
                polls = getattr(source, "capture_polls", list)()
                compiles = getattr(source, "capture_compiles", lambda: None)()
            finally:
                if run["profiling"]:
                    import jax.profiler

                    jax.profiler.stop_trace()
        report: Dict[str, Any] = {"t0": run["t0"], "t1": t1}
        for group, values in after.items():
            before = run["before"].get(group, {})
            report[group] = {k: v - before.get(k, 0) for k, v in values.items()
                             if isinstance(v, (int, float))}
        report["requests"] = requests
        report["polls"] = polls
        if compiles is not None:
            report["compiles"] = compiles
        return report


_CAPTURE = CaptureControl()
register_capture_source = _CAPTURE.register
start_capture = _CAPTURE.start
stop_capture = _CAPTURE.stop

_COMPILES = CompileLog()
install_compile_log = _COMPILES.install
compile_stage = _COMPILES.stage
compiles_since = _COMPILES.since
compile_report = _COMPILES.report
