"""Device-time ledger: per-executable attribution for the serving loop.

Every warmed-executable dispatch the continuous batcher makes — prefill,
chunked prefill slice, decode burst, fused burst, spec burst, prefix
splice/insert/extract, swap cast — is timed and attributed per
``(kind, variant, tenant)``. The ledger turns that into live gauges:
bytes-read per variant are known statically (the family's cost model —
see ``DecoderLM.dispatch_read_bytes``), so live MBU is a divide over a
sliding window, not a profile run, and the dispatch-floor percentage is
the observed dispatch rate priced at the measured per-dispatch floor.

What a "measurement" means under JAX async dispatch, honestly:

* **shallow (default)** times the host-side dispatch call with
  ``time.perf_counter``. A dispatch returns as soon as XLA enqueues the
  work, so an unloaded pipeline under-reports device time — but the
  batcher bounds in-flight bursts at ``pipeline_depth``, and once the
  pipeline is full every dispatch blocks until a device slot frees, so
  under load (the regime the numbers matter in) the per-kind shares
  converge to device-time shares. Zero extra synchronization, which is
  what keeps the on-vs-off overhead probe inside its 2% gate.
* **deep (sampled, every ``deep_every``-th measured dispatch)** blocks
  until the dispatched arrays are ready inside a
  ``jax.profiler.TraceAnnotation`` stamped with the attribution tags
  (``ledger.<kind>[<variant>]``), so an XLA device profile taken during
  a deep window carries the same vocabulary as the ledger. Deep samples
  drain the dispatch pipeline — a deliberate, bounded perturbation.

The ledger NEVER touches the dispatched computation: hooks wrap the
call, never its arguments or results, so profiler on vs off is
byte-identical (greedy and seeded) and compiles nothing new — the gate
``tests/test_profiler.py`` pins with jit-cache sizes.

Thread model: ``record`` runs on the scheduler thread (and, for
``export_prefill``, transport handler threads); ``poll_flush`` on the
scheduler thread; ``summary``/``gauges`` on serving/metrics threads.
One lock covers the accumulation maps — held for dict arithmetic only,
never across a dispatch.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Tuple

__all__ = ["DeviceTimeLedger", "KINDS"]

# the executable-kind vocabulary (flight_report renders these; keep in
# sync with docs/operate.md "Observability")
KINDS = (
    "prefill",        # prefill_one/prefill_many + lane insert
    "chunk_prefill",  # one chunked-prefill slice
    "decode_burst",   # step-at-a-time whole-batch burst
    "fused_burst",    # stop-aware fused multi-step burst (per K)
    "spec_burst",     # speculative draft+verify round burst
    "splice",         # prefix/checkpoint donor slab splice into a slab
    "insert",         # prefilled slab insert into a lane of the cache
    "extract",        # prefix/checkpoint slab extract from the cache
    "replay",         # teacher-forced replay (preempt recompute-resume)
    "swap_cast",      # hot-swap weight cast/device_put
)
_KINDS_SET = frozenset(KINDS)


class _Measurement:
    """One in-flight measured dispatch; ``sync(arrays)`` is the deep-mode
    hook call sites feed the dispatched outputs to (no-op unless this
    dispatch was deep-sampled)."""

    __slots__ = ("_ledger", "kind", "variant", "tenant", "bytes_read",
                 "tokens", "_t0", "_deep", "_annot")

    def __init__(self, ledger, kind, variant, tenant, bytes_read, tokens,
                 deep):
        self._ledger = ledger
        self.kind = kind
        self.variant = variant
        self.tenant = tenant
        self.bytes_read = bytes_read
        self.tokens = tokens
        self._deep = deep
        self._annot = None
        self._t0 = 0.0

    def __enter__(self):
        if self._deep:
            try:
                import jax.profiler

                self._annot = jax.profiler.TraceAnnotation(
                    f"ledger.{self.kind}[{self.variant}]"
                )
                self._annot.__enter__()
            except ImportError:  # pragma: no cover - jax is baked in
                self._annot = None
        self._t0 = time.perf_counter()
        return self

    def sync(self, arrays: Any) -> None:
        """Deep mode only: block until the dispatched arrays are ready so
        the recorded duration covers the device work, not just the
        enqueue. Values are untouched — identity is preserved."""
        if self._deep:
            try:
                import jax

                jax.block_until_ready(arrays)
            except (ImportError, TypeError):  # non-jax test doubles
                pass

    def __exit__(self, exc_type, exc, tb):
        dt = time.perf_counter() - self._t0
        if self._annot is not None:
            self._annot.__exit__(exc_type, exc, tb)
        if exc_type is None:
            self._ledger._record(
                self.kind, self.variant, self.tenant, dt,
                self.bytes_read, self.tokens, self._deep,
            )
        return False


class _NoopMeasurement:
    __slots__ = ()

    def __enter__(self):
        return self

    def sync(self, arrays: Any) -> None:
        pass

    def __exit__(self, exc_type, exc, tb):
        return False


_NOOP = _NoopMeasurement()


class DeviceTimeLedger:
    """Accumulates measured dispatch time per (kind, variant, tenant).

    Two accumulation levels: a cumulative map (``summary``/metrics
    deltas read it) and a since-last-flush map the scheduler attaches to
    each flight-recorder poll record (``poll_flush``). A bounded window
    of recent records backs the live MBU / dispatch-floor gauges.
    """

    WINDOW_S = 10.0

    def __init__(
        self,
        enabled: bool = False,
        deep_every: int = 0,
        hbm_gb_s: float = 0.0,
        dispatch_floor_us: float = 0.0,
    ):
        self.enabled = bool(enabled)
        self.deep_every = max(0, int(deep_every))
        # MBU / dispatch-floor denominators (0 = unknown: the gauges are
        # omitted rather than published as lies). Benches pass measured
        # values; servers take them as knobs.
        self.hbm_gb_s = float(hbm_gb_s)
        self.dispatch_floor_us = float(dispatch_floor_us)
        self._lock = threading.Lock()
        # (kind, variant, tenant) -> [seconds, dispatches, bytes, tokens]
        self._cum: Dict[Tuple[str, str, str], List[float]] = {}
        self._poll: Dict[Tuple[str, str, str], List[float]] = {}
        self._seq = 0          # measured dispatches (deep-mode sampler)
        self._deep_count = 0
        import collections

        # (mono_t, seconds, bytes, dispatches, tokens) per record
        self._window = collections.deque(maxlen=8192)

    # -- hot path -----------------------------------------------------------

    def measure(
        self,
        kind: str,
        variant: str = "",
        tenant: str = "",
        bytes_read: int = 0,
        tokens: int = 0,
    ):
        """Context manager timing one dispatch. Disabled ledgers return a
        shared no-op — one attribute check and one call, nothing else on
        the hot path."""
        if not self.enabled:
            return _NOOP
        if kind not in _KINDS_SET:
            # the kind vocabulary is a rendering contract (flight_report,
            # docs); a typo'd hook must fail loudly, not mint a series
            raise ValueError(f"unknown ledger kind {kind!r}")
        deep = False
        if self.deep_every > 0:
            self._seq += 1
            deep = (self._seq % self.deep_every) == 0
        return _Measurement(self, kind, variant, tenant, bytes_read,
                            tokens, deep)

    def _record(self, kind, variant, tenant, seconds, bytes_read, tokens,
                deep) -> None:
        key = (kind, variant, tenant)
        with self._lock:
            for m in (self._cum, self._poll):
                row = m.get(key)
                if row is None:
                    row = [0.0, 0.0, 0.0, 0.0]
                    m[key] = row
                row[0] += seconds
                row[1] += 1.0
                row[2] += bytes_read
                row[3] += tokens
            if deep:
                self._deep_count += 1
            self._window.append(
                (time.monotonic(), seconds, bytes_read, 1.0, tokens)
            )

    # -- flush / export -----------------------------------------------------

    @staticmethod
    def _rows(m: Dict[Tuple[str, str, str], List[float]]) -> List[Dict[str, Any]]:
        out = []
        for (kind, variant, tenant), (s, n, b, t) in sorted(m.items()):
            row = {
                "kind": kind, "variant": variant,
                "s": round(s, 6), "n": int(n),
                "bytes": int(b), "tokens": int(t),
            }
            if tenant:
                row["tenant"] = tenant
            out.append(row)
        return out

    def poll_flush(self) -> Optional[List[Dict[str, Any]]]:
        """Per-(kind,variant,tenant) deltas since the last flush, cleared
        on read — the scheduler attaches the result to its per-poll
        flight-recorder record. None when nothing was measured."""
        if not self.enabled:
            return None
        with self._lock:
            if not self._poll:
                return None
            rows = self._rows(self._poll)
            self._poll.clear()
        return rows

    def buckets(self) -> Dict[Tuple[str, str, str], Tuple[float, float, float, float]]:
        """Cumulative (seconds, dispatches, bytes, tokens) per
        (kind, variant, tenant) — the metrics() exporter window-diffs
        these through CounterDeltas."""
        with self._lock:
            return {k: tuple(v) for k, v in self._cum.items()}

    def _window_rates(self) -> Tuple[float, float, float, float]:
        """(span_s, bytes/s, dispatches/s, device_s/s) over the sliding
        window; zeros when the window is empty or degenerate."""
        now = time.monotonic()
        horizon = now - self.WINDOW_S
        with self._lock:
            live = [r for r in self._window if r[0] >= horizon]
        if len(live) < 2:
            return 0.0, 0.0, 0.0, 0.0
        span = max(1e-6, now - live[0][0])
        b = sum(r[2] for r in live)
        n = sum(r[3] for r in live)
        s = sum(r[1] for r in live)
        return span, b / span, n / span, s / span

    def gauges(self) -> Dict[str, float]:
        """Live derived gauges over the sliding window. ``mbu_pct`` needs
        ``hbm_gb_s``; ``dispatch_floor_pct`` needs ``dispatch_floor_us``
        — each is omitted when its denominator is unknown."""
        span, bytes_s, disp_s, busy = self._window_rates()
        out: Dict[str, float] = {}
        if span <= 0.0:
            return out
        out["device_busy_frac"] = round(min(1.0, busy), 4)
        if self.hbm_gb_s > 0:
            out["mbu_pct"] = round(
                100.0 * bytes_s / (self.hbm_gb_s * 1e9), 2
            )
        if self.dispatch_floor_us > 0:
            # fraction of wall time the measured per-dispatch floor alone
            # would consume at the observed dispatch rate: near 100 means
            # the workload is dispatch-bound
            out["dispatch_floor_pct"] = round(
                min(100.0, 100.0 * disp_s * self.dispatch_floor_us * 1e-6),
                2,
            )
        return out

    def summary(self) -> Dict[str, Any]:
        """Cumulative rollup for /fleet, flight_dump and bench entries."""
        with self._lock:
            rows = self._rows(self._cum)
            deep = self._deep_count
        total_s = sum(r["s"] for r in rows)
        by_kind: Dict[str, float] = {}
        for r in rows:
            by_kind[r["kind"]] = round(
                by_kind.get(r["kind"], 0.0) + r["s"], 6
            )
        out: Dict[str, Any] = {
            "enabled": self.enabled,
            "device_time_s": round(total_s, 6),
            "by_kind": by_kind,
            "buckets": rows,
            "deep_samples": deep,
        }
        out.update(self.gauges())
        return out
