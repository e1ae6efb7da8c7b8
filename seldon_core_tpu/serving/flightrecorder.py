"""Scheduler flight recorder: a bounded ring of per-poll decision records.

The continuous batcher (serving/continuous.py) makes a scheduling
decision every poll — which requests admit, the burst it dispatched
(mode, K, lanes, bucket), which long prompts advanced a prefill chunk,
what got shed — and none of it used to survive the poll. This recorder
keeps the last ``capacity`` decisions as plain dicts in a
``collections.deque`` ring so a tail-latency regression can be
attributed after the fact (queue wait vs prefill interleave vs
eviction) without re-running traffic under a profiler.

Cost model: recording must be cheap enough to leave ON in production.
One small dict is built per *poll* (device-burst cadence, milliseconds),
never per token; ``deque.append`` with ``maxlen`` drops the oldest entry
under pressure without locking (the scheduler thread writes poll records;
shed records arrive concurrently from submitting threads, and both
``deque.append`` and the ``itertools.count`` sequence stamp are atomic
under the GIL); readers snapshot with ``list(...)`` and never block the
scheduler. ``enabled = False`` short-circuits to a single attribute
check on the hot path.

Every record carries ``type``, ``seq``, ``t`` (``time.monotonic()``: the
clock of the request stamps and of a capture's ``t0``/``t1``) and ``t_us``
(the same moment as wall-clock microseconds, for the route's readers).

A ``poll`` record (one per scheduler poll that admitted, advanced a chunk
or dispatched a burst; ``ContinuousBatcher._loop``) is a span, not a
point. ``t`` is where the stretch it accounts for began; over that
stretch, end to end with the record before it:

``phase_s``       the scheduler thread's seconds by phase
                  (``tracing.PhaseClock.lap``)
``host``          the host's account of that thread
                  (``tracing.HostClock.lap``): ``cpu_s`` on a core and
                  ``runq_s`` runnable with none, the machine's
                  ``busy_share``, the latest heartbeat ``beat_late_s``,
                  collector seconds ``gc_s`` (left out at 0; any field the
                  host cannot give is left out)
``compiles``      what XLA compiled since the unit said ready
                  (``tracing.CompileLog``: ``{t, name, kind, s, cache}``
                  each; left out when nothing did)
``bursts``        each burst read back (``dispatch_t``, ``read_t``, ``k``,
                  ``lanes``, ``late``)

and as points: ``poll`` (the loop's poll number), ``queue``, ``active``,
``chunked``, ``pending_bursts``, ``drained`` (the device had finished all
it was given at the poll's first dispatch), ``plan`` and ``dispatched_t``
(the burst it sent), ``admitted`` / ``admitted_ids``, ``prefill_chunks``,
``prefix_hits``, ``prefix_evicted``, and ``device_time`` with the
device-time ledger on.

The other types are points, written where the event happens: ``shed``;
``preempt``, ``preempt_resume``, ``pressure_reclaim``, ``pressure_budget``
(HBM pressure); ``kv_demote``, ``kv_promote``, ``tier_hit`` (the host KV
tier); ``kv_export``, ``remote_insert``, ``degraded_local_prefill``,
``peer_ejected``, ``peer_readmitted`` (disaggregated prefill);
``weight_swap``, ``swap_straggler_preempt``, ``drain``,
``checkpoint_export``, ``migrated_resume`` (rollout and migration);
``weight_page_in``, ``weight_page_out``, ``tenant_switch`` (the weight
pager); ``planner_retune``; ``batcher_restart``; and the fusion
pseudo-unit's ``fused_dispatch``, ``fusion_fallback``, ``fusion_skipped``.

Consumed by the engine's ``/flightrecorder`` route (graph/service.py),
``tools/flight_report.py``, which turns a dump into a human-readable
diagnosis, and ``tracing.stop_capture()``, whose report carries the ring
as ``polls``.
"""

from __future__ import annotations

import itertools
import time
from collections import deque
from typing import Any, Dict, List, Optional

from ..tracing import wall_us


class FlightRecorder:
    """Bounded, drop-oldest ring buffer of scheduler decision records."""

    def __init__(self, capacity: int = 4096, enabled: bool = True):
        self.capacity = int(capacity)
        self.enabled = bool(enabled) and self.capacity > 0
        self._ring: deque = deque(maxlen=max(1, self.capacity))
        # monotonically growing record count: next(self._seq) is atomic
        # under the GIL, so concurrent writers (scheduler polls + shed
        # events off submitting threads) never duplicate a seq
        self._seq = itertools.count()

    def record(self, entry: Dict[str, Any]) -> None:
        """Append one record. The caller owns ``entry`` (it is stored, not
        copied); ``seq``, ``t`` and ``t_us`` are stamped here, from one
        clock read, so every record is orderable and attributable on the
        monotonic and the wall clock. A ``t`` the caller set stays (a
        poll record's is where its stretch began)."""
        if not self.enabled:
            return
        entry["seq"] = next(self._seq)
        now = time.monotonic()
        entry.setdefault("t", now)
        entry.setdefault("t_us", wall_us(now))
        self._ring.append(entry)

    def snapshot(self, limit: Optional[int] = None) -> List[Dict[str, Any]]:
        """Most-recent-last copy of the ring (the scheduler keeps writing
        while we read; list() of a deque is safe under the GIL)."""
        entries = list(self._ring)
        if limit is not None and limit >= 0:
            entries = entries[-limit:] if limit else []
        return entries

    def dump(self, limit: Optional[int] = None) -> Dict[str, Any]:
        """JSON-shaped export for the ``/flightrecorder`` route."""
        entries = self.snapshot(limit)
        # total ever recorded = the newest entry's seq + 1 (the counter
        # itself is not readable without consuming it)
        try:
            recorded = self._ring[-1]["seq"] + 1
        except (IndexError, KeyError):
            recorded = 0
        return {
            "capacity": self.capacity,
            "enabled": self.enabled,
            "recorded_total": recorded,
            "dropped": max(0, recorded - len(self._ring)),
            "entries": entries,
        }

    def clear(self) -> None:
        self._ring.clear()
