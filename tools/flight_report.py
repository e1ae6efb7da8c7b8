#!/usr/bin/env python3
"""Turn a scheduler flight-recorder dump into a human-readable diagnosis.

Input: the JSON served at the engine's ``/flightrecorder`` route (either
the full ``{"units": {name: dump}}`` payload or one unit's dump), from a
file argument or stdin (``-``). Output: a per-unit report attributing
where generation time is going — queue wait vs first-token latency vs
decode pacing — plus what the scheduler actually decided poll by poll
(the burst's mode, K and bucket, chunked-prefill interleave,
prefix-cache hits, shed events) and what each poll cost: the scheduler
thread's seconds by phase on every poll record (``phase_s``) beside the
host's account of the same stretch (``host``: the thread's CPU and
run-queue seconds, the machine's busy share, the heartbeat, the
collector; ``compiles``: what XLA compiled), from which the five slowest
polls are listed and a stalled one is diagnosed by its cause.

Usage::

    curl -s localhost:8000/flightrecorder | python tools/flight_report.py -
    python tools/flight_report.py dump.json
    python tools/flight_report.py --json dump.json   # machine-readable:
    # {unit: {"lines": [...], "diagnosis": [DIAGNOSIS subset],
    #         "slowest_polls": [poll records]}}
"""

from __future__ import annotations

import json
import sys
from typing import Any, Dict, List


def _pct(n: float, d: float) -> float:
    return 100.0 * n / d if d else 0.0


def _swap_lines(swaps: List[Dict[str, Any]]) -> List[str]:
    """Weight hot-swap records, shown inline with the scheduling story:
    each flip names the version pair and how long the drain held the
    poll loop (lanes in flight when staged, polls spent waiting)."""
    lines: List[str] = []
    for s in swaps:
        lines.append(
            f"weight swap: {s.get('old_version')!r} -> "
            f"{s.get('new_version')!r} after draining "
            f"{s.get('drained_lanes', 0)} in-flight lanes over "
            f"{s.get('waited_polls', 0)} polls (prefix cache re-keyed)"
        )
    if len(swaps) > 1:
        lines.append(
            f"DIAGNOSIS: {len(swaps)} weight swaps inside one ring window — "
            "each flip purges the prefix cache and pauses admissions for "
            "the drain; batch rollouts should space swaps out"
        )
    return lines


def _kv_lines(
    exports: List[Dict[str, Any]], inserts: List[Dict[str, Any]]
) -> List[str]:
    """Disaggregated-serving records, shown inline with the scheduling
    story: slab exports (prefill pool) and remote inserts (decode pool)
    with their transfer-dedup coverage, so a dump from either pool shows
    which half of the handoff this scheduler is and what crossed the
    wire."""
    lines: List[str] = []
    if exports:
        total = sum(e.get("bytes", 0) for e in exports)
        suffix_only = [e for e in exports if e.get("covered_len", 0) > 0]
        chunked = [e for e in exports if e.get("chunks", 0) > 1]
        lines.append(
            f"kv export (prefill pool): {len(exports)} slabs, "
            f"{total / 1e6:.2f} MB shipped; {len(suffix_only)} suffix-only "
            f"(decode-side prefix cache deduplicated the rest), "
            f"{len(chunked)} built via chunked staging"
        )
    if inserts:
        total = sum(e.get("bytes", 0) for e in inserts)
        dedup = [e for e in inserts if e.get("covered_len", 0) > 0]
        saved_toks = sum(e.get("covered_len", 0) for e in inserts)
        lines.append(
            f"remote inserts (decode pool): {len(inserts)} slabs spliced, "
            f"{total / 1e6:.2f} MB received; {len(dedup)} rode a local "
            f"prefix hit ({saved_toks} prompt tokens never crossed the "
            "wire)"
        )
        if inserts and not dedup:
            lines.append(
                "DIAGNOSIS: every remote insert shipped its full slab — "
                "no decode-side prefix hits; if traffic shares prompts, "
                "set prefix_cache_hbm_bytes on the DECODE pool (it is "
                "the transfer-dedup layer)"
            )
    return lines


def _fault_lines(
    restarts: List[Dict[str, Any]],
    ejects: List[Dict[str, Any]],
    readmits: List[Dict[str, Any]],
    degraded: List[Dict[str, Any]],
) -> List[str]:
    """Fault-tolerance records, shown inline with the scheduling story:
    supervised batcher restarts, prefill-peer ejections/readmissions and
    local-prefill degradation — the diagnosis trail of a chaotic run."""
    lines: List[str] = []
    if restarts:
        latched = [r for r in restarts if r.get("outcome") == "latched_dead"]
        lines.append(
            f"scheduler supervision: {len(restarts)} loop death(s) — "
            + ", ".join(
                f"attempt {r.get('attempt')}/{r.get('budget')} "
                f"({r.get('outcome')}, backoff {r.get('backoff_s')}s)"
                for r in restarts
            )
        )
        if latched:
            lines.append(
                "DIAGNOSIS: the crash-loop budget is EXHAUSTED — this "
                "member is latched unready and will only recover by "
                "replacement; look at the paired loop-death tracebacks "
                "in the server log"
            )
        elif len(restarts) > 1:
            lines.append(
                "DIAGNOSIS: repeated loop deaths inside one ring window "
                "— the fault is recurring, not transient; each restart "
                "pays a cache rebuild + re-warm and fails every "
                "in-flight request"
            )
    if ejects:
        peers: Dict[str, int] = {}
        for e in ejects:
            peers[e.get("peer", "?")] = peers.get(e.get("peer", "?"), 0) + 1
        lines.append(
            "prefill-peer failover: "
            + ", ".join(f"{p} ejected {n}x" for p, n in sorted(peers.items()))
            + f"; {len(readmits)} readmission(s)"
        )
    if degraded:
        lines.append(
            f"degraded local prefill: {len(degraded)} remote prefills "
            "served LOCALLY (entire prefill pool ejected) — decode kept "
            "answering, but the isolation win is suspended"
        )
        lines.append(
            "DIAGNOSIS: the decode pool is doing prefill work; check "
            "the prefill listeners (seldon_engine_peer_ejections) and "
            "expect TTFT isolation to regress until readmission"
        )
    return lines


def _pressure_lines(
    preempts: List[Dict[str, Any]],
    resumes: List[Dict[str, Any]],
    reclaims: List[Dict[str, Any]],
    budgets: List[Dict[str, Any]],
    pressure: Dict[str, Any],
) -> List[str]:
    """HBM-pressure records, shown inline with the scheduling story:
    ledger re-budgets, reclaim-ladder rungs, decode-lane preemptions and
    their recompute-resumes — the trail of an overload window."""
    lines: List[str] = []
    if budgets:
        lines.append(
            f"pressure budget: {len(budgets)} re-budget(s) — now "
            f"{budgets[-1].get('budget_bytes', 0) / 1e6:.2f} MB"
            + (" (restored)" if budgets[-1].get("restored") else "")
        )
    evicts = [r for r in reclaims if r.get("action") == "evict_prefix"]
    spec_off = [r for r in reclaims if r.get("action") == "cancel_speculation"]
    if evicts:
        lines.append(
            f"pressure reclaim: {sum(r.get('evicted', 0) for r in evicts)} "
            f"prefix slab(s) evicted across {len(evicts)} ladder pass(es)"
        )
    if spec_off:
        lines.append(
            "pressure reclaim: speculation cancelled (draft cache freed) "
            f"{len(spec_off)}x"
        )
    if preempts:
        lanes = [p for p in preempts if p.get("kind") != "chunked"]
        chunked = [p for p in preempts if p.get("kind") == "chunked"]
        recompute = sum(p.get("emitted", 0) for p in lanes)
        lines.append(
            f"decode-lane preemption: {len(lanes)} lane(s) checkpointed "
            f"to host ({recompute} generated tokens to recompute), "
            f"{len(chunked)} chunked admission(s) requeued; "
            f"{len(resumes)} recompute-resume(s) landed"
        )
        # only checkpoint-carrying preemptions produce preempt_resume
        # records (a zero-emitted or chunked victim requeues whole and
        # re-enters through the plain admit path) — comparing against
        # ALL preempts would cry wolf on a healthy run
        checkpointed = [p for p in preempts if p.get("emitted", 0) > 0]
        if len(resumes) < len(checkpointed):
            lines.append(
                "DIAGNOSIS: preempted requests are still waiting to "
                "resume — the ledger has not cleared its low watermark; "
                "if this persists, the budget is too small for even one "
                "lane of this depth (raise hbm_ledger_bytes)"
            )
        else:
            lines.append(
                "DIAGNOSIS: every preemption resumed — output stays "
                "byte-identical (recompute-resume continues the exact "
                "sampling stream); the cost was the recomputed prefill "
                "plus the wait, visible as TTFT/TPOT inflation in the "
                "SLO block above"
            )
    if pressure:
        used = pressure.get("used_bytes", 0)
        budget = pressure.get("budget_bytes", 0)
        state = "ACTIVE" if pressure.get("active") else "clear"
        comp = pressure.get("components") or {}
        comp_txt = ", ".join(
            f"{k} {v / 1e6:.2f}" for k, v in sorted(comp.items()) if v
        ) or "idle"
        lines.append(
            f"pressure ledger: {used / 1e6:.2f} of {budget / 1e6:.2f} MB "
            f"({state}; MB by component: {comp_txt})"
        )
    return lines


def _tier_lines(
    demotes: List[Dict[str, Any]],
    promotes: List[Dict[str, Any]],
    tier_hits: List[Dict[str, Any]],
    tier: Dict[str, Any],
) -> List[str]:
    """Host-KV-tier records, shown inline with the scheduling story:
    demotions (prefix slabs + lane checkpoints spilled to host RAM),
    promotions back to device, and tier hits (local match, peer lookup
    served, checkpoint copy-back) — plus a THRASH diagnosis when the
    same slab keeps bouncing between HBM and the tier."""
    lines: List[str] = []
    if demotes:
        prefixes = [d for d in demotes if d.get("kind") == "prefix"]
        ckpts = [d for d in demotes if d.get("kind") == "ckpt"]
        total = sum(d.get("bytes", 0) for d in demotes)
        lines.append(
            f"kv tier demotions: {len(prefixes)} prefix slab(s) + "
            f"{len(ckpts)} lane checkpoint(s) spilled to host RAM "
            f"({total / 1e6:.2f} MB)"
        )
    if promotes:
        copybacks = [p for p in promotes if p.get("kind") == "ckpt"]
        peer = [p for p in promotes if p.get("source") == "peer"]
        lines.append(
            f"kv tier promotions: {len(promotes)} slab(s) back to device "
            f"({len(copybacks)} copy-back resume(s), {len(peer)} pulled "
            "from a peer's tier)"
        )
    if tier_hits:
        served_peers = [h for h in tier_hits if h.get("source") == "peer"]
        if served_peers:
            lines.append(
                f"kv tier peer lookups: {len(served_peers)} prefix(es) "
                "served to peers from this member's host tier"
            )
    # thrash: the SAME slab (by prompt-hash) demoted AND promoted
    # repeatedly inside one ring window — each cycle pays a PCIe round
    # trip that a wider watermark gap would have avoided
    cycles: Dict[str, List[int]] = {}
    for d in demotes:
        if d.get("phash"):
            cycles.setdefault(d["phash"], [0, 0])[0] += 1
    for p in promotes:
        if p.get("phash"):
            cycles.setdefault(p["phash"], [0, 0])[1] += 1
    thrashing = [
        (ph, c) for ph, c in cycles.items() if c[0] >= 2 and c[1] >= 2
    ]
    if thrashing:
        worst = max(thrashing, key=lambda t: min(t[1]))
        lines.append(
            f"DIAGNOSIS: kv tier THRASH — {len(thrashing)} slab(s) "
            f"demoted→promoted repeatedly (worst {worst[0]}: "
            f"{worst[1][0]} demotions / {worst[1][1]} promotions in one "
            "ring window); the ledger is re-tripping its high watermark "
            "right after reclaim — widen the pressure_high/pressure_low "
            "gap (or raise hbm_ledger_bytes) so a promoted slab fits "
            "inside it"
        )
    if tier:
        lines.append(
            f"kv tier: {tier.get('used_bytes', 0) / 1e6:.2f} of "
            f"{tier.get('budget_bytes', 0) / 1e6:.2f} MB host RAM "
            f"({tier.get('prefix_entries', 0)} prefix entries, "
            f"{tier.get('ckpt_entries', 0)} checkpoint(s); "
            f"{tier.get('evictions', 0)} eviction(s))"
        )
    return lines


def _migration_lines(
    drains: List[Dict[str, Any]],
    exports: List[Dict[str, Any]],
    migrated: List[Dict[str, Any]],
    swap_preempts: List[Dict[str, Any]],
) -> List[str]:
    """Live-migration records, shown inline with the scheduling story:
    graceful drains (lanes checkpointed at a poll boundary), SGC1
    checkpoint exports, and migrated resumes — on the SOURCE a
    ``migrated_resume`` record carries ``handed`` (checkpoints the peer
    accepted); on the PEER each resumed checkpoint records one."""
    lines: List[str] = []
    for d in drains:
        lines.append(
            f"graceful drain: {d.get('lanes', 0)} lane(s) checkpointed "
            f"({d.get('checkpoints', 0)} with emitted tokens), "
            f"{d.get('chunked', 0)} chunked admission(s), "
            f"{d.get('handed', 0)} request(s) handed to migration"
        )
    resumed = sum(r.get("handed", 1) for r in migrated)
    if exports:
        lines.append(
            f"checkpoint export: {len(exports)} SGC1 checkpoint(s) "
            f"({sum(e.get('emitted', 0) for e in exports)} emitted "
            f"tokens carried); {resumed} resumed at/confirmed by a peer"
        )
        if len(exports) > resumed:
            lines.append(
                f"DIAGNOSIS: {len(exports) - resumed} exported "
                "checkpoint(s) have no peer resume — the drain stranded "
                "work (peer refused the weight_version, or the handoff "
                "failed); those requests failed typed instead of "
                "migrating"
            )
    elif migrated:
        lines.append(
            f"migrated resumes: {resumed} checkpoint(s) resumed here "
            "(crediting continues after each checkpoint — no span "
            "re-sent)"
        )
    for sp in swap_preempts:
        lines.append(
            f"weight-swap straggler bound: {sp.get('lanes', 0)} lane(s) "
            f"preempt-checkpointed after swap_drain_ms="
            f"{sp.get('swap_drain_ms')} (policy {sp.get('policy')!r})"
        )
    return lines


def _pager_lines(
    page_ins: List[Dict[str, Any]],
    page_outs: List[Dict[str, Any]],
    switches: List[Dict[str, Any]],
    pager: Dict[str, Any],
    sched: Dict[str, Any],
) -> List[str]:
    """Multi-tenant weight-pager records (generate.md §13): page-in /
    page-out cycles and tenant switches, plus a THRASH diagnosis when
    tenants keep displacing each other inside one ring window — every
    such cycle pays a host→HBM upload + swap drain that a longer
    residency would have amortized."""
    lines: List[str] = []
    if switches:
        forced = [s for s in switches if s.get("forced")]
        costs = [s["cost_ms"] for s in switches if "cost_ms" in s]
        avg_cost = sum(costs) / len(costs) if costs else 0.0
        lines.append(
            f"tenant switches: {len(switches)} flip(s) "
            f"({len(forced)} forced by the starvation bound), "
            f"avg page-in cost {avg_cost:.1f}ms"
        )
    if page_ins or page_outs:
        lines.append(
            f"weight pager: {len(page_ins)} page-in(s), "
            f"{len(page_outs)} page-out(s) in the recorded window"
        )
    # thrash: two or more tenants each paged IN repeatedly inside one
    # ring window — the working set is alternating faster than
    # residency amortizes, so throughput tracks page-in bandwidth
    per_tenant: Dict[str, int] = {}
    for p in page_ins:
        t = p.get("tenant")
        if t:
            per_tenant[t] = per_tenant.get(t, 0) + 1
    cyclers = {t: n for t, n in per_tenant.items() if n >= 2}
    if len(cyclers) >= 2:
        worst = max(cyclers.items(), key=lambda kv: kv[1])
        lines.append(
            f"DIAGNOSIS: weight pager THRASH — {len(cyclers)} tenant(s) "
            f"paged in repeatedly (worst {worst[0]!r}: {worst[1]} "
            "page-ins in one ring window); each cycle pays drain + "
            "host→HBM upload — raise tenant_min_resident_ms so the "
            "batch-deeper rule amortizes residency, or give hot "
            "tenants a dedicated member"
        )
    if pager:
        lines.append(
            f"weight pager staging: {pager.get('host_bytes', 0) / 1e6:.2f} "
            f"of {pager.get('budget_bytes', 0) / 1e6:.2f} MB host RAM "
            f"({len(pager.get('tenants') or [])} tenant(s), resident "
            f"{pager.get('resident')!r}; {pager.get('evictions', 0)} "
            f"eviction(s), {pager.get('refused', 0)} refusal(s), "
            f"{pager.get('corrupt_dropped', 0)} corrupt drop(s))"
        )
    if sched:
        queued = sched.get("queued") or {}
        if queued:
            lines.append(
                "tenant queues at dump time: "
                + ", ".join(f"{t}={n}" for t, n in sorted(queued.items()))
            )
    return lines


def _fusion_lines(
    dispatches: List[Dict[str, Any]],
    fallbacks: List[Dict[str, Any]],
    segments: Dict[str, Any],
) -> List[str]:
    """Graph-fusion records (the executor's ``(fusion)`` pseudo-unit):
    fused-segment dispatches vs counted fallbacks to the per-unit walk,
    with a DIAGNOSIS when the fallback rate says fusion is configured
    but barely serving."""
    lines: List[str] = []
    if not dispatches and not fallbacks and not segments:
        return lines
    for name, seg in sorted(segments.items()):
        stages = seg.get("stages") or []
        lines.append(
            f"fused segment {name}: {' -> '.join(stages)} "
            f"({seg.get('kind', '?')}, {len(stages)} stages -> 1 dispatch): "
            f"{seg.get('dispatches', 0)} dispatch(es), fallbacks "
            f"{seg.get('fallbacks') or {}}"
        )
    if dispatches:
        durs = sorted(e.get("dur_ms", 0.0) for e in dispatches)
        lines.append(
            f"fused dispatches in window: {len(dispatches)}, "
            f"p50 {durs[len(durs) // 2]:.2f} ms"
        )
    if fallbacks:
        # first-occurrence markers only (the ring is protected from
        # per-request flooding); cumulative counts live on the segments
        plan_reasons = sorted({
            f.get("reason", "?") for f in fallbacks
            if f.get("reason") in ("remote", "faults", "microbatch", "hedge")
        })
        if plan_reasons:
            lines.append(
                "fusion plan-time exclusions: "
                + ", ".join(plan_reasons)
                + " (per-unit semantics kept those units on the "
                "hop-by-hop path)"
            )
    # the fallback RATE comes from the cumulative per-segment totals:
    # every per-request fallback lands on its segment's counter, while
    # plan-time exclusions (structure, not traffic) never do — so the
    # rate cannot false-alarm a low-traffic window
    total_disp = sum(s.get("dispatches", 0) for s in segments.values())
    req_reasons: Dict[str, int] = {}
    for seg in segments.values():
        for r, n in (seg.get("fallbacks") or {}).items():
            req_reasons[r] = req_reasons.get(r, 0) + n
    total_fb = sum(req_reasons.values())
    if req_reasons:
        lines.append(
            "fusion fallbacks (cumulative): "
            + ", ".join(f"{n}x {r}" for r, n in sorted(req_reasons.items()))
        )
        rate = _pct(total_fb, total_disp + total_fb)
        if rate >= 50.0:
            dominant = max(req_reasons.items(), key=lambda kv: kv[1])[0]
            hint = {
                "deadline": "deadline-carrying traffic always takes the "
                "per-unit path — fusion buys this workload nothing",
                "shadow": "a live shadow rollout inhibits fusion; expected "
                "until the rollout goes terminal",
                "breaker_open": "an interior unit's breaker is open — fix "
                "the sick unit, fusion resumes with it",
            }.get(dominant, "look at the per-reason records above")
            lines.append(
                f"DIAGNOSIS: {rate:.0f}% of fusable requests FELL BACK to "
                f"hop-by-hop (dominant reason: {dominant}) — the compiled "
                f"segments are mostly idle; {hint}"
            )
    return lines


def _planner_lines(retunes: List[Dict[str, Any]]) -> List[str]:
    """Autonomic planner ``planner_retune`` records (operate.md
    §"Autonomic planning"): knob changes the scheduler applied at poll
    boundaries — with a DIAGNOSIS when the controller is thrashing (the
    same knob rewritten over and over, or flipped straight back) rather
    than converging."""
    if not retunes:
        return []
    lines: List[str] = []
    knob_counts: Dict[str, int] = {}
    for r in retunes:
        for knob in (r.get("changed") or {}):
            knob_counts[knob] = knob_counts.get(knob, 0) + 1
    deferred = sum(1 for r in retunes if r.get("waited_polls"))
    knob_txt = ", ".join(
        f"{k} x{n}" for k, n in sorted(knob_counts.items())
    ) or "no knobs changed"
    lines.append(
        f"planner retunes: {len(retunes)} applied at poll boundaries "
        f"({knob_txt})"
        + (
            f"; {deferred} deferred for in-flight chunked prefills"
            if deferred else ""
        )
    )
    thrash = []
    for knob, n in sorted(knob_counts.items()):
        trans = [
            tuple(r["changed"][knob]) for r in retunes
            if knob in (r.get("changed") or {})
        ]
        reverted = any(
            trans[j][1] == trans[i][0]
            for i in range(len(trans))
            for j in range(i + 1, len(trans))
        )
        if n >= 3 or (n >= 2 and reverted):
            thrash.append(knob)
    if thrash:
        lines.append(
            f"DIAGNOSIS: planner retunes are THRASHING on "
            f"{', '.join(thrash)} — the same knob keeps being rewritten "
            "inside one ring window, so the decision table is "
            "oscillating between configs instead of converging; raise "
            "the planner's retune cooldown, or re-profile (two grid "
            "points are priced closer than the live noise)"
        )
    return lines


def _working_s(poll: Dict[str, Any]) -> float:
    """A poll record's seconds outside ``idle`` (waiting for a request)."""
    return sum(v for k, v in poll.get("phase_s", {}).items() if k != "idle")


def slowest_polls(polls: List[Dict[str, Any]], n: int = 5) -> List[Dict[str, Any]]:
    """The ``n`` poll records that held the scheduler thread longest
    outside ``idle``, longest first; records without ``phase_s`` (an
    older dump's) are left out."""
    timed = [p for p in polls if "phase_s" in p]
    return sorted(timed, key=_working_s, reverse=True)[:n]


def burst_periods(polls: List[Dict[str, Any]]) -> List[float]:
    """Seconds from one burst's dispatch to the next's: each poll record
    that dispatched one against the last before it that did, but across
    an idle wait (the benchmark's ``burst_period_p99_ms`` pairs so too)."""
    periods, last, idled = [], None, False
    for row in polls:
        idled = idled or "idle" in row.get("phase_s", {})
        if "dispatched_t" not in row:
            continue
        if last is not None and not idled:
            periods.append(row["dispatched_t"] - last)
        last, idled = row["dispatched_t"], False
    return periods


HOST_FIELDS = (   # key, label, scale, unit
    ("cpu_s", "cpu", 1e3, " ms"), ("runq_s", "run-queue wait", 1e3, " ms"),
    ("busy_share", "machine busy", 1e2, "%"),
    ("beat_late_s", "heartbeat late", 1e3, " ms"),
    ("gc_s", "collector", 1e3, " ms"),
)


def _host_text(poll: Dict[str, Any]) -> str:
    """A poll record's ``host`` fields and compiles on one line, empty
    for a record without them (an older dump's)."""
    host = poll.get("host") or {}
    parts = [f"{label} {host[key] * scale:.1f}{unit}"
             for key, label, scale, unit in HOST_FIELDS if key in host]
    text = "; host: " + ", ".join(parts) if parts else ""
    if poll.get("compiles"):
        text += "; compiled: " + _compiled_text(poll["compiles"])
    return text


def _compiled_text(compiles: List[Dict[str, Any]]) -> str:
    """``name seconds (cache)`` of a record's compile events, by name."""
    by_name: Dict[str, List[Any]] = {}
    for e in compiles:
        entry = by_name.setdefault(e["name"], [0.0, None])
        entry[0] += e["s"]
        entry[1] = e.get("cache") or entry[1]
    return ", ".join(
        f"{name} {s:.3f} s" + (f" (cache {cache})" if cache else "")
        for name, (s, cache) in sorted(by_name.items(), key=lambda kv: -kv[1][0]))


def held_by(poll: Dict[str, Any], phase: str) -> str:
    """What held the scheduler thread through ``phase`` of a poll record,
    named from the record's own account: a compile (``compiles``), the
    thread runnable and not run (``host.runq_s``), the whole process late
    (``beat_late_s``, ``gc_s``), the machine over its cores
    (``busy_share``), or, none of these with the beats on time, the
    runtime or the device."""
    held = poll["phase_s"][phase]
    compiles = poll.get("compiles") or []
    if sum(e["s"] for e in compiles) > held / 2:
        return "XLA compiled meanwhile: " + _compiled_text(compiles)
    host = poll.get("host")
    if not host:
        return ("the record carries no `host` account (an older program's "
                "dump): a starved thread, a stopped process, the runtime "
                "and the device cannot be told apart")
    busy = (f"; the machine was {host['busy_share']:.0%} busy"
            if "busy_share" in host else "")
    if host.get("runq_s", 0.0) > held / 2:
        return (f"the scheduler thread stood runnable with no core for "
                f"{host['runq_s']:.3f} s of it (`runq_s`): the host starved "
                f"the thread{busy}")
    late, gc_s = host.get("beat_late_s", 0.0), host.get("gc_s", 0.0)
    if max(late, gc_s) > held / 2:
        return (f"the whole process stood: the heartbeat came {late:.3f} s "
                f"late, the collector ran {gc_s:.3f} s (the interpreter "
                f"lock held, the process stopped or paged out){busy}")
    if host.get("busy_share", 0.0) >= 0.95:
        return (f"the machine was over its cores ({host['busy_share']:.0%} "
                "busy over the record's stretch) though this thread waited "
                f"for one only {host.get('runq_s', 0.0):.3f} s")
    if "beat_late_s" not in host:
        return ("no compile and no collection, but the record has no "
                "heartbeat to say whether the process's threads ran: a "
                "starved process and the runtime cannot be told apart")
    waited = (f"run-queue wait {host['runq_s']:.3f} s" if "runq_s" in host
              else "this host gives no run-queue wait")
    return (f"the thread slept (cpu {host['cpu_s']:.3f} s, {waited}) while "
            f"the process's beats came on time (latest {late * 1e3:.1f} "
            f"ms) and nothing compiled{busy}: the runtime or the device "
            "held the burst")


def _clock_lines(polls: List[Dict[str, Any]]) -> List[str]:
    """What the polls cost: the slowest five by phase with the host's
    account of each (``host``, ``compiles``), and a DIAGNOSIS when one
    poll's ``admit`` (a dispatch of an admission blocked) or ``read_wait``
    (a burst's tokens did not come back) took over ten times the dump's
    median burst period — every lane stood still for it. The cause is
    named from the record (:func:`held_by`), not guessed."""
    slow = slowest_polls(polls)
    if not slow:
        return []
    lines = ["slowest polls (scheduler seconds outside idle):"]
    for p in slow:
        phases = sorted(
            ((k, v) for k, v in p["phase_s"].items() if k != "idle"),
            key=lambda kv: -kv[1],
        )
        lines.append(
            f"  poll {p.get('poll', '?')} at t={p['t']:.3f}: "
            f"{_working_s(p) * 1e3:.1f} ms ("
            + ", ".join(f"{k} {v * 1e3:.1f}" for k, v in phases)
            + f"), {p.get('admitted', 0)} admitted, "
            f"{p.get('pending_bursts', 0)} bursts in flight, "
            f"device {'drained' if p.get('drained') else 'busy'} at its "
            "first dispatch" + _host_text(p)
        )
    periods = sorted(burst_periods(polls))
    if not periods:
        return lines
    median = periods[len(periods) // 2]
    for phase in ("admit", "read_wait"):
        over = [p for p in polls
                if p.get("phase_s", {}).get(phase, 0.0) > 10 * median]
        if not over:
            continue
        worst = max(over, key=lambda p: p["phase_s"][phase])
        ids = worst.get("admitted_ids")
        lines.append(
            f"DIAGNOSIS: poll {worst.get('poll', '?')} at "
            f"t={worst['t']:.3f} spent {worst['phase_s'][phase]:.3f} s in "
            f"`{phase}`, {worst['phase_s'][phase] / median:.0f}x the median "
            f"burst period ({median * 1e3:.1f} ms over {len(periods)}); "
            f"{len(over)} poll(s) over ten periods — "
            f"{held_by(worst, phase)}; no lane got a token meanwhile"
            + (f"; requests admitted in that poll: {ids}" if ids else "")
        )
    return lines


def _device_time_lines(
    polls: List[Dict[str, Any]],
    profiler: Dict[str, Any],
    slo_burn: Dict[str, Any],
) -> List[str]:
    """Device-time ledger + SLO burn records (operate.md §4): per-poll
    ``device_time`` rows aggregated by executable kind over the recorded
    window, the cumulative ledger summary with its live gauges, and the
    burn-rate verdicts — with a DIAGNOSIS when one executable kind
    dominates >80% of the window's attributed device time."""
    lines: List[str] = []
    # window view: the per-poll deltas that rode the ring
    by_kind: Dict[str, List[float]] = {}
    for p in polls:
        for row in p.get("device_time") or []:
            agg = by_kind.setdefault(row.get("kind", "?"), [0.0, 0.0, 0.0])
            agg[0] += row.get("s", 0.0)
            agg[1] += row.get("n", 0)
            agg[2] += row.get("bytes", 0)
    total_s = sum(v[0] for v in by_kind.values())
    if by_kind:
        parts = ", ".join(
            f"{k} {_pct(v[0], total_s):.0f}% ({int(v[1])} disp)"
            for k, v in sorted(
                by_kind.items(), key=lambda kv: -kv[1][0]
            )
        )
        lines.append(
            f"device-time window: {total_s * 1e3:.1f} ms attributed "
            f"across {len(by_kind)} kind(s) — {parts}"
        )
        dominant, agg = max(by_kind.items(), key=lambda kv: kv[1][0])
        share = _pct(agg[0], total_s)
        if share > 80.0 and len(by_kind) > 1:
            hint = {
                "prefill": "admissions dominate — look at chunked "
                "prefill / prefix caching to take prompt work off the "
                "serving path",
                "decode_burst": "plain decode bursts dominate — fused "
                "decode (decode_fuse_steps) cuts their dispatch floor",
                "fused_burst": "expected shape for a healthy fused "
                "decode workload",
                "swap_cast": "weight swaps dominate — space rollouts "
                "out; each cast walks every parameter",
                "splice": "KV splices dominate — prefix-cache hit "
                "tokens are being re-spliced every admit; check hit "
                "lengths vs prompt lengths",
            }.get(dominant, "see the kind's dispatch sites in "
                  "serving/continuous.py")
        elif share > 80.0:
            hint = "single-kind window (one-shape workload)"
        if share > 80.0:
            lines.append(
                f"DIAGNOSIS: executable kind '{dominant}' consumed "
                f"{share:.0f}% of attributed device time this window — "
                f"{hint}"
            )
    if profiler:
        gauges = []
        if "device_busy_frac" in profiler:
            gauges.append(f"busy {profiler['device_busy_frac'] * 100:.1f}%")
        if "mbu_pct" in profiler:
            gauges.append(f"MBU {profiler['mbu_pct']:.1f}%")
        if "dispatch_floor_pct" in profiler:
            gauges.append(
                f"dispatch floor {profiler['dispatch_floor_pct']:.1f}%"
            )
        lines.append(
            f"device-time ledger (cumulative): "
            f"{profiler.get('device_time_s', 0.0) * 1e3:.1f} ms over "
            f"{len(profiler.get('buckets') or {})} (kind,variant,tenant) "
            f"bucket(s), {profiler.get('deep_samples', 0)} deep sample(s)"
            + ("; " + ", ".join(gauges) if gauges else "")
        )
    if slo_burn:
        for v in slo_burn.get("verdicts") or []:
            if v.get("severity") in ("warn", "page"):
                who = f" tenant {v['tenant']!r}" if v.get("tenant") else ""
                lines.append(
                    f"SLO burn {v['severity'].upper()}:{who} "
                    f"{v.get('slo')} burning "
                    f"{v.get('fast_burn', 0):.1f}x budget (fast) / "
                    f"{v.get('slow_burn', 0):.1f}x (slow), "
                    f"{v.get('budget_remaining', 0) * 100:.0f}% of the "
                    "error budget left"
                )
                if v["severity"] == "page":
                    lines.append(
                        "DIAGNOSIS: both burn windows exceed the page "
                        "rate — the error budget will exhaust within "
                        "hours at this rate; the deployment controller "
                        "is already vetoing scale-down and applying "
                        "scale-up pressure"
                    )
    return lines


def diagnose(dump: Dict[str, Any]) -> List[str]:
    """Report lines for one unit's flight-recorder dump."""
    lines: List[str] = []
    entries = dump.get("entries") or []
    polls = [e for e in entries if e.get("type") == "poll"]
    sheds = [e for e in entries if e.get("type") == "shed"]
    preempts = [e for e in entries if e.get("type") == "preempt"]
    resumes = [e for e in entries if e.get("type") == "preempt_resume"]
    reclaims = [e for e in entries if e.get("type") == "pressure_reclaim"]
    budgets = [e for e in entries if e.get("type") == "pressure_budget"]
    swaps = [e for e in entries if e.get("type") == "weight_swap"]
    drains = [e for e in entries if e.get("type") == "drain"]
    ck_exports = [
        e for e in entries if e.get("type") == "checkpoint_export"
    ]
    migrated = [e for e in entries if e.get("type") == "migrated_resume"]
    swap_preempts = [
        e for e in entries if e.get("type") == "swap_straggler_preempt"
    ]
    kv_exports = [e for e in entries if e.get("type") == "kv_export"]
    kv_inserts = [e for e in entries if e.get("type") == "remote_insert"]
    kv_demotes = [e for e in entries if e.get("type") == "kv_demote"]
    kv_promotes = [e for e in entries if e.get("type") == "kv_promote"]
    tier_hits = [e for e in entries if e.get("type") == "tier_hit"]
    restarts = [e for e in entries if e.get("type") == "batcher_restart"]
    ejects = [e for e in entries if e.get("type") == "peer_ejected"]
    readmits = [e for e in entries if e.get("type") == "peer_readmitted"]
    degraded = [
        e for e in entries if e.get("type") == "degraded_local_prefill"
    ]
    fused_disp = [e for e in entries if e.get("type") == "fused_dispatch"]
    fused_fb = [e for e in entries if e.get("type") == "fusion_fallback"]
    page_ins = [e for e in entries if e.get("type") == "weight_page_in"]
    page_outs = [e for e in entries if e.get("type") == "weight_page_out"]
    tenant_switches = [
        e for e in entries if e.get("type") == "tenant_switch"
    ]
    planner_retunes = [
        e for e in entries if e.get("type") == "planner_retune"
    ]
    lines.append(
        f"recorded {dump.get('recorded_total', len(entries))} records "
        f"(ring holds {len(entries)}, dropped "
        f"{dump.get('dropped', 0)} oldest)"
    )
    if "segments" in dump:
        # the executor's (fusion) pseudo-unit: no scheduler, no SLO
        # reservoir — its whole story is the dispatch/fallback stream
        lines.extend(_fusion_lines(
            fused_disp, fused_fb, dump.get("segments") or {}
        ))
        return lines

    # -- SLO attribution ----------------------------------------------------
    slo = dump.get("slo")
    if slo:
        qw, ttft, tpot = slo["queue_wait_ms"], slo["ttft_ms"], slo["tpot_ms"]
        # tpot is None when every completion was single-token (no
        # inter-token interval exists)
        tpot_txt = (
            f"TPOT p50 {tpot['p50_ms']}ms / p99 {tpot['p99_ms']}ms"
            if tpot else "TPOT n/a (single-token completions)"
        )
        lines.append(
            f"SLO over {slo['samples']} completed requests: "
            f"queue wait p50 {qw['p50_ms']}ms / p99 {qw['p99_ms']}ms, "
            f"TTFT p50 {ttft['p50_ms']}ms / p99 {ttft['p99_ms']}ms, "
            f"{tpot_txt}"
        )
        # what dominates the tail: the wait before a lane, or the work on it
        prefill_p99 = max(0.0, ttft["p99_ms"] - qw["p99_ms"])
        if ttft["p99_ms"] > 0:
            if qw["p99_ms"] >= 0.5 * ttft["p99_ms"]:
                lines.append(
                    f"DIAGNOSIS: p99 TTFT dominated by QUEUE WAIT "
                    f"({_pct(qw['p99_ms'], ttft['p99_ms']):.0f}% of it) — "
                    "add lanes/chips or shed earlier; the scheduler is not "
                    "the bottleneck"
                )
            else:
                lines.append(
                    f"DIAGNOSIS: p99 TTFT dominated by ADMIT+PREFILL "
                    f"(~{prefill_p99:.1f}ms after the queue) — look at "
                    "prefill bucketing / chunked-prefill interleave"
                )
    else:
        lines.append("SLO: no completed requests in the reservoir yet")

    if not polls:
        lines.append("no poll records (no traffic since the ring opened)")
        if sheds:
            lines.append(f"{len(sheds)} shed events recorded")
        lines.extend(_swap_lines(swaps))
        lines.extend(_migration_lines(
            drains, ck_exports, migrated, swap_preempts
        ))
        # a prefill-role pool member never polls: its whole story is the
        # export stream
        lines.extend(_kv_lines(kv_exports, kv_inserts))
        lines.extend(_tier_lines(
            kv_demotes, kv_promotes, tier_hits, dump.get("kv_tier") or {}
        ))
        lines.extend(_pager_lines(
            page_ins, page_outs, tenant_switches,
            dump.get("weight_pager") or {},
            dump.get("tenant_scheduler") or {},
        ))
        lines.extend(_fault_lines(restarts, ejects, readmits, degraded))
        lines.extend(_pressure_lines(
            preempts, resumes, reclaims, budgets, dump.get("pressure") or {}
        ))
        lines.extend(_device_time_lines(
            polls, dump.get("profiler") or {}, dump.get("slo_burn") or {}
        ))
        lines.extend(_planner_lines(planner_retunes))
        return lines

    # -- batch composition --------------------------------------------------
    avg_active = sum(p.get("active", 0) for p in polls) / len(polls)
    avg_queue = sum(p.get("queue", 0) for p in polls) / len(polls)
    admits = sum(p.get("admitted", 0) for p in polls)
    lines.append(
        f"{len(polls)} working polls: avg {avg_active:.1f} active lanes, "
        f"avg admit-queue depth {avg_queue:.1f}, {admits} admissions"
    )

    # -- the bursts' plans ---------------------------------------------------
    planned = [p for p in polls if "plan" in p]
    decode = [p for p in planned if p["plan"].get("mode") in ("decode", "fused")]
    if decode:
        mixed = [p for p in decode if p["plan"].get("distinct_buckets", 1) > 1]
        lines.append(
            f"attention depths: {len(mixed)}/{len(decode)} decode polls had "
            f"lanes in more than one attention bucket (deepest "
            f"{max(p['plan'].get('bucket', 0) for p in decode)})"
        )
    spec = [p for p in planned if p["plan"].get("mode") == "spec"]
    if spec:
        lines.append(f"speculative decode: {len(spec)} spec-burst polls")

    # -- fused multi-step decode ---------------------------------------------
    fused = [p for p in planned if p["plan"].get("mode") == "fused"]
    if fused:
        ks = [p["plan"].get("k", 1) for p in fused]
        k_max = max(p["plan"].get("k_max", 1) for p in fused)
        reasons: Dict[str, int] = {}
        for p in fused:
            r = p["plan"].get("shrunk_by")
            if r:
                reasons[r] = reasons.get(r, 0) + 1
        reason_txt = (
            "; shrunk by " + ", ".join(
                f"{n}x {r}" for r, n in sorted(reasons.items())
            )
            if reasons else ""
        )
        lines.append(
            f"fused decode: {len(fused)} fused polls, realized K avg "
            f"{sum(ks) / len(ks):.1f} / min {min(ks)} "
            f"(configured {k_max}){reason_txt}"
        )
        # collapse = realized K pinned at its observed floor, well below
        # the configured max. _fused_plan never shrinks below
        # min(steps_per_poll, k_max), so "k <= 1" would be dead code for
        # any steps_per_poll > 1 — compare against the floor the run
        # actually hit instead.
        floor = min(ks)
        collapsed = [k for k in ks if k <= floor]
        if floor < k_max and len(collapsed) >= max(4, len(ks) // 2):
            lines.append(
                f"DIAGNOSIS: K collapsed to {floor} (configured {k_max}) "
                f"on {_pct(len(collapsed), len(ks)):.0f}% of fused polls "
                f"— each dispatch carries only {floor} step(s), giving "
                "back most of the fused dispatch-floor win; look at the "
                "shrink reasons above (persistent `pressure` means the "
                "HBM ledger is latched — see "
                "seldon_engine_pressure_active; persistent `stop_budget` "
                "means short budgets dominate traffic)"
            )

    # -- chunked prefill interleave ------------------------------------------
    chunk_polls = [p for p in polls if p.get("prefill_chunks")]
    if chunk_polls:
        n_chunks = sum(p["prefill_chunks"] for p in chunk_polls)
        lines.append(
            f"chunked prefill: {n_chunks} chunks interleaved across "
            f"{len(chunk_polls)} polls "
            f"({_pct(len(chunk_polls), len(polls)):.0f}% of polls carried a "
            "chunk between decode bursts)"
        )

    # -- what the polls cost (the loop's clock on each record) ----------------
    lines.extend(_clock_lines(polls))

    # -- live weight swaps ----------------------------------------------------
    lines.extend(_swap_lines(swaps))

    # -- live migration (graceful drain, checkpoint handoff, resumes) --------
    lines.extend(_migration_lines(
        drains, ck_exports, migrated, swap_preempts
    ))

    # -- disaggregated serving (KV-slab handoff) ------------------------------
    lines.extend(_kv_lines(kv_exports, kv_inserts))

    # -- tiered KV memory (host-RAM spill tier) -------------------------------
    lines.extend(_tier_lines(
        kv_demotes, kv_promotes, tier_hits, dump.get("kv_tier") or {}
    ))

    # -- multi-tenant weight paging -------------------------------------------
    lines.extend(_pager_lines(
        page_ins, page_outs, tenant_switches,
        dump.get("weight_pager") or {},
        dump.get("tenant_scheduler") or {},
    ))

    # -- fault tolerance (supervision, peer failover, degradation) -----------
    lines.extend(_fault_lines(restarts, ejects, readmits, degraded))

    # -- HBM pressure (ledger, reclaim ladder, preemption) -------------------
    lines.extend(_pressure_lines(
        preempts, resumes, reclaims, budgets, dump.get("pressure") or {}
    ))

    # -- device-time ledger + SLO burn ----------------------------------------
    lines.extend(_device_time_lines(
        polls, dump.get("profiler") or {}, dump.get("slo_burn") or {}
    ))

    # -- autonomic planner retunes --------------------------------------------
    lines.extend(_planner_lines(planner_retunes))

    # -- prefix cache ---------------------------------------------------------
    hits = sum(p.get("prefix_hits", 0) for p in polls)
    evicted = sum(p.get("prefix_evicted", 0) for p in polls)
    if hits or evicted:
        lines.append(
            f"prefix cache: {hits} admit hits, {evicted} radix evictions "
            "inside the recorded window"
        )

    # -- shed -----------------------------------------------------------------
    if sheds:
        reasons: Dict[str, int] = {}
        for s in sheds:
            reasons[s.get("reason", "?")] = reasons.get(s.get("reason", "?"), 0) + 1
        lines.append(
            "load shedding: "
            + ", ".join(f"{n}x {r}" for r, n in sorted(reasons.items()))
        )
        lines.append(
            "DIAGNOSIS: requests were shed before work — clients saw 429s; "
            "queue depth above exceeds what the observed completion rate "
            "can drain"
        )
    return lines


def report(payload: Dict[str, Any]) -> Dict[str, Dict[str, List[Any]]]:
    """Per-unit structured report: every narrative line, the DIAGNOSIS
    subset broken out (dashboards key alerts off it) and the slowest
    polls' records as they are."""
    units = payload.get("units")
    if units is None:
        units = {"(batcher)": payload}
    out: Dict[str, Dict[str, List[Any]]] = {}
    for name, dump in units.items():
        lines = diagnose(dump)
        out[name] = {
            "lines": lines,
            "diagnosis": [l for l in lines if l.startswith("DIAGNOSIS")],
            "slowest_polls": slowest_polls([
                e for e in dump.get("entries") or []
                if e.get("type") == "poll"
            ]),
        }
    return out


def render(payload: Dict[str, Any]) -> str:
    out: List[str] = []
    for name, unit in report(payload).items():
        out.append(f"=== flight report: {name} ===")
        out.extend("  " + line for line in unit["lines"])
    return "\n".join(out)


def main(argv: List[str]) -> int:
    args = [a for a in argv[1:] if a != "--json"]
    as_json = "--json" in argv[1:]
    if len(args) != 1 or args[0] in ("-h", "--help"):
        print(__doc__, file=sys.stderr)
        return 2
    raw = sys.stdin.read() if args[0] == "-" else open(args[0]).read()
    payload = json.loads(raw)
    if as_json:
        print(json.dumps(report(payload), indent=2, sort_keys=True))
    else:
        print(render(payload))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
