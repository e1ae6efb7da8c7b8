#!/usr/bin/env python3
"""CI smoke for the fault-tolerant disaggregated generate path.

Boots a two-listener prefill pool and a decode engine over the chunked
TCP transport with the **SELDON_FAULTS env grammar** driving the chaos:
seeded KV-transport faults on both peers (CRC corruption on one,
connect-refused on the other) plus one induced scheduler poll death on
the decode batcher. Then asserts:

* every greedy response through the chaotic decode engine is
  byte-identical to the fault-free unified server's (failover retries
  and local degradation absorb the faults; a transient 503 with
  Retry-After during the supervised restart is the only tolerated
  non-200, and the retry must succeed);
* the recovery counters are exercised — ``peer_ejections`` from the
  transport faults, ``batcher_restarts`` from the induced poll death,
  and ``degraded_local_prefill`` once both listeners are torn down;
* the ``seldon_engine_batcher_restarts`` / ``seldon_engine_peer_ejections``
  (and ``_degraded_local_prefill`` / ``_batcher_healthy``) series land
  in the Prometheus exposition.

Run directly (``JAX_PLATFORMS=cpu python tools/chaos_smoke.py``) or from
the CI chaos step. Exits non-zero on any failure.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # runtime thread-role assertions (analysis/roles.py): a scheduler
    # thread violation during chaos recovery fails the smoke loudly
    # instead of corrupting device state (must precede seldon imports)
    os.environ.setdefault("SELDON_DEBUG_THREADS", "1")
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    import http.client

    from seldon_core_tpu.graph.engine_metrics import REGISTRY
    from seldon_core_tpu.servers.generateserver import GenerateServer
    from seldon_core_tpu.serving.disagg import PrefillTransportServer
    from seldon_core_tpu.testing import EngineHarness, write_model_dir

    failures = []

    def check(name: str, ok: bool, detail: str = ""):
        print(f"{'ok  ' if ok else 'FAIL'} {name}" + (f": {detail}" if detail else ""))
        if not ok:
            failures.append(name)

    with tempfile.TemporaryDirectory(prefix="chaos-smoke-") as root:
        cfg = {"vocab_size": 256, "d_model": 32, "n_layers": 2, "n_heads": 2,
               "n_kv_heads": 2, "d_ff": 64, "max_seq": 64}
        model_dir = write_model_dir(root, "llm", cfg)
        common = dict(model_uri=model_dir, steps_per_poll=4,
                      warmup_prompt_lens=[4], warmup_max_new_tokens=6)

        # reference + prefill pool load BEFORE the fault env exists:
        # only the decode engine runs chaotic
        unified = GenerateServer(slots=2, **common)
        unified.load()
        pf1 = GenerateServer(role="prefill", **common)
        pf1.load()
        pf2 = GenerateServer(role="prefill", **common)
        pf2.load()
        l1 = PrefillTransportServer(pf1, port=0)
        l2 = PrefillTransportServer(pf2, port=0)

        # the SELDON_FAULTS grammar under test: kv targets per peer +
        # the scheduler-death section (docs/operate.md "Resilience")
        os.environ["SELDON_FAULTS"] = json.dumps({
            "seed": 7,
            "rules": [
                {"unit": f"kv:127.0.0.1:{l1.port}", "kv_corrupt_rate": 0.7},
                {"unit": f"kv:127.0.0.1:{l2.port}",
                 "kv_connect_refused_rate": 0.5},
            ],
            "scheduler": {"die_after_polls": 6, "times": 1},
        })
        try:
            dec = GenerateServer(
                slots=2, role="decode",
                peer=f"127.0.0.1:{l1.port},127.0.0.1:{l2.port}",
                peer_eject_backoff_s=0.2, restart_backoff_s=0.1,
                **common,
            )
            dec.load()
        finally:
            del os.environ["SELDON_FAULTS"]

        uni_h = EngineHarness(unified, name="chaos-unified").start()
        dec_h = EngineHarness(dec, name="chaos-decode").start()
        headers = {"Content-Type": "application/json"}

        def greedy(port: int, prompt, retries: int = 4) -> dict:
            """One greedy request; a 503 (supervised restart in flight)
            must carry Retry-After and succeed on retry."""
            last = None
            for _ in range(retries):
                conn = http.client.HTTPConnection("127.0.0.1", port)
                conn.request("POST", "/api/v0.1/predictions", json.dumps({
                    "jsonData": {"prompt_tokens": [list(prompt)],
                                 "max_new_tokens": 6, "temperature": 0.0},
                }).encode(), headers)
                resp = conn.getresponse()
                payload = resp.read()
                retry_after = resp.getheader("Retry-After")
                conn.close()
                if resp.status == 200:
                    return json.loads(payload)["jsonData"]
                last = (resp.status, retry_after, payload[:120])
                check("503 during restart carries Retry-After",
                      resp.status == 503 and retry_after is not None,
                      f"status={resp.status} retry_after={retry_after}")
                time.sleep(min(2.0, float(retry_after or 1)))
            raise RuntimeError(f"request never succeeded: {last}")

        try:
            prompts = [[5, 6, 7, 8], [9, 10, 11], [1, 2, 3, 4, 5],
                       [7, 7, 7, 7], [2, 4, 6, 8], [11, 12, 13]]
            refs = [greedy(uni_h.http_port, p)["tokens"][0] for p in prompts]

            # drive chaotic traffic until the induced scheduler death has
            # fired and restarted (plus enough transfers to eject peers)
            identical = True
            deadline = time.monotonic() + 60.0
            rounds = 0
            while time.monotonic() < deadline:
                for p, r in zip(prompts, refs):
                    got = greedy(dec_h.http_port, p)["tokens"][0]
                    if got != r:
                        identical = False
                rounds += 1
                if (dec.batcher.stats["batcher_restarts"] >= 1
                        and dec.batcher.stats["peer_ejections"] >= 1
                        and dec.batcher.health == "serving"
                        and rounds >= 2):
                    break
            st = dec.batcher.stats
            check("chaotic greedy responses byte-identical", identical)
            check("peer ejections exercised", st["peer_ejections"] >= 1,
                  f"ejections={st['peer_ejections']}")
            check("induced scheduler death recovered",
                  st["batcher_restarts"] >= 1
                  and dec.batcher.health == "serving",
                  f"restarts={st['batcher_restarts']} "
                  f"health={dec.batcher.health}")

            # full-pool outage: both listeners torn down -> local prefill
            l1.close()
            l2.close()
            time.sleep(0.3)
            for p, r in zip(prompts[:3], refs[:3]):
                got = greedy(dec_h.http_port, p)["tokens"][0]
                check("pool-down greedy identical (local prefill)",
                      got == r, "" if got == r else f"{got} != {r}")
            check("degraded_local_prefill exercised",
                  st["degraded_local_prefill"] >= 1,
                  f"degraded={st['degraded_local_prefill']}")

            # recovery series in the Prometheus exposition
            expo = REGISTRY.expose()
            for series in ("seldon_engine_batcher_restarts",
                           "seldon_engine_peer_ejections",
                           "seldon_engine_degraded_local_prefill",
                           "seldon_engine_batcher_healthy"):
                check(f"exposition has {series}", series in expo)
            check("batcher restart counter counts the death",
                  REGISTRY.counter_total(
                      "seldon_engine_batcher_restarts", {}) >= 1)
            check("peer ejection counter counts the faults",
                  REGISTRY.counter_total(
                      "seldon_engine_peer_ejections", {}) >= 1)

            # -- pressure leg: SELDON_FAULTS pressure grammar shrinks
            # the HBM ledger mid-run -> decode-lane preemption ->
            # byte-identical recompute-resume, then the exposition must
            # carry the seldon_engine_pressure_* / _preemptions series
            long_kw = {"max_new_tokens": 40, "temperature": 0.0}
            long_prompts = prompts[:3]
            long_refs = [
                unified.batcher.generate(list(p), **long_kw)
                for p in long_prompts
            ]
            # ~1.3 lanes of end-of-generation footprint: two live lanes
            # must preempt, one always fits (no livelock)
            kvb = unified.batcher._kv_key_bytes
            shrink_to = int(1.3 * 64 * kvb)
            os.environ["SELDON_FAULTS"] = json.dumps({
                "pressure": {"shrink_to_bytes": shrink_to,
                             "after_polls": 4,
                             "restore_after_polls": 24},
            })
            try:
                prs = GenerateServer(
                    slots=2, hbm_ledger_bytes=1 << 40, **common
                )
                prs.load()
            finally:
                del os.environ["SELDON_FAULTS"]
            prs_h = EngineHarness(prs, name="chaos-pressure").start()
            try:
                futs = [
                    prs.batcher.submit(list(p), **long_kw)
                    for p in long_prompts
                ]
                outs = [f.result(timeout=60) for f in futs]
                st = prs.batcher.stats
                check("pressure shrink preempted a lane",
                      st["preemptions"] >= 1,
                      f"preemptions={st['preemptions']}")
                check("preempted requests resumed byte-identical",
                      outs == long_refs and
                      st["preempt_resumes"] >= 1,
                      f"resumes={st['preempt_resumes']}")
                # one engine-served request flushes the gen_* metrics
                # into the registry so the series land in /metrics
                # (greedy() asks for 6 new tokens — compare like for like)
                short_ref = unified.batcher.generate(
                    list(long_prompts[0]), max_new_tokens=6,
                    temperature=0.0)
                got = greedy(prs_h.http_port, long_prompts[0])
                check("pressure engine path byte-identical",
                      got["tokens"][0] == short_ref)
                expo = REGISTRY.expose()
                for series in ("seldon_engine_preemptions",
                               "seldon_engine_preemption_resumes",
                               "seldon_engine_pressure_used_bytes",
                               "seldon_engine_pressure_budget_bytes",
                               "seldon_engine_pressure_active"):
                    check(f"exposition has {series}", series in expo)
                check("preemption counter counts the reclaim",
                      REGISTRY.counter_total(
                          "seldon_engine_preemptions", {}) >= 1)
            finally:
                prs_h.stop()
                prs.close()

            # -- tier leg: the SAME pressure grammar with the host KV
            # tier on — the preempted lane must resume via host-tier
            # COPY-BACK (kv_tier_hits > 0, the replay-fallback counter
            # quiet) with greedy AND seeded output byte-identical to
            # tier-off, a demoted prefix must promote back on resume of
            # traffic, and the seldon_engine_kv_tier_* series must land
            # in the exposition
            tier_kw = {"max_new_tokens": 40, "temperature": 0.0}
            seeded_kw = {"max_new_tokens": 30, "temperature": 0.8,
                         "seed": 9}
            tier_prompts = prompts[:3]
            tier_refs = [
                unified.batcher.generate(list(p), **tier_kw)
                for p in tier_prompts
            ]
            seeded_refs = [
                unified.batcher.generate(list(p), **seeded_kw)
                for p in tier_prompts
            ]
            os.environ["SELDON_FAULTS"] = json.dumps({
                "pressure": {"shrink_to_bytes": shrink_to,
                             "after_polls": 4,
                             "restore_after_polls": 24},
            })
            try:
                tsv = GenerateServer(
                    slots=2, hbm_ledger_bytes=1 << 40,
                    host_kv_tier_bytes=64 << 20, kv_tier_min_tokens=2,
                    prefix_cache_hbm_bytes=1 << 20,
                    prefix_cache_min_tokens=4, **common,
                )
                tsv.load()
            finally:
                del os.environ["SELDON_FAULTS"]
            tsv_h = EngineHarness(tsv, name="chaos-kvtier").start()
            try:
                futs = [
                    tsv.batcher.submit(list(p), **tier_kw)
                    for p in tier_prompts
                ]
                outs = [f.result(timeout=60) for f in futs]
                tb = tsv.batcher
                tb.sync_kv_tier_stats()
                st = tb.stats
                check("tier leg preempted a lane", st["preemptions"] >= 1,
                      f"preemptions={st['preemptions']}")
                check("tier copy-back resume exercised",
                      st["kv_tier_hits"] >= 1,
                      f"hits={st['kv_tier_hits']}")
                check("tier replay-fallback counter quiet",
                      st["kv_tier_replay_fallbacks"] == 0,
                      f"fallbacks={st['kv_tier_replay_fallbacks']}")
                check("tier greedy resume byte-identical",
                      outs == tier_refs)
                # seeded window: arm a second shrink through the hook
                from seldon_core_tpu.resilience.faults import FaultInjector
                inj = FaultInjector([], pressure={
                    "shrink_to_bytes": shrink_to,
                    "after_polls": tb._work_poll_count + 2,
                    "restore_after_polls": 24,
                })
                tb.pressure_hook = inj.pressure_hook()
                sfuts = [
                    tb.submit(list(p), **seeded_kw) for p in tier_prompts
                ]
                souts = [f.result(timeout=60) for f in sfuts]
                check("tier seeded resume byte-identical",
                      souts == seeded_refs)
                tb.sync_kv_tier_stats()
                check("tier demotions recorded",
                      st["kv_tier_demotions"] >= 1,
                      f"demotions={st['kv_tier_demotions']}")
                # one engine-served request flushes the gen_kv_tier_*
                # deltas into the registry
                short_ref2 = unified.batcher.generate(
                    list(tier_prompts[0]), max_new_tokens=6,
                    temperature=0.0)
                got = greedy(tsv_h.http_port, tier_prompts[0])
                check("tier engine path byte-identical",
                      got["tokens"][0] == short_ref2)
                expo = REGISTRY.expose()
                for series in ("seldon_engine_kv_tier_demotions",
                               "seldon_engine_kv_tier_promotions",
                               "seldon_engine_kv_tier_hits",
                               "seldon_engine_kv_tier_evictions",
                               "seldon_engine_kv_tier_replay_fallbacks",
                               "seldon_engine_kv_tier_bytes"):
                    check(f"exposition has {series}", series in expo)
                check("tier hit counter counts the copy-backs",
                      REGISTRY.counter_total(
                          "seldon_engine_kv_tier_hits", {}) >= 1)
                check("tier replay-fallback series quiet",
                      REGISTRY.counter_total(
                          "seldon_engine_kv_tier_replay_fallbacks",
                          {}) == 0)
            finally:
                tsv_h.stop()
                tsv.close()

            # -- migration leg: graceful drain over TCP (POST /drain),
            # then a decode member killed MID-STREAM with the client
            # resuming on the peer from the span's SGC1 resume token —
            # byte-identical total output, no span re-sent, and the
            # seldon_engine_drains/migrations series in the exposition
            pf3 = GenerateServer(role="prefill", **common)
            pf3.load()
            l3 = PrefillTransportServer(pf3, port=0)
            mig_kw = dict(common, steps_per_poll=1)
            dA = GenerateServer(  # the member that will be killed
                slots=2, role="decode", peer=f"127.0.0.1:{l3.port}",
                resume_tokens=1, restart_budget=0, **mig_kw,
            )
            dA.load()
            dB = GenerateServer(  # the kill's resume target
                slots=2, role="decode", peer=f"127.0.0.1:{l3.port}",
                resume_tokens=1, **mig_kw,
            )
            dB.load()
            dC = GenerateServer(  # the drain's handoff target
                slots=2, role="decode", peer=f"127.0.0.1:{l3.port}",
                resume_tokens=1, **mig_kw,
            )
            dC.load()
            a_h = EngineHarness(dA, name="mig-kill").start()
            b_h = EngineHarness(dB, name="mig-resume").start()
            c_h = EngineHarness(dC, name="mig-drain-dst").start()
            mig_prompt = [3, 1, 4, 1]
            mig_gen = dict(max_new_tokens=56, temperature=0.8, seed=5)
            mig_ref = unified.batcher.generate(
                list(mig_prompt), eos_id=None, **mig_gen,
            )

            def sse_events(resp, stop_after=None, on_first=None):
                """Parse `data: {...}` events off a live SSE response;
                optionally fire a callback after the first span."""
                events = []
                while True:
                    line = resp.readline()
                    if not line:
                        break
                    if not line.startswith(b"data: "):
                        continue
                    ev = json.loads(line[6:])
                    events.append(ev)
                    if on_first is not None and len(events) == 1:
                        on_first()
                        on_first = None
                    if ev.get("done") or (
                        stop_after is not None and len(events) >= stop_after
                    ):
                        break
                return events

            try:
                # (1) graceful drain over TCP: a stream in flight on dB,
                # POST /drain hands its checkpoint to dC's engine
                conn = http.client.HTTPConnection(
                    "127.0.0.1", b_h.http_port, timeout=60)
                conn.request("POST", "/api/v0.1/generate", json.dumps({
                    "jsonData": {"prompt_tokens": mig_prompt, **mig_gen},
                }).encode(), headers)
                stream_resp = conn.getresponse()
                first_ev = sse_events(stream_resp, stop_after=1)[0]
                drained_spans = list(first_ev["tokens"])
                dconn = http.client.HTTPConnection(
                    "127.0.0.1", b_h.http_port, timeout=60)
                dconn.request("POST", "/drain", json.dumps({
                    "to": f"127.0.0.1:{c_h.http_port}",
                }).encode(), headers)
                dresp = dconn.getresponse()
                dout = json.loads(dresp.read())
                dconn.close()
                check("TCP drain route answers 200", dresp.status == 200,
                      str(dout)[:120])
                # the ORIGINAL stream keeps delivering through the drain
                tail = sse_events(stream_resp)
                conn.close()
                for ev in tail:
                    if not ev.get("done"):
                        drained_spans.extend(ev["tokens"])
                final = next((e for e in tail if e.get("done")), {})
                check("drained stream completes byte-identical",
                      final.get("tokens") == mig_ref)
                check("drained stream re-sends no span",
                      drained_spans == mig_ref[len(mig_prompt):])
                check("draining member refuses new work",
                      dB.batcher.health == "draining")

                # (2) member kill mid-stream: dA dies after the first
                # span; the resume token continues on dB's peer engine
                conn = http.client.HTTPConnection(
                    "127.0.0.1", a_h.http_port, timeout=60)
                conn.request("POST", "/api/v0.1/generate", json.dumps({
                    "jsonData": {"prompt_tokens": mig_prompt, **mig_gen},
                }).encode(), headers)
                resp = conn.getresponse()

                def kill():
                    def die(_n):
                        raise RuntimeError("chaos: injected member kill")
                    dA.batcher.fault_hook = die

                events = []
                try:
                    events = sse_events(resp, on_first=kill)
                except Exception:  # noqa: BLE001 - severed mid-stream
                    pass
                conn.close()
                delivered, token = [], None
                for ev in events:
                    if ev.get("done"):
                        break
                    delivered.extend(ev["tokens"])
                    token = ev.get("resume_token", token)
                check("killed stream delivered spans with resume tokens",
                      bool(delivered) and token is not None)
                check("member latched dead after kill",
                      dA.batcher.health == "dead")
                rconn = http.client.HTTPConnection(
                    "127.0.0.1", c_h.http_port, timeout=60)
                rconn.request("POST", "/api/v0.1/generate", json.dumps({
                    "jsonData": {"resume_token": token},
                }).encode(), headers)
                r_events = sse_events(rconn.getresponse())
                rconn.close()
                resumed = []
                r_final = {}
                for ev in r_events:
                    if ev.get("done"):
                        r_final = ev
                        break
                    resumed.extend(ev["tokens"])
                check("kill resumed byte-identical on the peer",
                      r_final.get("tokens") == mig_ref)
                check("kill resume re-sends no span",
                      delivered + resumed == mig_ref[len(mig_prompt):],
                      f"{len(delivered)}+{len(resumed)} vs "
                      f"{len(mig_ref) - len(mig_prompt)}")

                expo = REGISTRY.expose()
                for series in ("seldon_engine_drains_total",
                               "seldon_engine_migrations_total",
                               "seldon_engine_migrations_resumed",
                               "seldon_engine_checkpoint_exports"):
                    check(f"exposition has {series}", series in expo)
                check("drain counter counts the drain",
                      REGISTRY.counter_total(
                          "seldon_engine_drains_total", {}) >= 1)
            finally:
                for hh in (a_h, b_h, c_h):
                    hh.stop()
                l3.close()
                for c in (pf3, dA, dB, dC):
                    c.close()
        finally:
            uni_h.stop()
            dec_h.stop()
            for listener in (l1, l2):
                listener.close()
            for c in (unified, pf1, pf2, dec):
                c.close()

    if failures:
        print(f"\nchaos smoke FAILED: {failures}", file=sys.stderr)
        return 1
    print("\nchaos smoke passed")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
