#!/usr/bin/env python3
"""CI smoke for the progressive-delivery subsystem.

Boots TWO predictor versions of the same tiny checkpoint behind real
engines on sockets (a "baseline" and a "canary"), then drives the whole
rollout surface end to end:

* a canary rollout plan applied to a real ``ResourceStore`` — the
  ``RolloutController`` starts the ramp, one analysis window of live
  greedy traffic earns a **promote** (the store's traffic weights
  actually move, byte-identical responses at both steps);
* a second rollout is breached on purpose (error traffic at the canary)
  — the controller **auto-rolls-back**, restoring baseline weights
  within one analysis interval;
* the shadow mirror duplicates live requests to a diverging target and
  the token-level differ counts the drift;
* the ``seldon_rollout_{step,verdicts,mirrors,divergence}`` series are
  asserted in the Prometheus exposition.

Run directly (``JAX_PLATFORMS=cpu python tools/rollout_smoke.py``) or
from the CI progressive-delivery step. Exits non-zero on any failure.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    import http.client

    from seldon_core_tpu.controlplane import ResourceStore, SeldonDeployment
    from seldon_core_tpu.graph.engine_metrics import REGISTRY
    from seldon_core_tpu.rollout import RolloutController, ShadowMirror
    from seldon_core_tpu.servers.generateserver import GenerateServer
    from seldon_core_tpu.testing import EngineHarness, write_model_dir

    failures = []

    def check(name: str, ok: bool, detail: str = ""):
        print(f"{'ok  ' if ok else 'FAIL'} {name}" + (f": {detail}" if detail else ""))
        if not ok:
            failures.append(name)

    def rollout_dep(name: str, steps: str) -> SeldonDeployment:
        return SeldonDeployment.from_dict({
            "name": name,
            "predictors": [
                {"name": "baseline", "traffic": 100,
                 "graph": {"name": "model", "implementation": "SIMPLE_MODEL"}},
                {"name": "canary", "traffic": 0,
                 "annotations": {
                     "seldon.io/rollout": "canary",
                     "seldon.io/rollout-steps": steps,
                     "seldon.io/rollout-interval-s": "1",
                     "seldon.io/rollout-min-samples": "2",
                     # twin engines share one CI host: TTFT/TPOT ratios
                     # are load noise there; the smoke's gate proof is
                     # the error-rate breach below
                     "seldon.io/rollout-max-ttft-ratio": "1000",
                     "seldon.io/rollout-max-tpot-ratio": "1000",
                 },
                 "graph": {"name": "model", "implementation": "SIMPLE_MODEL"}},
            ],
        })

    with tempfile.TemporaryDirectory(prefix="rollout-smoke-") as root:
        cfg = {"vocab_size": 256, "d_model": 32, "n_layers": 2, "n_heads": 2,
               "n_kv_heads": 2, "d_ff": 64, "max_seq": 64}
        model_dir = write_model_dir(root, "llm", cfg)

        def boot(name: str):
            c = GenerateServer(model_uri=model_dir, slots=2, steps_per_poll=4,
                               warmup_prompt_lens=[4], warmup_max_new_tokens=6)
            c.load()
            return c, EngineHarness(c, name=name).start()

        old, baseline_h = boot("baseline")  # the two predictor versions
        new, canary_h = boot("canary")
        headers = {"Content-Type": "application/json"}

        def greedy(port: int, prompt) -> list:
            conn = http.client.HTTPConnection("127.0.0.1", port)
            conn.request("POST", "/api/v0.1/predictions", json.dumps({
                "jsonData": {"prompt_tokens": [prompt], "max_new_tokens": 6,
                             "temperature": 0.0},
            }).encode(), headers)
            resp = conn.getresponse()
            payload = resp.read()
            conn.close()
            if resp.status != 200:
                raise RuntimeError(f"HTTP {resp.status}: {payload[:120]!r}")
            return json.loads(payload)["jsonData"]["tokens"][0]

        clock = [1000.0]
        store = ResourceStore()
        ctl = RolloutController(store, metrics=REGISTRY, now=lambda: clock[0])
        prompt = [5, 6, 7, 8]
        try:
            # -- one ramp step, gated on live traffic ---------------------
            reference = greedy(baseline_h.http_port, prompt)
            store.apply(rollout_dep("smoke-ramp", "25,100"))
            v = ctl.tick_all().get("default/smoke-ramp")
            check("rollout starts at first step", v == "start", repr(v))
            w = {p.name: p.traffic
                 for p in store.get("smoke-ramp").predictors}
            check("store weights moved to 25/75", w == {"baseline": 75, "canary": 25}, repr(w))
            for _ in range(3):  # one analysis window of canary+baseline traffic
                out_c = greedy(canary_h.http_port, prompt)
                out_b = greedy(baseline_h.http_port, prompt)
                check("canary greedy bytes identical", out_c == reference)
                check("baseline greedy bytes identical", out_b == reference)
            clock[0] += 1.0
            v = ctl.tick_all().get("default/smoke-ramp")
            check("healthy window promotes", v == "promote", repr(v))
            w = {p.name: p.traffic
                 for p in store.get("smoke-ramp").predictors}
            check("ramp advanced to 100/0", w == {"baseline": 0, "canary": 100}, repr(w))

            # -- forced gate breach -> auto-rollback ----------------------
            store.apply(rollout_dep("smoke-breach", "50,100"))
            ctl.tick_all()
            bad = list(range(1, cfg["max_seq"] + 32))  # over every bucket
            for _ in range(3):
                try:
                    greedy(canary_h.http_port, bad)
                except RuntimeError:
                    pass  # counted as a canary error at the engine
                greedy(baseline_h.http_port, prompt)
            clock[0] += 1.0
            v = ctl.tick_all().get("default/smoke-breach")
            check("gate breach rolls back", v == "rollback", repr(v))
            w = {p.name: p.traffic
                 for p in store.get("smoke-breach").predictors}
            check("rollback restored baseline weights within one interval",
                  w == {"baseline": 100, "canary": 0}, repr(w))
            trail = [e["event"] for e in ctl.events("default/smoke-breach")]
            check("event trail records start->step->rollback",
                  trail[0] == "start" and trail[-1] == "rollback", repr(trail))

            # -- shadow mirror + divergence diffing -----------------------
            mirror = ShadowMirror(
                [("canary", canary_h.app)], deployment="default/smoke-ramp",
                metrics=REGISTRY,
            )
            baseline_h.app.shadow_mirror = mirror
            greedy(baseline_h.http_port, prompt)  # identical twin: no drift

            def diverging(message):  # a canary that drifts one token
                toks = list(reference)
                toks[-1] = (toks[-1] + 1) % cfg["vocab_size"]
                return {"jsonData": {"tokens": [toks]}}

            mirror.targets = [("canary", diverging)]
            greedy(baseline_h.http_port, prompt)
            deadline = time.monotonic() + 5.0
            while mirror.counts["mirrored"] < 2 and time.monotonic() < deadline:
                time.sleep(0.05)
            check("mirror dispatched fire-and-forget",
                  mirror.counts["mirrored"] >= 2, repr(mirror.counts))
            check("differ counted exactly the drifting mirror",
                  mirror.counts["diverged"] == 1, repr(mirror.counts))
            recent = list(mirror.recent)
            check("divergence sample carries token-level detail",
                  bool(recent) and recent[0].get("kind") == "generate"
                  and recent[0].get("mismatch_tokens", 0) >= 1, repr(recent))

            # -- the seldon_rollout_* exposition --------------------------
            expo = REGISTRY.expose()
            for series in ("seldon_rollout_step", "seldon_rollout_verdicts",
                           "seldon_rollout_mirrors", "seldon_rollout_divergence"):
                check(f"exposition has {series}", series in expo)
            check("divergence counter incremented",
                  REGISTRY.counter_total("seldon_rollout_divergence",
                                         {"predictor": "canary"}) >= 1.0)
        finally:
            baseline_h.app.shadow_mirror = None
            baseline_h.stop()
            canary_h.stop()
            for c in (old, new):
                if c.batcher is not None:
                    c.batcher.close()

    if failures:
        print(f"\nrollout smoke FAILED: {failures}", file=sys.stderr)
        return 1
    print("\nrollout smoke passed")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
