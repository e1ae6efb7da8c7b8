#!/usr/bin/env python3
"""CI smoke for disaggregated prefill/decode serving.

Boots a tiny checkpoint three ways behind real engines on sockets — a
unified server, a prefill-pool server exporting KV over BOTH the
loopback and the chunked TCP transport, and a decode-pool server per
transport — then asserts:

* greedy responses through the disaggregated engines (loopback AND TCP)
  are byte-identical to the unified server's;
* a shared-prefix repeat through the prefix-cache-enabled decode pool
  reports ``cache_hit_tokens`` (the transfer-dedup accounting) and
  bumps ``kv_transfer_bytes_saved``;
* the ``seldon_engine_kv_transfer_*`` series are present in the
  Prometheus exposition with export/import directions;
* peer death mid-run: with the prefill listener killed, the decode
  pool ejects the peer (``peer_ejected`` flight record) and keeps
  serving byte-identical greedy output via failover/local degradation.

Run directly (``JAX_PLATFORMS=cpu python tools/disagg_smoke.py``) or
from the CI disaggregation step. Exits non-zero on any failure.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # runtime thread-role assertions (analysis/roles.py): remote-admit /
    # failover paths run on worker threads — a scheduler-thread violation
    # fails the smoke loudly (must precede seldon imports)
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

    with tempfile.TemporaryDirectory(prefix="disagg-smoke-") as root:
        cfg = {"vocab_size": 256, "d_model": 32, "n_layers": 2, "n_heads": 2,
               "n_kv_heads": 2, "d_ff": 64, "max_seq": 64}
        model_dir = write_model_dir(root, "llm", cfg)
        common = dict(model_uri=model_dir, steps_per_poll=4,
                      warmup_prompt_lens=[4], warmup_max_new_tokens=6,
                      prefix_cache_hbm_bytes=1 << 20,
                      prefix_cache_min_tokens=8)

        unified = GenerateServer(slots=2, **common)
        unified.load()
        prefill = GenerateServer(role="prefill", **{
            **common, "prefix_cache_hbm_bytes": 0,
        })
        prefill.load()
        kv_listener = PrefillTransportServer(prefill, port=0)
        dec_lo = GenerateServer(slots=2, role="decode", **common)
        dec_lo.load()
        dec_lo.set_peer(prefill)
        dec_tcp = GenerateServer(
            slots=2, role="decode", peer=f"127.0.0.1:{kv_listener.port}",
            **common,
        )
        dec_tcp.load()

        uni_h = EngineHarness(unified, name="unified").start()
        lo_h = EngineHarness(dec_lo, name="disagg-loopback").start()
        tcp_h = EngineHarness(dec_tcp, name="disagg-tcp").start()
        headers = {"Content-Type": "application/json"}

        def greedy(port: int, prompt) -> dict:
            conn = http.client.HTTPConnection("127.0.0.1", port)
            conn.request("POST", "/api/v0.1/predictions", json.dumps({
                "jsonData": {"prompt_tokens": [prompt], "max_new_tokens": 6,
                             "temperature": 0.0},
            }).encode(), headers)
            resp = conn.getresponse()
            payload = resp.read()
            conn.close()
            if resp.status != 200:
                raise RuntimeError(f"HTTP {resp.status}: {payload[:160]!r}")
            return json.loads(payload)["jsonData"]

        try:
            # -- byte identity: unified vs loopback vs TCP ----------------
            prompts = [[5, 6, 7, 8], [9, 10, 11], [1, 2, 3, 4, 5, 6]]
            for p in prompts:
                ref = greedy(uni_h.http_port, p)["tokens"][0]
                lo = greedy(lo_h.http_port, p)["tokens"][0]
                tcp = greedy(tcp_h.http_port, p)["tokens"][0]
                check(f"loopback greedy identical (len {len(p)})", lo == ref,
                      "" if lo == ref else f"{lo} != {ref}")
                check(f"tcp greedy identical (len {len(p)})", tcp == ref,
                      "" if tcp == ref else f"{tcp} != {ref}")

            # -- shared-prefix transfer dedup -----------------------------
            system = list(range(20, 32))  # 12-token shared system prompt
            first = greedy(lo_h.http_port, system + [40, 41])
            ref2 = greedy(uni_h.http_port, system + [50, 51])["tokens"][0]
            second = greedy(lo_h.http_port, system + [50, 51])
            check("shared-prefix greedy identical",
                  second["tokens"][0] == ref2)
            hits = (second.get("cache_hit_tokens") or [0])[0]
            check("decode side reports cache_hit_tokens on remote admit",
                  hits >= 8, f"hits={hits}")
            saved = dec_lo.batcher.stats["kv_transfer_bytes_saved"]
            check("kv_transfer_bytes_saved > 0", saved > 0, f"saved={saved}")

            # -- the seldon_engine_kv_transfer_* exposition ---------------
            expo = REGISTRY.expose()
            for series in ("seldon_engine_kv_transfer_slabs",
                           "seldon_engine_kv_transfer_bytes",
                           "seldon_engine_kv_transfer_bytes_saved"):
                check(f"exposition has {series}", series in expo)
            check("import direction labeled",
                  'direction="import"' in expo)
            check("import slab counter counts the transfers",
                  REGISTRY.counter_total(
                      "seldon_engine_kv_transfer_slabs",
                      {"direction": "import"},
                  ) >= len(prompts) * 2 + 2)
            check("bytes_saved series counts the dedup",
                  REGISTRY.counter_total(
                      "seldon_engine_kv_transfer_bytes_saved", {},
                  ) > 0)
            _ = first  # first shared request seeds the radix cache

            # -- tiered KV memory: peer prefix pull over TCP --------------
            # a prefill member with the host tier on publishes every
            # exported slab; a FRESH decode member (empty local radix)
            # pulls the shared prefix from the PEER'S tier over TCP,
            # promotes it locally, and ships the suffix only — the
            # kv_transfer_bytes_saved accounting must stay correct
            # (decode-side count, covered tokens priced per slab token)
            tier_common = dict(common, host_kv_tier_bytes=64 << 20,
                               kv_tier_min_tokens=8)
            pf_tier = GenerateServer(role="prefill", **tier_common)
            pf_tier.load()
            tier_listener = PrefillTransportServer(pf_tier, port=0)
            dec_tier = GenerateServer(
                slots=2, role="decode",
                peer=f"127.0.0.1:{tier_listener.port}", **tier_common,
            )
            dec_tier.load()
            tier_h = EngineHarness(dec_tier, name="disagg-kvtier").start()
            try:
                shared = list(range(40, 52))  # 12-token shared prefix
                ref_t = greedy(uni_h.http_port, shared + [60, 61])["tokens"][0]
                # seed the PREFILL tier: one export publishes the slab
                pf_tier.batcher.export_prefill(shared + [55, 56],
                                               max_new_tokens=6)
                check("prefill tier holds the exported prefix",
                      pf_tier.batcher.kv_tier_summary()["prefix_entries"]
                      >= 1)
                saved0 = dec_tier.batcher.stats["kv_transfer_bytes_saved"]
                out_t = greedy(tier_h.http_port, shared + [60, 61])
                check("peer tier pull greedy identical",
                      out_t["tokens"][0] == ref_t)
                pulled = (out_t.get("cache_hit_tokens") or [0])[0]
                check("peer tier pull covered the shared prefix",
                      pulled >= 8, f"covered={pulled}")
                check("decode member promoted the peer slab",
                      dec_tier.batcher.stats["kv_tier_promotions"] >= 1)
                saved = (dec_tier.batcher.stats["kv_transfer_bytes_saved"]
                         - saved0)
                want_saved = pulled * dec_tier.batcher._slab_token_bytes
                check("bytes_saved accounting matches covered tokens",
                      saved == want_saved,
                      f"saved={saved} want={want_saved}")
                pf_tier.batcher.sync_kv_tier_stats()
                check("prefill tier counted the peer hit",
                      pf_tier.batcher.stats["kv_tier_hits"] >= 1)
                expo = REGISTRY.expose()
                check("exposition has seldon_engine_kv_tier_promotions",
                      "seldon_engine_kv_tier_promotions" in expo)
            finally:
                tier_h.stop()
                tier_listener.close()
                pf_tier.close()
                dec_tier.close()

            # -- peer death mid-run: failover / local degradation ---------
            # kill the TCP listener, then keep issuing requests through
            # the decode engine: the dead peer is ejected (peer_ejected
            # flight record + counter) and every request still answers
            # byte-identical to unified — this pool's only peer is gone,
            # so service degrades to LOCAL unified prefill
            probe = [12, 13, 14, 15]
            ref3 = greedy(uni_h.http_port, probe)["tokens"][0]
            kv_listener.close()
            import time as _time

            _time.sleep(0.2)  # let the OS actually drop the listen port
            for i in range(3):
                got = greedy(tcp_h.http_port, probe)["tokens"][0]
                check(f"peer-death request {i} byte-identical", got == ref3,
                      "" if got == ref3 else f"{got} != {ref3}")
            st = dec_tcp.batcher.stats
            check("peer ejected after listener death",
                  st["peer_ejections"] >= 1,
                  f"ejections={st['peer_ejections']}")
            check("decode degraded to local prefill",
                  st["degraded_local_prefill"] >= 1,
                  f"degraded={st['degraded_local_prefill']}")
            eject_recs = [
                e for e in dec_tcp.batcher.flight.dump()["entries"]
                if e["type"] == "peer_ejected"
            ]
            check("peer_ejected flight record present", bool(eject_recs))
            check("peer-ejection series in exposition",
                  "seldon_engine_peer_ejections" in REGISTRY.expose())
        finally:
            uni_h.stop()
            lo_h.stop()
            tcp_h.stop()
            kv_listener.close()
            for c in (unified, prefill, dec_lo, dec_tcp):
                c.close()

    if failures:
        print(f"\ndisagg smoke FAILED: {failures}", file=sys.stderr)
        return 1
    print("\ndisagg smoke passed")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
