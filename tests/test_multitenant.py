"""Multi-tenant multi-model serving (serving/weightpager.py).

The load-bearing contracts: (1) byte-identity — every tenant's greedy
AND seeded outputs on the multi-tenant paged server equal a dedicated
single-tenant server's, including across a mid-stream demote→promote
cycle of another tenant; (2) scale-to-zero — a demoted tenant's next
request pages back in from host RAM without recompiling anything (the
warmed executables are shape-keyed, not weight-keyed); (3) the
starvation bound — every tenant's queued work advances within
``tenant_max_wait_polls`` batcher polls; (4) weight-version
namespacing — a page-in of tenant B never purges tenant A's prefix
slabs or tier checkpoints; (5) the pager's host tier keeps the
HostKVTier discipline (LRU, half-budget refusal, CRC-drop typed).
"""

import json
import threading
import time
import types

import numpy as np
import pytest

from seldon_core_tpu.models.llm import DecoderLM
from seldon_core_tpu.serving.continuous import ContinuousBatcher, GenRequest
from seldon_core_tpu.serving.kvtier import HostKVTier
from seldon_core_tpu.serving.prefix_cache import (
    RadixPrefixIndex,
    version_namespace,
    version_retains,
)
from seldon_core_tpu.serving.weightpager import (
    PagerEntryCorrupt,
    PagerRefused,
    TenantUnknown,
    WeightPager,
    _decode_ckpt,
    _encode_ckpt,
    parse_tenant_spec,
    stamp_tenant_meta,
    tenant_from_meta,
)

CFG = dict(
    vocab_size=256,
    d_model=32,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,
    d_ff=64,
    max_seq=64,
    dtype="float32",
)

PROMPTS = [[3, 17, 42, 99, 7], [1, 2, 3], [9, 8, 7, 6]]


def _tree(seed: int, kb: int = 4):
    rng = np.random.RandomState(seed)
    return {
        "w": rng.randn(kb * 1024 // 8 // 2, 2).astype(np.float32),
        "b": rng.randn(8).astype(np.float32),
    }


# -- version namespacing (the PR 17 fix) -------------------------------------


def test_version_namespace_and_retains_truth_table():
    assert version_namespace("acme@3") == "acme"
    assert version_namespace("a@b@7") == "a@b"  # rsplit: seq is last
    assert version_namespace("v1") is None
    assert version_namespace(0) is None
    # same version: the tenant paged back, weights unchanged — retained
    assert version_retains("acme@1", "acme@1")
    # different namespaces: the other tenant's cache survives a page-in
    assert version_retains("acme@1", "globex@1")
    # same tenant, new weights: purge
    assert not version_retains("acme@1", "acme@2")
    # legacy un-namespaced lineage on either side: full-purge back-compat
    assert not version_retains("v1", "acme@1")
    assert not version_retains("acme@1", "v2")
    assert not version_retains(0, 1)


def test_prefix_index_namespaced_purge_and_page_back():
    idx = RadixPrefixIndex(1 << 20)
    idx.set_version("acme@1")
    slab_a = {"k": np.ones((2, 1, 2, 8, 4), np.float32)}
    idx.insert(list(range(8)), slab_a, 4096)
    assert idx.match(list(range(8)))[0] == 8
    # tenant B pages in: A's slab is retained but INVISIBLE
    assert idx.set_version("globex@1") == 0  # nothing purged
    assert idx.match(list(range(8)))[0] == 0
    # B publishes its own slab (disjoint prompt space)
    slab_b = {"k": np.zeros((2, 1, 2, 8, 4), np.float32)}
    idx.insert(list(range(100, 108)), slab_b, 4096)
    assert idx.match(list(range(100, 108)))[0] == 8
    # A pages back: its slab is warm again, untouched
    idx.set_version("acme@1")
    depth, slab = idx.match(list(range(8)))
    assert depth == 8 and (slab["k"] == 1).all()
    # A re-puts (new weights): ONLY acme@1 entries purge
    assert idx.set_version("acme@2") >= 1
    assert idx.match(list(range(8)))[0] == 0
    idx.set_version("globex@1")
    depth, slab = idx.match(list(range(100, 108)))
    assert depth == 8 and (slab["k"] == 0).all()
    # legacy un-namespaced switch purges everything (back-compat)
    assert idx.set_version(7) >= 1
    idx.set_version("globex@1")
    assert idx.match(list(range(100, 108)))[0] == 0


def test_host_tier_namespaced_ckpt_and_prefix_purges():
    tier = HostKVTier(1 << 20, min_tokens=4)
    tier.set_version("acme@1")
    slab = {
        "k": np.arange(2 * 2 * 8 * 4, dtype=np.float32).reshape(2, 1, 2, 8, 4),
        "v": np.zeros((2, 1, 2, 8, 4), np.float32),
    }
    toks = list(range(8))
    assert tier.put_prefix(toks, slab, "acme@1")
    key = ("lane", 0)
    assert tier.put_ckpt(key, {"emitted": [1]}, slab, "acme@1")
    # B pages in: A's entries survive in host RAM, gated invisible
    tier.set_version("globex@1")
    assert tier.match_prefix(toks, "globex@1") is None
    assert tier.take_ckpt(key, "globex@1") is None
    # ...and the gated lookups did NOT destroy the entries
    tier.set_version("acme@1")
    hit = tier.match_prefix(toks, "acme@1")
    assert hit is not None and hit[0] == 8
    assert tier.take_ckpt(key, "acme@1") is not None
    # A re-puts: acme@* entries die
    tier.set_version("acme@2")
    tier.set_version("acme@1")
    assert tier.match_prefix(toks, "acme@1") is None


# -- WeightPager unit --------------------------------------------------------


def test_pager_codec_roundtrip():
    import io

    leaves = [np.arange(12, dtype=np.float32).reshape(3, 4),
              np.array([7], np.int32)]
    blob = _encode_ckpt({"tenant": "t", "weight_version": "t@1"}, leaves)
    meta, out = _decode_ckpt(io.BytesIO(blob).read)
    assert meta["tenant"] == "t"
    assert all((a == b).all() for a, b in zip(leaves, out))


def test_pager_put_promote_and_versions():
    pager = WeightPager(1 << 20)
    v1 = pager.put("acme", _tree(0), "strict")
    assert v1 == "acme@1"
    pager.mark_resident("acme")
    assert pager.resident == "acme"
    assert pager.slo_class("acme") == "strict"
    params, version = pager.promote("acme")
    assert version == "acme@1"
    assert (params["w"] == _tree(0)["w"]).all()
    # a re-put bumps the seq — the tenant's OWN caches invalidate
    assert pager.put("acme", _tree(1), "strict") == "acme@2"
    with pytest.raises(TenantUnknown):
        pager.promote("nobody")


def test_pager_lru_budget_refusal_and_resident_pin():
    blob = len(_encode_ckpt({}, list(_tree(0).values())))
    pager = WeightPager(int(blob * 2.5))
    pager.put("a", _tree(0))
    pager.mark_resident("a")
    pager.put("b", _tree(1))
    # staging is full (2 blobs in a 2.5-blob budget): c evicts the LRU
    # cold tenant (b), NEVER the resident
    pager.promote("b")  # touch b…
    pager.put("c", _tree(2))  # …still b evicts: a is resident-pinned
    assert set(pager.tenants()) == {"a", "c"}
    assert pager.stats["evictions"] == 1
    # half-budget refusal: one entry that fills staging would thrash
    with pytest.raises(PagerRefused):
        WeightPager(blob + 8).put("big", _tree(3))
    # a failed RE-put keeps the old checkpoint
    with pytest.raises(PagerRefused):
        pager.put("a", _tree(4, kb=3 * (blob // 1024)))
    assert "a" in pager.tenants()
    assert pager.promote("a")[1] == "a@1"


def test_pager_crc_drop_is_typed_and_terminal():
    pager = WeightPager(1 << 20)
    pager.put("acme", _tree(0))
    entry = pager._entries["acme"]
    bad = bytearray(entry.payload)
    bad[len(bad) // 2] ^= 0xFF
    entry.payload = bytes(bad)
    with pytest.raises(PagerEntryCorrupt):
        pager.promote("acme")
    assert pager.stats["corrupt_dropped"] == 1
    # dropped FIRST: it can never page again
    with pytest.raises(TenantUnknown):
        pager.promote("acme")


def test_tenant_spec_grammar_strict():
    assert parse_tenant_spec("a=strict, b=best_effort@/m/b") == [
        ("a", "strict", None), ("b", "best_effort", "/m/b"),
    ]
    for bad in ("a", "a=", "a=gold", "a=strict,a=strict", "", "a b=strict"):
        with pytest.raises(ValueError):
            parse_tenant_spec(bad)


def test_tenant_meta_stamp_roundtrip():
    msg = stamp_tenant_meta({"jsonData": {}}, "acme")
    assert tenant_from_meta(msg["meta"]) == "acme"
    assert tenant_from_meta(None) is None
    assert tenant_from_meta({}) is None
    # no tenant: the message is returned untouched (no meta allocation)
    m = {"jsonData": {}}
    assert stamp_tenant_meta(m, None) is m


# -- tenant-aware victim policy (satellite 2) --------------------------------


def _lane(tenant, slo, emitted=0, max_new=40, deadline_t=None):
    req = GenRequest(tokens=[1, 2], max_new_tokens=max_new,
                     tenant=tenant, slo=slo, deadline_t=deadline_t)
    return types.SimpleNamespace(request=req, emitted=[0] * emitted)


def test_pick_victim_prefers_best_effort_and_protects_strict():
    model = DecoderLM(**CFG)
    b = ContinuousBatcher(model, model.init_params(0), slots=4, max_seq=64,
                          prefill_buckets=(8,))
    try:
        # no scheduler thread is alive yet: direct calls are legal
        b._active = {0: _lane("acme", "strict"),
                     1: _lane("globex", "best_effort")}
        # best-effort yields before strict, even though lane 0 has the
        # same remaining budget
        assert b._pick_victim() == ("lane", 1)
        # strict tenant's ONLY live lane is protected while any
        # best-effort lane exists — even one that would otherwise win
        # on the progress key
        b._active = {0: _lane("acme", "strict", emitted=39),
                     1: _lane("globex", "best_effort", emitted=0)}
        assert b._pick_victim() == ("lane", 1)
        # two strict lanes of the SAME tenant: not a last lane, the
        # base policy picks among them once best-effort is gone
        b._active = {0: _lane("acme", "strict", emitted=10),
                     1: _lane("acme", "strict", emitted=2)}
        assert b._pick_victim() == ("lane", 1)
        # all-protected fallback: every lane is a strict singleton →
        # the guard stands down (pressure relief must stay possible)
        b._active = {0: _lane("acme", "strict"),
                     1: _lane("initech", "strict"),
                     2: _lane("globex", "best_effort", emitted=39)}
        v = b._pick_victim()
        assert v[0] == "lane" and v[1] == 2
        # single-tenant servers (tenant=None, slo default): the
        # pre-tenant ordering is unchanged — deadline-free first,
        # most remaining budget first
        b._active = {0: _lane(None, "standard", emitted=5),
                     1: _lane(None, "standard", emitted=0),
                     2: _lane(None, "standard", emitted=0,
                              deadline_t=time.monotonic() + 60)}
        assert b._pick_victim() == ("lane", 1)
    finally:
        b._active = {}
        b.close()


# -- the multi-tenant server -------------------------------------------------


def _write_model_dir(path, seed=0):
    path.mkdir()
    (path / "jax_config.json").write_text(
        json.dumps({"family": "llm", "config": {**CFG, "seed": seed}})
    )
    return str(path)


@pytest.fixture(scope="module")
def model_dirs(tmp_path_factory):
    root = tmp_path_factory.mktemp("tenants")
    return (_write_model_dir(root / "acme", seed=0),
            _write_model_dir(root / "globex", seed=7))


def _mk_server(model_dirs, **kw):
    from seldon_core_tpu.servers.generateserver import GenerateServer

    dir_a, dir_b = model_dirs
    kw.setdefault("slots", 2)
    kw.setdefault("steps_per_poll", 2)
    return GenerateServer(
        model_uri=dir_a,
        tenants=f"acme=strict,globex=best_effort@{dir_b}",
        weight_pager_host_bytes=64 << 20,
        **kw,
    )


def _gen(server, prompt, tenant=None, n=12, temperature=0.0, seed=0):
    body = {"prompt_tokens": [list(prompt)], "max_new_tokens": n,
            "temperature": temperature, "seed": seed}
    if tenant is not None:
        body["tenant"] = tenant
    return server.predict(body, [])["tokens"][0]


@pytest.fixture(scope="module")
def dedicated_refs(model_dirs):
    """Per-tenant greedy + seeded outputs from dedicated servers."""
    from seldon_core_tpu.servers.generateserver import GenerateServer

    refs = {}
    for name, d in zip(("acme", "globex"), model_dirs):
        s = GenerateServer(model_uri=d, slots=2, steps_per_poll=2)
        try:
            s.load()
            refs[name] = {
                "greedy": [_gen(s, p) for p in PROMPTS],
                "sampled": [_gen(s, p, temperature=0.8, seed=11 + i)
                            for i, p in enumerate(PROMPTS)],
            }
        finally:
            s.close()
    return refs


def test_multitenant_byte_identity_across_paging(model_dirs, dedicated_refs):
    """The house gate: greedy+seeded per-tenant outputs on the paged
    server equal the dedicated servers', interleaved so every tenant's
    requests straddle demote→promote cycles of the other."""
    s = _mk_server(model_dirs, tenant_min_resident_ms=0)
    try:
        s.load()
        assert s.tenant_pager.resident == "acme"
        got = {"acme": {"greedy": [], "sampled": []},
               "globex": {"greedy": [], "sampled": []}}
        # interleave A and B per prompt: each B request forces A out,
        # each following A request pages A back mid-run
        for i, p in enumerate(PROMPTS):
            for t in ("acme", "globex"):
                got[t]["greedy"].append(_gen(s, p, tenant=t))
            for t in ("acme", "globex"):
                got[t]["sampled"].append(
                    _gen(s, p, tenant=t, temperature=0.8, seed=11 + i)
                )
        assert got == dedicated_refs
        # the interleave really paged: every flip is a page-in, and
        # both tenants held residency at some point
        assert s.tenant_pager.stats["page_ins"] >= 3
        assert s.tenant_scheduler.stats["switches"] >= 2
    finally:
        s.close()


def test_scale_to_zero_pages_back_without_recompiling(model_dirs):
    """DeepServe's prewarm property: after a demote→promote round trip
    the jit caches have not grown — a cold-start is a page-in, never a
    recompile."""
    s = _mk_server(model_dirs, tenant_min_resident_ms=0)
    try:
        s.load()
        b = s.batcher
        # first full cycle compiles every shape both tenants need
        _gen(s, PROMPTS[0], tenant="acme")
        _gen(s, PROMPTS[0], tenant="globex")
        _gen(s, PROMPTS[0], tenant="acme")
        sizes = {
            name: fn._cache_size()
            for name, fn in (("prefill", b._prefill_fn),
                             ("burst", b._burst_fn))
            if fn is not None
        }
        switches_before = s.tenant_scheduler.stats["switches"]
        t0 = time.monotonic()
        assert _gen(s, PROMPTS[1], tenant="globex")  # acme demotes
        assert _gen(s, PROMPTS[1], tenant="acme")    # …and pages back
        cold_cycle_s = time.monotonic() - t0
        assert s.tenant_scheduler.stats["switches"] >= switches_before + 2
        for name, fn in (("prefill", b._prefill_fn), ("burst", b._burst_fn)):
            if fn is not None and name in sizes:
                assert fn._cache_size() == sizes[name], name
        # the bench's cold-start bound is seconds-scale; a recompile of
        # even this toy model would blow far past it
        assert cold_cycle_s < 30.0
    finally:
        s.close()


def test_starvation_bound_forces_the_flip(model_dirs):
    """Every tenant advances within tenant_max_wait_polls: a waiter is
    paged in by force even while the resident tenant never goes idle."""
    s = _mk_server(model_dirs, tenant_max_wait_polls=1,
                   tenant_min_resident_ms=0)
    try:
        s.load()
        stop = threading.Event()

        def flood():
            while not stop.is_set():
                try:
                    _gen(s, PROMPTS[0], tenant="acme", n=8)
                except RuntimeError:
                    return

        t = threading.Thread(target=flood, daemon=True)
        t.start()
        try:
            out = _gen(s, PROMPTS[1], tenant="globex", n=8)
            assert len(out) == len(PROMPTS[1]) + 8
        finally:
            stop.set()
            t.join(timeout=60)
        assert s.tenant_scheduler.stats["switches"] >= 1
        # K=1: the flip that served globex was the forced kind
        assert s.tenant_scheduler.stats["forced_switches"] >= 1
    finally:
        s.close()


def test_per_tenant_slo_split_and_metrics_tags(model_dirs):
    """PR 4's SLO triple splits per tenant, and the server's metrics()
    ships per-tenant counters/TIMERs tagged with the tenant id."""
    s = _mk_server(model_dirs, tenant_min_resident_ms=0)
    try:
        s.load()
        for t in ("acme", "globex", "acme"):
            _gen(s, PROMPTS[0], tenant=t)
        b = s.batcher
        assert b.tenant_slo["acme"]["finished"] >= 2
        assert b.tenant_slo["globex"]["finished"] >= 1
        assert len(b.tenant_slo_recent["acme"]) >= 2
        assert b.tenant_slo_recent["acme"][0][1] > 0        # its ttft
        ms = s.metrics()
        by_key = {}
        for m in ms:
            by_key.setdefault(m["key"], []).append(m)
        pager_keys = {"gen_weight_page_ins", "gen_weight_page_outs",
                      "gen_weight_pager_host_bytes",
                      "gen_weight_pager_resident_bytes",
                      "gen_tenants_registered", "gen_tenant_switches"}
        assert pager_keys <= set(by_key)
        assert by_key["gen_tenants_registered"][0]["value"] == 2.0
        req_tags = {m["tags"]["tenant"] for m in by_key["gen_tenant_requests"]}
        assert req_tags == {"acme", "globex"}
        ttft_tags = {m["tags"]["tenant"] for m in by_key["gen_tenant_ttft_ms"]}
        assert ttft_tags == {"acme", "globex"}
        # deltas are per-(key, tags): a second export after one more
        # acme request reports 1 for acme, 0 for globex — not clamped
        # by the other tenant's running total
        _gen(s, PROMPTS[1], tenant="acme")
        again = {
            m["tags"]["tenant"]: m["value"] for m in s.metrics()
            if m["key"] == "gen_tenant_requests"
        }
        assert again["acme"] == 1.0 and again["globex"] == 0.0
        # flight dump carries pager + scheduler summaries and the
        # tenant_switch / weight_page_in records
        dump = s.flight_dump()
        assert dump["weight_pager"]["resident"] in ("acme", "globex")
        assert dump["tenant_scheduler"]["switches"] >= 1
        kinds = {e.get("type") for e in dump["entries"]}
        assert "weight_page_in" in kinds and "tenant_switch" in kinds
    finally:
        s.close()


def test_pressure_ledger_counts_pager_component(model_dirs):
    s = _mk_server(model_dirs, hbm_ledger_bytes=1 << 30,
                   tenant_min_resident_ms=0)
    try:
        s.load()
        _gen(s, PROMPTS[0], tenant="acme")
        deadline = time.monotonic() + 30
        while (not s.batcher._pressure.components.get("pager")
               and time.monotonic() < deadline):
            time.sleep(0.002)  # update() swaps the dict — re-read it
        comp = s.batcher._pressure.components
        assert comp["pager"] > 0
        assert s.tenant_pager.resident_hbm_bytes > 0
    finally:
        s.close()


def test_unknown_tenant_refuses_typed(model_dirs):
    s = _mk_server(model_dirs)
    try:
        s.load()
        with pytest.raises(TenantUnknown):
            _gen(s, PROMPTS[0], tenant="nobody")
        # tenant-less traffic routes to the first declared tenant
        assert _gen(s, PROMPTS[0]) == _gen(s, PROMPTS[0], tenant="acme")
    finally:
        s.close()


def test_tenants_knob_refuses_misconfiguration(model_dirs):
    from seldon_core_tpu.servers.generateserver import GenerateServer

    dir_a, _ = model_dirs
    with pytest.raises(ValueError):
        GenerateServer(model_uri=dir_a, tenants="a=gold",
                       weight_pager_host_bytes=1 << 20)
    with pytest.raises(ValueError):  # pager budget is mandatory
        GenerateServer(model_uri=dir_a, tenants="a=strict")
    with pytest.raises(ValueError):  # no disagg roles
        GenerateServer(model_uri=dir_a, tenants="a=strict",
                       weight_pager_host_bytes=1 << 20, role="decode")


# -- controlplane plumbing ---------------------------------------------------


def test_tenants_annotation_parse_and_injection():
    from seldon_core_tpu.graph.spec import (
        GraphSpecError,
        PredictorSpec,
        inject_tenants_param,
        parse_tenants_annotation,
        validate_predictor,
    )

    def spec(ann=None, params=None, impl="GENERATE_SERVER"):
        return PredictorSpec.from_dict({
            "name": "p",
            "annotations": ann or {},
            "graph": {
                "name": "gen", "type": "MODEL", "implementation": impl,
                "modelUri": "file:///m",
                "parameters": params or [],
            },
        })

    assert parse_tenants_annotation(spec()) is None
    s = spec({"seldon.io/tenants": "a=strict,b=best_effort@gs://m/b"})
    assert parse_tenants_annotation(s) == [
        ("a", "strict", None), ("b", "best_effort", "gs://m/b"),
    ]
    validate_predictor(s)
    with pytest.raises(GraphSpecError):
        parse_tenants_annotation(spec({"seldon.io/tenants": "a=gold"}))
    with pytest.raises(GraphSpecError):
        parse_tenants_annotation(
            spec({"seldon.io/tenants": "a=strict"}, impl="SKLEARN_SERVER")
        )
    with pytest.raises(GraphSpecError):  # the annotation owns the param
        parse_tenants_annotation(spec(
            {"seldon.io/tenants": "a=strict"},
            params=[{"name": "tenants", "value": "x=strict",
                     "type": "STRING"}],
        ))
    d = spec({"seldon.io/tenants": "a=strict"}).to_dict()
    out = inject_tenants_param(d, "a=strict")
    names = {p["name"]: p["value"] for p in out["graph"]["parameters"]}
    assert names["tenants"] == "a=strict"


def test_reconciler_injects_tenants_param():
    import asyncio

    from seldon_core_tpu.controlplane.reconciler import DeploymentController
    from seldon_core_tpu.controlplane.resource import SeldonDeployment

    rec = DeploymentController.__new__(DeploymentController)
    rec._kv_ports = {}
    rec.components = {}
    dep = SeldonDeployment.from_dict({
        "metadata": {"name": "d", "namespace": "ns"},
        "spec": {"predictors": [{
            "name": "p",
            "annotations": {"seldon.io/tenants": "a=strict,b=standard"},
            "graph": {"name": "gen", "type": "MODEL",
                      "implementation": "GENERATE_SERVER",
                      "modelUri": "file:///m"},
        }]},
    })
    specs = asyncio.run(rec.desired_components(dep))
    engines = [c for c in specs if c.kind == "engine"]
    assert engines
    for es in engines:
        params = {
            p["name"]: p["value"]
            for p in es.engine_spec["graph"].get("parameters") or []
        }
        assert params.get("tenants") == "a=strict,b=standard"
        assert "seldon.io/tenants" not in (
            es.engine_spec.get("annotations") or {}
        )


def test_engine_stamps_tenant_header_into_meta():
    import asyncio

    from seldon_core_tpu.graph.engine_metrics import MetricsRegistry
    from seldon_core_tpu.graph.service import EngineApp
    from seldon_core_tpu.graph.spec import PredictorSpec

    seen = {}

    class Probe:
        def predict(self, X, names, meta=None):
            seen["tenant"] = tenant_from_meta(meta)
            return {"routed": True}

    spec = PredictorSpec.from_dict({
        "name": "p",
        "graph": {"name": "m", "type": "MODEL",
                  "implementation": "SIMPLE_MODEL"},
    })
    app = EngineApp(spec, registry={"m": Probe()},
                    metrics=MetricsRegistry())
    asyncio.run(app.predict(
        {"jsonData": {"x": 1}}, headers={"seldon-tenant": "acme"}
    ))
    assert seen["tenant"] == "acme"


def test_flight_report_renders_pager_and_thrash_diagnosis():
    import importlib.util
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "flight_report", os.path.join(root, "tools", "flight_report.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    entries = []
    for i in range(3):  # acme and globex displacing each other
        for t, other in (("acme", "globex"), ("globex", "acme")):
            entries.append({"type": "weight_page_out", "tenant": other,
                            "host_bytes": 8192})
            entries.append({"type": "weight_page_in", "tenant": t,
                            "version": f"{t}@1", "cost_ms": 12.5})
            entries.append({"type": "tenant_switch", "from": other,
                            "to": t, "forced": i == 2, "cost_ms": 12.5,
                            "queued": 1})
    dump = {
        "entries": entries, "recorded_total": len(entries), "dropped": 0,
        "weight_pager": {"budget_bytes": 1 << 20, "host_bytes": 16384,
                         "tenants": ["acme", "globex"], "resident": "acme",
                         "evictions": 0, "refused": 0, "corrupt_dropped": 0},
        "tenant_scheduler": {"queued": {"globex": 2}},
    }
    text = mod.render(dump)
    assert "tenant switches: 6 flip(s) (2 forced" in text
    assert "weight pager: 6 page-in(s), 6 page-out(s)" in text
    assert "THRASH" in text and "tenant_min_resident_ms" in text
    assert "weight pager staging" in text
    assert "tenant queues at dump time: globex=2" in text
    # one tenant paging in once is a working feature, not thrash
    calm = {
        "entries": [
            {"type": "weight_page_in", "tenant": "acme",
             "version": "acme@1", "cost_ms": 9.0},
            {"type": "tenant_switch", "from": None, "to": "acme",
             "forced": False, "cost_ms": 9.0, "queued": 0},
        ],
        "recorded_total": 2, "dropped": 0,
    }
    assert "THRASH" not in mod.render(calm)


def test_tenant_metrics_map_to_first_class_series():
    from seldon_core_tpu.graph.engine_metrics import MetricsRegistry

    reg = MetricsRegistry()
    reg.record_custom([
        {"type": "COUNTER", "key": "gen_tenant_requests", "value": 2,
         "tags": {"tenant": "acme"}},
        {"type": "COUNTER", "key": "gen_tenant_requests", "value": 5,
         "tags": {"tenant": "globex"}},
        {"type": "COUNTER", "key": "gen_tenant_switches", "value": 3},
        {"type": "COUNTER", "key": "gen_weight_page_ins", "value": 4},
        {"type": "COUNTER", "key": "gen_weight_page_outs", "value": 3},
        {"type": "COUNTER", "key": "gen_weight_pager_evictions", "value": 0},
        {"type": "COUNTER", "key": "gen_weight_pager_refused", "value": 0},
        {"type": "GAUGE", "key": "gen_weight_pager_host_bytes",
         "value": 4096.0},
        {"type": "GAUGE", "key": "gen_weight_pager_resident_bytes",
         "value": 2048.0},
        {"type": "GAUGE", "key": "gen_tenants_registered", "value": 2.0},
        {"type": "TIMER", "key": "gen_tenant_ttft_ms", "value": 12.0,
         "tags": {"tenant": "acme"}},
        {"type": "TIMER", "key": "gen_tenant_tpot_ms", "value": 3.0,
         "tags": {"tenant": "acme"}},
        {"type": "TIMER", "key": "gen_tenant_queue_wait_ms", "value": 1.0,
         "tags": {"tenant": "acme"}},
    ], {"unit": "gen"})
    expo = reg.expose()
    for series in (
        "seldon_engine_tenant_requests",
        "seldon_engine_tenant_switches",
        "seldon_engine_weight_page_ins",
        "seldon_engine_weight_page_outs",
        "seldon_engine_weight_pager_evictions",
        "seldon_engine_weight_pager_refused",
        "seldon_engine_weight_pager_host_bytes",
        "seldon_engine_weight_pager_resident_bytes",
        "seldon_engine_tenants_registered",
        "seldon_engine_tenant_ttft_seconds",
        "seldon_engine_tenant_tpot_seconds",
        "seldon_engine_tenant_queue_wait_seconds",
    ):
        assert series in expo, series
    # the tenant tag became a label: per-tenant totals separate
    assert reg.counter_total(
        "seldon_engine_tenant_requests", {"tenant": "acme"}
    ) == 2.0
    assert reg.counter_total(
        "seldon_engine_tenant_requests", {"tenant": "globex"}
    ) == 5.0
