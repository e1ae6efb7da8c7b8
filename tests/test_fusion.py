"""Graph fusion (graph/fusion.py): single-executable multi-stage inference.

The load-bearing contracts: (1) byte-identity — a fused segment's
response (tensor payload, names, tags, requestPath) is identical to the
hop-by-hop walk's, chain and combiner fan-in alike, RAG greedy-generate
tail included; (2) per-unit semantics are never hidden — a remote
client, fault injector, micro-batcher, open breaker, deadline budget or
live shadow mirror forces a counted, logged fallback to the per-unit
path, never silently changed behavior; (3) one fused segment is ONE
device hop — a single ``gen.fused_segment`` span replaces the N
per-stage spans.
"""

import asyncio
import json
import os
import time

import numpy as np
import pytest

from seldon_core_tpu.graph import GraphExecutor, PredictorSpec
from seldon_core_tpu.graph.client import UnitCallError
from seldon_core_tpu.graph.engine_metrics import MetricsRegistry
from seldon_core_tpu.graph.spec import (
    GraphSpecError,
    default_predictor,
    parse_fuse_annotation,
)
from seldon_core_tpu.servers.generateserver import COMPILE_TELEMETRY_KEYS
from seldon_core_tpu.user_model import JAXComponent, JAXTransformComponent

FUSE_ANN = {"seldon.io/fuse": "true"}


def run(coro):
    return asyncio.run(coro)


class MatMul(JAXComponent):
    """Tiny jitted stage: x @ W, with a distinguishable W per instance."""

    warmup_shape = (4,)

    def __init__(self, scale=0.1, out=4, **kw):
        super().__init__(**kw)
        self._scale = scale
        self._out = out

    def build(self):
        import jax.numpy as jnp

        w = (jnp.arange(4 * self._out, dtype=jnp.float32)
             .reshape(4, self._out) * self._scale)
        return (lambda p, x: x @ p), w


class MatMulTransform(JAXTransformComponent, MatMul):
    pass


def make_executor(graph, registry, fuse=True, annotations=None,
                  metrics=None, faults=None):
    ann = dict(FUSE_ANN) if fuse else {}
    ann.update(annotations or {})
    spec = default_predictor(PredictorSpec.from_dict({
        "name": "p",
        **({"annotations": ann} if ann else {}),
        "graph": json.loads(json.dumps(graph)),
    }))
    return GraphExecutor(spec, registry=registry, metrics=metrics,
                         faults=faults)


def chain_graph(*names, types=None):
    node = None
    for i, name in reversed(list(enumerate(names))):
        t = (types or {}).get(name, "MODEL")
        node = {"name": name, "type": t,
                **({"children": [node]} if node else {})}
    return node


def strip_puid(out):
    out = json.loads(json.dumps(out))
    out.get("meta", {}).pop("puid", None)
    return out


@pytest.fixture()
def loaded_pair():
    a, b = MatMul(0.1), MatMul(0.3, out=3)
    a.load()
    b.load()
    return a, b


REQ = {"data": {"ndarray": [[1.0, 2.0, 3.0, 4.0]]}}


# -- planning ----------------------------------------------------------------


def test_plans_model_chain_segment(loaded_pair):
    a, b = loaded_pair
    ex = make_executor(chain_graph("a", "b"), {"a": a, "b": b})
    assert set(ex.fusion.segments) == {"a"}
    seg = ex.fusion.segments["a"]
    assert seg.names == ["a", "b"] and seg.kind == "subtree"


def test_fusion_off_by_default(loaded_pair):
    a, b = loaded_pair
    ex = make_executor(chain_graph("a", "b"), {"a": a, "b": b}, fuse=False)
    assert ex.fusion is None


def test_parse_fuse_annotation_strict():
    spec = default_predictor(PredictorSpec.from_dict({
        "name": "p", "annotations": {"seldon.io/fuse": "tru"},
        "graph": {"name": "m", "type": "MODEL"},
    }))
    with pytest.raises(GraphSpecError, match="seldon.io/fuse"):
        parse_fuse_annotation(spec)
    spec.annotations["seldon.io/fuse"] = "TRUE"
    assert parse_fuse_annotation(spec) is True
    spec.annotations.pop("seldon.io/fuse")
    assert parse_fuse_annotation(spec) is False


def test_remote_unit_is_counted_plan_fallback(loaded_pair):
    """A remote hop in the middle keeps everything per-unit: the chain
    around it is too short to fuse, and the exclusion is counted."""
    a, b = loaded_pair
    graph = {
        "name": "a", "type": "MODEL", "children": [{
            "name": "r", "type": "MODEL",
            "endpoint": {"service_host": "127.0.0.1", "service_port": 19987,
                         "transport": "REST"},
            "children": [{"name": "b", "type": "MODEL"}],
        }],
    }
    reg = MetricsRegistry()
    ex = make_executor(graph, {"a": a, "b": b}, metrics=reg)
    assert not ex.fusion.segments
    assert reg.counter_total(
        "seldon_engine_fusion_fallbacks", {"unit": "r", "reason": "remote"}
    ) == 1.0


def test_fault_injected_unit_is_counted_plan_fallback(loaded_pair):
    from seldon_core_tpu.resilience import FaultInjector

    a, b = loaded_pair
    reg = MetricsRegistry()
    faults = FaultInjector([{"unit": "b", "latency_ms": 1}])
    ex = make_executor(chain_graph("a", "b"), {"a": a, "b": b},
                       metrics=reg, faults=faults)
    assert not ex.fusion.segments
    assert reg.counter_total(
        "seldon_engine_fusion_fallbacks", {"unit": "b", "reason": "faults"}
    ) == 1.0


def test_microbatched_unit_not_fused(loaded_pair):
    a, b = loaded_pair
    spec = default_predictor(PredictorSpec.from_dict({
        "name": "p", "annotations": dict(FUSE_ANN),
        "graph": chain_graph("a", "b"),
    }))
    reg = MetricsRegistry()
    ex = GraphExecutor(spec, registry={"a": a, "b": b}, metrics=reg,
                       batching={"b": {"max_batch": 4}})
    assert not ex.fusion.segments
    assert reg.counter_total(
        "seldon_engine_fusion_fallbacks",
        {"unit": "b", "reason": "microbatch"},
    ) == 1.0


def test_bare_jaxcomponent_on_transformer_node_not_fused(loaded_pair):
    """A bare JAXComponent's transform hooks degrade to identity — fusing
    its executable on a TRANSFORMER node would CHANGE the output."""
    a, b = loaded_pair
    ex = make_executor(
        chain_graph("a", "b", types={"a": "TRANSFORMER"}), {"a": a, "b": b}
    )
    assert not ex.fusion.segments


def test_transform_component_chain_fuses_with_output_transformer():
    """TRANSFORMER -> MODEL -> OUTPUT_TRANSFORMER, all executable-backed:
    one subtree segment whose execution order is in, model, out."""
    t_in, model, t_out = MatMulTransform(0.1), MatMul(0.2), MatMulTransform(0.3)
    for c in (t_in, model, t_out):
        c.load()
    graph = {
        "name": "out", "type": "OUTPUT_TRANSFORMER", "children": [{
            "name": "in", "type": "TRANSFORMER",
            "children": [{"name": "model", "type": "MODEL"}],
        }],
    }
    reg = {"in": t_in, "model": model, "out": t_out}
    ex_f = make_executor(graph, reg)
    ex_h = make_executor(graph, reg, fuse=False)
    seg = ex_f.fusion.segments["out"]
    assert [s.name for s in seg.stages] == ["in", "model", "out"]
    of = strip_puid(run(ex_f.predict(dict(REQ))))
    oh = strip_puid(run(ex_h.predict(dict(REQ))))
    assert of == oh
    assert seg.dispatches == 1


# -- byte-identity -----------------------------------------------------------


def test_chain_byte_identity_with_tags_and_request_path(loaded_pair):
    a, b = loaded_pair
    ex_f = make_executor(chain_graph("a", "b"), {"a": a, "b": b})
    ex_h = make_executor(chain_graph("a", "b"), {"a": a, "b": b}, fuse=False)
    of = strip_puid(run(ex_f.predict(dict(REQ))))
    oh = strip_puid(run(ex_h.predict(dict(REQ))))
    assert of == oh
    assert list(of["meta"]["requestPath"]) == ["a", "b"]


def test_combiner_fanin_fuses_and_matches_hop_by_hop():
    """AVERAGE_COMBINER over two IDENTICAL jitted children (the mean is
    then exact at every precision — the fused f32 mean and the host f64
    mean agree bitwise)."""
    m1, m2 = MatMul(0.25), MatMul(0.25)
    m1.load()
    m2.load()
    graph = {
        "name": "comb", "implementation": "AVERAGE_COMBINER",
        "children": [
            {"name": "m1", "type": "MODEL"},
            {"name": "m2", "type": "MODEL"},
        ],
    }
    reg = {"m1": m1, "m2": m2}
    ex_f = make_executor(graph, reg)
    ex_h = make_executor(graph, reg, fuse=False)
    seg = ex_f.fusion.segments["comb"]
    assert seg.kind == "subtree"
    assert [s.name for s in seg.stages] == ["m1", "m2", "comb"]
    of = strip_puid(run(ex_f.predict(dict(REQ))))
    oh = strip_puid(run(ex_h.predict(dict(REQ))))
    assert of == oh
    assert seg.dispatches == 1


def test_fused_segment_is_one_span_with_stage_names(loaded_pair):
    from seldon_core_tpu import tracing

    a, b = loaded_pair
    tracer = tracing.init_tracer(enabled=True)
    try:
        ex_f = make_executor(chain_graph("a", "b"), {"a": a, "b": b})
        run(ex_f.predict(dict(REQ)))
        ops = [s.operation for s in tracer.finished_spans()]
        assert "gen.fused_segment" in ops
        # the N per-stage dispatch spans are GONE: one hop
        assert "a.predict" not in ops and "b.predict" not in ops
        fused = next(s for s in tracer.finished_spans()
                     if s.operation == "gen.fused_segment")
        assert fused.tags["units"] == "a,b"
        ex_h = make_executor(chain_graph("a", "b"), {"a": a, "b": b},
                             fuse=False)
        run(ex_h.predict(dict(REQ)))
        ops = [s.operation for s in tracer.finished_spans()]
        assert "a.predict" in ops and "b.predict" in ops
    finally:
        tracing.init_tracer(enabled=False)


# -- dynamic fallbacks -------------------------------------------------------


def test_deadline_request_falls_back_counted(loaded_pair):
    from seldon_core_tpu.resilience import Deadline

    a, b = loaded_pair
    reg = MetricsRegistry()
    ex_f = make_executor(chain_graph("a", "b"), {"a": a, "b": b},
                         metrics=reg)
    ex_h = make_executor(chain_graph("a", "b"), {"a": a, "b": b},
                         fuse=False)
    of = strip_puid(run(ex_f.predict(dict(REQ), deadline=Deadline(30_000))))
    oh = strip_puid(run(ex_h.predict(dict(REQ), deadline=Deadline(30_000))))
    assert of == oh
    seg = ex_f.fusion.segments["a"]
    assert seg.dispatches == 0 and seg.fallbacks == {"deadline": 1}
    assert reg.counter_total(
        "seldon_engine_fusion_fallbacks",
        {"unit": "a|b", "reason": "deadline"},
    ) == 1.0


def test_open_breaker_on_interior_unit_forces_fallback(loaded_pair):
    """With the breaker CLOSED the segment fuses; the moment it is not,
    every request takes the per-unit path where the breaker's own
    refusal applies — fused and unfused engines stay behaviorally
    identical on both sides of the transition."""
    from seldon_core_tpu.resilience.breaker import OPEN

    a, b = loaded_pair
    ann = {"seldon.io/breaker.b": "true"}
    reg_f, reg_h = MetricsRegistry(), MetricsRegistry()
    ex_f = make_executor(chain_graph("a", "b"), {"a": a, "b": b},
                         annotations=ann, metrics=reg_f)
    ex_h = make_executor(chain_graph("a", "b"), {"a": a, "b": b},
                         annotations=ann, fuse=False, metrics=reg_h)
    seg = ex_f.fusion.segments["a"]
    assert [s.name for s in seg.stages] == ["a", "b"]
    assert strip_puid(run(ex_f.predict(dict(REQ)))) == strip_puid(
        run(ex_h.predict(dict(REQ)))
    )
    assert seg.dispatches == 1

    def force_open(ex):
        rc = ex.root.children[0].client  # ResilientClient around b
        rc.breaker.state = OPEN
        rc.breaker._opened_at = time.monotonic()

    force_open(ex_f)
    force_open(ex_h)
    with pytest.raises(UnitCallError) as ef:
        run(ex_f.predict(dict(REQ)))
    with pytest.raises(UnitCallError) as eh:
        run(ex_h.predict(dict(REQ)))
    assert ef.value.status == eh.value.status == 503
    assert seg.fallbacks == {"breaker_open": 1}
    assert reg_f.counter_total(
        "seldon_engine_fusion_fallbacks",
        {"unit": "a|b", "reason": "breaker_open"},
    ) == 1.0


def test_shadow_mirror_active_forces_fallback(loaded_pair):
    a, b = loaded_pair
    ex_f = make_executor(chain_graph("a", "b"), {"a": a, "b": b})
    ex_h = make_executor(chain_graph("a", "b"), {"a": a, "b": b}, fuse=False)
    mirror_on = [True]
    ex_f.shadow_active_fn = lambda: mirror_on[0]
    of = strip_puid(run(ex_f.predict(dict(REQ))))
    oh = strip_puid(run(ex_h.predict(dict(REQ))))
    assert of == oh
    seg = ex_f.fusion.segments["a"]
    assert seg.dispatches == 0 and seg.fallbacks == {"shadow": 1}
    # shadow unwired (rollout terminal): fusion resumes
    mirror_on[0] = False
    assert strip_puid(run(ex_f.predict(dict(REQ)))) == oh
    assert seg.dispatches == 1


def test_engine_app_wires_shadow_inhibit(loaded_pair):
    from seldon_core_tpu.graph.service import EngineApp

    a, b = loaded_pair
    spec = default_predictor(PredictorSpec.from_dict({
        "name": "p", "annotations": dict(FUSE_ANN),
        "graph": chain_graph("a", "b"),
    }))
    app = EngineApp(spec, registry={"a": a, "b": b},
                    metrics=MetricsRegistry())
    assert app.executor.shadow_active_fn() is False
    app.shadow_mirror = object()
    assert app.executor.shadow_active_fn() is True


def test_fused_dispatch_error_falls_back_to_per_unit_path(loaded_pair):
    a, b = loaded_pair
    ex_f = make_executor(chain_graph("a", "b"), {"a": a, "b": b})
    ex_h = make_executor(chain_graph("a", "b"), {"a": a, "b": b}, fuse=False)
    seg = ex_f.fusion.segments["a"]

    def boom(_params, _x):
        raise RuntimeError("device exploded")

    seg._fn = boom
    of = strip_puid(run(ex_f.predict(dict(REQ))))
    oh = strip_puid(run(ex_h.predict(dict(REQ))))
    assert of == oh  # the hop path served the request
    assert seg.fallbacks == {"error": 1} and seg.dispatches == 0


def test_non_tensor_payload_falls_back(loaded_pair):
    a, b = loaded_pair
    ex_f = make_executor(chain_graph("a", "b"), {"a": a, "b": b})
    seg = ex_f.fusion.segments["a"]
    with pytest.raises(Exception):
        run(ex_f.predict({"strData": "not a tensor"}))
    assert seg.fallbacks == {"payload": 1}


# -- observability -----------------------------------------------------------


def test_fused_segments_metric_and_flight_dump(loaded_pair):
    a, b = loaded_pair
    reg = MetricsRegistry()
    ex = make_executor(chain_graph("a", "b"), {"a": a, "b": b}, metrics=reg)
    run(ex.predict(dict(REQ)))
    run(ex.predict(dict(REQ)))
    assert reg.counter_total(
        "seldon_engine_fused_segments", {"unit": "a|b"}
    ) == 2.0
    dump = ex.fusion.dump()
    assert dump["segments"]["a"]["dispatches"] == 2
    assert dump["segments"]["a"]["stages"] == ["a", "b"]
    recs = [e for e in dump["entries"] if e["type"] == "fused_dispatch"]
    assert len(recs) == 2 and recs[0]["stages"] == 2
    exposition = reg.expose()
    assert "seldon_engine_fused_segments" in exposition


def test_flightrecorder_route_serves_fusion_dump(loaded_pair, rest_client):
    from seldon_core_tpu.graph.service import EngineApp

    a, b = loaded_pair
    spec = default_predictor(PredictorSpec.from_dict({
        "name": "p", "annotations": dict(FUSE_ANN),
        "graph": chain_graph("a", "b"),
    }))
    app = EngineApp(spec, registry={"a": a, "b": b},
                    metrics=MetricsRegistry())
    run(app.predict(dict(REQ)))
    client = rest_client(app.rest_app())
    status, body = client.call("/flightrecorder", method="GET")
    assert status == 200
    assert "(fusion)" in body["units"]
    assert body["units"]["(fusion)"]["segments"]["a"]["dispatches"] == 1


# -- the RAG graph -----------------------------------------------------------


RAG_E, RAG_K, RAG_L, RAG_V = 16, 4, 6, 256


def _write_model(root, family, cfg):
    d = os.path.join(root, family)
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "jax_config.json"), "w") as f:
        json.dump({"family": family, "config": cfg}, f)
    return d


@pytest.fixture(scope="module")
def rag_components(tmp_path_factory):
    from seldon_core_tpu.graph.units import RagPromptBuilder
    from seldon_core_tpu.servers.generateserver import GenerateServer
    from seldon_core_tpu.servers.jaxserver import JAXServer

    root = str(tmp_path_factory.mktemp("rag-models"))
    bert_cfg = {"vocab_size": RAG_V, "d_model": 32, "n_layers": 2,
                "n_heads": 2, "d_ff": 64, "max_seq": 32,
                "num_classes": RAG_E}
    ret_cfg = {"corpus_size": 64, "d_embed": RAG_E, "top_k": RAG_K,
               "doc_len": RAG_L, "vocab_size": RAG_V, "seed": 7}
    llm_cfg = {"vocab_size": RAG_V, "d_model": 32, "n_layers": 2,
               "n_heads": 2, "n_kv_heads": 2, "d_ff": 64, "max_seq": 32}
    embed = JAXServer(model_uri=_write_model(root, "bert", bert_cfg))
    embed.load()
    retrieve = JAXServer(model_uri=_write_model(root, "retrieval", ret_cfg))
    retrieve.load()
    rerank = JAXServer(model_uri=_write_model(root, "reranker", ret_cfg))
    rerank.load()
    gen = GenerateServer(
        model_uri=_write_model(root, "llm", llm_cfg), slots=2,
        steps_per_poll=1, warmup_prompt_lens=[RAG_L],
        warmup_max_new_tokens=8,
    )
    gen.load()
    comps = {
        "embed": embed, "retrieve": retrieve, "rerank": rerank,
        "prompt": RagPromptBuilder(max_new_tokens=8), "generate": gen,
    }
    yield comps
    gen.close()


RAG_GRAPH = {
    "name": "embed", "type": "MODEL", "children": [{
        "name": "retrieve", "type": "MODEL", "children": [{
            "name": "rerank", "type": "MODEL", "children": [{
                "name": "prompt", "implementation": "RAG_PROMPT_BUILDER",
                "children": [{"name": "generate", "type": "MODEL"}],
            }],
        }],
    }],
}


def _rag_request(n=2, seed=0):
    rs = np.random.RandomState(seed)
    return {"data": {"ndarray": rs.randint(1, RAG_V, (n, 8)).tolist()}}


def test_rag_graph_fused_vs_hop_byte_identity(rag_components):
    """The acceptance gate: embed -> retrieve -> rerank fuses into one
    executable (prefix segment continuing at the prompt builder), the
    greedy generate tail included in the comparison; token output and
    meta identical, latency telemetry excluded (wall time is not
    data)."""
    ex_f = make_executor(RAG_GRAPH, rag_components)
    ex_h = make_executor(RAG_GRAPH, rag_components, fuse=False)
    seg = ex_f.fusion.segments["embed"]
    assert seg.kind == "prefix"
    assert seg.names == ["embed", "retrieve", "rerank"]
    assert seg.continue_at.name == "prompt"
    for seed in range(3):
        of = strip_puid(run(ex_f.predict(_rag_request(seed=seed))))
        oh = strip_puid(run(ex_h.predict(_rag_request(seed=seed))))
        # TIMER metrics are wall-clock telemetry; every other byte of
        # the response (tokens, tags, requestPath, counters) must match
        for o in (of, oh):
            o["meta"]["metrics"] = [
                m for m in o["meta"].get("metrics", [])
                if m.get("type") != "TIMER"
                # nor is what XLA compiled when: a shape's first call
                # compiles, whichever executor makes it
                and m.get("key") not in COMPILE_TELEMETRY_KEYS
            ]
        assert of == oh
        assert of["jsonData"]["tokens"]  # the greedy tail actually ran
        assert list(of["meta"]["requestPath"]) == [
            "embed", "retrieve", "rerank", "prompt", "generate",
        ]
    assert seg.dispatches == 3 and seg.fallbacks == {}


def test_rag_retrieval_families_corpus_contract():
    """retrieval + reranker configured alike serve the SAME corpus; a
    corpus past the bf16-exact integer range is refused at build."""
    from seldon_core_tpu.models.retrieval import (
        Reranker,
        RetrievalIndex,
        corpus_params,
    )

    emb1, docs1 = corpus_params(3, 32, 8, 5, 100)
    emb2, docs2 = corpus_params(3, 32, 8, 5, 100)
    assert (np.asarray(emb1) == np.asarray(emb2)).all()
    assert (np.asarray(docs1) == np.asarray(docs2)).all()
    assert np.asarray(docs1).min() >= 1  # 0 stays PAD
    with pytest.raises(ValueError, match="corpus_size"):
        RetrievalIndex(corpus_size=512, d_embed=8)
    with pytest.raises(ValueError, match="corpus_size"):
        Reranker(corpus_size=512, d_embed=8)
    with pytest.raises(ValueError, match="top_k"):
        RetrievalIndex(corpus_size=4, top_k=8)


def test_rag_prompt_builder_bridges_tensor_to_generate_body():
    from seldon_core_tpu.graph.units import RagPromptBuilder

    pb = RagPromptBuilder(max_new_tokens="12", temperature="0.5",
                          seed="3", eos_id="7")
    body = pb.transform_input(np.array([[5, 6, 7], [8, 9, 10]]), [])
    assert body == {
        "prompt_tokens": [[5, 6, 7], [8, 9, 10]],
        "max_new_tokens": 12, "temperature": 0.5, "seed": 3, "eos_id": 7,
    }
    with pytest.raises(ValueError, match="doc_len"):
        pb.transform_input(np.array([1, 2, 3]), [])


class Bf16MatMul(JAXComponent):
    """Stage whose OUTPUT stays bfloat16 — the hop-by-hop walk then
    flips the wire encoding to 'raw' at this hop, and raw is sticky."""

    warmup_shape = (4,)

    def build(self):
        import jax.numpy as jnp

        w = jnp.ones((4, 4), jnp.bfloat16) * jnp.bfloat16(0.5)
        return (lambda p, x: x @ p), w


def test_bf16_intermediate_keeps_sticky_raw_encoding():
    """An extended-dtype intermediate forces the unfused walk onto the
    raw wire encoding for every later hop; the fused response must
    mirror that, or fused-vs-unfused responses differ in shape."""
    from seldon_core_tpu.payload import jsonable

    a, b = Bf16MatMul(), MatMul(0.3, out=3)
    a.load()
    b.load()
    ex_f = make_executor(chain_graph("a", "b"), {"a": a, "b": b})
    ex_h = make_executor(chain_graph("a", "b"), {"a": a, "b": b}, fuse=False)
    assert ex_f.fusion.segments["a"]._forces_raw is True
    of = strip_puid(jsonable(run(ex_f.predict(dict(REQ)))))
    oh = strip_puid(jsonable(run(ex_h.predict(dict(REQ)))))
    assert "raw" in oh["data"]  # the hop path really did go raw
    assert of == oh


class NoWarmupBf16(Bf16MatMul):
    """bf16-emitting stage that declares NO warmup shape: the encoding
    probe cannot run at warm and must run on the first dispatch."""

    warmup_shape = None


def test_no_warmup_shape_probes_encoding_on_first_dispatch():
    from seldon_core_tpu.payload import jsonable

    a, b = NoWarmupBf16(), MatMul(0.3, out=3)
    a.load()
    b.load()
    ex_f = make_executor(chain_graph("a", "b"), {"a": a, "b": b})
    ex_h = make_executor(chain_graph("a", "b"), {"a": a, "b": b}, fuse=False)
    seg = ex_f.fusion.segments["a"]
    assert seg._probed is False  # warm had nothing to probe with
    of = strip_puid(jsonable(run(ex_f.predict(dict(REQ)))))
    oh = strip_puid(jsonable(run(ex_h.predict(dict(REQ)))))
    assert seg._probed is True and seg._forces_raw is True
    assert "raw" in oh["data"]
    assert of == oh


def test_tensorless_data_body_counts_payload_not_error(loaded_pair):
    a, b = loaded_pair
    ex_f = make_executor(chain_graph("a", "b"), {"a": a, "b": b})
    seg = ex_f.fusion.segments["a"]
    with pytest.raises(Exception):
        run(ex_f.predict({"data": {"names": ["x"]}}))
    assert seg.fallbacks == {"payload": 1}


def test_executor_rejects_junk_fuse_annotation(loaded_pair):
    """The executor parses seldon.io/fuse with the SAME strict parser
    admission uses: a typo'd value fails construction instead of
    silently serving hop-by-hop."""
    a, b = loaded_pair
    with pytest.raises(GraphSpecError, match="seldon.io/fuse"):
        make_executor(chain_graph("a", "b"), {"a": a, "b": b}, fuse=False,
                      annotations={"seldon.io/fuse": "yes"})
