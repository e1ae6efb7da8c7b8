"""Generation-path observability: per-request timeline spans stitched
into the engine trace, the scheduler flight recorder (+ /flightrecorder
route and tools/flight_report.py), and the TTFT/TPOT/queue-wait SLO
metrics — plus the byte-identity and overhead contracts (recording and
tracing must never change greedy output)."""

import asyncio
import importlib.util
import json
import os
import time

import pytest

from seldon_core_tpu.graph.engine_metrics import MetricsRegistry
from seldon_core_tpu.graph.service import EngineApp
from seldon_core_tpu.graph.spec import PredictorSpec, default_predictor
from seldon_core_tpu.http_server import Request
from seldon_core_tpu.models.llm import DecoderLM
from seldon_core_tpu.serving.continuous import ContinuousBatcher
from seldon_core_tpu.tracing import get_tracer, init_tracer

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


@pytest.fixture(scope="module")
def model_and_params():
    model = DecoderLM(**CFG)
    return model, model.init_params(0)


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("llm")
    (d / "jax_config.json").write_text(
        json.dumps({"family": "llm", "config": CFG})
    )
    return str(d)


def _generate_server(model_dir, **kw):
    from seldon_core_tpu.servers.generateserver import GenerateServer

    kw.setdefault("slots", 2)
    kw.setdefault("steps_per_poll", 4)
    kw.setdefault("attn_bucket", 16)
    return GenerateServer(model_uri=model_dir, **kw)


@pytest.fixture(scope="module")
def shared_server(model_dir):
    """One loaded generate server for the read-only tests (loading builds
    the jit executables — per-test servers would dominate the suite)."""
    server = _generate_server(model_dir)
    server.load()
    yield server
    if server.batcher:
        server.batcher.close()


def _engine(component, name="p"):
    spec = default_predictor(
        PredictorSpec.from_dict(
            {"name": name, "graph": {"name": "gen", "type": "MODEL"}}
        )
    )
    return EngineApp(spec, registry={"gen": component})


# -- per-request timelines ---------------------------------------------------


def test_generate_request_traced_end_to_end(shared_server):
    """A generate request renders as ONE stitched trace: engine root →
    graph hop → queue_wait / prefill / lane_insert / decode spans, all
    under one trace id, in lifecycle order, ending complete."""
    init_tracer("obs-test", enabled=True)
    app = _engine(shared_server)
    try:
        out = asyncio.run(app.predict({"jsonData": {
            "prompt_tokens": [[1, 2, 3, 4, 5]],
            "max_new_tokens": 6, "temperature": 0.0,
        }}))
        assert len(out["jsonData"]["tokens"][0]) == 11
        spans = get_tracer().finished_spans()
        by_op = {}
        for s in spans:
            by_op.setdefault(s.operation, []).append(s)
        root = by_op["predictions"][0]
        hop = by_op["gen.predict"][0]
        for op in ("gen.queue_wait", "gen.prefill", "gen.lane_insert",
                   "gen.decode"):
            assert op in by_op, sorted(by_op)
            for s in by_op[op]:
                # one trace id end to end, parented under the graph hop
                assert s.trace_id == root.trace_id
                assert s.parent_id == hop.span_id
        queue = by_op["gen.queue_wait"][0]
        prefill = by_op["gen.prefill"][0]
        decode = by_op["gen.decode"][0]
        # lifecycle order on the timeline: queue → prefill → decode
        assert queue.start_us <= prefill.start_us <= decode.start_us
        assert decode.tags["outcome"] == "complete"
        assert decode.tags["tokens"] == 6
        assert decode.tags["ttft_ms"] >= 0
    finally:
        init_tracer(enabled=False)


def test_chunked_prefill_spans(model_dir):
    """Chunked admissions emit one gen.prefill_chunk span per interleaved
    slice, still inside the request's trace."""
    init_tracer("obs-chunk", enabled=True)
    server = _generate_server(model_dir, prefill_chunk=16)
    app = _engine(server)
    try:
        asyncio.run(app.predict({"jsonData": {
            "prompt_tokens": [list(range(1, 30))],
            "max_new_tokens": 4, "temperature": 0.0,
        }}))
        spans = get_tracer().finished_spans()
        chunks = [s for s in spans if s.operation == "gen.prefill_chunk"]
        assert len(chunks) == 2  # 29-token prompt at chunk=16
        root = next(s for s in spans if s.operation == "predictions")
        assert all(s.trace_id == root.trace_id for s in chunks)
        assert chunks[-1].tags["last"] is True
    finally:
        if server.batcher:
            server.batcher.close()
        init_tracer(enabled=False)


def test_untraced_requests_emit_no_spans(shared_server):
    """Tracing off (the default): the scheduler stamps timestamps but
    records no spans, and output is identical to a traced run."""
    init_tracer(enabled=False)
    try:
        out = shared_server.predict(
            {"prompt_tokens": [[1, 2, 3]], "max_new_tokens": 4}, []
        )
        assert get_tracer().finished_spans() == []
        init_tracer("obs-on", enabled=True)
        out2 = shared_server.predict(
            {"prompt_tokens": [[1, 2, 3]], "max_new_tokens": 4}, []
        )
        assert out2["tokens"] == out["tokens"]
    finally:
        init_tracer(enabled=False)


# -- flight recorder ---------------------------------------------------------


def test_flight_recorder_poll_records(model_and_params):
    model, params = model_and_params
    b = ContinuousBatcher(model, params, slots=2, max_seq=64,
                          prefill_buckets=(8, 16), steps_per_poll=4)
    try:
        b.generate([1, 2, 3, 4], max_new_tokens=6)
        entries = b.flight.snapshot()
        assert entries, "no flight records"
        polls = [e for e in entries if e["type"] == "poll"]
        assert polls
        admits = [e for e in polls if e.get("admitted")]
        assert admits, "admission never recorded"
        plans = [e["plan"] for e in polls if "plan" in e]
        assert any(p["mode"] == "decode" for p in plans)
        decode = next(p for p in plans if p["mode"] == "decode")
        # the plan explains the poll: burst length, lanes and bucket
        assert decode["k"] == 4
        assert decode["lanes"] == 1 and decode["bucket"] >= 64
        assert decode["distinct_buckets"] == 1
        assert "groups" not in decode and "merged" not in decode
        # seq monotonically increases and the dump is JSON-clean
        seqs = [e["seq"] for e in entries]
        assert seqs == sorted(seqs)
        json.dumps(b.flight.dump())
        assert len(b.flight.dump(limit=1)["entries"]) == 1
    finally:
        b.close()


def test_flight_recorder_shed_and_drop_oldest(model_and_params):
    model, params = model_and_params
    from seldon_core_tpu.resilience import ShedError

    b = ContinuousBatcher(model, params, slots=2, max_seq=64,
                          prefill_buckets=(8,), admit_queue_limit=1,
                          flight_recorder_capacity=4)
    try:
        # fill the admit queue past the cap without starting the loop, so
        # the shed decision is deterministic
        b._queue.put(object())
        with pytest.raises(ShedError):
            b.submit([1, 2, 3], max_new_tokens=2)
        sheds = [e for e in b.flight.snapshot() if e["type"] == "shed"]
        assert sheds and sheds[0]["reason"] == "queue_full"
        # drop-oldest under pressure: the ring never exceeds capacity
        for i in range(10):
            b.flight.record({"type": "poll", "i": i})
        dump = b.flight.dump()
        assert len(dump["entries"]) == 4
        assert dump["dropped"] == dump["recorded_total"] - 4
        assert dump["entries"][-1]["i"] == 9
    finally:
        b._queue.get_nowait()
        b.close()


def test_flight_recorder_off_and_byte_identity(model_dir):
    """flight_recorder=0 disables recording; greedy output is
    byte-identical with the recorder on vs off."""
    on = _generate_server(model_dir)
    off = _generate_server(model_dir, flight_recorder=0)
    try:
        body = {"prompt_tokens": [[9, 8, 7, 6]], "max_new_tokens": 8}
        t_on = on.predict(dict(body), [])["tokens"]
        t_off = off.predict(dict(body), [])["tokens"]
        assert t_on == t_off
        assert off.batcher.flight is None
        assert off.flight_dump() is None
        assert on.flight_dump()["entries"]
    finally:
        for s in (on, off):
            if s.batcher:
                s.batcher.close()


def test_flightrecorder_route(shared_server):
    """/flightrecorder explains each poll's decisions and carries the SLO
    summary; ?limit= caps entries; non-generate graphs 404."""
    app = _engine(shared_server)
    asyncio.run(app.predict({"jsonData": {
        "prompt_tokens": [[1, 2, 3, 4]], "max_new_tokens": 5,
    }}))
    rest = app.rest_app()
    handler = rest.routes["/flightrecorder"]
    resp = asyncio.run(handler(Request("GET", "/flightrecorder", "", {}, b"")))
    assert resp.status == 200
    payload = json.loads(resp.body)
    dump = payload["units"]["gen"]
    assert any(e["type"] == "poll" for e in dump["entries"])
    assert dump["slo"]["samples"] >= 1
    assert dump["stats"]["finished"] >= 1
    resp = asyncio.run(
        handler(Request("GET", "/flightrecorder", "limit=1", {}, b""))
    )
    assert len(json.loads(resp.body)["units"]["gen"]["entries"]) == 1

    class Plain:
        def predict(self, X, names, meta=None):
            return X

    plain_app = _engine(Plain(), name="plain")
    handler = plain_app.rest_app().routes["/flightrecorder"]
    resp = asyncio.run(handler(Request("GET", "/flightrecorder", "", {}, b"")))
    assert resp.status == 404


def test_wrapper_flightrecorder_route(shared_server):
    """A standalone (wrapper-served) generate server exposes its flight
    recorder too; components without one don't grow the route."""
    from seldon_core_tpu.wrapper import get_rest_microservice

    shared_server.predict(
        {"prompt_tokens": [[3, 1, 4]], "max_new_tokens": 3}, []
    )
    ms = get_rest_microservice(shared_server)
    handler = ms.routes["/flightrecorder"]
    resp = asyncio.run(handler(Request("GET", "/flightrecorder", "", {}, b"")))
    assert resp.status == 200
    dump = json.loads(resp.body)
    assert dump["entries"] and dump["slo"]["samples"] >= 1

    class Plain:
        def predict(self, X, names, meta=None):
            return X

    assert "/flightrecorder" not in get_rest_microservice(Plain()).routes


def test_flight_report_diagnosis(shared_server):
    """tools/flight_report.py renders a dump into a readable diagnosis."""
    spec = importlib.util.spec_from_file_location(
        "flight_report",
        os.path.join(os.path.dirname(__file__), "..", "tools",
                     "flight_report.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    shared_server.predict({"prompt_tokens": [[1, 2, 3, 4]],
                           "max_new_tokens": 5}, [])
    # a request resolves in its burst's credit; the poll's record is
    # written once the poll's reads are done, a moment later
    deadline = time.monotonic() + 10.0
    while (not shared_server.flight_dump()["entries"]
           and time.monotonic() < deadline):
        time.sleep(0.01)
    report = mod.render({"units": {"gen": shared_server.flight_dump()}})
    assert "flight report: gen" in report
    assert "SLO over" in report
    assert "working polls" in report
    # empty dump still renders (no traffic case)
    empty = mod.render({"entries": [], "recorded_total": 0, "dropped": 0})
    assert "no poll records" in empty


# -- SLO metrics -------------------------------------------------------------


def test_slo_timers_and_delta_counters(model_dir):
    server = _generate_server(model_dir)
    try:
        server.predict({"prompt_tokens": [[1, 2, 3, 4, 5, 6]],
                        "max_new_tokens": 6}, [])
        out = server.metrics()
        by_key = {}
        for m in out:
            by_key.setdefault(m["key"], []).append(m)
        # one TIMER triple per completed request
        assert by_key["gen_ttft_ms"][0]["type"] == "TIMER"
        assert by_key["gen_queue_wait_ms"][0]["type"] == "TIMER"
        assert by_key["gen_tpot_ms"][0]["type"] == "TIMER"
        assert by_key["gen_ttft_ms"][0]["value"] >= by_key[
            "gen_queue_wait_ms"][0]["value"]
        # scheduler totals ship as COUNTER deltas (the CounterDeltas
        # contract): tokens counted once, a traffic-less rescrape reads 0
        assert by_key["gen_tokens"][0]["type"] == "COUNTER"
        assert by_key["gen_tokens"][0]["value"] == 6.0
        assert by_key["gen_finished"][0]["value"] == 1.0
        again = {m["key"]: m for m in server.metrics()}
        assert again["gen_tokens"]["value"] == 0.0
        assert "gen_ttft_ms" not in again  # drained
        # batcher-side aggregates feed bench summaries
        slo = server.batcher.slo_summary()
        assert slo["samples"] == 1
        assert slo["ttft_ms"]["p99_ms"] >= slo["queue_wait_ms"]["p99_ms"]
    finally:
        if server.batcher:
            server.batcher.close()


def test_single_token_completion_has_no_tpot(model_dir):
    """A 1-token generation has no inter-token interval: every TPOT view
    (TIMER export, reservoir percentiles, flight report) must skip the
    sample identically instead of some counting a meaningless 0.0."""
    server = _generate_server(model_dir)
    try:
        server.predict({"prompt_tokens": [[1, 2, 3]],
                        "max_new_tokens": 1, "temperature": 0.0}, [])
        keys = {m["key"] for m in server.metrics()}
        assert "gen_ttft_ms" in keys and "gen_queue_wait_ms" in keys
        assert "gen_tpot_ms" not in keys
        slo = server.batcher.slo_summary()
        assert slo["samples"] == 1
        assert slo["tpot_ms"] is None
        dump = server.flight_dump()
        json.dumps(dump)  # the route payload must stay serializable
        spec = importlib.util.spec_from_file_location(
            "flight_report",
            os.path.join(os.path.dirname(__file__), "..", "tools",
                         "flight_report.py"),
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert "TPOT n/a" in mod.render({"units": {"gen": dump}})
    finally:
        if server.batcher:
            server.batcher.close()


def test_engine_prometheus_end_to_end(shared_server):
    """Through the real engine app: TIMER samples land as first-class
    TTFT/TPOT/queue-wait histograms per graph node on /prometheus (the
    CI smoke's in-process twin)."""
    reg = MetricsRegistry()
    spec = default_predictor(
        PredictorSpec.from_dict(
            {"name": "p", "graph": {"name": "gen", "type": "MODEL"}}
        )
    )
    app = EngineApp(spec, registry={"gen": shared_server}, metrics=reg)
    asyncio.run(app.predict({"jsonData": {
        "prompt_tokens": [[2, 4, 6, 8]], "max_new_tokens": 4,
    }}))
    handler = app.rest_app().routes["/prometheus"]
    text = asyncio.run(
        handler(Request("GET", "/prometheus", "", {}, b""))
    ).body.decode()
    assert "seldon_engine_generate_ttft_seconds_bucket" in text
    assert "seldon_engine_generate_tpot_seconds_bucket" in text
    assert "seldon_engine_generate_queue_wait_seconds_bucket" in text
    assert 'unit="gen"' in text

