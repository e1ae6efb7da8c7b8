"""Tracing tests: span tree, context propagation across engine graph hops
and REST process boundaries, Jaeger export shape (reference behavior:
engine TracingProvider + wrapper FlaskTracer, SURVEY §5)."""

import asyncio
import json
import os
import random
import threading
import time

import numpy as np
import pytest

from seldon_core_tpu import tracing
from seldon_core_tpu.graph.service import EngineApp
from seldon_core_tpu.graph.spec import PredictorSpec, default_predictor
from seldon_core_tpu.tracing import TRACE_HEADER, Tracer, get_tracer, init_tracer


def test_span_nesting_and_collection():
    t = Tracer("test", enabled=True)
    with t.span("root", tags={"a": 1}) as root:
        with t.span("child") as child:
            child.log(event="work")
        assert t.active_span() is root
    spans = t.finished_spans()
    assert [s.operation for s in spans] == ["child", "root"]
    assert spans[0].trace_id == spans[1].trace_id
    assert spans[0].parent_id == spans[1].span_id
    assert spans[1].tags == {"a": 1}
    assert spans[0].logs[0]["fields"] == {"event": "work"}


def test_span_error_tagging():
    t = Tracer(enabled=True)
    try:
        with t.span("boom"):
            raise ValueError("nope")
    except ValueError:
        pass
    s = t.finished_spans()[0]
    assert s.tags["error"] is True
    assert any(f["fields"].get("message") == "nope" for f in s.logs)


def test_disabled_tracer_is_noop():
    t = Tracer(enabled=False)
    with t.span("x") as s:
        s.set_tag("ignored", 1)
    assert t.finished_spans() == []
    assert t.inject({}) == {}


def test_inject_extract_roundtrip():
    t = Tracer(enabled=True)
    with t.span("parent"):
        headers = t.inject({})
        assert TRACE_HEADER in headers
    remote = Tracer.extract(headers)
    parent = t.finished_spans()[0]
    assert remote.trace_id == parent.trace_id
    assert remote.span_id == parent.span_id
    # malformed header is ignored
    assert Tracer.extract({TRACE_HEADER: "garbage"}) is None
    assert Tracer.extract({}) is None


def test_header_continues_trace():
    t = Tracer(enabled=True)
    with t.span("server", headers={TRACE_HEADER: "aaaa:bbbb:0:1"}) as s:
        assert s.trace_id == "aaaa"
        assert s.parent_id == "bbbb"


def test_jaeger_export_shape():
    t = Tracer("svc", enabled=True)
    with t.span("op", tags={"k": "v"}):
        pass
    out = t.export_jaeger()
    trace = out["data"][0]
    span = trace["spans"][0]
    assert span["operationName"] == "op"
    assert span["tags"] == [{"key": "k", "type": "string", "value": "v"}]
    assert trace["processes"]["p1"]["serviceName"] == "svc"
    json.dumps(out)  # serializable


def test_engine_graph_spans():
    """One request through a 2-level graph yields a stitched span tree."""
    init_tracer("engine-test", enabled=True)
    spec = default_predictor(
        PredictorSpec.from_dict(
            {
                "name": "p",
                "graph": {
                    "name": "combiner",
                    "implementation": "AVERAGE_COMBINER",
                    "children": [
                        {"name": "m1", "implementation": "SIMPLE_MODEL"},
                        {"name": "m2", "implementation": "SIMPLE_MODEL"},
                    ],
                },
            }
        )
    )
    app = EngineApp(spec)
    out = asyncio.run(app.predict({"data": {"ndarray": [[1.0, 2.0]]}}))
    assert "data" in out
    spans = get_tracer().finished_spans()
    ops = {s.operation for s in spans}
    assert {"predictions", "m1.predict", "m2.predict", "combiner.aggregate"} <= ops
    root = next(s for s in spans if s.operation == "predictions")
    assert all(s.trace_id == root.trace_id for s in spans)
    hops = [s for s in spans if s.operation != "predictions"]
    assert all(s.parent_id == root.span_id for s in hops)
    init_tracer(enabled=False)  # don't leak into other tests


def test_trace_crosses_rest_process_boundary():
    """Engine → remote microservice over a real socket: microservice-side
    spans continue the engine's trace via the injected header."""
    from seldon_core_tpu.user_model import SeldonComponent
    from seldon_core_tpu.wrapper import get_rest_microservice

    from _net import free_port, serve_on_thread

    class Doubler(SeldonComponent):
        def predict(self, X, names, meta=None):
            return np.asarray(X) * 2

    tracer = init_tracer("xproc", enabled=True)
    port = free_port()
    ms_app = get_rest_microservice(Doubler())
    stop = serve_on_thread(ms_app.serve_forever("127.0.0.1", port), port)

    spec = default_predictor(
        PredictorSpec.from_dict(
            {
                "name": "p",
                "graph": {
                    "name": "remote",
                    "type": "MODEL",
                    "endpoint": {"service_host": "127.0.0.1",
                                 "service_port": port, "transport": "REST"},
                },
            }
        )
    )
    engine = EngineApp(spec)
    out = asyncio.run(engine.predict({"data": {"ndarray": [[1.0]]}}))
    assert out["data"]["ndarray"] == [[2.0]]
    spans = tracer.finished_spans()
    root = next(s for s in spans if s.operation == "predictions")
    server_side = [s for s in spans if s.operation == "predict"]
    assert server_side, [s.operation for s in spans]
    # same trace id across the socket hop
    assert server_side[0].trace_id == root.trace_id
    stop()
    init_tracer(enabled=False)


def test_device_trace_annotation_smoke():
    import jax.numpy as jnp

    with tracing.device_trace("matmul"):
        x = jnp.ones((4, 4))
        (x @ x).block_until_ready()


# -- out-of-process export (VERDICT r3 #9) ----------------------------------


def _udp_collector():
    """Fake jaeger agent: bound UDP socket + drained datagrams."""
    import socket

    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    sock.bind(("127.0.0.1", 0))
    sock.settimeout(2.0)
    return sock, sock.getsockname()[1]


def test_jaeger_udp_export_reaches_agent(monkeypatch):
    """Spans land in a fake agent as thrift-compact emitBatch datagrams —
    the wire jaeger-client's UDPSender speaks (reference env parity:
    JAEGER_AGENT_HOST/PORT, microservice.py:116-151)."""
    sock, port = _udp_collector()
    monkeypatch.setenv("TRACING", "1")
    monkeypatch.setenv("JAEGER_AGENT_HOST", "127.0.0.1")
    monkeypatch.setenv("JAEGER_AGENT_PORT", str(port))
    monkeypatch.setenv("JAEGER_SERVICE_NAME", "svc-under-test")
    tracer = init_tracer()
    try:
        with tracer.span("score-request", tags={"deployment": "dep-1"}):
            pass
        assert tracer.flush() == 1
        pkt, _ = sock.recvfrom(65536)
    finally:
        sock.close()
        init_tracer(enabled=False)
    # thrift compact message header: protocol id 0x82, ONEWAY<<5|version
    assert pkt[0] == 0x82 and pkt[1] == 0x81
    assert b"emitBatch" in pkt
    # strings ride verbatim in thrift compact
    assert b"svc-under-test" in pkt
    assert b"score-request" in pkt
    assert b"deployment" in pkt and b"dep-1" in pkt


def test_engine_and_wrapper_spans_land_in_collector(monkeypatch):
    """End-to-end: engine graph spans AND the microservice wrapper's
    server-side spans both push to the same fake agent."""
    import asyncio

    from _net import free_port, serve_on_thread

    from seldon_core_tpu.graph.service import EngineApp
    from seldon_core_tpu.graph.spec import PredictorSpec, default_predictor
    from seldon_core_tpu.wrapper import get_rest_microservice

    sock, aport = _udp_collector()
    monkeypatch.setenv("TRACING", "1")
    monkeypatch.setenv("JAEGER_AGENT_HOST", "127.0.0.1")
    monkeypatch.setenv("JAEGER_AGENT_PORT", str(aport))
    tracer = init_tracer()

    class M:
        def predict(self, X, names, meta=None):
            import numpy as np

            return np.asarray(X)

    mport = free_port()
    stop = serve_on_thread(
        get_rest_microservice(M()).serve_forever("127.0.0.1", mport), mport
    )
    try:
        spec = default_predictor(
            PredictorSpec.from_dict(
                {
                    "name": "d",
                    "graph": {
                        "name": "m", "type": "MODEL",
                        "endpoint": {"service_host": "127.0.0.1",
                                     "service_port": mport, "transport": "REST"},
                    },
                }
            )
        )
        engine = EngineApp(spec)
        asyncio.run(engine.predict({"data": {"ndarray": [[1.0]]}}))
        tracer.flush()
        blob = b""
        for _ in range(4):
            try:
                pkt, _ = sock.recvfrom(65536)
                blob += pkt
            except TimeoutError:
                break
    finally:
        stop()
        sock.close()
        init_tracer(enabled=False)
    assert b"predictions" in blob  # engine root span
    assert b"predict" in blob      # wrapper server-side span (same process
    # tracer here, but it crossed the REST hop via uber-trace-id)


def test_sampled_bit_honored_across_hops():
    """The flags field of uber-trace-id carries the root's sampling
    decision: a downstream hop must NOT re-sample a request the upstream
    hop already dropped (it would export orphan fragments)."""
    upstream = Tracer("up", enabled=True, sample_rate=0.0)
    with upstream.span("root") as s:
        headers = upstream.inject({})
        # the dropped request still propagates a context — flags 0
        assert headers[TRACE_HEADER].endswith(":0")
        assert s.operation == "noop"
    assert upstream.finished_spans() == []

    downstream = Tracer("down", enabled=True, sample_rate=1.0)
    with downstream.span("server", headers=headers):
        with downstream.span("nested"):
            pass
        # nested hops inherit the drop too
        out = downstream.inject({})
        assert out[TRACE_HEADER].endswith(":0")
    assert downstream.finished_spans() == []

    # sampled header (flags 1) keeps working, and flags parse as hex
    assert Tracer.extract({TRACE_HEADER: "aaaa:bbbb:0:1"}).trace_id == "aaaa"
    assert Tracer.extract({TRACE_HEADER: "aaaa:bbbb:0:3"}).flags == 3
    assert Tracer.extract({TRACE_HEADER: "aaaa:bbbb:0:zz"}) is None
    with downstream.span("kept", headers={TRACE_HEADER: "aaaa:bbbb:0:1"}):
        pass
    assert len(downstream.finished_spans()) == 1


def test_sampled_context_header_keeps_flags():
    t = Tracer(enabled=True)
    with t.span("parent") as s:
        assert s.context_header().endswith(":1")


def test_traces_export_filters():
    """/traces query params: operation substring, since_us floor, limit
    keeps the N most recent spans."""
    import time as _time

    t = Tracer("filt", enabled=True)
    with t.span("alpha.op"):
        pass
    with t.span("beta.op"):
        pass
    _time.sleep(0.002)  # distinct start_us for the since_us cutoff
    with t.span("alpha.other"):
        pass
    spans = t.finished_spans()

    def ops(out):
        return [s["operationName"] for tr in out["data"] for s in tr["spans"]]

    assert sorted(ops(t.export_jaeger(operation="alpha"))) == [
        "alpha.op", "alpha.other"
    ]
    assert ops(t.export_jaeger(operation="nothing")) == []
    assert ops(t.export_jaeger(limit=1)) == ["alpha.other"]
    cutoff = spans[-1].start_us
    assert "beta.op" not in ops(t.export_jaeger(since_us=cutoff))
    # no filters = everything (back compat)
    assert len(ops(t.export_jaeger())) == 3


def test_traces_route_query_params():
    """The engine's /traces route parses the query string into filters."""
    import asyncio

    from seldon_core_tpu.http_server import Request

    init_tracer("route-test", enabled=True)
    tracer = get_tracer()
    with tracer.span("keep.me"):
        pass
    with tracer.span("drop.me"):
        pass
    spec = default_predictor(
        PredictorSpec.from_dict(
            {"name": "p", "graph": {"name": "m",
                                    "implementation": "SIMPLE_MODEL"}}
        )
    )
    app = EngineApp(spec)
    handler = app.rest_app().routes["/traces"]
    resp = asyncio.run(
        handler(Request("GET", "/traces", "operation=keep&limit=10", {}, b""))
    )
    out = json.loads(resp.body)
    ops = [s["operationName"] for tr in out["data"] for s in tr["spans"]]
    assert ops == ["keep.me"]
    init_tracer(enabled=False)


def test_probabilistic_sampling_gates_root_spans(monkeypatch):
    monkeypatch.setenv("TRACING", "1")
    monkeypatch.delenv("JAEGER_AGENT_HOST", raising=False)
    monkeypatch.setenv("JAEGER_SAMPLER_TYPE", "probabilistic")
    monkeypatch.setenv("JAEGER_SAMPLER_PARAM", "0.0")
    tracer = init_tracer()
    for _ in range(20):
        with tracer.span("never-sampled"):
            pass
    assert tracer.finished_spans() == []
    monkeypatch.setenv("JAEGER_SAMPLER_PARAM", "1.0")
    tracer = init_tracer()
    with tracer.span("always-sampled"):
        pass
    assert len(tracer.finished_spans()) == 1
    init_tracer(enabled=False)


# -- wall_us: the monotonic-anchored wall clock (seldon-lint wall-clock
# rule). Regression tests for the PR-8 fixes: span/flight-recorder
# timestamps must be derived from time.monotonic() via the process
# anchor, so an NTP step can never disorder spans or corrupt intervals.


def test_wall_us_ignores_wall_clock_steps(monkeypatch):
    """A backwards wall-clock step between two events must not reorder
    their anchored timestamps (the old code stamped raw time.time())."""
    a = tracing.wall_us()
    monkeypatch.setattr(tracing.time, "time", lambda: 0.0)  # epoch jump
    b = tracing.wall_us()
    assert b >= a  # derived from monotonic: unaffected by the step


def test_wall_us_places_past_monotonic_readings():
    m0 = tracing.time.monotonic()
    now = tracing.wall_us()
    past = tracing.wall_us(m0)
    assert past <= now
    # the offset between the readings matches the monotonic gap (~0)
    assert now - past < 1_000_000


def test_span_start_us_survives_wall_step(monkeypatch):
    tracer = Tracer(enabled=True)
    with tracer.span("first"):
        pass
    monkeypatch.setattr(tracing.time, "time", lambda: 0.0)
    with tracer.span("second"):
        pass
    first, second = tracer.finished_spans()[-2:]
    assert second.start_us >= first.start_us


def test_flight_recorder_t_us_survives_wall_step(monkeypatch):
    """flight_report diffs t_us between records: ordering must follow
    seq even when the wall clock steps backwards mid-run."""
    from seldon_core_tpu.serving import flightrecorder as fr

    rec = fr.FlightRecorder(capacity=4)
    rec.record({"type": "poll"})
    monkeypatch.setattr(tracing.time, "time", lambda: 0.0)
    rec.record({"type": "poll"})
    entries = rec.snapshot()
    assert entries[1]["seq"] == entries[0]["seq"] + 1
    assert entries[1]["t_us"] >= entries[0]["t_us"]


# -- the host's account of a thread: Heartbeat, HostClock ----------------------


class MadeUpTime:
    """A clock the test moves, and a sleeper that oversleeps by ``late``."""

    def __init__(self, t):
        self.t, self.late = t, 0.0

    def clock(self):
        return self.t

    def sleep(self, seconds):
        self.t += seconds + self.late


def no_proc_clock(made_up, beat):
    """A host clock on a machine whose ``/proc`` gives neither file, its
    thread's CPU clock standing still."""
    return tracing.HostClock(beat=beat, schedstat="/nonexistent/schedstat",
                             stat="/nonexistent/stat", clock=made_up.clock,
                             cpu_clock=lambda: 7.0)


def laps_but_gc(host):
    """A lap without ``gc_s``: the process's collector runs when it will."""
    lap = host.lap()
    lap.pop("gc_s", None)
    return lap


def test_a_late_beat_is_the_next_laps_maximum_and_the_one_after_reads_zero():
    at = MadeUpTime(1000.012)
    # a period the clock's floats hold exactly
    beat = tracing.Heartbeat(period=0.0625, clock=at.clock, sleep=at.sleep)
    host = no_proc_clock(at, beat)
    host.start()
    assert laps_but_gc(host) == {"cpu_s": 0.0}     # no beat yet: left out
    beat.beat()                             # on time, at the next multiple
    assert at.t == 1000.0625
    at.late = 0.4
    beat.beat()                             # due at 1000.125, woke at 1000.525
    at.late = 0.0
    beat.beat()                             # the next multiple: 1000.5625
    beat.beat()
    assert at.t == 1000.625
    assert laps_but_gc(host) == {"cpu_s": 0.0, "beat_late_s": pytest.approx(0.4)}
    beat.beat()
    assert laps_but_gc(host) == {"cpu_s": 0.0,
                                 "beat_late_s": pytest.approx(0.0, abs=1e-9)}
    # a beat overdue and not yet noted when the lap is taken counts as late
    # as it is by then: the taker may run before the beat it waited with
    at.t += 0.3
    assert laps_but_gc(host) == {"cpu_s": 0.0, "beat_late_s": pytest.approx(0.3)}
    host.stop()


def test_the_heartbeat_thread_beats_stops_and_starts_once():
    beat = tracing.Heartbeat(period=0.01)
    beat.start()
    thread = beat._thread
    beat.start()                            # a second start is the same thread
    assert beat._thread is thread and thread.daemon
    time.sleep(0.05)
    late = beat.take(time.monotonic())
    assert late is not None and 0.0 <= late < 5.0
    beat.stop()
    assert not thread.is_alive()
    assert beat.take(time.monotonic()) is None


def test_host_clock_differences_made_up_proc_text(tmp_path):
    sched, stat = tmp_path / "schedstat", tmp_path / "stat"
    sched.write_text("1000000000 500000000 7\n")
    stat.write_text("cpu  100 0 50 800 50 0 0 0 0 0\ncpu0 100 0 50 800 50 0 0 0 0 0\n")
    at = MadeUpTime(5.0)
    cpu = iter([3.0, 3.03, 3.03, 3.03, 3.03])
    beat = tracing.Heartbeat(period=0.0625, clock=at.clock, sleep=at.sleep)
    host = tracing.HostClock(beat=beat, schedstat=str(sched), stat=str(stat),
                             clock=at.clock, cpu_clock=lambda: next(cpu))
    host.start()
    # the files are the heartbeat's to read, at every beat: the owner's lap
    # makes no system call
    assert beat.on_beat == host.sample
    # 30 ms on a core, 2.5 s runnable and not run; the machine's 200 ticks:
    # 20 idle, 10 in iowait
    sched.write_text("1030000000 3000000000 9\n")
    stat.write_text("cpu  250 0 70 820 60 0 0 0 0 0\ncpu0 250 0 70 820 60 0 0 0 0 0\n")
    # not yet sampled: the lap sees the files as the last beat left them
    assert laps_but_gc(host) == {"cpu_s": pytest.approx(0.03), "runq_s": 0.0}
    beat.beat()
    assert laps_but_gc(host) == {"cpu_s": 0.0, "runq_s": pytest.approx(2.5),
                          "busy_share": pytest.approx(1.0 - 30 / 200),
                          "beat_late_s": pytest.approx(0.0, abs=1e-9)}
    # laps lie end to end: nothing moved since, and no tick of the
    # machine's fell between the samples, so no share of it can be given
    beat.beat()
    assert laps_but_gc(host) == {"cpu_s": 0.0, "runq_s": 0.0,
                          "beat_late_s": pytest.approx(0.0, abs=1e-9)}
    # text in another form leaves its fields out
    sched.write_text("no numbers here\n")
    stat.write_text("intr 1 2 3\n")
    beat.beat()
    assert laps_but_gc(host) == {"cpu_s": 0.0,
                          "beat_late_s": pytest.approx(0.0, abs=1e-9)}
    host.stop()
    assert host._fds == [None, None] and beat.on_beat is None


def test_a_proc_stat_that_counts_nothing_is_not_read_again(tmp_path):
    """A sandbox kernel's ``/proc/stat`` stands at zero (the machine the
    chips are on, PR 53): the descriptor is closed at the start, and with
    no file left to read the heartbeat is given nothing to do."""
    stat = tmp_path / "stat"
    stat.write_text("cpu  0 0 0 0 0 0 0 0 0 0\ncpu0 0 0 0 0 0 0 0 0 0 0\n")
    at = MadeUpTime(5.0)
    beat = tracing.Heartbeat(clock=at.clock, sleep=at.sleep)
    host = tracing.HostClock(beat=beat, schedstat="/nonexistent/schedstat",
                             stat=str(stat), clock=at.clock,
                             cpu_clock=lambda: 1.0)
    host.start()
    assert host._fds == [None, None] and beat.on_beat is None
    stat.write_text("cpu  9 9 9 9 9 9 9 9 0 0\n")
    host.sample()
    assert laps_but_gc(host) == {"cpu_s": 0.0}
    host.stop()


def test_host_clock_on_this_machine():
    host = tracing.HostClock()
    seen = {}

    def owner():                            # thread-self is the opener's
        host.start()
        t0 = time.monotonic()
        while time.monotonic() - t0 < 0.1:
            pass
        seen.update(host.lap(), stretch=time.monotonic() - t0)
        host.stop()
    thread = threading.Thread(target=owner)
    thread.start()
    thread.join(timeout=30)
    assert not thread.is_alive()
    # the thread spun: on a core or waiting for one, never more than the
    # stretch (the kernel adds to the wait at its ticks: a tick of slack)
    assert 0.0 < seen["cpu_s"] <= seen["stretch"]
    assert ("runq_s" in seen) == os.path.exists("/proc/thread-self/schedstat")
    assert seen["cpu_s"] + seen.get("runq_s", 0.0) <= seen["stretch"] + 0.02
    assert 0.0 <= seen.get("busy_share", 0.0) <= 1.0
    assert "beat_late_s" not in seen        # its heartbeat was never started


def test_collector_seconds_land_on_the_lap_and_are_left_out_at_zero():
    import gc

    at = MadeUpTime(0.0)
    host = no_proc_clock(at, tracing.Heartbeat(clock=at.clock, sleep=at.sleep))
    was = gc.isenabled()
    gc.disable()
    try:
        host.start()
        assert "gc_s" not in host.lap()
        gc.collect()
        assert host.lap()["gc_s"] > 0.0
        assert "gc_s" not in host.lap()
    finally:
        if was:
            gc.enable()
        host.stop()


# -- the compile log -----------------------------------------------------------


@pytest.fixture
def compile_log():
    import jax.monitoring

    log = tracing.CompileLog(ring=8)
    raw = []

    def listen(event, secs, **kw):
        raw.append((event.rsplit("/", 1)[-1], kw.get("fun_name"), secs))
    jax.monitoring.register_event_duration_secs_listener(listen)
    log.install()
    log.install()
    log.raw = raw
    try:
        yield log
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
        jax.monitoring.unregister_event_duration_listener(log._on_duration)
        jax.monitoring.unregister_event_listener(log._on_event)


def fresh_function():
    """A function no cache has seen: a constant of its own in its HLO."""
    import jax.numpy as jnp

    c = random.random()

    def my_fn(x):
        return jnp.sin(x) * c + x
    return my_fn


def test_install_twice_registers_once(compile_log):
    from jax._src import monitoring

    assert monitoring.get_event_duration_listeners().count(
        compile_log._on_duration) == 1
    assert monitoring.get_event_listeners().count(compile_log._on_event) == 1
    assert compile_log.report()["stages"] == {}     # stage "load", nothing yet


def test_a_first_call_leaves_a_trace_a_lower_and_a_backend_event(compile_log):
    import jax
    import jax.numpy as jnp

    x = jnp.ones(4)
    f = jax.jit(fresh_function())
    compile_log.stage("warm")
    f(x).block_until_ready()
    f(x).block_until_ready()                # a second call compiles nothing
    compile_log.stage("load")
    rep = compile_log.report()
    warm = rep["stages"]["warm"]
    assert warm["n"] == 1 and warm["cache_hits"] == 0 and warm["cache_misses"] == 1
    assert min(warm["trace_s"], warm["lower_s"], warm["backend_s"]) > 0.0
    mine, = (e for e in rep["executables"] if e["stage"] == "warm")
    assert mine == dict(warm, stage="warm", name="jit_my_fn")
    assert rep["serve_events"] == []        # the ring is stage serve's alone
    # nested trace events (sin, multiply, add under my_fn) are not summed
    # twice: the log's trace seconds are my_fn's own event, which holds them
    traces = [(name, s) for kind, name, s in compile_log.raw
              if kind == "jaxpr_trace_duration"]
    assert {"sin", "my_fn"} <= {name for name, _ in traces}
    assert warm["trace_s"] == dict(traces)["my_fn"]
    assert warm["trace_s"] < sum(s for _, s in traces)


def test_the_cache_says_miss_then_hit_and_serve_events_fill_the_ring(compile_log):
    import jax
    import jax.numpy as jnp

    # conftest.py turns the persistent cache on for every executable
    assert jax.config.jax_compilation_cache_dir
    x = jnp.ones(4)
    f = jax.jit(fresh_function())
    compile_log.stage("serve")
    cursor = compile_log.since()[0]
    assert cursor == 0 and compile_log.since(cursor) == (0, ())
    t0 = time.monotonic()
    f(x).block_until_ready()
    jax.clear_caches()
    f(x).block_until_ready()
    t1 = time.monotonic()
    compile_log.stage("load")
    total, events = compile_log.since(cursor)
    assert total == compile_log.serve_total == 6
    assert [(e["name"], e["kind"], e["cache"]) for e in events] == [
        ("jit_my_fn", "trace", None), ("jit_my_fn", "lower", None),
        ("jit_my_fn", "backend", "miss"),
        ("jit_my_fn", "trace", None), ("jit_my_fn", "lower", None),
        ("jit_my_fn", "backend", "hit")]
    stamps = [e["t"] for e in events]
    assert stamps == sorted(stamps) and t0 < stamps[0] and stamps[-1] < t1
    assert compile_log.since(total) == (6, ())
    assert compile_log.since(4)[1] == events[4:]
    serve = compile_log.report()["stages"]["serve"]
    assert (serve["n"], serve["cache_hits"], serve["cache_misses"]) == (2, 1, 1)
    # a ring of 8: four more compiles and the oldest events are gone, the
    # count is not
    compile_log.stage("serve")
    for _ in range(4):
        jax.jit(fresh_function())(x).block_until_ready()
    compile_log.stage("load")
    total, events = compile_log.since(0)
    assert total == 18 and len(events) == 8
    assert events == compile_log.report()["serve_events"]
    assert json.loads(json.dumps(compile_log.report())) == compile_log.report()


class SourceWithoutALog:
    def capture_counters(self):
        return {}

    def capture_requests(self):
        return []


class SourceWithALog(SourceWithoutALog):
    def capture_compiles(self):
        return {"stages": {}, "executables": [], "serve_events": []}


@pytest.mark.parametrize("source", [SourceWithALog(), SourceWithoutALog()],
                         ids=["with_a_log", "without"])
def test_the_capture_report_carries_the_compile_log_where_the_source_has_one(source):
    control = tracing.CaptureControl()
    control.register(source)
    control.start()
    report = control.stop()
    if isinstance(source, SourceWithALog):
        assert report["compiles"] == source.capture_compiles()
    else:
        assert "compiles" not in report
