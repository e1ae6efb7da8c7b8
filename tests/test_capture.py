"""The capture control, the request timeline and the scheduler loop's phases
(docs/operate.md "Observability"), and the benchmark's readers of them.

The contracts: (1) every completed request leaves one timeline entry whose
stamps are ordered, whatever path admitted it; (2) the loop's phase seconds
account for its wall time; (3) a capture changes no token and compiles
nothing; (4) ``start_capture`` / ``stop_capture`` are a strict pair; (5) a
streamed request's scheduler and front spans share one trace id; (6) each
per-layer reader of the report computes what its file says, under both
shapes of a traced run, and reads nothing where there is no report.
"""

import asyncio
import json
import os

import pytest

from seldon_core_tpu import tracing
from seldon_core_tpu.http_server import Request
from seldon_core_tpu.models.llm import DecoderLM
from seldon_core_tpu.serving.continuous import ContinuousBatcher
from test_profiler import jit_cache_size

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

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

PROMPTS = [[3, 17, 42, 99, 7], [1, 2, 3], [9, 8, 7, 6], [5, 5, 5, 5, 5, 5]]
BUDGETS = [20, 7, 13, 9]
LONG_PROMPTS = [list(range(1, 30)), list(range(40, 60))]   # bucket 32

SCHEDULER_STAMPS = ("submit_t", "admit_t", "decode_start_t",
                    "first_dispatch_t", "first_tok_t", "done_t")


@pytest.fixture(scope="module")
def model_and_params():
    model = DecoderLM(**CFG)
    return model, model.init_params(0)


def make_batcher(model_and_params, **kw):
    model, params = model_and_params
    kw.setdefault("slots", 4)
    kw.setdefault("max_seq", 64)
    kw.setdefault("prefill_buckets", (8, 16, 32))
    kw.setdefault("steps_per_poll", 2)
    return ContinuousBatcher(model, params, **kw)


def run_batch(b, prompts=PROMPTS, temperature=0.0):
    futures = [
        b.submit(p, max_new_tokens=m, temperature=temperature, seed=11 + i)
        for i, (p, m) in enumerate(zip(prompts, BUDGETS))
    ]
    return [f.result(timeout=120) for f in futures]


def run_in_one_wave(b, prompts):
    """All prompts queued before the scheduler's first poll, so one wave
    admits them together (``_admit_many`` for four of one bucket)."""
    start, b.start = b.start, lambda: None
    try:
        futures = [b.submit(p, max_new_tokens=m)
                   for p, m in zip(prompts, BUDGETS)]
    finally:
        b.start = start
    b.start()
    return [f.result(timeout=120) for f in futures]


# -- the request timeline ------------------------------------------------------

ADMISSIONS = {
    "plain": ({}, PROMPTS, run_batch),
    "fused": ({"fused_steps_per_dispatch": 8}, PROMPTS, run_batch),
    "chunked": ({"prefill_chunk": 16}, LONG_PROMPTS, run_batch),
    "batched": ({}, [p + [1] * (5 - len(p)) for p in PROMPTS], run_in_one_wave),
}


@pytest.mark.parametrize("admission", sorted(ADMISSIONS))
def test_timeline_stamps_are_ordered(model_and_params, admission):
    kw, prompts, run = ADMISSIONS[admission]
    b = make_batcher(model_and_params, **kw)
    try:
        outs = run(b, prompts)
        rows = b.capture_requests()
        if admission == "chunked":
            assert b.stats["prefill_chunks"] >= 2 * len(prompts)
        if admission == "batched":
            assert b.stats["prefill_steps"] == 1    # one forward for the four
    finally:
        b.close()
    assert len(rows) == len(prompts) == b.stats["slo_samples"]
    by_len = {len(p): (p, out) for p, out in zip(prompts, outs)}
    for row in rows:
        stamps = [row[k] for k in SCHEDULER_STAMPS]
        assert all(stamps), row
        assert stamps == sorted(stamps), row
        if admission != "batched":      # there every prompt has one length
            prompt, out = by_len[row["prompt_len"]]
            assert row["tokens"] == len(out) - len(prompt)
        assert row["bucket"] >= row["prompt_len"]
        assert row["cache_hit_tokens"] == 0
        # no front served these: its stamps stay unset
        assert row["received_t"] == row["first_write_t"] == row["done_write_t"] == 0.0
    assert len({row["id"] for row in rows}) == len(rows)


# -- the loop's phases ----------------------------------------------------------


@pytest.mark.parametrize("kw", [{}, {"fused_steps_per_dispatch": 8}],
                         ids=["plain", "fused"])
def test_loop_phases_account_for_the_wall_time(model_and_params, kw):
    control = tracing.CaptureControl()
    b = make_batcher(model_and_params, **kw)
    control.register(b)
    try:
        run_batch(b)            # the scheduler thread runs from here on
        started = control.start()
        outs = run_batch(b)
        report = control.stop()
    finally:
        b.close()
    loop = report["loop"]
    phases = {k: v for k, v in loop.items()
              if k.endswith("_s") and k not in ("wall_s", "burst_read_lag_s_sum")}
    assert set(phases) == {"admit_s", "chunks_s", "dispatch_s", "read_wait_s",
                           "credit_s", "idle_s", "other_s"}
    assert all(v >= 0.0 for v in phases.values())
    assert sum(phases.values()) == pytest.approx(loop["wall_s"], rel=0.05)
    assert loop["wall_s"] == pytest.approx(report["t1"] - report["t0"], rel=0.05)
    assert report["t0"] == started["t"] == report["clock"]["monotonic_s"]
    assert report["clock"]["unix_ns"] > 0
    assert loop["polls"] > 0 and loop["bursts"] > 0
    assert loop["burst_read_lag_s_sum"] > 0.0
    assert loop["dispatch_s"] > 0.0 and loop["read_wait_s"] > 0.0
    # the batcher's counters ride along as differences over the capture
    assert report["counters"]["finished"] == len(outs)
    assert report["counters"]["tokens"] == sum(BUDGETS)
    in_capture = [r for r in report["requests"] if r["submit_t"] >= report["t0"]]
    assert len(in_capture) == len(outs)
    assert len(report["requests"]) == 2 * len(outs)     # the ring holds both


@pytest.mark.parametrize("reenter", [True, False],
                         ids=["reentered", "lost_without"])
def test_phase_in_progress_at_capture_start_is_in_the_trace(tmp_path, reenter):
    """A span entered before the profiler started is never recorded, and a
    ``read_wait`` can be most of a second: a device gap under it would be
    ``no_host_span``. ``start_capture`` has the phase in progress re-entered."""
    import threading

    from jax.profiler import ProfileData

    class Source:
        def __init__(self):
            self.clock = tracing.PhaseClock({}, "batcher", "loop", ("read_wait",))

        def capture_counters(self):
            return {"loop": self.clock.read()}

        def capture_requests(self):
            return []

        def capture_started(self):
            if reenter:
                self.clock.reenter()

    source = Source()
    waiting, go = threading.Event(), threading.Event()

    def owner():
        source.clock.start()
        source.clock.to("read_wait")    # before the profiler records
        waiting.set()
        go.wait(10)
        source.clock.stop()

    thread = threading.Thread(target=owner)
    thread.start()
    waiting.wait(5)
    control = tracing.CaptureControl()
    control.register(source)
    control.start(str(tmp_path))
    go.set()
    thread.join()
    control.stop()
    found = [os.path.join(d, f) for d, _s, fs in os.walk(tmp_path)
             for f in fs if f.endswith(".xplane.pb")]
    names = [e.name for plane in ProfileData.from_file(found[0]).planes
             for line in plane.lines for e in line.events
             if e.name.startswith("batcher.")]
    assert names == (["batcher.read_wait"] if reenter else []) + ["batcher.other"]


# -- a capture changes nothing ---------------------------------------------------


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "seeded"])
def test_capture_on_off_byte_identical_and_no_new_executables(
        model_and_params, tmp_path, temperature):
    def run(b):
        if not temperature:
            return run_batch(b)
        # one at a time: which lanes share a burst never depends on timing
        return [b.submit(p, max_new_tokens=m, temperature=temperature,
                         seed=11 + i).result(timeout=120)
                for i, (p, m) in enumerate(zip(PROMPTS, BUDGETS))]

    b_off = make_batcher(model_and_params, fused_steps_per_dispatch=8)
    try:
        ref = run(b_off)
        cache_ref = jit_cache_size(b_off)
    finally:
        b_off.close()
    control = tracing.CaptureControl()
    b_on = make_batcher(model_and_params, fused_steps_per_dispatch=8)
    control.register(b_on)
    try:
        control.start(str(tmp_path / "trace"))      # with the profiler
        got = run(b_on)
        report = control.stop()
        assert got == ref
        assert jit_cache_size(b_on) == cache_ref
    finally:
        b_on.close()
    assert report["loop"]["bursts"] > 0
    written = [f for _d, _s, fs in os.walk(tmp_path / "trace") for f in fs]
    assert any(f.endswith(".xplane.pb") for f in written)


# -- the control is a strict pair -------------------------------------------------


@pytest.mark.parametrize("case", ["second_start", "stop_without_start",
                                  "start_after_stop"])
def test_capture_control_is_a_strict_pair(case):
    control = tracing.CaptureControl()
    if case == "stop_without_start":
        with pytest.raises(tracing.CaptureError):
            control.stop()
        return
    control.start()
    if case == "second_start":
        with pytest.raises(tracing.CaptureError):
            control.start()
    report = control.stop()
    assert report["t1"] >= report["t0"] and report["requests"] == []
    if case == "start_after_stop":
        control.start()
        control.stop()
    with pytest.raises(tracing.CaptureError):
        control.stop()


def test_the_old_profiler_pair_is_gone():
    assert not hasattr(tracing, "start_device_profile")
    assert not hasattr(tracing, "stop_device_profile")


# -- one trace id from the front to the scheduler -----------------------------------


def test_sse_request_is_one_trace_with_front_and_scheduler_spans(tmp_path):
    from seldon_core_tpu.graph.service import EngineApp
    from seldon_core_tpu.graph.spec import PredictorSpec, default_predictor
    from seldon_core_tpu.servers.generateserver import GenerateServer

    (tmp_path / "jax_config.json").write_text(
        json.dumps({"family": "llm", "config": CFG}))
    server = GenerateServer(model_uri=str(tmp_path), slots=2, steps_per_poll=2,
                            attn_bucket=16)
    spec = default_predictor(PredictorSpec.from_dict(
        {"name": "p", "graph": {"name": "gen", "type": "MODEL"}}))
    app = EngineApp(spec, registry={"gen": server})
    tracing.init_tracer("capture-test", enabled=True)
    try:
        body = json.dumps({"jsonData": {"prompt_tokens": [[1, 2, 3, 4, 5]],
                                        "max_new_tokens": 6}}).encode()
        resp = asyncio.run(app.rest_app()._dispatch(Request(
            "POST", "/api/v0.1/generate", "",
            {"content-type": "application/json"}, body)))
        assert resp.status == 200
        events = [json.loads(chunk[len(b"data: "):]) for chunk in resp.iterator]
        assert events[-1]["done"] and len(events[-1]["tokens"]) == 11
        spans = tracing.get_tracer().finished_spans()
        by_op = {}
        for s in spans:
            by_op.setdefault(s.operation, []).append(s)
        root = by_op["generate_stream"][0]
        for op in ("gen.queue_wait", "gen.prefill", "gen.first_token_hold",
                   "front.first_write", "gen.decode"):
            assert op in by_op, sorted(by_op)
            assert all(s.trace_id == root.trace_id for s in by_op[op])
            assert all(s.parent_id == root.span_id for s in by_op[op])
        hold = by_op["gen.first_token_hold"][0]
        assert hold.tags["first_dispatch_ms"] >= 0.0
        assert hold.start_us <= by_op["front.first_write"][0].start_us
        # the same stamps in the ring, the front's among them
        row = server.batcher.capture_requests()[-1]
        order = [row[k] for k in ("received_t", *SCHEDULER_STAMPS)]
        assert all(order) and order == sorted(order), row
        assert row["first_write_t"] >= row["first_tok_t"]
        assert row["done_write_t"] >= row["done_t"]
    finally:
        tracing.init_tracer(enabled=False)
        if server.batcher:
            server.batcher.close()


# -- the benchmark's readers of the report --------------------------------------------

WINDOW = (100.0, 140.0)


def _request(submit_t, hold_s, front_s):
    """A timeline whose scheduler holds the first token ``hold_s`` and
    whose front adds ``front_s``, half before the submit, half after the
    first token."""
    first = submit_t + 0.05 + hold_s
    return {
        "id": int(submit_t * 10), "prompt_len": 7, "bucket": 8, "tokens": 4,
        "cache_hit_tokens": 0, "received_t": submit_t - front_s / 2,
        "submit_t": submit_t, "admit_t": submit_t + 0.04,
        "decode_start_t": submit_t + 0.05, "first_dispatch_t": submit_t + 0.06,
        "first_tok_t": first, "done_t": first + 0.5,
        "first_write_t": first + front_s / 2, "done_write_t": first + 0.51,
    }


def _run(shape):
    """A hand-built ``run`` as ``benchmark/run.py`` hands it to a reader:
    ``trace1`` captures inside the window, ``trace2`` after it."""
    before = _request(95.0, 1.0, 0.100)             # before the window opened
    in_window = [_request(101.0, 0.3, 0.004), _request(103.0, 0.5, 0.008)]
    if shape == "trace1":
        trace_window = (101.0, 105.0)
        requests = [before, *in_window]
    else:
        trace_window = (141.0, 145.0)
        # one more inside the window, and one submitted during the capture
        requests = [before, *in_window, _request(120.0, 0.7, 0.012),
                    _request(142.0, 0.9, 0.100)]
    program = None if shape == "no_report" else {
        "t0": trace_window[0], "t1": trace_window[1],
        "clock": {"monotonic_s": trace_window[0], "unix_ns": 1},
        "loop": {"admit_s": 0.2, "chunks_s": 0.0, "dispatch_s": 0.5,
                 "read_wait_s": 2.0, "credit_s": 0.2, "idle_s": 1.0,
                 "other_s": 0.1, "wall_s": 4.0, "polls": 30, "bursts": 20,
                 "burst_read_lag_s_sum": 6.0},
        "counters": {}, "requests": requests,
    }
    stop = {"t": trace_window[1], "stats": {}, "slo": []}
    if program is not None:
        stop["program"] = program
    return {
        "window": WINDOW, "trace_window": trace_window,
        "trace_counters": ({"t": trace_window[0], "stats": {}, "slo": []}, stop),
        "trace": None if shape == "no_report" else {
            "busy_s": 4.0, "window_s": 4.0, "modules": {
                "jit_prefill_one": {"runs": 3, "seconds": 0.3},
                "jit_prefill_many": {"runs": 1, "seconds": 0.1},
                "jit_fused_burst": {"runs": 30, "seconds": 3.5}}},
    }


READINGS = {
    # metric: (under --trace 1, under --trace 2)
    "first_token_hold_p50_ms": (400.0, 500.0),
    "front_span_p50_ms": (6.0, 8.0),
    "burst_read_lag_ms": (300.0, 300.0),
    "scheduler_host_share": (25.0, 25.0),
    "prefill_device_share": (10.0, 10.0),
}


@pytest.mark.parametrize("shape", ["trace1", "trace2", "no_report"])
@pytest.mark.parametrize("metric", sorted(READINGS))
def test_layer_reader_on_a_hand_built_run(metric, shape):
    from benchmark import manifest

    man = manifest.load(ROOT)
    assert metric in {m["name"] for m in man["per_layer"]}
    value = manifest.layer_reader(ROOT, man, metric)(_run(shape))
    if shape == "no_report":
        assert value is None
    else:
        assert value == pytest.approx(READINGS[metric][shape == "trace2"])
