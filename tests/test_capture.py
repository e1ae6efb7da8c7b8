"""The capture control, the request timeline and the scheduler loop's phases
(docs/operate.md "Observability"), and the benchmark's readers of them.

The contracts: (1) every completed request leaves one timeline entry whose
stamps are ordered, whatever path admitted it; (2) the loop's phase seconds
account for its wall time; (3) a capture changes no token and compiles
nothing; (4) ``start_capture`` / ``stop_capture`` are a strict pair; (5) a
streamed request's scheduler and front spans share one trace id; (6) each
per-layer reader of the report computes what its file says, under both
shapes of a traced run, and reads nothing where there is no report; (7) the
flight recorder's poll rows are spans of the scheduler thread's time: they
lie end to end, sum to the loop's clock, name the requests they admitted
and the bursts they read, and a held dispatch or burst shows as seconds in
one row; the ring changes no token, compiles nothing, and rides the
capture's report.
"""

import asyncio
import importlib.util
import itertools
import json
import os
import time

import numpy as np
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

SCHEDULER_STAMPS = ("submit_t", "admit_t", "insert_t", "decode_start_t",
                    "first_dispatch_t", "first_tok_t", "done_t")
PHASES = ("admit", "chunks", "dispatch", "read_wait", "credit", "idle", "other")


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


def run_one_at_a_time(b, temperature):
    """Which lanes share a burst never depends on timing."""
    return [b.submit(p, max_new_tokens=m, temperature=temperature,
                     seed=11 + i).result(timeout=120)
            for i, (p, m) in enumerate(zip(PROMPTS, BUDGETS))]


def poll_rows(b):
    return [r for r in b.capture_polls() if r["type"] == "poll"]


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
        polls = {r["poll"]: r for r in poll_rows(b)}
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
        # the request names the poll that activated its lane, and that
        # poll's row names the request
        assert row["id"] in polls[row["admit_poll"]]["admitted_ids"]
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
    assert report["t0"] == started["t"] and "clock" not in report
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
    report = control.stop()
    found = [os.path.join(d, f) for d, _s, fs in os.walk(tmp_path)
             for f in fs if f.endswith(".xplane.pb")]
    events = [e.name for plane in ProfileData.from_file(found[0]).planes
              for line in plane.lines for e in line.events]
    names = [n for n in events if n.startswith("batcher.")]
    assert names == (["batcher.read_wait"] if reenter else []) + ["batcher.other"]
    # the trace's one anchor to the report's clock: the span that opens it
    # is named by the monotonic reading it began at
    mark, = (n for n in events if n.startswith(tracing.CAPTURE_CLOCK_SPAN))
    assert report["t0"] <= float(mark[len(tracing.CAPTURE_CLOCK_SPAN):]) <= report["t1"]


# -- a capture changes nothing ---------------------------------------------------


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "seeded"])
def test_capture_on_off_byte_identical_and_no_new_executables(
        model_and_params, tmp_path, temperature):
    def run(b):
        # greedy: all four queued before the first poll, so that which
        # prompts share a prefill (and so which executables exist) does not
        # depend on how fast the submits ran (under a loaded machine
        # ``run_batch`` compiled 4 executables for one batcher and 5 for the
        # other: PR 55, on the parent's tree as on the change's)
        return (run_one_at_a_time(b, temperature) if temperature
                else run_in_one_wave(b, PROMPTS))

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


# -- the poll record carries the loop's clock -----------------------------------------


@pytest.mark.parametrize("kw", [{}, {"fused_steps_per_dispatch": 8},
                                {"prefill_chunk": 16}],
                         ids=["plain", "fused", "chunked"])
def test_poll_rows_lie_end_to_end_and_sum_to_the_clock(model_and_params, kw):
    prompts = LONG_PROMPTS if "prefill_chunk" in kw else PROMPTS
    b = make_batcher(model_and_params, **kw)
    try:
        run_batch(b, prompts)
        time.sleep(0.2)     # idle iterations write no row: the next one takes them
        run_batch(b, prompts)
    finally:
        b.close()
    rows = poll_rows(b)
    assert len(rows) > 4
    assert [r["seq"] for r in rows] == sorted(r["seq"] for r in rows)
    # what the stopped loop left after its last row is one more lap
    t_end, rest = b._clock.lap()
    total = dict.fromkeys(PHASES, 0.0)
    for row, t_next in zip(rows, [r["t"] for r in rows[1:]] + [t_end]):
        assert set(row["phase_s"]) <= set(PHASES)
        assert all(v > 0.0 for v in row["phase_s"].values())     # zeros left out
        assert row["t"] + sum(row["phase_s"].values()) == pytest.approx(
            t_next, abs=1e-6)
        for phase, v in row["phase_s"].items():
            total[phase] += v
    for phase, v in rest.items():
        total[phase] += v
    clock = b._clock.read()
    for phase in PHASES:
        assert total[phase] == pytest.approx(clock[f"{phase}_s"], rel=1e-9,
                                             abs=1e-9)
    # the idle stretch went to ONE row, the first of the second batch
    after_idle = [r for r in rows if r["phase_s"].get("idle", 0.0) >= 0.15]
    assert len(after_idle) == 1
    assert after_idle[0].get("admitted") or after_idle[0]["prefill_chunks"]
    assert all("chunks" in r["phase_s"] for r in rows if r.get("prefill_chunks"))


def test_poll_rows_carry_the_hosts_account_of_their_stretch(model_and_params):
    """``host`` lies end to end as ``phase_s`` does: the scheduler thread's
    seconds on a core and waiting for one never exceed the row's stretch
    (the kernel adds to both at its ticks: a tick of slack), nor their sum
    the rows' span; the heartbeat lives as long as the scheduler thread."""
    b = make_batcher(model_and_params)
    try:
        run_batch(b)
        time.sleep(0.2)
        run_batch(b)
        beat = b._host.beat._thread
        assert beat.is_alive() and beat.daemon
    finally:
        b.close()
    assert not beat.is_alive() and b._host._fds == [None, None]
    rows = poll_rows(b)
    assert len(rows) > 4 and all("host" in r for r in rows)
    proc = os.path.exists("/proc/thread-self/schedstat")
    on_thread = 0.0
    for row in rows:
        host = row["host"]
        assert set(host) <= {"cpu_s", "runq_s", "busy_share", "beat_late_s", "gc_s"}
        assert "cpu_s" in host and ("runq_s" in host) == proc
        assert 0.0 <= host["beat_late_s"] < 60.0
        assert host.get("gc_s", 1.0) > 0.0              # left out at zero
        assert 0.0 <= host.get("busy_share", 0.0) <= 1.0
        stretch = sum(row["phase_s"].values())
        on_core_or_waiting = host["cpu_s"] + host.get("runq_s", 0.0)
        assert 0.0 <= on_core_or_waiting <= stretch + 0.02
        on_thread += on_core_or_waiting
    span = rows[-1]["t"] + sum(rows[-1]["phase_s"].values()) - rows[0]["t"]
    assert 0.0 < on_thread <= span + 0.02
    # the idle stretch between the batches went to one row: the thread
    # slept through it, on no core and in no run queue
    idled, = (r for r in rows if r["phase_s"].get("idle", 0.0) >= 0.15)
    assert idled["host"]["cpu_s"] + idled["host"].get("runq_s", 0.0) < 0.1
    assert json.loads(json.dumps(rows)) == rows


def test_a_ring_set_to_zero_starts_no_heartbeat(model_and_params):
    b = make_batcher(model_and_params, flight_recorder_capacity=0)
    try:
        run_batch(b)
        assert b._host is None
        assert b._host is None and b.capture_polls() == []
    finally:
        b.close()


@pytest.fixture
def serving_stage():
    """The process's compile log installed, as ``GenerateServer.load``
    installs it, and put back to ``load`` afterwards so that what later
    tests compile lands on no row."""
    tracing.install_compile_log()
    try:
        yield tracing.compile_stage
    finally:
        tracing.compile_stage("load")


def test_a_length_warm_was_not_told_of_compiles_on_the_next_row(
        model_and_params, serving_stage):
    b = make_batcher(model_and_params)
    control = tracing.CaptureControl()
    control.register(b)
    try:
        serving_stage("warm")
        b.warm(prompt_lens=(len(PROMPTS[0]),), max_new_tokens=BUDGETS[0])
        serving_stage("serve")
        # a process's first admission compiles one eager conversion that
        # warm() does not reach (milliseconds); from then on a warmed
        # length compiles nothing
        b.submit(PROMPTS[0], max_new_tokens=BUDGETS[0]).result(timeout=120)
        seen = b.stats["compiles_after_ready"]
        rows_before = len(poll_rows(b))
        b.submit(PROMPTS[0], max_new_tokens=BUDGETS[0]).result(timeout=120)
        assert b.stats["compiles_after_ready"] == seen
        assert not any("compiles" in r for r in poll_rows(b)[rows_before:])
        # bucket 32 was never warmed: its prefill and insert compile while
        # the request waits, in the admit turn of the row that names them
        control.start()
        rows_before = len(poll_rows(b))
        seconds = b.stats["compile_after_ready_s"]
        b.submit(LONG_PROMPTS[0], max_new_tokens=4).result(timeout=120)
        report = control.stop()
    finally:
        b.close()
    row, = (r for r in poll_rows(b)[rows_before:] if "compiles" in r)
    assert row["admitted"] == 1
    backends = [e for e in row["compiles"] if e["kind"] == "backend"]
    assert {"jit_prefill_one", "jit_insert"} <= {e["name"] for e in backends}
    assert all(e["cache"] in ("hit", "miss") for e in backends)
    assert {e["kind"] for e in row["compiles"]} == {"trace", "lower", "backend"}
    assert b.stats["compiles_after_ready"] == seen + len(backends)
    spent = sum(e["s"] for e in row["compiles"])
    assert b.stats["compile_after_ready_s"] == pytest.approx(seconds + spent)
    # the thread compiled inside its admit phase, inside the row's stretch
    assert spent <= row["phase_s"]["admit"]
    assert all(row["t"] <= e["t"] <= row["t"] + sum(row["phase_s"].values())
               for e in row["compiles"])
    # the report: the log absolute, the counters differenced
    log = report["compiles"]
    assert set(log) == {"stages", "executables", "serve_events"}
    assert [e for e in log["serve_events"] if e in row["compiles"]] == row["compiles"]
    assert log["stages"]["serve"]["n"] >= seen + len(backends)
    assert log["stages"]["warm"]["n"] > 0
    assert report["counters"]["compiles_after_ready"] == len(backends)
    assert any(e["stage"] == "serve" and e["name"] == "jit_prefill_one"
               for e in log["executables"])


def hold(fn, seconds):
    """``fn`` with its first call held ``seconds`` before it goes out: a
    dispatch that blocks."""
    calls = itertools.count()

    def held(*args):
        if next(calls) == 0:
            time.sleep(seconds)
        return fn(*args)
    return held


def test_a_held_insert_is_one_row_that_names_its_request(model_and_params):
    b = make_batcher(model_and_params)
    try:
        # one request alone: _admit's own prefill and insert are compiled
        b.submit([7, 8, 9], max_new_tokens=4).result(timeout=120)
        warm = len(poll_rows(b))    # those rows hold the compiles
        b._insert_fn = hold(b._insert_fn, 0.3)
        b.submit([4, 5, 6], max_new_tokens=4).result(timeout=120)
        request = b.capture_requests()[-1]
    finally:
        b.close()
    slow = [r for r in poll_rows(b)[warm:]
            if r["phase_s"].get("admit", 0.0) >= 0.3]
    assert len(slow) == 1
    row = slow[0]
    assert row["admitted"] == 1 and row["admitted_ids"] == [request["id"]]
    assert request["admit_poll"] == row["poll"]
    # the seconds lie in the insert's dispatch, not the prefill's
    assert request["decode_start_t"] - request["insert_t"] >= 0.3
    assert request["insert_t"] - request["admit_t"] < 0.3
    end = row["t"] + sum(row["phase_s"].values())
    assert row["t"] <= request["admit_t"] <= request["decode_start_t"] <= end
    assert row["dispatched_t"] >= request["decode_start_t"]


class HeldTokens:
    """A burst's tokens as a test double holds them: not ready, and a read
    of them blocks, until ``until``."""

    def __init__(self, array, until):
        self.array, self.until = array, until

    def is_ready(self):
        return time.monotonic() >= self.until

    def copy_to_host_async(self):
        pass

    def __array__(self, dtype=None, copy=None):
        time.sleep(max(0.0, self.until - time.monotonic()))
        return np.asarray(self.array)


def test_a_held_burst_is_read_wait_in_one_row_and_the_device_is_not_drained(
        model_and_params, monkeypatch):
    from seldon_core_tpu.serving import continuous

    held = []       # (array, until): the double's word on what the device holds
    is_ready = continuous._is_ready
    monkeypatch.setattr(continuous, "_is_ready", lambda a: is_ready(a) and not any(
        a is array and time.monotonic() < until for array, until in held))
    b = make_batcher(model_and_params)
    try:
        run_batch(b)
        warm = len(poll_rows(b))    # those rows hold the compiles
        time.sleep(0.2)
        burst_fn, calls = b._burst_fn, itertools.count()

        def second_burst_held(*args):
            toks, cur_tok, *rest = burst_fn(*args)
            if next(calls) == 1:
                until = time.monotonic() + 0.35
                held.append((cur_tok, until))       # what a burst leaves newest
                toks = HeldTokens(toks, until)
            return (toks, cur_tok, *rest)

        b._burst_fn = second_burst_held
        b.submit([4, 5, 6], max_new_tokens=12).result(timeout=120)
    finally:
        b.close()
    # once the loop has stopped: a request resolves in its burst's credit,
    # before the poll's record claims the burst
    unclaimed = list(b._row_bursts)
    rows = poll_rows(b)
    # the first poll after an idle stretch finds the device drained
    after_idle = [r for r in rows if r["phase_s"].get("idle", 0.0) >= 0.15]
    assert len(after_idle) == 1 and after_idle[0]["drained"] is True
    slow = [r for r in rows[warm:]
            if r["phase_s"].get("read_wait", 0.0) >= 0.3]
    assert len(slow) == 1
    row = slow[0]
    # the host waited for the held burst; one queued behind it was done by
    # the time the host came for it
    waited, *behind = row["bursts"]
    assert waited["late"] is False and all(x["late"] for x in behind)
    assert waited["read_t"] - waited["dispatch_t"] >= 0.3
    assert waited["k"] == 2 and waited["lanes"] == 1
    # the held burst was in flight when this poll came to its dispatch
    assert row["drained"] is False and row["pending_bursts"] == 2
    before = rows[rows.index(row) - 1]
    assert before["dispatched_t"] == waited["dispatch_t"]
    # every burst read is in some row, and `late` is the counter's own check
    read = [x for r in rows for x in r.get("bursts", ())] + unclaimed
    assert len(read) == b.stats["bursts"]
    assert sum(x["late"] for x in read) == b.stats["bursts_read_late"]
    assert all(x["dispatch_t"] <= x["read_t"] for x in read)


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "seeded"])
def test_ring_on_off_byte_identical_and_no_new_executables(
        model_and_params, temperature):
    outs, caches, rows = [], [], []
    for capacity in (4096, 0):
        b = make_batcher(model_and_params, fused_steps_per_dispatch=8,
                         flight_recorder_capacity=capacity)
        try:
            outs.append(run_one_at_a_time(b, temperature))
            caches.append(jit_cache_size(b))
            rows.append(b.capture_polls())
        finally:
            b.close()
    assert outs[0] == outs[1]
    assert caches[0] == caches[1]
    assert rows[0] and rows[1] == [] and b.flight is None
    assert b.capture_requests()[-1]["admit_poll"] > 0      # stamped all the same


class NoRing:
    """A capture source from before the ring rode the report."""

    def capture_counters(self):
        return {}

    def capture_requests(self):
        return []


@pytest.mark.parametrize("source", ["batcher", "ring_off", "without_the_method",
                                    "none"])
def test_the_report_carries_the_ring(model_and_params, source):
    control = tracing.CaptureControl()
    b = None
    if source in ("batcher", "ring_off"):
        b = make_batcher(model_and_params,
                         flight_recorder_capacity=4096 if source == "batcher" else 0)
        control.register(b)
    elif source == "without_the_method":
        keep = NoRing()
        control.register(keep)
    try:
        if b is not None:
            b.flight and b.flight.record({"type": "shed", "reason": "queue_full"})
            run_batch(b)
        control.start()
        if b is not None:
            run_batch(b)
        report = control.stop()
    finally:
        if b is not None:
            b.close()
    if source != "batcher":
        assert report["polls"] == []
        return
    rows = json.loads(json.dumps(report["polls"]))      # plain types
    assert rows == report["polls"]
    # the ring as it stands: every type, and back past the capture's start
    assert rows[0]["type"] == "shed" and rows[0]["t"] < report["t0"]
    t0, t1 = report["t0"], report["t1"]
    polls = [dict(r, end=r["t"] + sum(r["phase_s"].values())) for r in rows[1:]]
    assert all(r["type"] == "poll" for r in polls)
    touching = [r for r in polls if r["end"] > t0 and r["t"] < t1]
    assert sum(r.get("admitted", 0) for r in touching) == len(PROMPTS)
    # each phase's seconds over the rows that lie inside the capture agree
    # with the report's own, to within the rows that straddle its edges and
    # what the loop spent after its last row
    inside = [r for r in touching if t0 <= r["t"] and r["end"] <= t1]
    assert inside
    edges = sum(r["end"] - r["t"] for r in touching if r not in inside)
    edges += t1 - touching[-1]["end"]
    for phase in PHASES:
        over = report["loop"][f"{phase}_s"] - sum(
            r["phase_s"].get(phase, 0.0) for r in inside)
        assert -1e-6 <= over <= edges + 1e-6, (phase, over, edges)


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


def _poll(seq, t, dispatched_t=None, drained=False, **phase_s):
    row = {"type": "poll", "seq": seq, "poll": 10 * seq, "t": t,
           "drained": drained, "phase_s": phase_s, "pending_bursts": 2}
    if dispatched_t is not None:
        row["dispatched_t"] = dispatched_t
    return row


def _polls(shape):
    """The ring of a hand-built run: a stalled row before the window opened,
    a row of another type, six poll rows inside it of which one closes no
    burst period (it waited idle) and, where the capture lies after the
    window, one more inside and a stalled one past its end."""
    rows = [
        _poll(0, 95.0, 95.0, True, admit=9.0, read_wait=9.0),
        {"type": "shed", "seq": 1, "t": 100.2, "reason": "queue_full"},
        _poll(2, 100.50, 100.50, True, dispatch=0.001, read_wait=0.049),
        _poll(3, 100.55, 100.55, read_wait=0.045, credit=0.005),
        _poll(4, 100.60, 101.01, admit=0.4, dispatch=0.01, read_wait=0.05),
        _poll(5, 101.06, 101.07, read_wait=0.04),
        _poll(6, 101.10, 101.31, True, idle=0.2, admit=0.01, read_wait=0.11),
        _poll(7, 101.43, 101.44, read_wait=0.06, other=0.01),
    ]
    if shape != "trace1":
        rows += [_poll(8, 101.50, 101.51, admit=0.7, read_wait=0.02),
                 _poll(9, 141.0, 141.0, True, admit=3.0, read_wait=3.0)]
    if shape == "wrapped":      # the window's first rows fell off the ring
        rows = rows[3:]
    return rows


def _run(shape):
    """A hand-built ``run`` as ``benchmark/run.py`` hands it to a reader:
    ``trace1`` captures inside the window, ``trace2`` after it;
    ``no_polls`` and ``wrapped`` are ``trace2`` from a program whose report
    carries no ring, and whose ring did not hold the window."""
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
        "loop": {"admit_s": 0.2, "chunks_s": 0.0, "dispatch_s": 0.5,
                 "read_wait_s": 2.0, "credit_s": 0.2, "idle_s": 1.0,
                 "other_s": 0.1, "wall_s": 4.0, "polls": 30, "bursts": 20,
                 "burst_read_lag_s_sum": 6.0},
        "counters": {}, "requests": requests,
    }
    if shape not in ("no_report", "no_polls"):
        program["polls"] = _polls(shape)
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
# the readers of the ring over the WINDOW: rows whose ``t`` lies in it
POLL_READINGS = {
    "admit_turn_max_ms": (400.0, 700.0),
    "read_wait_max_ms": (110.0, 110.0),
    "dispatch_found_drained_share": (100.0 * 2 / 6, 100.0 * 2 / 7),
    # periods 50, 460, 60, 130 (, 70): 99% of the way to the largest
    "burst_period_p99_ms": (130.0 + 0.97 * 330.0, 130.0 + 0.96 * 330.0),
}


@pytest.mark.parametrize("shape", ["trace1", "trace2", "no_report", "no_polls",
                                   "wrapped"])
@pytest.mark.parametrize("metric", sorted({**READINGS, **POLL_READINGS}))
def test_layer_reader_on_a_hand_built_run(metric, shape, capsys):
    from benchmark import manifest

    man = manifest.load(ROOT)
    assert metric in {m["name"] for m in man["per_layer"]}
    value = manifest.layer_reader(ROOT, man, metric)(_run(shape))
    said = capsys.readouterr().err
    if shape == "no_report" or (metric in POLL_READINGS
                                and shape in ("no_polls", "wrapped")):
        assert value is None
        assert ("wrapped inside the window" in said) == (
            shape == "wrapped" and metric in POLL_READINGS)
    else:
        expected = {**READINGS, **POLL_READINGS}[metric]
        assert value == pytest.approx(expected[shape != "trace1"])
        # a ring read inside the window says how much of it the rows cover
        assert ("cover its first 5.0s of 40.0s" in said) == (
            shape == "trace1" and metric in POLL_READINGS)
        if metric == "burst_period_p99_ms":     # with its counts
            assert (f"over {4 + (shape != 'trace1')} periods "
                    f"(1 across an idle wait dropped)") in said


def test_a_burst_period_spans_a_row_that_dispatched_no_burst(capsys):
    """A poll that only admitted or read lies INSIDE the period from the
    burst before it to the burst after it; only an idle wait breaks a run
    of bursts, wherever in the gap the row that idled lies."""
    from benchmark import manifest

    run = _run("trace2")
    run["trace_counters"][1]["program"]["polls"] = [
        _poll(0, 100.00, 100.00, read_wait=0.05),
        _poll(1, 100.05, 100.05, read_wait=0.05),
        _poll(2, 100.10, None, admit=2.7),              # admitted, no burst
        _poll(3, 102.80, 102.81, read_wait=0.05),       # 2760 ms after row 1
        _poll(4, 102.86, None, idle=1.0, admit=0.01),   # idled, no burst
        _poll(5, 103.87, 103.88, read_wait=0.05),       # across the wait
        _poll(6, 103.93, 103.93, read_wait=0.05),
    ]
    man = manifest.load(ROOT)
    value = manifest.layer_reader(ROOT, man, "burst_period_p99_ms")(run)
    assert "over 3 periods (1 across an idle wait dropped)" in capsys.readouterr().err
    # periods 50, 2760, 50: 98% of the way from the median to the largest
    assert value == pytest.approx(50.0 + 0.98 * 2710.0)


def _dump(stalled):
    """A recorded ring: sixty polls a burst period of 50 ms apart, the
    thirtieth of which sat ``stalled`` (a phase) for 2.7 s."""
    rows, t = [], 500.0
    for i in range(60):
        row = _poll(i, t, None, dispatch=0.002, read_wait=0.046, credit=0.002)
        if i == 30 and stalled:
            row["phase_s"][stalled] = 2.7
            row.update(admitted=3, admitted_ids=[71, 72, 73])
        t += sum(row["phase_s"].values())
        row["dispatched_t"] = t - 0.048
        rows.append(row)
    return {"capacity": 4096, "enabled": True, "recorded_total": 60,
            "dropped": 0, "entries": rows}


def _flight_report():
    """``tools/flight_report.py`` as a module."""
    spec = importlib.util.spec_from_file_location(
        "flight_report", os.path.join(ROOT, "tools", "flight_report.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("stalled", ["admit", "read_wait", None])
def test_flight_report_lists_the_slowest_polls_and_diagnoses_a_stall(stalled):
    flight_report = _flight_report()
    unit = flight_report.report(_dump(stalled))["(batcher)"]
    at = unit["lines"].index("slowest polls (scheduler seconds outside idle):")
    listed = unit["lines"][at + 1:at + 6]
    assert len(unit["slowest_polls"]) == len(listed) == 5
    stalls = [line for line in unit["diagnosis"] if "burst period" in line]
    if stalled is None:
        assert stalls == []
        return
    assert unit["slowest_polls"][0]["poll"] == 300
    assert listed[0].startswith("  poll 300 at t=") and f"{stalled} 2700.0" in listed[0]
    assert "3 admitted, 2 bursts in flight, device busy" in listed[0]
    assert len(stalls) == 1
    assert f"poll 300 at t=" in stalls[0] and f"spent 2.700 s in `{stalled}`" in stalls[0]
    assert "54x the median burst period (50.0 ms over 59)" in stalls[0]
    assert "[71, 72, 73]" in stalls[0]
    json.dumps(unit)


QUIET = {"cpu_s": 0.004, "runq_s": 0.0002, "busy_share": 0.41,
         "beat_late_s": 0.0004}
# what the stalled row's ``host`` and ``compiles`` read, and what the
# diagnosis must name from them
CAUSES = {
    "compiled": (dict(QUIET, cpu_s=2.6), [
        {"t": 501.6, "name": "jit_prefill_one", "kind": "trace", "s": 0.3,
         "cache": None},
        {"t": 501.9, "name": "jit_prefill_one", "kind": "lower", "s": 0.3,
         "cache": None},
        {"t": 503.9, "name": "jit_prefill_one", "kind": "backend", "s": 2.0,
         "cache": "miss"}],
        "XLA compiled meanwhile: jit_prefill_one 2.600 s (cache miss)"),
    "starved": (dict(QUIET, runq_s=2.6, busy_share=0.99), None,
                "stood runnable with no core for 2.600 s of it (`runq_s`): "
                "the host starved the thread; the machine was 99% busy"),
    "process_late": (dict(QUIET, beat_late_s=2.65), None,
                     "the whole process stood: the heartbeat came 2.650 s late"),
    "collector": (dict(QUIET, gc_s=1.9, beat_late_s=0.3), None,
                  "the collector ran 1.900 s"),
    "machine_full": (dict(QUIET, runq_s=0.3, busy_share=0.97), None,
                     "the machine was over its cores (97% busy"),
    "runtime": (QUIET, None,
                "the thread slept (cpu 0.004 s, run-queue wait 0.000 s) while "
                "the process's beats came on time (latest 0.4 ms) and nothing "
                "compiled; the machine was 41% busy: the runtime or the device "
                "held the burst"),
    # the machine the chips are on: no schedstat, a /proc/stat of zeros
    "runtime_sandbox": ({"cpu_s": 0.004, "beat_late_s": 0.0011}, None,
                        "the thread slept (cpu 0.004 s, this host gives no "
                        "run-queue wait) while the process's beats came on "
                        "time (latest 1.1 ms) and nothing compiled: the "
                        "runtime or the device held the burst"),
    "late_sandbox": ({"cpu_s": 0.004, "beat_late_s": 2.5}, None,
                     "the whole process stood: the heartbeat came 2.500 s late"),
    "no_beat": ({"cpu_s": 0.1, "busy_share": 0.2}, None,
                "the record has no heartbeat to say whether"),
    "older_dump": (None, None, "the record carries no `host` account"),
}


@pytest.mark.parametrize("cause", sorted(CAUSES))
def test_flight_report_names_the_cause_of_a_stall_from_the_row(cause):
    flight_report = _flight_report()
    host, compiles, said = CAUSES[cause]
    dump = _dump("read_wait")
    for row in dump["entries"]:
        if cause != "older_dump":
            row["host"] = dict(QUIET)
    stalled = dump["entries"][30]
    if host is not None:
        stalled["host"] = host
    if compiles:
        stalled["compiles"] = compiles
    unit = flight_report.report(dump)["(batcher)"]
    stall, = (line for line in unit["diagnosis"] if "burst period" in line)
    assert "spent 2.700 s in `read_wait`" in stall and said in stall, stall
    assert stall.endswith("no lane got a token meanwhile; requests admitted "
                          "in that poll: [71, 72, 73]")
    # of the causes one is named
    assert sum(text in stall for _h, _c, text in CAUSES.values()) == 1
    # the slowest polls print the row's own account
    at = unit["lines"].index("slowest polls (scheduler seconds outside idle):")
    first = unit["lines"][at + 1]
    assert first.startswith("  poll 300 at t=")
    if cause == "older_dump":
        assert "host:" not in first
    if cause == "starved":
        assert first.endswith("; host: cpu 4.0 ms, run-queue wait 2600.0 ms, "
                              "machine busy 99.0%, heartbeat late 0.4 ms")
    if cause == "compiled":
        assert first.endswith("; compiled: jit_prefill_one 2.600 s (cache miss)")
    # an unloaded dump's rows, host and all, are diagnosed as nothing
    quiet = _dump(None)
    for row in quiet["entries"]:
        row["host"] = dict(QUIET)
    assert not [line for line in flight_report.report(quiet)["(batcher)"]["diagnosis"]
                if "burst period" in line]
