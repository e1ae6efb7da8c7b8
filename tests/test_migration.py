"""Zero-loss generate serving: live-lane migration, graceful drain, and
resumable streams (serving/migration.py + ContinuousBatcher.drain /
submit_checkpoint + GenerateServer.drain_to / resume tokens).

The load-bearing contract: a drained or killed member's in-flight
generations continue on a peer BYTE-IDENTICAL to an uninterrupted run —
greedy and seeded sampling, unary and streaming — with already-delivered
stream spans never re-sent, queued requests never dropped, and every
refusal typed (WeightVersionMismatch 409, ChecksumError, draining 503).
"""

import threading
import time

import pytest

from seldon_core_tpu.models.llm import DecoderLM
from seldon_core_tpu.serving import migration
from seldon_core_tpu.serving.continuous import (
    BatcherDead,
    ContinuousBatcher,
)
from seldon_core_tpu.serving.disagg import (
    ChecksumError,
    TruncatedStream,
    WeightVersionMismatch,
)
from seldon_core_tpu.serving.migration import (
    MigrationError,
    checkpoint_of,
    checkpoint_token,
    decode_checkpoint,
    derive_lane_key,
    encode_checkpoint,
    parse_token,
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


@pytest.fixture(scope="module")
def references(model_and_params):
    """Undisturbed single-member outputs: greedy and seeded."""
    b = make_batcher(model_and_params)
    try:
        greedy = [
            b.generate(p, max_new_tokens=40, temperature=0.0)
            for p in PROMPTS
        ]
        sampled = [
            b.generate(p, max_new_tokens=30, temperature=0.8, seed=11 + i)
            for i, p in enumerate(PROMPTS)
        ]
    finally:
        b.close()
    return {"greedy": greedy, "sampled": sampled}


def wait_lanes(b, n, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if len(b._active) + len(b._chunked) >= n:
            return True
        time.sleep(0.001)
    return False


# -- SGC1 codec ---------------------------------------------------------------


def test_codec_round_trip_and_token():
    ck = {
        "v": 1, "prompt": [1, 2, 3], "emitted": [4, 5],
        "rng_key": [7, 9], "max_new_tokens": 16, "temperature": 0.5,
        "eos_id": None, "seed": 3, "weight_version": 0,
        "wait_s": 0.25, "submit_wall_us": 123456, "deadline_s": None,
        "stream_pos": 2,
    }
    assert decode_checkpoint(encode_checkpoint(ck)) == ck
    assert parse_token(checkpoint_token(ck)) == ck


def test_codec_typed_refusals():
    ck = {"v": 1, "prompt": [1], "emitted": [], "seed": 0}
    raw = bytearray(encode_checkpoint(ck))
    raw[-2] ^= 0xFF  # corrupt the JSON payload
    with pytest.raises(ChecksumError):
        decode_checkpoint(bytes(raw))
    with pytest.raises(TruncatedStream):
        decode_checkpoint(encode_checkpoint(ck)[:-4])
    with pytest.raises(MigrationError, match="magic"):
        decode_checkpoint(b"XXXX" + encode_checkpoint(ck)[4:])
    with pytest.raises(MigrationError, match="version"):
        decode_checkpoint(encode_checkpoint({**ck, "v": 99}))
    with pytest.raises(MigrationError, match="base64"):
        parse_token("!!not//base64!!")
    with pytest.raises(MigrationError, match="prompt"):
        decode_checkpoint(encode_checkpoint({"v": 1, "prompt": []}))


# -- drain + checkpoint resume (batcher level) --------------------------------


def test_drain_mid_decode_resumes_byte_identical(
    model_and_params, references
):
    """Mixed greedy+seeded batch drained mid-decode: every checkpoint
    resumes on a peer byte-identical to the undisturbed run, and the
    exact post-split RNG key rides the checkpoint."""
    a = make_batcher(model_and_params, steps_per_poll=1)
    b = make_batcher(model_and_params)
    try:
        futs = [
            a.submit(p, max_new_tokens=40, temperature=0.0)
            for p in PROMPTS[:2]
        ]
        futs.append(a.submit(
            PROMPTS[2], max_new_tokens=30, temperature=0.8, seed=13,
        ))
        assert wait_lanes(a, 3)
        drained = a.drain()
        assert a.health == "draining"
        assert a.stats["drains"] == 1
        s_ref_b = make_batcher(model_and_params)
        try:
            s_ref = s_ref_b.generate(
                PROMPTS[2], max_new_tokens=30, temperature=0.8, seed=13
            )
        finally:
            s_ref_b.close()
        want = {
            tuple(PROMPTS[0]): references["greedy"][0],
            tuple(PROMPTS[1]): references["greedy"][1],
            tuple(PROMPTS[2]): s_ref,
        }
        for req in drained:
            ck = checkpoint_of(req, a.weight_version)
            out = b.submit_checkpoint(ck).result(timeout=30)
            assert out == want[tuple(req.tokens)]
        # anything NOT drained must have already completed locally,
        # byte-identical (zero loss either way)
        for f, p in zip(futs, PROMPTS):
            if f.done():
                assert f.result() == want[tuple(p)]
        assert b.stats["migrated_resumes"] == len(drained)
    finally:
        a.close()
        b.close()


def test_derived_lane_key_matches_live_checkpoint(model_and_params):
    """Crash tokens ship keyless; derive_lane_key must reproduce the
    EXACT key a drain reads off the device — the invariant that makes
    token-based seeded-sampling resume byte-identical."""
    b = make_batcher(model_and_params, steps_per_poll=1)
    try:
        b.submit(PROMPTS[0], max_new_tokens=40, temperature=0.7, seed=5)
        assert wait_lanes(b, 1)
        drained = b.drain()
        req = drained[0]
        if req.resume is None:
            pytest.skip("drained before any token was credited")
        assert derive_lane_key(5, len(req.resume["emitted"])) == \
            req.resume["key"]
    finally:
        b.close()


def test_draining_member_refuses_typed_503(model_and_params):
    b = make_batcher(model_and_params)
    try:
        b.drain()
        with pytest.raises(BatcherDead) as ei:
            b.submit([1, 2, 3])
        assert ei.value.status == 503
        assert "draining" in str(ei.value)
        with pytest.raises(BatcherDead):
            b.submit_checkpoint({"prompt": [1, 2], "emitted": []})
        with pytest.raises(BatcherDead):
            b.drain()  # the drain latch holds: one drain per member
    finally:
        b.close()


def test_drain_timeout_cancels_and_member_resumes_serving(
    model_and_params,
):
    """A drain that outruns its timeout must not strand the member in
    the draining latch: the job cancels, the scheduler clears the
    latch, and admissions resume."""
    b = make_batcher(model_and_params)
    entered = threading.Event()
    block = threading.Event()

    def slow_poll(_n):
        entered.set()
        block.wait(0.5)

    b.fault_hook = slow_poll
    b.start()
    try:
        assert entered.wait(10)
        with pytest.raises(RuntimeError, match="drain did not complete"):
            b.drain(timeout_s=0.05)
        block.set()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and b.health != "serving":
            time.sleep(0.01)
        assert b.health == "serving"
        b.fault_hook = None
        out = b.generate([1, 2, 3], max_new_tokens=4)
        assert len(out) == 7
    finally:
        block.set()
        b.close()


def test_dead_member_drain_raises_typed(model_and_params):
    """A latched-dead member has nothing drainable (its queued futures
    were already failed typed): drain() propagates BatcherDead instead
    of pretending to migrate."""
    b = make_batcher(model_and_params, restart_budget=0)

    def die(_n):
        raise RuntimeError("injected death")

    b.fault_hook = die
    b.submit([1, 2, 3], max_new_tokens=4)
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline and b.health != "dead":
        time.sleep(0.005)
    assert b.health == "dead"
    with pytest.raises(BatcherDead):
        b.drain()
    b.close()


def test_malformed_resume_token_is_client_fault_400():
    from seldon_core_tpu.serving.migration import ResumeTokenError

    ck = {"v": 1, "prompt": [1, 2], "emitted": [3], "seed": 0}
    tok = checkpoint_token(ck)
    corrupted = tok[:-6] + ("AAAAAA" if not tok.endswith("AAAAAA")
                            else "BBBBBB")
    for bad in ("!!not//base64!!", corrupted, tok[: len(tok) // 2]):
        with pytest.raises(ResumeTokenError) as ei:
            parse_token(bad)
        assert ei.value.status == 400


def test_drain_collects_queued_requests(model_and_params, references):
    """Queued-not-admitted requests ride the drain too: a 2-slot member
    with 3 submissions hands all three over, none dropped."""
    a = make_batcher(model_and_params, slots=2, steps_per_poll=1)
    b = make_batcher(model_and_params)
    try:
        for p in PROMPTS:
            a.submit(p, max_new_tokens=40, temperature=0.0)
        assert wait_lanes(a, 2)
        drained = a.drain()
        done_locally = 3 - len(drained)
        assert len(drained) + done_locally == 3
        for req in drained:
            ck = checkpoint_of(req, a.weight_version)
            out = b.submit_checkpoint(ck).result(timeout=30)
            i = PROMPTS.index(list(req.tokens))
            assert out == references["greedy"][i]
    finally:
        a.close()
        b.close()


def test_checkpoint_weight_version_mismatch_refused(model_and_params):
    b = make_batcher(model_and_params)
    try:
        with pytest.raises(WeightVersionMismatch):
            b.submit_checkpoint({
                "prompt": [1, 2, 3], "emitted": [4],
                "weight_version": "v-other",
            })
        assert b.stats["migrated_resumes"] == 0
    finally:
        b.close()


def test_checkpoint_wait_anchor_is_cumulative(model_and_params):
    """Satellite: a migrated lane must not lose its original submit
    anchor — the queue-wait SLO sample covers source wait + local wait,
    and the first-class histogram sees the cumulative value."""
    b = make_batcher(model_and_params)
    try:
        ck = {
            "prompt": list(PROMPTS[1]), "emitted": [],
            "max_new_tokens": 8, "temperature": 0.0, "seed": 0,
            "wait_s": 2.5, "submit_wall_us": 777,
        }
        f = b.submit_checkpoint(ck)
        f.result(timeout=30)
        assert b.slo_recent[-1][0] >= 2.5       # its queue-wait sample
        req = f.gen_request
        assert req.submit_wall_us == 777
        # the histogram path: the server ships the TIMER, the engine
        # registry folds it into the first-class queue-wait series
        from seldon_core_tpu.graph.engine_metrics import MetricsRegistry
        from seldon_core_tpu.servers.generateserver import GenerateServer

        srv = GenerateServer.__new__(GenerateServer)
        srv.batcher = b
        from seldon_core_tpu.metrics import CounterDeltas

        srv._deltas = CounterDeltas()
        reg = MetricsRegistry()
        reg.record_custom(srv.metrics(), {"unit": "g"})
        total, count = reg.histogram_totals(
            "seldon_engine_generate_queue_wait_seconds", {"unit": "g"}
        )
        assert count >= 1 and total >= 2.5
    finally:
        b.close()


def test_resume_queue_survives_supervised_restart(model_and_params):
    """Satellite: queued resumes are host-side checkpoints — a scheduler
    death + supervised restart (_alloc_device_state rebuild) must bring
    them back byte-identical, including a seeded-sampling lane."""
    from seldon_core_tpu.resilience.faults import FaultInjector

    refs = {}
    r = make_batcher(model_and_params, slots=2)
    try:
        refs["g"] = r.generate(PROMPTS[0], max_new_tokens=40,
                               temperature=0.0)
        refs["s"] = r.generate(PROMPTS[2], max_new_tokens=30,
                               temperature=0.8, seed=21)
    finally:
        r.close()
    b = make_batcher(
        model_and_params, slots=2, steps_per_poll=1,
        hbm_ledger_bytes=1 << 40, restart_backoff_s=0.05,
    )
    try:
        # shrink the ledger to ~1.3 lanes so one of the two live lanes
        # preempts into the resume queue (the pressure machinery)
        shrink = int(1.3 * b._attn_need(64) * b._kv_key_bytes)
        inj = FaultInjector([], pressure={
            "shrink_to_bytes": shrink,
            "after_polls": b._work_poll_count + 3,
        })
        b.pressure_hook = inj.pressure_hook()
        fg = b.submit(PROMPTS[0], max_new_tokens=40, temperature=0.0)
        fs = b.submit(PROMPTS[2], max_new_tokens=30, temperature=0.8,
                      seed=21)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and not b._resume_queue:
            time.sleep(0.001)
        assert b._resume_queue, "no preemption landed"
        queued = {tuple(req.tokens) for req in b._resume_queue}
        # induce ONE loop death while the resume queue is populated
        state = {"armed": True}

        def die(_n):
            if state["armed"]:
                state["armed"] = False
                raise RuntimeError("injected death with queued resumes")

        b.fault_hook = die
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and not b.stats["batcher_restarts"]:
            time.sleep(0.001)
        assert b.stats["batcher_restarts"] >= 1
        # restore the budget so the resumes can re-admit
        from seldon_core_tpu.serving.continuous import GenRequest  # noqa: F401

        b._pressure.restore_budget()
        outs = {}
        for f, key, want in ((fg, "g", refs["g"]), (fs, "s", refs["s"])):
            try:
                outs[key] = f.result(timeout=60)
            except BatcherDead:
                # only a lane that was ACTIVE at death may fail typed;
                # queued resumes must survive
                p = PROMPTS[0] if key == "g" else PROMPTS[2]
                assert tuple(p) not in queued
                continue
            assert outs[key] == want, key
        assert outs, "every request failed — resume queue did not survive"
        resumed_keys = {
            "g" if q == tuple(PROMPTS[0]) else "s" for q in queued
        }
        for key in resumed_keys:
            assert key in outs, f"queued resume {key} was dropped"
    finally:
        b.close()


# -- hot-swap straggler bound (satellite) -------------------------------------


def test_swap_straggler_bound_resume_policy(model_and_params):
    """A long generation may no longer stall a weight flip forever:
    after swap_drain_ms the straggler is preempt-checkpointed, the swap
    lands, and (policy=resume) the lane finishes on the new weights."""
    model, _params = model_and_params
    b = make_batcher(
        model_and_params, slots=2, steps_per_poll=1,
        swap_drain_ms=40, swap_resume_policy="resume",
    )
    try:
        f = b.submit([1, 2, 3], max_new_tokens=58, temperature=0.0)
        assert wait_lanes(b, 1)
        sw = b.request_weight_swap(model.init_params(1), version="v9")
        assert sw.result(timeout=30) == "v9"
        out = f.result(timeout=30)
        assert len(out) == 3 + 58
        assert b.stats["swap_preemptions"] >= 1
        assert b.weight_version == "v9"
    finally:
        b.close()


def test_swap_straggler_bound_fail_policy(model_and_params):
    model, _params = model_and_params
    b = make_batcher(
        model_and_params, slots=2, steps_per_poll=1,
        swap_drain_ms=40, swap_resume_policy="fail",
    )
    try:
        f = b.submit([1, 2, 3], max_new_tokens=58, temperature=0.0)
        assert wait_lanes(b, 1)
        sw = b.request_weight_swap(model.init_params(2), version="v2")
        assert sw.result(timeout=30) == "v2"
        with pytest.raises(WeightVersionMismatch):
            f.result(timeout=30)
        assert b.stats["swap_preemptions"] >= 1
    finally:
        b.close()


def test_swap_without_straggler_bound_keeps_waiting(model_and_params):
    """Regression guard for the default: swap_drain_ms=0 never preempts
    — the flip waits for in-flight lanes exactly as before."""
    model, _params = model_and_params
    b = make_batcher(model_and_params, slots=2, steps_per_poll=1)
    try:
        f = b.submit([1, 2, 3], max_new_tokens=40, temperature=0.0)
        assert wait_lanes(b, 1)
        sw = b.request_weight_swap(model.init_params(1), version="v1")
        out = f.result(timeout=30)
        assert len(out) == 3 + 40
        assert sw.result(timeout=30) == "v1"
        assert b.stats["swap_preemptions"] == 0
    finally:
        b.close()


def test_bad_swap_resume_policy_rejected(model_and_params):
    with pytest.raises(ValueError, match="swap_resume_policy"):
        make_batcher(model_and_params, swap_resume_policy="maybe")


# -- server level: streams, resume tokens, drain_to ---------------------------


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    from seldon_core_tpu.testing import write_model_dir

    root = tmp_path_factory.mktemp("mig-model")
    return write_model_dir(str(root), "llm", {
        "vocab_size": 256, "d_model": 32, "n_layers": 2, "n_heads": 2,
        "n_kv_heads": 2, "d_ff": 64, "max_seq": 64,
    })


def _server(model_dir, **kw):
    from seldon_core_tpu.servers.generateserver import GenerateServer

    kw.setdefault("slots", 2)
    kw.setdefault("steps_per_poll", 1)
    srv = GenerateServer(model_uri=model_dir, **kw)
    srv.load()
    return srv


def test_drain_to_peer_keeps_stream_alive(model_dir):
    """The rolling-drain proof at server level: a live stream's member
    drains mid-decode; the stream completes byte-identical through the
    ORIGINAL connection with no span re-sent and zero errors."""
    prompt = [5, 6, 7, 8]
    kw = dict(max_new_tokens=24, temperature=0.8, eos_id=None, seed=9)
    ref = _server(model_dir)
    try:
        want = ref.batcher.generate(list(prompt), **kw)
    finally:
        ref.close()
    a = _server(model_dir)
    b = _server(model_dir)
    try:
        handle = a.stream({"prompt_tokens": prompt, **kw})
        spans, final_box = [], {}
        done = threading.Event()

        def consume():
            try:
                for ch in handle.chunks:
                    if ch.get("done"):
                        final_box["final"] = ch
                        break
                    spans.append(list(ch["tokens"]))
            except Exception as e:  # noqa: BLE001
                final_box["error"] = e
            finally:
                done.set()

        threading.Thread(target=consume, daemon=True).start()
        while not a.batcher._active:
            time.sleep(0.001)
        summary = a.drain_to(b)
        assert done.wait(30)
        assert "error" not in final_box, final_box
        assert final_box["final"]["tokens"] == want
        flat = [t for s in spans for t in s]
        assert flat == want[len(prompt):]  # no span re-sent, none lost
        if summary["drained"]:
            assert a.batcher.stats["checkpoint_exports"] >= 1
            assert a.batcher.stats["migrations"] == summary["handed"]
            assert b.batcher.stats["migrated_resumes"] == summary["handed"]
        # counters match the flight-recorder records (the acceptance bit)
        recs = a.batcher.flight.snapshot()
        assert sum(1 for r in recs if r.get("type") == "drain") == \
            a.batcher.stats["drains"]
        assert sum(
            1 for r in recs if r.get("type") == "checkpoint_export"
        ) == a.batcher.stats["checkpoint_exports"]
    finally:
        a.close()
        b.close()


def test_member_kill_resume_token_stream(model_dir):
    """Crash survival: a member dies mid-stream (induced loop death,
    budget 0 latches dead); the client resumes on a peer with the last
    span's resume token — byte-identical total, no re-sent span."""
    prompt = [2, 4, 6, 8]
    kw = dict(max_new_tokens=20, temperature=0.8, eos_id=None, seed=4)
    ref = _server(model_dir)
    try:
        want = ref.batcher.generate(list(prompt), **kw)
    finally:
        ref.close()
    a = _server(model_dir, resume_tokens=1, restart_budget=0)
    b = _server(model_dir, resume_tokens=1)
    try:
        handle = a.stream({"prompt_tokens": prompt, **kw})
        it = iter(handle.chunks)
        first = next(it)
        assert "resume_token" in first
        delivered = list(first["tokens"])
        token = first["resume_token"]

        def die(_n):
            raise RuntimeError("injected member kill")

        a.batcher.fault_hook = die
        died = None
        try:
            for ch in it:
                if ch.get("done"):
                    break
                delivered.extend(ch["tokens"])
                token = ch.get("resume_token", token)
        except Exception as e:  # noqa: BLE001
            died = e
        assert died is not None and getattr(died, "status", None) == 503
        assert a.batcher.health == "dead"
        # one engine-internal retry: the token continues on the peer
        h2 = b.stream({"resume_token": token})
        resumed, final = [], None
        for ch in h2.chunks:
            if ch.get("done"):
                final = ch
                break
            resumed.extend(ch["tokens"])
        assert final["tokens"] == want
        assert delivered + resumed == want[len(prompt):]
        assert b.batcher.stats["migrated_resumes"] == 1
    finally:
        a.close()
        b.close()


def test_unary_resume_token_round_trip(model_dir):
    prompt = [7, 7, 7]
    kw = dict(max_new_tokens=10, temperature=0.6, eos_id=None, seed=2)
    a = _server(model_dir, resume_tokens=1)
    try:
        out = a.predict({"prompt_tokens": [list(prompt)], **kw}, None)
        want = out["tokens"][0]
        assert len(out["resume_tokens"]) == 1
        # resubmitting the final-state token reproduces the response
        # (the resumed lane has nothing left to decode)
        out2 = a.predict({"resume_token": out["resume_tokens"][0]}, None)
        assert out2["tokens"][0] == want
    finally:
        a.close()


def test_text_mode_survives_token_resume(model_dir):
    """A strData stream's resume token carries text_mode, so the
    resumed stream keeps decoding ``text`` fields."""
    a = _server(model_dir, resume_tokens=1)
    b = _server(model_dir, resume_tokens=1)
    try:
        h = a.stream({"prompt": "hi", "max_new_tokens": 6,
                      "temperature": 0.0})
        it = iter(h.chunks)
        first = next(it)
        assert "text" in first
        tok = first["resume_token"]
        assert parse_token(tok)["text_mode"] is True
        for _ch in it:
            pass  # let the original finish; resume the token on b
        h2 = b.stream({"resume_token": tok})
        chunks = list(h2.chunks)
        assert all("text" in ch for ch in chunks)
    finally:
        a.close()
        b.close()


def test_resume_tokens_refused_with_speculation(model_dir):
    from seldon_core_tpu.servers.generateserver import GenerateServer

    with pytest.raises(ValueError, match="resume_tokens"):
        GenerateServer(
            model_uri=model_dir, resume_tokens=1,
            speculate_tokens=2, draft_layers=1,
        )


def test_engine_drain_route_tcp(model_dir):
    """The wire path: POST /drain {"to": peer} on the source engine
    checkpoints over TCP to the peer engine's /drain import mode, and
    the draining member's readiness goes red ("draining" health)."""
    import http.client
    import json as _json

    from seldon_core_tpu.testing import EngineHarness

    prompt = [1, 3, 5, 7]
    kw = dict(max_new_tokens=24, temperature=0.8, eos_id=None, seed=6)
    ref = _server(model_dir)
    try:
        want = ref.batcher.generate(list(prompt), **kw)
    finally:
        ref.close()
    a = _server(model_dir)
    b = _server(model_dir)
    ah = EngineHarness(a, name="mig-src").start()
    bh = EngineHarness(b, name="mig-dst").start()
    try:
        fut = a.batcher.submit(list(prompt), **kw)
        while not a.batcher._active:
            time.sleep(0.001)
        conn = http.client.HTTPConnection("127.0.0.1", ah.http_port,
                                          timeout=60)
        conn.request(
            "POST", "/drain",
            _json.dumps({"to": f"127.0.0.1:{bh.http_port}"}).encode(),
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        payload = _json.loads(resp.read())
        conn.close()
        assert resp.status == 200, payload
        unit = next(iter(payload["units"].values()))
        assert unit["failed"] == 0
        assert fut.result(timeout=30) == want
        assert a.batcher.health == "draining"
        # readiness goes red on the draining member (the engine's
        # periodic graph poll consumes this hook)
        with pytest.raises(RuntimeError, match="draining"):
            a.health_status()
        if unit["drained"]:
            assert b.batcher.stats["migrated_resumes"] >= 1
    finally:
        ah.stop()
        bh.stop()
        a.close()
        b.close()


def test_gateway_retries_generate_503_on_another_member():
    """Engine-internal retry: a 503-class refusal from one routable
    member (dead / restarting / DRAINING batcher) is retried once on a
    different member — the client sees one 200, not a 5xx."""
    import asyncio
    import json as _json

    from seldon_core_tpu.controlplane.ingress import Gateway
    from seldon_core_tpu.graph.client import UnitCallError
    from seldon_core_tpu.http_server import Request

    class FakeApp:
        def __init__(self, fail):
            self.fail = fail
            self.calls = 0
            self.shadow_mirror = None

        async def predict(self, message, headers=None):
            self.calls += 1
            if self.fail:
                e = UnitCallError(
                    503, "batcher is draining; retry another member"
                )
                e.retry_after_s = 1.0
                raise e
            return {"jsonData": {"tokens": [[1, 2, 3]]}}

    class FakeHandle:
        def __init__(self, app):
            self.app = app

    class P:
        name = "gen"
        traffic = 100
        annotations: dict = {}

    class Dep:
        key = "default/mig"
        predictors = [P()]

    gw = Gateway(seed=0)
    dead, live = FakeApp(True), FakeApp(False)
    gw.set_routes(Dep(), {"gen": [FakeHandle(dead), FakeHandle(live)]})
    app = gw.app()
    body = _json.dumps({"jsonData": {"prompt_tokens": [1, 2]}}).encode()

    async def post():
        req = Request(
            "POST", "/seldon/default/mig/api/v0.1/predictions", "",
            {"content-type": "application/json"}, body,
        )
        return await app._dispatch(req)

    resp = asyncio.run(post())
    assert resp.status == 200
    assert dead.calls == 1 and live.calls == 1
    # with no second member the typed 503 + Retry-After surfaces
    gw.set_routes(Dep(), {"gen": [FakeHandle(dead)]})
    resp = asyncio.run(post())
    assert resp.status == 503
    assert resp.headers.get("Retry-After")


def test_reconciler_drains_member_before_scale_down(model_dir):
    """Control-plane integration: scaling a generate predictor 2 -> 1
    drains the removed member's in-flight generation to the survivor
    before teardown — the client's future completes byte-identical."""
    import asyncio

    from seldon_core_tpu.controlplane import (
        DeploymentController,
        ResourceStore,
        SeldonDeployment,
    )
    from seldon_core_tpu.controlplane.runtime import InProcessRuntime

    def dep(replicas):
        return SeldonDeployment.from_dict({
            "name": "mig",
            "annotations": {"seldon.io/drain-seconds": "20"},
            "predictors": [{
                "name": "gen",
                "replicas": replicas,
                "graph": {
                    "name": "g", "implementation": "GENERATE_SERVER",
                    "modelUri": model_dir,
                    "parameters": [
                        {"name": "slots", "value": "2", "type": "INT"},
                        {"name": "steps_per_poll", "value": "1",
                         "type": "INT"},
                    ],
                },
            }],
        })

    async def run():
        store = ResourceStore()
        ctl = DeploymentController(
            store, runtime=InProcessRuntime(open_ports=False)
        )
        store.apply(dep(2))
        await ctl.reconcile(store.list()[0].clone())
        units = []
        for _name, (h, _) in sorted(ctl.components.items()):
            u = ctl._generate_unit(h, "drain_to")
            if u is not None:
                units.append(u)
        assert len(units) == 2
        # replica index 1 is the one a 2->1 scale removes
        removed = units[1]
        survivor = units[0]
        prompt = [4, 4, 2]
        kw = dict(max_new_tokens=30, temperature=0.8, eos_id=None, seed=8)
        want = survivor.batcher.generate(list(prompt), **kw)
        fut = removed.batcher.submit(list(prompt), **kw)
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline and not removed.batcher._active:
            await asyncio.sleep(0.001)
        store.apply(dep(1))
        await ctl.reconcile(store.list()[0].clone())
        out = fut.result(timeout=30)
        assert out == want
        assert removed.batcher.stats["drains"] >= 1 or fut.done()
        for _name, (h, _) in list(ctl.components.items()):
            await h.stop()

    asyncio.run(run())
