"""Fault-tolerant generate serving: scheduler supervision (typed
BatcherDead, crash-loop restart with budget + backoff, health/readiness
latching), prefill-peer failover (ejection, probe readmission,
retry-once, degraded local prefill), and the chaos harness (KV-transport
byte faults, induced scheduler death).

Tiers: failover-layer unit tests over stub transports (no model),
KV-fault determinism through the real codec, batcher-level supervision
tests, and server-level degradation/streaming tests over the tiny LLM.
"""

import io
import threading
import time

import numpy as np
import pytest

from seldon_core_tpu.models.llm import DecoderLM
from seldon_core_tpu.resilience.faults import FaultInjector, FaultRule, KVFaults
from seldon_core_tpu.serving.continuous import BatcherDead, ContinuousBatcher
from seldon_core_tpu.serving.disagg import (
    AllPeersDown,
    ChecksumError,
    DisaggError,
    FailoverKVClient,
    PeerBusy,
    PrefixGone,
    TruncatedStream,
    WeightVersionMismatch,
    decode_slab,
    encode_slab,
    make_failover,
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


@pytest.fixture(scope="module")
def model_and_params():
    model = DecoderLM(**CFG)
    return model, model.init_params(0)


def _fast_batcher(model, params, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_seq", 64)
    kw.setdefault("prefill_buckets", (8, 16))
    kw.setdefault("steps_per_poll", 2)
    kw.setdefault("restart_backoff_s", 0.02)
    return ContinuousBatcher(model, params, **kw)


def _die_once():
    state = {"armed": True}

    def hook(_poll):
        if state["armed"]:
            state["armed"] = False
            raise RuntimeError("injected poll death")

    return hook, state


# -- failover layer over stub transports -------------------------------------


class _StubPeer:
    def __init__(self, addr, fail=None, probe_ok=True):
        self.addr = addr
        self.name = "stub"
        self.fail = fail          # exception instance to raise, or None
        self.probe_ok = probe_ok
        self.calls = 0
        self.probes = 0

    def prefill(self, request, deadline_s=None):
        self.calls += 1
        if self.fail is not None:
            raise self.fail
        return {"peer": self.addr}, {"k": np.zeros(1), "v": np.zeros(1)}

    def probe(self, timeout_s=2.0):
        self.probes += 1
        return self.probe_ok

    def close(self):
        pass


def test_failover_retries_once_on_next_peer_and_ejects():
    dead = _StubPeer("a:1", fail=DisaggError("peer a unreachable"))
    good = _StubPeer("b:2")
    ejected, readmitted = [], []
    fc = FailoverKVClient(
        [dead, good], eject_backoff_s=60.0,
        on_eject=lambda addr, why: ejected.append((addr, why)),
        on_readmit=lambda addr: readmitted.append(addr),
    )
    meta, _slab = fc.prefill({"tokens": [1]})
    assert meta["peer"] == "b:2"          # one retry absorbed the failure
    assert ejected and ejected[0][0] == "a:1"
    assert not readmitted
    assert fc.healthy_count() == 1
    # subsequent transfers skip the ejected peer entirely (backoff 60s)
    for _ in range(3):
        assert fc.prefill({"tokens": [1]})[0]["peer"] == "b:2"
    assert dead.calls == 1


def test_failover_readmits_on_probe_success():
    flaky = _StubPeer("a:1", fail=DisaggError("down"))
    good = _StubPeer("b:2")
    readmitted = []
    fc = FailoverKVClient(
        [flaky, good], eject_backoff_s=0.01,
        on_readmit=lambda addr: readmitted.append(addr),
    )
    with pytest.raises(DisaggError):
        FailoverKVClient([flaky], eject_backoff_s=0.01).prefill({})
    fc.prefill({})  # ejects flaky, serves from good
    assert fc.healthy_count() <= 2
    # peer recovers: probe readmits it after the backoff
    flaky.fail = None
    time.sleep(0.05)
    assert fc.probe_ejected() >= 0  # lazy path also allowed below
    deadline = time.monotonic() + 5.0
    while fc.healthy_count() < 2 and time.monotonic() < deadline:
        time.sleep(0.02)
        fc.probe_ejected()
    assert fc.healthy_count() == 2
    assert readmitted and readmitted[-1] == "a:1"
    assert flaky.probes >= 1


def test_failover_all_peers_down_typed():
    a = _StubPeer("a:1", fail=DisaggError("down"), probe_ok=False)
    b = _StubPeer("b:2", fail=DisaggError("down"), probe_ok=False)
    fc = FailoverKVClient([a, b], eject_backoff_s=60.0)
    with pytest.raises(DisaggError):
        fc.prefill({})  # both tried, both ejected
    with pytest.raises(AllPeersDown):
        fc.prefill({})  # pool fully ejected -> the degradation trigger


def test_failover_busy_rotates_without_eject():
    busy = _StubPeer("a:1", fail=PeerBusy("at capacity"))
    good = _StubPeer("b:2")
    fc = FailoverKVClient([busy, good], eject_backoff_s=60.0)
    for _ in range(4):
        assert fc.prefill({})[0]["peer"] == "b:2"
    assert fc.healthy_count() == 2  # busy peer was never ejected
    # every peer busy: the capacity error surfaces, not AllPeersDown
    fc2 = FailoverKVClient(
        [_StubPeer("a:1", fail=PeerBusy("full")),
         _StubPeer("b:2", fail=PeerBusy("full"))],
        eject_backoff_s=60.0,
    )
    with pytest.raises(PeerBusy):
        fc2.prefill({})
    assert fc2.healthy_count() == 2


def test_failover_request_errors_pass_through():
    """WeightVersionMismatch / PrefixGone are about the request, not the
    peer: no ejection, no blind retry that would mask the typed
    contract the decode server's retry paths key off."""
    for exc in (WeightVersionMismatch("stale"), PrefixGone("evicted")):
        peer = _StubPeer("a:1", fail=exc)
        fc = FailoverKVClient([peer, _StubPeer("b:2")], eject_backoff_s=60.0)
        with pytest.raises(type(exc)):
            fc.prefill({})
        assert fc.healthy_count() == 2
        assert peer.calls == 1


def test_make_failover_splits_comma_list():
    fc = make_failover("127.0.0.1:9001,127.0.0.1:9002")
    assert isinstance(fc, FailoverKVClient)
    assert [p.addr for p in fc.peers] == ["127.0.0.1:9001", "127.0.0.1:9002"]


# -- KV byte faults through the real codec -----------------------------------


def _slab_bytes():
    rs = np.random.RandomState(0)
    slab = {"k": rs.randn(2, 1, 2, 8, 4).astype(np.float32),
            "v": rs.randn(2, 1, 2, 8, 4).astype(np.float32)}
    buf = io.BytesIO()
    for frame in encode_slab({"tokens": [1, 2]}, slab, chunk_bytes=64):
        buf.write(frame)
    return buf.getvalue()


def test_kv_fault_corrupt_hits_real_checksum():
    raw = _slab_bytes()
    kv = KVFaults([FaultRule(kv_corrupt_rate=1.0)], seed=3, addr="p:1")
    read = kv.wrap_read(io.BytesIO(raw).read)
    with pytest.raises((ChecksumError, DisaggError)):
        decode_slab(read)
    assert kv.injected["corrupt"] == 1


def test_kv_fault_truncate_hits_real_truncation():
    raw = _slab_bytes()
    kv = KVFaults([FaultRule(kv_truncate_rate=1.0)], seed=3, addr="p:1")
    with pytest.raises(TruncatedStream):
        decode_slab(kv.wrap_read(io.BytesIO(raw).read))
    assert kv.injected["truncate"] == 1


def test_kv_fault_drop_refused_downstream():
    raw = _slab_bytes()
    kv = KVFaults([FaultRule(kv_drop_rate=1.0)], seed=5, addr="p:1")
    with pytest.raises(DisaggError):  # checksum/length/truncated — typed
        decode_slab(kv.wrap_read(io.BytesIO(raw).read))
    assert kv.injected["drop"] == 1


def test_kv_fault_deterministic_per_seed():
    raw = _slab_bytes()

    def run(seed):
        kv = KVFaults([FaultRule(kv_corrupt_rate=0.5)], seed=seed, addr="p:1")
        outcomes = []
        for _ in range(8):
            try:
                decode_slab(kv.wrap_read(io.BytesIO(raw).read))
                outcomes.append("ok")
            except DisaggError as e:
                outcomes.append(type(e).__name__)
        return outcomes

    assert run(11) == run(11)
    assert "ok" in run(11) and "ChecksumError" in run(11)


def test_kv_fault_connect_refused_and_off_path():
    kv = KVFaults([FaultRule(kv_connect_refused_rate=1.0)], seed=1, addr="p")
    with pytest.raises(ConnectionRefusedError):
        kv.before_connect()
    assert not kv.connectable()
    # no byte-fault rules -> the reader passes through untouched
    kv2 = KVFaults([FaultRule(kv_connect_refused_rate=1.0)], seed=1, addr="p")
    read = io.BytesIO(b"xyz").read
    assert kv2.wrap_read(read) is read


def test_fault_injector_kv_grammar_and_scheduler_hook():
    inj = FaultInjector(
        [{"unit": "kv:10.0.0.5:9001", "kv_corrupt_rate": 0.5},
         {"unit": "clf", "error_rate": 0.3}],
        seed=7,
        scheduler={"die_after_polls": 3, "times": 2},
    )
    assert inj.kv_faults_for("10.0.0.5:9001") is not None
    assert inj.kv_faults_for("10.0.0.6:9001") is None  # wrong peer
    # a plain unit rule never becomes a kv fault
    assert not FaultRule(error_rate=0.3).has_kv_faults()
    hook = inj.scheduler_hook()
    hook(1)
    hook(2)
    with pytest.raises(Exception, match="poll death 1/2"):
        hook(3)
    hook(4)  # spaced: next death at last+3
    with pytest.raises(Exception, match="poll death 2/2"):
        hook(6)
    hook(9)  # budget spent: no further deaths
    assert FaultInjector([], seed=0).scheduler_hook() is None


# -- scheduler supervision (batcher level) -----------------------------------


def test_supervised_restart_fails_inflight_typed_then_recovers(
    model_and_params,
):
    model, params = model_and_params
    b = _fast_batcher(model, params, restart_budget=2)
    try:
        ref = b.generate([1, 2, 3], max_new_tokens=6)
        # arm BEFORE the admit, firing on the first poll that sees a
        # live lane: the death is guaranteed to land mid-decode (waiting
        # to arm until the main thread OBSERVES the lane raced the tiny
        # model's generation — the request could finish first)
        state = {"armed": True}

        def hook(_poll):
            if state["armed"] and b._active:
                state["armed"] = False
                raise RuntimeError("injected poll death")

        b.fault_hook = hook
        fut = b.submit([4, 5, 6], max_new_tokens=40)
        with pytest.raises(BatcherDead) as ei:
            fut.result(timeout=60)
        assert ei.value.retry_after_s > 0
        assert ei.value.status == 503
        # supervised recovery: health returns, service is byte-identical
        deadline = time.monotonic() + 30
        while b.health != "serving" and time.monotonic() < deadline:
            time.sleep(0.02)
        assert b.health == "serving"
        assert b.stats["batcher_restarts"] == 1
        assert b.generate([1, 2, 3], max_new_tokens=6) == ref
        recs = [e for e in b.flight.dump()["entries"]
                if e["type"] == "batcher_restart"]
        assert recs and recs[0]["outcome"] == "restarting"
    finally:
        b.close()


def test_queued_requests_survive_a_restart(model_and_params):
    """Queued-not-admitted work is host-side only: a supervised restart
    serves it afterwards instead of failing it with the in-flight."""
    model, params = model_and_params
    b = _fast_batcher(model, params, restart_budget=2)
    try:
        ref = b.generate([7, 8, 9], max_new_tokens=4)
        hook, _ = _die_once()
        b.fault_hook = hook  # dies on the NEXT poll, before any admit
        fut = b.submit([7, 8, 9], max_new_tokens=4)
        assert fut.result(timeout=60) == ref
        assert b.stats["batcher_restarts"] == 1
    finally:
        b.close()


def test_budget_exhaustion_latches_dead_and_typed_everywhere(
    model_and_params,
):
    model, params = model_and_params
    b = _fast_batcher(model, params, restart_budget=0)
    try:
        b.generate([1, 2], max_new_tokens=2)
        b.fault_hook = lambda n: (_ for _ in ()).throw(
            RuntimeError("always dies")
        )
        fut = b.submit([1, 2, 3], max_new_tokens=4)
        with pytest.raises(BatcherDead):
            fut.result(timeout=60)
        deadline = time.monotonic() + 20
        while b.health != "dead" and time.monotonic() < deadline:
            time.sleep(0.02)
        assert b.health == "dead"
        assert b.stats["batcher_restarts"] == 0
        # every entrypoint refuses typed, carrying retry_after_s
        for call in (
            lambda: b.submit([1, 2]),
            lambda: b.export_prefill([1, 2]),
            lambda: b.admit_remote({"k": None, "v": None}, {"tokens": [1]}),
            lambda: b.request_weight_swap(params),
        ):
            with pytest.raises(BatcherDead) as ei:
                call()
            assert ei.value.retry_after_s > 0
        recs = [e for e in b.flight.dump()["entries"]
                if e["type"] == "batcher_restart"]
        assert recs[-1]["outcome"] == "latched_dead"
    finally:
        b.close()


def test_restart_resets_prefix_index(model_and_params):
    """The rebuilt loop must never splice pre-crash radix slabs (they
    referenced the invalidated cache stream): the index is reset and
    re-fills from post-restart completions."""
    model, params = model_and_params
    b = _fast_batcher(
        model, params, restart_budget=2, prefix_cache_hbm_bytes=1 << 20,
        prefix_cache_min_tokens=4,
    )
    try:
        prompt = list(range(1, 9))
        ref = b.generate(prompt, max_new_tokens=4)
        deadline = time.monotonic() + 10
        while b._prefix_index.covered_len(prompt) == 0 and (
            time.monotonic() < deadline
        ):
            time.sleep(0.02)
        assert b._prefix_index.covered_len(prompt) > 0
        hook, _ = _die_once()
        b.fault_hook = hook
        b.submit([9, 9], max_new_tokens=2)  # drive a poll -> death
        deadline = time.monotonic() + 30
        while b.stats["batcher_restarts"] == 0 and (
            time.monotonic() < deadline
        ):
            time.sleep(0.02)
        assert b._prefix_index.covered_len(prompt) == 0  # fresh index
        assert b.generate(prompt, max_new_tokens=4) == ref
    finally:
        b.close()


def test_dead_batcher_maps_to_503_with_retry_after(model_and_params):
    """The engine contract: BatcherDead carries a wire status, so the
    executor surfaces it as UnitCallError(503) with retry_after_s — the
    REST front then adds the Retry-After header (chaos smoke asserts
    the live header end to end)."""
    import asyncio

    from seldon_core_tpu.graph.client import UnitCallError
    from seldon_core_tpu.graph.service import EngineApp
    from seldon_core_tpu.graph.spec import PredictorSpec

    class DeadUnit:
        def predict(self, X, names, meta=None):
            raise BatcherDead("continuous batcher died; restarting",
                              retry_after_s=2.5)

    spec = PredictorSpec.from_dict({
        "name": "p",
        "graph": {"name": "g", "type": "MODEL"},
    })
    app = EngineApp(spec, registry={"g": DeadUnit()})

    async def go():
        with pytest.raises(UnitCallError) as ei:
            await app.predict({"jsonData": {"prompt_tokens": [[1]]}})
        assert ei.value.status == 503
        assert ei.value.retry_after_s == 2.5

    asyncio.run(go())


def test_health_status_flips_readiness(model_and_params):
    from seldon_core_tpu.servers.generateserver import GenerateServer

    model, params = model_and_params
    srv = GenerateServer.__new__(GenerateServer)
    assert srv.health_status() == "ok"  # not loaded: lenient
    srv.batcher = _fast_batcher(model, params, restart_budget=0)
    try:
        assert srv.health_status() == "ok"
        srv.batcher.health = "restarting"
        with pytest.raises(RuntimeError, match="restarting"):
            srv.health_status()
        srv.batcher.health = "dead"
        with pytest.raises(RuntimeError, match="dead"):
            srv.health_status()
        srv.batcher.health = "serving"
    finally:
        srv.batcher.close()


# -- server-level degradation + streaming faults -----------------------------


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    from seldon_core_tpu.testing import write_model_dir

    root = tmp_path_factory.mktemp("ft-model")
    return write_model_dir(str(root), "llm", {
        "vocab_size": 256, "d_model": 32, "n_layers": 2, "n_heads": 2,
        "n_kv_heads": 2, "d_ff": 64, "max_seq": 64,
    })


def test_decode_degrades_to_local_prefill_byte_identical(model_dir):
    from seldon_core_tpu.servers.generateserver import GenerateServer

    uni = GenerateServer(model_uri=model_dir, slots=2, steps_per_poll=4)
    uni.load()
    pf = GenerateServer(model_uri=model_dir, role="prefill")
    pf.load()
    dec = GenerateServer(model_uri=model_dir, slots=2, steps_per_poll=4,
                         role="decode", peer_eject_backoff_s=30.0)
    dec.load()
    dec.set_peer(pf)
    body = {"prompt_tokens": [[5, 6, 7, 8]], "max_new_tokens": 6,
            "temperature": 0.0}
    try:
        ref = uni.predict(dict(body), [])["tokens"]
        assert dec.predict(dict(body), [])["tokens"] == ref
        # kill the (only) prefill peer: loopback probes/exports now fail
        pf.close()
        for _ in range(2):
            assert dec.predict(dict(body), [])["tokens"] == ref
        st = dec.batcher.stats
        assert st["degraded_local_prefill"] >= 1
        assert st["peer_ejections"] >= 1
        recs = {e["type"] for e in dec.batcher.flight.dump()["entries"]}
        assert "peer_ejected" in recs
        assert "degraded_local_prefill" in recs
        # the recovery counters ride metrics() as deltas
        keys = {m["key"] for m in dec.metrics()}
        assert "gen_peer_ejections" in keys
        assert "gen_degraded_local_prefill" in keys
        assert "gen_batcher_healthy" in keys
    finally:
        for s in (uni, dec):
            s.close()


@pytest.fixture(scope="module")
def two_peer_pool(model_dir):
    """A decode server in front of two prefill peers over TCP, and the
    unified server's answers to the same prompts."""
    from seldon_core_tpu.servers.generateserver import GenerateServer
    from seldon_core_tpu.serving.disagg import PrefillTransportServer

    body = {"prompt_tokens": [[5, 6, 7, 8], [9, 10, 11], [3, 1, 4, 1, 5]],
            "max_new_tokens": 6, "temperature": 0.0}
    uni = GenerateServer(model_uri=model_dir, slots=2, steps_per_poll=4)
    uni.load()
    refs = uni.predict(dict(body), [])["tokens"]
    uni.close()
    prefills = [GenerateServer(model_uri=model_dir, role="prefill")
                for _ in range(2)]
    for pf in prefills:
        pf.load()
    listeners = [PrefillTransportServer(pf, port=0) for pf in prefills]
    addrs = [f"127.0.0.1:{l.port}" for l in listeners]
    dec = GenerateServer(model_uri=model_dir, slots=2, steps_per_poll=4,
                         role="decode", peer=",".join(addrs),
                         peer_eject_backoff_s=30.0)
    dec.load()
    yield dec, addrs, body, refs
    dec.close()
    for l in listeners:
        l.close()
    for pf in prefills:
        pf.close()


@pytest.mark.parametrize("fault,ejects", [
    (FaultRule(kv_connect_refused_rate=1.0), True),
    (FaultRule(kv_corrupt_rate=1.0), True),
    (FaultRule(kv_truncate_rate=1.0), True),
    (FaultRule(kv_drop_rate=1.0), True),
    (FaultRule(kv_stall_rate=1.0, kv_stall_ms=50.0), False),
], ids=["connect_refused", "corrupt", "truncate", "frame_drop", "stall"])
def test_second_peer_absorbs_a_faulted_first(two_peer_pool, fault, ejects):
    """Every transfer from the first peer faulted one way: each request
    still completes byte-identical to the unified server's, through the
    clean second peer (never by local prefill), and the faulted peer is
    ejected where the fault breaks the transfer (a stall only delays)."""
    dec, addrs, body, refs = two_peer_pool
    dec._kv_client.close()
    dec.set_peer(",".join(addrs))  # a fresh client: no peer ejected yet
    first = next(p for p in dec._kv_client.peers if p.addr == addrs[0])
    first.transport._fault = KVFaults([fault], seed=7, addr=first.addr)
    before = dict(dec.batcher.stats)
    assert dec.predict(dict(body), [])["tokens"] == refs
    after = dec.batcher.stats
    assert (after["peer_ejections"] > before["peer_ejections"]) is ejects
    assert after["degraded_local_prefill"] == before["degraded_local_prefill"]


def test_stream_midstream_batcher_death_surfaces_typed_no_hang(model_dir):
    """The streaming satellite: a fault AFTER response bytes exist must
    surface a typed error to the stream consumer — never a hang. The
    consumer reads real token spans, then the scheduler loop is killed;
    the iterator must terminate promptly with BatcherDead."""
    from seldon_core_tpu.servers.generateserver import GenerateServer

    srv = GenerateServer(model_uri=model_dir, slots=2, steps_per_poll=2,
                         pipeline_depth=1, restart_budget=1)
    srv.load()
    try:
        # a long-but-legal budget (prompt 3 + 58 <= max_seq 64): the
        # overrun case is now a typed 413 at submit, not a silent clamp
        handle = srv.stream({"prompt_tokens": [3, 4, 5],
                             "max_new_tokens": 58})
        got_spans = []
        err = None
        done = threading.Event()

        def consume():
            nonlocal err
            try:
                for chunk in handle.chunks:
                    got_spans.append(chunk)
            except Exception as e:  # noqa: BLE001 - the assertion target
                err = e
            finally:
                done.set()

        t = threading.Thread(target=consume, daemon=True)
        t.start()
        deadline = time.monotonic() + 20
        while not got_spans and time.monotonic() < deadline:
            time.sleep(0.01)
        assert got_spans, "stream produced no bytes before the fault"
        # response bytes exist NOW — kill the scheduler loop
        hook = lambda n: (_ for _ in ()).throw(  # noqa: E731
            RuntimeError("injected mid-stream death")
        )
        srv.batcher.fault_hook = hook
        assert done.wait(timeout=60), "stream consumer hung after the fault"
        assert isinstance(err, BatcherDead)
        assert err.retry_after_s > 0
        srv.batcher.fault_hook = None
    finally:
        srv.close()


def test_stream_setup_transport_fault_degrades_not_hangs(model_dir):
    """Mid-transfer truncation on the STREAMING decode path, before any
    response bytes: with the pool's lone peer ejected the stream
    degrades to local prefill and still yields byte-identical output —
    and never hangs."""
    from seldon_core_tpu.servers.generateserver import GenerateServer
    from seldon_core_tpu.serving.disagg import PrefillTransportServer

    uni = GenerateServer(model_uri=model_dir, slots=2, steps_per_poll=4)
    uni.load()
    pf = GenerateServer(model_uri=model_dir, role="prefill")
    pf.load()
    listener = PrefillTransportServer(pf, port=0)
    dec = GenerateServer(model_uri=model_dir, slots=2, steps_per_poll=4,
                         role="decode", peer_eject_backoff_s=30.0)
    dec.load()
    dec.set_peer(f"127.0.0.1:{listener.port}")
    # every transfer truncates mid-stream (typed TruncatedStream inside)
    for peer in dec._kv_client.peers:
        peer.transport._fault = KVFaults(
            [FaultRule(kv_truncate_rate=1.0)], seed=3, addr=peer.addr
        )
    try:
        ref = uni.predict({"prompt_tokens": [[5, 6, 7, 8]],
                           "max_new_tokens": 6, "temperature": 0.0},
                          [])["tokens"][0]
        t0 = time.monotonic()
        handle = dec.stream({"prompt_tokens": [5, 6, 7, 8],
                             "max_new_tokens": 6})
        final = None
        for chunk in handle.chunks:
            if chunk.get("done"):
                final = chunk["tokens"]
        assert final == ref
        assert time.monotonic() - t0 < 60.0
        assert dec.batcher.stats["peer_ejections"] >= 1
        assert dec.batcher.stats["degraded_local_prefill"] >= 1
    finally:
        listener.close()
        for s in (uni, pf, dec):
            s.close()
