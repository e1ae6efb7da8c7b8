"""Disaggregated prefill/decode serving: KV-slab wire codec, transports,
remote admits, role-split GenerateServers, and reconciler pool splitting.

Tiers: codec unit tests (round-trip across dtypes, corruption/truncation
refusals, weight-version mismatch), batcher-level handoff equivalence
(greedy byte-identity vs unified, with and without decode-side prefix
hits), server-level roles over loopback AND TCP, and the control-plane
pool split with independent scaling.
"""

import asyncio
import io
import struct
import time

import numpy as np
import pytest

from seldon_core_tpu.models.llm import DecoderLM
from seldon_core_tpu.serving.continuous import ContinuousBatcher
from seldon_core_tpu.serving.disagg import (
    ChecksumError,
    DisaggError,
    LoopbackTransport,
    PrefillTransportServer,
    PrefixGone,
    TcpKVClient,
    TruncatedStream,
    WeightVersionMismatch,
    decode_slab,
    encode_slab,
    prompt_hash,
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


def _slab(dtype, L=2, kv=2, w=8, dh=4, seed=0):
    rs = np.random.RandomState(seed)
    shape = (L, 1, kv, w, dh)
    return {
        "k": rs.randn(*shape).astype(dtype),
        "v": rs.randn(*shape).astype(dtype),
    }


def _wire(meta, slab, chunk_bytes=64):
    buf = io.BytesIO()
    for frame in encode_slab(meta, slab, chunk_bytes=chunk_bytes):
        buf.write(frame)
    return buf.getvalue()


# -- wire codec --------------------------------------------------------------


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_codec_roundtrip_across_dtypes(dtype):
    import ml_dtypes

    np_dtype = np.dtype(
        ml_dtypes.bfloat16 if dtype == "bfloat16" else np.float32
    )
    slab = _slab(np_dtype)
    meta = {"tokens": [1, 2, 3], "first_token": 7, "weight_version": 0}
    raw = _wire(meta, slab)
    got_meta, got = decode_slab(io.BytesIO(raw).read)
    assert got_meta["tokens"] == [1, 2, 3]
    assert got_meta["slab_dtype"] == str(np_dtype)
    for name in ("k", "v"):
        assert got[name].dtype == np_dtype
        np.testing.assert_array_equal(got[name], slab[name])


def test_codec_corruption_rejected_by_checksum():
    raw = bytearray(_wire({"tokens": [1]}, _slab(np.float32)))
    # flip a byte deep in the payload region (past header), leaving the
    # frame lengths intact — only the CRC can catch it
    raw[len(raw) // 2] ^= 0xFF
    with pytest.raises(ChecksumError):
        decode_slab(io.BytesIO(bytes(raw)).read)


def test_codec_header_corruption_rejected():
    """A bit flip landing in the JSON header (e.g. first_token) must be
    caught by the header CRC — a still-valid-JSON header would otherwise
    seed a lane with silently wrong output."""
    raw = bytearray(_wire({"tokens": [1], "first_token": 1234},
                          _slab(np.float32)))
    ix = raw.index(b"1234")  # the first_token digits inside the header
    raw[ix] = ord("9")
    with pytest.raises(ChecksumError, match="header"):
        decode_slab(io.BytesIO(bytes(raw)).read)


def test_codec_truncated_stream_clean_error():
    raw = _wire({"tokens": [1]}, _slab(np.float32))
    for cut in (2, len(raw) // 3, len(raw) - 3):
        with pytest.raises(TruncatedStream):
            decode_slab(io.BytesIO(raw[:cut]).read)


def test_codec_bad_magic_and_version():
    raw = _wire({"tokens": [1]}, _slab(np.float32))
    with pytest.raises(DisaggError, match="magic"):
        decode_slab(io.BytesIO(b"XXXX" + raw[4:]).read)


def test_codec_error_frame_roundtrips_typed():
    from seldon_core_tpu.serving.disagg import encode_error

    raw = encode_error(WeightVersionMismatch("stale"))
    with pytest.raises(WeightVersionMismatch, match="stale"):
        decode_slab(io.BytesIO(raw).read)


# -- batcher handoff ---------------------------------------------------------


def test_export_admit_greedy_identical(model_and_params):
    """The acceptance bit at the scheduler level: export on one batcher,
    admit on another, greedy output byte-identical to unified — through
    the full wire codec."""
    model, params = model_and_params
    prompt = [3, 17, 42, 99, 7]
    uni = ContinuousBatcher(model, params, slots=2, max_seq=64,
                            prefill_buckets=(8, 16, 32))
    ref = uni.generate(prompt, max_new_tokens=10)
    uni.close()

    pf = ContinuousBatcher(model, params, slots=1, max_seq=64,
                           prefill_buckets=(8, 16, 32))
    dec = ContinuousBatcher(model, params, slots=2, max_seq=64,
                            prefill_buckets=(8, 16, 32))
    try:
        meta, slab = pf.export_prefill(prompt, max_new_tokens=10)
        meta2, slab2 = decode_slab(io.BytesIO(_wire(meta, slab)).read)
        got = dec.admit_remote(slab2, meta2).result(timeout=120)
        assert got == ref
        assert pf.stats["kv_exports"] == 1
        assert dec.stats["kv_imports"] == 1
        assert dec.stats["kv_import_bytes"] == pf.stats["kv_export_bytes"]
    finally:
        pf.close()
        dec.close()


def test_export_chunked_staging_path_identical(model_and_params):
    """A prefill-role batcher with prefill_chunk set builds the slab via
    the PR 3 staging path; the decode side must still match unified."""
    model, params = model_and_params
    prompt = list(range(1, 25))  # bucket 32, chunked by 8
    uni = ContinuousBatcher(model, params, slots=2, max_seq=64,
                            prefill_buckets=(8, 16, 32))
    ref = uni.generate(prompt, max_new_tokens=8)
    uni.close()
    pf = ContinuousBatcher(model, params, slots=1, max_seq=64,
                           prefill_buckets=(8, 16, 32), prefill_chunk=8)
    dec = ContinuousBatcher(model, params, slots=2, max_seq=64,
                            prefill_buckets=(8, 16, 32))
    try:
        meta, slab = pf.export_prefill(prompt, max_new_tokens=8)
        assert meta["bucket"] == 32
        assert pf.stats["prefill_chunks"] >= 3
        got = dec.admit_remote(slab, meta).result(timeout=120)
        assert got == ref
    finally:
        pf.close()
        dec.close()


def test_remote_admit_prefix_dedup_identical_and_counted(model_and_params):
    """Suffix-only transfer over a decode-side radix hit: greedy bytes
    identical to unified, cache_hit_tokens reported on the request, and
    kv_transfer_bytes_saved counts the skipped wire bytes."""
    model, params = model_and_params
    system = list(range(1, 17))
    p1 = system + [50, 51, 52]
    p2 = system + [60, 61]
    uni = ContinuousBatcher(model, params, slots=2, max_seq=64,
                            prefill_buckets=(8, 16, 32),
                            prefix_cache_hbm_bytes=1 << 20)
    ref1 = uni.generate(p1, max_new_tokens=8)
    ref2 = uni.generate(p2, max_new_tokens=8)
    uni.close()

    pf = ContinuousBatcher(model, params, slots=1, max_seq=64,
                           prefill_buckets=(8, 16, 32))
    dec = ContinuousBatcher(model, params, slots=2, max_seq=64,
                            prefill_buckets=(8, 16, 32),
                            prefix_cache_hbm_bytes=1 << 20)
    try:
        def remote(p):
            covered = dec.remote_covered_len(p)
            meta, slab = pf.export_prefill(
                p, max_new_tokens=8, covered_len=covered
            )
            fut = dec.admit_remote(slab, meta)
            return fut.result(timeout=120), fut.gen_request, covered

        got1, req1, c1 = remote(p1)
        assert got1 == ref1 and c1 == 0
        # the completed request publishes its prompt K/V; wait for it
        deadline = time.monotonic() + 10.0
        while dec.remote_covered_len(p2) == 0 and time.monotonic() < deadline:
            time.sleep(0.02)
        got2, req2, c2 = remote(p2)
        assert got2 == ref2
        assert c2 >= 16
        assert req2.cache_hit_tokens == c2
        assert dec.stats["kv_transfer_bytes_saved"] > 0
        # the suffix slab really was smaller on the wire
        assert dec.stats["kv_import_bytes"] < 2 * pf.stats["kv_export_bytes"]
    finally:
        pf.close()
        dec.close()


def test_remote_admit_weight_version_mismatch_refused(model_and_params):
    """A hot-swap landing between prefill and admit makes the slab
    stale: the admit must refuse with the typed error, and the decode
    pool keeps serving (no half-admitted lane)."""
    model, params = model_and_params
    pf = ContinuousBatcher(model, params, slots=1, max_seq=64,
                           prefill_buckets=(8, 16, 32))
    dec = ContinuousBatcher(model, params, slots=2, max_seq=64,
                            prefill_buckets=(8, 16, 32))
    try:
        meta, slab = pf.export_prefill([1, 2, 3, 4], max_new_tokens=6)
        dec.request_weight_swap(model.init_params(1)).result(timeout=60)
        with pytest.raises(WeightVersionMismatch):
            dec.admit_remote(slab, meta)
        assert dec.stats["kv_imports"] == 0
        assert not dec._active
        # a fresh slab under the new version still admits fine
        pf2 = ContinuousBatcher(
            model, model.init_params(1), slots=1, max_seq=64,
            prefill_buckets=(8, 16, 32),
        )
        meta2, slab2 = pf2.export_prefill([1, 2, 3, 4], max_new_tokens=6)
        meta2["weight_version"] = dec.weight_version
        out = dec.admit_remote(slab2, meta2).result(timeout=120)
        assert len(out) == 4 + 6
        pf2.close()
    finally:
        pf.close()
        dec.close()


def test_remote_admit_truncated_slab_no_half_admitted_lane(model_and_params):
    """A truncated stream dies in the codec, before admit_remote ever
    runs — and a corrupt-meta admit raises before any lane state
    exists; the decode pool stays fully serviceable either way."""
    model, params = model_and_params
    pf = ContinuousBatcher(model, params, slots=1, max_seq=64,
                           prefill_buckets=(8, 16, 32))
    dec = ContinuousBatcher(model, params, slots=2, max_seq=64,
                            prefill_buckets=(8, 16, 32))
    try:
        meta, slab = pf.export_prefill([9, 8, 7], max_new_tokens=6)
        raw = _wire(meta, slab)
        with pytest.raises(TruncatedStream):
            decode_slab(io.BytesIO(raw[: len(raw) - 5]).read)
        # wrong-shape slab: typed refusal, nothing half-admitted
        bad = {"k": np.asarray(slab["k"])[:, :, :, :-1, :],
               "v": np.asarray(slab["v"])[:, :, :, :-1, :]}
        with pytest.raises(DisaggError, match="shape"):
            dec.admit_remote(bad, meta)
        assert not dec._active and dec.stats["kv_imports"] == 0
        # the lane pool still serves both remote and local traffic
        got = dec.admit_remote(slab, meta).result(timeout=120)
        ref = dec.generate([9, 8, 7], max_new_tokens=6)
        assert got == ref
    finally:
        pf.close()
        dec.close()


def test_remote_admit_prefix_gone_typed(model_and_params):
    """A suffix-only slab whose donor prefix is not resident fails the
    admit with PrefixGone (the retry trigger), never a corrupt lane."""
    model, params = model_and_params
    pf = ContinuousBatcher(model, params, slots=1, max_seq=64,
                           prefill_buckets=(8, 16, 32))
    dec = ContinuousBatcher(model, params, slots=2, max_seq=64,
                            prefill_buckets=(8, 16, 32),
                            prefix_cache_hbm_bytes=1 << 20)
    try:
        p = list(range(1, 20))
        meta, slab = pf.export_prefill(p, max_new_tokens=6, covered_len=16)
        fut = dec.admit_remote(slab, meta)
        with pytest.raises(PrefixGone):
            fut.result(timeout=120)
        assert not dec._active
        # no-prefix-cache decode pool refuses synchronously
        dec2 = ContinuousBatcher(model, params, slots=2, max_seq=64,
                                 prefill_buckets=(8, 16, 32))
        with pytest.raises(PrefixGone):
            dec2.admit_remote(slab, meta)
        dec2.close()
    finally:
        pf.close()
        dec.close()


def test_remote_admit_flight_records_and_stats(model_and_params):
    """kv_export lands in the prefill-side ring, remote_insert in the
    decode-side ring; flight_report renders both."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))
    import flight_report

    model, params = model_and_params
    pf = ContinuousBatcher(model, params, slots=1, max_seq=64,
                           prefill_buckets=(8, 16, 32))
    dec = ContinuousBatcher(model, params, slots=2, max_seq=64,
                            prefill_buckets=(8, 16, 32))
    try:
        meta, slab = pf.export_prefill([4, 5, 6], max_new_tokens=4)
        dec.admit_remote(slab, meta).result(timeout=120)
        exp = [e for e in pf.flight.dump()["entries"]
               if e["type"] == "kv_export"]
        ins = [e for e in dec.flight.dump()["entries"]
               if e["type"] == "remote_insert"]
        assert exp and exp[0]["bytes"] > 0
        assert ins and ins[0]["tokens"] == 3
        text = flight_report.render({"units": {
            "prefill": pf.flight.dump(), "decode": dec.flight.dump(),
        }})
        assert "kv export (prefill pool)" in text
        assert "remote inserts (decode pool)" in text
    finally:
        pf.close()
        dec.close()


# -- server roles over both transports ---------------------------------------


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    from seldon_core_tpu.testing import write_model_dir

    root = tmp_path_factory.mktemp("disagg-model")
    return write_model_dir(str(root), "llm", {
        "vocab_size": 256, "d_model": 32, "n_layers": 2, "n_heads": 2,
        "n_kv_heads": 2, "d_ff": 64, "max_seq": 64,
    })


def test_server_roles_loopback_and_tcp_identical(model_dir):
    from seldon_core_tpu.servers.generateserver import GenerateServer

    uni = GenerateServer(model_uri=model_dir, slots=2, steps_per_poll=4)
    uni.load()
    pf = GenerateServer(model_uri=model_dir, role="prefill")
    pf.load()
    listener = PrefillTransportServer(pf, port=0)
    dec_lo = GenerateServer(model_uri=model_dir, slots=2, steps_per_poll=4,
                            role="decode")
    dec_lo.load()
    dec_lo.set_peer(pf)
    dec_tcp = GenerateServer(
        model_uri=model_dir, slots=2, steps_per_poll=4, role="decode",
        peer=f"127.0.0.1:{listener.port}",
    )
    dec_tcp.load()
    try:
        body = {"prompt_tokens": [[5, 6, 7, 8], [9, 10, 11]],
                "max_new_tokens": 6, "temperature": 0.0}
        ref = uni.predict(dict(body), [])["tokens"]
        assert dec_lo.predict(dict(body), [])["tokens"] == ref
        assert dec_tcp.predict(dict(body), [])["tokens"] == ref
        # prefill-role members never serve generate traffic directly
        with pytest.raises(RuntimeError, match="prefill"):
            pf.predict(dict(body), [])
        # the kv transfer counters ship through metrics()
        keys = {m["key"] for m in dec_lo.metrics()}
        assert "gen_kv_import_slabs" in keys
        assert "gen_kv_import_bytes" in keys
        pkeys = {m["key"] for m in pf.metrics()}
        assert "gen_kv_export_slabs" in pkeys
    finally:
        listener.close()
        for s in (uni, pf, dec_lo, dec_tcp):
            s.close()


def test_server_decode_stream_over_loopback(model_dir):
    from seldon_core_tpu.servers.generateserver import GenerateServer

    uni = GenerateServer(model_uri=model_dir, slots=2, steps_per_poll=4)
    uni.load()
    pf = GenerateServer(model_uri=model_dir, role="prefill")
    pf.load()
    dec = GenerateServer(model_uri=model_dir, slots=2, steps_per_poll=4,
                         role="decode")
    dec.load()
    dec.set_peer(pf)
    try:
        ref = uni.predict({"prompt_tokens": [[5, 6, 7, 8]],
                           "max_new_tokens": 6, "temperature": 0.0},
                          [])["tokens"][0]
        handle = dec.stream({"prompt_tokens": [5, 6, 7, 8],
                             "max_new_tokens": 6})
        final = None
        spans = []
        for chunk in handle.chunks:
            if chunk.get("done"):
                final = chunk["tokens"]
            else:
                spans.extend(chunk["tokens"])
        assert final == ref
        assert final[-len(spans):] == spans  # streamed spans == tail
    finally:
        for s in (uni, pf, dec):
            s.close()


def test_prefill_listener_sheds_over_capacity(model_dir):
    """The prefill listener bounds concurrent handlers: with every slot
    held, a new transfer gets an immediate typed shed frame instead of
    queueing a device forward behind the listener."""
    from seldon_core_tpu.servers.generateserver import GenerateServer

    pf = GenerateServer(model_uri=model_dir, role="prefill")
    pf.load()
    listener = PrefillTransportServer(pf, port=0, max_inflight=1)
    client = TcpKVClient(f"127.0.0.1:{listener.port}")
    try:
        assert listener._slots.acquire(blocking=False)  # hold the slot
        try:
            with pytest.raises(DisaggError, match="capacity"):
                client.prefill({"tokens": [1, 2, 3], "max_new_tokens": 4})
        finally:
            listener._slots.release()
        # slot free again: the same client serves normally
        meta, slab = client.prefill({"tokens": [1, 2, 3],
                                     "max_new_tokens": 4})
        assert meta["n_tokens"] == 3
    finally:
        listener.close()
        pf.close()


def test_tcp_client_unreachable_peer_typed(model_dir):
    client = TcpKVClient("127.0.0.1:1")  # nothing listens on port 1
    with pytest.raises(DisaggError, match="unreachable"):
        client.prefill({"tokens": [1, 2, 3]})


def test_loopback_transport_runs_the_codec(model_dir):
    """Loopback is not a shortcut: the slab must round-trip the real
    frames (a codec bug cannot hide behind in-process references)."""
    from seldon_core_tpu.servers.generateserver import GenerateServer

    pf = GenerateServer(model_uri=model_dir, role="prefill")
    pf.load()
    try:
        transport = LoopbackTransport(pf)
        meta, slab = transport.prefill({"tokens": [1, 2, 3],
                                        "max_new_tokens": 4})
        assert meta["prompt_hash"] == prompt_hash([1, 2, 3])
        assert meta["wire_version"] == 1
        assert isinstance(slab["k"], np.ndarray)
    finally:
        pf.close()


# -- graph spec + reconciler pool split --------------------------------------


def test_disagg_annotations_validate_strictly():
    from seldon_core_tpu.graph.spec import (
        GraphSpecError,
        PredictorSpec,
        parse_disagg_annotations,
        validate_predictor,
    )

    def spec(ann, graph=None):
        return PredictorSpec.from_dict({
            "name": "gen",
            "annotations": ann,
            "graph": graph or {
                "name": "g", "implementation": "GENERATE_SERVER",
                "modelUri": "/tmp/m",
            },
        })

    ok = spec({"seldon.io/disagg": "true",
               "seldon.io/disagg-prefill-replicas": "2",
               "seldon.io/disagg-decode-replicas": "3"})
    assert parse_disagg_annotations(ok) == (2, 3)
    assert parse_disagg_annotations(spec({})) is None
    # defaults: 1 prefill, decode = predictor replicas
    assert parse_disagg_annotations(
        spec({"seldon.io/disagg": "true"})
    ) == (1, 1)
    with pytest.raises(GraphSpecError, match="single-node"):
        validate_predictor(spec(
            {"seldon.io/disagg": "true"},
            graph={"name": "g", "implementation": "GENERATE_SERVER",
                   "modelUri": "/tmp/m",
                   "children": [{"name": "c", "type": "MODEL"}]},
        ))
    with pytest.raises(GraphSpecError, match="GENERATE_SERVER"):
        validate_predictor(spec(
            {"seldon.io/disagg": "true"},
            graph={"name": "g", "implementation": "JAX_SERVER",
                   "modelUri": "/tmp/m"},
        ))
    with pytest.raises(GraphSpecError, match=">= 1"):
        validate_predictor(spec({
            "seldon.io/disagg": "true",
            "seldon.io/disagg-decode-replicas": "0",
        }))
    with pytest.raises(GraphSpecError, match="malformed"):
        validate_predictor(spec({
            "seldon.io/disagg": "true",
            "seldon.io/disagg-prefill-replicas": "two",
        }))
    with pytest.raises(GraphSpecError, match="role"):
        validate_predictor(spec(
            {"seldon.io/disagg": "true"},
            graph={"name": "g", "implementation": "GENERATE_SERVER",
                   "modelUri": "/tmp/m",
                   "parameters": [{"name": "role", "value": "decode"}]},
        ))


def test_disagg_pool_scale_keeps_component_names():
    """Changing a pool-size annotation must not rename surviving
    components (spec_hash excludes the disagg replica annotations the
    same way it excludes `replicas`)."""
    from seldon_core_tpu.controlplane import SeldonDeployment

    def dep(decode):
        return SeldonDeployment.from_dict({
            "name": "d",
            "predictors": [{
                "name": "gen",
                "annotations": {
                    "seldon.io/disagg": "true",
                    "seldon.io/disagg-decode-replicas": str(decode),
                },
                "graph": {"name": "g", "implementation": "GENERATE_SERVER",
                          "modelUri": "/tmp/m"},
            }],
        })

    a, b = dep(2), dep(5)
    assert a.spec_hash(include_replicas=False) == b.spec_hash(
        include_replicas=False
    )
    assert a.spec_hash() != b.spec_hash()  # still a real spec change


def test_reconciler_splits_pools_and_scales_independently(model_dir):
    from seldon_core_tpu.controlplane import (
        DeploymentController,
        ResourceStore,
        SeldonDeployment,
    )
    from seldon_core_tpu.controlplane.runtime import InProcessRuntime

    def dep(prefill=1, decode=2):
        return SeldonDeployment.from_dict({
            "name": "disagg",
            "predictors": [{
                "name": "gen",
                "annotations": {
                    "seldon.io/disagg": "true",
                    "seldon.io/disagg-prefill-replicas": str(prefill),
                    "seldon.io/disagg-decode-replicas": str(decode),
                },
                "graph": {
                    "name": "g", "implementation": "GENERATE_SERVER",
                    "modelUri": model_dir,
                    "parameters": [
                        {"name": "slots", "value": "2", "type": "INT"},
                        {"name": "steps_per_poll", "value": "4",
                         "type": "INT"},
                    ],
                },
            }],
        })

    async def go():
        store = ResourceStore()
        ctl = DeploymentController(
            store, runtime=InProcessRuntime(open_ports=False)
        )
        d, _ = store.apply(dep())
        status = await ctl.reconcile(d.clone())
        assert status.state == "Available"
        # availability is judged against the DECODE pool
        assert status.predictor_status[0].replicas == 2
        names = sorted(ctl.components)
        prefill = [n for n in names if "/pf0/" in n]
        decode = [n for n in names if "/pf" not in n]
        assert len(prefill) == 1 and len(decode) == 2
        # prefill members are not routable; decode members are
        for n in prefill:
            assert not ctl.components[n][0].spec.routable
        for n in decode:
            assert ctl.components[n][0].spec.routable
        # a request through a decode engine round-trips the handoff
        handle = ctl.components[decode[0]][0]
        out = await handle.app.predict({"jsonData": {
            "prompt_tokens": [[5, 6, 7, 8]], "max_new_tokens": 6,
            "temperature": 0.0,
        }})
        assert len(out["jsonData"]["tokens"][0]) == 4 + 6
        # decode members are wired with the FULL peer candidate list
        # (failover transport), not one round-robin pick
        dec_handle = ctl.components[decode[0]][0]
        dec_spec = dec_handle.spec.engine_spec
        peer_param = next(
            p["value"] for p in dec_spec["graph"]["parameters"]
            if p["name"] == "peer"
        )
        assert len(peer_param.split(",")) == 1  # one prefill listener
        # scale the decode pool only: the prefill member AND the existing
        # decode members survive by name (no restarts)
        d2, _ = store.apply(dep(decode=3))
        await ctl.reconcile(d2.clone())
        names2 = sorted(ctl.components)
        assert [n for n in names2 if "/pf0/" in n] == prefill
        assert set(decode) <= set(names2)
        assert len([n for n in names2 if "/pf" not in n]) == 3
        # resize the PREFILL pool: the candidate set grows/shrinks but NO
        # decode survivor is renamed or re-pointed — the failover layer
        # owns peer selection at runtime, so a resize never restarts the
        # decode pool (new members pick up the full current list)
        d3, _ = store.apply(dep(prefill=2, decode=4))
        await ctl.reconcile(d3.clone())
        names3 = sorted(ctl.components)
        assert len([n for n in names3 if "/pf" in n]) == 2
        decode3 = [n for n in names3 if "/pf" not in n]
        assert len(decode3) == 4
        assert set(decode) <= set(names3)   # every survivor keeps its name
        # the member created in THIS reconcile (replica 3); replica 2 was
        # created under d2's single-listener world and keeps its list
        new_member = sorted(set(decode3) - set(decode))[-1]
        new_peers = next(
            p["value"]
            for p in ctl.components[new_member][0].spec.engine_spec[
                "graph"]["parameters"]
            if p["name"] == "peer"
        )
        assert len(new_peers.split(",")) == 2  # new member sees BOTH
        # every decode member still answers through the handoff
        out3 = await ctl.components[decode3[1]][0].app.predict({"jsonData": {
            "prompt_tokens": [[5, 6, 7, 8]], "max_new_tokens": 6,
            "temperature": 0.0,
        }})
        assert len(out3["jsonData"]["tokens"][0]) == 4 + 6
        await ctl.shutdown()

    asyncio.run(go())


def test_engine_metrics_kv_transfer_series():
    from seldon_core_tpu.graph.engine_metrics import MetricsRegistry

    reg = MetricsRegistry()
    reg.record_custom(
        [
            {"type": "COUNTER", "key": "gen_kv_export_bytes", "value": 100},
            {"type": "COUNTER", "key": "gen_kv_import_bytes", "value": 80},
            {"type": "COUNTER", "key": "gen_kv_transfer_bytes_saved",
             "value": 20},
        ],
        {"deployment": "d"},
    )
    expo = reg.expose()
    assert 'seldon_engine_kv_transfer_bytes{deployment="d",direction="export"} 100' in expo
    assert 'seldon_engine_kv_transfer_bytes{deployment="d",direction="import"} 80' in expo
    assert reg.counter_total(
        "seldon_engine_kv_transfer_bytes_saved", {"deployment": "d"}
    ) == 20.0
