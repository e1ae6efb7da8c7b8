"""seldon_core_tpu/testing.py held directly: the three helpers the
socket-level tests, the tools/*_smoke.py scripts and chip_smoke.py stand
on (``free_port``, ``write_model_dir``, ``EngineHarness``)."""

import json
import os
import socket
import subprocess
import sys

import numpy as np

from _net import grpc_predict, post_predictions

from seldon_core_tpu.graph.engine_metrics import MetricsRegistry
from seldon_core_tpu.payload import json_to_proto, proto_to_json
from seldon_core_tpu.proto import prediction_pb2 as pb
from seldon_core_tpu.testing import EngineHarness, free_port, write_model_dir
from seldon_core_tpu.user_model import SeldonComponent

REQUESTS = "seldon_api_engine_server_requests"
BODY = {"data": {"ndarray": [[1.0, 2.0], [3.0, 4.0]]}}


class Doubler(SeldonComponent):
    def predict(self, X, names, meta=None):
        return np.asarray(X) * 2


def rest_predict(port):
    status, answer = post_predictions(port, json.dumps(BODY).encode())
    assert status == 200, answer[:200]
    return json.loads(answer)


def refuses(port):
    try:
        socket.create_connection(("127.0.0.1", port), 1.0).close()
    except OSError:
        return True
    return False


def test_free_port_gives_ports_that_bind_at_once():
    ports = [free_port() for _ in range(8)]
    for port in ports:
        assert 0 < port < 65536
        s = socket.socket()
        try:
            s.bind(("127.0.0.1", port))
        finally:
            s.close()


def test_write_model_dir_holds_family_and_config_and_loads(tmp_path):
    from seldon_core_tpu.servers.jaxserver import JAXServer

    cfg = {"in_features": 4, "hidden": [8], "num_classes": 3, "seed": 0}
    model_dir = write_model_dir(str(tmp_path), "mlp", cfg)
    assert model_dir == os.path.join(str(tmp_path), "mlp")
    assert os.listdir(model_dir) == ["jax_config.json"]
    with open(os.path.join(model_dir, "jax_config.json")) as f:
        assert json.load(f) == {"family": "mlp", "config": cfg}
    server = JAXServer(model_uri=model_dir)
    server.load()
    probs = np.asarray(server.predict(np.ones((2, 4), np.float32), []))
    assert probs.shape == (2, 3)
    assert np.isfinite(probs).all()


def test_harness_answers_rest_and_grpc_with_the_same_payload():
    h = EngineHarness(Doubler()).start()
    try:
        assert h.http_port != h.grpc_port
        over_rest = rest_predict(h.http_port)
        over_grpc = proto_to_json(pb.SeldonMessage.FromString(
            grpc_predict(h.grpc_port, json_to_proto(BODY).SerializeToString())
        ))
        assert over_rest["data"]["ndarray"] == [[2.0, 4.0], [6.0, 8.0]]
        assert over_grpc["data"] == over_rest["data"]
    finally:
        h.stop()


def test_stop_closes_both_ports_and_a_second_stop_is_harmless():
    h = EngineHarness(Doubler()).start()
    rest_predict(h.http_port)
    h.stop()
    assert refuses(h.http_port)
    assert refuses(h.grpc_port)
    h.stop()
    assert refuses(h.http_port)


def test_stop_before_start_is_harmless():
    EngineHarness(Doubler()).stop()


def test_harnesses_with_registries_of_their_own_keep_their_counters_apart():
    reg_a, reg_b = MetricsRegistry(), MetricsRegistry()
    a = EngineHarness(Doubler(), name="a", metrics=reg_a).start()
    b = EngineHarness(Doubler(), name="b", metrics=reg_b).start()
    try:
        for _ in range(3):
            rest_predict(a.http_port)
        rest_predict(b.http_port)
        assert reg_a.counter_total(REQUESTS) == 3.0
        assert reg_b.counter_total(REQUESTS) == 1.0
        assert reg_a.counter_total(REQUESTS, {"deployment": "b"}) == 0.0
        assert 'deployment="a"' not in reg_b.expose()
    finally:
        a.stop()
        b.stop()


def test_module_imports_without_jax():
    """chip_smoke.py's parent imports it, and a parent that touched jax
    would hold the chip its children need."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "import sys; sys.path.insert(0, {repo!r}); import seldon_core_tpu.testing as t; "
        "assert t.free_port() > 0; "
        "bad = [m for m in sys.modules if m == 'jax' or m.startswith('jax.')]; "
        "assert not bad, bad"
    ).format(repo=repo)
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=120
    )
    assert proc.returncode == 0, proc.stderr
