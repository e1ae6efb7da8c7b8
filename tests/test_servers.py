"""Prepackaged server tests: sklearn (iris parity) + jaxserver (mlp family).

Counterpart of the reference's server wiring tests and the sklearn iris
config in BASELINE.json ("sklearnserver iris SeldonDeployment").
"""

import asyncio
import json
import os

import numpy as np
import pytest

from seldon_core_tpu.graph import GraphExecutor
from seldon_core_tpu.graph.spec import PredictorSpec, default_predictor


@pytest.fixture(scope="module")
def iris_model_dir(tmp_path_factory):
    import joblib
    from sklearn.datasets import load_iris
    from sklearn.linear_model import LogisticRegression

    d = tmp_path_factory.mktemp("iris")
    X, y = load_iris(return_X_y=True)
    clf = LogisticRegression(max_iter=200).fit(X, y)
    joblib.dump(clf, d / "model.joblib")
    return str(d)


@pytest.fixture(scope="module")
def mlp_model_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("mlp")
    with open(d / "jax_config.json", "w") as f:
        json.dump(
            {
                "family": "mlp",
                "config": {"in_features": 4, "hidden": [8], "num_classes": 3, "seed": 0,
                           "class_names": ["setosa", "versicolor", "virginica"]},
            },
            f,
        )
    return str(d)


def test_sklearn_server_serves_iris(iris_model_dir):
    spec = default_predictor(
        PredictorSpec.from_dict(
            {
                "name": "iris",
                "graph": {
                    "name": "clf",
                    "implementation": "SKLEARN_SERVER",
                    "modelUri": iris_model_dir,
                },
            }
        )
    )
    ex = GraphExecutor(spec)
    out = asyncio.run(ex.predict({"data": {"ndarray": [[5.1, 3.5, 1.4, 0.2]]}}))
    probs = np.asarray(out["data"]["ndarray"])
    assert probs.shape == (1, 3)
    np.testing.assert_allclose(probs.sum(), 1.0, atol=1e-6)
    assert int(np.argmax(probs)) == 0  # setosa
    assert out["data"]["names"] == ["t:0", "t:1", "t:2"]


def test_jaxserver_serves_mlp(mlp_model_dir):
    spec = default_predictor(
        PredictorSpec.from_dict(
            {
                "name": "jax",
                "graph": {
                    "name": "model",
                    "implementation": "JAX_SERVER",
                    "modelUri": mlp_model_dir,
                },
            }
        )
    )
    ex = GraphExecutor(spec)
    out = asyncio.run(ex.predict({"data": {"ndarray": [[1.0, 2.0, 3.0, 4.0]]}}))
    probs = np.asarray(out["data"]["ndarray"])
    assert probs.shape == (1, 3)
    np.testing.assert_allclose(probs.sum(), 1.0, atol=1e-3)
    assert out["data"]["names"] == ["setosa", "versicolor", "virginica"]
    assert out["meta"]["tags"]["server"] == "jaxserver"


def test_jaxserver_checkpoint_roundtrip(tmp_path):
    """Params saved with orbax are restored bit-exact and change outputs."""
    import jax
    import orbax.checkpoint as ocp

    from seldon_core_tpu.models import build

    model = build("mlp", in_features=4, hidden=[8], num_classes=3)
    params = model.init_params(seed=42)
    ckpt_dir = tmp_path / "ckpt"
    with ocp.PyTreeCheckpointer() as ckptr:
        ckptr.save(str(ckpt_dir), params)
    with open(tmp_path / "jax_config.json", "w") as f:
        json.dump(
            {"family": "mlp", "config": {"in_features": 4, "hidden": [8], "num_classes": 3, "seed": 0},
             "checkpoint": "ckpt"},
            f,
        )
    from seldon_core_tpu.servers.jaxserver import JAXServer

    srv = JAXServer(model_uri=str(tmp_path))
    srv.load()
    x = np.asarray([[1.0, 2.0, 3.0, 4.0]], dtype=np.float32)
    got = np.asarray(srv.predict(x, []))
    want = np.asarray(jax.jit(model.apply)(params, x))
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_gated_servers_give_clear_errors(tmp_path):
    from seldon_core_tpu.servers.xgboostserver import XGBoostServer

    with pytest.raises(RuntimeError, match="xgboost"):
        XGBoostServer(model_uri=str(tmp_path)).load()


# -- TRT / Triton proxy ------------------------------------------------------


def make_trt(transport):
    from seldon_core_tpu.servers.trtserver import TRTServer

    return TRTServer(url="http://trt:8000", model_name="resnet", transport=transport)


def test_trt_proxy_negotiates_dtype_and_infers():
    calls = []

    def transport(url, body, timeout):
        calls.append((url, body))
        if body is None:
            return {
                "name": "resnet",
                "inputs": [{"name": "input0", "datatype": "INT32", "shape": [-1, 3]}],
                "outputs": [{"name": "prob"}],
            }
        req = json.loads(body)
        assert req["inputs"][0]["datatype"] == "INT32"
        assert req["inputs"][0]["shape"] == [2, 3]
        return {
            "outputs": [
                {"name": "prob", "datatype": "FP32", "shape": [2, 2],
                 "data": [0.9, 0.1, 0.2, 0.8]}
            ]
        }

    server = make_trt(transport)
    out = server.predict(np.asarray([[1.5, 2.5, 3.5], [4, 5, 6]]), [])
    assert out.shape == (2, 2)
    np.testing.assert_allclose(out[0], [0.9, 0.1])
    # metadata fetched once, infer posted to /infer
    assert calls[0][0] == "http://trt:8000/v2/models/resnet"
    assert calls[1][0].endswith("/v2/models/resnet/infer")
    assert server.class_names() == ["prob"]


def test_trt_proxy_error_on_no_outputs():
    def transport(url, body, timeout):
        if body is None:
            return {"inputs": [{"name": "x", "datatype": "FP32"}]}
        return {"outputs": []}

    server = make_trt(transport)
    with pytest.raises(RuntimeError, match="no outputs"):
        server.predict(np.zeros((1, 2)), [])


def test_trt_proxy_through_engine():
    """TRITON_SERVER wires through the graph executor like any
    prepackaged server."""
    import asyncio

    from seldon_core_tpu.graph.service import EngineApp
    from seldon_core_tpu.graph.spec import PredictorSpec, default_predictor
    from seldon_core_tpu.servers.trtserver import TRTServer

    def transport(url, body, timeout):
        if body is None:
            return {"inputs": [{"name": "x", "datatype": "FP64", "shape": [-1, 2]}]}
        req = json.loads(body)
        rows = np.asarray(req["inputs"][0]["data"]).reshape(req["inputs"][0]["shape"])
        return {
            "outputs": [{"name": "y", "datatype": "FP64",
                         "shape": list(rows.shape), "data": (rows * 3).ravel().tolist()}]
        }

    spec = default_predictor(
        PredictorSpec.from_dict({"name": "t", "graph": {"name": "m", "type": "MODEL"}})
    )
    app = EngineApp(spec, registry={"m": TRTServer(transport=transport)})
    out = asyncio.run(app.predict({"data": {"ndarray": [[1.0, 2.0]]}}))
    assert out["data"]["ndarray"] == [[3.0, 6.0]]


# -- SageMaker proxy ---------------------------------------------------------


class FakeSMClient:
    def __init__(self, fn):
        self.fn = fn
        self.calls = []

    def invoke_endpoint(self, EndpointName, ContentType, Accept, Body):
        self.calls.append((EndpointName, ContentType, Body))
        import io as _io

        return {"Body": _io.BytesIO(self.fn(Body, ContentType))}


def test_sagemaker_proxy_json_round_trip():
    from seldon_core_tpu.servers.sagemakerserver import SageMakerServer

    def fn(body, ctype):
        arr = np.asarray(json.loads(body)["instances"])
        return json.dumps({"predictions": (arr * 2).tolist()}).encode()

    client = FakeSMClient(fn)
    server = SageMakerServer(endpoint_name="ep1", client_factory=lambda: client)
    out = server.predict(np.asarray([[1.0, 2.0]]), [])
    np.testing.assert_allclose(out, [[2.0, 4.0]])
    assert client.calls[0][0] == "ep1"


def test_sagemaker_proxy_csv_mode():
    from seldon_core_tpu.servers.sagemakerserver import SageMakerServer

    def fn(body, ctype):
        arr = np.loadtxt(__import__("io").StringIO(body.decode()), delimiter=",", ndmin=2)
        out = __import__("io").StringIO()
        np.savetxt(out, arr + 1, delimiter=",", fmt="%g")
        return out.getvalue().encode()

    server = SageMakerServer(
        endpoint_name="ep2", content_type="text/csv",
        client_factory=lambda: FakeSMClient(fn),
    )
    out = server.predict(np.asarray([[1.0, 2.0], [3.0, 4.0]]), [])
    np.testing.assert_allclose(out, [[2.0, 3.0], [4.0, 5.0]])


def test_sagemaker_requires_endpoint():
    from seldon_core_tpu.servers.sagemakerserver import SageMakerServer

    with pytest.raises(ValueError, match="endpoint_name"):
        SageMakerServer()


# -- TFServer via injected loader --------------------------------------------


def test_tfserver_with_injected_loader(tmp_path):
    from seldon_core_tpu.servers.tfserver import TFServer

    model_dir = tmp_path / "saved"
    model_dir.mkdir()
    (model_dir / "saved_model.pb").write_bytes(b"\x00")
    seen = {}

    def loader(path, signature):
        seen["dir"] = path
        seen["sig"] = signature
        return lambda arr: arr * 10

    server = TFServer(model_uri=str(model_dir), loader=loader)
    out = server.predict(np.asarray([[1.0, 2.0]]), [])
    np.testing.assert_allclose(out, [[10.0, 20.0]])
    assert seen["sig"] == "serving_default"
    import os as _os

    assert _os.path.exists(_os.path.join(seen["dir"], "saved_model.pb"))


def _rest_binary(harness, request: bytes):
    from _net import post_predictions

    status, answer = post_predictions(harness.http_port, request,
                                      "application/x-protobuf")
    assert status == 200, answer[:200]
    return answer


def _grpc_binary(harness, request: bytes):
    from _net import grpc_predict

    return grpc_predict(harness.grpc_port, request)


@pytest.mark.parametrize("family,config,wire,send", [
    ("resnet50", {"image_size": 32, "num_classes": 10},
     "uint8 jpeg-rows", _rest_binary),
    ("bert", {"vocab_size": 512, "d_model": 64, "n_layers": 2, "n_heads": 2,
              "d_ff": 128, "max_seq": 64}, "int32", _grpc_binary),
], ids=["resnet50-rest-jpeg-rows", "bert-grpc-int32"])
def test_jaxserver_behind_the_engine_on_the_binary_wire(
        tmp_path, family, config, wire, send):
    """A model family loaded by JAXServer, micro-batched by the engine on
    real sockets, fed binary ``RawTensor`` bodies by concurrent clients:
    JPEG-per-row uint8 images over REST, int32 token ids over gRPC. Every
    caller gets the rows the model gives the same decoded input."""
    import threading

    from seldon_core_tpu import payload
    from seldon_core_tpu.proto import prediction_pb2 as pb
    from seldon_core_tpu.servers.jaxserver import JAXServer
    from seldon_core_tpu.testing import EngineHarness, write_model_dir

    component = JAXServer(model_uri=write_model_dir(str(tmp_path), family, config))
    component.load()
    rs = np.random.RandomState(0)
    if wire == "int32":
        x = rs.randint(1, config["vocab_size"], (2, 16), dtype=np.int32)
        raw = payload.array_to_raw(x)
    else:
        x = rs.randint(0, 256, (2, 32, 32, 3), dtype=np.uint8)
        raw = payload.array_to_raw(x, encoding="jpeg-rows", jpeg_quality=95)
        assert raw.encoding == "jpeg-rows"
    want = np.asarray(component.predict(payload.raw_to_array(raw), []), np.float32)
    assert want.shape[0] == 2 and np.isfinite(want).all()
    request = pb.SeldonMessage(data=pb.DefaultData(raw=raw)).SerializeToString()
    harness = EngineHarness(
        component, batching={"max_batch": 8, "timeout_ms": 20.0},
        annotations={"seldon.io/max-inflight": "4"},
    ).start()
    answers, errors = [], []

    def client():
        try:
            answers.append(send(harness, request))
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(e)

    try:
        threads = [threading.Thread(target=client) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        harness.stop()
    assert not errors, errors
    assert len(answers) == 4
    for answer in answers:
        got = payload.proto_data_to_array(pb.SeldonMessage.FromString(answer).data)
        np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                   rtol=2e-2, atol=2e-2)
