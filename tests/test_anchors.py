"""Anchors (components/anchors.py): the reference's default explainer
family (alibi anchors, seldondeployment_explainers.go:32-187) rebuilt
black-box — rule + precision + coverage for non-differentiable models."""


import numpy as np
import pytest

from _net import free_port, serve_on_thread

from seldon_core_tpu.components.anchors import AnchorTabular, AnchorText
from seldon_core_tpu.components.explainer import Explainer


def test_anchor_pins_the_deciding_feature():
    """Model depends only on f0; the anchor must pin f0 (and only f0),
    clear the precision threshold, and report honest coverage."""
    rng = np.random.RandomState(0)
    train = rng.uniform(-1, 1, size=(800, 3))

    def predict(z):
        return (np.asarray(z)[:, 0] > 0).astype(np.int64)

    exp = AnchorTabular(predict, train, feature_names=["a", "b", "c"], seed=1)
    out = exp.explain(np.array([0.9, 0.1, -0.5]))
    assert out["anchor_features"] == ["a"]
    assert out["prediction"] == 1
    assert out["converged"] is True
    assert out["precision"] >= 0.95
    # f0 pinned to its top quantile bin: ~1/4 of train matches
    assert 0.1 < out["coverage"] < 0.45
    assert "a >" in out["anchor"][0]


def test_anchor_grows_until_precise():
    """AND of two features forces a 2-predicate anchor."""
    rng = np.random.RandomState(0)
    train = rng.uniform(-1, 1, size=(1000, 4))

    def predict(z):
        z = np.asarray(z)
        return ((z[:, 0] > 0) & (z[:, 2] > 0)).astype(np.int64)

    exp = AnchorTabular(predict, train, seed=2)
    out = exp.explain(np.array([0.9, 0.0, 0.9, 0.0]))
    assert set(out["anchor_features"]) == {"f0", "f2"}
    assert out["converged"] and out["precision"] >= 0.95


def test_anchor_shape_mismatch_rejected():
    exp = AnchorTabular(lambda z: np.zeros(len(z)), np.zeros((10, 3)))
    with pytest.raises(ValueError, match="features"):
        exp.explain(np.zeros(5))


def test_anchor_text_pins_the_deciding_word():
    def predict(texts):
        return np.asarray([1 if "good" in t.split() else 0 for t in texts])

    exp = AnchorText(predict, seed=3)
    out = exp.explain("this movie is good fun")
    assert out["anchor"] == ["good"]
    assert out["prediction"] == 1
    assert out["converged"] and out["precision"] >= 0.95


def test_sklearn_iris_anchor_behind_explain_route(tmp_path, rest_client):
    """The VERDICT acceptance test: an sklearn-iris predictor served over
    REST, an anchor_tabular Explainer pointed at it, /explain returning
    anchor rules with precision/coverage."""
    sklearn = pytest.importorskip("sklearn")
    import joblib
    from sklearn.datasets import load_iris
    from sklearn.linear_model import LogisticRegression

    from seldon_core_tpu.servers.sklearnserver import SKLearnServer
    from seldon_core_tpu.wrapper import get_rest_microservice

    iris = load_iris()
    clf = LogisticRegression(max_iter=500).fit(iris.data, iris.target)
    model_dir = tmp_path / "model"
    model_dir.mkdir()
    joblib.dump(clf, model_dir / "model.joblib")
    np.save(tmp_path / "train.npy", iris.data)

    server = SKLearnServer(model_uri=f"file://{model_dir}")
    server.load()
    port = free_port()
    stop = serve_on_thread(
        get_rest_microservice(server).serve_forever("127.0.0.1", port), port
    )
    try:
        explainer = Explainer(
            explainer_type="anchor_tabular",
            predictor_endpoint=f"127.0.0.1:{port}",
            predictor_path="/predict",
            train_data_uri=f"file://{tmp_path}/train.npy",
            feature_names=list(iris.feature_names),
            anchor_seed=0,
        )
        app = get_rest_microservice(explainer)
        client = rest_client(app)
        status, body = client.call(
            "/explain", {"data": {"ndarray": [iris.data[0].tolist()]}}
        )
    finally:
        stop()
    assert status == 200
    out = body["jsonData"]
    assert out["explainer"] == "anchor_tabular"
    assert out["anchors"][0]["precision"] >= 0.9
    assert 0.0 < out["anchors"][0]["coverage"] <= 1.0
    assert out["anchors"][0]["anchor"], "empty anchor rule"
    # setosa is linearly separable on petal features: the rule should
    # mention a petal measurement
    assert any("petal" in rule for rule in out["anchors"][0]["anchor"])
    assert out["prediction"] == int(clf.predict(iris.data[:1])[0])

