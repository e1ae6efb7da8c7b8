"""Model zoo tests: registry, ResNet-50, BERT (tiny configs on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu import models


def test_registry_unknown_family():
    with pytest.raises(ValueError, match="unknown model family"):
        models.build("nope")


def test_resnet50_forward_tiny():
    m = models.build("resnet50", num_classes=10, image_size=32)
    p = m.init_params(0)
    x = jnp.asarray(np.random.RandomState(0).rand(2, 32, 32, 3), jnp.float32)
    logits = jax.jit(m.apply)(p, x)
    assert logits.shape == (2, 10)
    assert np.isfinite(np.asarray(logits)).all()
    # full ResNet-50 structure: 3+4+6+3 bottlenecks
    assert [len(s) for s in p["stages"]] == [3, 4, 6, 3]
    assert p["stages"][3][0]["conv3"].shape == (1, 1, 512, 2048)


def test_bert_forward_and_padding_mask():
    m = models.build(
        "bert", vocab_size=100, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        max_seq=16, num_classes=3, dtype="float32",
    )
    p = m.init_params(0)
    toks = jnp.asarray([[5, 6, 7, 0, 0, 0, 0, 0]], jnp.int32)
    logits = jax.jit(m.apply)(p, toks)
    assert logits.shape == (1, 3)
    # padding must be inert: same content without the trailing PADs gives
    # the same [CLS] classification (masked positions contribute nothing)
    logits_short = jax.jit(m.apply)(p, toks[:, :3])
    np.testing.assert_allclose(logits_short, logits, atol=1e-5)
    # ...but changing a real token must change the output
    toks3 = toks.at[0, 1].set(8)
    assert not np.allclose(jax.jit(m.apply)(p, toks3), logits, atol=1e-6)


def test_bert_tp_sharding_specs():
    from seldon_core_tpu.parallel import make_mesh

    m = models.build(
        "bert", vocab_size=100, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        max_seq=16, dtype="float32",
    )
    p = m.init_params(0)
    mesh = make_mesh({"data": 2, "model": 4})
    shardings = m.param_sharding(mesh, p)
    p_sharded = jax.device_put(p, shardings)
    logits = jax.jit(m.apply)(p_sharded, jnp.ones((4, 8), jnp.int32))
    assert logits.shape == (4, 2)


def test_vit_forward_and_patch_equivalence():
    m = models.build(
        "vit", image_size=32, patch_size=8, d_model=32, n_layers=2,
        n_heads=4, d_ff=64, num_classes=5, dtype="float32",
    )
    p = m.init_params(0)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(2, 32, 32, 3), jnp.float32)
    logits = jax.jit(m.apply)(p, x)
    assert logits.shape == (2, 5)
    assert np.isfinite(np.asarray(logits)).all()
    # uint8 input takes the same path (serving's raw image encoding)
    xu8 = jnp.asarray(rng.randint(0, 256, (2, 32, 32, 3)), jnp.uint8)
    logits_u8 = jax.jit(m.apply)(p, xu8)
    assert logits_u8.shape == (2, 5)
    # the patchify reshape must agree with an explicit per-patch gather
    g, P = 32 // 8, 8
    xh = np.asarray(x)
    patches = np.stack(
        [
            xh[:, i * P:(i + 1) * P, j * P:(j + 1) * P, :].reshape(2, -1)
            for i in range(g) for j in range(g)
        ],
        axis=1,
    )
    emb_manual = patches @ np.asarray(p["patch_embed"]["w"]) + np.asarray(
        p["patch_embed"]["b"]
    )
    xp = xh.reshape(2, g, P, g, P, 3).transpose(0, 1, 3, 2, 4, 5).reshape(2, g * g, -1)
    emb_reshape = xp @ np.asarray(p["patch_embed"]["w"]) + np.asarray(
        p["patch_embed"]["b"]
    )
    np.testing.assert_allclose(emb_manual, emb_reshape, atol=1e-5)
    # non-tiling patch size rejected at build
    with pytest.raises(ValueError, match="tile"):
        models.build("vit", image_size=30, patch_size=8)


def test_vit_tp_sharding_specs():
    from seldon_core_tpu.parallel import make_mesh

    m = models.build(
        "vit", image_size=16, patch_size=8, d_model=32, n_layers=2,
        n_heads=4, d_ff=64, num_classes=4, dtype="float32",
    )
    p = m.init_params(0)
    mesh = make_mesh({"data": 2, "model": 4})
    p_sharded = jax.device_put(p, m.param_sharding(mesh, p))
    x = jnp.ones((4, 16, 16, 3), jnp.float32)
    logits = jax.jit(m.apply)(p_sharded, x)
    assert logits.shape == (4, 4)
    assert np.isfinite(np.asarray(logits)).all()


def test_vit_serves_through_jaxserver(tmp_path):
    import json as _json

    from seldon_core_tpu.servers.jaxserver import JAXServer

    d = tmp_path / "vit"
    d.mkdir()
    (d / "jax_config.json").write_text(
        _json.dumps(
            {
                "family": "vit",
                "config": {
                    "image_size": 16, "patch_size": 8, "d_model": 32,
                    "n_layers": 1, "n_heads": 2, "d_ff": 64,
                    "num_classes": 3, "dtype": "float32",
                },
            }
        )
    )
    s = JAXServer(model_uri=str(d))
    s.load()
    img = np.random.RandomState(0).randint(0, 256, (2, 16, 16, 3), dtype=np.uint8)
    out = np.asarray(s.predict(img, []))
    assert out.shape == (2, 3)
    assert np.isfinite(out).all()


def test_flops_analytics_sane():
    from seldon_core_tpu.models.bert import BertClassifier
    from seldon_core_tpu.models.llm import DecoderLM
    from seldon_core_tpu.models.resnet import ResNet50

    # ResNet-50 @224 is ~8.2 GFLOP under the 2xMAC convention
    assert 7.5e9 < ResNet50().flops_per_row() < 9.0e9
    # BERT-base @128 tokens ~22 GFLOP
    assert 18e9 < BertClassifier().flops_per_row(128) < 26e9
    lm = DecoderLM()
    assert lm.flops_per_token(64) > 0
    assert lm.flops_per_row(64) > lm.flops_per_token(64)


def test_n_params_matches_pytree():
    import jax

    from seldon_core_tpu.models.llm import DecoderLM

    for cfg in (
        dict(vocab_size=128, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2, d_ff=64),
        dict(vocab_size=64, d_model=16, n_layers=2, n_heads=2, n_kv_heads=1,
             d_ff=32, n_experts=2),
    ):
        m = DecoderLM(**cfg)
        counted = sum(
            np.prod(a.shape) for a in jax.tree_util.tree_leaves(m.init_params(0))
        )
        assert m.n_params() == counted, cfg
