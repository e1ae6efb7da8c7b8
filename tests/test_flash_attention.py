"""Pallas flash-attention kernel: interpret-mode equivalence on CPU.

Tier-1 strategy (SURVEY §4): the kernel's math is checked against the
plain XLA einsum reference at f32 precision, and the lowering guards at
the bottom lower prefill for the TPU platform from this CPU host. The
Mosaic compile itself needs the chip: chip_smoke.py's kernel leg.
"""

import jax
import jax.numpy as jnp
import pytest

from seldon_core_tpu.ops.flash_attention import (
    _xla_attention,
    attention,
    flash_attention,
)


@pytest.mark.parametrize(
    "b,h,t_q,t_k,dh,causal",
    [
        (2, 4, 256, 256, 64, True),
        (1, 2, 128, 256, 64, False),  # cross-length, non-causal
        (2, 2, 256, 256, 128, True),
        (1, 1, 384, 384, 64, True),  # 3 blocks, diagonal not block-aligned^2
        (1, 1, 128, 128, 64, True),  # single block
    ],
)
def test_kernel_matches_xla(b, h, t_q, t_k, dh, causal):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, h, t_q, dh), jnp.float32)
    k = jax.random.normal(ks[1], (b, h, t_k, dh), jnp.float32)
    v = jax.random.normal(ks[2], (b, h, t_k, dh), jnp.float32)
    ref = _xla_attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, interpret=True)
    assert float(jnp.abs(ref - got).max()) < 1e-5


def test_kernel_block_sizes():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 2, 512, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 512, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 512, 64), jnp.float32)
    ref = _xla_attention(q, k, v, causal=True)
    for bq, bk in ((128, 128), (256, 256), (512, 512), (128, 256)):
        got = flash_attention(
            q, k, v, causal=True, block_q=bq, block_k=bk, interpret=True
        )
        assert float(jnp.abs(ref - got).max()) < 1e-5, (bq, bk)


def test_kernel_rejects_ragged_shapes():
    q = jnp.zeros((1, 1, 130, 64))
    with pytest.raises(ValueError, match="tile"):
        flash_attention(q, q, q)


def test_dispatcher_falls_back_off_tpu():
    """attention() must serve any shape on any backend (the kernel is a
    TPU fast path, not a requirement)."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (2, 2, 17, 32), jnp.float32)  # untileable
    k = jax.random.normal(ks[1], (2, 2, 23, 32), jnp.float32)
    v = jax.random.normal(ks[2], (2, 2, 23, 32), jnp.float32)
    out = attention(q, k, v, causal=False)
    ref = _xla_attention(q, k, v, causal=False)
    assert float(jnp.abs(ref - out).max()) < 1e-6


def test_dispatcher_kv_len_mask():
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (1, 2, 8, 16), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 8, 16), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 8, 16), jnp.float32)
    out = attention(q, k, v, kv_len=5, causal=False)
    ref = _xla_attention(q, k[:, :, :5], v[:, :, :5], causal=False)
    assert float(jnp.abs(ref - out).max()) < 1e-6


def test_prefill_unchanged_by_dispatch():
    """DecoderLM.prefill output is identical with the ops.attention hook
    (CPU falls back to the einsum path — exact same math)."""
    import numpy as np

    from seldon_core_tpu.models.llm import DecoderLM

    model = DecoderLM(
        vocab_size=128, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2,
        d_ff=64, max_seq=64, dtype="float32",
    )
    params = model.init_params(0)
    prompt = jnp.asarray(
        np.random.RandomState(0).randint(0, 128, (2, 16)), jnp.int32
    )
    logits, cache = model.prefill(params, prompt, 32)
    assert logits.shape == (2, 128)
    assert bool(jnp.isfinite(logits).all())


def _export_prefill_for_tpu(monkeypatch, mesh):
    """Lower DecoderLM.prefill for the TPU platform from this CPU host,
    with attention()'s kernel branch selected, at a 128-token bucket and
    head_dim 128. Lowering runs Pallas -> Mosaic MLIR and the SPMD
    partitioner's custom-call rules; only libtpu's Mosaic compile needs
    the chip (chip_smoke.py's kernel leg)."""
    import numpy as np

    from seldon_core_tpu.models.llm import DecoderLM

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = DecoderLM(
        vocab_size=256, d_model=256, n_layers=2, n_heads=2, n_kv_heads=2,
        d_ff=256, max_seq=128,
    )
    assert model.cfg.head_dim == 128
    params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16), model.init_params(0)
    )
    if mesh is not None:
        model.set_serving_mesh(mesh)
        params = jax.device_put(params, model.param_sharding(mesh, params))
    prompt = jnp.asarray(
        np.random.RandomState(0).randint(0, 256, (1, 128)), jnp.int32
    )
    fn = jax.jit(lambda p, t: model.prefill(p, t, 128))
    return jax.export.export(fn, platforms=["tpu"])(params, prompt)


def test_prefill_lowers_to_mosaic_call_for_tpu(monkeypatch):
    exported = _export_prefill_for_tpu(monkeypatch, mesh=None)
    assert "tpu_custom_call" in exported.mlir_module()


def test_meshed_prefill_lowers_for_tpu(monkeypatch):
    """Mosaic kernels cannot be partitioned by GSPMD: under the serving
    mesh the kernel call must sit inside a shard_map, or the first
    meshed prefill compile on real chips raises NotImplementedError."""
    from seldon_core_tpu.parallel import make_mesh

    mesh = make_mesh({"data": 1, "model": 2})
    exported = _export_prefill_for_tpu(monkeypatch, mesh=mesh)
    assert "tpu_custom_call" in exported.mlir_module()
