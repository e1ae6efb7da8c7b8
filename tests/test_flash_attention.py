"""Pallas flash-attention kernel: interpret-mode equivalence on CPU.

Tier-1 strategy (SURVEY §4): the kernel's math is checked against the
plain XLA einsum reference at f32 precision, and the lowering guards at
the bottom lower prefill for the TPU platform from this CPU host. The
Mosaic compile itself needs the chip: chip_smoke.py's kernel leg.
"""

import sys

import jax
import jax.numpy as jnp
import pytest

from seldon_core_tpu.ops.flash_attention import (
    _xla_attention,
    attention,
    flash_attention,
)


from seldon_core_tpu.ops.flash_attention import _prefixed_attention, _tile

# the package exports the function under the module's name
flash_module = sys.modules[flash_attention.__module__]


def _qkv(seed, b, h, t_q, t_k, dh, dv=None, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, h, t_q, dh), dtype),
            jax.random.normal(ks[1], (b, h, t_k, dh), dtype),
            jax.random.normal(ks[2], (b, h, t_k, dv or dh), dtype))


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
    q, k, v = _qkv(0, b, h, t_q, t_k, dh)
    ref = _xla_attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, interpret=True)
    assert float(jnp.abs(ref - got).max()) < 1e-5


@pytest.mark.parametrize(
    "t,dh,dv,block_q,block_k,window",
    [
        # the committed rule's tiles (``_tile``) at the cells' shapes
        (1792, 128, 128, 256, 512, None),  # 512 does not divide: a lead tile of 256
        (1024, 128, 128, 256, 512, None),
        (512, 64, 64, 256, 512, None),     # one key tile, two q-blocks
        (256, 192, 128, 256, 256, None),   # keys wider than values
        (768, 192, 128, 256, 512, None),   # ... behind a lead tile
        (512, 256, 128, 256, 512, None),
        (640, 64, 128, 128, 512, None),    # a lead tile of 128
        (128, 128, 128, 128, 128, None),
        # a band that starts mid-tile, whole key tiles left of it
        (1024, 128, 128, 256, 512, 300),
        (1536, 64, 64, 256, 512, 700),
        (768, 64, 64, 256, 256, 200),
        (1024, 64, 64, 256, 512, 1),       # every row sees itself alone
        # unequal either way, and more rows than columns
        (1024, 64, 64, 512, 256, None),
        (1024, 64, 64, 128, 512, 384),
        (896, 64, 64, 128, 384, None),
    ],
)
def test_kernel_walks_unequal_tiles(t, dh, dv, block_q, block_k, window):
    q, k, v = _qkv(t + dh, 1, 2, t, t, dh, dv)
    ref = _xla_attention(q, k, v, True, None, window)
    got = flash_attention(q, k, v, causal=True, block_q=block_q,
                          block_k=block_k, window=window, interpret=True)
    assert got.shape == (1, 2, t, dv)
    assert float(jnp.abs(ref - got).max()) < 2e-5


@pytest.mark.parametrize("visible", [0, 100, 256, 384, 500, 640])
@pytest.mark.parametrize("block_q,block_k", [(256, 512), (128, 256)])
def test_kernel_behind_a_prefix_that_ends_mid_tile(visible, block_q, block_k):
    """A prefix of 640 rows (no multiple of either key tile) of which 0,
    part of a tile, whole tiles or all are visible, before 512 causal keys."""
    q, k, v = _qkv(7, 1, 2, 512, 640 + 512, 128)
    n = jnp.int32(visible)
    got = flash_attention(q, k, v, causal=True, block_q=block_q,
                          block_k=block_k, prefix=640, prefix_len=n,
                          interpret=True)
    assert float(jnp.abs(_prefixed_attention(q, k, v, 640, n) - got).max()) < 2e-5
    # a prefix tile past the visible ones is never read
    poisoned = k.at[:, :, -(-visible // block_k) * block_k:640].set(jnp.nan)
    again = flash_attention(q, poisoned, v, causal=True, block_q=block_q,
                            block_k=block_k, prefix=640, prefix_len=n,
                            interpret=True)
    assert bool((again == got).all())


@pytest.mark.parametrize("block_q,block_k", [
    (128, 128), (256, 256), (512, 512), (128, 256), (256, 512), (256, 128),
    (512, 256), (128, 512)])
def test_kernel_block_sizes(block_q, block_k):
    q, k, v = _qkv(1, 1, 2, 512, 512, 64)
    ref = _xla_attention(q, k, v, causal=True)
    got = flash_attention(
        q, k, v, causal=True, block_q=block_q, block_k=block_k, interpret=True
    )
    assert float(jnp.abs(ref - got).max()) < 1e-5


@pytest.mark.parametrize("block_q,block_k", [(128, 128), (256, 512)])
def test_kernel_multiplies_bfloat16_as_given(block_q, block_k):
    """Serving's operands: bfloat16 into both products (the probabilities
    cast to the values' dtype), float32 scores and accumulators, against
    the float32 dots of the same bfloat16 inputs."""
    q, k, v = _qkv(3, 1, 2, 768, 768, 128, dtype=jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True, block_q=block_q,
                          block_k=block_k, interpret=True)
    assert got.dtype == jnp.bfloat16
    ref = _xla_attention(q, k, v, causal=True).astype(jnp.float32)
    assert float(jnp.abs(ref - got.astype(jnp.float32)).max()) < 2e-2


# (t_q, t_k, dh, dv, window, prefix) -> (block_q, block_k): every prefill
# shape a benchmark cell runs, then shapes no cell has
CELL_TILES = [
    ((1792, 1792, 128, 128, None, None), (256, 512)),   # mistral docqa
    ((2048, 2048, 128, 128, None, None), (256, 512)),
    ((512, 512, 128, 128, None, None), (256, 512)),     # the batch cells, chat
    ((1024, 1024, 128, 128, None, None), (256, 512)),
    ((128, 128, 128, 128, None, None), (128, 128)),
    ((32, 32, 128, 128, None, None), None),              # under a tile: XLA
    ((4096, 4096, 128, 128, 2048, None), (256, 512)),   # trinity-mini's band
    ((4096, 4096, 128, 128, None, None), (256, 512)),   # ... and its global layers
    ((512, 512, 128, 128, 2048, None), (256, 512)),
    ((128, 128, 128, 128, 2048, None), (128, 128)),
    ((4096, 4096, 256, 256, None, None), (256, 512)),   # qwen3-next
    ((512, 512, 256, 256, None, None), (256, 512)),
    ((1792, 1792, 192, 128, None, None), (256, 512)),   # joyai: latent keys
    ((256, 256, 192, 128, None, None), (256, 256)),
    ((6144, 6144, 192, 128, None, None), (256, 512)),
    ((2048, 2048 + 896, 128, 128, None, 896), (256, 512)),  # evabyte
    ((1024, 1024, 128, 128, None, None), (256, 512)),
    ((1792, 1792, 128, 128, 512, None), (256, 256)),    # a band takes no lead tile
    ((640, 640, 64, 64, 256, None), (128, 128)),
    ((384, 384, 64, 64, None, None), (128, 384)),
    ((512, 512, 96, 96, None, None), None),              # a head no tile takes
    ((130, 130, 64, 64, None, None), None),
]


@pytest.mark.parametrize("shape,tile", CELL_TILES)
def test_dispatcher_picks_the_rules_tile(monkeypatch, shape, tile):
    """``attention()`` on a TPU hands the kernel ``_tile``'s tile for the
    call's shapes (and the XLA dots what no tile takes), and that tile is
    one the kernel walks: the call here is the kernel itself, interpreted,
    at a head or two of the cell's shape where that is quick."""
    t_q, t_k, dh, dv, window, prefix = shape
    seen = []

    def recorder(q, k, v, **kw):
        seen.append((kw["block_q"], kw["block_k"]))
        assert kw.get("window") == window and kw.get("prefix") == prefix
        if t_q > 2048:
            return jnp.zeros(q.shape[:3] + (v.shape[-1],), q.dtype)
        return flash_attention(q, k, v, interpret=True, **kw)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(flash_module, "flash_attention", recorder)
    q, k, v = _qkv(11, 1, 1, t_q, t_k, dh, dv)
    n = None if prefix is None else jnp.int32(300)
    got = attention(q, k, v, window=window, prefix=prefix, prefix_len=n)
    assert seen == ([] if tile is None else [tile])
    if tile is not None:
        assert tile == _tile(t_q, t_k, window, prefix or 0)
    if t_q <= 2048:
        monkeypatch.undo()
        want = attention(q, k, v, window=window, prefix=prefix, prefix_len=n)
        assert float(jnp.abs(want - got).max()) < 2e-5


def test_kernel_rejects_ragged_shapes():
    q = jnp.zeros((1, 1, 130, 64))
    with pytest.raises(ValueError, match="tile"):
        flash_attention(q, q, q)
    q = jnp.zeros((1, 1, 640, 64))
    # a band's walk has no lead tile
    with pytest.raises(ValueError, match="tile"):
        flash_attention(q, q, q, block_k=512, window=100, interpret=True)
    flash_attention(q, q, q, block_k=512, interpret=True)
    # a prefix's tiles may not run past the keys
    k = jnp.zeros((1, 1, 128 + 640, 64))
    with pytest.raises(ValueError, match="prefix"):
        flash_attention(q[:, :, :128], k[:, :, :256], k[:, :, :256], block_k=256,
                        prefix=128, prefix_len=jnp.int32(0), interpret=True)


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


# -- a sink in the softmax, and a window smaller than every tile (ISSUE 57) -----

@pytest.mark.parametrize("t,window,dh,dv", [
    (512, 128, 192, 128),     # the band inside one key tile of 512
    (768, 128, 192, 128),     # 256 divides, 512 does not: a key tile of 256
    (512, None, 192, 128),    # a sink under the plain causal mask
    (256, 16, 64, 64),
])
def test_kernel_with_a_sink_matches_the_concatenated_logit(t, window, dh, dv):
    """The sink joins every row's softmax after its last tile and has no
    value row: the kernel (interpreted, at the rule's tile) against the
    masked dots with the logit concatenated; without it the rows differ."""
    q, k, v = _qkv(5, 1, 4, t, t, dh, dv)
    sink = 3.0 + jax.random.normal(jax.random.PRNGKey(9), (4,), jnp.float32)
    block_q, block_k = _tile(t, t, window)
    got = flash_attention(q, k, v, block_q=block_q, block_k=block_k,
                          window=window, sink=sink, interpret=True,
                          name="swa_prefill_attention")
    want = _xla_attention(q, k, v, True, None, window, None, sink)
    assert got.shape == (1, 4, t, dv)
    assert float(jnp.abs(got - want).max()) < 2e-5
    bare = flash_attention(q, k, v, block_q=block_q, block_k=block_k,
                           window=window, interpret=True)
    assert float(jnp.abs(got - bare).max()) > 1e-2


def test_dispatcher_takes_a_sink_off_tpu_and_refuses_it_under_a_mesh():
    q, k, v = _qkv(6, 1, 2, 128, 128, 64)
    sink = jnp.asarray([2.0, -1.0], jnp.float32)
    got = attention(q, k, v, window=32, sink=sink)
    want = _xla_attention(q, k, v, True, None, 32, None, sink)
    assert float(jnp.abs(got - want).max()) < 1e-6
    with pytest.raises(ValueError, match="a sink takes no mesh"):
        attention(q, k, v, sink=sink, mesh=object())
