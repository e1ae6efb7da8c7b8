"""Plain reference of the decoder: the yardstick ``correct`` is held to.

The forward pass of a llama-style decoder (RMSNorm, rotary embeddings in
the half-split convention, grouped-query attention, SwiGLU, untied head)
in straightforward ``jax.numpy``: float32, ``highest`` matmul precision, no
kernel, no cache, no batching, no scan. It shares no code with
``seldon_core_tpu.models.llm``. Weights are taken layer by layer and cast
to float32 one matrix at a time, so that 7B widths fit beside the served
model; the head is applied in vocabulary blocks for the same reason.
``benchmark/architectures/decoder.py`` drives the served model beside it
and holds the tolerance.

Departure from the published models: none in the mathematics. InternLM2
publishes its attention projections fused (``wqkv``); they are split here
as the served model holds them, which is the same linear map.
"""

from __future__ import annotations

import numpy as np

HEAD_BLOCK = 16384


def _rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x * jnp.reciprocal(jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * w


def _rope(x, theta):
    """x: [T, H, Dh]; rotate pairs (i, i + Dh/2) by position * theta^(-2i/Dh)."""
    import jax.numpy as jnp

    t, _h, dh = x.shape
    half = dh // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def logits(params, cfg, tokens, positions) -> np.ndarray:
    """Full causal forward over ``tokens`` [T]; returns float32 logits
    [len(positions), V] at the given positions. ``cfg`` needs n_heads,
    n_kv_heads, head_dim, rope_theta, norm_eps."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    heads, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    blocks = params["blocks"]
    n_layers = blocks["wq"].shape[0]
    tokens = jnp.asarray(tokens, jnp.int32)
    t = tokens.shape[0]
    causal = jnp.tril(jnp.ones((t, t), bool))
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(f32)
        for layer in range(n_layers):
            def w(name, layer=layer):
                # one matrix at a time in float32: a whole 7B-wide layer
                # would not fit beside the served model and its cache
                return blocks[name][layer].astype(f32)

            h = _rms_norm(x, w("ln1"), cfg.norm_eps)
            q = _rope((h @ w("wq")).reshape(t, heads, dh), cfg.rope_theta)
            k = _rope((h @ w("wk")).reshape(t, kv, dh), cfg.rope_theta)
            v = (h @ w("wv")).reshape(t, kv, dh)
            k = jnp.repeat(k, heads // kv, axis=1)
            v = jnp.repeat(v, heads // kv, axis=1)
            s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(dh)
            s = jnp.where(causal[None], s, -jnp.inf)
            o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
            x = x + o.reshape(t, heads * dh) @ w("wo")
            h = _rms_norm(x, w("ln2"), cfg.norm_eps)
            x = x + (jax.nn.silu(h @ w("w1")) * (h @ w("w3"))) @ w("w2")
        x = _rms_norm(x, params["ln_f"].astype(f32), cfg.norm_eps)
        x = x[jnp.asarray(positions)]
        vocab = params["unembed"].shape[1]
        out = [
            np.asarray(x @ params["unembed"][:, lo:lo + HEAD_BLOCK].astype(f32))
            for lo in range(0, vocab, HEAD_BLOCK)
        ]
    return np.concatenate(out, axis=-1)
