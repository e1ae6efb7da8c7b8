"""The dense llama-style decoder: everything the benchmark knows of it.

A configuration whose file says ``"architecture": "decoder"`` is served,
compared and costed by this module (``manifest.architecture``); nothing
else under ``benchmark/`` knows a key of its published config. The parent
process loads it too, for the kwargs and the costs, and never imports jax:
jax and the program are imported inside the functions that need them.

**The served family.** ``DecoderLM.init_params`` draws every matrix in
float32 with an eagerly compiled program of its own: at 7B widths the
float32 tree does not fit the chip beside anything else, and each matrix
costs a compilation. A real checkpoint pays neither; a synthetic cell would
pay both on every run. So the benchmark's model directory names the family
``benchmark_llm``, registered through the program's own
``models.register``: the same ``DecoderLM`` in every method but
``init_params``, which runs the program's own draw under one ``jit`` and
casts each leaf to the served dtype inside it. The values are
``init_params(seed)``'s, rounded once to bfloat16 as
``GenerateServer.load`` would round them.

**The costs.** Operations and bytes a step must do, from shapes: the
benchmark's own copy. The program has the same arithmetic
(``DecoderLM.flops_per_token``, ``dispatch_read_bytes``); a roofline share
computed with the program's own functions could be moved by a change to
them, so these are kept here. Everything counted is work the algorithm
needs: padding rows the program chooses to compute are counted for prefill
(the share is of the padded bucket it ran), recomputation and copies are
not. A dense decoder reads and computes the same whatever was routed, so
``counters`` is not read.
"""

from __future__ import annotations

from benchmark.manifest import ManifestError

FAMILY = "benchmark_llm"

# Agreement asked of the served path, as max |served - reference| over the
# compared logits divided by the reference logits' standard deviation.
# The served path computes in bfloat16 with float32 accumulation: each
# activation is rounded to 8 bits of mantissa some hundred times along the
# depth, and the largest of ~10^5 compared logits sits 4-5 deviations out.
# On the chip the ratio read 0.052 (InternLM2-1.8B, 24 layers) and 0.040
# (Mistral widths, 14 layers) (my chip runs, PR 24). A lower precision is
# far off: with the weights alone rounded to 8-bit floats the reference's
# own logits move by 0.40 (e5m2) and 0.68 (e4m3) at InternLM2-1.8B's
# widths (CPU, float32 maths, PR 24). So 0.1: twice what bfloat16 reads, a
# quarter of what 8 bits give.
TOLERANCE = 0.1

BYTES = 2  # bfloat16 weights and cache


# -- the served family ---------------------------------------------------------

def __getattr__(name: str):
    # ``SeededDecoderLM`` is built when the program asks for it by its
    # dotted path: defining it imports the program, and with it jax
    if name != "SeededDecoderLM":
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    from seldon_core_tpu.models.llm import DecoderLM

    class SeededDecoderLM(DecoderLM):
        def init_params(self, seed: int = 0):
            import jax
            import jax.numpy as jnp

            dt = jnp.dtype(self.cfg.dtype)
            draw = super().init_params

            def served(s):
                return jax.tree_util.tree_map(lambda a: a.astype(dt), draw(s))

            return jax.jit(served)(jnp.uint32(seed))

    globals()[name] = SeededDecoderLM
    return SeededDecoderLM


def register() -> None:
    from seldon_core_tpu import models

    models.register(FAMILY, f"{__name__}.SeededDecoderLM")


def model_kwargs(cfg: dict, seed: int) -> dict:
    """The published config's keys as ``DecoderLM`` takes them."""
    if cfg["hidden_size"] != cfg["num_attention_heads"] * cfg["head_dim"]:
        raise ManifestError(f"{cfg['name']}: hidden != heads x head_dim")
    return {
        "vocab_size": cfg["vocab_size"],
        "d_model": cfg["hidden_size"],
        "n_layers": cfg["num_hidden_layers"],
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "d_ff": cfg["intermediate_size"],
        "max_seq": cfg["server"]["max_seq"],
        "rope_theta": float(cfg["rope_theta"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "dtype": cfg["torch_dtype"],
        "residual_scale": cfg["weights"]["residual_scale"],
        # PRNGKey takes 32 bits; the driver's seeds are larger
        "seed": seed % (2**31 - 1),
    }


def rehearsal(cfg: dict) -> dict:
    """The sizes ``--rehearse-cpu`` puts over the configuration's."""
    return {
        "hidden_size": 256, "num_attention_heads": 2, "num_key_value_heads": 1,
        "head_dim": 128, "intermediate_size": 512, "num_hidden_layers": 2,
        "vocab_size": 1024,
    }


# -- the served model against the plain reference ----------------------------------

def compare_served(model, params, seed: int, prompt_len: int = 256,
                   decode_steps: int = 4) -> dict:
    """Prefill, then ``decode_steps`` steps through the cache, as the
    served model computes them, against one full forward pass of the
    reference over the same tokens. Logits are compared, not tokens: with
    random weights the largest logit changes on rounding."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import decoder as reference

    cfg = model.cfg
    rng = np.random.default_rng(seed % (2**63))
    total = prompt_len + decode_steps
    tokens = rng.integers(0, cfg.vocab_size, size=total, dtype=np.int64)
    cache_len = -(-total // 128) * 128
    prompt = jnp.asarray(tokens[None, :prompt_len], jnp.int32)
    served = []
    first, cache = jax.jit(
        lambda p, t: model.prefill(p, t, cache_len)
    )(params, prompt)
    served.append(np.asarray(first[0]))
    step = jax.jit(
        lambda p, c, tok, pos: model.decode_step_ragged(p, c, tok, pos, cache_len)
    )
    for i in range(decode_steps):
        pos = prompt_len + i
        out, cache = step(params, cache,
                          jnp.asarray(tokens[None, pos:pos + 1], jnp.int32),
                          jnp.asarray([pos], jnp.int32))
        served.append(np.asarray(out[0]))
    del cache
    served = np.stack(served)
    positions = list(range(prompt_len - 1, total))
    ref = reference.logits(params, cfg, tokens, positions)
    scale = float(ref.std())
    err = float(np.max(np.abs(served - ref))) / scale
    return {
        "ratio": err, "tolerance": TOLERANCE, "logit_std": scale,
        "positions": len(positions), "prompt_len": prompt_len,
        "finite": bool(np.isfinite(served).all()),
        "ok": bool(np.isfinite(served).all() and err <= TOLERANCE),
    }


# -- what a step must read and a prefill must compute -------------------------------

def layer_matmul_params(cfg: dict) -> int:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return d * q + 2 * d * kv + q * d + 3 * d * f


def kv_bytes_per_position(cfg: dict) -> int:
    return (cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"]
            * cfg["head_dim"] * BYTES)


def decode_step_bytes(cfg: dict, live_positions: float, counters: dict) -> float:
    """Bytes one decode step must read: every layer's matrices and norms,
    the final norm and the head once, and the keys and values cached for
    the live requests. The embedding table is not read (one row a lane)."""
    d = cfg["hidden_size"]
    weights = (cfg["num_hidden_layers"] * (layer_matmul_params(cfg) + 2 * d)
               + d + d * cfg["vocab_size"]) * BYTES
    return weights + live_positions * kv_bytes_per_position(cfg)


def prefill_flops(cfg: dict, padded_tokens: float, sequences: float,
                  counters: dict) -> float:
    """FLOPs of prefilling ``sequences`` prompts padded to ``padded_tokens``
    positions in all: the layer matmuls for every padded position, causal
    attention (half the square), and the head at each prompt's last
    position. Only the sum of the padded lengths is known, so the sum of
    their squares is taken at its least, (sum)^2 / n: never counted high."""
    if sequences <= 0:
        return 0.0
    layers = cfg["num_hidden_layers"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    matmuls = 2.0 * layers * layer_matmul_params(cfg) * padded_tokens
    attention = 2.0 * layers * q * padded_tokens ** 2 / sequences
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * sequences
    return matmuls + attention + head
