"""Operations and bytes a step must do, from shapes: the benchmark's own copy.

The program has the same arithmetic (``DecoderLM.flops_per_token``,
``dispatch_read_bytes``); a roofline share computed with the program's own
functions could be moved by a change to them, so these are kept here.
``cfg`` is the configuration file's dict (the published config's keys).
Everything counted is work the algorithm needs: padding rows the program
chooses to compute are counted for prefill (the share is of the padded
bucket it ran), recomputation and copies are not.
"""

from __future__ import annotations

BYTES = 2  # bfloat16 weights and cache


def layer_matmul_params(cfg: dict) -> int:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return d * q + 2 * d * kv + q * d + 3 * d * f


def kv_bytes_per_position(cfg: dict) -> int:
    return (cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"]
            * cfg["head_dim"] * BYTES)


def decode_step_bytes(cfg: dict, live_positions: float) -> float:
    """Bytes one decode step must read: every layer's matrices and norms,
    the final norm and the head once, and the keys and values cached for
    the live requests. The embedding table is not read (one row a lane)."""
    d = cfg["hidden_size"]
    weights = (cfg["num_hidden_layers"] * (layer_matmul_params(cfg) + 2 * d)
               + d + d * cfg["vocab_size"]) * BYTES
    return weights + live_positions * kv_bytes_per_position(cfg)


def prefill_flops(cfg: dict, padded_tokens: float, sequences: float) -> float:
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
