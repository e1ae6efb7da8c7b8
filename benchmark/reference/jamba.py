"""Plain reference of the jamba decoder (AI21-Jamba2-3B): the yardstick
``correct`` is held to for a configuration of ``"architecture": "jamba"``.

Written from the published config's keys and the layer equations of the
``jamba`` modelling code as remembered (the configuration file's ``assumed``
lists what the config does not state), in straightforward ``jax.numpy``:
float32, ``highest`` matmul precision, no kernel, no cache, no batching: the
convolution is a sum of shifted copies of the whole sequence, the
recurrence one ``lax.scan`` over time as it is written below, attention one
causal softmax over every earlier key. It shares no code with
``seldon_core_tpu``. Weights are cast to float32 one layer's piece at a
time, attention goes through in blocks of queries and the head in
vocabulary blocks, so that a few thousand positions at the published widths
fit beside the served model and its cache. Each piece of a layer runs under
one ``jax.jit`` (``_pieces``).

    N(x) = x * rsqrt(mean(x^2) + eps) * w
    every layer:  h = x + Mixer(N_in(x));  y = h + SwiGLU(N_ff(h))
    layer i is attention iff i % attn_layer_period == attn_layer_offset

    Mamba:  [a | z] = u W_in
            c_t = SiLU(sum_{j < K} w[j] * a_{t - (K - 1) + j} + b_conv)
            [dt | B | C] = c W_x;  dt, B, C = N_dt(dt), N_b(B), N_c(C)
            delta = softplus(dt W_dt + b_dt);  A = -exp(A_log)
            S_t = exp(delta_t A) * S_{t-1} + B_t (delta_t c_t)   (S_{-1} = 0)
            y_t = C_t . S_t + D * c_t
            Mixer = (y * SiLU(z)) W_out
    attention:  q = u W_q (H heads), k = u W_k, v = u W_v (KV heads) of
            head_dim, NO positional term
            p = softmax(q . k / sqrt(head_dim)), causal;  Mixer = concat(p v) W_o
    logits = N_f(h_L) E^T        (the head is the embedding's transpose)

Departures from the published code, each a choice of form and none of
mathematics: the state is held [N, C] (``A_log`` too), the published [C, N]
transposed, as the parameters come; the convolution's weight is held [K, C]
(the checkpoint's [C, 1, K] transposed: tap j still multiplies the input
``K - 1 - j`` positions back); the Mamba layers' parameters come stacked by
run (``params["runs"]``) and the attention layers' beside them
(``params["attn"]``) and are read in the layers' published order; the
published cache keeps ``K`` inputs a lane of which the oldest is never read
again, the served model keeps ``K - 1``; the published cache keeps the
state in the model's dtype and computes the update in float32, the served
model and this file keep it in float32 (``state_bf16`` is the control); no
dropout: inference.
"""

from __future__ import annotations

import numpy as np

# the dense reference's vocabulary block: one copy among the references
# (none is the program's)
from benchmark.reference.decoder import HEAD_BLOCK
# the plain RMSNorm and SwiGLU, the float32 load and the controls' rounding
# to e4m3 (op by op, outside any ``jit``); plain causal grouped-query
# attention over a block of queries and the rotary the control adds: the
# joyai and lfm2 references', the same arithmetic
from benchmark.reference.joyai_llm_flash import (
    _load, _norm, _swiglu, _weights)
from benchmark.reference.lfm2_moe import _attend, _rotary

QUERY_BLOCK = 256     # x 2.3k keys x 20 heads of float32 scores: 47 MB

# the wrong models the controls compute, each of which must fail a limit
VARIANTS = ("weights_8bit", "state_bf16", "no_dt_norm", "no_conv_bias",
            "no_D", "A_positive", "rotary")

MAMBA = ("w_in", "conv_w", "conv_b", "w_x", "dt_norm", "b_norm", "c_norm",
         "w_dt", "b_dt", "A_log", "D", "w_out")
ATTENTION = ("wq", "wk", "wv")
FFN = ("w1", "w3", "w2")


def _mamba(u, cfg, variant, w):
    """The Mamba mixer over u [T, D] float32: its output, the convolution's
    input ``a`` [T, C] (whose last ``K - 1`` rows a cache keeps) and the
    state after EVERY position [T, N, C]."""
    import jax
    import jax.numpy as jnp

    t = u.shape[0]
    n, r = cfg.mamba_d_state, cfg.mamba_dt_rank
    az = u @ w("w_in")
    c_in = az.shape[1] // 2
    a, z = az[:, :c_in], az[:, c_in:]
    taps = w("conv_w")
    k = taps.shape[0]
    padded = jnp.pad(a, ((k - 1, 0), (0, 0)))
    conv = sum(taps[j] * padded[j:j + t] for j in range(k))
    if variant != "no_conv_bias":
        conv = conv + w("conv_b")
    c = jax.nn.silu(conv)
    dbc = c @ w("w_x")
    step, b, c_ = dbc[:, :r], dbc[:, r:r + n], dbc[:, r + n:]
    if variant != "no_dt_norm":
        step = _norm(step, w("dt_norm"), cfg.norm_eps)
    b = _norm(b, w("b_norm"), cfg.norm_eps)
    c_ = _norm(c_, w("c_norm"), cfg.norm_eps)
    delta = jax.nn.softplus(step @ w("w_dt") + w("b_dt"))           # [T, C]
    a_log = jnp.exp(w("A_log"))                                     # [N, C]
    big_a = a_log if variant == "A_positive" else -a_log

    def token(s, xs):
        delta_t, c_t, b_t, c__t = xs
        s = jnp.exp(delta_t[None, :] * big_a) * s + (
            b_t[:, None] * (delta_t * c_t)[None, :])
        if variant == "state_bf16":
            # not a pair of converts: inside a ``jit`` the compiler takes
            # those out (it may keep excess precision) and nothing is
            # rounded (my chip run, PR 55: the control read the sound
            # reading digit for digit)
            s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        return s, (s, jnp.sum(s * c__t[:, None], axis=0))

    _, (states, y) = jax.lax.scan(
        token, jnp.zeros_like(big_a), (delta, c, b, c_))
    if variant != "no_D":
        y = y + w("D") * c
    return (y * jax.nn.silu(z)) @ w("w_out"), a, states


def _project(u, cfg, variant, w):
    """An attention layer's projections of u [T, D] float32: q [T, H, Dh],
    and the rows a cache holds, k and v [T, KV, Dh]."""
    t = u.shape[0]
    dh = cfg.head_dim
    q = (u @ w("wq")).reshape(t, cfg.n_heads, dh)
    k = (u @ w("wk")).reshape(t, cfg.n_kv_heads, dh)
    v = (u @ w("wv")).reshape(t, cfg.n_kv_heads, dh)
    if variant == "rotary":
        q, k = _rotary(q, 1e4), _rotary(k, 1e4)
    return q, k, v


_PIECES: dict = {}


def _pieces(cfg, variant):
    """The layer's pieces, each under one ``jax.jit``: the arithmetic is
    the functions' above; compiled, a forward is a few dozen programs."""
    import jax

    key = (variant, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.norm_eps,
           cfg.mamba_d_state, cfg.mamba_dt_rank)
    if key not in _PIECES:

        def w_of(p):
            return lambda name: _load(p[name])

        _PIECES[key] = {
            "norm": jax.jit(lambda x, w: _norm(
                x, w.astype(x.dtype), cfg.norm_eps)),
            "mamba": jax.jit(lambda u, p, at: (
                lambda out, a, states: (out, a, states[at]))(
                    *_mamba(u, cfg, variant, w_of(p)))),
            "project": jax.jit(lambda u, p: _project(u, cfg, variant, w_of(p))),
            "attend": jax.jit(lambda q, lo, k, v, wo: _attend(
                q, lo, k, v, _load(wo), cfg)),
            "ffn": jax.jit(lambda m, p: _swiglu(
                m, *(w_of(p)(n) for n in FFN))),
            "head": jax.jit(lambda x, e: x @ _load(e).T),
        }
    return _PIECES[key]


def layers_of(params, cfg):
    """The layers' parameters in the published order, ``(is attention,
    dict)``, one at a time (a generator: a Mamba layer is cut from its
    run's stack when it is asked for, 208 MB at the published widths, and
    goes when the next is): layer ``i`` is attention iff ``i %
    attn_layer_period == attn_layer_offset``."""
    import jax

    run = at = full = 0
    for i in range(cfg.n_layers):
        if i % cfg.attn_layer_period == cfg.attn_layer_offset:
            yield True, params["attn"][full]
            full += 1
            continue
        if at == params["runs"][run]["w_in"].shape[0]:
            run, at = run + 1, 0
        yield False, jax.tree_util.tree_map(
            lambda a, j=at: a[j], params["runs"][run])
        at += 1


def mamba_leaf(params, name: str) -> np.ndarray:
    """One parameter of every Mamba layer, in the layers' order, float32:
    [Lm, ...]."""
    return np.concatenate(
        [np.asarray(run[name], np.float32) for run in params["runs"]])


def forward(params, cfg, tokens, positions, variant: str = "",
            state_at=()) -> tuple:
    """Full causal forward over ``tokens`` [T]. Returns float32 logits
    [len(positions), V] at the given positions; per attention layer the rows
    a cache of it holds, ``(k, v)`` each [T, KV, Dh] float32; per Mamba layer
    the convolution's input ``a`` [T, C], of which a cache holds the last
    ``K - 1`` rows; and per Mamba layer the state AFTER each position of
    ``state_at``, [len(state_at), N, C] float32. ``cfg`` needs n_layers,
    n_heads, n_kv_heads, head_dim, norm_eps, attn_layer_period,
    attn_layer_offset, mamba_d_state and mamba_dt_rank.

    ``variant`` computes a WRONG model for the controls, which must fail
    (``VARIANTS``): "weights_8bit" (every matrix rounded to e4m3 as it is
    loaded), "state_bf16" (the state rounded to bfloat16 after every token,
    as a cache in the model's dtype would hold it), "no_dt_norm"
    (``dt_layernorm`` left out), "no_conv_bias", "no_D" (the skip term left
    out), "A_positive" (``A`` without its sign), "rotary" (a half-split
    rotary at theta 1e4 on q and k)."""
    import jax
    import jax.numpy as jnp

    if variant and variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}: {VARIANTS}")
    tokens = jnp.asarray(tokens, jnp.int32)
    t = tokens.shape[0]
    at = jnp.asarray(list(state_at), jnp.int32)
    all_kv, all_a, all_states = [], [], []
    with jax.default_matmul_precision("highest"):
        piece = _pieces(cfg, variant)
        x = params["embed"][tokens].astype(jnp.float32)
        for is_attn, p in layers_of(params, cfg):
            p = _weights(p, variant)
            u = piece["norm"](x, p["ln_in"])
            if is_attn:
                q, k, v = piece["project"](u, {n: p[n] for n in ATTENTION})
                all_kv.append((np.asarray(k), np.asarray(v)))
                for lo in range(0, t, QUERY_BLOCK):   # a block of queries
                    x = x.at[lo:lo + QUERY_BLOCK].add(piece["attend"](
                        q[lo:lo + QUERY_BLOCK], jnp.int32(lo), k, v, p["wo"]))
                del q, k, v
            else:
                out, a, states = piece["mamba"](u, {n: p[n] for n in MAMBA}, at)
                x = x + out
                all_a.append(np.asarray(a))
                all_states.append(np.asarray(states))
                del out, a, states
            m = piece["norm"](x, p["ln_ff"])
            x = x + piece["ffn"](m, {n: p[n] for n in FFN})
        x = piece["norm"](x, params["ln_f"])[jnp.asarray(positions)]
        vocab = params["embed"].shape[0]
        out = [
            np.asarray(piece["head"](x, _weights(
                params["embed"][lo:lo + HEAD_BLOCK], variant)))
            for lo in range(0, vocab, HEAD_BLOCK)
        ]
    return np.concatenate(out, axis=-1), all_kv, all_a, all_states


def logits(params, cfg, tokens, positions) -> np.ndarray:
    return forward(params, cfg, tokens, positions)[0]


def generate(params, cfg, prompt, new_tokens: int) -> list:
    """The greedy loop, one full forward a token (over the whole length
    each time: what lies after a position does not reach it, and one shape
    compiles once): what a served greedy request's tokens are compared with
    at a small size."""
    tokens = np.zeros(len(prompt) + new_tokens, np.int64)
    tokens[:len(prompt)] = prompt
    for at in range(len(prompt), len(tokens)):
        tokens[at] = int(np.argmax(logits(params, cfg, tokens, [at - 1])[0]))
    return tokens[len(prompt):].tolist()
