"""Plain reference of the lfm2_moe decoder (LFM2-24B-A2B): the yardstick
``correct`` is held to for a configuration of ``"architecture": "lfm2_moe"``.

Written from the published config's keys and the layer equations of the
``lfm2_moe`` modelling code as remembered (the configuration file's
``assumed`` lists what the config does not state), in straightforward
``jax.numpy``: float32, ``highest`` matmul precision, no kernel, no cache,
no batching: the convolution is a sum of shifted copies of the whole
sequence, attention one causal softmax over every earlier key. It shares
no code with ``seldon_core_tpu``. Weights are cast to float32 one layer's
piece at a time, attention goes through in blocks of queries, the dense
FFN in blocks of rows and the head in vocabulary blocks, so that 14
thousand positions at the published widths fit beside the served model
and its cache. Each piece of a layer runs under one ``jax.jit``
(``_pieces``).

    N(x) = x * rsqrt(mean(x^2) + eps) * w
    every layer:  h = x + Op(N_op(x));  y = h + FFN(N_ffn(h))

    conv:  [B | C | u] = a W_in;  z = B * u
           c_t = sum_{j < K} w[j] * z_{t - (K - 1) + j}      (z_s = 0, s < 0)
           Op = (C * c) W_out
    full_attention:
           q = a W_q (H heads), k = a W_k, v = a W_v (KV heads) of head_dim
           q, k = N_q(q), N_k(k) over each head; half-split rotary over the
           whole head, theta^(-i / (head_dim / 2))
           p = softmax(q . k / sqrt(head_dim)), causal;  Op = concat(p v) W_o
    FFN:   SwiGLU(d_ff) in the first ``n_dense_layers`` layers; after them
           s = sigmoid(m W_r) over ALL experts; picks = top k of s + b
           w = s[picks] / (sum(s[picks]) + 1e-6) x route_scale
           FFN(m) = sum over the picks that are HELD of w_e expert_e(m)
    logits = N_f(h_L) E^T        (the head is the embedding's transpose)

``held = (lo, n)``: the parameters hold experts ``lo .. lo + n - 1`` of
each expert layer, one chip's share; a pick that lands on another chip's
expert adds nothing, here as in the served model. None: all of them.

Departures from the published code, each a choice of form and none of
mathematics: the convolution's weight is held [K, d_model] (the
checkpoint's [d_model, 1, K] transposed: tap j still multiplies the input
``K - 1 - j`` positions back); ``W_in``'s three blocks are read B, C, u in
that order; the published code keeps ``K`` inputs a lane in its cache of
which the oldest is never read again, the served model keeps ``K - 1``; no
auxiliary loss, no dropout: inference. The served model's router adds 1e-20
to the weights' sum where this file adds the published 1e-6: a relative
1e-6 of a weight.
"""

from __future__ import annotations

import numpy as np

# the dense reference's vocabulary block: one copy among the references
# (none is the program's)
from benchmark.reference.decoder import HEAD_BLOCK
# the plain RMSNorm and SwiGLU, the float32 load and the controls' rounding
# to e4m3 (op by op, outside any ``jit``): the joyai reference's, the same
# arithmetic
from benchmark.reference.joyai_llm_flash import (
    _e4m3, _load, _norm, _swiglu, _weights)

QUERY_BLOCK = 256     # x 14k keys x 32 heads of float32 scores: 0.46 GB
ROW_BLOCK = 4096      # the dense FFN's rows a call: 11,776 wide, three times
CONV, FULL = "conv", "full_attention"

# the wrong models the controls compute, each of which must fail a limit
VARIANTS = ("weights_8bit", "bias_in_weights", "taps_reversed", "no_qk_norm",
            "rope_theta_1e4")


def _rotary(x, theta):
    """x [T, H, d]: position t turns the pair (i, i + d / 2) by ``t x
    theta^(-2i / d)``."""
    import jax.numpy as jnp

    t, _h, d = x.shape
    half = d // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _short_conv(a, cfg, variant, w):
    """The gated short convolution over a [T, D] float32: its output and
    the convolution's input ``z = B * u`` [T, D], whose last ``K - 1`` rows
    are what a cache keeps."""
    import jax.numpy as jnp

    t, d = a.shape
    bcu = a @ w("w_in")
    z, gate = bcu[:, :d] * bcu[:, 2 * d:], bcu[:, d:2 * d]
    taps = w("conv_w")
    if variant == "taps_reversed":
        taps = taps[::-1]
    k = taps.shape[0]
    padded = jnp.pad(z, ((k - 1, 0), (0, 0)))
    c = sum(taps[j] * padded[j:j + t] for j in range(k))
    return (gate * c) @ w("w_out"), z


def _project(a, cfg, variant, w):
    """An attention layer's projections of a [T, D] float32: q [T, H, Dh],
    and the rows a cache holds, k (normed, rotated) and v [T, KV, Dh]."""
    t = a.shape[0]
    dh = cfg.head_dim
    theta = 1e4 if variant == "rope_theta_1e4" else cfg.rope_theta
    q = (a @ w("wq")).reshape(t, cfg.n_heads, dh)
    k = (a @ w("wk")).reshape(t, cfg.n_kv_heads, dh)
    v = (a @ w("wv")).reshape(t, cfg.n_kv_heads, dh)
    if variant != "no_qk_norm":
        q = _norm(q, w("q_norm"), cfg.norm_eps)
        k = _norm(k, w("k_norm"), cfg.norm_eps)
    return _rotary(q, theta), _rotary(k, theta), v


def _attend(q, lo, k, v, wo, cfg):
    """A block of queries, the first at position ``lo``, over all the
    keys: plain causal grouped-query attention, then ``W_o``."""
    import jax
    import jax.numpy as jnp

    n_q = q.shape[0]
    rep = cfg.n_heads // cfg.n_kv_heads
    q = q.reshape(n_q, cfg.n_kv_heads, rep, cfg.head_dim)
    s = jnp.einsum("qgrd,kgd->grqk", q, k) / np.sqrt(cfg.head_dim)
    seen = jnp.arange(k.shape[0])[None, :] <= (lo + jnp.arange(n_q))[:, None]
    s = jnp.where(seen[None, None], s, -jnp.inf)
    o = jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(s, -1), v)
    return o.reshape(n_q, -1) @ wo


def _routed_ffn(m, p, cfg, held, variant, route_as=None):
    """m [R, D] float32 -> (FFN(m), the router's picks [R, k] over all
    experts, the scores [R, E] it selected on, the weights [R, k] it gave
    the experts the rows were sent to) for one block of rows.
    ``route_as`` [R, k]: experts to send the rows to in place of the
    router's own picks (which are still returned); the weights are the
    router's own scores of those experts."""
    import jax
    import jax.numpy as jnp

    def load(name):
        return _load(p[name])

    s = jax.nn.sigmoid(m @ load("router"))
    chosen_on = s + load("expert_bias")
    _, own = jax.lax.top_k(chosen_on, cfg.experts_per_tok)
    picks = own if route_as is None else jnp.asarray(route_as, own.dtype)
    # the bias enters the selection only; the control weighs by it too
    sel = jnp.take_along_axis(
        chosen_on if variant == "bias_in_weights" else s, picks, -1)
    w = sel / (sel.sum(-1, keepdims=True) + 1e-6) * cfg.route_scale
    lo, n = held if held is not None else (0, s.shape[-1])

    def expert(out, held_e):
        # every held expert over every row, weighted 0 where a row did not
        # pick it: no shape, gather or host decision depends on the data
        e, w1, w3, w2 = held_e
        we = jnp.sum(jnp.where(picks == lo + e, w, 0.0), -1, keepdims=True)
        return out + we * _swiglu(m, _load(w1), _load(w3), _load(w2)), None

    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(m),
        (jnp.arange(n), p["we1"][:n], p["we3"][:n], p["we2"][:n]))
    return out, own, chosen_on, w


_PIECES: dict = {}


def _pieces(cfg, variant):
    """The layer's pieces, each under one ``jax.jit``: the arithmetic is
    the functions' above; compiled, a forward is a few dozen programs."""
    import jax

    key = (variant, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.rope_theta,
           cfg.norm_eps, cfg.experts_per_tok, cfg.route_scale,
           cfg.experts_held)
    if key not in _PIECES:
        held = cfg.experts_held

        def w_of(p):
            return lambda name: _load(p[name])

        _PIECES[key] = {
            "norm": jax.jit(lambda x, w: _norm(
                x, w.astype(x.dtype), cfg.norm_eps)),
            "conv": jax.jit(lambda a, p: _short_conv(a, cfg, variant, w_of(p))),
            "project": jax.jit(lambda a, p: _project(a, cfg, variant, w_of(p))),
            "attend": jax.jit(lambda q, lo, k, v, wo: _attend(
                q, lo, k, v, _load(wo), cfg)),
            "dense": jax.jit(lambda m, p: _swiglu(
                m, *(w_of(p)(n) for n in ("w1", "w3", "w2")))),
            "routed": jax.jit(lambda m, p, route_as: _routed_ffn(
                m, p, cfg, held, variant, route_as)),
            "head": jax.jit(lambda x, e: x @ _load(e).T),
        }
    return _PIECES[key]


SHORT_CONV = ("w_in", "conv_w", "w_out")
ATTENTION = ("wq", "wk", "wv", "q_norm", "k_norm")
DENSE = ("w1", "w3", "w2")
ROUTED = ("router", "expert_bias", "we1", "we3", "we2")


def forward(params, cfg, tokens, positions, variant: str = "",
            route_as=None) -> tuple:
    """Full causal forward over ``tokens`` [T]. Returns float32 logits
    [len(positions), V] at the given positions; per routed layer, the
    router's picks [T, k] and the scores ``s + b`` [T, E] it selected on;
    per attention layer the rows a cache of it holds, ``(k, v)`` each [T,
    KV, Dh] float32 (k normed and rotated); and per convolution layer the
    convolution's input ``z`` [T, D], of which a cache holds the last ``K -
    1`` rows; and per routed layer the weights [T, k] of the experts the
    positions were sent to (the router's own picks, or ``route_as``). ``cfg`` needs n_heads, n_kv_heads, head_dim, rope_theta,
    norm_eps, layer_types, n_dense_layers, experts_per_tok, route_scale
    and experts_held.

    ``params`` hold the share ``cfg.experts_held`` of each expert layer's
    experts (None: all of them).

    ``route_as`` (per routed layer [T, k], optional) routes every position
    as given, so that logits can be compared under one routing; the
    router's OWN picks and scores are returned either way.

    ``variant`` computes a WRONG model for the controls, which must fail
    (``VARIANTS``): "weights_8bit" (every matrix rounded to e4m3 as it is
    loaded), "bias_in_weights" (``expert_bias`` added to the picked scores
    that are normed into weights, as well as to the selection),
    "taps_reversed" (the convolution's taps in the other order),
    "no_qk_norm" (``q_layernorm`` and ``k_layernorm`` left out),
    "rope_theta_1e4"."""
    import jax
    import jax.numpy as jnp

    if variant and variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}: {VARIANTS}")
    tokens = jnp.asarray(tokens, jnp.int32)
    t = tokens.shape[0]
    all_picks, all_scores, all_kv, all_z, all_weights = [], [], [], [], []
    with jax.default_matmul_precision("highest"):
        piece = _pieces(cfg, variant)
        x = params["embed"][tokens].astype(jnp.float32)
        for layer, (p, kind) in enumerate(zip(params["layers"],
                                              cfg.layer_types)):
            p = _weights(p, variant)
            a = piece["norm"](x, p["ln_op"])
            if kind == CONV:
                out, z = piece["conv"](a, {n: p[n] for n in SHORT_CONV})
                x = x + out
                all_z.append(np.asarray(z))
            else:
                q, k, v = piece["project"](a, {n: p[n] for n in ATTENTION})
                all_kv.append((np.asarray(k), np.asarray(v)))
                for lo in range(0, t, QUERY_BLOCK):   # a block of queries
                    x = x.at[lo:lo + QUERY_BLOCK].add(piece["attend"](
                        q[lo:lo + QUERY_BLOCK], jnp.int32(lo), k, v, p["wo"]))
                del q, k, v
            m = piece["norm"](x, p["ln_ffn"])
            if layer < cfg.n_dense_layers:
                for lo in range(0, t, ROW_BLOCK):       # a block of rows
                    x = x.at[lo:lo + ROW_BLOCK].add(piece["dense"](
                        m[lo:lo + ROW_BLOCK], {n: p[n] for n in DENSE}))
                continue
            given = None if route_as is None else jnp.asarray(
                route_as[len(all_picks)], jnp.int32)
            out, own, scores, weights = piece["routed"](
                m, {n: p[n] for n in ROUTED}, given)
            x = x + out
            all_picks.append(np.asarray(own))
            all_scores.append(np.asarray(scores))
            all_weights.append(np.asarray(weights))
        x = piece["norm"](x, params["ln_f"])[jnp.asarray(positions)]
        vocab = params["embed"].shape[0]
        out = [
            np.asarray(piece["head"](x, _weights(
                params["embed"][lo:lo + HEAD_BLOCK], variant)))
            for lo in range(0, vocab, HEAD_BLOCK)
        ]
    return (np.concatenate(out, axis=-1), all_picks, all_scores, all_kv, all_z,
            all_weights)


def logits(params, cfg, tokens, positions) -> np.ndarray:
    return forward(params, cfg, tokens, positions)[0]


def generate(params, cfg, prompt, new_tokens: int) -> list:
    """The greedy loop, one full forward a token (over the whole length
    each time: what lies after a position does not reach it, and one shape
    compiles once): what a served greedy request's tokens are compared
    with at a small size."""
    tokens = np.zeros(len(prompt) + new_tokens, np.int64)
    tokens[:len(prompt)] = prompt
    for at in range(len(prompt), len(tokens)):
        tokens[at] = int(np.argmax(logits(params, cfg, tokens, [at - 1])[0]))
    return tokens[len(prompt):].tolist()
