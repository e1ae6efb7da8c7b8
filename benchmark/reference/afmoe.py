"""Plain reference of the afmoe decoder (arcee-ai Trinity): the yardstick
``correct`` is held to for a configuration of ``"architecture": "afmoe"``.

Written from the published config's keys and the layer equations of the
family's public modelling code, in straightforward ``jax.numpy``: float32,
``highest`` matmul precision, no kernel, no cache, no batching, no scan,
experts by a plain loop over the experts with a mask. It shares no code
with ``seldon_core_tpu``. Weights are taken one matrix (one expert) at a
time and cast to float32 as they are used; attention goes one KV head's
queries at a time, the rows through a layer's FFN in blocks of 4096 and the
head in vocabulary blocks, so that 2.3k positions at Trinity-Mini's widths
fit beside the served model.

    h0 = E[token] * sqrt(hidden)
    a = RMSNorm_in(h); q, k, v, g = a Wq, a Wk, a Wv, a Wg
    q, k = RMSNorm over each head's 128 (one weight vector each)
    sliding layer: half-split rotary on q, k; key j seen by query i iff
        i - window < j <= i.      full layer: NO rotary; j <= i
    o = softmax(q k^T / sqrt(head_dim)) v, flattened, * sigmoid(g)
    h = h + RMSNorm_post_attn(o Wo)
    m = RMSNorm_pre_mlp(h); h = h + RMSNorm_post_mlp(FFN(m))
    dense FFN: (silu(m W1) * (m W3)) W2
    routed FFN: s = sigmoid(m Wr); picks = top_k(s + b); w = s[picks] /
        (sum s[picks] + 1e-20) * route_scale;
        FFN(m) = shared(m) + sum_k w_k expert_{pick_k}(m)
    logits = RMSNorm(h_L) W_head

Departures from the published code, each a choice of form and none of
mathematics: the grouped matmul (``use_grouped_mm``) is a loop over
experts; ``expert_bias`` is read from the parameters (published as zeros)
and enters the selection only; expert groups (``n_group`` 1) are one
group; no auxiliary loss, no dropout: inference.
"""

from __future__ import annotations

import numpy as np

# the dense reference's norm, half-split rotary and vocabulary block: one
# copy of each among the references (none is the program's)
from benchmark.reference.decoder import HEAD_BLOCK, _rms_norm, _rope

ROW_BLOCK = 4096
SLIDING = "sliding_attention"


def _load(a, variant):
    """One matrix in float32; under the control "weights_8bit" rounded to
    8-bit floats (e4m3) on the way: the nearest precision below bfloat16."""
    import jax.numpy as jnp

    if variant == "weights_8bit" and a.ndim >= 2:
        a = a.astype(jnp.float8_e4m3fn)
    return a.astype(jnp.float32)


def _swiglu(m, w1, w3, w2):
    import jax

    return (jax.nn.silu(m @ w1) * (m @ w3)) @ w2


def _routed_ffn(m, p, cfg, variant, route_as=None):
    """m [R, D] float32 -> (FFN(m), picks [R, k], selection scores [R, E])
    for one block of rows. ``route_as`` [R, k]: experts to send the rows
    to in place of the router's own picks (which are still returned, with
    the scores ``s + b`` they are the top k of); the weights are the
    router's own scores of those experts."""
    import jax
    import jax.numpy as jnp

    def load(name, e=None):
        return _load(p[name] if e is None else p[name][e], variant)

    s = jax.nn.sigmoid(m @ load("router"))
    bias = load("expert_bias")
    _, picks = jax.lax.top_k(s + bias, cfg.experts_per_tok)
    base = s + bias if variant == "bias_in_weights" else s
    own = picks
    if route_as is not None:
        picks = jnp.asarray(route_as, picks.dtype)
    sel = jnp.take_along_axis(base, picks, -1)
    w = sel / (sel.sum(-1, keepdims=True) + 1e-20) * cfg.route_scale
    out = jnp.zeros_like(m)
    for e in range(cfg.n_routed_experts):
        # every expert over every row, weighted 0 where a row did not pick
        # it: 16 times the arithmetic of the picks, and no shape, gather or
        # host decision depends on the data
        we = jnp.sum(jnp.where(picks == e, w, 0.0), -1, keepdims=True)
        out = out + we * _swiglu(m, load("we1", e), load("we3", e), load("we2", e))
        if e % 8 == 7:
            # the host runs far ahead of the device, and every expert in
            # flight holds its float32 matrices: 1.5 GB of the chip, more
            # than anything served, when all 128 were let go at once
            out.block_until_ready()
    if cfg.n_shared_experts:
        out = out + _swiglu(m, load("ws1"), load("ws3"), load("ws2"))
    return out, own, s + bias


def forward(params, cfg, tokens, positions, variant: str = "",
            route_as=None) -> tuple:
    """Full causal forward over ``tokens`` [T]. Returns float32 logits
    [len(positions), V] at the given positions and, per routed layer, the
    router's picks [T, k] and the scores [T, E] it selected on. ``cfg`` needs n_heads, n_kv_heads, head_dim, d_model,
    rope_theta, norm_eps, layer_types, sliding_window, n_dense_layers,
    n_routed_experts, experts_per_tok, n_shared_experts, route_scale.

    ``route_as`` (per routed layer [T, k], optional) routes every position
    as given, so that logits can be compared under one routing: a router
    score that differs by rounding flips a pick, and the two models then
    run different experts at that position. The router's OWN picks and
    scores are returned either way: how far the routing itself agrees is
    theirs to say.

    ``variant`` computes a WRONG model for the controls, which must fail:
    "no_window", "rope_on_full", "no_gate", "bias_in_weights", and
    "weights_8bit" (every matrix rounded to e4m3 as it is loaded)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    heads, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    tokens = jnp.asarray(tokens, jnp.int32)
    t = tokens.shape[0]
    i = jnp.arange(t)[:, None]
    j = jnp.arange(t)[None, :]
    all_picks, all_scores = [], []
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(f32) * np.sqrt(cfg.d_model)
        for layer, p in enumerate(params["layers"]):
            def w(name, p=p):
                return _load(p[name], variant)

            sliding = cfg.layer_types[layer] == SLIDING
            a = _rms_norm(x, w("ln_in"), cfg.norm_eps)
            q = _rms_norm((a @ w("wq")).reshape(t, heads, dh), w("q_norm"),
                          cfg.norm_eps)
            k = _rms_norm((a @ w("wk")).reshape(t, kv, dh), w("k_norm"),
                          cfg.norm_eps)
            v = (a @ w("wv")).reshape(t, kv, dh)
            g = a @ w("wg")
            if sliding or variant == "rope_on_full":
                q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
            seen = j <= i
            if sliding and variant != "no_window":
                seen = seen & (j > i - cfg.sliding_window)
            rep = heads // kv
            groups = []
            for grp in range(kv):       # one KV head's queries at a time
                qg = q[:, grp * rep:(grp + 1) * rep]
                s = jnp.einsum("qhd,kd->hqk", qg, k[:, grp]) / np.sqrt(dh)
                s = jnp.where(seen[None], s, -jnp.inf)
                groups.append(jnp.einsum(
                    "hqk,kd->qhd", jax.nn.softmax(s, -1), v[:, grp]))
            o = jnp.concatenate(groups, axis=1).reshape(t, heads * dh)
            if variant != "no_gate":
                o = o * jax.nn.sigmoid(g)
            x = x + _rms_norm(o @ w("wo"), w("ln_post_attn"), cfg.norm_eps)
            m = _rms_norm(x, w("ln_pre_mlp"), cfg.norm_eps)
            if layer < cfg.n_dense_layers:
                f = jnp.concatenate([
                    _swiglu(m[lo:lo + ROW_BLOCK], w("w1"), w("w3"), w("w2"))
                    for lo in range(0, t, ROW_BLOCK)])
            else:
                given = None if route_as is None else route_as[len(all_picks)]
                blocks = [_routed_ffn(
                    m[lo:lo + ROW_BLOCK], p, cfg, variant,
                    None if given is None else given[lo:lo + ROW_BLOCK])
                    for lo in range(0, t, ROW_BLOCK)]
                f = jnp.concatenate([b[0] for b in blocks])
                all_picks.append(np.concatenate([np.asarray(b[1]) for b in blocks]))
                all_scores.append(np.concatenate([np.asarray(b[2]) for b in blocks]))
            x = x + _rms_norm(f, w("ln_post_mlp"), cfg.norm_eps)
        x = _rms_norm(x, params["ln_f"].astype(f32), cfg.norm_eps)
        x = x[jnp.asarray(positions)]
        vocab = params["unembed"].shape[1]
        out = [
            np.asarray(x @ _load(params["unembed"][:, lo:lo + HEAD_BLOCK], variant))
            for lo in range(0, vocab, HEAD_BLOCK)
        ]
    return np.concatenate(out, axis=-1), all_picks, all_scores


def logits(params, cfg, tokens, positions) -> np.ndarray:
    return forward(params, cfg, tokens, positions)[0]
