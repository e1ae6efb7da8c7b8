"""Plain reference of the joyai_llm_flash decoder (JoyAI-LLM-Flash): the
yardstick ``correct`` is held to for a configuration of ``"architecture":
"joyai_llm_flash"``.

Written from the published config's keys and the layer equations of the
DeepSeek-V3 family's public modelling code (whose keys this config
carries), in straightforward ``jax.numpy``: float32, ``highest`` matmul
precision, no kernel, no cache, no batching, and NO ABSORPTION: keys and
values are expanded from the latent at every position and attended to as
any multi-head attention is. It shares no code with ``seldon_core_tpu``.
Weights are cast to float32 one layer's piece at a time, attention goes
through in blocks of queries and the head in vocabulary blocks, so that 6k
positions at the published widths fit beside the served model and its
cache. Each piece of a layer runs under one ``jax.jit`` (``_pieces``):
op by op a forward was some 800 programs to compile in every process.

    N(x) = x * rsqrt(mean(x^2) + eps) * w
    every layer:  h = x + MLA(N_in(x));  y = h + FFN(N_post(h))

    MLA:  c_q = N(a W_qa);  [q_n | q_r] = c_q W_qb      H x (nope | rope)
          [c | k_r] = a W_kva;  c = N(c)                rank | rope
          q_r, k_r = rotary on the PAIRS (2i, 2i + 1), theta^(-2i / rope)
          k_n[h] = c W_UK[h];  v[h] = c W_UV[h]
          p = softmax(([q_n | q_r] . [k_n | k_r]) / sqrt(nope + rope)), causal
          MLA = concat_h(p v[h]) W_o
    FFN:  SwiGLU(d_ff) in the first ``n_dense_layers`` layers; after them
          s = sigmoid(m W_r) over ALL experts; picks = top k of s + b
          w = s[picks] / sum(s[picks]) x route_scale
          FFN(m) = sum over the picks that are HELD of w_e expert_e(m)
                   + shared(m)
    logits = N(h_L) W_head

``held = (lo, n)``: the parameters hold experts ``lo .. lo + n - 1`` of
each expert layer, one chip's share; a pick that lands on another chip's
expert adds nothing, here as in the served model. None: all of them.

Departures from the published code, each a choice of form and none of
mathematics: the checkpoint's one ``kv_b_proj`` is read as the per-head
stacks ``w_uk`` [H, nope, rank] and ``w_uv`` [H, rank, v] the served model
holds (the same linear map); the rotary turns the pairs where they lie
(the public code first moves them into halves: a permutation of the dims
that ``q_r . k_r`` does not see); the multi-token-prediction module is not
part of the forward pass; no auxiliary loss, no dropout: inference.
"""

from __future__ import annotations

import numpy as np

# the dense reference's vocabulary block: one copy among the references
# (none is the program's)
from benchmark.reference.decoder import HEAD_BLOCK

QUERY_BLOCK = 512

# the wrong models the controls compute, each of which must fail a limit
VARIANTS = ("weights_8bit", "latent_8bit", "rotary_half_split",
            "route_scale_1", "scale_128", "latent_unnormed")


def _e4m3(a):
    """Rounded to 8-bit floats and back: the nearest precision below
    bfloat16."""
    import jax.numpy as jnp

    return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _load(a):
    import jax.numpy as jnp

    return a.astype(jnp.float32)


def _weights(tree, variant):
    """A piece's parameters as the piece is given them: as they are, or
    under the control "weights_8bit" every matrix rounded to e4m3. The
    rounding runs op by op, OUTSIDE the pieces' ``jit``: inside one the
    compiler takes a pair of converts out (it may keep excess precision),
    and the control then rounds nothing."""
    import jax

    if variant != "weights_8bit":
        return tree
    return jax.tree_util.tree_map(
        lambda a: _e4m3(a) if a.ndim >= 2 else a, tree)


def _norm(x, w, eps):
    import jax.numpy as jnp

    return x * jnp.reciprocal(
        jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * w


def _swiglu(m, w1, w3, w2):
    import jax

    return (jax.nn.silu(m @ w1) * (m @ w3)) @ w2


def _rotary(x, theta, half_split=False):
    """x [T, H, d]: position t turns pair i by ``t x theta^(-2i / d)``; the
    pair is dims (2i, 2i + 1), or under the control (i, i + d / 2)."""
    import jax.numpy as jnp

    t, _h, d = x.shape
    half = d // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    if half_split:
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(x.shape)


def _project(a, cfg, variant, w):
    """The MLA projections of a [T, D] float32: the queries' two parts and
    the rows a cache holds, the normed ``c`` [T, rank] and the rotated
    ``k_r`` [T, rope]."""
    heads, rank = cfg.n_heads, cfg.kv_lora_rank
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    t = a.shape[0]
    half_split = variant == "rotary_half_split"
    cq = _norm(a @ w("w_qa"), w("q_norm"), cfg.norm_eps)
    q = (cq @ w("w_qb")).reshape(t, heads, nope + rope)
    q_n, q_r = q[..., :nope], _rotary(q[..., nope:], cfg.rope_theta, half_split)
    kv = a @ w("w_kva")
    c = kv[:, :rank]
    if variant != "latent_unnormed":
        c = _norm(c, w("kv_norm"), cfg.norm_eps)
    k_r = _rotary(kv[:, None, rank:], cfg.rope_theta, half_split)[:, 0]
    return q_n, q_r, c, k_r


def _expand(c, w_uk, w_uv):
    """Every head's keys and values from the cached latent."""
    import jax.numpy as jnp

    return (jnp.einsum("tc,hnc->thn", c, w_uk),
            jnp.einsum("tc,hcv->thv", c, w_uv))


def _attend(q_n, q_r, lo, k_n, k_r, v, wo, cfg, variant):
    """A block of queries, the first at position ``lo``, over all the
    keys: plain causal multi-head attention, then ``W_o``."""
    import jax
    import jax.numpy as jnp

    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    scale = 1.0 / np.sqrt(nope if variant == "scale_128" else nope + rope)
    s = (jnp.einsum("qhn,khn->hqk", q_n, k_n)
         + jnp.einsum("qhr,kr->hqk", q_r, k_r)) * scale
    seen = jnp.arange(k_n.shape[0])[None, :] <= (
        lo + jnp.arange(q_n.shape[0]))[:, None]
    s = jnp.where(seen[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khv->qhv", jax.nn.softmax(s, -1), v)
    return o.reshape(q_n.shape[0], -1) @ wo


def _routed_ffn(m, p, cfg, held, variant, route_as=None):
    """m [R, D] float32 -> (FFN(m), the router's picks [R, k] over all
    experts, the scores [R, E] it selected on) for one block of rows.
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
    sel = jnp.take_along_axis(s, picks, -1)
    scale = 1.0 if variant == "route_scale_1" else cfg.route_scale
    w = sel / (sel.sum(-1, keepdims=True) + 1e-20) * scale
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
    if cfg.n_shared_experts:
        out = out + _swiglu(m, load("ws1"), load("ws3"), load("ws2"))
    return out, own, chosen_on


_PIECES: dict = {}


def _pieces(cfg, variant):
    """The layer's pieces, each under one ``jax.jit``: the arithmetic is
    the functions' above; compiled, a forward is a few dozen programs, and
    op by op it was ~800 of a third of a second each in every process that
    takes the comparison."""
    import jax

    key = (variant, cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
           cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.rope_theta, cfg.norm_eps,
           cfg.experts_per_tok, cfg.n_shared_experts, cfg.route_scale,
           cfg.experts_held)
    if key not in _PIECES:
        held = cfg.experts_held

        def w_of(p):
            return lambda name: _load(p[name])

        _PIECES[key] = {
            "norm": jax.jit(lambda x, w: _norm(
                x, w.astype(x.dtype), cfg.norm_eps)),
            "project": jax.jit(lambda a, p: _project(a, cfg, variant, w_of(p))),
            "expand": jax.jit(lambda c, w_uk, w_uv: _expand(
                c, _load(w_uk), _load(w_uv))),
            "attend": jax.jit(lambda q_n, q_r, lo, k_n, k_r, v, wo: _attend(
                q_n, q_r, lo, k_n, k_r, v, _load(wo), cfg, variant)),
            "dense": jax.jit(lambda m, p: _swiglu(
                m, *(w_of(p)(n) for n in ("w1", "w3", "w2")))),
            "routed": jax.jit(lambda m, p, route_as: _routed_ffn(
                m, p, cfg, held, variant, route_as)),
            "head": jax.jit(lambda x, w: x @ _load(w)),
        }
    return _PIECES[key]


ATTENTION = ("w_qa", "q_norm", "w_qb", "w_kva", "kv_norm")
DENSE = ("w1", "w3", "w2")
ROUTED = ("router", "expert_bias", "we1", "we3", "we2", "ws1", "ws3", "ws2")


def forward(params, cfg, tokens, positions, variant: str = "",
            route_as=None) -> tuple:
    """Full causal forward over ``tokens`` [T]. Returns float32 logits
    [len(positions), V] at the given positions; per routed layer, the
    router's picks [T, k] and the scores ``s + b`` [T, E] it selected on;
    and per layer the rows a cache of it holds, ``[N(c) | rope(k_r)]`` [T,
    rank + rope] float32. ``cfg`` needs n_heads, q_lora_rank,
    kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
    rope_theta, norm_eps, n_dense_layers, experts_per_tok,
    n_shared_experts, route_scale and experts_held.

    ``params`` hold the share ``cfg.experts_held`` of each expert layer's
    experts (None: all of them).

    ``route_as`` (per routed layer [T, k], optional) routes every position
    as given, so that logits can be compared under one routing; the
    router's OWN picks and scores are returned either way.

    ``variant`` computes a WRONG model for the controls, which must fail
    (``VARIANTS``): "weights_8bit" (every matrix rounded to e4m3 as it is
    loaded), "latent_8bit" (the rows ``c`` and ``k_r`` rounded to e4m3
    before anything reads them), "rotary_half_split" (the rotary pairs dim
    i with i + rope / 2), "route_scale_1" (``routed_scaling_factor`` left
    out), "scale_128" (scores over sqrt(nope)), "latent_unnormed" (``c``
    kept, and used, as it was before its RMSNorm)."""
    import jax
    import jax.numpy as jnp

    if variant and variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}: {VARIANTS}")
    tokens = jnp.asarray(tokens, jnp.int32)
    t = tokens.shape[0]
    all_picks, all_scores, all_rows = [], [], []
    with jax.default_matmul_precision("highest"):
        piece = _pieces(cfg, variant)
        x = params["embed"][tokens].astype(jnp.float32)
        for layer, p in enumerate(params["layers"]):
            p = _weights(p, variant)
            a = piece["norm"](x, p["ln_in"])
            q_n, q_r, c, k_r = piece["project"](a, {n: p[n] for n in ATTENTION})
            if variant == "latent_8bit":
                c, k_r = _e4m3(c), _e4m3(k_r)      # op by op: ``_weights``
            k_n, v = piece["expand"](c, p["w_uk"], p["w_uv"])
            all_rows.append(np.concatenate(
                [np.asarray(c), np.asarray(k_r)], axis=-1))
            for lo in range(0, t, QUERY_BLOCK):   # a block of queries at a time
                x = x.at[lo:lo + QUERY_BLOCK].add(piece["attend"](
                    q_n[lo:lo + QUERY_BLOCK], q_r[lo:lo + QUERY_BLOCK],
                    jnp.int32(lo), k_n, k_r, v, p["wo"]))
            del q_n, q_r, k_n, v
            m = piece["norm"](x, p["ln_post"])
            if layer < cfg.n_dense_layers:
                x = x + piece["dense"](m, {n: p[n] for n in DENSE})
                continue
            given = None if route_as is None else jnp.asarray(
                route_as[len(all_picks)], jnp.int32)
            out, own, scores = piece["routed"](
                m, {n: p[n] for n in ROUTED if n in p}, given)
            x = x + out
            all_picks.append(np.asarray(own))
            all_scores.append(np.asarray(scores))
        x = piece["norm"](x, params["ln_f"])[jnp.asarray(positions)]
        vocab = params["unembed"].shape[1]
        out = [
            np.asarray(piece["head"](x, _weights(
                params["unembed"][:, lo:lo + HEAD_BLOCK], variant)))
            for lo in range(0, vocab, HEAD_BLOCK)
        ]
    return np.concatenate(out, axis=-1), all_picks, all_scores, all_rows


def logits(params, cfg, tokens, positions) -> np.ndarray:
    return forward(params, cfg, tokens, positions)[0]
