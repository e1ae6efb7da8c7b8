"""Plain reference of the mimo_v2 decoder (MiMo-V2.5's language model): the
yardstick ``correct`` is held to for a configuration of ``"architecture":
"mimo_v2"``.

Written from the published config's keys (the configuration file's
``assumed`` lists what the config does not state), in straightforward
``jax.numpy``: float32, ``highest`` matmul precision, no kernel, no cache,
no ring, no batching: one causal forward over the whole sequence, the
window a MASK on the scores and the sink a CONCATENATED logit. It shares no
code with ``seldon_core_tpu``. Weights are cast to float32 one layer's piece
at a time, attention goes through in blocks of queries, the dense FFN in
blocks of rows and the head in vocabulary blocks, so that twelve thousand
positions at the published widths fit beside the served model and its
cache. Each piece of a layer runs under one ``jax.jit`` (``_pieces``).

    N(x) = x * rsqrt(mean(x^2) + eps) * w
    every layer:  h = x + Attn(N_op(x));  y = h + FFN(N_ffn(h))

    Attn:  q = a W_q (H heads of Dk), k = a W_k (KV heads of Dk), v = a W_v
           (KV heads of Dv); KV = n_kv_heads in a full layer, swa_n_kv_heads
           in a window layer; the first ``rotary_dim`` dims of each q and k
           head turned, pair (i, i + rotary_dim / 2) by t x theta^(-2i /
           rotary_dim), theta = rope_theta (full) | swa_rope_theta (window)
           s_ij = q_i . k_j / sqrt(Dk); a full layer sees j <= i, a window
           layer i - swa_window < j <= i and one more logit b_h, a head's
           sink, which has no value: p = softmax([s_i, b_h])[:-1]
           Attn = concat_h(value_scale x p v) W_o
    FFN:   SwiGLU(d_ff) in the first ``n_dense_layers`` layers; after them
           s = sigmoid(m W_r) over ALL experts; picks = top k of s + b
           w = s[picks] / sum(s[picks]) x route_scale
           FFN(m) = sum over the picks that are HELD of w_e expert_e(m)
    logits = N_f(h_L) W_head

``held = (lo, n)``: the parameters hold experts ``lo .. lo + n - 1`` of
each expert layer, one chip's share; a pick that lands on another chip's
expert adds nothing, here as in the served model. None: all of them.
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
    _load, _norm, _swiglu, _weights)

QUERY_BLOCK = 64      # x 12k keys x 64 heads of float32 scores: 0.2 GB
ROW_BLOCK = 2048      # the dense FFN's rows a call
COLUMN_BLOCK = 4096   # and its columns: three float32 blocks of 67 MB
SLIDING = "sliding_attention"

# the wrong models the controls compute, each of which must fail a limit
VARIANTS = ("weights_8bit", "no_sink", "sink_on_full", "one_rope_base",
            "rotary_all", "rotary_interleaved", "window_127", "window_129",
            "no_value_scale", "kv_groups_swapped", "all_bfloat16")


def _dtype(variant):
    """What the reference computes in: float32, or under the control
    "all_bfloat16" bfloat16 wherever float32 is stated."""
    import jax.numpy as jnp

    return jnp.bfloat16 if variant == "all_bfloat16" else jnp.float32


def _rotary(x, theta, dims, interleaved=False, first=0):
    """x [n, H, d], rows at positions ``first .. first + n - 1``: of each
    head's first ``dims`` dims, position t turns pair (i, i + dims / 2) by
    ``t x theta^(-2i / dims)``; under the control the pair is (2i, 2i + 1).
    The rest of the head is as it was."""
    import jax.numpy as jnp

    t = x.shape[0]
    half = dims // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    at = first + jnp.arange(t)
    ang = at.astype(jnp.float32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    turned, rest = x[..., :dims], x[..., dims:]
    if interleaved:
        a, b = turned[..., 0::2], turned[..., 1::2]
        out = jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(
            turned.shape)
    else:
        a, b = turned[..., :half], turned[..., half:]
        out = jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)
    return jnp.concatenate([out.astype(x.dtype), rest], -1)


def _turn(x, first, cfg, window, variant):
    """The kind's rotary on x [n, H, Dk], whose first row is position
    ``first``."""
    theta = cfg.swa_rope_theta if (
        window and variant != "one_rope_base") else cfg.rope_theta
    dims = cfg.head_dim if variant == "rotary_all" else cfg.rotary_dim
    return _rotary(x, theta, dims, variant == "rotary_interleaved", first)


def _project(a, cfg, window, variant, w):
    """The rows a cache holds of a layer, from a [T, D] float32: k (rotated)
    [T, KV, Dk] and v [T, KV, Dv]."""
    t = a.shape[0]
    kv = cfg.swa_n_kv_heads if window else cfg.n_kv_heads
    k = (a @ w("wk")).reshape(t, kv, cfg.head_dim)
    v = (a @ w("wv")).reshape(t, kv, cfg.v_head_width)
    return _turn(k, 0, cfg, window, variant), v


def _attend(a, lo, k, v, wq, wo, sink, cfg, window, variant):
    """A block of rows a [n, D], the first at position ``lo``: its queries
    (projected and rotated here, a block at a time: 12 thousand positions'
    float32 queries are 0.6 GB) over all the keys: the causal mask (and the
    band's, in a window layer), the sink as one more logit a head, the
    value scale, ``W_o``."""
    import jax
    import jax.numpy as jnp

    n_q, heads = a.shape[0], cfg.n_heads
    q = _turn((a @ wq).reshape(n_q, heads, cfg.head_dim), lo, cfg, window,
              variant)
    kv = k.shape[1]
    # query head h reads key head h // (H / KV); the control groups by the
    # OTHER kind's count
    other = cfg.n_kv_heads if window else cfg.swa_n_kv_heads
    groups = other if variant == "kv_groups_swapped" else kv
    if groups != kv:
        of = (jnp.arange(heads) // (heads // groups)) % kv
        k, v, kv = k[:, of], v[:, of], heads
    q = q.reshape(n_q, kv, heads // kv, cfg.head_dim)
    s = (jnp.einsum("qgrd,kgd->grqk", q, k)
         / float(np.sqrt(cfg.head_dim))).reshape(heads, n_q, -1)
    row = (lo + jnp.arange(n_q))[:, None]
    col = jnp.arange(k.shape[0])[None, :]
    seen = col <= row
    if window:
        width = {"window_127": 127, "window_129": 129}.get(
            variant, cfg.swa_window)
        seen = seen & (col > row - width)
    s = jnp.where(seen[None], s, -jnp.inf)
    sunk = (window and variant != "no_sink") or (
        not window and variant == "sink_on_full")
    if sunk:
        column = jnp.broadcast_to(
            sink.astype(s.dtype)[:, None, None], (heads, n_q, 1))
        p = jax.nn.softmax(jnp.concatenate([s, column], -1), -1)[..., :-1]
    else:
        p = jax.nn.softmax(s, -1)
    o = jnp.einsum("grqk,kgd->qgrd", p.reshape(kv, heads // kv, n_q, -1), v)
    if variant != "no_value_scale":
        o = o * cfg.value_scale
    return o.reshape(n_q, -1) @ wo


def _routed_ffn(m, p, cfg, held, route_as=None):
    """m [R, D] float32 (the control's: bfloat16, and then everything here
    is) -> (FFN(m), the router's picks [R, k] over all
    experts, the scores [R, E] it selected on) for one block of rows.
    ``route_as`` [R, k]: experts to send the rows to in place of the
    router's own picks (which are still returned); the weights are the
    router's own scores of those experts."""
    import jax
    import jax.numpy as jnp

    _load = lambda a: a.astype(m.dtype)  # noqa: E731
    s = jax.nn.sigmoid(m @ _load(p["router"]))
    chosen_on = s + _load(p["expert_bias"])
    _, own = jax.lax.top_k(chosen_on, cfg.experts_per_tok)
    picks = own if route_as is None else jnp.asarray(route_as, own.dtype)
    sel = jnp.take_along_axis(s, picks, -1)
    w = sel / sel.sum(-1, keepdims=True) * cfg.route_scale
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
    return out, own, chosen_on


_PIECES: dict = {}
_FIELDS = ("n_heads", "n_kv_heads", "swa_n_kv_heads", "head_dim",
           "v_head_width", "rotary_dim", "swa_window", "rope_theta",
           "swa_rope_theta", "value_scale", "norm_eps", "experts_per_tok",
           "route_scale", "experts_held")


def _pieces(cfg, variant):
    """The layer's pieces, each under one ``jax.jit``: the arithmetic is
    the functions' above; compiled, a forward is a few dozen programs."""
    import jax

    key = (variant, *(getattr(cfg, name) for name in _FIELDS))
    if key not in _PIECES:
        dt = _dtype(variant)
        _load = lambda a: a.astype(dt)  # noqa: E731

        def w_of(p):
            return lambda name: _load(p[name])

        _PIECES[key] = {
            "norm": jax.jit(lambda x, w: _norm(
                x, w.astype(x.dtype), cfg.norm_eps)),
            "project": jax.jit(
                lambda a, p, window: _project(a, cfg, window, variant, w_of(p)),
                static_argnums=2),
            "attend": jax.jit(
                lambda a, lo, k, v, wq, wo, sink, window: _attend(
                    a, lo, k, v, _load(wq), _load(wo), sink, cfg, window,
                    variant),
                static_argnums=7),
            "dense": jax.jit(lambda m, w1, w3, w2: _swiglu(
                m, _load(w1), _load(w3), _load(w2))),
            "routed": jax.jit(lambda m, p, route_as: _routed_ffn(
                m, p, cfg, cfg.experts_held, route_as)),
            "head": jax.jit(lambda x, w: x @ _load(w)),
        }
    return _PIECES[key]


PROJECTIONS = ("wk", "wv")
ROUTED = ("router", "expert_bias", "we1", "we3", "we2")


def forward(params, cfg, tokens, positions, variant: str = "",
            route_as=None) -> tuple:
    """Full causal forward over ``tokens`` [T]. Returns float32 logits
    [len(positions), V] at the given positions; per routed layer, the
    router's picks [T, k] and the scores ``s + b`` [T, E] it selected on;
    and per layer the rows a cache of it holds, ``(k, v)`` [T, KV, Dk] and
    [T, KV, Dv] float32 (k rotated, v as projected), in the layers' order
    (a window layer's ring holds the last ``swa_window`` of them).

    ``params`` hold the share ``cfg.experts_held`` of each expert layer's
    experts (None: all of them). ``route_as`` (per routed layer [T, k],
    optional) routes every position as given, so that logits can be
    compared under one routing; the router's OWN picks and scores are
    returned either way.

    ``variant`` computes a WRONG model for the controls, which must fail
    (``VARIANTS``): "weights_8bit" (every matrix rounded to e4m3 as it is
    loaded), "no_sink", "sink_on_full" (the full layers' softmax takes the
    first window layer's sinks too), "one_rope_base" (``rope_theta`` in both kinds),
    "rotary_all" (over the whole head), "rotary_interleaved" (pairs (2i,
    2i + 1)), "window_127", "window_129", "no_value_scale",
    "kv_groups_swapped" (a query head grouped by the other kind's count of
    key heads), "all_bfloat16" (the stream, the router's logits, the scores,
    the softmax and every product's result rounded to bfloat16, where
    float32 is stated)."""
    import jax
    import jax.numpy as jnp

    if variant and variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}: {VARIANTS}")
    tokens = jnp.asarray(tokens, jnp.int32)
    t = tokens.shape[0]
    all_picks, all_scores, all_kv = [], [], []
    a_sink = next((p["sink"] for p in params["layers"] if "sink" in p), None)
    with jax.default_matmul_precision("highest"):
        piece = _pieces(cfg, variant)
        x = params["embed"][tokens].astype(_dtype(variant))
        for layer, (p, kind) in enumerate(zip(params["layers"],
                                              cfg.layer_types)):
            window = kind == SLIDING
            p = _weights(p, variant)
            a = piece["norm"](x, p["ln_op"])
            k, v = piece["project"](a, {n: p[n] for n in PROJECTIONS}, window)
            all_kv.append((np.asarray(k, np.float32), np.asarray(v, np.float32)))
            sink = _load(p.get("sink", a_sink))
            for lo in range(0, t, QUERY_BLOCK):   # a block of queries
                x = x.at[lo:lo + QUERY_BLOCK].add(piece["attend"](
                    a[lo:lo + QUERY_BLOCK], jnp.int32(lo), k, v, p["wq"],
                    p["wo"], sink, window))
            del a, k, v
            m = piece["norm"](x, p["ln_ffn"])
            if layer < cfg.n_dense_layers:
                # a block of rows through a block of the FFN's columns: the
                # sum over the column blocks is the SwiGLU's own
                for lo in range(0, t, ROW_BLOCK):
                    for c in range(0, p["w1"].shape[1], COLUMN_BLOCK):
                        x = x.at[lo:lo + ROW_BLOCK].add(piece["dense"](
                            m[lo:lo + ROW_BLOCK],
                            p["w1"][:, c:c + COLUMN_BLOCK],
                            p["w3"][:, c:c + COLUMN_BLOCK],
                            p["w2"][c:c + COLUMN_BLOCK]))
                continue
            given = None if route_as is None else jnp.asarray(
                route_as[len(all_picks)], jnp.int32)
            out, own, scores = piece["routed"](
                m, {n: p[n] for n in ROUTED}, given)
            x = x + out
            all_picks.append(np.asarray(own))
            all_scores.append(np.asarray(scores, np.float32))
        x = piece["norm"](x, params["ln_f"])[jnp.asarray(positions)]
        vocab = params["unembed"].shape[1]
        out = [
            np.asarray(piece["head"](x, _weights(
                params["unembed"][:, lo:lo + HEAD_BLOCK], variant)), np.float32)
            for lo in range(0, vocab, HEAD_BLOCK)
        ]
    return np.concatenate(out, axis=-1), all_picks, all_scores, all_kv


def logits(params, cfg, tokens, positions) -> np.ndarray:
    return forward(params, cfg, tokens, positions)[0]


def sink_mass(params, cfg, tokens) -> float:
    """The mean share of a window layer's softmax its sink takes over
    ``tokens`` [T], first window layer, every head and position: what the
    seeded draw of the sinks gives (the configuration's file states it)."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        at = list(cfg.layer_types).index(SLIDING)
        p = params["layers"][at]
        x = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
        a = _norm(x, _load(p["ln_op"]), cfg.norm_eps)
        k, _v = _project(a, cfg, True, "", lambda n: _load(p[n]))
        q = _turn((a @ _load(p["wq"])).reshape(
            len(tokens), cfg.n_heads, cfg.head_dim), 0, cfg, True, "")
        of = jnp.arange(cfg.n_heads) // (cfg.n_heads // k.shape[1])
        s = jnp.einsum("qhd,khd->hqk", q, k[:, of]) / np.sqrt(cfg.head_dim)
        row = jnp.arange(len(tokens))[:, None]
        col = jnp.arange(len(tokens))[None, :]
        s = jnp.where(((col <= row) & (col > row - cfg.swa_window))[None], s,
                      -jnp.inf)
        column = jnp.broadcast_to(_load(p["sink"])[:, None, None],
                                  (*s.shape[:2], 1))
        return float(jax.nn.softmax(
            jnp.concatenate([s, column], -1), -1)[..., -1].mean())


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
