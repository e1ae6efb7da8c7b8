"""Plain reference of the evabyte decoder (EvaByte): the yardstick
``correct`` is held to for a configuration of ``"architecture": "evabyte"``.

Written from the published config's keys and the layer equations of ISSUE
44 in straightforward ``jax.numpy``: float32, ``highest`` matmul precision,
ONE causal forward over all positions: no cache, no kernel, no batching, no
ring and NO WINDOW WALK: per head the full ``[T, T + T / C]`` score matrix
over every position and every chunk's summary, masked, in blocks of queries
so that 15k positions fit beside the served model and its cache. It shares
no code with ``seldon_core_tpu``.

    N(x) = x * rsqrt(mean(x^2) + eps) * (1 + w)
    every layer:  h = x + EVA(N(x));   y = h + W_2(silu(W_1 N(h)) * W_3 N(h))

    q, k, v = N(x) W_q, W_k, W_v -> H heads x Dh;   q, k rotated (half-split
        pairs (i, i + Dh / 2), theta^(-2i / Dh), absolute positions)
    position t: window w = t // W; chunk c = positions [C c, C c + C)
    a chunk's summary, a head (s = 1 / sqrt(Dh)):
        k~_c = sum_j softmax_j(s k_j . mu_k) k_j
        v~_c = sum_j softmax_j(s k_j . phi) v_j              j in chunk c
    attention of t, a head, ONE softmax over two sets of keys:
        local   { j : W w <= j <= t }        s q_t . k_j     values v_j
        remote  { c : c < (W / C) w }        s q_t . k~_c    values v~_c
    logits = N(x_L) W_head -> [P, V], head-major; head 0 is the next byte

A trailing partial chunk has no summary (nothing reads one before its chunk
is whole). ``forward`` also returns, for the comparison of the cache, the
rotated keys and the values at the positions asked for and every whole
chunk's ``k~`` and ``v~``, a layer.
"""

from __future__ import annotations

import numpy as np

QUERY_BLOCK = 128
ROW_BLOCK = 2048

# the wrong models the controls compute, each of which must fail a limit
VARIANTS = ("weights_8bit", "pool_mean", "mu_phi_swapped",
            "summaries_of_current_window", "two_softmaxes", "sliding_window",
            "summaries_8bit", "rope_theta_1e4", "norm_w_only")

NEG = -1e30


def _e4m3(a):
    """Rounded to 8-bit floats and back: the nearest precision below
    bfloat16."""
    import jax.numpy as jnp

    return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _weights(tree, variant):
    """A layer's parameters as the pieces are given them: as they are (a
    piece casts each to float32 where it multiplies by it, so no float32
    copy of a layer is held), or under "weights_8bit" every matrix rounded
    to e4m3 first and handed on in its own dtype, which holds every e4m3
    value exactly (op by op, outside any ``jit``: inside one the compiler
    may keep the excess precision and the control rounds nothing)."""
    import jax
    import jax.numpy as jnp

    def load(a):
        a = jnp.asarray(a)
        if variant == "weights_8bit" and a.ndim >= 2:
            a = _e4m3(a).astype(a.dtype)
        return a

    return jax.tree_util.tree_map(load, tree)


def _norm(x, w, eps, offset):
    import jax.numpy as jnp

    return x * jnp.reciprocal(
        jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * (w + offset)


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


def _pieces(cfg, variant):
    """The jitted pieces of one layer, a process's one set a variant."""
    import functools

    import jax
    import jax.numpy as jnp

    key = (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.window_size,
           cfg.chunk_size, float(cfg.rope_theta), float(cfg.norm_eps),
           bool(cfg.norm_add_unit_offset), variant)
    if key in _PIECES:
        return _PIECES[key]
    H, Dh, W, C = cfg.n_heads, cfg.head_dim, cfg.window_size, cfg.chunk_size
    s = 1.0 / np.sqrt(Dh)
    theta = 1e4 if variant == "rope_theta_1e4" else float(cfg.rope_theta)
    offset = 1.0 if (cfg.norm_add_unit_offset
                     and variant != "norm_w_only") else 0.0
    norm = functools.partial(_norm, eps=cfg.norm_eps, offset=offset)

    def f32(fn):
        """``fn`` jitted, its parameter tree cast to float32 inside."""
        return jax.jit(lambda *args: fn(*args[:-1], jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), args[-1])))

    @f32
    def project(x, p):
        a = norm(x, p["ln_in"])
        t = x.shape[0]
        q = _rotary((a @ p["wq"]).reshape(t, H, Dh), theta)
        k = _rotary((a @ p["wk"]).reshape(t, H, Dh), theta)
        return q, k, (a @ p["wv"]).reshape(t, H, Dh)

    @f32
    def pool(k, v, p):
        """k, v [n C, H, Dh] of whole chunks -> k~, v~ [n, H, Dh]."""
        n = k.shape[0] // C
        kc, vc = k.reshape(n, C, H, Dh), v.reshape(n, C, H, Dh)
        mu, phi = p["mu_k"], p["phi"]
        if variant == "mu_phi_swapped":
            mu, phi = phi, mu

        def pooled(vec, rows):
            logits = jnp.einsum("nchd,hd->nch", kc, vec) * s
            if variant == "pool_mean":
                logits = jnp.zeros_like(logits)
            return jnp.einsum("nch,nchd->nhd", jax.nn.softmax(logits, 1), rows)

        return pooled(mu, kc), pooled(phi, vc)

    @jax.jit
    def attend(q, k, v, ks, vs, first):
        """Queries q [n, H, Dh] at positions first .. first + n - 1 over
        ALL positions' k, v [T, H, Dh] and ALL summaries ks, vs [Tc, H,
        Dh], masked."""
        t = first + jnp.arange(q.shape[0])[:, None]          # [n, 1]
        j = jnp.arange(k.shape[0])[None, :]
        c = jnp.arange(ks.shape[0])[None, :]
        if variant == "sliding_window":
            local = (j <= t) & (j > t - W)
            remote = (c + 1) * C <= t - W + 1
        else:
            local = (j <= t) & (j >= t // W * W)
            remote = c < t // W * (W // C)
            if variant == "summaries_of_current_window":
                remote = c < t // C
        sl = jnp.where(local[None], jnp.einsum("nhd,jhd->hnj", q, k) * s, NEG)
        sr = jnp.where(remote[None], jnp.einsum("nhd,chd->hnc", q, ks) * s, NEG)
        if variant == "two_softmaxes":
            pr = jnp.where(remote[None], jax.nn.softmax(sr, -1), 0.0)
            return (jnp.einsum("hnj,jhd->nhd", jax.nn.softmax(sl, -1), v)
                    + jnp.einsum("hnc,chd->nhd", pr, vs))
        p = jax.nn.softmax(jnp.concatenate([sl, sr], -1), -1)
        n_local = k.shape[0]
        return (jnp.einsum("hnj,jhd->nhd", p[..., :n_local], v)
                + jnp.einsum("hnc,chd->nhd", p[..., n_local:], vs))

    @f32
    def mix(x, o, p):
        h = x + o.reshape(o.shape[0], H * Dh) @ p["wo"]
        m = norm(h, p["ln_post"])
        return h + (jax.nn.silu(m @ p["w1"]) * (m @ p["w3"])) @ p["w2"]

    @f32
    def head(x, p):
        return norm(x, p["ln_f"]) @ p["unembed"]

    _PIECES[key] = (project, pool, attend, mix, head)
    return _PIECES[key]


_PIECES: dict = {}


def forward(params, cfg, tokens, positions, variant: str = "", rows_at=()):
    """ONE causal forward over ``tokens`` [T]. Returns

    * the logits of every head at ``positions``: [n, P, V];
    * a layer, the rotated keys and the values at ``rows_at``: two lists of
      [m, H, Dh];
    * a layer, every whole chunk's pooled key and pooled value: two lists
      of [T // C, H, Dh].

    ``cfg``: an object with the sizes (``d_model``, ``n_heads``,
    ``head_dim``, ``window_size``, ``chunk_size``, ``num_pred_heads``,
    ``vocab_size``, ``rope_theta``, ``norm_eps``,
    ``norm_add_unit_offset``); ``params``: ``embed`` [V, D], ``ln_f``,
    ``unembed`` [D, P V] and per layer ``ln_in``, ``ln_post``, ``wq``,
    ``wk``, ``wv``, ``wo``, ``mu_k``, ``phi`` [H, Dh], ``w1``, ``w3``,
    ``w2``. ``variant``: one of ``VARIANTS``, a wrong model."""
    import jax
    import jax.numpy as jnp

    if variant and variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}; have {VARIANTS}")
    project, pool, attend, mix, head = _pieces(cfg, variant)
    C = cfg.chunk_size
    tokens = np.asarray(tokens)
    whole = len(tokens) // C * C
    # padded to whole blocks, so that every block is one compiled shape: a
    # causal forward's earlier positions do not see the padding, and the
    # summaries of chunks it touches are cut off below
    T = -(-len(tokens) // ROW_BLOCK) * ROW_BLOCK
    tokens = np.concatenate([tokens, np.zeros(T - len(tokens), tokens.dtype)])
    rows_at = jnp.asarray(np.asarray(rows_at, np.int64), jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(params["embed"])[jnp.asarray(tokens, jnp.int32)
                                         ].astype(jnp.float32)
        keys, values, pooled_k, pooled_v = [], [], [], []
        for layer in params["layers"]:
            p = _weights(layer, variant)
            q, k, v = project(x, p)
            ks, vs = pool(k, v, p)
            ks, vs = ks[:whole // C], vs[:whole // C]
            if variant == "summaries_8bit":
                # op by op, outside the pieces' ``jit`` (``_weights``)
                ks, vs = _e4m3(ks), _e4m3(vs)
            o = jnp.concatenate([
                attend(q[i:i + QUERY_BLOCK], k, v, ks, vs, jnp.int32(i))
                for i in range(0, T, QUERY_BLOCK)])
            del q
            x = jnp.concatenate([
                mix(x[i:i + ROW_BLOCK], o[i:i + ROW_BLOCK], p)
                for i in range(0, T, ROW_BLOCK)])
            keys.append(np.asarray(k[rows_at]))
            values.append(np.asarray(v[rows_at]))
            pooled_k.append(np.asarray(ks))
            pooled_v.append(np.asarray(vs))
            del k, v, ks, vs, o, p
        top = _weights({"ln_f": params["ln_f"], "unembed": params["unembed"]},
                       variant)
        logits = head(x[jnp.asarray(np.asarray(positions), jnp.int32)], top)
    return (np.asarray(logits).reshape(len(positions), cfg.num_pred_heads,
                                       cfg.vocab_size),
            keys, values, pooled_k, pooled_v)
