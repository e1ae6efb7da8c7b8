"""Plain reference of the sdar_moe decoder (JetLM SDAR, the expert models):
the yardstick ``correct`` is held to for a configuration of
``"architecture": "sdar_moe"``.

Written from the published config's keys, the layer equations of the
family's public modelling code and its public ``generate.py``, as
remembered, in straightforward ``jax.numpy``: float32, ``highest`` matmul
precision, no kernel, no cache, no batching, no scan, no ``jit``, experts by
a plain loop over the experts (sixteen at a time) with a mask. It shares no
code with ``seldon_core_tpu``. Weights are taken one matrix (sixteen
experts) at a time and cast to float32 as they are used.

    h0 = E[token]
    a = RMSNorm_in(h); q, k, v = a Wq, a Wk, a Wv
    q, k = RMSNorm over each head's 128 (one weight vector each), then
        half-split rotary at the absolute position
    key j is seen by query i iff j < (i // B + 1) B   (B = block_length)
    h = h + softmax(q k^T / sqrt(head_dim)) v Wo
    m = RMSNorm_post(h); s = softmax(m Wr) over all experts (float32);
        picks = top_k(s); w = s[picks] / sum s[picks]
    h = h + sum_k w_k W2_k (silu(W1_k m) * W3_k m)
    logits = RMSNorm(h_L) W_head, at each position ITSELF (no shift)

``forward`` is that over one canvas of tokens and ``[MASK]``s.
``block_forward`` is the same mathematics for blocks of ``B`` positions
whose earlier blocks' keys and values are given (what ``forward``
returned for them): a block sees nothing after itself, so a block's rows
of ``forward`` over a canvas are ``block_forward``'s over the block and
the rows before it; the comparison at the served sizes runs many lanes'
blocks through it at once. ``generate`` is the generation loop over
``forward``.

Departures from the published description, each noted where it is made:
``[MASK]``'s logit is -inf before the argmax and the confidence (the
published loop leaves the mask's id to the trained weights; random ones
would emit it); ties in confidence go to the lower position (the
published ``topk`` does not say); the prompt's whole blocks are not
recomputed when a later block is denoised (block-causal: they cannot
change); the grouped matmul is a loop over experts; no dropout, no
auxiliary loss: inference.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference.decoder import HEAD_BLOCK, _rms_norm

ROW_BLOCK = 4096
EXPERT_GROUP = 16   # experts cast to float32 and multiplied at a time
# wrong models, for the controls that must fail
VARIANTS = ("weights_8bit", "mask_causal", "blocks_from_prompt_end",
            "no_qk_norm", "rope_theta_1e4", "no_commit")


def _load(a, variant):
    """One matrix in float32; under the control "weights_8bit" rounded to
    8-bit floats (e4m3) on the way: the nearest precision below bfloat16."""
    import jax.numpy as jnp

    if variant == "weights_8bit" and a.ndim >= 2:
        a = a.astype(jnp.float8_e4m3fn)
    return a.astype(jnp.float32)


def _rope(x, positions, theta):
    """x [T, H, Dh] at ``positions`` [T]: pairs (i, i + Dh/2) rotated by
    position * theta^(-2i/Dh)."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.asarray(positions, jnp.float32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _block_end(i, cfg, variant, offset):
    """One past the last key position query ``i`` sees. ``mask_causal``: the
    mask a plain decoder has. ``blocks_from_prompt_end``: blocks counted
    from ``offset`` (a prompt's length mod B) and not from 0."""
    B = cfg.block_length
    if variant == "mask_causal":
        return i + 1
    if variant == "blocks_from_prompt_end":
        return ((i - offset) // B + 1) * B + offset
    return (i // B + 1) * B


def _routed_ffn(m, p, cfg, variant, route_as=None):
    """m [R, D] float32 -> (FFN(m), picks [R, k], scores [R, E]).
    ``route_as`` [R, k]: experts to send the rows to in place of the
    router's own picks (which are still returned, with the scores they are
    the top k of); the weights are the router's own scores of those."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.softmax(m @ _load(p["router"], variant), axis=-1)
    _, picks = jax.lax.top_k(s, cfg.experts_per_tok)
    own = picks
    if route_as is not None:
        picks = jnp.asarray(route_as, picks.dtype)
    sel = jnp.take_along_axis(s, picks, -1)
    w = sel / sel.sum(-1, keepdims=True)
    out = jnp.zeros_like(m)
    for lo in range(0, cfg.n_routed_experts, EXPERT_GROUP):
        # every expert over every row, weighted 0 where a row did not pick
        # it: 16 times the arithmetic of the picks, and no shape, gather or
        # host decision depends on the data
        ids = jnp.arange(lo, min(lo + EXPERT_GROUP, cfg.n_routed_experts))
        we = jnp.sum(jnp.where(picks[None] == ids[:, None, None], w[None], 0.0),
                     -1)                                        # [G, R]
        w1, w3, w2 = (_load(p[name][lo:lo + EXPERT_GROUP], variant)
                      for name in ("we1", "we3", "we2"))
        h = jax.nn.silu(jnp.einsum("rd,gdf->grf", m, w1)) * jnp.einsum(
            "rd,gdf->grf", m, w3)
        out = out + jnp.einsum("grf,gfd,gr->rd", h, w2, we)
        out.block_until_ready()     # one group's float32 in flight
    return out, own, s


def _layer(x, p, cfg, positions, seen_rows, variant, route_as):
    """One layer over rows x [R, D] at ``positions`` [R]. ``seen_rows(k, v)``
    -> the attention's output [R, H, Dh] given the rows' own keys and
    values [R, KV, Dh]. Returns ``(x, k, v, picks, scores, sizes)``, the
    last the two branches' norms over the stream's they are added to."""
    import jax.numpy as jnp

    r = x.shape[0]
    heads, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    theta = 1e4 if variant == "rope_theta_1e4" else cfg.rope_theta
    a = _rms_norm(x, _load(p["ln_in"], variant), cfg.norm_eps)
    q = (a @ _load(p["wq"], variant)).reshape(r, heads, dh)
    k = (a @ _load(p["wk"], variant)).reshape(r, kv, dh)
    v = (a @ _load(p["wv"], variant)).reshape(r, kv, dh)
    if variant != "no_qk_norm":
        q = _rms_norm(q, _load(p["q_norm"], variant), cfg.norm_eps)
        k = _rms_norm(k, _load(p["k_norm"], variant), cfg.norm_eps)
    q, k = _rope(q, positions, theta), _rope(k, positions, theta)
    o = seen_rows(q, k, v)
    attended = o.reshape(r, heads * dh) @ _load(p["wo"], variant)
    size = [float(jnp.linalg.norm(attended) / jnp.linalg.norm(x))]
    x = x + attended
    m = _rms_norm(x, _load(p["ln_post"], variant), cfg.norm_eps)
    blocks = [_routed_ffn(m[lo:lo + ROW_BLOCK], p, cfg, variant,
                          None if route_as is None
                          else route_as[lo:lo + ROW_BLOCK])
              for lo in range(0, r, ROW_BLOCK)]
    routed = jnp.concatenate([b[0] for b in blocks])
    size.append(float(jnp.linalg.norm(routed) / jnp.linalg.norm(x)))
    return (x + routed, k, v,
            np.concatenate([np.asarray(b[1]) for b in blocks]),
            np.concatenate([np.asarray(b[2]) for b in blocks]), size)


def _attend(q, k, v, seen, dh):
    """q [Q, H, Dh] over keys k, v [K, KV, Dh] under ``seen`` [Q, K]: one KV
    head's queries at a time."""
    import jax
    import jax.numpy as jnp

    rep = q.shape[1] // k.shape[1]
    groups = []
    for g in range(k.shape[1]):
        s = jnp.einsum("qhd,kd->hqk", q[:, g * rep:(g + 1) * rep],
                       k[:, g]) / np.sqrt(dh)
        s = jnp.where(seen[None], s, -jnp.inf)
        groups.append(jnp.einsum("hqk,kd->qhd", jax.nn.softmax(s, -1), v[:, g]))
    return jnp.concatenate(groups, axis=1)


def _head(params, x, variant):
    vocab = params["unembed"].shape[1]
    return np.concatenate([
        np.asarray(x @ _load(params["unembed"][:, lo:lo + HEAD_BLOCK], variant))
        for lo in range(0, vocab, HEAD_BLOCK)], axis=-1)


def forward(params, cfg, tokens, positions, variant: str = "",
            route_as=None, offset: int = 0) -> tuple:
    """Full forward over a canvas ``tokens`` [T] (tokens and ``[MASK]``s)
    under the block mask. Returns float32 logits [len(positions), V] at the
    given positions, per layer the router's picks [T, k] and scores [T, E],
    per layer the keys and values [T, KV, Dh] (float32, left where they
    were computed), and per layer the attention's and the experts'
    branch's norm over the stream's. ``cfg``
    needs n_heads, n_kv_heads, head_dim, rope_theta, norm_eps,
    block_length, n_routed_experts, experts_per_tok. ``route_as`` (per
    layer [T, k]) routes every position as given. ``variant``: one of
    ``VARIANTS`` (``offset``: ``blocks_from_prompt_end``'s)."""
    import jax
    import jax.numpy as jnp

    tokens = jnp.asarray(tokens, jnp.int32)
    t = tokens.shape[0]
    at = np.arange(t)
    seen = jnp.asarray(
        at[None, :] < _block_end(at, cfg, variant, offset)[:, None])
    picks, scores, rows, sizes = [], [], [], []
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        for l, p in enumerate(params["layers"]):
            x, k, v, pk, sc, size = _layer(
                x, p, cfg, at,
                lambda q, k, v: _attend(q, k, v, seen, cfg.head_dim),
                variant, None if route_as is None else route_as[l])
            picks.append(pk)
            scores.append(sc)
            rows.append((k, v))
            sizes.append(size)
        x = _rms_norm(x, _load(params["ln_f"], variant), cfg.norm_eps)
        out = _head(params, x[jnp.asarray(positions)], variant)
    return out, picks, scores, rows, sizes


def block_forward(params, cfg, blocks, bases, shared, own, own_len,
                  variant: str = "", route_as=None, offsets=None) -> tuple:
    """``forward`` for n blocks at once, one of each of n lanes that hold a
    prefix of ONE sequence: blocks [n, B] tokens (and ``[MASK]``s) at
    positions ``bases[i] .. + B - 1``. What lane i sees before its block:
    the first ``bases[i] - own_len[i]`` rows of ``shared`` (per layer (k
    [P, KV, Dh], v): ``forward``'s over the sequence's whole blocks), then
    the first ``own_len[i]`` rows of ``own`` (per layer (k [n, M, KV, Dh],
    v): the rows an earlier call returned for the blocks the lane has
    committed since). Every shape is the same from call to call. Returns
    float32 logits [n, B, V], per layer picks [n B, k] and scores [n B, E],
    and per layer the blocks' own keys and values [n, B, KV, Dh].
    ``route_as`` per layer [n B, k]; ``offsets`` [n]:
    ``blocks_from_prompt_end``'s."""
    import jax
    import jax.numpy as jnp

    blocks = np.asarray(blocks)
    n, B = blocks.shape
    bases, own_len = np.asarray(bases), np.asarray(own_len)
    offsets = np.zeros(n, int) if offsets is None else np.asarray(offsets)
    positions = bases[:, None] + np.arange(B)[None, :]               # [n, B]
    P, M = shared[0][0].shape[0], own[0][0].shape[1]
    start = bases - own_len                  # where a lane's own rows begin
    end = np.stack([_block_end(positions[i], cfg, variant, offsets[i])
                    for i in range(n)])                              # [n, B]
    key_at = np.concatenate([
        np.broadcast_to(np.arange(P), (n, P)),
        start[:, None] + np.arange(M), positions], axis=1)           # [n, K]
    held = np.concatenate([
        np.arange(P)[None, :] < start[:, None],
        np.arange(M)[None, :] < own_len[:, None], np.ones((n, B), bool)], 1)
    seen = jnp.asarray(held[:, None, :] & (key_at[:, None, :] < end[:, :, None]))
    rep = cfg.n_heads // cfg.n_kv_heads
    picks, scores, rows = [], [], []
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(blocks.reshape(-1), jnp.int32)].astype(
            jnp.float32)
        for l, p in enumerate(params["layers"]):
            def seen_rows(q, k, v, l=l):
                q = q.reshape(n, B, cfg.n_kv_heads, rep, cfg.head_dim)
                k, v = (t.reshape(n, B, cfg.n_kv_heads, cfg.head_dim)
                        for t in (k, v))
                groups = []
                for g in range(cfg.n_kv_heads):   # one KV head's queries
                    s = jnp.concatenate([
                        jnp.einsum("nbrd,pd->nrbp", q[:, :, g], shared[l][0][:, g]),
                        jnp.einsum("nbrd,nmd->nrbm", q[:, :, g], own[l][0][:, :, g]),
                        jnp.einsum("nbrd,nxd->nrbx", q[:, :, g], k[:, :, g]),
                    ], axis=-1) / np.sqrt(cfg.head_dim)
                    w = jax.nn.softmax(
                        jnp.where(seen[:, None], s, -jnp.inf), axis=-1)
                    groups.append(
                        jnp.einsum("nrbp,pd->nbrd", w[..., :P], shared[l][1][:, g])
                        + jnp.einsum("nrbm,nmd->nbrd", w[..., P:P + M],
                                     own[l][1][:, :, g])
                        + jnp.einsum("nrbx,nxd->nbrd", w[..., P + M:], v[:, :, g]))
                return jnp.stack(groups, axis=2).reshape(
                    n * B, cfg.n_heads, cfg.head_dim)

            x, k, v, pk, sc, _size = _layer(
                x, p, cfg, positions.reshape(-1), seen_rows, variant,
                None if route_as is None else route_as[l])
            picks.append(pk)
            scores.append(sc)
            rows.append((k.reshape(n, B, *k.shape[1:]),
                         v.reshape(n, B, *v.shape[1:])))
        x = _rms_norm(x, _load(params["ln_f"], variant), cfg.norm_eps)
        out = _head(params, x, variant).reshape(n, B, -1)
    return out, picks, scores, rows


def unmask(logits, masked, n_pass, cfg, temperature: float = 0.0, rng=None):
    """One block's denoising step. logits [B, V] at the block's positions,
    masked [B] bool -> ``(x0 [B], take [B] bool)``: the tokens proposed
    and the masked positions that take theirs. ``[MASK]``'s logit is -inf
    first (a departure: see the module's docstring); ``x0`` the argmax, or
    a draw from ``softmax(logits / temperature)`` by ``rng`` (a numpy
    Generator); confidence ``softmax(logits)[x0]`` in float32; ties to the
    lower position (a departure)."""
    logits = np.array(logits, np.float32)
    logits[:, cfg.mask_token_id] = -np.inf
    z = logits - logits.max(-1, keepdims=True)
    prob = np.exp(z) / np.exp(z).sum(-1, keepdims=True)
    if temperature > 0:
        zt = z / temperature
        pt = np.exp(zt - zt.max(-1, keepdims=True))
        x0 = np.array([rng.choice(len(p), p=p / p.sum()) for p in pt])
    else:
        x0 = logits.argmax(-1)
    conf = np.where(masked, prob[np.arange(len(x0)), x0], -1.0)
    B, T = cfg.block_length, cfg.denoising_steps
    share = B // T + (min(n_pass, T - 1) < B % T)
    order = sorted(range(len(conf)), key=lambda i: (-conf[i], i))
    take = np.zeros(len(conf), bool)
    take[order[:share]] = True
    if cfg.remasking == "low_confidence_dynamic":
        take |= conf > cfg.confidence_threshold
    return x0, take & masked


def generate(params, cfg, prompt, max_new_tokens: int, variant: str = "",
             trace=None) -> list:
    """The generation loop, greedy: ``max_new_tokens`` tokens after
    ``prompt``. Block by block from the block that holds the prompt's tail
    (or follows its last whole block): the block starts as the tail and
    ``[MASK]`` elsewhere; a denoising pass is ``forward`` over the canvas up
    to the block's end, ``unmask`` at the block; when nothing is masked the
    block is final (its committed keys and values are what ``forward`` over
    the final canvas computes: there is no cache here to commit to). A last
    block that runs past the budget is computed whole and cut. ``trace``
    (a list, optional) takes ``(canvas, base, masked)`` of every pass, the
    commit's (nothing masked) included, for a comparison of logits."""
    B = cfg.block_length
    canvas = [int(t) for t in prompt]
    base = len(canvas) // B * B
    out = []
    while len(out) < max_new_tokens:
        tail = len(canvas) - base
        block = canvas[base:] + [cfg.mask_token_id] * (B - tail)
        masked = np.array([False] * tail + [True] * (B - tail))
        n_pass = 0
        while masked.any():
            full = canvas[:base] + block
            if trace is not None:
                trace.append((list(full), base, masked.copy()))
            logits = forward(params, cfg, full, list(range(base, base + B)),
                             variant)[0]
            x0, take = unmask(logits, masked, n_pass, cfg)
            block = [int(x0[i]) if take[i] else block[i] for i in range(B)]
            masked = masked & ~take
            n_pass += 1
        if trace is not None:
            trace.append((canvas[:base] + block, base, masked.copy()))
        out += block[tail:]
        canvas = canvas[:base] + block
        base += B
    return out[:max_new_tokens]
