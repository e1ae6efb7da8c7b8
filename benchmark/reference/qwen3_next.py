"""Plain reference of the qwen3_next decoder (Qwen3-Next): the yardstick
``correct`` is held to for a configuration of ``"architecture":
"qwen3_next"``.

Written from the published config's keys and the layer equations of the
family's public modelling code, in straightforward ``jax.numpy``: float32,
``highest`` matmul precision, no kernel, no cache, no chunks, no batching.
The Gated DeltaNet layers are a ``lax.scan`` over positions of the
recurrence itself, attention is one masked softmax a KV head, the experts a
plain loop over the experts that are held. It shares no code with
``seldon_core_tpu``. Weights are cast to float32 one matrix (one expert) at
a time, the rows go through a layer's experts in blocks and the head in
vocabulary blocks, so that 2.3k positions at the published widths fit
beside the served model.

    N(x) = x * rsqrt(mean(x^2) + eps) * (1 + w)          (zero-centred)
    every layer:  h = x + Mixer(N_in(x));  y = h + MoE(N_post(h))

    full attention:  q, gate = a Wq, a Wg;  k, v = a Wk, a Wv
        q, k = N over each head's dims;  half-split rotary on the first
        ``rotary`` dims of a head, the others pass through
        o = softmax(q k^T / sqrt(head_dim), causal) v
        Mixer = (o * sigmoid(gate)) Wo
    Gated DeltaNet:  q, k, v, z = a W_qkvz;  b, a_ = a W_ba
        u = silu(causal depthwise conv of width K over [q k v]); split
        q, k = L2-norm over a head (eps 1e-6); q = q / sqrt(Dk); a key head
        serves Hv / Hk consecutive value heads
        beta = sigmoid(b);  g = -exp(A_log) softplus(a_ + dt_bias)
        per value head, S [Dk, Dv] = 0 at the start, float32:
            S = exp(g_t) S;  S = S + k_t (beta_t (v_t - S^T k_t))^T
            o_t = S^T q_t
        Mixer = (RMSNorm(o_t) * w_o * silu(z_t)) W_out         (plain w_o)
    MoE:  p = softmax(m Wr) over ALL experts; top k; w = p / sum of picked p
        MoE(m) = sum over the picks that are HELD of w_e expert_e(m)
                 + sigmoid(m w_sg) * shared(m)
    logits = N(h_L) W_head

``held = (lo, n)``: the parameters hold experts ``lo .. lo + n - 1`` of
each layer, one chip's share of an expert-parallel layer; a pick that lands
on another chip's expert adds nothing, here as in the served model, and
that partial result goes on to the next layer. ``held=None``: the
parameters hold every expert, and the layer is whole.

Departures from the published code, each a choice of form and none of
mathematics: the checkpoint's one ``q_proj`` (query and gate interleaved
per head) and one ``in_proj_qkvz`` / ``in_proj_ba`` (interleaved per key
head) are read as the column blocks the served model holds, the same
linear maps under a seeded draw; the chunked kernel is the recurrence it
computes; the multi-token-prediction module is not part of the forward
pass; no auxiliary loss, no dropout: inference.
"""

from __future__ import annotations

import numpy as np

# the dense reference's vocabulary block and half-split rotary: one copy
# among the references (none is the program's)
from benchmark.reference.decoder import HEAD_BLOCK, _rope

ROW_BLOCK = 4096
LINEAR = "linear_attention"


def _load(a, variant):
    """One matrix in float32; under the control "weights_8bit" rounded to
    8-bit floats (e4m3) on the way: the nearest precision below bfloat16."""
    import jax.numpy as jnp

    if variant == "weights_8bit" and a.ndim >= 2:
        a = a.astype(jnp.float8_e4m3fn)
    return a.astype(jnp.float32)


def _norm(x, w, eps):
    """The zero-centred RMSNorm."""
    import jax.numpy as jnp

    return x * jnp.reciprocal(
        jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * (1.0 + w)


def _swiglu(m, w1, w3, w2):
    import jax

    return (jax.nn.silu(m @ w1) * (m @ w3)) @ w2


def _moe(m, p, cfg, held, variant, route_as=None):
    """m [R, D] float32 -> (MoE(m), the router's picks [R, k] over all
    experts, its softmax [R, E]) for one block of rows. ``route_as`` [R,
    k]: experts to send the rows to in place of the router's own picks
    (which are still returned); the weights are the router's own
    probabilities of those experts."""
    import jax
    import jax.numpy as jnp

    def load(name, e=None):
        return _load(p[name] if e is None else p[name][e], variant)

    probs = jax.nn.softmax(m @ load("router"), axis=-1)
    _, own = jax.lax.top_k(probs, cfg.experts_per_tok)
    picks = own if route_as is None else jnp.asarray(route_as, own.dtype)
    sel = jnp.take_along_axis(probs, picks, -1)
    w = sel / sel.sum(-1, keepdims=True)
    lo, n = held if held is not None else (0, probs.shape[-1])
    out = jnp.zeros_like(m)
    for e in range(n):
        # every held expert over every row, weighted 0 where a row did not
        # pick it: no shape, gather or host decision depends on the data
        we = jnp.sum(jnp.where(picks == lo + e, w, 0.0), -1, keepdims=True)
        out = out + we * _swiglu(m, load("we1", e), load("we3", e), load("we2", e))
        if e % 8 == 7:
            out.block_until_ready()     # the host runs far ahead otherwise
    shared = _swiglu(m, load("ws1"), load("ws3"), load("ws2"))
    return out + jax.nn.sigmoid(m @ load("w_sg")) * shared, own, probs


def _delta_net(a, p, cfg, variant, w, states_at=()):
    """The Gated DeltaNet mixer over a [T, D] float32, and the state [Hv,
    Dk, Dv] after each of the positions ``states_at``."""
    import jax
    import jax.numpy as jnp

    hk, dk = cfg.linear_key_heads, cfg.linear_key_dim
    hv, dv = cfg.linear_value_heads, cfg.linear_value_dim
    kw = cfg.linear_conv_kernel
    t = a.shape[0]
    c = 2 * hk * dk + hv * dv
    qkvz = a @ w("w_qkvz")
    ba = a @ w("w_ba")
    x, z = qkvz[:, :c], qkvz[:, c:]
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(w("A_log")) * jax.nn.softplus(ba[:, hv:] + w("dt_bias"))
    if variant == "no_decay":
        g = jnp.zeros_like(g)
    conv = w("conv_w")                                            # [K, C]
    padded = jnp.concatenate([jnp.zeros((kw - 1, c), x.dtype), x])
    u = jax.nn.silu(sum(padded[j:j + t] * conv[j] for j in range(kw)))
    q = u[:, :hk * dk].reshape(t, hk, dk)
    k = u[:, hk * dk:2 * hk * dk].reshape(t, hk, dk)
    v = u[:, 2 * hk * dk:].reshape(t, hv, dv)

    def l2(y):
        return y * jax.lax.rsqrt(jnp.sum(y * y, -1, keepdims=True) + 1e-6)

    q = jnp.repeat(l2(q) / np.sqrt(dk), hv // hk, axis=1)
    k = jnp.repeat(l2(k), hv // hk, axis=1)

    # where a position's state is kept: its place in ``states_at``, or one
    # past them (a slot nobody reads)
    keep = np.full((t,), len(states_at), np.int32)
    keep[np.asarray(states_at, np.int64)] = np.arange(len(states_at))

    def token(carry, xs):
        s, kept = carry
        q_t, k_t, v_t, g_t, b_t, slot = xs      # [Hv, Dk] ... [Hv], []
        s = s * jnp.exp(g_t)[:, None, None]
        seen = jnp.einsum("hkv,hk->hv", s, k_t)
        s = s + jnp.einsum("hk,hv->hkv", k_t, b_t[:, None] * (v_t - seen))
        if variant == "state_bf16":
            # the control: the state kept in bfloat16 between tokens
            # (a pair of converts would be taken out: the compiler may
            # keep excess precision)
            s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        kept = jax.lax.dynamic_update_slice(kept, s[None], (slot, 0, 0, 0))
        return (s, kept), jnp.einsum("hkv,hk->hv", s, q_t)

    zero = jnp.zeros((hv, dk, dv), jnp.float32)
    (_, kept), o = jax.lax.scan(
        token, (zero, jnp.zeros((len(states_at) + 1, hv, dk, dv), jnp.float32)),
        (q, k, v, g, beta, jnp.asarray(keep)))                    # [T, Hv, Dv]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.norm_eps)
    o = o * w("o_norm") * jax.nn.silu(z.reshape(t, hv, dv))
    return o.reshape(t, hv * dv) @ w("w_out"), np.asarray(kept[:-1])


def _attention(a, p, cfg, variant, w):
    """The gated softmax-attention mixer over a [T, D] float32."""
    import jax
    import jax.numpy as jnp

    heads, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    t = a.shape[0]
    rot = dh if variant == "rotary_all" else int(dh * cfg.partial_rotary_factor)
    q = _norm((a @ w("wq")).reshape(t, heads, dh), w("q_norm"), cfg.norm_eps)
    k = _norm((a @ w("wk")).reshape(t, kv, dh), w("k_norm"), cfg.norm_eps)
    v = (a @ w("wv")).reshape(t, kv, dh)
    gate = a @ w("wg")

    def rotary(x):
        return jnp.concatenate(
            [_rope(x[..., :rot], cfg.rope_theta), x[..., rot:]], -1)

    q, k = rotary(q), rotary(k)
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    rep = heads // kv
    groups = []
    for grp in range(kv):               # one KV head's queries at a time
        qg = q[:, grp * rep:(grp + 1) * rep]
        s = jnp.einsum("qhd,kd->hqk", qg, k[:, grp]) / np.sqrt(dh)
        s = jnp.where(seen[None], s, -jnp.inf)
        groups.append(jnp.einsum("hqk,kd->qhd", jax.nn.softmax(s, -1),
                                 v[:, grp]))
    o = jnp.concatenate(groups, axis=1).reshape(t, heads * dh)
    return (o * jax.nn.sigmoid(gate)) @ w("wo")


def forward(params, cfg, tokens, positions, variant: str = "",
            route_as=None, states_at=()) -> tuple:
    """Full causal forward over ``tokens`` [T]. Returns float32 logits
    [len(positions), V] at the given positions; per layer, the router's
    picks [T, k] and its softmax [T, E]; and per LINEAR layer the
    recurrent state [len(states_at), Hv, Dk, Dv] after each of the
    positions ``states_at``. ``cfg`` needs n_heads, n_kv_heads,
    head_dim, rope_theta, partial_rotary_factor, norm_eps, layer_types, the
    ``linear_*`` sizes, experts_per_tok and experts_held.

    ``params`` hold the share ``cfg.experts_held`` of each layer's experts
    (None: all of them).

    ``route_as`` (per layer [T, k], optional) routes every position as
    given, so that logits can be compared under one routing; the router's
    OWN picks and probabilities are returned either way.

    ``variant`` computes a WRONG model for the controls, which must fail:
    "weights_8bit" (every matrix rounded to e4m3 as it is loaded),
    "state_bf16" (the recurrent state rounded to bfloat16 after every
    token), "no_decay" (``g`` left out), "rotary_all" (rotary on all of a
    head's dims)."""
    import jax
    import jax.numpy as jnp

    held = cfg.experts_held
    tokens = jnp.asarray(tokens, jnp.int32)
    t = tokens.shape[0]
    all_picks, all_probs, all_states = [], [], []
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        for layer, p in enumerate(params["layers"]):
            def w(name, p=p):
                return _load(p[name], variant)

            a = _norm(x, w("ln_in"), cfg.norm_eps)
            if cfg.layer_types[layer] == LINEAR:
                mixed, states = _delta_net(a, p, cfg, variant, w, states_at)
                all_states.append(states)
            else:
                mixed = _attention(a, p, cfg, variant, w)
            x = x + mixed
            m = _norm(x, w("ln_post"), cfg.norm_eps)
            given = None if route_as is None else route_as[layer]
            blocks = [_moe(m[lo:lo + ROW_BLOCK], p, cfg, held, variant,
                           None if given is None else given[lo:lo + ROW_BLOCK])
                      for lo in range(0, t, ROW_BLOCK)]
            x = x + jnp.concatenate([b[0] for b in blocks])
            all_picks.append(np.concatenate([np.asarray(b[1]) for b in blocks]))
            all_probs.append(np.concatenate([np.asarray(b[2]) for b in blocks]))
        x = _norm(x, params["ln_f"].astype(jnp.float32), cfg.norm_eps)
        x = x[jnp.asarray(positions)]
        vocab = params["unembed"].shape[1]
        out = [
            np.asarray(x @ _load(params["unembed"][:, lo:lo + HEAD_BLOCK], variant))
            for lo in range(0, vocab, HEAD_BLOCK)
        ]
    return np.concatenate(out, axis=-1), all_picks, all_probs, all_states


def logits(params, cfg, tokens, positions) -> np.ndarray:
    return forward(params, cfg, tokens, positions)[0]
