"""AfmoeLM: a decoder whose layers are of mixed kinds (arcee-ai's Trinity
family publishes it as ``model_type: afmoe``).

A ``DecoderFamily`` (``models/family.py``: the interface the scheduler and
the server ask), registered there as ``"afmoe"``:
``DecoderLM(block="afmoe", ...)`` and ``AfmoeLM(...)`` build it, over an
``AfmoeConfig``. It shares with the llama block ``_rms_norm``, ``_rope``
and the SwiGLU, takes the embedding lookup, the K/V cache's layout and its
answers to the scheduler from the interface's defaults, reads the cache
through the same ops (``ops.decode_attention``, the flash kernel), and
differs layer by layer:

* attention is *window + rotary* (``sliding_attention``: query i sees keys
  (i - window, i]) or *full + no rotary* (``full_attention``), per
  ``cfg.layer_types``; each head's q and k are RMS-normed over the head
  (one weight vector each), and the attention output is gated by
  ``sigmoid(x Wg)`` before ``wo``; heads x head_dim need not be d_model;
* the FFN is dense (``d_ff``) in the first ``n_dense_layers`` layers and
  ``n_routed_experts`` drop-free top-``experts_per_tok`` experts beside
  ``n_shared_experts`` shared ones after them (``ops/experts.py``);
* every layer has four norms: before and after attention, before and
  after the FFN (a post-norm rescales its branch, so ``wo`` and ``w2``
  need no ``residual_scale``; the post-norms' weights set a branch's size);
* the embedding is scaled by sqrt(d_model).

Layers differ, so they are a LIST (``params["layers"][l]``: no stacked
arrays to slice a layer from, no ``lax.scan`` over identical blocks), and
every forward is a Python loop over them with the kinds known when it is
traced. The cache is the llama block's: one [S, KV, T, Dh] pair a layer,
``max_seq`` long for every kind.

Serving only. What it refuses is ``serving_refuses``; training
(``loss_fn``, ``backbone``) and the uniform-batch ``generate`` family
(``decode_step``, ``decode_step_ragged``: the stacked scan) are the
interface's typed refusals.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np

from .family import DecoderFamily
from .llm import LLMConfig, _rms_norm, _rope

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass
class AfmoeConfig(LLMConfig):
    """The shared fields (``layer_types``: "sliding_attention" |
    "full_attention" a layer, None = all full; the routed experts') and
    this family's own."""
    block: str = "afmoe"
    sliding_window: int = 0       # keys a sliding_attention query sees


class AfmoeLM(DecoderFamily):
    config_class = AfmoeConfig
    step_counter_names = (
        # per decode step, summed over the expert layers: distinct experts
        # some live lane picked, (lane, pick) pairs routed, expert layers run
        "moe_experts_touched", "moe_rows_routed", "moe_layer_steps",
    )
    prefill_counter_names = (
        # per prefill, summed over the expert layers: the (row, pick) pairs
        # the grouped experts moved (every pair of the bucket: all experts
        # are held) and routed, and the rows of the row tiles the grouped
        # kernel worked, a tile once for every group it was worked for (a
        # pad row's picks join no group)
        "moe_prefill_pairs_moved", "moe_prefill_pairs_routed",
        "moe_prefill_tile_rows",
    )
    serving_refuses = {
        "speculation": "the draft is the first layers of a stacked llama "
                       "block, and the verify window has no counted path",
        "mesh": "the expert and window kernels are not partitioned, and "
                "param_sharding knows no expert axis",
        "kv_tier": "the tier's spill and copy-back are untested with "
                   "layers that read a window of the cache",
    }

    def __init__(self, **config):
        super().__init__(**config)
        cfg = self.cfg
        types = cfg.layer_types or (FULL,) * cfg.n_layers
        if len(types) != cfg.n_layers or set(types) - {SLIDING, FULL}:
            raise ValueError(
                f"layer_types must name {cfg.n_layers} layers as "
                f"{SLIDING!r} or {FULL!r}: {types}")
        if SLIDING in types and cfg.sliding_window <= 0:
            raise ValueError("sliding_attention layers need sliding_window")
        if cfg.n_dense_layers < cfg.n_layers and not (
                0 < cfg.experts_per_tok <= cfg.n_routed_experts
                and cfg.expert_width > 0):
            raise ValueError(
                "layers past n_dense_layers need n_routed_experts, "
                "experts_per_tok and expert_width")
        # the kinds, resolved here and never in a traced function
        self._windows: Tuple[Optional[int], ...] = tuple(
            cfg.sliding_window if t == SLIDING else None for t in types)
        self._routed: Tuple[bool, ...] = tuple(
            l >= cfg.n_dense_layers for l in range(cfg.n_layers))

    def attention_kinds(self):
        kinds = {}
        for w in self._windows:
            kinds[w] = kinds.get(w, 0) + 1
        return tuple((n, w) for w, n in kinds.items())

    # -- sizes ---------------------------------------------------------------

    def _layer_params(self, routed: bool, experts: float) -> float:
        """Parameters of one layer with ``experts`` of its routed experts
        counted (all of them: what is held; fewer: what a step reads)."""
        cfg = self.cfg
        D, Dh = cfg.d_model, cfg.head_dim
        h, kv = cfg.n_heads * Dh, cfg.n_kv_heads * Dh
        n = 4 * D + 2 * Dh + 3 * D * h + 2 * D * kv   # norms, wq wg wo, wk wv
        if not routed:
            return n + 3 * D * cfg.d_ff
        one = 3 * D * cfg.expert_width
        return (n + D * cfg.n_routed_experts + cfg.n_routed_experts
                + (experts + cfg.n_shared_experts) * one)

    def n_params(self) -> int:
        cfg = self.cfg
        return int(sum(self._layer_params(r, cfg.n_routed_experts)
                       for r in self._routed)
                   + 2 * cfg.vocab_size * cfg.d_model + cfg.d_model)

    def step_param_bytes(self, rows: int, param_bytes: int = 2) -> float:
        """Bytes of weights a decode step over ``rows`` live lanes reads:
        everything outside the routed experts once, and of each expert layer
        the experts that ``rows`` x k uniform picks are expected to touch.
        The embedding table is not read."""
        cfg = self.cfg
        touched = cfg.n_routed_experts * (
            1.0 - (1.0 - cfg.experts_per_tok / max(1, cfg.n_routed_experts))
            ** max(0, rows))
        n = sum(self._layer_params(r, touched) for r in self._routed)
        return (n + cfg.vocab_size * cfg.d_model + cfg.d_model) * param_bytes

    def flops_per_token(self, context_len: int) -> float:
        cfg = self.cfg
        D, h = cfg.d_model, cfg.n_heads * cfg.head_dim
        kv = cfg.n_kv_heads * cfg.head_dim
        total = 2.0 * D * cfg.vocab_size
        for w, routed in zip(self._windows, self._routed):
            seen = context_len if w is None else min(context_len, w)
            total += 2.0 * (3 * D * h + 2 * D * kv) + 4.0 * seen * h
            if routed:
                total += 2.0 * D * cfg.n_routed_experts + 6.0 * D * (
                    cfg.expert_width
                    * (cfg.experts_per_tok + cfg.n_shared_experts))
            else:
                total += 6.0 * D * cfg.d_ff
        return total

    def decode_bytes_per_token(self, context_len: float, batch: int = 1,
                               param_bytes: int = 2) -> float:
        cfg = self.cfg
        per = 2 * cfg.n_kv_heads * cfg.head_dim * 2
        cache = sum(
            per * (context_len if w is None else min(context_len, w))
            for w in self._windows)
        return self.step_param_bytes(batch, param_bytes) / max(1, batch) + cache

    def dispatch_read_bytes(self, kind: str, *, rows: int = 1,
                            live: int = None, k: int = 1, bucket: int = 0,
                            tokens: int = 0, param_bytes: float = None,
                            kv_row_bytes: float = None) -> float:
        """As the llama block's, but a decode step reads by live lane
        (``live`` of ``rows``; all of them where it is not given): of the
        routed experts what that many lanes are expected to touch
        (``param_bytes``, every weight, prices the other kinds only), and
        of a window layer at most its window of a lane's bucket."""
        if kind in ("decode_burst", "fused_burst", "spec_burst"):
            live = rows if live is None else live
            if kv_row_bytes is None:
                kv_row_bytes = float(self.kv_bytes_per_token())
            layer = kv_row_bytes / self.cfg.n_layers
            kv = sum(layer * (bucket if w is None else min(bucket, w))
                     for w in self._windows)
            return k * (self.step_param_bytes(live) + live * kv)
        return super().dispatch_read_bytes(
            kind, rows=rows, k=k, bucket=bucket, tokens=tokens,
            param_bytes=param_bytes, kv_row_bytes=kv_row_bytes)

    # -- params ----------------------------------------------------------------

    def init_params(self, seed: int = 0):
        """Seeded float32 draw. Matrices are N(0, 1 / fan_in), the embedding
        N(0, 1), pre-norms and head norms ones, ``expert_bias`` zeros (as
        published). The POST-norms' weights are ``residual_scale x
        sqrt(d_model)``: a post-norm gives its branch that size whatever
        ``wo`` / ``w2`` hold, against a residual stream that starts at
        ``|h0| = sqrt(d_model)`` x the embedding's, so ``residual_scale`` is
        a branch's size over the embedding's, as it is in the llama block."""
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        D, Dh, V = cfg.d_model, cfg.head_dim, cfg.vocab_size
        h, kv = cfg.n_heads * Dh, cfg.n_kv_heads * Dh
        E, Fe = cfg.n_routed_experts, cfg.expert_width
        keys = iter(jax.random.split(jax.random.PRNGKey(seed),
                                     16 * cfg.n_layers + 4))

        def init(shape, fan_in):
            return jax.random.normal(next(keys), shape, jnp.float32) * (
                1.0 / np.sqrt(fan_in))

        ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
        post = float(cfg.residual_scale) * np.sqrt(D)
        layers = []
        for routed in self._routed:
            p = {
                "ln_in": ones(D), "q_norm": ones(Dh), "k_norm": ones(Dh),
                "wq": init((D, h), D), "wk": init((D, kv), D),
                "wv": init((D, kv), D), "wg": init((D, h), D),
                "wo": init((h, D), h),
                "ln_post_attn": ones(D) * post, "ln_pre_mlp": ones(D),
                "ln_post_mlp": ones(D) * post,
            }
            if routed:
                Fs = Fe * cfg.n_shared_experts
                p.update({
                    "router": init((D, E), D),
                    "expert_bias": jnp.zeros((E,), jnp.float32),
                    "we1": init((E, D, Fe), D), "we3": init((E, D, Fe), D),
                    "we2": init((E, Fe, D), Fe),
                })
                if Fs:
                    p.update({"ws1": init((D, Fs), D), "ws3": init((D, Fs), D),
                              "ws2": init((Fs, D), Fs)})
            else:
                F = cfg.d_ff
                p.update({"w1": init((D, F), D), "w3": init((D, F), D),
                          "w2": init((F, D), F)})
            layers.append(p)
        return {
            "embed": jax.random.normal(next(keys), (V, D), jnp.float32),
            "layers": layers,
            "ln_f": ones(D),
            "unembed": init((D, V), D),
        }

    # -- one layer ---------------------------------------------------------------

    def _embed_tokens(self, params, tokens):
        import jax.numpy as jnp

        x = super()._embed_tokens(params, tokens)
        scale = jnp.float32(np.sqrt(self.cfg.d_model))
        return (x.astype(jnp.float32) * scale).astype(x.dtype)

    def _heads(self, p, x, positions, window):
        """The layer's input norm and projections: q [B, H, T, Dh], k and
        v [B, KV, T, Dh] (q, k normed per head; rotary on a window layer
        only) and the output gate's logits [B, T, H Dh]."""
        cfg = self.cfg
        dt = x.dtype
        B, T, _ = x.shape
        Dh = cfg.head_dim
        a = _rms_norm(x, p["ln_in"].astype(dt), cfg.norm_eps)
        q = (a @ p["wq"].astype(dt)).reshape(B, T, cfg.n_heads, Dh)
        k = (a @ p["wk"].astype(dt)).reshape(B, T, cfg.n_kv_heads, Dh)
        v = (a @ p["wv"].astype(dt)).reshape(B, T, cfg.n_kv_heads, Dh)
        g = a @ p["wg"].astype(dt)
        q = _rms_norm(q, p["q_norm"].astype(dt), cfg.norm_eps)
        k = _rms_norm(k, p["k_norm"].astype(dt), cfg.norm_eps)
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        if window is not None:
            q = _rope(q, positions, cfg.rope_theta)
            k = _rope(k, positions, cfg.rope_theta)
        return q, k, v, g

    def _close(self, p, x, o, g, routed, live=None, real=None):
        """From the attention's output o [B, H, T, Dh] to the layer's: the
        gate, ``wo``, the post-norm and residual, then the FFN between its
        two norms. ``live`` ([B], a decode step: T == 1) sends the routed
        experts through the touched-only read. ``real`` ([B, T] bool, a
        prefill's): the rows that are some sequence's tokens. Returns
        ``(x, picks, counts)``: a routed layer's picks [B, T, k], else
        None, and ``ops.experts.routed_ffn``'s counts: (experts touched,
        rows routed, rows here) of the touched-only read,
        ``GROUPED_COUNTS`` of the grouped path, else None."""
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        dt = x.dtype
        B, T, D = x.shape
        o = o.transpose(0, 2, 1, 3).reshape(B, T, -1)
        o = o * jax.nn.sigmoid(g.astype(jnp.float32)).astype(dt)
        x = x + _rms_norm(o @ p["wo"].astype(dt), p["ln_post_attn"].astype(dt),
                          cfg.norm_eps)
        m = _rms_norm(x, p["ln_pre_mlp"].astype(dt), cfg.norm_eps)

        def swiglu(w1, w3, w2):
            return (jax.nn.silu(m @ p[w1].astype(dt)) * (m @ p[w3].astype(dt))
                    ) @ p[w2].astype(dt)

        picks = counts = None
        if not routed:
            f = swiglu("w1", "w3", "w2")
        else:
            from ..ops.experts import routed_ffn

            # every expert is held; a pad row's picks go to no expert
            y, picks, counts = routed_ffn(
                m.reshape(B * T, D), p["router"], p["expert_bias"],
                cfg.experts_per_tok, cfg.route_scale, "sigmoid",
                tuple(p[n].astype(dt) for n in ("we1", "we3", "we2")),
                live=live, real=real, held=None,
                n_routed=cfg.n_routed_experts, mesh=self._serving_mesh,
                redirect_pads=True)
            f = y.astype(dt).reshape(B, T, D)
            if cfg.n_shared_experts:
                f = f + swiglu("ws1", "ws3", "ws2")
        x = x + _rms_norm(f, p["ln_post_mlp"].astype(dt), cfg.norm_eps)
        return x, None if picks is None else picks.reshape(B, T, -1), counts

    def _head(self, params, x, last_index=None, every=False):
        """Final norm and unembed: at every position, or at ``last_index``
        ([B]; default the last) of each row."""
        import jax.numpy as jnp

        cfg = self.cfg
        dt = x.dtype
        x = _rms_norm(x, params["ln_f"].astype(dt), cfg.norm_eps)
        if not every:
            x = self._last_rows(x, last_index)
        return (x @ params["unembed"].astype(dt)).astype(jnp.float32)

    @staticmethod
    def _stack(ks, vs):
        import jax.numpy as jnp

        return {"k": jnp.stack(ks), "v": jnp.stack(vs)}

    # -- whole-prompt forward --------------------------------------------------------

    def _forward(self, params, tokens, pad_to: Optional[int], last_index=None):
        """One pass over whole prompts tokens [B, T], a sequence's real
        tokens being its first ``last_index + 1`` (all T without it): the
        residual stream, where ``pad_to`` is given each layer's K and V
        padded to it, the routed layers' picks [B, T, k] and the
        ``prefill_counter_names``."""
        import jax.numpy as jnp

        from ..ops import attention as prefill_attention

        cfg = self.cfg
        B, T = tokens.shape
        x = self._embed_tokens(params, tokens)
        positions = jnp.arange(T)
        real = (None if last_index is None else positions[None, :] <= (
            jnp.asarray(last_index, jnp.int32)[:, None]))
        rep = cfg.n_heads // cfg.n_kv_heads
        ks, vs, picked = [], [], []
        counts = jnp.zeros((2,), jnp.int32)
        for p, window, routed in zip(params["layers"], self._windows,
                                     self._routed):
            q, k, v, g = self._heads(p, x, positions, window)
            o = prefill_attention(
                q, jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1),
                causal=True, window=window)
            x, picks, grouped = self._close(p, x, o, g, routed, real=real)
            if routed:
                picked.append(picks)
                counts = counts + grouped
            if pad_to is not None:
                pad = ((0, 0), (0, 0), (0, pad_to - T), (0, 0))
                ks.append(jnp.pad(k, pad))
                vs.append(jnp.pad(v, pad))
        routed_pairs = sum(picks.size for picks in picked)
        return x, ks, vs, picked, jnp.stack([
            counts[0], jnp.int32(routed_pairs), counts[1]])

    def apply(self, params, tokens):
        """tokens [B, T] int32 -> logits [B, T, V] (float32)."""
        x = self._forward(params, tokens, None)[0]
        return self._head(params, x, every=True)

    def _prefill(self, params, prompt, max_seq: int, last_index=None):
        """``prefill`` and the routed layers' picks [B, T, k] (a comparison
        with a reference takes them from this very program: another
        program's roundings, and so its picks at near-ties, are its own)."""
        x, ks, vs, picked, _ = self._forward(params, prompt, max_seq, last_index)
        return self._head(params, x, last_index), self._stack(ks, vs), picked

    def prefill(self, params, prompt, max_seq: int, last_index=None):
        return self._prefill(params, prompt, max_seq, last_index)[:2]

    def prefill_counted(self, params, prompt, max_seq: int, last_index=None):
        """``prefill`` and, after the cache's rows, its
        ``prefill_counter_names`` as an int32 vector."""
        x, ks, vs, _, counts = self._forward(params, prompt, max_seq, last_index)
        return self._head(params, x, last_index), self._stack(ks, vs), counts

    # -- a window of positions over a cache --------------------------------------------

    def _over_cache(self, params, tokens, positions, caches, attn_len):
        """tokens [B, W] at ``positions`` [B, W]; ``caches(l, k, v)`` lands
        layer l's new K and V and returns the cache pair the queries read
        (bounded at ``attn_len``) and whatever the caller keeps of it."""
        from ..ops.decode_attention import cache_attention

        x = self._embed_tokens(params, tokens)
        kept = []
        for l, (p, window, routed) in enumerate(zip(
                params["layers"], self._windows, self._routed)):
            q, k, v, g = self._heads(p, x, positions, window)
            (ck, cv), keep = caches(l, k, v)
            kept.append(keep)
            gk, gv = self._cache_read(ck, cv, attn_len)
            lo = None if window is None else positions - (window - 1)
            o = cache_attention(q, gk, gv, positions, x.dtype, lo=lo)
            x = self._close(p, x, o, g, routed)[0]
        return x, kept

    def prefill_chunk(self, params, slab, tokens, start_pos, attn_len,
                      last_index=None, want_logits=True):
        import jax.numpy as jnp
        from jax import lax

        start_pos = jnp.asarray(start_pos, jnp.int32)
        positions = start_pos + jnp.arange(
            tokens.shape[1], dtype=jnp.int32)[None, :]

        def caches(l, k, v):
            pair = tuple(
                lax.dynamic_update_slice(slab[n][l], new, (0, 0, start_pos, 0))
                for n, new in (("k", k), ("v", v)))
            return pair, pair

        x, kept = self._over_cache(params, tokens, positions, caches, attn_len)
        new_slab = self._stack(*zip(*kept))
        if not want_logits:
            return None, new_slab
        return self._head(params, x, last_index), new_slab

    def prefill_with_prefix(self, params, prefix_kv, tokens, start_pos,
                            last_index=None):
        import jax.numpy as jnp
        from jax import lax

        B, W = tokens.shape
        dt = jnp.dtype(self.cfg.dtype)
        start_pos = jnp.asarray(start_pos, jnp.int32)
        positions = start_pos + jnp.arange(W, dtype=jnp.int32)[None, :]

        def caches(l, k, v):
            pad = jnp.zeros(k.shape, dt)
            pair = tuple(
                lax.dynamic_update_slice(
                    jnp.concatenate([prefix_kv[n][l].astype(dt), pad], axis=2),
                    new, (0, 0, start_pos, 0))
                for n, new in (("k", k), ("v", v)))
            return pair, (k, v)

        x, kept = self._over_cache(params, tokens, positions, caches, None)
        return self._head(params, x, last_index), self._stack(*zip(*kept))

    def decode_chunk_ragged_list(self, params, ks, vs, tokens, pos,
                                 attn_len=None):
        import jax.numpy as jnp

        pos = pos.astype(jnp.int32)
        positions = pos[:, None] + jnp.arange(
            tokens.shape[1], dtype=jnp.int32)[None, :]

        def caches(l, k, v):
            pair = (self._cache_write(ks[l], k, positions),
                    self._cache_write(vs[l], v, positions))
            return pair, pair

        x, kept = self._over_cache(params, tokens, positions, caches, attn_len)
        nks, nvs = zip(*kept)
        return self._head(params, x, every=True), list(nks), list(nvs)

    # -- the decode step ------------------------------------------------------------------

    def decode_step_ragged_list(self, params, ks, vs, tokens, pos,
                                attn_len=None, write_pos=None, lens=None):
        """The llama block's contract (``DecoderLM.decode_step_ragged_list``)
        over this family's layers, and a fourth result: the step's
        ``step_counter_names`` as an int32 vector. A window layer reads
        each lane from ``max(0, lens - window)``
        (``ops.decode_attention(starts=...)``), and the routed experts read
        only what the lanes with ``lens > 0`` picked."""
        return self._step(params, ks, vs, tokens, pos, attn_len, write_pos,
                          lens)[:4]

    def _step(self, params, ks, vs, tokens, pos, attn_len=None,
              write_pos=None, lens=None):
        """``decode_step_ragged_list`` and the routed layers' picks
        [B, 1, k] (for a comparison with a reference, as ``_prefill``)."""
        import jax.numpy as jnp

        from ..ops import decode_attention

        pos = pos.astype(jnp.int32)
        wp = pos if write_pos is None else write_pos.astype(jnp.int32)
        lens = pos + 1 if lens is None else lens.astype(jnp.int32)
        live = lens > 0
        x = self._embed_tokens(params, tokens)  # [B, 1, D]
        nks, nvs, picked = [], [], []
        touched = routed_rows = jnp.int32(0)
        for l, (p, window, routed) in enumerate(zip(
                params["layers"], self._windows, self._routed)):
            q, k, v, g = self._heads(p, x, pos[:, None], window)
            starts = None if window is None else jnp.maximum(0, lens - window)
            o, nk, nv = decode_attention(
                q, ks[l], vs[l], k, v, wp, pos, lens, attn_len=attn_len,
                mesh=self._serving_mesh, starts=starts)
            nks.append(nk)
            nvs.append(nv)
            x, picks, counts = self._close(p, x, o, g, routed, live=live)
            if routed:
                picked.append(picks)
                touched, routed_rows = (touched + counts[0],
                                        routed_rows + counts[1])
        counts = jnp.stack(
            [touched, routed_rows, jnp.int32(sum(self._routed))])
        return self._head(params, x), nks, nvs, counts, picked
