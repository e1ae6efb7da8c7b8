"""JoyaiLLMFlashLM: multi-head latent attention (MLA) with a compressed
cache of one row a position, and a chip's share of sigmoid-routed experts
(jdopensource publishes the family as ``model_type: joyai_llm_flash``, with
the keys of the DeepSeek-V3 family's config).

A ``DecoderFamily`` (``models/family.py``: the interface the scheduler and
the server ask), registered there as ``"joyai_llm_flash"``:
``DecoderLM(block="joyai_llm_flash", ...)`` and ``JoyaiLLMFlashLM(...)``
build it, over a ``JoyaiLLMFlashConfig``. With the other blocks it shares
the embedding lookup, ``_rms_norm``, the flash kernel, the routed experts
(``ops/experts.py``) and the batcher's cache dict. Every layer is pre-norm,

    h = x + MLA(N(x));   y = h + FFN(N(h))

``FFN`` a SwiGLU of ``d_ff`` in the first ``n_dense_layers`` layers and
after them ``experts_per_tok`` of ``n_routed_experts`` experts beside
``n_shared_experts`` shared ones: scores ``s = sigmoid(x W_r)``, the picks
the top k of ``s + b`` (``expert_bias``: the selection only), the weights
``s[picks] / sum x route_scale`` (``ops.experts.route``). ``experts_held =
(lo, n)``: this chip holds experts ``lo .. lo + n - 1`` of every layer and
computes the picks that land on them (None: all).

**The attention.** Low-rank on both sides:

    c_q = N(x W_qa);  [q_n | q_r] = c_q W_qb      H heads x (nope | rope)
    [c | k_r] = x W_kva;  c = N(c)                rank | rope, ONE k_r for
    q_r, k_r = rope(q_r), rope(k_r)               all heads; pairs (2i, 2i+1)
    k_n[h] = c W_UK[h];  v[h] = c W_UV[h]         nope | v_head_dim a head

and two ways through it, which give the same function of the cache:

* **prefill** expands ``k_n`` and ``v`` and runs causal attention over
  keys ``[k_n | k_r]`` of ``nope + rope`` (192) and values of
  ``v_head_dim`` (128), scale ``1 / sqrt(nope + rope)``: the flash kernel
  at those widths (scope ``latent_prefill_attention``);
* **decode** never expands the cache: ``q' = q_n W_UK^T`` [H, rank] is
  the query against ``c`` itself, ``p = softmax((q' . c + q_r . k_r) *
  scale)``, ``o = (p c) W_UV``: ``ops/latent_attention.py`` (scope
  ``latent_decode_attention``; a ragged Pallas kernel on a TPU, the same
  arithmetic in ``jax.numpy`` elsewhere).

**The cache** is ONE kind, ``{"latent": [...]}``: a [S, T, W] array a
layer whose row at a position is ``[N(c) | rope(k_r) | 0]``, ``W =
row_width(rank, rope)`` (512 + 64 -> 640 lanes: an array's last axis is
tiled by 128 in HBM, so the 64 zeros are there whatever the array
declares). 1,280 bytes a position and layer are allocated, 1,152 of them
hold something. The checkpoint's one ``kv_b_proj`` [rank, H x (nope + v)]
is held as the two per-head stacks ``w_uk`` [H, nope, rank] and ``w_uv``
[H, rank, v] the decode step multiplies by: the same linear map.

Serving only, as the other two expert families; what it refuses is
``serving_refuses``.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np

from .family import DecoderFamily
from .llm import LLMConfig, _rms_norm

_KV_BY_NAME = (
    "the batcher's {0} copies the cache's ``k`` and ``v`` kinds by name "
    "and as [S, KV, T, Dh]; a latent slab is neither")


def rope_pairs(x, positions, theta: float):
    """Rotary embedding on INTERLEAVED pairs: dims (2i, 2i + 1) of x
    [B, H, T, d] turn by ``position x theta^(-2i / d)`` (``rope_interleave``;
    ``llm._rope`` pairs dim i with i + d / 2). ``positions`` [T] or [B, T]."""
    import jax.numpy as jnp

    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    if positions.ndim == 1:
        angles = (positions[:, None].astype(jnp.float32) * freqs)[None, None]
    else:
        angles = positions[:, None, :, None].astype(jnp.float32) * freqs
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    pairs = x.reshape(*x.shape[:-1], half, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


@dataclasses.dataclass
class JoyaiLLMFlashConfig(LLMConfig):
    """The shared fields (the routed experts', ``experts_held``) and this
    family's own: multi-head latent attention, every layer caching one row
    a position."""
    block: str = "joyai_llm_flash"
    q_lora_rank: int = 0          # the query's low-rank bottleneck
    kv_lora_rank: int = 0         # the cached, normed latent c
    qk_nope_head_dim: int = 0     # a head's key dims expanded from c
    qk_rope_head_dim: int = 0     # the one rotary key all heads share
    v_head_dim: int = 0           # a head's value dims expanded from c


class JoyaiLLMFlashLM(DecoderFamily):
    config_class = JoyaiLLMFlashConfig
    step_counter_names = (
        # per decode step, summed over the expert layers: distinct held
        # experts some live lane picked, (lane, pick) pairs routed over ALL
        # experts, expert layers run, the pairs that landed on a held
        # expert; summed over the latent layers (every layer): positions
        # the ragged read streams for the live lanes (``LATENT_BLOCK x ceil(len
        # / LATENT_BLOCK)``: counted beside the call from ``lens``, what the kernel
        # walks; the dots off a TPU read the bound of every lane), the
        # lanes' lengths, and (lane, layer) reads
        "moe_experts_touched", "moe_rows_routed", "moe_layer_steps",
        "moe_rows_held", "mla_positions_read", "mla_positions_live",
        "mla_lane_steps",
    )
    prefill_counter_names = (
        # as the qwen3_next block's: the (row, pick) pairs the grouped
        # experts moved and the pairs routed, over the expert layers
        "moe_prefill_pairs_moved", "moe_prefill_pairs_routed",
        # the rows of the row tiles the grouped experts' kernel worked
        "moe_prefill_tile_rows",
    )
    serving_refuses = {
        "speculation": "the draft is the first layers of a stacked llama "
                       "block; the model's own next-token-prediction module "
                       "is not served, and a verify window has no absorbed "
                       "path over a latent cache",
        "mesh": "the latent has one key head for all query heads and cannot "
                "be divided by heads; the latent and expert kernels are not "
                "partitioned, and param_sharding knows no expert axis",
        "kv_tier": _KV_BY_NAME.format("tier spill and copy-back"),
        "prefix_cache": _KV_BY_NAME.format("prefix extract and splice"),
        "chunked_prefill": "a chunk would attend to the latent rows the "
                           "chunks before it left, expanded or absorbed, and "
                           "prefill_chunk has neither path over a latent slab",
        "preemption": _KV_BY_NAME.format("checkpoint replay"),
        "migration": _KV_BY_NAME.format("shipped slab"),
    }

    def __init__(self, **config):
        super().__init__(**config)
        cfg = self.cfg
        if not (cfg.q_lora_rank > 0 and cfg.kv_lora_rank > 0
                and cfg.qk_nope_head_dim > 0 and cfg.v_head_dim > 0
                and cfg.qk_rope_head_dim > 0 and cfg.qk_rope_head_dim % 2 == 0):
            raise ValueError(
                "latent attention needs q_lora_rank, kv_lora_rank, "
                "qk_nope_head_dim, v_head_dim and an even qk_rope_head_dim")
        if cfg.n_dense_layers < cfg.n_layers and not (
                0 < cfg.experts_per_tok <= cfg.n_routed_experts
                and cfg.expert_width > 0):
            raise ValueError(
                "layers past n_dense_layers need n_routed_experts, "
                "experts_per_tok and expert_width")
        held = cfg.experts_held
        if held is not None and not (
                0 <= held[0] and held[1] > 0
                and held[0] + held[1] <= cfg.n_routed_experts):
            raise ValueError(f"experts_held {held} outside the layer's "
                             f"{cfg.n_routed_experts} experts")
        from ..ops.latent_attention import row_width

        # resolved here and never in a traced function
        self._routed: Tuple[bool, ...] = tuple(
            l >= cfg.n_dense_layers for l in range(cfg.n_layers))
        self._n_routed_layers = sum(self._routed)
        self._n_held = cfg.n_routed_experts if held is None else held[1]
        self._row = row_width(cfg.kv_lora_rank, cfg.qk_rope_head_dim)
        self._qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        self._scale = 1.0 / float(np.sqrt(self._qk))

    def attention_kinds(self):
        # no layer reads a [S, KV, T, Dh] cache: the scheduler's window
        # arithmetic has nothing to count
        return ()

    # -- sizes ---------------------------------------------------------------

    def _attention_params(self) -> int:
        cfg = self.cfg
        D, H = cfg.d_model, cfg.n_heads
        return (D * cfg.q_lora_rank + cfg.q_lora_rank
                + cfg.q_lora_rank * H * self._qk
                + D * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
                + cfg.kv_lora_rank
                + cfg.kv_lora_rank * H * (cfg.qk_nope_head_dim + cfg.v_head_dim)
                + H * cfg.v_head_dim * D)

    def _layer_params(self, routed: bool, experts: float) -> float:
        """Parameters of one layer with ``experts`` of its held routed
        experts counted (all of them: what is held; fewer: what a step
        reads)."""
        cfg = self.cfg
        D = cfg.d_model
        n = 2 * D + self._attention_params()
        if not routed:
            return n + 3 * D * cfg.d_ff
        one = 3 * D * cfg.expert_width
        return (n + D * cfg.n_routed_experts + cfg.n_routed_experts
                + (experts + cfg.n_shared_experts) * one)

    def n_params(self) -> int:
        cfg = self.cfg
        return int(sum(self._layer_params(r, self._n_held)
                       for r in self._routed)
                   + 2 * cfg.vocab_size * cfg.d_model + cfg.d_model)

    def _expected_touched(self, rows: int) -> float:
        cfg = self.cfg
        return self._n_held * (1.0 - (
            1.0 - cfg.experts_per_tok / max(1, cfg.n_routed_experts))
            ** max(0, rows))

    def step_param_bytes(self, rows: int, param_bytes: int = 2) -> float:
        """Bytes of weights a decode step over ``rows`` live lanes reads:
        everything outside the routed experts once and, of each expert
        layer, the held experts that ``rows`` x k uniform picks over ALL
        experts are expected to touch. The embedding table is not read."""
        cfg = self.cfg
        touched = self._expected_touched(rows)
        n = sum(self._layer_params(r, touched) for r in self._routed)
        return (n + cfg.vocab_size * cfg.d_model + cfg.d_model) * param_bytes

    def flops_per_token(self, context_len: int) -> float:
        """Of the absorbed decode step: the projections, the absorbed
        query and output, the scores against ``rank + rope`` and the values
        of ``rank`` a position and head, the FFN's share."""
        cfg = self.cfg
        D, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
        share = self._n_held / max(1, cfg.n_routed_experts)
        attn = (2.0 * (self._attention_params() - cfg.q_lora_rank - r)
                + 2.0 * context_len * H * (2 * r + cfg.qk_rope_head_dim))
        total = 2.0 * D * cfg.vocab_size
        for routed in self._routed:
            total += attn
            if routed:
                total += 2.0 * D * cfg.n_routed_experts + 6.0 * D * (
                    cfg.expert_width
                    * (cfg.experts_per_tok * share + cfg.n_shared_experts))
            else:
                total += 6.0 * D * cfg.d_ff
        return total

    def latent_bytes_per_position(self) -> int:
        """The bytes of one position in one layer that hold something: the
        latent and the rotary key in the served dtype's two bytes (the
        row's padding to whole registers is allocated, and copied, beside
        them: ``cache_position_bytes``)."""
        cfg = self.cfg
        return (cfg.kv_lora_rank + cfg.qk_rope_head_dim) * 2

    def kv_bytes_per_token(self) -> int:
        return self.cfg.n_layers * self.latent_bytes_per_position()

    def decode_bytes_per_token(self, context_len: float, batch: int = 1,
                               param_bytes: int = 2) -> float:
        return (self.step_param_bytes(batch, param_bytes) / max(1, batch)
                + self.kv_bytes_per_token() * context_len)

    def dispatch_read_bytes(self, kind: str, *, rows: int = 1,
                            live: int = None, k: int = 1, bucket: int = 0,
                            tokens: int = 0, param_bytes: float = None,
                            kv_row_bytes: float = None) -> float:
        """As the afmoe block's: a decode step reads by live lane: the held
        experts that many lanes are expected to touch, and each lane's
        latent rows up to the bucket."""
        if kind in ("decode_burst", "fused_burst", "spec_burst"):
            live = rows if live is None else live
            if kv_row_bytes is None:
                kv_row_bytes = float(self.kv_bytes_per_token())
            return k * (self.step_param_bytes(live)
                        + live * bucket * kv_row_bytes)
        return super().dispatch_read_bytes(
            kind, rows=rows, k=k, bucket=bucket, tokens=tokens,
            param_bytes=param_bytes, kv_row_bytes=kv_row_bytes)

    # -- what the scheduler asks of the cache ------------------------------------

    def position_layers(self, cache):
        return list(cache["latent"])

    def prefill_slab_bytes(self, rows: int, bucket: int) -> int:
        return self.cfg.n_layers * rows * bucket * self._row * 2

    def burst_reads_ragged(self, cache, mesh=None) -> bool:
        import jax.numpy as jnp

        from ..ops.latent_attention import latent_reads_ragged

        layer0 = cache["latent"][0]
        return latent_reads_ragged(
            next(iter(layer0.devices())).platform,
            (layer0.shape[0], self.cfg.n_heads, layer0.shape[2]),
            layer0.shape, (jnp.dtype(self.cfg.dtype), layer0.dtype),
            self.cfg.kv_lora_rank, mesh)

    # -- params ----------------------------------------------------------------

    # the seeded draw's weight of ``q_norm`` and ``kv_norm`` and the scale
    # of W_kva's rotary columns. Under unit norms and N(0, 1 / fan_in)
    # projections a head's scores have unit deviation and softmax over a
    # few thousand keys is all but flat: nothing downstream can then tell
    # a wrong rotary, scale or mask from rounding (the qwen3_next block's
    # finding). At 1.75 on both sides the scores' deviation is 3, a third
    # of it the rotary key's, and a query attends to a handful of keys, as
    # a trained head does
    ATTENTION_DRAW = 1.75

    def init_params(self, seed: int = 0):
        """Seeded float32 draw, a key a layer (``init_layer``) and one for
        the embedding and the head (``init_top``). Matrices N(0, 1 /
        fan_in), the embedding N(0, 1), the layers' and the final norms'
        weights ones, ``q_norm`` and ``kv_norm`` ``ATTENTION_DRAW`` and
        W_kva's rotary columns scaled by it, ``expert_bias`` zeros (as
        published). The projections that write to the residual stream
        (``wo``, ``w2``, ``we2``, ``ws2``) are scaled by
        ``residual_scale``."""
        import jax

        keys = jax.random.split(jax.random.PRNGKey(seed), self.cfg.n_layers + 1)
        return dict(
            self.init_top(keys[-1]),
            layers=[self.init_layer(keys[l], routed)
                    for l, routed in enumerate(self._routed)])

    def init_top(self, key):
        """The embedding, the final norm and the head."""
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        D, V = cfg.d_model, cfg.vocab_size
        k_embed, k_head = jax.random.split(key)
        return {
            "embed": jax.random.normal(k_embed, (V, D), jnp.float32),
            "ln_f": jnp.ones((D,), jnp.float32),
            "unembed": jax.random.normal(k_head, (D, V), jnp.float32) / np.sqrt(D),
        }

    def init_layer(self, key, routed: bool):
        """One layer's draw: a layer is a function of its key and its kind
        alone, so a caller may draw (and cast) the layers one at a time
        under one compiled program a kind."""
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        D, H = cfg.d_model, cfg.n_heads
        rq, r, rope = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_rope_head_dim
        nope, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
        E, Fe = cfg.n_routed_experts, cfg.expert_width
        keys = iter(jax.random.split(key, 16))
        res = float(cfg.residual_scale)

        def init(shape, fan_in, scale=1.0):
            return jax.random.normal(next(keys), shape, jnp.float32) * (
                scale / np.sqrt(fan_in))

        ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
        w_kva = init((D, r + rope), D)
        p = {
            "ln_in": ones(D), "ln_post": ones(D),
            "w_qa": init((D, rq), D),
            "q_norm": ones(rq) * self.ATTENTION_DRAW,
            "w_qb": init((rq, H * self._qk), rq),
            "w_kva": w_kva.at[:, r:].multiply(self.ATTENTION_DRAW),
            "kv_norm": ones(r) * self.ATTENTION_DRAW,
            "w_uk": init((H, nope, r), r),
            "w_uv": init((H, r, dv), r),
            "wo": init((H * dv, D), H * dv, res),
        }
        if not routed:
            F = cfg.d_ff
            p.update({"w1": init((D, F), D), "w3": init((D, F), D),
                      "w2": init((F, D), F, res)})
            return p
        Fs = Fe * cfg.n_shared_experts
        p.update({
            "router": init((D, E), D),
            "expert_bias": jnp.zeros((E,), jnp.float32),
            "we1": init((self._n_held, D, Fe), D),
            "we3": init((self._n_held, D, Fe), D),
            "we2": init((self._n_held, Fe, D), Fe, res),
        })
        if Fs:
            p.update({"ws1": init((D, Fs), D), "ws3": init((D, Fs), D),
                      "ws2": init((Fs, D), Fs, res)})
        return p

    # -- the cache ---------------------------------------------------------------

    def init_cache(self, batch: int, max_seq=None):
        """``{"latent": [...]}``: one [batch, T, W] array a layer, a row
        ``[N(c) | rope(k_r) | 0]``."""
        import jax.numpy as jnp

        cfg = self.cfg
        T = max_seq or cfg.max_seq
        dt = jnp.dtype(cfg.dtype)
        return {"latent": [jnp.zeros((batch, T, self._row), dt)
                           for _ in range(cfg.n_layers)]}

    # -- one layer ---------------------------------------------------------------

    def _latent(self, p, a, positions):
        """The layer's projections of the normed input a [B, T, D]: the
        queries' two parts q_n [B, H, T, nope] and q_r [B, H, T, rope]
        (rotated), and the cache's row [B, T, W]: ``[N(c) | rope(k_r) |
        0]``."""
        import jax.numpy as jnp

        cfg = self.cfg
        dt = a.dtype
        B, T, _ = a.shape
        r, rope, nope = cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.qk_nope_head_dim
        cq = _rms_norm(a @ p["w_qa"].astype(dt), p["q_norm"].astype(dt),
                       cfg.norm_eps)
        q = (cq @ p["w_qb"].astype(dt)).reshape(
            B, T, cfg.n_heads, self._qk).transpose(0, 2, 1, 3)
        q_n = q[..., :nope]
        q_r = rope_pairs(q[..., nope:], positions, cfg.rope_theta)
        kv = a @ p["w_kva"].astype(dt)
        c = _rms_norm(kv[..., :r], p["kv_norm"].astype(dt), cfg.norm_eps)
        k_r = rope_pairs(kv[:, None, :, r:], positions, cfg.rope_theta)[:, 0]
        pad = jnp.zeros((B, T, self._row - r - rope), dt)
        return q_n, q_r, jnp.concatenate([c, k_r, pad], axis=-1)

    def _expanded(self, p, q_n, q_r, row):
        """The prefill's attention: keys and values expanded from the
        rows, causal, every head its own ``[k_n | k_r]``."""
        import jax
        import jax.numpy as jnp

        from ..ops import attention as prefill_attention

        cfg = self.cfg
        dt = row.dtype
        r, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
        c, k_r = row[..., :r], row[..., r:r + rope]
        k_n = jnp.einsum("btc,hnc->bhtn", c, p["w_uk"].astype(dt))
        v = jnp.einsum("btc,hcv->bhtv", c, p["w_uv"].astype(dt))
        k = jnp.concatenate(
            [k_n, jnp.broadcast_to(k_r[:, None], k_n.shape[:3] + (rope,))], -1)
        q = jnp.concatenate([q_n, q_r], axis=-1)
        with jax.named_scope("latent_prefill_attention"):
            return prefill_attention(q, k, v, causal=True,
                                     name="latent_prefill_attention")

    def _absorbed_query(self, p, q_n, q_r):
        """One position's queries [B, H, 1, *] -> [B, H, W] against a row:
        ``[q_n W_UK^T | q_r | 0]``."""
        import jax.numpy as jnp

        cfg = self.cfg
        dt = q_n.dtype
        q_abs = jnp.einsum("bhn,hnc->bhc", q_n[:, :, 0], p["w_uk"].astype(dt))
        pad = jnp.zeros(q_abs.shape[:2] + (
            self._row - cfg.kv_lora_rank - cfg.qk_rope_head_dim,), dt)
        return jnp.concatenate([q_abs, q_r[:, :, 0], pad], axis=-1)

    def _attention_out(self, p, o):
        """o [B, H, T, v] -> the mixer's output [B, T, D]."""
        B, _, T, _ = o.shape
        return o.transpose(0, 2, 1, 3).reshape(B, T, -1) @ p["wo"].astype(o.dtype)

    def _ffn(self, p, h, routed, live=None, real=None):
        """h [B, T, D] after the attention -> the layer's output, a routed
        layer's picks [B, T, k] over ALL experts (else None) and, for a
        decode step (``live`` [B]), (held experts touched, rows routed,
        rows that landed here); for a prefill, its grouped experts'
        ``GROUPED_COUNTS``. ``real`` [B, T] bool (a prefill's): the rows that
        are some sequence's tokens."""
        import jax

        from ..ops.experts import routed_ffn

        cfg = self.cfg
        dt = h.dtype
        B, T, D = h.shape
        m = _rms_norm(h, p["ln_post"].astype(dt), cfg.norm_eps)

        def swiglu(w1, w3, w2):
            return (jax.nn.silu(m @ p[w1].astype(dt)) * (m @ p[w3].astype(dt))
                    ) @ p[w2].astype(dt)

        if not routed:
            return h + swiglu("w1", "w3", "w2"), None, None
        # a share sends its padding nowhere (the qwen3_next block's finding)
        y, picks, counts = routed_ffn(
            m.reshape(B * T, D), p["router"], p["expert_bias"],
            cfg.experts_per_tok, cfg.route_scale, "sigmoid",
            tuple(p[n].astype(dt) for n in ("we1", "we3", "we2")),
            live=live, real=real, held=cfg.experts_held,
            n_routed=cfg.n_routed_experts, mesh=self._serving_mesh,
            redirect_pads=cfg.experts_held is not None)
        out = h + y.astype(dt).reshape(B, T, D)
        if cfg.n_shared_experts:
            out = out + swiglu("ws1", "ws3", "ws2")
        return out, picks.reshape(B, T, -1), counts

    def _head(self, params, x, last_index=None, every=False):
        import jax.numpy as jnp

        dt = x.dtype
        if not every:
            x = self._last_rows(x, last_index)
        x = _rms_norm(x, params["ln_f"].astype(dt), self.cfg.norm_eps)
        return (x @ params["unembed"].astype(dt)).astype(jnp.float32)

    # -- whole-prompt forward --------------------------------------------------------

    def _forward(self, params, tokens, pad_to, last_index):
        """One pass over whole prompts tokens [B, T], a sequence's real
        tokens being its first ``last_index + 1``: the residual stream,
        the cache's rows stacked over the layers and padded to ``pad_to``
        (None without it), the routed layers' picks [B, T, k] and the
        ``prefill_counter_names``."""
        import jax.numpy as jnp

        cfg = self.cfg
        B, T = tokens.shape
        x = self._embed_tokens(params, tokens)
        positions = jnp.arange(T)
        real = (None if last_index is None else positions[None, :] <= (
            jnp.asarray(last_index, jnp.int32)[:, None]))
        rows, picked = [], []
        grouped = jnp.zeros((2,), jnp.int32)
        for p, routed in zip(params["layers"], self._routed):
            a = _rms_norm(x, p["ln_in"].astype(x.dtype), cfg.norm_eps)
            q_n, q_r, row = self._latent(p, a, positions)
            x = x + self._attention_out(p, self._expanded(p, q_n, q_r, row))
            if pad_to is not None:
                rows.append(jnp.pad(row, ((0, 0), (0, pad_to - T), (0, 0))))
            x, picks, counts = self._ffn(p, x, routed, real=real)
            if routed:
                picked.append(picks)
                grouped = grouped + counts
        slab = None if pad_to is None else {"latent": jnp.stack(rows)}
        n_routed = sum(picks.size for picks in picked)
        return x, slab, picked, jnp.stack([
            grouped[0], jnp.int32(n_routed), grouped[1]])

    def apply(self, params, tokens):
        """tokens [B, T] int32 -> logits [B, T, V] (float32)."""
        x = self._forward(params, tokens, None, None)[0]
        return self._head(params, x, every=True)

    def _prefill(self, params, prompt, max_seq: int, last_index=None):
        """``prefill`` and the routed layers' picks [B, T, k] (a comparison
        with a reference takes them from this very program, as the afmoe
        block's)."""
        x, slab, picked, _ = self._forward(params, prompt, max_seq, last_index)
        return self._head(params, x, last_index), slab, picked

    def prefill(self, params, prompt, max_seq: int, last_index=None):
        """Logits [B, V] at each prompt's ``last_index`` and the cache's
        rows of these prompts: ``{"latent": [L, B, max_seq, W]}``."""
        return self._prefill(params, prompt, max_seq, last_index)[:2]

    def prefill_counted(self, params, prompt, max_seq: int, last_index=None):
        """``prefill`` and, after the cache's rows, its
        ``prefill_counter_names`` as an int32 vector."""
        x, slab, _, counts = self._forward(params, prompt, max_seq, last_index)
        return self._head(params, x, last_index), slab, counts

    # -- the decode step ------------------------------------------------------------------

    def decode_step_cache(self, params, cache, tokens, pos, attn_len=None,
                          write_pos=None, lens=None):
        """One token a lane over the cache ``init_cache`` laid out: tokens
        [B, 1] at ``pos`` [B]. Returns ``(logits [B, V], cache, counts)``
        with ``counts`` the step's ``step_counter_names``. ``lens`` [B]:
        ``pos + 1`` for a lane whose output anyone reads, 0 for one that
        is idle or done: such a lane's rows stay as they are (the kernel;
        the scatter off a TPU still writes them, where no read admits
        them). ``attn_len``, ``write_pos``: as
        ``DecoderLM.decode_step_ragged_list`` takes them."""
        return self._step(params, cache, tokens, pos, attn_len, write_pos,
                          lens)[:3]

    def _step(self, params, cache, tokens, pos, attn_len=None, write_pos=None,
              lens=None):
        """``decode_step_cache`` and the routed layers' picks [B, 1, k]."""
        import jax.numpy as jnp

        from ..ops.latent_attention import (
            LATENT_BLOCK, latent_decode_attention)

        cfg = self.cfg
        pos = pos.astype(jnp.int32)
        wp = pos if write_pos is None else write_pos.astype(jnp.int32)
        lens = pos + 1 if lens is None else lens.astype(jnp.int32)
        live = lens > 0
        mesh = self._serving_mesh
        x = self._embed_tokens(params, tokens)  # [B, 1, D]
        new, picked = [], []
        touched = routed_rows = held = jnp.int32(0)
        for l, (p, routed) in enumerate(zip(params["layers"], self._routed)):
            a = _rms_norm(x, p["ln_in"].astype(x.dtype), cfg.norm_eps)
            q_n, q_r, row = self._latent(p, a, pos[:, None])
            o, rows = latent_decode_attention(
                self._absorbed_query(p, q_n, q_r), cache["latent"][l],
                row[:, 0], wp, pos, lens, rank=cfg.kv_lora_rank,
                scale=self._scale, attn_len=attn_len, mesh=mesh)
            new.append(rows)
            o = jnp.einsum("bhc,hcv->bhv", o, p["w_uv"].astype(o.dtype))
            x = x + self._attention_out(p, o[:, :, None])
            x, picks, counts = self._ffn(p, x, routed, live=live)
            if routed:
                picked.append(picks)
                touched, routed_rows, held = (
                    touched + counts[0], routed_rows + counts[1],
                    held + counts[2])
        n = jnp.int32(cfg.n_layers)
        counts = jnp.stack([
            touched, routed_rows, jnp.int32(self._n_routed_layers), held,
            jnp.sum(-(-lens // LATENT_BLOCK) * LATENT_BLOCK) * n,
            jnp.sum(lens) * n,
            live.sum(dtype=jnp.int32) * n])
        return self._head(params, x), {"latent": new}, counts, picked
