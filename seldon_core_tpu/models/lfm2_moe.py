"""Lfm2MoeLM: gated short-convolution layers beside grouped-query attention
in one layer of four, and a chip's share of bias-selected sigmoid experts
(LiquidAI publishes the family as ``model_type: lfm2_moe``).

A ``DecoderFamily`` (``models/family.py``: the interface the scheduler and
the server ask), registered there as ``"lfm2_moe"``:
``DecoderLM(block="lfm2_moe", ...)`` and ``Lfm2MoeLM(...)`` build it, over
a ``Lfm2MoeConfig``. With the other blocks it shares the embedding lookup,
``_rms_norm``, ``_rope``, the KV cache's ops (``ops.decode_attention``, the
flash kernel), the causal depthwise convolution and its tail
(``ops/gated_delta.py``: ``conv_prefill`` / ``conv_step``, the qwen3_next
block's, here with ``activation=None``) and the routed experts
(``ops/experts.py``). Every layer is pre-norm,

    h = x + Op(N(x));   y = h + FFN(N(h))

``N`` the plain RMSNorm, the operator by ``cfg.layer_types``:

* ``conv`` (the gated short convolution): ``[B, C, u] = x W_in`` (three
  column blocks of ``d_model`` in that order), ``z = B * u``, ``c_t = sum_j
  w[j] * z_{t - (K - 1) + j}`` (depthwise, causal, zeros before the
  sequence's start, no bias, no activation), ``Op(x) = (C * c) W_out``.
  What the next token reads of the past is ``z`` at the last ``K - 1``
  positions: a tail of 2 rows of ``d_model`` a lane where ``K = 3``, and
  nothing else. Scope ``short_conv`` (from ``x W_in`` to ``W_out``), in the
  step and in the prefill.
* ``full_attention``: ``q``, ``k``, ``v`` projections, ``q`` and ``k``
  RMS-normed over each head by one weight vector each, half-split rotary
  over the whole head at the absolute position, causal softmax attention
  scaled by ``1 / sqrt(head_dim)``, ``W_o``.

``FFN`` a SwiGLU of ``d_ff`` in the first ``n_dense_layers`` layers and
after them ``experts_per_tok`` of ``n_routed_experts`` experts, no shared
one: scores ``s = sigmoid(x W_r)`` in float32, the picks the top k of ``s
+ expert_bias`` (the bias enters the SELECTION only), the weights
``s[picks] / sum x route_scale`` (``ops.experts.route``: its sum's epsilon
is 1e-20 where the published code adds 1e-6, a relative 1e-6 of a weight
at these scores: the served path keeps the shared router, the benchmark's
reference the published epsilon). ``experts_held = (lo, n)``: this chip
holds experts ``lo .. lo + n - 1`` of every layer and computes the picks
that land on them (None: all). The head is tied to the embedding.

**The cache** is per kind, not per layer: ``{"k", "v"}`` one pair an
ATTENTION layer, ``{"conv"}`` one [S, K - 1, d_model] tail a CONVOLUTION
layer; a layer without keys allocates none. A head of 64 fills half of a
row's 128 lanes, and Mosaic refuses to slice one (``ops.decode_attention``:
the cache's tiling in HBM is (8, 128)(2, 1)), so K and V lie ``_pack = 2``
heads a row: [S, KV / 2, T, 128], a row ``[head 2g | head 2g + 1]``. The
step's queries go in zero outside their own head's half and times sqrt(2)
(the op scales the scores by a ROW's ``1 / sqrt(128)``; the factor goes
into the query in float32, before its one rounding to the cache's dtype,
so the op takes no scale of this family's): ``q_pad . row / sqrt(128) = q
. k_head / sqrt(64)``, and the half of ``p V`` that is the query's own
head's is kept. Per position 2 x KV x head_dim x 2 bytes an attention layer, as the
heads laid one a row would be if rows of 64 could be held; the read is the
ragged kernel's at 4 heads of 128.

Serving only, as the other expert families; what it refuses is
``serving_refuses``: everything that truncates, splices or copies COLUMNS
of a KV cache needs the convolutions' tails at that position carried or
copied, and nothing keeps them.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np

from .family import DecoderFamily
from .llm import LLMConfig, _rms_norm, _rope

CONV, FULL = "conv", "full_attention"
_NEEDS_TAILS = (
    "a convolution layer's tail has no position axis: what {0} does to "
    "columns of a KV cache needs the tails at that position (8 KB a layer "
    "at the published width), and none is kept")


@dataclasses.dataclass
class Lfm2MoeConfig(LLMConfig):
    """The shared fields (``layer_types``: "conv" | "full_attention" a
    layer; ``n_dense_layers``, ``d_ff``; the routed experts',
    ``experts_held``) and this family's own."""
    block: str = "lfm2_moe"
    conv_kernel: int = 3          # ``conv_L_cache``: taps of the convolution


class Lfm2MoeLM(DecoderFamily):
    config_class = Lfm2MoeConfig
    step_counter_names = (
        # per decode step, summed over the expert layers: distinct held
        # experts some live lane picked, (lane, pick) pairs routed over ALL
        # experts, expert layers run, the pairs that landed on a held
        # expert; summed over the attention layers: positions of K and V
        # the step's read streams (``_kv_rows_read``: under the kernel each
        # live lane's length rounded up to the block of its walk,
        # ``walk_block`` asked; under the dots the static bound of EVERY
        # lane; counted beside the call, by the branch the lowering takes)
        # and the lanes' lengths; and (live lane, convolution layer) tails
        # written
        "moe_experts_touched", "moe_rows_routed", "moe_layer_steps",
        "moe_rows_held", "kv_rows_read", "kv_rows_live",
        "conv_tails_written",
    )
    prefill_counter_names = (
        # as the qwen3_next block's: the (row, pick) pairs the grouped
        # experts moved and the pairs routed, over the expert layers, and
        # the rows of the row tiles the grouped experts' kernel worked
        "moe_prefill_pairs_moved", "moe_prefill_pairs_routed",
        "moe_prefill_tile_rows",
    )
    serving_refuses = {
        "speculation": "the draft is the first layers of a stacked llama "
                       "block, and a rejected window would have to roll the "
                       "convolutions' tails back",
        "mesh": "two heads of 64 lie in one row of the cache and cannot be "
                "divided by heads as they are; the expert and attention "
                "kernels are not partitioned, and param_sharding knows no "
                "expert axis",
        "kv_tier": _NEEDS_TAILS.format("the tier's spill and copy-back"),
        "prefix_cache": _NEEDS_TAILS.format("a prefix's reuse or splice"),
        "chunked_prefill": "a chunk would start from the tails the last one "
                           "left, and prefill_chunk carries none",
        "preemption": _NEEDS_TAILS.format("a checkpoint's replay"),
        "migration": _NEEDS_TAILS.format("a shipped slab"),
    }

    def __init__(self, **config):
        super().__init__(**config)
        cfg = self.cfg
        types = cfg.layer_types or ()
        if len(types) != cfg.n_layers or set(types) - {CONV, FULL}:
            raise ValueError(
                f"layer_types must name {cfg.n_layers} layers as "
                f"{CONV!r} or {FULL!r}: {types}")
        if CONV in types and cfg.conv_kernel < 2:
            raise ValueError("conv layers need conv_kernel >= 2 taps")
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
        if cfg.head_dim % 2 or cfg.n_heads % cfg.n_kv_heads:
            raise ValueError("an even head_dim and whole GQA groups")
        # resolved here and never in a traced function
        self._conv: Tuple[bool, ...] = tuple(t == CONV for t in types)
        self._n_conv = sum(self._conv)
        self._n_full = cfg.n_layers - self._n_conv
        self._routed: Tuple[bool, ...] = tuple(
            l >= cfg.n_dense_layers for l in range(cfg.n_layers))
        self._n_routed_layers = sum(self._routed)
        self._n_held = cfg.n_routed_experts if held is None else held[1]
        # KV heads a row of the cache: two of 64 fill a row's 128 lanes
        self._pack = 2 if cfg.head_dim == 64 and cfg.n_kv_heads % 2 == 0 else 1

    def attention_kinds(self):
        # the convolution layers read no cache of positions
        return ((self._n_full, None),) if self._n_full else ()

    # -- sizes ---------------------------------------------------------------

    def tail_bytes_per_lane(self) -> int:
        """The convolutions' tails of one lane over every conv layer."""
        cfg = self.cfg
        return self._n_conv * (cfg.conv_kernel - 1) * cfg.d_model * 2

    def _operator_params(self, conv: bool) -> int:
        cfg = self.cfg
        D = cfg.d_model
        if conv:
            return 3 * D * D + cfg.conv_kernel * D + D * D
        h, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
        return 2 * D * h + 2 * D * kv + 2 * cfg.head_dim

    def _layer_params(self, conv: bool, routed: bool, experts: float) -> float:
        """Parameters of one layer with ``experts`` of its held routed
        experts counted (all of them: what is held; fewer: what a step
        reads)."""
        cfg = self.cfg
        D = cfg.d_model
        n = 2 * D + self._operator_params(conv)
        if not routed:
            return n + 3 * D * cfg.d_ff
        return (n + D * cfg.n_routed_experts + cfg.n_routed_experts
                + experts * 3 * D * cfg.expert_width)

    def n_params(self) -> int:
        cfg = self.cfg
        return int(sum(self._layer_params(c, r, self._n_held)
                       for c, r in zip(self._conv, self._routed))
                   + cfg.vocab_size * cfg.d_model + cfg.d_model)

    def _expected_touched(self, rows: int) -> float:
        cfg = self.cfg
        return self._n_held * (1.0 - (
            1.0 - cfg.experts_per_tok / max(1, cfg.n_routed_experts))
            ** max(0, rows))

    def step_param_bytes(self, rows: int, param_bytes: int = 2) -> float:
        """Bytes of weights a decode step over ``rows`` live lanes reads:
        everything outside the routed experts once and, of each expert
        layer, the held experts that ``rows`` x k uniform picks over ALL
        experts are expected to touch. The tied embedding is read once, as
        the head."""
        cfg = self.cfg
        touched = self._expected_touched(rows)
        n = sum(self._layer_params(c, r, touched)
                for c, r in zip(self._conv, self._routed))
        return (n + cfg.vocab_size * cfg.d_model + cfg.d_model) * param_bytes

    def flops_per_token(self, context_len: int) -> float:
        cfg = self.cfg
        D = cfg.d_model
        share = self._n_held / max(1, cfg.n_routed_experts)
        total = 2.0 * D * cfg.vocab_size
        for conv, routed in zip(self._conv, self._routed):
            total += 2.0 * self._operator_params(conv)
            if not conv:
                total += 4.0 * context_len * cfg.n_heads * cfg.head_dim
            if routed:
                total += 2.0 * D * cfg.n_routed_experts + 6.0 * D * (
                    cfg.expert_width * cfg.experts_per_tok * share)
            else:
                total += 6.0 * D * cfg.d_ff
        return total

    def kv_bytes_per_token(self) -> int:
        cfg = self.cfg
        return self._n_full * 2 * cfg.n_kv_heads * cfg.head_dim * 2

    def decode_bytes_per_token(self, context_len: float, batch: int = 1,
                               param_bytes: int = 2) -> float:
        return (self.step_param_bytes(batch, param_bytes) / max(1, batch)
                + self.kv_bytes_per_token() * context_len
                + 2 * self.tail_bytes_per_lane())

    def dispatch_read_bytes(self, kind: str, *, rows: int = 1,
                            live: int = None, k: int = 1, bucket: int = 0,
                            tokens: int = 0, param_bytes: float = None,
                            kv_row_bytes: float = None) -> float:
        """As the afmoe block's: a decode step reads by live lane: the held
        experts that many lanes are expected to touch, an attention layer's
        keys up to the bucket, and every convolution layer's tail."""
        if kind in ("decode_burst", "fused_burst", "spec_burst"):
            live = rows if live is None else live
            if kv_row_bytes is None:
                kv_row_bytes = float(self.kv_bytes_per_token())
            return k * (self.step_param_bytes(live) + live * (
                bucket * kv_row_bytes + self.tail_bytes_per_lane()))
        return super().dispatch_read_bytes(
            kind, rows=rows, k=k, bucket=bucket, tokens=tokens,
            param_bytes=param_bytes, kv_row_bytes=kv_row_bytes)

    # -- what the scheduler asks of the cache ------------------------------------

    def lane_cache_bytes(self, cache):
        """``positions -> bytes``: a row a position in the attention layers
        alone, and the convolution layers' tails whole, whatever the lane
        holds."""
        per_position = self.cache_position_bytes(cache)
        tails = sum(a.nbytes // a.shape[0] for a in cache.get("conv", ()))

        def lane_bytes(positions: int) -> int:
            return positions * per_position + tails if positions > 0 else 0

        return lane_bytes

    def prefill_slab_bytes(self, rows: int, bucket: int) -> int:
        return rows * (bucket * self.kv_bytes_per_token()
                       + self.tail_bytes_per_lane())

    # the rows (prompts x bucket) one batched prefill takes: ``x W_in`` of
    # eight prompts of 16,384 alone is 1.6 GB beside a cache that leaves 5.
    # A context here is 1.5k-14k: past the batcher's 1792 the lengths are
    # the default rule's (``DecoderFamily.prefill_lengths``: a prompt of
    # 4,100 pads to 4,608 and not to 16,384), one prompt a call either way
    PREFILL_ROWS = 16384

    def prefill_rows_max(self, bucket: int, added: bool = False) -> int:
        return max(1, min(8, self.PREFILL_ROWS // max(1, bucket)))

    # ``admissions_per_turn`` stays the default, every free lane: the
    # prompts of a turn that share a bucket go through one batched prefill,
    # which reads the weights once and fills the experts' row tiles; what
    # one call holds the device for is bounded by ``PREFILL_ROWS``, not by
    # the number admitted (``admit_turn`` in the cell: PERF.md section 5)


    # -- params ----------------------------------------------------------------

    # the seeded draw's ``q_norm`` and ``k_norm`` weight. Under unit norms
    # a head's scores ``q . k / sqrt(64)`` have unit deviation and softmax
    # over a few thousand keys is all but flat: nothing downstream can then
    # tell a wrong rotary or a missing norm from rounding (the qwen3_next
    # block's finding). At 1.75 on both sides the scores' deviation is 3
    # at 64 wide as at 128, and a query attends to a handful of keys
    QK_NORM_DRAW = 1.75
    # the deviation of the seeded ``expert_bias``: wide enough that about
    # one pick in ten differs from the plain top k of the scores (0.095 of
    # 4 picks over 64 experts under unit router logits: the gap between the
    # fourth and fifth score is ~0.015), so that a bias left out or added
    # to the weights can be told; no wider, so that the experts touched
    # stay near uniform. What it decides of the experts a step touches is
    # part of the benchmark's yardstick
    EXPERT_BIAS_DRAW = 0.015

    def init_params(self, seed: int = 0):
        """Seeded float32 draw, a key a layer (``init_layer``) and one for
        the embedding (``init_top``). Matrices N(0, 1 / fan_in), the
        convolution's taps N(0, 1 / K), the embedding N(0, 1); the layers'
        norms ones, ``q_norm`` and ``k_norm`` ``QK_NORM_DRAW``,
        ``expert_bias`` N(0, ``EXPERT_BIAS_DRAW``^2). The head is the
        embedding's transpose, so the final norm's weight is ``1 /
        sqrt(d_model)``: logits of unit deviation, as an untied N(0, 1 /
        d_model) head draws them (under ones they would have the
        embedding's sqrt(d_model) and the softmax would be one token). The
        projections that write to the residual stream (``wo``, ``w_out``,
        ``w2``, ``we2``) are scaled by ``residual_scale``."""
        import jax

        keys = jax.random.split(jax.random.PRNGKey(seed), self.cfg.n_layers + 1)
        return dict(
            self.init_top(keys[-1]),
            layers=[self.init_layer(keys[l], conv, routed) for l, (conv, routed)
                    in enumerate(zip(self._conv, self._routed))])

    def init_top(self, key):
        """The embedding (and head) and the final norm."""
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        D, V = cfg.d_model, cfg.vocab_size
        return {
            "embed": jax.random.normal(key, (V, D), jnp.float32),
            "ln_f": jnp.full((D,), 1.0 / np.sqrt(D), jnp.float32),
        }

    def init_layer(self, key, conv: bool, routed: bool):
        """One layer's draw: a function of its key and its kinds alone, so
        a caller may draw (and cast) the layers one at a time under one
        compiled program a kind."""
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        D, Dh = cfg.d_model, cfg.head_dim
        h, kv = cfg.n_heads * Dh, cfg.n_kv_heads * Dh
        E, Fe = cfg.n_routed_experts, cfg.expert_width
        keys = iter(jax.random.split(key, 12))
        res = float(cfg.residual_scale)

        def init(shape, fan_in, scale=1.0):
            return jax.random.normal(next(keys), shape, jnp.float32) * (
                scale / np.sqrt(fan_in))

        ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
        p = {"ln_op": ones(D), "ln_ffn": ones(D)}
        if conv:
            p.update({
                "w_in": init((D, 3 * D), D),
                "conv_w": init((cfg.conv_kernel, D), cfg.conv_kernel),
                "w_out": init((D, D), D, res),
            })
        else:
            p.update({
                "wq": init((D, h), D), "wk": init((D, kv), D),
                "wv": init((D, kv), D),
                "q_norm": ones(Dh) * self.QK_NORM_DRAW,
                "k_norm": ones(Dh) * self.QK_NORM_DRAW,
                "wo": init((h, D), h, res),
            })
        if not routed:
            F = cfg.d_ff
            p.update({"w1": init((D, F), D), "w3": init((D, F), D),
                      "w2": init((F, D), F, res)})
            return p
        p.update({
            "router": init((D, E), D),
            "expert_bias": jax.random.normal(next(keys), (E,), jnp.float32)
            * self.EXPERT_BIAS_DRAW,
            "we1": init((self._n_held, D, Fe), D),
            "we3": init((self._n_held, D, Fe), D),
            "we2": init((self._n_held, Fe, D), Fe, res),
        })
        return p

    # -- the cache ---------------------------------------------------------------

    def init_cache(self, batch: int, max_seq=None):
        """``{"k", "v"}``: a [batch, KV / pack, T, pack x Dh] pair an
        attention layer; ``{"conv"}``: a [batch, K - 1, d_model] tail a
        convolution layer. Lists, in the layers' order within their
        kind."""
        import jax.numpy as jnp

        cfg = self.cfg
        T = max_seq or cfg.max_seq
        dt = jnp.dtype(cfg.dtype)
        kv = (batch, cfg.n_kv_heads // self._pack, T, self._pack * cfg.head_dim)
        tail = (batch, cfg.conv_kernel - 1, cfg.d_model)
        cache = {"k": [jnp.zeros(kv, dt) for _ in range(self._n_full)],
                 "v": [jnp.zeros(kv, dt) for _ in range(self._n_full)]}
        if self._n_conv:
            cache["conv"] = [jnp.zeros(tail, dt) for _ in range(self._n_conv)]
        return cache

    def _packed_rows(self, rows):
        """K or V rows [B, KV, T, Dh] as the cache holds them: [B, KV /
        pack, T, pack x Dh], a row ``[head pack g | ... | head pack g + pack
        - 1]``."""
        n = self._pack
        if n == 1:
            return rows
        B, KV, T, Dh = rows.shape
        return rows.reshape(B, KV // n, n, T, Dh).transpose(
            0, 1, 3, 2, 4).reshape(B, KV // n, T, n * Dh)

    def _packed_queries(self, q):
        """q [B, H, T, Dh] against packed rows: [B, H, T, pack x Dh], each
        query in its own KV head's part of the row and zero in the others,
        so that its dot with a row is its dot with its own head's key, and
        times ``sqrt(pack)``: ``ops.decode_attention`` scales the scores by
        ``1 / sqrt(pack x Dh)``, the row's width, where a head's is ``1 /
        sqrt(Dh)``. The product is float32's, rounded once."""
        import jax.numpy as jnp

        n = self._pack
        if n == 1:
            return q
        B, H, T, Dh = q.shape
        rep = H // self.cfg.n_kv_heads
        wide = q.astype(jnp.float32).reshape(B, -1, n, rep, T, 1, Dh)
        own = jnp.eye(n, dtype=jnp.float32)[:, None, None, :, None] * np.sqrt(n)
        return (wide * own).astype(q.dtype).reshape(B, H, T, n * Dh)

    def _own_part(self, o):
        """``p V`` over packed rows [B, H, T, pack x Dh] -> each query's own
        KV head's part [B, H, T, Dh]."""
        import jax.numpy as jnp

        n = self._pack
        if n == 1:
            return o
        B, H, T, W = o.shape
        Dh = W // n
        rep = H // self.cfg.n_kv_heads
        o = o.reshape(B, -1, n, rep, T, n, Dh)
        return jnp.stack([o[:, :, i, :, :, i] for i in range(n)],
                         axis=2).reshape(B, H, T, Dh)

    # -- one layer ---------------------------------------------------------------

    def _norm(self, x, w):
        return _rms_norm(x, w.astype(x.dtype), self.cfg.norm_eps)

    def _gates(self, p, a):
        """A convolution layer's projection of the normed input a [..., D]:
        the convolution's input ``z = B * u`` and the output gate ``C``."""
        D = self.cfg.d_model
        bcu = a @ p["w_in"].astype(a.dtype)
        return bcu[..., :D] * bcu[..., 2 * D:], bcu[..., D:2 * D]

    def _heads(self, p, a, positions):
        """An attention layer's projections of the normed input a [B, T,
        D]: q [B, H, T, Dh], k and v [B, KV, T, Dh], q and k normed per
        head and rotated."""
        cfg = self.cfg
        dt = a.dtype
        B, T, _ = a.shape
        Dh = cfg.head_dim
        q = (a @ p["wq"].astype(dt)).reshape(B, T, cfg.n_heads, Dh)
        k = (a @ p["wk"].astype(dt)).reshape(B, T, cfg.n_kv_heads, Dh)
        v = (a @ p["wv"].astype(dt)).reshape(B, T, cfg.n_kv_heads, Dh)
        q, k = self._norm(q, p["q_norm"]), self._norm(k, p["k_norm"])
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        return (_rope(q, positions, cfg.rope_theta),
                _rope(k, positions, cfg.rope_theta), v)

    def _attention_out(self, p, o):
        """o [B, H, T, Dh] -> the operator's output [B, T, D]."""
        B, _, T, _ = o.shape
        return o.transpose(0, 2, 1, 3).reshape(B, T, -1) @ p["wo"].astype(o.dtype)

    def _ffn(self, p, h, routed, live=None, real=None):
        """h [B, T, D] after the operator -> the layer's output, a routed
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
        m = self._norm(h, p["ln_ffn"])
        if not routed:
            return h + (jax.nn.silu(m @ p["w1"].astype(dt))
                        * (m @ p["w3"].astype(dt))) @ p["w2"].astype(dt), None, None
        # a share sends its padding nowhere (the qwen3_next block's finding)
        y, picks, counts = routed_ffn(
            m.reshape(B * T, D), p["router"], p["expert_bias"],
            cfg.experts_per_tok, cfg.route_scale, "sigmoid",
            tuple(p[n].astype(dt) for n in ("we1", "we3", "we2")),
            live=live, real=real, held=cfg.experts_held,
            n_routed=cfg.n_routed_experts, mesh=self._serving_mesh,
            redirect_pads=cfg.experts_held is not None)
        return h + y.astype(dt).reshape(B, T, D), picks.reshape(B, T, -1), counts

    def _weights(self, p, h):
        """The routing weights [B, T, k] float32 of a routed layer's input h
        [B, T, D], beside ``_ffn``'s picks: ``ops.experts.route`` again, for
        a comparison with a reference alone (``_prefill``, ``_step``).
        Inside one ``jit`` it is the computation ``routed_ffn`` makes, once;
        ``prefill`` and ``decode_step_cache`` drop it."""
        from ..ops.experts import route

        cfg = self.cfg
        B, T, D = h.shape
        m = self._norm(h, p["ln_ffn"])
        return route(m.reshape(B * T, D), p["router"], p["expert_bias"],
                     cfg.experts_per_tok, cfg.route_scale, "sigmoid")[1].reshape(
                         B, T, -1)

    def _head(self, params, x, last_index=None, every=False):
        """The final norm and the head, the embedding's transpose."""
        import jax.numpy as jnp
        from jax import lax

        if not every:
            x = self._last_rows(x, last_index)
        x = self._norm(x, params["ln_f"])
        return lax.dot_general(
            x, params["embed"].astype(x.dtype),
            (((x.ndim - 1,), (1,)), ((), ()))).astype(jnp.float32)

    # -- whole-prompt forward --------------------------------------------------------

    def _forward(self, params, tokens, pad_to, last_index):
        """One pass over whole prompts tokens [B, T], a sequence's real
        tokens being its first ``last_index + 1``: the residual stream,
        the cache's leaves as ``prefill`` stacks them (None without
        ``pad_to``), the routed layers' picks [B, T, k] and the
        ``prefill_counter_names``."""
        import jax
        import jax.numpy as jnp

        from ..ops import attention as prefill_attention
        from ..ops.gated_delta import conv_prefill

        cfg = self.cfg
        B, T = tokens.shape
        lens = (jnp.full((B,), T, jnp.int32) if last_index is None
                else jnp.asarray(last_index, jnp.int32) + 1)
        x = self._embed_tokens(params, tokens)
        positions = jnp.arange(T)
        real = (None if last_index is None
                else positions[None, :] < lens[:, None])
        rep = cfg.n_heads // cfg.n_kv_heads
        leaves = {"k": [], "v": [], "conv": []}
        picked, weighed = [], []
        grouped = jnp.zeros((2,), jnp.int32)
        for p, conv, routed in zip(params["layers"], self._conv, self._routed):
            a = self._norm(x, p["ln_op"])
            if conv:
                with jax.named_scope("short_conv"):
                    z, gate = self._gates(p, a)
                    # the tail at each prompt's own last positions, not at
                    # the padded bucket's end
                    c, tail = conv_prefill(z, p["conv_w"], lens, activation=None)
                    x = x + (gate * c) @ p["w_out"].astype(x.dtype)
                leaves["conv"].append(tail)
            else:
                q, k, v = self._heads(p, a, positions)
                o = prefill_attention(
                    q, jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1),
                    causal=True)
                x = x + self._attention_out(p, o)
                if pad_to is not None:
                    pad = ((0, 0), (0, 0), (0, pad_to - T), (0, 0))
                    leaves["k"].append(jnp.pad(self._packed_rows(k), pad))
                    leaves["v"].append(jnp.pad(self._packed_rows(v), pad))
            if routed:
                weighed.append(self._weights(p, x))
            x, picks, counts = self._ffn(p, x, routed, real=real)
            if routed:
                picked.append(picks)
                grouped = grouped + counts
        slab = None if pad_to is None else {
            name: jnp.stack(each) for name, each in leaves.items() if each}
        n_routed = sum(picks.size for picks in picked)
        return x, slab, (picked, weighed), jnp.stack([
            grouped[0], jnp.int32(n_routed), grouped[1]])

    def apply(self, params, tokens):
        """tokens [B, T] int32 -> logits [B, T, V] (float32)."""
        x = self._forward(params, tokens, None, None)[0]
        return self._head(params, x, every=True)

    def _prefill(self, params, prompt, max_seq: int, last_index=None):
        """``prefill`` and the routed layers' picks [B, T, k] and their
        weights (a comparison with a reference takes them from this very
        program, as the afmoe block's)."""
        x, slab, (picked, weighed), _ = self._forward(
            params, prompt, max_seq, last_index)
        return self._head(params, x, last_index), slab, picked, weighed

    def prefill(self, params, prompt, max_seq: int, last_index=None):
        """Logits [B, V] at each prompt's ``last_index`` and the cache's
        rows of these prompts, each leaf stacked over the layers of its
        kind: ``k``, ``v`` [La, B, KV / pack, max_seq, pack x Dh]; ``conv``
        [Lc, B, K - 1, D] AT ``last_index``, whatever the prompts were
        padded to."""
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
        is idle or done: such a lane's keys and tails stay as they are
        (the kernel; the scatter off a TPU still writes its K and V row,
        where no read admits it). ``attn_len``, ``write_pos``: as
        ``DecoderLM.decode_step_ragged_list`` takes them, for the
        attention layers."""
        return self._step(params, cache, tokens, pos, attn_len, write_pos,
                          lens)[:3]

    def _rows_walked(self, layer0, lens):
        """Positions of one attention layer the ragged kernel streams for
        lanes of ``lens`` [B]: each rounded up to the block of its walk over
        ``layer0`` [B, KV / pack, T, pack x Dh] (``walk_block`` asked)."""
        import jax.numpy as jnp

        from ..ops.decode_attention import walk_block

        block = walk_block(layer0.shape[1], layer0.shape[3], layer0.dtype,
                           layer0.shape[2])
        return jnp.sum(-(-lens // block) * block)

    def _kv_rows_read(self, cache, lens, attn_len, mesh):
        """``kv_rows_read`` of one step: what ``decode_attention`` streams
        of K and V over the attention layers, by the lowering that runs. The
        kernel walks each lane's own length (``_rows_walked``); the dots read
        the static bound of EVERY lane, idle ones too. Chosen as the op
        chooses (``reads_ragged`` for a TPU's lowering, then the platform),
        so a step that fell to the dots on the chip counts 6.4 GB of rows at
        64 lanes of 16,384 and not the lanes' 3.1."""
        import jax.numpy as jnp
        from jax import lax

        from ..ops.decode_attention import reads_ragged

        if not self._n_full:
            return jnp.int32(0)
        layer0 = cache["k"][0]
        B, _, T, width = layer0.shape
        bound = T if attn_len is None else min(int(attn_len), T)
        every = jnp.int32(B * bound)
        dt = jnp.dtype(self.cfg.dtype)
        if reads_ragged("tpu", (B, self.cfg.n_heads, 1, width), layer0.shape,
                        (dt, layer0.dtype, cache["v"][0].dtype), mesh):
            read = lax.platform_dependent(
                jnp.minimum(lens, bound),
                tpu=lambda lens: self._rows_walked(layer0, lens),
                default=lambda lens: every)
        else:
            read = every
        return read * self._n_full

    def _step(self, params, cache, tokens, pos, attn_len=None, write_pos=None,
              lens=None):
        """``decode_step_cache``, the routed layers' picks [B, 1, k] and
        their weights."""
        import jax
        import jax.numpy as jnp

        from ..ops import decode_attention
        from ..ops.gated_delta import conv_step

        pos = pos.astype(jnp.int32)
        wp = pos if write_pos is None else write_pos.astype(jnp.int32)
        lens = pos + 1 if lens is None else lens.astype(jnp.int32)
        live = lens > 0
        mesh = self._serving_mesh
        x = self._embed_tokens(params, tokens)  # [B, 1, D]
        new = {name: [] for name in cache}
        picked, weighed = [], []
        touched = routed_rows = held = jnp.int32(0)
        full = at = 0
        for p, conv, routed in zip(params["layers"], self._conv, self._routed):
            a = self._norm(x, p["ln_op"])
            if conv:
                with jax.named_scope("short_conv"):
                    z, gate = self._gates(p, a[:, 0])
                    c, tail = conv_step(z, cache["conv"][at], p["conv_w"],
                                        live, activation=None)
                    x = x + ((gate * c) @ p["w_out"].astype(x.dtype))[:, None]
                new["conv"].append(tail)
                at += 1
            else:
                q, k, v = self._heads(p, a, pos[:, None])
                o, nk, nv = decode_attention(
                    self._packed_queries(q), cache["k"][full], cache["v"][full],
                    self._packed_rows(k), self._packed_rows(v), wp, pos, lens,
                    attn_len=attn_len, mesh=mesh)
                x = x + self._attention_out(p, self._own_part(o))
                new["k"].append(nk)
                new["v"].append(nv)
                full += 1
            if routed:
                weighed.append(self._weights(p, x))
            x, picks, counts = self._ffn(p, x, routed, live=live)
            if routed:
                picked.append(picks)
                touched, routed_rows, held = (
                    touched + counts[0], routed_rows + counts[1],
                    held + counts[2])
        counts = jnp.stack([
            touched, routed_rows, jnp.int32(self._n_routed_layers), held,
            self._kv_rows_read(cache, lens, attn_len, mesh),
            jnp.sum(lens) * self._n_full,
            live.sum(dtype=jnp.int32) * self._n_conv])
        return self._head(params, x), new, counts, picked, weighed
