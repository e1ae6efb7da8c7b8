"""Qwen3NextLM: Gated DeltaNet layers beside gated softmax attention, and a
chip's share of softmax-routed experts (Qwen publishes the family as
``model_type: qwen3_next``).

A ``DecoderFamily`` (``models/family.py``: the interface the scheduler and
the server ask), registered there as ``"qwen3_next"``:
``DecoderLM(block="qwen3_next", ...)`` and ``Qwen3NextLM(...)`` build it,
over a ``Qwen3NextConfig``. With the other blocks it shares the embedding
lookup, ``_rms_norm``, ``_rope``, the KV cache's ops
(``ops.decode_attention``, the flash kernel) and the routed experts
(``ops/experts.py``). Every layer is

    h = x + Mixer(N_in(x));   y = h + MoE(N_post(h))

with ``N`` the zero-centred RMSNorm (``x * rsqrt(mean(x^2) + eps) * (1 +
w)``), and the mixer by ``cfg.layer_types``:

* ``full_attention``: ``q, gate = x Wq, x Wg`` (the checkpoint interleaves
  the two per head in one matrix: the same linear map), ``k, v``; q and k
  normed over each head (zero-centred); half-split rotary on the first
  ``partial_rotary_factor`` of a head's dims, the rest passed through;
  causal softmax attention; ``(o * sigmoid(gate)) Wo``.
* ``linear_attention`` (Gated DeltaNet): ``q, k, v, z = x W_qkvz``, ``b, a =
  x W_ba``; q, k, v through a causal depthwise convolution with SiLU; q,
  k L2-normed, q scaled by ``Dk^-0.5``, each key head serving ``Hv / Hk``
  value heads; ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
  dt_bias)``; the gated delta rule (``ops/gated_delta.py``) over a
  float32 state [Hv, Dk, Dv] a sequence; ``RMSNorm(o) * w * silu(z)`` per
  head (plain weight), then ``W_out``.

The MoE routes over all ``n_routed_experts`` by the softmax of the router's
float32 logits, top ``experts_per_tok``, weights normed over the picks,
beside one shared expert gated by ``sigmoid(x w_sg)``. ``experts_held =
(lo, n)``: this chip holds experts ``lo .. lo + n - 1`` of every layer and
computes the picks that land on them; what the other chips of the layer
would add is left out (``ops/experts.py``). None: all of them.

**The cache** is per kind, not per layer: ``{"k", "v"}`` one [S, KV, T,
Dh] pair a FULL layer, ``{"conv", "state"}`` one [S, K - 1, C] tail and
one float32 [S, Hv, Dk, Dv] matrix a LINEAR layer, every leaf leading with
the lane axis. A layer without keys allocates none. The batcher carries
the dict through its burst and writes an admitted prompt's rows at its
lane (``ContinuousBatcher``: ``decode_step_cache``).

Serving only, as the afmoe block; what it refuses is ``serving_refuses``:
everything that truncates, splices or copies COLUMNS of a KV cache needs
a snapshot of the state here, and has none yet.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np

from .family import DecoderFamily
from .llm import LLMConfig, _rms_norm, _rope

LINEAR, FULL = "linear_attention", "full_attention"
_NEEDS_SNAPSHOT = (
    "a lane's recurrent state has no position axis: what {0} does to "
    "columns of a KV cache needs a snapshot of the state at that "
    "position, and none is kept")


@dataclasses.dataclass
class Qwen3NextConfig(LLMConfig):
    """The shared fields (``layer_types``: "linear_attention" |
    "full_attention" a layer; the routed experts', ``experts_held``) and
    this family's own."""
    block: str = "qwen3_next"
    linear_key_heads: int = 0
    linear_value_heads: int = 0
    linear_key_dim: int = 0
    linear_value_dim: int = 0
    linear_conv_kernel: int = 0
    partial_rotary_factor: float = 1.0   # share of a head's dims rotated
    shared_expert_width: int = 0


class Qwen3NextLM(DecoderFamily):
    config_class = Qwen3NextConfig
    step_counter_names = (
        # per decode step, summed over the layers: distinct held experts
        # some live lane picked, (lane, pick) pairs routed over ALL
        # experts, expert layers run, the pairs that landed on a held
        # expert, and (lane, linear layer) state updates
        "moe_experts_touched", "moe_rows_routed", "moe_layer_steps",
        "moe_rows_held", "gdn_lane_steps",
    )
    prefill_counter_names = (
        # per prefill, summed over the layers: the (row, pick) pairs the
        # grouped experts moved (a share: its room x its passes; all of
        # them where every expert is held) and the pairs routed
        "moe_prefill_pairs_moved", "moe_prefill_pairs_routed",
        # per (sequence, linear layer): the chunks of the gated delta rule
        # that hold a token of the sequence (``ceil(lens / CHUNK)``: what
        # the prefill kernel walks) and the chunks of its bucket
        "gdn_prefill_chunks_walked", "gdn_prefill_chunks_bucket",
        # the rows of the row tiles the grouped experts' kernel worked, a
        # tile once for every group it was worked for
        "moe_prefill_tile_rows",
    )
    serving_refuses = {
        "speculation": "the draft is the first layers of a stacked llama "
                       "block, and a rejected window would have to roll "
                       "the recurrent state back",
        "mesh": "the expert, state and attention kernels are not "
                "partitioned, and param_sharding knows no expert axis",
        "kv_tier": _NEEDS_SNAPSHOT.format("the tier's spill and copy-back"),
        "prefix_cache": _NEEDS_SNAPSHOT.format("a prefix's reuse or splice"),
        "chunked_prefill": "a chunk would start from the state and the "
                           "convolution's tail the last one left, and "
                           "prefill_chunk carries neither",
        "preemption": _NEEDS_SNAPSHOT.format("a checkpoint's replay"),
        "migration": _NEEDS_SNAPSHOT.format("a shipped slab"),
    }

    def __init__(self, **config):
        super().__init__(**config)
        cfg = self.cfg
        types = cfg.layer_types or ()
        if len(types) != cfg.n_layers or set(types) - {LINEAR, FULL}:
            raise ValueError(
                f"layer_types must name {cfg.n_layers} layers as "
                f"{LINEAR!r} or {FULL!r}: {types}")
        if LINEAR in types and not (
                cfg.linear_key_heads > 0 and cfg.linear_key_dim > 0
                and cfg.linear_value_dim > 0 and cfg.linear_conv_kernel > 1
                and cfg.linear_value_heads % cfg.linear_key_heads == 0):
            raise ValueError(
                "linear_attention layers need linear_key_heads, "
                "linear_value_heads (a multiple of them), linear_key_dim, "
                "linear_value_dim and linear_conv_kernel")
        if not (0 < cfg.experts_per_tok <= cfg.n_routed_experts
                and cfg.expert_width > 0 and cfg.shared_expert_width > 0):
            raise ValueError("every layer needs n_routed_experts, "
                             "experts_per_tok, expert_width and "
                             "shared_expert_width")
        held = cfg.experts_held
        if held is not None and not (
                0 <= held[0] and held[1] > 0
                and held[0] + held[1] <= cfg.n_routed_experts):
            raise ValueError(f"experts_held {held} outside the layer's "
                             f"{cfg.n_routed_experts} experts")
        rot = int(cfg.head_dim * cfg.partial_rotary_factor)
        if rot <= 0 or rot % 2 or rot > cfg.head_dim:
            raise ValueError(f"partial_rotary_factor gives {rot} rotary dims")
        self._rotary_dims = rot
        # the kinds, resolved here and never in a traced function
        self._linear: Tuple[bool, ...] = tuple(t == LINEAR for t in types)
        self._n_linear = sum(self._linear)
        self._n_full = cfg.n_layers - self._n_linear
        self._n_held = cfg.n_routed_experts if held is None else held[1]

    def attention_kinds(self):
        return ((self._n_full, None),) if self._n_full else ()

    # -- sizes ---------------------------------------------------------------

    def _conv_channels(self) -> int:
        cfg = self.cfg
        return (2 * cfg.linear_key_heads * cfg.linear_key_dim
                + cfg.linear_value_heads * cfg.linear_value_dim)

    def _value_width(self) -> int:
        return self.cfg.linear_value_heads * self.cfg.linear_value_dim

    def state_bytes_per_lane_and_layer(self) -> int:
        """The float32 matrix and the convolution's tail of one lane in one
        linear layer."""
        cfg = self.cfg
        return (cfg.linear_value_heads * cfg.linear_key_dim
                * cfg.linear_value_dim * 4
                + (cfg.linear_conv_kernel - 1) * self._conv_channels() * 2)

    def _layer_params(self, linear: bool, experts: float) -> float:
        """Parameters of one layer with ``experts`` of its held routed
        experts counted (all of them: what is held; fewer: what a step
        reads)."""
        cfg = self.cfg
        D = cfg.d_model
        n = 2 * D + D * cfg.n_routed_experts + D + (
            3 * D * cfg.shared_expert_width) + experts * 3 * D * cfg.expert_width
        if linear:
            c, vw = self._conv_channels(), self._value_width()
            return n + D * (c + vw) + D * 2 * cfg.linear_value_heads + (
                cfg.linear_conv_kernel * c + 2 * cfg.linear_value_heads
                + cfg.linear_value_dim + vw * D)
        h, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
        return n + 3 * D * h + 2 * D * kv + 2 * cfg.head_dim

    def n_params(self) -> int:
        cfg = self.cfg
        return int(sum(self._layer_params(l, self._n_held) for l in self._linear)
                   + 2 * cfg.vocab_size * cfg.d_model + cfg.d_model)

    def _expected_touched(self, rows: int) -> float:
        cfg = self.cfg
        return self._n_held * (1.0 - (
            1.0 - cfg.experts_per_tok / cfg.n_routed_experts) ** max(0, rows))

    def step_param_bytes(self, rows: int, param_bytes: int = 2) -> float:
        """Bytes of weights a decode step over ``rows`` live lanes reads:
        everything outside the routed experts once and, of each layer, the
        held experts that ``rows`` x k uniform picks over ALL experts are
        expected to touch. The embedding table is not read."""
        cfg = self.cfg
        touched = self._expected_touched(rows)
        n = sum(self._layer_params(l, touched) for l in self._linear)
        return (n + cfg.vocab_size * cfg.d_model + cfg.d_model) * param_bytes

    def flops_per_token(self, context_len: int) -> float:
        cfg = self.cfg
        D = cfg.d_model
        share = self._n_held / cfg.n_routed_experts
        moe = 2.0 * D * (cfg.n_routed_experts + 1) + 6.0 * D * (
            cfg.expert_width * cfg.experts_per_tok * share
            + cfg.shared_expert_width)
        total = 2.0 * D * cfg.vocab_size
        for linear in self._linear:
            if linear:
                c, vw = self._conv_channels(), self._value_width()
                total += 2.0 * D * (c + vw + 2 * cfg.linear_value_heads) + (
                    2.0 * vw * D + 2.0 * cfg.linear_conv_kernel * c
                    + 6.0 * vw * cfg.linear_key_dim)
            else:
                h, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
                total += 2.0 * (3 * D * h + 2 * D * kv) + 4.0 * context_len * h
            total += moe
        return total

    def kv_bytes_per_token(self) -> int:
        cfg = self.cfg
        return self._n_full * 2 * cfg.n_kv_heads * cfg.head_dim * 2

    def decode_bytes_per_token(self, context_len: float, batch: int = 1,
                               param_bytes: int = 2) -> float:
        return (self.step_param_bytes(batch, param_bytes) / max(1, batch)
                + self.kv_bytes_per_token() * context_len
                + 2 * self._n_linear * self.state_bytes_per_lane_and_layer())

    def dispatch_read_bytes(self, kind: str, *, rows: int = 1,
                            live: int = None, k: int = 1, bucket: int = 0,
                            tokens: int = 0, param_bytes: float = None,
                            kv_row_bytes: float = None) -> float:
        """As the afmoe block's: a decode step reads by live lane: the held
        experts that many lanes are expected to touch, a full layer's keys
        up to the bucket, and every linear layer's state of a live lane."""
        if kind in ("decode_burst", "fused_burst", "spec_burst"):
            live = rows if live is None else live
            if kv_row_bytes is None:
                kv_row_bytes = float(self.kv_bytes_per_token())
            state = self._n_linear * self.state_bytes_per_lane_and_layer()
            return k * (self.step_param_bytes(live)
                        + live * (bucket * kv_row_bytes + state))
        return super().dispatch_read_bytes(
            kind, rows=rows, k=k, bucket=bucket, tokens=tokens,
            param_bytes=param_bytes, kv_row_bytes=kv_row_bytes)

    # -- params ----------------------------------------------------------------

    # the seeded draw's q_norm and k_norm weight (zero-centred: the norm
    # multiplies by 1.75). Under random projections a head's scores have
    # unit deviation and softmax over a few thousand keys is all but flat:
    # the attention layers then add a twentieth of what a DeltaNet layer
    # adds and nothing downstream can tell a wrong rotary or mask. With
    # both norms at 1.75 the scores' deviation is 3 and a query attends to
    # a handful of keys, as a trained head does
    QK_NORM_DRAW = 0.75

    def init_params(self, seed: int = 0):
        """Seeded float32 draw. Matrices N(0, 1 / fan_in), the embedding
        N(0, 1), the input, post-mixer and final norms' weights 0 (the
        zero-centred norm is then plain), ``q_norm`` and ``k_norm``
        ``QK_NORM_DRAW``, the DeltaNet output norm ones. The projections that write
        to the residual stream (``wo``, ``w_out``, ``we2``, ``ws2``) are
        scaled by ``residual_scale``, as in the llama block. ``A_log`` and
        ``dt_bias`` as the Gated DeltaNet reference implementation draws
        them: ``A`` uniform in (0, 16), ``dt`` log-uniform in (1e-3, 1e-1)
        and ``dt_bias`` its inverse softplus, so a head's decay a token
        spans memories of a few tokens to a few thousand."""
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        D, Dh, V = cfg.d_model, cfg.head_dim, cfg.vocab_size
        h, kv = cfg.n_heads * Dh, cfg.n_kv_heads * Dh
        E, Fe, Fs = cfg.n_routed_experts, cfg.expert_width, cfg.shared_expert_width
        Hv, c, vw = cfg.linear_value_heads, self._conv_channels(), self._value_width()
        keys = iter(jax.random.split(jax.random.PRNGKey(seed),
                                     20 * cfg.n_layers + 4))
        res = float(cfg.residual_scale)

        def init(shape, fan_in, scale=1.0):
            return jax.random.normal(next(keys), shape, jnp.float32) * (
                scale / np.sqrt(fan_in))

        zeros = lambda n: jnp.zeros((n,), jnp.float32)  # noqa: E731
        layers = []
        for linear in self._linear:
            p = {
                "ln_in": zeros(D), "ln_post": zeros(D),
                "router": init((D, E), D),
                "we1": init((self._n_held, D, Fe), D),
                "we3": init((self._n_held, D, Fe), D),
                "we2": init((self._n_held, Fe, D), Fe, res),
                "ws1": init((D, Fs), D), "ws3": init((D, Fs), D),
                "ws2": init((Fs, D), Fs, res), "w_sg": init((D, 1), D),
            }
            if linear:
                a = jax.random.uniform(next(keys), (Hv,), jnp.float32,
                                       1e-3, 16.0)
                dt = jnp.exp(jax.random.uniform(
                    next(keys), (Hv,), jnp.float32,
                    np.log(1e-3), np.log(1e-1)))
                p.update({
                    "w_qkvz": init((D, c + vw), D),
                    "w_ba": init((D, 2 * Hv), D),
                    "conv_w": init((cfg.linear_conv_kernel, c),
                                   cfg.linear_conv_kernel),
                    "A_log": jnp.log(a),
                    "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                    "o_norm": jnp.ones((cfg.linear_value_dim,), jnp.float32),
                    "w_out": init((vw, D), vw, res),
                })
            else:
                p.update({
                    "wq": init((D, h), D), "wg": init((D, h), D),
                    "wk": init((D, kv), D), "wv": init((D, kv), D),
                    "q_norm": zeros(Dh) + self.QK_NORM_DRAW,
                    "k_norm": zeros(Dh) + self.QK_NORM_DRAW,
                    "wo": init((h, D), h, res),
                })
            layers.append(p)
        return {
            "embed": jax.random.normal(next(keys), (V, D), jnp.float32),
            "layers": layers,
            "ln_f": zeros(D),
            "unembed": init((D, V), D),
        }

    # -- the cache ---------------------------------------------------------------

    def init_cache(self, batch: int, max_seq=None):
        """``{"k", "v"}``: a [batch, KV, T, Dh] pair a full layer;
        ``{"conv", "state"}``: a [batch, K - 1, C] tail and a float32
        [batch, Hv, Dk, Dv] matrix a linear layer. Lists, in the layers'
        order within their kind."""
        import jax.numpy as jnp

        cfg = self.cfg
        T = max_seq or cfg.max_seq
        dt = jnp.dtype(cfg.dtype)
        kv = (batch, cfg.n_kv_heads, T, cfg.head_dim)
        conv = (batch, cfg.linear_conv_kernel - 1, self._conv_channels())
        state = (batch, cfg.linear_value_heads, cfg.linear_key_dim,
                 cfg.linear_value_dim)
        return {
            "k": [jnp.zeros(kv, dt) for _ in range(self._n_full)],
            "v": [jnp.zeros(kv, dt) for _ in range(self._n_full)],
            "conv": [jnp.zeros(conv, dt) for _ in range(self._n_linear)],
            "state": [jnp.zeros(state, jnp.float32)
                      for _ in range(self._n_linear)],
        }

    # -- the layers ----------------------------------------------------------------

    def _norm(self, x, w):
        """The zero-centred RMSNorm."""
        return _rms_norm(x, (1.0 + w.astype(np.float32)).astype(x.dtype),
                         self.cfg.norm_eps)

    def _heads(self, p, a, positions):
        """A full layer's projections of the normed input a [B, T, D]: q
        [B, H, T, Dh], k and v [B, KV, T, Dh] (q, k normed per head, rotary
        on the first ``_rotary_dims``) and the gate's logits [B, T, H Dh]."""
        import jax.numpy as jnp

        cfg = self.cfg
        dt = a.dtype
        B, T, _ = a.shape
        Dh, rot = cfg.head_dim, self._rotary_dims
        q = (a @ p["wq"].astype(dt)).reshape(B, T, cfg.n_heads, Dh)
        k = (a @ p["wk"].astype(dt)).reshape(B, T, cfg.n_kv_heads, Dh)
        v = (a @ p["wv"].astype(dt)).reshape(B, T, cfg.n_kv_heads, Dh)
        g = a @ p["wg"].astype(dt)
        q, k = self._norm(q, p["q_norm"]), self._norm(k, p["k_norm"])
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))

        def rotary(x):
            return jnp.concatenate(
                [_rope(x[..., :rot], positions, cfg.rope_theta), x[..., rot:]],
                axis=-1)

        return rotary(q), rotary(k), v, g

    def _gate_out(self, p, o, g):
        """o [B, H, T, Dh] and the gate's logits -> the mixer's output."""
        import jax
        import jax.numpy as jnp

        B, _, T, _ = o.shape
        dt = o.dtype
        o = o.transpose(0, 2, 1, 3).reshape(B, T, -1)
        o = o * jax.nn.sigmoid(g.astype(jnp.float32)).astype(dt)
        return o @ p["wo"].astype(dt)

    def _delta_inputs(self, p, a):
        """A linear layer's projections of a [B, T, D]: the convolution's
        input [B, T, C], z [B, T, Hv Dv], and in float32 beta and g [B, T,
        Hv]."""
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        dt = a.dtype
        c = self._conv_channels()
        qkvz = a @ p["w_qkvz"].astype(dt)
        ba = jnp.dot(a, p["w_ba"].astype(dt),
                     preferred_element_type=jnp.float32)
        b, a_ = ba[..., :cfg.linear_value_heads], ba[..., cfg.linear_value_heads:]
        g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
            a_ + p["dt_bias"].astype(jnp.float32))
        return qkvz[..., :c], qkvz[..., c:], jax.nn.sigmoid(b), g

    def _delta_heads(self, u):
        """The convolution's output u [..., C] -> q, k [..., Hv, Dk]
        (L2-normed, q scaled, each key head repeated for its value heads)
        and v [..., Hv, Dv], in u's dtype."""
        import jax.numpy as jnp

        from ..ops.gated_delta import l2norm

        cfg = self.cfg
        Hk, Dk = cfg.linear_key_heads, cfg.linear_key_dim
        Hv, Dv = cfg.linear_value_heads, cfg.linear_value_dim
        lead = u.shape[:-1]
        q = u[..., :Hk * Dk].reshape(*lead, Hk, Dk)
        k = u[..., Hk * Dk:2 * Hk * Dk].reshape(*lead, Hk, Dk)
        v = u[..., 2 * Hk * Dk:].reshape(*lead, Hv, Dv)
        q = (l2norm(q) * Dk ** -0.5).astype(u.dtype)
        k = l2norm(k).astype(u.dtype)
        rep = Hv // Hk
        return jnp.repeat(q, rep, axis=-2), jnp.repeat(k, rep, axis=-2), v

    def _delta_out(self, p, o, z):
        """o [..., Hv, Dv] float32 and z [..., Hv Dv] -> the mixer's
        output: per head RMSNorm(o) * w * silu(z), then ``w_out``."""
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        dt = z.dtype
        o = _rms_norm(o.astype(dt), p["o_norm"].astype(dt), cfg.norm_eps)
        zh = z.reshape(o.shape).astype(jnp.float32)
        o = (o.astype(jnp.float32) * jax.nn.silu(zh)).astype(dt)
        return o.reshape(*z.shape) @ p["w_out"].astype(dt)

    def _moe(self, p, h, live=None, real=None):
        """h [B, T, D] after the mixer -> the layer's output, the picks
        [B, T, k] over ALL experts and, for a decode step (``live`` [B]),
        (held experts touched, rows routed, rows that landed here); for a
        prefill, its grouped experts' ``GROUPED_COUNTS``. ``real`` [B, T] bool
        (a prefill's): the rows that are some sequence's tokens."""
        import jax
        import jax.numpy as jnp

        from ..ops.experts import routed_ffn

        cfg = self.cfg
        dt = h.dtype
        B, T, D = h.shape
        m = self._norm(h, p["ln_post"])
        # padding routes together: a bucket's worth of it on a held expert
        # would overflow the share's room, so a share sends it nowhere
        y, picks, counts = routed_ffn(
            m.reshape(B * T, D), p["router"], None, cfg.experts_per_tok, 1.0,
            "softmax", tuple(p[n].astype(dt) for n in ("we1", "we3", "we2")),
            live=live, real=real, held=cfg.experts_held,
            n_routed=cfg.n_routed_experts, mesh=self._serving_mesh,
            redirect_pads=cfg.experts_held is not None)
        shared = (jax.nn.silu(m @ p["ws1"].astype(dt))
                  * (m @ p["ws3"].astype(dt))) @ p["ws2"].astype(dt)
        gate = jax.nn.sigmoid(jnp.dot(
            m, p["w_sg"].astype(dt), preferred_element_type=jnp.float32))
        out = h + y.astype(dt).reshape(B, T, D) + (
            shared.astype(jnp.float32) * gate).astype(dt)
        return out, picks.reshape(B, T, -1), counts

    def _head(self, params, x, last_index=None, every=False):
        import jax.numpy as jnp

        dt = x.dtype
        if not every:
            x = self._last_rows(x, last_index)
        x = self._norm(x, params["ln_f"])
        return (x @ params["unembed"].astype(dt)).astype(jnp.float32)

    # -- whole-prompt forward --------------------------------------------------------

    def _forward(self, params, tokens, pad_to, last_index):
        """One pass over whole prompts tokens [B, T], a sequence's real
        tokens being its first ``last_index + 1``: the residual stream,
        the cache's leaves as ``prefill`` stacks them (None without
        ``pad_to``), every layer's picks [B, T, k] and the
        ``prefill_counter_names``."""
        import jax.numpy as jnp

        from ..ops import attention as prefill_attention
        from ..ops import gated_delta

        cfg = self.cfg
        B, T = tokens.shape
        lens = (jnp.full((B,), T, jnp.int32) if last_index is None
                else jnp.asarray(last_index, jnp.int32) + 1)
        x = self._embed_tokens(params, tokens)
        positions = jnp.arange(T)
        real = (None if last_index is None
                else positions[None, :] < lens[:, None])
        rep = cfg.n_heads // cfg.n_kv_heads
        leaves = {"k": [], "v": [], "conv": [], "state": []}
        picked = []
        grouped = jnp.zeros((2,), jnp.int32)
        for p, linear in zip(params["layers"], self._linear):
            a = self._norm(x, p["ln_in"])
            if linear:
                qkv, z, beta, g = self._delta_inputs(p, a)
                u, tail = gated_delta.conv_prefill(qkv, p["conv_w"], lens)
                q, k, v = self._delta_heads(u)
                o, state = gated_delta.gated_delta_prefill(
                    q, k, v, g, beta, lens,
                    mesh=self._serving_mesh)
                x = x + self._delta_out(p, o, z)
                leaves["conv"].append(tail)
                leaves["state"].append(state)
            else:
                q, k, v, g = self._heads(p, a, positions)
                o = prefill_attention(
                    q, jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1),
                    causal=True)
                x = x + self._gate_out(p, o, g)
                if pad_to is not None:
                    pad = ((0, 0), (0, 0), (0, pad_to - T), (0, 0))
                    leaves["k"].append(jnp.pad(k, pad))
                    leaves["v"].append(jnp.pad(v, pad))
            x, picks, counts = self._moe(p, x, real=real)
            picked.append(picks)
            grouped = grouped + counts
        slab = None if pad_to is None else {
            name: jnp.stack(each) for name, each in leaves.items() if each}
        routed = sum(picks.size for picks in picked)
        # counted beside the calls, from what the kernel is told
        walked = jnp.sum(-(-lens // gated_delta.CHUNK)) * self._n_linear
        bucket = B * -(-T // gated_delta.CHUNK) * self._n_linear
        return x, slab, picked, jnp.stack([
            grouped[0], jnp.int32(routed), walked, jnp.int32(bucket),
            grouped[1]])

    def apply(self, params, tokens):
        """tokens [B, T] int32 -> logits [B, T, V] (float32)."""
        x = self._forward(params, tokens, None, None)[0]
        return self._head(params, x, every=True)

    def _prefill(self, params, prompt, max_seq: int, last_index=None):
        """``prefill`` and every layer's picks [B, T, k] (a comparison with
        a reference takes them from this very program, as the afmoe
        block's)."""
        x, slab, picked, _ = self._forward(params, prompt, max_seq, last_index)
        return self._head(params, x, last_index), slab, picked

    def prefill(self, params, prompt, max_seq: int, last_index=None):
        """Logits [B, V] at each prompt's ``last_index`` and the cache's
        rows of these prompts, each leaf stacked over the layers of its
        kind: ``k``, ``v`` [Lf, B, KV, max_seq, Dh]; ``conv`` [Ll, B, K -
        1, C] and ``state`` [Ll, B, Hv, Dk, Dv] AT ``last_index``, whatever
        the prompts were padded to."""
        return self._prefill(params, prompt, max_seq, last_index)[:2]

    def prefill_counted(self, params, prompt, max_seq: int, last_index=None):
        """``prefill`` and, after the cache's rows, its
        ``prefill_counter_names`` as an int32 vector (what a decode
        step's counts are to ``step_counter_names``)."""
        x, slab, _, counts = self._forward(params, prompt, max_seq, last_index)
        return self._head(params, x, last_index), slab, counts

    # -- the decode step ------------------------------------------------------------------

    def decode_step_cache(self, params, cache, tokens, pos, attn_len=None,
                          write_pos=None, lens=None):
        """One token a lane over the cache ``init_cache`` laid out: tokens
        [B, 1] at ``pos`` [B]. Returns ``(logits [B, V], cache, counts)``
        with ``counts`` the step's ``step_counter_names``. ``lens`` [B]:
        ``pos + 1`` for a lane whose output anyone reads, 0 for one that
        is idle or done: such a lane's keys, state and tail stay as they
        are. ``attn_len``, ``write_pos``: as
        ``DecoderLM.decode_step_ragged_list`` takes them, for the full
        layers."""
        return self._step(params, cache, tokens, pos, attn_len, write_pos,
                          lens)[:3]

    def _step(self, params, cache, tokens, pos, attn_len=None, write_pos=None,
              lens=None):
        """``decode_step_cache`` and every layer's picks [B, 1, k]."""
        import jax.numpy as jnp

        from ..ops import decode_attention, gated_delta

        pos = pos.astype(jnp.int32)
        wp = pos if write_pos is None else write_pos.astype(jnp.int32)
        lens = pos + 1 if lens is None else lens.astype(jnp.int32)
        live = lens > 0
        mesh = self._serving_mesh
        x = self._embed_tokens(params, tokens)  # [B, 1, D]
        new = {name: [] for name in cache}
        picked = []
        touched = routed = held = jnp.int32(0)
        full = lin = 0
        for p, linear in zip(params["layers"], self._linear):
            a = self._norm(x, p["ln_in"])
            if linear:
                qkv, z, beta, g = self._delta_inputs(p, a)
                u, tail = gated_delta.conv_step(
                    qkv[:, 0], cache["conv"][lin], p["conv_w"], live)
                q, k, v = self._delta_heads(u)
                state, o = gated_delta.gated_delta_step(
                    cache["state"][lin], q, k, v, g[:, 0], beta[:, 0], live,
                    mesh=mesh)
                x = x + self._delta_out(p, o, z[:, 0])[:, None]
                new["conv"].append(tail)
                new["state"].append(state)
                lin += 1
            else:
                q, k, v, g = self._heads(p, a, pos[:, None])
                o, nk, nv = decode_attention(
                    q, cache["k"][full], cache["v"][full], k, v, wp, pos,
                    lens, attn_len=attn_len, mesh=mesh)
                x = x + self._gate_out(p, o, g)
                new["k"].append(nk)
                new["v"].append(nv)
                full += 1
            x, picks, counts = self._moe(p, x, live=live)
            picked.append(picks)
            touched, routed, held = (touched + counts[0], routed + counts[1],
                                     held + counts[2])
        counts = jnp.stack([
            touched, routed, jnp.int32(len(self._linear)), held,
            live.sum(dtype=jnp.int32) * self._n_linear])
        return self._head(params, x), new, counts, picked
