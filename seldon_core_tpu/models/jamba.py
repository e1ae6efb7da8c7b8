"""JambaLM: Mamba-1 selective-state-space layers beside position-free
multi-query attention in one layer of ``attn_layer_period``, a dense SwiGLU
after every mixer (AI21 publishes the family as ``model_type: jamba``).

A ``DecoderFamily`` (``models/family.py``: the interface the scheduler and
the server ask), registered there as ``"jamba"``: ``DecoderLM(block="jamba",
...)`` and ``JambaLM(...)`` build it, over a ``JambaConfig``. With the other
blocks it shares the embedding lookup, ``_rms_norm``, the KV cache's ops
(``ops.decode_attention``, the flash kernel) and the causal depthwise
convolution with its tail (``ops/gated_delta.py``: ``conv_prefill`` /
``conv_step``, the qwen3_next block's at its own width of 4 with SiLU, here
with a ``bias``; the step's, on a TPU, is ``conv_tail_step``'s kernel over
the one array of tails); the recurrence is ``ops/selective_scan.py``. Every layer is
pre-norm,

    h = x + Mixer(N(x));   y = h + SwiGLU(N(h))

``N`` the plain RMSNorm, layer ``i`` an attention layer iff ``i %
attn_layer_period == attn_layer_offset`` and a Mamba layer otherwise:

* Mamba (``C = mamba_expand x d_model`` channels, ``N = mamba_d_state``,
  ``R = mamba_dt_rank``, ``K = mamba_d_conv``): ``[a, z] = u W_in``; ``c =
  SiLU(conv_K(a) + b_conv)`` (depthwise, causal); ``[dt, B, C'] = c W_x``,
  each through an RMSNorm of its own (Jamba's addition to Mamba-1);
  ``delta = softplus(dt W_dt + b_dt)``; ``A = -exp(A_log)``; the selective
  scan over a float32 state a sequence, ``y = S C' + D c``; ``(y SiLU(z))
  W_out``. Scope ``mamba_mixer`` (from ``u W_in`` to ``W_out``), in the
  step and in the prefill. A lane keeps the state and the convolution's
  last ``K - 1`` inputs ``a`` and nothing else.
* attention: ``q`` (``n_heads`` of ``head_dim``), ``k``, ``v``
  (``n_kv_heads``), no bias, NO rotary and no other positional term, causal
  softmax attention scaled by ``1 / sqrt(head_dim)``, ``W_o``.

The head is tied to the embedding. ``num_experts`` 1 makes every FFN the
dense SwiGLU; a configuration with routed experts is refused.

**The layers are scanned, not unrolled.** The Mamba layers between two
attention layers are alike: their weights are stacked by run
(``params["runs"]``: 7, 13 and 6 layers at the published period of 14 over
28) and each run is one ``lax.scan`` whose body is traced once, in the
prefill and in the step; the attention layers (``params["attn"]``) stand
between the runs. A burst's program then holds three Mamba layers and the
attention layers, not twenty-eight.

**The cache** is per kind: ``{"k", "v"}`` one [S, KV, T, Dh] pair an
ATTENTION layer; ``{"conv", "state"}`` ONE array each over ALL the Mamba
layers, [S, Lm, K - 1, C] and float32 [S, Lm, N, C] (the published state is
[C, N]: held transposed, ``N`` along sublanes and the channels along lanes,
so that a row of the state fills lanes), the lane axis first as the batcher
asks and the layer next: the runs' scans carry the two arrays whole and the
step's two kernels (``ops/selective_scan.py``: ``conv_tail_step``,
``selective_scan_step``) update the layer's blocks of the tails and of the
state in place, so no layer's tails or state are sliced out of a stack or
written back into one, and no op of its own touches a per-lane array
between the mixer's matrix products.

Serving only; what it refuses is ``serving_refuses``: everything that
truncates, splices or copies COLUMNS of a KV cache needs the state and the
tail at that position, and nothing keeps them.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np

from .family import DecoderFamily
from .llm import LLMConfig, _rms_norm

_NEEDS_SNAPSHOT = (
    "a Mamba layer's state and tail have no position axis: what {0} does "
    "to columns of a KV cache needs them as they were at that position "
    "(358 KB a layer at the published width), and none is kept")


@dataclasses.dataclass
class JambaConfig(LLMConfig):
    """The shared fields and this family's own (the published names)."""
    block: str = "jamba"
    attn_layer_period: int = 8
    attn_layer_offset: int = 4
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_dt_rank: int = 0        # 0: ceil(d_model / 16), the published rule
    mamba_expand: int = 2

    def __post_init__(self):
        super().__post_init__()
        if not self.mamba_dt_rank:
            self.mamba_dt_rank = -(-self.d_model // 16)


class JambaLM(DecoderFamily):
    config_class = JambaConfig
    step_counter_names = (
        # per decode step: (live lane, Mamba layer) state updates and Mamba
        # layers run (the steps themselves, for whoever divides by them);
        # summed over the attention layers, positions of K and V the step's
        # read streams (``_kv_rows_read``: as the lfm2_moe block counts
        # them) and the lanes' lengths
        "ssm_lane_steps", "ssm_layer_steps", "kv_rows_read", "kv_rows_live",
    )
    prefill_counter_names = (
        # per (sequence, Mamba layer): the steps of the scan that are some
        # token of the sequence (``lens``: what the prefill kernel walks)
        # and the steps of its bucket
        "ssm_prefill_steps_walked", "ssm_prefill_steps_bucket",
    )
    serving_refuses = {
        "speculation": "the draft is the first layers of a stacked llama "
                       "block, and a rejected window would have to roll the "
                       "state and the tails back",
        "mesh": "the scan and attention kernels are not partitioned, and "
                "param_sharding knows no stacked run",
        "kv_tier": _NEEDS_SNAPSHOT.format("the tier's spill and copy-back"),
        "prefix_cache": _NEEDS_SNAPSHOT.format("a prefix's reuse or splice"),
        "chunked_prefill": "a chunk would start from the state and the tail "
                           "the last one left, and prefill_chunk carries "
                           "neither",
        "preemption": _NEEDS_SNAPSHOT.format("a checkpoint's replay"),
        "migration": _NEEDS_SNAPSHOT.format("a shipped slab"),
    }

    def __init__(self, **config):
        super().__init__(**config)
        cfg = self.cfg
        if cfg.n_routed_experts > 1 or cfg.n_experts > 1:
            raise ValueError("the jamba block serves num_experts 1: every "
                             "FFN the dense SwiGLU")
        if not 0 <= cfg.attn_layer_offset < cfg.attn_layer_period:
            raise ValueError("attn_layer_offset lies inside the period")
        if cfg.n_heads % cfg.n_kv_heads:
            raise ValueError("whole GQA groups")
        if cfg.mamba_d_conv < 2 or min(cfg.mamba_d_state, cfg.mamba_dt_rank,
                                       cfg.mamba_expand) < 1:
            raise ValueError("mamba_d_conv >= 2 taps, and a state, a rank "
                             "and an expansion of at least 1")
        # the kinds, resolved here and never in a traced function: the
        # layers in order as ("mamba", first of the run among the Mamba
        # layers, layers of the run) and ("attn", its index among the
        # attention layers, 1)
        self._attn: Tuple[bool, ...] = tuple(
            i % cfg.attn_layer_period == cfg.attn_layer_offset
            for i in range(cfg.n_layers))
        self._n_full = sum(self._attn)
        self._n_mamba = cfg.n_layers - self._n_full
        segments, mamba, full = [], 0, 0
        for attn in self._attn:
            if attn:
                segments.append(("attn", full, 1))
                full += 1
            elif segments and segments[-1][0] == "mamba":
                segments[-1] = ("mamba", segments[-1][1], segments[-1][2] + 1)
                mamba += 1
            else:
                segments.append(("mamba", mamba, 1))
                mamba += 1
        self._segments = tuple(segments)
        self._runs = tuple(n for kind, _, n in segments if kind == "mamba")
        self._channels = cfg.mamba_expand * cfg.d_model

    def attention_kinds(self):
        # the Mamba layers read no cache of positions
        return ((self._n_full, None),) if self._n_full else ()

    # -- sizes ---------------------------------------------------------------

    def state_bytes_per_lane_and_layer(self) -> int:
        """The float32 state and the convolution's tail of one lane in one
        Mamba layer."""
        cfg = self.cfg
        return self._channels * (cfg.mamba_d_state * 4
                                 + (cfg.mamba_d_conv - 1) * 2)

    def _mixer_params(self, attn: bool) -> int:
        cfg = self.cfg
        D = cfg.d_model
        if attn:
            h, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
            return 2 * D * h + 2 * D * kv
        C, N, R = self._channels, cfg.mamba_d_state, cfg.mamba_dt_rank
        return (D * 2 * C + (cfg.mamba_d_conv + 1) * C + C * (R + 2 * N)
                + R + 2 * N + R * C + C + C * N + C + C * D)

    def n_params(self) -> int:
        cfg = self.cfg
        D = cfg.d_model
        return int(sum(self._mixer_params(a) + 2 * D + 3 * D * cfg.d_ff
                       for a in self._attn) + cfg.vocab_size * D + D)

    def flops_per_token(self, context_len: int) -> float:
        cfg = self.cfg
        total = 2.0 * cfg.d_model * cfg.vocab_size
        for attn in self._attn:
            total += 2.0 * self._mixer_params(attn) + 6.0 * cfg.d_model * cfg.d_ff
            if attn:
                total += 4.0 * context_len * cfg.n_heads * cfg.head_dim
            else:
                # the recurrence's element-wise work, a state's number a few
                # operations (not matrix products)
                total += 6.0 * self._channels * cfg.mamba_d_state
        return total

    def kv_bytes_per_token(self) -> int:
        cfg = self.cfg
        return self._n_full * 2 * cfg.n_kv_heads * cfg.head_dim * 2

    def decode_bytes_per_token(self, context_len: float, batch: int = 1,
                               param_bytes: int = 2) -> float:
        return (self.n_params() * param_bytes / max(1, batch)
                + self.kv_bytes_per_token() * context_len
                + 2 * self._n_mamba * self.state_bytes_per_lane_and_layer())

    def dispatch_read_bytes(self, kind: str, *, rows: int = 1,
                            live: int = None, k: int = 1, bucket: int = 0,
                            tokens: int = 0, param_bytes: float = None,
                            kv_row_bytes: float = None) -> float:
        """A decode step reads the weights once, an attention layer's keys
        up to the bucket and every Mamba layer's state and tail of a live
        lane."""
        if kind in ("decode_burst", "fused_burst", "spec_burst"):
            live = rows if live is None else live
            if param_bytes is None:
                param_bytes = self.n_params() * 2.0
            if kv_row_bytes is None:
                kv_row_bytes = float(self.kv_bytes_per_token())
            state = self._n_mamba * self.state_bytes_per_lane_and_layer()
            return k * (param_bytes + live * (bucket * kv_row_bytes + state))
        return super().dispatch_read_bytes(
            kind, rows=rows, k=k, bucket=bucket, tokens=tokens,
            param_bytes=param_bytes, kv_row_bytes=kv_row_bytes)

    # -- what the scheduler asks of the cache ------------------------------------

    def lane_cache_bytes(self, cache):
        """``positions -> bytes``: a row a position in the attention layers
        alone, and the Mamba layers' state and tails whole, whatever the
        lane holds: the term that does not grow with the length is the
        larger here (8.5 MB of state against 1 KB a position)."""
        per_position = self.cache_position_bytes(cache)
        fixed = sum(a.nbytes // a.shape[0]
                    for name in ("conv", "state") for a in cache.get(name, ()))

        def lane_bytes(positions: int) -> int:
            return positions * per_position + fixed if positions > 0 else 0

        return lane_bytes

    def prefill_slab_bytes(self, rows: int, bucket: int) -> int:
        return rows * (bucket * self.kv_bytes_per_token()
                       + self._n_mamba * self.state_bytes_per_lane_and_layer())

    # -- params ----------------------------------------------------------------

    # the deviation of the seeded ``wq`` and ``wk`` over N(0, 1 / fan_in)'s.
    # The family norms neither q nor k and turns neither: under unit
    # projections a head's scores ``q . k / sqrt(Dh)`` have unit deviation
    # and softmax over a thousand keys is all but flat, the attention layers
    # then add a twentieth of what a Mamba layer adds and nothing downstream
    # can tell a wrong mask or an added rotary from rounding (the qwen3_next
    # block's finding, there on the norms' weights). At 1.75 on both sides
    # the scores' deviation is 3 and a query attends to a handful of keys
    QK_DRAW = 1.75

    def init_params(self, seed: int = 0):
        """Seeded float32 draw, a key a layer (``init_mamba``,
        ``init_attention``) and one for the embedding (``init_top``), the
        Mamba layers stacked by run. Matrices N(0, 1 / fan_in), ``wq`` and
        ``wk`` times ``QK_DRAW``; the convolution's taps N(0, 1 / K) and its
        bias uniform in (-1 / sqrt(K), 1 / sqrt(K)) (torch's default);
        ``A_log`` ``log(1 .. N)`` a channel and ``D`` ones, as published;
        ``b_dt`` the inverse softplus of a step log-uniform in (1e-3, 1e-1),
        as published, so that some channels forget in tens of tokens and
        some in thousands; the three small norms uniform in (0.5, 1.5), so
        that leaving one out is another model; the layers' norms ones. The
        head is the embedding's transpose, so the final norm's weight is ``1
        / sqrt(d_model)`` (the lfm2_moe block's reason). The projections
        that write to the residual stream (``w_out``, ``wo``, ``w2``) are
        scaled by ``residual_scale``."""
        import jax
        import jax.numpy as jnp

        keys = jax.random.split(jax.random.PRNGKey(seed), self.cfg.n_layers + 1)
        layers = [self.init_attention(k) if attn else self.init_mamba(k)
                  for k, attn in zip(keys[:-1], self._attn)]
        runs, at = [], 0
        mamba = [p for p, attn in zip(layers, self._attn) if not attn]
        for n in self._runs:
            runs.append(jax.tree_util.tree_map(
                lambda *leaves: jnp.stack(leaves), *mamba[at:at + n]))
            at += n
        return dict(
            self.init_top(keys[-1]), runs=runs,
            attn=[p for p, attn in zip(layers, self._attn) if attn])

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

    def _init(self, key, n: int):
        import jax
        import jax.numpy as jnp

        keys = iter(jax.random.split(key, n))

        def init(shape, fan_in, scale=1.0):
            return jax.random.normal(next(keys), shape, jnp.float32) * (
                scale / np.sqrt(fan_in))

        return keys, init

    def _init_ffn(self, init):
        import jax.numpy as jnp

        cfg = self.cfg
        D, F = cfg.d_model, cfg.d_ff
        return {"ln_in": jnp.ones((D,), jnp.float32),
                "ln_ff": jnp.ones((D,), jnp.float32),
                "w1": init((D, F), D), "w3": init((D, F), D),
                "w2": init((F, D), F, float(cfg.residual_scale))}

    def init_mamba(self, key):
        """One Mamba layer's draw: a function of its key alone, so a caller
        may draw (and cast) the layers one at a time under one compiled
        program. ``A_log`` is [N, C], the published [C, N] transposed."""
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        D, C, N, R, K = (cfg.d_model, self._channels, cfg.mamba_d_state,
                         cfg.mamba_dt_rank, cfg.mamba_d_conv)
        keys, init = self._init(key, 16)

        def uniform(shape, lo, hi):
            return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

        dt = jnp.exp(uniform((C,), np.log(1e-3), np.log(1e-1)))
        return dict(
            self._init_ffn(init),
            w_in=init((D, 2 * C), D), conv_w=init((K, C), K),
            conv_b=uniform((C,), -1.0 / np.sqrt(K), 1.0 / np.sqrt(K)),
            w_x=init((C, R + 2 * N), C),
            dt_norm=uniform((R,), 0.5, 1.5), b_norm=uniform((N,), 0.5, 1.5),
            c_norm=uniform((N,), 0.5, 1.5),
            w_dt=init((R, C), R), b_dt=dt + jnp.log(-jnp.expm1(-dt)),
            A_log=jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[:, None],
                (N, C)),
            D=jnp.ones((C,), jnp.float32),
            w_out=init((C, D), C, float(cfg.residual_scale)))

    def init_attention(self, key):
        cfg = self.cfg
        D, Dh = cfg.d_model, cfg.head_dim
        h, kv = cfg.n_heads * Dh, cfg.n_kv_heads * Dh
        _, init = self._init(key, 8)
        return dict(
            self._init_ffn(init),
            wq=init((D, h), D, self.QK_DRAW), wk=init((D, kv), D, self.QK_DRAW),
            wv=init((D, kv), D), wo=init((h, D), h, float(cfg.residual_scale)))

    # -- the cache ---------------------------------------------------------------

    def init_cache(self, batch: int, max_seq=None):
        """``{"k", "v"}``: a [batch, KV, T, Dh] pair an attention layer;
        ``{"conv", "state"}``: ONE [batch, Lm, K - 1, C] array of tails and
        ONE float32 [batch, Lm, N, C] array of states over all the Mamba
        layers. Lists, as the batcher carries them."""
        import jax.numpy as jnp

        cfg = self.cfg
        T = max_seq or cfg.max_seq
        dt = jnp.dtype(cfg.dtype)
        kv = (batch, cfg.n_kv_heads, T, cfg.head_dim)
        cache = {"k": [jnp.zeros(kv, dt) for _ in range(self._n_full)],
                 "v": [jnp.zeros(kv, dt) for _ in range(self._n_full)]}
        if self._n_mamba:
            cache["conv"] = [jnp.zeros(
                (batch, self._n_mamba, cfg.mamba_d_conv - 1, self._channels), dt)]
            cache["state"] = [jnp.zeros(
                (batch, self._n_mamba, cfg.mamba_d_state, self._channels),
                jnp.float32)]
        return cache

    # -- one layer ---------------------------------------------------------------

    def _norm(self, x, w):
        return _rms_norm(x, w.astype(x.dtype), self.cfg.norm_eps)

    def _ffn(self, p, h):
        import jax

        dt = h.dtype
        m = self._norm(h, p["ln_ff"])
        return h + (jax.nn.silu(m @ p["w1"].astype(dt))
                    * (m @ p["w3"].astype(dt))) @ p["w2"].astype(dt)

    def _mamba_in(self, p, u, whole=False):
        """The normed input u [..., D] -> the convolution's input ``a`` and
        the gate ``z`` [..., C]; ``whole``: ``a`` is the product itself,
        [..., 2 C], for a reader that stops at C."""
        az = u @ p["w_in"].astype(u.dtype)
        return (az if whole else az[..., :self._channels],
                az[..., self._channels:])

    def _ssm_inputs(self, p, c):
        """The convolution's output c [..., C] -> what the scan takes
        beside it: ``delta`` [..., C], ``b``, ``c'`` [..., N] in c's dtype,
        ``A`` [N, C] and ``D`` [C] float32."""
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        dt = c.dtype
        R, N = cfg.mamba_dt_rank, cfg.mamba_d_state
        dbc = c @ p["w_x"].astype(dt)
        step = self._norm(dbc[..., :R], p["dt_norm"])
        b = self._norm(dbc[..., R:R + N], p["b_norm"])
        c_ = self._norm(dbc[..., R + N:], p["c_norm"])
        delta = jax.nn.softplus(
            step @ p["w_dt"].astype(dt) + p["b_dt"].astype(dt))
        a = -jnp.exp(p["A_log"].astype(jnp.float32))
        return delta, b, c_, a, p["D"].astype(jnp.float32)

    def _mamba_out(self, p, y, z):
        import jax

        dt = z.dtype
        return (y.astype(dt) * jax.nn.silu(z)) @ p["w_out"].astype(dt)

    def _heads(self, p, a):
        """An attention layer's projections of the normed input a [B, T,
        D]: q [B, H, T, Dh], k and v [B, KV, T, Dh]: as they are, no
        positional term."""
        cfg = self.cfg
        dt = a.dtype
        B, T, _ = a.shape
        Dh = cfg.head_dim
        q = (a @ p["wq"].astype(dt)).reshape(B, T, cfg.n_heads, Dh)
        k = (a @ p["wk"].astype(dt)).reshape(B, T, cfg.n_kv_heads, Dh)
        v = (a @ p["wv"].astype(dt)).reshape(B, T, cfg.n_kv_heads, Dh)
        return tuple(t.transpose(0, 2, 1, 3) for t in (q, k, v))

    def _attention_out(self, p, o):
        """o [B, H, T, Dh] -> the mixer's output [B, T, D]."""
        B, _, T, _ = o.shape
        return o.transpose(0, 2, 1, 3).reshape(B, T, -1) @ p["wo"].astype(o.dtype)

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
        tokens being its first ``last_index + 1``: the residual stream, the
        cache's leaves as ``prefill`` stacks them (None without ``pad_to``)
        and the ``prefill_counter_names``."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        from ..ops import attention as prefill_attention
        from ..ops.gated_delta import conv_prefill
        from ..ops.selective_scan import selective_scan_prefill

        cfg = self.cfg
        B, T = tokens.shape
        lens = (jnp.full((B,), T, jnp.int32) if last_index is None
                else jnp.asarray(last_index, jnp.int32) + 1)
        x = self._embed_tokens(params, tokens)
        rep = cfg.n_heads // cfg.n_kv_heads

        def mamba(x, p):
            with jax.named_scope("mamba_mixer"):
                a, z = self._mamba_in(p, self._norm(x, p["ln_in"]))
                # the tail at each prompt's own last positions, not at the
                # padded bucket's end; the state likewise
                c, tail = conv_prefill(a, p["conv_w"], lens, bias=p["conv_b"])
                y, state = selective_scan_prefill(
                    c, *self._ssm_inputs(p, c), lens)
                x = x + self._mamba_out(p, y, z)
            return self._ffn(p, x), (tail, state)

        tails, states, ks, vs = [], [], [], []
        for kind, at, _n in self._segments:
            if kind == "mamba":
                x, (tail, state) = lax.scan(
                    mamba, x, params["runs"][len(tails)])
                tails.append(tail)
                states.append(state)
                continue
            p = params["attn"][at]
            q, k, v = self._heads(p, self._norm(x, p["ln_in"]))
            o = prefill_attention(
                q, jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1),
                causal=True)
            x = self._ffn(p, x + self._attention_out(p, o))
            if pad_to is not None:
                pad = ((0, 0), (0, 0), (0, pad_to - T), (0, 0))
                ks.append(jnp.pad(k, pad))
                vs.append(jnp.pad(v, pad))
        slab = None
        if pad_to is not None:
            slab = {}
            if ks:
                slab.update(k=jnp.stack(ks), v=jnp.stack(vs))
            if tails:
                # [Lm, B, ...] over the runs -> the kind's one array [1, B,
                # Lm, ...]
                slab.update(
                    conv=jnp.moveaxis(jnp.concatenate(tails), 0, 1)[None],
                    state=jnp.moveaxis(jnp.concatenate(states), 0, 1)[None])
        counts = jnp.stack([jnp.sum(lens) * self._n_mamba,
                            jnp.int32(B * T * self._n_mamba)])
        return x, slab, counts

    def apply(self, params, tokens):
        """tokens [B, T] int32 -> logits [B, T, V] (float32)."""
        x = self._forward(params, tokens, None, None)[0]
        return self._head(params, x, every=True)

    def prefill(self, params, prompt, max_seq: int, last_index=None):
        """Logits [B, V] at each prompt's ``last_index`` and the cache's
        rows of these prompts: ``k``, ``v`` [La, B, KV, max_seq, Dh];
        ``conv`` [1, B, Lm, K - 1, C] and ``state`` [1, B, Lm, N, C] AT
        ``last_index``, whatever the prompts were padded to."""
        return self.prefill_counted(params, prompt, max_seq, last_index)[:2]

    def prefill_counted(self, params, prompt, max_seq: int, last_index=None):
        """``prefill`` and, after the cache's rows, its
        ``prefill_counter_names`` as an int32 vector."""
        x, slab, counts = self._forward(params, prompt, max_seq, last_index)
        return self._head(params, x, last_index), slab, counts

    # -- the decode step ------------------------------------------------------------------

    def _kv_rows_read(self, cache, lens, attn_len, mesh):
        """``kv_rows_read`` of one step, as the lfm2_moe block counts it:
        what ``decode_attention`` streams of K and V over the attention
        layers, by the lowering that runs: the kernel walks each lane's own
        length in whole blocks (``walk_block`` asked: 1,024 keys at this
        block's ONE KV head of 128 in bfloat16, whose K and V are the 512 KiB
        that cover an iteration's chain), the dots read the static bound of
        EVERY lane."""
        import jax.numpy as jnp
        from jax import lax

        from ..ops.decode_attention import reads_ragged, walk_block

        if not self._n_full:
            return jnp.int32(0)
        layer0 = cache["k"][0]
        B, kv, T, width = layer0.shape
        bound = T if attn_len is None else min(int(attn_len), T)
        every = jnp.int32(B * bound)
        dt = jnp.dtype(self.cfg.dtype)
        block = walk_block(kv, width, layer0.dtype, T)
        if reads_ragged("tpu", (B, self.cfg.n_heads, 1, width), layer0.shape,
                        (dt, layer0.dtype, cache["v"][0].dtype), mesh):
            read = lax.platform_dependent(
                jnp.minimum(lens, bound),
                tpu=lambda lens: jnp.sum(-(-lens // block) * block),
                default=lambda lens: every)
        else:
            read = every
        return read * self._n_full

    def decode_step_cache(self, params, cache, tokens, pos, attn_len=None,
                          write_pos=None, lens=None):
        """One token a lane over the cache ``init_cache`` laid out: tokens
        [B, 1] at ``pos`` [B]. Returns ``(logits [B, V], cache, counts)``
        with ``counts`` the step's ``step_counter_names``. ``lens`` [B]:
        ``pos + 1`` for a lane whose output anyone reads, 0 for one that is
        idle or done: such a lane's keys, state and tails stay as they are.
        ``attn_len``, ``write_pos``: as
        ``DecoderLM.decode_step_ragged_list`` takes them, for the attention
        layers."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        from ..ops import decode_attention
        from ..ops.selective_scan import (conv_tail_step, lanes_walked,
                                          selective_scan_step)

        pos = pos.astype(jnp.int32)
        wp = pos if write_pos is None else write_pos.astype(jnp.int32)
        lens = pos + 1 if lens is None else lens.astype(jnp.int32)
        live = lens > 0
        # what the mixers' kernels take of ``live``: once a step, not once a
        # layer
        walk = lanes_walked(live)
        mesh = self._serving_mesh
        x = self._embed_tokens(params, tokens)  # [B, 1, D]
        new = {name: list(layers) for name, layers in cache.items()}

        def mamba(carry, xs):
            x, tails, states = carry
            p, layer = xs
            with jax.named_scope("mamba_mixer"):
                # ``a`` as it lies in ``x W_in``: no slice of its own
                a, z = self._mamba_in(p, self._norm(x[:, 0], p["ln_in"]),
                                      whole=True)
                c, tails = conv_tail_step(
                    tails, layer, a, p["conv_w"], p["conv_b"], live,
                    mesh=mesh, walk=walk)
                states, y = selective_scan_step(
                    states, layer, c, *self._ssm_inputs(p, c), live,
                    mesh=mesh, walk=walk)
                x = x + self._mamba_out(p, y, z)[:, None]
            return (self._ffn(p, x), tails, states), None

        run = 0
        for kind, at, n in self._segments:
            if kind == "mamba":
                (x, new["conv"][0], new["state"][0]), _ = lax.scan(
                    mamba, (x, new["conv"][0], new["state"][0]),
                    (params["runs"][run], at + jnp.arange(n, dtype=jnp.int32)))
                run += 1
                continue
            p = params["attn"][at]
            q, k, v = self._heads(p, self._norm(x, p["ln_in"]))
            o, new["k"][at], new["v"][at] = decode_attention(
                q, cache["k"][at], cache["v"][at], k, v, wp, pos, lens,
                attn_len=attn_len, mesh=mesh)
            x = self._ffn(p, x + self._attention_out(p, o))
        counts = jnp.stack([
            live.sum(dtype=jnp.int32) * self._n_mamba,
            jnp.int32(self._n_mamba),
            self._kv_rows_read(cache, lens, attn_len, mesh),
            jnp.sum(lens) * self._n_full])
        return self._head(params, x), new, counts
