"""EvaByteLM: a byte-level decoder with EVA attention (EvaByte publishes the
family as ``model_type: evabyte``, ``attention_class: eva``).

A ``DecoderFamily`` (``models/family.py``: the interface the scheduler and
the server ask), registered there as ``"evabyte"``:
``DecoderLM(block="evabyte", ...)`` and ``EvaByteLM(...)`` build it, over an
``EvaByteConfig``. With the other blocks it shares the embedding lookup, the
rotary embedding (half-split pairs), the flash kernel (here behind a
visible prefix) and the batcher's cache dict. Every layer is pre-norm,
``N(x) = x * rsqrt(mean(x^2) + eps) * (1 + w)`` (``norm_add_unit_offset``),
and the residual stream adds in float32 (``fp32_skip_add``):

    h = x + EVA(N(x));   y = h + W_down(silu(W_gate N(h)) * W_up N(h))

**The attention.** ``q, k, v = N(x) W_q, W_k, W_v`` -> H heads x Dh, q and k
rotated at absolute positions. Position t lies in the aligned window ``w = t
// W`` (``window_size``) and in the chunk ``c = t // C`` (``chunk_size``; W /
C chunks a window). A chunk's summary, a head (``mu_k`` and ``phi``: two
learned Dh-vectors a head and layer; ``s = 1 / sqrt(Dh)``):

    k~_c = sum_j softmax_j(s * k_j . mu_k) k_j
    v~_c = sum_j softmax_j(s * k_j . phi) v_j             j in chunk c

and position t attends, under ONE softmax, to its own window exactly and to
every earlier window's summaries:

    local   { j : W w <= j <= t }      scores s * q_t . k_j     values v_j
    remote  { c : c < (W / C) w }      scores s * q_t . k~_c    values v~_c

A chunk of the current window is never read as a summary.

**The cache** is two kinds of two lengths, K and V of each (``cache_layers``):
the RING ``window_k`` / ``window_v`` [S, H, W, Dh] (row ``t mod W``: a new
window starts over at row 0 and the old rows are residue) and the SUMMARIES
``summary_k`` / ``summary_v`` [S, H, max_seq / C, Dh] (row ``t // C``,
written by the step that completes the chunk, ``(t + 1) mod C == 0``, from
the ring's last C rows, inside the decode kernel's call; visible from the
next window on). A position costs
``2 H Dh`` values a layer while it is in its window and a C-th of that
after. What the scheduler must know of such a cache it asks:
``park_index``, ``lane_cache_bytes``, ``prefill_lengths``,
``prefill_rows_max``.

**Three forms.** ``_window`` (a prompt's window: the flash kernel, the
earlier windows' summaries a visible prefix before its causal part; scope
``eva_prefill_attention``), ``ops/eva_attention.py`` (a decode step: a
ragged kernel over both kinds on a TPU, the same arithmetic in
``jax.numpy`` elsewhere; scope ``eva_decode_attention``; it pools the chunk
a step completes and writes its summary row) and ``chunk_summary`` (a
prompt's chunks; scope ``eva_chunk_summary``). A prompt longer than a
window is prefilled by ONE executable that walks the prompt's own
``ceil(len / W)`` windows, window-major (a window through all layers, then
the next): window ``w`` needs of each layer its own W rows and the
summaries windows ``< w`` left there.

**The head** is one [D, P x V] matrix, head-major (``num_pred_heads`` P):
head 0 is the next byte, heads 1.. draft the bytes after it. All P heads'
logits are computed in float32 (``_prefill``, ``_step``); the serving path
samples from head 0, and accepting several bytes a step is refused
(``serving_refuses["speculation"]``).
"""

from __future__ import annotations

import dataclasses
from typing import Sequence, Tuple

import numpy as np

from .family import DecoderFamily
from .llm import LLMConfig, _rope

_RING = (
    "the batcher's {0} copies the cache's ``k`` and ``v`` kinds by name, "
    "one row a position from 0; a ring of one window and a row a chunk of "
    "the earlier ones is neither")


@dataclasses.dataclass
class EvaByteConfig(LLMConfig):
    """The shared fields and this family's own: EVA attention, the
    norms' and the residual stream's conventions, the prediction heads."""
    block: str = "evabyte"
    window_size: int = 0          # positions of one aligned window
    chunk_size: int = 0           # positions pooled into one summary row
    num_pred_heads: int = 1       # heads of vocab_size logits a position
    norm_add_unit_offset: bool = False   # a norm's weight is 1 + w
    fp32_skip_add: bool = False   # the residual stream adds in float32


class EvaByteLM(DecoderFamily):
    config_class = EvaByteConfig
    step_counter_names = (
        # per decode step, summed over the live lanes and the layers: ring
        # rows a lane's position admits (``(t mod W) + 1``), summary rows
        # (``(W / C) floor(t / W)``), the rows of both kinds the ragged
        # read streams (each kind rounded up to ``EVA_BLOCK``: counted
        # beside the call, what the kernel walks; the dots off a TPU read
        # both arrays whole), what a cache of one row a position would
        # read (``t + 1``), summaries written, and (lane, layer) reads
        "eva_window_rows_live", "eva_summary_rows_live", "eva_rows_read",
        "eva_positions_live", "eva_summaries_written", "eva_lane_steps",
    )
    prefill_counter_names = (
        # windows a prefill walked (``ceil(len / W)`` a prompt) and the
        # windows its bucket holds (what a prefill padded to the bucket
        # would have computed)
        "eva_prefill_windows_walked", "eva_prefill_windows_bucket",
    )
    # the cache's kinds, as ``cache_layers`` and a prefill's slab name them
    _KINDS = ("window_k", "window_v", "summary_k", "summary_v")
    serving_refuses = {
        "speculation": "the heads past the first draft the bytes after the "
                       "next one for the model's own self-speculative "
                       "decoding; accepting several tokens a lane and step "
                       "is not what the burst's scan yields, and a verify "
                       "window has no path over a ring and its summaries",
        "mesh": "the ring-and-summary kernel is not partitioned, and a "
                "prompt's window walk carries every layer's summaries "
                "through one loop on one chip",
        "kv_tier": _RING.format("tier spill and copy-back"),
        "prefix_cache": _RING.format("prefix extract and splice"),
        "chunked_prefill": "the family's prefill is its own walk over a "
                           "prompt's windows, one executable; prefill_chunk "
                           "has no path over a ring and its summaries",
        "preemption": _RING.format("checkpoint replay"),
        "migration": _RING.format("shipped slab"),
    }

    def __init__(self, **config):
        super().__init__(**config)
        cfg = self.cfg
        W, C = cfg.window_size, cfg.chunk_size
        if not (C > 0 and W > 0 and W % C == 0 and cfg.max_seq % W == 0):
            raise ValueError(
                f"EVA attention needs chunk_size ({C}) to divide window_size "
                f"({W}) and window_size to divide max_seq ({cfg.max_seq})")
        if cfg.n_kv_heads != cfg.n_heads or cfg.num_pred_heads < 1:
            raise ValueError("EVA attention has a key head a query head, "
                             "and the model at least one prediction head")
        self._scale = 1.0 / float(np.sqrt(cfg.head_dim))
        self._per_window = W // C

    def attention_kinds(self):
        # no layer reads a [S, KV, T, Dh] cache of one row a position: the
        # scheduler's window arithmetic has nothing to count
        return ()

    # -- sizes ---------------------------------------------------------------

    def _layer_params(self) -> int:
        cfg = self.cfg
        D, hd = cfg.d_model, cfg.n_heads * cfg.head_dim
        return 2 * D + 4 * D * hd + 2 * hd + 3 * D * cfg.d_ff

    def n_params(self) -> int:
        cfg = self.cfg
        return (cfg.n_layers * self._layer_params() + cfg.d_model
                + cfg.vocab_size * cfg.d_model * (1 + cfg.num_pred_heads))

    def step_param_bytes(self, param_bytes: int = 2) -> int:
        """Bytes of weights a decode step reads: every layer, the final
        norm and the whole head; not the embedding table."""
        cfg = self.cfg
        return (cfg.n_layers * self._layer_params() + cfg.d_model
                + cfg.num_pred_heads * cfg.vocab_size * cfg.d_model
                ) * param_bytes

    def rows_at(self, positions):
        """``(ring rows, summary rows)`` a lane that holds ``positions``
        positions reads at its next step's end (its last position ``t =
        positions - 1``): ``(t mod W) + 1`` and ``(W / C) floor(t / W)``.
        Python ints or arrays."""
        W = self.cfg.window_size
        t = positions - 1
        return t % W + 1, t // W * self._per_window

    def row_bytes(self) -> int:
        """K and V of one row of either kind in one layer."""
        cfg = self.cfg
        return 2 * cfg.n_heads * cfg.head_dim * 2

    def flops_per_token(self, context_len: int) -> float:
        cfg = self.cfg
        D = cfg.d_model
        ring, summ = self.rows_at(max(1, int(context_len)))
        per_layer = (2.0 * 4 * D * cfg.n_heads * cfg.head_dim
                     + 4.0 * (ring + summ) * cfg.n_heads * cfg.head_dim
                     + 6.0 * D * cfg.d_ff)
        return (cfg.n_layers * per_layer
                + 2.0 * D * cfg.vocab_size * cfg.num_pred_heads)

    def kv_bytes_per_token(self) -> int:
        """A position's bytes while it is in its window, every layer."""
        return self.cfg.n_layers * self.row_bytes()

    def decode_bytes_per_token(self, context_len: float, batch: int = 1,
                               param_bytes: int = 2) -> float:
        ring, summ = self.rows_at(max(1, int(context_len)))
        return (self.step_param_bytes(param_bytes) / max(1, batch)
                + self.kv_bytes_per_token() * (ring + summ))

    def dispatch_read_bytes(self, kind: str, *, rows: int = 1,
                            live: int = None, k: int = 1, bucket: int = 0,
                            tokens: int = 0, param_bytes: float = None,
                            kv_row_bytes: float = None) -> float:
        """A decode step reads by live lane: each lane's ring rows and
        summary rows at the bucket's position."""
        if kind in ("decode_burst", "fused_burst", "spec_burst"):
            live = rows if live is None else live
            ring, summ = self.rows_at(max(1, int(bucket)))
            return k * (self.step_param_bytes()
                        + live * (ring + summ) * self.kv_bytes_per_token())
        return super().dispatch_read_bytes(
            kind, rows=rows, k=k, bucket=bucket, tokens=tokens,
            param_bytes=param_bytes, kv_row_bytes=kv_row_bytes)

    # -- what the scheduler asks of the cache ------------------------------------

    def position_layers(self, cache):
        """The ring's arrays: what every step writes one row each of (a
        summary row is written by one step in ``chunk_size``)."""
        return [*cache["window_k"], *cache["window_v"]]

    def park_index(self, cache) -> int:
        """Past every position a lane can hold: the ring wraps (``park mod
        W`` would alias a live row), so a parked lane is told apart by its
        position, not by where the ring ends."""
        return cache["summary_k"][0].shape[-2] * self.cfg.chunk_size

    def lane_cache_bytes(self, cache):
        """``positions -> bytes`` a lane that holds them occupies over every
        layer: its window's rows whole and a row a chunk of the windows
        before."""
        per_row = sum(a.nbytes // (a.shape[0] * a.shape[-2])
                      for a in self.position_layers(cache))

        def lane_bytes(positions: int) -> int:
            if positions <= 0:
                return 0
            ring, summ = self.rows_at(positions)
            return (ring + summ) * per_row

        return lane_bytes

    def prefill_lengths(self, buckets: Sequence[int], max_seq: int
                        ) -> Tuple[int, ...]:
        """Of the batcher's buckets, those a prefill of this family takes:
        whole chunks inside one window, or whole windows (walked). A prompt
        past them takes ``max_seq``, whole windows by construction."""
        cfg = self.cfg
        return tuple(b for b in buckets if (
            b % cfg.chunk_size == 0 and b <= cfg.window_size)
            or b % cfg.window_size == 0)

    def prefill_rows_max(self, bucket: int, added: bool = False) -> int:
        """A walked bucket takes one prompt a call: a batch would walk
        every row through the longest row's windows and return a ring and
        all summaries a row (``prefill_slab_bytes``)."""
        return 1 if bucket > self.cfg.window_size else 8

    def admissions_per_turn(self) -> int:
        """One: a walked prompt holds the device for its windows (0.37 s at
        12,200 bytes on a v5e) and nothing batches it with another, so a
        wave of admissions back to back would keep the lanes already
        admitted, and every live lane, silent until its last prefill ends.
        A burst runs between two (PERF.md section 6, PR 44)."""
        return 1

    def prefill_slab_bytes(self, rows: int, bucket: int) -> int:
        cfg = self.cfg
        ring = min(bucket, cfg.window_size)
        return (cfg.n_layers * rows * (ring + bucket // cfg.chunk_size)
                * self.row_bytes())

    def burst_reads_ragged(self, cache, mesh=None) -> bool:
        # kernel or dots, the read is bounded by each lane's own rows of
        # both kinds and never by a bucket: one burst executable per k
        return True

    def step_counters_in_kernel(self, cache, mesh=None):
        """``{stats key: step counter}``: the writes a step counts that the
        decode kernel lands itself where the step over ``cache`` is lowered
        for the platform its arrays live on (``eva_reads_ragged``: a
        completed chunk is pooled and its summary row written inside
        ``eva_decode_attention``); the counter is None where the scatters
        write them, and the key then stays 0."""
        import jax.numpy as jnp

        from ..ops.eva_attention import eva_reads_ragged

        ring, summ = cache["window_k"][0], cache["summary_k"][0]
        lowered = eva_reads_ragged(
            next(iter(ring.devices())).platform,
            (ring.shape[0], self.cfg.n_heads, ring.shape[3]), ring.shape,
            summ.shape,
            (jnp.dtype(self.cfg.dtype), ring.dtype, cache["window_v"][0].dtype,
             summ.dtype, cache["summary_v"][0].dtype),
            self.cfg.chunk_size, mesh)
        return {"eva_summaries_written_in_kernel":
                "eva_summaries_written" if lowered else None}

    # -- params ----------------------------------------------------------------

    # the seeded draw's scale of W_q and W_k, and the reciprocal of the
    # pooling vectors' deviation. Under N(0, 1 / fan_in) projections a
    # head's scores have unit deviation and softmax over thousands of keys
    # is all but flat: nothing downstream can then tell a wrong mask, rotary
    # or pooling from rounding (the qwen3_next block's finding). At 1.75 on
    # both sides the scores' deviation is 3; ``mu_k`` and ``phi`` N(0,
    # (POOLING_DRAW / 1.75)^2) give the pooling logits ``s * k . mu`` a
    # deviation of ``POOLING_DRAW`` over a chunk, the attention's own: a
    # summary is then led by a chunk's one or two best keys and keeps most
    # of a key's norm, so that a summary row competes with exact rows. At
    # the published ``init_std`` pooling is all but a mean of 16, and at
    # unit deviation a summary's score is a third of a key's: reading
    # summaries where none belong then moves the logits by less than
    # bfloat16's rounding does (PERF.md, PR 44: 0.020 for 0.018)
    ATTENTION_DRAW = 1.75
    POOLING_DRAW = 3.0
    NORM_DRAW = 0.1   # deviation of a norm's ``w`` (its weight is 1 + w)

    def init_params(self, seed: int = 0):
        """Seeded float32 draw, a key a layer (``init_layer``) and one for
        the embedding, the final norm and the head (``init_top``)."""
        import jax

        keys = jax.random.split(jax.random.PRNGKey(seed), self.cfg.n_layers + 1)
        return dict(self.init_top(keys[-1]),
                    layers=[self.init_layer(keys[l])
                            for l in range(self.cfg.n_layers)])

    def init_top(self, key):
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        D, V, P = cfg.d_model, cfg.vocab_size, cfg.num_pred_heads
        k_embed, k_norm, k_head = jax.random.split(key, 3)
        return {
            "embed": jax.random.normal(k_embed, (V, D), jnp.float32),
            "ln_f": jax.random.normal(k_norm, (D,), jnp.float32) * self.NORM_DRAW,
            # head-major: column p * V + v is head p's logit of byte v
            "unembed": jax.random.normal(k_head, (D, P * V), jnp.float32)
            / np.sqrt(D),
        }

    def init_layer(self, key):
        """One layer's draw: matrices N(0, 1 / fan_in), W_q and W_k scaled
        by ``ATTENTION_DRAW``, the pooling vectors N(0, 1) over it, the
        norms' ``w`` N(0, ``NORM_DRAW``^2); the projections that write to
        the residual stream scaled by ``residual_scale``."""
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        D, H, Dh, F = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff
        keys = iter(jax.random.split(key, 11))
        res = float(cfg.residual_scale)

        def init(shape, fan_in, scale=1.0):
            return jax.random.normal(next(keys), shape, jnp.float32) * (
                scale / np.sqrt(fan_in))

        return {
            "ln_in": init((D,), 1, self.NORM_DRAW),
            "ln_post": init((D,), 1, self.NORM_DRAW),
            "wq": init((D, H * Dh), D, self.ATTENTION_DRAW),
            "wk": init((D, H * Dh), D, self.ATTENTION_DRAW),
            "wv": init((D, H * Dh), D),
            "wo": init((H * Dh, D), H * Dh, res),
            "mu_k": init((H, Dh), 1, self.POOLING_DRAW / self.ATTENTION_DRAW),
            "phi": init((H, Dh), 1, self.POOLING_DRAW / self.ATTENTION_DRAW),
            "w1": init((D, F), D), "w3": init((D, F), D),
            "w2": init((F, D), F, res),
        }

    def burst_params(self, params):
        """Every layer's q / k / v projection weights [D, H Dh] and the
        head [D, P V] held [out, D]: the burst's projections of its 20 rows
        consume them contraction-minor, and handed the stored layout the
        TPU compiler relays all 25 at the top of every burst (826 MB
        written and read again: all of the burst's scratch; PERF.md
        section 6, PR 54). (No serving mesh: ``serving_refuses``.)"""
        return {**self.relaid(params, ("unembed",)),
                "layers": [self.relaid(p, ("wq", "wk", "wv"))
                           for p in params["layers"]]}

    # -- the cache ---------------------------------------------------------------

    def init_cache(self, batch: int, max_seq=None):
        """The four kinds, a list over the layers each: the ring [batch, H,
        W, Dh] and the summaries [batch, H, T / C, Dh], K and V."""
        import jax.numpy as jnp

        cfg = self.cfg
        T = max_seq or cfg.max_seq
        if T % cfg.chunk_size:
            raise ValueError(f"a cache of {T} positions is not whole chunks "
                             f"of {cfg.chunk_size}")
        dt = jnp.dtype(cfg.dtype)
        ring = (batch, cfg.n_heads, cfg.window_size, cfg.head_dim)
        summ = (batch, cfg.n_heads, T // cfg.chunk_size, cfg.head_dim)

        def layers(shape):
            return [jnp.zeros(shape, dt) for _ in range(cfg.n_layers)]

        return dict(zip(self._KINDS, (layers(ring), layers(ring),
                                      layers(summ), layers(summ))))

    # -- one layer ---------------------------------------------------------------

    def _embed(self, params, tokens):
        import jax.numpy as jnp

        x = self._embed_tokens(params, tokens)
        return x.astype(jnp.float32) if self.cfg.fp32_skip_add else x

    def _norm(self, x, w):
        """RMSNorm in float32 with the weight ``1 + w``
        (``norm_add_unit_offset``; else ``w``), out in the compute dtype."""
        import jax.numpy as jnp
        from jax import lax

        cfg = self.cfg
        x32 = x.astype(jnp.float32)
        w32 = w.astype(jnp.float32)
        if cfg.norm_add_unit_offset:
            w32 = 1.0 + w32
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        return (x32 * lax.rsqrt(var + cfg.norm_eps) * w32).astype(
            jnp.dtype(cfg.dtype))

    def _heads(self, p, a, positions):
        """The layer's projections of the normed input a [B, T, D]: q, k
        (rotated at ``positions``: [T] or [B, T]) and v, [B, H, T, Dh]."""
        cfg = self.cfg
        B, T, _ = a.shape

        def heads(w):
            return self.project(p, w, a).reshape(
                B, T, cfg.n_heads, cfg.head_dim).transpose(0, 2, 1, 3)

        return (_rope(heads("wq"), positions, cfg.rope_theta),
                _rope(heads("wk"), positions, cfg.rope_theta), heads("wv"))

    def _summaries(self, p, k, v):
        """k, v [B, H, T, Dh] of whole chunks -> their pooled rows [B, H, T
        / C, Dh]."""
        from ..ops.eva_attention import chunk_summary

        B, H, T, Dh = k.shape
        C = self.cfg.chunk_size
        shape = (B, H, T // C, C, Dh)
        return chunk_summary(k.reshape(shape), v.reshape(shape), p["mu_k"],
                             p["phi"], self._scale)

    def _mix(self, p, x, o):
        """The attention's output o [B, H, T, Dh] into the residual stream
        x, and the FFN: the layer's output."""
        import jax

        B, _, T, _ = o.shape
        dt = o.dtype
        h = x + (o.transpose(0, 2, 1, 3).reshape(B, T, -1)
                 @ p["wo"].astype(dt)).astype(x.dtype)
        m = self._norm(h, p["ln_post"])
        y = (jax.nn.silu(m @ p["w1"].astype(dt)) * (m @ p["w3"].astype(dt))
             ) @ p["w2"].astype(dt)
        return h + y.astype(x.dtype)

    def _head(self, params, x):
        """x [..., D] -> every head's logits [..., P, V], float32."""
        import jax.numpy as jnp

        cfg = self.cfg
        a = self._norm(x, params["ln_f"])
        logits = self.project(params, "unembed", a,
                              preferred_element_type=jnp.float32)
        return logits.reshape(*x.shape[:-1], cfg.num_pred_heads,
                              cfg.vocab_size)

    def _window(self, p, x, positions, earlier=None):
        """One layer over one window's positions x [B, T, D] (T <= W, whole
        chunks). ``earlier``: ``(sum_k, sum_v [B, H, P, Dh], visible)``, the
        layer's summaries so far, of which the first ``visible`` (traced)
        are the earlier windows'; None in a first window. Returns the
        layer's output and the window's k, v and pooled k~, v~."""
        import jax
        import jax.numpy as jnp

        from ..ops import attention as prefill_attention

        a = self._norm(x, p["ln_in"])
        q, k, v = self._heads(p, a, positions)
        with jax.named_scope("eva_prefill_attention"):
            if earlier is None:
                o = prefill_attention(q, k, v, causal=True,
                                      name="eva_prefill_attention")
            else:
                sum_k, sum_v, visible = earlier
                o = prefill_attention(
                    q, jnp.concatenate([sum_k, k], axis=2),
                    jnp.concatenate([sum_v, v], axis=2),
                    prefix=sum_k.shape[2], prefix_len=visible,
                    name="eva_prefill_attention")
        return (self._mix(p, x, o), k, v, *self._summaries(p, k, v))

    # -- whole-prompt forward --------------------------------------------------------

    def _forward(self, params, tokens, last_index):
        """Prompts tokens [B, T], a row's real tokens its first
        ``last_index + 1`` (None: all T): the residual stream at each row's
        last position [B, D] (None: of every position, [B, T, D]; one
        window only) and the slab: each row's last window's ring rows and
        its summaries, stacked over the layers. T is whole chunks inside one
        window, or whole windows: then ONE loop walks the windows the
        longest row has, a window through all layers before the next."""
        import jax.numpy as jnp
        from jax import lax

        cfg = self.cfg
        W, C, L = cfg.window_size, cfg.chunk_size, cfg.n_layers
        B, T = tokens.shape
        if T % C or (T > W and T % W):
            raise ValueError(
                f"a prefill of {T} positions is neither whole chunks of {C} "
                f"inside a window of {W} nor whole windows "
                "(prefill_lengths)")
        layers = params["layers"]
        if T <= W:
            x = self._embed(params, tokens)
            kinds = [[], [], [], []]
            for p in layers:
                x, *rows = self._window(p, x, jnp.arange(T))
                for kind, r in zip(kinds, rows):
                    kind.append(r)
            if last_index is not None:
                x = x[jnp.arange(B), jnp.asarray(last_index, jnp.int32)]
            return x, dict(zip(self._KINDS, map(jnp.stack, kinds)))

        last = jnp.asarray(last_index, jnp.int32)
        own = last // W                    # each row's last window
        prefix = (T - W) // C              # summaries of all windows but one
        dt = jnp.dtype(cfg.dtype)
        ring = jnp.zeros((L, B, cfg.n_heads, W, cfg.head_dim), dt)
        summ = jnp.zeros((L, B, cfg.n_heads, T // C, cfg.head_dim), dt)

        def keep(mine, new, old):
            """A row's slab takes its OWN last window's rows."""
            if B == 1:
                return new                 # the walk ends there
            return jnp.where(mine.reshape((B,) + (1,) * (new.ndim - 1)),
                             new, old)

        def window(carry):
            w, x_last, ring_k, ring_v, sum_k, sum_v = carry
            x = self._embed(params, lax.dynamic_slice_in_dim(
                tokens, w * W, W, axis=1))
            positions = w * W + jnp.arange(W)
            visible = w * self._per_window
            mine = own == w
            for l, p in enumerate(layers):
                x, k, v, pooled_k, pooled_v = self._window(
                    p, x, positions,
                    (sum_k[l, :, :, :prefix], sum_v[l, :, :, :prefix],
                     visible))
                ring_k = ring_k.at[l].set(keep(mine, k, ring_k[l]))
                ring_v = ring_v.at[l].set(keep(mine, v, ring_v[l]))
                sum_k = lax.dynamic_update_slice(
                    sum_k, pooled_k[None], (l, 0, 0, visible, 0))
                sum_v = lax.dynamic_update_slice(
                    sum_v, pooled_v[None], (l, 0, 0, visible, 0))
            here = x[jnp.arange(B), jnp.clip(last - w * W, 0, W - 1)]
            x_last = jnp.where(mine[:, None], here, x_last)
            return w + 1, x_last, ring_k, ring_v, sum_k, sum_v

        x_last = jnp.zeros((B, cfg.d_model),
                           jnp.float32 if cfg.fp32_skip_add else dt)
        _, x_last, *slab = lax.while_loop(
            lambda carry: carry[0] <= own.max(), window,
            (jnp.int32(0), x_last, ring, ring, summ, summ))
        return x_last, dict(zip(self._KINDS, slab))

    def windows_walked(self, bucket: int, last_index):
        """``prefill_counter_names`` of a prefill in ``bucket``: windows
        walked (a row's own ``ceil(len / W)``) and the bucket's."""
        import jax.numpy as jnp

        W = self.cfg.window_size
        last = jnp.asarray(last_index, jnp.int32)
        return jnp.stack([
            jnp.sum(last // W + 1, dtype=jnp.int32),
            jnp.int32(last.shape[0] * max(1, bucket // W))])

    def apply(self, params, tokens):
        """tokens [B, T] (T inside one window) -> head 0's logits [B, T,
        V] (float32)."""
        x, _ = self._forward(params, tokens, None)
        return self._head(params, x)[..., 0, :]

    @staticmethod
    def _last(prompt, last_index):
        """Each prompt's last position: given, or the bucket's end."""
        import jax.numpy as jnp

        if last_index is None:
            return jnp.full((prompt.shape[0],), prompt.shape[1] - 1, jnp.int32)
        return jnp.asarray(last_index, jnp.int32)

    def _prefill(self, params, prompt, max_seq: int, last_index=None):
        """``prefill`` with every head's logits [B, P, V]."""
        x, slab = self._forward(params, prompt, self._last(prompt, last_index))
        return self._head(params, x), slab

    def prefill(self, params, prompt, max_seq: int, last_index=None):
        """Head 0's logits [B, V] at each prompt's ``last_index`` and the
        cache's rows of these prompts (``max_seq``: the prompt's padded
        length, as the batcher gives it): ``window_k`` / ``window_v`` [L, B,
        H, min(T, W), Dh], the last window's ring, and ``summary_k`` /
        ``summary_v`` [L, B, H, T / C, Dh]."""
        logits, slab = self._prefill(params, prompt, max_seq, last_index)
        return logits[:, 0], slab

    def prefill_counted(self, params, prompt, max_seq: int, last_index=None):
        """``prefill`` and, after the cache's rows, its
        ``prefill_counter_names`` as an int32 vector."""
        last_index = self._last(prompt, last_index)
        logits, slab = self.prefill(params, prompt, max_seq, last_index)
        return logits, slab, self.windows_walked(prompt.shape[1], last_index)

    # -- the decode step ------------------------------------------------------------------

    def decode_step_cache(self, params, cache, tokens, pos, attn_len=None,
                          write_pos=None, lens=None):
        """One token a lane over the cache ``init_cache`` laid out: tokens
        [B, 1] at ``pos`` [B]. Returns ``(head 0's logits [B, V], cache,
        counts)`` with ``counts`` the step's ``step_counter_names``.
        ``lens`` [B]: ``pos + 1`` for a lane whose output anyone reads, 0
        for one that is idle or done; ``write_pos``: ``pos``, or
        ``park_index`` for a lane that must write nothing. A lane with
        ``lens == 0`` or a parked ``write_pos`` writes in neither kind.
        ``attn_len`` bounds nothing here: each lane reads its own rows."""
        logits, cache, counts = self._step(
            params, cache, tokens, pos, attn_len, write_pos, lens)
        return logits[:, 0], cache, counts

    def _step(self, params, cache, tokens, pos, attn_len=None, write_pos=None,
              lens=None):
        """``decode_step_cache`` with every head's logits [B, P, V]."""
        import jax.numpy as jnp

        from ..ops.eva_attention import EVA_BLOCK, eva_decode_attention

        cfg = self.cfg
        W, C, L = cfg.window_size, cfg.chunk_size, cfg.n_layers
        t = pos.astype(jnp.int32)
        n_sum_rows = cache["summary_k"][0].shape[2]
        live = (t + 1 if lens is None else lens.astype(jnp.int32)) > 0
        writes = live
        if write_pos is not None:
            writes = writes & (write_pos.astype(jnp.int32) < n_sum_rows * C)
        at = t % W
        n_ring = jnp.where(live, at + 1, 0)
        n_sum = jnp.where(live, t // W * self._per_window, 0)
        ring_at = jnp.where(writes, at, W)           # W: dropped
        ends_chunk = writes & ((t + 1) % C == 0)
        # the step that completes a chunk pools it into its summary row
        sum_at = jnp.where(ends_chunk, t // C, n_sum_rows)
        mesh = self._serving_mesh

        x = self._embed(params, tokens)  # [B, 1, D]
        new = {name: [] for name in self._KINDS}
        for l, p in enumerate(params["layers"]):
            a = self._norm(x, p["ln_in"])
            q, k, v = self._heads(p, a, t[:, None])
            o, *kinds = eva_decode_attention(
                q[:, :, 0], cache["window_k"][l], cache["window_v"][l],
                cache["summary_k"][l], cache["summary_v"][l], k[:, :, 0],
                v[:, :, 0], ring_at, n_ring, n_sum, p["mu_k"], p["phi"],
                sum_at, scale=self._scale, chunk=C, mesh=mesh)
            for name, kind in zip(self._KINDS, kinds):
                new[name].append(kind)
            x = self._mix(p, x, o[:, :, None])

        def read(n):
            return -(-n // EVA_BLOCK) * EVA_BLOCK

        counts = jnp.stack([
            n_ring.sum(), n_sum.sum(), (read(n_ring) + read(n_sum)).sum(),
            jnp.where(live, t + 1, 0).sum(),
            ends_chunk.sum(dtype=jnp.int32),
            live.sum(dtype=jnp.int32)]).astype(jnp.int32) * jnp.int32(L)
        return self._head(params, x[:, 0]), new, counts
