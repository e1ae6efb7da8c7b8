"""SdarMoeLM: a decoder that generates by diffusion over blocks (JetLM's
SDAR family publishes its expert models as ``model_type: sdar_moe``).

A ``DecoderFamily`` (``models/family.py``), registered there as
``"sdar_moe"``, over an ``SdarMoeConfig``. The layer is the plain
pre-norm one, every layer alike:

    h = x + Attn(RMSNorm(x));  y = h + MoE(RMSNorm(h))

* ``Attn``: GQA, q and k RMS-normed over each head (one weight vector
  each) before half-split rotary, no bias, no gate. **The mask is causal
  over blocks of ``block_length`` positions and open inside one**: key j
  is visible to query i iff ``j < (i // B + 1) B``, blocks aligned on
  absolute positions, in a prompt as in what is generated.
* ``MoE``: softmax over all the router's logits, the ``experts_per_tok``
  largest, weights the picked scores over their sum; no shared expert, no
  selection bias (``ops/experts.py``: ``routed_ffn(score="softmax")``).

**Generation** is what the interface calls generation by blocks
(``block_tokens() == block_length``). A prompt's whole blocks are
prefilled under the mask above (``prefill``; the rows it returns past them
are nobody's, a pass overwrites them before any read admits them). Then
block by block: the block starts as the prompt's tail, if any, and
``[MASK]`` elsewhere; a *denoising pass* (``decode_block_cache``) is one
forward over the block's positions against the cache and the block
itself, logits at each position itself (no shift); ``block_unmask`` gives
each masked position its ``x0`` (the argmax, or a seeded draw at the
lane's temperature) and its confidence ``softmax(logits)[x0]`` in float32,
and fills in the most confident: ``low_confidence_static`` the pass's
share of ``denoising_steps`` passes (``B // T``, one more in the first ``B
mod T``; all that are left if fewer), ``low_confidence_dynamic`` every one
over ``confidence_threshold`` and at least that share. Ties go to the
lower position. ``[MASK]`` is never emitted: its logit is -inf before the
argmax and the confidence. When a block has no masked position, one more
pass over it, the *commit*, leaves its K and V rows in the cache (the rows
a denoising pass wrote saw ``[MASK]`` embeddings, in this layer and,
through the block's open attention, in every later one). Masked positions
are the lane's mask BITS, never an id compared: a prompt may hold the
mask's id.

A pass's attention is ``ops.decode_attention.block_decode_attention``: on
a TPU the ragged kernel's second entry, which lands the block's rows and
has every query of the block read the lane's whole length.

Layers are a list (``params["layers"][l]``), the cache the interface's
default: one [S, KV, T, Dh] pair a layer. Serving only; what it refuses is
``serving_refuses``.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from .family import DecoderFamily
from .llm import LLMConfig, _rms_norm, _rope

STATIC, DYNAMIC = "low_confidence_static", "low_confidence_dynamic"

_BLOCKS = (
    "a lane's position is a block's first, and its cache rows past that "
    "are a half-denoised block's: the batcher's {0} knows one token a "
    "position and a cache that is final up to the lane's position")


@dataclasses.dataclass
class SdarMoeConfig(LLMConfig):
    """The shared fields (the routed experts') and this family's own: how
    it generates."""
    block: str = "sdar_moe"
    block_length: int = 4          # positions of one block (a power of two)
    denoising_steps: int = 4       # passes that fill a whole block in
    remasking: str = STATIC        # | "low_confidence_dynamic"
    confidence_threshold: float = 0.9
    mask_token_id: int = 151669


class SdarMoeLM(DecoderFamily):
    config_class = SdarMoeConfig
    # q_norm and k_norm in the seeded draw (``init_params``)
    QK_NORM_DRAW = 1.75
    step_counter_names = (
        # per pass: live lanes (each ran one forward over its block), those
        # of them whose block had nothing masked (commits: they yield
        # nothing), positions ``block_unmask`` filled in, cache rows the
        # block kernel read (a live lane's length rounded up to the
        # kernel's block, ``ops.decode_attention.walk_block``, over the
        # layers; K's, and as many of V) and the rows those lengths hold
        "block_forwards", "block_commit_forwards", "block_tokens_unmasked",
        "block_rows_read", "block_rows_live",
        # summed over the layers, as the other expert families name them:
        # distinct experts some live row picked, (row, pick) pairs routed,
        # expert layers run
        "moe_experts_touched", "moe_rows_routed", "moe_layer_steps",
    )
    prefill_counter_names = (
        "moe_prefill_pairs_moved", "moe_prefill_pairs_routed",
        "moe_prefill_tile_rows",
    )
    serving_refuses = {
        "speculation": "a pass already fills in several positions of a "
                       "block; a draft's proposals have no place in it",
        "mesh": "the block kernel and the expert kernels are not "
                "partitioned, and param_sharding knows no expert axis",
        "fused": "the stop-aware fused burst counts one token a lane and "
                 "step; a block pass commits four or none",
        "chunked_prefill": _BLOCKS.format("chunked prefill"),
        "prefix_cache": _BLOCKS.format("prefix splice (which would have "
                                       "to end on a block's edge)"),
        "kv_tier": _BLOCKS.format("tier spill and copy-back"),
        "preemption": _BLOCKS.format("checkpoint replay"),
        "migration": _BLOCKS.format("shipped slab"),
    }

    def __init__(self, **config):
        super().__init__(**config)
        cfg = self.cfg
        B = cfg.block_length
        if B < 1 or B & (B - 1) or 8 % B or cfg.max_seq % B:
            raise ValueError(
                f"block_length {B}: a power of two up to 8 (a block lies in "
                f"one group of the cache's rows) that divides max_seq "
                f"{cfg.max_seq}")
        if not 1 <= cfg.denoising_steps <= B:
            raise ValueError(
                f"denoising_steps {cfg.denoising_steps}: 1 to {B} passes "
                "fill a block in")
        if cfg.remasking not in (STATIC, DYNAMIC):
            raise ValueError(f"remasking {cfg.remasking!r}: {STATIC!r} or "
                             f"{DYNAMIC!r}")
        if not 0 <= cfg.mask_token_id < cfg.vocab_size:
            raise ValueError("mask_token_id lies inside the vocabulary")
        if not (0 < cfg.experts_per_tok <= cfg.n_routed_experts
                and cfg.expert_width > 0):
            raise ValueError("every layer is an expert layer: "
                             "n_routed_experts, experts_per_tok, expert_width")
        # positions pass p of a block fills in (static; the dynamic rule's
        # least): B // T, one more in the first B mod T passes
        T = cfg.denoising_steps
        self._transfer = tuple(B // T + (p < B % T) for p in range(T))

    def block_tokens(self) -> int:
        return self.cfg.block_length

    # -- sizes -----------------------------------------------------------------

    def _layer_params(self, experts: float) -> float:
        cfg = self.cfg
        D, Dh = cfg.d_model, cfg.head_dim
        h, kv = cfg.n_heads * Dh, cfg.n_kv_heads * Dh
        return (2 * D + 2 * Dh + 2 * D * h + 2 * D * kv
                + D * cfg.n_routed_experts + experts * 3 * D * cfg.expert_width)

    def n_params(self) -> int:
        cfg = self.cfg
        return int(cfg.n_layers * self._layer_params(cfg.n_routed_experts)
                   + 2 * cfg.vocab_size * cfg.d_model + cfg.d_model)

    def step_param_bytes(self, rows: int, param_bytes: int = 2) -> float:
        """Bytes of weights a pass over ``rows`` live lanes reads: of each
        layer's experts what ``rows x block_length x k`` uniform picks are
        expected to touch; not the embedding table."""
        cfg = self.cfg
        touched = cfg.n_routed_experts * (1.0 - (
            1.0 - cfg.experts_per_tok / cfg.n_routed_experts)
            ** max(0, rows * cfg.block_length))
        return (cfg.n_layers * self._layer_params(touched)
                + cfg.vocab_size * cfg.d_model + cfg.d_model) * param_bytes

    def flops_per_token(self, context_len: int) -> float:
        cfg = self.cfg
        D, h = cfg.d_model, cfg.n_heads * cfg.head_dim
        kv = cfg.n_kv_heads * cfg.head_dim
        layer = (2.0 * (2 * D * h + 2 * D * kv) + 4.0 * context_len * h
                 + 2.0 * D * cfg.n_routed_experts
                 + 6.0 * D * cfg.expert_width * cfg.experts_per_tok)
        return cfg.n_layers * layer + 2.0 * D * cfg.vocab_size

    def dispatch_read_bytes(self, kind: str, *, rows: int = 1,
                            live: int = None, k: int = 1, bucket: int = 0,
                            tokens: int = 0, param_bytes: float = None,
                            kv_row_bytes: float = None) -> float:
        """As the llama block's, but a pass reads by live lane: the experts
        that many lanes' blocks are expected to touch and the lanes' own
        buckets."""
        if kind in ("decode_burst", "fused_burst", "spec_burst"):
            live = rows if live is None else live
            if kv_row_bytes is None:
                kv_row_bytes = float(self.kv_bytes_per_token())
            return k * (self.step_param_bytes(live)
                        + live * bucket * kv_row_bytes)
        return super().dispatch_read_bytes(
            kind, rows=rows, k=k, bucket=bucket, tokens=tokens,
            param_bytes=param_bytes, kv_row_bytes=kv_row_bytes)

    def burst_reads_ragged(self, cache, mesh=None) -> bool:
        import jax.numpy as jnp

        from ..ops.decode_attention import block_reads_ragged

        layer0 = cache["k"][0]
        return block_reads_ragged(
            next(iter(layer0.devices())).platform,
            (layer0.shape[0], self.cfg.n_heads, self.cfg.block_length,
             layer0.shape[3]),
            layer0.shape,
            (jnp.dtype(self.cfg.dtype), layer0.dtype, cache["v"][0].dtype),
            mesh,
        )

    # -- params ------------------------------------------------------------------

    def init_params(self, seed: int = 0):
        """Seeded float32 draw. Matrices N(0, 1 / fan_in), the embedding
        N(0, 1), the layer norms and the final norm ones; ``wo`` and the
        experts' down projections scaled by ``residual_scale``, as in the
        llama block. ``q_norm`` and ``k_norm`` are ``QK_NORM_DRAW``: under
        random projections a head's scores have unit deviation and a
        softmax over a few thousand keys is all but flat, so that a wrong
        mask, rotary or an uncommitted block could not be told from
        rounding; at 1.75 x 1.75 the deviation is 3 and a query attends to
        a handful of keys, as a trained head does."""
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        D, Dh, V = cfg.d_model, cfg.head_dim, cfg.vocab_size
        h, kv = cfg.n_heads * Dh, cfg.n_kv_heads * Dh
        E, Fe = cfg.n_routed_experts, cfg.expert_width
        keys = iter(jax.random.split(jax.random.PRNGKey(seed),
                                     8 * cfg.n_layers + 2))
        res = float(cfg.residual_scale)

        def init(shape, fan_in, scale=1.0):
            return jax.random.normal(next(keys), shape, jnp.float32) * (
                scale / np.sqrt(fan_in))

        ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
        layers = [{
            "ln_in": ones(D), "ln_post": ones(D),
            "q_norm": ones(Dh) * self.QK_NORM_DRAW,
            "k_norm": ones(Dh) * self.QK_NORM_DRAW,
            "wq": init((D, h), D), "wk": init((D, kv), D),
            "wv": init((D, kv), D), "wo": init((h, D), h, res),
            "router": init((D, E), D),
            "we1": init((E, D, Fe), D), "we3": init((E, D, Fe), D),
            "we2": init((E, Fe, D), Fe, res),
        } for _ in range(cfg.n_layers)]
        return {
            "embed": jax.random.normal(next(keys), (V, D), jnp.float32),
            "layers": layers,
            "ln_f": ones(D),
            "unembed": init((D, V), D),
        }

    # -- one layer -----------------------------------------------------------------

    def _heads(self, p, x, positions):
        """The layer's input norm and projections: q [B, H, T, Dh], k and v
        [B, KV, T, Dh], q and k normed per head and then rotated."""
        cfg = self.cfg
        dt = x.dtype
        B, T, _ = x.shape
        Dh = cfg.head_dim
        a = _rms_norm(x, p["ln_in"].astype(dt), cfg.norm_eps)
        q = (a @ p["wq"].astype(dt)).reshape(B, T, cfg.n_heads, Dh)
        k = (a @ p["wk"].astype(dt)).reshape(B, T, cfg.n_kv_heads, Dh)
        v = (a @ p["wv"].astype(dt)).reshape(B, T, cfg.n_kv_heads, Dh)
        q = _rms_norm(q, p["q_norm"].astype(dt), cfg.norm_eps)
        k = _rms_norm(k, p["k_norm"].astype(dt), cfg.norm_eps)
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        return (_rope(q, positions, cfg.rope_theta),
                _rope(k, positions, cfg.rope_theta), v)

    def _close(self, p, x, o, live=None, real=None):
        """From the attention's output o [B, H, T, Dh] to the layer's:
        ``wo`` and the residual, then the routed experts on the normed
        stream. ``live`` [B T] bool (a pass: the rows of live lanes) sends
        them through the touched-only read; ``real`` [B, T] bool (a
        prefill's): the rows that are some sequence's tokens. Returns
        ``(x, picks [B, T, k], counts)``, the counts
        ``ops.experts.routed_ffn``'s."""
        from ..ops.experts import routed_ffn

        cfg = self.cfg
        dt = x.dtype
        B, T, D = x.shape
        x = x + o.transpose(0, 2, 1, 3).reshape(B, T, -1) @ p["wo"].astype(dt)
        m = _rms_norm(x, p["ln_post"].astype(dt), cfg.norm_eps)
        y, picks, counts = routed_ffn(
            m.reshape(B * T, D), p["router"], None, cfg.experts_per_tok, 1.0,
            "softmax", tuple(p[n].astype(dt) for n in ("we1", "we3", "we2")),
            live=live, real=real, held=None, n_routed=cfg.n_routed_experts,
            mesh=None, redirect_pads=True)
        return x + y.astype(dt).reshape(B, T, D), picks.reshape(B, T, -1), counts

    def _head(self, params, x, last_index=None, every=False):
        import jax.numpy as jnp

        dt = x.dtype
        x = _rms_norm(x, params["ln_f"].astype(dt), self.cfg.norm_eps)
        if not every:
            x = self._last_rows(x, last_index)
        return (x @ params["unembed"].astype(dt)).astype(jnp.float32)

    # -- whole-prompt forward ----------------------------------------------------------

    def _forward(self, params, tokens, pad_to, last_index=None):
        """One pass over whole canvases tokens [B, T] under the block mask:
        the residual stream, where ``pad_to`` is given each layer's K and V
        padded to it, the layers' picks and the ``prefill_counter_names``."""
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
        for p in params["layers"]:
            q, k, v = self._heads(p, x, positions)
            o = prefill_attention(
                q, jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1),
                causal=True, block=cfg.block_length)
            x, picks, grouped = self._close(p, x, o, real=real)
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
        """tokens [B, T] int32 (a canvas: tokens and ``[MASK]``s) -> logits
        [B, T, V] (float32), each at its own position."""
        return self._head(params, self._forward(params, tokens, None)[0],
                          every=True)

    def _prefill(self, params, prompt, max_seq: int, last_index=None):
        """``prefill``, the layers' picks [B, T, k] (for a comparison with a
        reference: from this very program) and the prefill counters."""
        import jax.numpy as jnp

        x, ks, vs, picked, counts = self._forward(
            params, prompt, max_seq, last_index)
        return (self._head(params, x, last_index),
                {"k": jnp.stack(ks), "v": jnp.stack(vs)}, picked, counts)

    def prefill(self, params, prompt, max_seq: int, last_index=None):
        """The interface's, under the block mask. The rows of a prompt's
        whole blocks are the cache's; those of its tail (and the logits,
        which saw the bucket's padding through the tail block's open
        attention) are nobody's: the first pass over that block overwrites
        them."""
        return self._prefill(params, prompt, max_seq, last_index)[:2]

    def prefill_counted(self, params, prompt, max_seq: int, last_index=None):
        logits, rows, _, counts = self._prefill(params, prompt, max_seq, last_index)
        return logits, rows, counts

    # -- a pass over a block ----------------------------------------------------------------

    def decode_block_cache(self, params, cache, tokens, base, masked=None,
                           attn_len=None, lens=None):
        return self._pass(params, cache, tokens, base, masked, attn_len, lens)[:3]

    def _pass(self, params, cache, tokens, base, masked=None, attn_len=None,
              lens=None):
        """``decode_block_cache`` and the layers' picks [B, W, k]."""
        import jax
        import jax.numpy as jnp

        from ..ops.decode_attention import block_decode_attention, walk_block

        W = tokens.shape[1]
        base = base.astype(jnp.int32)
        lens = base + W if lens is None else lens.astype(jnp.int32)
        live = lens > 0
        positions = base[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
        x = self._embed_tokens(params, tokens)            # [B, W, D]
        rows_live = jnp.repeat(live, W)
        nks, nvs, picked = [], [], []
        touched = routed = jnp.int32(0)
        for l, p in enumerate(params["layers"]):
            q, k, v = self._heads(p, x, positions)
            o, nk, nv = block_decode_attention(
                q, cache["k"][l], cache["v"][l], k, v, base, lens,
                attn_len=attn_len)
            nks.append(nk)
            nvs.append(nv)
            x, picks, counts = self._close(p, x, o, live=rows_live)
            picked.append(picks)
            touched, routed = touched + counts[0], routed + counts[1]
        with jax.named_scope("block_unmask"):
            logits = self._head(params, x, every=True)
        n_layers = len(params["layers"])
        k0 = cache["k"][0]                                # [B, KV, T, Dh]
        block = walk_block(k0.shape[1], k0.shape[3], k0.dtype, k0.shape[2])
        commits = live if masked is None else live & ~masked.any(axis=-1)
        counts = self._counts(
            block_forwards=live.sum(dtype=jnp.int32),
            block_commit_forwards=commits.sum(dtype=jnp.int32),
            block_rows_read=n_layers * jnp.sum(
                (lens + block - 1) // block * block, dtype=jnp.int32),
            block_rows_live=n_layers * jnp.sum(lens, dtype=jnp.int32),
            moe_experts_touched=touched, moe_rows_routed=routed,
            moe_layer_steps=jnp.int32(n_layers))
        return logits, {"k": nks, "v": nvs}, counts, picked

    def _counts(self, **named):
        """``step_counter_names`` as an int32 vector, zeros but for ``named``."""
        import jax.numpy as jnp

        return jnp.stack([jnp.asarray(named.get(n, 0), jnp.int32)
                          for n in self.step_counter_names])

    def block_unmask(self, logits, tokens, masked, n_pass, alive, temps, keys,
                     any_stoch: bool = True):
        """What a pass fills in. logits [S, W, V] float32 at each position
        itself; tokens [S, W], masked [S, W] bool and n_pass [S] the lanes'
        block registers; alive [S] bool; temps [S], keys [S] the lanes'
        sampling state (``any_stoch`` False, static: every lane is greedy
        and no draw is compiled). Returns ``(tokens, masked, keys,
        counts)``: a lane with nothing masked (its pass was the commit) and
        a lane that is not alive come back as they were."""
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        S, W, _ = logits.shape
        with jax.named_scope("block_unmask"):
            # [MASK] is never emitted (a departure from the published loop,
            # which leaves that to the trained weights)
            logits = jnp.where(
                jnp.arange(logits.shape[-1]) == cfg.mask_token_id, -jnp.inf,
                logits)
            x0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            if any_stoch:
                split = jax.vmap(jax.random.split)(keys)
                keys, subs = split[:, 0], split[:, 1]
                drawn = jax.vmap(lambda k, lg, t: jax.random.categorical(
                    k, lg / jnp.maximum(t, 1e-6), axis=-1))(
                        subs, logits, temps).astype(jnp.int32)
                x0 = jnp.where((temps > 0)[:, None], drawn, x0)
            # softmax(logits)[x0] in float32, without a [S, W, V] softmax
            top = jnp.take_along_axis(logits, x0[..., None], axis=-1)[..., 0]
            conf = jnp.exp(top - jax.nn.logsumexp(logits, axis=-1))
            conf = jnp.where(masked, conf, -1.0)
            # a position's rank among its lane's: ties to the lower position
            i = jnp.arange(W)
            ahead = (conf[:, None, :] > conf[:, :, None]) | (
                (conf[:, None, :] == conf[:, :, None])
                & (i[None, None, :] < i[None, :, None]))
            rank = ahead.sum(axis=-1)
            share = jnp.asarray(self._transfer, jnp.int32)[
                jnp.minimum(n_pass, len(self._transfer) - 1)]
            take = rank < share[:, None]
            if cfg.remasking == DYNAMIC:
                take = take | (conf > cfg.confidence_threshold)
            take = take & masked & alive[:, None]
            return (jnp.where(take, x0, tokens), masked & ~take, keys,
                    self._counts(
                        block_tokens_unmasked=take.sum(dtype=jnp.int32)))
