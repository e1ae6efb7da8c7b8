"""DecoderLM: llama-style decoder-only transformer (flagship model family).

The llama block of the decoder families (``models/family.py``: the
interface every family answers, and the registry ``DecoderLM(block=...)``
looks a block up in), and the one with every optional path: training, the
stacked scan, the serving mesh, the chunk and prefix prefills. Here too:
the configuration the families' own extend (``LLMConfig``) and the two
primitives they share (``_rms_norm``, ``_rope``).

Serves BASELINE.json's "Llama-2-7B generate() with engine-side dynamic
batching" config class. Architecture: RMSNorm, rotary embeddings, GQA,
SwiGLU FFN (optionally Switch-MoE every k-th layer), tied-free unembed.
Pure param-pytree + functions; layers stacked on a leading axis and
executed with ``lax.scan`` so XLA compiles one block.

Parallelism (models the scaling-book recipe, fully manual inside
shard_map — see ``make_train_step``):
  tp: heads/FFN columns sharded over ``model``; row-parallel mats psum
  sp: sequence chunks over ``seq`` with ring attention (parallel/ring.py)
  pp: layer stages over ``stage`` via GPipe ppermute (parallel/pipeline.py)
  dp: batch over ``data``; gradient psum over (data, seq)
  ep: experts all_to_all over the combined (data, seq) ranks (parallel/moe.py)

The reference has no counterpart for any of this (SURVEY.md §2: its only
parallelism is pod replicas / HTTP fan-out); this is the TPU-native
capability that replaces it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import numpy as np

from .family import DecoderFamily, UnsupportedByModel, family_class  # noqa: F401


@dataclasses.dataclass
class LLMConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 8
    n_heads: int = 8
    n_kv_heads: int = 8
    d_ff: int = 1408
    max_seq: int = 2048
    rope_theta: float = 10000.0
    # MoE: 0 experts = dense SwiGLU everywhere
    n_experts: int = 0
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    # scale on the residual-writing projections (wo, w2) at init. < 1
    # makes each block a small perturbation of the residual stream, so
    # early-exit drafts (speculative decoding's draft_layers) agree with
    # the full depth — the property trained nets exhibit (LayerSkip-style
    # depth redundancy) that a plain random init lacks. Bench/synthetic
    # checkpoints only; converted checkpoints never touch it.
    residual_scale: float = 1.0
    # width of one head; 0 = d_model // n_heads (the llama families).
    # Stated where heads x head_dim is not the hidden size
    head_dim: int = 0
    # which family's file serves this configuration: a key of
    # ``family.FAMILIES``. "llama" is the block of this file, every layer
    # alike; ``DecoderLM(block=...)`` builds the registered class of any
    # other, whose own dataclass extends this one with the fields only it
    # reads. A family's kinds of layer are resolved when the model is
    # built, never in a traced function.
    block: str = "llama"
    # -- routed experts and mixed layers: what two or three families read --
    # per layer, a kind the family names ("sliding_attention" |
    # "full_attention" | "linear_attention"); None = all alike
    layer_types: Optional[Tuple[str, ...]] = None
    n_dense_layers: int = 0       # leading layers with a dense FFN (d_ff)
    n_routed_experts: int = 0     # drop-free top-k experts a later layer
    experts_per_tok: int = 0
    expert_width: int = 0         # FFN width of one expert
    n_shared_experts: int = 0     # experts every token takes, beside them
    route_scale: float = 1.0
    # (lo, n): this chip holds experts lo .. lo + n - 1 of the
    # n_routed_experts the router ranges over; None: all of them
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        if not self.head_dim:
            self.head_dim = self.d_model // self.n_heads
        if self.layer_types is not None:
            self.layer_types = tuple(self.layer_types)
        if self.experts_held is not None:
            self.experts_held = tuple(int(n) for n in self.experts_held)


def _rms_norm(x, w, eps=1e-5):
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jnp.reciprocal(jnp.sqrt(var + eps))).astype(x.dtype) * w


def _rope(x, positions, theta: float):
    """x: [B, H, T, Dh]; positions: [B, T] or [T]."""
    import jax.numpy as jnp

    dh = x.shape[-1]
    half = dh // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    if positions.ndim == 1:
        angles = positions[:, None].astype(jnp.float32) * freqs[None, :]  # [T, half]
        angles = angles[None, None]  # [1,1,T,half]
    else:
        angles = positions[:, None, :, None].astype(jnp.float32) * freqs[None, None, None, :]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    ).astype(x.dtype)


class DecoderLM(DecoderFamily):
    config_class = LLMConfig
    # set_serving_mesh's other knob: the cache length sharded over ``seq``
    _serving_shard_seq = False

    def __new__(cls, **config):
        # ``DecoderLM(block=...)`` builds the block's registered family
        # (``family.FAMILIES``). Another class's instance comes back fully
        # built: Python runs ``__init__`` on what ``__new__`` returns only
        # where that is an instance of ``cls``
        if cls is DecoderLM:
            family = family_class(config.get("block", "llama"))
            if family is not DecoderLM:
                return family(**config)
        return super().__new__(cls)

    def flops_per_token(self, context_len: int) -> float:
        """Matmul FLOPs to process ONE token attending over ``context_len``
        keys: q/kv/out projections + scores/attn*V + gated FFN (3 matmuls;
        only the routed expert is active under MoE) + lm head."""
        cfg = self.cfg
        D, F = cfg.d_model, cfg.d_ff
        kv_dim = cfg.n_kv_heads * cfg.head_dim
        per_layer = (
            2.0 * D * D                  # q proj
            + 2.0 * 2.0 * D * kv_dim     # k,v proj
            + 2.0 * D * D                # out proj
            + 4.0 * context_len * D      # scores + attn*V
            + 6.0 * D * F                # SwiGLU: gate, up, down
        )
        return cfg.n_layers * per_layer + 2.0 * D * cfg.vocab_size

    def n_params(self) -> int:
        """Exact parameter count of ``init_params``' pytree (closed form)."""
        cfg = self.cfg
        D, F, L, V = cfg.d_model, cfg.d_ff, cfg.n_layers, cfg.vocab_size
        kv = cfg.n_kv_heads * cfg.head_dim
        h = cfg.n_heads * cfg.head_dim
        per_layer = 2 * D + D * h + 2 * D * kv + h * D  # norms + q,k,v,o
        if cfg.n_experts > 0:
            per_layer += D * cfg.n_experts + cfg.n_experts * 2 * D * F
        else:
            per_layer += 3 * D * F
        return L * per_layer + 2 * V * D + D  # blocks + embed/unembed + ln_f

    def decode_bytes_per_token(self, context_len: float, batch: int = 1,
                               param_bytes: int = 2) -> float:
        """HBM bytes touched per DECODED TOKEN at the given batch size:
        params are read once per fused step (amortised over the batch),
        plus each lane's KV-cache read for its context. The MBU lens —
        decode is bandwidth-bound, so tok/s x this / measured HBM BW is
        the honest utilisation number (MFU is uninformative here)."""
        cfg = self.cfg
        kv_bytes_per_tok_layer = 2 * cfg.n_kv_heads * cfg.head_dim * 2  # k+v, bf16
        cache_read = cfg.n_layers * kv_bytes_per_tok_layer * context_len
        return self.n_params() * param_bytes / max(1, batch) + cache_read

    # ------------------------------------------------------------------
    # params
    # ------------------------------------------------------------------

    def init_params(self, seed: int = 0):
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        k = jax.random.PRNGKey(seed)
        keys = jax.random.split(k, 16)
        D, H, KV, Dh, F, L, V = (
            cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.d_ff, cfg.n_layers, cfg.vocab_size,
        )

        def init(key, shape, scale):
            return (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(jnp.float32)

        s = 1.0 / np.sqrt(D)
        rs = float(cfg.residual_scale)
        blocks: Dict[str, Any] = {
            "ln1": jnp.ones((L, D), jnp.float32),
            "wq": init(keys[1], (L, D, H * Dh), s),
            "wk": init(keys[2], (L, D, KV * Dh), s),
            "wv": init(keys[3], (L, D, KV * Dh), s),
            "wo": init(keys[4], (L, H * Dh, D), rs / np.sqrt(H * Dh)),
            "ln2": jnp.ones((L, D), jnp.float32),
        }
        if cfg.n_experts > 0:
            E = cfg.n_experts
            blocks["router"] = init(keys[5], (L, D, E), s)
            blocks["w1e"] = init(keys[6], (L, E, D, F), s)
            blocks["w2e"] = init(keys[7], (L, E, F, D), rs / np.sqrt(F))
        else:
            blocks["w1"] = init(keys[5], (L, D, F), s)
            blocks["w3"] = init(keys[6], (L, D, F), s)
            blocks["w2"] = init(keys[7], (L, F, D), rs / np.sqrt(F))
        return {
            "embed": init(keys[0], (V, D), 1.0),
            "blocks": blocks,
            "ln_f": jnp.ones((D,), jnp.float32),
            "unembed": init(keys[8], (D, V), s),
        }

    # ------------------------------------------------------------------
    # forward building blocks (axis-parametrised: None => single chip)
    # ------------------------------------------------------------------

    def _attention(
        self, p, x, positions, *, tp_axis=None, sp_axis=None, kv_cache=None,
        attn_len=None, lens=None,
    ):
        import jax.numpy as jnp
        from jax import lax

        from ..parallel.ring import full_attention, ring_attention

        cfg = self.cfg
        dt = x.dtype
        B, T, D = x.shape
        h = _rms_norm(x, p["ln1"].astype(dt), cfg.norm_eps)
        q = self.project(p, "wq", h)  # [B,T,Hl*Dh] (Hl = local heads under tp)
        k = self.project(p, "wk", h)
        v = self.project(p, "wv", h)
        Hl = q.shape[-1] // cfg.head_dim
        KVl = k.shape[-1] // cfg.head_dim
        q = q.reshape(B, T, Hl, cfg.head_dim).transpose(0, 2, 1, 3)
        k = k.reshape(B, T, KVl, cfg.head_dim).transpose(0, 2, 1, 3)
        v = v.reshape(B, T, KVl, cfg.head_dim).transpose(0, 2, 1, 3)
        # decode passes per-batch positions [B]; lift to [B, T=1] so _rope
        # takes the batched branch (1-D means "shared [T] positions")
        rope_pos = positions[:, None] if (kv_cache is not None and positions.ndim == 1) else positions
        q = _rope(q, rope_pos, cfg.rope_theta)
        k = _rope(k, rope_pos, cfg.rope_theta)
        new_cache = None
        if kv_cache is not None:
            # decode: append this step's k/v at position `cache_pos` —
            # scalar (uniform batch) or [B] vector (ragged continuous
            # batch: every row writes at its own position)
            ck, cv, cache_pos = kv_cache
            if lens is not None:
                # ragged single-position decode: each lane's row lands at
                # its ``cache_pos[b]``, then the lane reads its own
                # ``lens[b]`` positions of the unsliced cache (both inside
                # the kernel on a TPU; the scatter and the two dots over
                # the bucket elsewhere)
                from ..ops import decode_attention

                o, ck, cv = decode_attention(
                    q, ck, cv, k, v, cache_pos, positions, lens,
                    attn_len=attn_len,
                    mesh=self._serving_mesh,
                )
            else:
                if getattr(cache_pos, "ndim", 0):
                    ck = self._cache_write(ck, k, cache_pos[:, None])
                    cv = self._cache_write(cv, v, cache_pos[:, None])
                else:
                    ck = lax.dynamic_update_slice(ck, k, (0, 0, cache_pos, 0))
                    cv = lax.dynamic_update_slice(cv, v, (0, 0, cache_pos, 0))
                # decode attention over the (sliced) cache — see
                # _cache_attention for why the GQA repeat must not happen here
                k, v = self._cache_read(ck, cv, attn_len)
                o = self._cache_attention(q, k, v, positions, dt)
            new_cache = (ck, cv)
        else:
            if KVl < Hl:  # GQA: repeat kv groups (compute-bound prefill
                # path only; the decode path reads grouped to keep the
                # cache traffic at one copy)
                rep = Hl // KVl
                k = jnp.repeat(k, rep, axis=1)
                v = jnp.repeat(v, rep, axis=1)
            if sp_axis is not None:
                o = ring_attention(q, k, v, sp_axis, causal=True)
            else:
                o = full_attention(q, k, v, causal=True)
        o = o.transpose(0, 2, 1, 3).reshape(B, T, Hl * cfg.head_dim)
        o = o @ p["wo"].astype(dt)  # row-parallel under tp
        if tp_axis is not None:
            o = lax.psum(o, tp_axis)
        return o, new_cache

    def _qkv(self, p, x, positions):
        """A layer's input norm, its three projections and the rotary
        embedding at ``positions``: q [B, Hl, T, Dh], k and v [B, KVl, T,
        Dh] (the local heads under tp)."""
        cfg = self.cfg
        dt = x.dtype
        B, T, _ = x.shape
        h = _rms_norm(x, p["ln1"].astype(dt), cfg.norm_eps)
        q = self.project(p, "wq", h)
        k = self.project(p, "wk", h)
        v = self.project(p, "wv", h)
        q, k, v = (a.reshape(B, T, -1, cfg.head_dim).transpose(0, 2, 1, 3)
                   for a in (q, k, v))
        return (_rope(q, positions, cfg.rope_theta),
                _rope(k, positions, cfg.rope_theta), v)

    @staticmethod
    def _cache_attention(q, kc, vc, bound, dt):
        """Attention over the (sliced) KV cache with a ``key_pos <= bound``
        mask (``bound`` [B] or [B, T]), the GQA group read grouped: the
        two dots of ``ops.decode_attention.cache_attention``. The chunked
        and speculative windows, prefix prefill and the uniform-batch
        ``generate`` read the cache through this; the ragged
        single-position step goes through ``ops.decode_attention()``,
        which on a TPU reads only each lane's live positions."""
        from ..ops.decode_attention import cache_attention

        return cache_attention(q, kc, vc, bound, dt)

    def _ffn(self, p, x, *, tp_axis=None, ep_axes=None):
        import jax
        import jax.numpy as jnp
        from jax import lax

        cfg = self.cfg
        dt = x.dtype
        h = _rms_norm(x, p["ln2"].astype(dt), cfg.norm_eps)
        if cfg.n_experts > 0:
            from ..parallel.moe import moe_ffn

            B, T, D = h.shape
            out, aux = moe_ffn(
                h.reshape(B * T, D),
                p["router"].astype(dt),
                p["w1e"].astype(dt),
                p["w2e"].astype(dt),
                ep_axes,
                cfg.capacity_factor,
            )
            return out.reshape(B, T, D), aux
        a = h @ p["w1"].astype(dt)
        g = h @ p["w3"].astype(dt)
        out = (jax.nn.silu(a) * g) @ p["w2"].astype(dt)
        if tp_axis is not None:
            out = lax.psum(out, tp_axis)
        return out, jnp.float32(0.0)

    def _block(self, p, x, positions, *, tp_axis=None, sp_axis=None, ep_axes=None):
        attn_out, _ = self._attention(p, x, positions, tp_axis=tp_axis, sp_axis=sp_axis)
        x = x + attn_out
        ffn_out, aux = self._ffn(p, x, tp_axis=tp_axis, ep_axes=ep_axes)
        return x + ffn_out, aux

    def backbone(self, blocks, x, positions, *, tp_axis=None, sp_axis=None, ep_axes=None):
        """Scan all (local) layers. blocks: leading-axis-stacked params."""
        from jax import lax

        def body(carry, layer_p):
            x, aux = carry
            x, aux_l = self._block(
                layer_p, x, positions, tp_axis=tp_axis, sp_axis=sp_axis, ep_axes=ep_axes
            )
            return (x, aux + aux_l), None

        import jax.numpy as jnp

        from ..parallel.vma import pvary, tree_vma, vma_of

        # The scan carry must vary over every axis the block OUTPUT varies
        # over: the params' varying axes (e.g. 'stage' for stage-sharded
        # blocks) minus the tp axis, whose variance both sublayers remove
        # with their closing psum.
        need = tree_vma(blocks) - vma_of(x) - {tp_axis}
        x = pvary(x, tuple(need))
        aux0 = pvary(jnp.float32(0.0), tuple(vma_of(x)))
        (x, aux), _ = lax.scan(body, (x, aux0), blocks)
        return x, aux

    # ------------------------------------------------------------------
    # single-chip serving forward
    # ------------------------------------------------------------------

    def apply(self, params, tokens):
        """tokens [B, T] int32 -> logits [B, T, V] (float32)."""
        import jax.numpy as jnp

        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        params = self._tp_gather(params)  # exact serving-mesh entry gather
        tokens = tokens.astype(jnp.int32)
        x = params["embed"][tokens].astype(dt)
        positions = jnp.arange(tokens.shape[1])
        x, _ = self.backbone(params["blocks"], x, positions)
        x = _rms_norm(x, params["ln_f"].astype(dt), cfg.norm_eps)
        return (x @ params["unembed"].astype(dt)).astype(jnp.float32)

    # ------------------------------------------------------------------
    # KV-cache generate (single chip; engine-side continuous batching sits
    # in front of this via graph/batching.py). The cache's layout, its
    # write and narrowed read, and ``decode_step_cache`` are the
    # interface's defaults (models/family.py)
    # ------------------------------------------------------------------

    def _decode_layer(self, layer_p, x, positions, ck, cv, cache_pos, attn_len,
                      lens=None):
        """One decoder layer with KV-cache attention: returns the residual
        stream and this layer's updated cache. Shared by the stacked-scan
        decode (_decode) and the unstacked list decode (which passes each
        lane's live length ``lens``)."""
        attn_out, (nk, nv) = self._attention(
            layer_p, x, positions, kv_cache=(ck, cv, cache_pos),
            attn_len=attn_len, lens=lens,
        )
        x = x + attn_out
        ffn_out, _ = self._ffn(layer_p, x)
        return x + ffn_out, nk, nv

    def _decode_head(self, params, x):
        """Final norm + unembed of the last-position residual stream."""
        import jax.numpy as jnp

        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        x = _rms_norm(x, params["ln_f"].astype(dt), cfg.norm_eps)
        return (x[:, 0] @ params["unembed"].astype(dt)).astype(jnp.float32)

    def _decode(self, params, cache, tokens, positions, cache_pos, attn_len=None):
        """Shared decode-step pipeline: embed -> scan blocks with KV-cache
        attention -> final norm -> unembed. ``positions`` is [B] int32;
        ``cache_pos`` is a scalar (aligned batch) or [B] (ragged batch) —
        ``_attention`` branches on its rank for the K/V write + mask.
        ``attn_len`` (static int, optional) bounds the cache READ length."""
        from jax import lax

        # serving-mesh entry gather / exit reshard (see set_serving_mesh)
        params = self._tp_gather(params)
        cache = self._tp_gather(cache)
        x = self._embed_tokens(params, tokens)  # [B,1,D]

        def body(x, inputs):
            layer_p, ck, cv = inputs
            x, nk, nv = self._decode_layer(
                layer_p, x, positions, ck, cv, cache_pos, attn_len
            )
            return x, (nk, nv)

        x, (nk, nv) = lax.scan(body, x, (params["blocks"], cache["k"], cache["v"]))
        return self._decode_head(params, x), self._tp_slab({"k": nk, "v": nv})

    def decode_step(self, params, cache, tokens, pos):
        """One decode step: tokens [B, 1], pos scalar int. Returns
        (logits [B, V], updated cache). jit-friendly: static shapes."""
        import jax.numpy as jnp

        positions = jnp.full((tokens.shape[0],), pos, jnp.int32)
        return self._decode(params, cache, tokens, positions, pos)

    def decode_step_ragged(self, params, cache, tokens, pos, attn_len=None):
        """One decode step over a RAGGED batch: tokens [B, 1], pos [B]
        int32 — every row sits at its own position (continuous batching:
        requests admitted mid-flight decode side-by-side with older ones).
        K/V land via a per-row scatter; attention masks each row to its
        own prefix. Static shapes throughout, so one XLA executable serves
        every mix of in-flight requests. Returns (logits [B, V], cache).

        ``attn_len`` (static int): upper bound on every row's position + 1;
        the attention read stops there (decode is cache-bandwidth-bound,
        so a tight bucket ~halves step time mid-generation).
        """
        import jax.numpy as jnp

        pos = pos.astype(jnp.int32)
        return self._decode(params, cache, tokens, pos, pos, attn_len=attn_len)

    def decode_step_ragged_list(self, params, ks, vs, tokens, pos, attn_len=None,
                                write_pos=None, lens=None):
        """Ragged decode step over an UNSTACKED cache: ``ks``/``vs`` are
        per-layer lists of [B, KV, T, Dh] arrays. Returns
        ``(logits [B, V], new_ks, new_vs)``.

        Why a second layout: the stacked [L, ...] cache flowing through the
        layer scan as xs/ys makes XLA rewrite the whole cache every step —
        decode cost then scales with TOTAL cache bytes, not the attended
        prefix (measured ~2.5x step-time on a v5e). The per-layer arrays
        are carried through the caller's step loop (the batcher's fused
        burst donates them), and per step and layer the compiled burst
        does one thing to a cache array: ``ops.decode_attention()``. On a
        TPU that is ONE kernel call with the array aliased in and out,
        which puts each live lane's new ``Dh`` row per KV head into the
        donated buffer itself and copies the lane's live blocks out of
        the buffer where it lies; elsewhere ``cache_write``'s scatter of
        those rows into the buffer and the two dots reading ``[B, KV,
        attn_len, Dh]`` of it as a fused operand. No copy on entry or
        exit, no slice written out, on a TPU no scatter:
        ``tools/burst_hlo_check.py`` compiles the burst at the
        benchmark's shapes and fails on any of them
        (``tests/test_burst_hlo.py`` runs it for a described v5e); before
        ISSUE 26 the same check found 96 cache-sized copies a burst and
        4.3 GB of scratch beside a 5.6 GB cache. The continuous batcher
        (serving/continuous.py) keeps its persistent cache in this layout.

        ``lens`` ([B] int32, optional): how many positions of its cache
        each row reads: ``pos + 1``, or 0 for a lane that is idle or done
        (the kernel then copies nothing for it, in or out: its K/V row
        is NOT written, being that of a token nobody sampled at a
        position no read admits before the lane's next occupant
        overwrites it; the scatter off a TPU still writes it; the caller
        drops its logits). Defaults to ``pos + 1`` on every row.

        ``write_pos`` ([B] int32, optional): per-row K/V WRITE position
        when it must differ from the attention position — the fused
        stop-aware burst parks finished lanes' writes out of bounds
        (index >= T, dropped by JAX scatter semantics) so a done lane's
        cache is frozen while live lanes keep decoding. Defaults to
        ``pos`` (write where you attend — the ordinary decode step).
        """
        import jax
        import jax.numpy as jnp

        pos = pos.astype(jnp.int32)
        wp = pos if write_pos is None else write_pos.astype(jnp.int32)
        lens = pos + 1 if lens is None else lens.astype(jnp.int32)
        # serving-mesh entry gather / exit reshard (see set_serving_mesh)
        params = self._tp_gather(params)
        ks = self._tp_gather(ks)
        vs = self._tp_gather(vs)
        x = self._embed_tokens(params, tokens)  # [B,1,D]
        blocks = params["blocks"]
        nks: list = []
        nvs: list = []
        # a layer's weights are cut from the stacked arrays when the layer
        # before it has written its cache (the first's: when the embedding
        # is there), tied to that here: left free, the TPU compiler cuts
        # every layer's wq, wk and wv at the top of the step, keeps the
        # few that fit in VMEM there and sends the rest through an HBM
        # temporary each (written, prefetched, read: 1.0-2.6 ms of a
        # 6.3-11.3 ms step and 0.35-0.6 GB of scratch, PERF.md section 6,
        # PR 32). The tie is a value that is written out anyway: tying to
        # the layer's input splits the fusion that makes it (the next
        # norm's sum of squares then reads the rounded sum, and greedy
        # tokens change)
        tie = x
        for l in range(len(ks)):
            tie, blocks = jax.lax.optimization_barrier((tie, blocks))
            if l == 0:
                x = tie
            else:
                nks[-1], nvs[-1] = tie
            layer_p = jax.tree_util.tree_map(lambda a, l=l: a[l], blocks)
            x, nk, nv = self._decode_layer(
                layer_p, x, pos, ks[l], vs[l], wp, attn_len, lens
            )
            nks.append(self._tp_cache(nk))
            nvs.append(self._tp_cache(nv))
            tie = (nks[-1], nvs[-1])
        return self._decode_head(params, x), nks, nvs

    def decode_chunk_ragged_list(self, params, ks, vs, tokens, pos, attn_len=None):
        """Decode a WINDOW of tokens per lane in ONE forward over the
        unstacked cache: ``tokens`` [B, W], ``pos`` [B] start positions —
        row b's token j sits at position pos[b]+j. Returns
        ``(logits [B, W, V], new_ks, new_vs)`` where logits[:, j] is the
        next-token distribution AFTER consuming tokens[:, j].

        This is the speculative-decoding verify step (γ drafted tokens +
        the entry token are scored in one target forward instead of γ+1
        sequential steps) and doubles as chunked decode for any
        multi-token advance. K/V for all W positions are scattered into
        the cache first; the mask ``key_pos <= pos+j`` then covers both
        the prefix and in-window causality. Positions beyond a row's
        accepted prefix simply get overwritten by later writes and are
        never read (mask), so rejected drafts need no rollback.
        """
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        pos = pos.astype(jnp.int32)
        B, W = tokens.shape
        positions = pos[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]  # [B,W]
        # serving-mesh entry gather / exit reshard (see set_serving_mesh)
        params = self._tp_gather(params)
        ks = self._tp_gather(ks)
        vs = self._tp_gather(vs)
        x = self._embed_tokens(params, tokens)  # [B,W,D]
        blocks = params["blocks"]
        nks: list = []
        nvs: list = []
        for l in range(len(ks)):
            p = jax.tree_util.tree_map(lambda a, l=l: a[l], blocks)
            q, k, v = self._qkv(p, x, positions)
            Hl = q.shape[1]
            # the whole window lands first: ck[b,:,pos[b]+j,:] = k[b,:,j,:]
            ck = self._cache_write(ks[l], k, positions)
            cv = self._cache_write(vs[l], v, positions)
            nks.append(self._tp_cache(ck))
            nvs.append(self._tp_cache(cv))
            kc, vc = self._cache_read(ck, cv, attn_len)
            # grouped cache read (prefix + in-window causality via the
            # [B, W] bound) — no head-repeated cache copy
            o = self._cache_attention(q, kc, vc, positions, dt)
            o = o.transpose(0, 2, 1, 3).reshape(B, W, Hl * cfg.head_dim)
            x = x + o @ p["wo"].astype(dt)
            ffn_out, _ = self._ffn(p, x)
            x = x + ffn_out
        x = _rms_norm(x, params["ln_f"].astype(dt), cfg.norm_eps)
        logits = (x @ params["unembed"].astype(dt)).astype(jnp.float32)
        return logits, nks, nvs

    def prefill_chunk(self, params, slab, tokens, start_pos, attn_len,
                      last_index=None, want_logits=True):
        """Extend a STAGING prompt slab with one chunk WITHOUT re-reading
        the already-prefilled prefix (the model half of the continuous
        batcher's chunked-prefill interleave).

        ``slab``: stacked ``{"k","v"}`` arrays ``[L, 1, KV, B, Dh]`` —
        the ``cache_one`` layout ``prefill`` produces and the batcher's
        lane insert consumes — holding valid K/V for ``[0, start_pos)``.
        Living OUTSIDE the decode cache is the point: in-flight decode
        bursts can never touch a half-built prompt, and the decode
        executables stay bit-for-bit the ones a whole-prompt admission
        uses. ``tokens`` ``[1, C]``: the chunk, padded to a static
        length; token j sits at absolute position ``start_pos + j``
        (traced, so one executable serves every offset at a given
        ``(B, C, attn_len)``). Per layer the chunk's K/V land in the
        slab at ``start_pos`` and attention reads the slab bounded at
        ``attn_len`` (static, ``>= start_pos + C``) under the
        ``key_pos <= start_pos + j`` bound — prior chunks are READ, not
        recomputed, so a P-token prompt costs one prefill's K/V writes
        plus bounded reads, not P^2/C re-reads. Pad positions past the
        real prompt get garbage K/V exactly like the bucketed full
        prefill (decode overwrites them before the mask can admit them).

        Returns ``(logits [1, V] at last_index | None, new_slab)``;
        ``want_logits=False`` (mid-prompt chunks) skips the final-norm +
        unembed read — only the LAST chunk samples a token.
        """
        import jax.numpy as jnp
        from jax import lax

        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        # serving-mesh entry gather: the scan body below must be the
        # byte-identical single-device program (see set_serving_mesh)
        params = self._tp_gather(params)
        slab = self._tp_gather(slab)
        B, C = tokens.shape
        start_pos = jnp.asarray(start_pos, jnp.int32)
        positions = start_pos + jnp.arange(C, dtype=jnp.int32)[None, :]  # [1, C]
        x = self._embed_tokens(params, tokens)

        def body(x, xs):
            p, pk, pv = xs  # pk/pv: [1, KV, B, Dh]
            q, k, v = self._qkv(p, x, positions)
            Hl = q.shape[1]
            ck = lax.dynamic_update_slice(pk, k, (0, 0, start_pos, 0))
            cv = lax.dynamic_update_slice(pv, v, (0, 0, start_pos, 0))
            gk, gv = self._cache_read(ck, cv, attn_len)
            o = self._cache_attention(q, gk, gv, positions, dt)
            o = o.transpose(0, 2, 1, 3).reshape(B, C, Hl * cfg.head_dim)
            x = x + o @ p["wo"].astype(dt)
            ffn_out, _ = self._ffn(p, x)
            return x + ffn_out, (ck, cv)

        x, (nk, nv) = lax.scan(
            body, x, (params["blocks"], slab["k"], slab["v"])
        )
        # exit reshard: the staging slab lives sharded between chunks
        new_slab = self._tp_slab({"k": nk, "v": nv})
        if not want_logits:
            return None, new_slab
        x = _rms_norm(x, params["ln_f"].astype(dt), cfg.norm_eps)
        logits = (self._last_rows(x, last_index)
                  @ params["unembed"].astype(dt)).astype(jnp.float32)
        return logits, new_slab

    def prefill_with_prefix(self, params, prefix_kv, tokens, start_pos,
                            last_index=None):
        """Suffix prefill over a CACHED prefix (the prefix-splice cache op
        behind the continuous batcher's radix prefix cache).

        ``prefix_kv``: stacked ``{"k","v"}`` slab ``[L, 1, KV, Tp, Dh]``
        holding valid K/V for positions ``[0, start_pos)`` of this
        sequence — the ``cache_one`` layout an earlier prefill of a
        prompt sharing the prefix produced (``start_pos`` is traced, so
        one executable serves every match depth at a given slab/window
        bucket pair). ``tokens`` ``[1, W]``: the remaining prompt, padded
        to a bucket; token j sits at absolute position ``start_pos + j``
        (RoPE uses absolute positions, so any split point is exact).

        Per layer the window's K/V are spliced into a W-extended copy of
        the prefix slab at ``start_pos`` and attention runs over the
        grouped combined cache with the ``key_pos <= start_pos + j``
        bound — covering the cached prefix AND in-window causality while
        masking slab residue beyond the match (``_cache_attention``; the
        donor's positions past ``start_pos`` belong to the DONOR's
        prompt, never this one). Returns ``(logits [1, V]`` at
        ``last_index`` within the window, suffix slab
        ``[L, 1, KVl, W, Dh])`` for splicing into a decode lane.
        """
        import jax.numpy as jnp
        from jax import lax

        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        # serving-mesh entry gather (see set_serving_mesh)
        params = self._tp_gather(params)
        prefix_kv = self._tp_gather(prefix_kv)
        B, W = tokens.shape
        start_pos = jnp.asarray(start_pos, jnp.int32)
        positions = start_pos + jnp.arange(W, dtype=jnp.int32)[None, :]  # [1, W]
        x = params["embed"][tokens.astype(jnp.int32)].astype(dt)

        def body(x, xs):
            layer_p, pk, pv = xs  # pk/pv: [1, KV, Tp, Dh]
            q, k, v = self._qkv(layer_p, x, positions)
            Hl, KVl = q.shape[1], k.shape[1]
            # W-extended combined cache: start_pos <= Tp always (the slab
            # covers at least the match), so the traced-start splice never
            # clamps
            pad = jnp.zeros((B, KVl, W, cfg.head_dim), dt)
            ck = lax.dynamic_update_slice(
                jnp.concatenate([pk.astype(dt), pad], axis=2), k,
                (0, 0, start_pos, 0),
            )
            cv = lax.dynamic_update_slice(
                jnp.concatenate([pv.astype(dt), pad], axis=2), v,
                (0, 0, start_pos, 0),
            )
            o = self._cache_attention(q, ck, cv, positions, dt)
            o = o.transpose(0, 2, 1, 3).reshape(B, W, Hl * cfg.head_dim)
            x = x + o @ layer_p["wo"].astype(dt)
            ffn_out, _ = self._ffn(layer_p, x)
            return x + ffn_out, (k, v)

        x, (sk, sv) = lax.scan(
            body, x, (params["blocks"], prefix_kv["k"], prefix_kv["v"])
        )
        x = _rms_norm(x, params["ln_f"].astype(dt), cfg.norm_eps)
        logits = (self._last_rows(x, last_index)
                  @ params["unembed"].astype(dt)).astype(jnp.float32)
        return logits, self._tp_slab({"k": sk, "v": sv})

    def prefill(self, params, prompt, max_seq: int, last_index=None):
        """Batched prefill: ONE forward over the whole prompt, K/V for all
        positions computed in parallel and written into a fresh cache of
        length ``max_seq``. Returns (last-position logits [B, V], cache).
        ~Tp x cheaper time-to-first-token than stepping decode_step.

        ``last_index`` ([B] int32, optional): per-row index of the last
        REAL prompt token when the batch is right-padded to a bucket
        length (continuous batching pads prompts to a few fixed lengths
        to bound XLA compilations); defaults to the final position."""
        import jax.numpy as jnp
        from jax import lax

        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        params = self._tp_gather(params)  # exact serving-mesh entry gather
        B, Tp = prompt.shape
        x = params["embed"][prompt.astype(jnp.int32)].astype(dt)
        positions = jnp.arange(Tp)

        def body(x, layer_p):
            q, k, v = self._qkv(layer_p, x, positions)
            Hl, KVl = q.shape[1], k.shape[1]
            kr, vr = k, v
            if KVl < Hl:
                rep = Hl // KVl
                kr = jnp.repeat(k, rep, axis=1)
                vr = jnp.repeat(v, rep, axis=1)
            # flash (pallas) on TPU for MXU-tileable prompt lengths; XLA
            # einsum elsewhere. Prefill is inference-only, so the
            # kernel needs no VJP (training keeps parallel/ring.py paths).
            from ..ops import attention as prefill_attention

            o = prefill_attention(
                q, kr, vr, causal=True,
                mesh=self._serving_mesh,
            )
            o = o.transpose(0, 2, 1, 3).reshape(B, Tp, Hl * cfg.head_dim)
            x = x + o @ layer_p["wo"].astype(dt)
            ffn_out, _ = self._ffn(layer_p, x)
            # pad this layer's K/V out to the full cache length
            pad = max_seq - Tp
            k_cache = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
            v_cache = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
            return x + ffn_out, (k_cache, v_cache)

        x, (ck, cv) = lax.scan(body, x, params["blocks"])
        x = _rms_norm(x, params["ln_f"].astype(dt), cfg.norm_eps)
        logits = (self._last_rows(x, last_index)
                  @ params["unembed"].astype(dt)).astype(jnp.float32)
        return logits, self._tp_slab({"k": ck, "v": cv})

    def generate(self, params, prompt, max_new_tokens: int, temperature: float = 0.0, seed: int = 0):
        """Greedy/temperature sampling. prompt [B, Tp] -> [B, Tp+N]."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        B, Tp = prompt.shape
        if max_new_tokens <= 0:
            return prompt
        total = Tp + max_new_tokens
        logits, cache = self.prefill(params, prompt, total)

        def sample(logits, key):
            if temperature <= 0.0:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return jax.random.categorical(key, logits / temperature, axis=-1).astype(jnp.int32)

        def decode_body(carry, t):
            cache, prev_tok, key = carry
            key, sub = jax.random.split(key)
            logits, cache = self.decode_step(params, cache, prev_tok[:, None], t)
            nxt = sample(logits, sub)
            return (cache, nxt, key), nxt

        first = sample(logits, jax.random.PRNGKey(seed))
        (_, _, _), toks = lax.scan(
            decode_body,
            (cache, first, jax.random.PRNGKey(seed + 1)),
            jnp.arange(Tp, total - 1),
        )
        out = jnp.concatenate(
            [prompt, first[:, None], toks.T.astype(jnp.int32)], axis=1
        )
        return out

    # ------------------------------------------------------------------
    # loss / train step
    # ------------------------------------------------------------------

    def loss_fn(self, params, tokens):
        """Next-token CE (+ MoE load-balancing aux) on a single chip."""
        import jax.numpy as jnp
        import optax

        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        inputs = tokens[:, :-1].astype(jnp.int32)
        x = params["embed"][inputs].astype(dt)
        x, aux = self.backbone(params["blocks"], x, jnp.arange(inputs.shape[1]))
        x = _rms_norm(x, params["ln_f"].astype(dt), cfg.norm_eps)
        logits = (x @ params["unembed"].astype(dt)).astype(jnp.float32)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, tokens[:, 1:])
        return ce.mean() + cfg.aux_loss_weight * aux

    def input_sharding(self, mesh):
        from jax.sharding import NamedSharding, PartitionSpec as P

        axis = "data" if "data" in mesh.axis_names else None
        return NamedSharding(mesh, P(axis, None))

    def burst_params(self, params):
        """The stacked q / k / v projection weights [L, D, out] held [L,
        out, D]: the burst's projection of its few rows, fused with the
        head split and the rotary, consumes them contraction-minor, and
        handed the stored layout the TPU compiler relays all three at the
        top of every burst (402 MB written and read again in InternLM, 705
        MB in Mistral: all of the burst's scratch; PERF.md section 6, PR
        54)."""
        if self._serving_mesh is not None:
            return params
        return {**params,
                "blocks": self.relaid(params["blocks"], ("wq", "wk", "wv"))}

    def param_sharding(self, mesh, params):
        """TP layout over the ``model`` axis for pjit-style serving."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        if "model" not in mesh.axis_names:
            repl = NamedSharding(mesh, P())
            return jax.tree_util.tree_map(lambda _: repl, params)

        def spec_for(path, leaf):
            name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
            col = {"wq", "wk", "wv", "w1", "w3"}
            row = {"wo", "w2"}
            nd = leaf.ndim
            if name in col:
                return NamedSharding(mesh, P(*([None] * (nd - 1)), "model"))
            if name in row:
                return NamedSharding(mesh, P(*([None] * (nd - 2)), "model", None))
            if name == "w1e":
                return NamedSharding(mesh, P(None, None, None, "model"))
            if name == "w2e":
                return NamedSharding(mesh, P(None, None, "model", None))
            return NamedSharding(mesh, P())

        return jax.tree_util.tree_map_with_path(spec_for, params)

    def set_serving_mesh(self, mesh, shard_seq=False):
        """Arm the sharded-STORAGE / replicated-COMPUTE serving mode
        (the continuous batcher calls this when it puts params under
        :meth:`param_sharding`).

        Why not classic psum-TP: GSPMD left alone lowers the
        row-parallel contractions (``wo``, ``w2``) and the head-split
        cache attention to partial ops + all-reduce — a different
        summation association (and different fused codegen) than the
        single-device executable, so greedy argmax flips the moment a
        near-tie sits inside reduction noise and the 1-vs-N
        byte-identity contract breaks. Measured on the 8-virtual-device
        CPU mesh: bf16 logits drift ~1e-2 and per-operand resharding
        constraints do NOT close it (fusion still reorders reductions
        inside ``lax.scan`` bodies).

        Armed instead, every serving executable gathers its sharded
        operands to full replication at ENTRY (:meth:`_tp_gather` — an
        all-gather of disjoint shards, pure data movement, zero
        arithmetic), runs the byte-identical single-device program, and
        re-shards its cache/slab writes at EXIT (:meth:`_tp_cache` /
        :meth:`_tp_slab` — a local slice, also exact). Params and the
        KV cache therefore LIVE at 1/N per chip — the pod-scale
        capacity win this mesh exists for — while the arithmetic is the
        single-device program by construction. Compute-parallel TP
        (psum-based) stays available via the explicit ``tp_axis``
        shard_map path, which does not carry the identity gate."""
        self._serving_mesh = mesh
        self._serving_shard_seq = bool(shard_seq)

    def _tp_gather(self, tree):
        """Constrain every leaf of ``tree`` to full replication — the
        exact entry all-gather of the serving mesh mode. No-op when no
        serving mesh is armed."""
        mesh = self._serving_mesh
        if mesh is None:
            return tree
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        def repl(x):
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(*([None] * x.ndim)))
            )

        return jax.tree_util.tree_map(repl, tree)

    def _tp_cache(self, arr):
        """Constrain a per-layer decode-cache buffer ``[S, KV, T, Dh]``
        back to the persistent sharded layout at executable exit (a
        local slice — exact). The value is pinned to full replication
        FIRST: without that inner annotation GSPMD propagates the
        sharded exit spec backward through the attention math and turns
        the compute into partial-sum tensor parallelism, which is
        exactly the reduction reordering this mode exists to avoid.
        No-op unmeshed."""
        mesh = self._serving_mesh
        if mesh is None:
            return arr
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        arr = jax.lax.with_sharding_constraint(
            arr, NamedSharding(mesh, P(*([None] * arr.ndim)))
        )
        return jax.lax.with_sharding_constraint(
            arr,
            self.cache_sharding(
                mesh, shard_seq=self._serving_shard_seq
            ),
        )

    def _tp_slab(self, tree):
        """Constrain a stacked K/V slab ``{"k","v"} [L, S, KV, T, Dh]``
        back to the sharded staging layout at executable exit, pinning
        each leaf replicated first to stop backward propagation into
        the compute (see :meth:`_tp_cache`). No-op unmeshed."""
        mesh = self._serving_mesh
        if mesh is None:
            return tree
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        sh = self.slab_sharding(mesh)
        return jax.tree_util.tree_map(
            lambda a: jax.lax.with_sharding_constraint(
                jax.lax.with_sharding_constraint(
                    a, NamedSharding(mesh, P(*([None] * a.ndim)))
                ),
                sh,
            ),
            tree
        )

    def cache_sharding(self, mesh, kv_heads=None, shard_seq=False):
        """Sharding for one per-layer KV cache buffer ``[S, KV, T, Dh]``.

        The KV-head axis partitions over ``model`` (it is the activation
        counterpart of the column-parallel wk/wv layout, so attention
        never gathers the cache), the lane axis S stays data-parallel
        (replicated — lanes are scheduler state, not a batch collective),
        and T optionally partitions over ``seq`` when sequence parallelism
        is on. When the KV head count does not divide the model axis (GQA
        targets, thin draft models) the heads replicate instead — the
        byte-identity contract holds either way, sharding only moves
        where the bytes live."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        kv = self.cfg.n_kv_heads if kv_heads is None else kv_heads
        model_ax = "model" if "model" in mesh.axis_names else None
        if model_ax and kv % mesh.shape["model"] != 0:
            model_ax = None
        seq_ax = None
        if shard_seq and "seq" in mesh.axis_names and mesh.shape["seq"] > 1:
            seq_ax = "seq"
        return NamedSharding(mesh, P(None, model_ax, seq_ax, None))

    def slab_sharding(self, mesh, kv_heads=None):
        """Sharding for a stacked staging/transfer slab
        ``[L, 1, KV, bucket, Dh]`` (the per-request prefill slab layout):
        same model-axis split of the KV heads as :meth:`cache_sharding`,
        everything else replicated. Host-side wire bytes (SKV1, tier
        demote) always gather first, so the wire layout never sees this."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        kv = self.cfg.n_kv_heads if kv_heads is None else kv_heads
        model_ax = "model" if "model" in mesh.axis_names else None
        if model_ax and kv % mesh.shape["model"] != 0:
            model_ax = None
        return NamedSharding(mesh, P(None, None, model_ax, None, None))
