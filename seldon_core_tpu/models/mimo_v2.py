"""MimoV2LM: window layers with a ring a lane beside full layers, the two
kinds with their own KV heads, keys wider than values, a learned sink in
the window layers' softmax, and a chip's share of sigmoid-routed experts
(XiaomiMiMo publishes the family as ``model_type: mimo_v2``).

A ``DecoderFamily`` (``models/family.py``: the interface the scheduler and
the server ask), registered there as ``"mimo_v2"``:
``DecoderLM(block="mimo_v2", ...)`` and ``MimoV2LM(...)`` build it, over a
``MimoV2Config``. With the other blocks it shares the embedding lookup,
``_rms_norm``, ``_rope``, the KV cache's ops (``ops.decode_attention``, the
flash kernel) and the routed experts (``ops/experts.py``). Every layer is
pre-norm,

    h = x + Attn(N(x));   y = h + FFN(N(h))

``N`` the plain RMSNorm, the attention by ``cfg.layer_types``:

* ``full_attention``: ``n_heads`` query heads over ``n_kv_heads`` key heads
  of ``head_dim`` (192) and value heads of ``v_head_dim`` (128), no bias;
  half-split rotary over the FIRST ``rotary_dim`` (64) dims of a head, base
  ``rope_theta``; scores ``q . k / sqrt(head_dim)``, causal; the output
  times ``value_scale`` (linear in ``v``: on ``v`` or on ``o`` the same
  function; here on ``o``, so the cache holds the values as projected).
* ``sliding_attention``: the same with ``swa_n_kv_heads`` key heads, base
  ``swa_rope_theta``, query i seeing keys (i - ``swa_window``, i], and a
  learned ``sink`` logit a query head that joins the softmax and has no
  value row: ``p_j = exp(s_j - m) / (sum_j exp(s_j - m) + exp(b_h - m))``.

``FFN`` a SwiGLU of ``d_ff`` in the first ``n_dense_layers`` layers and
after them ``experts_per_tok`` of ``n_routed_experts`` experts, no shared
one: ``s = sigmoid(x W_r)`` in float32, the picks the top k of ``s +
expert_bias``, the weights ``s[picks] / sum x route_scale``
(``ops.experts.route``). ``experts_held = (lo, n)``: this chip holds experts
``lo .. lo + n - 1`` of every layer and computes the picks that land on
them. Final norm, untied head.

**The cache** is laid out by KIND OF LAYER, the kinds differing in heads and
in length: ``{"k", "v"}`` a [S, KV, max_seq, .] pair a FULL layer, a row a
position; ``{"wk", "wv"}`` a [S, KVw, swa_window, .] pair a WINDOW layer, a
RING written at ``pos mod swa_window``: a window layer's step reads
``min(len, swa_window)`` rows and never a ``max_seq``-long array, and a
cached position past the last ``swa_window`` costs it nothing. Keys carry
their rotary, so the softmax over a ring needs no order, only the bound of
the slots written. A key row of 192 occupies 256 lanes in HBM whoever lays
it out and Mosaic slices no row of 192 (``ops.decode_attention.
block_reads_ragged``), so a key is CUT at 128: its first 128 dims a row of
its head's own, and its 64-wide rest beside another head's in one of KV / 2
rows that follow the heads' on the same axis (``ops.decode_attention.
pack_keys``): ``k`` [S, KV + KV / 2, max_seq, 128], a ring ``wk`` [S, KVw +
KVw / 2, swa_window, 128], 2,560 B a position in a full layer and 5,120 a
ring row, none of it padding. The step hands the op its queries' two parts
and the new row so laid, and the scores are ``(q . k + q_rest . k_rest) /
sqrt(192)`` (a dot product is a sum over dims: where the cut falls is
free). The rule is on the shapes, a kind at a time
(``ops.decode_attention.packed_key_rows``): a key of 128 < ``head_dim`` <
256 whose rest divides 128, in a kind whose KV heads fill whole rows. Any
other key is HELD in a row of the next multiple of 128, zero past the key
(``_key_row``; the same kernel with no packed row), and the step's queries
go in as wide and times ``sqrt(key row / head_dim)`` (the op scales by a
ROW's width there; the factor goes into the query in float32 before its
one rounding: the lfm2_moe block's way). ``cached_rows`` gives back the
keys whole whichever way they are held.

Serving only; what it refuses is ``serving_refuses``: everything that
truncates, splices or copies COLUMNS of a KV cache would have to rebuild a
ring at that position, and nothing does.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import numpy as np

from .family import DecoderFamily
from .llm import LLMConfig, _rms_norm, _rope

SLIDING, FULL = "sliding_attention", "full_attention"
_NEEDS_RING = (
    "a window layer keeps its last swa_window positions as a ring: what {0} "
    "does to columns of a KV cache needs the ring as it was at that "
    "position, and none is kept")


@dataclasses.dataclass
class MimoV2Config(LLMConfig):
    """The shared fields (``layer_types``: "sliding_attention" |
    "full_attention" a layer; ``n_kv_heads``, ``head_dim``, ``rope_theta``:
    the FULL layers'; ``n_dense_layers``, ``d_ff``; the routed experts',
    ``experts_held``) and this family's own."""
    block: str = "mimo_v2"
    v_head_width: int = 128       # a value head (``v_head_dim``), both kinds
    rotary_dim: int = 64          # leading dims of a head that are rotated
    swa_window: int = 128         # keys a window layer's query sees
    swa_n_kv_heads: int = 8       # a window layer's key / value heads
    swa_rope_theta: float = 10000.0
    value_scale: float = 0.707    # ``attention_value_scale``


@functools.lru_cache(maxsize=None)
def _row_gather(packed: int, head_dim: int):
    """``MimoV2LM.cached_rows`` over one layer's K and V arrays, jitted: a
    program a (kind's packing, key width)."""
    import jax

    from ..ops.decode_attention import unpack_keys

    def gather(k, v, lanes, positions):
        at = lanes[:, None]
        rows = k[at, :, positions]                   # [n, m, KV + packed, .]
        if packed:
            rows = unpack_keys(rows[..., None, :], packed)[..., 0, :]
        return rows[..., :head_dim], v[at, :, positions]

    return jax.jit(gather)


class MimoV2LM(DecoderFamily):
    config_class = MimoV2Config
    step_counter_names = (
        # per decode step, summed over the expert layers: distinct held
        # experts some live lane picked, (lane, pick) pairs routed over ALL
        # experts, expert layers run, the pairs that landed on a held
        # expert; summed over the FULL layers: positions of K and V the
        # step's read streams (``_rows_read``: under the kernel each live
        # lane's length rounded up to the block of its walk, under the dots
        # the static bound of EVERY lane; counted by the branch the lowering
        # takes) and the lanes' lengths; summed over the WINDOW layers: the
        # ring's rows the read streams (the kernel: one block of a live
        # lane's ring; the dots: every lane's), the rows a query sees
        # (``min(len, swa_window)``) and the lanes' lengths, what a
        # ``max_seq``-long cache would hold for the same lanes
        "moe_experts_touched", "moe_rows_routed", "moe_layer_steps",
        "moe_rows_held", "kv_rows_read", "kv_rows_live",
        "kv_positions_read_window", "kv_positions_seen_window",
        "kv_positions_live_window",
    )
    prefill_counter_names = (
        # as the lfm2_moe block's: the (row, pick) pairs the grouped experts
        # moved and the pairs routed, over the expert layers, and the rows
        # of the row tiles the grouped experts' kernel worked
        "moe_prefill_pairs_moved", "moe_prefill_pairs_routed",
        "moe_prefill_tile_rows",
    )
    serving_refuses = {
        "speculation": "the draft is the first layers of a stacked llama "
                       "block, and a rejected window would have to roll the "
                       "rings back; the checkpoint's multi-token-prediction "
                       "modules are not served",
        "mesh": "the expert and attention kernels are not partitioned, and "
                "param_sharding knows no expert axis",
        "kv_tier": _NEEDS_RING.format("the tier's spill and copy-back"),
        "prefix_cache": _NEEDS_RING.format("a prefix's reuse or splice"),
        "chunked_prefill": "a chunk would start from the rings the last one "
                           "left, and prefill_chunk carries none",
        "preemption": _NEEDS_RING.format("a checkpoint's replay"),
        "migration": _NEEDS_RING.format("a shipped slab"),
    }
    # a key row as the cache holds it (the module's note): the next multiple
    # of a register's 128 lanes
    _LANES = 128

    def __init__(self, **config):
        super().__init__(**config)
        cfg = self.cfg
        types = cfg.layer_types or ()
        if len(types) != cfg.n_layers or set(types) - {SLIDING, FULL}:
            raise ValueError(
                f"layer_types must name {cfg.n_layers} layers as "
                f"{SLIDING!r} or {FULL!r}: {types}")
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
        if cfg.n_heads % cfg.n_kv_heads or cfg.n_heads % cfg.swa_n_kv_heads:
            raise ValueError("whole GQA groups in both kinds of layer")
        if cfg.rotary_dim % 2 or not 0 < cfg.rotary_dim <= cfg.head_dim:
            raise ValueError("an even rotary_dim inside the head")
        if SLIDING in types and (cfg.swa_window < 8 or cfg.swa_window % 8):
            raise ValueError("a window of whole groups of 8 positions")
        if FULL not in types:
            raise ValueError("at least one full_attention layer: a position "
                             "is parked past the full layers' rows")
        # resolved here and never in a traced function
        self._window: Tuple[bool, ...] = tuple(t == SLIDING for t in types)
        self._n_window = sum(self._window)
        self._n_full = cfg.n_layers - self._n_window
        self._routed: Tuple[bool, ...] = tuple(
            l >= cfg.n_dense_layers for l in range(cfg.n_layers))
        self._n_routed_layers = sum(self._routed)
        self._n_held = cfg.n_routed_experts if held is None else held[1]
        self._key_row = -(-cfg.head_dim // self._LANES) * self._LANES
        from ..ops.decode_attention import packed_key_rows

        # by kind (``window``): the rows of its K arrays' KV axis that hold
        # keys' rests (the module's note); 0: keys padded to ``_key_row``
        self._packed = {window: packed_key_rows(cfg.head_dim,
                                                self._kv_heads(window))
                        for window in (False, True)}

    def attention_kinds(self):
        cfg = self.cfg
        return tuple(kind for kind in ((self._n_full, None),
                                       (self._n_window, cfg.swa_window))
                     if kind[0])

    def row_cache_windows(self):
        """None: the window layers hold rings, whose reads the step counts
        itself (``kv_positions_*_window`` of ``step_counter_names``)."""
        return ()

    def _kv_heads(self, window: bool) -> int:
        return self.cfg.swa_n_kv_heads if window else self.cfg.n_kv_heads

    # -- sizes ---------------------------------------------------------------

    def _key_held(self, window: bool) -> int:
        """What one head's key occupies of the kind's K arrays: the key's
        own width where its rest is packed, else its padded row's."""
        return self.cfg.head_dim if self._packed[window] else self._key_row

    def row_bytes(self, window: bool) -> int:
        """K and V of one position in one layer of the kind, as the cache
        holds them (``_key_held``), bfloat16."""
        return self._kv_heads(window) * (
            self._key_held(window) + self.cfg.v_head_width) * 2

    def _attention_params(self, window: bool) -> int:
        cfg = self.cfg
        D, H, kv = cfg.d_model, cfg.n_heads, self._kv_heads(window)
        return (D * H * cfg.head_dim + D * kv * cfg.head_dim
                + D * kv * cfg.v_head_width + H * cfg.v_head_width * D
                + (H if window else 0))

    def _layer_params(self, window: bool, routed: bool, experts: float) -> float:
        """Parameters of one layer with ``experts`` of its held routed
        experts counted (all of them: what is held; fewer: what a step
        reads)."""
        cfg = self.cfg
        D = cfg.d_model
        n = 2 * D + self._attention_params(window)
        if not routed:
            return n + 3 * D * cfg.d_ff
        return (n + D * cfg.n_routed_experts + cfg.n_routed_experts
                + experts * 3 * D * cfg.expert_width)

    def n_params(self) -> int:
        cfg = self.cfg
        return int(sum(self._layer_params(w, r, self._n_held)
                       for w, r in zip(self._window, self._routed))
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
        n = sum(self._layer_params(w, r, touched)
                for w, r in zip(self._window, self._routed))
        return (n + cfg.vocab_size * cfg.d_model + cfg.d_model) * param_bytes

    def flops_per_token(self, context_len: int) -> float:
        cfg = self.cfg
        D = cfg.d_model
        share = self._n_held / max(1, cfg.n_routed_experts)
        per_key = 2.0 * cfg.n_heads * (cfg.head_dim + cfg.v_head_width)
        total = 2.0 * D * cfg.vocab_size
        for window, routed in zip(self._window, self._routed):
            seen = min(context_len, cfg.swa_window) if window else context_len
            total += 2.0 * self._attention_params(window) + per_key * seen
            if routed:
                total += 2.0 * D * cfg.n_routed_experts + 6.0 * D * (
                    cfg.expert_width * cfg.experts_per_tok * share)
            else:
                total += 6.0 * D * cfg.d_ff
        return total

    def kv_bytes_per_token(self) -> int:
        """What a cached position costs: its rows in the FULL layers."""
        return self._n_full * self.row_bytes(False)

    def decode_bytes_per_token(self, context_len: float, batch: int = 1,
                               param_bytes: int = 2) -> float:
        cfg = self.cfg
        ring = min(context_len, cfg.swa_window) * self._n_window
        return (self.step_param_bytes(batch, param_bytes) / max(1, batch)
                + self.kv_bytes_per_token() * context_len
                + ring * self.row_bytes(True))

    def dispatch_read_bytes(self, kind: str, *, rows: int = 1,
                            live: int = None, k: int = 1, bucket: int = 0,
                            tokens: int = 0, param_bytes: float = None,
                            kv_row_bytes: float = None) -> float:
        """As the lfm2_moe block's: a decode step reads by live lane: the
        held experts that many lanes are expected to touch, a full layer's
        keys up to the bucket, and every window layer's ring."""
        if kind in ("decode_burst", "fused_burst", "spec_burst"):
            live = rows if live is None else live
            if kv_row_bytes is None:
                kv_row_bytes = float(self.kv_bytes_per_token())
            ring = min(bucket, self.cfg.swa_window) * self._n_window
            return k * (self.step_param_bytes(live) + live * (
                bucket * kv_row_bytes + ring * self.row_bytes(True)))
        return super().dispatch_read_bytes(
            kind, rows=rows, k=k, bucket=bucket, tokens=tokens,
            param_bytes=param_bytes, kv_row_bytes=kv_row_bytes)

    # -- what the scheduler asks of the cache ------------------------------------

    def position_layers(self, cache):
        """An entry a row a step writes: the full layers' arrays first (a
        position's own row there; the read's block is asked of the first),
        then the rings."""
        return [*cache["k"], *cache["v"],
                *cache.get("wk", ()), *cache.get("wv", ())]

    def cache_position_bytes(self, cache) -> int:
        """What ONE more cached position costs: its rows in the full
        layers. A ring holds ``swa_window`` rows whatever the lane holds
        (``lane_cache_bytes`` prices them)."""
        return sum(a.nbytes // (a.shape[0] * a.shape[-2])
                   for a in (*cache["k"], *cache["v"]))

    def park_index(self, cache) -> int:
        """Past the full layers' positions: ``max_seq``. A ring wraps
        (``park mod swa_window`` would alias a live row), so the step
        tells a parked lane by its position and sends its ring row past
        the ring's end (``_step``)."""
        return cache["k"][0].shape[-2]

    def lane_cache_bytes(self, cache):
        """``positions -> bytes``: a row a position in the full layers, and
        in the window layers the rows of the ring that hold something,
        ``min(positions, swa_window)``."""
        per_position = self.cache_position_bytes(cache)
        ring_row = sum(a.nbytes // (a.shape[0] * a.shape[-2])
                       for a in (*cache.get("wk", ()), *cache.get("wv", ())))
        window = self.cfg.swa_window

        def lane_bytes(positions: int) -> int:
            if positions <= 0:
                return 0
            return positions * per_position + min(positions, window) * ring_row

        return lane_bytes

    def prefill_slab_bytes(self, rows: int, bucket: int) -> int:
        """A prompt's slab: its full layers' rows and the LAST
        ``swa_window`` rows of its window layers."""
        ring = min(bucket, self.cfg.swa_window) * self._n_window
        return rows * (bucket * self.kv_bytes_per_token()
                       + ring * self.row_bytes(True))

    # the rows (prompts x bucket) one batched prefill takes: the routed
    # layers' scratch and the full layers' head-repeated keys of eight
    # prompts of 1,792 are 2 GB beside a cache that leaves 4 (the lfm2_moe
    # block's rule, at this block's widths)
    PREFILL_ROWS = 8192

    def prefill_rows_max(self, bucket: int, added: bool = False) -> int:
        if added:       # the default's: one prompt past the batcher's buckets
            return 1
        return max(1, min(8, self.PREFILL_ROWS // max(1, bucket)))

    def burst_reads_ragged(self, cache, mesh=None) -> bool:
        import jax.numpy as jnp

        from ..ops.decode_attention import reads_ragged

        k, v = cache["k"][0], cache["v"][0]
        return reads_ragged(
            next(iter(k.devices())).platform,
            (k.shape[0], self.cfg.n_heads, 1, k.shape[3]), k.shape,
            (jnp.dtype(self.cfg.dtype), k.dtype, v.dtype), mesh, v.shape[3],
            k.shape[1] - v.shape[1])

    # -- what a comparison that borrows the serving cache asks --------------------

    def cached_rows(self, cache, kind: str, layer: int, lanes, positions):
        """Of layer ``layer`` of the ``kind`` (``"full"`` | ``"window"``,
        counted within the kind) the K and V rows of ``lanes`` [n] at
        ``positions`` [n, m] (a window layer's: ring slots): ``([n, m, KV,
        head_dim], [n, m, KV, v_head_width])``, the stored bits, the keys
        whole however they are held (packed rests put back beside their
        parts, padding dropped). A jitted gather of the rows asked; no
        layer is materialised. ``cache``: the serving cache, or a prefill's
        slab (the same leaves stacked over a kind's layers, a row a
        prompt)."""
        window = kind == "window"
        k, v = ("wk", "wv") if window else ("k", "v")
        return _row_gather(self._packed[window], self.cfg.head_dim)(
            cache[k][layer], cache[v][layer], lanes, positions)

    def read_block(self, cache_len: int) -> int:
        """The block of positions the full layers' ragged walk streams over
        a cache ``cache_len`` long: ``kv_rows_read`` counts a live lane's
        length rounded up to it."""
        import jax.numpy as jnp

        from ..ops.decode_attention import walk_block

        cfg = self.cfg
        return walk_block(cfg.n_kv_heads, self._key_held(False),
                          jnp.dtype(cfg.dtype), cache_len, cfg.v_head_width)

    # -- params ----------------------------------------------------------------

    # ``wq`` and ``wk`` are drawn this much wider than N(0, 1 / fan_in):
    # under unit draws a head's scores have unit deviation and a softmax
    # over thousands of keys is all but flat, so that a wrong rotary, scale
    # or mask cannot be told from rounding (the joyai and lfm2 blocks'
    # finding). At sqrt(3) on both sides the scores' deviation is 3
    QK_DRAW = 3.0 ** 0.5
    # the sinks' deviation about ``_sink_mean()``, which gives a sink a
    # fifth of a head's mass over a full window: the draw then spans a few
    # percent to a half, and a sink left out, or put on the full layers too,
    # is another model. (At zero it would weigh e^-9 at the published window.)
    SINK_DRAW = 1.0

    def _sink_mean(self) -> float:
        """Scores of deviation ``QK_DRAW``^2 = 3 over a full window have the
        log-sum-exp ``ln(window) + 9 / 2`` (9.35 at 128); a sink ``ln 4``
        under it takes a fifth: 7.97 at the published window."""
        return float(np.log(self.cfg.swa_window)
                     + 0.5 * self.QK_DRAW ** 4 - np.log(4.0))

    def init_params(self, seed: int = 0):
        """Seeded float32 draw, a key a layer (``init_layer``) and one for
        the embedding and head (``init_top``). Matrices N(0, 1 / fan_in),
        ``wq`` and ``wk`` times ``QK_DRAW``, the sinks N(``_sink_mean()``,
        ``SINK_DRAW``^2), the
        embedding N(0, 1); norms ones, ``expert_bias`` zeros (as
        published). The projections that write to the residual stream
        (``wo``, ``w2``, ``we2``) are scaled by ``residual_scale``."""
        import jax

        keys = jax.random.split(jax.random.PRNGKey(seed), self.cfg.n_layers + 1)
        return dict(
            self.init_top(keys[-1]),
            layers=[self.init_layer(keys[l], window, routed)
                    for l, (window, routed)
                    in enumerate(zip(self._window, self._routed))])

    def init_top(self, key):
        """The embedding, the final norm and the untied head."""
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        D, V = cfg.d_model, cfg.vocab_size
        k_embed, k_head = jax.random.split(key)
        return {
            "embed": jax.random.normal(k_embed, (V, D), jnp.float32),
            "ln_f": jnp.ones((D,), jnp.float32),
            "unembed": jax.random.normal(k_head, (D, V), jnp.float32)
            / np.sqrt(D),
        }

    def init_layer(self, key, window: bool, routed: bool):
        """One layer's draw: a function of its key and its kinds alone, so
        a caller may draw (and cast) the layers one at a time under one
        compiled program a kind."""
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        D, Dk, Dv, H = cfg.d_model, cfg.head_dim, cfg.v_head_width, cfg.n_heads
        kv = self._kv_heads(window)
        E, Fe = cfg.n_routed_experts, cfg.expert_width
        keys = iter(jax.random.split(key, 12))
        res = float(cfg.residual_scale)

        def init(shape, fan_in, scale=1.0):
            return jax.random.normal(next(keys), shape, jnp.float32) * (
                scale / np.sqrt(fan_in))

        ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
        p = {
            "ln_op": ones(D), "ln_ffn": ones(D),
            "wq": init((D, H * Dk), D, self.QK_DRAW),
            "wk": init((D, kv * Dk), D, self.QK_DRAW),
            "wv": init((D, kv * Dv), D),
            "wo": init((H * Dv, D), H * Dv, res),
        }
        if window:
            p["sink"] = self._sink_mean() + self.SINK_DRAW * (
                jax.random.normal(next(keys), (H,), jnp.float32))
        if not routed:
            F = cfg.d_ff
            p.update({"w1": init((D, F), D), "w3": init((D, F), D),
                      "w2": init((F, D), F, res)})
            return p
        p.update({
            "router": init((D, E), D),
            "expert_bias": jnp.zeros((E,), jnp.float32),
            "we1": init((self._n_held, D, Fe), D),
            "we3": init((self._n_held, D, Fe), D),
            "we2": init((self._n_held, Fe, D), Fe, res),
        })
        return p

    def burst_params(self, params):
        """Every layer's q / k / v projection weights [D, out] held [out,
        D]: the burst's projections of its 64 rows consume them
        contraction-minor, and handed the stored layout the TPU compiler
        relays all 21 at the top of every burst (830 MB written and read
        again, all of the burst's scratch: ``tools/burst_hlo_check.py``
        names them; the evabyte and llama blocks' finding). Where a kind's
        keys are held packed, ``wq`` and ``wk`` go under ``<name>_cut_t``
        with their outputs in the order the step hands them on: every
        head's first 128 dims, then every head's rest (heads in order, so
        the rests of ``wk`` ARE the packed rows): the step then cuts with a
        slice at a multiple of 128 and a reshape, no gather a step. Only
        where the rotary lies inside the first 128, which the step rotates
        after the cut. (No serving mesh: ``serving_refuses``.)"""
        import jax.numpy as jnp

        cut = self.cfg.rotary_dim <= self._LANES
        layers = []
        for p, window in zip(params["layers"], self._window):
            p = self.relaid(p, ("wq", "wk", "wv"))
            for name in ("wq", "wk") if cut and self._packed[window] else ():
                w = p.pop(name + self._RELAID)
                heads = w.reshape(-1, self.cfg.head_dim, w.shape[-1])
                p[name + "_cut" + self._RELAID] = jnp.concatenate([
                    heads[:, :self._LANES].reshape(-1, w.shape[-1]),
                    heads[:, self._LANES:].reshape(-1, w.shape[-1])])
            layers.append(p)
        return {**params, "layers": layers}

    # -- the cache ---------------------------------------------------------------

    def init_cache(self, batch: int, max_seq=None):
        """``{"k", "v"}``: a [batch, KV + packed, T, 128] (keys held
        packed; else [batch, KV, T, key row]) / [batch, KV, T, Dv] pair a
        FULL layer; ``{"wk", "wv"}``: the same with KVw heads and
        ``swa_window`` rows a WINDOW layer, the rings; lists in the layers'
        order."""
        import jax.numpy as jnp

        cfg = self.cfg
        T = max_seq or cfg.max_seq
        dt = jnp.dtype(cfg.dtype)
        cache = {}
        for (k, v), n, window, rows in (
                (("k", "v"), self._n_full, False, T),
                (("wk", "wv"), self._n_window, True, cfg.swa_window)):
            if n or k == "k":
                kv, packed = self._kv_heads(window), self._packed[window]
                held = self._LANES if packed else self._key_row
                cache[k] = [jnp.zeros((batch, kv + packed, rows, held), dt)
                            for _ in range(n)]
                cache[v] = [jnp.zeros((batch, kv, rows, cfg.v_head_width), dt)
                            for _ in range(n)]
        return cache

    # -- one layer ---------------------------------------------------------------

    def _norm(self, x, w):
        return _rms_norm(x, w.astype(x.dtype), self.cfg.norm_eps)

    def _rotated(self, x, positions, theta):
        """Half-split rotary over the first ``rotary_dim`` dims of each
        head of x [B, h, T, Dk]; the rest as they are."""
        import jax.numpy as jnp

        r = self.cfg.rotary_dim
        return jnp.concatenate(
            [_rope(x[..., :r], positions, theta), x[..., r:]], axis=-1)

    def _heads(self, p, a, positions, window: bool):
        """The layer's projections of the normed input a [B, T, D]: q [B,
        H, T, Dk] and k [B, KV, T, Dk] rotated by the kind's base, v [B,
        KV, T, Dv]."""
        cfg = self.cfg
        dt = a.dtype
        B, T, _ = a.shape
        kv = self._kv_heads(window)
        theta = cfg.swa_rope_theta if window else cfg.rope_theta
        q = self.project(p, "wq", a).reshape(B, T, cfg.n_heads, cfg.head_dim)
        k = self.project(p, "wk", a).reshape(B, T, kv, cfg.head_dim)
        v = self.project(p, "wv", a).reshape(B, T, kv, cfg.v_head_width)
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        return (self._rotated(q, positions, theta),
                self._rotated(k, positions, theta), v)

    def _key_rows(self, k):
        """Keys [..., KV, T, Dk] as the cache holds them: packed where the
        shapes meet the rule ([..., KV + packed, T, 128]), else [..., key
        row], zeros past the key."""
        from ..ops.decode_attention import pack_keys, packed_key_rows

        packed = k.ndim > 2 and packed_key_rows(k.shape[-1], k.shape[-3])
        return pack_keys(k, packed) if packed else self._padded(k)

    def _padded(self, x):
        """x [..., Dk] -> [..., key row], zeros past the key."""
        import jax.numpy as jnp

        pad = self._key_row - x.shape[-1]
        return x if not pad else jnp.pad(
            x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])

    def _cut_heads(self, p, a, pos, window: bool):
        """The step's projections of a [B, 1, D] for a kind whose keys are
        held packed, rotated: the queries' first 128 dims [B, H, 1, 128]
        and their rests [B, H, 1, Dk - 128], this step's key rows as the
        cache holds them [B, KV + packed, 1, 128] (a position's rests pack
        by a reshape) and v [B, KV, 1, Dv]. Against the burst's tree
        (``burst_params``) a cut is a slice at a multiple of 128; against
        the stored one the heads are rotated whole and cut after."""
        import jax.numpy as jnp

        cfg = self.cfg
        B, lanes, Dk = a.shape[0], self._LANES, cfg.head_dim
        theta = cfg.swa_rope_theta if window else cfg.rope_theta

        def cut(name, heads):
            if name in p or name + self._RELAID in p:
                y = self._rotated(
                    self.project(p, name, a).reshape(B, heads, 1, Dk),
                    pos[:, None], theta)
                return y[..., :lanes], y[..., lanes:]
            y = self.project(p, name + "_cut", a)
            part = y[..., :heads * lanes].reshape(B, heads, 1, lanes)
            return (self._rotated(part, pos[:, None], theta),
                    y[..., heads * lanes:].reshape(B, heads, 1, Dk - lanes))

        kv = self._kv_heads(window)
        q, q_rest = cut("wq", cfg.n_heads)
        k, k_rest = cut("wk", kv)
        rows = jnp.concatenate(
            [k, k_rest.reshape(B, self._packed[window], 1, lanes)], axis=1)
        v = self.project(p, "wv", a).reshape(B, kv, 1, cfg.v_head_width)
        return q, q_rest, rows, v

    def _row_queries(self, q):
        """The step's queries [B, H, 1, Dk] against PADDED key rows: [B, H,
        1, key row], zero past the key and times ``sqrt(key row / Dk)``:
        ``ops.decode_attention`` scales the scores by ``1 / sqrt(key row)``
        where a head's is ``1 / sqrt(Dk)``. The product is float32's,
        rounded once. (Packed keys need neither: ``_cut_heads``.)"""
        import jax.numpy as jnp

        if self._key_row == q.shape[-1]:
            return q
        wide = q.astype(jnp.float32) * np.sqrt(self._key_row / q.shape[-1])
        return self._padded(wide.astype(q.dtype))

    def _attention_out(self, p, o):
        """o [B, H, T, Dv] -> the attention's output [B, T, D]: the value
        scale, then ``wo``."""
        import jax.numpy as jnp

        B, _, T, _ = o.shape
        o = (o.astype(jnp.float32) * self.cfg.value_scale).astype(o.dtype)
        return o.transpose(0, 2, 1, 3).reshape(B, T, -1) @ p["wo"].astype(o.dtype)

    def _ffn(self, p, h, routed, live=None, real=None):
        """h [B, T, D] after the attention -> the layer's output, a routed
        layer's picks [B, T, k] over ALL experts (else None) and, for a
        decode step (``live`` [B]), (held experts touched, rows routed,
        rows that landed here); for a prefill, its grouped experts'
        ``GROUPED_COUNTS``. ``real`` [B, T] bool (a prefill's): the rows
        that are some sequence's tokens."""
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

    def _head(self, params, x, last_index=None, every=False):
        """The final norm and the untied head."""
        import jax.numpy as jnp

        if not every:
            x = self._last_rows(x, last_index)
        x = self._norm(x, params["ln_f"])
        return (x @ params["unembed"].astype(x.dtype)).astype(jnp.float32)

    # -- whole-prompt forward --------------------------------------------------------

    def _ring_rows(self, rows, lens):
        """A prompt's rows [B, KV, T, .] as its ring holds them after its
        last REAL token (``lens`` [B]: the prompts are right-padded): [B,
        KV, R, .] with ``R = min(T, swa_window)``, slot ``s`` the row of
        the last position ``p < len`` with ``p mod swa_window == s`` (a
        slot no position of a short prompt reaches holds row 0, which no
        read admits: a ring is read up to ``min(len, swa_window)``)."""
        import jax.numpy as jnp

        W = self.cfg.swa_window
        R = min(rows.shape[2], W)
        last = (lens - 1)[:, None]
        at = last - (last - jnp.arange(R, dtype=jnp.int32)[None]) % W
        return jnp.take_along_axis(
            rows, jnp.maximum(at, 0)[:, None, :, None], axis=2)

    def _forward(self, params, tokens, pad_to, last_index):
        """One pass over whole prompts tokens [B, T], a sequence's real
        tokens being its first ``last_index + 1``: the residual stream,
        the cache's leaves as ``prefill`` stacks them (None without
        ``pad_to``), the routed layers' picks [B, T, k] and the
        ``prefill_counter_names``."""
        import jax.numpy as jnp

        from ..ops import attention as prefill_attention

        cfg = self.cfg
        B, T = tokens.shape
        lens = (jnp.full((B,), T, jnp.int32) if last_index is None
                else jnp.asarray(last_index, jnp.int32) + 1)
        x = self._embed_tokens(params, tokens)
        positions = jnp.arange(T)
        real = (None if last_index is None
                else positions[None, :] < lens[:, None])
        leaves = {"k": [], "v": [], "wk": [], "wv": []}
        picked = []
        grouped = jnp.zeros((2,), jnp.int32)
        for p, window, routed in zip(params["layers"], self._window,
                                     self._routed):
            q, k, v = self._heads(p, self._norm(x, p["ln_op"]), positions,
                                  window)
            rep = cfg.n_heads // self._kv_heads(window)
            how = dict(window=cfg.swa_window, sink=p["sink"],
                       name="swa_prefill_attention") if window else {}
            o = prefill_attention(
                q, jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1),
                causal=True, **how)
            x = x + self._attention_out(p, o)
            if pad_to is not None and window:
                leaves["wk"].append(self._ring_rows(self._key_rows(k), lens))
                leaves["wv"].append(self._ring_rows(v, lens))
            elif pad_to is not None:
                pad = ((0, 0), (0, 0), (0, pad_to - T), (0, 0))
                leaves["k"].append(jnp.pad(self._key_rows(k), pad))
                leaves["v"].append(jnp.pad(v, pad))
            x, picks, counts = self._ffn(p, x, routed, real=real)
            if routed:
                picked.append(picks)
                grouped = grouped + counts
        slab = None
        if pad_to is not None:
            slab = {name: jnp.stack(each)
                    for name, each in leaves.items() if each}
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
        rows of these prompts, each leaf stacked over the layers of its
        kind, as ``init_cache`` lays a layer's out: ``k`` [Lf, B, KV +
        packed, max_seq, 128] (else [Lf, B, KV, max_seq, key row]), ``v`` [Lf,
        B, KV, max_seq, Dv]; ``wk``, ``wv`` [Lw, B, ., min(T, swa_window), .]:
        each prompt's rings as its last REAL token leaves them, whatever
        the prompts were padded to."""
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
        ``pos + 1`` for a lane whose output anyone reads, 0 for one that is
        idle or done: such a lane's rows and rings stay as they are.
        ``write_pos``: ``pos``, or ``park_index`` for a lane that must
        write nothing. ``attn_len`` bounds the full layers' read where the
        step takes the dots; a ring is read whole at most."""
        return self._step(params, cache, tokens, pos, attn_len, write_pos,
                          lens)[:3]

    def _reads(self, cache, lens, ring_lens, attn_len, mesh):
        """``(kv_rows_read, kv_positions_read_window)`` of one step: what
        ``decode_attention`` streams over the full layers and over the
        rings, by the lowering that runs. The kernel walks each live
        lane's own rows in whole blocks (``walk_block`` asked); the dots
        read the static bound of EVERY lane, idle ones too. Chosen as the
        op chooses (``reads_ragged`` for a TPU's lowering, then the
        platform)."""
        import jax.numpy as jnp
        from jax import lax

        from ..ops.decode_attention import reads_ragged, walk_block

        dt = jnp.dtype(self.cfg.dtype)
        out = []
        for names, lengths, n, window in (
                (("k", "v"), lens, self._n_full, False),
                (("wk", "wv"), ring_lens, self._n_window, True)):
            if not n:
                out.append(jnp.int32(0))
                continue
            k, v = cache[names[0]][0], cache[names[1]][0]
            B, _rows, T, width = one = k.shape
            bound = T if attn_len is None else min(int(attn_len), T)
            every = jnp.int32(B * bound)
            block = walk_block(self._kv_heads(window), self._key_held(window),
                               k.dtype, T, v.shape[-1])
            if reads_ragged("tpu", (B, self.cfg.n_heads, 1, width), one,
                            (dt, k.dtype, v.dtype), mesh, v.shape[-1],
                            self._packed[window]):
                read = lax.platform_dependent(
                    jnp.minimum(lengths, bound),
                    tpu=lambda n_, block=block: jnp.sum(
                        -(-n_ // block) * block),
                    default=lambda n_, every=every: every)
            else:
                read = every
            out.append(read * n)
        return out

    def _step(self, params, cache, tokens, pos, attn_len=None, write_pos=None,
              lens=None):
        """``decode_step_cache`` and the routed layers' picks [B, 1, k]."""
        import jax.numpy as jnp

        from ..ops import decode_attention

        cfg = self.cfg
        W = cfg.swa_window
        pos = pos.astype(jnp.int32)
        wp = pos if write_pos is None else write_pos.astype(jnp.int32)
        lens = pos + 1 if lens is None else lens.astype(jnp.int32)
        live = lens > 0
        mesh = self._serving_mesh
        # the ring: a live lane's row lands at its position modulo the
        # window, a parked or idle lane's past the ring's end (dropped);
        # the read is bounded by the slots written
        parked = wp >= cache["k"][0].shape[2]
        ring_at = jnp.where(live & ~parked, wp % W, W)
        ring_lens = jnp.minimum(lens, W)
        x = self._embed_tokens(params, tokens)  # [B, 1, D]
        new = {name: [] for name in cache}
        picked = []
        touched = routed_rows = held = jnp.int32(0)
        for p, window, routed in zip(params["layers"], self._window,
                                     self._routed):
            a = self._norm(x, p["ln_op"])
            if self._packed[window]:
                q, q_rest, k, v = self._cut_heads(p, a, pos, window)
                how = dict(mesh=mesh, q_rest=q_rest)
            else:
                q, k, v = self._heads(p, a, pos[:, None], window)
                q, k = self._row_queries(q), self._key_rows(k)
                how = dict(mesh=mesh)
            if window:
                at = len(new["wk"])
                o, nk, nv = decode_attention(
                    q, cache["wk"][at], cache["wv"][at], k, v, ring_at,
                    ring_lens - 1, ring_lens, sink=p["sink"],
                    name="swa_ring_attention", **how)
                new["wk"].append(nk)
                new["wv"].append(nv)
            else:
                at = len(new["k"])
                o, nk, nv = decode_attention(
                    q, cache["k"][at], cache["v"][at], k, v, wp, pos, lens,
                    attn_len=attn_len, **how)
                new["k"].append(nk)
                new["v"].append(nv)
            x = x + self._attention_out(p, o)
            x, picks, counts = self._ffn(p, x, routed, live=live)
            if routed:
                picked.append(picks)
                touched, routed_rows, held = (
                    touched + counts[0], routed_rows + counts[1],
                    held + counts[2])
        rows_read, ring_read = self._reads(cache, lens, ring_lens, attn_len,
                                           mesh)
        counts = jnp.stack([
            touched, routed_rows, jnp.int32(self._n_routed_layers), held,
            rows_read, jnp.sum(lens) * self._n_full,
            ring_read, jnp.sum(ring_lens) * self._n_window,
            jnp.sum(lens) * self._n_window])
        return self._head(params, x), new, counts, picked
