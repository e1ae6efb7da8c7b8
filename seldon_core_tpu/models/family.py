"""DecoderFamily: everything the scheduler and the server may ask of a decoder.

A decoder family is ONE file, ``models/<family>.py`` (a class that extends
``DecoderFamily``, a dataclass that extends ``llm.LLMConfig`` with the
family's own fields), and one line of ``FAMILIES``. ``serving/continuous.py``
and ``servers/generateserver.py`` name no family and probe for nothing: what
they call is on this page, in the order the batcher asks it: declarations;
what every family implements; questions whose default is the answer of a
cache of one K/V row a position (``{"k", "v"}``, one [S, KV, T, Dh] pair a
layer: the llama and afmoe blocks'); optional paths, each defined here ONCE
as a typed refusal and overridden by the family that serves it.
``docs/generate.md`` section 1b is the same list as a table, with the
scheduler decision that reads each answer.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Dict, Optional, Tuple

from .base import ServedModel

# ``LLMConfig.block`` -> the class that serves it, imported when asked for
# (as ``models/__init__.py:build`` does): the one place a family's name is
# mapped to code. ``DecoderLM(block=...)`` looks it up.
FAMILIES: Dict[str, str] = {
    "llama": "seldon_core_tpu.models.llm.DecoderLM",
    "afmoe": "seldon_core_tpu.models.afmoe.AfmoeLM",
    "qwen3_next": "seldon_core_tpu.models.qwen3_next.Qwen3NextLM",
    "joyai_llm_flash": "seldon_core_tpu.models.joyai_llm_flash.JoyaiLLMFlashLM",
    "evabyte": "seldon_core_tpu.models.evabyte.EvaByteLM",
    "sdar_moe": "seldon_core_tpu.models.sdar_moe.SdarMoeLM",
    "lfm2_moe": "seldon_core_tpu.models.lfm2_moe.Lfm2MoeLM",
    "jamba": "seldon_core_tpu.models.jamba.JambaLM",
    "mimo_v2": "seldon_core_tpu.models.mimo_v2.MimoV2LM",
}


def family_class(block: str):
    """The registered class of a block variant."""
    if block not in FAMILIES:
        raise ValueError(f"unknown block variant {block!r}")
    module, name = FAMILIES[block].rsplit(".", 1)
    return getattr(importlib.import_module(module), name)


class UnsupportedByModel(ValueError):
    """A serving feature was asked of a model family that has no path for
    it (``DecoderFamily.serving_refuses``): refused at load, not computed
    as something else."""


class DecoderFamily(ServedModel):
    # -- declarations ------------------------------------------------------
    # the dataclass of this family's configuration: ``llm.LLMConfig`` or an
    # extension of it by the fields only this family reads. ``__init__``
    # takes the keywords that are its fields; the rest land in ``_extra``
    config_class: type = None
    # what ``decode_step_cache`` returns after its cache, where a family
    # counts what its step did: names of the int32 vector's entries, which
    # the batcher adds into ``stats``. The llama block has none and returns
    # no third result.
    step_counter_names: Tuple[str, ...] = ()
    # likewise for a prefill: a family that names counters here has a
    # ``prefill_counted`` that returns ``prefill``'s two results and the
    # int32 vector, which the batcher adds up on the device (its insert)
    # and brings home beside the next burst it reads
    prefill_counter_names: Tuple[str, ...] = ()
    # serving features this family has no path for -> why; the batcher
    # refuses them typed at load (``UnsupportedByModel``)
    serving_refuses: Dict[str, str] = {}
    # what ``set_serving_mesh`` armed; None in a family that refuses ``mesh``
    _serving_mesh = None

    def __init__(self, **config):
        names = {f.name for f in dataclasses.fields(self.config_class)}
        self.cfg = self.config_class(
            **{k: v for k, v in config.items() if k in names})
        self._extra = {k: v for k, v in config.items() if k not in names}
        self.example_input_shape = (16,)  # token ids
        self.compute_dtype = self.cfg.dtype

    def check_serves(self, **asked: bool) -> None:
        """Raise ``UnsupportedByModel`` for the first feature that is
        asked for (``speculation=True``, ...) and that this family
        refuses. The server and the batcher call it at load."""
        for feature, why in self.serving_refuses.items():
            if asked.get(feature):
                raise UnsupportedByModel(
                    f"{type(self).__name__} does not serve with {feature}: {why}")

    def config_differs(self, other: "DecoderFamily") -> list:
        """The configuration fields, sorted, in which ``other``'s
        architecture differs from this model's (another family's own fields
        among them): empty where a checkpoint of ``other`` can be served by
        this model's executables. ``residual_scale`` only shapes synthetic
        INIT draws, not the forward."""
        mine, theirs = dataclasses.asdict(self.cfg), dataclasses.asdict(other.cfg)
        return sorted(k for k in (set(mine) | set(theirs)) - {"residual_scale"}
                      if mine.get(k) != theirs.get(k))

    @staticmethod
    def params_swappable(old, new) -> "Tuple[bool, str]":
        """Whether ``new`` can replace ``old`` under live serving without
        recompiling a single executable: the jitted prefill/decode/burst
        functions are specialized on the param pytree's STRUCTURE and
        every leaf's shape+dtype, so a hot-swap (continuous batching's
        ``request_weight_swap``) is only sound when both match leaf for
        leaf. Returns ``(ok, reason)`` — reason names the first offender
        so a wrong-checkpoint swap fails with an actionable message
        instead of an XLA retrace mid-traffic."""
        import jax

        old_leaves, old_def = jax.tree_util.tree_flatten(old)
        new_leaves, new_def = jax.tree_util.tree_flatten(new)
        if old_def != new_def:
            return False, (
                "param tree structure differs (different architecture or "
                "checkpoint family)"
            )
        paths = [
            jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(old)[0]
        ]
        for path, a, b in zip(paths, old_leaves, new_leaves):
            sa = getattr(a, "shape", None)
            sb = getattr(b, "shape", None)
            if sa != sb:
                return False, f"{path}: shape {sb} != served {sa}"
            da = getattr(a, "dtype", None)
            db = getattr(b, "dtype", None)
            if da != db:
                return False, f"{path}: dtype {db} != served {da}"
        return True, ""

    # -- what every family implements (``init_params``, ``apply``:
    # ``ServedModel``'s) -----------------------------------------------------

    def init_cache(self, batch: int, max_seq: Optional[int] = None):
        import jax.numpy as jnp

        cfg = self.cfg
        T = max_seq or cfg.max_seq
        shape = (cfg.n_layers, batch, cfg.n_kv_heads, T, cfg.head_dim)
        dt = jnp.dtype(cfg.dtype)
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}

    def cache_layers(self, batch: int, max_seq: Optional[int] = None):
        """The cache as the serving bursts carry it: a dict of kinds ("k"
        and "v"; a family may declare more), each a list over the layers
        that have the kind of one array whose first axis is the lane: a
        stacked kind of ``init_cache`` cut into its layers, a kind it laid
        out as such a list already as it is (the cache is allocated once)."""
        return {
            name: kind if isinstance(kind, list) else [
                kind[l] for l in range(kind.shape[0])]
            for name, kind in self.init_cache(batch, max_seq).items()
        }

    def prefill(self, params, prompt, max_seq: int, last_index=None):
        """Batched prefill: ONE forward over the whole prompt [B, Tp].
        Returns (logits [B, V] at ``last_index`` ([B] int32: each row's
        last REAL token where the batch is right-padded to a bucket;
        default the final position), the cache's rows of these prompts:
        ``cache_layers``' kinds, each stacked over its layers)."""
        raise NotImplementedError

    def prefill_counted(self, params, prompt, max_seq: int, last_index=None):
        """``prefill`` and, after the cache's rows, its
        ``prefill_counter_names`` as an int32 vector: what the batcher
        calls in ``prefill``'s place where a family names such counters."""
        raise NotImplementedError

    def decode_step_cache(self, params, cache, tokens, pos, **how):
        """One token a lane over the dict ``cache_layers`` lays out:
        tokens [B, 1] at ``pos`` [B]. Returns ``(logits [B, V], cache,
        *counts)``, the one step the serving bursts call whatever kinds a
        family's cache holds; ``counts`` the step's ``step_counter_names``
        as an int32 vector where it names any. ``how``: ``attn_len``,
        ``write_pos``, ``lens``, as ``decode_step_ragged_list`` takes them,
        which this default calls over the "k" and "v" kinds."""
        logits, ks, vs, *counts = self.decode_step_ragged_list(
            params, cache["k"], cache["v"], tokens, pos, **how)
        return (logits, {"k": ks, "v": vs}, *counts)

    def attention_kinds(self) -> Tuple[Tuple[int, Optional[int]], ...]:
        """``(layers, window)`` per kind of attention layer: how many
        layers read the cache that way and how many positions back a
        query sees (None: all of them). The scheduler's arithmetic of
        what a burst reads (``kv_positions_*``) takes the kinds from
        here; every layer of the llama block reads everything."""
        return ((self.cfg.n_layers, None),)

    def row_cache_windows(self) -> Tuple[int, ...]:
        """The window of every kind of layer that has one and holds a row a
        position, ``max_seq`` long: the scheduler counts what such a layer's
        step streams (``kv_positions_*_window``: from the block that holds
        the window's start) from these. A family whose window layers hold a
        ring instead counts their reads in its own step
        (``step_counter_names``) and answers ``()``."""
        return tuple(w for _n, w in self.attention_kinds() if w is not None)

    # -- what the scheduler asks of a cache it did not lay out -------------
    # (serving/continuous.py names no kind and no family: these say what a
    # position costs and how a burst reads it)

    def position_layers(self, cache):
        """The arrays of ``cache_layers``' dict that hold one row a
        position, positions along the second-to-last axis (a lane's
        recurrent state, which has no position axis, is not among them):
        what a decode step writes one row each of."""
        return [*cache["k"], *cache["v"]]

    def cache_position_bytes(self, cache) -> int:
        """Bytes ONE cached position occupies over every layer of
        ``cache``, by the live arrays' dtypes and shapes (an array's bytes
        over its lanes and positions: ``itemsize x KV x Dh`` of a [S, KV,
        T, Dh] array): the unit of the modeled burst read and of the
        pressure ledger."""
        return sum(a.nbytes // (a.shape[0] * a.shape[-2])
                   for a in self.position_layers(cache))

    def park_index(self, cache) -> int:
        """The write position the stop-aware burst gives a lane that must
        write nothing: past the end of every kind's positions, so that the
        scatter and the kernel drop the row (a family whose kinds differ in
        length, or wrap, says where that is)."""
        return self.position_layers(cache)[0].shape[-2]

    def lane_cache_bytes(self, cache):
        """``positions -> bytes``: what a lane that holds ``positions``
        positions occupies of ``cache`` over every layer, the pressure
        ledger's and the insert records' price. One row a position here;
        a family whose position costs more in one kind than in another
        prices its own."""
        per_position = self.cache_position_bytes(cache)
        return lambda positions: positions * per_position

    # the padded lengths a prefill takes past the batcher's own buckets:
    # every multiple of this below the cache's length. The batcher's
    # buckets end at 1792, and a prompt of 2,048 padded to a ``max_seq`` of
    # 4096 pays twice its projections and four times its attention. 512 is
    # the flash kernel's key tile (``ops.flash_attention._tile``) and a
    # multiple of every family's chunk and block: a constant, not a knob
    PREFILL_STEP = 512

    def prefill_lengths(self, buckets, max_seq: int):
        """The padded lengths this family's ``prefill`` takes, ascending: of
        the batcher's prompt buckets those it takes (every one here), then
        every multiple of ``PREFILL_STEP`` past the last and below
        ``max_seq``, so that a prompt past the buckets is prefilled at its
        own length rounded up and not at the cache's. A prompt past them
        all goes to ``max_seq``. A length is compiled where ``warm()`` was
        told of a prompt that pads to it, else on first use."""
        step = self.PREFILL_STEP
        more = range(-(-(max(buckets, default=0) + 1) // step) * step,
                     max_seq, step)
        return (*buckets, *more)

    def prefill_rows_max(self, bucket: int, added: bool = False) -> int:
        """The most prompts one batched prefill in ``bucket`` takes;
        ``added`` where the batcher was not configured with the length and
        ``prefill_lengths`` added it. Such a length takes ONE: a batched
        prefill exists to fill the matrix unit and to read the weights once
        for several prompts, which the rows of one prompt past the
        batcher's last bucket do already, a turn that admits four of one
        long length is rare outside the ramp, and so a length costs one
        executable to warm and not three."""
        return 1 if added else 8

    def block_tokens(self) -> int:
        """Positions ONE decode step covers in a lane: 1, a token a lane and
        step, everywhere but in a family that generates by blocks, whose
        step is a pass over a block of this many positions
        (``decode_block_cache``) that a lane's mask bits say are filled in
        or not, and which the scheduler then drives through
        ``block_unmask`` pass by pass until a block has no masked position,
        one more pass commits its rows to the cache, and its tokens go to
        the client. The cache's length is a multiple of it."""
        return 1

    def admissions_per_turn(self) -> int:
        """The most prompts the scheduler admits between two decode bursts;
        0 for every free lane, so that one batched prefill takes them
        together. A family whose prefill holds the device for long says
        fewer, and the live lanes decode between two of them."""
        return 0

    def prefill_slab_bytes(self, rows: int, bucket: int) -> int:
        """Bytes of the slab a batched prefill of ``rows`` prompts in
        ``bucket`` returns (a transient beside params and cache)."""
        cfg = self.cfg
        return (2 * cfg.n_layers * rows * cfg.n_kv_heads * bucket
                * cfg.head_dim * 2)

    def burst_params(self, params):
        """``params`` as the serving bursts take them: the tree the batcher
        derives once where it takes its params (and again at a weight
        swap) and hands to the executables that run the decode step alone
        (the bursts, a checkpoint's replay); prefill and every other
        executable keep ``params``. The default is the object it was
        given: the burst is then the program it always was. A family whose
        compiled burst wants a weight in another layout than the stored one
        (the TPU compiler else relays it at the top of EVERY burst, a copy
        in and out of HBM of a weight that never changes) answers with a
        tree that holds that weight in that layout under another name
        (``relaid``), and its step contracts against the leaf the tree it
        is handed has (``project``). Under a serving mesh the answer is
        ``params``: ``param_sharding``'s column and row sets name the
        stored layout."""
        return params

    # the suffix of a leaf ``relaid`` holds contraction-minor, and the one
    # place that names it
    _RELAID = "_t"

    @classmethod
    def relaid(cls, p: dict, names) -> dict:
        """``p`` with each weight of ``names`` ([..., D, out]) replaced by
        its transpose over the last two axes ([..., out, D], row-major)
        under ``<name>_t``: what a projection of a few rows fused with what
        follows it consumes on a TPU, the contraction dimension minor."""
        import jax.numpy as jnp

        out = {k: v for k, v in p.items() if k not in names}
        for name in names:
            out[name + cls._RELAID] = jnp.swapaxes(p[name], -1, -2)
        return out

    @classmethod
    def project(cls, p: dict, name: str, x, **how):
        """``x [..., D] @ p[name] [D, out]`` in ``x``'s dtype, against the
        leaf ``p`` has: the stored weight, or its ``relaid`` transpose (a
        Python test of the tree while tracing: a trace handed the stored
        tree is the code it always was). ``how``: ``dot_general``'s
        keywords (``preferred_element_type``)."""
        from jax import lax

        w = p.get(name)
        if w is not None:
            dims = (((x.ndim - 1,), (0,)), ((), ()))
        else:
            w = p[name + cls._RELAID]
            dims = (((x.ndim - 1,), (1,)), ((), ()))
        return lax.dot_general(x, w.astype(x.dtype), dims, **how)

    def burst_reads_ragged(self, cache, mesh=None) -> bool:
        """Whether the decode step over ``cache``, lowered for the platform
        its arrays live on, bounds each lane's read by the lane's own
        length (``ops.decode_attention.reads_ragged``): a burst then needs
        no bucket and no executable per bucket."""
        import jax.numpy as jnp

        from ..ops.decode_attention import reads_ragged

        layer0 = cache["k"][0]
        return reads_ragged(
            next(iter(layer0.devices())).platform,
            (layer0.shape[0], self.cfg.n_heads, 1, layer0.shape[3]),
            layer0.shape,
            (jnp.dtype(self.cfg.dtype), layer0.dtype, cache["v"][0].dtype),
            mesh,
        )

    def step_counters_in_kernel(self, cache, mesh=None) -> Dict[str, Optional[str]]:
        """``{stats key: step counter}``: the writes a step counts that the
        decode kernel lands itself where the step over ``cache`` is lowered
        for the platform its arrays live on (as ``kv_rows_written_in_kernel``
        shadows ``kv_rows_written``); the counter is None where another op
        writes them, and the key then stays 0. A family that names none gets
        no key: the default."""
        return {}

    # -- the K/V cache's own helpers, and the lookup every family shares ----

    def _embed_tokens(self, params, tokens):
        import jax.numpy as jnp

        dt = jnp.dtype(self.cfg.dtype)
        return params["embed"][tokens.astype(jnp.int32)].astype(dt)

    @staticmethod
    def _last_rows(x, last_index=None):
        """x [B, T, D] at each row's ``last_index`` ([B]; default the last
        position): [B, D]."""
        import jax.numpy as jnp

        if last_index is None:
            return x[:, -1]
        return x[jnp.arange(x.shape[0]), jnp.asarray(last_index, jnp.int32)]

    @staticmethod
    def _cache_write(cache, new, positions):
        """The ragged cache write by scatter: ``new`` [B, KV, W, Dh] lands
        in ``cache`` [B, KV, T, Dh] at ``positions`` [B, W]; a position
        outside [0, T) is DROPPED. It is
        ``ops.decode_attention.cache_write`` (why its window is one ``Dh``
        row is told there). The speculative and chunked windows, prefix
        prefill and the stacked scan write through this; the ragged
        single-position step hands its row to ``ops.decode_attention()``,
        which on a TPU lands it from inside the read's kernel."""
        from ..ops.decode_attention import cache_write

        return cache_write(cache, new, positions)

    @staticmethod
    def _cache_read(ck, cv, attn_len):
        """The ONE narrowed cache read: the prefix the scheduler proved can
        hold keys (``attn_len``: a STATIC bucket >= every lane's position
        + 1, so one executable per bucket; ``None`` reads it all). The
        write above always addresses the full cache — only the read
        narrows. The slice is an operand of the two dots that follow, not
        an array of its own."""
        from jax import lax

        if attn_len is None or attn_len >= ck.shape[2]:
            return ck, cv
        return (lax.slice_in_dim(ck, 0, attn_len, axis=2),
                lax.slice_in_dim(cv, 0, attn_len, axis=2))

    # -- the device-time ledger's price list (serving/profiler.py, off by
    # default, and its only reader in the program). On the interface until
    # ROADMAP D11 retires the ledger; a family prices its own ``n_params``,
    # ``flops_per_token`` and decode kinds -----------------------------------

    def flops_per_row(self, seq_len: int = None) -> float:
        """Full-forward FLOPs for one sequence (causal: average context T/2)."""
        T = int(seq_len or self.example_input_shape[0])
        return T * self.flops_per_token(T / 2.0)

    def kv_bytes_per_token(self) -> int:
        """K+V bytes ONE cached position occupies across every layer
        (bf16) — the per-(row, position) unit every read model below is
        priced in, and the closed-form twin of ``cache_position_bytes``
        (which reads the live cache's dtypes/shapes)."""
        cfg = self.cfg
        return cfg.n_layers * 2 * cfg.n_kv_heads * cfg.head_dim * 2

    def dispatch_read_bytes(
        self,
        kind: str,
        *,
        rows: int = 1,
        live: int = None,
        k: int = 1,
        bucket: int = 0,
        tokens: int = 0,
        param_bytes: float = None,
        kv_row_bytes: float = None,
    ) -> float:
        """Modeled HBM bytes READ by ONE warmed-executable dispatch of the
        given kind — the static cost model the serving-time device-time
        ledger attributes MBU with (``serving/profiler.py``).

        ``param_bytes``/``kv_row_bytes`` default to the unsharded bf16
        closed forms; the batcher passes its live (shard-aware) values.
        Decode-family bursts read the params once per step plus each
        row's bucketed KV columns (``live``, how many of ``rows`` decode,
        is for a family whose step reads by live lane: this one is priced
        by its rows); prefill-family dispatches read the
        params once and write (not read) their KV, so params dominate;
        splice/extract move ``tokens`` cache positions; a swap cast
        touches every param byte once."""
        if param_bytes is None:
            param_bytes = self.n_params() * 2.0
        if kv_row_bytes is None:
            kv_row_bytes = float(self.kv_bytes_per_token())
        if kind in ("decode_burst", "fused_burst", "spec_burst"):
            # a verify chunk is one full forward over gamma+1 positions per
            # lane; drafts are priced by the caller (their params differ)
            return k * (param_bytes + rows * bucket * kv_row_bytes)
        if kind in ("prefill", "chunk_prefill", "replay"):
            return param_bytes + tokens * kv_row_bytes
        if kind in ("splice", "insert", "extract"):
            return tokens * kv_row_bytes
        if kind == "swap_cast":
            return param_bytes
        return 0.0

    # -- the optional paths: the typed refusal here, the path in the family
    # that serves it. The batcher has refused the ``serving_refuses``
    # feature named beside each at load, before it could call the path ------

    def _no(self, what: str, feature: Optional[str] = None):
        """The one typed refusal: names the block and, where ``feature``
        guards the path, the family's reason for refusing the feature."""
        why = self.serving_refuses.get(feature) or (
            "it serves through what its class defines of prefill* and "
            "decode_*")
        raise UnsupportedByModel(
            f"the {self.cfg.block} block has no {what}: {why}")

    def decode_chunk_ragged_list(self, *a, **kw):
        """``(params, ks, vs, tokens [B, W], pos [B], attn_len=None) ->
        (logits [B, W, V], ks, vs)``: a window of tokens per lane in one
        forward: speculation's verify, a checkpoint's replay."""
        self._no("window of positions over a cache (speculation, "
                 "preemption's replay)", "speculation")

    def prefill_chunk(self, *a, **kw):
        """``(params, slab, tokens [1, C], start_pos, attn_len,
        last_index=None, want_logits=True) -> (logits | None, slab)``:
        extend a staging slab by one chunk."""
        self._no("chunked prefill (chunked_prefill)", "chunked_prefill")

    def prefill_with_prefix(self, *a, **kw):
        """``(params, prefix_kv, tokens [1, W], start_pos,
        last_index=None) -> (logits, suffix slab)`` over a cached prefix."""
        self._no("prefix splice (prefix_cache)", "prefix_cache")

    def param_sharding(self, *a, **kw):
        """``mesh``: params, cache and slabs sharded over a serving mesh:
        ``param_sharding(mesh, params)``, ``set_serving_mesh(mesh,
        shard_seq=False)`` (armed before any executable is traced),
        ``cache_sharding(mesh, kv_heads=None, shard_seq=False)`` of one
        per-layer buffer, ``slab_sharding(mesh, kv_heads=None)`` of a
        stacked staging slab."""
        self._no("serving mesh", "mesh")

    set_serving_mesh = cache_sharding = slab_sharding = param_sharding

    def decode_block_cache(self, *a, **kw):
        """Generation by blocks (``block_tokens() > 1``): a pass over a block
        of ``W`` positions a lane over the dict ``cache_layers`` lays out,
        ``(params, cache, tokens [B, W], base [B], masked=None [B, W] bool,
        attn_len=None, lens=None) -> (logits [B, W, V], cache, counts)``:
        the block's rows land at ``base .. base + W - 1`` (``base`` a
        multiple of ``W``; a later pass overwrites them, the pass over a
        block with nothing masked, the commit, leaves them), every query
        sees the lane's cache and the whole block, logits at each position
        itself; ``counts`` the family's ``step_counter_names``. And
        ``block_unmask(logits, tokens, masked, n_pass, alive, temps, keys,
        any_stoch) -> (tokens, masked, keys, counts)``: how a pass fills
        in: which masked positions of a lane take which token, by the
        family's configuration; ``counts`` again the whole vector, which
        the scheduler adds to the pass's."""
        self._no("pass over a block of positions (generation by blocks)")

    block_unmask = decode_block_cache

    # -- the llama block's own: the stacked scan, training, and the K/V-only
    # step ``decode_step_cache``'s default calls (a family of "k" and "v"
    # kinds alone implements that one: afmoe) ---------------------------------

    def decode_step_ragged_list(self, *a, **kw):
        """``(params, ks, vs, tokens [B, 1], pos [B], attn_len=None,
        write_pos=None, lens=None) -> (logits [B, V], ks, vs, *counts)``
        over per-layer lists of [B, KV, T, Dh] (the contract: ``DecoderLM``'s)."""
        self._no("k/v-only decode step: its cache holds other kinds "
                 "(decode_step_cache)")

    def backbone(self, *a, **kw):
        self._no("stacked-scan backbone (training, tp / sp / pp / ep)")

    def loss_fn(self, *a, **kw):
        self._no("loss (serving only)")

    def _decode(self, *a, **kw):
        self._no("stacked-cache decode step (decode_step, "
                 "decode_step_ragged, generate)")

    decode_step = decode_step_ragged = generate = _decode
