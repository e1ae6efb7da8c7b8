"""The jamba decoder (AI21-Jamba2-3B): everything the benchmark knows of it.

A configuration whose file says ``"architecture": "jamba"`` is served,
compared and costed by this module (``manifest.architecture``). The parent
process loads it too and never imports jax: jax and the program are
imported inside the functions that need them.

**The served family.** ``benchmark_jamba``: the program's own
``DecoderLM(block="jamba")`` (``seldon_core_tpu/models/jamba.py``) in every
method but ``init_params``, which runs the program's own draw a layer at a
time under one compiled program a kind of layer (a run of Mamba layers is
one ``lax.map`` of it, so the stack comes out as the family holds it) and
casts each leaf to the served dtype inside it.

**Nothing is cut.** The configuration's file keeps every published key but
``max_position_embeddings``: 28 layers, every width, 20 / 1 heads, the
whole vocabulary; one chip is one whole replica.

**The costs.** Operations and bytes from shapes, the benchmark's own copy.
What a decode step moves of the lanes' state and of their keys and values
depends on which lanes are live and how long, so it comes from the
program's counters, as the capture gives them; where they are missing the
bytes are ``None``, never a guess.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os

# the engine's batcher found by its parameters, and the process's peak: one
# copy among the modules whose comparison borrows the serving cache
from benchmark.architectures.joyai_llm_flash import (
    _memory_peak, _serving_batcher)
from benchmark.manifest import ManifestError

FAMILY = "benchmark_jamba"

# Agreement asked of the served path: six limits, any of which fails it
# (``compare_served`` says what each compares). Each lies between two
# readings on the chip at the cell's own lengths (my chip runs, PR 55, calls
# 1-3 and the engine's own runs: 192 lanes, 168 live, each prefilled at its
# own length 3-2,273 by the batcher's own executables in the buckets the
# traffic pads to, 128, 512 and 1024 (eight rows a call and one), and 2560
# past them; the batcher's own burst of 8 steps; the reference over 2,281
# tokens): the largest over 16 sound seeds (calls 1-3, and the engine's own
# thirteen runs of calls 4-6) and the least of the controls
# that must fail (one seed; ``weights_8bit`` is the nearest precision below
# the configuration's and fails every limit but the slow channels' own).
#
# ``TOLERANCE``: max |served - reference| over the compared logits (every
# live lane at every decode step x the whole vocabulary, and the whole
# prompt's last position) over the reference logits' standard deviation.
# Sound 0.089-0.140; ``dt_layernorm`` left out 0.828, the conv bias left out
# 1.034, a rotary added 1.084, 8-bit weights 1.114, ``D`` left out 3.03, ``A``
# without its sign not finite (the state kept in bfloat16 0.099: the logits
# cannot tell it, ``SLOW_STATE_TOLERANCE`` does). So 0.3: 2.1 times the
# largest sound reading, 0.36 of the least control's.
#
# ``STATE_TOLERANCE``: each live lane's state after the steps (and, for the
# lanes at the traffic's own lengths, the shortest and the longest, as the
# batcher's prefill and insert left it) against the reference's state after
# that position: |served - reference|_F / |reference|_F over a layer's [N,
# C], the mean over the 26 Mamba layers, the largest over the lanes. Sound
# 0.0162-0.0171 (the operands' bfloat16 and the stream's); a rotary added
# 0.176, 8-bit weights 0.242, ``D`` left out 0.405, the conv bias left out
# 0.600, ``dt_layernorm`` left out 0.996 (the state in bfloat16 0.0267: not
# apart). So 0.04: 2.3 times the largest sound reading, 0.23 of the least
# control's.
#
# ``SLOW_STATE_TOLERANCE``: the same states over the quarter of a layer's
# channels that forget slowest (the least ``b_dt``: a step near 1e-3 before
# the input's own term, a memory of hundreds of tokens), in the lanes that
# hold ``SLOW_FROM`` positions or more: relative a layer, the mean over the
# layers, the largest over those lanes. Over the whole state the channels
# that forget in tens of tokens carry the norm, and there a state rounded to
# bfloat16 after every token is off by little more than the served path's
# own bfloat16 operands are (a walk of ten roundings of 2^-9 against one
# rounding of each of four operands); in the slow channels the walk is a
# thousand roundings long while the operands' roundings average out, and the
# two readings stand apart: sound 0.0089-0.0104 (the mean over the 139
# lanes 0.0083-0.0097), the state kept in bfloat16 0.0657 (mean 0.0484). So
# 0.025: 2.4 times the largest sound reading, 0.38 of the control's. (The control rounds with
# ``lax.reduce_precision``: a pair of converts inside a ``jit`` was taken
# out by the compiler and the first reading, call 2, was the sound one
# digit for digit.)
#
# ``TAILS_TOLERANCE``: every live lane's convolution tails (the 3 rows of
# ``a`` a layer, as the batcher's prefill left them at the lane's OWN length
# and as the last decode step left them) against the reference's ``a`` at
# those positions, relative, the mean over the layers, the largest over the
# lanes. Sound 0.0098-0.0100; a rotary added 0.114, ``dt_layernorm`` left
# out 0.116, the conv bias left out 0.117, 8-bit weights 0.137. So 0.03: 3.0
# times the largest sound reading, 0.26 of the least control's.
#
# ``ROWS_TOLERANCE``: the K and V rows against the reference's own at that
# position, relative over a layer's rows, the mean over the 2 attention
# layers and over K and V; the largest of the whole prompt's 2,273 as the
# family's prefill returned them, the 8 rows the steps wrote in each live
# lane, and lane by lane what the batcher's own prefill and insert left
# (the lanes at 3, 90, 250, 450, 900 and 2,273). Sound 0.0099-0.0102;
# ``dt_layernorm`` left out 0.067, the conv bias left out 0.113, 8-bit
# weights 0.136, ``D`` left out 0.269, a rotary added 0.671. So 0.025: 2.5
# times the largest sound reading, 0.37 of the least control's.
#
# The batcher's own programs against the family's, which the reference
# follows. Its prefills hand out a token and no logits: ``prefill_margin``,
# how far under the reference's largest logit at a lane's last prompt
# position the token lies that the lane's prefill sampled, in deviations, by
# ``TOLERANCE``: 0.0 in every lane of every seed. Its burst against the
# program's own step fed the burst's tokens: ``burst_margin`` by
# ``TOLERANCE`` too, 0.0. ``BURST_TOLERANCE``: the rows, the tails and the
# states the burst left against the steps', relative, the largest over
# layers: sound 0.0004-0.0028 (two compilations of one step); a live lane
# the burst leaves out: rows 0.044, tails 0.110, states 0.103, and the
# counters do not hold. So 0.008: 2.9 times the largest sound reading, 0.18
# of the control's least.
TOLERANCE = 0.3
STATE_TOLERANCE = 0.04
SLOW_STATE_TOLERANCE = 0.025
TAILS_TOLERANCE = 0.03
ROWS_TOLERANCE = 0.025
BURST_TOLERANCE = 0.008

BYTES = 2          # bfloat16 weights, keys, values and convolution tails
STATE_BYTES = 4    # the float32 state
BURST_FAULTS = ("burst_idles_a_lane",)


# -- the served family ---------------------------------------------------------

def __getattr__(name: str):
    # built when the program asks for it by its dotted path: defining it
    # imports the program, and with it jax
    if name != "SeededJambaLM":
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    from seldon_core_tpu.models.jamba import JambaLM

    class SeededJambaLM(JambaLM):
        def init_params(self, seed: int = 0):
            """The program's own draw, layer by layer: one compiled program
            a run of Mamba layers (``lax.map`` of ``init_mamba`` over the
            run's keys: the stack comes out as the family holds it, one
            layer's float32 draw alive at a time), one for an attention
            layer and one for the embedding, each leaf cast to the served
            dtype inside it. The keys are ``init_params``' own, a layer
            its own."""
            import jax
            import jax.numpy as jnp

            dt = jnp.dtype(self.cfg.dtype)

            def cast(tree):
                return jax.tree_util.tree_map(lambda a: a.astype(dt), tree)

            run = jax.jit(lambda keys: jax.lax.map(
                lambda key: cast(self.init_mamba(key)), keys))
            attention = jax.jit(lambda key: cast(self.init_attention(key)))
            keys = jax.random.split(jax.random.PRNGKey(seed),
                                    self.cfg.n_layers + 1)
            runs, attn, at = [], [], 0
            for kind, _first, n in self._segments:
                if kind == "mamba":
                    runs.append(run(keys[at:at + n]))
                else:
                    attn.append(attention(keys[at]))
                at += n
            return dict(jax.jit(lambda key: cast(self.init_top(key)))(keys[-1]),
                        runs=runs, attn=attn)

    globals()[name] = SeededJambaLM
    return SeededJambaLM


def register() -> None:
    from seldon_core_tpu import models
    # a program without the family fails here, at once and cleanly
    from seldon_core_tpu.models import jamba  # noqa: F401

    models.register(FAMILY, f"{__name__}.SeededJambaLM")


def n_kinds(cfg: dict) -> tuple:
    """``(Mamba layers, attention layers)``: layer ``i`` is attention iff
    ``i % attn_layer_period == attn_layer_offset``."""
    full = sum(1 for i in range(cfg["num_hidden_layers"])
               if i % cfg["attn_layer_period"] == cfg["attn_layer_offset"])
    return cfg["num_hidden_layers"] - full, full


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def model_kwargs(cfg: dict, seed: int) -> dict:
    """The published config's keys as ``DecoderLM(block="jamba")`` takes
    them."""
    if cfg["num_experts"] != 1 or cfg["num_experts_per_tok"] != 1:
        raise ManifestError(f"{cfg['name']}: num_experts 1, every FFN dense")
    if not cfg["mamba_conv_bias"] or cfg["mamba_proj_bias"]:
        raise ManifestError(f"{cfg['name']}: a bias on the convolution alone")
    if not cfg["tie_word_embeddings"] or cfg["hidden_act"] != "silu":
        raise ManifestError(f"{cfg['name']}: a tied head, SiLU")
    if cfg.get("sliding_window") is not None:
        raise ManifestError(f"{cfg['name']}: no window")
    return {
        "block": "jamba",
        "vocab_size": cfg["vocab_size"],
        "d_model": cfg["hidden_size"],
        "n_layers": cfg["num_hidden_layers"],
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": head_dim(cfg),
        "d_ff": cfg["intermediate_size"],
        "max_seq": cfg["server"]["max_seq"],
        "norm_eps": float(cfg["rms_norm_eps"]),
        "dtype": cfg["torch_dtype"],
        "attn_layer_period": cfg["attn_layer_period"],
        "attn_layer_offset": cfg["attn_layer_offset"],
        "mamba_d_state": cfg["mamba_d_state"],
        "mamba_d_conv": cfg["mamba_d_conv"],
        "mamba_dt_rank": cfg["mamba_dt_rank"],
        "mamba_expand": cfg["mamba_expand"],
        "residual_scale": cfg["weights"]["residual_scale"],
        # PRNGKey takes 32 bits; the driver's seeds are larger
        "seed": seed % (2**31 - 1),
    }


def rehearsal(cfg: dict) -> dict:
    """The sizes ``--rehearse-cpu`` puts over the configuration's: a period
    of 4 with one attention layer over 6 layers (runs of 1 and 3 Mamba
    layers before attention layers 1 and 5), one KV head of 128 under 4
    query heads, a state of 16, a rank above 1, a cache of 1024 positions."""
    return {
        "server": dict(cfg["server"], max_seq=1024),
        "hidden_size": 512, "num_attention_heads": 4, "num_key_value_heads": 1,
        "intermediate_size": 256, "num_hidden_layers": 6,
        "attn_layer_period": 4, "attn_layer_offset": 1,
        "mamba_dt_rank": 8, "vocab_size": 1024,
    }


# -- the served model against the plain reference ----------------------------------

IDLE_EVERY = 8      # lanes 5, 13, 21, ... idle among the live ones
SLOW_FROM = 512     # positions a lane holds before its slow channels are judged
READ_BLOCK = 256    # the ragged read's block at one KV head of 128 (``ops.
#                     decode_attention.walk_block``; asked again in ``serve``)
STEP = 512          # what an unwarmed batcher's prompt is rounded down to
MANY = (8, 4)       # the rows of a batched prefill the scheduler warms and uses


def lane_lengths(lanes: int, prompt_len: int, asked: tuple = (),
                 taps: int = 4) -> dict:
    """``{lane: tokens it holds before its first step}`` for the live lanes
    (every eighth idle): spread evenly from ``prompt_len // 16`` to
    ``prompt_len`` (the lane whose steps end where the cell's longest
    contexts end), no two alike, and lanes moved, each the free lane that
    lay nearest: to the lengths ``asked`` (the prompt lengths the batcher
    was warmed for: the traffic's own), to both sides of the read's block
    edge (``READ_BLOCK`` less one, where the first step's read ends on the
    block's last key; the block itself; one more), to a prompt shorter than
    the convolution's ``taps`` (its tail holds zeros before the sequence's
    start) and to prompts that fill their bucket (128, 512, 1024)."""
    import numpy as np

    live = [j for j in range(lanes) if j % IDLE_EVERY != 5]
    lens = np.unique(np.linspace(max(8, prompt_len // 16), prompt_len,
                                 len(live)).round().astype(int))
    if len(lens) < len(live):
        raise ValueError(f"{len(live)} lanes do not fit apart in "
                         f"{prompt_len} positions")
    wanted = [*asked, READ_BLOCK - 1, READ_BLOCK, READ_BLOCK + 1, taps - 1,
              128, 512, 1024]
    taken = {len(lens) - 1}
    if len(live) >= 16:
        for to in wanted:
            if not 0 < to < prompt_len or to in lens:
                continue
            at = next(i for i in np.abs(lens - to).argsort().tolist()
                      if i not in taken)
            lens[at] = to
            taken.add(at)
    return dict(zip(live, lens.tolist()))


def compare_served(model, params, seed: int, prompt_len: int = 0,
                   decode_steps: int = 0, variant: str = "",
                   batcher=None) -> dict:
    """The served path at the cell's lengths and from the programs the
    window drives, against ONE full causal forward of the reference over
    the same ``prompt_len + decode_steps`` tokens.

    ``batcher``: the ``ContinuousBatcher`` whose cache, lanes and
    executables are used: the one given, else the process's own that serves
    ``params`` (the engine's: idle while the parent asks for the
    comparison; ``borrowed`` says it was found). None is built here. The
    cache is handed back with the comparison's rows and states in it, which
    a lane's next occupant overwrites at its insert. ``prompt_len``: where
    the batcher was warmed (the engine's, for the cell's traffic), its
    longest prompt and its most new tokens less the steps: the longest
    lane's steps end where the cell's longest contexts end, 2,281 of 8,192;
    else what fits. ``decode_steps``: the batcher's ``_k``, so that the
    burst is the TIMED executable.

    A recurrent state cannot be cut back to a shorter prompt as a KV
    cache's columns can, so each live lane's rows, tails and state come
    from a prefill of ITS OWN: lane j holds the first L_j tokens
    (``lane_lengths``), prefilled by the BATCHER'S OWN compiled prefill
    (``_prefill_fn``; ``_prefill_many_fn`` where lanes share a bucket that
    takes several rows a call) in the smallest bucket the batcher was
    warmed for that holds it (past the last of them the whole prompt's
    bucket, one more executable) and put into its lane by the batcher's own
    compiled insert. The steps below write at L_j, L_j + 1, ...: the K and
    V rows of each run are overwritten by the next before it reads them,
    the tails and the states are put back from the inserts' own (kept on
    the device) before each run.

    (0) The family's ``prefill`` over the whole prompt, one row: its last
    logits and its K and V rows. The batcher's prefills hand out a token
    and no logits and are held to the reference by what they leave: the
    first token each sampled (``prefill_margin``), the K and V rows, the
    tails and the states as the cache holds them after the insert, and
    their counters (``ssm_prefill_steps_walked`` / ``_bucket``: the lengths'
    and the buckets' own arithmetic). (1) The batcher's compiled burst
    (``_burst_fn`` at its ``_k``, the cache carried through its scan and
    donated): its tokens, its counters, the rows, tails and states it
    leaves. (2) The program's own step (``decode_step_cache``, which the
    burst's body calls) one step at a time, fed the BURST'S tokens: the
    burst must have sampled each step's argmax, left the same rows, tails
    and states in the same lanes, and counted the same. (3) That step fed
    the prompt's own next tokens, whose logits, rows, tails and states the
    reference's one forward can be compared with. ``variant``: one of
    ``reference.VARIANTS`` (a wrong reference) or of ``BURST_FAULTS`` (a
    live lane the burst leaves out): the controls that must fail.

    Held: ``ratio`` <= ``TOLERANCE``; ``state_ratio`` <= ``STATE_TOLERANCE``;
    ``slow_state_ratio`` <= ``SLOW_STATE_TOLERANCE``;
    ``tails_ratio`` <= ``TAILS_TOLERANCE``; ``rows_ratio`` <=
    ``ROWS_TOLERANCE``; ``prefill_margin`` and ``burst_margin`` <=
    ``TOLERANCE``, the burst's rows, tails and states against the steps' <=
    ``BURST_TOLERANCE``; an idle lane's tails and state bit for bit what
    they were; the counters the lanes' and lengths' own arithmetic, the
    burst's the steps' sum."""
    served = serve(model, params, seed, prompt_len, decode_steps,
                   variant == "burst_idles_a_lane", batcher)
    return judge(model, served, params,
                 "" if variant in BURST_FAULTS else variant)


def serve(model, params, seed: int, prompt_len: int = 0,
          decode_steps: int = 0, burst_idles_a_lane: bool = False,
          batcher=None) -> dict:
    """The served half of ``compare_served``: everything the program
    computed, as numpy, for ``judge`` to hold against a reference (one
    serving, several references: the controls)."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from seldon_core_tpu.ops.decode_attention import walk_block

    t0 = time.monotonic()
    peak_before = _memory_peak()
    cfg = model.cfg
    borrowed = batcher is None
    if borrowed:
        batcher = _serving_batcher(params)
    if batcher is None:
        raise ValueError("no ContinuousBatcher of this process serves these "
                         "parameters, and none was given")
    lanes, cache_len = batcher.slots, batcher.max_seq
    decode_steps = decode_steps or batcher._k
    # what the batcher was warmed for is the traffic: its prompt lengths
    # and where its longest context ends
    warmed = batcher._warm_args or {}
    asked = tuple(sorted({n for n in warmed.get("prompt_lens", ())
                          if n <= cache_len}))
    if not prompt_len and asked:
        prompt_len = min(cache_len, asked[-1] + warmed["max_new_tokens"]
                         ) - decode_steps
    prompt_len = prompt_len or (cache_len - decode_steps) // STEP * STEP
    total = prompt_len + decode_steps
    if total > cache_len or prompt_len < 8:
        raise ValueError(f"{total} positions in a cache of {cache_len}")
    warm_buckets = sorted({batcher._bucket(n) for n in asked})
    rng = np.random.default_rng(seed % (2**63))
    tokens = rng.integers(0, cfg.vocab_size, size=total, dtype=np.int64)
    start = lane_lengths(lanes, prompt_len, asked, cfg.mamba_d_conv)
    live = np.array([j in start for j in range(lanes)])
    at = np.array([start.get(j, 0) for j in range(lanes)])
    n_mamba, n_full = model._n_mamba, model._n_full
    top = batcher._bucket(prompt_len)

    def bucket_of(n):
        """The smallest warmed bucket that holds ``n`` tokens: an
        executable the window drives (past the last of them the whole
        prompt's bucket, one more executable and not one a length); where
        nothing was warmed, the batcher's own."""
        return next((b for b in warm_buckets if n <= b),
                    top if warm_buckets else batcher._bucket(n))

    def padded(rows, bucket):
        out = np.zeros((len(rows), bucket), np.int32)
        for i, n in enumerate(rows):
            out[i, :n] = tokens[:n]
        return jnp.asarray(out)

    def rows_of(a, n):
        """K or V [..., KV, T, Dh] -> [..., n, KV, Dh] float32."""
        return np.moveaxis(np.asarray(a[..., :n, :], np.float32), -3, -2)

    # (0) the whole prompt through the family's prefill, one row: the
    # logits at its end and its rows
    logits, slab = jax.jit(
        lambda p, t, last: model.prefill(p, t, top, last))(
            params, padded([prompt_len], top),
            jnp.asarray([prompt_len - 1], jnp.int32))
    first = np.asarray(logits[0])
    slab_kv = [(rows_of(slab["k"][l, 0], prompt_len),
                rows_of(slab["v"][l, 0], prompt_len)) for l in range(n_full)]
    del logits, slab

    # every live lane from a prefill of its own, by the batcher's own
    # executables: lanes that share a bucket in the rows a call the
    # scheduler would give them
    by_bucket: dict = {}
    for j in sorted(start, key=lambda j: start[j]):
        by_bucket.setdefault(bucket_of(start[j]), []).append(j)
    calls = []
    for bucket, group in by_bucket.items():
        while group:
            ok = {8: batcher._chunk8_ok(bucket), 4: batcher._rows_ok(4, bucket)}
            m = next((m for m in MANY if len(group) >= m and ok[m]), 1)
            calls.append((bucket, group[:m]))
            group = group[m:]
    cache = batcher._cache
    batcher._cache = None       # donated below; handed back at the end
    cur_tok = jnp.zeros((lanes,), jnp.int32)
    lane_pos = jnp.zeros((lanes,), jnp.int32)
    keys = jnp.zeros((lanes, 2), jnp.uint32)
    no_counts = batcher._no_prefill_counts
    sampled, prefill_counts = {}, np.zeros(2, np.int64)
    try:
        for bucket, group in calls:
            m = len(group)
            begin = np.array([start[j] for j in group])
            last = jnp.asarray(begin - 1, jnp.int32)
            if m == 1:
                firsts, slab, lane_key, *counts = batcher._prefill_fn(
                    params, padded(begin, bucket), last, jnp.int32(0),
                    jnp.float32(0.0))
                cache, cur_tok, lane_pos, keys, *_ = batcher._insert_fn(
                    cache, slab, int(group[0]), jnp.int32(tokens[begin[0]]),
                    int(begin[0]), lane_key, cur_tok, lane_pos, keys,
                    *no_counts, *counts)
            else:
                firsts, slab, lane_keys, *counts = batcher._prefill_many_fn(
                    params, padded(begin, bucket), last,
                    jnp.zeros((m,), jnp.int32), jnp.zeros((m,), jnp.float32))
                cache, cur_tok, lane_pos, keys, *_ = batcher._insert_many_fn(
                    cache, slab, jnp.asarray(group, jnp.int32),
                    jnp.asarray(tokens[begin], jnp.int32),
                    jnp.asarray(begin, jnp.int32), lane_keys,
                    cur_tok, lane_pos, keys, *no_counts, *counts)
            for row, j in enumerate(group):
                sampled[j] = int(np.asarray(firsts).reshape(-1)[row])
            prefill_counts += np.asarray(counts[0], np.int64)
            del slab
        inserted = bool(
            np.array_equal(np.asarray(lane_pos), at) and np.array_equal(
                np.asarray(cur_tok)[live], tokens[at[live]]))
        prefill_counters_hold = prefill_counts.tolist() == [
            int(at[live].sum()) * n_mamba,
            sum(bucket * len(group) for bucket, group in calls) * n_mamba]
        # what the batcher's prefills left, as the cache holds it: the K and
        # V rows and the states of the lanes at the traffic's own lengths,
        # the shortest and the longest; every live lane's tails
        shown = sorted({j for j in start if start[j] in asked}
                       | {max(start, key=start.get), min(start, key=start.get)})
        # (one gather an array: a slice a lane and length is a program each)
        at_shown = jnp.asarray(shown, jnp.int32)
        kv_shown = [(np.asarray(cache["k"][l][at_shown]),
                     np.asarray(cache["v"][l][at_shown])) for l in range(n_full)]
        lane_rows = {
            j: [(rows_of(k[i], start[j]), rows_of(v[i], start[j]))
                for k, v in kv_shown] for i, j in enumerate(shown)}
        states_at_insert = np.asarray(cache["state"][0][at_shown])  # [shown, Lm, N, C]
        del kv_shown
        # the tails and the states as the inserts left them, every lane's:
        # each run below starts from these
        tails0, states0 = jnp.copy(cache["conv"][0]), jnp.copy(cache["state"][0])
        tails_at_insert = np.asarray(tails0, np.float32)[live]
        idle0 = (np.asarray(tails0)[~live], np.asarray(states0)[~live])

        live_ix = jnp.asarray(np.flatnonzero(live), jnp.int32)
        new_at = at[live, None] + np.arange(decode_steps)[None]   # [live, steps]
        gather = jax.jit(lambda cache, j, p: (
            [a[j[:, None], :, p] for a in cache["k"]],
            [a[j[:, None], :, p] for a in cache["v"]]))

        def written(cache):
            """What ``decode_steps`` steps leave: the K and V rows at each
            live lane's new positions, per layer [live, steps, KV, Dh]; the
            live lanes' tails [live, Lm, K - 1, C] and states [live, Lm, N,
            C]; the idle lanes' tails and states."""
            ks, vs = gather(cache, live_ix, jnp.asarray(new_at, jnp.int32))
            rows = [(np.asarray(k, np.float32), np.asarray(v, np.float32))
                    for k, v in zip(ks, vs)]
            tails, states = np.asarray(cache["conv"][0]), np.asarray(
                cache["state"][0])
            return (rows, tails[live].astype(np.float32), states[live],
                    (tails[~live], states[~live]))

        def restarted(cache):
            """The cache with every lane's tails and state as the inserts
            left them; what the last run left goes first (1.8 GB at the
            cell's size, beside the copies and the kept ones)."""
            del cache["conv"], cache["state"]
            cache.update(conv=[jnp.copy(tails0)], state=[jnp.copy(states0)])
            return cache

        def relative(a, b):
            return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))

        def by_layer(a, b):
            """[live, Lm, ...] against its like: relative, the largest over
            the layers."""
            return max(relative(a[:, l], b[:, l]) for l in range(a.shape[1]))

        # (1) the batcher's burst: the timed executable where k is its _k
        active = live.copy()
        if burst_idles_a_lane:
            active[np.flatnonzero(live)[0]] = False
        toks, _cur, _pos, cache, _k, burst_counts = batcher._burst_fn(
            params, cache, cur_tok, lane_pos, jnp.asarray(active),
            jnp.zeros((lanes,), jnp.float32), keys, decode_steps,
            None if batcher._ragged_read else cache_len)
        toks = np.asarray(toks)                   # [steps + 1, lanes]
        burst_counts = np.asarray(burst_counts)
        burst_rows, burst_tails, burst_states, idle_after = written(cache)
        idle_untouched = all(
            np.array_equal(a, b) for a, b in zip(idle0, idle_after))
        del idle_after

        step = jax.jit(model.decode_step_cache, donate_argnums=(1,))

        def steps(cache, feed):
            """``decode_steps`` steps over all lanes, step i fed ``feed(i)``
            [lanes]: each step's logits at the live lanes and its counters,
            and the cache."""
            outs = []
            for i in range(decode_steps):
                pos = np.where(live, at + i, 0)
                out, cache, counts = step(
                    params, cache,
                    jnp.asarray(np.where(live, feed(i), 0)[:, None], jnp.int32),
                    jnp.asarray(pos, jnp.int32),
                    lens=jnp.asarray(np.where(live, pos + 1, 0), jnp.int32))
                outs.append((np.asarray(out[live_ix]), np.asarray(counts)))
            return outs, cache

        # (2) the step, fed the burst's tokens
        outs, cache = steps(restarted(cache), lambda i: toks[i])
        step_rows, step_tails, step_states, _idle = written(cache)
        burst_margin, agree = 0.0, []
        on = active[live]
        for i, (out, _c) in enumerate(outs):
            mine, theirs = out[on], toks[i + 1][live][on]
            agree.append(mine.argmax(-1) == theirs)
            burst_margin = max(burst_margin, float(
                (mine.max(-1) - mine[np.arange(len(mine)), theirs]).max()
                / mine.std()))
        burst_rows_ratio = max(
            [relative(b, a) for mine, theirs in zip(burst_rows, step_rows)
             for b, a in zip(mine, theirs)] or [0.0])
        burst_tails_ratio = by_layer(burst_tails, step_tails)
        burst_states_ratio = by_layer(burst_states, step_states)
        burst_counters_hold = bool(np.array_equal(
            burst_counts, np.sum([c for _o, c in outs], axis=0)))
        del burst_rows, burst_tails, burst_states, _idle

        # (3) the step, fed the prompt's own tokens: what the reference follows
        outs, cache = steps(restarted(cache), lambda i: tokens[at + i])
        step_rows, step_tails, step_states, _idle = written(cache)
        del tails0, states0, _idle
    finally:
        batcher._cache = cache      # handed back, the comparison's rows in it
    read_block = walk_block(cfg.n_kv_heads, cfg.head_dim,
                            cache["k"][0].dtype, cache_len)
    del cache
    lens_live = at[live]
    served, positions = [first[None]], [prompt_len - 1]
    counters_hold = True
    for i, (out, counts) in enumerate(outs):
        served.append(out)
        positions += (lens_live + i).tolist()
        # the kernel walks each live lane's length in whole blocks; the dots
        # read the bound of every lane (``JambaLM._kv_rows_read``)
        n_read = int((-(-(lens_live + i + 1) // read_block) * read_block).sum()
                     ) if batcher._ragged_read else lanes * cache_len
        counters_hold &= counts.tolist() == [
            int(live.sum()) * n_mamba, n_mamba, n_read * n_full,
            int((lens_live + i + 1).sum()) * n_full]
    return dict(
        tokens=tokens, positions=positions, served=np.concatenate(served),
        slab_kv=slab_kv, step_rows=step_rows, new_at=new_at,
        step_tails=step_tails, step_states=step_states,
        tails_at_insert=tails_at_insert, states_at_insert=states_at_insert,
        lengths=lens_live, shown_lengths=np.array([start[j] for j in shown]),
        buckets=np.array([bucket_of(n) for n in lens_live]),
        sampled=np.array([sampled[j] for j in sorted(start)]),
        lane_rows=[(start[j], lane_rows[j]) for j in shown],
        prefill_calls=[(bucket, len(group)) for bucket, group in calls],
        prompt_len=prompt_len, bucket=top, lanes=lanes, cache_len=cache_len,
        read_block=read_block, lanes_live=int(live.sum()), borrowed=borrowed,
        decode_steps=decode_steps, counters_hold=bool(counters_hold),
        prefill_counters_hold=bool(prefill_counters_hold),
        agree=float(np.mean(agree)), burst_margin=burst_margin,
        burst_rows_ratio=burst_rows_ratio, burst_tails_ratio=burst_tails_ratio,
        burst_states_ratio=burst_states_ratio,
        burst_counters_hold=burst_counters_hold, inserted=inserted,
        idle_untouched=bool(idle_untouched), served_s=time.monotonic() - t0,
        memory_peak_bytes=[peak_before, _memory_peak()])


def judge(model, served: dict, params, variant: str = "") -> dict:
    """The reference's half: ONE causal forward of the plain reference
    (``variant``: a wrong one) over the tokens ``serve`` served, and the
    limits."""
    import time

    import numpy as np

    from benchmark.reference import jamba as reference

    t1 = time.monotonic()
    cfg = model.cfg
    s = served
    tokens, positions = s["tokens"], s["positions"]
    prompt_len, decode_steps = s["prompt_len"], s["decode_steps"]
    lengths, shown, sampled = s["lengths"], s["shown_lengths"], s["sampled"]
    # the steps' positions, then each live lane's last prompt position (the
    # batcher's prefills handed out a token there and no logits); the states
    # after each live lane's last step, then after the shown lanes' prompts
    ref, ref_kv, ref_a, ref_states = reference.forward(
        params, cfg, tokens, positions + (lengths - 1).tolist(), variant,
        state_at=(lengths + decode_steps - 1).tolist() + (shown - 1).tolist())
    ref, ref_last = ref[:len(positions)], ref[len(positions):]
    scale = float(ref.std())
    prefill_margin = float((ref_last.max(-1) - ref_last[
        np.arange(len(sampled)), sampled]).max() / scale)
    by_position = (np.max(np.abs(s["served"] - ref), axis=-1) / scale).tolist()
    err = max(by_position)

    def relative(a, b):
        return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))

    def by_lane(mine, theirs):
        """mine [lanes, Lm, ...] against theirs (a list over the layers of
        [lanes, ...]): relative a layer, the mean over the layers, a lane a
        number."""
        return [float(np.mean([relative(mine[j, l], theirs[l][j])
                               for l in range(len(theirs))]))
                for j in range(mine.shape[0])]

    new_at = s["new_at"]
    prefill_rows = [relative(mine, theirs[:prompt_len])
                    for pair, ref_pair in zip(s["slab_kv"], ref_kv)
                    for mine, theirs in zip(pair, ref_pair)]
    stepped = [relative(mine, theirs[new_at])
               for pair, ref_pair in zip(s["step_rows"], ref_kv)
               for mine, theirs in zip(pair, ref_pair)]
    # what the batcher's own prefills left in the cache, a lane at a time
    lane_rows = [float(np.mean([relative(mine, theirs[:n])
                                for pair, ref_pair in zip(pairs, ref_kv)
                                for mine, theirs in zip(pair, ref_pair)]))
                 for n, pairs in s["lane_rows"]]
    rows_ratio = max(float(np.mean(prefill_rows or [0.0])),
                     float(np.mean(stepped or [0.0])), *lane_rows)
    # the tails: ``a`` at the last K - 1 positions a lane holds, zeros
    # before the sequence's start
    k1 = cfg.mamba_d_conv - 1
    back = np.arange(-k1, 0)

    def tails_of(ends):
        return [np.concatenate([np.zeros((k1, a.shape[1]), a.dtype), a])[
            np.asarray(ends)[:, None] + k1 + back[None]] for a in ref_a]

    tails_insert = by_lane(s["tails_at_insert"], tails_of(lengths))
    tails_steps = by_lane(s["step_tails"], tails_of(lengths + decode_steps))
    tails_ratio = max(tails_insert + tails_steps)
    n_live = len(lengths)
    state_steps = by_lane(s["step_states"], [r[:n_live] for r in ref_states])
    state_insert = by_lane(s["states_at_insert"],
                           [r[n_live:] for r in ref_states])
    state_ratio = max(state_steps + state_insert)
    # the slow quarter of each layer's channels, in the lanes long enough
    # for a thousand-token memory to show
    b_dt = reference.mamba_leaf(params, "b_dt")                      # [Lm, C]
    slow = b_dt <= np.quantile(b_dt, 0.25, axis=1, keepdims=True)
    long = np.flatnonzero(lengths + decode_steps >= SLOW_FROM)
    slow_steps = [float(np.mean([
        relative(s["step_states"][j, l][:, slow[l]],
                 ref_states[l][j][:, slow[l]]) for l in range(len(slow))]))
        for j in long]
    slow_state_ratio = max(slow_steps or [0.0])
    finite = bool(np.isfinite(s["served"]).all() and np.isfinite(ref).all())
    burst_holds = (s["inserted"] and s["idle_untouched"]
                   and s["burst_counters_hold"] and s["prefill_counters_hold"]
                   and prefill_margin <= TOLERANCE
                   and s["burst_margin"] <= TOLERANCE
                   and s["burst_rows_ratio"] <= BURST_TOLERANCE
                   and s["burst_tails_ratio"] <= BURST_TOLERANCE
                   and s["burst_states_ratio"] <= BURST_TOLERANCE)
    return {
        "ratio": err, "ratio_at": positions[int(np.argmax(by_position))],
        "tolerance": TOLERANCE,
        "state_ratio": state_ratio, "state_tolerance": STATE_TOLERANCE,
        "state_ratio_steps": max(state_steps),
        "state_ratio_steps_mean": float(np.mean(state_steps)),
        "state_ratio_insert": dict(zip((str(n) for n in shown), state_insert)),
        "slow_state_ratio": slow_state_ratio,
        "slow_state_tolerance": SLOW_STATE_TOLERANCE,
        "slow_state_ratio_mean": float(np.mean(slow_steps or [0.0])),
        "slow_state_lanes": len(long),
        "tails_ratio": tails_ratio, "tails_tolerance": TAILS_TOLERANCE,
        "tails_ratio_insert": max(tails_insert),
        "tails_ratio_steps": max(tails_steps),
        "rows_ratio": rows_ratio, "rows_tolerance": ROWS_TOLERANCE,
        "rows_ratio_prefill": float(np.mean(prefill_rows or [0.0])),
        "rows_ratio_steps": float(np.mean(stepped or [0.0])),
        "rows_ratio_lanes": dict(zip(
            (str(n) for n, _ in s["lane_rows"]), lane_rows)),
        "prefill_margin": prefill_margin,
        "prefill_calls": s["prefill_calls"],
        "logit_std": scale, "positions": len(positions),
        "prompt_len": prompt_len, "bucket": s["bucket"],
        "decode_steps": decode_steps, "read_block": s["read_block"],
        "lanes_live": s["lanes_live"], "lanes": s["lanes"],
        "cache_len": s["cache_len"], "borrowed": s["borrowed"],
        "counters_are_the_lengths": s["counters_hold"],
        "prefill_counters_hold": s["prefill_counters_hold"], "finite": finite,
        "burst_tokens_agree": s["agree"], "burst_margin": s["burst_margin"],
        "burst_rows_ratio": s["burst_rows_ratio"],
        "burst_tails_ratio": s["burst_tails_ratio"],
        "burst_states_ratio": s["burst_states_ratio"],
        "burst_tolerance": BURST_TOLERANCE,
        "burst_counters_hold": s["burst_counters_hold"],
        "inserted": s["inserted"], "idle_untouched": s["idle_untouched"],
        "served_s": s["served_s"], "reference_s": time.monotonic() - t1,
        # the process's peak so far: before the comparison, after its
        # served half, after the reference
        "memory_peak_bytes": s["memory_peak_bytes"] + [_memory_peak()],
        "ok": bool(finite and err <= TOLERANCE
                   and state_ratio <= STATE_TOLERANCE
                   and slow_state_ratio <= SLOW_STATE_TOLERANCE
                   and tails_ratio <= TAILS_TOLERANCE
                   and rows_ratio <= ROWS_TOLERANCE and s["counters_hold"]
                   and burst_holds),
    }


# -- what a step must read and a prefill must compute -------------------------------

def channels(cfg: dict) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


def mamba_params(cfg: dict) -> int:
    """One Mamba mixer: W_in, the taps and their bias, W_x, the three small
    norms, W_dt and its bias, A_log, D, W_out (41,241,792)."""
    d, c = cfg["hidden_size"], channels(cfg)
    n, r = cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    return (d * 2 * c + (cfg["mamba_d_conv"] + 1) * c + c * (r + 2 * n)
            + r + 2 * n + r * c + c + c * n + c + c * d)


def attention_params(cfg: dict) -> int:
    """One attention mixer: W_q, W_k, W_v, W_o (13,762,560)."""
    d = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * head_dim(cfg)
    kv = cfg["num_key_value_heads"] * head_dim(cfg)
    return 2 * d * q + 2 * d * kv


def ffn_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def total_params(cfg: dict) -> int:
    """Every parameter (3,029,337,472): the mixers, a SwiGLU and two norms
    a layer, the final norm, and the embedding once (the head is its
    transpose)."""
    d = cfg["hidden_size"]
    mamba, full = n_kinds(cfg)
    return (mamba * mamba_params(cfg) + full * attention_params(cfg)
            + (mamba + full) * (ffn_params(cfg) + 2 * d) + d
            + d * cfg["vocab_size"])


def ssm_state_bytes(cfg: dict) -> int:
    """One lane's state and tail in one Mamba layer (327,680 + 30,720 B):
    what one ``ssm_lane_steps`` reads once and writes once."""
    return channels(cfg) * (cfg["mamba_d_state"] * STATE_BYTES
                            + (cfg["mamba_d_conv"] - 1) * BYTES)


def ssm_kernel_state_bytes(cfg: dict) -> int:
    """The float32 state alone: what the ``selective_scan_step`` kernel
    copies in and out for a live lane (the tail goes through the
    convolution's own ops)."""
    return channels(cfg) * cfg["mamba_d_state"] * STATE_BYTES


def kv_bytes_per_position_and_layer(cfg: dict) -> int:
    """Keys and values of one position in ONE attention layer (512 B)."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * BYTES


def _steps(cfg: dict, counters: dict):
    """Decode steps the capture's counters cover, or None without them."""
    layer_steps = counters.get("ssm_layer_steps", 0)
    mamba, _ = n_kinds(cfg)
    return layer_steps / mamba if layer_steps > 0 and mamba else None


def ssm_step_bytes(cfg: dict, counters: dict):
    """Bytes of state and tails one decode step moves: each live lane's,
    read once and written once a Mamba layer (``ssm_lane_steps`` over the
    steps). None without the counters."""
    steps = _steps(cfg, counters)
    if steps is None:
        return None
    return counters.get("ssm_lane_steps", 0) / steps * 2 * ssm_state_bytes(cfg)


def decode_step_bytes(cfg: dict, live_positions: float, counters: dict):
    """Bytes one decode step must move: every weight once (the tied
    embedding as the head; the lookup is one row a lane), the live lanes'
    state and tails twice (``ssm_step_bytes``), and the live keys and
    values of the attention layers. None where the program gave no such
    counters."""
    state = ssm_step_bytes(cfg, counters)
    if state is None:
        return None
    _, full = n_kinds(cfg)
    return (total_params(cfg) * BYTES + state
            + full * kv_bytes_per_position_and_layer(cfg) * live_positions)


def ssm_step_share(cfg: dict, counters: dict):
    """``(bytes of a step that are the lanes' state and tails, bytes of the
    step)`` from the program's counters alone, the keys and values at the
    lanes' own lengths (``kv_rows_live``). None without the counters."""
    steps = _steps(cfg, counters)
    if steps is None:
        return None
    _, full = n_kinds(cfg)
    live = counters.get("kv_rows_live", 0) / steps / max(1, full)
    return ssm_step_bytes(cfg, counters), decode_step_bytes(cfg, live, counters)


def ssm_prefill_bytes(cfg: dict, counters: dict):
    """Bytes the prefill scan must stream for the steps it walked
    (``ssm_prefill_steps_walked``, a (sequence, Mamba layer, position)
    each): ``c`` and ``delta`` in, ``y`` out, [C] in bfloat16 each, and
    ``B`` and ``C`` [N]. None without the counter."""
    walked = counters.get("ssm_prefill_steps_walked", 0)
    if walked <= 0:
        return None
    return walked * (3 * channels(cfg) + 2 * cfg["mamba_d_state"]) * BYTES


def decode_attn_bytes(cfg: dict, counters: dict):
    """Bytes of K and V the decode attention kernel streamed over the
    capture: ``kv_rows_read`` (summed over the live lanes, the attention
    layers and the steps: each lane's length rounded up to the kernel's
    block) x one position's keys and values in one layer. None without the
    counter."""
    read = counters.get("kv_rows_read", 0)
    if read <= 0:
        return None
    return read * kv_bytes_per_position_and_layer(cfg)


def kernel_seconds(run: dict, executables: tuple, kernel: str):
    """Device seconds over the capture of the ops named ``kernel`` inside
    the executables whose names start with one of ``executables``. The
    trace's reduction names the ten ops of most time, each under its result
    shape; the prefill scan runs at a shape a (rows, bucket) and any of
    them may lie below the ten, so the seconds come from the run's own
    events (``trace_events.json.gz``, which the reduction leaves beside its
    result: the newest under the cell's runs), by the reduction's own
    labels; where there are no events, from the ten named ops. None where
    neither names the kernel."""
    from benchmark import trace

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    found = glob.glob(os.path.join(
        here, "_runs", run["cell"]["name"], "*", "trace_events.json.gz"))
    seconds = 0.0
    if found and run.get("trace"):
        with gzip.open(max(found, key=os.path.getmtime), "rt") as f:
            devices = json.load(f)["devices"]
        for dev in devices:
            mods = sorted(dev["modules"], key=lambda m: m[1])
            starts = [m[1] for m in mods]
            for name, s, d, text in dev["ops"]:
                if not trace.op_label(name, text).startswith(kernel):
                    continue
                i = bisect.bisect_right(starts, s) - 1
                if i >= 0 and s < mods[i][1] + mods[i][2] and trace.executable_of(
                        mods[i][0]).startswith(executables):
                    seconds += d
        seconds /= max(1, len(devices))
    else:
        for name, s in (run.get("trace") or {}).get("device_ops", []):
            exe, _, op = name.partition(":")
            if exe.startswith(executables) and op.startswith(kernel):
                seconds += s
    return seconds if seconds > 0 else None


def prefill_attention_flops(cfg: dict, padded_tokens: float,
                            sequences: float) -> float:
    """The attention layers' useful FLOPs over ``sequences`` prompts of
    ``padded_tokens`` positions in all: scores and values 128 wide a head,
    the causal half of the square, 2 layers."""
    if sequences <= 0:
        return 0.0
    t = padded_tokens / sequences
    per_pair = 4.0 * cfg["num_attention_heads"] * head_dim(cfg)
    return per_pair * sequences * n_kinds(cfg)[1] * t * t / 2.0


def prefill_flops(cfg: dict, padded_tokens: float, sequences: float,
                  counters: dict) -> float:
    """FLOPs of prefilling ``sequences`` prompts padded to ``padded_tokens``
    positions in all: per position every layer's matrix products (the
    mixer's projections and the SwiGLU; the convolution's four taps beside
    them); attention over half the square at the mean length (its least) in
    the attention layers; the head at each prompt's last position. The
    scan's element-wise work (a few operations a state's number, a token
    and a layer: 82 k numbers a token and layer, on the vector unit) is NOT
    in it: a share of the matrix unit's peak counts what the matrix unit
    does."""
    if sequences <= 0:
        return 0.0
    d = cfg["hidden_size"]
    mamba, full = n_kinds(cfg)
    per_token = (mamba * mamba_params(cfg) + full * attention_params(cfg)
                 + (mamba + full) * ffn_params(cfg))
    head = 2.0 * d * cfg["vocab_size"] * sequences
    return (2.0 * per_token * padded_tokens
            + prefill_attention_flops(cfg, padded_tokens, sequences) + head)
