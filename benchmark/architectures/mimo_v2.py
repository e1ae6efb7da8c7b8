"""The mimo_v2 decoder (MiMo-V2.5's language model): everything the benchmark
knows of it.

A configuration whose file says ``"architecture": "mimo_v2"`` is served,
compared and costed by this module (``manifest.architecture``). The parent
process loads it too and never imports jax: jax and the program are
imported inside the functions that need them.

**The served family.** ``benchmark_mimo_v2``: the program's own
``DecoderLM(block="mimo_v2")`` (``seldon_core_tpu/models/mimo_v2.py``) in
every method but ``init_params``, which runs the program's own draw a layer
at a time under one compiled program a kind of layer and casts each leaf to
the served dtype inside it.

**The cut.** The configuration's file keeps every published width. Depth:
``served_layers`` names the published layers that are served; their kinds
are read from the published ``hybrid_layer_pattern`` (0 full, 1 window) and
``moe_layer_freq`` (0 dense, 1 routed) at those indices. Experts:
``n_routed_experts`` is what this chip HOLDS of each expert layer,
``experts_held`` [first, end) which ones, ``n_routed_experts_published``
what the router ranges over. Vocabulary: ``vocab_size`` is the chip's
slice; ids, logits and sampling are over it.

**The costs.** Operations and bytes from shapes, the benchmark's own copy,
of what HOLDS something (a key row of 192, not the 256 it occupies). What a
decode step reads of the held experts, of the lanes' keys and values and of
the rings is data-dependent, so it comes from the program's counters, as
the capture gives them; where they are missing the bytes are ``None``,
never a guess.
"""

from __future__ import annotations

# one copy of the margin among the architecture modules (numpy only, as
# this module jax-free at import)
from benchmark.architectures.afmoe import picks_margin
# a kernel's device seconds from the run's own events, beyond the ten ops
# the reduction names: one copy among the modules
from benchmark.architectures.jamba import kernel_seconds  # noqa: F401
# the engine's batcher found by its parameters, and the process's peak: one
# copy among the modules whose comparison borrows the serving cache
from benchmark.architectures.joyai_llm_flash import (
    _memory_peak, _serving_batcher)
# the live lanes' lengths: spread to the longest context, on both sides of
# the read's 256-key block (every edge of it a multiple of the ring's 128
# too: a lane whose steps cross one wraps its ring), of the 1792 bucket's
# edge, and at the traffic's own prompt lengths
from benchmark.architectures.lfm2_moe import MANY, STEP, lane_lengths
from benchmark.manifest import ManifestError

FAMILY = "benchmark_mimo_v2"

# Agreement asked of the served path: four limits, any of which fails it
# (``compare_served`` says what each compares), each between two readings on
# the chip at the cell's own lengths (my chip runs, PR 57: 64 lanes, 56
# live, each prefilled at its own length 124-12,249 by the batcher's own
# executables in the buckets the traffic pads to, 1792 (four rows a call
# and one), 3584, 6144, 9728, and 12288 past them; lengths on both sides of
# the read's 256-key block and so of multiples of the ring's 128, three
# lanes' steps crossing one; 8 steps of the timed burst's own executable;
# the reference over 12,257 tokens): the largest over the sound seeds (call
# 1's engine run, call 2's calibration, the cell's thirteen runs of calls
# 3-4, calls 6-8: PERF.md section 6, PR 57) and the least of the controls
# that must fail (call 2, seed 3057000211, read before any limit was set;
# calls 6 and 7 through ``judge`` with the limits as they stand). The
# nearest precision below the configuration's: ``all_bfloat16`` (the stream,
# the router's logits, the scores, the softmax and every product's result in
# bfloat16, where float32 is stated), which the ROWS and the RINGS tell and
# the logits and the picks do not; ``weights_8bit`` fails every limit.
#
# ``TOLERANCE``: max |served - reference| over the compared logits (every
# live lane at every decode step x the sliced vocabulary, and the whole
# prompt's last position) over the reference logits' standard deviation,
# with the reference routed as the served model routed (unrouted, a flipped
# pick is a whole expert's output: the afmoe module's finding). Sound
# 0.0317-0.0367. 8-bit weights 0.381, no value scale 0.368, no sink 0.402,
# one rotary base 0.698, interleaved pairs 0.942, rotary over the whole
# head 1.058, the KV groups swapped 1.178; a window of 127 / 129 0.138 /
# 0.144, and a sink on the full layers too 0.0575 (two full layers of
# seven, under keys in the thousands where a sink at 8 weighs little: the
# logits hardly tell it; ``PICKS_MARGIN`` and the rows of the lanes do);
# ``all_bfloat16`` 0.045-0.047 (its own rounding beside the served path's:
# not told here). So 0.1: 2.7 times the largest sound reading, 0.72
# of a window off by one (call 6, under this limit: 0.162 / 0.150).
#
# ``PICKS_MARGIN``: how far outside the reference router's own top 8 a
# served pick may lie, in the router's score (``sigmoid`` of the logit), as
# the afmoe module's: 0 where the picks are the reference's. A score that
# differs by bfloat16 rounding swaps two experts whose reference scores lie
# closer than that rounding (``picks_agree`` 0.952-0.954): that is allowed,
# and no other. Sound 0.0028-0.0041; a window of 129 / 127 0.0129 / 0.0134,
# no value scale 0.048, 8-bit weights 0.053, a sink on the full layers
# 0.082, one rotary base 0.099 and more for the rest; ``all_bfloat16``
# 0.0039 / 0.0078 (a router in bfloat16 swaps near ties, ``picks_agree``
# 0.849: told on one seed of two). So 0.0065: 1.6 times the largest sound reading,
# half of a window off by one (call 6: 0.0142 / 0.0187).
#
# ``ROWS_TOLERANCE``: the FULL layers' K and V rows themselves against the
# reference's own at that position, |served - reference|_F / |reference|_F,
# the mean over the two layers and over K and V; the largest of (a) the
# whole prompt's 12,249 as the family's prefill returned them, (b) the 8
# rows the decode steps wrote in each live lane, (c) lane by lane what the
# BATCHER'S OWN prefill and insert left in the cache for the lanes at 1300,
# 3100, 5900, 9700 and 12249. Sound 0.00408-0.00425 ((a), (b) 0.00396-
# 0.00398; a row's rounding does not grow with its position). A window of
# 127 / 129 0.00802 / 0.00804 (layer 5's rows stand on four window layers'
# outputs), a sink on the full layers 0.0145 at 1300, no value scale
# 0.033, no sink 0.040, 8-bit weights 0.050, one rotary base 0.059 and more
# for the rest; ``all_bfloat16`` 0.00546-0.00560 (calls 7, 6: a row rounded
# twice, by the path and by the reference, is sqrt(2) x 0.0041 before the
# softmax's own rounding). So 0.0049, the geometric mean: 1.15 times the
# largest sound reading, 0.90 of ``all_bfloat16``'s least (rounding's
# statistics over 12 thousand rows of 768 numbers move in the third digit
# between seeds: 0.00408-0.00425 over all of them).
#
# ``RINGS_TOLERANCE``: every live lane's rings (the window layers' last
# ``min(len, 128)`` rows, each at its slot ``pos mod 128``) as the batcher's
# prefill and insert left them at the lane's OWN length, as the last decode
# step left them (a lane whose steps crossed a multiple of 128 has wrapped)
# and the whole prompt's, against the reference's rows at those positions,
# relative, the mean over the five window layers and over K and V. Sound
# 0.00467-0.00471. A window of 129 / 127 0.00919 / 0.00921, no sink 0.043,
# no value scale 0.043, 8-bit weights 0.061, the KV groups swapped 0.132,
# one rotary base 0.442 (the window layers' own base), a ring taken at the
# padded bucket's end 1.377 (every lane holds another position's rows;
# nothing else sees it); ``all_bfloat16`` 0.00655-0.00657 (calls 7, 6). So
# 0.0056, the geometric mean: 1.19 times the largest sound reading (the
# steadiest of the four: 0.00467-0.00471 over every seed), 0.85 of
# ``all_bfloat16``'s.
#
# The batcher's own programs against the family's, which the reference
# follows. Its prefills hand out a token and no logits: ``prefill_margin``,
# how far under the reference's largest logit at a lane's last prompt
# position the token lies that the lane's prefill sampled, in deviations,
# by ``TOLERANCE``: 0.0-0.025 (a near-tie's other side). Its burst against
# the program's own step fed the burst's tokens: ``burst_margin`` by
# ``TOLERANCE`` too, 0.0034-0.0289. ``BURST_TOLERANCE``: the rows and the
# rings the burst left against the steps', relative, the largest over
# layers: 0.0 on most sound seeds and up to 0.0002 / 0.00008 (two of the
# engine's runs in calls 6-7: two executables round a later layer's input
# an ulp apart); a live lane the burst leaves out: rows 0.069, rings
# 0.048, and the counters do not hold. So 0.02.
TOLERANCE = 0.1
PICKS_MARGIN = 0.0065
ROWS_TOLERANCE = 0.0049
RINGS_TOLERANCE = 0.0056
BURST_TOLERANCE = 0.02

BYTES = 2        # bfloat16 weights, keys and values
SLIDING, FULL = "sliding_attention", "full_attention"
# a control that is the served path's to get wrong, not the model's: the
# rings compared where a prefill that ignored ``last_index`` would have
# left them, at the padded bucket's end
RING_AT_BUCKET_END = "ring_at_bucket_end"
BURST_FAULTS = ("burst_idles_a_lane",)


# -- the served family ---------------------------------------------------------

def __getattr__(name: str):
    # built when the program asks for it by its dotted path: defining it
    # imports the program, and with it jax
    if name != "SeededMimoV2LM":
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    from seldon_core_tpu.models.mimo_v2 import MimoV2LM

    class SeededMimoV2LM(MimoV2LM):
        def init_params(self, seed: int = 0):
            """The program's own draw, layer by layer: one compiled program
            a kind of layer (three here: full dense, window routed, full
            routed) and one for the embedding and head, each leaf cast to
            the served dtype inside it. The float32 draw of an expert layer
            is 2 GB and goes when its cast is done."""
            import jax
            import jax.numpy as jnp

            dt = jnp.dtype(self.cfg.dtype)

            def cast(tree):
                return jax.tree_util.tree_map(lambda a: a.astype(dt), tree)

            layer = jax.jit(
                lambda key, window, routed: cast(
                    self.init_layer(key, window, routed)),
                static_argnums=(1, 2))
            keys = jax.random.split(jax.random.PRNGKey(seed), self.cfg.n_layers + 1)
            return dict(
                jax.jit(lambda key: cast(self.init_top(key)))(keys[-1]),
                layers=[layer(keys[l], window, routed) for l, (window, routed)
                        in enumerate(zip(self._window, self._routed))])

    globals()[name] = SeededMimoV2LM
    return SeededMimoV2LM


def register() -> None:
    from seldon_core_tpu import models
    # a program without the family fails here, at once and cleanly
    from seldon_core_tpu.models import mimo_v2  # noqa: F401

    models.register(FAMILY, f"{__name__}.SeededMimoV2LM")


def served_layer_types(cfg: dict) -> list:
    """The kinds of the layers that are served: the published
    ``hybrid_layer_pattern`` at the ``served_layers``' indices."""
    served = cfg["served_layers"]
    if len(served) != cfg["num_hidden_layers"]:
        raise ManifestError(
            f"{cfg['name']}: served_layers names {len(served)} layers, "
            f"num_hidden_layers says {cfg['num_hidden_layers']}")
    return [SLIDING if cfg["hybrid_layer_pattern"][i] else FULL for i in served]


def n_dense(cfg: dict) -> int:
    """Served layers with a dense FFN: the published leading ones."""
    dense = [not cfg["moe_layer_freq"][i] for i in cfg["served_layers"]]
    if dense != sorted(dense, reverse=True):
        raise ManifestError(f"{cfg['name']}: the dense layers lead")
    return sum(dense)


def held(cfg: dict) -> tuple:
    """``(first, count)`` of the experts this chip holds of each layer."""
    first, end = cfg["experts_held"]
    if end - first != cfg["n_routed_experts"] or not (
            0 <= first < end <= cfg["n_routed_experts_published"]):
        raise ManifestError(
            f"{cfg['name']}: experts_held {cfg['experts_held']} is not "
            f"n_routed_experts = {cfg['n_routed_experts']} of the published "
            f"{cfg['n_routed_experts_published']}")
    return first, end - first


def rotary_dim(cfg: dict) -> int:
    """``int(partial_rotary_factor x head_dim)``, an even number of dims."""
    return int(cfg["partial_rotary_factor"] * cfg["head_dim"]) // 2 * 2


def model_kwargs(cfg: dict, seed: int) -> dict:
    """The published config's keys as ``DecoderLM(block="mimo_v2")`` takes
    them."""
    if not (cfg["scoring_func"] == "sigmoid" and cfg["norm_topk_prob"]
            and cfg["n_group"] == 1 and cfg["topk_group"] == 1
            and cfg["n_shared_experts"] is None):
        raise ManifestError(
            f"{cfg['name']}: sigmoid scores, normed top-k weights, one "
            "group, no shared expert")
    if not cfg["add_swa_attention_sink_bias"] or cfg[
            "add_full_attention_sink_bias"]:
        raise ManifestError(
            f"{cfg['name']}: a sink in the window layers and none in the full")
    if cfg["attention_bias"] or cfg["tie_word_embeddings"]:
        raise ManifestError(f"{cfg['name']}: no bias; the head is untied")
    if cfg["rope_scaling"].get("rope_type", "default") != "default":
        raise ManifestError(f"{cfg['name']}: unscaled rotary")
    if (cfg["swa_head_dim"], cfg["swa_v_head_dim"],
            cfg["swa_num_attention_heads"], cfg["sliding_window_size"]) != (
            cfg["head_dim"], cfg["v_head_dim"], cfg["num_attention_heads"],
            cfg["sliding_window"]):
        raise ManifestError(
            f"{cfg['name']}: the two kinds share head widths, query heads "
            "and one window")
    return {
        "block": "mimo_v2",
        "vocab_size": cfg["vocab_size"],
        "d_model": cfg["hidden_size"],
        "n_layers": cfg["num_hidden_layers"],
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "v_head_width": cfg["v_head_dim"],
        "rotary_dim": rotary_dim(cfg),
        "swa_window": cfg["sliding_window"],
        "swa_n_kv_heads": cfg["swa_num_key_value_heads"],
        "swa_rope_theta": float(cfg["swa_rope_theta"]),
        "value_scale": float(cfg["attention_value_scale"]),
        "d_ff": cfg["intermediate_size"],
        "max_seq": cfg["server"]["max_seq"],
        "rope_theta": float(cfg["rope_theta"]),
        "norm_eps": float(cfg["layernorm_epsilon"]),
        "dtype": cfg["torch_dtype"],
        "layer_types": served_layer_types(cfg),
        "n_dense_layers": n_dense(cfg),
        "n_routed_experts": cfg["n_routed_experts_published"],
        "experts_held": list(held(cfg)),
        "experts_per_tok": cfg["num_experts_per_tok"],
        "expert_width": cfg["moe_intermediate_size"],
        "route_scale": float(cfg["routed_scaling_factor"] or 1.0),
        "residual_scale": cfg["weights"]["residual_scale"],
        # PRNGKey takes 32 bits; the driver's seeds are larger
        "seed": seed % (2**31 - 1),
    }


def rehearsal(cfg: dict) -> dict:
    """The sizes ``--rehearse-cpu`` puts over the configuration's: every
    ratio kept (2 full and 5 window layers in the served order, 1 and 2 KV
    heads for 4 and 8, keys of 24 beside values of 16 with 8 rotary dims,
    4 of 32 experts held, top 4), a window of 16 and a cache of 1024
    positions (a mix whose contexts end past it is not rehearsed: the tests
    rehearse under a tiny one)."""
    return {
        "server": dict(cfg["server"], max_seq=1024),
        "hidden_size": 128, "num_attention_heads": 8, "num_key_value_heads": 1,
        "swa_num_attention_heads": 8, "swa_num_key_value_heads": 2,
        "head_dim": 24, "swa_head_dim": 24, "v_head_dim": 16,
        "swa_v_head_dim": 16, "partial_rotary_factor": 0.334,
        "sliding_window": 16, "sliding_window_size": 16,
        "intermediate_size": 256, "moe_intermediate_size": 64,
        "n_routed_experts": 4, "experts_held": [0, 4],
        "n_routed_experts_published": 32, "num_experts_per_tok": 4,
        "vocab_size": 1024,
    }


# -- the rows a cache holds, asked of the family ----------------------------------

# The comparison reads what a cache HOLDS, never how its arrays are laid
# out: a family whose comparison borrows the serving cache offers
#
#   ``model.cached_rows(cache, kind, layer, lanes, positions)``: of layer
#   ``layer`` of the ``kind`` (``"full"`` | ``"window"``, counted within the
#   kind), the K and V rows of ``lanes`` [n] at ``positions`` [n, m] (a
#   window layer's: ring slots) as ``([n, m, KV, head_dim], [n, m, KV,
#   v_head_width])``, the stored bits in any float dtype, padding dropped;
#   a gather of the rows asked, never a layer materialised (the comparison
#   runs beside a 14.3 GB peak). ``cache``: the serving cache, or a
#   prefill's slab (the same leaves stacked over a kind's layers, a row a
#   prompt);
#   ``model.read_block(cache_len)``: the block of positions the full
#   layers' ragged walk streams over a cache that long (``kv_rows_read``
#   counts a lane's length rounded up to it).
#
# The window layers' leaves, which the comparison copies, puts back and
# holds bit for bit in the idle lanes, are WHATEVER arrays of the cache hold
# ``swa_window`` slots a lane and not the cache's positions, told by what
# they hold and not by a name (``window_leaf_names``, which refuses a cache
# it cannot tell apart), each led by the lanes (as the batcher's own insert
# takes them).
#
# ``models/mimo_v2.py`` offers neither yet (a ``benchmark`` PR touches no
# program file: PERF.md section 7, PR 58), so the two functions below stand
# in for it and are the ONLY lines of this module that know today's layout
# (``{"k", "v"}`` [lanes, KV, positions, row] a full layer, ``{"wk", "wv"}``
# a window layer's ring, a key row of 192 held 256 wide): they go when the
# family answers itself.

def _rows_as_laid_out_today(model):
    import jax

    dk = model.cfg.head_dim

    @jax.jit
    def gather(k, v, lanes, positions):
        at = lanes[:, None]
        return k[at, :, positions][..., :dk], v[at, :, positions]

    def cached_rows(cache, kind, layer, lanes, positions):
        k, v = ("wk", "wv") if kind == "window" else ("k", "v")
        return gather(cache[k][layer], cache[v][layer], lanes, positions)

    return cached_rows


def _read_block_as_laid_out_today(cache, cache_len: int) -> int:
    from seldon_core_tpu.ops.decode_attention import walk_block

    k, v = cache["k"][0], cache["v"][0]
    return walk_block(k.shape[1], k.shape[3], k.dtype, cache_len, v.shape[3])


def window_leaf_names(cache, cfg, lanes: int, cache_len: int) -> tuple:
    """The cache's keys that hold the window layers' leaves: every array of
    the cache is led by the lanes; one that holds ``cache_len`` positions a
    lane is a full layer's, any other holds ``swa_window`` slots a lane and
    is a ring's. A cache that does not divide so (a leaf of neither kind,
    both kinds under one key, or rings too small for the rows the window
    layers hold) is REFUSED: the comparison would restart and hold the
    wrong arrays, and nothing else would say so."""
    import jax
    import numpy as np

    window = cfg.swa_window
    if window == cache_len:
        raise ValueError(f"a cache of {cache_len} positions and rings of as "
                         f"many slots cannot be told apart")
    names, slots = [], 0
    for name, held in cache.items():
        shapes = [a.shape for a in jax.tree_util.tree_leaves(held)]
        if not shapes:
            continue
        full = [cache_len in shape[1:] for shape in shapes]
        ring = [window in shape[1:] and not f for shape, f in zip(shapes, full)]
        if any(shape[0] != lanes for shape in shapes) or not (
                all(full) or all(ring)):
            raise ValueError(
                f"cache[{name!r}] holds {shapes}: not {lanes} lanes of "
                f"{cache_len} positions each, nor of {window} slots each")
        if all(ring):
            names.append(name)
            slots += sum(int(np.prod(shape)) for shape in shapes)
    n_window = sum(t == SLIDING for t in cfg.layer_types)
    need = lanes * n_window * window * cfg.swa_n_kv_heads * (
        cfg.head_dim + cfg.v_head_width)
    if slots < need:
        raise ValueError(f"the rings {names} hold {slots} numbers, the window "
                         f"layers' rows are {need}")
    return tuple(names)


# -- the served model against the plain reference ----------------------------------

def compare_served(model, params, seed: int, prompt_len: int = 0,
                   decode_steps: int = 0, variant: str = "",
                   batcher=None) -> dict:
    """The served path at the cell's lengths and from the programs the
    window drives, against ONE full causal forward of the reference over
    the same ``prompt_len + decode_steps`` tokens.

    ``batcher``: the ``ContinuousBatcher`` whose cache, lanes and
    executables are used: the one given, else the process's own that
    serves ``params`` (the engine's: idle while the parent asks for the
    comparison; ``borrowed`` says it was found). None is built here: a
    second cache would not fit beside the first. ``prompt_len``: where the
    batcher was warmed (the engine's, for the cell's traffic), its longest
    prompt and its most new tokens less the steps: the longest lane's steps
    end where the cell's longest contexts end; else what fits.
    ``decode_steps``: the batcher's ``_k``, so that the burst is the TIMED
    executable.

    A ring cannot be cut back to a shorter prompt as a full layer's columns
    can, so each live lane's rows come from a prefill of ITS OWN: lane j
    holds the first L_j tokens (``lane_lengths``: most lanes live, every
    eighth idle, lengths spread to the longest context, on both sides of
    the read's 256-key block and so of multiples of the ring's 128, of a
    bucket's edge, the traffic's own prompt lengths and one under the
    window among them), prefilled by the BATCHER'S OWN compiled prefill in
    the smallest bucket the batcher was warmed for that holds it and put
    into its lane by the batcher's own compiled insert. The steps below
    write at L_j, L_j + 1, ...: the full layers' rows of each run are
    overwritten by the next before it reads them, the rings are put back
    from the inserts' own (kept on the device) before each run.

    (0) The family's ``model._prefill`` over the whole prompt, one row: its
    last logits, its rows and ring, and every position's picks, by which
    the reference is routed. (1) The batcher's compiled burst (``_burst_fn``
    at its ``_k``): its tokens, its counters, the rows and rings it leaves.
    (2) The program's own step (``model._step``) fed the BURST'S tokens: the
    burst must have sampled each step's argmax, left the same rows and
    rings, and counted the same. (3) That step fed the prompt's own next
    tokens, whose logits, picks, rows and rings the reference's one forward
    can be compared with. ``variant``: one of ``reference.VARIANTS`` (a
    wrong reference), ``RING_AT_BUCKET_END`` or of ``BURST_FAULTS``: the
    controls that must fail.

    Held: ``ratio`` <= ``TOLERANCE``; ``picks_margin`` <= ``PICKS_MARGIN``;
    ``rows_ratio`` <= ``ROWS_TOLERANCE``; ``rings_ratio`` <=
    ``RINGS_TOLERANCE``; ``prefill_margin`` and ``burst_margin`` <=
    ``TOLERANCE``, ``burst_rows_ratio`` and ``burst_rings_ratio`` <=
    ``BURST_TOLERANCE``; an idle lane's rings bit for bit what they were;
    the step's counters are the picks' and the lengths' own count and the
    burst's sum to the steps'; some lane wrapped its ring; and the step was
    busy (the held experts touched that uniform picks of the live lanes
    would touch, about the share's part of the picks held)."""
    served = serve(model, params, seed, prompt_len, decode_steps,
                   variant == "burst_idles_a_lane", batcher)
    return judge(model, served, params,
                 "" if variant in BURST_FAULTS else variant)


def ring_positions(lengths, window: int):
    """[len(lengths), window]: the position slot ``s`` of a ring holds for a
    lane of each length, ``-1`` where no position has reached the slot."""
    import numpy as np

    last = np.asarray(lengths)[:, None] - 1
    at = last - (last - np.arange(window)[None]) % window
    return np.where(at >= 0, at, -1)


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
    window = cfg.swa_window
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
    # one lane under the window whose steps reach it: its ring fills
    under = max(4, window - decode_steps // 2)
    start = lane_lengths(lanes, prompt_len, decode_steps, (*asked, under))

    def wraps(n):
        return n // window != (n + decode_steps) // window

    if not any(wraps(n) for n in start.values()):
        # too few lanes for the edges to be placed: the shortest lane moves
        # up to where its steps cross a multiple of the window
        j = min(start, key=start.get)
        start[j] = (start[j] // window + 1) * window - decode_steps // 2
    live = np.array([j in start for j in range(lanes)])
    at = np.array([start.get(j, 0) for j in range(lanes)])
    kinds = [t == SLIDING for t in cfg.layer_types]
    n_window = sum(kinds)
    n_full = len(kinds) - n_window
    n_routed_layers = len(kinds) - cfg.n_dense_layers
    lo, n_held = cfg.experts_held or (0, cfg.n_routed_experts)

    ask_rows = getattr(model, "cached_rows", None) or _rows_as_laid_out_today(
        model)

    def rows(cache, kind, layer, lanes, positions):
        """``cached_rows`` (the section above) of lanes [n] at positions [m]
        (every lane's) or [n, m], as float32 numpy: ([n, m, KV, Dk], [n, m,
        KV, Dv])."""
        lanes = np.asarray(lanes, np.int32).reshape(-1)
        at = np.broadcast_to(np.asarray(positions, np.int32),
                             (len(lanes), np.shape(positions)[-1]))
        k, v = ask_rows(cache, kind, layer, jnp.asarray(lanes), jnp.asarray(at))
        return np.asarray(k, np.float32), np.asarray(v, np.float32)

    top = batcher._bucket(prompt_len)

    def bucket_of(n):
        return next((b for b in warm_buckets if n <= b),
                    top if warm_buckets else batcher._bucket(n))

    def padded(rows, bucket):
        out = np.zeros((len(rows), bucket), np.int32)
        for i, n in enumerate(rows):
            out[i, :n] = tokens[:n]
        return jnp.asarray(out)

    # (0) the whole prompt through the family's prefill, one row
    logits, slab, routed = jax.jit(
        lambda p, t, last: model._prefill(p, t, top, last))(
            params, padded([prompt_len], top),
            jnp.asarray([prompt_len - 1], jnp.int32))
    first = np.asarray(logits[0])
    picks = [np.concatenate([np.asarray(r[0, :prompt_len]),
                             np.zeros_like(r[0, :decode_steps])])
             for r in routed]
    # the one prompt's rows, and its rings as its last token left them
    slots = np.arange(min(top, window))
    slab_kv = [tuple(a[0] for a in rows(slab, "full", l, [0],
                                        np.arange(prompt_len)))
               for l in range(n_full)]
    slab_ring = [tuple(a[0] for a in rows(slab, "window", l, [0], slots))
                 for l in range(n_window)]
    del logits, slab, routed

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
    sampled = {}
    ring_names = window_leaf_names(cache, cfg, lanes, cache_len)
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
            del slab
        inserted = bool(
            np.array_equal(np.asarray(lane_pos), at) and np.array_equal(
                np.asarray(cur_tok)[live], tokens[at[live]]))
        shown = sorted({j for j in start if start[j] in asked}
                       | {max(start, key=start.get)})
        lane_rows = {
            j: [tuple(a[0] for a in rows(cache, "full", l, [j],
                                         np.arange(start[j])))
                for l in range(n_full)] for j in shown}

        def ring_leaves(cache):
            """A copy of whatever the cache holds for the window layers."""
            return jax.tree_util.tree_map(
                jnp.copy, {n: cache[n] for n in ring_names})

        # the rings as the inserts left them, every lane's: each run below
        # starts from these (0.25 GB in all)
        rings0 = ring_leaves(cache)
        live_ix = np.flatnonzero(live)

        def rings_of(cache):
            """The live lanes' rings per window layer, ([live, W, KV, Dk],
            [live, W, KV, Dv]) float32, and the idle lanes' leaves as they
            are."""
            out = [rows(cache, "window", l, live_ix, np.arange(window))
                   for l in range(n_window)]
            idle = [np.asarray(a)[~live] for a in jax.tree_util.tree_leaves(
                {n: cache[n] for n in ring_names})]
            return out, idle

        rings_at_insert, idle_rings = rings_of(cache)
        new_at = at[live, None] + np.arange(decode_steps)[None]   # [live, steps]

        def written(cache):
            """The K and V rows at each live lane's new positions, per full
            layer ([live, steps, KV, Dk], [live, steps, KV, Dv])."""
            return [rows(cache, "full", l, live_ix, new_at)
                    for l in range(n_full)]

        def restarted(cache):
            return dict(cache, **ring_leaves(rings0))

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
        burst_rows = written(cache)
        burst_rings, idle_after = rings_of(cache)
        idle_untouched = all(
            np.array_equal(a, b) for a, b in zip(idle_rings, idle_after))

        step = jax.jit(model._step, donate_argnums=(1,))

        def steps(cache, feed):
            outs = []
            for i in range(decode_steps):
                pos = np.where(live, at + i, 0)
                out, cache, counts, routed = step(
                    params, cache,
                    jnp.asarray(np.where(live, feed(i), 0)[:, None], jnp.int32),
                    jnp.asarray(pos, jnp.int32),
                    lens=jnp.asarray(np.where(live, pos + 1, 0), jnp.int32))
                outs.append((np.asarray(out), np.asarray(counts),
                             [np.asarray(r)[:, 0] for r in routed]))
            return outs, cache

        # (2) the step, fed the burst's tokens
        outs, cache = steps(restarted(cache), lambda i: toks[i])
        step_rows = written(cache)
        step_rings, _idle = rings_of(cache)
        burst_margin, agree = 0.0, []
        for i, (out, _c, _r) in enumerate(outs):
            mine = out[active]
            theirs = mine[np.arange(len(mine)), toks[i + 1][active]]
            agree.append(mine.argmax(-1) == toks[i + 1][active])
            burst_margin = max(burst_margin, float(
                (mine.max(-1) - theirs).max() / mine.std()))

        def relative(a, b):
            return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))

        burst_rows_ratio = max(
            [relative(b, a) for mine, theirs in zip(burst_rows, step_rows)
             for b, a in zip(mine, theirs)] or [0.0])
        burst_rings_ratio = max(
            [relative(b, a) for mine, theirs in zip(burst_rings, step_rings)
             for b, a in zip(mine, theirs)] or [0.0])
        summed = np.sum([c for _o, c, _r in outs], axis=0)
        # what live lanes and positions there are sums exactly; a pick that
        # a rounding flips between the two programs moves the counts that
        # follow the picks
        exact = [1, 2, 4, 5, 6, 7, 8]
        burst_counters_hold = bool(
            np.array_equal(burst_counts[exact], summed[exact])
            and np.all(np.abs(burst_counts - summed) <= 0.02 * summed + 1))
        del burst_rows, burst_rings

        # (3) the step, fed the prompt's own tokens: what the reference follows
        outs, cache = steps(restarted(cache), lambda i: tokens[at + i])
        step_rows = written(cache)
        step_rings, _idle = rings_of(cache)
    finally:
        batcher._cache = cache      # handed back, the comparison's rows in it
    read_block = (model.read_block(cache_len) if hasattr(model, "read_block")
                  else _read_block_as_laid_out_today(cache, cache_len))
    del cache
    served, positions = [first], [prompt_len - 1]
    counters_hold = True
    touched = rows = rows_held = 0
    lens_live = at[live]
    for i, (out, counts, routed) in enumerate(outs):
        for j in start:
            served.append(out[j])
            positions.append(int(at[j] + i))
            for mine, r in zip(picks, routed):
                mine[at[j] + i] = r[j]
        here = [r[live][(r[live] >= lo) & (r[live] < lo + n_held)]
                for r in routed]
        distinct = sum(len(np.unique(h)) for h in here)
        pairs = sum(r[live].size for r in routed)
        landed = sum(h.size for h in here)
        now = lens_live + i + 1
        # the kernel walks each live lane's rows in whole blocks (a ring is
        # one block); the dots read the bound of every lane
        if batcher._ragged_read:
            n_read = int((-(-now // read_block) * read_block).sum())
            ring_read = window * int(live.sum())
        else:
            n_read, ring_read = lanes * cache_len, lanes * window
        counters_hold &= counts.tolist() == [
            distinct, pairs, n_routed_layers, landed, n_read * n_full,
            int(now.sum()) * n_full, ring_read * n_window,
            int(np.minimum(now, window).sum()) * n_window,
            int(now.sum()) * n_window]
        touched, rows, rows_held = (touched + distinct, rows + pairs,
                                    rows_held + landed)
    lengths = np.array([start[j] for j in sorted(start)])
    wrapped = sum(wraps(n) for n in lengths.tolist())
    return dict(
        tokens=tokens, positions=positions, served=np.stack(served),
        picks=picks, slab_kv=slab_kv, slab_ring=slab_ring,
        step_rows=step_rows, new_at=new_at, rings_at_insert=rings_at_insert,
        step_rings=step_rings, lengths=lengths,
        buckets=np.array([bucket_of(start[j]) for j in sorted(start)]),
        sampled=np.array([sampled[j] for j in sorted(start)]),
        lane_rows=[(start[j], lane_rows[j]) for j in shown],
        prefill_calls=[(bucket, len(group)) for bucket, group in calls],
        prompt_len=prompt_len, bucket=top, lanes=lanes, cache_len=cache_len,
        read_block=read_block, lanes_live=int(live.sum()), borrowed=borrowed,
        lanes_wrapped=wrapped, touched=touched, rows=rows, rows_held=rows_held,
        decode_steps=decode_steps, counters_hold=bool(counters_hold),
        agree=float(np.mean(agree)), burst_margin=burst_margin,
        burst_rows_ratio=burst_rows_ratio, burst_rings_ratio=burst_rings_ratio,
        burst_counters_hold=burst_counters_hold, inserted=inserted,
        idle_untouched=bool(idle_untouched), served_s=time.monotonic() - t0,
        memory_peak_bytes=[peak_before, _memory_peak()])


def judge(model, served: dict, params, variant: str = "") -> dict:
    """The reference's half: ONE causal forward of the plain reference
    (``variant``: a wrong one) over the tokens ``serve`` served, routed as
    the served model routed, and the limits."""
    import time

    import numpy as np

    from benchmark.reference import mimo_v2 as reference

    t1 = time.monotonic()
    cfg = model.cfg
    s = served
    tokens, positions, picks = s["tokens"], s["positions"], s["picks"]
    prompt_len, decode_steps = s["prompt_len"], s["decode_steps"]
    window = cfg.swa_window
    n_routed_layers = cfg.n_layers - cfg.n_dense_layers
    _lo, n_held = cfg.experts_held or (0, cfg.n_routed_experts)
    touched, rows, rows_held = s["touched"], s["rows"], s["rows_held"]
    rings_wrong = variant == RING_AT_BUCKET_END
    lengths, sampled = s["lengths"], s["sampled"]
    # the steps' positions, then each live lane's last prompt position: the
    # batcher's prefills handed out a token there and no logits
    ref, ref_picks, ref_scores, ref_kv = reference.forward(
        params, cfg, tokens, positions + (lengths - 1).tolist(),
        "" if rings_wrong else variant, route_as=picks)
    ref, ref_last = ref[:len(positions)], ref[len(positions):]
    scale = float(ref.std())
    prefill_margin = float((ref_last.max(-1) - ref_last[
        np.arange(len(sampled)), sampled]).max() / scale)
    by_position = (np.max(np.abs(s["served"] - ref), axis=-1) / scale).tolist()
    err = max(by_position)
    margin = max([picks_margin(mine, theirs)
                  for mine, theirs in zip(picks, ref_scores)] or [0.0])
    same = [np.all(np.sort(mine, -1) == np.sort(theirs, -1), -1)
            for mine, theirs in zip(picks, ref_picks)]
    kinds = [t == SLIDING for t in cfg.layer_types]
    ref_full = [kv for kv, w in zip(ref_kv, kinds) if not w]
    ref_ring = [kv for kv, w in zip(ref_kv, kinds) if w]

    def relative(a, b):
        return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))

    new_at = s["new_at"]
    prefill_rows = [relative(mine, theirs[:prompt_len])
                    for pair, ref_pair in zip(s["slab_kv"], ref_full)
                    for mine, theirs in zip(pair, ref_pair)]
    stepped = [relative(mine, theirs[new_at])
               for pair, ref_pair in zip(s["step_rows"], ref_full)
               for mine, theirs in zip(pair, ref_pair)]
    lane_rows = [float(np.mean([relative(mine, theirs[:n])
                                for pair, ref_pair in zip(pairs, ref_full)
                                for mine, theirs in zip(pair, ref_pair)]))
                 for n, pairs in s["lane_rows"]]
    rows_ratio = max(float(np.mean(prefill_rows)), float(np.mean(stepped)),
                     *lane_rows)

    def rings(mine, ends):
        """Rings [lanes, W, KV, .] per window layer against the reference's
        rows at the positions a ring of each length ``ends`` holds; slots
        nothing has reached are not compared."""
        at = ring_positions(ends, window)[:, :mine[0][0].shape[1]]
        ok = at >= 0
        return float(np.mean([
            relative(got[ok], theirs[np.maximum(at, 0)][ok])
            for pair, ref_pair in zip(mine, ref_ring)
            for got, theirs in zip(pair, ref_pair)] or [0.0]))

    where = np.minimum(s["buckets"], prompt_len) if rings_wrong else lengths
    ring_insert = rings(s["rings_at_insert"], where)
    ring_steps = rings(s["step_rings"], lengths + decode_steps)
    ring_prefill = rings([tuple(a[None] for a in pair)
                          for pair in s["slab_ring"]], [prompt_len])
    rings_ratio = max(ring_insert, ring_steps, ring_prefill)
    finite = bool(np.isfinite(s["served"]).all())
    per_layer_step = touched / max(1, n_routed_layers * decode_steps)
    share = n_held / cfg.n_routed_experts
    # what uniform picks of that many live lanes would touch of the share
    expected = n_held * (1.0 - (
        1.0 - cfg.experts_per_tok / cfg.n_routed_experts) ** s["lanes_live"])
    busy = (per_layer_step > 0.6 * expected
            and 0.6 * share < rows_held / max(1, rows) < 1.6 * share)
    burst_holds = (s["inserted"] and s["idle_untouched"]
                   and s["burst_counters_hold"]
                   and prefill_margin <= TOLERANCE
                   and s["burst_margin"] <= TOLERANCE
                   and s["burst_rows_ratio"] <= BURST_TOLERANCE
                   and s["burst_rings_ratio"] <= BURST_TOLERANCE)
    return {
        "ratio": err, "ratio_at": positions[int(np.argmax(by_position))],
        "tolerance": TOLERANCE, "picks_margin": margin,
        "picks_margin_most": PICKS_MARGIN, "rows_ratio": rows_ratio,
        "rows_tolerance": ROWS_TOLERANCE,
        "rows_ratio_prefill": float(np.mean(prefill_rows)),
        "rows_ratio_steps": float(np.mean(stepped)),
        "rows_ratio_lanes": dict(zip(
            (str(n) for n, _ in s["lane_rows"]), lane_rows)),
        "rings_ratio": rings_ratio, "rings_tolerance": RINGS_TOLERANCE,
        "rings_ratio_insert": ring_insert, "rings_ratio_steps": ring_steps,
        "rings_ratio_prefill": ring_prefill,
        "lanes_wrapped": s["lanes_wrapped"],
        "prefill_margin": prefill_margin,
        "prefill_calls": s["prefill_calls"],
        "picks_agree": float(np.mean(same)) if same else 1.0,
        "logit_std": scale, "positions": len(positions),
        "prompt_len": prompt_len, "bucket": s["bucket"],
        "decode_steps": decode_steps, "read_block": s["read_block"],
        "lanes_live": s["lanes_live"], "lanes": s["lanes"],
        "cache_len": s["cache_len"], "borrowed": s["borrowed"],
        "experts_touched_a_layer_step": per_layer_step,
        "rows_per_touched_expert": rows_held / max(1, touched),
        "held_rows_share": rows_held / max(1, rows),
        "counters_are_the_picks": s["counters_hold"], "finite": finite,
        "burst_tokens_agree": s["agree"], "burst_margin": s["burst_margin"],
        "burst_rows_ratio": s["burst_rows_ratio"],
        "burst_rings_ratio": s["burst_rings_ratio"],
        "burst_tolerance": BURST_TOLERANCE,
        "burst_counters_hold": s["burst_counters_hold"],
        "inserted": s["inserted"], "idle_untouched": s["idle_untouched"],
        "served_s": s["served_s"], "reference_s": time.monotonic() - t1,
        # the process's peak so far: before the comparison, after its
        # served half, after the reference
        "memory_peak_bytes": s["memory_peak_bytes"] + [_memory_peak()],
        "ok": bool(finite and err <= TOLERANCE and margin <= PICKS_MARGIN
                   and rows_ratio <= ROWS_TOLERANCE
                   and rings_ratio <= RINGS_TOLERANCE and s["counters_hold"]
                   and s["lanes_wrapped"] > 0 and busy and burst_holds),
    }


# -- what a step must read and a prefill must compute -------------------------------

def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _kinds(cfg: dict) -> tuple:
    """``(window layers, full layers)`` served."""
    kinds = served_layer_types(cfg)
    window = sum(1 for k in kinds if k == SLIDING)
    return window, len(kinds) - window


def kv_bytes_per_position_and_layer(cfg: dict, window: bool = False) -> int:
    """Keys and values of one position in ONE layer of the kind, what
    HOLDS something: a key of 192 and a value of 128 a KV head (the cache
    holds the key in a row of 256: the configuration's ``cache_row``)."""
    heads = cfg["swa_num_key_value_heads" if window else "num_key_value_heads"]
    return heads * (cfg["head_dim"] + cfg["v_head_dim"]) * BYTES


def attention_params(cfg: dict, window: bool) -> int:
    """One attention operator: W_q, W_k, W_v, W_o, and a window layer's
    sinks."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    kv = cfg["swa_num_key_value_heads" if window else "num_key_value_heads"]
    return (d * h * cfg["head_dim"] + d * kv * cfg["head_dim"]
            + d * kv * cfg["v_head_dim"] + h * cfg["v_head_dim"] * d
            + (h if window else 0))


def _fixed_params(cfg: dict) -> int:
    """Everything a step reads once outside the routed experts: every
    layer's attention and two norms, the dense layers' FFN, the expert
    layers' router and bias, the final norm and the head's slice (the
    embedding's lookup is one row a lane)."""
    d = cfg["hidden_size"]
    window, full = _kinds(cfg)
    dense = n_dense(cfg)
    routed = window + full - dense
    e = cfg["n_routed_experts_published"]
    return (window * attention_params(cfg, True)
            + full * attention_params(cfg, False)
            + (window + full) * 2 * d + dense * 3 * d * cfg["intermediate_size"]
            + routed * (d * e + e) + d + d * cfg["vocab_size"])


def _steps(cfg: dict, counters: dict):
    """Decode steps the capture's counters cover, or None without them."""
    layer_steps = counters.get("moe_layer_steps", 0)
    window, full = _kinds(cfg)
    routed = window + full - n_dense(cfg)
    return layer_steps / routed if layer_steps > 0 and routed else None


def ring_bytes(cfg: dict, counters: dict):
    """Bytes of the rings' rows the ring read streamed over the capture:
    ``kv_positions_read_window`` (summed over the live lanes, the window
    layers and the steps: a ring's one block of 128 rows) x one row's keys
    and values in one window layer. None without the counter."""
    read = counters.get("kv_positions_read_window", 0)
    if read <= 0:
        return None
    return read * kv_bytes_per_position_and_layer(cfg, True)


def decode_step_bytes(cfg: dict, live_positions: float, counters: dict):
    """Bytes one decode step must move: everything outside the routed
    experts once; of each expert layer the HELD experts the step's live
    lanes picked (``moe_experts_touched / moe_layer_steps`` over the
    capture); each live lane's ring rows that a query sees
    (``kv_positions_seen_window`` over the steps); the live keys and values
    of the FULL layers only. None where the program gave no such counters."""
    steps = _steps(cfg, counters)
    if steps is None:
        return None
    _window, full = _kinds(cfg)
    touched = counters["moe_experts_touched"] / steps       # all layers
    rings = (counters.get("kv_positions_seen_window", 0) / steps
             * kv_bytes_per_position_and_layer(cfg, True))
    return ((_fixed_params(cfg) + touched * expert_params(cfg)) * BYTES + rings
            + full * kv_bytes_per_position_and_layer(cfg) * live_positions)


def decode_attn_bytes(cfg: dict, counters: dict):
    """Bytes of K and V the FULL layers' decode attention kernel streamed
    over the capture: ``kv_rows_read`` (each lane's length rounded up to
    the kernel's block, over the full layers and the steps) x one
    position's keys and values in one full layer. None without the
    counter."""
    read = counters.get("kv_rows_read", 0)
    if read <= 0:
        return None
    return read * kv_bytes_per_position_and_layer(cfg)


def kv_step_bytes(cfg: dict, counters: dict):
    """``(bytes of a step that are the lanes' keys and values in the full
    layers, bytes of the step)`` from the program's counters alone. None
    without the counters."""
    steps = _steps(cfg, counters)
    live = counters.get("kv_rows_live", 0)
    if steps is None or live <= 0:
        return None
    _window, full = _kinds(cfg)
    mine = live / steps * kv_bytes_per_position_and_layer(cfg)
    return mine, decode_step_bytes(cfg, live / steps / full, counters)


def swa_band_flops(cfg: dict, padded_tokens: float, sequences: float) -> float:
    """The window layers' useful FLOPs over ``sequences`` prompts of
    ``padded_tokens`` positions in all: a query's scores over keys of 192
    and its product over values of 128, over the ``min(i + 1, window)``
    keys it sees (``T x W - W (W - 1) / 2`` pairs a sequence of T >= W)."""
    if sequences <= 0:
        return 0.0
    w = cfg["sliding_window"]
    pairs = max(0.0, padded_tokens * w - sequences * w * (w - 1) / 2.0)
    per_pair = 2.0 * cfg["num_attention_heads"] * (
        cfg["head_dim"] + cfg["v_head_dim"])
    return per_pair * pairs * _kinds(cfg)[0]


def prefill_attention_flops(cfg: dict, padded_tokens: float,
                            sequences: float) -> float:
    """The attention's useful FLOPs: the full layers' causal half of the
    square at both widths, and the window layers' band."""
    if sequences <= 0:
        return 0.0
    t = padded_tokens / sequences
    per_pair = 2.0 * cfg["num_attention_heads"] * (
        cfg["head_dim"] + cfg["v_head_dim"])
    return (per_pair * sequences * _kinds(cfg)[1] * t * t / 2.0
            + swa_band_flops(cfg, padded_tokens, sequences))


def prefill_flops(cfg: dict, padded_tokens: float, sequences: float,
                  counters: dict) -> float:
    """FLOPs of prefilling ``sequences`` prompts padded to ``padded_tokens``
    positions in all: per position a layer's attention projections; the
    dense FFN, or the router and the picks expected to land on a HELD
    expert (``num_experts_per_tok x n_routed_experts /
    n_routed_experts_published``: the router is near uniform under seeded
    weights); attention as ``prefill_attention_flops``; the head at each
    prompt's last position."""
    if sequences <= 0:
        return 0.0
    d = cfg["hidden_size"]
    window, full = _kinds(cfg)
    dense = n_dense(cfg)
    e = cfg["n_routed_experts_published"]
    picks = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / e
    moe = d * e + picks * expert_params(cfg)
    per_token = (window * attention_params(cfg, True)
                 + full * attention_params(cfg, False)
                 + dense * 3 * d * cfg["intermediate_size"]
                 + (window + full - dense) * moe)
    head = 2.0 * d * cfg["vocab_size"] * sequences
    return (2.0 * per_token * padded_tokens
            + prefill_attention_flops(cfg, padded_tokens, sequences) + head)
