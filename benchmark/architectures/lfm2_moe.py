"""The lfm2_moe decoder (LFM2-24B-A2B): everything the benchmark knows of it.

A configuration whose file says ``"architecture": "lfm2_moe"`` is served,
compared and costed by this module (``manifest.architecture``). The parent
process loads it too and never imports jax: jax and the program are
imported inside the functions that need them.

**The served family.** ``benchmark_lfm2_moe``: the program's own
``DecoderLM(block="lfm2_moe")`` (``seldon_core_tpu/models/lfm2_moe.py``) in
every method but ``init_params``, which runs the program's own draw a layer
at a time under one compiled program a kind of layer and casts each leaf to
the served dtype inside it.

**The cut.** The configuration's file keeps every published width. Depth:
``served_layers`` names the published layers that are served; their kinds
are read from the published ``layer_types`` at those indices, and a layer
is dense where its published index is below ``num_dense_layers``. Experts:
``num_experts`` is what this chip HOLDS of each expert layer,
``experts_held`` [first, end) which ones, ``num_experts_published`` what
the router ranges over. Vocabulary: ``vocab_size`` is the chip's slice;
ids, logits and sampling are over it, and the tied head is its transpose.

**The costs.** Operations and bytes from shapes, the benchmark's own copy.
What a decode step reads of the held experts and of the lanes' keys and
values is data-dependent, so it comes from the program's counters, as the
capture gives them; where they are missing the bytes are ``None``, never a
guess.
"""

from __future__ import annotations

# one copy of the margin among the architecture modules (numpy only, as
# this module jax-free at import)
from benchmark.architectures.afmoe import picks_margin
# a kernel's device seconds from the run's own events, beyond the ten ops
# the reduction names: one copy among the modules
from benchmark.architectures.jamba import kernel_seconds  # noqa: F401
# the engine's batcher found by its parameters, and the process's peak: one
# copy among the modules whose comparison borrows the serving cache (a
# second cache of 64 lanes x 16,384 positions would not fit beside the first)
from benchmark.architectures.joyai_llm_flash import (
    _memory_peak, _serving_batcher)
from benchmark.manifest import ManifestError

FAMILY = "benchmark_lfm2_moe"

# Agreement asked of the served path: five limits, any of which fails it
# (``compare_served`` says what each compares). Each lies between two
# readings on the chip at the cell's own lengths (my chip runs, PR 51,
# calls 6-8: 64 lanes, 56 live, each prefilled at its own length
# 883-14,131 by the batcher's own executables in the buckets the traffic
# pads to, 1792 (four rows a call and one), 4608, 8192, 12288, and 14336
# past them; lengths on both sides of the kernel's 256-key block and of the
# 1792 bucket's edge, the mix's own 1500 / 4100 / 7700 / 12100 among them;
# 8 steps of the timed burst's own executable; the reference over 14,139
# tokens): the largest over 22 sound seeds (8 drawn anew in
# ``_scratch/lfm2_calibrate.py``, 14 in the engine's own runs) and the least
# of the controls that must fail (one seed; ``weights_8bit`` is the nearest
# precision below the configuration's and fails every limit). The first
# session's readings at 224-3,584 tokens in one bucket of 3,584 (26 seeds)
# stand beside them: what the lengths moved is said at each limit.
#
# ``TOLERANCE``: max |served - reference| over the compared logits (every
# live lane at every decode step x the sliced vocabulary, and the whole
# prompt's last position) over the reference logits' standard deviation,
# with the reference routed as the served model routed (unrouted, a
# flipped pick is a whole expert's output: the afmoe module's finding).
# Sound 0.0720-0.1478 on the 22 (at 3.6k: 0.0553-0.0780 and 0.1087 once; a softmax
# over four times the keys). 8-bit weights 0.675, ``q_layernorm`` and
# ``k_layernorm`` left out 0.757, ``rope_theta`` 1e4 1.185, the taps
# reversed 3.847 (the bias in the weights 0.1035 for 0.0995 and the tails
# at the bucket's end the sound reading itself: the logits cannot tell
# them; ``WEIGHTS_TOLERANCE`` and ``TAILS_TOLERANCE`` do). So 0.25: 1.7
# times the largest sound reading, 0.37 of the least control's.
#
# ``PICKS_MARGIN``: how far outside the reference router's own top 4 a
# served pick may lie, in the router's score (``sigmoid`` of the logit,
# plus the bias), as the afmoe module's: 0 where the picks are the
# reference's. A score that differs by bfloat16 rounding swaps two experts
# whose reference scores lie closer than that rounding (3% of the positions
# hold such a swap: ``picks_agree`` 0.968-0.969): that is allowed, and no
# other. Sound 0.0068-0.0118 (at 3.6k: 0.0059-0.0112); 8-bit weights
# 0.117, no q / k norm 0.138, theta 1e4 0.231, the taps reversed 0.759. So
# 0.02: 1.7 times the largest sound reading of the 48 seeds, 0.17 of the
# least control's.
#
# ``ROWS_TOLERANCE``: the cache's K and V rows themselves against the
# reference's own at that position: |served - reference|_F /
# |reference|_F over a layer's rows, the mean over the 3 attention layers
# and over K and V; the largest of (a) the whole prompt's 14,131 as the
# family's prefill returned them, (b) the 8 rows the decode steps wrote in
# each of the 56 live lanes, (c) lane by lane, what the BATCHER'S OWN
# prefill and insert left in the cache for the lanes at 1500, 4100, 7700,
# 12100 and 14131 (``rows_ratio_lanes``). (a) 0.00663-0.00671 and (b)
# 0.00660-0.00666, as at 3.6k (0.00680-0.00708: a row's rounding does not
# grow with its position); (c) 0.00857-0.00956 at 1500 (the four-row
# prefill in the 1792 bucket), 0.0073-0.0085 at 7700, 0.0068-0.0072 at 4100
# and 12100, 0.00663-0.00671 at 14131: another compilation of the prefill
# flips a pick where two scores lie within a rounding, the reference is
# routed as (a)'s program routed, and a flipped position's rows in the
# layers after it differ by an expert's output; the fewer the rows, the
# more one weighs. 8-bit weights 0.0818, no q / k norm 0.424, the taps
# reversed 0.506, theta 1e4 0.745. So 0.02 (0.012 at 3.6k, where (c) was
# not compared): 2.1 times the largest sound reading, 0.24 of the least
# control's.
#
# ``TAILS_TOLERANCE``: every live lane's convolution tails (the two rows
# ``z = B * u`` a layer, as the batcher's prefill left them at the lane's
# OWN length and as the last decode step left them) against the
# reference's ``z`` at those positions, relative, the mean over the 10
# convolution layers. A product of two bfloat16 products, and at insert
# the other compilations' flipped picks as in (c): sound 0.0107-0.0141 at
# insert (at 3.6k, one program: 0.0101-0.0119), 0.01001-0.01018 after the
# steps; no q / k norm 0.0989, 8-bit weights 0.117, theta 1e4 0.144, the
# taps reversed 0.689, and the tails taken at the padded bucket's end
# 1.389 (every lane holds another position's rows). So 0.03: 2.1 times the
# largest sound reading, 0.30 of the least control's.
#
# ``WEIGHTS_TOLERANCE``: the routing weights the served model gave its
# picks (every position of the whole prompt, every live lane's steps)
# against the reference's own weights of those same experts, max |served -
# reference|. The logits cannot tell a bias of deviation 0.015 added to
# the weights as well as to the selection (it moves a weight by a fiftieth
# of itself, under the rounding of twelve layers); the weights can. Sound
# 0.0019-0.0038 (at 3.6k: 0.0016-0.0039); the bias in the weights 0.0185,
# 8-bit weights 0.0220, no q / k norm 0.0393, theta 1e4 0.0475, the taps
# reversed 0.201. So 0.008: 2.1 times the largest sound reading of the 48
# seeds, 0.43 of the least control's.
#
# The batcher's own programs against the family's, which the reference
# follows. Its prefills hand out a token and no logits: ``prefill_margin``,
# how far under the reference's largest logit at a lane's last prompt
# position the token lies that the lane's prefill sampled, in deviations,
# by ``TOLERANCE``: 0.0 in every lane of all 22 (each is the reference's
# argmax). Its burst against the program's own step fed the burst's tokens:
# ``burst_margin`` by ``TOLERANCE`` too, 0.0 on all 48 (every token the
# step's argmax). ``BURST_TOLERANCE``: the rows and the tails the burst left
# against the steps', relative, the largest over layers: 0.0 on most seeds
# and to 0.0018 (rows) and 0.0064 (tails) on the rest (a pick flipped
# between the two compilations puts a rounding into every row after it); a
# live lane the burst leaves out: rows 0.078, tails 0.195 (0.178 and 0.196
# at 3.6k), and the counters do not hold. So 0.02: 3.1 times the largest
# sound reading, 0.26 of the control's least (the first session held these
# to ``TOLERANCE``, which the control's rows passed under: the counters
# alone failed it).
TOLERANCE = 0.25
PICKS_MARGIN = 0.02
ROWS_TOLERANCE = 0.02
TAILS_TOLERANCE = 0.03
WEIGHTS_TOLERANCE = 0.008
BURST_TOLERANCE = 0.02

BYTES = 2        # bfloat16 weights, keys, values and convolution tails
CONV, FULL = "conv", "full_attention"
# a control that is the served path's to get wrong, not the model's: the
# tails compared where a prefill that ignored ``last_index`` would have
# taken them, at the padded bucket's end
TAIL_AT_BUCKET_END = "tail_at_bucket_end"
BURST_FAULTS = ("burst_idles_a_lane",)


# -- the served family ---------------------------------------------------------

def __getattr__(name: str):
    # built when the program asks for it by its dotted path: defining it
    # imports the program, and with it jax
    if name != "SeededLfm2MoeLM":
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    from seldon_core_tpu.models.lfm2_moe import Lfm2MoeLM

    class SeededLfm2MoeLM(Lfm2MoeLM):
        def init_params(self, seed: int = 0):
            """The program's own draw, layer by layer: one compiled
            program a kind of layer (four: a convolution or an attention
            operator, a dense or a routed FFN) and one for the embedding,
            each leaf cast to the served dtype inside it. The float32 draw
            of an expert layer is 0.6 GB and goes when its cast is done."""
            import jax
            import jax.numpy as jnp

            dt = jnp.dtype(self.cfg.dtype)

            def cast(tree):
                return jax.tree_util.tree_map(lambda a: a.astype(dt), tree)

            layer = jax.jit(
                lambda key, conv, routed: cast(self.init_layer(key, conv, routed)),
                static_argnums=(1, 2))
            keys = jax.random.split(jax.random.PRNGKey(seed), self.cfg.n_layers + 1)
            return dict(
                jax.jit(lambda key: cast(self.init_top(key)))(keys[-1]),
                layers=[layer(keys[l], conv, routed) for l, (conv, routed)
                        in enumerate(zip(self._conv, self._routed))])

    globals()[name] = SeededLfm2MoeLM
    return SeededLfm2MoeLM


def register() -> None:
    from seldon_core_tpu import models
    # a program without the family fails here, at once and cleanly
    from seldon_core_tpu.models import lfm2_moe  # noqa: F401

    models.register(FAMILY, f"{__name__}.SeededLfm2MoeLM")


def served_layer_types(cfg: dict) -> list:
    """The kinds of the layers that are served: the published
    ``layer_types`` at the ``served_layers``' indices."""
    served = cfg["served_layers"]
    if len(served) != cfg["num_hidden_layers"]:
        raise ManifestError(
            f"{cfg['name']}: served_layers names {len(served)} layers, "
            f"num_hidden_layers says {cfg['num_hidden_layers']}")
    kinds = [cfg["layer_types"][i] for i in served]
    if set(kinds) - {CONV, FULL}:
        raise ManifestError(f"{cfg['name']}: layer kinds {sorted(set(kinds))}")
    return kinds


def n_dense(cfg: dict) -> int:
    """Served layers with a dense FFN: the published leading ones."""
    dense = [i < cfg["num_dense_layers"] for i in cfg["served_layers"]]
    if dense != sorted(dense, reverse=True):
        raise ManifestError(f"{cfg['name']}: the dense layers lead")
    return sum(dense)


def held(cfg: dict) -> tuple:
    """``(first, count)`` of the experts this chip holds of each layer."""
    first, end = cfg["experts_held"]
    if end - first != cfg["num_experts"] or not (
            0 <= first < end <= cfg["num_experts_published"]):
        raise ManifestError(
            f"{cfg['name']}: experts_held {cfg['experts_held']} is not "
            f"num_experts = {cfg['num_experts']} of the published "
            f"{cfg['num_experts_published']}")
    return first, end - first


def model_kwargs(cfg: dict, seed: int) -> dict:
    """The published config's keys as ``DecoderLM(block="lfm2_moe")`` takes
    them."""
    if not (cfg["norm_topk_prob"] and cfg["use_expert_bias"]):
        raise ManifestError(
            f"{cfg['name']}: the router norms its top-k weights and selects "
            "under a bias")
    if cfg["conv_bias"] or not cfg["tie_word_embeddings"]:
        raise ManifestError(f"{cfg['name']}: no bias; the head is tied")
    rope = cfg["rope_parameters"]
    if rope.get("rope_type", "default") != "default":
        raise ManifestError(f"{cfg['name']}: unscaled rotary")
    if cfg["head_dim"] * cfg["num_attention_heads"] != cfg["hidden_size"]:
        raise ManifestError(f"{cfg['name']}: head_dim is hidden / heads")
    return {
        "block": "lfm2_moe",
        "vocab_size": cfg["vocab_size"],
        "d_model": cfg["hidden_size"],
        "n_layers": cfg["num_hidden_layers"],
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "d_ff": cfg["intermediate_size"],
        "max_seq": cfg["server"]["max_seq"],
        "rope_theta": float(rope["rope_theta"]),
        "norm_eps": float(cfg["norm_eps"]),
        "dtype": cfg["torch_dtype"],
        "layer_types": served_layer_types(cfg),
        "conv_kernel": cfg["conv_L_cache"],
        "n_dense_layers": n_dense(cfg),
        "n_routed_experts": cfg["num_experts_published"],
        "experts_held": list(held(cfg)),
        "experts_per_tok": cfg["num_experts_per_tok"],
        "expert_width": cfg["moe_intermediate_size"],
        "route_scale": float(cfg["routed_scaling_factor"]),
        "residual_scale": cfg["weights"]["residual_scale"],
        # PRNGKey takes 32 bits; the driver's seeds are larger
        "seed": seed % (2**31 - 1),
    }


def rehearsal(cfg: dict) -> dict:
    """The sizes ``--rehearse-cpu`` puts over the configuration's: the
    dense layer and one period of four, 4 of 16 experts held, a cache of
    1024 positions (a mix whose contexts end past it is not rehearsed: the
    tests rehearse under a tiny one). Heads of 64, so that two lie in a
    row as at the published widths."""
    return {
        "server": dict(cfg["server"], max_seq=1024),
        "hidden_size": 128, "num_attention_heads": 2, "num_key_value_heads": 2,
        "head_dim": 64, "intermediate_size": 256, "moe_intermediate_size": 64,
        "num_hidden_layers": 5, "served_layers": [1, 2, 3, 4, 5],
        "num_experts": 4, "experts_held": [0, 4], "num_experts_published": 16,
        "num_experts_per_tok": 4, "vocab_size": 1024,
    }


# -- the served model against the plain reference ----------------------------------

IDLE_EVERY = 8      # lanes 5, 13, 21, ... idle among the live ones
READ_BLOCK = 256    # the ragged read's block at these rows (``ops.
#                     decode_attention.walk_block``; asked again in ``serve``):
#                     lengths lie on both its sides
BUCKET_EDGE = 1792  # the batcher's last bucket of its own: lengths on both sides
STEP = 512          # what an unwarmed batcher's prompt is rounded down to
MANY = (8, 4)       # the rows of a batched prefill the scheduler warms and uses


def lane_lengths(lanes: int, prompt_len: int, decode_steps: int,
                 asked: tuple = ()) -> dict:
    """``{lane: tokens it holds before its first step}`` for the live
    lanes: spread evenly from ``prompt_len // 16`` to ``prompt_len`` (the
    lane whose steps end where the cell's longest contexts end), no two
    lanes' steps at one position, and lanes moved, each the lane that lay
    nearest: to the edges of the read's block (a multiple of ``READ_BLOCK``
    less one, where the first step's read ends on the block's last key; the
    multiple itself; one more), to the ``BUCKET_EDGE`` bucket's last length
    and the first past it, and to the lengths ``asked`` (the prompt lengths
    the batcher was warmed for: the traffic's own, one in each bucket the
    mix uses)."""
    import numpy as np

    live = [j for j in range(lanes) if j % IDLE_EVERY != 5]
    lens = np.linspace(max(4, prompt_len // 16), prompt_len,
                       len(live)).round().astype(int)
    blocks = READ_BLOCK * np.arange(lens[0] // READ_BLOCK + 1,
                                    (prompt_len - 1) // READ_BLOCK + 1)
    edges = []
    if len(blocks):
        edges += [(int(blocks[0]), -1), (int(blocks[len(blocks) // 2]), 0),
                  (int(blocks[-1]), 1)]
    if len(live) >= 8 and np.diff(lens).min() >= 3 * decode_steps:
        taken = set()

        def move(at, to):
            if at not in taken and 0 < at < len(lens) - 1 and (
                    lens[at - 1] + decode_steps <= to
                    <= lens[at + 1] - decode_steps):
                lens[at] = to
                taken.add(at)
                return True
            return False

        def place(to):
            """The nearest lane that can take ``to`` takes it."""
            for at in np.abs(lens - to).argsort().tolist():
                if move(at, to):
                    return at
            return None

        for length in asked:
            if 0 < length < prompt_len:
                place(int(length))
        if BUCKET_EDGE + 2 * decode_steps < prompt_len:
            # the bucket's last length, and the next lane the first past
            # where its steps end
            at = place(BUCKET_EDGE)
            if at is not None:
                move(at + 1, BUCKET_EDGE + decode_steps + 1)
        for edge, off in edges:
            place(edge + off)
    if len(live) > 1 and np.diff(lens).min() < decode_steps:
        raise ValueError(f"{len(live)} lanes of {decode_steps} steps do not "
                         f"fit apart in {prompt_len} positions")
    return dict(zip(live, lens.tolist()))


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
    test or a control passes one of the size it wants. The cache is handed
    back with the comparison's rows in it, which a lane's next occupant
    overwrites before any read admits them, as every lane's last
    occupant's are. ``prompt_len``: where the batcher was warmed (the
    engine's, for the cell's traffic), its longest prompt and its most new
    tokens less the steps: the longest lane's steps end where the cell's
    longest contexts end, 14,139 of 16,384; else what fits. ``decode_steps``:
    the batcher's ``_k``, so that the burst is the TIMED executable (no
    second compilation of it).

    A convolution's tail cannot be cut back to a shorter prompt as a KV
    cache's columns can, so each live lane's rows come from a prefill of
    ITS OWN: lane j holds the first L_j tokens (``lane_lengths``: most lanes
    live, every eighth idle, lengths spread to the longest context, on both
    sides of the kernel's block edge and of a bucket's edge, and the
    traffic's own prompt lengths among them), prefilled by the BATCHER'S OWN
    compiled prefill (``_prefill_fn``; ``_prefill_many_fn`` where lanes
    share a bucket that takes several rows a call, as the scheduler groups
    a turn's admissions under ``prefill_rows_max``) in the smallest bucket
    the batcher was warmed for that holds it (the buckets the window's
    prompts pad to; past the last of them, and where nothing was warmed,
    the batcher's own bucket of that length) and put into its lane by the
    batcher's own compiled insert. The steps below write at L_j, L_j + 1,
    ... and leave the tail of the last: the K and V rows of each run are
    overwritten by the next before it reads them, the tails are put back
    from the prefills' own (kept on the device) before each run.

    (0) The family's ``model._prefill`` over the whole prompt, one row (the
    comparison's own ``jit`` of the function the batcher's prefills call:
    the batcher's hand out a token and no picks): its last logits, its K
    and V rows, and every position's picks and weights, by which the
    reference is routed. The batcher's prefills are held to the reference
    by what they leave: the first token each sampled (``prefill_margin``),
    the K and V rows of the lanes at the traffic's own lengths and of the
    longest as the cache holds them after the insert (``rows_ratio_lanes``),
    every lane's tails.
    (1) The batcher's compiled burst (``_burst_fn`` at its ``_k``, the
    cache carried through its scan and donated): its tokens, its counters,
    the rows and tails it leaves. (2) The program's own step
    (``model._step``: ``decode_step_cache``, which the burst's body calls,
    and the picks) one step at a time, fed the BURST'S tokens: the burst
    must have sampled each step's argmax, left the same rows and tails in
    the same lanes, and counted the same. (3) That step fed the prompt's
    own next tokens, whose logits, picks, rows and tails the reference's
    one forward can be compared with: the ragged kernel over the lanes'
    lengths at two heads of 64 a row, the convolution's step from each
    lane's own tail, the touched-expert kernel over this chip's share.
    ``variant``: one of ``reference.VARIANTS`` (a wrong reference),
    ``TAIL_AT_BUCKET_END`` (the tails compared where a prefill that ignored
    ``last_index`` would have taken them) or of ``BURST_FAULTS`` (a live
    lane the burst leaves out): the controls that must fail.

    Held: ``ratio`` <= ``TOLERANCE``; ``picks_margin`` <= ``PICKS_MARGIN``;
    ``rows_ratio`` <= ``ROWS_TOLERANCE``; ``tails_ratio`` <=
    ``TAILS_TOLERANCE``; ``weights_err`` <= ``WEIGHTS_TOLERANCE``;
    ``prefill_margin`` and ``burst_margin`` <= ``TOLERANCE``,
    ``burst_rows_ratio`` and ``burst_tails_ratio`` <= ``BURST_TOLERANCE``
    (the batcher's prefills against the reference, its burst against the
    steps), an idle lane's tails bit
    for bit what they were; the step's counters are the picks' and the
    lengths' own count and the burst's sum to the steps' (``kv_rows_read``
    is the count of the branch the lowering took: each lane's length rounded
    up to the kernel's block a layer where the batcher's read is ragged, so
    a step that fell to the dots on the chip does not hold); and the step
    was busy (several rows on a touched expert, about a quarter of the
    picks held)."""
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
    start = lane_lengths(lanes, prompt_len, decode_steps, asked)
    live = np.array([j in start for j in range(lanes)])
    at = np.array([start.get(j, 0) for j in range(lanes)])
    kinds = [t == CONV for t in cfg.layer_types]
    n_conv = sum(kinds)
    n_full = len(kinds) - n_conv
    n_routed_layers = len(kinds) - cfg.n_dense_layers
    lo, n_held = cfg.experts_held or (0, cfg.n_routed_experts)
    kv_heads, dh = cfg.n_kv_heads, cfg.head_dim

    def unpacked(rows):
        """Cache rows [..., KV / pack, T, pack x Dh] -> [..., T, KV, Dh]
        float32: the packing undone by the benchmark's own arithmetic."""
        rows = np.asarray(rows, np.float32)
        lead, (g, t, w) = rows.shape[:-3], rows.shape[-3:]
        pack = w // dh
        rows = rows.reshape(*lead, g, t, pack, dh)
        rows = np.moveaxis(rows, -4, -3)          # [..., T, g, pack, Dh]
        return rows.reshape(*lead, t, g * pack, dh)

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

    # (0) the whole prompt through the family's prefill, one row: the
    # logits at its end, its rows, every position's picks and weights
    logits, slab, routed, weighed = jax.jit(
        lambda p, t, last: model._prefill(p, t, top, last))(
            params, padded([prompt_len], top),
            jnp.asarray([prompt_len - 1], jnp.int32))
    first = np.asarray(logits[0])
    picks, weights = (
        [np.concatenate([np.asarray(r[0, :prompt_len]),
                         np.zeros_like(r[0, :decode_steps])])
         for r in each] for each in (routed, weighed))
    slab_kv = [(unpacked(slab["k"][l, 0, :, :prompt_len]),
                unpacked(slab["v"][l, 0, :, :prompt_len]))
               for l in range(n_full)]
    del logits, slab, routed, weighed

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
    tails_at_insert, sampled = {}, {}
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
                tails_at_insert[j] = np.asarray(slab["conv"][:, row], np.float32)
                sampled[j] = int(firsts[row])
            del slab
        inserted = bool(
            np.array_equal(np.asarray(lane_pos), at) and np.array_equal(
                np.asarray(cur_tok)[live], tokens[at[live]]))
        # the rows the batcher's prefills left, as the cache holds them: the
        # lanes at the traffic's own lengths and the longest
        shown = sorted({j for j in start if start[j] in asked}
                       | {max(start, key=start.get)})
        lane_rows = {
            j: [(unpacked(cache["k"][l][j, :, :start[j]]),
                 unpacked(cache["v"][l][j, :, :start[j]]))
                for l in range(n_full)] for j in shown}
        # the tails as the inserts left them, every lane's: each run below
        # starts from these (80 KB a lane)
        tails0 = [jnp.copy(a) for a in cache["conv"]]
        idle_tails = [np.asarray(a)[~live] for a in tails0]

        live_ix = jnp.asarray(np.flatnonzero(live), jnp.int32)
        new_at = at[live, None] + np.arange(decode_steps)[None]   # [live, steps]
        gather = jax.jit(lambda cache, j, p: (
            [a[j[:, None], :, p] for a in cache["k"]],
            [a[j[:, None], :, p] for a in cache["v"]],
            [a[j] for a in cache["conv"]]))

        def written(cache):
            """What ``decode_steps`` steps leave: the K and V rows at each
            live lane's new positions, per layer [live, steps, KV, Dh], the
            live lanes' tails [live, 2, D], and the idle lanes' tails."""
            ks, vs, tails = gather(cache, live_ix, jnp.asarray(new_at, jnp.int32))
            # [live, steps, KV / pack, pack Dh] -> [live, steps, KV, Dh]
            rows = [(np.asarray(k, np.float32).reshape(*k.shape[:2], kv_heads, dh),
                     np.asarray(v, np.float32).reshape(*v.shape[:2], kv_heads, dh))
                    for k, v in zip(ks, vs)]
            return (rows, [np.asarray(t, np.float32) for t in tails],
                    [np.asarray(a)[~live] for a in cache["conv"]])

        def restarted(cache):
            return dict(cache, conv=[jnp.copy(a) for a in tails0])

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
        burst_rows, burst_tails, idle_after = written(cache)
        idle_untouched = all(
            np.array_equal(a, b) for a, b in zip(idle_tails, idle_after))

        step = jax.jit(model._step, donate_argnums=(1,))

        def steps(cache, feed):
            """``decode_steps`` steps over all lanes, step i fed ``feed(i)``
            [lanes]: each step's logits, picks and counters, and the cache."""
            outs = []
            for i in range(decode_steps):
                pos = np.where(live, at + i, 0)
                out, cache, counts, routed, weighed = step(
                    params, cache,
                    jnp.asarray(np.where(live, feed(i), 0)[:, None], jnp.int32),
                    jnp.asarray(pos, jnp.int32),
                    lens=jnp.asarray(np.where(live, pos + 1, 0), jnp.int32))
                outs.append((np.asarray(out), np.asarray(counts),
                             [np.asarray(r)[:, 0] for r in routed],
                             [np.asarray(w)[:, 0] for w in weighed]))
            return outs, cache

        # (2) the step, fed the burst's tokens
        outs, cache = steps(restarted(cache), lambda i: toks[i])
        step_rows, step_tails, _idle = written(cache)
        burst_margin, agree = 0.0, []
        for i, (out, _c, _r, _w) in enumerate(outs):
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
        burst_tails_ratio = max(
            [relative(b, a) for b, a in zip(burst_tails, step_tails)] or [0.0])
        summed = np.sum([c for _o, c, _r, _w in outs], axis=0)
        # what live lanes and positions there are sums exactly; a pick that
        # a rounding flips between the two programs moves the counts that
        # follow the picks
        exact = [1, 2, 4, 5, 6]
        burst_counters_hold = bool(
            np.array_equal(burst_counts[exact], summed[exact])
            and np.all(np.abs(burst_counts - summed) <= 0.02 * summed + 1))
        del burst_rows, burst_tails

        # (3) the step, fed the prompt's own tokens: what the reference follows
        outs, cache = steps(restarted(cache), lambda i: tokens[at + i])
        step_rows, step_tails, _idle = written(cache)
    finally:
        batcher._cache = cache      # handed back, the comparison's rows in it
    read_block = walk_block(kv_heads // model._pack, model._pack * dh,
                            cache["k"][0].dtype, cache_len)
    del cache
    served, positions = [first], [prompt_len - 1]
    counters_hold = True
    touched = rows = rows_held = 0
    lens_live = at[live]
    for i, (out, counts, routed, weighed) in enumerate(outs):
        for j in start:
            served.append(out[j])
            positions.append(int(at[j] + i))
            for mine, r in zip(picks + weights, routed + weighed):
                mine[at[j] + i] = r[j]
        here = [r[live][(r[live] >= lo) & (r[live] < lo + n_held)]
                for r in routed]
        distinct = sum(len(np.unique(h)) for h in here)
        pairs = sum(r[live].size for r in routed)
        landed = sum(h.size for h in here)
        # the kernel walks each live lane's length in whole blocks; the dots
        # read the bound of every lane (``Lfm2MoeLM._kv_rows_read``)
        n_read = int((-(-(lens_live + i + 1) // read_block) * read_block).sum()
                     ) if batcher._ragged_read else lanes * cache_len
        counters_hold &= counts.tolist() == [
            distinct, pairs, n_routed_layers, landed, n_read * n_full,
            int((lens_live + i + 1).sum()) * n_full,
            int(live.sum()) * n_conv]
        touched, rows, rows_held = (touched + distinct, rows + pairs,
                                    rows_held + landed)
    return dict(
        tokens=tokens, positions=positions, served=np.stack(served),
        picks=picks, weights=weights, slab_kv=slab_kv, step_rows=step_rows,
        new_at=new_at, step_tails=step_tails,
        tails_at_insert=np.stack([tails_at_insert[j] for j in sorted(start)], 1),
        lengths=np.array([start[j] for j in sorted(start)]),
        buckets=np.array([bucket_of(start[j]) for j in sorted(start)]),
        sampled=np.array([sampled[j] for j in sorted(start)]),
        lane_rows=[(start[j], lane_rows[j]) for j in shown],
        prefill_calls=[(bucket, len(group)) for bucket, group in calls],
        prompt_len=prompt_len, bucket=top, lanes=lanes, cache_len=cache_len,
        read_block=read_block, lanes_live=int(live.sum()), borrowed=borrowed,
        touched=touched, rows=rows, rows_held=rows_held,
        decode_steps=decode_steps, counters_hold=bool(counters_hold),
        agree=float(np.mean(agree)), burst_margin=burst_margin,
        burst_rows_ratio=burst_rows_ratio, burst_tails_ratio=burst_tails_ratio,
        burst_counters_hold=burst_counters_hold, inserted=inserted,
        idle_untouched=bool(idle_untouched), served_s=time.monotonic() - t0,
        memory_peak_bytes=[peak_before, _memory_peak()])


def judge(model, served: dict, params, variant: str = "") -> dict:
    """The reference's half: ONE causal forward of the plain reference
    (``variant``: a wrong one) over the tokens ``serve`` served, routed as
    the served model routed, and the limits."""
    import time

    import numpy as np

    from benchmark.reference import lfm2_moe as reference

    t1 = time.monotonic()
    cfg = model.cfg
    s = served
    tokens, positions, picks = s["tokens"], s["positions"], s["picks"]
    prompt_len, decode_steps = s["prompt_len"], s["decode_steps"]
    n_routed_layers = cfg.n_layers - cfg.n_dense_layers
    _lo, n_held = cfg.experts_held or (0, cfg.n_routed_experts)
    touched, rows, rows_held = s["touched"], s["rows"], s["rows_held"]
    tails_wrong = variant == TAIL_AT_BUCKET_END
    lengths, sampled = s["lengths"], s["sampled"]
    # the steps' positions, then each live lane's last prompt position: the
    # batcher's prefills handed out a token there and no logits
    ref, ref_picks, ref_scores, ref_kv, ref_z, ref_weights = reference.forward(
        params, cfg, tokens, positions + (lengths - 1).tolist(),
        "" if tails_wrong else variant, route_as=picks)
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
    weights_err = max([float(np.abs(mine - theirs).max())
                       for mine, theirs in zip(s["weights"], ref_weights)]
                      or [0.0])

    def relative(a, b):
        return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))

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
    rows_ratio = max(float(np.mean(prefill_rows)), float(np.mean(stepped)),
                     *lane_rows)
    # the tails: z at the last K - 1 positions a lane holds, zeros before
    # the sequence's start
    k1 = cfg.conv_kernel - 1
    where = np.minimum(s["buckets"], prompt_len) if tails_wrong else lengths
    back = np.arange(-k1, 0)

    def tails_of(z, ends):
        padded = np.concatenate([np.zeros((k1, z.shape[1]), z.dtype), z])
        return padded[np.asarray(ends)[:, None] + k1 + back[None]]

    at_insert = [relative(s["tails_at_insert"][l], tails_of(z, where))
                 for l, z in enumerate(ref_z)]
    after = [relative(s["step_tails"][l], tails_of(z, lengths + decode_steps))
             for l, z in enumerate(ref_z)]
    tails_ratio = max(float(np.mean(at_insert or [0.0])),
                      float(np.mean(after or [0.0])))
    finite = bool(np.isfinite(s["served"]).all())
    per_layer_step = touched / max(1, n_routed_layers * decode_steps)
    share = n_held / cfg.n_routed_experts
    busy = (per_layer_step > 0.4 * n_held and rows_held > touched
            and 0.6 * share < rows_held / max(1, rows) < 1.6 * share)
    burst_holds = (s["inserted"] and s["idle_untouched"]
                   and s["burst_counters_hold"]
                   and prefill_margin <= TOLERANCE
                   and s["burst_margin"] <= TOLERANCE
                   and s["burst_rows_ratio"] <= BURST_TOLERANCE
                   and s["burst_tails_ratio"] <= BURST_TOLERANCE)
    return {
        "ratio": err, "ratio_at": positions[int(np.argmax(by_position))],
        "tolerance": TOLERANCE, "picks_margin": margin,
        "picks_margin_most": PICKS_MARGIN, "rows_ratio": rows_ratio,
        "rows_tolerance": ROWS_TOLERANCE,
        "rows_ratio_prefill": float(np.mean(prefill_rows)),
        "rows_ratio_steps": float(np.mean(stepped)),
        "rows_ratio_lanes": dict(zip(
            (str(n) for n, _ in s["lane_rows"]), lane_rows)),
        "prefill_margin": prefill_margin,
        "prefill_calls": s["prefill_calls"],
        "tails_ratio": tails_ratio, "tails_tolerance": TAILS_TOLERANCE,
        "tails_ratio_insert": float(np.mean(at_insert or [0.0])),
        "tails_ratio_steps": float(np.mean(after or [0.0])),
        "weights_err": weights_err, "weights_tolerance": WEIGHTS_TOLERANCE,
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
        "burst_tails_ratio": s["burst_tails_ratio"],
        "burst_tolerance": BURST_TOLERANCE,
        "burst_counters_hold": s["burst_counters_hold"],
        "inserted": s["inserted"], "idle_untouched": s["idle_untouched"],
        "served_s": s["served_s"], "reference_s": time.monotonic() - t1,
        # the process's peak so far: before the comparison, after its
        # served half, after the reference
        "memory_peak_bytes": s["memory_peak_bytes"] + [_memory_peak()],
        "ok": bool(finite and err <= TOLERANCE and margin <= PICKS_MARGIN
                   and rows_ratio <= ROWS_TOLERANCE
                   and tails_ratio <= TAILS_TOLERANCE
                   and weights_err <= WEIGHTS_TOLERANCE and s["counters_hold"]
                   and busy and burst_holds),
    }


# -- what a step must read and a prefill must compute -------------------------------

def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _kinds(cfg: dict) -> tuple:
    """``(convolution layers, attention layers)`` served."""
    kinds = served_layer_types(cfg)
    conv = sum(1 for k in kinds if k == CONV)
    return conv, len(kinds) - conv


def kv_bytes_per_position_and_layer(cfg: dict) -> int:
    """Keys and values of one position in ONE attention layer: two heads of
    64 fill a row of the cache, nothing is padding."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * BYTES


def tail_bytes(cfg: dict) -> int:
    """One lane's tail in one convolution layer."""
    return (cfg["conv_L_cache"] - 1) * cfg["hidden_size"] * BYTES


def conv_params(cfg: dict) -> int:
    """One convolution operator: W_in, the taps, W_out (16.78 M)."""
    d = cfg["hidden_size"]
    return 3 * d * d + cfg["conv_L_cache"] * d + d * d


def attention_params(cfg: dict) -> int:
    """One attention operator: W_q, W_k, W_v, W_o and the two norms."""
    d = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return 2 * d * q + 2 * d * kv + 2 * cfg["head_dim"]


def _fixed_params(cfg: dict) -> int:
    """Everything a step reads once outside the routed experts: every
    layer's operator and two norms, the dense layers' FFN, the expert
    layers' router and bias, the final norm and the head's slice (the tied
    embedding, read once as the head; the lookup is one row a lane)."""
    d = cfg["hidden_size"]
    conv, full = _kinds(cfg)
    dense = n_dense(cfg)
    routed = conv + full - dense
    router = d * cfg["num_experts_published"] + cfg["num_experts_published"]
    return (conv * conv_params(cfg) + full * attention_params(cfg)
            + (conv + full) * 2 * d + dense * 3 * d * cfg["intermediate_size"]
            + routed * router + d + d * cfg["vocab_size"])


def _steps(cfg: dict, counters: dict):
    """Decode steps the capture's counters cover, or None without them."""
    layer_steps = counters.get("moe_layer_steps", 0)
    conv, full = _kinds(cfg)
    routed = conv + full - n_dense(cfg)
    return layer_steps / routed if layer_steps > 0 and routed else None


def decode_step_bytes(cfg: dict, live_positions: float, counters: dict):
    """Bytes one decode step must move: everything outside the routed
    experts once; of each expert layer the HELD experts the step's live
    lanes picked (``moe_experts_touched / moe_layer_steps`` over the
    capture); each live lane's convolution tails read once and written
    once (``conv_tails_written`` over the steps); the live keys and values
    of the attention layers only. None where the program gave no such
    counters."""
    steps = _steps(cfg, counters)
    if steps is None:
        return None
    _conv, full = _kinds(cfg)
    touched = counters["moe_experts_touched"] / steps       # all layers
    tails = counters.get("conv_tails_written", 0) / steps * 2 * tail_bytes(cfg)
    return ((_fixed_params(cfg) + touched * expert_params(cfg)) * BYTES + tails
            + full * kv_bytes_per_position_and_layer(cfg) * live_positions)


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


def kv_step_bytes(cfg: dict, counters: dict):
    """``(bytes of a step that are the lanes' keys and values, bytes of the
    step)`` from the program's counters alone: the live rows
    (``kv_rows_live``: the lanes' lengths summed over the attention layers)
    over ``decode_step_bytes`` at those same live positions. None without
    the counters."""
    steps = _steps(cfg, counters)
    live = counters.get("kv_rows_live", 0)
    if steps is None or live <= 0:
        return None
    _conv, full = _kinds(cfg)
    mine = live / steps * kv_bytes_per_position_and_layer(cfg)
    return mine, decode_step_bytes(cfg, live / steps / full, counters)


def prefill_attention_flops(cfg: dict, padded_tokens: float,
                            sequences: float) -> float:
    """The attention layers' useful FLOPs over ``sequences`` prompts of
    ``padded_tokens`` positions in all: scores and values 64 wide a head,
    the causal half of the square, 3 layers."""
    if sequences <= 0:
        return 0.0
    t = padded_tokens / sequences
    per_pair = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]
    return per_pair * sequences * _kinds(cfg)[1] * t * t / 2.0


def prefill_flops(cfg: dict, padded_tokens: float, sequences: float,
                  counters: dict) -> float:
    """FLOPs of prefilling ``sequences`` prompts padded to ``padded_tokens``
    positions in all: per position a layer's operator (the convolution's
    three taps beside its projections); the dense FFN, or the router and
    the picks expected to land on a HELD expert (``num_experts_per_tok x
    num_experts / num_experts_published``: the router is near uniform under
    seeded weights); attention over half the square at the mean length
    (its least) in the attention layers; the head at each prompt's last
    position."""
    if sequences <= 0:
        return 0.0
    d = cfg["hidden_size"]
    conv, full = _kinds(cfg)
    dense = n_dense(cfg)
    picks = (cfg["num_experts_per_tok"] * cfg["num_experts"]
             / cfg["num_experts_published"])
    moe = d * cfg["num_experts_published"] + picks * expert_params(cfg)
    per_token = (conv * conv_params(cfg) + full * attention_params(cfg)
                 + dense * 3 * d * cfg["intermediate_size"]
                 + (conv + full - dense) * moe)
    head = 2.0 * d * cfg["vocab_size"] * sequences
    return (2.0 * per_token * padded_tokens
            + prefill_attention_flops(cfg, padded_tokens, sequences) + head)
