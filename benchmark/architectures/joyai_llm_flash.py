"""The joyai_llm_flash decoder (JoyAI-LLM-Flash): everything the benchmark
knows of it.

A configuration whose file says ``"architecture": "joyai_llm_flash"`` is
served, compared and costed by this module (``manifest.architecture``).
The parent process loads it too and never imports jax: jax and the program
are imported inside the functions that need them.

**The served family.** ``benchmark_joyai_llm_flash``: the program's own
``DecoderLM(block="joyai_llm_flash")``
(``seldon_core_tpu/models/joyai_llm_flash.py``) in every method but
``init_params``, which runs the program's own draw a layer at a time under
one compiled program a kind of layer and casts each leaf to the served
dtype inside it.

**The cut.** The configuration's file keeps every published width. Depth:
``served_layers`` names the published layers that are served; a layer is
dense where its published index is below ``first_k_dense_replace``.
Experts: ``n_routed_experts`` is what this chip HOLDS of each expert
layer, ``experts_held`` [first, end) which ones,
``n_routed_experts_published`` what the router ranges over. Vocabulary:
``vocab_size`` is the chip's slice; ids, logits and sampling are over it.

**The costs.** Operations and bytes from shapes, the benchmark's own copy.
What a decode step reads of the held experts is data-dependent, so it
comes from the program's counters, as the capture gives them; where they
are missing the bytes are ``None``, never a guess. A cached position is
counted at the 1,152 bytes a layer that hold something (the latent's 512
and the rotary key's 64 in bfloat16), not at the 1,280 the served row's
640 lanes occupy and the kernel copies: a roofline that counted the
padding would credit the kernel for moving zeros.
"""

from __future__ import annotations

# one copy of the margin among the architecture modules (numpy only, as
# this module jax-free at import)
from benchmark.architectures.afmoe import picks_margin
from benchmark.manifest import ManifestError

FAMILY = "benchmark_joyai_llm_flash"

# Agreement asked of the served path: three limits, any of which fails it
# (``compare_served`` says what each compares). Each lies between two
# readings on the chip (my chip runs, PR 42): the largest over 32 sound
# seeds in the cell's regime (64 lanes, 56 live, lengths 256-5888, 225
# positions; calls 1-5 and 7) and the least of the controls that must fail
# (two seeds each; ``weights_8bit`` is the nearest precision below the
# configuration's, and fails every limit).
#
# ``TOLERANCE``: max |served - reference| over the compared logits (every
# live lane at every decode step x the sliced vocabulary, and the two
# prefills' last positions) over the reference logits' standard deviation,
# with the reference routed as the served model routed (unrouted, a
# flipped pick is a whole expert's output: the afmoe module's finding).
# Sound 0.0871-0.1017 (the 1792-bucket prefill alone 0.054-0.077): twice
# the other families' 0.04. The error's deviation is 1.8% of a logit's
# where theirs is 0.8%: 12 layers where they have 6 and 8, routed experts
# weighted 2.5, scores of deviation 3; in float32 the same program agrees
# to 2e-4 (``tests/test_joyai_llm_flash.py``). 8-bit weights 1.04-1.15,
# the latent in 8 bits 0.327-0.357, ``routed_scaling_factor`` left at 1
# 0.69-0.75, the scale 1 / sqrt(128) 1.47, the latent cached before its
# norm 2.30-2.37, rotary on half-split pairs 2.92-3.34. So 0.2, not the
# other families' 0.1 (which 4 seeds of 32 would fail): 1.97 times the
# largest sound reading, 0.61 of the least control's.
#
# ``PICKS_MARGIN``: how far outside the reference router's own top 8 a
# served pick may lie, in the router's score (``sigmoid`` of the logit,
# plus the bias), as the afmoe module's: over every (position, expert
# layer) the largest of (best reference score among the experts the served
# model left out) - (worst among the 8 it picked), 0 where the picks are
# the reference's. A score that differs by bfloat16 rounding swaps two
# experts whose reference scores lie closer than that rounding (10% of the
# positions hold such a swap): that is allowed, and no other. Sound
# 0.0085-0.0133; the latent in 8 bits 0.0375-0.0423, the scale left at 1
# 0.095-0.101, 8-bit weights 0.169-0.212, the others 0.22-0.68. So 0.02:
# 1.5 times the largest sound reading, 0.53 of the least control's.
#
# ``ROWS_TOLERANCE``: the cache rows themselves, ``[N(c) | rope(k_r)]`` of
# every position the comparison's cache holds (the long prefill's 5888,
# and the rows the decode steps wrote) against the reference's own at that
# position: |served - reference|_F / |reference|_F over a layer's rows,
# the MEAN over the layers (the worst layer beside it: 0.0154-0.0160).
# The served rows are bfloat16 products of bfloat16 activations: a
# relative rounding of 2^-9 a value and what the layers before them left:
# sound 0.00901-0.00939, the steadiest of the three. A latent kept in 8
# bits (e4m3: 2^-4) 0.0392-0.0393, the scale left at 1 0.061, 8-bit
# weights 0.102, the others 0.14-0.72. So 0.018: 1.92 times the largest
# sound reading, 0.46 of the least control's.
#
# The batcher's burst against the program's own step fed the burst's
# tokens, both by ``TOLERANCE``: ``burst_margin`` 0.004-0.021 (where the
# burst's token is not the step's argmax, 1-3% of the 224: near ties),
# ``burst_rows_ratio`` 0.0 on 20 seeds of 32, 0.0003-0.0154 on 12 (a pick
# flipped between the two compilations puts a rounding into every row
# after it); a live lane the burst leaves out: 1.21-1.23, and the
# counters do not hold.
TOLERANCE = 0.2
PICKS_MARGIN = 0.02
ROWS_TOLERANCE = 0.018

BYTES = 2        # bfloat16 weights and cache rows
DECODE_STEPS = 4  # decode steps the comparison takes


# -- the served family ---------------------------------------------------------

def __getattr__(name: str):
    # built when the program asks for it by its dotted path: defining it
    # imports the program, and with it jax
    if name != "SeededJoyaiLLMFlashLM":
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    from seldon_core_tpu.models.joyai_llm_flash import JoyaiLLMFlashLM

    class SeededJoyaiLLMFlashLM(JoyaiLLMFlashLM):
        def init_params(self, seed: int = 0):
            """The program's own draw, layer by layer: one compiled
            program a kind of layer (the dense one, an expert layer) and
            one for the embedding and the head, each leaf cast to the
            served dtype inside it. All twelve layers under one ``jit``
            took a minute to compile; the float32 draw of a layer is 0.7
            GB and goes when its cast is done."""
            import jax
            import jax.numpy as jnp

            dt = jnp.dtype(self.cfg.dtype)

            def cast(tree):
                return jax.tree_util.tree_map(lambda a: a.astype(dt), tree)

            layer = jax.jit(lambda key, routed: cast(self.init_layer(key, routed)),
                            static_argnums=(1,))
            keys = jax.random.split(jax.random.PRNGKey(seed), self.cfg.n_layers + 1)
            return dict(
                jax.jit(lambda key: cast(self.init_top(key)))(keys[-1]),
                layers=[layer(keys[l], routed)
                        for l, routed in enumerate(self._routed)])

    globals()[name] = SeededJoyaiLLMFlashLM
    return SeededJoyaiLLMFlashLM


def register() -> None:
    from seldon_core_tpu import models
    # a program without the family fails here, at once and cleanly
    from seldon_core_tpu.models import joyai_llm_flash  # noqa: F401

    models.register(FAMILY, f"{__name__}.SeededJoyaiLLMFlashLM")


def n_dense(cfg: dict) -> int:
    """Served layers with a dense FFN: the published leading ones."""
    served = cfg["served_layers"]
    if len(served) != cfg["num_hidden_layers"]:
        raise ManifestError(
            f"{cfg['name']}: served_layers names {len(served)} layers, "
            f"num_hidden_layers says {cfg['num_hidden_layers']}")
    dense = [i < cfg["first_k_dense_replace"] for i in served]
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


def model_kwargs(cfg: dict, seed: int) -> dict:
    """The published config's keys as ``DecoderLM(block="joyai_llm_flash")``
    takes them."""
    if not (cfg["norm_topk_prob"] and cfg["scoring_func"] == "sigmoid"
            and cfg["topk_method"] == "noaux_tc"
            and cfg["n_group"] == cfg["topk_group"] == 1):
        raise ManifestError(
            f"{cfg['name']}: the router is sigmoid, noaux_tc, one group, "
            "normed top-k weights")
    if not cfg["rope_interleave"] or cfg["rope_scaling"] is not None:
        raise ManifestError(
            f"{cfg['name']}: rotary on interleaved pairs, unscaled")
    if cfg["moe_layer_freq"] != 1 or cfg["attention_bias"]:
        raise ManifestError(f"{cfg['name']}: every later layer routes; no bias")
    if cfg["qk_head_dim"] != cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]:
        raise ManifestError(f"{cfg['name']}: qk_head_dim is nope + rope")
    return {
        "block": "joyai_llm_flash",
        "vocab_size": cfg["vocab_size"],
        "d_model": cfg["hidden_size"],
        "n_layers": cfg["num_hidden_layers"],
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["qk_head_dim"],
        "d_ff": cfg["intermediate_size"],
        "max_seq": cfg["server"]["max_seq"],
        "rope_theta": float(cfg["rope_theta"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "dtype": cfg["torch_dtype"],
        "q_lora_rank": cfg["q_lora_rank"],
        "kv_lora_rank": cfg["kv_lora_rank"],
        "qk_nope_head_dim": cfg["qk_nope_head_dim"],
        "qk_rope_head_dim": cfg["qk_rope_head_dim"],
        "v_head_dim": cfg["v_head_dim"],
        "n_dense_layers": n_dense(cfg),
        "n_routed_experts": cfg["n_routed_experts_published"],
        "experts_held": list(held(cfg)),
        "experts_per_tok": cfg["num_experts_per_tok"],
        "expert_width": cfg["moe_intermediate_size"],
        "n_shared_experts": cfg["n_shared_experts"],
        "route_scale": float(cfg["routed_scaling_factor"]),
        "residual_scale": cfg["weights"]["residual_scale"],
        # PRNGKey takes 32 bits; the driver's seeds are larger
        "seed": seed % (2**31 - 1),
    }


def rehearsal(cfg: dict) -> dict:
    """The sizes ``--rehearse-cpu`` puts over the configuration's: the
    dense layer and two expert layers, 4 of 16 experts held, a cache of
    1024 positions (the comparison's one prefill of the whole cache's
    length is then a CPU's work; a mix whose contexts end past it is not
    rehearsed: the tests rehearse under a tiny one)."""
    return {
        "server": dict(cfg["server"], max_seq=1024),
        "hidden_size": 128, "num_attention_heads": 4, "num_key_value_heads": 4,
        "q_lora_rank": 64, "kv_lora_rank": 128, "qk_nope_head_dim": 32,
        "qk_rope_head_dim": 16, "qk_head_dim": 48, "v_head_dim": 32,
        "intermediate_size": 256, "moe_intermediate_size": 64,
        "num_hidden_layers": 3, "served_layers": [0, 1, 2],
        "n_routed_experts": 4, "experts_held": [0, 4],
        "n_routed_experts_published": 16, "num_experts_per_tok": 4,
        "vocab_size": 1024,
    }


# -- the served model against the plain reference ----------------------------------

IDLE_EVERY = 8      # lanes 5, 13, 21, ... idle among the live ones
INSERT_ROWS = 8     # rows of one ``insert_many``
BLOCK = 128         # what the comparison's lengths are rounded to
PROMPT_LEN = 5888   # where the cell's longest contexts end
SHORT_BUCKET = 1792  # the prefill bucket the traffic uses most


def read_block() -> int:
    """The positions the ragged read streams a lane's length in whole
    multiples of, ASKED of the program when the comparison runs (a later
    kernel may rightly stream fewer): ``ops.latent_attention.LATENT_BLOCK``
    (512). The lengths lie on both its sides, and ``mla_positions_read``
    must count whole ones."""
    from seldon_core_tpu.ops import latent_attention

    return int(latent_attention.LATENT_BLOCK)


def lane_lengths(lanes: int, prompt_len: int, decode_steps: int,
                 block: int) -> dict:
    """``{lane: tokens it holds before its first step}`` for the live
    lanes: spread evenly from ``prompt_len // 23`` (256 of 5888) to
    ``prompt_len`` (the lane that goes on where the whole prompt ended), no
    two lanes' steps at one position, and three of them moved to an edge
    of the read's ``block`` (``read_block()``): a multiple of it (the
    first, the middle one, the last), one under it and one over it, each
    the lane that lay nearest."""
    import numpy as np

    live = [j for j in range(lanes) if j % IDLE_EVERY != 5]
    lens = np.linspace(max(4, prompt_len // 23), prompt_len,
                       len(live)).round().astype(int)
    # the edges inside the prompt (one at its very end has no far side)
    edges = block * np.arange(1, (prompt_len - 1) // block + 1)
    if len(edges) and len(live) >= 6 \
            and np.diff(lens).min() >= 3 * decode_steps:
        for edge, off in zip(edges[[0, len(edges) // 2, -1]], (0, -1, 1)):
            at = int(np.abs(lens[1:-1] - edge).argmin()) + 1
            edge = int(edge) + off
            if lens[at - 1] + decode_steps <= edge <= lens[at + 1] - decode_steps:
                lens[at] = edge
    if len(live) > 1 and np.diff(lens).min() < decode_steps:
        raise ValueError(f"{len(live)} lanes of {decode_steps} steps do not "
                         f"fit apart in {prompt_len} positions")
    return dict(zip(live, lens.tolist()))


def _serving_batcher(params):
    """The ``ContinuousBatcher`` of this process that serves ``params``,
    or None: the comparison then runs on the cache and the executables
    that the measured window runs on (a second cache of 64 lanes x 6144
    positions would not fit beside the first)."""
    import gc

    import jax

    from seldon_core_tpu.serving.continuous import ContinuousBatcher

    mine = jax.tree_util.tree_leaves(params)
    for obj in gc.get_objects():
        if isinstance(obj, ContinuousBatcher) and getattr(
                obj, "_cache", None) is not None:
            theirs = jax.tree_util.tree_leaves(obj.params)
            # the same arrays, whatever dict holds them
            if len(theirs) == len(mine) and all(
                    a is b for a, b in zip(theirs, mine)):
                return obj
    return None


def _memory_peak() -> int:
    import jax

    return max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in jax.local_devices()), default=0)


def compare_served(model, params, seed: int, prompt_len: int = 0,
                   decode_steps: int = DECODE_STEPS, variant: str = "",
                   batcher=None) -> dict:
    """The served path in the regime the cell times, against ONE full
    causal forward of the reference over the same ``prompt_len +
    decode_steps`` tokens.

    ``batcher``: the ``ContinuousBatcher`` whose cache, lanes and
    executables are used: the one given, else the process's own that
    serves ``params`` (the engine's: idle while the parent asks for the
    comparison; ``borrowed`` says it was found). None is built here: a
    test or a control passes one of the size it wants. The cache is handed
    back with the comparison's rows in it, which a lane's next occupant
    overwrites before any read admits them, as every lane's last
    occupant's are. ``prompt_len``: ``PROMPT_LEN`` where the cache is long
    enough, else what fits.

    A latent cache is causal as a KV cache is: a lane of length L holds
    the first L rows of a longer prompt's. So ONE prefill of the whole
    prompt (padded to the cache's length, the bucket past the batcher's
    largest: its executable is the comparison's alone) gives the rows of
    every lane, and the BATCHER'S OWN compiled ``insert_many`` lays them
    into every live lane (every eighth idle), lane j registered at its own
    length L_j (``lane_lengths``: 256 to 5888, on both sides of a block's
    edge): lane j reads rows [0, L_j) of them. The steps below write at
    L_j, L_j + 1, ...: each run overwrites those rows before it reads
    them, so the three runs start from the same cache without a copy.

    (1) The batcher's compiled burst (``_burst_fn``, k = ``decode_steps``,
    the cache carried through its scan and donated): its tokens, its
    counters and the rows it wrote. (2) The program's own step
    (``model._step``: ``decode_step_cache``, which the burst's body calls,
    and the picks) one step at a time, fed the BURST'S tokens: the burst
    must have sampled each step's argmax, written the same rows in the
    same lanes, and counted the same. (3) That step fed the prompt's own
    next tokens, whose logits, picks and rows the reference's one forward
    can be compared with: the ragged latent kernel over the lanes'
    lengths, the touched-expert kernel over this chip's share. Also the
    prefill in the ``SHORT_BUCKET`` bucket, the one the traffic uses most,
    on its own last-token logits against a forward of the reference over
    that prefix under that program's routing.
    ``variant="burst_idles_a_lane"``, the burst's own control: its first
    live lane is left out of the burst's ``active`` (the reference is the
    sound one); any other ``variant`` is the reference's.

    Held: ``ratio`` <= ``TOLERANCE`` (logits of every live lane at every
    step of (3) and the two prefills' last, the reference ROUTED AS THE
    SERVED MODEL ROUTED every position); ``picks_margin`` <=
    ``PICKS_MARGIN``; ``rows_ratio`` <= ``ROWS_TOLERANCE`` (the cache's
    rows against the reference's own: the prefill's, and the steps');
    ``burst_margin`` and ``burst_rows_ratio`` <= ``TOLERANCE`` (the burst
    against the steps), an idle lane's row where a step would have written
    left as it was; the step's counters are the picks' and the lengths'
    own count and the burst's sum to the steps'; and the step was busy
    (several rows on a touched expert, about an eighth of the picks
    held)."""
    served = serve(model, params, seed, prompt_len, decode_steps,
                   variant == "burst_idles_a_lane", batcher)
    return judge(model, served, params,
                 "" if variant == "burst_idles_a_lane" else variant)


def serve(model, params, seed: int, prompt_len: int = 0,
          decode_steps: int = DECODE_STEPS, burst_idles_a_lane: bool = False,
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
    width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    borrowed = batcher is None
    if borrowed:
        batcher = _serving_batcher(params)
    if batcher is None:
        raise ValueError("no ContinuousBatcher of this process serves these "
                         "parameters, and none was given")
    lanes, cache_len = batcher.slots, batcher.max_seq
    prompt_len = prompt_len or min(
        PROMPT_LEN, (cache_len - decode_steps) // BLOCK * BLOCK)
    total = prompt_len + decode_steps
    if total > cache_len:
        raise ValueError(f"{total} positions in a cache of {cache_len}")
    rng = np.random.default_rng(seed % (2**63))
    tokens = rng.integers(0, cfg.vocab_size, size=total, dtype=np.int64)
    block = read_block()
    start = lane_lengths(lanes, prompt_len, decode_steps, block)
    live = np.array([j in start for j in range(lanes)])
    at = np.array([start.get(j, 0) for j in range(lanes)])
    n_layers = cfg.n_layers
    n_routed_layers = n_layers - cfg.n_dense_layers
    lo, n_held = cfg.experts_held or (0, cfg.n_routed_experts)

    # ONE prefill of the whole prompt, padded to the cache's length
    prefill = jax.jit(lambda p, t, last, to: model._prefill(p, t, to, last),
                      static_argnums=(3,))
    padded = np.zeros((1, cache_len), np.int64)
    padded[0, :prompt_len] = tokens[:prompt_len]
    logits, slab, routed = prefill(
        params, jnp.asarray(padded, jnp.int32),
        jnp.asarray([prompt_len - 1], jnp.int32), cache_len)
    served = [np.asarray(logits[0])]
    positions = [prompt_len - 1]
    picks = [np.concatenate([np.asarray(r[0, :prompt_len]),
                             np.zeros_like(r[0, :decode_steps])])
             for r in routed]
    slab_rows = [np.asarray(slab["latent"][l, 0, :prompt_len, :width],
                            np.float32) for l in range(n_layers)]
    del logits, routed

    # the rows into every live lane, by the batcher's own insert_many
    cache = batcher._cache
    batcher._cache = None       # donated below; handed back at the end
    cur_tok = jnp.zeros((lanes,), jnp.int32)
    lane_pos = jnp.zeros((lanes,), jnp.int32)
    keys = jnp.zeros((lanes, 2), jnp.uint32)
    counted = list(batcher._no_prefill_counts) * 2
    order = sorted(start)
    many = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (a.shape[0], INSERT_ROWS) + a.shape[2:]),
        slab)
    del slab
    try:
        for g in range(0, len(order), INSERT_ROWS):
            group = order[g:g + INSERT_ROWS]
            n = len(group)
            begin = np.array([start[j] for j in group])
            cache, cur_tok, lane_pos, keys, *_ = batcher._insert_many_fn(
                cache, many if n == INSERT_ROWS else jax.tree_util.tree_map(
                    lambda a: a[:, :n], many),
                jnp.asarray(group, jnp.int32),
                jnp.asarray(tokens[begin], jnp.int32),
                jnp.asarray(begin, jnp.int32), jnp.zeros((n, 2), jnp.uint32),
                cur_tok, lane_pos, keys, *counted)
        del many
        inserted = bool(
            np.array_equal(np.asarray(lane_pos), at) and np.array_equal(
                np.asarray(cur_tok)[live], tokens[at[live]]))
        idle_lane = int(np.flatnonzero(~live)[0]) if (~live).any() else None

        new_at = at[live, None] + np.arange(decode_steps)[None]   # [live, steps]
        gather = jax.jit(lambda cache, j, p, idle: (
            [a[j[:, None], p][..., :width] for a in cache["latent"]],
            [a[idle, 0] for a in cache["latent"]]))

        def rows_written(cache):
            """The rows ``decode_steps`` steps leave at a live lane's new
            positions [live, steps, L, width], and an idle lane's row 0
            (no step writes there where the lane reads nothing)."""
            rows, idle = gather(
                cache, jnp.asarray(np.flatnonzero(live), jnp.int32),
                jnp.asarray(new_at, jnp.int32), jnp.int32(idle_lane or 0))
            return (np.stack([np.asarray(r, np.float32) for r in rows], axis=2),
                    np.stack([np.asarray(r) for r in idle]))

        _none, idle_before = rows_written(cache)

        # (1) the batcher's burst
        active = live.copy()
        if burst_idles_a_lane:
            active[np.flatnonzero(live)[0]] = False
        toks, _cur, _pos, cache, _k, burst_counts = batcher._burst_fn(
            params, cache, cur_tok, lane_pos, jnp.asarray(active),
            jnp.zeros((lanes,), jnp.float32), keys, decode_steps,
            None if batcher._ragged_read else cache_len)
        toks = np.asarray(toks)                   # [steps + 1, lanes]
        burst_counts = np.asarray(burst_counts)
        burst_rows, idle_after = rows_written(cache)
        idle_untouched = idle_lane is None or bool(
            np.array_equal(idle_before, idle_after) or not batcher._ragged_read)

        step = jax.jit(model._step, donate_argnums=(1,))

        def steps(cache, feed):
            """``decode_steps`` steps over all lanes, step i fed ``feed(i)``
            [lanes]: each step's logits, picks and counters, and the cache."""
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
        outs, cache = steps(cache, lambda i: toks[i])
        step_rows, _idle = rows_written(cache)
        burst_margin, agree = 0.0, []
        for i, (out, _c, _r) in enumerate(outs):
            mine = out[active]
            theirs = mine[np.arange(len(mine)), toks[i + 1][active]]
            agree.append(mine.argmax(-1) == toks[i + 1][active])
            burst_margin = max(burst_margin, float(
                (mine.max(-1) - theirs).max() / mine.std()))
        burst_rows_ratio = max(
            float(np.linalg.norm(burst_rows[n] - step_rows[n])
                  / np.linalg.norm(step_rows[n]))
            for n in range(len(step_rows)))
        summed = np.sum([c for _o, c, _r in outs], axis=0)
        # what live lanes and positions there are sums exactly; a pick that
        # a rounding flips between the two programs moves the counts that
        # follow the picks
        exact = [1, 2, 4, 5, 6]
        burst_counters_hold = bool(
            np.array_equal(burst_counts[exact], summed[exact])
            and np.all(np.abs(burst_counts - summed) <= 0.02 * summed))
        del burst_rows

        # (3) the step, fed the prompt's own tokens: what the reference follows
        outs, cache = steps(cache, lambda i: tokens[at + i])
        step_rows, _idle = rows_written(cache)
    finally:
        batcher._cache = cache      # handed back, the comparison's rows in it
    del cache
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
        n_read = int((-(-(lens_live + i + 1) // block) * block).sum())
        counters_hold &= counts.tolist() == [
            distinct, pairs, n_routed_layers, landed, n_read * n_layers,
            int((lens_live + i + 1).sum()) * n_layers,
            int(live.sum()) * n_layers]
        touched, rows, rows_held = (touched + distinct, rows + pairs,
                                    rows_held + landed)

    # the prefill in the bucket the traffic uses most
    short = min(SHORT_BUCKET, prompt_len // BLOCK * BLOCK)
    short_logits = short_picks = None
    if short >= BLOCK:
        logits, _slab, routed = prefill(
            params, jnp.asarray(tokens[None, :short], jnp.int32),
            jnp.asarray([short - 1], jnp.int32), short)
        short_logits = np.asarray(logits[0])
        short_picks = [np.asarray(r[0]) for r in routed]
        del logits, _slab, routed
    return dict(
        tokens=tokens, positions=positions, served=np.stack(served),
        picks=picks, slab_rows=slab_rows, step_rows=step_rows, new_at=new_at,
        short=short, short_logits=short_logits, short_picks=short_picks,
        prompt_len=prompt_len, lanes=lanes, cache_len=cache_len,
        read_block=block, lanes_live=int(live.sum()), borrowed=borrowed,
        touched=touched, rows=rows, rows_held=rows_held,
        decode_steps=decode_steps,
        counters_hold=bool(counters_hold), agree=float(np.mean(agree)),
        burst_margin=burst_margin, burst_rows_ratio=burst_rows_ratio,
        burst_counters_hold=burst_counters_hold, inserted=inserted,
        idle_untouched=bool(idle_untouched), served_s=time.monotonic() - t0,
        memory_peak_bytes=[peak_before, _memory_peak()])


def judge(model, served: dict, params, variant: str = "") -> dict:
    """The reference's half: ONE causal forward of the plain reference
    (``variant``: a wrong one) over the tokens ``serve`` served, routed as
    the served model routed, and the limits."""
    import time

    import numpy as np

    from benchmark.reference import joyai_llm_flash as reference

    t1 = time.monotonic()
    cfg = model.cfg
    s = served
    tokens, positions, picks = s["tokens"], s["positions"], s["picks"]
    prompt_len, short = s["prompt_len"], s["short"]
    short_logits, short_picks = s["short_logits"], s["short_picks"]
    slab_rows, step_rows, new_at = s["slab_rows"], s["step_rows"], s["new_at"]
    served = s["served"]
    n_layers = cfg.n_layers
    n_routed_layers = n_layers - cfg.n_dense_layers
    _lo, n_held = cfg.experts_held or (0, cfg.n_routed_experts)
    touched, rows, rows_held = s["touched"], s["rows"], s["rows_held"]
    decode_steps = s["decode_steps"]
    ref, ref_picks, ref_scores, ref_rows = reference.forward(
        params, cfg, tokens, positions, variant, route_as=picks)
    scale = float(ref.std())
    by_position = (np.max(np.abs(served - ref), axis=-1) / scale).tolist()
    short_ratio = 0.0
    if short_logits is not None:
        short_ref = reference.forward(
            params, cfg, tokens[:short], [short - 1], variant,
            route_as=short_picks)[0][0]
        short_ratio = float(np.max(np.abs(short_logits - short_ref)) / scale)
    err = max(max(by_position), short_ratio)
    margin = max([picks_margin(mine, theirs)
                  for mine, theirs in zip(picks, ref_scores)] or [0.0])
    same = [np.all(np.sort(mine, -1) == np.sort(theirs, -1), -1)
            for mine, theirs in zip(picks, ref_picks)]

    def relative(a, b):
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    prefill_rows = [relative(slab_rows[l], ref_rows[l][:prompt_len])
                    for l in range(n_layers)]
    stepped = [relative(step_rows[:, :, l], ref_rows[l][new_at])
               for l in range(n_layers)]
    rows_ratio = max(float(np.mean(prefill_rows)), float(np.mean(stepped)))
    finite = bool(np.isfinite(served).all())
    per_layer_step = touched / max(1, n_routed_layers * decode_steps)
    share = n_held / cfg.n_routed_experts
    busy = (per_layer_step > 0.4 * n_held and rows_held > touched
            and 0.6 * share < rows_held / max(1, rows) < 1.6 * share)
    burst_holds = (s["inserted"] and s["idle_untouched"]
                   and s["burst_counters_hold"]
                   and s["burst_margin"] <= TOLERANCE
                   and s["burst_rows_ratio"] <= TOLERANCE)
    return {
        "ratio": err, "ratio_at": positions[int(np.argmax(by_position))],
        "ratio_short_prefill": short_ratio,
        "tolerance": TOLERANCE, "picks_margin": margin,
        "picks_margin_most": PICKS_MARGIN, "rows_ratio": rows_ratio,
        "rows_tolerance": ROWS_TOLERANCE,
        "rows_ratio_prefill": float(np.mean(prefill_rows)),
        "rows_ratio_steps": float(np.mean(stepped)),
        "rows_ratio_worst_layer": max(prefill_rows + stepped),
        "picks_agree": float(np.mean(same)) if same else 1.0,
        "logit_std": scale, "positions": len(positions),
        "prompt_len": prompt_len, "lanes_live": s["lanes_live"],
        "lanes": s["lanes"], "cache_len": s["cache_len"],
        "read_block": s["read_block"], "borrowed": s["borrowed"],
        "experts_touched_a_layer_step": per_layer_step,
        "rows_per_touched_expert": rows_held / max(1, touched),
        "held_rows_share": rows_held / max(1, rows),
        "counters_are_the_picks": s["counters_hold"], "finite": finite,
        "burst_tokens_agree": s["agree"],
        "burst_margin": s["burst_margin"],
        "burst_rows_ratio": s["burst_rows_ratio"],
        "burst_counters_hold": s["burst_counters_hold"],
        "inserted": s["inserted"], "idle_untouched": s["idle_untouched"],
        "served_s": s["served_s"], "reference_s": time.monotonic() - t1,
        # the process's peak so far: before the comparison, after its
        # served half, after the reference
        "memory_peak_bytes": s["memory_peak_bytes"] + [_memory_peak()],
        "ok": bool(finite and err <= TOLERANCE and margin <= PICKS_MARGIN
                   and rows_ratio <= ROWS_TOLERANCE and s["counters_hold"] and busy
                   and burst_holds),
    }


# -- what a step must read and a prefill must compute -------------------------------

def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def latent_bytes_per_position(cfg: dict) -> int:
    """One position of ONE layer's cache: the latent and the rotary key."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * BYTES


def mla_params(cfg: dict) -> int:
    """One layer's attention: W_qa, its norm, W_qb, W_kva, its norm, W_UK
    and W_UV, W_o (26.35 M at the published widths)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return (d * rq + rq + rq * h * cfg["qk_head_dim"]
            + d * (r + cfg["qk_rope_head_dim"]) + r
            + r * h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * d)


def _fixed_params(cfg: dict) -> int:
    """Everything a step reads once outside the routed experts: every
    layer's attention and two norms, the dense layers' FFN, the expert
    layers' router, bias and shared expert, the final norm and the sliced
    head (not the embedding table: one row a lane)."""
    d = cfg["hidden_size"]
    layers, dense = cfg["num_hidden_layers"], n_dense(cfg)
    moe = (d * cfg["n_routed_experts_published"]
           + cfg["n_routed_experts_published"]
           + cfg["n_shared_experts"] * expert_params(cfg))
    return (layers * (mla_params(cfg) + 2 * d)
            + dense * 3 * d * cfg["intermediate_size"]
            + (layers - dense) * moe + d + d * cfg["vocab_size"])


def decode_step_bytes(cfg: dict, live_positions: float, counters: dict):
    """Bytes one decode step must move: everything outside the routed
    experts once; of each expert layer the HELD experts the step's live
    lanes picked (``moe_experts_touched / moe_layer_steps`` over the
    capture); the live positions' latent rows in every layer, at the bytes
    that hold something. None where the program gave no such counters."""
    layer_steps = counters.get("moe_layer_steps", 0)
    if layer_steps <= 0:
        return None
    steps = layer_steps / (cfg["num_hidden_layers"] - n_dense(cfg))
    touched = counters["moe_experts_touched"] / steps       # all layers
    return ((_fixed_params(cfg) + touched * expert_params(cfg)) * BYTES
            + cfg["num_hidden_layers"] * latent_bytes_per_position(cfg)
            * live_positions)


def mla_step_bytes(cfg: dict, counters: dict):
    """``(bytes of a step that latent attention moves, bytes of the
    step)`` from the program's counters alone: the lanes' live rows
    (``mla_positions_live``: summed over the layers) and the attention's
    weights, over ``decode_step_bytes`` at those same live positions. None
    without the counters."""
    layer_steps = counters.get("moe_layer_steps", 0)
    live = counters.get("mla_positions_live", 0)
    if layer_steps <= 0 or live <= 0:
        return None
    layers = cfg["num_hidden_layers"]
    steps = layer_steps / (layers - n_dense(cfg))
    mine = (live / steps * latent_bytes_per_position(cfg)
            + layers * mla_params(cfg) * BYTES)
    return mine, decode_step_bytes(cfg, live / steps / layers, counters)


def prefill_attention_flops(cfg: dict, padded_tokens: float,
                            sequences: float) -> float:
    """The expanded attention's useful FLOPs over ``sequences`` prompts of
    ``padded_tokens`` positions in all: scores 192 wide and values 128
    wide a head, the causal half of the square, every layer."""
    if sequences <= 0:
        return 0.0
    t = padded_tokens / sequences
    per_pair = 2.0 * cfg["num_attention_heads"] * (
        cfg["qk_head_dim"] + cfg["v_head_dim"])
    return per_pair * sequences * cfg["num_hidden_layers"] * t * t / 2.0


def prefill_flops(cfg: dict, padded_tokens: float, sequences: float,
                  counters: dict) -> float:
    """FLOPs of prefilling ``sequences`` prompts padded to ``padded_tokens``
    positions in all: per position a layer's attention projections with the
    expansion of its keys and values; the dense FFN, or the router, the
    shared expert and the picks expected to land on a held expert
    (``num_experts_per_tok x n_routed_experts / n_routed_experts_published``:
    the router is near uniform under seeded weights); attention over half
    the square at the mean length (its least); the head at each prompt's
    last position."""
    if sequences <= 0:
        return 0.0
    d = cfg["hidden_size"]
    layers, dense = cfg["num_hidden_layers"], n_dense(cfg)
    picks = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
             / cfg["n_routed_experts_published"])
    moe = (d * cfg["n_routed_experts_published"]
           + (cfg["n_shared_experts"] + picks) * expert_params(cfg))
    per_token = (layers * mla_params(cfg)
                 + dense * 3 * d * cfg["intermediate_size"]
                 + (layers - dense) * moe)
    head = 2.0 * d * cfg["vocab_size"] * sequences
    return (2.0 * per_token * padded_tokens
            + prefill_attention_flops(cfg, padded_tokens, sequences) + head)
