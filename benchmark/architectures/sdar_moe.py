"""The sdar_moe decoder (JetLM SDAR, the expert models): everything the
benchmark knows of it.

A configuration whose file says ``"architecture": "sdar_moe"`` is served,
compared and costed by this module (``manifest.architecture``). The parent
process loads it too and never imports jax: jax and the program are
imported inside the functions that need them.

**The served family.** ``benchmark_sdar_moe``: the program's own
``DecoderLM(block="sdar_moe")`` (``seldon_core_tpu/models/sdar_moe.py``) in
every method but ``init_params``, which runs the program's own draw under
one ``jit`` and casts each leaf to the served dtype inside it (a float32
expert stack of one layer is 2.4 GB and the chip holds six in bfloat16).

**Generation.** The configuration's ``generation`` block is the family's
own fields (``block_length``, ``denoising_steps``, ``remasking``,
``confidence_threshold``, ``mask_token_id``): a decode step is a pass over
a block of 4 positions a lane, and ``decode_step_bytes`` counts ONE pass.

**The costs.** Operations and bytes from shapes, the benchmark's own copy.
What a pass reads of the experts is data-dependent, so it comes from the
program's counters, as the capture gives them; where they are missing the
bytes are ``None``, never a guess.
"""

from __future__ import annotations

# one copy of the margin and of the scan for the process's batcher among the
# architecture modules (numpy and gc only)
from benchmark.architectures.afmoe import picks_margin
from benchmark.architectures.joyai_llm_flash import _serving_batcher
from benchmark.manifest import ManifestError

FAMILY = "benchmark_sdar_moe"

# Agreement asked of the served path: five limits, any of which fails it
# (``compare_served`` says what each compares). Readings on the chip at the
# timed sizes (32 lanes, 28 live, 8 passes: 224 block forwards, 71 commits)
# over 41 seeds, and the controls on one to three (my chip runs, PR 48,
# calls 3, 4, 6 and 7; PERF.md section 6 has them all). They are the
# configuration's ``residual_scale`` 1.0's: a branch four times the other
# expert files' carries four times their rounding (at 0.25 the same
# comparison read ``ratio`` 0.040-0.043 and 8-bit weights 0.49: call 2).
#
# ``TOLERANCE``: max |served - reference| over the compared logits (every
# live lane at every pass of the burst, all four positions of its block x
# the vocabulary) over the reference logits' standard deviation, with the
# reference routed as the served model routed (unrouted, a flipped pick is
# a whole expert's output: the afmoe module's finding). Under one routing
# the two differ by bfloat16 rounding: sound 0.146-0.176. 8-bit weights
# 1.32-1.35, the block mask made causal 5.10-6.23, blocks counted from the
# prompt's end 5.08-5.10, the commit left out 4.92-4.98, no q_norm / k_norm
# 4.28-4.30, rope_theta 1e4 5.49-6.16. So 0.4: 2.3 times the largest sound
# reading, 0.30 of the least control's.
#
# ``PICKS_MARGIN``: how far outside the reference router's own top 8 a
# served pick may lie, in the router's softmax probability over all 128 in
# units of the uniform probability 1 / 128 (as the qwen3_next module's):
# over every (position, layer), prefill and passes alike, the largest of
# (best reference probability among the experts the served model left out)
# - (worst among the 8 it picked). A probability that differs by bfloat16
# rounding swaps two experts whose reference probabilities lie closer than
# that rounding (11% of the pairs hold such a swap): that is allowed, and no
# other. Sound 0.27-0.52; 8-bit weights 3.87-4.65, the other controls
# 12.3-38.9. So 1.5: 2.9 times the largest sound reading, 0.39 of the least
# control's.
#
# ``ROWS_TOLERANCE``: the keys and values THE TIMED BURST left in the cache
# (the batcher's ``_block_burst_fn``, not a second compilation of its
# pass), against the reference's: a lane's committed blocks (every position
# the burst's commits left, all layers, K and V) and a sample of its
# prompt's rows, |served - reference|_F / |reference|_F, the worst lane.
# bfloat16 rows of float32 sums: sound 0.0130-0.0139 (committed) and
# 0.0101-0.0105 (the prompt's). 8-bit weights in the reference 0.124-0.130 /
# 0.087-0.089, in the burst alone 0.133-0.137 (the prompt's rows, another
# executable's, as sound); a commit left out keeps the rows a denoising
# pass wrote, which saw ``[MASK]`` embeddings: 1.12-1.13 (and the prompt's
# rows as sound: the control is the commit's alone). So 0.04: 2.9 times the
# largest sound reading, 0.32 of the least control's.
#
# ``BURST_TOLERANCE`` and ``BURST_ROWS_TOLERANCE``: a burst hands out no
# logits, so the logits held to ``TOLERANCE`` are those of the program's
# own pass fed the burst's states (two compilations of one pass: the scan's
# body and the pass alone), and these two tie the burst to that pass: where
# the burst filled a position in with a token that is not the pass's own
# argmax there, how far under its maximum the pass's logits put it, in
# their deviations; and the rows the two left in the cache, relative. Sound:
# 0.0 and 0.0 on every one of the 41 seeds (266 positions filled in, no
# lane-pass filled otherwise, bit-equal rows). The planted fault
# ``burst_weights_8bit`` (the burst alone on matrices rounded to 8 bits,
# the pass and the reference sound: ``ratio`` stays 0.149-0.167) over three
# seeds: 0.61-0.89 with 87-95 of 224 lane-passes filled otherwise, and rows
# 0.133-0.138. So 0.1, a sixth of the fault's least and over the
# 0.003-0.028 that two compilations read in the other families, and 0.02
# for the rows, a seventh of the fault's and four times what one last bit
# of every element would read (0.004-0.005). A live lane the burst leaves
# out (``burst_idles_a_lane``) fails by its own check.
TOLERANCE = 0.4
PICKS_MARGIN = 1.5
ROWS_TOLERANCE = 0.04
BURST_TOLERANCE = 0.1
BURST_ROWS_TOLERANCE = 0.02

BYTES = 2  # bfloat16 weights and cache


# -- the served family ---------------------------------------------------------

def __getattr__(name: str):
    # built when the program asks for it by its dotted path: defining it
    # imports the program, and with it jax
    if name != "SeededSdarMoeLM":
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    from seldon_core_tpu.models.sdar_moe import SdarMoeLM

    class SeededSdarMoeLM(SdarMoeLM):
        def init_params(self, seed: int = 0):
            import jax
            import jax.numpy as jnp

            dt = jnp.dtype(self.cfg.dtype)
            draw = super().init_params

            def served(s):
                return jax.tree_util.tree_map(lambda a: a.astype(dt), draw(s))

            return jax.jit(served)(jnp.uint32(seed))

    globals()[name] = SeededSdarMoeLM
    return SeededSdarMoeLM


def register() -> None:
    from seldon_core_tpu import models
    # a program without the family fails here, at once and cleanly
    from seldon_core_tpu.models import sdar_moe  # noqa: F401

    models.register(FAMILY, f"{__name__}.SeededSdarMoeLM")


def model_kwargs(cfg: dict, seed: int) -> dict:
    """The published config's keys as ``DecoderLM(block="sdar_moe")`` takes
    them, and the configuration's ``generation`` block."""
    if not cfg["norm_topk_prob"] or cfg["decoder_sparse_step"] != 1 \
            or cfg["mlp_only_layers"]:
        raise ManifestError(
            f"{cfg['name']}: every layer an expert layer, picks renormalised")
    if cfg["attention_bias"] or cfg["use_sliding_window"] \
            or cfg["tie_word_embeddings"] or cfg["rope_scaling"]:
        raise ManifestError(
            f"{cfg['name']}: no bias, window, tied head or rope scaling is served")
    if len(cfg["served_layers"]) != cfg["num_hidden_layers"]:
        raise ManifestError(f"{cfg['name']}: served_layers and "
                            "num_hidden_layers disagree")
    gen = cfg["generation"]
    return {
        "block": "sdar_moe",
        "vocab_size": cfg["vocab_size"],
        "d_model": cfg["hidden_size"],
        "n_layers": cfg["num_hidden_layers"],
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "d_ff": cfg["intermediate_size"],
        "max_seq": cfg["server"]["max_seq"],
        "rope_theta": float(cfg["rope_theta"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "dtype": cfg["torch_dtype"],
        "n_routed_experts": cfg["num_experts"],
        "experts_per_tok": cfg["num_experts_per_tok"],
        "expert_width": cfg["moe_intermediate_size"],
        "residual_scale": cfg["weights"]["residual_scale"],
        "block_length": gen["block_length"],
        "denoising_steps": gen["denoising_steps"],
        "remasking": gen["remasking"],
        "confidence_threshold": gen["confidence_threshold"],
        "mask_token_id": gen["mask_token_id"],
        # PRNGKey takes 32 bits; the driver's seeds are larger
        "seed": seed % (2**31 - 1),
    }


def rehearsal(cfg: dict) -> dict:
    """The sizes ``--rehearse-cpu`` puts over the configuration's: two
    layers, eight experts, a vocabulary that still holds the mask's id's
    stand-in, a cache the tiny mix fits."""
    return {
        "hidden_size": 256, "num_attention_heads": 4, "num_key_value_heads": 1,
        "head_dim": 128, "moe_intermediate_size": 128,
        "num_hidden_layers": 2, "served_layers": [0, 1], "num_experts": 8,
        "num_experts_per_tok": 2, "vocab_size": 1024,
        "generation": dict(cfg["generation"], mask_token_id=1023),
    }


# -- the served model against the plain reference ----------------------------------

IDLE_EVERY = 8      # lanes 5, 13, 21, 29 idle among the live ones
SAMPLE_HEAD, SAMPLE_TAIL = 8, 24    # prompt rows compared, first and last


def lane_lengths(lanes: int, cache_len: int, block: int, room: int) -> dict:
    """``{lane: prompt tokens it holds}`` for the live lanes (every eighth
    idle), each a length of its own: all four remainders mod ``block`` on
    both sides of the kernel's block edge at 128 (126 and 127: the first
    block ends at the edge and the next lies past it; 128-131), around a
    later edge (1,021-1,027), shorter than a block and than two, the cell's
    four prompt lengths, the rest spread to what leaves ``room`` positions
    for the burst's blocks."""
    import numpy as np

    live = [j for j in range(lanes) if j % IDLE_EVERY != 5]
    top = cache_len - room
    want = [126, 127, 128, 129, 130, 131, 1, 2, 3, 5, 6, 1021, 1023, 1024,
            1026, 250, 701, 1283, 1900, top]
    want = [n for n in want if 1 <= n <= top]
    lens = list(dict.fromkeys(want))[:len(live)]
    spread = np.linspace(140, top - 40, max(0, len(live) - len(lens)) + 2)
    for i, n in enumerate(spread[1:-1].round().astype(int).tolist()):
        if len(lens) < len(live):
            lens.append(n // block * block + i % block)
    return dict(zip(live, lens))


def _relative(a, b) -> float:
    import numpy as np

    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


BURST_FAULTS = ("burst_idles_a_lane", "burst_weights_8bit")


def compare_served(model, params, seed: int, variant: str = "",
                   batcher=None, top: int = 3300) -> dict:
    """The served path in the regime the cell times, against the plain
    reference computed in blocks. ``batcher``: the ``ContinuousBatcher``
    whose cache, lanes and executables are used: the one given, else the
    process's own that serves ``params``. None is built here. ``variant``:
    one of ``reference.VARIANTS``, a wrong reference, or of
    ``BURST_FAULTS``, a wrong burst (the controls)."""
    fault = variant if variant in BURST_FAULTS else ""
    served = serve(model, params, seed, batcher, fault, top)
    return judge(model, served, params, "" if fault else variant)


def serve(model, params, seed: int, batcher=None, burst_fault: str = "",
          top: int = 3300) -> dict:
    """The served half of ``compare_served``: everything the program
    computed, as numpy, for ``judge`` to hold against a reference (one
    serving, several references: the controls).

    Every lane holds a prefix of ONE token sequence, each at a length of its
    own (``lane_lengths``). The mask is causal over blocks, so the rows of a
    shorter prompt's whole blocks are the first rows of a longer one's: ONE
    prefill of the longest prompt (``model._prefill``: logits, rows and
    picks of one program, as the afmoe module found it must be) is laid
    into every live lane by the batcher's compiled insert, each lane
    starting at its own ``_lane_start``, its first block set by the
    batcher's ``_block_admit``. The rows past a lane's start are then a
    longer prompt's: nobody's, as after any prefill in a bucket.

    (1) the batcher's compiled burst (``_block_burst_fn``, its ``_k``
    passes, every live lane in a phase of its own), the executable the cell
    times: each pass's states and what it committed, the counters, and the
    rows it left in the cache, which are the rows the reference is compared
    with; (2) the lanes laid in anew, the program's own pass
    (``model._pass`` then ``model.block_unmask``), one pass at a time, FED
    THE BURST'S STATES: a burst hands out no logits, so the logits and picks
    the reference is compared with are this pass's, and the burst must have
    filled in what this pass would have and left the same rows.

    ``burst_fault``, a wrong burst that must fail: ``burst_idles_a_lane``
    leaves a live lane out of the burst; ``burst_weights_8bit`` runs the
    burst, and nothing else, on weights rounded to 8-bit floats (every
    matrix but the expert stacks: the chip does not hold a second copy of
    those beside the first)."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    t0 = time.monotonic()
    cfg = model.cfg
    W, L = cfg.block_length, cfg.n_layers
    if batcher is None:
        batcher = _serving_batcher(params)
    if batcher is None:
        raise ValueError("no ContinuousBatcher of this process serves these "
                         "parameters, and none was given")
    lanes, cache_len, k = batcher.slots, batcher.max_seq, batcher._k
    room = W * ((k + 1) // 2 + 1)
    start = lane_lengths(lanes, min(cache_len, top), W, room)
    longest = max(start.values())
    rng = np.random.default_rng(seed % (2**63))
    tokens = rng.integers(0, cfg.vocab_size, size=longest, dtype=np.int64)
    live = np.array([j in start for j in range(lanes)])
    at = np.array([start.get(j, 0) for j in range(lanes)])
    base0 = at - at % W

    bucket = batcher._bucket(longest)
    prompt = np.zeros((1, bucket), np.int32)
    prompt[0, :longest] = tokens
    _first, slab, routed, _counts = jax.jit(
        lambda p, t: model._prefill(p, t, bucket, jnp.asarray([longest - 1])))(
            params, jnp.asarray(prompt))
    prefill_picks = [np.asarray(r[0, :longest]) for r in routed]
    del routed

    class _Req:     # what ``_block_admit`` reads of a request
        eos_id = None
        max_new_tokens = 4 * room

        def __init__(self, n):
            self.tokens = tokens[:n].tolist()

    def fill():
        """Every live lane from the one slab, through the batcher's compiled
        insert and its own first-block registers."""
        counted = list(batcher._no_prefill_counts)
        nothing = [jnp.zeros_like(c) for c in counted]
        for j, n in start.items():
            (batcher._cache, batcher._cur_tok, batcher._pos, batcher._keys,
             *counted) = batcher._insert_fn(
                batcher._cache, slab, j, jnp.int32(0),
                batcher._lane_start(n), jax.random.PRNGKey(0),
                batcher._cur_tok, batcher._pos, batcher._keys,
                *counted, *nothing)
        batcher._block_regs = batcher._fresh_block_regs()
        batcher._block_admit(list(start), [_Req(n) for n in start.values()])

    def committed(cache, ends):
        """``{lane: float32 [2 (K, V), L, KV, n, Dh]}``: the rows from the
        lane's first block to ``ends[lane]``."""
        out = {}
        for j in start:
            lo, hi = int(base0[j]), int(ends[j])
            out[j] = np.stack([
                np.stack([np.asarray(cache[kind][l][j, :, lo:hi], np.float32)
                          for l in range(L)]) for kind in ("k", "v")])
        return out

    def prompt_rows(cache):
        """A sample of the prompt's rows as the lanes hold them: the longest
        lane's first and last, ``(positions, [2, L, KV, n, Dh])``."""
        j = max(start, key=start.get)
        n = int(base0[j])
        at_ = sorted(set(range(min(SAMPLE_HEAD, n)))
                     | set(range(max(0, n - SAMPLE_TAIL), n)))
        ix = jnp.asarray(at_, jnp.int32)
        return at_, np.stack([
            np.stack([np.asarray(cache[kind][l][j][:, ix], np.float32)
                      for l in range(L)]) for kind in ("k", "v")])

    active = live.copy()
    burst_params = params
    if burst_fault == "burst_idles_a_lane":
        active[np.flatnonzero(live)[0]] = False
    elif burst_fault == "burst_weights_8bit":
        burst_params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
            if a.ndim == 2 else a, params)
    elif burst_fault:
        raise ValueError(f"no burst fault {burst_fault!r}")
    temps = jnp.zeros((lanes,), jnp.float32)
    bound = None if batcher._ragged_read else cache_len

    # (1) the batcher's burst
    fill()
    (toks, emitted, bits, batcher._block_regs, batcher._pos, batcher._cache,
     batcher._keys, burst_counts) = batcher._block_burst_fn(
        burst_params, batcher._cache, batcher._block_regs, batcher._pos,
        jnp.asarray(active), temps, batcher._keys, k, bound, False)
    del burst_params
    toks, emitted, bits = (np.asarray(a) for a in (toks, emitted, bits))
    burst_counts = np.asarray(burst_counts)
    # a commit's row comes turned by the first block's skip, its tokens for
    # the client first: back to the block as the pass found it
    back = (np.arange(W) - (W - emitted)[:, :, None]) % W
    toks = np.take_along_axis(toks, back, axis=-1)
    masked = ((bits[:, :, None] >> np.arange(W)) & 1).astype(bool)  # [k, S, W]
    commits = (emitted > 0) & active[None, :]
    ends = base0 + W * commits.sum(axis=0)
    burst_rows = committed(batcher._cache, ends)
    at_rows, prefix_rows = prompt_rows(batcher._cache)
    pos_after = np.asarray(batcher._pos)
    registers_hold = bool(np.array_equal(pos_after[active], ends[active]))

    # (2) the program's own pass, fed the burst's states
    fill()
    step = jax.jit(model._pass, donate_argnums=(1,), static_argnums=(5,))
    unmask = jax.jit(lambda *a: model.block_unmask(*a, False))
    base = base0.copy()
    n_pass = np.zeros(lanes, np.int32)
    passes = []
    burst_margin, filled, differ, summed = 0.0, 0, 0, 0
    for r in range(k):
        # a lane the burst ran: one with a block to denoise or to commit
        ran = active & ((bits[r] > 0) | (emitted[r] > 0))
        logits, batcher._cache, counts, picked = step(
            params, batcher._cache, jnp.asarray(toks[r]),
            jnp.asarray(base, jnp.int32), jnp.asarray(masked[r]), bound,
            jnp.asarray(np.where(ran, base + W, 0), jnp.int32))
        new_tok, _m, _k, unmasked = unmask(
            logits, jnp.asarray(toks[r]), jnp.asarray(masked[r]),
            jnp.asarray(n_pass), jnp.asarray(ran), temps, batcher._keys)
        summed = summed + np.asarray(counts) + np.asarray(unmasked)
        logits = np.asarray(logits)
        passes.append({
            "lanes": np.flatnonzero(ran), "base": base[ran].copy(),
            "tokens": toks[r][ran], "masked": masked[r][ran],
            "logits": logits[ran],
            "picks": [np.asarray(p)[ran].reshape(-1, p.shape[-1])
                      for p in picked]})
        if r + 1 < k:
            # what the burst filled in here, against this pass's own logits
            moved = ran & ~commits[r]
            took = masked[r] & ~masked[r + 1] & moved[:, None]
            scale = logits[ran].std()
            for j, i in zip(*np.nonzero(took)):
                row = logits[j, i].copy()
                row[cfg.mask_token_id] = -np.inf
                burst_margin = max(burst_margin, float(
                    (row.max() - row[toks[r + 1][j, i]]) / scale))
                filled += 1
            differ += int((np.asarray(new_tok)[moved]
                           != toks[r + 1][moved]).any(axis=-1).sum())
        n_pass = np.where(commits[r], 0, n_pass + ran)
        base = base + W * commits[r]
    step_rows = committed(batcher._cache, ends)

    burst_rows_ratio = max(
        (_relative(burst_rows[j], step_rows[j]) for j in start
         if active[j] and step_rows[j].size), default=0.0)
    return {
        "tokens": tokens, "start": start, "live": live, "active": active,
        "base0": base0, "ends": ends, "commits": commits, "passes": passes,
        "prefill_picks": prefill_picks, "rows": burst_rows,
        "prompt_rows": (at_rows, prefix_rows),
        "burst_margin": burst_margin, "burst_filled": filled,
        "burst_lanes_differ": differ, "burst_rows_ratio": burst_rows_ratio,
        "burst_counters_hold": bool(np.array_equal(burst_counts, summed)),
        "burst_counts": dict(zip(model.step_counter_names,
                                 burst_counts.tolist())),
        "registers_hold": registers_hold, "lanes": lanes, "k": k,
        "served_s": time.monotonic() - t0,
    }


def judge(model, served: dict, params, variant: str = "") -> dict:
    """The reference half: ONE ``reference.forward`` over the longest
    prompt's whole blocks, routed as the prefill routed (its keys and values
    are every lane's earlier rows), then pass by pass ``block_forward`` over
    the live lanes' blocks as the burst's states had them, each seeing its
    prompt's rows and the rows of the blocks the lane has committed since:
    the reference's own rows of the commit's state, or under ``no_commit``
    those of the pass before it."""
    import time

    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import sdar_moe as reference

    t0 = time.monotonic()
    cfg = model.cfg
    W, L, E = cfg.block_length, cfg.n_layers, cfg.n_routed_experts
    tokens, start = served["tokens"], served["start"]
    base0 = served["base0"]
    whole = int(max(base0))
    # blocks counted from a prompt's end: a lane's own offset; the shared
    # forward takes the longest lane's
    longest = max(start, key=start.get)
    _out, ref_picks, ref_scores, ref_rows, sizes = reference.forward(
        params, cfg, tokens[:whole], [0], variant,
        route_as=[p[:whole] for p in served["prefill_picks"]],
        offset=start[longest] % W)
    margin = max(picks_margin(mine[:whole], theirs)
                 for mine, theirs in zip(served["prefill_picks"], ref_scores))
    same = [np.all(np.sort(mine[:whole], -1) == np.sort(theirs, -1), -1).mean()
            for mine, theirs in zip(served["prefill_picks"], ref_picks)]
    del ref_scores

    # the lanes every pass ran (all the active ones: the burst's budgets
    # outlast it), and per layer the reference's rows of the blocks each
    # has committed since the prefill: [n, M, KV, Dh], the first own_len
    lanes = np.flatnonzero(served["active"])
    n, M = len(lanes), W * ((served["k"] + 1) // 2)
    shape = (n, M, cfg.n_kv_heads, cfg.head_dim)
    own = [[jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32)]
           for _ in range(L)]
    own_len = np.zeros(n, int)
    at_m = np.arange(M)[None, :]
    before = None
    err, err_at, scale = 0.0, None, None
    for r, p in enumerate(served["passes"]):
        if not np.array_equal(p["lanes"], lanes):
            raise ValueError(f"pass {r} ran lanes {p['lanes']}, not {lanes}")
        out, _pk, scores, rows = reference.block_forward(
            params, cfg, p["tokens"], p["base"], ref_rows, own, own_len,
            variant, route_as=p["picks"],
            offsets=[start[j] % W for j in lanes])
        margin = max([margin] + [picks_margin(mine, theirs)
                                 for mine, theirs in zip(p["picks"], scores)])
        if scale is None:
            scale = float(out.std())
        gap = np.abs(p["logits"] - out).max(axis=(1, 2)) / scale
        if gap.max() > err:
            err, err_at = float(gap.max()), (r, int(lanes[gap.argmax()]))
        # a commit leaves the block's rows: this pass's, or under
        # "no_commit" those of the pass before it (a denoising pass's)
        commit = served["commits"][r][lanes]
        kept = before if variant == "no_commit" and before is not None else rows
        lands = jnp.asarray(commit[:, None] & (at_m >= own_len[:, None])
                            & (at_m < own_len[:, None] + W))[..., None, None]
        for l in range(L):
            for kind in (0, 1):
                own[l][kind] = jnp.where(
                    lands, jnp.tile(kept[l][kind], (1, M // W, 1, 1)),
                    own[l][kind])
        own_len = own_len + W * commit
        before = rows

    rows_ratio = 0.0
    for i, j in enumerate(lanes):
        if not own_len[i]:
            continue
        ref = np.stack([np.stack([
            np.asarray(own[l][kind][i, :own_len[i]]).transpose(1, 0, 2)
            for l in range(L)]) for kind in (0, 1)])
        rows_ratio = max(rows_ratio, _relative(served["rows"][j], ref))
    at_rows, prefix = served["prompt_rows"]
    ix = np.asarray(at_rows)
    ref = np.stack([np.stack([
        np.asarray(ref_rows[l][kind])[ix].transpose(1, 0, 2) for l in range(L)])
        for kind in (0, 1)])
    prompt_rows_ratio = _relative(prefix, ref)

    counts = served["burst_counts"]
    ran = sum(len(p["lanes"]) for p in served["passes"])
    committed = int(served["commits"][:, served["active"]].sum())
    counters_hold = bool(
        counts["block_forwards"] == ran
        and counts["block_commit_forwards"] == committed
        and counts["moe_rows_routed"] == ran * W * cfg.experts_per_tok * L
        and counts["moe_layer_steps"] == L * served["k"]
        and served["burst_counters_hold"])
    finite = bool(all(np.isfinite(p["logits"]).all() for p in served["passes"]))
    idled = served["active"].sum() < served["live"].sum()
    ok = bool(
        finite and err <= TOLERANCE and margin * E <= PICKS_MARGIN
        and max(rows_ratio, prompt_rows_ratio) <= ROWS_TOLERANCE
        and served["burst_margin"] <= BURST_TOLERANCE
        and served["burst_rows_ratio"] <= BURST_ROWS_TOLERANCE
        and counters_hold and served["registers_hold"] and not idled
        and committed > 0)
    return {
        "ratio": err, "ratio_at": err_at, "tolerance": TOLERANCE,
        "picks_margin": margin * E, "picks_margin_most": PICKS_MARGIN,
        "picks_agree": float(np.mean(same)),
        "rows_ratio": rows_ratio, "prompt_rows_ratio": prompt_rows_ratio,
        "rows_tolerance": ROWS_TOLERANCE,
        "burst_margin": served["burst_margin"],
        "burst_rows_ratio": served["burst_rows_ratio"],
        "burst_filled": served["burst_filled"],
        "burst_lanes_differ": served["burst_lanes_differ"],
        "burst_tolerance": BURST_TOLERANCE,
        "burst_rows_tolerance": BURST_ROWS_TOLERANCE,
        "counters_hold": counters_hold, "registers_hold": served["registers_hold"],
        "logit_std": scale, "passes": served["k"], "block_forwards": ran,
        "commits": committed, "lanes_live": int(served["live"].sum()),
        "lanes_active": int(served["active"].sum()), "lanes": served["lanes"],
        "experts_touched_a_layer_pass": counts["moe_experts_touched"] / max(
            1, counts["moe_layer_steps"]),
        "rows_per_touched_expert": counts["moe_rows_routed"] / max(
            1, counts["moe_experts_touched"]),
        "branch_sizes": [round(float(x), 3) for x in np.mean(sizes, axis=0)],
        "finite": finite, "variant": variant,
        "served_s": served["served_s"], "reference_s": time.monotonic() - t0,
        "ok": ok,
    }


# -- what a pass must read and a prefill must compute -------------------------------

def _attention_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return 2 * d * q + 2 * d * kv        # wq, wo; wk, wv


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def kv_bytes_per_position_and_layer(cfg: dict) -> int:
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * BYTES


def block_attn_bytes(cfg: dict, counters: dict):
    """Bytes of the cache rows the block kernel read over the capture: the
    program's ``block_rows_read`` (a live lane's length rounded up to the
    kernel's block of 128, summed over passes and layers) x one position's
    keys and values in one layer. None without the counter."""
    rows = counters.get("block_rows_read", 0)
    if rows <= 0:
        return None
    return rows * kv_bytes_per_position_and_layer(cfg)


def decode_step_bytes(cfg: dict, live_positions: float, counters: dict):
    """Bytes ONE pass must read: everything outside the experts once
    (attention, norms, routers, the final norm and the head; not the
    embedding table: four rows a lane), of each layer the experts the pass's
    live rows picked (``moe_experts_touched / moe_layer_steps`` over the
    capture), and the live lanes' keys and values in every layer (every
    query of a block reads the lane's whole length). None where the program
    gave no such counters."""
    steps = counters.get("moe_layer_steps", 0)
    if steps <= 0 or "moe_experts_touched" not in counters:
        return None
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    fixed = (layers * (_attention_params(cfg) + 2 * d + 2 * cfg["head_dim"]
                       + d * cfg["num_experts"])
             + d + d * cfg["vocab_size"])
    touched = counters["moe_experts_touched"] / steps       # a layer and pass
    kv = kv_bytes_per_position_and_layer(cfg) * layers * live_positions
    return (fixed + layers * touched * expert_params(cfg)) * BYTES + kv


def prefill_flops(cfg: dict, padded_tokens: float, sequences: float,
                  counters: dict) -> float:
    """FLOPs of prefilling ``sequences`` prompts padded to ``padded_tokens``
    positions in all: per position the attention projections and the router
    with 8 experts; attention over the block-causal half of the square (a
    query sees up to 3 positions past itself: the diagonal's blocks, counted
    with the half); the head at each prompt's last position. Only the sum
    of the padded lengths is known: the area is convex in a prompt's length,
    so it is taken at the mean length, its least: never counted high."""
    if sequences <= 0:
        return 0.0
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    per_token = layers * (
        _attention_params(cfg) + d * cfg["num_experts"]
        + expert_params(cfg) * cfg["num_experts_per_tok"])
    t = padded_tokens / sequences
    attention = 4.0 * q * sequences * layers * t * t / 2.0
    head = 2.0 * d * cfg["vocab_size"] * sequences
    return 2.0 * per_token * padded_tokens + attention + head
