"""The qwen3_next decoder (Qwen3-Next): everything the benchmark knows of it.

A configuration whose file says ``"architecture": "qwen3_next"`` is served,
compared and costed by this module (``manifest.architecture``). The parent
process loads it too and never imports jax: jax and the program are
imported inside the functions that need them.

**The served family.** ``benchmark_qwen3_next``: the program's own
``DecoderLM(block="qwen3_next")`` (``seldon_core_tpu/models/qwen3_next.py``)
in every method but ``init_params``, which runs the program's own draw
under one ``jit`` and casts each leaf to the served dtype inside it (a
float32 stack of one layer's 128 held experts is 1.6 GB).

**The cut.** The configuration's file keeps every published width. Depth:
``served_layers`` names the published layers that are served, and their
kinds come from the published rule (layer i is ``full_attention`` where
``(i + 1) % full_attention_interval == 0``). Experts: ``num_experts`` is
what this chip HOLDS of each layer, ``experts_held`` [first, end) which
ones, ``num_experts_published`` what the router ranges over. Vocabulary:
``vocab_size`` is the chip's slice; ids, logits and sampling are over it.

**The costs.** Operations and bytes from shapes, the benchmark's own copy.
What a decode step reads of the held experts and of the lanes' recurrent
state is data-dependent, so it comes from the program's counters, as the
capture gives them; where they are missing the bytes are ``None``, never a
guess.
"""

from __future__ import annotations

# one copy of the margin among the architecture modules (numpy only, as
# this module jax-free at import)
from benchmark.architectures.afmoe import picks_margin
from benchmark.manifest import ManifestError

FAMILY = "benchmark_qwen3_next"

# Agreement asked of the served path: three limits, any of which fails it
# (``compare_served`` says what each compares). Readings on the chip over
# 12 seeds in the cell's regime, 28 live lanes of 32, 113 positions each
# (my chip runs, PR 38, call 5), and the controls that must fail (the
# state's on 4 seeds, the others on 1 here and on 3 at 64 lanes, call 3,
# which read the same to the second digit).
#
# ``TOLERANCE``: max |served - reference| over the compared logits (every
# live lane at every decode step x the sliced vocabulary, and the
# prefill's last position) over the reference logits' standard deviation,
# with the reference routed as the served model routed (unrouted, a
# flipped pick is a whole expert's output: the afmoe module's finding).
# Sound 0.0382-0.0422 (0.0387-0.0452 at 64 lanes). 8-bit weights 0.357
# (0.358-0.396), rotary on all 256 dims 0.496 (0.522-0.566), the decay
# left out 1.67 (1.79-1.81). So 0.1, as the other families': 2.2 times
# the largest sound reading, 0.28 of the least control's. (The state kept
# in bfloat16 reads 0.041-0.043 here: the logits cannot tell it;
# ``STATE_TOLERANCE`` does.)
#
# ``PICKS_MARGIN``: how far outside the reference router's own top 10 a
# served pick may lie, in the router's softmax probability over all 512
# taken in units of the uniform probability 1 / 512 (so that the limit
# means the same at a rehearsal's 16 experts): over every (position,
# layer) the largest of (best reference probability among the experts the
# served model left out) - (worst among the 10 it picked), 0 where the
# picks are the reference's. A probability that differs by bfloat16
# rounding swaps two experts whose reference probabilities lie closer
# than that rounding (7% of the pairs hold such a swap): that is allowed,
# and no other. Sound 0.127-0.170 (0.114-0.205 at 64 lanes); 8-bit
# weights 1.69 (1.54-1.71), rotary on all dims 2.34 (2.16-2.74), no decay
# 14.2 (10.2-13.6). So 0.6: 2.9 times the largest sound reading, 0.39 of
# the least control's.
#
# ``STATE_TOLERANCE``: the live lanes' recurrent state after their last
# step against the reference's own at that position, |served -
# reference|_F / |reference|_F over a lane's 32 heads, the MEAN over
# (lane, linear layer): the worst of them is reported beside it
# (``state_ratio_worst``) and swings five times as much from seed to
# seed. The served state is a float32 sum of products of bfloat16
# operands: each write is off by a rounding of its own and the errors do
# not add up: 0.008314-0.008343 over the 12 seeds (worst 0.0113-0.0115).
# A state kept in bfloat16 between tokens (``reduce_precision`` after
# every token of the reference's scan) is rounded once a token for as
# long as a head remembers: 0.012654-0.012713 (worst 0.0153-0.0157), and
# neither other limit sees it. So 0.0103, between the two: 1.23 times the
# largest sound reading, 0.81 of the least control's, each side's own
# swing under 0.5%. (8-bit weights 0.089, rotary on all dims 0.052, no
# decay 0.92.)
#
# The batcher's burst against the program's own step fed the burst's
# tokens, both by ``TOLERANCE`` (two compilations of one step: the scan's
# body and the step alone), 12 seeds at 48 lanes (my chip runs, PR 38,
# call 9). ``burst_margin``: where the burst's token is not the step's
# argmax (2-4% of the 168: near ties), how far under its maximum the
# step's logits put it, in their deviations: 0.003-0.028. ``burst_cache_
# ratio``: the worst live lane's state, tail and new key and value rows,
# relative: bit-equal (0.0) on 9 seeds, 0.0015-0.0033 (state) and
# 0.0029-0.0078 (rows of bfloat16: one pick flipped between the two puts
# a rounding into every row after it) on 3. The control, a live lane the
# burst leaves out (``variant="burst_idles_a_lane"``): call 10.
TOLERANCE = 0.1
PICKS_MARGIN = 0.6
STATE_TOLERANCE = 0.0103

BYTES = 2        # bfloat16 weights, keys, values and convolution tails
STATE_BYTES = 4  # the recurrent state is float32
LINEAR, FULL = "linear_attention", "full_attention"


# -- the served family ---------------------------------------------------------

def __getattr__(name: str):
    # built when the program asks for it by its dotted path: defining it
    # imports the program, and with it jax
    if name != "SeededQwen3NextLM":
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    from seldon_core_tpu.models.qwen3_next import Qwen3NextLM

    class SeededQwen3NextLM(Qwen3NextLM):
        def init_params(self, seed: int = 0):
            import jax
            import jax.numpy as jnp

            dt = jnp.dtype(self.cfg.dtype)
            draw = super().init_params

            def served(s):
                return jax.tree_util.tree_map(lambda a: a.astype(dt), draw(s))

            return jax.jit(served)(jnp.uint32(seed))

    globals()[name] = SeededQwen3NextLM
    return SeededQwen3NextLM


def register() -> None:
    from seldon_core_tpu import models
    # a program without the family fails here, at once and cleanly
    from seldon_core_tpu.models import qwen3_next  # noqa: F401

    models.register(FAMILY, f"{__name__}.SeededQwen3NextLM")


def served_layer_types(cfg: dict) -> list:
    """The kinds of the layers that are served, by the published rule."""
    served = cfg["served_layers"]
    if len(served) != cfg["num_hidden_layers"]:
        raise ManifestError(
            f"{cfg['name']}: served_layers names {len(served)} layers, "
            f"num_hidden_layers says {cfg['num_hidden_layers']}")
    every = cfg["full_attention_interval"]
    return [FULL if (i + 1) % every == 0 else LINEAR for i in served]


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
    """The published config's keys as ``DecoderLM(block="qwen3_next")``
    takes them."""
    if not cfg["norm_topk_prob"]:
        raise ManifestError(f"{cfg['name']}: only normed top-k weights")
    if cfg["decoder_sparse_step"] != 1 or cfg["mlp_only_layers"]:
        raise ManifestError(f"{cfg['name']}: every layer is an expert layer")
    return {
        "block": "qwen3_next",
        "vocab_size": cfg["vocab_size"],
        "d_model": cfg["hidden_size"],
        "n_layers": cfg["num_hidden_layers"],
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "max_seq": cfg["server"]["max_seq"],
        "rope_theta": float(cfg["rope_theta"]),
        "partial_rotary_factor": float(cfg["partial_rotary_factor"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "dtype": cfg["torch_dtype"],
        "layer_types": served_layer_types(cfg),
        "linear_key_heads": cfg["linear_num_key_heads"],
        "linear_value_heads": cfg["linear_num_value_heads"],
        "linear_key_dim": cfg["linear_key_head_dim"],
        "linear_value_dim": cfg["linear_value_head_dim"],
        "linear_conv_kernel": cfg["linear_conv_kernel_dim"],
        "n_routed_experts": cfg["num_experts_published"],
        "experts_held": list(held(cfg)),
        "experts_per_tok": cfg["num_experts_per_tok"],
        "expert_width": cfg["moe_intermediate_size"],
        "shared_expert_width": cfg["shared_expert_intermediate_size"],
        "residual_scale": cfg["weights"]["residual_scale"],
        # PRNGKey takes 32 bits; the driver's seeds are larger
        "seed": seed % (2**31 - 1),
    }


def rehearsal(cfg: dict) -> dict:
    """The sizes ``--rehearse-cpu`` puts over the configuration's: one
    period of four layers, 4 of 16 experts held."""
    return {
        "hidden_size": 128, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 64, "moe_intermediate_size": 64,
        "shared_expert_intermediate_size": 64,
        "num_hidden_layers": 4, "served_layers": [0, 1, 2, 3],
        "linear_num_key_heads": 2, "linear_num_value_heads": 4,
        "linear_key_head_dim": 32, "linear_value_head_dim": 32,
        "num_experts": 4, "experts_held": [0, 4], "num_experts_published": 16,
        "num_experts_per_tok": 4, "vocab_size": 1024,
    }


# -- the served model against the plain reference ----------------------------------

IDLE_EVERY = 8      # lanes 5, 13, 21, ... idle among the live ones
PREFILL_ROWS = 8    # the batched prefill's rows: one program, several runs
CHUNK = 64          # the prefill's chunk: lengths are put on both its sides


def served_slots() -> int:
    """``server.slots`` of the configuration this module serves, from its
    file: the comparison's batch is the burst's."""
    import os

    from benchmark import manifest

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    man = manifest.load(root)
    mine = __name__.rsplit(".", 1)[-1]
    slots = {c["server"]["slots"]
             for c in (manifest.config(root, man, e["name"])
                       for e in man["configs"]) if c["architecture"] == mine}
    if len(slots) != 1:
        raise ManifestError(
            f"{mine}: its configurations serve {sorted(slots)} lanes; the "
            "comparison takes one batch")
    return slots.pop()


def lane_lengths(lanes: int, prompt_len: int, decode_steps: int) -> dict:
    """``{lane: tokens it holds before its first step}`` for the live
    lanes: spread evenly from ``prompt_len // 16`` to ``prompt_len`` (the
    lane that goes on where the whole prompt ended), no two lanes' steps
    at one position, and three of them moved to a chunk's edge: a multiple
    of ``CHUNK``, one under it and one over it."""
    import numpy as np

    live = [j for j in range(lanes) if j % IDLE_EVERY != 5]
    lens = np.linspace(max(4, prompt_len // 16), prompt_len,
                       len(live)).round().astype(int)
    if len(live) >= 6 and np.diff(lens).min() >= 3 * decode_steps:
        for at, off in ((1, 0), (len(live) // 2, -1), (len(live) - 2, 1)):
            edge = int(round(lens[at] / CHUNK)) * CHUNK + off
            if lens[at - 1] + decode_steps <= edge <= lens[at + 1] - decode_steps:
                lens[at] = edge
    if np.diff(lens).min() < decode_steps:
        raise ValueError(f"{len(live)} lanes of {decode_steps} steps do not "
                         f"fit apart in {prompt_len} positions")
    return dict(zip(live, lens.tolist()))


def compare_served(model, params, seed: int, prompt_len: int = 2304,
                   decode_steps: int = 4, variant: str = "",
                   lanes: int = 0) -> dict:
    """The served path in the regime the cell times, against ONE full
    causal forward of the reference over the same ``prompt_len +
    decode_steps`` tokens; ``lanes``: ``served_slots()`` where not given.

    A recurrent state cannot be cut back to a shorter prompt as a KV cache
    can, so each live lane's rows come from the prefill ITSELF: one
    batched prefill program (``PREFILL_ROWS`` copies of the prompt, padded
    to one bucket) is run with each row's ``last_index`` at a lane's own
    length L_j (``lane_lengths``: most lanes live, every eighth idle,
    lengths on both sides of a chunk's edge and all but one below the
    bucket), and the state and convolution tail it returns for the row
    ARE those after the lane's first L_j tokens. Each group's rows go into
    their lanes through the BATCHER'S OWN compiled ``insert_many`` over a
    cache the batcher laid out (a ``ContinuousBatcher`` of this
    comparison's own, ``lanes`` x the bucket: its executables are the
    serving one's functions): keys and values at ``[lane, :, :bucket]``, a
    state and a tail whole at ``[lane]``.

    Then three runs of ``decode_steps`` steps from that one filled cache.
    (1) The batcher's compiled burst (``_burst_fn``, k = ``decode_steps``,
    the cache carried through its scan and donated): its tokens, its
    counters and the cache it leaves. (2) The program's own step
    (``model._step``: ``decode_step_cache``, which the burst's body calls,
    and the picks) one step at a time, fed the BURST'S tokens: the burst
    must have sampled each step's argmax, left the same state, tails and
    new key and value rows in the same lanes, and counted the same. (3)
    That step fed the prompt's own next tokens, whose logits, picks and
    final states the reference's one forward can be compared with: the
    ragged attention kernel over the lanes' lengths, the state kernel
    skipping the idle lanes, the touched-expert kernel over this chip's
    share. ``variant="burst_idles_a_lane"``, the burst's own control:
    its first live lane is left out of the burst's ``active`` (the
    reference is the sound one).

    Held: ``ratio`` <= ``TOLERANCE`` (logits of every live lane at every
    step of (3) and the prefill's last, the reference ROUTED AS THE SERVED
    MODEL ROUTED every position, as the afmoe module does and for its
    reasons); ``picks_margin`` <= ``PICKS_MARGIN`` (the served picks are
    the reference router's top 10 but for swaps inside that margin of its
    probabilities, in units of the uniform 1 / experts); ``state_ratio``
    <= ``STATE_TOLERANCE`` (each live lane's recurrent state after its
    last step against the reference's own at that position, relative, the
    mean over (lane, layer)); the burst against the steps by the logits'
    limit: ``burst_margin`` <= ``TOLERANCE`` (where the burst's token is
    not the step's argmax, how far under it the step's logits put it, in
    their deviations: 0 where every token is) and ``burst_cache_ratio`` <=
    ``TOLERANCE`` (the worst live lane's state, tail or new key or value
    rows, relative), an idle lane's rows still the zeros they were
    laid out as; the step's counters are the picks' own count and the
    burst's sum to the steps'; and the step was busy (several rows on a
    touched expert, about a quarter of the picks held)."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import qwen3_next as reference
    from seldon_core_tpu.serving.continuous import ContinuousBatcher

    t0 = time.monotonic()
    lanes = lanes or served_slots()
    cfg = model.cfg
    rng = np.random.default_rng(seed % (2**63))
    total = prompt_len + decode_steps
    tokens = rng.integers(0, cfg.vocab_size, size=total, dtype=np.int64)
    cache_len = -(-total // 128) * 128
    start = lane_lengths(lanes, prompt_len, decode_steps)
    live = np.array([j in start for j in range(lanes)])
    at = np.array([start.get(j, 0) for j in range(lanes)])
    kinds = [t == LINEAR for t in cfg.layer_types]
    n_linear, n_layers = sum(kinds), len(kinds)
    lo, n_held = cfg.experts_held or (0, cfg.n_routed_experts)

    # logits, cache and picks of ONE program, run once per group of lanes,
    # each group's rows put into its lanes by the batcher's own insert
    batcher = ContinuousBatcher(model, params, slots=lanes, max_seq=cache_len)
    cache, cur_tok, lane_pos, keys = (batcher._cache, batcher._cur_tok,
                                      batcher._pos, batcher._keys)
    batcher._cache = None        # donated below: this comparison's alone
    prefill = jax.jit(lambda p, t, last: model._prefill(p, t, cache_len, last))
    prompt = jnp.asarray(
        np.broadcast_to(tokens[None, :prompt_len], (PREFILL_ROWS, prompt_len)),
        jnp.int32)
    order = sorted(start, key=lambda j: -start[j])    # the whole prompt first
    first = picks = None
    for g in range(0, len(order), PREFILL_ROWS):
        group = order[g:g + PREFILL_ROWS]
        n = len(group)
        begin = np.array([start[j] for j in group])
        last = np.concatenate([begin - 1, np.zeros(PREFILL_ROWS - n, int)])
        logits, slab, routed = prefill(params, prompt, jnp.asarray(last, jnp.int32))
        if first is None:
            # row 0 ended at prompt_len: its logits, and the picks every
            # row shares
            first = np.asarray(logits[0])
            picks = [np.concatenate([np.asarray(r[0]),
                                     np.zeros_like(r[0, :decode_steps])])
                     for r in routed]
        # a lane steps from the prompt's token at its own length
        cache, cur_tok, lane_pos, keys = batcher._insert_many_fn(
            cache, jax.tree_util.tree_map(lambda a: a[:, :n], slab),
            jnp.asarray(group, jnp.int32), jnp.asarray(tokens[begin], jnp.int32),
            jnp.asarray(begin, jnp.int32), jnp.zeros((n, 2), jnp.uint32),
            cur_tok, lane_pos, keys)
        del slab, logits, routed
    inserted = bool(np.array_equal(np.asarray(lane_pos), at) and np.array_equal(
        np.asarray(cur_tok)[live], tokens[at[live]]))

    def copy(tree):
        return jax.tree_util.tree_map(jnp.copy, tree)

    def rows_written(cache):
        """What ``decode_steps`` steps leave in a live lane, by kind: its
        state and tail, and the key and value rows at its new positions."""
        out = {"state": [np.asarray(a)[live] for a in cache["state"]],
               "conv": [np.asarray(a, np.float32)[live] for a in cache["conv"]]}
        new = at[live, None] + np.arange(decode_steps)[None]
        for name in ("k", "v"):
            out[name] = [np.stack([np.asarray(a[j, :, p], np.float32)
                                   for j, p in zip(np.flatnonzero(live), new)])
                         for a in cache[name]]
        idle = all(not np.asarray(a)[~live].any()
                   for a in cache["state"] + cache["conv"])
        return out, idle

    # (1) the batcher's burst
    second = copy(cache)
    active = live.copy()
    if variant == "burst_idles_a_lane":
        active[np.flatnonzero(live)[0]], variant = False, ""
    toks, *_, burst_cache, _k, burst_counts = batcher._burst_fn(
        params, cache, cur_tok, lane_pos, jnp.asarray(active),
        jnp.zeros((lanes,), jnp.float32), keys, decode_steps,
        None if batcher._ragged_read else cache_len)
    toks = np.asarray(toks)                   # [steps + 1, lanes]
    burst_counts = np.asarray(burst_counts)
    burst_rows, idle_untouched = rows_written(burst_cache)
    del burst_cache, cache

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
                         [np.asarray(r)[:, 0] for r in routed]))   # [lanes, k]
        return outs, cache

    # (2) the step, fed the burst's tokens
    third = copy(second)
    outs, second = steps(second, lambda i: toks[i])
    step_rows, _idle = rows_written(second)
    del second
    burst_margin, agree = 0.0, []
    for i, (out, _c, _r) in enumerate(outs):
        mine = out[live]
        theirs = mine[np.arange(len(mine)), toks[i + 1][live]]
        agree.append(mine.argmax(-1) == toks[i + 1][live])
        burst_margin = max(burst_margin, float(
            (mine.max(-1) - theirs).max() / mine.std()))
    burst_cache_ratio = {
        name: max(float(np.linalg.norm(b[n] - a[n]) / np.linalg.norm(a[n]))
                  for a, b in zip(step_rows[name], burst_rows[name])
                  for n in range(len(a)))
        for name in step_rows}
    summed = np.sum([c for _o, c, _r in outs], axis=0)
    # what live lanes there are sums exactly; a pick that a rounding flips
    # between the two programs moves the two that follow the picks
    burst_counters_hold = bool(
        np.array_equal(burst_counts[[1, 2, 4]], summed[[1, 2, 4]])
        and np.all(np.abs(burst_counts - summed) <= 0.02 * summed))
    del step_rows, burst_rows

    # (3) the step, fed the prompt's own tokens: what the reference follows
    outs, third = steps(third, lambda i: tokens[at + i])
    states = [np.asarray(a) for a in third["state"]]
    del third, batcher
    served, positions = [first], [prompt_len - 1]
    counters_hold = True
    touched = rows = rows_held = 0
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
        counters_hold &= counts.tolist() == [
            distinct, pairs, n_layers, landed, int(live.sum()) * n_linear]
        touched, rows, rows_held = (touched + distinct, rows + pairs,
                                    rows_held + landed)
    served = np.stack(served)
    t1 = time.monotonic()
    # the lanes' states are compared where their last step left them
    ends = {j: start[j] + decode_steps - 1 for j in start}
    ref, ref_picks, ref_probs, ref_states = reference.forward(
        params, cfg, tokens, positions, variant, route_as=picks,
        states_at=sorted(set(ends.values())))
    scale = float(ref.std())
    by_position = (np.max(np.abs(served - ref), axis=-1) / scale).tolist()
    err = max(by_position)
    margin = cfg.n_routed_experts * max(
        picks_margin(mine, theirs) for mine, theirs in zip(picks, ref_probs))
    same = [np.all(np.sort(mine, -1) == np.sort(theirs, -1), -1)
            for mine, theirs in zip(picks, ref_picks)]
    where = {p: n for n, p in enumerate(sorted(set(ends.values())))}
    state_errs = [
        float(np.linalg.norm(states[l][j] - ref_states[l][where[ends[j]]])
              / np.linalg.norm(ref_states[l][where[ends[j]]]))
        for j in start for l in range(n_linear)]
    state_err = float(np.mean(state_errs))
    finite = bool(np.isfinite(served).all())
    per_layer_step = touched / max(1, n_layers * decode_steps)
    busy = (per_layer_step > 0.4 * n_held and rows_held > touched
            and 0.15 < rows_held / max(1, rows) < 0.35)
    burst_holds = (inserted and idle_untouched and burst_counters_hold
                   and burst_margin <= TOLERANCE
                   and max(burst_cache_ratio.values()) <= TOLERANCE)
    return {
        "ratio": err, "ratio_at": positions[int(np.argmax(by_position))],
        "tolerance": TOLERANCE, "picks_margin": margin,
        "picks_margin_most": PICKS_MARGIN, "state_ratio": state_err,
        "state_tolerance": STATE_TOLERANCE, "state_ratio_worst": max(state_errs),
        "picks_agree": float(np.mean(same)), "logit_std": scale,
        "positions": len(positions), "prompt_len": prompt_len,
        "lanes_live": int(live.sum()), "lanes": lanes,
        "experts_touched_a_layer_step": per_layer_step,
        "rows_per_touched_expert": rows_held / max(1, touched),
        "held_rows_share": rows_held / max(1, rows),
        "counters_are_the_picks": bool(counters_hold), "finite": finite,
        "burst_tokens_agree": float(np.mean(agree)),
        "burst_margin": burst_margin, "burst_cache_ratio": burst_cache_ratio,
        "burst_counters_hold": burst_counters_hold, "inserted": inserted,
        "idle_untouched": bool(idle_untouched),
        "served_s": t1 - t0, "reference_s": time.monotonic() - t1,
        "ok": bool(finite and err <= TOLERANCE and margin <= PICKS_MARGIN
                   and state_err <= STATE_TOLERANCE and counters_hold and busy
                   and burst_holds),
    }


# -- what a step must read and a prefill must compute -------------------------------

def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _kinds(cfg: dict) -> tuple:
    """``(linear layers, full layers)`` served."""
    kinds = served_layer_types(cfg)
    linear = sum(1 for k in kinds if k == LINEAR)
    return linear, len(kinds) - linear


def _conv_channels(cfg: dict) -> int:
    return (2 * cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
            + cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"])


def _value_width(cfg: dict) -> int:
    return cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]


def _moe_fixed_params(cfg: dict) -> int:
    """A layer's two norms, router, shared expert and its gate."""
    d = cfg["hidden_size"]
    return (2 * d + d * cfg["num_experts_published"]
            + 3 * d * cfg["shared_expert_intermediate_size"] + d)


def _linear_params(cfg: dict) -> int:
    d, c, vw = cfg["hidden_size"], _conv_channels(cfg), _value_width(cfg)
    hv = cfg["linear_num_value_heads"]
    return (d * (c + vw) + d * 2 * hv + cfg["linear_conv_kernel_dim"] * c
            + 2 * hv + cfg["linear_value_head_dim"] + vw * d)


def _full_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return 3 * d * q + 2 * d * kv + 2 * cfg["head_dim"]   # wq, wg, wo; wk, wv


def gdn_state_bytes(cfg: dict) -> int:
    """One lane's recurrent matrix in one linear layer: what one
    ``gdn_lane_steps`` reads once and writes once."""
    return (cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"]
            * cfg["linear_value_head_dim"] * STATE_BYTES)


def kv_bytes_per_position(cfg: dict) -> int:
    """Keys and values of one position in the FULL layers: a linear layer
    holds none."""
    return (_kinds(cfg)[1] * 2 * cfg["num_key_value_heads"] * cfg["head_dim"]
            * BYTES)


def decode_step_bytes(cfg: dict, live_positions: float, counters: dict):
    """Bytes one decode step must move: everything outside the routed
    experts once (mixers, norms, routers, shared experts, the final norm
    and the sliced head; not the embedding table: one row a lane); of each
    layer the HELD experts the step's live lanes picked
    (``moe_experts_touched / moe_layer_steps`` over the capture); each
    live lane's recurrent state read once and written once a linear layer,
    and its convolution tail (``gdn_lane_steps`` over the steps); the live
    keys and values of the full layers only. None where the program gave
    no such counters."""
    layer_steps = counters.get("moe_layer_steps", 0)
    lane_steps = counters.get("gdn_lane_steps", 0)
    if layer_steps <= 0 or lane_steps <= 0:
        return None
    linear, full = _kinds(cfg)
    d = cfg["hidden_size"]
    steps = layer_steps / (linear + full)
    fixed = ((linear + full) * _moe_fixed_params(cfg)
             + linear * _linear_params(cfg) + full * _full_params(cfg)
             + d + d * cfg["vocab_size"])
    touched = counters["moe_experts_touched"] / steps       # all layers
    tail = (cfg["linear_conv_kernel_dim"] - 1) * _conv_channels(cfg) * BYTES
    state = lane_steps / steps * 2 * (gdn_state_bytes(cfg) + tail)
    return ((fixed + touched * expert_params(cfg)) * BYTES + state
            + kv_bytes_per_position(cfg) * live_positions)


def prefill_flops(cfg: dict, padded_tokens: float, sequences: float,
                  counters: dict) -> float:
    """FLOPs of prefilling ``sequences`` prompts padded to ``padded_tokens``
    positions in all: per position a layer's mixer projections, the router,
    the shared expert and the picks expected to land on a held expert
    (``num_experts_per_tok x num_experts / num_experts_published``: the
    router is near uniform under seeded weights); in a linear layer the
    recurrence (decay, read, write and output of a [Dk, Dv] state a value
    head: 7 Dk Dv) and the convolution; in a full layer attention over
    half the square, taken at the mean length (its least); the head at
    each prompt's last position."""
    if sequences <= 0:
        return 0.0
    d = cfg["hidden_size"]
    linear, full = _kinds(cfg)
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    picks = (cfg["num_experts_per_tok"] * cfg["num_experts"]
             / cfg["num_experts_published"])
    moe = (d * cfg["num_experts_published"] + d
           + 3 * d * cfg["shared_expert_intermediate_size"]
           + picks * expert_params(cfg))
    c, vw = _conv_channels(cfg), _value_width(cfg)
    lin = (d * (c + vw) + d * 2 * cfg["linear_num_value_heads"] + vw * d
           + cfg["linear_conv_kernel_dim"] * c
           + 3.5 * vw * cfg["linear_key_head_dim"])
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    per_token = (linear + full) * moe + linear * lin + full * (
        3 * d * q + 2 * d * kv)
    t = padded_tokens / sequences
    attention = 4.0 * q * sequences * full * t * t / 2.0
    head = 2.0 * d * cfg["vocab_size"] * sequences
    return 2.0 * per_token * padded_tokens + attention + head
