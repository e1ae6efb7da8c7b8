"""The afmoe decoder (arcee-ai Trinity): everything the benchmark knows of it.

A configuration whose file says ``"architecture": "afmoe"`` is served,
compared and costed by this module (``manifest.architecture``). The parent
process loads it too and never imports jax: jax and the program are
imported inside the functions that need them.

**The served family.** ``benchmark_afmoe``: the program's own
``DecoderLM(block="afmoe")`` (``seldon_core_tpu/models/afmoe.py``) in every
method but ``init_params``, which runs the program's own draw under one
``jit`` and casts each leaf to the served dtype inside it: a float32 expert
stack of one layer is 3.2 GB and the chip holds four of them in bfloat16.

**The depth cut.** The configuration's file keeps the published
``layer_types`` (32) whole and names the layers that are served in
``served_layers``: the kinds of the served layers are read off the
published list at those indices, so the pattern is the model's and not
this module's.

**The costs.** Operations and bytes from shapes, the benchmark's own copy
(a share computed with the program's arithmetic could be moved by a change
to it). What a decode step reads of the routed experts is data-dependent,
so it comes from the program's counters, as the capture gives them; where
they are missing the bytes are ``None``, never a guess.
"""

from __future__ import annotations

from benchmark.manifest import ManifestError

FAMILY = "benchmark_afmoe"

# Agreement asked of the served path: two limits, either of which fails it
# (``compare_served`` says what each compares; the readings behind them are
# in PERF.md, section 6, PR 33).
#
# ``PICKS_MARGIN``: how far outside the reference router's own top 8 a
# served pick may lie, in the router's score (``sigmoid`` of the logit, plus
# the bias): over every (position, routed layer) the largest of (best
# reference score among the experts the served model left out) - (worst
# reference score among the 8 it picked), 0 where the picks are the
# reference's. The served path computes in bfloat16, and a score that
# differs by rounding swaps two experts whose reference scores lie closer
# than that rounding: such a swap is allowed, and no other. On the chip
# 0.0032-0.0050 over 12 seeds (5% of the 9232 pairs hold such a swap); with
# the reference's weights rounded to 8-bit floats (e4m3) 0.046-0.047, with
# its window left off 0.054-0.061 (my chip runs, PR 33). So 0.015: three
# times the largest sound reading, a third of what 8 bits give.
#
# ``TOLERANCE``: max |served - reference| over the compared logits (every
# live lane at every decode step x the vocabulary, and the prefill's last
# position), divided by the reference logits' standard deviation, with the
# reference routed as the served model routed. Under one routing the two
# differ by rounding: 0.036-0.042 over the same 12 seeds (the dense decoders
# read 0.04-0.05), 8-bit weights 0.340-0.347, the window left off
# 0.467-0.484. So 0.1, as the dense decoders': 2.4 times the largest
# reading, a third of what 8 bits give. Two things this rests on, both
# found by failing without them. Unrouted, the same ratio read 0.32 and 0.59 on two seeds
# and 0.68 for the 8-bit control: a flip at a compared position is an
# expert's whole output, and no limit lies between those. And the served
# picks must come from the program whose logits are compared: taken from a
# second program over the same tokens (other fusions, other roundings,
# other picks at ~1% of the near-ties) 3 of 19 runs read 0.42-0.56.
TOLERANCE = 0.1
PICKS_MARGIN = 0.015

BYTES = 2  # bfloat16 weights and cache
SLIDING = "sliding_attention"


# -- the served family ---------------------------------------------------------

def __getattr__(name: str):
    # built when the program asks for it by its dotted path: defining it
    # imports the program, and with it jax
    if name != "SeededAfmoeLM":
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    from seldon_core_tpu.models.afmoe import AfmoeLM

    class SeededAfmoeLM(AfmoeLM):
        def init_params(self, seed: int = 0):
            import jax
            import jax.numpy as jnp

            dt = jnp.dtype(self.cfg.dtype)
            draw = super().init_params

            def served(s):
                return jax.tree_util.tree_map(lambda a: a.astype(dt), draw(s))

            return jax.jit(served)(jnp.uint32(seed))

    globals()[name] = SeededAfmoeLM
    return SeededAfmoeLM


def register() -> None:
    from seldon_core_tpu import models
    # a program without the family fails here, at once and cleanly
    from seldon_core_tpu.models import afmoe  # noqa: F401

    models.register(FAMILY, f"{__name__}.SeededAfmoeLM")


def served_layer_types(cfg: dict) -> list:
    """The kinds of the layers that are served, off the published list."""
    served = cfg["served_layers"]
    if len(served) != cfg["num_hidden_layers"]:
        raise ManifestError(
            f"{cfg['name']}: served_layers names {len(served)} layers, "
            f"num_hidden_layers says {cfg['num_hidden_layers']}")
    return [cfg["layer_types"][i] for i in served]


def n_dense(cfg: dict) -> int:
    return sum(1 for i in cfg["served_layers"] if i < cfg["num_dense_layers"])


def model_kwargs(cfg: dict, seed: int) -> dict:
    """The published config's keys as ``DecoderLM(block="afmoe")`` takes them."""
    if cfg["score_func"] != "sigmoid" or not cfg["route_norm"]:
        raise ManifestError(f"{cfg['name']}: only sigmoid scores, normed")
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ManifestError(f"{cfg['name']}: expert groups are not served")
    return {
        "block": "afmoe",
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
        "layer_types": served_layer_types(cfg),
        "sliding_window": cfg["sliding_window"],
        "n_dense_layers": n_dense(cfg),
        "n_routed_experts": cfg["num_experts"],
        "experts_per_tok": cfg["num_experts_per_tok"],
        "expert_width": cfg["moe_intermediate_size"],
        "n_shared_experts": cfg["num_shared_experts"],
        "route_scale": float(cfg["route_scale"]),
        "residual_scale": cfg["weights"]["residual_scale"],
        # PRNGKey takes 32 bits; the driver's seeds are larger
        "seed": seed % (2**31 - 1),
    }


def rehearsal(cfg: dict) -> dict:
    """The sizes ``--rehearse-cpu`` puts over the configuration's: one dense
    layer and a period of four, eight experts, a window the tiny prompts
    cross."""
    return {
        "hidden_size": 256, "num_attention_heads": 4, "num_key_value_heads": 1,
        "head_dim": 128, "intermediate_size": 512, "moe_intermediate_size": 128,
        "num_hidden_layers": 5, "served_layers": [0, 4, 5, 6, 7],
        "num_dense_layers": 1, "num_experts": 8, "num_experts_per_tok": 2,
        "sliding_window": 256, "vocab_size": 1024,
    }


# -- the served model against the plain reference ----------------------------------

LANES = 32          # the configuration's ``server.slots``: the burst's batch
IDLE_EVERY = 8      # lanes 5, 13, 21, 29 idle among the live ones


def lane_lengths(prompt_len: int, decode_steps: int, window: int) -> dict:
    """``{lane: keys it holds before its first step}`` for the live lanes:
    spread evenly from 128 to ``prompt_len`` (the lane that goes on where
    the prefill ended), so on both sides of the window, no two lanes' steps
    at one position, and the lane nearest the window moved to where its
    steps cross the window's edge."""
    import numpy as np

    live = [j for j in range(LANES) if j % IDLE_EVERY != 5]
    lens = np.linspace(min(128, prompt_len // 4), prompt_len,
                       len(live)).round().astype(int)
    if prompt_len > window:
        lens[np.argmin(np.abs(lens[:-1] - window))] = window - decode_steps // 2
    if np.diff(lens).min() < decode_steps:
        raise ValueError(f"{len(live)} lanes of {decode_steps} steps do not "
                         f"fit apart in {prompt_len} positions")
    return dict(zip(live, lens.tolist()))


def picks_margin(picks, scores) -> float:
    """picks [T, k] of the served model against the reference's scores
    [T, E]: the largest (best score left out) - (worst score picked), at
    least 0: how far from the reference's top k the served picks lie."""
    import numpy as np

    picked = np.take_along_axis(scores, picks, -1).min(-1)
    rest = scores.copy()
    np.put_along_axis(rest, picks, -np.inf, -1)
    return float(max(0.0, (rest.max(-1) - picked).max()))


def compare_served(model, params, seed: int, prompt_len: int = 2304,
                   decode_steps: int = 4, variant: str = "") -> dict:
    """The served path in the regime the cell times, against one full
    forward of the reference over the same ``prompt_len + decode_steps``
    tokens.

    Prefill of ``prompt_len`` seeded tokens (past the window, through the
    flash kernel's band and the grouped experts). Its cache is copied into
    every one of ``LANES`` lanes, and lane j is given its own length L_j
    (``lane_lengths``): it holds the first L_j tokens, which is what a
    causal model's cache of a longer prompt holds there, and the reference's
    logits at position L_j + i are what its i-th step must give. Then
    ``decode_steps`` steps of the program's own step (``model._step``:
    ``decode_step_ragged_list``, which the burst runs, and the picks) over
    all lanes at once: 28 live lanes at 28 lengths with 4 idle ones among
    them, so the ragged kernel's write and per-lane ``starts`` on both
    sides of the window, and the touched-expert kernel with most of the
    experts touched and several rows on one expert.

    Three things are held. ``picks_margin`` <= ``PICKS_MARGIN``: every
    position's served picks (the prefill's, and at a stepped position the
    step's) are the reference router's top 8 but for swaps inside that
    margin of its scores. ``ratio`` <= ``TOLERANCE``: the logits of every
    live lane at every step, and the prefill's last, against the
    reference's WITH EVERY POSITION ROUTED AS THE SERVED MODEL ROUTED IT
    (``route_as``): under one routing the two differ by rounding, and a
    wrong window, norm, gate or precision shows in the logits. (A lane
    reads the prefill's keys at a position another lane stepped; where the
    two programs picked differently there the reference has the step's:
    one key of hundreds, a layer on.) And the step's counters are the
    picks' own count: distinct experts of the live lanes, live lanes x 8
    rows, a layer, with far more than 8 experts touched and more than one
    row on each."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import afmoe as reference

    t0 = time.monotonic()
    cfg = model.cfg
    rng = np.random.default_rng(seed % (2**63))
    total = prompt_len + decode_steps
    tokens = rng.integers(0, cfg.vocab_size, size=total, dtype=np.int64)
    cache_len = -(-total // 128) * 128
    prompt = jnp.asarray(tokens[None, :prompt_len], jnp.int32)

    # logits, cache and picks of ONE program (TOLERANCE, above)
    first, cache, routed = jax.jit(
        lambda p, t: model._prefill(p, t, cache_len))(params, prompt)
    served = [np.asarray(first[0])]
    positions = [prompt_len - 1]
    # [total, k] a routed layer: the prefill's, then room for the steps'
    picks = [np.concatenate([np.asarray(r[0]), np.zeros_like(r[0, :decode_steps])])
             for r in routed]

    # [1, KV, T, Dh] -> [LANES, ...]: every lane a copy, the idle ones too
    ks = [jnp.repeat(cache["k"][l], LANES, axis=0) for l in range(cfg.n_layers)]
    vs = [jnp.repeat(cache["v"][l], LANES, axis=0) for l in range(cfg.n_layers)]
    del cache

    step = jax.jit(model._step, donate_argnums=(1, 2))
    start = lane_lengths(prompt_len, decode_steps, cfg.sliding_window)
    live = np.array([j in start for j in range(LANES)])
    at = np.array([start.get(j, 0) for j in range(LANES)])
    n_routed = len(picks)
    touched = rows = 0
    counters_hold = True
    for i in range(decode_steps):
        pos = np.where(live, at + i, 0)
        out, ks, vs, counts, routed = step(
            params, ks, vs,
            jnp.asarray(np.where(live, tokens[pos], 0)[:, None], jnp.int32),
            jnp.asarray(pos, jnp.int32),
            lens=jnp.asarray(np.where(live, pos + 1, 0), jnp.int32))
        out = np.asarray(out)
        routed = [np.asarray(r)[:, 0] for r in routed]      # [LANES, k]
        for j in start:
            served.append(out[j])
            positions.append(int(pos[j]))
            for mine, r in zip(picks, routed):
                mine[pos[j]] = r[j]
        distinct = sum(len(np.unique(r[live])) for r in routed)
        pairs = sum(r[live].size for r in routed)
        counters_hold &= np.asarray(counts).tolist() == [distinct, pairs, n_routed]
        touched += distinct
        rows += pairs
    del ks, vs
    served = np.stack(served)
    t1 = time.monotonic()
    ref, ref_picks, ref_scores = reference.forward(
        params, cfg, tokens, positions, variant, route_as=picks)
    scale = float(ref.std())
    by_position = (np.max(np.abs(served - ref), axis=-1) / scale).tolist()
    err = max(by_position)
    margin = max(picks_margin(mine, theirs)
                 for mine, theirs in zip(picks, ref_scores))
    same = [np.all(np.sort(mine, -1) == np.sort(theirs, -1), -1)
            for mine, theirs in zip(picks, ref_picks)]
    finite = bool(np.isfinite(served).all())
    per_layer_step = touched / max(1, n_routed * decode_steps)
    busy = per_layer_step > 3 * cfg.experts_per_tok and rows > touched
    return {
        "ratio": err, "ratio_at": positions[int(np.argmax(by_position))],
        "tolerance": TOLERANCE, "picks_margin": margin,
        "picks_margin_most": PICKS_MARGIN,
        "picks_agree": float(np.mean(same)), "logit_std": scale,
        "positions": len(positions), "prompt_len": prompt_len,
        "lanes_live": int(live.sum()), "lanes": LANES,
        "experts_touched_a_layer_step": per_layer_step,
        "rows_per_touched_expert": rows / max(1, touched),
        "counters_are_the_picks": bool(counters_hold), "finite": finite,
        "served_s": t1 - t0, "reference_s": time.monotonic() - t1,
        "ok": bool(finite and err <= TOLERANCE and margin <= PICKS_MARGIN
                   and counters_hold and busy),
    }


# -- what a step must read and a prefill must compute -------------------------------

def _attention_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return 3 * d * q + 2 * d * kv        # wq, wg, wo; wk, wv


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _layers(cfg: dict) -> tuple:
    """``(dense layers, routed layers, window layers, full layers)``."""
    dense = n_dense(cfg)
    kinds = served_layer_types(cfg)
    sliding = sum(1 for k in kinds if k == SLIDING)
    return dense, len(kinds) - dense, sliding, len(kinds) - sliding


def kv_bytes_per_position_and_layer(cfg: dict) -> int:
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * BYTES


def decode_step_bytes(cfg: dict, live_positions: float, counters: dict):
    """Bytes one decode step must read: everything outside the routed
    experts once (attention, norms, dense FFNs, routers, shared experts,
    the final norm and the head; not the embedding table: one row a
    lane), of each routed layer the experts the step's live lanes picked
    (``moe_experts_touched / moe_layer_steps`` over the capture), and the
    live keys and values: all of them in a full layer, ``min(len,
    window)`` of each lane's in a window layer (``kv_positions_seen_window
    / kv_positions_live_window`` of the live positions). None where the
    program gave no such counters."""
    steps = counters.get("moe_layer_steps", 0)
    live_w = counters.get("kv_positions_live_window", 0)
    if steps <= 0 or live_w <= 0:
        return None
    d = cfg["hidden_size"]
    dense, routed, sliding, full = _layers(cfg)
    head_dims = 2 * cfg["head_dim"]
    fixed = (
        (dense + routed) * (_attention_params(cfg) + 4 * d + head_dims)
        + dense * 3 * d * cfg["intermediate_size"]
        + routed * (d * cfg["num_experts"] + cfg["num_experts"]
                    + cfg["num_shared_experts"] * expert_params(cfg))
        + d + d * cfg["vocab_size"])
    touched = counters["moe_experts_touched"] / steps      # a routed layer
    seen = counters["kv_positions_seen_window"] / live_w
    kv = kv_bytes_per_position_and_layer(cfg) * live_positions * (
        full + sliding * seen)
    return (fixed + routed * touched * expert_params(cfg)) * BYTES + kv


def prefill_flops(cfg: dict, padded_tokens: float, sequences: float,
                  counters: dict) -> float:
    """FLOPs of prefilling ``sequences`` prompts padded to ``padded_tokens``
    positions in all: per position the attention projections, the dense
    FFN or the router with 8 + 1 experts; attention over half the square
    in a full layer and over the window's band in a window layer; the
    head at each prompt's last position. Only the sum of the padded
    lengths is known: both areas are convex in a prompt's length, so they
    are taken at the mean length, their least: never counted high."""
    if sequences <= 0:
        return 0.0
    d = cfg["hidden_size"]
    dense, routed, sliding, full = _layers(cfg)
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    per_token = (
        (dense + routed) * _attention_params(cfg)
        + dense * 3 * d * cfg["intermediate_size"]
        + routed * (d * cfg["num_experts"] + expert_params(cfg) * (
            cfg["num_experts_per_tok"] + cfg["num_shared_experts"])))
    t = padded_tokens / sequences
    w = cfg["sliding_window"]
    band = t * t / 2.0 if t <= w else w * t - w * w / 2.0
    attention = 4.0 * q * sequences * (full * t * t / 2.0 + sliding * band)
    head = 2.0 * d * cfg["vocab_size"] * sequences
    return 2.0 * per_token * padded_tokens + attention + head
