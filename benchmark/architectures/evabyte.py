"""The evabyte decoder (EvaByte): everything the benchmark knows of it.

A configuration whose file says ``"architecture": "evabyte"`` is served,
compared and costed by this module (``manifest.architecture``). The parent
process loads it too and never imports jax: jax and the program are
imported inside the functions that need them.

**The served family.** ``benchmark_evabyte``: the program's own
``DecoderLM(block="evabyte")`` (``seldon_core_tpu/models/evabyte.py``) in
every method but ``init_params``, which runs the program's own draw a layer
at a time under one compiled program and casts each leaf to the served
dtype inside it.

**The cut.** The configuration's file keeps every published width, head
count, the whole vocabulary of 320, all 8 prediction heads, the window of
2048 and the chunk of 16. Depth: ``served_layers`` names the published
layers that are served (one pipeline stage's). Context: ``server.max_seq``.

**The costs.** Operations and bytes from shapes, the benchmark's own copy.
A decode step reads the weights once and, a live lane and layer, the rows
its position admits of both kinds: ``(t mod W) + 1`` ring rows and ``(W /
C) floor(t / W)`` summary rows of ``eva_row_bytes`` each, from the program's
counters where the capture has them and from the live positions where not.
What the kernel streams past them (block rounding) is not counted: a
roofline that counted it would credit the kernel for moving residue.
"""

from __future__ import annotations

# one copy among the architecture modules of how the process's serving
# batcher is found and the device's peak read (jax-free at import)
from benchmark.architectures.joyai_llm_flash import (
    _memory_peak, _serving_batcher)
from benchmark.manifest import ManifestError

FAMILY = "benchmark_evabyte"

# Agreement asked of the served path: two limits, either of which fails it
# (``compare_served`` says what each compares). Each lies between two
# readings on the chip (my chip runs, PR 44, calls 3 and 6; CHANGES.md lists
# them): the largest over seven sound seeds on a batcher of the cell's
# shape (20 lanes, 18 live, lengths 15 to 15,970; the cell's own 13 runs
# passed both besides) and the least of the controls that must fail.
#
# ``TOLERANCE``: max |served - reference| over the compared logits (ALL 8
# heads x 320 at every live lane and decode step, and the two walked
# prefills' last positions) over the reference logits' standard deviation.
# Sound 0.0171-0.0217: half the other dense cells' 0.04 (8 layers,
# residual_scale 0.25). The controls: the current window's finished chunks
# ALSO read as summaries 0.129-0.135 (they matter where a window has few
# exact rows yet: the worst position is 17-22, one finished chunk beside 18
# keys), pooling as a mean and the pooling vectors exchanged 1.1-1.2,
# 8-bit weights 0.70-0.73, the others 1.6-48. So 0.06, not the other cells'
# 0.1 (which the first control would pass by a quarter): 2.8 times the
# largest sound reading, 0.47 of the least control's that fails it.
#
# ``ROWS_TOLERANCE``: the cache itself: ring rows (rotated K, and V) and
# summary rows (k~, v~) the prefills and the steps left, against the
# reference's own at those positions and chunks: |served - reference|_F /
# |reference|_F over a layer's compared rows of one kind, the MEAN over
# layers and over K and V, the larger of the two kinds. The served rows are
# bfloat16 products of bfloat16 activations: a relative rounding of 2^-9 a
# value and what the layers before them left: sound 0.00620-0.00628 (the
# summaries; the ring 0.0033). Summaries kept in 8 bits (e4m3: 2^-4)
# 0.0274 while the logits hardly move (0.047-0.052): this limit is the one
# that control fails; the current window's chunks as summaries 0.0163-0.0170,
# 8-bit weights 0.146-0.148. So 0.013: 2.1 times the largest sound reading,
# 0.47 of the least control's.
#
# The batcher's burst against the program's own step fed the burst's
# tokens: every token the step's argmax of head 0 on every seed,
# ``burst_rows_ratio`` 0.0009-0.0022 (held by ``TOLERANCE``); a live lane
# the burst leaves out: the counters do not hold.
TOLERANCE = 0.06
ROWS_TOLERANCE = 0.013

BYTES = 2        # bfloat16 weights and cache rows
# decode steps the comparison takes where the caller names none: the
# batcher's own burst length, so that the burst compared is the executable
# the cell's window runs (ISSUE 44 asked for 4: one more burst to compile
# in every run's set-up, and not the one that is measured)
DECODE_STEPS = None


# -- the served family ---------------------------------------------------------

def __getattr__(name: str):
    # built when the program asks for it by its dotted path: defining it
    # imports the program, and with it jax
    if name != "SeededEvaByteLM":
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    from seldon_core_tpu.models.evabyte import EvaByteLM

    class SeededEvaByteLM(EvaByteLM):
        def init_params(self, seed: int = 0):
            """The program's own draw, layer by layer under one compiled
            program, each leaf cast to the served dtype inside it (the
            float32 draw of a layer is 0.8 GB and goes when its cast is
            done)."""
            import jax
            import jax.numpy as jnp

            dt = jnp.dtype(self.cfg.dtype)

            def cast(tree):
                return jax.tree_util.tree_map(lambda a: a.astype(dt), tree)

            layer = jax.jit(lambda key: cast(self.init_layer(key)))
            keys = jax.random.split(jax.random.PRNGKey(seed), self.cfg.n_layers + 1)
            return dict(
                jax.jit(lambda key: cast(self.init_top(key)))(keys[-1]),
                layers=[layer(keys[l]) for l in range(self.cfg.n_layers)])

    globals()[name] = SeededEvaByteLM
    return SeededEvaByteLM


def register() -> None:
    from seldon_core_tpu import models
    # a program without the family fails here, at once and cleanly
    from seldon_core_tpu.models import evabyte  # noqa: F401

    models.register(FAMILY, f"{__name__}.SeededEvaByteLM")


def model_kwargs(cfg: dict, seed: int) -> dict:
    """The published config's keys as ``DecoderLM(block="evabyte")`` takes
    them."""
    if cfg["attention_class"] != "eva" or cfg["num_chunks"] is not None:
        raise ManifestError(f"{cfg['name']}: EVA attention, chunks by size")
    if cfg["rope_scaling"] is not None or cfg["attention_bias"] \
            or cfg["tie_word_embeddings"] or cfg["hidden_act"] != "silu":
        raise ManifestError(
            f"{cfg['name']}: unscaled rotary, no bias, an untied head, silu")
    if not (cfg["fp32_logits"] and cfg["mixedp_attn"]) or cfg["fp32_ln"]:
        raise ManifestError(
            f"{cfg['name']}: float32 logits and attention sums, norms out "
            "in the served dtype")
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ManifestError(f"{cfg['name']}: a key head a query head")
    if len(cfg["served_layers"]) != cfg["num_hidden_layers"]:
        raise ManifestError(
            f"{cfg['name']}: served_layers names {len(cfg['served_layers'])} "
            f"layers, num_hidden_layers says {cfg['num_hidden_layers']}")
    if cfg["max_seq_length"] != cfg["max_position_embeddings"] \
            or cfg["server"]["max_seq"] > cfg["max_position_embeddings"]:
        raise ManifestError(
            f"{cfg['name']}: one context under two keys, and the served "
            "cache inside it")
    return {
        "block": "evabyte",
        "vocab_size": cfg["vocab_size"],
        "d_model": cfg["hidden_size"],
        "n_layers": cfg["num_hidden_layers"],
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "d_ff": cfg["intermediate_size"],
        "max_seq": cfg["server"]["max_seq"],
        "rope_theta": float(cfg["rope_theta"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "dtype": cfg["torch_dtype"],
        "window_size": cfg["window_size"],
        "chunk_size": cfg["chunk_size"],
        "num_pred_heads": cfg["num_pred_heads"],
        "norm_add_unit_offset": bool(cfg["norm_add_unit_offset"]),
        "fp32_skip_add": bool(cfg["fp32_skip_add"]),
        "residual_scale": cfg["weights"]["residual_scale"],
        # PRNGKey takes 32 bits; the driver's seeds are larger
        "seed": seed % (2**31 - 1),
    }


def rehearsal(cfg: dict) -> dict:
    """The sizes ``--rehearse-cpu`` puts over the configuration's: two
    layers, a window of 64 in chunks of 8, a cache of eight windows."""
    return {
        "server": dict(cfg["server"], max_seq=512),
        "hidden_size": 128, "num_attention_heads": 4, "num_key_value_heads": 4,
        "intermediate_size": 256, "num_hidden_layers": 2,
        "served_layers": [0, 1], "window_size": 64, "chunk_size": 8,
        "num_pred_heads": 3, "max_position_embeddings": 512,
        "max_seq_length": 512,
    }


# -- the served model against the plain reference ----------------------------------

IDLE_EVERY = 8      # lanes 5, 13, ... idle among the live ones


def served_slots() -> int:
    """``server.slots`` of the configuration this module serves, from its
    file: the comparison's batch is the burst's."""
    import json
    import os

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "configs", "evabyte.json")) as f:
        return json.load(f)["server"]["slots"]


def lane_lengths(lanes: int, window: int, chunk: int, cache_len: int,
                 decode_steps: int = 8) -> dict:
    """``{lane: positions it holds before its first step}`` for the live
    lanes (every eighth idle), each a length of its own. In this order, as
    far as the lanes go: ``window - 2`` (the compared steps cross the
    window's edge, and the summary a step writes is read by the next); one
    past the middle edge (a ring of 1-4 rows beside whole windows of
    summaries); one short of the edge after it (a full ring); inside the
    first window (no summary visible); past the cache's last window but one
    (the most summaries); lengths with ``len mod chunk`` of 0, 1 and
    ``chunk - 1``; shorter than a chunk; the cell's two walked prompt
    lengths where the cache holds them; the rest spread over the cache."""
    import numpy as np

    live = [j for j in range(lanes) if j % IDLE_EVERY != 5]
    n_win = cache_len // window
    top = cache_len - decode_steps - 1
    mid = max(1, n_win // 2) * window
    want = [
        window - 2,
        mid + 1,
        min(top, mid + window) - decode_steps - 1,
        window // 3 + 1,
        top - window // 5,
        2 * window + 5 * chunk,
        3 * window + 7 * chunk + 1,
        window + 9 * chunk - 1,
        chunk - 1,
        3000, 12200,
    ]
    want = [n for n in want if 1 <= n <= top]
    lens = list(dict.fromkeys(want))[:len(live)]
    spread = np.linspace(2 * chunk + 3, top - chunk, max(0, len(live) - len(lens)) + 2)
    for n in spread[1:-1].round().astype(int).tolist():
        if len(lens) < len(live):
            lens.append(int(n))
    return dict(zip(live, lens))


SAMPLE_HEAD = 8     # rows of a kind compared from a lane's first ...
SAMPLE_TAIL = 24    # ... and from its last


def _sample(n: int) -> list:
    """Indices of [0, n) whose rows are compared: the first and the last."""
    if n <= SAMPLE_HEAD + SAMPLE_TAIL:
        return list(range(n))
    return list(range(SAMPLE_HEAD)) + list(range(n - SAMPLE_TAIL, n))


def compare_served(model, params, seed: int, decode_steps: int = DECODE_STEPS,
                   variant: str = "", batcher=None) -> dict:
    """The served path in the regime the cell times, against ONE causal
    forward of the plain reference over the longest lane's tokens.

    ``batcher``: the ``ContinuousBatcher`` whose cache, lanes and
    executables are used: the one given, else the process's own that
    serves ``params`` (``borrowed`` says it was found). None is built here.
    The cache is handed back with the comparison's rows in it, which a
    lane's next occupant overwrites before any read admits them.

    Every lane holds a prefix of ONE token sequence, each at a length of
    its own (``lane_lengths``). A ring is not a prefix of a longer
    prompt's ring, so each lane is filled by a prefill of its own: the
    BATCHER'S compiled prefill in the bucket the batcher picks (the
    family's window walk past a window) and its compiled insert. A step
    that crosses a window's edge overwrites the ring's first rows, so the
    lanes are filled anew before each of three runs:

    (1) the batcher's compiled burst (``_burst_fn``, k = ``decode_steps``):
    its tokens, its counters and the rows it wrote in both kinds; (2) the
    program's own step (``model._step``) one step at a time fed the BURST'S
    tokens: the burst must have sampled each step's argmax of head 0,
    written the same rows and counted the same; (3) that step fed the
    prompt's own next tokens, whose logits (all heads) and rows the
    reference's one forward can be compared with. Also the window walk's
    own last-position logits of all heads at the cell's two walked prompt
    lengths. ``variant``: one of ``reference.VARIANTS``, a wrong reference
    (the controls), or ``"burst_idles_a_lane"``.

    Held: ``ratio`` <= ``TOLERANCE``; ``rows_ratio`` <= ``ROWS_TOLERANCE``;
    ``burst_margin`` and ``burst_rows_ratio`` <= ``TOLERANCE``; an idle
    lane's rows of every kind left as they were; the step's counters are
    the lengths' own arithmetic and the burst's sum to the steps'; each
    prefill walked ``ceil(len / window)`` windows."""
    served = serve(model, params, seed, decode_steps,
                   variant == "burst_idles_a_lane", batcher)
    return judge(model, served, params,
                 "" if variant == "burst_idles_a_lane" else variant)


def serve(model, params, seed: int, decode_steps: int = DECODE_STEPS,
          burst_idles_a_lane: bool = False, batcher=None) -> dict:
    """The served half of ``compare_served``: everything the program
    computed, as numpy, for ``judge`` to hold against a reference (one
    serving, several references: the controls)."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from seldon_core_tpu.ops.eva_attention import EVA_BLOCK

    t0 = time.monotonic()
    peak_before = _memory_peak()
    cfg = model.cfg
    W, C, L = cfg.window_size, cfg.chunk_size, cfg.n_layers
    borrowed = batcher is None
    if borrowed:
        batcher = _serving_batcher(params)
    if batcher is None:
        raise ValueError("no ContinuousBatcher of this process serves these "
                         "parameters, and none was given")
    lanes, cache_len = batcher.slots, batcher.max_seq
    decode_steps = decode_steps or batcher._k
    start = lane_lengths(lanes, W, C, cache_len, decode_steps)
    total = max(start.values()) + decode_steps
    rng = np.random.default_rng(seed % (2**63))
    tokens = rng.integers(0, cfg.vocab_size, size=total, dtype=np.int64)
    live = np.array([j in start for j in range(lanes)])
    at = np.array([start.get(j, 0) for j in range(lanes)])
    idle_lane = int(np.flatnonzero(~live)[0]) if (~live).any() else None
    kinds = ("window_k", "window_v", "summary_k", "summary_v")

    def fill(cache, registers):
        """Every live lane from a prefill of its own, through the
        batcher's compiled prefill and insert; the prefills' counters."""
        cur_tok, lane_pos, keys = registers
        counted = list(batcher._no_prefill_counts)
        walked = []
        for j, n in start.items():
            bucket = batcher._bucket(n)
            prompt = np.zeros((1, bucket), np.int32)
            prompt[0, :n] = tokens[:n]
            first, slab, key, *counts = batcher._prefill_fn(
                params, jnp.asarray(prompt), jnp.asarray([n - 1], jnp.int32),
                jnp.int32(0), jnp.float32(0.0))
            walked.append((n, bucket, np.asarray(counts[0]).tolist()
                           if counts else None, int(first[0])))
            cache, cur_tok, lane_pos, keys, *counted = batcher._insert_fn(
                cache, slab, j, jnp.int32(tokens[n]), n, key,
                cur_tok, lane_pos, keys, *counted, *counts)
            del slab
        return cache, (cur_tok, lane_pos, keys), walked

    # what is compared of the cache: a lane's ring rows and summary rows,
    # the first and the last of each, and the rows the steps write
    def lane_rows(j):
        n = int(at[j]) + decode_steps          # positions after the steps
        w0 = (n - 1) // W * W
        ring = [w0 + i for i in _sample(n - w0)]        # positions
        # the steps' own positions, whatever window they lie in
        ring += [p for p in range(int(at[j]), n) if p < w0]
        return sorted(set(ring)), _sample(n // C)       # positions, chunks

    sampled = {j: lane_rows(j) for j in start}
    gather = jax.jit(lambda cache, lane, ring_ix, sum_ix: (
        [cache[k][l][lane][:, ring_ix] for k in kinds[:2] for l in range(L)],
        [cache[k][l][lane][:, sum_ix] for k in kinds[2:] for l in range(L)]))

    def stacked(rows):
        """A gather's 2 L arrays [H, n, Dh] as float32 [2 (K, V), L, H, n, Dh]."""
        return np.stack([np.asarray(r, np.float32) for r in rows]).reshape(
            2, L, *rows[0].shape)

    def rows_of(cache):
        """``{lane: (positions held, ring rows [2, L, H, n, Dh], chunks,
        summary rows [2, L, H, m, Dh])}`` of the sampled positions and
        chunks."""
        out = {}
        for j, (positions, chunks) in sampled.items():
            # a ring row holds the LAST position written there
            n = int(at[j]) + decode_steps
            w0 = (n - 1) // W * W
            held = [p for p in positions if p >= w0 or p + W >= n]
            ring, summ = gather(
                cache, j, jnp.asarray([p % W for p in held], jnp.int32),
                jnp.asarray(chunks, jnp.int32))
            out[j] = (held, stacked(ring), chunks, stacked(summ))
        return out

    def idle_rows(cache):
        if idle_lane is None:
            return None
        return [np.asarray(cache[k][l][idle_lane, :, :C])
                for k in kinds for l in range(L)]

    cache = batcher._cache
    batcher._cache = None       # donated below; handed back at the end
    registers = (jnp.zeros((lanes,), jnp.int32), jnp.zeros((lanes,), jnp.int32),
                 jnp.zeros((lanes, 2), jnp.uint32))
    step = jax.jit(model._step, donate_argnums=(1,))

    def steps(cache, feed):
        """``decode_steps`` steps over all lanes, step i fed ``feed(i)``
        [lanes]: each step's logits [lanes, P, V] and counters."""
        outs = []
        for i in range(decode_steps):
            pos = np.where(live, at + i, 0)
            out, cache, counts = step(
                params, cache,
                jnp.asarray(np.where(live, feed(i), 0)[:, None], jnp.int32),
                jnp.asarray(pos, jnp.int32),
                lens=jnp.asarray(np.where(live, pos + 1, 0), jnp.int32))
            outs.append((np.asarray(out), np.asarray(counts)))
        return outs, cache

    try:
        # (1) the batcher's burst
        cache, registers, walked = fill(cache, registers)
        cur_tok, lane_pos, keys = registers
        inserted = bool(
            np.array_equal(np.asarray(lane_pos)[live], at[live])
            and np.array_equal(np.asarray(cur_tok)[live], tokens[at[live]]))
        idle_before = idle_rows(cache)
        active = live.copy()
        if burst_idles_a_lane:
            active[np.flatnonzero(live)[0]] = False
        toks, _cur, _pos, cache, _k, burst_counts = batcher._burst_fn(
            params, cache, cur_tok, lane_pos, jnp.asarray(active),
            jnp.zeros((lanes,), jnp.float32), keys, decode_steps,
            None if batcher._ragged_read else cache_len)
        toks = np.asarray(toks)                   # [steps + 1, lanes]
        burst_counts = np.asarray(burst_counts)
        burst_rows = rows_of(cache)
        idle_after = idle_rows(cache)
        idle_untouched = idle_lane is None or all(
            np.array_equal(a, b) for a, b in zip(idle_before, idle_after))

        # (2) the step, fed the burst's tokens
        cache, registers, _w = fill(cache, registers)
        outs, cache = steps(cache, lambda i: toks[i])
        step_rows = rows_of(cache)
        burst_margin, agree = 0.0, []
        for i, (out, _c) in enumerate(outs):
            mine = out[active][:, 0]              # head 0: what is sampled
            theirs = mine[np.arange(len(mine)), toks[i + 1][active]]
            agree.append(mine.argmax(-1) == toks[i + 1][active])
            burst_margin = max(burst_margin, float(
                (mine.max(-1) - theirs).max() / mine.std()))

        def relative(a, b):
            return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))

        burst_rows_ratio = max(
            max(relative(burst_rows[j][1], step_rows[j][1]),
                relative(burst_rows[j][3], step_rows[j][3])
                if step_rows[j][3].size else 0.0)
            for j in start if active[j])
        summed = np.sum([c for _o, c in outs], axis=0)
        burst_counters_hold = bool(np.array_equal(burst_counts, summed))
        del burst_rows

        # (3) the step, fed the prompt's own tokens: what the reference follows
        cache, registers, _w = fill(cache, registers)
        outs, cache = steps(cache, lambda i: tokens[at + i])
        step_rows = rows_of(cache)
    finally:
        batcher._cache = cache      # handed back, the comparison's rows in it
    del cache

    served, positions = [], []
    counters_hold = True
    for i, (out, counts) in enumerate(outs):
        t = at[live] + i
        for j in start:
            served.append(out[j])
            positions.append(int(at[j] + i))
        n_ring, n_sum = t % W + 1, t // W * (W // C)
        n_read = sum(-(-n // EVA_BLOCK) * EVA_BLOCK for n in (n_ring, n_sum))
        counters_hold &= counts.tolist() == [L * int(v) for v in (
            n_ring.sum(), n_sum.sum(), n_read.sum(),
            (t + 1).sum(), ((t + 1) % C == 0).sum(), live.sum())]

    # the window walk's own logits, every head, at the cell's walked lengths
    walks = []
    prefill = jax.jit(lambda p, tk, last: model._prefill(
        p, tk, tk.shape[1], last)[0])
    for n in (3000, 12200):
        if n + 1 <= total and n > W:
            prompt = np.zeros((1, cache_len), np.int32)
            prompt[0, :n] = tokens[:n]
            walks.append((n - 1, np.asarray(prefill(
                params, jnp.asarray(prompt), jnp.asarray([n - 1], jnp.int32))[0])))
    walked_right = all(
        counts is None or counts == [-(-n // W), max(1, bucket // W)]
        for n, bucket, counts, _first in walked)
    return dict(
        tokens=tokens, positions=positions, served=np.stack(served),
        step_rows=step_rows, walks=walks, walked=walked,
        walked_right=bool(walked_right), start=start, lanes=lanes,
        cache_len=cache_len, lanes_live=int(live.sum()), borrowed=borrowed,
        decode_steps=decode_steps, counters_hold=bool(counters_hold),
        agree=float(np.mean(agree)), burst_margin=burst_margin,
        burst_rows_ratio=burst_rows_ratio,
        burst_counters_hold=burst_counters_hold, inserted=inserted,
        idle_untouched=bool(idle_untouched), served_s=time.monotonic() - t0,
        memory_peak_bytes=[peak_before, _memory_peak()])


def judge(model, served: dict, params, variant: str = "") -> dict:
    """The reference's half: ONE causal forward of the plain reference
    (``variant``: a wrong one) over the tokens ``serve`` served, and the
    limits."""
    import time

    import numpy as np

    from benchmark.reference import evabyte as reference

    t1 = time.monotonic()
    cfg = model.cfg
    L = cfg.n_layers
    s = served
    positions = s["positions"] + [p for p, _l in s["walks"]]
    rows_at = sorted({p for held, *_ in s["step_rows"].values() for p in held})
    ref, ref_k, ref_v, ref_sk, ref_sv = reference.forward(
        params, cfg, s["tokens"], positions, variant, rows_at=rows_at)
    index = {p: i for i, p in enumerate(rows_at)}
    scale = float(ref.std())
    mine = np.concatenate([s["served"]] + [l[None] for _p, l in s["walks"]])
    by_position = (np.abs(mine - ref).reshape(len(positions), -1).max(-1)
                   / scale)
    n_steps = len(s["positions"])
    by_head = (np.abs(mine - ref).max(axis=(0, 2)) / scale).tolist()
    err = float(by_position.max())

    def relative(a, b):
        return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))

    # [kind (k, v), layer]: the lanes' compared rows of one kind and layer
    # together
    ring_err, sum_err = np.zeros((2, L)), np.zeros((2, L))
    for which, (theirs_ring, theirs_sum) in enumerate(
            ((ref_k, ref_sk), (ref_v, ref_sv))):
        for l in range(L):
            a, b, c, d = [], [], [], []
            for held, ring, chunks, summ in s["step_rows"].values():
                ix = [index[p] for p in held]
                a.append(ring[which, l])                    # [H, n, Dh]
                b.append(theirs_ring[l][ix].transpose(1, 0, 2))
                c.append(summ[which, l])
                d.append(theirs_sum[l][chunks].transpose(1, 0, 2))
            ring_err[which, l] = relative(np.concatenate(a, 1),
                                          np.concatenate(b, 1))
            sum_err[which, l] = relative(np.concatenate(c, 1),
                                         np.concatenate(d, 1))
    rows_ratio = float(max(ring_err.mean(), sum_err.mean()))
    finite = bool(np.isfinite(mine).all())
    burst_holds = (s["inserted"] and s["idle_untouched"]
                   and s["burst_counters_hold"]
                   and s["burst_margin"] <= TOLERANCE
                   and s["burst_rows_ratio"] <= TOLERANCE)
    return {
        "ratio": err, "ratio_at": positions[int(np.argmax(by_position))],
        "ratio_steps": float(by_position[:n_steps].max()),
        "ratio_window_walk": float(by_position[n_steps:].max())
        if len(positions) > n_steps else 0.0,
        "ratio_by_head": by_head,
        "tolerance": TOLERANCE, "rows_ratio": rows_ratio,
        "rows_tolerance": ROWS_TOLERANCE,
        "rows_ratio_ring": float(ring_err.mean()),
        "rows_ratio_summaries": float(sum_err.mean()),
        "rows_ratio_worst_layer": float(max(ring_err.max(), sum_err.max())),
        "logit_std": scale, "positions": len(positions),
        "longest": int(max(s["start"].values())),
        "lengths": sorted(s["start"].values()),
        "lanes_live": s["lanes_live"], "lanes": s["lanes"],
        "cache_len": s["cache_len"], "borrowed": s["borrowed"],
        "windows_walked": [w[2] for w in s["walked"]],
        "walked_right": s["walked_right"],
        "counters_are_the_lengths": s["counters_hold"], "finite": finite,
        "burst_tokens_agree": s["agree"],
        "burst_margin": s["burst_margin"],
        "burst_rows_ratio": s["burst_rows_ratio"],
        "burst_counters_hold": s["burst_counters_hold"],
        "inserted": s["inserted"], "idle_untouched": s["idle_untouched"],
        "served_s": s["served_s"], "reference_s": time.monotonic() - t1,
        # the process's peak so far: before the comparison, after its
        # served half, after the reference
        "memory_peak_bytes": s["memory_peak_bytes"] + [_memory_peak()],
        "ok": bool(finite and err <= TOLERANCE and rows_ratio <= ROWS_TOLERANCE
                   and s["counters_hold"] and s["walked_right"]
                   and burst_holds),
    }


# -- what a step must read and a prefill must compute -------------------------------

def eva_row_bytes(cfg: dict) -> int:
    """K and V of one row of either kind in ONE layer: 2 x 32 x 128 x 2."""
    head = cfg["hidden_size"] // cfg["num_attention_heads"]
    return 2 * cfg["num_attention_heads"] * head * BYTES


def layer_params(cfg: dict) -> int:
    """One layer: the four attention matrices, the SwiGLU's three, two
    norms and the two pooling vectors (202.39 M at the published widths)."""
    d = cfg["hidden_size"]
    return 4 * d * d + 3 * d * cfg["intermediate_size"] + 2 * d + 2 * d


def step_weight_bytes(cfg: dict) -> int:
    """What a step reads once: every layer, the final norm and the whole
    8-head output matrix (not the embedding table: one row a lane)."""
    d = cfg["hidden_size"]
    return (cfg["num_hidden_layers"] * layer_params(cfg) + d
            + cfg["num_pred_heads"] * cfg["vocab_size"] * d) * BYTES


def rows_at(cfg: dict, positions: float) -> float:
    """Ring rows plus summary rows a lane that holds ``positions``
    positions reads."""
    w, c = cfg["window_size"], cfg["chunk_size"]
    t = max(0.0, positions - 1)
    return t % w + 1 + (t // w) * (w // c)


def eva_step_rows(cfg: dict, counters: dict):
    """Live rows of both kinds a decode step read, a layer (the program's
    ``eva_window_rows_live + eva_summary_rows_live`` over the capture's
    steps and layers); None without the counters."""
    steps = counters.get("steps", 0)
    if steps <= 0 or counters.get("eva_lane_steps", 0) <= 0:
        return None
    rows = (counters.get("eva_window_rows_live", 0)
            + counters.get("eva_summary_rows_live", 0))
    return rows / steps / cfg["num_hidden_layers"]


def decode_step_bytes(cfg: dict, live_positions: float, counters: dict):
    """Bytes one decode step must move: the weights once and the live rows
    of both kinds in every layer, from the program's counters; where the
    capture has none, from the live positions as if one lane held them
    (an upper bound on the summaries' saving: a reader that can do better
    has the counters)."""
    rows = eva_step_rows(cfg, counters)
    if rows is None:
        rows = rows_at(cfg, live_positions) if live_positions > 0 else 0.0
    return (step_weight_bytes(cfg)
            + cfg["num_hidden_layers"] * rows * eva_row_bytes(cfg))


def prefill_attention_flops(cfg: dict, tokens: float, sequences: float) -> float:
    """EVA attention's useful FLOPs over ``sequences`` prompts of ``tokens``
    positions in all: the local causal half of each window's square and the
    remote rectangle of the earlier windows' summaries, every layer."""
    if sequences <= 0:
        return 0.0
    w, c = cfg["window_size"], cfg["chunk_size"]
    d = cfg["hidden_size"]
    t = tokens / sequences
    full, rest = int(t // w), t % w
    local = full * w * w / 2.0 + rest * rest / 2.0
    remote = sum(i * (w // c) * w for i in range(full)) + full * (w // c) * rest
    return 4.0 * d * (local + remote) * sequences * cfg["num_hidden_layers"]


def prefill_flops(cfg: dict, padded_tokens: float, sequences: float,
                  counters: dict) -> float:
    """FLOPs of prefilling ``sequences`` prompts of ``padded_tokens``
    positions in all: a layer's projections and FFN a position, the
    pooling, EVA attention (``prefill_attention_flops``) and the 8 heads at
    each prompt's last position. Where the program counted the windows it
    walked, the positions are those windows' (a walked window is computed
    whole); else the padded ones."""
    if sequences <= 0:
        return 0.0
    walked = counters.get("eva_prefill_windows_walked", 0)
    bucket = counters.get("eva_prefill_windows_bucket", 0)
    tokens = padded_tokens
    if walked > 0 and bucket > 0:
        tokens = padded_tokens * walked / bucket
    d = cfg["hidden_size"]
    per_token = cfg["num_hidden_layers"] * (
        4 * d * d + 3 * d * cfg["intermediate_size"] + 2 * d)
    head = 2.0 * d * cfg["vocab_size"] * cfg["num_pred_heads"] * sequences
    return (2.0 * per_token * tokens
            + prefill_attention_flops(cfg, tokens, sequences) + head)
