"""The padded length a prompt prefills at (``DecoderFamily.prefill_lengths``,
``prefill_rows_max``; ``ContinuousBatcher._bucket``, ``_rows_ok``, ``warm``):
the batcher's own buckets, then every ``PREFILL_STEP`` below ``max_seq``, one
prompt a call at a length the step added; and the two counters that say what
padding costs (``prefill_tokens``, ``prefill_prompt_tokens``). Every family
that takes the default rule, at a small size in float32 on the CPU; the step
is read from the class, so it is 64 here."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models import family as families
from seldon_core_tpu.models.family import DecoderFamily
from seldon_core_tpu.models.llm import DecoderLM
from seldon_core_tpu.serving.continuous import ContinuousBatcher
from test_decoder_family import SMALL

STEP, MAX_SEQ = 64, 256
# the last bucket ends below a multiple of the step, as 1792 below 2048
BUCKETS = (16, 48)
LENGTHS = (16, 48, 64, 128, 192)
# evabyte keeps a rule of its own: its prompts walk their windows
DEFAULT_RULE = sorted(
    block for block in families.FAMILIES
    if families.family_class(block).prefill_lengths
    is DecoderFamily.prefill_lengths)


def _prompt(seed, n, vocab=60):
    return [int(t) for t in np.random.default_rng(seed).integers(0, vocab, size=n)]


def _in_one_wave(b, prompts, new):
    """All prompts queued before the scheduler's first poll (``submit``
    starts it), so that one turn admits them together."""
    start, b.start = b.start, lambda: None
    try:
        futures = [b.submit(p, max_new_tokens=new) for p in prompts]
    finally:
        b.start = start
    b.start()
    return [f.result(timeout=300) for f in futures]


def _lane(model, cache, lane, n):
    """What lane ``lane`` of ``cache`` holds of a prompt of ``n`` positions:
    the arrays with a row a position cut to them, the others whole."""
    by_position = {id(a) for a in model.position_layers(cache)}
    return [np.asarray(a[lane, ..., :n, :] if id(a) in by_position else a[lane])
            for a in jax.tree_util.tree_leaves(cache)]


def test_every_family_but_one_takes_the_default_rule():
    assert DEFAULT_RULE == sorted(set(families.FAMILIES) - {"evabyte"})
    assert DecoderFamily.PREFILL_STEP == 512
    model = DecoderLM(**SMALL["llama"])
    assert model.prefill_lengths((32, 128, 512, 1024, 1792), 4096) == (
        32, 128, 512, 1024, 1792, 2048, 2560, 3072, 3584)
    # nothing below the step's first multiple past the buckets: the dense
    # cells (``max_seq`` 2048) and every small test run the buckets alone
    assert model.prefill_lengths((32, 128, 512, 1024, 1792), 2048) == (
        32, 128, 512, 1024, 1792)
    assert model.prefill_lengths((8, 16, 32), 64) == (8, 16, 32)
    assert model.prefill_lengths((512,), 2048) == (512, 1024, 1536)
    assert model.prefill_lengths((), 1024) == (512,)
    assert [model.prefill_rows_max(2048, added=a) for a in (False, True)] == [8, 1]


@pytest.mark.parametrize("block", DEFAULT_RULE)
def test_a_prompt_past_the_buckets_prefills_at_its_length_rounded_up(
        block, monkeypatch):
    monkeypatch.setattr(DecoderFamily, "PREFILL_STEP", STEP)
    model = DecoderLM(block=block, **{
        **SMALL[block], "max_seq": MAX_SEQ, "dtype": "float32"})
    params = model.init_params(1)
    assert model.prefill_lengths(BUCKETS, MAX_SEQ) == LENGTHS
    b = ContinuousBatcher(model, params, slots=8, max_seq=MAX_SEQ,
                          prefill_buckets=BUCKETS, steps_per_poll=4)
    try:
        assert b.prefill_buckets == LENGTHS
        assert [b._bucket(n) for n in (1, 16, 17, 48, 49, 64, 65, 128, 129,
                                       192, 193, 256)] == [
            16, 16, 48, 48, 64, 64, 128, 128, 192, 192, 256, 256]
        # a configured bucket and ``max_seq`` batch 4 and 8 as they did; a
        # length the step added takes one prompt a call, unless the family
        # says otherwise (lfm2 counts rows: 16,384 of them are 8 prompts of
        # anything this small)
        for bucket in (*BUCKETS, MAX_SEQ):
            assert b._rows_ok(4, bucket) and b._chunk8_ok(bucket), bucket
        own_rows = block == "lfm2_moe"
        for bucket in (64, 128, 192):
            assert b._rows_ok(1, bucket)
            assert b._rows_ok(4, bucket) == own_rows, bucket
            assert b._chunk8_ok(bucket) == own_rows, bucket

        # warm() compiles the lengths the declared prompts pad to, the
        # added one for one prompt a call, and nothing else
        b.warm(prompt_lens=(40, 70), max_new_tokens=8, batch_sizes=(1, 4, 8))
        assert b._prefill_fn._cache_size() == 2             # 48, 128
        assert b._prefill_many_fn._cache_size() == (4 if own_rows else 2)

        # four prompts of an added length and four of a configured bucket,
        # in one turn
        long, short = 70, 40
        out = _in_one_wave(b, [_prompt(i, n) for i, n in enumerate(
            (long,) * 4 + (short,) * 4)], 5)
        assert [len(o) for o in out] == [long + 5] * 4 + [short + 5] * 4
        assert b.stats["prefill_steps"] == (1 if own_rows else 4) + 1
        assert b.stats["prefill_tokens"] == 4 * 128 + 4 * 48
        assert b.stats["prefill_prompt_tokens"] == 4 * long + 4 * short
        # no executable but the warmed ones
        assert b._prefill_fn._cache_size() == 2
        assert b._prefill_many_fn._cache_size() == (4 if own_rows else 2)
    finally:
        b.close()

    # the same prompt at its stepped length and padded to ``max_seq``, each
    # through the batcher's compiled prefill and insert into a lane of its
    # own: the same first token, the same rows at the prompt's positions
    # (and the same state, where a family keeps one)
    n = 70
    tokens = _prompt(99, n)
    assert b._bucket(n) == 128
    firsts = []
    for lane, bucket in enumerate((128, MAX_SEQ)):
        prompt = np.zeros((1, bucket), np.int32)
        prompt[0, :n] = tokens
        first, cache_one, lane_key, *counts = b._prefill_fn(
            params, jnp.asarray(prompt), jnp.asarray([n - 1], jnp.int32),
            jnp.int32(0), jnp.float32(0.0))
        b._cache, b._cur_tok, b._pos, b._keys, *_ = b._insert_fn(
            b._cache, cache_one, lane, first[0], b._lane_start(n), lane_key,
            b._cur_tok, b._pos, b._keys, *b._prefill_counts, *counts)
        firsts.append(int(first[0]))
    assert firsts[0] == firsts[1]
    for stepped, padded in zip(_lane(model, b._cache, 0, b._lane_start(n)),
                               _lane(model, b._cache, 1, b._lane_start(n))):
        np.testing.assert_allclose(stepped, padded, rtol=1e-5, atol=1e-5)


def test_prompt_tokens_and_padded_rows_add_up_over_every_admission():
    """``prefill_tokens`` counts the rows a prefill computed and
    ``prefill_prompt_tokens`` those of them that held a token of a prompt:
    a single and a batched admission, a prefix hit's suffix, a chunked
    prompt's chunks (its last slid back inside the slab) and an export."""
    model = DecoderLM(vocab_size=256, d_model=32, n_layers=2, n_heads=4,
                      n_kv_heads=2, d_ff=64, max_seq=64, dtype="float32")
    params = model.init_params(0)
    rng = np.random.RandomState(5)

    def counted(b):
        return b.stats["prefill_tokens"], b.stats["prefill_prompt_tokens"]

    # one alone (5 in 8), then four of one bucket in one batched call
    b = ContinuousBatcher(model, params, slots=4, max_seq=64,
                          prefill_buckets=(8, 16, 32))
    try:
        b.generate(rng.randint(0, 256, 5).tolist(), max_new_tokens=2)
        assert counted(b) == (8, 5)
        _in_one_wave(b, [rng.randint(0, 256, n).tolist()
                         for n in (9, 12, 16, 10)], 2)
        assert b.stats["prefill_steps"] == 2
        assert counted(b) == (8 + 4 * 16, 5 + 9 + 12 + 16 + 10)
    finally:
        b.close()

    # a prefix hit prefills the suffix alone, in the suffix's bucket
    shared = rng.randint(0, 256, 14).tolist()
    first, second = shared + [1, 2, 3, 4], shared + [5, 6, 7]
    b = ContinuousBatcher(model, params, slots=2, max_seq=64,
                          prefill_buckets=(8, 16, 32),
                          prefix_cache_hbm_bytes=1 << 26,
                          prefix_cache_min_tokens=4)
    try:
        b.generate(first, max_new_tokens=2)
        assert counted(b) == (32, 18)
        b.generate(second, max_new_tokens=2)
        hit = b.stats["prefix_tokens_saved"]
        assert b.stats["prefix_hits"] == 1 and 0 < hit <= 14
        assert counted(b) == (32 + b._bucket(17 - hit), 18 + 17 - hit)
    finally:
        b.close()

    # 27 tokens in chunks of 8 of a 32 slab: 0-7, 8-15, 16-23, then the
    # last slid back to 24-31, of which 24-26 hold a token
    b = ContinuousBatcher(model, params, slots=2, max_seq=64,
                          prefill_buckets=(8, 16, 32), prefill_chunk=8,
                          attn_bucket=16)
    try:
        b.generate(rng.randint(0, 256, 27).tolist(), max_new_tokens=2)
        assert b.stats["prefill_chunks"] == 4
        assert counted(b) == (32, 27)
        # 20 in 32: the last chunk starts at 16 and holds 4
        b.generate(rng.randint(0, 256, 20).tolist(), max_new_tokens=2)
        assert counted(b) == (32 + 24, 27 + 20)
        # an export runs the same chunks, or the whole bucket where one
        # chunk holds it
        b.export_prefill(rng.randint(0, 256, 20).tolist(), max_new_tokens=2)
        assert counted(b) == (32 + 24 + 24, 27 + 20 + 20)
        b.export_prefill(rng.randint(0, 256, 6).tolist(), max_new_tokens=2)
        assert counted(b) == (32 + 24 + 24 + 8, 27 + 20 + 20 + 6)
    finally:
        b.close()
