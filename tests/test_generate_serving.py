"""Continuous-batching generate serving.

Tiers (SURVEY §4): scheduler unit tests against a tiny DecoderLM,
equivalence with the model's own generate(), mesh-sharded cache on the
8-device CPU mesh, and the engine-served e2e path.
"""

import asyncio
import json

import numpy as np
import pytest

from seldon_core_tpu.models.llm import DecoderLM
from seldon_core_tpu.serving.continuous import ContinuousBatcher

CFG = dict(
    vocab_size=256,
    d_model=32,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,
    d_ff=64,
    max_seq=64,
    dtype="float32",
)


@pytest.fixture(scope="module")
def model_and_params():
    model = DecoderLM(**CFG)
    return model, model.init_params(0)


@pytest.fixture()
def batcher(model_and_params):
    model, params = model_and_params
    b = ContinuousBatcher(
        model, params, slots=4, max_seq=64, prefill_buckets=(8, 16, 32)
    )
    yield b
    b.close()


def test_decode_step_ragged_matches_scalar(model_and_params):
    """Ragged decode at uniform positions == the scalar-pos decode step."""
    import jax.numpy as jnp

    model, params = model_and_params
    B, Tp = 2, 5
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, 256, (B, Tp)).astype(np.int32)
    _, cache_a = model.prefill(params, jnp.asarray(prompt), 16)
    cache_b = {"k": cache_a["k"].copy(), "v": cache_a["v"].copy()}
    tok = jnp.asarray(prompt[:, -1:])

    logits_a, _ = model.decode_step(params, cache_a, tok, Tp)
    logits_b, _ = model.decode_step_ragged(
        params, cache_b, tok, jnp.full((B,), Tp, jnp.int32)
    )
    np.testing.assert_allclose(np.asarray(logits_a), np.asarray(logits_b), atol=1e-4)


def test_greedy_matches_model_generate(model_and_params, batcher):
    """The scheduler's greedy output == DecoderLM.generate (same model,
    radically different execution: bucketed prefill + ragged decode)."""
    import jax.numpy as jnp

    model, params = model_and_params
    prompt = [3, 17, 42, 99, 7]
    n_new = 10
    expected = np.asarray(
        model.generate(params, jnp.asarray([prompt], jnp.int32), n_new)
    )[0].tolist()
    got = batcher.generate(prompt, max_new_tokens=n_new)
    assert got == expected


def test_concurrent_requests_all_correct(model_and_params, batcher):
    """More requests than slots, different lengths — every result equals
    the sequential single-request reference output."""
    import jax.numpy as jnp

    model, params = model_and_params
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, 256, n).tolist() for n in (3, 7, 12, 5, 9, 4)]
    n_new = 6
    expected = [
        np.asarray(model.generate(params, jnp.asarray([p], jnp.int32), n_new))[0].tolist()
        for p in prompts
    ]
    futures = [batcher.submit(p, max_new_tokens=n_new) for p in prompts]
    results = [f.result(timeout=120) for f in futures]
    assert results == expected
    assert batcher.stats["finished"] == len(prompts)


def test_mid_flight_admission(model_and_params):
    """A request submitted while another decodes joins the running batch
    (admitted before the first finishes) and both come out right."""
    import time

    import jax.numpy as jnp

    model, params = model_and_params
    b = ContinuousBatcher(
        model, params, slots=2, max_seq=64, prefill_buckets=(8,), steps_per_poll=2
    )
    try:
        long_f = b.submit([1, 2, 3], max_new_tokens=40)
        time.sleep(0.2)  # first request should be mid-decode now
        short_f = b.submit([9, 8, 7], max_new_tokens=4)
        short = short_f.result(timeout=120)
        long_ = long_f.result(timeout=120)
        exp_short = np.asarray(
            model.generate(params, jnp.asarray([[9, 8, 7]], jnp.int32), 4)
        )[0].tolist()
        exp_long = np.asarray(
            model.generate(params, jnp.asarray([[1, 2, 3]], jnp.int32), 40)
        )[0].tolist()
        assert short == exp_short
        assert long_ == exp_long
        # both were in flight together: the short one was admitted while
        # the long one still had steps to go
        assert b.stats["admitted"] == 2
    finally:
        b.close()


def test_eos_stops_early(model_and_params, batcher):
    model, params = model_and_params
    prompt = [3, 17, 42]
    full = batcher.generate(prompt, max_new_tokens=20)
    gen = full[len(prompt):]
    eos = gen[3]  # pretend the 4th generated token is EOS
    stopped = batcher.generate(prompt, max_new_tokens=20, eos_id=eos)
    assert stopped == full[: len(prompt) + 4]


def test_temperature_sampling_varies(model_and_params, batcher):
    outs = {
        tuple(batcher.generate([5, 5, 5], max_new_tokens=8, temperature=1.5, seed=s))
        for s in range(4)
    }
    assert len(outs) > 1  # not all identical under sampling


def test_seed_reproducible_across_cotenants(model_and_params):
    """Same request + seed gives the same tokens regardless of what else
    shares the decode batch (per-lane PRNG streams)."""
    model, params = model_and_params
    b = ContinuousBatcher(model, params, slots=4, max_seq=64, prefill_buckets=(8,))
    try:
        alone = b.generate([7, 7, 7], max_new_tokens=6, temperature=1.0, seed=5)
        fs = [
            b.submit([i + 1, i + 2], max_new_tokens=12, temperature=0.9, seed=i)
            for i in range(3)
        ]
        crowded = b.generate([7, 7, 7], max_new_tokens=6, temperature=1.0, seed=5)
        for f in fs:
            f.result(timeout=120)
        assert alone == crowded
    finally:
        b.close()


def _churn(model, params, **kw):
    """More requests than slots, staggered submission, mixed lengths: the
    results in submission order."""
    import time

    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, 256, n).tolist() for n in (3, 9, 5, 14, 4, 6, 11, 2)]
    b = ContinuousBatcher(
        model, params, slots=3, max_seq=64, prefill_buckets=(8, 16),
        steps_per_poll=2, **kw,
    )
    try:
        futures = []
        for i, (p, m) in enumerate(zip(prompts, (7, 3, 12, 5, 9, 2, 6, 10))):
            futures.append(b.submit(p, max_new_tokens=m))
            if i % 3 == 2:
                time.sleep(0.05)  # stagger admissions mid-decode
        results = [f.result(timeout=120) for f in futures]
        assert b.stats["finished"] == len(prompts)
    finally:
        b.close()
    return results


@pytest.fixture(scope="module")
def churn_synchronous(model_and_params):
    return _churn(*model_and_params, pipeline_depth=1)


@pytest.mark.parametrize("depth", [None, 2, 3, 4])
def test_pipeline_depths_equivalent(model_and_params, churn_synchronous, depth):
    """Software-pipelined bursts (the default depth, and 2 to 4) must emit
    exactly the tokens of the synchronous scheduler (depth=1) under heavy
    churn: depth decides when the host LOOKS, not what the device computes."""
    kw = {} if depth is None else {"pipeline_depth": depth}
    assert _churn(*model_and_params, **kw) == churn_synchronous


class _HeldTokens:
    """A burst's token array as the loop sees a device array: ``is_ready``
    says what the test wants, the values are the real ones."""

    def __init__(self, array, ready):
        self._array, self._ready = array, ready

    def is_ready(self):
        return self._ready

    def copy_to_host_async(self):
        pass

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self._array, dtype=dtype)


def _loaded_loop(model, params, ready, **kw):
    """Six requests over two lanes with every burst's tokens held behind
    ``_HeldTokens(ready)``: (stats, the flight recorder's polls, results,
    the counters a capture would read)."""
    b = ContinuousBatcher(
        model, params, slots=2, max_seq=64, prefill_buckets=(8,),
        steps_per_poll=2, **kw,
    )
    burst = b._burst_fn

    def held(*args):
        toks, *rest = burst(*args)
        return (_HeldTokens(toks, ready), *rest)

    b._burst_fn = held
    try:
        futures = [b.submit([3 + i, 17, 42], max_new_tokens=16) for i in range(6)]
        results = [f.result(timeout=120) for f in futures]
    finally:
        b.close()
    polls = [e for e in b.flight.snapshot() if e.get("type") == "poll"]
    return dict(b.stats), polls, results, b.capture_counters()["counters"]


def test_default_depth_holds_two_bursts(model_and_params):
    """At the default depth a loaded loop holds one burst behind the running
    one and no more: with tokens that are never ready before the blocking
    read, ``pending_bursts`` reaches 2 and never 3, at depth 3 it reaches 3,
    and the tokens are the same."""
    model, params = model_and_params
    _, polls, results, _ = _loaded_loop(model, params, ready=False)
    assert max(e["pending_bursts"] for e in polls) == 2
    _, polls3, results3, _ = _loaded_loop(
        model, params, ready=False, pipeline_depth=3)
    assert max(e["pending_bursts"] for e in polls3) == 3
    assert results == results3


def test_default_depth_is_two():
    import inspect

    from seldon_core_tpu.servers.generateserver import GenerateServer

    for cls in (ContinuousBatcher, GenerateServer):
        assert inspect.signature(cls.__init__).parameters[
            "pipeline_depth"].default == 2, cls


@pytest.mark.parametrize("ready", [False, True], ids=["never_ready", "always_ready"])
def test_bursts_read_late(model_and_params, ready):
    """``bursts_read_late`` counts the bursts the device had finished before
    the host came to read them: none where the tokens are never ready before
    the read, every burst where they always are; ``capture.json`` gets it
    with every numeric entry of ``stats``."""
    stats, _, _, counters = _loaded_loop(*model_and_params, ready=ready)
    assert stats["bursts"] > 8
    assert stats["bursts_read_late"] == (stats["bursts"] if ready else 0)
    assert counters["bursts_read_late"] == stats["bursts_read_late"]
    assert counters["bursts"] == stats["bursts"]


def test_eos_equivalent_across_depths(model_and_params):
    """EOS mid-pipeline: the lane keeps decoding until the host notices —
    the OUTPUT must still stop exactly at eos."""
    model, params = model_and_params
    outs = {}
    for depth in (1, 3):
        b = ContinuousBatcher(
            model, params, slots=2, max_seq=64, prefill_buckets=(8,),
            steps_per_poll=2, pipeline_depth=depth,
        )
        try:
            prompt = [3, 17, 42]
            full = b.generate(prompt, max_new_tokens=20)
            eos = full[len(prompt) + 3]
            outs[depth] = b.generate(prompt, max_new_tokens=20, eos_id=eos)
        finally:
            b.close()
    assert outs[1] == outs[3]


def test_speculative_exact_with_bad_draft(model_and_params):
    """Greedy-exact speculation: even a DRAFT THAT SHARES NOTHING with the
    target (different depth/width, different seed — near-zero acceptance)
    must produce exactly the target's own greedy output, for every request
    in a churning batch. The draft only sets the compute cost."""
    model, params = model_and_params
    draft = DecoderLM(
        vocab_size=CFG["vocab_size"], d_model=16, n_layers=1, n_heads=2,
        n_kv_heads=1, d_ff=32, max_seq=64, dtype="float32",
    )
    dparams = draft.init_params(99)
    import jax.numpy as jnp

    b = ContinuousBatcher(
        model, params, slots=3, max_seq=64, prefill_buckets=(8, 16),
        steps_per_poll=2, pipeline_depth=3,
        draft_model=draft, draft_params=dparams, speculate_tokens=3,
    )
    try:
        rng = np.random.RandomState(3)
        prompts = [rng.randint(0, 256, n).tolist() for n in (3, 9, 5, 12, 4)]
        futures = [b.submit(p, max_new_tokens=m) for p, m in zip(prompts, (7, 4, 10, 3, 8))]
        results = [f.result(timeout=120) for f in futures]
        for p, m, got in zip(prompts, (7, 4, 10, 3, 8), results):
            exp = np.asarray(
                model.generate(params, jnp.asarray([p], jnp.int32), m)
            )[0].tolist()
            assert got == exp
    finally:
        b.close()


def test_speculative_self_draft_and_eos(model_and_params):
    """Draft == target: every proposal accepted (the acceptance fast path)
    and eos still stops the output exactly where plain decode does."""
    model, params = model_and_params
    import jax.numpy as jnp

    b = ContinuousBatcher(
        model, params, slots=2, max_seq=64, prefill_buckets=(8,),
        steps_per_poll=2, draft_model=model, draft_params=params,
        speculate_tokens=4,
    )
    try:
        prompt = [3, 17, 42]
        full = b.generate(prompt, max_new_tokens=20)
        exp = np.asarray(
            model.generate(params, jnp.asarray([prompt], jnp.int32), 20)
        )[0].tolist()
        assert full == exp
        eos = full[len(prompt) + 3]
        stopped = b.generate(prompt, max_new_tokens=20, eos_id=eos)
        assert stopped == full[: len(prompt) + 4]
        # full self-acceptance: far fewer target rounds than tokens
        assert b.stats["tokens"] > b.stats["steps"]
    finally:
        b.close()


SMALL_CFG = dict(
    vocab_size=16, d_model=16, n_layers=1, n_heads=2, n_kv_heads=2,
    d_ff=32, max_seq=16, dtype="float32",
)


def test_speculative_sampling_distribution_exact():
    """Stochastic speculation must SAMPLE the target distribution: the
    empirical distribution of the second generated token (the first one
    produced by the speculative path — token one comes from prefill
    sampling) matches the analytically computed target marginal, even
    with a draft that shares nothing with the target."""
    import jax.numpy as jnp

    model = DecoderLM(**SMALL_CFG)
    params = model.init_params(0)
    draft = DecoderLM(
        vocab_size=16, d_model=8, n_layers=1, n_heads=1, n_kv_heads=1,
        d_ff=16, max_seq=16, dtype="float32",
    )
    dparams = draft.init_params(123)
    prompt = [3, 5]
    T = 1.0
    V = SMALL_CFG["vocab_size"]

    # analytic marginal of token 2: sum_t1 p(t1|prompt) p(t2|prompt,t1)
    def probs_after(toks):
        lg = np.asarray(model.apply(params, jnp.asarray([toks], jnp.int32)))[0, -1]
        e = np.exp((lg - lg.max()) / T)
        return e / e.sum()

    p1 = probs_after(prompt)
    marginal = np.zeros(V)
    for t1 in range(V):
        marginal += p1[t1] * probs_after(prompt + [t1])

    b = ContinuousBatcher(
        model, params, slots=8, max_seq=16, prefill_buckets=(4,),
        steps_per_poll=1, draft_model=draft, draft_params=dparams,
        speculate_tokens=2,
    )
    try:
        n = 1200
        futures = [
            b.submit(prompt, max_new_tokens=2, temperature=T, seed=s)
            for s in range(n)
        ]
        second = np.array([f.result(timeout=300)[3] for f in futures])
    finally:
        b.close()
    emp = np.bincount(second, minlength=V) / n
    # bin sd <= sqrt(p(1-p)/n) ~ 0.014; 0.05 is a ~4-sigma band
    assert np.abs(emp - marginal).max() < 0.05, (emp, marginal)


def test_speculative_self_draft_accepts_everything_stochastic():
    """Draft == target at temperature: acceptance ratio p/q == 1, so every
    round emits ~gamma+1 tokens (the speculative-sampling fast path)."""
    model = DecoderLM(**SMALL_CFG)
    params = model.init_params(0)
    b = ContinuousBatcher(
        model, params, slots=2, max_seq=16, prefill_buckets=(4,),
        steps_per_poll=2, draft_model=model, draft_params=params,
        speculate_tokens=3,
    )
    try:
        for s in range(4):
            b.generate([1, 2], max_new_tokens=8, temperature=0.9, seed=s)
        per_round = b.stats["spec_emitted"] / max(1, b.stats["spec_rounds"])
        # gamma+1 = 4, minus the occasional numeric-jitter rejection (the
        # step-wise draft forward and the chunked verify forward differ at
        # ~1e-6, so ratio p/q dips just under 1 now and then)
        assert per_round > 3.5
    finally:
        b.close()


def test_generateserver_self_draft_speculation(tmp_path):
    """GenerateServer speculation config surface: draft_layers builds an
    early-exit self-draft and the served output equals the plain server's."""
    import json

    from seldon_core_tpu.servers.generateserver import GenerateServer

    d = tmp_path / "llm"
    d.mkdir()
    (d / "jax_config.json").write_text(
        json.dumps({"family": "llm", "config": CFG})
    )
    plain = GenerateServer(model_uri=str(d), slots=2, steps_per_poll=2)
    spec = GenerateServer(
        model_uri=str(d), slots=2, steps_per_poll=2,
        speculate_tokens=3, draft_layers=1,
    )
    try:
        body = {"prompt_tokens": [[5, 17, 42]], "max_new_tokens": 8}
        out_plain = plain.predict(dict(body), [])
        out_spec = spec.predict(dict(body), [])
        assert out_plain["tokens"] == out_spec["tokens"]
        assert spec.batcher.speculate_tokens == 3
    finally:
        if plain.batcher:
            plain.batcher.close()
        if spec.batcher:
            spec.batcher.close()


def test_moe_model_through_batcher(model_and_params):
    """A mixture-of-experts DecoderLM decodes through the scheduler's
    list-cache path identically to the model's own generate()."""
    import jax.numpy as jnp

    model = DecoderLM(
        vocab_size=128, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2,
        d_ff=64, max_seq=64, n_experts=4, dtype="float32",
    )
    params = model.init_params(0)
    b = ContinuousBatcher(
        model, params, slots=2, max_seq=64, prefill_buckets=(8,), steps_per_poll=4
    )
    try:
        got = b.generate([3, 5, 7], max_new_tokens=6)
        exp = np.asarray(
            model.generate(params, jnp.asarray([[3, 5, 7]], jnp.int32), 6)
        )[0].tolist()
        assert got == exp
    finally:
        b.close()


def test_cancelled_request_frees_its_lane(model_and_params):
    """A cancelled future (client disconnect) reclaims the decode lane
    instead of burning device time on the rest of its budget, and a
    cancelled queued request is never admitted."""
    import time

    model, params = model_and_params
    b = ContinuousBatcher(
        model, params, slots=1, max_seq=64, prefill_buckets=(8,), steps_per_poll=2
    )
    try:
        long_f = b.submit([1, 2, 3], max_new_tokens=50)
        queued_f = b.submit([4, 5], max_new_tokens=4)  # waits: 1 slot
        time.sleep(0.2)  # long request is mid-decode
        long_f.cancel()
        # the queued request gets the lane promptly (well before the 50
        # tokens the cancelled one would have decoded)
        out = queued_f.result(timeout=60)
        assert out[:2] == [4, 5] and len(out) == 6
        # a cancelled QUEUED request never runs
        blocker = b.submit([1, 2], max_new_tokens=40)
        doomed = b.submit([9, 9], max_new_tokens=4)
        doomed.cancel()
        blocker.result(timeout=60)
        for _ in range(100):
            if b.stats["cancelled"] >= 2:
                break
            time.sleep(0.05)
        assert b.stats["cancelled"] >= 2
    finally:
        b.close()


def test_scheduler_death_fails_all_waiters(model_and_params):
    """A PERSISTENT device fault mid-burst fails every in-flight request
    promptly (not hanging futures) with the typed BatcherDead, burns the
    crash-loop budget (each supervised restart rebuilds the donated
    cache, re-crashes) and then latches the batcher dead: health flips,
    ``_stop`` sets, and later submits refuse up front with the typed
    budget-exhausted error the reconciler's replace path keys off."""
    from seldon_core_tpu.serving.continuous import BatcherDead

    model, params = model_and_params
    b = ContinuousBatcher(
        model, params, slots=2, max_seq=64, prefill_buckets=(8,),
        steps_per_poll=2, restart_budget=1, restart_backoff_s=0.05,
    )
    try:
        b.generate([1, 2], max_new_tokens=2)  # warm, loop running

        def boom(*a, **kw):
            raise RuntimeError("synthetic device fault")

        b._burst_fn = boom
        # the scheduler may die (and latch dead) while we are still
        # submitting — a late submit is then ALLOWED to raise directly
        # instead of returning a doomed future
        futures = []
        for _ in range(4):
            try:
                futures.append(b.submit([3, 4, 5], max_new_tokens=8))
            except RuntimeError as e:
                assert "closed" in str(e) or "died" in str(e)
        for f in futures:
            with pytest.raises(RuntimeError, match="batcher died|died|closed"):
                f.result(timeout=60)
        # budget exhausted: latched dead for good, typed refusals up front
        for _ in range(200):
            if b._stop.is_set():
                break
            import time as _time

            _time.sleep(0.05)
        assert b.health == "dead"
        assert b.stats["batcher_restarts"] == 1  # one rebuild landed first
        with pytest.raises(BatcherDead, match="crash-loop"):
            b.submit([1, 2, 3])
    finally:
        b.close()


def test_submit_after_close_raises(model_and_params):
    model, params = model_and_params
    b = ContinuousBatcher(model, params, slots=2, max_seq=64, prefill_buckets=(8,))
    b.generate([1, 2], max_new_tokens=2)
    b.close()
    with pytest.raises(RuntimeError, match="closed"):
        b.submit([1, 2, 3])


def test_prompt_too_long_rejected(batcher):
    with pytest.raises(ValueError, match="exceeds"):
        batcher.submit(list(range(64)), max_new_tokens=4)


def test_bucket_overflow_raises_clear_error(batcher):
    """A request longer than every prefill bucket AND max_seq fails with
    a clear ValueError from _bucket, not an opaque downstream broadcast
    error when the prompt is packed into a too-small array."""
    with pytest.raises(ValueError, match="largest prefill bucket"):
        batcher._bucket(batcher.max_seq + 1)
    # in-range lengths still bucket normally
    assert batcher._bucket(5) == 8
    assert batcher._bucket(33) == batcher.max_seq  # falls back to max_seq


def test_prefix_cache_greedy_identical_and_counts(model_and_params):
    """The tentpole acceptance property: with the radix prefix KV cache
    ON, greedy outputs are byte-identical to cache-off AND to the model's
    own generate(), while repeat/shared-prefix traffic actually hits."""
    import jax.numpy as jnp

    model, params = model_and_params
    rng = np.random.RandomState(11)
    system = rng.randint(0, 256, 14).tolist()
    prompts = [system + rng.randint(0, 256, 4).tolist() for _ in range(4)]
    prompts.append(list(prompts[0]))  # exact repeat
    # same bucket, shorter shared prefix (10 of 14 system tokens)
    prompts.append(system[:10] + rng.randint(0, 256, 8).tolist())
    on = ContinuousBatcher(
        model, params, slots=2, max_seq=64, prefill_buckets=(8, 16, 32),
        prefix_cache_hbm_bytes=1 << 26, prefix_cache_min_tokens=4,
    )
    off = ContinuousBatcher(
        model, params, slots=2, max_seq=64, prefill_buckets=(8, 16, 32),
    )
    try:
        got_on = [on.generate(p, max_new_tokens=8) for p in prompts]
        got_off = [off.generate(p, max_new_tokens=8) for p in prompts]
        assert got_on == got_off
        expected = [
            np.asarray(
                model.generate(params, jnp.asarray([p], jnp.int32), 8)
            )[0].tolist()
            for p in prompts
        ]
        assert got_on == expected
        # request 1..: prompts 2-4 share the 14-token system prefix with
        # prompt 1's published slab, the repeat matches n-1, the
        # partial-prefix prompt matches 10 tokens inside the slab
        assert on.stats["prefix_hits"] >= 4
        assert on.stats["prefix_misses"] >= 1
        assert on.stats["prefix_tokens_saved"] > 0
        assert on.stats["prefix_cache_bytes"] > 0
        assert off.stats["prefix_hits"] == 0
    finally:
        on.close()
        off.close()


def test_prefix_cache_eviction_under_byte_budget(model_and_params):
    """A budget that holds ~one slab forces LRU eviction at radix-node
    granularity; correctness is unaffected (evicted prefixes just prefill
    in full again)."""
    import jax.numpy as jnp

    model, params = model_and_params
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, 256, 12).tolist() for _ in range(4)]
    # one slab at bucket 16 is 4KB (2 layers x k+v x [1, 2, 16, 8] f32);
    # a 5KB budget holds exactly one — every publish evicts the previous
    b = ContinuousBatcher(
        model, params, slots=2, max_seq=64, prefill_buckets=(16,),
        prefix_cache_hbm_bytes=5 << 10, prefix_cache_min_tokens=4,
    )
    try:
        for p in prompts:
            got = b.generate(p, max_new_tokens=6)
            exp = np.asarray(
                model.generate(params, jnp.asarray([p], jnp.int32), 6)
            )[0].tolist()
            assert got == exp
        assert b.stats["prefix_evicted"] >= 2
        assert b.stats["prefix_cache_bytes"] <= 5 << 10
        # a re-run of the LAST prompt (still resident) hits
        hits0 = b.stats["prefix_hits"]
        assert b.generate(prompts[-1], max_new_tokens=6) == exp
        assert b.stats["prefix_hits"] == hits0 + 1
    finally:
        b.close()


def test_prefix_cache_with_speculation_exact(model_and_params):
    """Prefix reuse composes with speculative decoding: target prefixes
    come from the pool, draft prefixes are re-derived — output still
    equals the target's own greedy decode."""
    import jax.numpy as jnp

    model, params = model_and_params
    draft = DecoderLM(
        vocab_size=CFG["vocab_size"], d_model=16, n_layers=1, n_heads=2,
        n_kv_heads=1, d_ff=32, max_seq=64, dtype="float32",
    )
    dparams = draft.init_params(99)
    b = ContinuousBatcher(
        model, params, slots=2, max_seq=64, prefill_buckets=(8, 16),
        steps_per_poll=2, draft_model=draft, draft_params=dparams,
        speculate_tokens=3,
        prefix_cache_hbm_bytes=1 << 26, prefix_cache_min_tokens=4,
    )
    try:
        rng = np.random.RandomState(2)
        shared = rng.randint(0, 256, 9).tolist()
        for tail_len in (3, 4, 2):
            p = shared + rng.randint(0, 256, tail_len).tolist()
            got = b.generate(p, max_new_tokens=6)
            exp = np.asarray(
                model.generate(params, jnp.asarray([p], jnp.int32), 6)
            )[0].tolist()
            assert got == exp
        assert b.stats["prefix_hits"] >= 2
    finally:
        b.close()


def test_prefix_cache_on_mesh(model_and_params):
    """The prefix pool's slabs inherit the sharded cache layout; splice +
    suffix prefill stay exact with the KV cache sharded over the mesh."""
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.parallel.mesh import make_mesh

    model, params = model_and_params
    mesh = make_mesh({"seq": 2, "model": 2}, jax.devices()[:4])
    b = ContinuousBatcher(
        model, params, slots=2, max_seq=64, mesh=mesh, shard_cache_seq=True,
        prefill_buckets=(8, 16),
        prefix_cache_hbm_bytes=1 << 26, prefix_cache_min_tokens=4,
    )
    try:
        rng = np.random.RandomState(3)
        shared = rng.randint(0, 256, 10).tolist()
        for tail_len in (3, 5):
            p = shared + rng.randint(0, 256, tail_len).tolist()
            exp = np.asarray(
                model.generate(params, jnp.asarray([p], jnp.int32), 8)
            )[0].tolist()
            assert b.generate(p, max_new_tokens=8) == exp
        assert b.stats["prefix_hits"] >= 1
    finally:
        b.close()


def test_generateserver_surfaces_cache_hit_tokens(tmp_path):
    """cache_hit_tokens rides the unary response (per request, in order)
    and the stream's final event; the metrics export carries the prefix
    counters so graph nodes report cache wins."""
    from seldon_core_tpu.servers.generateserver import GenerateServer

    d = tmp_path / "llm"
    d.mkdir()
    (d / "jax_config.json").write_text(
        json.dumps({"family": "llm", "config": CFG})
    )
    s = GenerateServer(
        model_uri=str(d), slots=2, steps_per_poll=2,
        prefix_cache_hbm_bytes=1 << 26, prefix_cache_min_tokens=4,
    )
    try:
        prompt = [7, 3, 9, 1, 4, 6, 2, 8]
        body = {"prompt_tokens": [prompt], "max_new_tokens": 4}
        first = s.predict(dict(body), [])
        assert first["cache_hit_tokens"] == [0]  # cold pool
        second = s.predict(dict(body), [])
        assert second["tokens"] == first["tokens"]
        assert second["cache_hit_tokens"] == [len(prompt) - 1]  # n-1 cap
        handle = s.stream(dict(body))
        chunks = list(handle.chunks)
        assert chunks[-1]["done"] is True
        assert chunks[-1]["cache_hit_tokens"] == len(prompt) - 1
        keys = {m["key"]: m for m in s.metrics()}
        assert keys["prefix_cache_hits"]["type"] == "COUNTER"
        assert keys["prefix_tokens_saved"]["value"] > 0
        assert keys["gen_prefill_steps"]["type"] == "COUNTER"
        assert "prefix_cache_bytes" in keys
        # counters export DELTAS: a second scrape with no traffic reads 0
        keys2 = {m["key"]: m for m in s.metrics()}
        assert keys2["prefix_cache_hits"]["value"] == 0
    finally:
        if s.batcher:
            s.batcher.close()


def test_mesh_sharded_cache(model_and_params):
    """tp (KV heads over `model`) + seq-sharded cache on the 8-device CPU
    mesh; greedy output equals the single-chip reference."""
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.parallel.mesh import make_mesh

    model, params = model_and_params
    mesh = make_mesh({"seq": 2, "model": 2}, jax.devices()[:4])
    b = ContinuousBatcher(
        model,
        params,
        slots=2,
        max_seq=64,
        mesh=mesh,
        shard_cache_seq=True,
        prefill_buckets=(8,),
    )
    try:
        prompt = [11, 22, 33, 44]
        expected = np.asarray(
            model.generate(params, jnp.asarray([prompt], jnp.int32), 8)
        )[0].tolist()
        got = b.generate(prompt, max_new_tokens=8)
        assert got == expected
        # cache really is sharded over the mesh (per-layer entries)
        shard_axes = {layer.sharding.spec for layer in b._cache["k"]}
        assert any(ax is not None for spec in shard_axes for ax in spec)
    finally:
        b.close()


def test_engine_served_generate_e2e(tmp_path):
    """store -> reconciler -> GENERATE_SERVER microservice -> engine
    /predictions with jsonData prompts (BASELINE config 5 shape)."""
    from seldon_core_tpu.controlplane.ingress import Gateway
    from seldon_core_tpu.controlplane.reconciler import DeploymentController
    from seldon_core_tpu.controlplane.resource import SeldonDeployment
    from seldon_core_tpu.controlplane.store import ResourceStore

    d = tmp_path / "llm"
    d.mkdir()
    (d / "jax_config.json").write_text(
        json.dumps({"family": "llm", "config": {**CFG, "seed": 0}})
    )
    dep = SeldonDeployment.from_dict(
        {
            "metadata": {"name": "gen", "namespace": "default"},
            "spec": {
                "predictors": [
                    {
                        "name": "main",
                        "traffic": 100,
                        "graph": {
                            "name": "llm",
                            "implementation": "GENERATE_SERVER",
                            "modelUri": str(d),
                            "parameters": [
                                {"name": "slots", "value": "2", "type": "INT"},
                                {"name": "max_seq", "value": "64", "type": "INT"},
                            ],
                        },
                    }
                ]
            },
        }
    )

    async def run():
        store = ResourceStore()
        gw = Gateway(seed=0)
        ctl = DeploymentController(store, gateway=gw)
        store.apply(dep)
        status = await ctl.reconcile(dep)
        assert status.state == "Available", status.description
        primary, _ = gw.select("default/gen")
        out = await gw._forward(
            primary,
            "/api/v0.1/predictions",
            {"jsonData": {"prompt_tokens": [[3, 17, 42]], "max_new_tokens": 5}},
        )
        toks = out["jsonData"]["tokens"]
        assert len(toks) == 1 and len(toks[0]) == 8
        assert toks[0][:3] == [3, 17, 42]
        await ctl.shutdown()

    asyncio.run(run())


def test_long_prompt_spans_seq_shards(model_and_params):
    """Long-context serving: a prompt much longer than one seq shard's
    cache chunk decodes correctly with the KV cache length sharded over a
    4-way seq axis (long prompts span ICI — the capability the reference
    never had; SURVEY §5 long-context)."""
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.parallel.mesh import make_mesh

    model, params = model_and_params
    mesh = make_mesh({"seq": 4, "model": 2}, jax.devices())
    b = ContinuousBatcher(
        model,
        params,
        slots=2,
        max_seq=256,  # 64 cache positions per seq shard
        mesh=mesh,
        shard_cache_seq=True,
        prefill_buckets=(128,),
    )
    try:
        rng = np.random.RandomState(7)
        prompt = rng.randint(1, CFG["vocab_size"], 100).tolist()  # > 1 shard
        expected = np.asarray(
            model.generate(params, jnp.asarray([prompt], jnp.int32), 12)
        )[0].tolist()
        got = b.generate(prompt, max_new_tokens=12)
        assert got == expected
        # cache shards over BOTH the model (KV heads) and seq (length) axes
        spec = b._cache["k"][0].sharding.spec
        assert "model" in spec and "seq" in spec
    finally:
        b.close()


def test_engine_grpc_generate_e2e(tmp_path):
    """generate() over the engine's gRPC front: jsonData prompts in a
    SeldonMessage through Seldon/Predict, tokens back — the reference's
    gRPC external API shape carrying the TPU-native generate payload."""
    import grpc

    from seldon_core_tpu.testing import EngineHarness
    from seldon_core_tpu.proto import prediction_pb2 as pb
    from seldon_core_tpu.proto.services import method_path
    from seldon_core_tpu.servers.generateserver import GenerateServer

    d = tmp_path / "llm"
    d.mkdir()
    (d / "jax_config.json").write_text(json.dumps({"family": "llm", "config": CFG}))
    component = GenerateServer(model_uri=str(d), slots=2, steps_per_poll=2)
    component.load()
    harness = EngineHarness(component).start()
    try:
        request = pb.SeldonMessage(
            json_data=json.dumps(
                {"prompt_tokens": [[5, 17, 42]], "max_new_tokens": 6}
            )
        ).SerializeToString()
        with grpc.insecure_channel(f"127.0.0.1:{harness.grpc_port}") as ch:
            rpc = ch.unary_unary(
                method_path("Seldon", "Predict"),
                request_serializer=lambda b: b,
                response_deserializer=pb.SeldonMessage.FromString,
            )
            out = rpc(request, timeout=120.0)
        toks = json.loads(out.json_data)["tokens"][0]
        assert toks[:3] == [5, 17, 42] and len(toks) == 9
    finally:
        harness.stop()
        if component.batcher:
            component.batcher.close()


def test_streaming_generate_over_sse(tmp_path):
    """/api/v0.1/generate streams SSE events whose token spans concatenate
    to exactly the unary result, with incremental delivery (more than one
    event before done) and an exact final payload."""
    import http.client

    from seldon_core_tpu.testing import EngineHarness
    from seldon_core_tpu.servers.generateserver import GenerateServer

    d = tmp_path / "llm"
    d.mkdir()
    (d / "jax_config.json").write_text(json.dumps({"family": "llm", "config": CFG}))
    component = GenerateServer(model_uri=str(d), slots=2, steps_per_poll=2)
    component.load()
    harness = EngineHarness(component).start()
    try:
        body = {"jsonData": {"prompt_tokens": [[5, 17, 42]], "max_new_tokens": 10}}
        unary_conn = http.client.HTTPConnection("127.0.0.1", harness.http_port)
        unary_conn.request(
            "POST", "/api/v0.1/predictions", json.dumps(body).encode(),
            {"Content-Type": "application/json"},
        )
        unary = json.loads(unary_conn.getresponse().read())["jsonData"]["tokens"][0]

        conn = http.client.HTTPConnection("127.0.0.1", harness.http_port)
        conn.request(
            "POST", "/api/v0.1/generate", json.dumps(body).encode(),
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.getheader("Content-Type") == "text/event-stream"
        events = []
        for line in resp.read().decode().split("\n\n"):
            if line.startswith("data: "):
                events.append(json.loads(line[len("data: "):]))
        assert events[-1]["done"] is True
        assert events[-1]["tokens"] == unary
        streamed = [t for ev in events[:-1] for t in ev["tokens"]]
        assert streamed == unary[3:]  # generated tokens only, in order
        assert len(events) > 2  # genuinely incremental, not one blob
    finally:
        harness.stop()
        if component.batcher:
            component.batcher.close()


def test_streaming_rejects_batch_and_multinode(tmp_path):
    """Batch bodies 400 at the HTTP layer (validation is EAGER — no 200 +
    truncated stream), and a non-generate graph 501s."""
    import http.client

    from seldon_core_tpu.testing import EngineHarness
    from seldon_core_tpu.servers.generateserver import GenerateServer
    from seldon_core_tpu.user_model import SeldonComponent

    d = tmp_path / "llm"
    d.mkdir()
    (d / "jax_config.json").write_text(json.dumps({"family": "llm", "config": CFG}))
    s = GenerateServer(model_uri=str(d), slots=2, steps_per_poll=2)
    try:
        with pytest.raises(ValueError, match="ONE prompt"):
            s.stream({"prompt_tokens": [[1, 2], [3, 4]]})

        harness = EngineHarness(s).start()
        try:
            conn = http.client.HTTPConnection("127.0.0.1", harness.http_port)
            conn.request(
                "POST", "/api/v0.1/generate",
                json.dumps({"jsonData": {"prompt_tokens": [[1, 2], [3, 4]]}}).encode(),
                {"Content-Type": "application/json"},
            )
            assert conn.getresponse().status == 400
        finally:
            harness.stop()
    finally:
        if s.batcher:
            s.batcher.close()

    class Plain(SeldonComponent):
        def predict(self, X, names, meta=None):
            return np.asarray(X)

    harness2 = EngineHarness(Plain()).start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", harness2.http_port)
        conn.request(
            "POST", "/api/v0.1/generate",
            json.dumps({"jsonData": {"prompt_tokens": [[1, 2]]}}).encode(),
            {"Content-Type": "application/json"},
        )
        assert conn.getresponse().status == 501
    finally:
        harness2.stop()


def test_streaming_disconnect_cancels_request(tmp_path):
    """Dropping the connection mid-stream cancels the request: the decode
    lane is reclaimed (cancelled stat) and the engine's in-flight gauge
    returns to zero."""
    import http.client
    import time

    from seldon_core_tpu.testing import EngineHarness
    from seldon_core_tpu.servers.generateserver import GenerateServer

    d = tmp_path / "llm"
    d.mkdir()
    (d / "jax_config.json").write_text(json.dumps({"family": "llm", "config": CFG}))
    s = GenerateServer(model_uri=str(d), slots=1, steps_per_poll=1)
    s.load()
    harness = EngineHarness(s).start()
    try:
        import socket as _socket

        body = json.dumps(
            {"jsonData": {"prompt_tokens": [[5, 6, 7]], "max_new_tokens": 55}}
        ).encode()
        sock = _socket.create_connection(("127.0.0.1", harness.http_port))
        sock.sendall(
            b"POST /api/v0.1/generate HTTP/1.1\r\nHost: x\r\n"
            b"Content-Type: application/json\r\n"
            + f"Content-Length: {len(body)}\r\n\r\n".encode() + body
        )
        assert sock.recv(16)  # first bytes arrived: stream is live
        sock.close()  # client vanishes mid-stream
        for _ in range(200):
            if s.batcher.stats["cancelled"] >= 1 and harness.app.inflight == 0:
                break
            time.sleep(0.05)
        assert s.batcher.stats["cancelled"] >= 1
        assert harness.app.inflight == 0
    finally:
        harness.stop()
        if s.batcher:
            s.batcher.close()


def test_streaming_generate_over_grpc(tmp_path):
    """gRPC twin of the SSE stream: Seldon/GenerateStream server-streaming
    responses concatenate to the unary result."""
    import grpc

    from seldon_core_tpu.testing import EngineHarness
    from seldon_core_tpu.payload import proto_to_json
    from seldon_core_tpu.proto import prediction_pb2 as pb
    from seldon_core_tpu.proto.services import method_path
    from seldon_core_tpu.servers.generateserver import GenerateServer

    d = tmp_path / "llm"
    d.mkdir()
    (d / "jax_config.json").write_text(json.dumps({"family": "llm", "config": CFG}))
    component = GenerateServer(model_uri=str(d), slots=2, steps_per_poll=2)
    component.load()
    harness = EngineHarness(component).start()
    try:
        request = pb.SeldonMessage(
            json_data=json.dumps({"prompt_tokens": [[5, 17, 42]], "max_new_tokens": 10})
        ).SerializeToString()
        with grpc.insecure_channel(f"127.0.0.1:{harness.grpc_port}") as ch:
            rpc = ch.unary_stream(
                method_path("Seldon", "GenerateStream"),
                request_serializer=lambda b: b,
                response_deserializer=pb.SeldonMessage.FromString,
            )
            events = [proto_to_json(m)["jsonData"] for m in rpc(request, timeout=120.0)]
        assert events[-1]["done"] is True
        expected = events[-1]["tokens"]
        streamed = [t for ev in events[:-1] for t in ev["tokens"]]
        assert [5, 17, 42] + streamed == expected
        assert len(events) > 2  # incremental
        # bad body -> INVALID_ARGUMENT before any stream items
        with grpc.insecure_channel(f"127.0.0.1:{harness.grpc_port}") as ch:
            rpc = ch.unary_stream(
                method_path("Seldon", "GenerateStream"),
                request_serializer=lambda b: b,
                response_deserializer=pb.SeldonMessage.FromString,
            )
            bad = pb.SeldonMessage(
                json_data=json.dumps({"prompt_tokens": [[1, 2], [3, 4]]})
            ).SerializeToString()
            with pytest.raises(grpc.RpcError) as e:
                list(rpc(bad, timeout=60.0))
            assert e.value.code() == grpc.StatusCode.INVALID_ARGUMENT
    finally:
        harness.stop()
        if component.batcher:
            component.batcher.close()


def test_speculation_on_mesh_with_thin_draft(model_and_params):
    """Speculation composes with tensor parallelism: the target shards
    over the mesh while a THIN draft (1 KV head, not divisible by the
    model axis) falls back to replicated KV — and stays exact."""
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.parallel import make_mesh

    model, params = model_and_params
    mesh = make_mesh({"model": 4})
    # self-draft (shards cleanly) AND a thin independent draft
    self_draft_params = {
        **params,
        "blocks": jax.tree_util.tree_map(lambda a: a[:1], params["blocks"]),
    }
    self_draft = DecoderLM(**{**CFG, "n_layers": 1})
    thin = DecoderLM(
        vocab_size=CFG["vocab_size"], d_model=16, n_layers=1, n_heads=4,
        n_kv_heads=1, d_ff=32, max_seq=64, dtype="float32",
    )
    thin_params = thin.init_params(9)
    prompt = [3, 5, 7]
    exp = np.asarray(
        model.generate(params, jnp.asarray([prompt], jnp.int32), 6)
    )[0].tolist()
    for draft, dparams in ((self_draft, self_draft_params), (thin, thin_params)):
        b = ContinuousBatcher(
            model, params, slots=2, max_seq=64, prefill_buckets=(8,),
            steps_per_poll=2, mesh=mesh,
            draft_model=draft, draft_params=dparams, speculate_tokens=3,
        )
        try:
            assert b.generate(prompt, max_new_tokens=6) == exp
        finally:
            b.close()


def test_stream_speculation_mesh_compose(tmp_path):
    """The whole round-2 serving stack at once: token STREAMING from a
    SPECULATIVE batcher whose target is SHARDED over the mesh (self-draft)
    — incremental chunks whose final event equals the unary result."""
    from seldon_core_tpu.parallel import make_mesh
    from seldon_core_tpu.servers.generateserver import GenerateServer

    d = tmp_path / "llm"
    d.mkdir()
    (d / "jax_config.json").write_text(json.dumps({"family": "llm", "config": CFG}))
    s = GenerateServer(
        model_uri=str(d), slots=2, steps_per_poll=2,
        speculate_tokens=3, draft_layers=1, mesh=make_mesh({"model": 4}),
    )
    s.load()
    try:
        handle = s.stream({"prompt_tokens": [[3, 5, 7]], "max_new_tokens": 8})
        chunks = list(handle.chunks)
        assert chunks[-1]["done"] is True
        streamed = [t for c in chunks[:-1] for t in c["tokens"]]
        assert [3, 5, 7] + streamed == chunks[-1]["tokens"]
        assert len(chunks) > 2  # incremental
        unary = s.predict({"prompt_tokens": [[3, 5, 7]], "max_new_tokens": 8}, [])
        assert chunks[-1]["tokens"] == unary["tokens"][0]
    finally:
        if s.batcher:
            s.batcher.close()


# ---------------------------------------------------------------------------
# Lanes at mixed depths: chunked prefill must never change greedy output
# — on vs off, under speculation, under the prefix cache — and a burst's
# bucket is the deepest of its lanes' own. Where the read is ragged the
# bucket is the host's arithmetic only: one executable per K.
# ---------------------------------------------------------------------------

MIXED_PROMPTS = [(3, 8), (40, 8), (5, 12), (35, 6), (9, 10), (28, 4)]


@pytest.fixture(autouse=True)
def _sub_tile_attn_buckets():
    """Lower the MXU-tileability clamp for this module's tests: lanes at
    mixed depths need several attention buckets inside a 64-token cache,
    which production's 64 floor forbids (by design)."""
    old = ContinuousBatcher.MIN_ATTN_BUCKET
    ContinuousBatcher.MIN_ATTN_BUCKET = 16
    yield
    ContinuousBatcher.MIN_ATTN_BUCKET = old


def _mixed_run(model, params, **kw):
    rng = np.random.RandomState(17)
    prompts = [rng.randint(0, 256, n).tolist() for n, _ in MIXED_PROMPTS]
    b = ContinuousBatcher(
        model, params, slots=4, max_seq=64, prefill_buckets=(8, 16, 32),
        attn_bucket=16, steps_per_poll=2, **kw
    )
    b.trace_groups = []
    try:
        import time

        futures = []
        for i, (p, (_, m)) in enumerate(zip(prompts, MIXED_PROMPTS)):
            futures.append(b.submit(p, max_new_tokens=m))
            if i % 2 == 1:
                time.sleep(0.03)  # stagger so depths genuinely mix
        out = [f.result(timeout=120) for f in futures]
    finally:
        b.close()
    return prompts, out, dict(b.stats), b.trace_groups


def test_chunked_prefill_greedy_identical(model_and_params):
    """Chunked prefill (long prompts trickling in between decode polls)
    is byte-identical to whole-prompt prefill, and really chunks."""
    model, params = model_and_params
    _, off, _, _ = _mixed_run(model, params)
    _, on, stats, _ = _mixed_run(model, params, prefill_chunk=16)
    assert on == off
    assert stats["prefill_chunks"] > 0


def test_chunked_prefill_with_speculation_exact(model_and_params):
    """Speculation composes with chunked prefill: output still equals
    the target's own greedy decode (chunked prompts feed the draft's
    full prefill at activation)."""
    import jax.numpy as jnp

    model, params = model_and_params
    draft = DecoderLM(
        vocab_size=CFG["vocab_size"], d_model=16, n_layers=1, n_heads=2,
        n_kv_heads=1, d_ff=32, max_seq=64, dtype="float32",
    )
    dparams = draft.init_params(99)
    _, out, stats, _ = _mixed_run(
        model, params, draft_model=draft, draft_params=dparams,
        speculate_tokens=3, prefill_chunk=16,
    )
    rng = np.random.RandomState(17)
    for (n, m), got in zip(MIXED_PROMPTS, out):
        p = rng.randint(0, 256, n).tolist()
        exp = np.asarray(
            model.generate(params, jnp.asarray([p], jnp.int32), m)
        )[0].tolist()
        assert got == exp
    assert stats["prefill_chunks"] > 0


def test_chunked_prefill_with_prefix_cache_exact(model_and_params):
    """Prefix-cache hits splice the donor slab and CHUNK the remaining
    prompt; outputs stay byte-identical to the model's own generate()
    and hits still register."""
    import jax.numpy as jnp

    model, params = model_and_params
    rng = np.random.RandomState(23)
    shared = rng.randint(0, 256, 20).tolist()
    prompts = [shared + rng.randint(0, 256, t).tolist() for t in (4, 6, 25, 3)]
    b = ContinuousBatcher(
        model, params, slots=2, max_seq=64, prefill_buckets=(8, 16, 32),
        attn_bucket=16, steps_per_poll=2,
        prefix_cache_hbm_bytes=1 << 26, prefix_cache_min_tokens=4,
        prefill_chunk=16,
    )
    try:
        for p in prompts:
            got = b.generate(p, max_new_tokens=6)
            exp = np.asarray(
                model.generate(params, jnp.asarray([p], jnp.int32), 6)
            )[0].tolist()
            assert got == exp
        assert b.stats["prefix_hits"] >= 2
        assert b.stats["prefill_chunks"] > 0
    finally:
        b.close()


def _sequential_run(model, params, ragged, fused=0):
    """Three prompts whose runs end in three different buckets, one at a
    time (so every count below is the same run after run), through a
    batcher warmed for them. ``ragged``: tell the batcher its platform
    reads each lane's own length, as a TPU with tileable heads does; on
    this CPU the dots then read the whole cache under the mask."""
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, 256, n).tolist() for n in (3, 20, 40)]
    b = ContinuousBatcher(
        model, params, slots=2, max_seq=64, prefill_buckets=(8, 32, 64),
        attn_bucket=16, steps_per_poll=2, fused_steps_per_dispatch=fused,
    )
    assert b._ragged_read is False  # float32 heads of 8 on a CPU
    b._ragged_read = ragged
    b.trace_groups = []
    try:
        b.warm(prompt_lens=[3, 20, 40], max_new_tokens=8)
        warmed = (b._burst_fn._cache_size(), b._fused_burst_fn._cache_size())
        out = [b.generate(p, max_new_tokens=8) for p in prompts]
        served = (b._burst_fn._cache_size(), b._fused_burst_fn._cache_size())
    finally:
        b.close()
    return out, warmed, served, dict(b.stats), b.trace_groups


@pytest.mark.parametrize("fused", [0, 4])
def test_ragged_read_warms_one_burst_per_k_and_serves_the_same(
        model_and_params, fused):
    """Where the read takes each lane's own length the plain and the
    stop-aware burst have no bucket axis: one executable per K, none
    compiled under load, while the host's arithmetic (each burst's
    bucket, ``kv_positions_bucket``, the modeled read bytes) and the
    greedy tokens are the bucketed batcher's. A batcher whose platform
    reads through the dots still warms one per bucket."""
    # a model of its own: the module's one keeps the serving mesh of the
    # mesh tests above, and arrays laid out over it would add entries to
    # the jit caches counted here
    model, params = DecoderLM(**CFG), model_and_params[1]
    out_b, warmed_b, served_b, stats_b, trace_b = _sequential_run(
        model, params, ragged=False, fused=fused)
    out_r, warmed_r, served_r, stats_r, trace_r = _sequential_run(
        model, params, ragged=True, fused=fused)
    # K is 2 plain; fused 4 warms K in {2, 4}
    ks = 2 if fused else 0
    assert warmed_r == (1, ks) and served_r == warmed_r
    # the buckets the three runs cross: 16 .. 64
    assert warmed_b[0] >= 3 and warmed_b[1] == ks * warmed_b[0]
    assert served_b == warmed_b
    buckets = [t["attn_len"] for t in trace_b]
    assert len(set(buckets)) >= 3
    assert [t["attn_len"] for t in trace_r] == buckets
    for t in trace_r:
        assert t["attn_len"] == max(t["need"].values())
    for key in ("kv_positions_bucket", "kv_positions_read", "kv_rows_written",
                "burst_read_bytes", "burst_reads", "steps"):
        assert stats_r[key] == stats_b[key], key
    # whose write it is follows the same rule as whose read
    assert stats_r["kv_rows_written_in_kernel"] == stats_r["kv_rows_written"] > 0
    assert stats_b["kv_rows_written_in_kernel"] == 0
    assert out_r == out_b


def test_burst_bucket_is_the_deepest_lanes_own(model_and_params):
    """Lanes at mixed depths ride ONE burst whose bucket is the deepest
    lane's; each lane's own ``need`` is recorded beside it."""
    model, params = model_and_params
    _, _, _, trace = _mixed_run(model, params)
    assert trace
    assert any(len(set(t["need"].values())) > 1 for t in trace)
    for t in trace:
        assert sorted(t["need"]) == list(t["lanes"])
        assert t["attn_len"] == max(t["need"].values())


def test_generateserver_chunk_knob_and_burst_metrics(tmp_path):
    """Knob plumbing + observability: GenerateServer forwards the chunk
    knob, serves identically to a knobs-off server, and exports the
    per-burst read-bytes counters."""
    from seldon_core_tpu.servers.generateserver import GenerateServer

    d = tmp_path / "llm"
    d.mkdir()
    (d / "jax_config.json").write_text(
        json.dumps({"family": "llm", "config": CFG})
    )
    plain = GenerateServer(model_uri=str(d), slots=2, steps_per_poll=2,
                           attn_bucket=16)
    tuned = GenerateServer(
        model_uri=str(d), slots=2, steps_per_poll=2, attn_bucket=16,
        prefill_chunk=16,
    )
    try:
        body = {"prompt_tokens": [list(range(1, 30)), [5, 17, 42]],
                "max_new_tokens": 8}
        out_plain = plain.predict(dict(body), [])
        out_tuned = tuned.predict(dict(body), [])
        assert out_plain["tokens"] == out_tuned["tokens"]
        assert tuned.batcher.prefill_chunk == 16
        keys = {m["key"]: m for m in tuned.metrics()}
        assert keys["gen_burst_reads"]["type"] == "COUNTER"
        assert keys["gen_burst_read_bytes"]["value"] > 0
        assert keys["gen_prefill_chunks"]["value"] > 0
    finally:
        if plain.batcher:
            plain.batcher.close()
        if tuned.batcher:
            tuned.batcher.close()
