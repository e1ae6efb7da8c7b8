"""``ops.experts.grouped_swiglu``: the grouped prefill's kernel pair, run
interpreted on the CPU at the three expert cells' shapes cut small, against
``lax.ragged_dot`` and the SwiGLU, over the group patterns that break
grouped kernels; and the whole grouped path over it, in a padded bucket and
in the prompt's own."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from seldon_core_tpu.ops import experts

TM = 32
PAIRS = 256
# the cells' experts (held, of), width and picks, cut small: trinity-mini's
# 128 of 128 at 1024, top 8; the qwen3 share's 128 of 512 at 512, top 10;
# the joyai share's 32 of 256 at 768, top 8
CELLS = {
    "trinity_all_of_16_at_256": (16, 16, 256, 4),
    "qwen3_8_of_32_at_128": (8, 32, 128, 5),
    "joyai_4_of_32_at_384": (4, 32, 384, 4),
}


def _sizes(pattern: str, n_experts: int):
    """Group sizes over ``PAIRS`` rows in tiles of ``TM``."""
    rng = np.random.default_rng(len(pattern))
    if pattern == "uniform":
        sizes = [PAIRS // n_experts] * n_experts
    elif pattern == "every_pair_on_one_expert":
        sizes = [0] * n_experts
        sizes[n_experts // 2] = PAIRS
    elif pattern == "experts_with_no_rows":
        sizes = [0] * n_experts
        for e in range(0, n_experts, 3):
            sizes[e] = PAIRS // (-(-n_experts // 3))
    elif pattern == "groups_smaller_than_a_tile":
        sizes = rng.integers(1, TM // 2, size=n_experts).tolist()
    elif pattern == "a_group_ends_on_a_tiles_edge":
        # the first ends on an edge, the second inside a tile, the third on
        # the next edge, the last fills a tile
        sizes = [TM, TM // 2, TM + TM // 2] + [0] * (n_experts - 4) + [TM]
    elif pattern == "trailing_pairs_of_no_expert":
        # three tiles at the end hold no group's row, one of them in part
        sizes = [(PAIRS - 2 * TM - 5) // n_experts] * n_experts
    else:
        raise ValueError(pattern)
    assert len(sizes) == n_experts and sum(sizes) <= PAIRS
    return sizes


def _case(cell: str, seed: int = 3, d: int = 128):
    held, _, width, _ = CELLS[cell]
    rng = np.random.default_rng(seed)
    mk = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    stacks = tuple(a.astype(jnp.bfloat16) for a in (
        mk(held, d, width) / 11, mk(held, d, width) / 11,
        mk(held, width, d) / np.sqrt(width)))
    return rng, mk, stacks


@pytest.mark.parametrize("pattern", [
    "uniform", "every_pair_on_one_expert", "experts_with_no_rows",
    "groups_smaller_than_a_tile", "a_group_ends_on_a_tiles_edge",
    "trailing_pairs_of_no_expert"])
@pytest.mark.parametrize("cell", list(CELLS))
def test_the_kernel_is_the_ragged_dots_and_the_swiglu(cell, pattern):
    rng, mk, (w1, w3, w2) = _case(cell)
    n_experts = w1.shape[0]
    sizes = jnp.asarray(_sizes(pattern, n_experts), jnp.int32)
    landed = int(sizes.sum())
    xs = mk(PAIRS, 128).astype(jnp.bfloat16)
    by_pair = jnp.asarray(rng.random(PAIRS), jnp.float32)
    got = experts.grouped_swiglu(xs, sizes, by_pair, w1, w3, w2, tm=TM,
                                 interpret=True)
    assert got.shape == xs.shape and got.dtype == xs.dtype
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    dot = lambda a, w: lax.ragged_dot(  # noqa: E731
        a, w, sizes, preferred_element_type=jnp.float32)
    # in float32, nothing rounded in between
    exact = dot(jax.nn.silu(dot(f32(xs), f32(w1))) * dot(f32(xs), f32(w3)),
                f32(w2)) * by_pair[:, None]
    # at the kernel's own rounding points: h once, the weighted product once
    h = (jax.nn.silu(dot(xs, w1)) * dot(xs, w3)).astype(xs.dtype)
    rounded = (dot(h, w2) * by_pair[:, None]).astype(xs.dtype)
    got, exact, rounded = (np.asarray(f32(a))[:landed]
                           for a in (got, exact, rounded))
    scale = float(np.abs(exact).max())
    assert scale > 0.1
    # two bfloat16 roundings of the result's size
    assert np.abs(got - exact).max() <= 2.0 ** -7 * scale
    # against the dots rounded alike: an accumulation's order, one ulp
    assert np.abs(got - rounded).max() <= 2.0 ** -8 * scale
    # the walk: groups and tiles in order, a visit for every tile a group
    # has a row in, none for a group of no rows or a tile past the end
    offsets, ids, turn, tile, (n, groups) = (
        np.asarray(a) for a in experts.tile_visits(sizes, PAIRS, TM))
    with_rows = [e for e in range(n_experts) if sizes[e] > 0]
    assert groups == len(with_rows) and ids[:groups].tolist() == with_rows
    want = [(e, t) for e in with_rows
            for t in range(offsets[e] // TM, -(-offsets[e + 1] // TM))]
    assert list(zip(ids[turn[:n]].tolist(), tile[:n].tolist())) == want
    assert n <= len(turn) == len(tile) == PAIRS // TM + n_experts - 1
    assert n == 0 or tile[n - 1] == (landed - 1) // TM
    # past the visits the walk stays where it ended: no block moves
    assert set(tile[n:].tolist()) <= {tile[max(n - 1, 0)]}
    assert set(turn[n:].tolist()) <= {turn[max(n - 1, 0)]}
    assert offsets.tolist() == [0] + np.cumsum(np.asarray(sizes)).tolist()


@pytest.mark.parametrize("pattern", [
    "uniform", "experts_with_no_rows", "a_group_ends_on_a_tiles_edge",
    "trailing_pairs_of_no_expert"])
def test_an_expert_too_wide_for_the_kernel_goes_through_in_slices(
        pattern, monkeypatch):
    """An expert whose whole width is past the kernel's VMEM: one call a
    slice of the width, each copying its columns of ``W1`` / ``W3`` and its
    rows of ``W2`` from where they lie, the slices' float32 shares summed
    and rounded once: the dots rounded alike, to an accumulation's order."""
    rng, mk, (w1, w3, w2) = _case("trinity_all_of_16_at_256")
    assert experts.width_slices(w1.shape, w1.dtype) == 1
    # the three [128, 256] matrices two slots apiece are 393 KB; halves fit
    monkeypatch.setattr(experts, "GROUPED_VMEM_BYTES", 700 << 10)
    assert experts.width_slices(w1.shape, w1.dtype) == 2
    sizes = jnp.asarray(_sizes(pattern, w1.shape[0]), jnp.int32)
    landed = int(sizes.sum())
    xs = mk(PAIRS, 128).astype(jnp.bfloat16)
    by_pair = jnp.asarray(rng.random(PAIRS), jnp.float32)
    walk = experts.tile_visits(sizes, PAIRS, TM)
    got = experts._swiglu(xs, walk, by_pair, w1, w3, w2, TM, True)
    assert got.shape == xs.shape and got.dtype == xs.dtype
    dot = lambda a, w: lax.ragged_dot(  # noqa: E731
        a, w, sizes, preferred_element_type=jnp.float32)
    h = (jax.nn.silu(dot(xs, w1)) * dot(xs, w3)).astype(xs.dtype)
    rounded = (dot(h, w2) * by_pair[:, None]).astype(xs.dtype)
    got, rounded = (np.asarray(a.astype(jnp.float32))[:landed]
                    for a in (got, rounded))
    scale = float(np.abs(rounded).max())
    assert scale > 0.1
    assert np.abs(got - rounded).max() <= 2.0 ** -8 * scale
    # one slice alone is not the product: both were summed
    half = experts._swiglu_call(xs, walk, by_pair, w1, w3, w2, TM, True,
                                (0, 128))
    assert half.dtype == jnp.float32
    assert np.abs(np.asarray(half)[:landed] - rounded).max() > 0.05 * scale


@pytest.mark.parametrize("d,width,want", [
    # the benchmark's experts: whole
    (2048, 512, 1), (2048, 768, 1), (2048, 1024, 1), (2048, 1536, 1),
    # compiled for a described v5e at D = 4096: 1152 fits whole, 1280 does
    # not, 2048 goes in two slices of 1024
    (4096, 1152, 1), (4096, 1280, 2), (4096, 2048, 2),
    # whole registers of 128 columns, or none
    (65536, 128, 0)])
def test_the_slices_of_an_experts_width(d, width, want):
    assert experts.width_slices((16, d, width), jnp.bfloat16) == want
    assert experts.groups_in_kernel(
        "tpu", (1920, d), (16, d, width), jnp.bfloat16) is (want > 0)


def _kernel_interpreted(xs, sizes, real, by_pair, w1, w3, w2, kernel=True):
    """``_pairs_ffn`` as a TPU lowers it, the kernel interpreted."""
    tm = experts.row_tile(xs.shape[0])
    y = experts.grouped_swiglu(xs, sizes, by_pair, w1, w3, w2, tm=tm,
                               interpret=True)
    worked = experts.tile_visits(sizes, xs.shape[0], tm)[4][0] * tm
    return jnp.where(real[:, None], y, jnp.zeros((), y.dtype)), worked


@pytest.mark.parametrize("cell", list(CELLS))
def test_a_prompts_rows_are_the_same_in_a_padded_bucket_and_in_its_own(
        cell, monkeypatch):
    """52 tokens in a bucket of 256 rows, their pad rows sent to no expert,
    and in one of 64 (their own length rounded up): the grouped path over
    the kernel gives the prompt's rows the same sums, the dots' of the
    parent, and works the tiles the real pairs reach, not the bucket's."""
    held, n_routed, _, k = CELLS[cell]
    rng, mk, stacks = _case(cell, seed=5)
    tokens, bucket, own = 52, 256, 64
    x = mk(bucket, 128).astype(jnp.bfloat16)
    x = x.at[tokens:].set(x[tokens])                    # padding routes together
    picks, weights = experts.route(
        x, mk(128, n_routed) / 8, None, k, 1.0, score="softmax")
    real = jnp.arange(bucket) < tokens
    sent = jnp.where(real[:, None], picks, n_routed)
    share = dict(held=(n_routed - held, held), n_routed=n_routed) if (
        held < n_routed) else {}

    def run(rows, picks):
        return experts.grouped_experts.__wrapped__(
            x[:rows], picks[:rows], weights[:rows], *stacks, **share)

    want, _ = run(bucket, sent)                         # the dots
    monkeypatch.setattr(experts, "_pairs_ffn", _kernel_interpreted)
    padded, (moved, worked) = run(bucket, sent)
    alone, (_, worked_alone) = run(own, sent)
    routed, (_, worked_routed) = run(bucket, picks)
    scale = float(jnp.abs(want[:tokens]).max())
    assert scale > 0.1
    for got in (padded[:tokens], alone[:tokens], routed[:tokens]):
        assert float(jnp.abs(got - want[:tokens]).max()) <= 2.0 ** -8 * scale
    assert not np.asarray(padded[tokens:]).any()        # a pad row adds nothing
    # tiles follow the real pairs: as many in the padded bucket as in the
    # prompt's own (a share's room is cut from the bucket's pairs, so its
    # tile is), and fewer than with the pad rows routed
    tm = experts.row_tile(int(moved)) if share else experts.row_tile(bucket * k)
    landed = int(((sent >= n_routed - held) & (sent < n_routed)).sum())
    assert landed <= int(worked) <= landed + (held + 1) * tm
    assert int(worked) < int(worked_routed)
    if not share:
        assert int(moved) == bucket * k
        assert int(worked_alone) <= int(worked)
