"""Window compaction's rank: the TPU branch against the binary search.

``stable_compact`` ranks the kept lanes with ``lax.platform_dependent``:
a compare-and-count on the TPU, the binary search elsewhere. The TPU
branch is called directly here, on the CPU, and must give the same
integers as the search and as NumPy, so a compacted window is
bit-identical whichever platform it is lowered for.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.sim import lanes
from repro.sim.lanes import rank_by_compare, rank_by_search, stable_compact

pytestmark = pytest.mark.tier1

WIDTHS = [1, 2, 96, 192, 384, 2688]
MASKS = ["random", "all", "none", "single"]
LANES = 5


def keep_masks(kind, K, seed):
    """``(LANES, K)`` keep masks of one kind."""
    rng = np.random.default_rng(seed * 7919 + K)
    if kind == "random":
        return rng.random((LANES, K)) < rng.uniform(0.05, 0.95, (LANES, 1))
    if kind == "all":
        return np.ones((LANES, K), bool)
    if kind == "none":
        return np.zeros((LANES, K), bool)
    keep = np.zeros((LANES, K), bool)
    keep[np.arange(LANES), rng.integers(0, K, LANES)] = True
    return keep


def ranks(rank, keep):
    """Both ranks as ``stable_compact`` calls them, vmapped over lanes."""
    K = keep.shape[1]

    def one(k):
        return rank(jnp.cumsum(k), jnp.arange(K) + 1)

    return np.asarray(jax.jit(jax.vmap(one))(jnp.asarray(keep)))


@pytest.mark.parametrize("kind", MASKS)
@pytest.mark.parametrize("K", WIDTHS)
def test_compare_rank_equals_the_binary_search_and_numpy(K, kind):
    keep = keep_masks(kind, K, seed=1)
    by_compare = ranks(rank_by_compare, keep)
    by_search = ranks(rank_by_search, keep)
    cs = np.cumsum(keep, axis=1)
    by_numpy = np.stack([np.searchsorted(c, np.arange(K) + 1, "left")
                         for c in cs])
    assert by_compare.dtype == by_search.dtype
    np.testing.assert_array_equal(by_compare, by_search)
    np.testing.assert_array_equal(by_compare, by_numpy)


@pytest.mark.parametrize("kind", MASKS)
@pytest.mark.parametrize("K", WIDTHS)
def test_stable_compact_is_identical_through_either_rank(K, kind,
                                                         monkeypatch):
    """``stable_compact`` with the default branch swapped for the TPU's
    rank returns the same arrays and ``n_keep``, and both keep the kept
    lanes in order ahead of the fills."""
    keep = keep_masks(kind, K, seed=2)
    rng = np.random.default_rng(K)
    flags = rng.random((LANES, K)) < 0.5
    times = rng.uniform(0.0, 1e6, (LANES, K)).astype(np.float32)
    sizes = rng.integers(1, 256, (LANES, K)).astype(np.float32)

    def compact(k, a, b, c):
        return stable_compact(k, [a, b, c], [False, 0.0, -1.0])

    def run():
        return jax.jit(jax.vmap(compact))(keep, flags, times, sizes)

    (a0, b0, c0), n0 = run()
    monkeypatch.setattr(lanes, "rank_by_search", rank_by_compare)
    (a1, b1, c1), n1 = run()
    for x, y in [(a0, a1), (b0, b1), (c0, c1), (n0, n1)]:
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    n = keep.sum(axis=1)
    np.testing.assert_array_equal(np.asarray(n1), n)
    for lane in range(LANES):
        kept = keep[lane]
        m = n[lane]
        np.testing.assert_array_equal(np.asarray(b1[lane, :m]),
                                      times[lane][kept])
        np.testing.assert_array_equal(np.asarray(c1[lane, :m]),
                                      sizes[lane][kept])
        np.testing.assert_array_equal(np.asarray(a1[lane, :m]),
                                      flags[lane][kept])
        assert not np.asarray(a1[lane, m:]).any()
        assert (np.asarray(c1[lane, m:]) == -1.0).all()
