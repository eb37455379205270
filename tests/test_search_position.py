"""The XLA batch search's list positions: ``searchsorted_left`` and its users.

``searchsorted_left`` replaces ``jnp.searchsorted`` (a ``while`` of scalar
gathers) with a loop-free block compare; it must count ``a < q`` exactly as
``np.searchsorted(side="left")`` does on padded sorted lists, whichever of
its two forms (compare-all up to one block, heads then one row beyond)
the list's static length picks.
"""
import jax
import numpy as np
import pytest

from repro.core.plan_cache import PlanCache
from repro.core.search_vec import (
    INT_PAD,
    SEARCH_BLOCK,
    ca_search_batch,
    searchsorted_left,
)

_search = jax.jit(searchsorted_left)


def _padded_list(rng, n, valid):
    a = np.full(n, INT_PAD, np.int32)
    a[:valid] = np.sort(rng.choice(8 * max(n, 1), valid, replace=False)) * 3 + 5
    return a


def _queries(rng, a, valid):
    ids = a[:valid]
    parts = [
        rng.integers(-10, 24 * max(a.size, 1) + 10, 512),  # anywhere
        [-1, 0, 4],  # below the first id (roots have parent -1)
        ids[:: max(1, valid // 200)],  # equal to ids
        ids[:: SEARCH_BLOCK],  # equal to block heads
        ids[SEARCH_BLOCK - 1 :: SEARCH_BLOCK],  # the last id of each block
        ids[::97] + 1,  # just above ids
        [INT_PAD - 1, INT_PAD],  # above the last id, and the pad itself
    ]
    if valid:
        parts.append([ids[-1], ids[-1] + 1])
    return np.concatenate([np.asarray(p, np.int64) for p in parts]).astype(np.int32)


@pytest.mark.parametrize(
    "n,valid",
    [
        (16, 0), (16, 9), (16, 16),
        (128, 0), (128, 100), (128, 128),
        (129, 129),  # a static length that is no multiple of the block
        (256, 129),  # 129 ids in their power-of-two bucket
        (4096, 0), (4096, 3000), (4096, 4096),
        (131072, 0), (131072, 100001), (131072, 131072),
    ],
)
def test_searchsorted_left_matches_numpy(n, valid):
    rng = np.random.default_rng(n + valid)
    a = _padded_list(rng, n, valid)
    q = _queries(rng, a, valid)
    got = np.asarray(_search(a, q))
    np.testing.assert_array_equal(got, np.searchsorted(a, q, side="left"))


def test_searchsorted_left_empty_list():
    q = np.array([-1, 0, 7, INT_PAD], np.int32)
    got = np.asarray(_search(np.zeros(0, np.int32), q))
    np.testing.assert_array_equal(got, np.zeros(4, np.int32))


@pytest.mark.parametrize("semantics", ["slca", "elca"])
@pytest.mark.parametrize("k", [2, 3, 4])
def test_ca_search_batch_matches_scalar(semantics, k):
    """Q1-Q9 grouped by keyword count, one launch per group: three queries
    pad to a rows bucket of 4, so the last row is all pad (``n0 = 0``)."""
    from repro.core import KeywordSearchEngine
    from repro.data import QUERIES, generate_discogs_tree

    tree = generate_discogs_tree(n_releases=120, seed=11)
    eng = KeywordSearchEngine(tree)
    queries = [kws for _cat, kws in QUERIES.values() if len(kws) == k]
    lists = [eng.base.idlists(eng.keyword_ids(kws)) for kws in queries]
    batch, kept, sig = PlanCache().pack(lists, list(range(len(lists))), semantics)
    assert kept == [0, 1, 2] and sig.rows == 4 and batch["n0"][3] == 0
    ids, mask = ca_search_batch(**batch, semantics=semantics, backend="xla")
    ids, mask = np.asarray(ids), np.asarray(mask)
    assert not mask[3].any()
    for r, kws in enumerate(queries):
        want = eng.query(kws, semantics=semantics, index="tree", backend="scalar")
        np.testing.assert_array_equal(
            ids[r][mask[r]], want, err_msg=f"{kws} {semantics}"
        )
