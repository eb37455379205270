"""The RCPM probe of a result set (``IDClusterIndex.probe_dummies``).

It must agree with NumPy's plain search, whose int64 keys promote the whole
int32 dummy table, and must not make that copy: the probe costs the size of
the answer, not of the corpus.
"""
import tracemalloc

import numpy as np
import pytest

from repro.core import KeywordSearchEngine, NodeSpec, PlanCache, build_tree
from repro.core.components import IDClusterIndex, RedundancyComponents
from repro.core.idlist import build_containment
from repro.core.search_dag import dag_search_vec_multi
from repro.data import QUERIES, generate_discogs_tree


def _promoted_probe(index, rc, ids):
    """The probe as it was: one searchsorted of int64 keys."""
    dummy_ids = index.rcs.dummy_ids
    if not dummy_ids.size or not ids.size:
        return np.zeros(ids.shape, np.intp), np.zeros(ids.shape, bool)
    wide = ids.astype(np.int64)
    pos = np.clip(np.searchsorted(dummy_ids, wide), 0, dummy_ids.size - 1)
    return pos, (dummy_ids[pos] == wide) & (wide != index.rc_root_id(rc))


@pytest.fixture(scope="module")
def discogs():
    return KeywordSearchEngine(generate_discogs_tree(n_releases=30, seed=3))


@pytest.fixture(scope="module")
def flat_index():
    """A tree with no repeated subtree, so an RCPM with no dummies."""
    root = NodeSpec(
        "bib", children=[NodeSpec(f"r{i}", f"v{i}") for i in range(6)]
    )
    return IDClusterIndex(build_tree(root))


def _nested_rc(index):
    """An RC whose root id is also a dummy id of its parent RC."""
    roots = index.rcs.rc_root
    return next(
        rc for rc in range(1, index.num_rcs)
        if np.isin(roots[rc], index.rcs.dummy_ids)
    )


def _case(index, name):
    dummy_ids = index.rcs.dummy_ids
    n = index.tree.num_nodes
    if name == "empty":
        return 0, np.zeros(0, np.int64)
    if name == "below_first":
        return 0, np.arange(int(dummy_ids[0]) + 1, dtype=np.int64)
    if name == "above_last":
        return 0, np.arange(int(dummy_ids[-1]), n, dtype=np.int64)
    if name == "own_root":
        rc = _nested_rc(index)
        root = index.rc_root_id(rc)
        return rc, np.asarray([root, *dummy_ids[::3], root + 1], np.int64)
    if name == "mixed":
        rng = np.random.default_rng(5)
        picks = np.concatenate([dummy_ids[::2], rng.integers(0, n, 40)])
        return 0, np.unique(picks).astype(np.int64)
    raise AssertionError(name)


@pytest.mark.parametrize("dtype", [np.int32, np.int64])
@pytest.mark.parametrize(
    "name", ["empty", "below_first", "above_last", "own_root", "mixed", "flat"]
)
def test_probe_matches_the_promoted_search(discogs, flat_index, name, dtype):
    if name == "flat":
        index = flat_index
        assert index.rcs.dummy_ids.size == 0
        rc, ids = 0, np.arange(index.tree.num_nodes, dtype=np.int64)
    else:
        index = discogs.cluster
        rc, ids = _case(index, name)
    ids = ids.astype(dtype)
    pos, is_dummy = index.probe_dummies(rc, ids)
    want_pos, want_dummy = _promoted_probe(index, rc, ids)
    np.testing.assert_array_equal(is_dummy, want_dummy)
    np.testing.assert_array_equal(pos, want_pos)
    nested = index.rcs.dummy_nested_rc[want_pos[want_dummy]].tolist()
    assert index.nested_rcs(rc, ids) == list(dict.fromkeys(nested))
    if name in ("own_root", "mixed"):
        assert is_dummy.any() and not is_dummy.all()
    if name == "own_root":
        assert not is_dummy[0]  # the RC's own root is a dummy of its parent


def test_probe_does_not_copy_the_table():
    """10 int64 keys against a 1M-entry int32 table: the table's int64 copy
    would take 8 MB."""
    tree = build_tree(NodeSpec("bib", children=[NodeSpec("r", "v")]))
    table = np.arange(1, 2_000_001, 2, dtype=np.int32)
    d = table.size
    rcs = RedundancyComponents(
        num_rcs=1,
        rc_of_node=np.zeros(tree.num_nodes, np.int32),
        rc_root=np.zeros(1, np.int32),
        rc_occ=np.ones(1, np.int64),
        dummy_ids=table,
        dummy_parent_rc=np.zeros(d, np.int32),
        dummy_nested_rc=np.zeros(d, np.int32),
        dummy_offset=np.zeros(d, np.int64),
    )
    index = IDClusterIndex(tree, build_containment(tree), rcs=rcs)
    ids = np.arange(0, 2_000_000, 200_000, dtype=np.int64)
    index.probe_dummies(0, ids)  # first call outside the trace
    tracemalloc.start()
    try:
        pos, is_dummy = index.probe_dummies(0, ids)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert not is_dummy.any() and pos.size == ids.size
    assert peak < table.size * 8 // 16, peak


def test_index_refuses_ids_its_table_cannot_hold():
    tree = build_tree(NodeSpec("bib", children=[NodeSpec("r", "v")] * 200))
    index = IDClusterIndex(tree)
    rcs = index.rcs
    narrow = RedundancyComponents(
        num_rcs=rcs.num_rcs,
        rc_of_node=rcs.rc_of_node,
        rc_root=rcs.rc_root,
        rc_occ=rcs.rc_occ,
        dummy_ids=rcs.dummy_ids.astype(np.int8),
        dummy_parent_rc=rcs.dummy_parent_rc,
        dummy_nested_rc=rcs.dummy_nested_rc,
        dummy_offset=rcs.dummy_offset,
    )
    with pytest.raises(ValueError, match="overflow"):
        IDClusterIndex(tree, index.containment, index.dag, narrow)


@pytest.mark.parametrize("semantics", ["slca", "elca"])
def test_batched_dag_search_matches_the_scalar_engine(discogs, semantics):
    names = [kws for _, kws in QUERIES.values()]
    names += [["vinyl", "uk"], ["release", "rpm"], ["electronic", "45"]]
    queries = [discogs.keyword_ids(q) for q in names]
    got = dag_search_vec_multi(
        discogs.cluster, queries, semantics=semantics, plan=PlanCache()
    )
    spliced = 0
    for kws, res in zip(names, got):
        want = discogs.query(kws, semantics=semantics, backend="scalar")
        np.testing.assert_array_equal(res, want)
        spliced += discogs.last_stats.data.get("rcs_searched", 0) > 1
    assert spliced  # some answer came through a nested RC's dummies
