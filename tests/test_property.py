"""Property tests: every engine configuration equals the brute-force oracle.

Random trees (``random_trees.random_tree``) carry deliberate redundancy
(shared NodeSpec subtrees) so DAG compression, dummy nodes, nested RCs, and
offset splicing are all exercised; hypothesis drives sizes/seeds/queries.
"""
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")  # optional test dep (see pyproject [test])
from hypothesis import given, settings, strategies as st
from random_trees import WORDS, random_tree

from repro.core import KeywordSearchEngine, brute


@st.composite
def tree_and_query(draw):
    seed = draw(st.integers(0, 2**31 - 1))
    n_target = draw(st.integers(5, 60))
    qlen = draw(st.integers(1, 3))
    rng = np.random.default_rng(seed + 1)
    query = list(rng.choice(WORDS, size=qlen, replace=False))
    return seed, n_target, query


@settings(max_examples=60, deadline=None)
@given(tree_and_query())
def test_all_engines_match_oracle(params):
    seed, n_target, query = params
    tree = random_tree(seed, n_target)
    tree.validate()
    eng = KeywordSearchEngine(tree)
    kws = eng.keyword_ids(query)
    if any(k < 0 for k in kws):
        return  # word absent from this random doc: nothing to check

    for sem, oracle_fn in (("slca", brute.slca_nodes), ("elca", brute.elca_nodes)):
        expect = oracle_fn(tree, kws)
        variants = [
            dict(index="tree", backend="scalar", algorithm=f"fwd_{sem}"),
            dict(index="tree", backend="scalar", algorithm=f"bwd_{sem}"),
            dict(index="tree", backend="jax"),
            dict(index="dag", backend="scalar", algorithm=f"fwd_{sem}"),
            dict(index="dag", backend="scalar", algorithm=f"bwd_{sem}"),
            dict(index="dag", backend="jax"),
        ]
        for v in variants:
            got = eng.query(query, semantics=sem, **v)
            np.testing.assert_array_equal(
                got, expect, err_msg=f"sem={sem} variant={v} seed={seed}"
            )


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(10, 80))
def test_index_invariants(seed, n_target):
    tree = random_tree(seed, n_target)
    eng = KeywordSearchEngine(tree)
    dag, rcs = eng.cluster.dag, eng.cluster.rcs

    # occurrence counts partition the node set
    assert int(dag.occ.sum()) == tree.num_nodes
    # canonical nodes map to themselves
    canon = dag.canon
    assert np.all(canon[canon] == canon)
    # every canonical node belongs to exactly one RC
    is_canon = canon == np.arange(tree.num_nodes)
    assert np.all(rcs.rc_of_node[is_canon] >= 0)
    assert np.all(rcs.rc_of_node[~is_canon] == -1)
    # RC roots: occurrence count changes at the boundary (or root of doc)
    for rc in range(rcs.num_rcs):
        r = int(rcs.rc_root[rc])
        assert rcs.rc_of_node[r] == rc
        p = int(tree.parent[r])
        if p >= 0:
            assert dag.occ[canon[p]] != dag.occ[r] or canon[p] != p
    # RCPM keys unique & sorted
    assert np.all(np.diff(rcs.dummy_ids) > 0) or rcs.dummy_ids.size <= 1
    # per-keyword IDLists well-formed in every RC
    for rc in range(min(rcs.num_rcs, 8)):
        for w in WORDS[:4]:
            lst = eng.cluster.idlist(rc, tree.vocab.get(w))
            lst.validate()
