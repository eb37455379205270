"""Deep redundancy-component nesting: one frontier round per nesting level.

``nested_tree(d)`` repeats a shared subtree twice inside the shared subtree
one level up, d levels deep, and twice under the root.  Every level then has
twice the occurrence count of the level above, so each is its own RC and the
RCs nest d deep below the root's RC.  A query whose keywords meet in the
innermost level needs one frontier round per RC: d + 1.
"""
import numpy as np
import pytest

from repro.core import KeywordSearchEngine, NodeSpec, brute, build_tree

ORACLES = {"slca": brute.slca_nodes, "elca": brute.elca_nodes}
INNERMOST = ["alpha", "beta"]  # both directly in every level1 node
QUERIES = [
    INNERMOST,
    ["gamma"],
    ["alpha", "delta"],
    ["gamma", "beta"],
    ["beta", "gamma", "delta"],
    ["alpha", "beta", "gamma", "delta"],
    ["alpha", "absent-word"],
]


def nested_tree(depth: int):
    shared = NodeSpec(
        "level1", text="alpha beta", children=[NodeSpec("leaf", text="gamma")]
    )
    for d in range(2, depth + 1):
        side = NodeSpec(f"side{d}", text="beta" if d % 3 == 0 else "delta")
        shared = NodeSpec(
            f"level{d}",
            text="gamma" if d % 2 else "alpha",
            children=[shared, side, shared],
        )
    tail = NodeSpec("tail", text="alpha delta")
    return build_tree(NodeSpec("root", children=[shared, tail, shared]))


@pytest.mark.parametrize("sem", sorted(ORACLES))
@pytest.mark.parametrize("depth", range(2, 9))
def test_deep_nesting_matches_oracle_one_round_per_level(depth, sem):
    tree = nested_tree(depth)
    eng = KeywordSearchEngine(tree)
    assert eng.cluster.num_rcs == depth + 1
    wants = [ORACLES[sem](tree, eng.keyword_ids(q)) for q in QUERIES]

    for q, want in zip(QUERIES, wants):
        got = eng.query(q, semantics=sem, index="dag", backend="jax")
        np.testing.assert_array_equal(got, want, err_msg=f"d={depth} {sem} {q}")
        if q is INNERMOST:
            assert want.size > 0
            assert eng.last_stats.data["rounds"] == depth + 1
            assert eng.last_stats.data["rcs_searched"] == depth + 1

    batched = eng.query_batch(QUERIES, semantics=sem)
    for q, got, want in zip(QUERIES, batched, wants):
        np.testing.assert_array_equal(
            got, want, err_msg=f"batch d={depth} {sem} {q}"
        )
    assert eng.last_stats.data["rounds"] == depth + 1
