"""The batched multi-query DAG search equals the oracle on redundant trees.

``KeywordSearchEngine.query_batch`` runs ``dag_search_vec_multi``, the path
of the service's admission window.  Each case is one batch of 16 queries of
1-4 keywords over a random tree with shared subtrees: the queries of one
keyword count share each round's launch whatever their list lengths, each
query claims its own nested RCs, and each query's nested results are spliced
through the RCPM.
"""
import numpy as np
import pytest
from random_trees import WORDS, random_tree

from repro.core import KeywordSearchEngine, brute

ORACLES = {"slca": brute.slca_nodes, "elca": brute.elca_nodes}
BATCH = 16
MIN_NODES = 20


def redundant_tree(seed: int):
    """The first tree of seeds ``seed``, ``seed + 16``, ... with at least
    ``MIN_NODES`` nodes: a smaller one seldom nests a redundancy component."""
    while True:
        tree = random_tree(seed, 60)
        if tree.num_nodes >= MIN_NODES:
            return tree
        seed += 16


def random_batch(seed: int) -> list[list[str]]:
    """16 queries of 1-4 distinct words; the second-last repeats the first,
    the last names a word no tree holds."""
    rng = np.random.default_rng(seed + 1000)
    batch = [
        list(rng.choice(WORDS, size=1 + i % 4, replace=False))
        for i in range(BATCH - 2)
    ]
    return batch + [batch[0], [batch[1][0], "absent-word"]]


@pytest.mark.parametrize("sem", sorted(ORACLES))
@pytest.mark.parametrize("seed", range(16))
def test_query_batch_matches_oracle_on_random_trees(seed, sem):
    tree = redundant_tree(seed)
    eng = KeywordSearchEngine(tree)
    batch = random_batch(seed)
    got = eng.query_batch(batch, semantics=sem)
    assert len(got) == BATCH
    for q, ids in zip(batch, got):
        want = ORACLES[sem](tree, eng.keyword_ids(q))
        np.testing.assert_array_equal(ids, want, err_msg=f"seed={seed} {sem} {q}")
    assert eng.last_stats.data["rounds"] >= 2  # nested RCs were searched
