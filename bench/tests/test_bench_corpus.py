"""The benchmark's corpus is the program generator's corpus, node for node."""
import numpy as np
import pytest

from bench.corpus import QUERIES, _Draws, generate
from repro.data import QUERIES as PROGRAM_QUERIES
from repro.data import generate_discogs_tree


@pytest.mark.parametrize("n,seed", [(1, 0), (60, 1), (700, 12345),
                                    (400, 2**31 + 7), (300, 2**40 + 3)])
def test_same_corpus_as_program_generator(n, seed):
    c = generate(n, seed)
    tree = generate_discogs_tree(n_releases=n, seed=seed)
    assert c.num_nodes == tree.num_nodes
    assert np.array_equal(c.parent, tree.parent)
    assert np.array_equal(c.size, tree.subtree_size)
    off, ids = c.kw_csr()
    ours = [sorted(c.words[k] for k in ids[off[i]:off[i + 1]])
            for i in range(c.num_nodes)]
    theirs = [sorted(tree.vocab.id_to_word[k] for k in tree.direct_keywords(i))
              for i in range(tree.num_nodes)]
    assert ours == theirs


def test_queries_are_the_programs():
    assert QUERIES == PROGRAM_QUERIES


@pytest.mark.parametrize("high", [3 << 30, (1 << 31) + 1, 200])
def test_bounded_draws_follow_numpy_with_rejections(high):
    # a bound of 3 * 2**30 rejects a quarter of its draws
    rng = np.random.default_rng(99)
    want = [int(rng.integers(0, high)) for _ in range(500)]
    d = _Draws(99, 16)
    got, pos = [], 0
    for _ in range(500):
        v, pos = d.one(pos, 0, high)
        got.append(v)
    assert got == want
