"""The plain reference agrees with the program's scalar engine, and its
control (SLCA served for ELCA) is called wrong."""
import numpy as np
import pytest

from bench import traffic
from bench.corpus import generate
from bench.harness import compare
from bench.reference import Reference, digest


@pytest.fixture(scope="module")
def small():
    from repro.core import KeywordSearchEngine
    from repro.data import generate_discogs_tree

    n, seed = 300, 21
    tree = generate_discogs_tree(n_releases=n, seed=seed)
    return generate(n, seed), KeywordSearchEngine(tree, build_dag=False)


def test_reference_equals_scalar_engine(small):
    corpus, engine = small
    ref = Reference(corpus)
    pool = traffic.pool(traffic.load("facet-80"))[:400]
    for words in pool:
        for sem in ("slca", "elca"):
            want = np.asarray(
                engine.query(words, sem, index="tree", backend="scalar"),
                np.int64)
            assert np.array_equal(ref.answer(words, sem), want), (words, sem)


def test_unknown_word_gives_nothing(small):
    corpus, _ = small
    assert Reference(corpus).answer(["vinyl", "no-such-word"], "elca").size == 0


def test_control_is_called_wrong(small):
    corpus, _ = small
    spec = traffic.load("facet-80")
    pool = traffic.pool(spec)
    recs = [{"key": (i, s), "status": 200, "digest": None}
            for _, i, s in traffic.open_schedule(spec, 10.0, 5)]
    ref = Reference(corpus)
    for r in recs:
        r["digest"] = digest(ref.answer(pool[r["key"][0]], r["key"][1]))
    assert compare(corpus, pool, recs)["wrong_answers"] == 0
    assert compare(corpus, pool, recs, control=True)["wrong_answers"] > 0
