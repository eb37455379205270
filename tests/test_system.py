"""End-to-end behaviour tests for the whole system.

These are the integration seams: corpus -> engine -> every paper query under
both semantics across the indices and backends; benchmark harness sanity.
"""
import numpy as np
import pytest

from repro.core import KeywordSearchEngine, brute
from repro.data import QUERIES, generate_discogs_tree

ORACLES = {"slca": brute.slca_nodes, "elca": brute.elca_nodes}


@pytest.fixture(scope="module")
def system():
    tree = generate_discogs_tree(n_releases=120, seed=42)
    return tree, KeywordSearchEngine(tree)


@pytest.mark.parametrize("sem", sorted(ORACLES))
@pytest.mark.parametrize("q", sorted(QUERIES))
def test_search_system_end_to_end(system, q, sem):
    tree, eng = system
    kws = QUERIES[q][1]
    kk = eng.keyword_ids(kws)
    absent = [w for w, k in zip(kws, kk) if k < 0]
    assert not absent, f"{q}: the corpus lacks {absent}"
    want = ORACLES[sem](tree, kk)
    for index in ("tree", "dag"):
        for backend in ("scalar", "jax"):
            got = eng.query(kws, semantics=sem, index=index, backend=backend)
            np.testing.assert_array_equal(
                got, want, err_msg=f"{q} {sem} {index} {backend}"
            )


def test_benchmark_sections_importable():
    import benchmarks.run as br  # noqa: F401
    from benchmarks import common

    eng = common.engine_for(60)
    us = common.time_query(eng, ["description", "rpm"], repeats=1)
    assert us > 0
