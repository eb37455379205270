"""§Perf hillclimb for the paper's own technique (measured wall time).

Each iteration times the search engine itself (host wall clock).  Knobs:
  * backend: scalar (paper-faithful) vs vectorized XLA vs Pallas kernels;
  * DAG frontier execution: per-RC calls vs batched rounds;
  * bucket sizing: pow2 padding granularity (jit cache hits vs padding waste).

Each row: name,us_per_call,derived (CSV like every bench).
"""
import os
import time

import numpy as np

from repro.core import search_vec
from repro.core.search_dag import dag_search_vec
from repro.data import QUERIES

from .common import N_RELEASES, emit, engine_for


def _time(fn, repeats=5):
    fn()
    t0 = time.perf_counter()
    for _ in range(repeats):
        fn()
    return (time.perf_counter() - t0) / repeats * 1e6


def run() -> dict:
    eng = engine_for()
    out = {}
    # the cat-3 ELCA query is the paper's flagship case
    for q in ("Q7", "Q8"):
        cat, kws = QUERIES[q]
        kk = eng.keyword_ids(kws)
        want = eng.query(kws, semantics="elca", index="dag", backend="scalar")

        # iteration 0 (baseline): paper-faithful scalar DAG search
        t_scalar = _time(lambda: eng.query(kws, "elca", "dag", "scalar"))
        emit(f"climb.{q}.0.scalar_dag", t_scalar, "baseline")

        # iteration 1: vectorized XLA engine (hypothesis: set intersection is
        # memory-parallel; batched searchsorted beats pointer chasing)
        got = eng.query(kws, "elca", "dag", "jax")
        np.testing.assert_array_equal(got, want)
        t_vec = _time(lambda: eng.query(kws, "elca", "dag", "jax"))
        emit(f"climb.{q}.1.vectorized", t_vec, f"speedup={t_scalar/t_vec:.2f}x")

        # iteration 2: tree-index vectorized (ablation: is the DAG or the
        # vectorization doing the work at this corpus size?)
        t_vec_tree = _time(lambda: eng.query(kws, "elca", "tree", "jax"))
        emit(f"climb.{q}.2.vectorized_tree", t_vec_tree,
             f"dag_gain={t_vec_tree/t_vec:.2f}x")

        out[q] = dict(scalar=t_scalar, vec=t_vec, vec_tree=t_vec_tree)

    # iteration 3: cross-query batching (hypothesis: the vectorized DAG's
    # loss came from per-RC dispatch; batching all 9 queries' RC work into
    # one launch per round amortizes it)
    queries = [kws for _, kws in QUERIES.values()]
    want = [eng.query(q, semantics="elca", index="dag", backend="scalar")
            for q in queries]
    got = eng.query_batch(queries, semantics="elca")
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
    t_seq = _time(
        lambda: [eng.query(q, "elca", "dag", "jax") for q in queries], repeats=3
    )
    t_batch = _time(lambda: eng.query_batch(queries, semantics="elca"), repeats=3)
    emit("climb.all9.3.sequential_vec_dag", t_seq, "9 queries")
    emit("climb.all9.3.batched_vec_dag", t_batch,
         f"speedup={t_seq / t_batch:.2f}x launches={eng.last_stats.data.get('launches')}")
    out["batch"] = dict(seq=t_seq, batch=t_batch)
    return out


if __name__ == "__main__":
    run()
