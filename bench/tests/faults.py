"""Drive a whole run at a tiny size on the CPU with the timed path broken.

    python3 bench/tests/faults.py <root> <workload> <fault> <seed>

``root`` holds a BENCHMARK.json and a bench/ copy sized for the CPU (see
``test_bench_faults.py``).  The harness's look for a chip is skipped; every
other step of a run is the real one.  Prints the run's result line.
"""
from __future__ import annotations

import json
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402


def alter_answer():
    """A launch's answer is changed where it is produced."""
    from repro.core.plan_cache import PlanCache

    run = PlanCache.run

    def broken(self, per_item, keys, *a, **kw):
        out = run(self, per_item, keys, *a, **kw)
        for key, ids in out.items():
            if ids.size:
                out[key] = ids[:-1]
                break
        return out

    PlanCache.run = broken


def half_the_batch():
    """Half of each drained window is left out: those queries get nothing."""
    import repro.serve.service as service

    multi = service.dag_search_vec_multi

    def broken(index, queries, *a, **kw):
        keep = (len(queries) + 1) // 2
        done = multi(index, queries[:keep], *a, **kw)
        return done + [np.zeros(0, np.int64)] * (len(queries) - keep)

    service.dag_search_vec_multi = broken


def no_exchange():
    """The router merges the first shard's answer and leaves the others'."""
    from repro.cluster.router import ClusterService

    merge = ClusterService._merge

    def broken(self, state, trace=None):
        shards = state.shards
        state.shards = shards[:1]
        try:
            return merge(self, state, trace)
        finally:
            state.shards = shards

    ClusterService._merge = broken


FAULTS = {"none": lambda: None, "alter_answer": alter_answer,
          "half_the_batch": half_the_batch, "no_exchange": no_exchange}


def main() -> None:
    root, workload, fault, seed = sys.argv[1:5]
    FAULTS[fault]()
    from bench import harness

    res = harness.run(workload, int(seed), 3.0, False, T0,
                      require_chip=False, root=root)
    print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main()
