"""QueryService + PlanCache behaviour.

Covers: concurrent service results equal sequential engine.query on every
paper query x both semantics; the PlanCache serves the second same-shaped
window without any new executable (no new compiles, zero new misses);
mixed-semantics windows; unknown keywords; stats surface.
"""
import numpy as np
import pytest

from repro.core import KeywordSearchEngine, PlanCache
from repro.data import QUERIES, generate_discogs_tree
from repro.obs import COMPILES
from repro.serve import QueryService


@pytest.fixture(scope="module")
def engine() -> KeywordSearchEngine:
    return KeywordSearchEngine(generate_discogs_tree(n_releases=60, seed=7))


QS = [kws for _, kws in QUERIES.values()]


def test_service_matches_sequential(engine):
    with QueryService(engine, max_batch=32, batch_window_ms=2.0) as svc:
        for sem in ("slca", "elca"):
            got = svc.map(QS, semantics=sem)
            for kws, res in zip(QS, got):
                np.testing.assert_array_equal(
                    res,
                    engine.query(kws, semantics=sem, backend="scalar"),
                    err_msg=f"{kws} {sem}",
                )


def test_plan_cache_reused_across_service_calls(engine):
    """Second same-shaped window: zero new compiles, zero new plan misses."""
    with QueryService(engine, max_batch=32, batch_window_ms=2.0) as svc:
        first = svc.map(QS, semantics="slca")  # warm: compiles what it needs
        misses0 = engine.plan_cache.misses
        launches0 = engine.plan_cache.launches
        compiles0 = COMPILES.snapshot()["compiles"]
        second = svc.map(QS, semantics="slca")
        assert engine.plan_cache.misses == misses0, "second call compiled a new plan"
        assert COMPILES.snapshot()["compiles"] == compiles0, "XLA compiled again"
        hits = engine.plan_cache.launches - launches0
        assert hits > 0 and engine.plan_cache.hits >= hits
    for a, b in zip(first, second):
        np.testing.assert_array_equal(a, b)


def test_mixed_semantics_window(engine):
    with QueryService(engine, max_batch=32, batch_window_ms=5.0) as svc:
        futs = [
            svc.submit(QS[0], "slca"),
            svc.submit(QS[3], "elca"),
            svc.submit(QS[6], "slca"),
        ]
        want = [
            engine.query(QS[0], semantics="slca", backend="scalar"),
            engine.query(QS[3], semantics="elca", backend="scalar"),
            engine.query(QS[6], semantics="slca", backend="scalar"),
        ]
        for f, w in zip(futs, want):
            np.testing.assert_array_equal(f.result(timeout=120), w)


def test_unknown_keyword_resolves_empty(engine):
    with QueryService(engine) as svc:
        assert svc.query(["zzz-not-a-word"]).size == 0


def test_bad_semantics_rejected(engine):
    with QueryService(engine) as svc:
        with pytest.raises(ValueError, match="semantics"):
            svc.submit(QS[0], "lca")


def test_stats_surface(engine):
    with QueryService(engine, max_batch=8, batch_window_ms=1.0) as svc:
        svc.map(QS, semantics="slca")
        stats = svc.stats().summary()
    assert stats["queries"] == len(QS)
    assert stats["batches"] >= 1 and stats["launches"] >= 1
    assert stats["p99_ms"] >= stats["p50_ms"] > 0
    assert 0.0 <= stats["plan_hit_rate"] <= 1.0


def test_submit_after_close_raises(engine):
    svc = QueryService(engine)
    svc.close()
    with pytest.raises(RuntimeError, match="closed"):
        svc.submit(QS[0])


def test_submit_after_close_is_immediate(engine):
    """The rejection must not depend on drain-thread teardown timing: it
    raises even when the drain thread is long gone, and close() is
    idempotent."""
    svc = QueryService(engine)
    svc.close()
    svc.close()  # second close is a no-op, not an error
    assert not svc._thread.is_alive()
    for _ in range(3):
        with pytest.raises(RuntimeError, match="closed"):
            svc.submit(QS[0])
    with pytest.raises(RuntimeError, match="closed"):
        svc.query(QS[0])


def test_submit_after_context_exit_raises(engine):
    with QueryService(engine) as svc:
        svc.query(QS[0])
    with pytest.raises(RuntimeError, match="closed"):
        svc.submit(QS[0])


def test_queue_depth_surfaces_in_stats(engine):
    svc = QueryService(engine, max_batch=64, batch_window_ms=60_000.0)
    try:
        assert svc.queue_depth == 0
        futs = [svc.submit(QS[0]), svc.submit(QS[1])]
        # parked drain window: both queries sit in the admission queue
        assert svc.queue_depth == 2
        assert svc.stats().summary()["queue_depth"] == 2
    finally:
        svc.close()
    for f in futs:
        assert f.result(timeout=120) is not None
    assert svc.stats().summary()["queue_depth"] == 0


def test_plan_counters_in_summary(engine):
    with QueryService(engine, batch_window_ms=1.0) as svc:
        svc.map(QS, semantics="slca")
        s = svc.stats().summary()
    for key in ("plan_launches_total", "plan_hits", "plan_misses", "queue_depth"):
        assert key in s, key
    assert s["plan_hits"] + s["plan_misses"] == s["plan_launches_total"]


@pytest.mark.parametrize("backend", ["scalar", "pallas", "fused"])
def test_service_backends_match_scalar(engine, backend):
    queries = QS[:4]
    with QueryService(engine, backend=backend, batch_window_ms=1.0) as svc:
        for sem in ("slca", "elca"):
            got = svc.map(queries, semantics=sem)
            for kws, res in zip(queries, got):
                np.testing.assert_array_equal(
                    res,
                    engine.query(kws, semantics=sem, backend="scalar"),
                    err_msg=f"{backend} {kws} {sem}",
                )


def test_service_rejects_unknown_backend(engine):
    with pytest.raises(ValueError, match="backend"):
        QueryService(engine, backend="cuda")


def test_query_stats_merge():
    from repro.core import QueryStats

    a = QueryStats(
        data={
            "queries": 2, "plan_hits": 3, "plan_launches_total": 4,
            "plan_hit_rate": 0.75, "note": "x",
        }
    )
    a.latencies_ms = [1.0, 2.0]
    b = QueryStats(
        data={
            "queries": 5, "plan_hits": 0, "plan_misses": 4,
            "plan_launches_total": 4, "plan_hit_rate": 0.0,
        }
    )
    b.latencies_ms = [3.0]
    merged = QueryStats.merge([a, b])
    assert merged.data["queries"] == 7
    assert merged.data["plan_hits"] == 3
    assert merged.data["plan_misses"] == 4
    assert merged.data["note"] == "x"
    # ratios are recomputed from merged counters, never summed
    assert merged.data["plan_hit_rate"] == round(3 / 8, 4)
    assert merged.latencies_ms == [1.0, 2.0, 3.0]


def test_plan_cache_row_bucketing():
    """Different work-item counts in the same R bucket share one plan."""
    from repro.core.idlist import IDList

    def lst(ids):
        ids = np.asarray(ids, np.int32)
        return IDList(
            ids=ids,
            pidpos=np.full(ids.shape, -1, np.int32),
            ndesc=np.ones(ids.shape, np.int32),
        )

    item = [lst([1, 5, 9]), lst([1, 5, 7, 9])]
    plan = PlanCache()
    r3 = plan.run([item] * 3, ["a", "b", "c"], semantics="slca")
    assert plan.misses == 1 and plan.launches == 1
    r4 = plan.run([item] * 4, ["a", "b", "c", "d"], semantics="slca")  # R 3->4,
    assert plan.misses == 1, "same R bucket must not re-pack a new plan"  # same bucket
    assert plan.hits == 1
    np.testing.assert_array_equal(r3["a"], r4["d"])
    r5 = plan.run([item] * 5, list("abcde"), semantics="slca")  # R=5 -> bucket 8
    assert plan.misses == 2 and plan.hits == 1
