"""Spans and counters inside a launch and the frontier loop.

Covers: the phase spans a traced batch emits nest (round → pack / launch →
upload, device wait, read-back; splice under ``service.execute``), and a
query's splice goes under that query alone; an untraced launch never
waits for the device; the plan cache counts the bytes it uploads and how
many are padding; the process compile counter sees no compile for a warm
launch and a thread cluster reports it once, not once per shard.
"""
import jax
import numpy as np
import pytest

from repro.cluster import ClusterService
from repro.core import KeywordSearchEngine, PlanCache
from repro.core.search_dag import dag_search_vec_multi
from repro.data import QUERIES, generate_discogs_tree
from repro.obs import (
    COMPILES,
    TRACER,
    Tracer,
    make_traceparent,
    new_span_id,
    new_trace_id,
)
from repro.serve import QueryService

QS = [kws for _, kws in QUERIES.values()]
# emitted spans are rounded to the microsecond (t0 and duration apart)
ROUNDING_MS = 2e-3


@pytest.fixture(scope="module")
def tree():
    return generate_discogs_tree(n_releases=40, seed=7)


@pytest.fixture(scope="module")
def engine(tree) -> KeywordSearchEngine:
    return KeywordSearchEngine(tree)


def _interval(span):
    return span["t0_ms"], span["t0_ms"] + span["dur_ms"]


def _walk(nodes):
    for node in nodes:
        yield node
        yield from _walk(node["children"])


def _traced_pair(engine, first, second):
    """Two traced queries drained in one window; their span forests."""
    tids = [new_trace_id(), new_trace_id()]
    with QueryService(engine, max_batch=2, batch_window_ms=500.0) as svc:
        futs = [
            svc.submit(kws, "slca", trace=make_traceparent(tid, new_span_id()))
            for kws, tid in zip((first, second), tids)
        ]
        results = [f.result(timeout=120) for f in futs]
        assert svc.stats().data["max_batch_seen"] == 2
    return results, [Tracer.build_tree(TRACER.collect(t)) for t in tids]


def test_phase_spans_nest_and_splice_only_under_its_query(engine):
    # Q7 resolves dummies through the RCPM: two frontier rounds
    results, forests = _traced_pair(engine, QS[0], QS[6])
    assert results[0].size != results[1].size
    for res, forest in zip(results, forests):
        (execute,) = forest  # the caller's span was never recorded
        assert execute["name"] == "service.execute"
        nodes = list(_walk(forest))
        names = [n["name"] for n in nodes]
        for name in ("plan.round", "plan.pack", "kernel.ca_search",
                     "kernel.upload", "kernel.device", "kernel.readback"):
            assert name in names
        for parent in nodes:
            lo, hi = _interval(parent)
            for child in parent["children"]:
                c_lo, c_hi = _interval(child)
                assert lo - ROUNDING_MS <= c_lo and c_hi <= hi + ROUNDING_MS, (
                    parent["name"], child["name"])
        kids = {c["name"] for c in execute["children"]}
        assert kids == {"plan.round", "plan.splice"}
        for rnd in (n for n in nodes if n["name"] == "plan.round"):
            assert {c["name"] for c in rnd["children"]} == {
                "plan.pack", "kernel.ca_search"}
            assert {"round", "items", "k"} <= set(rnd["attrs"])
        for launch in (n for n in nodes if n["name"] == "kernel.ca_search"):
            assert [c["name"] for c in launch["children"]] == [
                "kernel.upload", "kernel.device", "kernel.readback"]
        (splice,) = [n for n in nodes if n["name"] == "plan.splice"]
        assert splice["attrs"]["results"] == res.size
    dummies = [
        next(n for n in _walk(f) if n["name"] == "plan.splice")["attrs"]["dummies"]
        for f in forests
    ]
    assert dummies[0] == 0 and dummies[1] > 0


@pytest.mark.parametrize("pinned", [False, True])
def test_untraced_launch_never_waits_for_the_device(engine, monkeypatch, pinned):
    def refuse(*_a, **_k):
        raise AssertionError("an untraced launch waited for the device")

    plan = PlanCache(device=jax.devices()[0] if pinned else None)
    queries = [engine.keyword_ids(q) for q in QS]
    monkeypatch.setattr(jax, "block_until_ready", refuse)
    got = dag_search_vec_multi(engine.cluster, queries, plan=plan)
    monkeypatch.undo()
    for kws, res in zip(QS, got):
        np.testing.assert_array_equal(
            res, engine.query(kws, semantics="slca", backend="scalar"))
    assert plan.launches > 0


def test_upload_bytes_count_the_packed_arrays(engine):
    plan = PlanCache()
    pairs = [q for q in QS if len(engine.keyword_ids(q)) == 2]
    assert len(pairs) >= 2
    items = [engine.cluster.idlists(0, engine.keyword_ids(q)) for q in pairs]
    keys = list(range(len(items)))
    batch, kept, sig = plan.pack(items, keys)
    phases: list = []
    plan.run(items, keys, phases=phases)
    assert plan.upload_bytes == sum(a.nbytes for a in batch.values())
    lengths = sum(len(lst) for i in kept for lst in items[i])
    assert plan.upload_bytes_useful == 4 * (
        3 * int(batch["n0"].sum()) + 2 * int(batch["other_n"].sum())
        + len(kept) * sig.k)
    assert plan.upload_bytes_useful < plan.upload_bytes
    pack = phases[0]["attrs"]
    assert pack["n_useful"] == lengths
    assert (pack["bytes"], pack["useful_bytes"]) == (
        plan.upload_bytes, plan.upload_bytes_useful)
    snap = plan.snapshot()
    assert snap["upload_bytes"] == plan.upload_bytes
    assert snap["upload_bytes_useful"] == plan.upload_bytes_useful


def test_compile_counter_sees_real_compiles_only(engine):
    plan = PlanCache()
    queries = [engine.keyword_ids(q) for q in QS[:4]]
    before = COMPILES.snapshot()
    dag_search_vec_multi(engine.cluster, queries, plan=plan)
    warm = COMPILES.snapshot()
    dag_search_vec_multi(engine.cluster, queries, plan=plan)
    assert COMPILES.snapshot()["compiles"] == warm["compiles"]
    jax.jit(lambda x: x * 3 + 1)(np.arange(5))  # a program never seen
    after = COMPILES.snapshot()
    assert after["compiles"] == warm["compiles"] + 1
    assert after["compile_s"] > warm["compile_s"] >= before["compile_s"]


def test_thread_cluster_reports_compiles_once(tree):
    jax.jit(lambda x: x - 7)(np.arange(3))  # at least one compile counted
    svc = ClusterService.from_tree(tree, 2, transport="thread")
    try:
        svc.submit(QS[0], "slca").result(timeout=120)
        data = svc.stats().data
        assert data["num_shards"] == 2
        assert data["compiles"] == COMPILES.snapshot()["compiles"] > 0
        assert data["upload_bytes"] >= data["upload_bytes_useful"] > 0
    finally:
        svc.close()
