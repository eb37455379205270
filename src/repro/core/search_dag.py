"""DAG keyword search on the vectorized backend (frontier-batched RCs).

The paper searches redundancy components lazily and recursively.  JAX cannot
recurse data-dependently on device, so we run *frontier rounds*: every RC
referenced by a dummy result of the previous round is packed (same bucket
shapes) and searched in one batched device call.  Memoisation matches the
paper (each RC searched at most once per query); splicing nested results by
RCPM offsets is host-side numpy (data movement, not compute).
"""
from __future__ import annotations

import time

import numpy as np

from .components import IDClusterIndex
from .plan_cache import PlanCache

_FALLBACK_PLAN: PlanCache | None = None


def _plan_or_default(plan: PlanCache | None) -> PlanCache:
    """Callers without an engine share one module-level PlanCache."""
    global _FALLBACK_PLAN
    if plan is not None:
        return plan
    if _FALLBACK_PLAN is None:
        _FALLBACK_PLAN = PlanCache()
    return _FALLBACK_PLAN


def dag_search_vec(
    index: IDClusterIndex,
    kws: list[int],
    semantics: str = "slca",
    backend: str = "xla",
    stats: dict | None = None,
    plan: PlanCache | None = None,
    phases: list | None = None,
) -> np.ndarray:
    """Frontier-batched DAG search; returns sorted original node ids.

    ``phases`` (traced queries only) collects per-phase timing dicts: a
    ``plan.round`` per frontier round, over the plan cache's pack/launch
    steps (or the per-RC pallas dispatch loop, which runs outside the plan
    cache), and the ``plan.splice`` at the end.
    """
    plan = _plan_or_default(plan)
    launches0 = plan.launches
    pallas_launches = 0
    memo: dict[int, np.ndarray] = {}
    frontier = [0]
    rounds = 0
    while frontier:
        rounds += 1
        if phases is not None:
            r_at, r_p0 = _open_round(phases, rounds, len(frontier), len(kws))
        if backend == "pallas":
            from repro.kernels import ops as kernel_ops  # lazy: avoid cycle

            if phases is not None:
                w0, p0 = time.time() * 1e3, time.perf_counter()
            results = {
                rc: kernel_ops.run_query_pallas(
                    index.idlists(rc, kws), semantics=semantics
                )
                for rc in frontier
            }
            pallas_launches += len(frontier)
            if phases is not None:
                phases.append({
                    "name": "kernel.pallas_round",
                    "t0_ms": w0,
                    "dur_ms": (time.perf_counter() - p0) * 1e3,
                    "attrs": {"rcs": len(frontier), "round": rounds},
                })
        else:
            results = plan.run(
                [index.idlists(rc, kws) for rc in frontier],
                frontier,
                semantics=semantics,
                backend=backend,
                phases=phases,
            )
        nxt: list[int] = []
        for rc in frontier:
            res = results[rc]
            memo[rc] = res
            for nested in index.nested_rcs(rc, res):
                if nested not in memo and nested not in nxt:
                    nxt.append(nested)
        if phases is not None:
            _close_round(phases, r_at, r_p0)
        frontier = nxt
    if stats is not None:
        stats["rounds"] = rounds
        stats["rcs_searched"] = len(memo)
        if backend == "pallas":  # pallas dispatches per RC, outside the cache
            stats["launches"] = pallas_launches
        else:
            stats.update(plan.snapshot())  # lifetime counters (plan_* keys)
            stats["launches"] = plan.launches - launches0  # this call only
    if phases is not None:
        return _timed_splice(index, memo, semantics, phases)
    return _splice(index, memo, semantics)


def dag_search_vec_multi(
    index: IDClusterIndex,
    queries: list[list[int]],
    semantics: str = "slca",
    backend: str = "xla",
    stats: dict | None = None,
    plan: PlanCache | None = None,
    phases: list | None = None,
) -> list[np.ndarray]:
    """Serve a *batch* of queries: one device launch per frontier round.

    All (query, rc) work items of a round that share a keyword count are
    packed into one launch through the PlanCache — the cross-query batching
    that amortizes dispatch overhead (EXPERIMENTS.md §Perf, search iteration
    3) — and the cache's R-bucketing keeps the jit executable set shared
    across *calls*, not just rounds.  Memoisation is per query (different
    keyword sets ⇒ different RC results).

    ``backend`` picks the device path *inside* the shared batch search:
    "xla" (or "pallas" once :mod:`repro.kernels.ops` has registered its
    membership kernel) runs the jitted ``ca_search_batch``; "fused" hands
    the whole packed batch to the single-launch Pallas pipeline
    (:mod:`repro.kernels.fused_search`).  Either way every launch flows
    through the PlanCache, whose plan keys carry the backend name.

    ``phases`` (traced batches only) collects a ``plan.round`` per launch
    group of a frontier round, over its pack and launch phases, and one
    ``plan.splice`` per query, marked with its batch position
    (``"query"``) so that only that query's trace records it.
    """
    plan = _plan_or_default(plan)
    launches0 = plan.launches
    memos: list[dict[int, np.ndarray]] = [{} for _ in queries]
    frontier: list[tuple[int, int]] = [
        (qi, 0) for qi, kws in enumerate(queries) if all(k >= 0 for k in kws)
    ]
    rounds = 0
    while frontier:
        rounds += 1
        by_k: dict[int, list[tuple[int, int]]] = {}
        for qi, rc in frontier:
            by_k.setdefault(len(queries[qi]), []).append((qi, rc))
        nxt: list[tuple[int, int]] = []
        for k, items in by_k.items():
            if phases is not None:
                r_at, r_p0 = _open_round(phases, rounds, len(items), k)
            per_item = [index.idlists(rc, queries[qi]) for qi, rc in items]
            results = plan.run(
                per_item, items, semantics=semantics, backend=backend,
                phases=phases,
            )
            for qi, rc in items:
                res = results[(qi, rc)]
                memos[qi][rc] = res
                for nested in index.nested_rcs(rc, res):
                    if nested not in memos[qi]:
                        # claim with a placeholder so later items of this (and
                        # the next) round cannot re-enqueue the same RC; the
                        # claim is overwritten with the real result when its
                        # frontier round executes
                        memos[qi][nested] = None
                        nxt.append((qi, nested))
            if phases is not None:
                _close_round(phases, r_at, r_p0)
        frontier = nxt
    if stats is not None:
        stats["rounds"] = rounds
        stats.update(plan.snapshot())  # lifetime counters (plan_* keys)
        stats["launches"] = plan.launches - launches0  # this call only
    out = []
    for qi, kws in enumerate(queries):
        if not all(k >= 0 for k in kws):
            out.append(np.zeros(0, dtype=np.int64))
        elif phases is None:
            out.append(_splice(index, memos[qi], semantics))
        else:
            out.append(_timed_splice(index, memos[qi], semantics, phases, qi))
    return out


def _open_round(phases: list, rnd: int, items: int, k: int) -> tuple:
    """Append a ``plan.round`` phase, to be ended by :func:`_close_round`;
    returns its index and its perf-counter start."""
    phases.append({
        "name": "plan.round", "t0_ms": time.time() * 1e3, "dur_ms": 0.0,
        "attrs": {"round": rnd, "items": items, "k": k},
    })
    return len(phases) - 1, time.perf_counter()


def _close_round(phases: list, at: int, p0: float) -> None:
    """End the round at ``phases[at]``; the phases appended since, which
    name no parent of their own, become its children."""
    phases[at]["dur_ms"] = (time.perf_counter() - p0) * 1e3
    for p in phases[at + 1:]:
        p.setdefault("parent", at)


def _timed_splice(
    index: IDClusterIndex,
    memo: dict[int, np.ndarray],
    semantics: str,
    phases: list,
    query: int | None = None,
) -> np.ndarray:
    """:func:`_splice`, appending a ``plan.splice`` phase (of ``query``
    alone when a batch is spliced)."""
    w0, p0 = time.time() * 1e3, time.perf_counter()
    counts = {"dummies": 0}
    res = _splice(index, memo, semantics, counts)
    span = {
        "name": "plan.splice", "t0_ms": w0,
        "dur_ms": (time.perf_counter() - p0) * 1e3,
        "attrs": {"dummies": counts["dummies"], "results": int(res.size)},
    }
    if query is not None:
        span["query"] = query
    phases.append(span)
    return res


def _splice(
    index: IDClusterIndex,
    memo: dict[int, np.ndarray],
    semantics: str,
    counts: dict | None = None,
) -> np.ndarray:
    """Resolve dummy results through the RCPM (host-side materialization).

    The RCPM probe is vectorized per RC (one searchsorted over all results);
    only actual dummies loop.  ``counts`` (traced splices only) gets the
    number of dummies resolved under ``"dummies"``."""
    resolved: dict[int, np.ndarray] = {}
    rcs = index.rcs
    dummy_ids = rcs.dummy_ids

    def resolve(rc: int) -> np.ndarray:
        got = resolved.get(rc)
        if got is not None:
            return got
        root = index.rc_root_id(rc)
        res = memo.get(rc, np.zeros(0, dtype=np.int64))
        if dummy_ids.size and res.size:
            pos = np.searchsorted(dummy_ids, res)
            pos_c = np.clip(pos, 0, dummy_ids.size - 1)
            is_dummy = (dummy_ids[pos_c] == res) & (res != root)
        else:
            is_dummy = np.zeros(res.shape, dtype=bool)
        if not is_dummy.any():
            resolved[rc] = res
            return res
        if counts is not None:
            counts["dummies"] += int(is_dummy.sum())
        parts = [res[~is_dummy]]
        for _x, p in zip(res[is_dummy], pos_c[is_dummy]):
            parts.append(resolve(int(rcs.dummy_nested_rc[p])) + int(rcs.dummy_offset[p]))
        out = np.concatenate(parts)
        resolved[rc] = out
        return out

    return np.sort(resolve(0))
