"""ThreadWorker: the shard engine lives in-process (PR 2's transport).

One :class:`~repro.serve.service.QueryService` drain thread per shard,
exactly what ``router.py`` used to build inline — extracted here so the
router sees only the :class:`~repro.cluster.workers.base.Worker` seam.
``device`` pins the shard's launches to one JAX device: one process drives
every chip of a host, a shard per chip.
"""
from __future__ import annotations

from concurrent.futures import Future

from repro.core.engine import KeywordSearchEngine, QueryStats
from repro.obs import TRACER
from repro.serve.service import QueryService

from ..partition import ShardSpec, doc_roots
from .base import shard_doc_stats


class ThreadWorker:
    """One shard: engine + drain service + document-level query stats."""

    transport = "thread"

    def __init__(
        self,
        spec: ShardSpec,
        engine: KeywordSearchEngine,
        *,
        backend: str = "jax",
        max_batch: int = 64,
        batch_window_ms: float = 2.0,
        device=None,
    ):
        self.spec = spec
        self.engine = engine
        self.service = QueryService(
            engine,
            max_batch=max_batch,
            batch_window_ms=batch_window_ms,
            backend=backend,
            device=device,
        )
        # local ids of this shard's document roots (children of the replica
        # root), ascending — the probe set for doc_stats
        self._doc_roots = doc_roots(engine.tree)

    def submit(self, keywords: list[str], semantics: str, trace=None) -> Future:
        return self.service.submit(keywords, semantics, trace=trace)

    def doc_stats(self, kw_ids: list[int], trace=None) -> Future:
        fut: Future = Future()
        span = TRACER.start(trace, "worker.doc_stats", shard=self.spec.index)
        try:
            fut.set_result(
                shard_doc_stats(
                    self.engine.base.containment, self._doc_roots, kw_ids
                )
            )
        except Exception as e:
            span.annotate(error=f"{type(e).__name__}: {e}")
            fut.set_exception(e)
        span.end()
        return fut

    def health(self) -> tuple[int, int]:
        return 1, 1 if self.service._thread.is_alive() else 0

    def stats(self) -> QueryStats:
        return self.service.stats()

    def drain(self, timeout: float = 30.0) -> None:
        # QueryService.close drains the queue and stops the thread; the
        # engine stays readable, so doc_stats/stats keep working — exactly
        # the "drained but answerable" phase the router's shutdown needs
        self.service.close(timeout)

    def close(self, timeout: float = 30.0) -> None:
        self.service.close(timeout)
