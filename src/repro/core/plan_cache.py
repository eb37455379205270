"""PlanCache — shared shape-bucket registry for batched device searches.

Every vectorized DAG search round packs a set of work items (one list-of-
IDLists each) into padded device arrays and calls the jitted
``ca_search_batch``.  jit caches executables by *shape*, so the number of
distinct packed shapes is the number of compiles the process ever pays.
This module owns that shape policy in one place:

  * list lengths pad to power-of-two buckets (as before), and
  * the leading work-item axis R now *also* pads to a power-of-two bucket —
    previously every distinct frontier size compiled a fresh executable;
    a serving process saw a new R almost every batch.

Padded rows carry ``n0 = 0`` (no valid entries), which the kernel already
maps to an empty result, so R-padding is free of special cases.

The cache is engine-owned (each :class:`KeywordSearchEngine` carries one) but
can be shared across engines serving the same process; hit/miss/launch
counters feed ``QueryStats`` and the service benchmarks, among them the
bytes each launch uploads and how many of them are not padding.
``device`` pins every launch to one JAX device (a shard per chip); None
leaves placement to JAX's default device.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import numpy as np

from repro.obs import COMPILES

from .idlist import IDList
from .search_vec import INT_PAD, bucket, ca_search_batch

_EMPTY = np.zeros(0, dtype=np.int64)
_INT32 = 4  # every packed array is int32


@dataclass(frozen=True)
class PlanKey:
    """Static shape signature of one packed launch (one jit executable)."""

    rows: int  # R bucket (work items)
    k: int  # keywords per item
    m0: int  # shortest-list bucket
    mo: int  # other-list bucket
    semantics: str
    backend: str


class PlanCache:
    """Packs work items to bucketed shapes and tracks executable reuse."""

    def __init__(self, backend: str = "xla", min_rows: int = 1, device=None):
        self.backend = backend
        self.min_rows = min_rows
        self.device = device
        self.launches = 0  # device calls issued
        self.hits = 0  # launches whose shape signature was seen before
        self.misses = 0  # launches that compiled a new executable
        self.rows_padded = 0  # wasted rows across all launches (R padding)
        self.fused_fallbacks = 0  # fused launches demoted to chained (shape cap)
        self.upload_bytes = 0  # bytes of the packed arrays launched
        self.upload_bytes_useful = 0  # ... of them holding list entries
        self._seen: set[tuple] = set()
        COMPILES.install()

    # ------------------------------------------------------------------ #
    @property
    def plans(self) -> int:
        """Distinct shape signatures this cache has launched."""
        return len(self._seen)

    def hit_rate(self) -> float:
        return self.hits / self.launches if self.launches else 0.0

    def snapshot(self) -> dict:
        return {
            "plan_launches_total": self.launches,
            "plan_hits": self.hits,
            "plan_misses": self.misses,
            "plans": self.plans,
            "plan_hit_rate": round(self.hit_rate(), 4),
            "rows_padded": self.rows_padded,
            "fused_fallbacks": self.fused_fallbacks,
            "upload_bytes": self.upload_bytes,
            "upload_bytes_useful": self.upload_bytes_useful,
        }

    def reset_counters(self) -> None:
        """Zero the counters but keep the seen-shape set (plans stay warm)."""
        self.launches = self.hits = self.misses = self.rows_padded = 0
        self.upload_bytes = self.upload_bytes_useful = 0

    def _xla_batch(self, batch: dict, semantics: str, backend: str,
                   stamps: list | None = None):
        """The jitted batch search, on this cache's device when pinned.

        ``stamps`` (traced launches only) gets the perf-counter times at
        which the batch was on the device and the results were ready: only
        a traced launch uploads explicitly and waits for the device."""
        if stamps is None:
            if self.device is not None:
                batch = jax.device_put(batch, self.device)
            return ca_search_batch(**batch, semantics=semantics, backend=backend)
        batch = jax.block_until_ready(jax.device_put(batch, self.device))
        stamps.append(time.perf_counter())
        out = jax.block_until_ready(
            ca_search_batch(**batch, semantics=semantics, backend=backend)
        )
        stamps.append(time.perf_counter())
        return out

    # ------------------------------------------------------------------ #
    def pack(
        self,
        per_item: list[list[IDList]],
        keys: list,
        semantics: str = "slca",
        backend: str = "xla",
    ):
        """Pad items' lists to shared buckets; stack along a bucketed R axis.

        Items with any empty list are dropped (their intersection is empty).
        Returns (batch dict | None, kept_keys, plan_key | None).
        """
        keep = [i for i, ls in enumerate(per_item) if ls and all(len(l) for l in ls)]
        if not keep:
            return None, [], None
        keys = [keys[i] for i in keep]
        per_item = [per_item[i] for i in keep]
        k = len(per_item[0])
        m0 = bucket(max(min(len(l) for l in ls) for ls in per_item))
        mo = bucket(max(max(len(l) for l in ls) for ls in per_item))
        rows = bucket(len(keys), minimum=self.min_rows)
        self.rows_padded += rows - len(keys)

        ids0 = np.full((rows, m0), INT_PAD, np.int32)
        pid0 = np.full((rows, m0), -1, np.int32)
        nd0 = np.zeros((rows, m0), np.int32)
        oids = np.full((rows, k - 1, mo), INT_PAD, np.int32)
        ond = np.zeros((rows, k - 1, mo), np.int32)
        n0 = np.zeros((rows,), np.int32)
        on = np.zeros((rows, k - 1), np.int32)
        for r, ls in enumerate(per_item):
            order = np.argsort([len(l) for l in ls], kind="stable")
            ls = [ls[i] for i in order]
            l0 = ls[0]
            n = len(l0)
            ids0[r, :n] = l0.ids
            nd0[r, :n] = l0.ndesc
            pid0[r, :n] = np.where(
                l0.pidpos >= 0, l0.ids[np.clip(l0.pidpos, 0, n - 1)], -1
            )
            n0[r] = n
            for j, l in enumerate(ls[1:]):
                oids[r, j, : len(l)] = l.ids
                ond[r, j, : len(l)] = l.ndesc
                on[r, j] = len(l)
        # numpy on purpose: jit device_puts these on call, while the fused
        # backend's host window bookkeeping reads them without a device trip
        batch = dict(
            ids0=ids0,
            pid0=pid0,
            ndesc0=nd0,
            other_ids=oids,
            other_ndesc=ond,
            n0=n0,
            other_n=on,
        )
        return batch, keys, PlanKey(rows, k, m0, mo, semantics, backend)

    # ------------------------------------------------------------------ #
    def run(
        self,
        per_item: list[list[IDList]],
        keys: list,
        semantics: str = "slca",
        backend: str | None = None,
        phases: list | None = None,
    ) -> dict:
        """Search every work item in one (bucketed) launch.

        Returns {key: sorted int64 result ids} for *every* input key; items
        dropped at packing (an empty list => empty intersection) map to the
        empty result.

        ``phases`` (when a traced query asks for timing) collects
        ``{"name", "t0_ms", "dur_ms", "attrs"}`` dicts for the pack step
        and the kernel launch, and below the launch its upload, device
        wait and read-back (``"parent"`` indexes the launch's dict) — the
        per-phase spans the tracing layer attaches under the query's
        execute span.  ``None`` (every untraced call) skips all timing
        work.
        """
        backend = backend or self.backend
        out = {key: _EMPTY for key in keys}
        if phases is not None:
            w0, p0 = time.time() * 1e3, time.perf_counter()
        batch, kept, sig = self.pack(per_item, keys, semantics, backend)
        if batch is None:
            return out
        if sig in self._seen:
            self.hits += 1
            hit = True
        else:
            self._seen.add(sig)
            self.misses += 1
            hit = False
        self.launches += 1
        nbytes = sum(a.nbytes for a in batch.values())
        n0, on = int(batch["n0"].sum()), int(batch["other_n"].sum())
        useful = _INT32 * (3 * n0 + 2 * on + len(kept) * sig.k)
        self.upload_bytes += nbytes
        self.upload_bytes_useful += useful
        stamps = None
        if phases is not None:
            p1 = time.perf_counter()
            phases.append({
                "name": "plan.pack", "t0_ms": w0, "dur_ms": (p1 - p0) * 1e3,
                "attrs": {
                    "rows": sig.rows, "k": sig.k, "m0": sig.m0, "mo": sig.mo,
                    "plan_hit": hit, "n_useful": n0 + on, "bytes": nbytes,
                    "useful_bytes": useful,
                },
            })
            stamps = [p1]
        if backend == "fused":
            # lazy: fused_search pulls in pallas; PlanCache stays importable
            # without it (scalar-only deployments)
            from repro.kernels.fused_search import (
                MAX_FUSED_M0,
                fused_search_batch,
            )

            if sig.m0 > MAX_FUSED_M0:
                # giant shortest list: the fused variant would blow VMEM —
                # demote this launch to the chained batch path
                self.fused_fallbacks += 1
                ids, mask = self._xla_batch(batch, semantics, "xla", stamps)
                kstats = {"fallback": True}
            else:
                # uploads and reads back inside the kernel's wrapper, so a
                # traced fused launch has no child spans
                kstats = {}
                stamps = None
                ids, mask = fused_search_batch(
                    **batch, semantics=semantics, stats=kstats,
                    device=self.device,
                )
        else:
            ids, mask = self._xla_batch(batch, semantics, backend, stamps)
            kstats = None
        ids = np.asarray(ids)
        mask = np.asarray(mask)
        if phases is not None:
            p_end = time.perf_counter()

            def wall(p: float) -> float:
                return w0 + (p - p0) * 1e3

            attrs = {"backend": backend, "semantics": semantics, "rows": sig.rows}
            fused = kstats is not None and not kstats.get("fallback")
            if fused:
                attrs.update(kstats)
            at = len(phases)
            phases.append({
                "name": "kernel.fused_round" if fused else "kernel.ca_search",
                "t0_ms": wall(p1), "dur_ms": (p_end - p1) * 1e3,
                "attrs": attrs,
            })
            if stamps is not None:
                stamps.append(p_end)
                for name, a, b in zip(
                    ("kernel.upload", "kernel.device", "kernel.readback"),
                    stamps, stamps[1:],
                ):
                    phases.append({
                        "name": name, "t0_ms": wall(a), "dur_ms": (b - a) * 1e3,
                        "parent": at,
                    })
        for r, key in enumerate(kept):
            out[key] = ids[r][mask[r]].astype(np.int64)
        return out
