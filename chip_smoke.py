#!/usr/bin/env python3
"""Smoke test of the served keyword-search path on a TPU.

    python chip_smoke.py                          # one chip
    python chip_smoke.py --chips 4                # four chips, a shard each
    python chip_smoke.py --releases 2000          # a smaller corpus

One process, the entry points a deployment uses: a Discogs-shaped corpus
generated from ``--seed`` is published with ``build_cluster``, served by
``ClusterService.from_dir(transport="thread")`` behind an in-process HTTP
``Gateway``, and the paper's queries Q1-Q9 are sent over HTTP under SLCA and
ELCA.  Every answer must be byte-identical to the scalar reference engine
(``KeywordSearchEngine.query(..., index="tree", backend="scalar")``).

Default: a 1-shard cluster, once per device backend (``fused`` and ``jax``).
``--chips N``: only an N-shard cluster on the ``fused`` backend, shard ``i``
pinned to device ``i``; every device must show work.

The last line of stdout is ``{"ok": true, "device": {...}}``, printed only
when every check passed.  No TPU (e.g. ``JAX_PLATFORMS=cpu``), a mismatch or
any error exits non-zero without it.
"""
from __future__ import annotations

import argparse
import http.client
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")
# a cold query compiles every bucket variant it meets (seconds each)
COLD_QUERY_TIMEOUT_S = 600.0


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def log(msg: str) -> None:
    print(msg, flush=True)


def post(port: int, body: dict) -> dict:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900)
    try:
        conn.request("POST", "/query", body=json.dumps(body))
        resp = conn.getresponse()
        obj = json.loads(resp.read().decode())
        if resp.status != 200:
            raise RuntimeError(f"HTTP {resp.status}: {obj.get('error')}")
        return obj
    finally:
        conn.close()


def serve_and_check(path, backend, cases, want):
    """Serve the cluster at ``path`` on ``backend``: the queries over HTTP
    (cold: compiles), then again through the router (warm, the gateway's
    edge cache bypassed).  Returns (checks passed, stats, launches per
    shard, fallbacks per shard, cold seconds, warm seconds)."""
    import numpy as np

    from repro.api import Query
    from repro.cluster import ClusterService
    from repro.gateway import Gateway

    svc = ClusterService.from_dir(
        path, transport="thread", backends=backend, batch_window_ms=1.0,
        op_timeout=COLD_QUERY_TIMEOUT_S,
    )
    sizes = [w.engine.index_sizes() for w in svc.pool.workers]
    log(f"  {backend} index per shard: {sizes}")
    passed = 0
    with Gateway(svc, own_service=True).start() as gw:
        t0 = time.perf_counter()
        for name, sem, kws in cases:
            t1 = time.perf_counter()
            obj = post(gw.port, Query.make(kws, sem, backend=backend).to_dict())
            got = np.asarray(obj["ids"], dtype=np.int64)
            ok = got.tobytes() == want[name, sem].tobytes()
            passed += ok
            log(
                f"  {backend:5s} {name} {sem}: {'==' if ok else '!='} "
                f"{got.size} ids (scalar {want[name, sem].size}) cold_ms="
                f"{(time.perf_counter() - t1) * 1e3:.1f}"
            )
        cold_s = time.perf_counter() - t0
        warm_ms = []
        for name, sem, kws in cases:
            t0 = time.perf_counter()
            res = svc.submit(Query.make(kws, sem, backend=backend)).result()
            warm_ms.append((time.perf_counter() - t0) * 1e3)
            if res.ids.astype(np.int64).tobytes() != want[name, sem].tobytes():
                fail(f"{backend} {name} {sem}: warm answer differs")
        warm_s = sum(warm_ms) / 1e3
        log(f"  {backend} warm ms per query: " + " ".join(
            f"{name}/{sem}={ms:.1f}"
            for (name, sem, _), ms in zip(cases, warm_ms)
        ))
        stats = svc.stats().data
        caches = [w.engine.plan_cache for w in svc.pool.workers]
        launches = [c.launches for c in caches]
        fallbacks = [c.fused_fallbacks for c in caches]
    return passed, stats, launches, fallbacks, cold_s, warm_s


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--releases", type=int, default=100_000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--chips", type=int, default=0,
        help="run only an N-shard cluster, one shard per chip",
    )
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(SRC, "repro")):
        fail(f"the repro package is not at {SRC}: run from a checkout")
    sys.path.insert(0, SRC)

    import jax
    import numpy as np

    from repro.device import enable_compile_cache, pallas_interpret

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU found: JAX runs on {dev.platform!r}")
    if args.chips and len(devices) < args.chips:
        fail(f"--chips {args.chips} but JAX sees {len(devices)} devices")
    log(
        f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} pallas_interpret={pallas_interpret()} "
        f"compile_cache={cache_dir}"
    )

    from repro.cluster import build_cluster
    from repro.core import KeywordSearchEngine
    from repro.data import QUERIES, generate_discogs_tree

    t0 = time.perf_counter()
    tree = generate_discogs_tree(n_releases=args.releases, seed=args.seed)
    gen_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    ref = KeywordSearchEngine(tree, build_dag=False)
    cases = [
        (name, sem, kws) for name, (_, kws) in QUERIES.items()
        for sem in ("slca", "elca")
    ]
    want = {
        (name, sem): np.asarray(
            ref.query(kws, sem, index="tree", backend="scalar"), np.int64
        )
        for name, sem, kws in cases
    }
    ref_s = time.perf_counter() - t0
    shards = args.chips or 1
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as path:
        t0 = time.perf_counter()
        manifest = build_cluster(tree, shards, path)
        build_s = time.perf_counter() - t0
        log(
            f"corpus: releases={args.releases} seed={args.seed} "
            f"tree_nodes={tree.num_nodes} tree_index_entries="
            f"{ref.base.num_entries()} keywords={len(tree.vocab)} "
            f"shards={manifest['num_shards']}"
        )
        log(
            f"host: generate_s={gen_s:.3f} scalar_reference_s={ref_s:.3f} "
            f"build_cluster_s={build_s:.3f}"
        )
        del tree, ref

        backends = ["fused"] if args.chips else ["fused", "jax"]
        total = 0
        for backend in backends:
            passed, stats, launches, fallbacks, cold_s, warm_s = (
                serve_and_check(path, backend, cases, want)
            )
            total += passed
            fused = (
                stats["plan_launches_total"] - stats["fused_fallbacks"]
                if backend == "fused"
                else 0
            )
            log(
                f"{backend}: checks={passed}/{len(cases)} "
                f"cold_s={cold_s:.3f} warm_s={warm_s:.3f} "
                f"launches={stats['plan_launches_total']} "
                f"compiled_fused_launches={fused} "
                f"fused_fallbacks={stats['fused_fallbacks']} "
                f"plan_misses={stats['plan_misses']} "
                f"launches_per_shard={launches} "
                f"fallbacks_per_shard={fallbacks}"
            )
            if passed != len(cases):
                fail(f"{backend}: {len(cases) - passed} answers differ")
            if backend == "fused" and fused <= 0:
                fail("no fused launch ran compiled")
            if min(launches) <= 0:
                fail(f"a shard launched nothing: {launches}")
    if args.chips:
        peaks = [
            d.memory_stats()["peak_bytes_in_use"]
            for d in devices[: args.chips]
        ]
        log(f"peak_bytes_in_use per device: {peaks}")
        if min(peaks) <= 0:
            fail("a device shows no work")
    log(f"checks passed: {total}/{len(cases) * len(backends)}")
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(devices),
        },
    }), flush=True)


if __name__ == "__main__":
    main()
