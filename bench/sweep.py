#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest offered rate served with
completions keeping pace and no growing backlog.

    python3 bench/sweep.py --workload discogs-100k.facet-80 --seed 5 \\
        --seconds 20 --rates 4,8,12,16,24

One process sets the cell's cluster up once, then runs one window per rate
(a fresh gateway each, so each starts with an empty edge cache), each
warmed as a run warms its window.  Prints, per rate, the rate answered, the
latency quartiles and tail, and the median latency of the window's last
third over its first third (above 1.5: the queue grew).  The cell's
``rate_per_s`` is then set by hand to 0.8 of the knee.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    import numpy as np

    from bench import harness, traffic
    from bench.corpus import generate

    cell = harness.resolve(harness.load_benchmark(), args.workload, False)
    cfg = cell.config
    harness.compile_cache()
    devs = harness.check_devices(int(cell.workload["chips"]))
    Gateway = harness.program().Gateway
    rates = [float(r) for r in args.rates.split(",")]
    corpus = generate(int(cfg["releases"]), args.seed)
    pool = traffic.pool(cell.traffic)
    schedules = {}
    for rate in rates:
        spec = dict(cell.traffic, rate_per_s=rate)
        schedules[rate] = traffic.open_schedule(spec, args.seconds, args.seed)
    workdir = tempfile.mkdtemp(prefix="bench-sweep-")
    svc = None
    try:
        harness.publish(corpus, int(cfg["shards"]), workdir)
        svc = harness.serve(cfg, workdir)
        timeout = float(cfg["op_timeout_s"])
        pairs = harness.distinct(
            (i, s) for sch in schedules.values() for _, i, s in sch
        )
        t = time.perf_counter()
        harness.warm_singles(svc, pairs, pool, timeout)
        print(f"sweep: device={devs[0].device_kind} distinct_pairs="
              f"{len(pairs)} singles_s={time.perf_counter() - t}", flush=True)
        for rate in rates:
            sch = schedules[rate]
            t = time.perf_counter()
            steps = harness.warm_open(svc, sch, pool, timeout, singles=False)
            print(f"sweep: rate={rate} warm_s={time.perf_counter() - t} "
                  f"plan_misses_by_step={steps}", flush=True)
            m0 = harness.misses(svc)
            with Gateway(svc, cache_entries=int(cfg["cache_entries"]),
                         trace=False) as gw:
                gw.start()
                child = harness.start_client({
                    "mode": "open", "port": gw.port, "timeout_s": timeout,
                    "requests": [[at, pool[i], s] for at, i, s in sch],
                })
                child.stdin.write("go\n")
                child.stdin.flush()
                raw = harness.finish_client(
                    child, args.seconds + 2 * harness.ANSWER_WAIT_S + 60
                )
            recs = raw["records"]
            ok = [r for r in recs if r[4] == 200]
            lat = np.array([(r[3] - r[1]) * 1e3 for r in ok])
            late = np.array([(r[2] - r[1]) * 1e3 for r in recs])
            by_t = sorted(ok, key=lambda r: r[1])
            third = max(len(by_t) // 3, 1)
            first = np.median([(r[3] - r[1]) for r in by_t[:third]])
            last = np.median([(r[3] - r[1]) for r in by_t[-third:]])
            print("sweep: " + json.dumps({
                "offered_per_s": rate, "requests": len(recs),
                "answered": len(ok),
                "answered_per_s": len(ok) / max(r[3] for r in ok),
                "p50_ms": float(np.percentile(lat, 50)),
                "p95_ms": float(np.percentile(lat, 95)),
                "p99_ms": float(np.percentile(lat, 99)),
                "late_p95_ms": float(np.percentile(late, 95)),
                "growth": float(last / first),
                "window_misses": harness.misses(svc) - m0,
            }), flush=True)
    finally:
        if svc is not None:
            svc.close()
        shutil.rmtree(workdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
