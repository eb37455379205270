#!/usr/bin/env python3
"""Record the small device trace that ``test_bench_devtrace.py`` reads.

    python3 bench/tests/record_trace.py --out bench/tests/data

On a TPU: a 2,000-release cluster on one chip, a few warm queries through
the router while the profiler records about half a second.  Writes
``trace.xplane.pb`` and ``trace.json`` (the mark's wall time, the window,
and the span forests of the queries traced in it); the test reads the
trace xz-compressed (``xz -9 trace.xplane.pb``).
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    from bench import devtrace, harness
    from bench.corpus import QUERIES, generate

    harness.compile_cache()
    harness.check_devices(1)
    Query = harness.program().Query
    from repro.obs import TRACER, make_traceparent, new_span_id, new_trace_id

    work = tempfile.mkdtemp(prefix="bench-record-")
    svc = None
    try:
        harness.publish(generate(2000, 3), 1, os.path.join(work, "c"))
        svc = harness.serve(json.load(open(os.path.join(
            ROOT, "bench", "configs", "discogs-100k.json"))),
            os.path.join(work, "c"))
        queries = [(kws, sem) for _, kws in QUERIES.values()
                   for sem in ("slca", "elca")]
        for kws, sem in queries:  # compile every shape first
            svc.submit(Query.make(kws, sem)).result(600)
        prof: dict = {}
        thread = harness.profile_window(os.path.join(work, "t"), 0.0, 0.6,
                                        prof)
        time.sleep(0.1)
        ids = []
        for kws, sem in queries:
            tid = new_trace_id()
            q = Query.make(kws, sem).with_trace(
                make_traceparent(tid, new_span_id()))
            svc.submit(q).result(600)
            ids.append(tid)
        thread.join()
        from repro.obs import Tracer

        forests = [Tracer.build_tree(TRACER.collect(t)) for t in ids]
        os.makedirs(args.out, exist_ok=True)
        shutil.copy(devtrace.find_xplane(os.path.join(work, "t")),
                    os.path.join(args.out, "trace.xplane.pb"))
        with open(os.path.join(args.out, "trace.json"), "w") as f:
            json.dump({"mark_wall_ms": prof["mark_wall_ms"],
                       "window_ms": prof["window_ms"],
                       "traces": forests}, f)
    finally:
        if svc is not None:
            svc.close()
        shutil.rmtree(work, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
