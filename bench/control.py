#!/usr/bin/env python3
"""The comparison's control: the reference with a broken guarantee put in
the program's place, at a cell's own size.

    python3 bench/control.py --workload discogs-100k.facet-80 \\
        --seconds 30 --seeds 11,12,13

For each seed, the cell's corpus and window of requests (open loop: every
request of the schedule; closed loop: every query ready for every client)
are answered by ``Reference.answer(..., control=True)``, which serves the
SLCA set for ELCA queries, and compared as a run compares the program's
answers.  Prints one JSON line per seed; ``wrong_answers`` has to be
above the limit, 0, on every seed.  Needs no chip: the control runs on
the host, as the reference does.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    from bench import harness, traffic
    from bench.corpus import generate

    cell = harness.resolve(harness.load_benchmark(), args.workload, False)
    spec = cell.traffic
    pool = traffic.pool(spec)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        corpus = generate(int(cell.config["releases"]), seed)
        if spec["loop"] == "open":
            keys = [(i, s) for _, i, s in
                    traffic.open_schedule(spec, args.seconds, seed)]
        else:
            keys = [p for seq in traffic.closed_sequences(spec, seed)
                    for p in seq]
        recs = [{"key": k, "status": 200, "digest": None} for k in keys]
        out = harness.compare(corpus, pool, recs, control=True)
        out.update(seed=seed, requests=len(recs),
                   seconds=time.perf_counter() - t)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
