"""What a process-transport cluster does on a host with fewer chips than shards.

    python benchmarks/chip_process_cluster.py [--shards 2] [--releases 200]

Only one process may hold a chip.  This builds a small cluster in a child
process that runs JAX on the CPU, then serves it with
``ClusterService.from_dir(transport="process")`` in the environment it was
given; this process never opens a JAX backend itself.  On a one-chip host
the first shard child takes the chip and every other child must fail at
start-up: the pool must raise the typed ``WorkerDied`` carrying the child's
stderr tail, within ``--timeout`` seconds.  A cluster that comes up (shards
on different backends), a hang or a respawn loop exits 1.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import tempfile
import time

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src")
sys.path.insert(0, SRC)

BUILD = """
from repro.cluster import build_cluster
from repro.data import generate_discogs_tree
tree = generate_discogs_tree(n_releases={releases}, seed=0)
build_cluster(tree, {shards}, {path!r})
"""


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--shards", type=int, default=2)
    ap.add_argument("--releases", type=int, default=200)
    ap.add_argument("--timeout", type=float, default=300.0)
    args = ap.parse_args()

    from jax._src import xla_bridge

    from repro.cluster import ClusterService
    from repro.cluster.workers.base import WorkerDied

    print(f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}", flush=True)
    with tempfile.TemporaryDirectory(prefix="proc-probe-") as path:
        subprocess.run(
            [sys.executable, "-c", BUILD.format(
                releases=args.releases, shards=args.shards, path=path
            )],
            check=True,
            env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC),
        )
        t0 = time.perf_counter()
        try:
            svc = ClusterService.from_dir(
                path, transport="process", spawn_timeout=args.timeout,
                max_respawns=0,
            )
        except WorkerDied as e:
            took = time.perf_counter() - t0
            tail = str(e).splitlines()[-3:]
            print(f"WorkerDied after {took:.3f} s: shard {e.shard}", flush=True)
            print("  last lines: " + " | ".join(tail), flush=True)
            held = xla_bridge.backends_are_initialized()
            print(f"parent opened a JAX backend: {held}", flush=True)
            ok = "stderr tail" in str(e) and not held
            sys.exit(0 if ok else 1)
        took = time.perf_counter() - t0
        print(f"cluster came up after {took:.3f} s: not refused", flush=True)
        svc.close()
        sys.exit(1)


if __name__ == "__main__":
    main()
