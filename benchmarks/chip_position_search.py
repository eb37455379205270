"""Warm device time of ``ca_search_batch`` by how it finds list positions.

    python benchmarks/chip_position_search.py            # the 100k cell's shapes
    python benchmarks/chip_position_search.py --small    # tiny shapes (CPU check)

The XLA batch search finds each L0 id in every other list (membership) and,
for ELCA, each CA's parent in the CA set.  Variants, timed on the same
inputs (``chip_ca_compaction.make_inputs``, made from ``--seed``):

  * ``searchsorted``: ``jnp.searchsorted``, a ``while`` loop of log2(n)
    levels of scalar gathers (the search before ``searchsorted_left``);
  * ``block<B>``: ``repro.core.search_vec.searchsorted_left`` with rows of
    ``B`` ids (``SEARCH_BLOCK`` set to ``B`` while the variant is traced).

Per (shape, semantics, variant) it prints the compile seconds (the
persistent compile cache is turned off), the median and minimum of
``--reps`` warm calls, inputs resident on the device (host clock around
``block_until_ready``, so each includes a launch's fixed round trip), and
``device_ms``: ``--reps`` calls dispatched back to back and waited for
once, per call (the median of three such runs), which is the device's
time per launch wherever that exceeds the host's dispatch time; with the
ratio of each to ``searchsorted``'s.
Every variant must return what ``searchsorted`` returns; exits non-zero on
a mismatch.
"""
from __future__ import annotations

import argparse
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "..", "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chip_ca_compaction import make_inputs  # noqa: E402
from repro.core import search_vec as sv  # noqa: E402

# (rows, other lists, m0, mo): launch shapes of the facet-80 cell at 100k
# releases, 1 shard: the most frequent (m0 16384) and the costliest (the
# category-1 queries' 100k-entry lists)
SHAPES = [
    (1, 1, 16384, 16384),
    (1, 2, 4096, 16384),
    (1, 3, 16384, 131072),
    (1, 3, 16384, 262144),
    (1, 2, 16384, 524288),
    (1, 3, 4096, 524288),
    (4, 2, 4096, 131072),
    (1, 3, 131072, 524288),
    (1, 1, 524288, 524288),
    (8, 2, 16, 16),
]
SMALL = [(1, 2, 256, 1024), (4, 1, 64, 64), (2, 3, 512, 4096)]
BLOCKS = (128, 256, 512)


def _searchsorted(a, q):
    return jnp.searchsorted(a, q, side="left").astype(jnp.int32)


def compile_variant(search, block, inputs, semantics):
    """``ca_search_batch`` lowered with ``search`` as its position search
    and ``block`` as its row width; returns (compiled, seconds)."""
    saved = sv.searchsorted_left, sv.SEARCH_BLOCK
    sv.searchsorted_left, sv.SEARCH_BLOCK = search, block
    try:
        jax.clear_caches()
        t0 = time.perf_counter()
        compiled = sv.ca_search_batch.lower(
            *inputs, semantics=semantics, backend="xla"
        ).compile()
        return compiled, time.perf_counter() - t0
    finally:
        sv.searchsorted_left, sv.SEARCH_BLOCK = saved
        jax.clear_caches()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--small", action="store_true", help="tiny shapes")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    jax.config.update("jax_enable_compilation_cache", False)
    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind}", flush=True)
    variants = {"searchsorted": (_searchsorted, sv.SEARCH_BLOCK)}
    variants.update({f"block{b}": (sv.searchsorted_left, b) for b in BLOCKS})
    rng = np.random.default_rng(args.seed)
    bad = 0
    for rows, k1, m0, mo in SMALL if args.small else SHAPES:
        inputs = make_inputs(rng, rows, k1, m0, mo)
        for sem in ("slca", "elca"):
            compiled, comp_s = {}, {}
            for name, (search, block) in variants.items():
                compiled[name], comp_s[name] = compile_variant(
                    search, block, inputs, sem
                )
            want = [np.asarray(x) for x in compiled["searchsorted"](*inputs)]
            for name, f in compiled.items():
                got = [np.asarray(x) for x in f(*inputs)]
                if not all(np.array_equal(a, b) for a, b in zip(got, want)):
                    print(f"MISMATCH {name} R={rows} k1={k1} m0={m0} mo={mo} "
                          f"{sem}", flush=True)
                    bad += 1
            times = {name: [] for name in compiled}
            for _ in range(args.reps):  # interleaved, so drift hits all alike
                for name, f in compiled.items():
                    t0 = time.perf_counter()
                    jax.block_until_ready(f(*inputs))
                    times[name].append((time.perf_counter() - t0) * 1e3)
            piped = {name: [] for name in compiled}
            for _ in range(3):
                for name, f in compiled.items():
                    t0 = time.perf_counter()
                    jax.block_until_ready([f(*inputs) for _ in range(args.reps)])
                    piped[name].append(
                        (time.perf_counter() - t0) * 1e3 / args.reps
                    )
            base = statistics.median(times["searchsorted"])
            base_dev = statistics.median(piped["searchsorted"])
            for name, ts in times.items():
                med = statistics.median(ts)
                dev = statistics.median(piped[name])
                print(
                    f"R={rows} k1={k1} m0={m0} mo={mo} {sem} {name}: "
                    f"compile_s={comp_s[name]:.3f} warm_ms_median={med:.3f} "
                    f"warm_ms_min={min(ts):.3f} vs_searchsorted={med / base:.3f} "
                    f"device_ms={dev:.3f} device_vs_searchsorted="
                    f"{dev / base_dev:.3f} results={int(want[1].sum())}",
                    flush=True,
                )
    sys.exit(1 if bad else 0)


if __name__ == "__main__":
    main()
