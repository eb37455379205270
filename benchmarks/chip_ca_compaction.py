"""Warm device time of ``ca_search_batch`` under three CA-set compactions.

    python benchmarks/chip_ca_compaction.py            # the 100k bucket shapes
    python benchmarks/chip_ca_compaction.py --small    # tiny shapes (CPU check)

``ca_search`` compacts the common-ancestor set of each row before the
SLCA/ELCA rules.  Three ways of doing it are timed on the same inputs:

  * ``scatter``: the code in ``repro.core.search_vec`` (prefix-sum slots,
    one scatter per column, ELCA's ``segment_sum`` once per keyword);
  * ``sort``: one ``argsort`` of the masked ids and a gather per column,
    ELCA's ``segment_sum`` vmapped over the keywords;
  * ``scatter_unique``: the prefix-sum scatter with every slot distinct
    (dropped entries go to distinct out-of-range slots), declared with
    ``unique_indices=True``.

The default shapes are launch shapes the 1-shard served path meets at 100k
releases (``chip_smoke.py``'s corpus) when the XLA batch runs.  Inputs are
synthetic ascending posting lists made from ``--seed``; every variant must
return what ``scatter`` returns.  Per (shape, semantics, variant) it prints
the compile seconds (the persistent compile cache is turned off) and the
median and minimum of ``--reps`` warm calls, inputs resident on the device
(host clock around ``block_until_ready``).  Exits non-zero on a mismatch.
"""
from __future__ import annotations

import argparse
import os
import statistics
import sys
import time
from functools import partial

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import search_vec as sv  # noqa: E402
from repro.kernels.shapes import INT_PAD  # noqa: E402

# (rows, other lists, m0, mo): XLA launch shapes at 100k releases, 1 shard
SHAPES = [
    (1, 3, 16384, 131072),
    (1, 2, 65536, 262144),
    (1, 3, 131072, 524288),
    (1, 1, 524288, 524288),
    (8, 2, 16, 16),
]
SMALL = [(1, 2, 256, 1024), (4, 1, 64, 64)]


def sort_compact(ca_mask, ids0, pid0, nd):
    ca_ids = jnp.where(ca_mask, ids0, INT_PAD)
    order = jnp.argsort(ca_ids)
    par = jnp.where(ca_mask, pid0, -1)[order]
    return ca_ids[order], par, jnp.take(nd, order, axis=1)


def unique_scatter_compact(ca_mask, ids0, pid0, nd):
    m0 = ids0.shape[0]
    idx = jnp.arange(m0, dtype=jnp.int32)
    slot = jnp.where(ca_mask, jnp.cumsum(ca_mask, dtype=jnp.int32) - 1, m0 + idx)

    def put(v, fill):
        return jnp.full((m0,), fill, v.dtype).at[slot].set(
            v, mode="drop", unique_indices=True
        )

    return put(ids0, INT_PAD), put(pid0, -1), jnp.stack([put(r, 0) for r in nd])


def make_batch(compact, elca_vmapped: bool):
    """``ca_search_batch`` with the compaction swapped (k >= 2 only)."""

    def one(ids0, pid0, ndesc0, other_ids, other_ndesc, n0, other_n, *, semantics):
        m0 = ids0.shape[0]
        valid0 = jnp.arange(m0, dtype=jnp.int32) < n0
        found, pos = jax.vmap(sv.membership_xla)(
            other_ids, other_n, jnp.broadcast_to(ids0, (other_ids.shape[0], m0))
        )
        ca_mask = valid0 & jnp.all(found, axis=0)
        nd_others = jnp.take_along_axis(other_ndesc, pos, axis=1)
        nd = jnp.concatenate([ndesc0[None, :], nd_others], axis=0)
        ca_sorted, par_sorted, nd_sorted = compact(ca_mask, ids0, pid0, nd)
        cnt = jnp.sum(ca_mask).astype(jnp.int32)
        idx = jnp.arange(m0, dtype=jnp.int32)
        valid = idx < cnt
        if semantics == "slca":
            next_par = jnp.concatenate([par_sorted[1:], jnp.full((1,), -1, jnp.int32)])
            res_mask = valid & ((idx == cnt - 1) | (next_par != ca_sorted))
        else:
            pp = jnp.minimum(
                jnp.searchsorted(ca_sorted, par_sorted).astype(jnp.int32), m0 - 1
            )
            par_is_ca = valid & (par_sorted >= 0) & (ca_sorted[pp] == par_sorted)
            seg = jnp.where(par_is_ca, pp, m0)

            def child_sum(v):
                return jax.ops.segment_sum(
                    jnp.where(valid, v, 0), seg, num_segments=m0 + 1
                )[:m0]

            if elca_vmapped:
                sums = jax.vmap(child_sum)(nd_sorted)
            else:
                sums = jnp.stack([child_sum(v) for v in nd_sorted])
            res_mask = valid & jnp.all(nd_sorted - sums >= 1, axis=0)
        return jnp.where(res_mask, ca_sorted, INT_PAD), res_mask

    @partial(jax.jit, static_argnames=("semantics",))
    def batch(*args, semantics):
        return jax.vmap(partial(one, semantics=semantics))(*args)

    return batch


VARIANTS = {
    "scatter": partial(sv.ca_search_batch, backend="xla"),
    "sort": make_batch(sort_compact, elca_vmapped=True),
    "scatter_unique": make_batch(unique_scatter_compact, elca_vmapped=False),
}


def make_inputs(rng, rows, k1, m0, mo):
    """Ascending lists: list 0 fills 3/4 of its bucket, and every other
    list holds about half of list 0 plus ids of its own."""
    span = 8 * max(m0, mo)
    names = ("ids0", "pid0", "ndesc0", "other_ids", "other_ndesc", "n0", "other_n")
    out = {k: [] for k in names}
    for _ in range(rows):
        n0 = max(1, 3 * m0 // 4)
        ids0 = np.sort(rng.choice(span, n0, replace=False)).astype(np.int32)
        back = rng.integers(1, 5, n0)
        par_pos = np.arange(n0) - back
        pid0 = np.where(par_pos >= 0, ids0[np.maximum(par_pos, 0)], -1)
        oi = np.full((k1, mo), INT_PAD, np.int32)
        on = np.zeros((k1, mo), np.int32)
        ol = np.zeros(k1, np.int32)
        for j in range(k1):
            mine = ids0[rng.random(n0) < 0.5]
            extra = rng.choice(span, mo, replace=False)
            lst = np.union1d(mine, extra)[:mo].astype(np.int32)
            oi[j, : lst.size] = lst
            on[j, : lst.size] = rng.integers(1, 6, lst.size)
            ol[j] = lst.size
        def pad(v, fill):
            return np.concatenate([v, np.full(m0 - v.size, fill, np.int32)])

        out["ids0"].append(pad(ids0, INT_PAD))
        out["pid0"].append(pad(pid0.astype(np.int32), -1))
        out["ndesc0"].append(pad(rng.integers(1, 6, n0).astype(np.int32), 0))
        out["other_ids"].append(oi)
        out["other_ndesc"].append(on)
        out["n0"].append(np.int32(n0))
        out["other_n"].append(ol)
    return [jax.device_put(np.stack(v)) for v in out.values()]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--small", action="store_true", help="tiny shapes")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    jax.config.update("jax_enable_compilation_cache", False)
    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind}", flush=True)
    rng = np.random.default_rng(args.seed)
    bad = 0
    for rows, k1, m0, mo in SMALL if args.small else SHAPES:
        inputs = make_inputs(rng, rows, k1, m0, mo)
        for sem in ("slca", "elca"):
            compiled, comp_s = {}, {}
            for name, fn in VARIANTS.items():
                t0 = time.perf_counter()
                jitted = jax.jit(fn, static_argnames=("semantics",))
                compiled[name] = jitted.lower(*inputs, semantics=sem).compile()
                comp_s[name] = time.perf_counter() - t0
            want = [np.asarray(x) for x in compiled["scatter"](*inputs)]
            times = {name: [] for name in compiled}
            for name, f in compiled.items():
                got = [np.asarray(x) for x in f(*inputs)]
                if not all(np.array_equal(a, b) for a, b in zip(got, want)):
                    print(f"MISMATCH {name} R={rows} k1={k1} m0={m0} {sem}", flush=True)
                    bad += 1
            for _ in range(args.reps):  # interleaved, so drift hits all alike
                for name, f in compiled.items():
                    t0 = time.perf_counter()
                    jax.block_until_ready(f(*inputs))
                    times[name].append((time.perf_counter() - t0) * 1e3)
            for name, ts in times.items():
                print(
                    f"R={rows} k1={k1} m0={m0} mo={mo} {sem} {name}: "
                    f"compile_s={comp_s[name]:.3f} warm_ms_median="
                    f"{statistics.median(ts):.3f} warm_ms_min={min(ts):.3f} "
                    f"results={int(want[1].sum())}",
                    flush=True,
                )
    sys.exit(1 if bad else 0)


if __name__ == "__main__":
    main()
