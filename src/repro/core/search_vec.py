"""Vectorized (JAX) set-intersection keyword search.

TPU-native re-derivation of FwdSLCA/FwdELCA (DESIGN.md §2): instead of cursor
walking, we

  1. intersect by *membership*: every element of the shortest list L0 is
     located in the other lists (a loop-free block compare,
     `searchsorted_left`, or the Pallas block kernel when backend="pallas");
  2. compact the CA set by prefix sum (L0 is ascending, so it stays
     sorted; pad = INT32_MAX fills the tail);
  3. SLCA: a CA is SLCA iff the *next* CA's parent differs (ancestor-closure
     argument, DESIGN.md §2) — one shift-compare;
  4. ELCA: scatter-add child NDesc onto parent CA positions (`segment_sum`)
     and test `NDesc - Σchild >= 1` per keyword.

All shapes are static; callers pad to power-of-two buckets so jit caches a
small number of executables.  Everything works under `vmap` (the DAG engine
batches redundancy components along a leading axis).
"""
from __future__ import annotations

from functools import partial
from collections.abc import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.shapes import INT_PAD, bucket  # noqa: F401  (re-exported)

from .idlist import IDList

# A searched list is cut into rows of this many ids (one lane-width vector).
SEARCH_BLOCK = 128


def searchsorted_left(a: jax.Array, q: jax.Array) -> jax.Array:
    """``searchsorted(a, q, side="left")``: the count of ``a < q`` per query.

    ``a`` is sorted, of static length, padded with ``INT_PAD`` (which is
    below no query).  Loop-free, unlike ``jnp.searchsorted``'s ``while`` of
    log2(n) scalar gathers: a list of up to ``SEARCH_BLOCK`` ids is compared
    whole; a longer one is cut into ``SEARCH_BLOCK``-id rows, the row heads
    pick each query's row, and one gathered row is compared.  Every compare
    feeds a sum, so nothing of size queries x ids is materialised.
    """

    def below(rows: jax.Array) -> jax.Array:
        return jnp.sum(rows < q[:, None], axis=1, dtype=jnp.int32)

    n = a.shape[0]
    if n <= SEARCH_BLOCK:
        return below(a[None, :])
    a = jnp.pad(a, (0, -n % SEARCH_BLOCK), constant_values=INT_PAD)
    blocks = a.reshape(-1, SEARCH_BLOCK)
    blk = jnp.maximum(below(blocks[None, :, 0]) - 1, 0)
    return blk * SEARCH_BLOCK + below(blocks[blk])


# membership backend registry: name -> fn(sorted_arr, valid_len, queries)
#   -> (found_mask [m0] bool, positions [m0] int32)
_MEMBERSHIP_BACKENDS: dict[str, Callable] = {}


def register_membership_backend(name: str, fn: Callable) -> None:
    _MEMBERSHIP_BACKENDS[name] = fn


def membership_xla(
    sorted_arr: jax.Array, valid_len: jax.Array, queries: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Membership + position of each query in a padded sorted array."""
    m = sorted_arr.shape[0]
    pos = searchsorted_left(sorted_arr, queries)
    pos_c = jnp.minimum(pos, m - 1)
    found = (pos < valid_len) & (sorted_arr[pos_c] == queries)
    return found, pos_c


register_membership_backend("xla", membership_xla)


# --------------------------------------------------------------------------- #
# Core jitted search (single query, single component)
# --------------------------------------------------------------------------- #


@partial(jax.jit, static_argnames=("semantics", "backend"))
def ca_search(
    ids0: jax.Array,  # [m0] int32, ascending, padded with INT_PAD
    pid0: jax.Array,  # [m0] int32 parent *ids* (-1 if none), pad arbitrary
    ndesc0: jax.Array,  # [m0] int32
    other_ids: jax.Array,  # [k-1, M] int32 padded rows
    other_ndesc: jax.Array,  # [k-1, M] int32
    n0: jax.Array,  # scalar int32: valid length of list 0
    other_n: jax.Array,  # [k-1] int32 valid lengths
    *,
    semantics: str = "slca",
    backend: str = "xla",
) -> tuple[jax.Array, jax.Array]:
    """Return (result_ids [m0], result_mask [m0]): SLCA or ELCA of the lists.

    Results are compacted ascending; invalid tail slots hold INT_PAD.
    """
    m0 = ids0.shape[0]
    member_fn = _MEMBERSHIP_BACKENDS[backend]
    valid0 = jnp.arange(m0, dtype=jnp.int32) < n0

    if other_ids.shape[0]:
        found, pos = jax.vmap(member_fn)(
            other_ids, other_n, jnp.broadcast_to(ids0, (other_ids.shape[0], m0))
        )
        ca_mask = valid0 & jnp.all(found, axis=0)
        nd_others = jnp.take_along_axis(other_ndesc, pos, axis=1)  # [k-1, m0]
        nd = jnp.concatenate([ndesc0[None, :], nd_others], axis=0)  # [k, m0]
    else:  # single-keyword query: every list entry is a CA
        ca_mask = valid0
        nd = ndesc0[None, :]

    # compact the CA set ascending (pads to the tail).  ids0 is ascending, so
    # a stable compaction -- prefix-sum slots, one scatter -- keeps it sorted
    # without a sort, whose TPU compile alone takes seconds per bucket
    slot = jnp.where(ca_mask, jnp.cumsum(ca_mask, dtype=jnp.int32) - 1, m0)

    def compact(v, fill):
        return jnp.full((m0,), fill, v.dtype).at[slot].set(v, mode="drop")

    ca_sorted = compact(ids0, INT_PAD)
    cnt = jnp.sum(ca_mask).astype(jnp.int32)
    idx = jnp.arange(m0, dtype=jnp.int32)
    valid = idx < cnt

    par_sorted = compact(pid0, -1)

    if semantics == "slca":
        next_par = jnp.concatenate([par_sorted[1:], jnp.full((1,), -1, jnp.int32)])
        is_last = idx == cnt - 1
        res_mask = valid & (is_last | (next_par != ca_sorted))
    elif semantics == "elca":
        # position of each CA's parent inside the compacted CA array
        pp = searchsorted_left(ca_sorted, par_sorted)
        pp_c = jnp.minimum(pp, m0 - 1)
        par_is_ca = valid & (par_sorted >= 0) & (ca_sorted[pp_c] == par_sorted)
        seg = jnp.where(par_is_ca, pp_c, m0)  # overflow bucket for roots/invalid
        # one 1-D scatter per keyword (k is static and tiny): the same
        # scatter vmapped over the keyword axis compiles ~10x slower on TPU
        res_mask = valid
        for nd_k in nd:
            nd_sorted = compact(nd_k, 0)
            child_sum = jax.ops.segment_sum(
                jnp.where(valid, nd_sorted, 0), seg, num_segments=m0 + 1
            )[:m0]
            res_mask = res_mask & (nd_sorted - child_sum >= 1)
    elif semantics == "ca":
        res_mask = valid
    else:  # pragma: no cover
        raise ValueError(f"unknown semantics {semantics!r}")

    res_ids = jnp.where(res_mask, ca_sorted, INT_PAD)
    return res_ids, res_mask


@partial(jax.jit, static_argnames=("semantics", "backend"))
def ca_search_batch(
    ids0, pid0, ndesc0, other_ids, other_ndesc, n0, other_n,
    *, semantics: str = "slca", backend: str = "xla",
):
    """ca_search over a leading batch axis (components or queries)."""
    fn = lambda *a: ca_search(*a, semantics=semantics, backend=backend)
    return jax.vmap(fn)(ids0, pid0, ndesc0, other_ids, other_ndesc, n0, other_n)


# --------------------------------------------------------------------------- #
# Host-side padding / bucketing helpers
# --------------------------------------------------------------------------- #


def pad_list(lst: IDList, m: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    n = len(lst)
    ids = np.full(m, INT_PAD, dtype=np.int32)
    nd = np.zeros(m, dtype=np.int32)
    pid = np.full(m, -1, dtype=np.int32)
    ids[:n] = lst.ids
    nd[:n] = lst.ndesc
    # parent *ids* resolved from pidpos once on host
    if n:
        pp = lst.pidpos
        pid[:n] = np.where(pp >= 0, lst.ids[np.clip(pp, 0, n - 1)], -1)
    return ids, pid, nd


def pack_query(lists: list[IDList]) -> dict | None:
    """Order lists (shortest first), pad to buckets; None if any list empty."""
    if not lists or any(len(l) == 0 for l in lists):
        return None
    order = np.argsort([len(l) for l in lists], kind="stable")
    lists = [lists[i] for i in order]
    m0 = bucket(len(lists[0]))
    mo = bucket(max((len(l) for l in lists[1:]), default=1))
    ids0, pid0, nd0 = pad_list(lists[0], m0)
    k1 = len(lists) - 1
    other_ids = np.full((k1, mo), INT_PAD, dtype=np.int32)
    other_nd = np.zeros((k1, mo), dtype=np.int32)
    other_n = np.zeros((k1,), dtype=np.int32)
    for i, l in enumerate(lists[1:]):
        other_ids[i, : len(l)] = l.ids
        other_nd[i, : len(l)] = l.ndesc
        other_n[i] = len(l)
    return dict(
        ids0=jnp.asarray(ids0),
        pid0=jnp.asarray(pid0),
        ndesc0=jnp.asarray(nd0),
        other_ids=jnp.asarray(other_ids),
        other_ndesc=jnp.asarray(other_nd),
        n0=jnp.int32(len(lists[0])),
        other_n=jnp.asarray(other_n),
    )


def run_query(
    lists: list[IDList], semantics: str = "slca", backend: str = "xla"
) -> np.ndarray:
    """Vectorized search over one set of IDLists -> sorted result node ids."""
    packed = pack_query(lists)
    if packed is None:
        return np.zeros(0, dtype=np.int64)
    ids, mask = ca_search(**packed, semantics=semantics, backend=backend)
    ids = np.asarray(ids)
    mask = np.asarray(mask)
    return ids[mask].astype(np.int64)
