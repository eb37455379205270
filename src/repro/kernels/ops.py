"""Jit-ready wrappers around the Pallas kernels + the kernel-backed query path.

The membership kernel needs one *static* parameter — the window width (how
many blocks of the larger list a query block may span).  It is data-dependent,
so these wrappers are host-driven: numpy computes block starts and the
bucketed window, then dispatches one of a handful of compiled kernel variants.
On a real TPU the bookkeeping is a few hundred bytes per call; the heavy
compare runs in the kernel.  The kernels compile for the chip on a TPU and
run in Pallas interpret mode on the CPU (``repro.device.pallas_interpret``,
decided per call); every wrapper also takes an explicit ``interpret=``
keyword override.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.idlist import IDList
from repro.core.search_vec import register_membership_backend

from .elca_segsum import elca_segsum_pallas_call
from .intersect import membership_pallas_call
from .searchsorted import searchsorted_pallas_call
from .shapes import INT_PAD, bucket_pow2, pad_to


# canonical homes moved to kernels/shapes.py; kept under the old private
# names because tests and downstream code import them from here
_pad_to = pad_to
_bucket_pow2 = bucket_pow2


def intersect_membership(
    a_sorted: np.ndarray,
    queries_sorted: np.ndarray,
    *,
    bq: int = 512,
    ba: int = 512,
    interpret: bool | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """found/pos of each (sorted) query in sorted unique int32 array ``a``."""
    nq = queries_sorted.shape[0]
    a_p = _pad_to(np.asarray(a_sorted, np.int32), ba, INT_PAD)
    q_p = _pad_to(np.asarray(queries_sorted, np.int32), bq, INT_PAD)
    na_blocks = a_p.shape[0] // ba
    nq_blocks = q_p.shape[0] // bq

    # window bookkeeping (host): first/last a-block per q-block
    q_lo = q_p[::bq]
    q_hi = q_p[bq - 1 :: bq]
    a_start = np.minimum(
        np.searchsorted(a_p, q_lo, side="left") // ba, na_blocks - 1
    ).astype(np.int32)
    a_end = np.minimum(
        (np.maximum(np.searchsorted(a_p, q_hi, side="right") - 1, 0)) // ba,
        na_blocks - 1,
    )
    window = int(np.max(a_end - a_start + 1)) if nq_blocks else 1
    window = min(_bucket_pow2(window), na_blocks)

    found, pos = membership_pallas_call(
        jnp.asarray(a_p), jnp.asarray(q_p), jnp.asarray(a_start), window,
        bq=bq, ba=ba, interpret=interpret,
    )
    return np.asarray(found)[:nq], np.asarray(pos)[:nq]


def searchsorted_positions(
    a_sorted: np.ndarray,
    queries: np.ndarray,
    *,
    bq: int = 512,
    ba: int = 512,
    interpret: bool | None = None,
) -> np.ndarray:
    nq = queries.shape[0]
    na = a_sorted.shape[0]
    a_p = _pad_to(np.asarray(a_sorted, np.int32), ba, INT_PAD)
    q_p = _pad_to(np.asarray(queries, np.int32), bq, INT_PAD)
    pos = searchsorted_pallas_call(
        jnp.asarray(a_p), jnp.asarray(q_p), bq=bq, ba=ba, interpret=interpret
    )
    return np.minimum(np.asarray(pos)[:nq], na)


def membership_pallas(sorted_arr, valid_len, queries):
    """Jit-traceable membership backend built on the searchsorted kernel.

    Registered as the ``"pallas"`` entry of the search_vec membership
    registry so the *batched* jitted search (``ca_search_batch`` behind the
    PlanCache) can run its intersection hot loop in Pallas: unlike
    :func:`intersect_membership`, which computes window starts on the host,
    this variant uses the windowless block-counting searchsorted kernel and
    therefore stays traceable under jit and vmap.  Contract matches
    ``membership_xla``: ``pos`` is only meaningful where ``found`` holds, and
    pad queries report not-found (pos == valid_len fails the bound check).
    """
    m = int(sorted_arr.shape[0])
    pos = searchsorted_pallas_call(
        sorted_arr,
        queries,
        bq=min(512, queries.shape[0]),
        ba=min(512, m),
    ).astype(jnp.int32)
    pos_c = jnp.minimum(pos, m - 1)
    found = (pos < valid_len) & (sorted_arr[pos_c] == queries)
    return found, pos_c


register_membership_backend("pallas", membership_pallas)


def elca_child_sums(
    ca_ids: np.ndarray,
    par_ids: np.ndarray,
    nd: np.ndarray,  # [K, M] aligned with ca/par
    *,
    bi: int = 512,
    bj: int = 512,
    interpret: bool | None = None,
) -> np.ndarray:
    mi = ca_ids.shape[0]
    ca_p = _pad_to(np.asarray(ca_ids, np.int32), bi, INT_PAD)
    par_p = _pad_to(np.asarray(par_ids, np.int32), bj, -1)
    nd_p = _pad_to(np.asarray(nd, np.int32), bj, 0)
    out = elca_segsum_pallas_call(
        jnp.asarray(ca_p), jnp.asarray(par_p), jnp.asarray(nd_p),
        bi=bi, bj=bj, interpret=interpret,
    )
    return np.asarray(out)[:, :mi]


# --------------------------------------------------------------------------- #
# Full kernel-backed query path (engine backend="pallas")
# --------------------------------------------------------------------------- #


def run_query_pallas(
    lists: list[IDList], semantics: str = "slca", *, block: int = 512
) -> np.ndarray:
    """SLCA/ELCA via the Pallas kernels (host-compacted; see DESIGN.md §2)."""
    if not lists or any(len(l) == 0 for l in lists):
        return np.zeros(0, dtype=np.int64)
    order = np.argsort([len(l) for l in lists], kind="stable")
    lists = [lists[i] for i in order]
    l0 = lists[0]
    k = len(lists)

    ca_mask = np.ones(len(l0), dtype=bool)
    nd = [l0.ndesc.astype(np.int64)]
    for l in lists[1:]:
        found, pos = intersect_membership(l.ids, l0.ids, bq=block, ba=block)
        ca_mask &= found
        nd.append(l.ndesc[np.minimum(pos, len(l) - 1)].astype(np.int64))

    ca = l0.ids[ca_mask].astype(np.int64)
    if ca.size == 0:
        return np.zeros(0, dtype=np.int64)
    pid0 = np.where(l0.pidpos >= 0, l0.ids[np.clip(l0.pidpos, 0, len(l0) - 1)], -1)
    par = pid0[ca_mask].astype(np.int64)

    if semantics == "slca":
        next_par = np.concatenate([par[1:], [-1]])
        keep = next_par != ca
        return ca[keep]
    if semantics == "elca":
        nd_ca = np.stack([row[ca_mask] for row in nd])  # [k, m]
        sums = elca_child_sums(ca, par, nd_ca, bi=block, bj=block)
        keep = np.all(nd_ca - sums >= 1, axis=0)
        return ca[keep]
    if semantics == "ca":
        return ca
    raise ValueError(f"unknown semantics {semantics!r}")
