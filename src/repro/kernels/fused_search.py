"""Fused multi-query Pallas search pipeline: membership -> intersect -> ELCA
in ONE kernel launch per batched round.

The chained backend (``ops.run_query_pallas``) dispatches searchsorted
membership, the intersect compare, and the ELCA segsum as separate
host-driven ``pallas_call`` launches with numpy bookkeeping between them:
every phase round-trips the id arrays through HBM.  This module is the
hardware analogue of the paper's DAG win (search each repeated substructure
once): touch each posting list's bytes once per *batch*, not once per query
per phase.

Layout / grid (DESIGN mirrors ``elca_segsum`` + ``intersect``):

  grid = (R, W): R bucketed work items (query x RC rows from the PlanCache),
  W posting-block window steps.  Per row, the shortest list L0 (ids/parent
  ids/NDesc, bucket m0) stays VMEM-resident across the whole W walk; each
  step DMAs one (k-1, BO) tile of the other posting lists and

    1. membership: (ci x BO) broadcast-compare of L0 ids against the tile,
       OR-accumulated into a per-keyword found mask;
    2. ndesc gather, fused into the same compare: ids are unique per list,
       so sum(where(eq, nd_tile, 0)) IS the gather at the matching position
       -- no positions array, no second pass;
    3. at the last step, CA mask + the SLCA/ELCA parent aggregation as a
       masked (ci x cj) mat-sum over the resident row, where all K keyword
       NDesc rows share one equality mask per tile (the ``elca_segsum``
       fusion, now inside the same launch).

SLCA needs no sort/shift here: the CA set is ancestor-closed, so a CA is an
SLCA iff *no* CA's parent id equals it -- the same equality mask that feeds
the ELCA child sums, contracted to a count.  Padding is INT32_MAX
self-masking padding on ids (pad == pad hits are killed by the n0 validity
iota), -1 on parent ids, 0 on NDesc.

Per-query window starts are scalar-prefetched (the index map clamps past
the last block; the kernel body masks the revisit so the non-idempotent
ndesc accumulation never double-counts).  Window widths bucket to powers
of two, so the variant count stays logarithmic; variants are cached as
jitted closures keyed by the full static signature.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.device import pallas_interpret

from .shapes import INT_PAD, bucket_pow2

DEFAULT_BO = 512  # other-list block (the streamed axis)
DEFAULT_CI = 512  # L0-axis chunk for the compare tiles

# VMEM guard: above this L0 bucket the resident row + compare tiles leave
# the comfortable half of a TPU core's ~16 MB VMEM; the PlanCache falls
# back to the chained per-phase path for such (rare, giant) shapes.
MAX_FUSED_M0 = 8192


def _chunks(n: int, body, init):
    """``fori_loop`` over the ``n`` L0 chunks; a lone chunk stays static.

    A rolled loop keeps the kernel body (and its compile time) the same size
    whatever the bucket, where a Python-unrolled one grows with ``m0`` (and
    quadratically in the finalize pass)."""
    if n == 1:
        return body(0, init)
    return jax.lax.fori_loop(0, n, body, init)


def _fused_kernel(
    start_ref,  # scalar prefetch: [R] int32 first other-block per row
    n0_ref,  # scalar prefetch: [R] int32 valid length of L0 per row
    ids0_ref,  # [1, 1, m0] L0 ids (ascending, INT_PAD tail)
    pid0_ref,  # [1, 1, m0] L0 parent *ids* (-1 if none)
    nd0_ref,  # [1, 1, m0] L0 NDesc
    oth_ref,  # [1, k1m, BO] other-list ids tile
    ond_ref,  # [1, k1m, BO] other-list NDesc tile
    keep_ids_ref,  # out [1, 1, m0]: result ids (INT_PAD at dropped slots)
    keep_mask_ref,  # out [1, 1, m0] int32: 1 where keep_ids is a result
    found_ref,  # out/acc [1, k1m, m0] int32 membership mask per keyword
    ndo_ref,  # out/acc [1, k1m, m0] int32 gathered other NDesc
    cam_ref,  # scratch [1, m0] int32: CA mask (finalize pass 1)
    *,
    k1: int,
    m0: int,
    bo: int,
    nob: int,
    window: int,
    ci: int,
    semantics: str,
):
    r = pl.program_id(0)
    j = pl.program_id(1)
    nci = m0 // ci

    def chunk(c):
        # lane window of L0 chunk c: static for a lone chunk, else aligned
        # (ci is a power of two and a multiple of 128 when m0 > ci)
        if isinstance(c, int):
            return pl.ds(c * ci, ci)
        return pl.ds(pl.multiple_of(c * ci, ci), ci)

    @pl.when(j == 0)
    def _init():
        found_ref[...] = jnp.zeros_like(found_ref)
        ndo_ref[...] = jnp.zeros_like(ndo_ref)

    if k1:
        # the index map clamps (start + j) to the last block; a clamped
        # revisit must contribute nothing (the ndesc sum is not idempotent)
        @pl.when(start_ref[r] + j < nob)
        def _stream():
            # streamed phase: membership + ndesc gather for this tile
            def body(c, carry):
                sl = chunk(c)
                q = ids0_ref[0, 0, sl]  # [ci]
                for kk in range(k1):  # k is tiny (1-3): unrolled
                    tile = oth_ref[0, kk, :]  # [BO]
                    ndt = ond_ref[0, kk, :]
                    eq = q[:, None] == tile[None, :]  # [ci, BO]
                    hit = jnp.any(eq, axis=1).astype(jnp.int32)
                    # ids unique per list => at most one eq per row: the
                    # masked sum IS the gather of the matching entry's NDesc
                    nds = jnp.sum(jnp.where(eq, ndt[None, :], 0), axis=1)
                    found_ref[0, kk, sl] |= hit
                    ndo_ref[0, kk, sl] += nds
                return carry

            _chunks(nci, body, 0)

    @pl.when(j == window - 1)
    def _finalize():
        n0 = n0_ref[r]
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, ci), 1)[0]

        # pass 1: CA mask (valid & member of every other list)
        def ca_pass(c, carry):
            sl = chunk(c)
            ca = c * ci + lane < n0
            for kk in range(k1):
                ca = ca & (found_ref[0, kk, sl] != 0)
            cam_ref[0, sl] = ca.astype(jnp.int32)
            return carry

        _chunks(nci, ca_pass, 0)

        def nd_rows(sl):
            return [nd0_ref[0, 0, sl]] + [ndo_ref[0, kk, sl] for kk in range(k1)]

        # pass 2: parent aggregation over the CA set.  One equality mask
        # per (ci x cj) tile serves the SLCA child count and all K ELCA
        # NDesc rows -- the segsum fusion, inside the same launch.
        def row_pass(c, carry):
            sl_i = chunk(c)
            ids_c = ids0_ref[0, 0, sl_i]
            cam_c = cam_ref[0, sl_i] != 0

            def col_pass(d, acc):
                sl_d = chunk(d)
                eq = (pid0_ref[0, 0, sl_d][None, :] == ids_c[:, None]) & (
                    cam_ref[0, sl_d] != 0
                )[None, :]
                if semantics == "slca":
                    return (acc[0] + jnp.sum(eq.astype(jnp.int32), axis=1),)
                return tuple(
                    a + jnp.sum(jnp.where(eq, row[None, :], 0), axis=1)
                    for a, row in zip(acc, nd_rows(sl_d))
                )

            keep = cam_c
            if semantics in ("slca", "elca"):
                n_acc = 1 if semantics == "slca" else k1 + 1
                zero = jnp.zeros((ci,), jnp.int32)
                acc = _chunks(nci, col_pass, (zero,) * n_acc)
                if semantics == "slca":
                    # ancestor closure: SLCA iff no CA child anywhere
                    keep = keep & (acc[0] == 0)
                else:
                    for row, sums in zip(nd_rows(sl_i), acc):
                        keep = keep & (row - sums >= 1)
            keep_mask_ref[0, 0, sl_i] = keep.astype(jnp.int32)
            keep_ids_ref[0, 0, sl_i] = jnp.where(keep, ids_c, INT_PAD)
            return carry

        _chunks(nci, row_pass, 0)


@functools.lru_cache(maxsize=None)
def _fused_variant(
    rows: int,
    k1: int,
    m0: int,
    bo: int,
    nob: int,
    window: int,
    ci: int,
    semantics: str,
    interpret: bool,
):
    """One compiled executable per static shape signature (jit-cached).

    Row operands are laid out ``[R, 1, m0]`` so each block's last two dims
    equal the array's (Mosaic's tiling rule); a ``(1, m0)`` block over
    ``[R, m0]`` compiles only for ``R == 1``.
    """
    k1m = max(k1, 1)

    def row_map(r, j, starts, n0):
        return (r, 0, 0)

    def tile_map(r, j, starts, n0):
        return (r, 0, jnp.minimum(starts[r] + j, nob - 1))

    row_spec = pl.BlockSpec((1, 1, m0), row_map)
    acc_spec = pl.BlockSpec((1, k1m, m0), row_map)
    tile_spec = pl.BlockSpec((1, k1m, bo), tile_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(rows, window),
        in_specs=[row_spec, row_spec, row_spec, tile_spec, tile_spec],
        out_specs=[row_spec, row_spec, acc_spec, acc_spec],
        scratch_shapes=[pltpu.VMEM((1, m0), jnp.int32)],
    )
    kernel = functools.partial(
        _fused_kernel, k1=k1, m0=m0, bo=bo, nob=nob, window=window, ci=ci,
        semantics=semantics,
    )
    row_out = jax.ShapeDtypeStruct((rows, 1, m0), jnp.int32)
    acc_out = jax.ShapeDtypeStruct((rows, k1m, m0), jnp.int32)
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[row_out, row_out, acc_out, acc_out],
        interpret=interpret,
    )
    return jax.jit(call)


def _block_windows(
    ids0: np.ndarray, n0: np.ndarray, other_ids: np.ndarray, bo: int
) -> tuple[np.ndarray, int]:
    """Host bookkeeping: first other-block per row + bucketed window width.

    Every match is a value both lists contain, so for each row only the
    other-list blocks whose value range intersects [L0_min, L0_max] can
    contribute; the union over the row's k-1 lists gives one conservative
    [start, start+window) walk shared by the whole row.
    """
    rows, k1 = other_ids.shape[0], other_ids.shape[1]
    nob = other_ids.shape[2] // bo
    lo = ids0[:, 0]
    hi = ids0[np.arange(rows), np.maximum(n0 - 1, 0)]
    starts = np.zeros(rows, dtype=np.int32)
    need = 1
    for r in range(rows):
        if n0[r] == 0:
            continue  # R-padding row: any window is fine, nothing survives
        s_blk, e_blk = nob - 1, 0
        for kk in range(k1):
            a = other_ids[r, kk]
            s = min(int(np.searchsorted(a, lo[r], side="left")) // bo, nob - 1)
            e = min(
                max(int(np.searchsorted(a, hi[r], side="right")) - 1, 0) // bo,
                nob - 1,
            )
            s_blk, e_blk = min(s_blk, s), max(e_blk, e)
        starts[r] = s_blk
        need = max(need, e_blk - s_blk + 1)
    return starts, min(bucket_pow2(need), nob)


def fused_search_batch(
    ids0: np.ndarray,  # [R, m0] int32 ascending, INT_PAD tail
    pid0: np.ndarray,  # [R, m0] int32 parent ids (-1 pad)
    ndesc0: np.ndarray,  # [R, m0] int32 (0 pad)
    other_ids: np.ndarray,  # [R, k-1, mo] int32 ascending rows, INT_PAD tail
    other_ndesc: np.ndarray,  # [R, k-1, mo] int32 (0 pad)
    n0: np.ndarray,  # [R] int32 valid lengths of L0
    other_n: np.ndarray | None = None,  # [R, k-1] (unused: pads self-mask)
    *,
    semantics: str = "slca",
    bo: int = DEFAULT_BO,
    ci: int = DEFAULT_CI,
    interpret: bool | None = None,
    stats: dict | None = None,
    device=None,
) -> tuple[np.ndarray, np.ndarray]:
    """One fused launch over a PlanCache-packed batch.

    Same output contract as ``search_vec.ca_search_batch``: per row, result
    ids (ascending -- L0 order is preserved, no sort needed) with INT_PAD at
    dropped slots, plus the boolean keep mask.  ``stats`` (optional) gets
    the window bookkeeping this launch used (for tracing / roofline attrs).
    ``device`` (optional) is where the launch runs; default JAX's default.
    """
    ids0 = np.ascontiguousarray(ids0, dtype=np.int32)
    rows, m0 = ids0.shape
    k1 = other_ids.shape[1]
    if k1:
        bo = min(bo, other_ids.shape[2])
        starts, window = _block_windows(ids0, np.asarray(n0), other_ids, bo)
        nob = other_ids.shape[2] // bo
        oth, ond = other_ids, other_ndesc
    else:  # single-keyword rows: no streamed phase, one finalize step
        bo = min(bo, m0)
        starts = np.zeros(rows, dtype=np.int32)
        window, nob = 1, 1
        oth = np.zeros((rows, 1, bo), dtype=np.int32)
        ond = np.zeros((rows, 1, bo), dtype=np.int32)
    ci = min(ci, m0)
    fn = _fused_variant(
        rows, k1, m0, bo, nob, window, ci, semantics,
        pallas_interpret(interpret),
    )
    args = [
        np.asarray(starts, dtype=np.int32),
        np.asarray(n0, dtype=np.int32),
        ids0.reshape(rows, 1, m0),
        np.asarray(pid0, dtype=np.int32).reshape(rows, 1, m0),
        np.asarray(ndesc0, dtype=np.int32).reshape(rows, 1, m0),
        np.ascontiguousarray(oth, dtype=np.int32),
        np.ascontiguousarray(ond, dtype=np.int32),
    ]
    keep_ids, keep_mask, _found, _ndo = fn(*jax.device_put(args, device))
    if stats is not None:
        stats.update(
            window=int(window), bo=int(bo), nob=int(nob), rows=int(rows),
            k=int(k1 + 1), m0=int(m0),
        )
    return np.asarray(keep_ids)[:, 0], np.asarray(keep_mask)[:, 0] != 0


def run_query_fused(lists, semantics: str = "slca") -> np.ndarray:
    """Single-query convenience over the fused pipeline (engine tree path).

    Packs one work item through a private PlanCache (R bucket 1) so the
    tree-index ``backend="fused"`` shares variants across calls.
    """
    global _SINGLE_PLAN
    if _SINGLE_PLAN is None:
        from repro.core.plan_cache import PlanCache  # late: avoid cycle

        _SINGLE_PLAN = PlanCache(backend="fused")
    return _SINGLE_PLAN.run([list(lists)], [0], semantics, backend="fused")[0]


_SINGLE_PLAN = None
