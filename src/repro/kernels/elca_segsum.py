"""Pallas TPU kernel: ELCA child-NDesc aggregation as a masked mat-sum.

The scalar algorithm scatter-adds each CA child's NDesc onto its parent — a
pattern TPUs hate.  Reformulated densely (DESIGN.md §2):

    child_sum[k, i] = Σ_j  [ parent_id[j] == ca_id[i] ] · ndesc[k, j]

i.e. a (BI × BJ) equality mask contracted against NDesc rows.  K keyword rows
share one mask per (i, j) tile — the kernel's fusion win over K separate
segment-sums.  Integer math on the VPU keeps it exact for any int32 NDesc.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.device import pallas_interpret

DEFAULT_BI = 512
DEFAULT_BJ = 512


def _segsum_kernel(ca_ref, par_ref, nd_ref, out_ref, *, k: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ca = ca_ref[0, :]  # [BI]
    par = par_ref[0, :]  # [BJ]
    eq = par[None, :] == ca[:, None]  # [BI, BJ] shared across keyword rows
    for kk in range(k):  # k is tiny (2-4): unrolled
        nd = nd_ref[kk, :]  # [BJ]
        out_ref[kk, :] += jnp.sum(jnp.where(eq, nd[None, :], 0), axis=1)


def elca_segsum_pallas_call(
    ca_padded: jax.Array,  # [MI] int32 CA ids (INT32_MAX tail)
    par_padded: jax.Array,  # [MJ] int32 parent ids aligned with nd (-1 pad)
    nd_padded: jax.Array,  # [K, MJ] int32 NDesc rows (0 pad)
    *,
    bi: int = DEFAULT_BI,
    bj: int = DEFAULT_BJ,
    interpret: bool | None = None,
) -> jax.Array:
    mi, mj = ca_padded.shape[0], par_padded.shape[0]
    k = nd_padded.shape[0]
    assert mi % bi == 0 and mj % bj == 0 and nd_padded.shape[1] == mj
    grid = (mi // bi, mj // bj)
    out = pl.pallas_call(
        functools.partial(_segsum_kernel, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bi), lambda i, j: (0, i)),
            pl.BlockSpec((1, bj), lambda i, j: (0, j)),
            pl.BlockSpec((k, bj), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((k, bi), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((k, mi), jnp.int32),
        interpret=pallas_interpret(interpret),
    )(ca_padded[None, :], par_padded[None, :], nd_padded)
    return out
