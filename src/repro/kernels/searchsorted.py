"""Pallas TPU kernel: vectorized searchsorted by block counting.

pos[q] = #{a in A : a < q} — computed as a (BQ × BA) compare + row-sum,
accumulated over A blocks.  Used for parent-position lookups inside compacted
CA arrays (they are CA-sized, so the full cross-product grid is cheap and
needs no window bookkeeping).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.device import pallas_interpret

DEFAULT_BQ = 512
DEFAULT_BA = 512


def _ss_kernel(q_ref, a_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    q = q_ref[0, :]
    a = a_ref[0, :]
    lt = a[None, :] < q[:, None]  # [BQ, BA]
    out_ref[0, :] += jnp.sum(lt.astype(jnp.int32), axis=1)


def searchsorted_pallas_call(
    a_padded: jax.Array,  # [MA] int32 ascending (INT32_MAX tail)
    q_padded: jax.Array,  # [MQ] int32 (any order)
    *,
    bq: int = DEFAULT_BQ,
    ba: int = DEFAULT_BA,
    interpret: bool | None = None,
) -> jax.Array:
    ma, mq = a_padded.shape[0], q_padded.shape[0]
    assert ma % ba == 0 and mq % bq == 0
    grid = (mq // bq, ma // ba)
    out = pl.pallas_call(
        _ss_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq), lambda qi, j: (0, qi)),
            pl.BlockSpec((1, ba), lambda qi, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, bq), lambda qi, j: (0, qi)),
        out_shape=jax.ShapeDtypeStruct((1, mq), jnp.int32),
        interpret=pallas_interpret(interpret),
    )(q_padded[None, :], a_padded[None, :])
    return out[0]
