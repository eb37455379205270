"""Pallas TPU kernel: sorted-set membership (the CA-intersection hot loop).

TPU adaptation of the paper's per-element binary search (DESIGN.md §2):
both sides are sorted, so each query block maps to a *contiguous window* of
blocks of the larger list.  The window start per query block is scalar-
prefetched; the grid walks (query_block, window_slot) and performs a dense
(BQ × BA) broadcast-compare in VMEM — no serial binary search anywhere.

Guarantees:
  * exact — equality is ground truth, ids are unique within a list, and the
    caller sizes the window to cover every true position, so no false
    positives or negatives;
  * padding with INT32_MAX is self-masking (pad != any real id; pad==pad
    matches are filtered by the caller's validity mask);
  * window overshoot clamps to the last block — the index map repeats the
    same block index, so Pallas skips the DMA (pure re-visit).

VMEM per grid step: two id tiles (BQ+BA)·4B plus the Mosaic-register-tiled
(BQ × BA) compare — ~1 MB at 512/512, far under a TPU core's ~16 MB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.device import pallas_interpret

DEFAULT_BQ = 512
DEFAULT_BA = 512


def _membership_kernel(
    a_start_ref, q_ref, a_ref, found_ref, pos_ref, *, ba: int, na_blocks: int
):
    qi = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        found_ref[...] = jnp.zeros_like(found_ref)
        pos_ref[...] = jnp.zeros_like(pos_ref)

    q = q_ref[0, :]  # [BQ]
    a = a_ref[0, :]  # [BA]
    eq = q[:, None] == a[None, :]  # [BQ, BA] dense compare (VPU)
    hit = jnp.any(eq, axis=1)
    # ids are unique, so at most one lane matches: the masked iota sum is
    # its position (Mosaic lowers argmax only for float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, eq.shape, 1)
    local = jnp.sum(jnp.where(eq, lane, 0), axis=1)
    # global block index actually visited (must mirror the index_map clamp)
    blk = jnp.minimum(a_start_ref[qi] + j, na_blocks - 1)
    gpos = blk * ba + local
    found_ref[0, :] = found_ref[0, :] | hit.astype(jnp.int32)
    pos_ref[0, :] = jnp.where(hit, gpos, pos_ref[0, :])


def membership_pallas_call(
    a_padded: jax.Array,  # [MA] int32, ascending, INT_PAD tail
    q_padded: jax.Array,  # [MQ] int32, ascending, INT_PAD tail
    a_start: jax.Array,  # [MQ // bq] int32: first a-block per q-block
    window: int,  # static: #a-blocks each q-block visits
    *,
    bq: int = DEFAULT_BQ,
    ba: int = DEFAULT_BA,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Raw pallas_call; see ops.intersect_membership for the friendly wrapper."""
    ma, mq = a_padded.shape[0], q_padded.shape[0]
    assert ma % ba == 0 and mq % bq == 0, (ma, ba, mq, bq)
    na_blocks = ma // ba
    nq_blocks = mq // bq

    def q_index(qi, j, a_start_ref):
        return (0, qi)

    def a_index(qi, j, a_start_ref):
        return (0, jnp.minimum(a_start_ref[qi] + j, na_blocks - 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nq_blocks, window),
        in_specs=[
            pl.BlockSpec((1, bq), q_index),
            pl.BlockSpec((1, ba), a_index),
        ],
        out_specs=[
            pl.BlockSpec((1, bq), q_index),
            pl.BlockSpec((1, bq), q_index),
        ],
    )
    kernel = functools.partial(_membership_kernel, ba=ba, na_blocks=na_blocks)
    found, pos = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((1, mq), jnp.int32),
            jax.ShapeDtypeStruct((1, mq), jnp.int32),
        ],
        interpret=pallas_interpret(interpret),
    )(a_start, q_padded[None, :], a_padded[None, :])
    return found[0] != 0, pos[0]
