"""Pure-jnp oracles for every Pallas kernel (the correctness contracts)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

INT_PAD = jnp.int32(2**31 - 1)


def membership_ref(
    a_padded: jax.Array, q_padded: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """(found, pos) of each query in a sorted padded array (searchsorted)."""
    ma = a_padded.shape[0]
    pos = jnp.searchsorted(a_padded, q_padded, side="left").astype(jnp.int32)
    pos_c = jnp.minimum(pos, ma - 1)
    found = a_padded[pos_c] == q_padded
    return found, jnp.where(found, pos_c, 0)


def searchsorted_ref(a_padded: jax.Array, q_padded: jax.Array) -> jax.Array:
    """#elements of A strictly below each query (searchsorted left)."""
    return jnp.searchsorted(a_padded, q_padded, side="left").astype(jnp.int32)


def elca_segsum_ref(
    ca_padded: jax.Array, par_padded: jax.Array, nd_padded: jax.Array
) -> jax.Array:
    """child_sum[k, i] = sum of nd[k, j] where par[j] == ca[i] (dense oracle)."""
    eq = par_padded[None, :] == ca_padded[:, None]  # [MI, MJ]
    return jnp.einsum("ij,kj->ki", eq.astype(jnp.int32), nd_padded)
