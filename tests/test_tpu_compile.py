"""The search kernels compile for a TPU v5e chip (described, not attached).

Interpret mode cannot show what Mosaic refuses (block tiling, VMEM use,
unsupported reductions), so each kernel of the served path is compiled here
for one chip of a described ``v5e:2x2`` topology at real bucket sizes.
Nothing runs; a compile that passes says nothing about results or speed.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and test workers import every
test file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda *dims: jax.ShapeDtypeStruct(dims, jnp.int32, sharding=one_chip)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "rows,k,m0,semantics",
    [
        (8, k, m0, sem)
        for k in (2, 3)
        for m0 in (512, 2048)
        for sem in ("slca", "elca")
    ]
    + [
        (8, 3, 8192, "elca"),  # MAX_FUSED_M0: rolled loops keep this quick
        (2, 1, 64, "slca"),  # single keyword, one chunk, no streamed phase
        (8, 2, 16, "elca"),  # the smallest list bucket
    ],
)
def test_fused_compiles(shape, rows, k, m0, semantics):
    from repro.kernels.fused_search import (
        DEFAULT_BO,
        DEFAULT_CI,
        _fused_variant,
    )

    k1 = k - 1
    if k1:
        mo = 4 * m0
        bo = min(DEFAULT_BO, mo)
        nob = mo // bo
        window = min(4, nob)
    else:
        mo = bo = min(DEFAULT_BO, m0)
        nob = window = 1
    fn = _fused_variant(
        rows, k1, m0, bo, nob, window, min(DEFAULT_CI, m0), semantics, False
    )
    k1m = max(k1, 1)
    compiled = fn.lower(
        shape(rows), shape(rows),
        shape(rows, 1, m0), shape(rows, 1, m0), shape(rows, 1, m0),
        shape(rows, k1m, mo), shape(rows, k1m, mo),
    ).compile()
    _assert_kernel(compiled)


def test_searchsorted_compiles(shape):
    from repro.kernels.searchsorted import searchsorted_pallas_call

    fn = jax.jit(functools.partial(searchsorted_pallas_call, interpret=False))
    _assert_kernel(fn.lower(shape(4096), shape(2048)).compile())


def test_membership_compiles(shape):
    from repro.kernels.intersect import membership_pallas_call

    fn = jax.jit(
        functools.partial(membership_pallas_call, window=4, interpret=False)
    )
    _assert_kernel(fn.lower(shape(8192), shape(2048), shape(4)).compile())


def test_elca_segsum_compiles(shape):
    from repro.kernels.elca_segsum import elca_segsum_pallas_call

    fn = jax.jit(functools.partial(elca_segsum_pallas_call, interpret=False))
    _assert_kernel(fn.lower(shape(2048), shape(2048), shape(3, 2048)).compile())


@pytest.mark.parametrize(
    "backend,semantics", [("xla", "slca"), ("xla", "elca"), ("pallas", "elca")]
)
def test_ca_search_batch_compiles(shape, monkeypatch, backend, semantics):
    """The jitted batch search; ``pallas`` membership asks the platform
    rule at trace time, which must answer as on the chip.  ``xla`` is
    compiled at a bucket of the 100k-release cell: three other lists of
    131072 ids searched by 8192."""
    import repro.kernels.ops  # noqa: F401  (registers the pallas backend)
    from repro.core.search_vec import ca_search_batch

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if backend == "xla":
        rows, k1, m0, mo = 1, 3, 8192, 131072
    else:
        rows, k1, m0, mo = 8, 2, 1024, 4096
    compiled = ca_search_batch.lower(
        shape(rows, m0), shape(rows, m0), shape(rows, m0),
        shape(rows, k1, mo), shape(rows, k1, mo), shape(rows), shape(rows, k1),
        semantics=semantics, backend=backend,
    ).compile()
    if backend == "pallas":
        _assert_kernel(compiled)
    else:
        text = compiled.as_text()
        # a sort costs seconds of TPU compile per bucket: the CA set is
        # compacted by prefix sum instead
        assert " sort(" not in text
        # list positions come from a block compare, not a binary search's
        # loop of scalar gathers
        assert " while(" not in text
        # the compares feed their sums in fusions: a materialised
        # lists x queries x heads table (3 x 8192 x 1024) would take 25 MB
        # as bool and 100 MB as int32; the program needs under 1 MB
        assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20
