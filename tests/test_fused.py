"""Fused single-launch search pipeline == chained paths, everywhere.

Property-style equivalence (seeded random corpora, so the sweep always
runs — no optional deps): the fused kernel must be byte-identical to

  * the jitted xla batch search (``ca_search_batch``) on PlanCache-packed
    batches — random corpora, every semantics (slca/elca/ca), batched rows;
  * an oracle assembled from :mod:`repro.kernels.ref` (``membership_ref``
    for the CA mask + NDesc gather, ``elca_segsum_ref`` for child sums) —
    a route through entirely different code;
  * the scalar engine paths end-to-end (tree + dag index, real corpus).

Edge cases called out by the kernel design: all-pad R-padding rows,
single-element posting lists, single-keyword queries (no streamed phase),
and multi-block windows with clamped revisits (small ``bo`` forces the
window walk, where a non-idempotent ndesc accumulation would double-count
if the revisit mask were wrong).
"""
import numpy as np
import pytest

from repro.core.idlist import IDList, make_pidpos
from repro.core.plan_cache import PlanCache
from repro.core.search_vec import INT_PAD, ca_search_batch
from repro.kernels import ref
from repro.kernels.fused_search import fused_search_batch, run_query_fused
from repro.kernels.shapes import pad_to


# --------------------------------------------------------------------------- #
# Random valid corpora: preorder-numbered trees, ancestor-closed lists
# --------------------------------------------------------------------------- #


def preorder_tree(rng, n):
    """Random tree with preorder ids (descendants contiguous after parent)."""
    raw_par = [-1] + [int(rng.integers(0, i)) for i in range(1, n)]
    kids = [[] for _ in range(n)]
    for i in range(1, n):
        kids[raw_par[i]].append(i)
    par = np.full(n, -1, np.int64)
    stack = [(0, -1)]
    count = 0
    while stack:
        v, p = stack.pop()
        nid = count
        count += 1
        par[nid] = p
        for c in reversed(kids[v]):
            stack.append((c, nid))
    return par


def keyword_list(rng, n, par, n_direct):
    """Ancestor-closed IDList from random direct postings (the invariant
    ``build_containment`` guarantees for real corpora)."""
    direct = rng.choice(n, size=n_direct, replace=False)
    nd: dict[int, int] = {}
    for d in direct:
        v = int(d)
        while v >= 0:
            nd[v] = nd.get(v, 0) + 1
            v = int(par[v])
    ids = np.array(sorted(nd), dtype=np.int32)
    ndesc = np.array([nd[i] for i in sorted(nd)], dtype=np.int32)
    return IDList(ids=ids, pidpos=make_pidpos(ids, par), ndesc=ndesc)


def random_items(rng, n_items, k):
    items = []
    for _ in range(n_items):
        n = int(rng.integers(5, 400))
        par = preorder_tree(rng, n)
        items.append([
            keyword_list(rng, n, par, int(rng.integers(1, max(2, n // 2))))
            for _ in range(k)
        ])
    return items


# --------------------------------------------------------------------------- #
# Kernel-level: fused == xla batch search on packed batches
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("semantics", ["slca", "elca", "ca"])
@pytest.mark.parametrize("k", [1, 2, 3])
def test_fused_matches_xla_batch(semantics, k):
    rng = np.random.default_rng(k * 100 + len(semantics))
    cache = PlanCache(backend="fused")
    for trial in range(6):
        items = random_items(rng, int(rng.integers(1, 6)), k)
        keys = list(range(len(items)))
        batch, kept, sig = cache.pack(items, keys, semantics, "fused")
        assert batch is not None
        w_ids, w_mask = ca_search_batch(
            **batch, semantics=semantics, backend="xla"
        )
        g_ids, g_mask = fused_search_batch(**batch, semantics=semantics)
        for r in range(len(kept)):
            np.testing.assert_array_equal(
                np.sort(np.asarray(w_ids[r])[np.asarray(w_mask[r])]),
                g_ids[r][g_mask[r]],
                err_msg=f"trial={trial} row={r} {semantics} k={k}",
            )


def test_fused_multi_block_window_revisit():
    """Tiny ``bo`` forces nob > 1 and window clamping: the ndesc
    accumulation is NOT idempotent, so a wrong revisit mask double-counts
    and breaks ELCA here."""
    rng = np.random.default_rng(7)
    cache = PlanCache(backend="fused")
    for semantics in ("slca", "elca"):
        items = random_items(rng, 3, 3)
        batch, kept, _ = cache.pack(items, list(range(3)), semantics, "fused")
        w_ids, w_mask = ca_search_batch(
            **batch, semantics=semantics, backend="xla"
        )
        stats: dict = {}
        g_ids, g_mask = fused_search_batch(
            **batch, semantics=semantics, bo=16, stats=stats
        )
        assert stats["nob"] > 1  # the window walk actually happened
        for r in range(len(kept)):
            np.testing.assert_array_equal(
                np.sort(np.asarray(w_ids[r])[np.asarray(w_mask[r])]),
                g_ids[r][g_mask[r]],
                err_msg=f"row={r} {semantics}",
            )


def test_fused_all_pad_rows():
    """R-padding rows (n0 == 0, all-INT_PAD lists) must yield empty rows."""
    rng = np.random.default_rng(11)
    cache = PlanCache(backend="fused", min_rows=8)
    items = random_items(rng, 3, 2)  # rows bucket to 8 => 5 all-pad rows
    batch, kept, sig = cache.pack(items, list(range(3)), "slca", "fused")
    assert sig.rows == 8 and len(kept) == 3
    g_ids, g_mask = fused_search_batch(**batch, semantics="slca")
    for r in range(3, 8):
        assert not g_mask[r].any()
        assert np.all(g_ids[r] == INT_PAD)
    w_ids, w_mask = ca_search_batch(**batch, semantics="slca", backend="xla")
    for r in range(3):
        np.testing.assert_array_equal(
            np.sort(np.asarray(w_ids[r])[np.asarray(w_mask[r])]),
            g_ids[r][g_mask[r]],
        )


def test_fused_single_element_lists():
    """Every list one element (the root): the root is the lone CA/SLCA."""
    for k in (1, 2, 3):
        lists = [
            IDList(
                ids=np.array([0], np.int32),
                pidpos=np.array([-1], np.int32),
                ndesc=np.array([1], np.int32),
            )
            for _ in range(k)
        ]
        for semantics in ("slca", "elca", "ca"):
            got = run_query_fused(lists, semantics=semantics)
            np.testing.assert_array_equal(got, np.array([0], np.int64))


def test_fused_interpret_override():
    """The explicit keyword wins over the platform rule: interpret=True
    must work regardless."""
    rng = np.random.default_rng(3)
    cache = PlanCache(backend="fused")
    items = random_items(rng, 2, 2)
    batch, _, _ = cache.pack(items, [0, 1], "slca", "fused")
    a_ids, a_mask = fused_search_batch(
        **batch, semantics="slca", interpret=True
    )
    b_ids, b_mask = fused_search_batch(**batch, semantics="slca")
    np.testing.assert_array_equal(a_ids, b_ids)
    np.testing.assert_array_equal(a_mask, b_mask)


# --------------------------------------------------------------------------- #
# vs kernels/ref.py: CA mask + gather from membership_ref, child sums from
# elca_segsum_ref — an oracle through entirely different code
# --------------------------------------------------------------------------- #


def _ref_oracle_row(ids0, pid0, nd0, oth, ond, n0, semantics):
    m0 = ids0.shape[0]
    valid = np.arange(m0) < n0
    ca = valid.copy()
    nds = [nd0.astype(np.int64)]
    for kk in range(oth.shape[0]):
        f, p = ref.membership_ref(oth[kk], ids0)
        ca &= np.asarray(f)
        nds.append(ond[kk][np.asarray(p)].astype(np.int64))
    ca_ids = ids0[ca].astype(np.int64)  # ids0 ascending => already sorted
    if semantics == "ca":
        return ca_ids
    par = pid0[ca].astype(np.int64)
    if semantics == "slca":
        nxt = np.concatenate([par[1:], [-1]])
        return ca_ids[nxt != ca_ids]
    nd_ca = np.stack([row[ca] for row in nds])  # [k, m]
    sums = np.asarray(
        ref.elca_segsum_ref(
            pad_to(ca_ids, 128, INT_PAD),
            pad_to(par, 128, -1),
            pad_to(nd_ca, 128, 0),
        )
    )[:, : ca_ids.size]
    return ca_ids[np.all(nd_ca - sums >= 1, axis=0)]


@pytest.mark.parametrize("semantics", ["slca", "elca", "ca"])
def test_fused_matches_ref_oracle(semantics):
    rng = np.random.default_rng(42)
    cache = PlanCache(backend="fused")
    for trial in range(4):
        k = int(rng.integers(1, 4))
        items = random_items(rng, 3, k)
        batch, kept, _ = cache.pack(items, [0, 1, 2], semantics, "fused")
        g_ids, g_mask = fused_search_batch(**batch, semantics=semantics)
        for r in range(len(kept)):
            want = _ref_oracle_row(
                batch["ids0"][r], batch["pid0"][r], batch["ndesc0"][r],
                batch["other_ids"][r], batch["other_ndesc"][r],
                int(batch["n0"][r]), semantics,
            )
            np.testing.assert_array_equal(
                g_ids[r][g_mask[r]].astype(np.int64), want,
                err_msg=f"trial={trial} row={r} {semantics} k={k}",
            )


# --------------------------------------------------------------------------- #
# End-to-end: fused backend == scalar backend on a real corpus
# --------------------------------------------------------------------------- #


def test_fused_query_end_to_end():
    from repro.core import KeywordSearchEngine
    from repro.data import QUERIES, generate_discogs_tree

    tree = generate_discogs_tree(n_releases=60, seed=3)
    eng = KeywordSearchEngine(tree)
    for q, (_cat, kws) in QUERIES.items():
        for sem in ("slca", "elca"):
            want = eng.query(kws, semantics=sem, index="tree", backend="scalar")
            for index in ("tree", "dag"):
                got = eng.query(kws, semantics=sem, index=index, backend="fused")
                np.testing.assert_array_equal(
                    got, want, err_msg=f"{q} {sem} {index}"
                )


def test_fused_batched_service_drain():
    from repro.core import KeywordSearchEngine
    from repro.data import QUERIES, generate_discogs_tree
    from repro.serve.service import QueryService

    tree = generate_discogs_tree(n_releases=30, seed=5)
    eng = KeywordSearchEngine(tree)
    queries = [kws for _, kws in QUERIES.values()]
    with QueryService(eng, backend="fused", batch_window_ms=2.0) as svc:
        for sem in ("slca", "elca"):
            got = svc.map(queries, semantics=sem)
            for kws, res in zip(queries, got):
                want = eng.query(kws, semantics=sem, backend="scalar")
                np.testing.assert_array_equal(res, want, err_msg=f"{kws} {sem}")
    assert eng.plan_cache.snapshot()["fused_fallbacks"] == 0


def test_fused_phase_span_and_fallback_counter():
    """Traced fused launches emit one ``kernel.fused_round`` span (the
    kernel's wrapper uploads and reads back, so it has no child spans)
    after a ``plan.pack`` that carries the launch's bytes; a giant m0
    bucket demotes to the chained path and bumps ``fused_fallbacks``."""
    from repro.core import KeywordSearchEngine
    from repro.data import generate_discogs_tree
    import repro.kernels.fused_search as fs

    tree = generate_discogs_tree(n_releases=10, seed=5)
    eng = KeywordSearchEngine(tree, plan_cache=PlanCache(backend="fused"))
    phases: list = []
    eng._query(["vinyl", "reissue"], "slca", "dag", "fused", None, phases=phases)
    names = [p["name"] for p in phases]
    assert "kernel.fused_round" in names
    assert "kernel.upload" not in names
    pack = phases[names.index("plan.pack")]["attrs"]
    assert 0 < pack["useful_bytes"] <= pack["bytes"]
    # shape-cap fallback: demoted launches still answer, and are counted
    old = fs.MAX_FUSED_M0
    fs.MAX_FUSED_M0 = 1
    try:
        want = eng._query(["vinyl", "reissue"], "slca", "dag", "scalar", None)
        got = eng._query(["vinyl", "reissue"], "slca", "dag", "fused", None)
        np.testing.assert_array_equal(got, want)
        assert eng.plan_cache.fused_fallbacks > 0
    finally:
        fs.MAX_FUSED_M0 = old


# --------------------------------------------------------------------------- #
# Interpret mode follows the platform, never the environment
# --------------------------------------------------------------------------- #


def test_interpret_env_parsing(monkeypatch):
    """Interpret mode is the platform's rule, not an environment knob: tpu
    compiles, cpu interprets, anything else is an error, and an explicit
    keyword always wins."""
    import jax

    from repro.device import pallas_interpret

    assert jax.default_backend() == "cpu"
    assert pallas_interpret() is True
    assert pallas_interpret(False) is False
    assert pallas_interpret(True) is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pallas_interpret() is False
    assert pallas_interpret(True) is True
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        pallas_interpret()
    assert pallas_interpret(False) is False


def test_compile_cache_dir(monkeypatch):
    """The persistent compile cache goes where JAX_COMPILATION_CACHE_DIR
    says (JAX reads it; nothing else is set), else to the fixed
    ``<checkout>/.jax_cache``."""
    import os

    import jax

    from repro.device import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(checkout, ".jax_cache")
        assert enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
