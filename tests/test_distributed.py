"""Distributed-layer tests that need multiple devices.

jax locks the device count at first init, so these run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8.  Covered:
  * shard_map distributed IDList search (halo/all-gather semantics) equals
    the scalar engine on both semantics;
  * sharded train_step on a (4, 2) mesh produces the same loss trajectory as
    the single-device step (numerical sanity of the sharding rules);
  * elastic checkpoint restore onto a different mesh shape.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys, json
    sys.path.insert(0, %(src)r)
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import AxisType
    AUTO = (AxisType.Auto,) * 2  # sharding constraints need Auto axes

    out = {}

    # ---- distributed search equals scalar engine -------------------------
    from repro.core import KeywordSearchEngine
    from repro.data import generate_discogs_tree, QUERIES
    from repro.dist.search_shard import distributed_query
    tree = generate_discogs_tree(n_releases=60, seed=11)
    eng = KeywordSearchEngine(tree)
    mesh = jax.make_mesh((1, 8), ("data", "model"), axis_types=AUTO)
    checks = 0
    for q, (cat, kws) in QUERIES.items():
        kk = eng.keyword_ids(kws)
        lists = eng.base.idlists(kk)
        for sem in ("slca", "elca"):
            want = eng.query(kws, semantics=sem, index="tree", backend="scalar")
            got = distributed_query(lists, mesh, semantics=sem)
            assert np.array_equal(got, want), (q, sem)
            checks += 1
    out["search_checks"] = checks

    # ---- sharded train step == single-device train step -------------------
    from repro.configs import get_config
    from repro.dist import sharding as shd, ctx as shard_ctx
    from repro.models import init_params
    from repro.train.train_step import make_train_step
    cfg = get_config("smollm-135m").reduced(n_layers=2, d_model=64,
                                            vocab=128, n_heads=4, n_kv_heads=2)
    init_state, train_step = make_train_step(cfg, optimizer="adamw", base_lr=1e-3)
    params = init_params(jax.random.key(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.key(1), (8, 32), 0, cfg.vocab)}

    ref_state, ref_metrics = jax.jit(train_step)(init_state(params), batch)

    mesh2 = jax.make_mesh((4, 2), ("data", "model"), axis_types=AUTO)
    with shard_ctx.use(mesh2):
        state_shape = jax.eval_shape(lambda: init_state(params))
        spec = shd.param_specs(state_shape, mesh2)
        dspec = shd.data_specs(batch, mesh2)
        with mesh2:
            jitted = jax.jit(
                train_step,
                in_shardings=(shd.to_named(spec, mesh2), shd.to_named(dspec, mesh2)),
                out_shardings=(shd.to_named(spec, mesh2), None),
            )
            sh_state, sh_metrics = jitted(init_state(params), batch)
    out["loss_ref"] = float(ref_metrics["loss"])
    out["loss_sharded"] = float(sh_metrics["loss"])
    # param agreement after one step
    diffs = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))),
        ref_state["params"], jax.device_get(sh_state["params"]))
    out["max_param_diff"] = max(jax.tree.leaves(diffs))

    # ---- elastic restore onto a different mesh ----------------------------
    import tempfile
    from repro.train import checkpoint as ckpt
    d = tempfile.mkdtemp()
    ckpt.save_checkpoint(d, 1, ref_state)
    like = init_state(params)
    mesh3 = jax.make_mesh((2, 4), ("data", "model"), axis_types=AUTO)
    spec3 = shd.param_specs(jax.eval_shape(lambda: like), mesh3)
    restored, _ = ckpt.restore_checkpoint(
        d, like, shardings=shd.to_named(spec3, mesh3))
    rd = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))),
        ref_state["params"], restored["params"])
    out["restore_diff"] = max(jax.tree.leaves(rd))
    print("RESULT " + json.dumps(out))
    """
)


@pytest.fixture(scope="module")
def results():
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT % {"src": os.path.abspath(src)}],
        capture_output=True, text=True, timeout=1200,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_distributed_search_matches(results):
    assert results["search_checks"] == 18


def test_sharded_train_step_matches(results):
    assert abs(results["loss_ref"] - results["loss_sharded"]) < 0.05
    assert results["max_param_diff"] < 0.05


def test_elastic_restore(results):
    assert results["restore_diff"] < 1e-5
