import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

__doc__ = """Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST stay first (before any jax-importing import): jax
locks the device count at first init, and only the dry-run wants 512
placeholder host devices.

Per valid cell this driver:
  1. builds the production mesh (16,16) or (2,16,16);
  2. builds ShapeDtypeStruct inputs (no allocation);
  3. jit-lowers + compiles the *production* step (scan-over-layers) with
     explicit shardings — memory_analysis() proves fit, and a successful
     compile proves the distribution config is coherent;
  4. (single-pod) additionally lowers two *accounting clones* — depth 1 and
     2 scan units, fully unrolled — because XLA cost analysis counts while
     bodies once; linear extrapolation
         total = m1 + (num_steps - 1) · (m2 - m1)
     then yields exact per-device FLOPs / bytes / collective bytes for the
     §Roofline table;
  5. appends a JSON record to --out.

Usage:
  python -m repro.launch.dryrun --arch smollm-135m --shape train_4k
  python -m repro.launch.dryrun --all --multi-pod both --out results.json
  python -m repro.launch.dryrun --search   # the paper's engine on the mesh
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (
    SHAPES,
    all_cells,
    cell_applicable,
    get_config,
    input_specs_for,
)
from repro.dist import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.models import ModelConfig, decode_step, init_params, prefill
from repro.roofline.analysis import analyze, collective_bytes_from_hlo, model_flops_for
from repro.train.optimizer import pick_optimizer
from repro.train.train_step import make_train_step


def _named(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def microbatches_for(cfg: ModelConfig, shape: str) -> int:
    """Activation-memory knob for the big training cells (§Perf tunes this)."""
    if shape != "train_4k":
        return 1
    n = cfg.param_count()
    if n >= 100e9:
        return 16  # 340-400B: peak temp must stay under 16GB/chip
    if n >= 10e9:
        return 2
    return 1


def _depth_clone(cfg: ModelConfig, units: int) -> ModelConfig:
    """A depth-``units`` clone with the scan fully unrolled (exact costs)."""
    return dataclasses.replace(
        cfg,
        n_layers=cfg.scan_period * units,
        layer_pattern=cfg.scan_unit * units,
        scan_unroll=units,
        head_dim=cfg.head_dim,
    )


def lower_step(cfg: ModelConfig, shape: str, mesh, microbatches: int = 1):
    """Build + lower the production step for (cfg, shape) on mesh."""
    from repro.dist import ctx as shard_ctx

    with shard_ctx.use(mesh):
        return _lower_step_inner(cfg, shape, mesh, microbatches)


def _lower_step_inner(cfg: ModelConfig, shape: str, mesh, microbatches: int = 1):
    cell = SHAPES[shape]
    specs = input_specs_for(cfg, shape)

    params_shape = jax.eval_shape(
        lambda key: init_params(key, cfg), jax.ShapeDtypeStruct((2,), jnp.uint32)
    )
    p_spec = shd.param_specs(params_shape, mesh)

    if cell.kind == "train":
        init_state, train_step = make_train_step(
            cfg, optimizer=pick_optimizer(cfg), microbatches=microbatches
        )
        state_shape = jax.eval_shape(init_state, params_shape)
        state_spec = shd.param_specs(state_shape, mesh)
        batch_spec = shd.data_specs(specs["batch"], mesh)
        return jax.jit(
            train_step,
            in_shardings=(_named(mesh, state_spec), _named(mesh, batch_spec)),
            out_shardings=(_named(mesh, state_spec), None),
            donate_argnums=(0,),
        ).lower(state_shape, specs["batch"])

    if cell.kind == "prefill":
        if cfg.encoder_only:
            from repro.models.model import forward

            def enc_fwd(params, embeddings):
                logits, _ = forward(params, cfg, embeddings=embeddings)
                return logits

            e_spec = shd.data_specs(specs["embeddings"], mesh)
            return jax.jit(
                enc_fwd,
                in_shardings=(_named(mesh, p_spec), _named(mesh, e_spec)),
            ).lower(params_shape, specs["embeddings"])

        cache_spec = shd.cache_specs(specs["cache"], mesh)
        if "embeddings" in specs:
            def fn(params, tokens, cache, embeddings):
                return prefill(params, cfg, tokens, cache, embeddings=embeddings)

            return jax.jit(
                fn,
                in_shardings=(
                    _named(mesh, p_spec),
                    _named(mesh, shd.data_specs(specs["tokens"], mesh)),
                    _named(mesh, cache_spec),
                    _named(mesh, shd.data_specs(specs["embeddings"], mesh)),
                ),
                out_shardings=(None, _named(mesh, cache_spec)),
                donate_argnums=(2,),
            ).lower(
                params_shape, specs["tokens"], specs["cache"], specs["embeddings"]
            )

        def fn(params, tokens, cache):
            return prefill(params, cfg, tokens, cache)

        return jax.jit(
            fn,
            in_shardings=(
                _named(mesh, p_spec),
                _named(mesh, shd.data_specs(specs["tokens"], mesh)),
                _named(mesh, cache_spec),
            ),
            out_shardings=(None, _named(mesh, cache_spec)),
            donate_argnums=(2,),
        ).lower(params_shape, specs["tokens"], specs["cache"])

    # decode
    cache_spec = shd.cache_specs(specs["cache"], mesh)

    def fn(params, token, cache, step_position):
        return decode_step(params, cfg, token, cache, step_position)

    return jax.jit(
        fn,
        in_shardings=(
            _named(mesh, p_spec),
            _named(mesh, shd.data_specs(specs["token"], mesh)),
            _named(mesh, cache_spec),
            None,
        ),
        out_shardings=(None, _named(mesh, cache_spec)),
        donate_argnums=(2,),
    ).lower(params_shape, specs["token"], specs["cache"], specs["step_position"])


def _extract_costs(compiled) -> dict:
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):  # older jaxlib: one dict per device
        cost = cost[0] if cost else {}
    coll = collective_bytes_from_hlo(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": float(coll["total"]),
        "collectives": coll,
    }


def account_cell(cfg: ModelConfig, shape: str, mesh, microbatches: int) -> dict:
    """Exact per-device costs via the 1-unit / 2-unit clone extrapolation."""
    m1 = _extract_costs(lower_step(_depth_clone(cfg, 1), shape, mesh, 1).compile())
    steps = cfg.num_scan_steps
    if steps == 1:  # whole net in one unit (e.g. deepseek): m1 is exact
        m2 = m1
    else:
        m2 = _extract_costs(
            lower_step(_depth_clone(cfg, 2), shape, mesh, 1).compile()
        )

    def extra(key):
        # clamp: GSPMD occasionally emits *fewer* collectives at depth 2
        # (cross-layer CSE), which would extrapolate negative
        return max(0.0, m1[key] + (steps - 1) * (m2[key] - m1[key]))

    return {
        "flops": extra("flops"),
        "bytes": extra("bytes"),
        "collective_bytes": extra("collective_bytes"),
        "per_unit": {k: m2[k] - m1[k] for k in ("flops", "bytes", "collective_bytes")},
        "outside": {k: 2 * m1[k] - m2[k] for k in ("flops", "bytes", "collective_bytes")},
    }


def lower_cell(
    arch: str,
    shape: str,
    multi_pod: bool,
    verbose: bool = True,
    with_accounting: bool | None = None,
    cfg_override: ModelConfig | None = None,
    microbatches: int | None = None,
) -> dict:
    cfg = cfg_override or get_config(arch)
    cell = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(len(mesh.devices.flat))
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    mb = microbatches_for(cfg, shape) if microbatches is None else microbatches
    if with_accounting is None:
        with_accounting = not multi_pod

    t0 = time.time()
    with mesh:
        compiled = lower_step(cfg, shape, mesh, mb).compile()
        mem = compiled.memory_analysis()
        full_costs = _extract_costs(compiled)
        acct = account_cell(cfg, shape, mesh, mb) if with_accounting else None

    rec = {
        "arch": arch,
        "shape": shape,
        "mesh": mesh_name,
        "chips": chips,
        "microbatches": mb,
        "ok": True,
        "compile_s": round(time.time() - t0, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        },
        "hlo_once_costs": full_costs,  # while bodies counted once (reference)
    }
    if acct is not None:
        report = analyze(
            arch=arch,
            shape=shape,
            mesh_name=mesh_name,
            chips=chips,
            cost={"flops": acct["flops"], "bytes accessed": acct["bytes"]},
            hlo_text="",
            model_flops=model_flops_for(cfg, cell, cell.kind == "train"),
        )
        # patch the collective term with the extrapolated bytes
        from repro.roofline.analysis import V5E, peaks

        report.collective_bytes = acct["collective_bytes"]
        report.collective_s = acct["collective_bytes"] / peaks(V5E)["ici_bw"]
        terms = {
            "compute": report.compute_s,
            "memory": report.memory_s,
            "collective": report.collective_s,
        }
        report.bottleneck = max(terms, key=terms.get)
        report.step_time_bound_s = max(terms.values())
        report.hw_fraction = (
            report.compute_s / report.step_time_bound_s
            if report.step_time_bound_s
            else 0.0
        )
        rec["accounting"] = acct
        rec["roofline"] = report.to_dict()

    if verbose:
        ms = rec["memory"]
        line = (
            f"[dryrun] {arch} × {shape} × {mesh_name}: OK "
            f"({rec['compile_s']}s) args={_gb(ms['argument_bytes'])} "
            f"temp={_gb(ms['temp_bytes'])}"
        )
        if acct is not None:
            r = rec["roofline"]
            line += (
                f"\n         roofline: compute={r['compute_s']*1e3:.2f}ms"
                f" memory={r['memory_s']*1e3:.2f}ms"
                f" collective={r['collective_s']*1e3:.2f}ms"
                f" -> {r['bottleneck']}-bound"
                f" useful={r['useful_ratio']:.2f} frac={r['hw_fraction']:.2f}"
            )
        print(line, flush=True)
    return rec


def lower_search(multi_pod: bool, verbose: bool = True) -> dict:
    """Dry-run for the paper's engine: batched distributed keyword search."""
    from repro.dist.search_shard import make_distributed_search

    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    m = mesh.shape["model"]
    k, seg, q = 3, 8192, 64 * (2 if multi_pod else 1)
    fn = make_distributed_search(mesh, k, "elca")
    spec = jax.ShapeDtypeStruct((q, k, m, seg), jnp.int32)
    t0 = time.time()
    with mesh:
        lowered = jax.jit(fn).lower(spec, spec, spec)
        compiled = lowered.compile()
    costs = _extract_costs(compiled)
    rec = {
        "arch": "idcluster-search",
        "shape": f"q{q}_seg{seg}_k{k}",
        "mesh": mesh_name,
        "ok": True,
        "compile_s": round(time.time() - t0, 1),
        "hlo_once_costs": costs,
    }
    if verbose:
        print(
            f"[dryrun] idcluster-search × {mesh_name}: OK ({rec['compile_s']}s) "
            f"coll={_gb(costs['collective_bytes'])}",
            flush=True,
        )
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", choices=["off", "on", "both"], default="off")
    ap.add_argument("--search", action="store_true")
    ap.add_argument("--no-accounting", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    pods = {"off": [False], "on": [True], "both": [False, True]}[args.multi_pod]
    records = []
    cells: list[tuple[str, str]] = []
    if args.all:
        cells = [(a, s) for a, s, ok, _ in all_cells() if ok]
    elif args.arch and args.shape:
        cells = [(args.arch, args.shape)]
    elif not args.search:
        ap.error("need --arch+--shape, --all, or --search")

    failed = 0
    for multi_pod in pods:
        if args.search:
            records.append(lower_search(multi_pod))
        for arch, shape in cells:
            ok, reason = cell_applicable(get_config(arch), shape)
            if not ok:
                print(f"[dryrun] {arch} × {shape}: SKIP ({reason})", flush=True)
                continue
            try:
                records.append(
                    lower_cell(
                        arch, shape, multi_pod,
                        with_accounting=(not multi_pod) and not args.no_accounting,
                    )
                )
            except Exception as e:  # a failure here is a bug in the system
                failed += 1
                traceback.print_exc()
                records.append(
                    {"arch": arch, "shape": shape, "multi_pod": multi_pod,
                     "ok": False, "error": f"{type(e).__name__}: {e}"}
                )
    if args.out:
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
        print(f"[dryrun] wrote {len(records)} records -> {args.out}", flush=True)
    if failed:
        print(f"[dryrun] {failed} FAILURES", flush=True)
    return 1 if failed else 0


def _gb(n):
    return "-" if n is None else f"{n/2**30:.2f}GiB"


if __name__ == "__main__":
    sys.exit(main())
