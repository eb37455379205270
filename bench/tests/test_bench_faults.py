"""A run whose timed path is broken underneath comes out not correct.

Each case drives a whole run on the CPU at a tiny size (the look for a
chip skipped) with one fault planted in the program: an answer altered
where a launch produces it, half of each drained batch left out (where
the cell batches: its one-client cell never drains two queries together),
and, on four shards, the exchange between shards left out of the merge.  A
step that returns its state unchanged has no counterpart in a search
service.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELLS = {
    # a rate high enough that drained windows hold several queries
    "discogs-100k.facet-80": dict(releases=300, shards=1, chips=1,
                                  traffic={"rate_per_s": 100.0}),
    "discogs-40k-4chip.facet-c1": dict(releases=400, shards=4, chips=4,
                                       traffic={"per_client": 400}),
}


def tiny_root(tmp, workload):
    size = CELLS[workload]
    shutil.copytree(os.path.join(ROOT, "bench"), tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bm = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    wl = next((w for w in bm["workloads"] if w["name"] == workload), None)
    if wl is None:  # a cell whose files are ready but not yet registered
        config, mix = workload.split(".")
        wl = {"name": workload, "config": config, "traffic": mix}
        bm["workloads"].append(wl)
        bm["configs"].append(dict(bm["configs"][0], name=config,
                                  file=f"bench/configs/{config}.json"))
    wl["chips"] = size["chips"]
    cfg_path = tmp / "bench" / "configs" / f"{wl['config']}.json"
    cfg = json.loads(cfg_path.read_text())
    cfg.update(releases=size["releases"], shards=size["shards"])
    cfg_path.write_text(json.dumps(cfg))
    tr_path = tmp / "bench" / "traffic" / f"{wl['traffic']}.json"
    tr = json.loads(tr_path.read_text())
    tr.update(size.get("traffic", {}))
    tr_path.write_text(json.dumps(tr))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bm))
    return tmp


def run(tmp, workload, fault, seed=2**31 + 5):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "faults.py"),
         str(tiny_root(tmp, workload)), workload, fault, str(seed)],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_sound_run_is_correct(tmp_path, workload):
    res = run(tmp_path, workload, "none")
    assert res["correct"] is True
    assert res["checks"]["wrong_answers"] == {"value": 0, "limit": 0}


@pytest.mark.parametrize("workload,fault", [
    ("discogs-100k.facet-80", "alter_answer"),
    ("discogs-100k.facet-80", "half_the_batch"),
    ("discogs-40k-4chip.facet-c1", "alter_answer"),
    ("discogs-40k-4chip.facet-c1", "no_exchange"),
])
def test_broken_run_is_not_correct(tmp_path, workload, fault):
    res = run(tmp_path, workload, fault)
    assert res["correct"] is False
    assert res["checks"]["wrong_answers"]["value"] > 0
