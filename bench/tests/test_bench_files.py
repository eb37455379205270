"""BENCHMARK.json holds its contract, and a cell, a traffic mix and a metric
are added as new files, with no edit to a file already there."""
import hashlib
import json
import os
import re
import shutil

import pytest

from bench import harness, traffic

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bm():
    return harness.load_benchmark()


def test_keys_names_and_units(bm):
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bm[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bm["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_files_and_readers_exist(bm):
    for c in bm["configs"]:
        assert c["file"].startswith(bm["paths"][0] + "/")
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["name"] == c["name"]
        assert all(k in cfg for k in c["reduced"])
    for w in bm["workloads"]:
        harness.resolve(bm, w["name"], False)
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert callable(harness.reader(m["name"]))


def test_every_cell_reports_what_its_metrics_move(bm):
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    for w in bm["workloads"]:
        name = w["name"]
        mine = [m["name"] for m in bm["end_to_end"]
                if name in m.get("workloads", [name])]
        assert "setup_s" in mine and len(mine) >= 2
        layer = [m for m in bm["per_layer"]
                 if name in m.get("workloads", [name])]
        assert layer
    for m in bm["per_layer"]:
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in moved.get("workloads", [w])


def test_run_seconds_fits_a_full_check(bm):
    runs = 2 + 14 * 24
    total = runs * (bm["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert 1 <= bm["run_seconds"] <= 51 and total <= 43200


def digest_tree(path):
    h = {}
    for dirpath, _, files in os.walk(path):
        for f in files:
            p = os.path.join(dirpath, f)
            h[os.path.relpath(p, path)] = hashlib.sha256(
                open(p, "rb").read()).hexdigest()
    return h


def test_new_cell_mix_and_metric_are_new_files(tmp_path, bm):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = digest_tree(tmp_path / "bench")
    cfg = json.load(open(os.path.join(ROOT, "bench/configs/discogs-100k.json")))
    cfg.update(name="discogs-2k", releases=2000)
    (tmp_path / "bench/configs/discogs-2k.json").write_text(json.dumps(cfg))
    mix = traffic.load("facet-80")
    mix.update(rate_per_s=3.0, slca_share=1.0)
    (tmp_path / "bench/traffic/slca-3.json").write_text(json.dumps(mix))
    (tmp_path / "bench/metrics/answers_per_s.py").write_text(
        "def read(ctx):\n    return len(ctx.records) / ctx.seconds\n")
    new = dict(bm)
    new["configs"] = bm["configs"] + [dict(
        bm["configs"][0], name="discogs-2k", file="bench/configs/discogs-2k.json")]
    new["workloads"] = bm["workloads"] + [{
        "name": "discogs-2k.slca-3", "config": "discogs-2k",
        "traffic": "slca-3", "chips": 1, "why": "a test cell"}]
    new["per_layer"] = bm["per_layer"] + [{
        "name": "answers_per_s", "unit": "req/s", "better": "higher",
        "source": "host_clock", "layer": "client", "moves": "qps",
        "workloads": ["discogs-2k.slca-3"]}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))
    after = digest_tree(tmp_path / "bench")
    assert all(after[k] == v for k, v in before.items())
    cell = harness.resolve(harness.load_benchmark(str(tmp_path)),
                           "discogs-2k.slca-3", True, str(tmp_path / "bench"))
    assert cell.config["releases"] == 2000
    assert cell.traffic["slca_share"] == 1.0
    assert [m["name"] for m in cell.metrics] == ["answers_per_s"]
    read = harness.reader("answers_per_s", str(tmp_path / "bench"))
    ctx = harness.Ctx(seconds=2.0, setup_s=0, records=[{}] * 6,
                      stats0={}, stats1={}, cache0={}, cache1={},
                      device_kind="", devices=[])
    assert read(ctx) == 3.0
