"""Each traffic mix is deterministic for a seed and holds its parameters."""
import collections
import glob
import os

import numpy as np
import pytest

from bench import traffic
from bench.corpus import QUERIES

MIXES = sorted(os.path.basename(p)[:-5]
               for p in glob.glob(os.path.join(traffic.TRAFFIC_DIR, "*.json")))


def requests(spec, seed):
    if spec["loop"] == "open":
        return [(i, s) for _, i, s in traffic.open_schedule(spec, 30.0, seed)]
    return [p for seq in traffic.closed_sequences(spec, seed) for p in seq]


@pytest.mark.parametrize("mix", MIXES)
def test_deterministic_for_a_seed(mix):
    spec = traffic.load(mix)
    assert traffic.pool(spec) == traffic.pool(spec)
    assert requests(spec, 2**31 + 11) == requests(spec, 2**31 + 11)
    assert requests(spec, 3) != requests(spec, 4)


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_reorder_the_same_work(mix):
    spec = traffic.load(mix)
    a, b = requests(spec, 1), requests(spec, 2**33)
    assert sorted(a) == sorted(b)
    if spec["loop"] == "open":
        gaps = [sorted(np.diff([0.0] + [t for t, _, _ in
                                        traffic.open_schedule(spec, 30.0, s)]))
                for s in (1, 2)]
        assert np.allclose(gaps[0], gaps[1])


@pytest.mark.parametrize("mix", MIXES)
def test_pool_holds_its_parameters(mix):
    spec = traffic.load(mix)
    pool = traffic.pool(spec)
    p = spec["pool"]
    assert len(pool) == p["size"]
    assert len({frozenset(q) for q in pool}) == p["size"]
    head = 9 if p.get("paper_queries_first") else 0
    assert pool[:head] == [kws for _, kws in QUERIES.values()][:head]
    ks = collections.Counter(len(q) for q in pool[head:])
    assert set(ks) == set(p["k"])
    share = len(pool[head:]) / len(p["k"])
    assert all(abs(v - share) <= 1 for v in ks.values())


@pytest.mark.parametrize("mix", MIXES)
def test_requests_hold_slca_share_and_zipf_head(mix):
    spec = traffic.load(mix)
    reqs = requests(spec, 7)
    n = len(reqs)
    slca = sum(1 for _, s in reqs if s == "slca")
    assert slca == round(n * spec["slca_share"])
    size = spec["pool"]["size"]
    w = 1.0 / np.arange(1, size + 1) ** spec["zipf_s"]
    head = w[:9].sum() / w.sum()  # the share of ranks 1-9
    got = sum(1 for i, _ in reqs if i < 9) / n
    assert abs(got - head) < 4 * np.sqrt(head * (1 - head) / n)


def test_open_loop_rate_and_window():
    spec = traffic.load("facet-80")
    sch = traffic.open_schedule(spec, 30.0, 9)
    assert len(sch) == round(spec["rate_per_s"] * 30.0)
    times = [t for t, _, _ in sch]
    assert times == sorted(times) and 0 < times[0] and abs(times[-1] - 30) < 1e-9
