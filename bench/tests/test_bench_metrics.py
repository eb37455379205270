"""Each metric reader against values worked by hand on synthetic runs."""
import os

import pytest

from bench import devtrace
from bench.harness import BENCH, Ctx, load_benchmark, reader


def span(name, t0, dur, children=(), **attrs):
    return {"name": name, "t0_ms": t0, "dur_ms": dur, "attrs": attrs,
            "children": list(children)}


# one request that ran: gateway 10 ms, of which the cache probe 0.5 and the
# router 8; the shard gathered for 6, its query queued 1 ms before the 4 ms
# execute (pack 0.5, launch 2); the merge took 1 ms
RAN = [span("gateway.request", 0.0, 10.0, [
    span("gateway.cache", 0.5, 0.5),
    span("router.submit", 1.0, 8.0, [
        span("shard.gather", 1.5, 6.0, [
            span("service.execute", 3.0, 4.0, [
                span("plan.pack", 3.5, 0.5, rows=1, k=2, m0=16, mo=32),
                span("kernel.ca_search", 4.0, 2.0),
            ], queued_ms=1.0),
        ]),
        span("router.merge", 7.6, 1.0),
    ]),
])]
# one request the edge cache answered
CACHED = [span("gateway.request", 20.0, 2.0, [span("gateway.cache", 20.5, 0.5)])]


@pytest.fixture
def ctx():
    tr = devtrace.from_events(
        {"/device:TPU:0": {"XLA Ops": [("a", 10e6, 10e6), ("b", 15e6, 15e6),
                                       ("c", 50e6, 10e6)]}},
        mark_ns=0.0, mark_wall_ms=0.0, window_ms=(0.0, 100.0))
    return Ctx(
        seconds=2.0, setup_s=42.0,
        records=[{"scheduled": 0.5, "sent": 0.5, "done": 0.6, "status": 200},
                 {"scheduled": 1.0, "sent": 1.0, "done": 1.3, "status": 200},
                 {"scheduled": 1.5, "sent": 1.6, "done": 2.0, "status": 200}],
        stats0={"queries": 10, "coalesced": 1, "plan_launches_total": 5},
        stats1={"queries": 30, "coalesced": 3, "plan_launches_total": 11},
        cache0={"hits": 4, "misses": 4}, cache1={"hits": 5, "misses": 5},
        device_kind="TPU v5 lite", devices=["/device:TPU:0"],
        traces=[RAN, CACHED], device=tr,
    )


WANT = {
    "p50_ms": 300.0,                 # latencies 100, 300, 500 ms
    "p95_ms": 480.0,                 # 300 + 0.9 * 200
    "qps": 1.5,                      # 3 answers over 2 s
    "setup_s": 42.0,
    "gateway.self_ms": 1.5,          # (10 - 0.5 - 8) and (2 - 0.5)
    "gateway.cache_hit_share": 50.0,
    "router.self_ms": 1.5,           # (1 + 1 + 1) and 0
    "router.self_ms.c1": 1.5,
    "router.coalesced_share": 10.0,  # 2 of 20
    "service.queued_ms": 1.0,
    "plan.pack_ms": 0.25,            # 0.5 and 0
    "plan.launches_per_query": 2.0,  # 6 launches, 3 requests
    "kernel.launch_ms": 1.0,         # 2 and 0
    "kernel.launch_ms.c1": 1.0,
    "device.idle_share": 70.0,       # busy [10, 30) and [50, 60) of 100 ms
    "device.idle_share.c1": 70.0,
    "p95_ms.c1": 480.0,
    # 536 compulsory bytes over 819 GB/s, against 30 ms busy
    "search_roofline": 100.0 * 536 / 819e9 / 0.030,
}


def test_every_metric_has_a_worked_value():
    bm = load_benchmark()
    named = {m["name"] for m in bm["end_to_end"] + bm["per_layer"]}
    files = {f[:-3] for f in os.listdir(os.path.join(BENCH, "metrics"))
             if f.endswith(".py")}
    assert named <= files == set(WANT)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader(ctx, name):
    assert reader(name, BENCH)(ctx) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", ["device.idle_share", "search_roofline",
                                  "gateway.self_ms", "service.queued_ms"])
def test_nothing_to_read_gives_nothing(ctx, name):
    ctx.traces, ctx.device = [], None
    assert reader(name, BENCH)(ctx) is None
