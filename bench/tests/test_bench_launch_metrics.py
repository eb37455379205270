"""The readers of the spans and counters inside a launch and the frontier
loop, against values worked by hand; and each reads nothing from a
program that emits no such span or counter."""
import pytest

from bench.harness import BENCH, Ctx, reader


def span(name, t0, dur, children=(), **attrs):
    return {"name": name, "t0_ms": t0, "dur_ms": dur, "attrs": attrs,
            "children": list(children)}


def execute(children):
    return [span("gateway.request", 0.0, 20.0, [
        span("service.execute", 2.0, 16.0, children, queued_ms=1.0)])]


# one request that ran: a round of 10 ms (pack 1, launch 6: upload 1,
# device 4, read-back 0.5), a second round of 2 ms with no launch
# children, and a 1.5 ms splice
RAN = execute([
    span("plan.round", 2.5, 10.0, [
        span("plan.pack", 3.0, 1.0, rows=2, k=2, m0=16, mo=32),
        span("kernel.ca_search", 4.0, 6.0, [
            span("kernel.upload", 4.0, 1.0),
            span("kernel.device", 5.0, 4.0),
            span("kernel.readback", 9.0, 0.5),
        ]),
    ], round=1, items=2, k=2),
    span("plan.round", 13.0, 2.0, round=2, items=1, k=2),
    span("plan.splice", 15.5, 1.5, dummies=3, results=40),
])
# one request the edge cache answered
CACHED = [span("gateway.request", 30.0, 2.0, [span("gateway.cache", 30.5, 0.5)])]


@pytest.fixture
def ctx():
    return Ctx(
        seconds=2.0, setup_s=200.0,
        records=[{"scheduled": 0.1, "sent": 0.1, "done": 0.2, "status": 200},
                 {"scheduled": 0.5, "sent": 0.5, "done": 0.6, "status": 200}],
        stats0={"upload_bytes": 1_000_000, "upload_bytes_useful": 400_000,
                "compiles": 40, "compile_s": 111.5},
        stats1={"upload_bytes": 5_000_000, "upload_bytes_useful": 1_400_000,
                "compiles": 40, "compile_s": 111.5},
        cache0={"hits": 0, "misses": 0}, cache1={"hits": 1, "misses": 1},
        device_kind="TPU v5 lite", devices=["/device:TPU:0"],
        traces=[RAN, CACHED],
    )


WANT = {
    "kernel.upload_ms": 0.5,             # 1 and 0
    "kernel.device_ms": 2.0,             # 4 and 0
    "kernel.readback_ms": 0.25,          # 0.5 and 0
    "plan.round_self_ms": 2.5,           # (10 - 1 - 6) + 2, and 0
    "plan.splice_ms": 0.75,              # 1.5 and 0
    "plan.upload_mb_per_query": 2.0,     # 4 MB over 2 requests
    "plan.padding_share": 75.0,          # 1 MB useful of 4 MB
    "plan.window_compiles": 0.0,
    "setup.compile_s": 111.5,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader(ctx, name):
    assert reader(name, BENCH)(ctx) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_program_without_them_gives_nothing(ctx, name):
    # the program before these spans and counters: the same request with
    # only the launch span, and no such counters
    ctx.traces = [execute([
        span("plan.pack", 3.0, 1.0, rows=2, k=2, m0=16, mo=32),
        span("kernel.ca_search", 4.0, 6.0),
    ]), CACHED]
    ctx.stats0 = ctx.stats1 = {"plan_launches_total": 3}
    assert reader(name, BENCH)(ctx) is None


def test_compiles_in_the_window_are_counted(ctx):
    ctx.stats1 = dict(ctx.stats1, compiles=43)
    assert reader("plan.window_compiles", BENCH)(ctx) == 3.0
