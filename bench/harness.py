"""The benchmark harness: one cell of ``BENCHMARK.json``, one run.

A cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``); each metric is read by
``bench/metrics/<metric>.py``.  Nothing here names a cell, a mix or a
metric: a later change adds one by adding files and entries.

A run, in one process that holds the chips:

  1. set-up: generate the corpus from ``--seed`` (``bench.corpus``),
     publish it with the program's ``build_cluster``, serve it with
     ``ClusterService.from_dir(transport="thread")``, and warm up through
     the router: each distinct query of the window once, then a replay of
     the window's traffic with repeats left out (the edge cache answers
     those in the window), so every shape the window meets is compiled;
  2. the window: an in-process HTTP ``Gateway`` in front of the cluster,
     driven over localhost by ``bench.loadgen`` in a child process that
     never imports JAX; with ``--trace 1`` every request is traced and the
     JAX profiler records a few steady seconds in the middle;
  3. after the window: read the device's peak memory, close the program,
     and compare every answer received against ``bench.reference``.

The last line of stdout is the result; the numbers compared, each beside
its limit, are the last lines of stderr and the last key of the result.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from itertools import combinations
from types import SimpleNamespace

import numpy as np

from . import devtrace, spans, traffic
from .corpus import Corpus, generate
from .reference import Reference, digest

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
# a request the window sent is waited for this long past the window's end
ANSWER_WAIT_S = 60.0
# the profiler records this long, from this far into the window
TRACE_AT = 0.35
TRACE_S = 4.0
# the warm-up sends together the first arrivals this close in the window,
# in every subset of up to GROUP_MAX of one semantics and keyword count
GROUP_S = 0.5
GROUP_MAX = 4


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, flush=True)


# --------------------------------------------------------------------- #
# BENCHMARK.json and the files it names
# --------------------------------------------------------------------- #
def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


@dataclass
class Cell:
    workload: dict
    config: dict
    traffic: dict
    metrics: list[dict]  # this run's metrics, end-to-end or per-layer


def resolve(bm: dict, name: str, trace: bool, bench_dir: str = BENCH) -> Cell:
    wl = next((w for w in bm["workloads"] if w["name"] == name), None)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cfg = next(c for c in bm["configs"] if c["name"] == wl["config"])
    with open(os.path.join(os.path.dirname(bench_dir), cfg["file"])) as f:
        config = json.load(f)
    spec = traffic.load(wl["traffic"], os.path.join(bench_dir, "traffic"))
    pool = bm["per_layer"] if trace else bm["end_to_end"]
    metrics = [m for m in pool if name in m.get("workloads", [name])]
    return Cell(wl, config, spec, metrics)


def reader(metric: str, bench_dir: str = BENCH):
    """``read(ctx)`` of ``bench/metrics/<metric>.py``."""
    path = os.path.join(bench_dir, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --------------------------------------------------------------------- #
# What a metric reader gets
# --------------------------------------------------------------------- #
@dataclass
class Ctx:
    """Everything one run measured, for ``bench/metrics/*.py``.

    ``records``: the window's requests as the client saw them: dicts with
    ``scheduled``, ``sent``, ``done`` (s from the window's start),
    ``status``, ``cached``.  ``stats0``/``stats1``: the cluster's counters
    (``ClusterService.stats().data``) before and after the window;
    ``cache0``/``cache1`` the gateway's edge cache the same way.  With
    tracing, ``traces`` holds each request's span forest and ``device`` the
    profiler's window (:class:`bench.devtrace.DeviceTrace`).
    """

    seconds: float
    setup_s: float
    records: list[dict]
    stats0: dict
    stats1: dict
    cache0: dict
    cache1: dict
    device_kind: str
    devices: list[str]
    traces: list[list[dict]] = field(default_factory=list)
    device: devtrace.DeviceTrace | None = None

    def delta(self, key: str) -> float:
        return float(self.stats1.get(key, 0)) - float(self.stats0.get(key, 0))

    def latencies_ms(self) -> np.ndarray:
        """Per request, from its scheduled send (open loop) or its send
        (closed loop) to its answer; a request with no answer counts as
        waiting until the client gave up."""
        return np.array([
            (r["done"] - r["scheduled"]) * 1e3 for r in self.records
        ])

    def window_s(self) -> float:
        return max(r["done"] for r in self.records)


# --------------------------------------------------------------------- #
# Set-up
# --------------------------------------------------------------------- #
def compile_cache() -> str:
    """Turn on JAX's persistent compilation cache at a fixed directory of
    the checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), keeping
    every compiled program, however quick it was to compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def check_devices(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no accelerator: JAX runs on {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def program() -> SimpleNamespace:
    """The program's served entry points (the system under test) and the
    types its input and its refusals come in."""
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.api import Query
    from repro.cluster import ClusterService, Overloaded, build_cluster
    from repro.core.xml_tree import Vocab, XMLTree
    from repro.gateway import Gateway

    return SimpleNamespace(
        Query=Query, ClusterService=ClusterService, Overloaded=Overloaded,
        build_cluster=build_cluster, Vocab=Vocab, XMLTree=XMLTree,
        Gateway=Gateway,
    )


def publish(corpus: Corpus, shards: int, path: str) -> None:
    """Hand the corpus to the program's ``build_cluster``."""
    p = program()
    offsets, ids = corpus.kw_csr()
    words = list(corpus.words)
    tree = p.XMLTree(
        corpus.parent.copy(), corpus.size.copy(), offsets, ids,
        p.Vocab(word_to_id={w: i for i, w in enumerate(words)},
                id_to_word=words),
    )
    p.build_cluster(tree, shards, path)


def serve(config: dict, path: str):
    return program().ClusterService.from_dir(
        path, transport="thread", backends=config["backend"],
        max_batch=config["max_batch"],
        batch_window_ms=config["batch_window_ms"],
        max_queue_per_shard=config["max_queue_per_shard"],
        op_timeout=config["op_timeout_s"],
    )


def misses(svc) -> int:
    return int(svc.stats().data.get("plan_misses", 0))


def warm_singles(svc, pairs, pool, timeout: float) -> None:
    """Each distinct (query, semantics) once, alone, through the router."""
    Query = program().Query
    for idx, sem in pairs:
        svc.submit(Query.make(pool[idx], sem)).result(timeout)


def warm_replay_open(svc, schedule, pool, timeout: float) -> None:
    """The window's arrivals through the router, at their times, sending
    only each (query, semantics)'s first arrival: the edge cache answers
    the rest in the window."""
    Query, Overloaded = program().Query, program().Overloaded
    seen, futs = set(), []
    t0 = time.perf_counter()
    for at, idx, sem in schedule:
        if (idx, sem) in seen:
            continue
        seen.add((idx, sem))
        delay = t0 + at - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        try:
            futs.append(svc.submit(Query.make(pool[idx], sem)))
        except Overloaded:  # past the knee: the window would shed it too
            pass
    for f in futs:
        f.result(timeout)


def warm_groups(svc, schedule, pool, timeout: float) -> int:
    """Every batch the window's arrivals can form, sent at once.

    The router batches the queries that wait in a shard's admission
    window together, one launch per semantics and keyword count, and a
    launch's shape is the largest of its items'.  Which queries wait
    together depends on timing, so a replay alone misses some batches.
    Here, for each first arrival of a (query, semantics), the first
    arrivals within ``GROUP_S`` after it are split by semantics and keyword
    count, and every subset of two or more of a split is submitted back to
    back (one admission window).  Returns the number of batches sent."""
    Query = program().Query
    firsts, seen = [], set()
    for at, idx, sem in schedule:
        if (idx, sem) not in seen:
            seen.add((idx, sem))
            firsts.append((at, idx, sem))
    batches: set[frozenset] = set()
    for i, (at, _, _) in enumerate(firsts):
        near = [(idx, sem) for t, idx, sem in firsts[i:] if t - at < GROUP_S]
        split: dict[tuple, list] = {}
        for idx, sem in near:
            split.setdefault((sem, len(pool[idx])), []).append((idx, sem))
        for items in split.values():
            for n in range(2, min(len(items), GROUP_MAX) + 1):
                batches.update(frozenset(c) for c in combinations(items, n))
            batches.add(frozenset(items))
    for batch in sorted(batches, key=sorted):
        if len(batch) < 2:
            continue
        futs = [svc.submit(Query.make(pool[idx], sem)) for idx, sem in batch]
        for f in futs:
            f.result(timeout)
    return len(batches)


def warm_open(svc, schedule, pool, timeout: float, singles: bool = True):
    """Warm an open-loop window: each (query, semantics) alone, then every
    batch its arrivals can form.  Returns the plan misses after each step.
    (A replay of the arrivals after this compiled nothing more at 12-20
    req/s on one v5e, so it is not made.)"""
    steps = []
    if singles:
        warm_singles(svc, distinct((i, s) for _, i, s in schedule), pool,
                     timeout)
    steps.append(misses(svc))
    warm_groups(svc, schedule, pool, timeout)
    steps.append(misses(svc))
    return steps


def warm_replay_closed(svc, sequences, pool, seconds: float,
                       timeout: float) -> list[int]:
    """The closed loop through the router for ``seconds``, leaving out what
    the edge cache would answer; returns how far each client got."""
    Query = program().Query
    seen, lock = set(), threading.Lock()
    reach = [0] * len(sequences)
    end = time.perf_counter() + seconds

    def client(c):
        seq, j = sequences[c], 0
        while time.perf_counter() < end:
            idx, sem = seq[j % len(seq)]
            j += 1
            reach[c] = j
            with lock:
                if (idx, sem) in seen:
                    continue
                seen.add((idx, sem))
            svc.submit(Query.make(pool[idx], sem)).result(timeout)

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(len(sequences))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return reach


def distinct(pairs) -> list[tuple[int, str]]:
    seen: dict = {}
    for p in pairs:
        seen.setdefault(p, None)
    return list(seen)


# --------------------------------------------------------------------- #
# The window
# --------------------------------------------------------------------- #
def start_client(plan: dict) -> subprocess.Popen:
    """The load generator, ready to go (it has read its plan)."""
    child = subprocess.Popen(
        [sys.executable, "-m", "bench.loadgen"], cwd=ROOT,
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
    )
    child.stdin.write(json.dumps(plan) + "\n")
    child.stdin.flush()
    if child.stdout.readline().strip() != "ready":
        child.kill()
        child.wait()
        raise RuntimeError("the load generator did not start")
    return child


def finish_client(child: subprocess.Popen, timeout: float) -> dict:
    try:
        out, _ = child.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        child.kill()
        child.wait()
        raise
    if child.returncode != 0:
        raise RuntimeError(f"the load generator failed ({child.returncode})")
    return json.loads(out.strip().splitlines()[-1])


def profile_window(log_dir: str, start_s: float, length_s: float,
                   out: dict) -> threading.Thread:
    """Record ``length_s`` of the device from ``start_s`` into the window,
    on a thread; ``out`` gets the mark's wall time and the window."""
    import jax

    # no Python tracer: it would slow the host it is measuring; host level
    # 1 keeps the annotations, the mark among them
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1

    def run():
        time.sleep(start_s)
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        w0 = time.time() * 1e3
        with jax.profiler.TraceAnnotation(devtrace.MARK):
            out["mark_wall_ms"] = w0
        time.sleep(length_s)
        out["window_ms"] = (w0, time.time() * 1e3)
        jax.profiler.stop_trace()

    t = threading.Thread(target=run, name="bench-profiler")
    t.start()
    return t


def records_of(raw: dict, key_of) -> list[dict]:
    """The client's records as dicts, each with its (query, semantics)."""
    recs = []
    for client, sched, sent, done, status, n, dig, cached, i in raw["records"]:
        recs.append({
            "client": client, "scheduled": sched, "sent": sent,
            "done": done if done is not None else sched + ANSWER_WAIT_S,
            "status": status, "n": n, "digest": dig, "cached": cached,
            "key": key_of(client, i),
        })
    return recs


# --------------------------------------------------------------------- #
# Correctness
# --------------------------------------------------------------------- #
def compare(corpus: Corpus, pool, records, control: bool = False) -> dict:
    """Each answer received against the reference's answer to its query.

    Returns the numbers compared: ``wrong_answers`` (an answer that says
    something else than the reference) and ``unanswered`` (a request that
    got no answer).  With ``control``, the reference's control answers in
    the program's place (the comparison must call them wrong)."""
    ref = Reference(corpus)
    want: dict[tuple[int, str], str] = {}
    wrong = unanswered = 0
    for r in records:
        key = r["key"]
        if key not in want:
            idx, sem = key
            want[key] = digest(ref.answer(pool[idx], sem))
        if control:
            idx, sem = key
            got = digest(ref.answer(pool[idx], sem, control=True))
        elif r["status"] != 200 or r["digest"] is None:
            unanswered += 1
            continue
        else:
            got = r["digest"]
        wrong += got != want[key]
    return {"wrong_answers": wrong, "unanswered": unanswered,
            "distinct_checked": len(want)}


LIMITS = {"wrong_answers": 0, "unanswered": 0}


# --------------------------------------------------------------------- #
# One run
# --------------------------------------------------------------------- #
def run(workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, *, require_chip: bool = True,
        root: str = ROOT) -> dict:
    """Run one cell; returns the result object (the last stdout line)."""
    bm = load_benchmark(root)
    cell = resolve(bm, workload, trace, os.path.join(root, "bench"))
    cfg, spec = cell.config, cell.traffic
    chips = int(cell.workload["chips"])
    cache = compile_cache()
    import jax

    devs = check_devices(chips) if require_chip else jax.devices()[:chips]
    dev = devs[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)} compile_cache={cache}")
    Gateway = program().Gateway

    t = time.perf_counter()
    corpus = generate(int(cfg["releases"]), seed)
    pool = traffic.pool(spec)
    if spec["loop"] == "open":
        schedule = traffic.open_schedule(spec, seconds, seed)
        pairs = distinct((i, s) for _, i, s in schedule)
        plan = {"mode": "open", "timeout_s": cfg["op_timeout_s"],
                "requests": [[at, pool[i], s] for at, i, s in schedule]}

        def key_of(client, i):
            return schedule[i][1], schedule[i][2]
    else:
        sequences = traffic.closed_sequences(spec, seed)
        plan = {"mode": "closed", "timeout_s": cfg["op_timeout_s"],
                "seconds": seconds,
                "clients": [[[pool[i], s] for i, s in seq]
                            for seq in sequences]}

        def key_of(client, j):
            seq = sequences[client]
            return seq[j % len(seq)]
    generate_s = time.perf_counter() - t

    workdir = tempfile.mkdtemp(prefix="bench-cluster-")
    svc = gw = child = None
    try:
        t = time.perf_counter()
        publish(corpus, int(cfg["shards"]), workdir)
        publish_s = time.perf_counter() - t
        t = time.perf_counter()
        svc = serve(cfg, workdir)
        load_s = time.perf_counter() - t

        t = time.perf_counter()
        timeout = float(cfg["op_timeout_s"])
        if spec["loop"] == "open":
            steps = warm_open(svc, schedule, pool, timeout)
        else:
            reach = warm_replay_closed(svc, sequences, pool, seconds, timeout)
            steps = [misses(svc)]
            pairs = distinct(
                s[j % len(s)] for s, r in zip(sequences, reach)
                for j in range(int(1.5 * r) + 1)
            )
            warm_singles(svc, pairs, pool, timeout)
            steps.append(misses(svc))
        warm_s = time.perf_counter() - t
        log(f"warm: distinct_pairs={len(pairs)} plan_misses_by_step={steps}")

        gw = Gateway(svc, cache_entries=int(cfg["cache_entries"]),
                     trace=trace, slow_log_entries=1 << 20).start()
        plan["port"] = gw.port
        stats0, cache0 = svc.stats().data, gw.cache.snapshot()
        child = start_client(plan)
        prof: dict = {}
        log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        setup_s = time.perf_counter() - t_start
        log(f"setup: generate_s={generate_s} publish_s={publish_s} "
            f"load_s={load_s} warm_s={warm_s} total_s={setup_s}")
        child.stdin.write("go\n")
        child.stdin.flush()
        prof_thread = (
            profile_window(log_dir, TRACE_AT * seconds,
                           min(TRACE_S, 0.5 * seconds), prof)
            if trace else None
        )
        raw = finish_client(child, seconds + 2 * ANSWER_WAIT_S + 60)
        child = None
        if prof_thread is not None:
            prof_thread.join()
        stats1, cache1 = svc.stats().data, gw.cache.snapshot()
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in devs]
        traces = (
            [e["spans"] for e in gw.slow_log.worst(1 << 20)] if trace else []
        )
    finally:
        if child is not None:
            child.kill()
            child.wait()
        if gw is not None:
            gw.close()
        if svc is not None:
            svc.close()
        shutil.rmtree(workdir, ignore_errors=True)

    records = records_of(raw, key_of)
    device = None
    if trace:
        device = devtrace.load(devtrace.find_xplane(log_dir),
                               prof["mark_wall_ms"], prof["window_ms"])
        shutil.rmtree(log_dir, ignore_errors=True)
    ctx = Ctx(seconds=seconds, setup_s=setup_s,
              records=records, stats0=stats0, stats1=stats1, cache0=cache0,
              cache1=cache1, device_kind=dev.device_kind,
              devices=[f"/device:TPU:{d.id}" for d in devs],
              traces=traces, device=device)
    late = np.array([r["sent"] - r["scheduled"] for r in records
                     if r["sent"] is not None]) * 1e3
    log(f"window: requests={len(records)} plan_misses="
        f"{int(ctx.delta('plan_misses'))} plan_launches="
        f"{int(ctx.delta('plan_launches_total'))} cache_hits="
        f"{cache1['hits'] - cache0['hits']} coalesced="
        f"{int(ctx.delta('coalesced'))} generator_lateness_p95_ms="
        f"{float(np.percentile(late, 95)) if late.size else 0.0}")
    log(f"memory: peak_bytes_in_use={peaks}")

    metrics = {}
    for m in cell.metrics:
        value = reader(m["name"], os.path.join(root, "bench"))(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    t = time.perf_counter()
    checks = compare(corpus, pool, records)
    log(f"reference: distinct_checked={checks.pop('distinct_checked')} "
        f"seconds={time.perf_counter() - t}")
    failed = sum(1 for r in records if r["status"] != 200)
    result = {
        "correct": all(checks[k] <= LIMITS[k] for k in LIMITS),
        "attempted": len(records),
        "failed": failed + checks["wrong_answers"],
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devs), "memory_peak_bytes": max(peaks)},
    }
    if trace:
        busy = [devtrace.busy_ms(device, d) / 1e3 for d in ctx.devices]
        lo, hi = device.window_ms
        result["device"]["busy_s"] = sum(busy) / len(busy)
        result["device"]["window_s"] = (hi - lo) / 1e3
        host = [(s["name"], *spans.interval(s)) for t in traces
                for s in spans.flatten(t)]
        gaps = [g for d in ctx.devices for g in devtrace.idle_gaps(device, d)]
        result["breakdown"] = {
            "device_ops": devtrace.top_ops(device),
            "idle_gaps": devtrace.label_gaps(gaps, host),
        }
    result["checks"] = {k: {"value": v, "limit": LIMITS[k]}
                        for k, v in checks.items()}
    for k, v in checks.items():
        print(f"check: {k}={v} limit={LIMITS[k]}", file=sys.stderr,
              flush=True)
    return result
