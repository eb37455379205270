"""Worker-layer unit tests: frame protocol (round-trips plus the
fuzz/negative matrix — truncations at every byte boundary, oversized and
negative lengths, non-JSON headers, lying payload lengths), the
ProcessWorker and RemoteWorker lifecycles (ready → serve → drain → close),
typed WorkerDied on kill/corruption (no hangs), and pool supervision
(bounded respawn through the router's gather path).

Transport *equivalence* on full query matrices lives in test_cluster.py;
this file exercises the seam itself.
"""
import io
import json
import os
import socket
import struct
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.cluster import ClusterService, WorkerDied
from repro.cluster.partition import split_doc_ranges
from repro.cluster.workers import (
    ProcessWorker,
    RemoteWorker,
    ThreadWorker,
    shard_doc_stats,
)
from repro.cluster.workers.proto import (
    MAX_FRAME_BYTES,
    ProtocolError,
    dump_array,
    load_array,
    read_frame,
    write_frame,
)
from repro.cluster.workers.server import launch_server
from repro.core import KeywordSearchEngine
from repro.data import QUERIES, generate_discogs_tree

N_RELEASES = 12


@pytest.fixture(scope="module")
def corpus():
    return generate_discogs_tree(n_releases=N_RELEASES, seed=11)


@pytest.fixture(scope="module")
def engine(corpus):
    return KeywordSearchEngine(corpus)


@pytest.fixture(scope="module")
def artifact(engine, tmp_path_factory):
    """A single-shard artifact (the whole corpus as shard 0)."""
    path = str(tmp_path_factory.mktemp("worker") / "shard")
    engine.save(path)
    return path


@pytest.fixture(scope="module")
def spec(corpus):
    return split_doc_ranges(corpus, 1)[0]


# --------------------------------------------------------------------------- #
# Frame protocol
# --------------------------------------------------------------------------- #


def test_proto_frame_roundtrip():
    buf = io.BytesIO()
    arr = np.arange(17, dtype=np.int64)
    write_frame(buf, {"id": 3, "op": "submit", "ok": True}, dump_array(arr))
    write_frame(buf, {"id": 4, "op": "drain", "ok": True})
    buf.seek(0)
    h1, p1 = read_frame(buf)
    assert h1["id"] == 3 and h1["payload_len"] == len(p1)
    np.testing.assert_array_equal(load_array(p1), arr)
    h2, p2 = read_frame(buf)
    assert h2 == {"id": 4, "op": "drain", "ok": True} and p2 == b""
    h3, _ = read_frame(buf)  # EOF is a (None, b"") result, not an exception
    assert h3 is None


def test_proto_truncated_frame_is_eof():
    buf = io.BytesIO()
    write_frame(buf, {"id": 1, "op": "x"}, b"12345678")
    raw = buf.getvalue()
    for cut in (2, len(raw) - 3):
        h, _ = read_frame(io.BytesIO(raw[:cut]))
        assert h is None


def test_proto_numpy_scalars_in_header():
    buf = io.BytesIO()
    write_frame(buf, {"id": 0, "full": np.int64(7), "rate": np.float32(0.5)})
    buf.seek(0)
    h, _ = read_frame(buf)
    assert h["full"] == 7 and abs(h["rate"] - 0.5) < 1e-6


def test_proto_truncated_at_every_boundary():
    """Any strict prefix of a valid frame reads as clean EOF — inside the
    length prefix, inside the header JSON, inside the payload — never an
    exception and never a partial header."""
    buf = io.BytesIO()
    write_frame(buf, {"id": 9, "op": "submit", "ok": True},
                dump_array(np.arange(5, dtype=np.int64)))
    raw = buf.getvalue()
    for cut in range(len(raw)):
        h, p = read_frame(io.BytesIO(raw[:cut]))
        assert h is None and p == b"", f"cut at byte {cut}"
    h, p = read_frame(io.BytesIO(raw))  # the whole frame still parses
    assert h["id"] == 9 and len(p) == h["payload_len"]


def test_proto_oversized_header_len_raises():
    """A corrupt/hostile length prefix must raise typed, not allocate GBs."""
    for n in (MAX_FRAME_BYTES + 1, 0xFFFFFFFF):
        raw = struct.pack(">I", n) + b"garbage-after-a-corrupt-length"
        with pytest.raises(ProtocolError):
            read_frame(io.BytesIO(raw))


def test_proto_bad_payload_len_raises():
    for n in (MAX_FRAME_BYTES + 1, -1):
        hdr = json.dumps({"id": 0, "op": "x", "payload_len": n}).encode()
        raw = struct.pack(">I", len(hdr)) + hdr
        with pytest.raises(ProtocolError):
            read_frame(io.BytesIO(raw))


def test_proto_non_json_header_raises():
    # not JSON at all, and JSON that is not an object
    for hdr in (b"ab{cd", b'[1, 2]', b'"str"'):
        raw = struct.pack(">I", len(hdr)) + hdr
        with pytest.raises(ProtocolError):
            read_frame(io.BytesIO(raw))


def test_proto_payload_len_lies_about_npy():
    """A payload_len that undercuts the npy stream parses as a frame but
    fails array decode — a per-request error, not a link death."""
    payload = dump_array(np.arange(100, dtype=np.int64))
    hdr = json.dumps(
        {"id": 0, "op": "submit", "ok": True, "payload_len": 8}
    ).encode()
    raw = struct.pack(">I", len(hdr)) + hdr + payload
    h, p = read_frame(io.BytesIO(raw))
    assert h["payload_len"] == 8 and len(p) == 8
    with pytest.raises(ValueError):
        load_array(p)


def test_proto_write_side_cap(monkeypatch):
    """An oversized payload is rejected before any byte hits the stream, so
    the sender fails its own request instead of desynchronizing the link."""
    from repro.cluster.workers import proto

    monkeypatch.setattr(proto, "MAX_FRAME_BYTES", 64)
    buf = io.BytesIO()
    with pytest.raises(ProtocolError):
        proto.write_frame(buf, {"id": 0, "op": "x"}, b"x" * 65)
    assert buf.getvalue() == b""


# --------------------------------------------------------------------------- #
# ProcessWorker lifecycle
# --------------------------------------------------------------------------- #


def test_process_worker_serves_and_matches_thread(corpus, engine, artifact, spec):
    tw = ThreadWorker(spec, engine, batch_window_ms=1.0)
    pw = ProcessWorker(spec, artifact, batch_window_ms=1.0)
    try:
        assert pw.wait_ready(300.0) and pw.pid is not None
        for _name, kws in list(QUERIES.values())[:4]:
            for sem in ("slca", "elca"):
                a = tw.submit(kws, sem).result(timeout=120)
                b = pw.submit(kws, sem).result(timeout=120)
                np.testing.assert_array_equal(a, b, err_msg=f"{kws} {sem}")
        kw_ids = [corpus.vocab.get(w) for w in QUERIES["Q4"][1]]
        dk_t, full_t = tw.doc_stats(kw_ids).result(timeout=30)
        dk_p, full_p = pw.doc_stats(kw_ids).result(timeout=30)
        np.testing.assert_array_equal(dk_t, dk_p)
        assert full_t == full_p
        snap = pw.stats()
        assert snap.data["queries"] == 8 and snap.latencies_ms
        # drain: queued work flushes, the worker stays answerable...
        pw.drain()
        np.testing.assert_array_equal(dk_p, pw.doc_stats(kw_ids).result(30)[0])
        # ...but new submits are rejected by the remote (closed service)
        with pytest.raises(RuntimeError, match="closed"):
            pw.submit(QUERIES["Q1"][1], "slca").result(timeout=30)
    finally:
        tw.close()
        pw.close()
        pw.close()  # idempotent
    assert pw._proc.poll() is not None  # the subprocess actually exited


def test_process_worker_kill_fails_fast_typed(corpus, artifact, spec):
    # a huge batch window parks the submitted query inside the subprocess,
    # so the kill reliably lands mid-query
    pw = ProcessWorker(spec, artifact, batch_window_ms=60_000.0)
    try:
        assert pw.wait_ready(300.0)
        fut = pw.submit(QUERIES["Q1"][1], "slca")
        pw._proc.kill()
        with pytest.raises(WorkerDied) as exc_info:
            fut.result(timeout=60)  # typed failure, no hang
        assert exc_info.value.shard == spec.index
        # death is sticky: later submits raise synchronously
        deadline = time.time() + 30
        while pw._dead is None and time.time() < deadline:
            time.sleep(0.05)
        with pytest.raises(WorkerDied):
            pw.submit(QUERIES["Q1"][1], "slca")
    finally:
        pw.close()


def test_pool_respawns_killed_worker(corpus, engine):
    """Through the router: kill mid-query => typed WorkerDied surfaces on the
    caller's future, the supervisor respawns the shard (bounded), and the
    next query runs on the replacement."""
    kws = QUERIES["Q1"][1]
    want = engine.query(kws, backend="scalar")
    with ClusterService.from_tree(
        corpus, 1, transport="process", batch_window_ms=2_000.0
    ) as svc:
        first = svc.pool.workers[0]
        fut = svc.submit(kws, "slca")
        first._proc.kill()
        with pytest.raises(WorkerDied):
            fut.result(timeout=120)
        deadline = time.time() + 300
        while svc.pool.workers[0] is first and time.time() < deadline:
            time.sleep(0.1)
        assert svc.pool.workers[0] is not first, "pool did not respawn"
        np.testing.assert_array_equal(svc.query(kws, "slca"), want)
        snap = svc.stats().summary()
        assert snap["worker_respawns"] == 1
        assert snap["queue_depth_per_shard"] == [0]


# --------------------------------------------------------------------------- #
# RemoteWorker lifecycle (against a live localhost shard server)
# --------------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def shard_server(artifact):
    """One standalone shard server over the module artifact."""
    proc, endpoint = launch_server(artifact, shard=0, batch_window_ms=1.0)
    yield endpoint
    proc.kill()
    proc.wait(10)


def test_remote_worker_serves_and_matches_thread(
    corpus, engine, shard_server, spec
):
    tw = ThreadWorker(spec, engine, batch_window_ms=1.0)
    rw = RemoteWorker(spec, shard_server)
    try:
        assert rw.wait_ready(60.0) and rw.pid is not None
        for _name, kws in list(QUERIES.values())[:3]:
            for sem in ("slca", "elca"):
                a = tw.submit(kws, sem).result(timeout=120)
                b = rw.submit(kws, sem).result(timeout=120)
                np.testing.assert_array_equal(a, b, err_msg=f"{kws} {sem}")
        kw_ids = [corpus.vocab.get(w) for w in QUERIES["Q4"][1]]
        dk_t, full_t = tw.doc_stats(kw_ids).result(timeout=30)
        dk_r, full_r = rw.doc_stats(kw_ids).result(timeout=30)
        np.testing.assert_array_equal(dk_t, dk_r)
        assert full_t == full_r
        assert rw.stats().data["queries"] >= 6  # the server's service counts
        # drain is client-side (flush our in-flight); stays answerable
        rw.drain()
        np.testing.assert_array_equal(dk_r, rw.doc_stats(kw_ids).result(30)[0])
    finally:
        tw.close()
        rw.close()
        rw.close()  # idempotent
    # closing one connection must NOT take the server down (other routers
    # may hold sockets to it): a fresh connection serves immediately
    rw2 = RemoteWorker(spec, shard_server)
    try:
        assert rw2.wait_ready(60.0)
        res = rw2.submit(QUERIES["Q1"][1], "slca").result(timeout=120)
        assert res is not None
    finally:
        rw2.close()


def test_remote_connect_refused_raises_workerdied(spec):
    # port 1 is never listening on localhost: constructor fails typed
    with pytest.raises(WorkerDied):
        RemoteWorker(spec, "127.0.0.1:1", connect_timeout=5.0)


def test_remote_corrupt_stream_dies_typed(spec):
    """A peer speaking garbage framing (here: a 4 GB length prefix) kills
    the link with a typed WorkerDied carrying the ProtocolError — it never
    attempts the allocation and never hangs."""
    srv = socket.create_server(("127.0.0.1", 0))
    port = srv.getsockname()[1]

    def bad_server():
        conn, _ = srv.accept()
        conn.sendall(struct.pack(">I", 0xFFFFFFFF) + b"junk")
        conn.close()

    threading.Thread(target=bad_server, daemon=True).start()
    rw = RemoteWorker(spec, f"127.0.0.1:{port}")
    try:
        assert not rw.wait_ready(30.0)  # dead, not timed out
        assert isinstance(rw._dead, WorkerDied)
        assert "ProtocolError" in rw._dead.detail
        with pytest.raises(WorkerDied):
            rw.submit(QUERIES["Q1"][1], "slca")
    finally:
        rw.close()
        srv.close()


# --------------------------------------------------------------------------- #
# shard_doc_stats helper
# --------------------------------------------------------------------------- #


def test_shard_doc_stats_counts(corpus, engine):
    doc_roots = np.where(corpus.parent == 0)[0].astype(np.int64)
    vinyl = corpus.vocab.get("vinyl")
    release = corpus.vocab.get("release")
    docs_k, full = shard_doc_stats(
        engine.base.containment, doc_roots, [release]
    )
    assert docs_k[0] == N_RELEASES and full == N_RELEASES  # in every doc
    docs_k, full = shard_doc_stats(
        engine.base.containment, doc_roots, [release, vinyl]
    )
    assert docs_k[0] == N_RELEASES and full == docs_k[1]  # ANDing with vinyl


def test_process_reload_bad_artifact_raises_workerdied(corpus):
    """ProcessPool.spawn verifies the replacement child actually loads its
    artifact: a bad path raises typed WorkerDied at the reload call site
    (symmetric with the thread transport) and the shard keeps serving."""
    kws = QUERIES["Q1"][1]
    with ClusterService.from_tree(
        corpus, 1, transport="process", batch_window_ms=1.0
    ) as svc:
        before = svc.query(kws, "slca")
        with pytest.raises(WorkerDied):
            svc.reload_shard(0, "/nonexistent/artifact")
        assert svc.stats().summary()["reloads"] == 0
        np.testing.assert_array_equal(svc.query(kws, "slca"), before)


def test_process_worker_death_carries_stderr_tail(spec):
    """A child that dies before it is ready (here: no artifact; on a chip
    host: the chip is held by another process) fails with the typed
    WorkerDied carrying its last stderr lines — never a hang."""
    pw = ProcessWorker(spec, "/nonexistent/artifact", batch_window_ms=1.0)
    try:
        assert not pw.wait_ready(120)
        assert isinstance(pw._dead, WorkerDied)
        assert "stderr tail" in pw._dead.detail
        assert "FileNotFoundError" in pw._dead.detail
        with pytest.raises(WorkerDied):
            pw.submit(["vinyl"], "slca")
    finally:
        pw.close(5.0)


def test_refuse_cpu_fallback_rule(monkeypatch):
    """Only an unasked-for CPU on a host with a TPU runtime is refused."""
    import jax

    import repro.device as device

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(device, "_tpu_runtime_installed", lambda: True)
    with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
        device.refuse_cpu_fallback()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    device.refuse_cpu_fallback()  # on the chip
    monkeypatch.undo()
    opened = []
    monkeypatch.setattr(jax, "default_backend", lambda: opened.append(1) or "tpu")
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    device.refuse_cpu_fallback()
    assert opened  # the backend is opened even when JAX_PLATFORMS is set
    monkeypatch.undo()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(device, "_tpu_runtime_installed", lambda: True)
    device.refuse_cpu_fallback()  # the CPU, asked for
    monkeypatch.delenv("JAX_PLATFORMS")
    monkeypatch.setattr(device, "_tpu_runtime_installed", lambda: False)
    device.refuse_cpu_fallback()  # a host without a TPU runtime


_FALLBACK_SCRIPT = """
import os, sys
sys.path.insert(0, {src!r})
import jax  # reads JAX_PLATFORMS=cpu: no chip is looked for
os.environ.pop("JAX_PLATFORMS")  # ...then unset, as on a chip host
import repro.device
repro.device._tpu_runtime_installed = lambda: True
from repro.cluster.workers import subproc
sys.exit(subproc.main(["--dir", "/nonexistent/artifact"]))
"""


def test_shard_child_refuses_cpu_fallback():
    """A shard child that JAX put on the CPU of a chip host (the chip held
    by another process, JAX_PLATFORMS unset) dies before it is ready, with
    the reason on stderr, so the pool raises WorkerDied carrying it."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", _FALLBACK_SCRIPT.format(src=os.path.abspath(src))],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode != 0
    assert out.stdout == ""  # no ready frame
    assert "fell back to the CPU" in out.stderr, out.stderr[-3000:]
    assert "FileNotFoundError" not in out.stderr  # refused before loading


_PIN_SCRIPT = """
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, {src!r})
import jax
import repro.core.plan_cache as pc
from repro.cluster import ClusterService
from repro.data import QUERIES, generate_discogs_tree

seen = set()
orig = pc.ca_search_batch

def spy(**kw):
    out = orig(**kw)
    seen.update(d.id for d in out[0].devices())
    return out

pc.ca_search_batch = spy
tree = generate_discogs_tree(n_releases=40, seed=11)
with ClusterService.from_tree(
    tree, 4, transport="thread", backends="jax", batch_window_ms=1.0
) as svc:
    pinned = [w.engine.plan_cache.device.id for w in svc.pool.workers]
    svc.query(QUERIES["Q1"][1], "slca")
print(pinned, sorted(seen))
"""


def test_thread_pool_pins_one_device_per_shard():
    """One process drives every chip: shard i's launches land on device i."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", _PIN_SCRIPT.format(src=os.path.abspath(src))],
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.split("\n")[-2] == "[0, 1, 2, 3] [0, 1, 2, 3]"
