"""ProcessWorker: one spawned subprocess per shard over its mmap'd artifact.

The parent side of the :mod:`~repro.cluster.workers.subproc` RPC — the
pipelined client machinery (request registry, reader thread, typed death)
is the shared :class:`~repro.cluster.workers.base.RpcWorker`; this class
owns the ``Popen`` carrier.  The subprocess loads the shard with
``KeywordSearchEngine.load(mmap=True)``, so N workers + the publisher share
one page-cache copy of every index page; nothing crosses the pipe but
keyword strings in and result ``.npy`` vectors out.

Death is a first-class outcome, not a hang: EOF / a broken pipe / a
nonzero exit fails every in-flight Future with the typed
:class:`~repro.cluster.workers.base.WorkerDied`, subsequent submits raise
it synchronously, and the ``on_death`` callback lets the
:class:`~repro.cluster.workers.pool.ProcessPool` respawn the shard.
"""
from __future__ import annotations

import collections
import os
import subprocess
import sys
import threading

import repro

from ..partition import ShardSpec
from .base import DEFAULT_OP_TIMEOUT, RpcWorker
from .proto import write_frame

STDERR_TAIL_LINES = 20  # child stderr lines kept for the WorkerDied detail


def _pythonpath_for_child() -> str:
    """The child must resolve ``repro`` exactly like this process did."""
    pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(repro.__file__)))
    prev = os.environ.get("PYTHONPATH", "")
    return pkg_root + (os.pathsep + prev if prev else "")


class ProcessWorker(RpcWorker):
    """Worker seam over a per-shard subprocess (spawned immediately).

    Construction is non-blocking: the Popen + reader thread start here, and
    requests written before the child finishes loading simply sit in the
    pipe — callers who want spawn failures surfaced eagerly wait on
    :meth:`~repro.cluster.workers.base.RpcWorker.wait_ready` (the pool
    does, with a timeout).
    """

    transport = "process"

    def __init__(
        self,
        spec: ShardSpec,
        shard_dir: str,
        *,
        backend: str = "jax",
        max_batch: int = 64,
        batch_window_ms: float = 2.0,
        op_timeout: float = DEFAULT_OP_TIMEOUT,
        on_death=None,
    ):
        super().__init__(spec, op_timeout=op_timeout, on_death=on_death)
        self.shard_dir = os.fspath(shard_dir)
        self.backend = backend
        self.max_batch = max_batch
        self.batch_window_ms = batch_window_ms
        self._drained = False
        env = dict(os.environ, PYTHONPATH=_pythonpath_for_child())
        self._proc = subprocess.Popen(
            [
                sys.executable, "-m", "repro.cluster.workers.subproc",
                "--dir", self.shard_dir,
                "--shard", str(spec.index),
                "--backend", backend,
                "--max-batch", str(int(max_batch)),
                "--batch-window-ms", repr(float(batch_window_ms)),
            ],
            stdin=subprocess.PIPE,
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            env=env,
        )
        self._rfile = self._proc.stdout
        self._wfile = self._proc.stdin
        # the child's stderr is forwarded to ours (tracebacks stay visible)
        # and its tail kept for the WorkerDied detail
        self._stderr_tail: collections.deque[bytes] = collections.deque(
            maxlen=STDERR_TAIL_LINES
        )
        self._stderr_pump = threading.Thread(
            target=self._pump_stderr, name=f"shard{spec.index}-stderr",
            daemon=True,
        )
        self._stderr_pump.start()
        self._start_reader(f"shard{spec.index}-rpc-reader")

    def _pump_stderr(self) -> None:
        for line in self._proc.stderr:
            self._stderr_tail.append(line)
            try:
                os.write(2, line)
            except OSError:
                pass

    # ------------------------------------------------------------------ #
    # Worker protocol (the RPC ops live on RpcWorker)
    # ------------------------------------------------------------------ #
    def drain(self, timeout: float = 30.0) -> None:
        with self._lock:
            if self._drained:
                return
            self._drained = True
        try:
            self._request({"op": "drain"}).result(timeout)
        except Exception:
            pass  # dead worker: its pending Futures already carry WorkerDied

    def close(self, timeout: float = 30.0) -> None:
        with self._lock:
            already = self._closing
            self._closing = True
            if not already and self._dead is None:
                try:
                    write_frame(self._proc.stdin, {"op": "close", "id": -1})
                except (OSError, ValueError):
                    pass  # pipe gone: the child is exiting anyway
        try:
            self._proc.stdin.close()
        except OSError:
            pass
        try:
            self._proc.wait(timeout)
        except subprocess.TimeoutExpired:
            self._proc.kill()
            self._proc.wait(5.0)
        self._reader.join(5.0)

    def _death_detail(self, detail: str) -> str:
        # the child's last words arrive on stderr around its EOF: give the
        # pump a moment to drain them (bounded: a wedged child cannot hang
        # the death path)
        self._stderr_pump.join(5.0)
        tail = b"".join(self._stderr_tail).decode(errors="replace").rstrip()
        out = f"{detail} (exit code {self._proc.poll()})"
        return f"{out}; stderr tail:\n{tail}" if tail else out
