"""Reduction of a JAX profiler trace (``.xplane.pb``) to device numbers.

The profiler keeps one plane per device (``/device:TPU:0`` ...) and one for
the host (``/host:CPU``).  A device plane's ``XLA Ops`` line holds every
operation that ran on it, with a start and a duration in ns.  Times
are ns from the trace's own origin; the host-side ``bench.mark``
annotation, opened at a known wall-clock time, puts them on the wall clock
the program's spans use.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

MARK = "bench.mark"


@dataclass
class DeviceTrace:
    """Per device: its operations as (name, start_ms, end_ms) on the wall
    clock; the profiled window the same way."""

    ops: dict[str, list[tuple[str, float, float]]] = field(default_factory=dict)
    window_ms: tuple[float, float] = (0.0, 0.0)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str, mark_wall_ms: float, window_ms: tuple[float, float],
         device_prefix: str = "/device:TPU:") -> DeviceTrace:
    """Read ``path``; ``mark_wall_ms`` is the wall time the mark opened."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    mark_ns = None
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == MARK:
                    mark_ns = float(ev.start_ns)
                    break
    if mark_ns is None:
        raise ValueError(f"no {MARK!r} annotation in {path}")
    return from_events(
        {
            plane.name: {
                line.name: [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                            for ev in line.events]
                for line in plane.lines
            }
            for plane in pd.planes if plane.name.startswith(device_prefix)
        },
        mark_ns, mark_wall_ms, window_ms,
    )


def from_events(planes: dict[str, dict[str, list]], mark_ns: float,
                mark_wall_ms: float, window_ms) -> DeviceTrace:
    """``planes``: device -> line name -> [(name, start_ns, dur_ns)]."""
    out = DeviceTrace(window_ms=tuple(window_ms))

    def wall(start_ns, dur_ns):
        a = mark_wall_ms + (start_ns - mark_ns) / 1e6
        return a, a + dur_ns / 1e6

    for dev, lines in sorted(planes.items()):
        ops = lines.get("XLA Ops")
        if ops is None:  # no op line: every event on the device counts
            ops = [e for evs in lines.values() for e in evs]
        out.ops[dev] = [(n, *wall(s, d)) for n, s, d in ops if d > 0]
    return out


def _clip(events, lo, hi):
    for name, a, b in events:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            yield name, a, b


def busy_ms(tr: DeviceTrace, device: str) -> float:
    """Union of the device's operation intervals inside the window."""
    lo, hi = tr.window_ms
    total, end = 0.0, None
    for _, a, b in sorted(_clip(tr.ops.get(device, ()), lo, hi), key=lambda e: e[1]):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def op_kind(name: str) -> str:
    """The HLO opcode of an ``XLA Ops`` event (``%while.12 = (s32[], ...)
    while(...)`` is ``while``): instruction names change with every
    compile, opcodes do not."""
    if " = " not in name:
        return name
    rest = name.split(" = ", 1)[1]
    if rest.startswith("("):  # a tuple shape: skip to its closing paren
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[-1]
    return rest.strip().split("(", 1)[0] or name


def top_ops(tr: DeviceTrace, n: int = 10) -> list[list]:
    """[[opcode, seconds]] of the operations that took most time, summed
    over devices."""
    lo, hi = tr.window_ms
    acc: dict[str, float] = {}
    for events in tr.ops.values():
        for name, a, b in _clip(events, lo, hi):
            kind = op_kind(name)
            acc[kind] = acc.get(kind, 0.0) + (b - a) / 1e3
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: DeviceTrace, device: str) -> list[tuple[float, float]]:
    """The device's idle intervals inside the window, in wall ms."""
    lo, hi = tr.window_ms
    gaps, cur = [], lo
    for _, a, b in sorted(_clip(tr.ops.get(device, ()), lo, hi), key=lambda e: e[1]):
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def label_gaps(gaps, host_spans, n: int = 10) -> list[list]:
    """[[label, seconds]] of the ``n`` longest gaps, each labelled with the
    innermost program span open on the host at its midpoint (the latest
    opened of those covering it), or ``no request`` when none is."""
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (a + b) / 2
        best = None
        for name, s0, s1 in host_spans:
            if s0 <= mid < s1 and (best is None or s0 >= best[1]):
                best = (name, s0)
        out.append([best[0] if best else "no request", (b - a) / 1e3])
    return out
