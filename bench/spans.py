"""Reductions over the program's host spans (``repro.obs`` span trees).

A span is a dict with ``name``, ``t0_ms`` (wall clock, epoch ms),
``dur_ms``, ``attrs`` and ``children``.  A request's trace arrives as the
list of root trees the gateway's slow-query log keeps.
"""
from __future__ import annotations


def flatten(trees: list[dict]) -> list[dict]:
    """Every span of a forest, parents before children."""
    out, stack = [], list(reversed(trees))
    while stack:
        s = stack.pop()
        out.append(s)
        stack.extend(reversed(s.get("children", ())))
    return out


def interval(span: dict) -> tuple[float, float]:
    """A span's [start, end) in wall ms.  A ``service.execute`` span starts
    where its query was queued: the wait in the admission window is the
    service's time, not its caller's."""
    t0 = float(span["t0_ms"])
    t1 = t0 + float(span.get("dur_ms") or 0.0)
    if span["name"] == "service.execute":
        t0 -= float(span.get("attrs", {}).get("queued_ms", 0.0))
    return t0, t1


def union_length(intervals, lo: float | None = None,
                 hi: float | None = None) -> float:
    """Length of the union of ``[a, b)`` intervals, clipped to [lo, hi)."""
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if lo is not None:
            a = max(a, lo)
        if hi is not None:
            b = min(b, hi)
        if b <= a:
            continue
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def self_ms(span: dict) -> float:
    """The span's time not covered by its children."""
    lo, hi = interval(span)
    covered = union_length(
        (interval(c) for c in span.get("children", ())), lo, hi
    )
    return max(hi - lo - covered, 0.0)


def per_request_sum(traces: list[list[dict]], names: set[str],
                    fn=None) -> list[float]:
    """Per request: the sum of ``fn(span)`` (default: duration) over its
    spans named in ``names``."""
    fn = fn or (lambda s: float(s.get("dur_ms") or 0.0))
    return [
        sum(fn(s) for s in flatten(t) if s["name"] in names) for t in traces
    ]


def launches(traces: list[list[dict]]) -> list[dict]:
    """The distinct ``plan.pack`` spans of the window.  A batch's phase
    spans are copied under every query it served, so a launch is keyed by
    its start and shape."""
    seen: dict[tuple, dict] = {}
    for t in traces:
        for s in flatten(t):
            if s["name"] != "plan.pack":
                continue
            a = s.get("attrs", {})
            key = (s["t0_ms"], s["dur_ms"], a.get("rows"), a.get("k"),
                   a.get("m0"), a.get("mo"))
            seen.setdefault(key, s)
    return sorted(seen.values(), key=lambda s: s["t0_ms"])


def mean(values) -> float | None:
    values = list(values)
    return sum(values) / len(values) if values else None
