"""Load generator: the window's HTTP client, run in a child process.

    python3 -m bench.loadgen < plan.json

It never imports JAX (the parent holds the chips) and imports nothing of
the program.  The plan on stdin is one JSON object:

  * ``{"port", "mode": "open", "requests": [[offset_s, keywords, sem]],
    "timeout_s"}``: each request is sent at its offset from the start,
    whether or not earlier ones have been answered.  Its latency counts
    from that scheduled time, so a late send shows in it; how late the
    send was is kept apart.
  * ``{"port", "mode": "closed", "clients": [[[keywords, sem]]],
    "seconds", "timeout_s"}``: each client sends its next query when its
    answer arrives, until ``seconds`` have passed, starting its list again
    if it comes to the end.

The child prints ``ready``, waits for a line on stdin, runs, and prints one
JSON object: the window's start on its monotonic clock and, per request,
``[client or -1, scheduled, sent, done, status, n_ids, digest, cached]`` in
seconds from the start.  The digest is the same as
``bench.reference.digest``: blake2b-128 of the ids as little-endian int64.
"""
from __future__ import annotations

import asyncio
import hashlib
import json
import sys
import time
from array import array


def _digest(ids: list[int]) -> str:
    a = array("q", ids)
    if sys.byteorder != "little":
        a.byteswap()
    return hashlib.blake2b(a.tobytes(), digest_size=16).hexdigest()


async def _post(port: int, body: bytes, timeout: float):
    """(status, parsed JSON body or None) of one POST /query."""
    reader, writer = await asyncio.wait_for(
        asyncio.open_connection("127.0.0.1", port), timeout
    )
    try:
        writer.write(
            b"POST /query HTTP/1.1\r\nHost: localhost\r\n"
            b"Content-Type: application/json\r\nConnection: close\r\n"
            b"Content-Length: " + str(len(body)).encode() + b"\r\n\r\n" + body
        )
        await writer.drain()
        raw = await asyncio.wait_for(reader.read(), timeout)
    finally:
        writer.close()
    head, _, payload = raw.partition(b"\r\n\r\n")
    status = int(head.split(b" ", 2)[1]) if head else 0
    try:
        obj = json.loads(payload) if payload else None
    except ValueError:
        obj = None
    return status, obj


async def _one(port, words, sem, timeout, rec, t0):
    body = json.dumps({"keywords": words, "semantics": sem}).encode()
    rec[2] = time.monotonic() - t0
    try:
        status, obj = await _post(port, body, timeout)
    except (OSError, asyncio.TimeoutError) as e:
        status, obj = 0, {"error": type(e).__name__}
    rec[3] = time.monotonic() - t0
    rec[4] = status
    if status == 200 and isinstance(obj, dict) and "ids" in obj:
        rec[5] = len(obj["ids"])
        rec[6] = _digest(obj["ids"])
        rec[7] = bool(obj.get("cached"))


async def _open(plan, t0):
    recs, tasks = [], []
    for i, (at, words, sem) in enumerate(plan["requests"]):
        delay = t0 + at - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        rec = [-1, at, None, None, 0, 0, None, False, i]
        recs.append(rec)
        tasks.append(asyncio.create_task(
            _one(plan["port"], words, sem, plan["timeout_s"], rec, t0)
        ))
    await asyncio.gather(*tasks)
    return recs


async def _closed(plan, t0):
    recs = []
    end = t0 + float(plan["seconds"])

    async def client(c, seq):
        j = 0
        while time.monotonic() < end:
            words, sem = seq[j % len(seq)]  # from the top again if need be
            rec = [c, None, None, None, 0, 0, None, False, j]
            recs.append(rec)
            await _one(plan["port"], words, sem, plan["timeout_s"], rec, t0)
            rec[1] = rec[2]
            j += 1

    await asyncio.gather(*(client(c, s) for c, s in enumerate(plan["clients"])))
    return recs


def main() -> None:
    plan = json.loads(sys.stdin.readline())
    print("ready", flush=True)
    sys.stdin.readline()
    t0 = time.monotonic()
    run = _open if plan["mode"] == "open" else _closed
    recs = asyncio.run(run(plan, t0))
    json.dump({"t0": t0, "records": recs}, sys.stdout)
    sys.stdout.write("\n")
    sys.stdout.flush()


if __name__ == "__main__":
    main()
