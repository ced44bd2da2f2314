"""The load generator: a child process that imports no JAX, sends the
drawn requests to the gateway as real HTTP ``POST /generate`` calls on
localhost, and records when each streamed token reached it.

    python bench/loadgen.py <plan.json> <result.json>

``plan.json`` holds ``port``, ``loop`` ("open" or "closed"),
``concurrency``, ``first`` and ``requests`` (as ``bench/traffic.py`` draws
them), the
traffic's start ``t0`` and the window ``[window_start, window_end]`` on
``time.monotonic()`` (one clock for every process of the machine), and
``drain_s``. An open loop sends each request at ``t0 + send_at`` while
that is before the window's end; a closed loop keeps ``concurrency``
clients busy, each sending the next request (the first wave's, then the
pool's) when its last one ends, until the window's end. Requests in flight then get
``drain_s`` to finish. ``result.json`` holds one record per request sent
and how late the sends ran.
"""
from __future__ import annotations

import asyncio
import json
import sys
import time


async def _one(port: int, body: dict, rec: dict) -> None:
    payload = json.dumps(body).encode()
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        writer.write(b"POST /generate HTTP/1.1\r\nHost: bench\r\n"
                     b"Content-Length: %d\r\n\r\n" % len(payload) + payload)
        await writer.drain()
        rec["sent"] = time.monotonic()
        head = await reader.readuntil(b"\r\n\r\n")
        status = int(head.split(b" ", 2)[1])
        buf = b""
        while True:
            chunk = await reader.read(1 << 16)
            if not chunk:
                break
            now = time.monotonic()
            buf += chunk
            *lines, buf = buf.split(b"\n")
            n = 0
            for line in lines:
                if not line.strip():
                    continue
                ev = json.loads(line)
                if ev.get("event") == "token":
                    rec["tokens"].append(ev["token"])
                    n += 1
                else:
                    rec["end"] = ev
            if n:
                rec["bursts"].append([now, n])
        if buf.strip():
            rec["end"] = json.loads(buf)
        rec["status"] = (rec["end"].get("event", "error")
                         if rec.get("end") else f"http {status}, no end")
    finally:
        writer.close()
        try:
            await writer.wait_closed()
        except ConnectionError:
            pass


def _record(i: int, req: dict, due: float) -> dict:
    return {"i": i, "due": due, "sent": None, "tokens": [], "bursts": [],
            "end": None, "status": "pending",
            "prompt_len": len(req["tokens"]),
            "prefix_id": req.get("prefix_id"),
            "max_new_tokens": req["max_new_tokens"]}


def _body(req: dict) -> dict:
    body = {"tokens": req["tokens"], "max_new_tokens": req["max_new_tokens"]}
    if req.get("prefix_id"):
        body["prefix_id"] = req["prefix_id"]
    return body


async def _guarded(port, body, rec):
    try:
        await _one(port, body, rec)
    except (OSError, asyncio.IncompleteReadError, ValueError) as exc:
        rec["status"] = f"error: {exc!r}"


async def run(plan: dict) -> dict:
    port, reqs, first = plan["port"], plan["requests"], plan.get("first", [])
    t0, w_end = plan["t0"], plan["window"][1]
    records, tasks, late = [], [], []
    if plan["loop"] == "open":
        for i, req in enumerate(reqs):
            due = t0 + req["send_at"]
            if due >= w_end:
                break
            delay = due - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            late.append(time.monotonic() - due)
            rec = _record(i, req, due)
            records.append(rec)
            tasks.append(asyncio.ensure_future(
                _guarded(port, _body(req), rec)))
    else:
        counter = iter(range(1 << 62))

        async def client():
            due = t0            # a client sends as its last request ends
            while time.monotonic() < w_end:
                i = next(counter)
                req = (first[i] if i < len(first)
                       else reqs[(i - len(first)) % len(reqs)])
                rec = _record(i, req, due)
                records.append(rec)
                await _guarded(port, _body(req), rec)
                if rec["sent"] is not None:
                    late.append(rec["sent"] - due)
                due = time.monotonic()
        delay = t0 - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks = [asyncio.ensure_future(client())
                 for _ in range(plan["concurrency"])]
    rest = max(0.0, w_end - time.monotonic()) + plan["drain_s"]
    done, pending = await asyncio.wait(tasks, timeout=rest) if tasks else ((), ())
    for t in pending:
        t.cancel()
    for t in done:
        t.result()
    for rec in records:
        if rec["status"] == "pending":
            rec["status"] = "timeout"
    late.sort()
    return {"records": records,
            "late_s": {"n": len(late),
                       "p50": late[len(late) // 2] if late else 0.0,
                       "p99": late[int(len(late) * 0.99)] if late else 0.0,
                       "max": late[-1] if late else 0.0}}


def main() -> None:
    plan_path, out_path = sys.argv[1], sys.argv[2]
    with open(plan_path) as f:
        plan = json.load(f)
    result = asyncio.run(run(plan))
    with open(out_path, "w") as f:
        json.dump(result, f)


if __name__ == "__main__":
    main()
