#!/usr/bin/env python3
"""The load generator: one process of its own that never imports JAX.

It reads one JSON plan line on standard input: the node's port, the
request path, the pool of request bodies, the traffic mix, the window's
length and the seed. It opens its keep-alive connections to 127.0.0.1,
prints ``ready``, waits for a ``go`` line, runs the window and prints
``closed`` once every request of the window has its answer (or a minute
past the close has gone by). Then it prints one JSON object with a
record per request: ``[pool index, due, sent, done, status]`` in seconds
from the window's start (``done`` is null for a request never answered),
and the answers' bodies.

- ``"loop": "open"``: ``round(rate * seconds)`` arrivals at seeded
  uniform times in the window (a Poisson process given its count, so
  every seed offers the same load in another order), each sent when due
  on an idle connection, or a new one. Latency counts from the due time;
  ``sent - due`` is how late the generator ran.
- ``"loop": "closed"``: ``clients`` connections, each sending its next
  request when the last one is answered, until the window closes.

``--sweep RATES`` (a plan key ``sweep`` when driven by ``run.py``) runs
the open loop at each rate in turn for the window's length and reports
offered and completed rates, the backlog left at the close and the tails:
the one-time search for the knee. It stops after the first rate that
leaves more than half of its requests unanswered at the close.
"""

from __future__ import annotations

import asyncio
import json
import math
import random
import sys
from typing import List, Optional

GRACE_S = 60.0          # how long past the close an answer is waited for
MAX_CONNECTIONS = 512
OPEN_CONNECTIONS = 64   # opened before the window, so it starts warm


def open_schedule(rate: float, seconds: float, seed: int, pool: int):
    rng = random.Random(seed * 2 + 1)
    n = max(1, round(rate * seconds))
    due = sorted(rng.uniform(0.0, seconds) for _ in range(n))
    return [(t, rng.randrange(pool)) for t in due]


def closed_sequence(seed: int, pool: int):
    rng = random.Random(seed * 2 + 1)
    while True:
        yield rng.randrange(pool)


class Client:
    """Keep-alive HTTP/1.1 connections to the node, reused when idle."""

    def __init__(self, port: int, path: str):
        self.port = port
        self.head = (f"POST {path} HTTP/1.1\r\nHost: 127.0.0.1\r\n"
                     f"Content-Type: application/json\r\n").encode()
        self.idle: List[tuple] = []

    async def connect(self):
        return await asyncio.open_connection("127.0.0.1", self.port)

    async def fill(self, n: int) -> None:
        self.idle.extend([await self.connect() for _ in range(n)])

    async def post(self, conn, body: bytes):
        """(status, raw body) of one request on ``conn``."""
        reader, writer = conn
        writer.write(self.head + b"Content-Length: %d\r\n\r\n" % len(body)
                     + body)
        line = await reader.readline()
        if not line:
            raise ConnectionError("connection closed")
        status = int(line.split()[1])
        length = 0
        while True:
            line = await reader.readline()
            if line in (b"\r\n", b"\n", b""):
                break
            key, _, value = line.partition(b":")
            if key.strip().lower() == b"content-length":
                length = int(value)
        raw = await reader.readexactly(length) if length else b""
        return status, raw

    def close(self) -> None:
        for _reader, writer in self.idle:
            writer.close()
        self.idle.clear()


async def run_open(client: Client, bodies: List[bytes], schedule,
                   keep: bool):
    loop = asyncio.get_running_loop()
    t0 = loop.time() + 0.005
    records: List[list] = []
    answers: List[Optional[str]] = []
    slots = asyncio.Semaphore(MAX_CONNECTIONS)

    async def one(i: int, due: float, q: int):
        rec = records[i]
        rec[2] = loop.time() - t0
        async with slots:
            conn = client.idle.pop() if client.idle else \
                await client.connect()
            try:
                status, raw = await client.post(conn, bodies[q])
            except (OSError, ValueError, asyncio.IncompleteReadError):
                rec[4] = -1
                conn[1].close()
                return
            client.idle.append(conn)
        rec[3], rec[4] = loop.time() - t0, status
        if keep:
            answers[i] = raw.decode()

    tasks = []
    for i, (due, q) in enumerate(schedule):
        records.append([q, due, None, None, 0])
        answers.append(None)
        wait = t0 + due - loop.time()
        if wait > 0:
            await asyncio.sleep(wait)
        tasks.append(loop.create_task(one(i, due, q)))
    end = schedule[-1][0] if schedule else 0.0
    await asyncio.wait(tasks, timeout=max(0.0, t0 + end + GRACE_S
                                          - loop.time()))
    for t in tasks:
        t.cancel()
    return records, answers


async def run_closed(client: Client, bodies: List[bytes], clients: int,
                     seconds: float, seed: int, keep: bool):
    loop = asyncio.get_running_loop()
    seq = closed_sequence(seed, len(bodies))
    t0 = loop.time()
    records: List[list] = []
    answers: List[Optional[str]] = []

    async def worker():
        conn = await client.connect()
        try:
            while loop.time() - t0 < seconds:
                q = next(seq)
                i = len(records)
                sent = loop.time() - t0
                records.append([q, sent, sent, None, 0])
                answers.append(None)
                try:
                    status, raw = await client.post(conn, bodies[q])
                except (OSError, ValueError, asyncio.IncompleteReadError):
                    records[i][4] = -1
                    conn = await client.connect()
                    continue
                records[i][3], records[i][4] = loop.time() - t0, status
                if keep:
                    answers[i] = raw.decode()
        finally:
            conn[1].close()

    workers = [loop.create_task(worker()) for _ in range(clients)]
    await asyncio.wait(workers, timeout=seconds + GRACE_S)
    for w in workers:
        w.cancel()
    return records, answers


def summarize(records, seconds: float) -> dict:
    """Offered and completed rates, backlog at the close and latency
    tails (from the due time) of one open-loop window."""
    lat = sorted((r[3] - r[1]) if r[3] is not None and r[4] == 200
                 else math.inf for r in records)
    done = sum(1 for r in records
               if r[3] is not None and r[4] == 200 and r[3] <= seconds)

    def pct(p):
        return lat[max(1, math.ceil(p / 100 * len(lat))) - 1] * 1e3

    return {"offered_qps": len(records) / seconds,
            "completed_qps": done / seconds,
            "backlog": sum(1 for r in records
                           if r[3] is None or r[3] > seconds),
            "p50_ms": pct(50), "p99_ms": pct(99),
            "late_p99_ms": 1e3 * sorted(
                (r[2] - r[1]) if r[2] is not None else math.inf
                for r in records)[max(0, math.ceil(0.99 * len(records)) - 1)]}


def say(obj) -> None:
    sys.stdout.write((obj if isinstance(obj, str) else json.dumps(obj))
                     + "\n")
    sys.stdout.flush()


async def main_async(plan: dict) -> None:
    bodies = [b.encode() for b in plan["bodies"]]
    traffic = plan["traffic"]
    seconds, seed = float(plan["seconds"]), int(plan["seed"])
    client = Client(int(plan["port"]), plan["path"])
    loop = asyncio.get_running_loop()
    if traffic["loop"] == "open":
        await client.fill(OPEN_CONNECTIONS)
    say("ready")
    if (await loop.run_in_executor(None, sys.stdin.readline)).strip() \
            != "go":
        return
    if plan.get("sweep"):
        for rate in plan["sweep"]:
            records, _ = await run_open(
                client, bodies,
                open_schedule(rate, seconds, seed, len(bodies)), False)
            got = summarize(records, seconds)
            say({"rate": rate, **got})
            if got["backlog"] > len(records) / 2:
                break               # saturated: higher rates add nothing
        say("closed")
        say({"records": [], "answers": []})
        return
    if traffic["loop"] == "open":
        records, answers = await run_open(
            client, bodies, open_schedule(float(traffic["rate"]), seconds,
                                          seed, len(bodies)), True)
    else:
        records, answers = await run_closed(
            client, bodies, int(traffic["clients"]), seconds, seed, True)
    say("closed")
    client.close()
    say({"records": records, "answers": answers})


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--sweep", default=None,
                    help="comma-separated open-loop rates (queries/s), "
                         "each run for the plan's window")
    args = ap.parse_args(argv)
    plan = json.loads(sys.stdin.readline())
    if args.sweep:
        plan["sweep"] = [float(r) for r in args.sweep.split(",")]
    asyncio.run(main_async(plan))
    return 0


if __name__ == "__main__":
    sys.exit(main())
