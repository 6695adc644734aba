"""The load generator: a child process that never imports JAX.

    python3 benchmark/loadgen.py <spec.json>

It reads the spec (port, seed, traffic mix, store object names, output
path), builds its payload pool and one body buffer per closed-loop
client, prints one JSON line ``{"ready": ...}`` with the rate at which it
can build request bodies, then waits for one line ``{"t1": <monotonic
end of window>}`` on stdin.  Closed-loop clients start one after
another, spread evenly over the mix's warm-up, and stop issuing at
``t1``; requests in flight then are drained.  A client builds each PUT
body in its own buffer, in a worker thread, so building never holds up
the other clients' sends (a fresh 256 MiB array pays a page fault every
4 KiB: some 0.4 s a body on a TPU v5e host).  Every request is recorded as
``[op, start, end, status, nbytes, name, sha256 or null]`` with times on
``time.monotonic()`` — for a closed loop the start is when it was sent,
for an open loop when it was due.  The records go to the output path
and the last stdout line is ``{"done": <count>}``.
"""

from __future__ import annotations

import asyncio
import hashlib
import json
import os
import random
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from payload import Payload  # noqa: E402

#: a request that takes longer has failed
REQUEST_TIMEOUT_S = 120.0


def choose(rng: random.Random, weighted) -> object:
    """``weighted`` is a value or a list of ``[value, weight]``."""
    if not isinstance(weighted, list):
        return weighted
    values = [v for v, _ in weighted]
    return rng.choices(values, weights=[w for _, w in weighted])[0]


class Generator:
    def __init__(self, spec: dict) -> None:
        self.spec = spec
        self.traffic = spec["traffic"]
        self.seed = spec["seed"]
        self.base = f"http://127.0.0.1:{spec['port']}"
        self.store = spec.get("store_names", [])
        sizes = self.traffic.get("object_bytes", 0)
        self.max_size = (max(v for v, _ in sizes)
                         if isinstance(sizes, list) else sizes)
        self.payload = Payload(self.seed, self.max_size) \
            if self.max_size else None
        self.records: list = []
        self.next_index = 0
        clients = (0 if self.traffic.get("rate_per_s")
                   else int(self.traffic["clients"]))
        #: each closed-loop client's body buffer, its pages touched here
        self.buffers = ([_touched(self.max_size) for _ in range(clients)]
                        if self.payload else [])

    def gen_rate(self) -> float:
        """Bytes per second this process builds request bodies at."""
        if self.payload is None:
            return 0.0
        out = (self.buffers[0] if self.buffers
               else _touched(self.max_size))
        t = time.perf_counter()
        n = 0
        while time.perf_counter() - t < 0.5:
            n += len(self.payload.body_into(1 << 31, self.max_size, out))
        return n / (time.perf_counter() - t)

    async def one(self, http, rng: random.Random, due: float,
                  buf: np.ndarray | None) -> None:
        """One request; a PUT builds its body in ``buf`` (a closed-loop
        client's own buffer) or, for an open loop, a fresh array."""
        import aiohttp

        op = choose(rng, self.traffic["op"])
        status, nbytes, digest, name = 0, 0, None, ""
        if op == "put":
            size = choose(rng, self.traffic["object_bytes"])
            index = self.next_index
            self.next_index += 1
            name = f"o/{index}"
            out = buf if buf is not None else np.empty(size, np.uint8)
            body = memoryview(await asyncio.to_thread(
                self.payload.body_into, index, size, out))
        start = due if due else time.monotonic()
        try:
            if op == "put":
                async with http.put(f"{self.base}/{name}",
                                    data=body) as r:
                    await r.read()
                    status, nbytes = r.status, size
            else:
                name = rng.choice(self.store)
                method = http.get if op == "get" else http.head
                async with method(f"{self.base}/{name}") as r:
                    h = hashlib.sha256()
                    async for block in r.content.iter_chunked(1 << 20):
                        h.update(block)
                        nbytes += len(block)
                    status = r.status
                    digest = h.hexdigest() if op == "get" else None
        except (aiohttp.ClientError, asyncio.TimeoutError) as err:
            print(f"loadgen: {op} {name}: {err!r}", file=sys.stderr)
            status = -1
        self.records.append([op, start, time.monotonic(), status, nbytes,
                             name, digest])

    async def run(self, t1: float) -> None:
        import aiohttp

        clients = int(self.traffic["clients"])
        timeout = aiohttp.ClientTimeout(total=REQUEST_TIMEOUT_S)
        conn = aiohttp.TCPConnector(limit=clients)
        async with aiohttp.ClientSession(connector=conn,
                                         timeout=timeout) as http:
            rate = self.traffic.get("rate_per_s")
            if rate:
                await self.open_loop(http, float(rate), t1)
            else:
                await asyncio.gather(*(
                    self.closed_loop(http, c, t1) for c in range(clients)))

    async def closed_loop(self, http, client: int, t1: float) -> None:
        """Client ``client`` starts ``client / clients`` of the mix's
        warm-up after the go, so the clients' requests do not run in
        step."""
        rng = random.Random(f"{self.seed}:{client}")
        buf = self.buffers[client] if self.buffers else None
        await asyncio.sleep(client * float(self.traffic.get(
            "warmup_seconds", 2)) / int(self.traffic["clients"]))
        while time.monotonic() < t1:
            await self.one(http, rng, 0.0, buf)

    async def open_loop(self, http, rate: float, t1: float) -> None:
        """Arrivals at seeded exponential gaps; each request is timed
        from when it was due, so a stall counts against later ones."""
        rng = random.Random(f"{self.seed}:open")
        tasks = set()
        due = time.monotonic()
        while due < t1:
            await asyncio.sleep(max(0.0, due - time.monotonic()))
            task = asyncio.create_task(self.one(http, rng, due, None))
            tasks.add(task)
            task.add_done_callback(tasks.discard)
            due += rng.expovariate(rate)
        await asyncio.gather(*tasks)


def _touched(size: int) -> np.ndarray:
    buf = np.empty(size, dtype=np.uint8)
    buf.fill(0)
    return buf


def main() -> int:
    with open(sys.argv[1]) as f:
        spec = json.load(f)
    gen = Generator(spec)
    print(json.dumps({"ready": True, "gen_bytes_per_s": gen.gen_rate()}),
          flush=True)
    go = json.loads(sys.stdin.readline())
    asyncio.run(gen.run(go["t1"]))
    with open(spec["out"], "w") as f:
        json.dump(gen.records, f)
    print(json.dumps({"done": len(gen.records)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
