"""Child process for tests/test_tracing_spans.py: one gateway PUT and
one degraded GET through a ``backend: jax`` cluster (the CPU here)
under a JAX profiler session.

    python tests/tracing_worker.py <work dir>

Run in its own process: a profiler session belongs to one process,
and the trace it leaves holds this one's events alone.  Prints the
``.xplane.pb`` path and the object size as JSON.
"""

import asyncio
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

CHUNK = 1 << 16
PAYLOAD_BYTES = 2 * 3 * CHUNK + 1000


def main(work: str) -> None:
    import jax

    from chunky_bits_tpu.cluster import Cluster
    from chunky_bits_tpu.gateway import make_app

    dirs = []
    for i in range(5):
        d = os.path.join(work, f"disk{i}")
        os.makedirs(d)
        dirs.append(d)
    meta = os.path.join(work, "meta")
    os.makedirs(meta)
    cluster = Cluster.from_obj({
        "destinations": [{"location": d} for d in dirs],
        "metadata": {"type": "path", "format": "yaml", "path": meta},
        "profiles": {"default": {"data": 3, "parity": 2,
                                 "chunk_size": 16}},
        "tunables": {"backend": "jax"},
    })
    payload = os.urandom(PAYLOAD_BYTES)
    trace_dir = os.path.join(work, "trace")

    async def serve() -> None:
        from aiohttp.test_utils import TestClient, TestServer

        app = make_app(cluster, sendfile=False)
        async with TestClient(TestServer(app)) as client:
            # compile every shape first, so the traced requests dispatch
            # warm programs
            for name in ("warm", "obj"):
                if name == "obj":
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    jax.profiler.start_trace(trace_dir,
                                             profiler_options=opts)
                resp = await client.put(f"/{name}", data=payload)
                assert resp.status == 200
                ref = await cluster.get_file_ref(name)
                for part in ref.parts:
                    os.remove(part.data[0].locations[0].target)
                resp = await client.get(f"/{name}")
                assert await resp.read() == payload
            jax.profiler.stop_trace()
        await cluster.tunables.location_context().aclose()

    asyncio.run(serve())
    path = next(os.path.join(d, f) for d, _, fs in os.walk(trace_dir)
                for f in fs if f.endswith(".xplane.pb"))
    print(json.dumps({"xplane": path, "bytes": PAYLOAD_BYTES}))


if __name__ == "__main__":
    main(sys.argv[1])
