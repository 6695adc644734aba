#!/usr/bin/env python3
"""Run one benchmark cell: the HTTP gateway on the chip, driven from a
child process, or the repair loop in this process; then the reference
check.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

This process holds the chip.  It builds the cell's store under
``<checkout>/.bench_work``, warms every program the cell's traffic
dispatches, serves ``chunky_bits_tpu.gateway.serve(cluster, workers=1)``
on its event loop and starts ``benchmark/loadgen.py``, which never
imports JAX.  The window opens after the mix's warm-up seconds and lasts
``--seconds``; requests still in flight when it closes are drained
outside it.  Then the server stops, the device's peak memory is read,
and what the window produced is compared with ``benchmark/reference.py``
(or the reference the configuration names).  A repair mix (``op:
resilver`` or ``op: scrub``) serves nothing: its ``clients`` workers run
on this process's event loop, each replaying one destination's loss on
an object and then the CLI's ``resilver``, or the scrubber's pass of
``scrub --once``, on it.

The last stdout line is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace
0``, its per-layer metrics with ``--trace 1``), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``: each number compared
with its limit.  Without a TPU, or with fewer chips than the cell asks
for, it exits 3 and prints no result.
"""

from __future__ import annotations

import argparse
import asyncio
import copy
import itertools
import json
import math
import os
import shutil
import sys
import time
import traceback
from dataclasses import dataclass

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import check  # noqa: E402
import spec as specmod  # noqa: E402
import store  # noqa: E402

GIB = float(1 << 30)
OK = {"put": (200,), "get": (200,), "head": (200,), "resilver": (200,),
      "scrub": (200,)}
#: the repair planner's counters the window takes per code: key -> its
#: ``cb_repair_*`` family (``obs/metrics.py``) and labels
REPAIR_COUNTERS = {
    **{f"plans_{k}": ("cb_repair_plans_total", {"kind": k})
       for k in ("copy", "decode", "msr", "fallback")},
    **{f"helper_bytes_{s}": ("cb_repair_helper_bytes_total",
                             {"source": s})
       for s in ("replica", "decode", "msr")},
    "bytes_rebuilt": ("cb_repair_bytes_rebuilt_total", {}),
}
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class CompileCounter:
    """Backend compiles (a compile or a load from the persistent cache:
    either is a program the window had not warmed), by monotonic time.
    One listener per process; jax.monitoring cannot remove one."""

    _instance = None

    def __init__(self) -> None:
        self.times: list[tuple[float, str]] = []

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            from jax import monitoring

            cls._instance = cls()
            monitoring.register_event_duration_secs_listener(
                cls._instance._on_event)
        return cls._instance

    def _on_event(self, name: str, secs: float, **kw) -> None:
        if name == COMPILE_EVENT:
            self.times.append((time.monotonic(), kw.get("fun_name", "")))

    def between(self, t0: float, t1: float) -> list[str]:
        """Names of the programs compiled in ``[t0, t1)``."""
        return [f for t, f in self.times if t0 <= t < t1]


@dataclass
class Window:
    """What the per-layer readers see (``benchmark/metrics/*.py``)."""

    cell: str
    config: dict
    traffic: dict
    seconds: float
    #: counter deltas over the window
    counters: dict
    #: parts of acknowledged PUTs / served GETs, each request weighted by
    #: the share of its duration inside the window
    parts_put: float
    parts_get: float
    #: trace_reduce.Summary of the traced window, or None
    trace: object = None


def overlap(start: float, end: float, t0: float, t1: float) -> float:
    """The share of ``[start, end]`` inside ``[t0, t1]``."""
    if end <= start:
        return float(t0 <= start < t1)
    return max(0.0, min(end, t1) - max(start, t0)) / (end - start)


def gib_s(records: list, op: str, t0: float, t1: float) -> float:
    """GiB/s of the bytes of ``op``'s successful records over the window
    ``[t0, t1]``, each record weighted by the share of its duration
    inside it."""
    done = sum(r[4] * overlap(r[1], r[2], t0, t1) for r in records
               if r[0] == op and r[3] in OK[op])
    return done / GIB / (t1 - t0)


def registry_counters() -> dict:
    """From the process registry (``obs/metrics.py``), 0 before their
    first event: ``cb_batch_parts_total`` and
    ``cb_batch_dispatches_total`` of ``op="reconstruct"`` (every decode
    batcher's, the per-file ones of a repair too), and the repair
    planner's ``cb_repair_*`` counters by code, named
    ``repair.<code>.plans_<kind>``, ``repair.<code>.helper_bytes_<source>``
    and ``repair.<code>.bytes_rebuilt``.  The planners' counters are
    those of the planners alive: the caller keeps its scrub daemons
    alive across the window."""
    from chunky_bits_tpu.cluster.repair import CODES
    from chunky_bits_tpu.obs import metrics

    fams = {f["name"]: f["samples"]
            for f in metrics.get_registry().snapshot()["families"]}

    def total(name: str, **labels) -> float:
        return float(sum(s["value"] for s in fams.get(name, [])
                         if all(s["labels"].get(k) == v
                                for k, v in labels.items())))

    out = {"reconstruct_batch_parts":
           total("cb_batch_parts_total", op="reconstruct"),
           "reconstruct_batch_dispatches":
           total("cb_batch_dispatches_total", op="reconstruct")}
    for code in CODES:
        for key, (fam, labels) in REPAIR_COUNTERS.items():
            out[f"repair.{code}.{key}"] = total(fam, code=code, **labels)
    return out


def window_counters(before: dict, after: dict, counter, t0: float,
                    t1: float) -> dict:
    """The counters' deltas over the window, the host's thread count and
    the compiles inside it."""
    counters = {k: after[k] - before[k] for k in after}
    counters["host_threads"] = after["host_threads"]
    compiled = counter.between(t0, t1)
    counters["compiles"] = len(compiled)
    if compiled:
        print(f"run: compiled in the window: {compiled}", file=sys.stderr)
    return counters


def percentile(values: list[float], q: float) -> float:
    """Linear interpolation between order statistics (numpy's default);
    an infinite value (a failed request) stays beyond any limit."""
    if not values:
        return math.nan
    a = sorted(values)
    pos = q * (len(a) - 1)
    lo = int(pos)
    frac = pos - lo
    if frac == 0 or lo + 1 >= len(a):
        return a[lo]
    if math.isinf(a[lo + 1]):
        return math.inf
    return a[lo] + (a[lo + 1] - a[lo]) * frac


def parts_of(size: int, profile: dict) -> int:
    part = profile["data"] * (1 << profile["chunk_size"])
    return max(1, -(-size // part))


class Session:
    def __init__(self, cell: specmod.Cell, args, root: str, work: str,
                 devices: list, t_start: float, fault=None) -> None:
        self.cell = cell
        self.args = args
        self.root = root
        self.work = work
        self.devices = devices
        self.t_start = t_start
        self.fault = fault
        self.loop = None

    # ---- the window's counters ----

    def snapshot(self, cluster) -> dict:
        enc = cluster._encode_batchers.get(self.loop)
        rec = cluster._reconstruct_batchers.get(self.loop)
        stats = cluster.host_pipeline().stats()
        return {
            "encode_dispatches": enc.dispatches if enc else 0,
            "reconstruct_dispatches": rec.dispatches if rec else 0,
            "hash_busy_s": sum(s.busy_s for s in stats.stages
                               if s.stage == "hash"),
            "verify_busy_s": sum(s.busy_s for s in stats.stages
                                 if s.stage == "verify"),
            "host_threads": stats.threads,
            **registry_counters(),
        }

    async def run(self) -> dict:
        from chunky_bits_tpu.cluster import Cluster

        cell, args = self.cell, self.args
        traffic, config = cell.traffic, cell.config
        self.loop = asyncio.get_running_loop()
        names: list[str] = []
        if traffic.get("store_objects"):
            names = await store.build(self.work, config, traffic, args.seed)
        cluster = Cluster.from_obj(store.cluster_obj(
            self.work, config, config["backend"]))
        store.warm(cluster, config, traffic)
        counter = CompileCounter.get()
        spans = None
        if args.trace:
            import spans as spans_mod

            spans = spans_mod.annotate()
        if self.fault is not None:
            self.fault.__enter__()
        try:
            if store.ops_of(traffic) & set(store.REPAIR_OPS):
                return await self._repair(cluster, names, counter)
            return await self._serve(cluster, names, counter)
        finally:
            if self.fault is not None:
                self.fault.__exit__(None, None, None)
            if spans is not None:
                spans.undo()

    async def _serve(self, cluster, names: list, counter) -> dict:
        from chunky_bits_tpu.gateway import serve

        cell, args, traffic = self.cell, self.args, self.cell.traffic
        ready: asyncio.Future = self.loop.create_future()
        server = asyncio.create_task(serve(
            cluster, "127.0.0.1", 0, workers=1,
            on_ready=ready.set_result))
        child = None
        try:
            port = await asyncio.wait_for(ready, 120)
            records_path = os.path.join(self.work, "records.json")
            spec_path = os.path.join(self.work, "loadgen.json")
            with open(spec_path, "w") as f:
                json.dump({"port": port, "seed": args.seed,
                           "traffic": traffic, "store_names": names,
                           "out": records_path}, f)
            child = await asyncio.create_subprocess_exec(
                sys.executable, os.path.join(BENCH, "loadgen.py"),
                spec_path, stdin=asyncio.subprocess.PIPE,
                stdout=asyncio.subprocess.PIPE)
            hello = json.loads(await asyncio.wait_for(
                child.stdout.readline(), 120))
            t0 = self.loop.time() + float(traffic.get("warmup_seconds", 2))
            t1 = t0 + args.seconds
            child.stdin.write((json.dumps({"t1": t1}) + "\n").encode())
            await child.stdin.drain()
            await asyncio.sleep(max(0.0, t0 - self.loop.time()))
            setup_s = time.perf_counter() - self.t_start
            trace_dir = os.path.join(self.work, "trace")
            if args.trace:
                import jax

                jax.profiler.start_trace(
                    trace_dir, profiler_options=_trace_options())
            before = self.snapshot(cluster)
            await asyncio.sleep(max(0.0, t1 - self.loop.time()))
            after = self.snapshot(cluster)
            if args.trace:
                # off the loop: the drain goes on while the trace is saved
                await asyncio.to_thread(jax.profiler.stop_trace)
            done = await asyncio.wait_for(child.stdout.readline(), 300)
            if not done:
                raise RuntimeError("load generator ended without a result")
            await asyncio.wait_for(child.wait(), 60)
            with open(records_path) as f:
                records = json.load(f)
        finally:
            if child is not None and child.returncode is None:
                child.kill()
                await child.wait()
            server.cancel()
            await asyncio.gather(server, return_exceptions=True)
        counters = window_counters(before, after, counter, t0, t1)
        return self._result(records, t0, t1, setup_s, counters, hello,
                            names, trace_dir)

    async def _repair(self, cluster, names: list, counter) -> dict:
        """The repair loop: worker ``w`` of the mix's ``clients`` takes
        the objects ``w``, ``w + clients``, ... in turn, so no object is
        in two jobs at once.  A job replays the loss on its object
        (``store.Loss``, not timed), then repairs it (timed) and is
        recorded as ``[op, start, end, status, rebuilt chunk bytes,
        name, None]``: ``resilver`` runs the CLI's ``resilver`` on it;
        ``scrub`` runs the scrubber's pass of ``scrub --once`` (its
        repair planner on, its byte rate the CLI's default) over a
        namespace that holds the object alone.  Workers stop taking jobs
        at the window's close; the jobs in flight then are drained."""
        from chunky_bits_tpu.cli.cluster_location import ClusterLocation
        from chunky_bits_tpu.cli.config import Config
        from chunky_bits_tpu.cluster.scrub import ScrubDaemon

        args, traffic = self.args, self.cell.traffic
        op = traffic["op"]
        loss = store.Loss(self.work, names, traffic["lost_destination"])
        config = Config(clusters={"bench": {"cluster": cluster}})
        clients = int(traffic["clients"])
        records: list = []
        # a daemon's planner counts into the registry while it lives
        daemons: list = []
        t0 = time.monotonic() + float(traffic.get("warmup_seconds", 2))
        t1 = t0 + args.seconds

        async def resilver(name: str, lost: int) -> tuple[int, int]:
            report = await ClusterLocation(
                "cluster", cluster="bench", path=name).resilver(config)
            return store.repair_outcome(report, lost)

        async def scrub(name: str, lost: int) -> tuple[int, int]:
            # the cluster with its metadata narrowed to the one file;
            # destinations, tunables, health scoreboard, host pipeline
            # and batchers are the cluster's own
            one = copy.copy(cluster)
            one.metadata = store.OneFile(cluster.metadata, name)
            daemon = ScrubDaemon(one, bytes_per_sec=None)
            daemons.append(daemon)
            stats = await daemon.run_once()
            return store.scrub_outcome(stats, lost, loss.nbytes[name])

        job = {"resilver": resilver, "scrub": scrub}[op]

        async def worker(mine: list) -> None:
            for i in itertools.count():
                if not mine or time.monotonic() >= t1:
                    return
                name = mine[i % len(mine)]
                lost = await asyncio.to_thread(loss.apply, name)
                start = time.monotonic()
                try:
                    status, nbytes = await job(name, lost)
                # a job that raises is recorded as failed, and the
                # others go on
                except Exception:
                    traceback.print_exc()
                    status, nbytes = -1, 0
                records.append([op, start, time.monotonic(), status,
                                nbytes, name, None])

        tasks = [asyncio.create_task(worker(names[w::clients]))
                 for w in range(clients)]
        trace_dir = os.path.join(self.work, "trace")
        try:
            await asyncio.sleep(max(0.0, t0 - time.monotonic()))
            setup_s = time.perf_counter() - self.t_start
            if args.trace:
                import jax

                jax.profiler.start_trace(
                    trace_dir, profiler_options=_trace_options())
            before = self.snapshot(cluster)
            await asyncio.sleep(max(0.0, t1 - time.monotonic()))
            after = self.snapshot(cluster)
            if args.trace:
                await asyncio.to_thread(jax.profiler.stop_trace)
            await asyncio.wait_for(asyncio.gather(*tasks), 300)
        finally:
            for task in tasks:
                task.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
        counters = window_counters(before, after, counter, t0, t1)
        return self._result(records, t0, t1, setup_s, counters, None,
                            names, trace_dir)

    # ---- metrics and the check ----

    def _result(self, records, t0, t1, setup_s, counters, hello, names,
                trace_dir) -> dict:
        cell, args = self.cell, self.args
        profile = cell.config["profile"]
        window = t1 - t0
        in_window = [r for r in records if t0 <= r[1] < t1]
        failed = [r for r in in_window if r[3] not in OK[r[0]]]
        acked = [r for r in records if r[3] in OK[r[0]]]
        parts = {op: sum(parts_of(r[4], profile) * overlap(r[1], r[2],
                                                          t0, t1)
                         for r in acked if r[0] == op)
                 for op in ("put", "get")}

        def p95(op: str) -> float:
            return 1000.0 * percentile(
                [r[2] - r[1] if r[3] in OK[op] else math.inf
                 for r in in_window if r[0] == op], 0.95)

        values = {"setup_s": setup_s,
                  "put_gib_s": gib_s(records, "put", t0, t1),
                  "repair_gib_s": sum(gib_s(records, op, t0, t1)
                                      for op in store.REPAIR_OPS),
                  "put_p95_ms": p95("put"), "get_p95_ms": p95("get")}
        device = device_info(self.devices, cell.chips)
        result = {"correct": None, "attempted": len(in_window),
                  "failed": len(failed)}
        breakdown = None
        if args.trace:
            import trace_reduce

            summary = trace_reduce.reduce(_xplane(trace_dir),
                                          device["kind"], cell.chips)
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            win = Window(cell.name, cell.config, cell.traffic, window,
                         counters, parts["put"], parts["get"], summary)
            metrics = {}
            for m in cell.per_layer:
                value = m.reader(win)
                if value is not None:
                    metrics[m.name] = {"value": value, "unit": m.unit}
            for c in summary.chips:
                print(f"trace {c.name}: busy {c.busy_s} s, codec "
                      f"{c.codec_s} s (least {c.codec_least_s} s, "
                      f"bounds {c.bounds}), collectives "
                      f"{c.collective_s} s, (B, k, r, S) calls "
                      f"{sorted(c.shapes.items())}", file=sys.stderr)
            breakdown = {"device_ops": [list(kv) for kv
                                        in summary.device_ops()],
                         "idle_gaps": summary.idle_gaps}
        else:
            metrics = {m.name: {"value": values[m.name], "unit": m.unit}
                       for m in cell.end_to_end}
        gen = ("" if hello is None else f", load generator builds "
               f"{hello['gen_bytes_per_s'] / GIB} GiB/s")
        print(f"run: window {window} s, {len(records)} requests "
              f"({len(in_window)} started in the window, {len(failed)} "
              f"failed){gen}, counters {counters}", file=sys.stderr)
        print(f"run: store holds {store.bytes_on_disk(self.work)} bytes",
              file=sys.stderr)
        checks = check.compare(self.work, cell, args.seed, records, names,
                               len(failed), counters)
        result["correct"] = all(c["ok"] for c in checks.values())
        result["metrics"] = metrics
        result["device"] = device
        if breakdown is not None:
            result["breakdown"] = breakdown
        result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                            for k, c in checks.items()}
        return result


def _trace_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def _xplane(trace_dir: str) -> str:
    for dirpath, _dirs, files in os.walk(trace_dir):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(dirpath, f)
    raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")


def device_info(devices: list, chips: int) -> dict:
    used = devices[:chips]
    peak = 0
    for d in used:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "memory_peak_bytes": peak}


def main(argv=None, *, root: str | None = None, require_chip: bool = True,
         fault=None) -> int:
    t_start = time.perf_counter()
    args = parse_args(argv)
    root = root or os.path.dirname(BENCH)
    cell = specmod.load(root, args.workload)
    if require_chip:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
            root, ".jax_cache")
        # libtpu would log under /tmp, outside the checkout
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell.chips):
        print(f"run: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    if root not in sys.path:
        sys.path.insert(0, root)
    work = os.path.join(root, ".bench_work")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    try:
        result = asyncio.run(Session(cell, args, root, work, devices,
                                     t_start, fault).run())
    finally:
        shutil.rmtree(work, ignore_errors=True)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
