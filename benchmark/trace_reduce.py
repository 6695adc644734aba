"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

Per chip (planes ``/device:TPU:<n>``):

* busy: the union of the intervals of the events on the ``XLA Ops``
  line; idle share is 1 - busy / window;
* the codec programs: events on ``XLA Modules`` whose ops carry a GF
  bit matrix ``s8|bf16[8r, 8k]`` and a shard array ``[B, r|8r|k, S]`` —
  found by that signature, not by name, since the program names none of
  them (today the modules are ``jit_call`` for the Pallas kernel,
  ``jit_apply_bitplane`` for the einsum path and the shard_map'd
  programs of ``parallel/mesh.py``).  Their device time is the union of
  their own ops' intervals, not the module span: on a mesh a chip's
  module span also holds its wait for the other chips, when it does no
  work.  Their work comes from ``work.gf_apply_work`` on the shapes;
* collectives: the union of the ops that are all-reduce, all-gather,
  reduce-scatter, collective-permute or all-to-all.

The window is the profile's own start-to-stop span (``Task Environment``
plane), so the share needs no host clock.
"""

from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field

import work

SHAPE = re.compile(r"\b(u8|s8|bf16|s16|s32|f32)\[([\d,]*)\]")
COLLECTIVE = re.compile(r"\b(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)(-start|-done)?\(")


@dataclass
class Chip:
    name: str
    busy_s: float = 0.0
    collective_s: float = 0.0
    codec_s: float = 0.0
    codec_least_s: float = 0.0
    codec_calls: int = 0
    bounds: dict = field(default_factory=dict)
    shapes: dict = field(default_factory=dict)
    ops: dict = field(default_factory=dict)
    busy: list = field(default_factory=list)


@dataclass
class Summary:
    window_s: float
    chips: list
    idle_gaps: list

    @property
    def busy_s(self) -> float:
        return sum(c.busy_s for c in self.chips) / len(self.chips)

    def device_ops(self, top: int = 10) -> list:
        total: dict = {}
        for c in self.chips:
            for k, v in c.ops.items():
                total[k] = total.get(k, 0.0) + v / len(self.chips)
        return sorted(total.items(), key=lambda kv: -kv[1])[:top]


def _union(intervals: list) -> list:
    out: list = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return out


def _length(intervals: list) -> float:
    return sum(hi - lo for lo, hi in intervals)


def codec_shape(op_names: list) -> tuple[int, int, int, int] | None:
    """``(B, k_in, r_out, S)`` of a codec program from its op texts, or
    None when the module is not one."""
    mats, arrays = [], []
    for text in op_names:
        for dtype, dims in SHAPE.findall(text):
            shape = tuple(int(x) for x in dims.split(",") if x)
            if len(shape) == 2 and dtype in ("s8", "bf16") \
                    and shape[0] % 8 == 0 and shape[1] % 8 == 0:
                mats.append(shape)
            elif len(shape) == 3:
                arrays.append(shape)
    for r8, k8 in mats:
        r, k = r8 // 8, k8 // 8
        for b, mid, s in arrays:
            if mid in (r, r8, k):
                return b, k, r, s
    return None


def _op_key(module: str, text: str) -> str:
    op = text.split(" = ", 1)[0].lstrip("%")
    return f"{module.split('(', 1)[0]}/{re.sub(r'[.][0-9]+$', '', op)}"


def _reduce_chip(plane, peak: dict) -> Chip:
    chip = Chip(plane.name)
    lines = {line.name: list(line.events) for line in plane.lines}
    ops = sorted(((e.start_ns, e.start_ns + e.duration_ns, e.name)
                  for e in lines.get("XLA Ops", [])))
    starts = [o[0] for o in ops]
    chip.busy = _union([(lo, hi) for lo, hi, _ in ops])
    chip.busy_s = _length(chip.busy) * 1e-9
    chip.collective_s = _length(_union(
        [(lo, hi) for lo, hi, text in ops if COLLECTIVE.search(text)])) * 1e-9
    codec: list = []
    for m in lines.get("XLA Modules", []):
        lo, hi = m.start_ns, m.start_ns + m.duration_ns
        inside = ops[bisect.bisect_left(starts, lo):
                     bisect.bisect_right(starts, hi)]
        for olo, ohi, text in inside:
            key = _op_key(m.name, text)
            chip.ops[key] = chip.ops.get(key, 0.0) + (ohi - olo) * 1e-9
        shape = codec_shape([text for _, _, text in inside])
        if shape is None:
            continue
        least, bound = work.least_seconds(*work.gf_apply_work(*shape), peak)
        codec.extend((olo, min(ohi, hi)) for olo, ohi, _ in inside)
        chip.codec_least_s += least
        chip.codec_calls += 1
        chip.bounds[bound] = chip.bounds.get(bound, 0) + 1
        chip.shapes[shape] = chip.shapes.get(shape, 0) + 1
    chip.codec_s = _length(_union(codec)) * 1e-9
    assert chip.codec_s <= chip.busy_s + 1e-12, (
        f"{plane.name}: codec {chip.codec_s} s over busy {chip.busy_s} s")
    return chip


def _host_events(planes) -> list:
    out = []
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0:
                        out.append((e.start_ns, e.start_ns + e.duration_ns,
                                    e.name))
    return sorted(out)


def _label(gap: tuple, host: list) -> str:
    """What the host did in an idle gap: the span name that covers most
    of it, preferring the harness's leaf spans (``host.*``) over the
    batcher groups that wrap them; "no host span" where none does (the
    event loop: HTTP, file I/O, metadata)."""
    lo, hi = gap
    cover: dict = {}
    for elo, ehi, ename in host:
        if elo >= hi:
            break
        c = min(hi, ehi) - max(lo, elo)
        if c > 0:
            cover[ename] = cover.get(ename, 0.0) + c
    for prefix in ("host.", "batching.", ""):
        named = {k: v for k, v in cover.items() if k.startswith(prefix)}
        if named:
            return max(named, key=named.get)[:80]
    return "no host span"


def reduce(path: str, device_kind: str, n_chips: int) -> Summary:
    """Summarise the trace at ``path`` over its first ``n_chips``
    device planes."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = list(data.planes)
    env = next((dict(p.stats) for p in planes
                if p.name == "Task Environment"), {})
    window_ns = env.get("profile_stop_time", 0) - env.get(
        "profile_start_time", 0)
    devices = sorted((p for p in planes if p.name.startswith("/device:TPU:")),
                     key=lambda p: int(p.name.rsplit(":", 1)[1]))[:n_chips]
    if not devices or window_ns <= 0:
        raise ValueError(f"{path}: no device plane or no profile window")
    peak = work.peaks(device_kind)
    chips = [_reduce_chip(p, peak) for p in devices]
    gaps, last = [], 0.0
    for lo, hi in chips[0].busy + [[window_ns, window_ns]]:
        if lo > last:
            gaps.append((last, lo))
        last = max(last, hi)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    host = _host_events(planes)
    idle = [[_label(g, host), (g[1] - g[0]) * 1e-9] for g in gaps]
    return Summary(window_ns * 1e-9, chips, idle)
