"""From a JAX profiler trace to device busy time, kernel time and idle gaps.

`load_xplane()` reads an `.xplane.pb` with nothing but JAX
(`jax.profiler.ProfileData`) into a small plain form, which is also what the
recorded fixture under `benchmarks/fixtures/` holds:

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops", "events": [[name, start_ns, dur_ns], ...]}]}]}

`reduce()` works on that form alone. A device plane is one named
`/device:TPU:<n>`. Its `XLA Ops` line holds one event per operation that ran
on the chip; its `XLA Modules` line one event per launch of a jitted program,
under the program's name (`jit__ivf_search(...)`). Busy time is the union of
the operation intervals inside the window, averaged over the device planes.
The window is marked by the harness with two `jax.profiler.TraceAnnotation`s
(`bench_slice_begin`, `bench_slice_end`), which the host plane carries on the
same clock as the device planes.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional

from harness.stats import union_seconds

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
BEGIN, END = "bench_slice_begin", "bench_slice_end"


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def load_xplane(path: str) -> dict:
    """The plain form of a trace file: every device plane whole, and of the
    other planes only the harness's own annotations."""
    from jax.profiler import ProfileData

    planes, summary = [], []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            total, events = 0, []
            for e in line.events:
                total += 1
                if device or e.name in (BEGIN, END):
                    events.append([e.name, float(e.start_ns), float(e.duration_ns)])
            summary.append([plane.name, line.name, total])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes, "summary": summary}


_HLO = re.compile(r"^%?(\S+) = (.+?) ([a-z][a-z0-9_.\-]*)\(")


def short_op(name: str) -> str:
    """An operation's name without its operands: the trace names an op by
    its whole HLO line (`%fusion.3 = f32[32]{0:T(128)} fusion(...), kind=...`);
    what identifies it is its name, its opcode and its result shape (layouts
    dropped, a tuple's shape cut short)."""
    m = _HLO.match(name)
    if not m:
        return name[:80]
    shape = re.sub(r"\{[^}]*\}", "", m.group(2))
    return f"{m.group(1)} {m.group(3)} {shape[:48]}"


def _window(ir: dict) -> Optional[tuple]:
    begin = end = None
    for plane in ir["planes"]:
        for line in plane["lines"]:
            for name, start, _ in line["events"]:
                if name == BEGIN:
                    begin = start if begin is None else min(begin, start)
                elif name == END:
                    end = start if end is None else max(end, start)
    if begin is None or end is None or end <= begin:
        return None
    return begin, end


def _clip(events: List[list], lo: float, hi: float) -> List[tuple]:
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e))
    return out


def reduce(ir: dict, kernel_pattern: Optional[str] = None, top: int = 10) -> Optional[dict]:
    """Window length, busy seconds (mean over the device planes), the
    operations that took most time, the jitted programs by name, the time
    and launches of the programs matching `kernel_pattern`, and the longest
    gaps in which no operation ran on the first device (seconds from the
    window's start). None when the trace holds no device plane with
    operations: a run that never reached a chip has no device number."""
    devices = [p for p in ir["planes"] if DEVICE_PLANE.match(p["name"])]
    span = _window(ir)
    if span is None:
        starts = [e[1] for p in devices for l in p["lines"] for e in l["events"]]
        ends = [e[1] + e[2] for p in devices for l in p["lines"] for e in l["events"]]
        if not starts:
            return None
        span = (min(starts), max(ends))
    lo, hi = span
    busy, op_seconds, modules, gaps = [], {}, {}, []
    kernel_s, kernel_n = 0.0, 0
    for i, plane in enumerate(sorted(devices, key=lambda p: p["name"])):
        by_line = {l["name"]: l["events"] for l in plane["lines"]}
        ops = _clip(by_line.get(OPS_LINE, []), lo, hi)
        busy.append(union_seconds([(s, e) for _, s, e in ops]) / 1e9)
        for name, s, e in ops:
            op_seconds[short_op(name)] = op_seconds.get(short_op(name), 0.0) + (e - s) / 1e9
        for name, s, e in _clip(by_line.get(MODULES_LINE, []), lo, hi):
            base = re.sub(r"\(\d+\)$", "", name)
            m = modules.setdefault(base, {"seconds": 0.0, "launches": 0})
            m["seconds"] += (e - s) / 1e9
            m["launches"] += 1
            if kernel_pattern and re.search(kernel_pattern, name):
                kernel_s += (e - s) / 1e9
                kernel_n += 1
        if i == 0:
            cur = lo
            for s, e in sorted((s, e) for _, s, e in ops):
                if s > cur:
                    gaps.append(((cur - lo) / 1e9, (s - cur) / 1e9))
                cur = max(cur, e)
            if hi > cur:
                gaps.append(((cur - lo) / 1e9, (hi - cur) / 1e9))
    if not busy or not any(b > 0 for b in busy):
        return None
    ranked = sorted(op_seconds.items(), key=lambda kv: -kv[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy),
        "devices": len(busy),
        "device_ops": [[n, s] for n, s in ranked[:top]],
        "modules": modules,
        "kernel_s": kernel_s / len(busy),
        "kernel_launches": kernel_n,
        "gaps": sorted(gaps, key=lambda g: -g[1])[:200],
        "gap_total_s": sum(g[1] for g in gaps),
    }


def attribute_gaps(gaps: List[tuple], spans: List[tuple], top: int = 10) -> List[list]:
    """Name each idle gap by what the host was doing at its middle: the
    narrowest span of a tagged request that covers that instant (`spans` are
    (name, start_s, end_s) on the gaps' clock), or `no_tagged_request` where
    no tagged request was in flight. Returns [[name, seconds], ...], the
    seconds summed by name, largest first."""
    by_name: Dict[str, float] = {}
    for start, dur in gaps:
        mid = start + dur / 2.0
        best = None
        for name, s, e in spans:
            if s <= mid < e and (best is None or e - s < best[1]):
                best = (name, e - s)
        key = best[0] if best else "no_tagged_request"
        by_name[key] = by_name.get(key, 0.0) + dur
    return [[n, s] for n, s in sorted(by_name.items(), key=lambda kv: -kv[1])[:top]]
