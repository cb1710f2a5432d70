"""From a profiler trace to device metrics.

``extract`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData`` into
plain lists of ``(name, start_ns, end_ns)``: the operations and the
programs (XLA modules) that ran on each TPU, and the host spans whose
names start with ``bench.`` (the harness's ``TraceAnnotation``s).
``reduce`` turns those lists into the numbers the readers use: the time
the device was busy (union of its operation intervals), the traced
window, the operations that took most time, the longest stretches of idle
device time, each cut where a harness span starts or ends and named by the
innermost span over it, and the device time of each program.  The two
steps are apart so the reduction can be checked on a recorded trace
without a chip.
"""

from __future__ import annotations

import bisect
import collections
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
WINDOW_SPAN = "bench.traced_window"


def _op_name(hlo: str) -> str:
    """``%fusion.14 = f32[8,4096]{...} fusion(...)`` -> ``fusion.14``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def extract(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, dict] = {}
    host = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(_op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events]
                elif line.name == "XLA Modules":
                    modules = [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
            devices[plane.name] = {"ops": ops, "modules": modules}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events if e.name.startswith("bench."))
    return {"devices": devices, "host": host}


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted intervals."""
    out: list[list[float]] = []
    for a, b in sorted((a, b) for a, b in intervals if b > a):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(intervals, t0, t1):
    return [(max(a, t0), min(b, t1)) for a, b in intervals if b > t0 and a < t1]


def _program(name: str) -> str:
    return name.split("(")[0]


def _covering(spans, t):
    """Innermost harness span that contains ``t``."""
    best = None
    for name, a, b in spans:
        if a <= t <= b and name != WINDOW_SPAN and (best is None or b - a < best[2] - best[1]):
            best = (name, a, b)
    return best[0] if best else "outside harness spans"


def reduce(ex: dict, top: int = 10) -> dict | None:
    """Device numbers over the traced window, or None without device events."""
    windows = [(a, b) for n, a, b in ex["host"] if n == WINDOW_SPAN]
    devices = [d for d in ex["devices"].values() if d["ops"]]
    if not windows or not devices:
        return None
    t0, t1 = windows[0]
    window_s = (t1 - t0) * 1e-9
    busy = []
    op_time: collections.Counter = collections.Counter()
    program_time: collections.Counter = collections.Counter()
    program_calls: collections.Counter = collections.Counter()
    gaps = []
    for dev in devices:
        ops = [(n, a, b) for n, a, b in dev["ops"] if b > t0 and a < t1]
        merged = union(_clip([(a, b) for _, a, b in ops], t0, t1))
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        mods = sorted(dev["modules"], key=lambda m: m[1])
        starts = [m[1] for m in mods]
        for name, a, b in ops:
            # name each operation by the program that ran it
            i = bisect.bisect_right(starts, a) - 1
            prog = _program(mods[i][0]) if i >= 0 and mods[i][2] >= a else "?"
            op_time[f"{prog}/{name}"] += (min(b, t1) - max(a, t0)) * 1e-9
        for name, a, b in mods:
            if b > t0 and a < t1:
                program_time[_program(name)] += (min(b, t1) - max(a, t0)) * 1e-9
                program_calls[_program(name)] += 1
        edges = [t0] + [x for ab in merged for x in ab] + [t1]
        idle = sorted(((b - a, a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a),
                      reverse=True)
        cuts = sorted({x for _, a, b in ex["host"] for x in (a, b)})
        for length, a, b in idle:
            # a piece is never longer than its gap: stop once no gap left
            # can reach the shortest piece kept
            if len(gaps) >= top and length * 1e-9 < sorted(g[1] for g in gaps)[-top]:
                break
            gaps.extend(_split(ex["host"], cuts, a, b))
    n = len(devices)
    gaps.sort(key=lambda g: -g[1])
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / n,
        "idle_share": 1.0 - sum(busy) / n / window_s,
        "device_ops": [[k, v / n] for k, v in op_time.most_common(top)],
        "idle_gaps": [[k, v] for k, v in gaps[:top]],
        "program_s": {k: v / n for k, v in program_time.items()},
        "program_calls": {k: v / n for k, v in program_calls.items()},
    }


def _split(spans, cuts, a, b):
    """Pieces of the idle gap [a, b], cut where a harness span starts or
    ends, each named by the innermost span over it; neighbours with one
    name are merged."""
    lo, hi = bisect.bisect_right(cuts, a), bisect.bisect_left(cuts, b)
    pts = [a] + cuts[lo:hi] + [b]
    pieces: list[list] = []
    for x, y in zip(pts, pts[1:]):
        if y <= x:
            continue
        name = _covering(spans, (x + y) / 2)
        if pieces and pieces[-1][0] == name:
            pieces[-1][1] += (y - x) * 1e-9
        else:
            pieces.append([name, (y - x) * 1e-9])
    return [tuple(p) for p in pieces]
