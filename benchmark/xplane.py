#!/usr/bin/env python3
"""From a profiler trace (``*.xplane.pb``) to the numbers the readers use.

    python3 benchmark/xplane.py <trace dir or .xplane.pb>   -> one JSON object
    python3 benchmark/xplane.py <...> --describe            -> planes, lines, first events
    python3 benchmark/xplane.py <...> --slice 0.40,0.05     -> the planes of 50 ms, as JSON
                                                               (how tests/benchmark/data got its trace)

Runs as a child with ``JAX_PLATFORMS=cpu``: reading the file needs JAX's
``ProfileData`` and nothing of a device. Per device plane (``/device:TPU:n``):

- ``busy_s``: the union of the intervals of the op-level line (``XLA Ops``),
  averaged over the device planes; ``window_s``: the traced window, from the
  first to the last event of any plane;
- ``programs``: the module-level line (``XLA Modules``) summed by program
  name with the trailing ``(id)`` dropped: [name, seconds, calls];
- ``ops``: op-level events summed by name, longest first, every one of
  them: [name, seconds, calls] (``readers/op_time.py`` times a kernel inside
  a program from it); ``device_ops``: the ten longest, [name, seconds], as
  the breakdown prints them;
- ``idle_gaps``: the longest gaps of the union, each named by the host event
  that covers most of it (profiler annotations before ``$file:line`` Python
  frames, which are every gap's backdrop).
"""

from __future__ import annotations

import json
import math
import re
import sys
from pathlib import Path

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
MIN_HOST_EVENT_NS = 50_000
_ID = re.compile(r"\(\d+\)$")
_ARRAY = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def short_op(text: str) -> str:
    """An op event's name is its whole HLO line; keep the op's own name and
    the largest array it yields: ``while.1 u8[32768,6144]``."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text[:80]
    result = rest.split(" ", 1)[0] if not rest.startswith("(") else rest[:rest.find(") ") + 1]
    arrays = _ARRAY.findall(result)
    size = lambda a: math.prod(int(d) for d in a[a.index("[") + 1:-1].split(",") if d)
    return name.lstrip("%") + (" " + max(arrays, key=size) if arrays else "")


def name_gap(gap: tuple[float, float], host_events: list[tuple[float, float, str]]) -> str:
    """The host event that overlaps ``gap`` longest; annotations win over
    Python frames, and of equal overlaps the shortest event (the innermost)."""
    best = None
    for s, e, name in host_events:
        overlap = min(e, gap[1]) - max(s, gap[0])
        if overlap <= 0:
            continue
        key = (not name.startswith("$"), round(overlap / (gap[1] - gap[0]), 2), -(e - s))
        if best is None or key > best[0]:
            best = (key, name)
    return best[1] if best else "no host event"


def reduce(planes: list[dict], top: int = 10) -> dict:
    """``planes``: [{"name", "lines": [{"name", "events": [(start_s, dur_s, name)]}]}]."""
    devices = [p for p in planes if p["name"].startswith("/device:TPU:")]
    if not devices:
        raise ValueError(f"no /device:TPU:n plane in the trace (planes: {[p['name'] for p in planes]})")
    starts, ends = [], []
    for p in planes:
        for ln in p["lines"]:
            for s, d, _ in ln["events"]:
                starts.append(s)
                ends.append(s + d)
    window_s = max(ends) - min(starts)
    host_events = [(s, s + d, n) for p in planes if p["name"].startswith("/host:")
                   for ln in p["lines"] for s, d, n in ln["events"]]
    busy, ops, programs, gaps = [], {}, {}, []
    for p in devices:
        by_line = {ln["name"]: ln["events"] for ln in p["lines"]}
        if OPS_LINE not in by_line:
            raise ValueError(f"{p['name']} has no {OPS_LINE!r} line (lines: {sorted(by_line)})")
        merged = union([(s, s + d) for s, d, _ in by_line[OPS_LINE]])
        busy.append(sum(e - s for s, e in merged))
        for s, d, n in by_line[OPS_LINE]:
            cell = ops.setdefault(short_op(n), [0.0, 0])
            cell[0] += d
            cell[1] += 1
        for s, d, n in by_line.get(MODULES_LINE, []):
            cell = programs.setdefault(_ID.sub("", n), [0.0, 0])
            cell[0] += d
            cell[1] += 1
        gaps += [(b[0] - a[1], (a[1], b[0])) for a, b in zip(merged, merged[1:])]
    gaps.sort(reverse=True)
    ops = sorted(([n, t, c] for n, (t, c) in ops.items()), key=lambda r: -r[1])
    return {
        "busy_s": sum(busy) / len(busy), "window_s": window_s, "devices": len(devices),
        "programs": sorted(([n, t, c] for n, (t, c) in programs.items()), key=lambda r: -r[1]),
        "device_ops": [[n, t] for n, t, _ in ops[:top]],
        "ops": ops,
        "idle_gaps": [[name_gap(g, host_events), length] for length, g in gaps[:top]],
    }


def load(path: Path) -> list[dict]:
    from jax.profiler import ProfileData

    if path.is_dir():
        files = sorted(path.rglob("*.xplane.pb"))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = files[-1]
    data = ProfileData.from_file(str(path))
    planes = []
    for p in data.planes:
        host = p.name.startswith("/host:")
        lines = []
        for ln in p.lines:
            # A host plane holds a Python frame for every call: only events
            # long enough to lie under an idle gap worth naming are kept.
            events = [(e.start_ns / 1e9, e.duration_ns / 1e9, e.name) for e in ln.events
                      if not host or e.duration_ns >= MIN_HOST_EVENT_NS]
            lines.append({"name": ln.name, "events": events})
        planes.append({"name": p.name, "lines": lines})
    return planes


def describe(planes: list[dict]) -> str:
    out = []
    for p in planes:
        out.append(f"PLANE {p['name']}: {len(p['lines'])} lines")
        for ln in p["lines"][:40]:
            out.append(f"  LINE {ln['name']!r}: {len(ln['events'])} events; first {ln['events'][:3]}")
    return "\n".join(out)


def cut(planes: list[dict], start_s: float, length_s: float) -> dict:
    """The events that begin inside [start, start + length) after the
    trace's first event, times counted from the slice's start, names held
    once in a table (an op's name is its whole HLO line)."""
    t0 = min(s for p in planes for ln in p["lines"] for s, _, _ in ln["events"]) + start_s
    names: dict[str, int] = {}
    out = []
    for p in planes:
        lines = [{"name": ln["name"],
                  "events": [(round(s - t0, 9), d, names.setdefault(n, len(names)))
                             for s, d, n in ln["events"] if t0 <= s < t0 + length_s]}
                 for ln in p["lines"]]
        lines = [ln for ln in lines if ln["events"]]
        if lines:
            out.append({"name": p["name"], "lines": lines})
    return {"names": list(names), "planes": out}


def uncut(doc: dict) -> list[dict]:
    """The planes of a :func:`cut` document, names put back."""
    names = doc["names"]
    return [{"name": p["name"],
             "lines": [{"name": ln["name"], "events": [(s, d, names[i]) for s, d, i in ln["events"]]}
                       for ln in p["lines"]]}
            for p in doc["planes"]]


if __name__ == "__main__":
    loaded = load(Path(sys.argv[1]))
    if "--describe" in sys.argv:
        print(describe(loaded), file=sys.stderr)
    if "--slice" in sys.argv:
        start, length = (float(v) for v in sys.argv[sys.argv.index("--slice") + 1].split(","))
        print(json.dumps(cut(loaded, start, length)))
    else:
        print(json.dumps(reduce(loaded)))
