#!/usr/bin/env python3
"""Read a ``POST /debug/trace`` recording by the program's own annotations.

    python3 tools/trace_batches.py <trace dir or .xplane.pb> [--stats stats.json] [--top 10]

Needs JAX's ``ProfileData`` and no device (``JAX_PLATFORMS=cpu``). Prints one
JSON object:

- ``clock``: the two ``twd.clock`` markers and the offset (seconds) that puts
  ``time.monotonic()`` stamps (``batch_timeline``, ``GET /debug/trace``,
  ``/stats -> profile``) on the recording's own clock;
- ``idle_gaps``: the longest gaps of the device's op-level line, each with
  the ``twd.`` annotations that lie over it (name, batch ``seq``, share of the
  gap covered: ``under`` the longest overlaps, ``batch_spans`` those that name
  a batch) and, where ``--stats`` gives ``/stats -> profile``, the batches
  that stood between ``t_launch`` and ``t_done`` during it (``launched``);
- ``h2d``: per batch ``seq``, the ``twd.h2d`` annotation (the ``device_put``s)
  beside the start of that batch's unpack program on the device: if the
  program starts later than ``twd.h2d`` ends by about the copy's length, the
  annotation (and the ``device_transfer`` stage) times the enqueue, not the
  copy.

The benchmark's ``xplane.py`` names a gap by whichever host event covers most
of it; this tool looks at ``twd.`` events alone and keeps their stats.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark.xplane import MODULES_LINE, OPS_LINE, union  # noqa: E402

UNPACK_MODULE = "jit__lambda"


def load(path: Path) -> tuple[list[tuple], list[tuple], list[tuple]]:
    """(twd events [(start_s, end_s, name, stats)], device op intervals,
    device module events [(start_s, end_s, name)]) of the first device."""
    from jax.profiler import ProfileData

    if path.is_dir():
        files = sorted(path.rglob("*.xplane.pb"))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = files[-1]
    twd, ops, modules = [], [], []
    for plane in ProfileData.from_file(str(path)).planes:
        device = plane.name.startswith("/device:TPU:0")
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                span = (e.start_ns / 1e9, (e.start_ns + e.duration_ns) / 1e9)
                if device and line.name == OPS_LINE:
                    ops.append(span)
                elif device and line.name == MODULES_LINE:
                    modules.append((*span, e.name))
                elif not device and e.name.startswith("twd."):
                    twd.append((*span, e.name, dict(e.stats)))
    return sorted(twd), ops, sorted(modules)


def clock(twd: list[tuple]) -> dict:
    marks = [(s, st["mono_ns"]) for s, _, name, st in twd if name == "twd.clock"]
    if not marks:
        return {"markers": 0, "offset_s": None}
    offsets = [mono / 1e9 - s for s, mono in marks]
    return {"markers": len(marks), "offset_s": offsets[0], "offset_drift_s": max(offsets) - min(offsets),
            "recorded_s": marks[-1][0] - marks[0][0]}


def covering(gap: tuple[float, float], twd: list[tuple]) -> list[dict]:
    """The ``twd.`` events over ``gap``, longest overlap first."""
    out = []
    for s, e, name, st in twd:
        overlap = min(e, gap[1]) - max(s, gap[0])
        if overlap > 0 and name != "twd.clock":
            out.append({"name": name, "seq": st.get("seq"), "share": round(overlap / (gap[1] - gap[0]), 3),
                        "event_ms": round(1e3 * (e - s), 3)})
    return sorted(out, key=lambda r: (-r["share"], r["event_ms"]))


def idle_gaps(twd, ops, top: int, offset_s: float | None, batches: list[dict]) -> list[dict]:
    merged = union(ops)
    gaps = sorted(((b[0] - a[1], (a[1], b[0])) for a, b in zip(merged, merged[1:])), reverse=True)[:top]
    rows = []
    for length, gap in gaps:
        over = covering(gap, twd)
        row = {"ms": round(1e3 * length, 3), "at_s": round(gap[0], 6),
               "batch_spans": [r for r in over if r["seq"] is not None][:6], "under": over[:6]}
        if offset_s is not None and batches:
            lo, hi = gap[0] + offset_s, gap[1] + offset_s
            row["launched"] = [b["seq"] for b in batches if b.get("t_launch") is not None
                               and b["t_launch"] < hi and (b["t_done"] is None or b["t_done"] > lo)]
        rows.append(row)
    return rows


def h2d(twd, modules) -> list[dict]:
    """Each batch's ``twd.h2d`` beside its unpack program's start on the
    device. The device runs programs in the order they were enqueued, so the
    unpack calls that begin after the first ``twd.unpack_enqueue`` of the
    recording pair off with the enqueues in order."""
    puts = {st["seq"]: (s, e) for s, e, name, st in twd if name.startswith("twd.h2d") and "seq" in st}
    enqueues = sorted((s, st["seq"]) for s, _, name, st in twd
                      if name.startswith("twd.unpack_enqueue") and "seq" in st)
    if not enqueues:
        return []
    runs = [(s, e) for s, e, name in modules if name.startswith(UNPACK_MODULE) and s >= enqueues[0][0]]
    rows = []
    for (enq_s, seq), (run_s, run_e) in zip(enqueues, runs):
        if seq not in puts:
            continue
        put_s, put_e = puts[seq]
        rows.append({"seq": seq, "h2d_ms": round(1e3 * (put_e - put_s), 3),
                     "h2d_end_to_unpack_start_ms": round(1e3 * (run_s - put_e), 3),
                     "h2d_start_to_unpack_start_ms": round(1e3 * (run_s - put_s), 3),
                     "unpack_ms": round(1e3 * (run_e - run_s), 3)})
    return rows


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("trace", type=Path)
    p.add_argument("--stats", type=Path, help="a /stats document (or its profile block) read after the recording")
    p.add_argument("--top", type=int, default=10)
    args = p.parse_args(argv)
    twd, ops, modules = load(args.trace)
    batches = []
    if args.stats:
        doc = json.loads(args.stats.read_text())
        batches = (doc.get("profile") or doc).get("batches", [])
    ck = clock(twd)
    by_seq = {b["seq"]: b for b in batches}
    rows = h2d(twd, modules)
    for r in rows:
        if by_seq.get(r["seq"], {}).get("h2d_bytes"):
            r["h2d_mb"] = round(by_seq[r["seq"]]["h2d_bytes"] / 1e6, 1)
    print(json.dumps({"clock": ck, "twd_events": len(twd), "device_ops": len(ops),
                      "idle_gaps": idle_gaps(twd, ops, args.top, ck["offset_s"], batches), "h2d": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
