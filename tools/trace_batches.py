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
- ``batches``: where ``--stats`` gives ``/stats -> profile``, each batch
  whose outputs were computed inside the recording, with the phases of its
  flight as the program stamped them (``h2d_ms``, ``device_queue_ms``,
  ``device_ms``, ``d2h_ms``; ``late`` names an upper bound) and the device
  programs it ran: every ``XLA Modules`` event is joined to the batch whose
  ``t_ready`` is the first at or after its end, on the ``twd.clock`` offset
  (a device runs its calls in order, and ``t_ready`` is stamped by a thread
  that was waiting for that call). ``stamp_lag_ms`` is how long after its
  last program's end the stamp came; ``unjoined`` counts the programs that
  end after the last stamp of the recording.

The benchmark's ``xplane.py`` names a gap by whichever host event covers most
of it; this tool looks at ``twd.`` events alone and keeps their stats.
"""

from __future__ import annotations

import argparse
import bisect
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark.xplane import MODULES_LINE, OPS_LINE, union  # noqa: E402

# A program's end on the device and the stamp of its outputs on the host lie
# on one clock; the stamp comes after the end, give or take the planes' skew.
STAMP_SLACK_S = 5e-4


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


def join(modules, offset_s: float | None, batches: list[dict]) -> tuple[list[dict], int]:
    """(the batches whose ``t_ready`` lies in the recording, each with the
    programs joined to it; how many programs joined none)."""
    if offset_s is None:
        return [], 0
    stamped = sorted(((b["t_ready"] - offset_s, b) for b in batches if b.get("t_ready") is not None),
                     key=lambda x: x[0])
    lo = modules[0][0] if modules else float("-inf")
    rows = {b["seq"]: _phases(b) for at, b in stamped if at >= lo}
    ends = [at for at, _ in stamped]
    unjoined = 0
    for s, e, name in modules:
        i = bisect.bisect_left(ends, e - STAMP_SLACK_S)
        if i == len(ends):
            unjoined += 1
            continue
        at, b = stamped[i]
        row = rows.get(b["seq"])
        if row is None:
            continue
        row["programs"].append({"name": name, "start_s": round(s, 6), "ms": round(1e3 * (e - s), 3)})
        row["stamp_lag_ms"] = round(1e3 * (at - e), 3)
    return list(rows.values()), unjoined


def _phases(b: dict) -> dict:
    """A batch record's flight as the program stamped it, in ms."""
    def ms(a, z):
        return None if b.get(z) is None else round(1e3 * (b[z] - b[a]), 3)

    return {"seq": b["seq"], "rows": b.get("rows"), "h2d_ms": ms("t_launch", "t_h2d_done"),
            "device_queue_ms": ms("t_h2d_done", "t_dev_start"), "device_ms": ms("t_dev_start", "t_ready"),
            "d2h_ms": ms("t_ready", "t_done"), "late": list(b.get("late") or ()), "programs": [],
            "stamp_lag_ms": None}


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
    rows, unjoined = join(modules, ck["offset_s"], batches)
    print(json.dumps({"clock": ck, "twd_events": len(twd), "device_ops": len(ops),
                      "idle_gaps": idle_gaps(twd, ops, args.top, ck["offset_s"], batches),
                      "batches": rows, "unjoined": unjoined}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
