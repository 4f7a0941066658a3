#!/usr/bin/env python3
"""Finding a cell's knee and its failures, on the chip, in one boot.

Not part of a check's runs: the tool a ``benchmark`` PR uses when it fixes a
rate in a traffic file or looks for the cause of a failed request.

    python3 benchmark/probe.py --workload <cell> --seed <n> --out <dir>
        [--rates 60,80,100 --step-seconds 20]     open-loop steps, one table row each
        [--long-seconds 100 --long-seeds 3]       long windows at --rate or 4/5 of the knee found
        [--old-loadgen-seconds 60]                tools/loadgen.py --rate as it stands, for comparison
        [--trace-seconds 8]                       one traced window; the .xplane.pb is kept in --out
        [--controls int8,fp8]                     the reference's 8-bit controls over the last window's sample
        [--serve-dtype int8]                      the program's own lower tier in the stated one's place: a control

Once the server has gone, every long window's own answers go through
the configuration's check child under its limits, one line each.

Every operation that did not end in a correct 200 goes to
``<out>/non_200.jsonl`` with its due and send times, status, reason,
exception, the connection's age and request count, and ``X-Trace-Id``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import subprocess
import sys
import time
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark import loadgen, run as R  # noqa: E402
from benchmark.manifest import ROOT, load_cell, load_manifest, named  # noqa: E402


def summarise(w, mix, seconds: float) -> dict:
    ok = [o for o in w.outcomes if o.answers is not None]
    lat = sorted(o.latency_s * 1e3 for o in w.outcomes)
    thirds = [sorted(o.latency_s * 1e3 for o in w.outcomes
                     if lo <= (o.sent if o.due is None else o.due) < lo + seconds / 3)
              for lo in (0.0, seconds / 3, 2 * seconds / 3)]
    late = [o for o in w.outcomes if o.due is not None and o.sent - o.due > loadgen.LATE_S]
    return {"attempted": len(w.outcomes), "failed": len(w.outcomes) - len(ok),
            "images_per_s": sum(o.images for o in ok) / max([seconds, *(o.done for o in w.outcomes)]),
            "p50_ms": loadgen.percentile(lat, 50), "p95_ms": loadgen.percentile(lat, 95),
            "p99_ms": loadgen.percentile(lat, 99), "max_ms": lat[-1] if lat else None,
            "p50_by_third_ms": [loadgen.percentile(t, 50) for t in thirds],
            "late_share": len(late) / max(1, len(w.outcomes)),
            "connections_opened": w.result.connections_opened}


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--out", default=str(ROOT / "chiprun_out" / "probe"))
    p.add_argument("--rates", default="")
    p.add_argument("--step-seconds", type=float, default=20.0)
    p.add_argument("--rate", type=float, default=0.0)
    p.add_argument("--long-seconds", type=float, default=0.0)
    p.add_argument("--long-seeds", type=int, default=3)
    p.add_argument("--old-loadgen-seconds", type=float, default=0.0)
    p.add_argument("--trace-seconds", type=float, default=0.0)
    p.add_argument("--controls", default="")
    p.add_argument("--serve-dtype", default=None)
    p.add_argument("--allow-cpu", action="store_true", help="rehearsal: numbers mean nothing")
    p.add_argument("--bench-dir", default=None, help="rehearsal: a benchmark directory of tiny files, "
                   "with its BENCHMARK.json beside it")
    args = p.parse_args(argv)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    if args.bench_dir:
        bench = Path(args.bench_dir).resolve()
        cell = load_cell(args.workload, load_manifest(bench.parent / "BENCHMARK.json"), bench)
    else:
        cell = load_cell(args.workload)
    note = lambda **kv: print(json.dumps(kv), flush=True)
    t0 = time.monotonic()
    b = R.boot(cell, args.seed, serve_dtype=args.serve_dtype)
    senders = int(cell.config["http_workers"])
    windows = []
    try:
        note(booted=args.workload, boot_s=round(b.boot_s, 1), device=b.device,
             aot=b.stats_boot.get("aot_cache"), setup_s=round(time.monotonic() - t0, 1))
        if b.device["platform"] != "tpu" and not args.allow_cpu:
            return 3
        knee = 0.0
        for rate in [float(r) for r in args.rates.split(",") if r]:
            mix = dataclasses.replace(b.mix, loop="open", rate_per_s=rate)
            w = R.measure(b.server, mix, b.source, args.seed, args.step_seconds, senders, b.model)
            s = summarise(w, mix, args.step_seconds)
            first, _, third = s["p50_by_third_ms"]
            steady = (s["failed"] == 0 and s["late_share"] < 0.05 and first and third
                      and third < 1.5 * first + 5.0)
            if steady:
                knee = max(knee, rate)
            note(sweep_rate=rate, steady=bool(steady), **s)
        rate = args.rate or 0.8 * knee
        note(knee=knee, rate_for_long_windows=rate)
        with open(out / "non_200.jsonl", "a") as log:
            for i in range(args.long_seeds if args.long_seconds else 0):
                seed = args.seed + 1000 * (i + 1)
                mix = b.mix if b.mix.loop == "closed" else dataclasses.replace(b.mix, rate_per_s=rate)
                w = R.measure(b.server, mix, b.source, seed, args.long_seconds, senders, b.model)
                windows.append((w, seed))
                s = summarise(w, mix, args.long_seconds)
                bad = R.failure_log(w.outcomes)
                for row in bad:
                    log.write(json.dumps({"window": i, "seed": seed, **row}) + "\n")
                slow = b.server.get("/debug/slow") if bad else None
                if slow is not None:
                    (out / f"debug_slow_{i}.json").write_text(json.dumps(slow))
                delta = lambda k: w.after["cache"][k] - w.before["cache"][k]
                note(long_window=i, seed=seed, rate=mix.rate_per_s, cache_hits=delta("hits_total"),
                     cache_misses=delta("misses_total"), **s)
        if args.old_loadgen_seconds:
            cmd = [sys.executable, str(ROOT / "tools" / "loadgen.py"), "--url",
                   f"http://127.0.0.1:{b.server.port}/predict", "--rate", str(rate or 100),
                   "--duration", str(args.old_loadgen_seconds)]
            proc = subprocess.run(cmd, cwd=ROOT, env=R.child_env(), capture_output=True, timeout=600)
            (out / "old_loadgen.txt").write_bytes(proc.stdout[-20000:] + b"\n---stderr---\n" + proc.stderr[-8000:])
            note(old_loadgen_exit=proc.returncode, stdout_tail=proc.stdout.decode(errors="replace")[-2500:])
        if args.trace_seconds:
            trace_dir = b.work / "trace"
            shutil.rmtree(trace_dir, ignore_errors=True)
            mix = b.mix if b.mix.loop == "closed" else dataclasses.replace(b.mix, rate_per_s=rate or b.mix.rate_per_s)
            w = R.measure(b.server, mix, b.source, args.seed + 7, args.trace_seconds, senders, b.model, trace_dir)
            windows.append((w, args.seed + 7))
            files = list(trace_dir.rglob("*.xplane.pb"))
            note(trace_status=w.result.trace_status, files=[(str(f), f.stat().st_size) for f in files],
                 **summarise(w, mix, args.trace_seconds))
            for f in files:
                if f.stat().st_size < 48 << 20:
                    shutil.copy(f, out / f"{args.workload}.xplane.pb")
            (out / f"{args.workload}.stats_before.json").write_text(json.dumps(w.before))
            (out / f"{args.workload}.stats_after.json").write_text(json.dumps(w.after))
        shutil.copy(b.work / "server.log", out / f"{args.workload}.server.log")
    finally:
        note(server_exit=b.server.stop())
    for i, (w, seed) in enumerate(windows):
        sample = R.draw_sample(w.outcomes, b.source.requests, seed, named(cell.config).sample_images)
        controls = [c for c in args.controls.split(",") if c] if i == len(windows) - 1 else []
        for control in [None, *controls]:
            t = time.monotonic()
            # the weights are the boot seed's, whatever seed drew the traffic
            check = R.run_check(cell, args.seed, sample, b.source.requests, control)
            note(check_window=i, seed=seed, serve_dtype=args.serve_dtype, check_control=control,
                 seconds=round(time.monotonic() - t, 1), **check)
    return 0


if __name__ == "__main__":
    sys.exit(main())
