"""Op-level profile of the serving hot path on the current backend.

Productizes the workflow that drove round-5's optimization (space-to-depth
stems, s2d handshake, parallel-fixpoint NMS — each found by reading this
table on a live v5e): build an engine, run the serve computation scan-
amortized under ``jax.profiler``, convert the xplane trace with xprof, and
print device ops ranked by self-time. The same command works on CPU (for
smoke/CI) and TPU (for real numbers).

    python tools/profile_serve.py --model native:inception_v3 --batch 32
    python tools/profile_serve.py --model native:ssd_mobilenet --canvas 304
    python tools/profile_serve.py --server http://host:8500   # live stage table

``--server`` skips the local engine entirely: it reads a LIVE server's
request-span aggregates (/stats "tracing") and prints the per-stage
attribution table — the request-path complement to the device op table
(decode vs queue vs staging vs device vs postprocess), with no profiler
attached and no traffic interrupted.

Interpretation notes: wall-time per batch includes one dispatch and one
scalar fetch over the host's PCIe, amortized over --scan-batches; the
"device busy" total is the honest compute number. A large wall-vs-busy gap
at high K means per-iteration idle (loop sync, slice feeds), not compute.

On a CPU backend the wall number still prints, but jax's CPU profiler may
emit no per-op device rows (observed on jax 0.9 single-core hosts) — the
tool says so instead of showing an empty table. The op table is the TPU
feature.
"""

from __future__ import annotations

import argparse
import glob
import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def capture(model: str, batch: int, canvas: int, wire: str, resize: str, k: int, trace_dir: str):
    """Compile + run the scan-amortized serve once, then re-run under the
    profiler. Returns (wall seconds per batch, effective batch, n_devices).
    The scanned computation comes from ``bench.make_scan_serve`` — the
    profiled program IS the benchmarked one, by construction."""
    import jax
    import jax.numpy as jnp

    from bench import _stacked_inputs, make_engine, make_scan_serve
    from tensorflow_web_deploy_tpu.utils.env import enable_compilation_cache

    enable_compilation_cache()
    n_dev = len(jax.devices())
    batch = max(batch, n_dev) // n_dev * n_dev  # shard evenly, like bench.py
    engine, _ = make_engine(model, batch, canvas, wire, resize, n_dev)
    canv, hws = _stacked_inputs(engine, batch, canvas, k)
    scan_serve = make_scan_serve(engine, canv, hws)

    float(scan_serve(engine._params, canv, hws, jnp.float32(0)))  # compile
    t0 = time.perf_counter()
    float(scan_serve(engine._params, canv, hws, jnp.float32(1)))
    wall = (time.perf_counter() - t0) / k

    jax.profiler.start_trace(trace_dir)
    float(scan_serve(engine._params, canv, hws, jnp.float32(2)))
    jax.profiler.stop_trace()
    return wall, batch, n_dev


def op_table(trace_dir: str, k: int, n_dev: int, top: int):
    """Parse the xplane trace into (busy_s_per_batch_per_device, rows).

    framework_op_stats sums self-time over ALL device cores, so the total
    is divided by ``n_dev`` — per-device busy wall-time (assumes the mesh
    is balanced, which batch-sharding over 'data' makes true)."""
    from xprof.convert import raw_to_tool_data as rtd

    files = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    if not files:
        raise FileNotFoundError(f"no xplane trace under {trace_dir}")
    data, _ = rtd.xspace_to_tool_data(files, "framework_op_stats", {})
    if data is None:
        raise RuntimeError(
            "xprof could not convert the trace (corrupt/partial xplane.pb "
            f"or xprof/jax version skew); raw files kept under {trace_dir}"
        )
    parsed = json.loads(data if isinstance(data, str) else data.decode())
    rows = parsed[0]["rows"] if isinstance(parsed, list) else parsed["rows"]
    ops = []
    for r in rows:
        c = [x["v"] if isinstance(x, dict) else x for x in r["c"]]
        if c[1] == "Device":
            # (self_time_us, op_type, op_name, occurrences)
            ops.append((float(c[7]), str(c[2]), str(c[3]), int(c[4])))
    ops.sort(reverse=True)
    total = sum(o[0] for o in ops) / 1e6 / k / n_dev
    return total, ops[:top]


def server_stage_table(base_url: str) -> int:
    """Print a live server's per-stage span attribution plus its device-
    economics roofline table (see module doc). Both read /stats — no
    profiler attached, no traffic interrupted — and the economics rows
    are the SAME live block bench.py's http sections print, rendered by
    the same formatter, so the two tools cannot diverge on methodology."""
    from tools.loadgen import (
        fetch_stats, format_econ_table, format_stage_table,
        stage_attribution,
    )

    stats = fetch_stats(base_url.rstrip("/") + "/predict")
    if stats is None:
        print(f"could not fetch /stats from {base_url}", file=sys.stderr)
        return 1
    tracing = stats.get("tracing")
    attr = stage_attribution(None, tracing)
    print(f"# {base_url} — request-span stage attribution (since server start)")
    print(format_stage_table(attr))
    by_status = (tracing or {}).get("requests_by_status", {})
    if by_status:
        print("requests by status: "
              + ", ".join(f"{k}={v}" for k, v in sorted(by_status.items())))
    # Roofline attribution from the live economics block: per-(model,
    # replica, canvas, batch-bucket) MFU, arithmetic intensity, the
    # binding roofline side + achieved fraction, and padding waste.
    print("\n# device economics (live /stats 'economics' block)")
    print(format_econ_table(stats.get("economics")))
    return 0


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--server", default=None, metavar="URL",
                   help="read a live server's /stats span aggregates and "
                        "print its stage-attribution table (no local engine)")
    p.add_argument("--model", default="native:inception_v3")
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--canvas", type=int, default=300)
    p.add_argument("--wire", default="yuv420", choices=["rgb", "yuv420"])
    p.add_argument("--resize", default="matmul", choices=["matmul", "gather", "pallas"])
    p.add_argument("--scan-batches", type=int, default=16)
    p.add_argument("--top", type=int, default=25)
    p.add_argument("--trace-dir", default=None, help="keep the raw trace here")
    args = p.parse_args()

    if args.server:
        sys.exit(server_stage_table(args.server))

    trace_dir = args.trace_dir or tempfile.mkdtemp(prefix="serve_trace_")
    wall, batch, n_dev = capture(
        args.model, args.batch, args.canvas, args.wire, args.resize,
        args.scan_batches, trace_dir,
    )
    busy, ops = op_table(trace_dir, args.scan_batches, n_dev, args.top)

    k = args.scan_batches
    print(f"# {args.model} batch={batch} canvas={args.canvas} "
          f"wire={args.wire} resize={args.resize} scan_k={k} n_dev={n_dev}")
    print(f"wall: {wall * 1e3:.2f} ms/batch   device busy: {busy * 1e3:.2f} "
          f"ms/batch/device   (gap = dispatch/k + per-iteration idle)")
    if not ops:
        print("(no per-op device rows in the trace — jax's CPU profiler can "
              "emit none; run on TPU for the op table)")
    print(f"{'ms/batch':>9}  {'occ':>5}  {'type':<22} name   (per device)")
    for self_us, typ, name, occ in ops:
        print(f"{self_us / 1e3 / k / n_dev:9.3f}  {occ:>5}  {typ:<22} {name[-90:]}")
    print(f"\ntrace kept at: {trace_dir}" if args.trace_dir else
          f"\n(trace in {trace_dir}; pass --trace-dir to keep it elsewhere)")


if __name__ == "__main__":
    main()
