#!/usr/bin/env python
"""Driver benchmark entry point.

Measures the flagship north-star metric (BASELINE.json): Inception-v3
images/sec through the full serving path — on-device resize + normalize
(ops.image), bfloat16 forward on the MXU, on-device top-k — with the
dispatch/fetch overlap the batcher uses in production.

Prints exactly ONE JSON line on stdout:
    {"metric": ..., "value": N, "unit": "images/sec", "vs_baseline": N, ...}
All human-readable progress goes to stderr.

The JSON is self-describing about its substrate: ``backend`` is the JAX
backend actually used, ``flops_per_image`` is the analytic XLA cost of the
compiled serving program, and ``mfu`` is achieved/peak bf16 FLOP/s against
the one peak table in ``serving/costmodel.py``. ``python bench.py`` (no
block argument) measures a TPU and fails without one — a CPU timing is
never printed under these metric names. The ``python bench.py <block>``
mains force an 8-device virtual CPU mesh and say ``backend: cpu``.

``vs_baseline`` compares against the reference serving path (frozen-graph
Inception-v3 executed by TensorFlow). The reference repo publishes no
numbers (SURVEY.md §6) and this environment has no GPU, so the baseline is
a *measured* TF-on-CPU number, labeled as such. Set BENCH_REF=live to
re-measure it in-process instead of using the stored figure.

Measurement methodology: the device-resident number runs the serve
computation K times inside ONE dispatch (``lax.scan`` over K distinct
on-device batches, plus a per-call salt) and forces it with a scalar
fetch, so dispatch and fetch cost is paid once per K batches; the e2e
number ships distinct host buffers and fetches every batch's outputs (real
transfers + real executions by construction). The scan×64 depth and the
salt were sized against a remote-device link that no longer exists; what
a dispatch costs on a chip on the host's own PCIe, and so whether the
scan is still needed, is not measured on a directly attached chip
(ROADMAP D1).

Env knobs: BENCH_MODEL (default native:inception_v3), BENCH_BATCH (32),
BENCH_ITERS (20), BENCH_WIRE (yuv420|rgb, default yuv420),
BENCH_RESIZE (matmul|gather|pallas, default matmul), BENCH_CANVAS
(default 300 for yuv420 / 299 for rgb), BENCH_DEPTH (4, in-flight batches),
BENCH_SCAN_BATCHES (64), BENCH_HTTP (1; 0 disables), BENCH_HTTP_SECS (8),
BENCH_THROUGHPUT_BATCH (256; 0 disables the throughput-mode sub-bench),
BENCH_HTTP_BATCH (8 files/request for the batch-client HTTP run; ≤1 off),
BENCH_HOT_SWAP (1; error rate + p99 through a live model hot-swap),
BENCH_CACHE (1; response-cache goodput at Zipf traffic vs --cache-bytes 0,
coalesce count, zero-stale hot-swap — ``python bench.py cache`` runs ONLY
this block on a forced 8-device virtual CPU mesh), BENCH_CACHE_MODEL
(native:mobilenet_v2), BENCH_CACHE_CORPUS (32), BENCH_CACHE_ZIPF (1.1),
BENCH_BULK (1; bulk-job img/s vs interactive open-loop + the isolation
p99 pair + restart-resume zero-lost proof — ``python bench.py bulk``
runs ONLY this block on a forced 8-device virtual CPU mesh),
BENCH_BULK_MODEL (native:mobilenet_v2), BENCH_BULK_BATCH (256),
BENCH_BULK_IMAGES (1024), BENCH_BULK_CORPUS (48),
BENCH_CONVERTER (1; frozen-.pb path sub-bench), BENCH_CONVERTER_CONFIGS
(default inception_v3,mobilenet_v2,resnet50,ssd_mobilenet — one
converter-path row per preset), BENCH_CONFIGS
(default mobilenet_v2,resnet50,ssd_mobilenet; "" disables),
BENCH_PREPROCESS (1; matmul-vs-pallas resize timing),
BENCH_MESH_SCALING (1; HTTP open-loop img/s at placement replicas=1→2→4→8
— needs ≥2 devices; ``python bench.py mesh_scaling`` runs ONLY this block
on a forced 8-device virtual CPU mesh), BENCH_MESH_MODEL
(native:mobilenet_v2), BENCH_MESH_WIDTH (0.35),
BENCH_RAW_SECS (3; ``python bench.py raw_speed`` runs ONLY the quantized
raw-speed-tier block — per-(preset, dtype) img/s + roofline fractions +
the fused depthwise A/B), BENCH_RAW_PRESETS, BENCH_RAW_DTYPES
(float32,bfloat16,int8), BENCH_RAW_WIDTH (0.35), BENCH_RAW_SIZE (96),
BENCH_RAW_BATCH (8),
BENCH_DAG_SECS (6; ``python bench.py pipeline_dag`` runs ONLY the
pipeline-DAG block — device-resident detect→crop→classify via ONE
POST /pipelines/{name} vs the client-side two-request composition, e2e
img/s + p99 + D2H bytes/image + golden parity vs the stage-by-stage host
reference), BENCH_DAG_CORPUS (24), BENCH_DAG_IMAGE_PX (768),
BENCH_BUDGET_S (1500; optional sections are skipped past this),
BENCH_REF (stored|live).
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

import numpy as np

# Reference path measured 2026-07-29 on this machine: tf.keras InceptionV3
# frozen-style concrete function, batch 8, CPU (no GPU in the image).
# SURVEY.md §6: the honest substrate label matters — this is TF-CPU, not
# TF-GPU; the ≥4× north-star target was written against TF-GPU.
STORED_REF = {"images_per_sec": 10.28, "substrate": "tf-cpu-batch8"}

def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def measure_ref_live() -> float:
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
    os.environ.setdefault("CUDA_VISIBLE_DEVICES", "-1")
    import tensorflow as tf

    tf.keras.utils.set_random_seed(3)
    m = tf.keras.applications.InceptionV3(weights=None, input_shape=(299, 299, 3))
    b = 8
    cf = tf.function(lambda x: m(x)).get_concrete_function(
        tf.TensorSpec([b, 299, 299, 3], tf.float32)
    )
    x = tf.constant(np.random.rand(b, 299, 299, 3).astype(np.float32))
    for _ in range(2):
        cf(x).numpy()
    iters = 5
    t0 = time.perf_counter()
    for _ in range(iters):
        cf(x).numpy()
    return b * iters / (time.perf_counter() - t0)


# -------------------------------------------------------------------- cost


def analyze_cost(engine, batch, canvas) -> dict:
    """Analytic per-image FLOPs (+ bytes) of the compiled serving program.

    ``cost_analysis`` needs no hardware counters — XLA reports the static
    FLOP/byte cost of the executable on any backend. Under a sharded jit
    the numbers are per-device; multiplying by device count restores the
    whole-batch cost (the batch axis is sharded over 'data'). The
    per-device semantics are verified against a known-FLOP matmul, and
    pinned by tests/test_cost_analysis.py so a jax upgrade cannot silently
    flip them.
    """
    import jax

    try:
        if engine.cfg.packed_io:
            args = (jax.ShapeDtypeStruct(engine.packed_shape(batch, canvas),
                                         np.uint8, sharding=engine._data_sharding),)
        else:
            args = (
                jax.ShapeDtypeStruct(engine.canvas_shape(batch, canvas), np.uint8,
                                     sharding=engine._data_sharding),
                jax.ShapeDtypeStruct((batch, 2), np.int32,
                                     sharding=engine._data_sharding),
            )
        compiled = engine._serve.lower(engine._params, *args).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        n_dev = len(jax.devices())
        flops = float(ca.get("flops", 0.0)) * n_dev
        out = {"flops_per_image": round(flops / batch) if flops else None}
        bytes_accessed = float(ca.get("bytes accessed", 0.0)) * n_dev
        if bytes_accessed:
            out["hbm_bytes_per_image"] = round(bytes_accessed / batch)
        return out
    except Exception as e:  # cost_analysis is best-effort diagnostics
        log(f"cost_analysis unavailable: {e}")
        return {"flops_per_image": None}


# ------------------------------------------------------------ measurement


def make_engine(model_name, batch, canvas, wire, resize, n_dev):
    from tensorflow_web_deploy_tpu.serving.engine import InferenceEngine
    from tensorflow_web_deploy_tpu.utils.config import ServerConfig, model_config

    cfg = ServerConfig(
        model=model_config(model_name),
        max_batch=batch,
        canvas_buckets=(canvas,),
        batch_buckets=(n_dev, batch) if batch > n_dev else (batch,),
        wire_format=wire,
        resize=resize,
        warmup=False,
    )
    return InferenceEngine(cfg), cfg


def _stacked_inputs(engine, batch, canvas, k, seed=0):
    """K distinct uint8 canvas batches generated ON the device (no host
    shipping), sharded so the inner batch axis lands on the mesh."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    shape = engine.canvas_shape(batch, canvas)

    @jax.jit
    def gen(key):
        keys = jax.random.split(key, k)
        return jax.vmap(
            lambda kk: jax.random.randint(kk, shape, 0, 256, jnp.uint8)
        )(keys)

    spec = engine._data_sharding.spec
    stack_c = NamedSharding(engine.mesh, P(None, *spec))
    canv = jax.device_put(gen(jax.random.PRNGKey(seed)), stack_c)
    hws = jax.device_put(
        jnp.full((k, batch, 2), canvas, jnp.int32), stack_c
    )
    return canv, hws


def make_scan_serve(engine, canv, hws):
    """jit'd ``(params, canv, hws, salt) → checksum`` running the serve
    computation over the K stacked batches in ONE dispatch (module
    docstring, "Measurement methodology"). The single definition of the
    device-resident harness — shared by :func:`scan_throughput` and
    tools/profile_serve.py so the profiled computation is exactly the
    benchmarked one."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    serve = engine._serve_raw

    @functools.partial(
        jax.jit,
        in_shardings=(
            engine._replicated,
            canv.sharding,
            hws.sharding,
            NamedSharding(engine.mesh, P()),
        ),
    )
    def scan_serve(params, canv, hws, salt):
        def body(acc, ch):
            outs = serve(params, ch[0], ch[1])
            s = sum(jnp.sum(o.astype(jnp.float32)) for o in jax.tree.leaves(outs))
            return acc + s, None
        acc, _ = lax.scan(body, salt.astype(jnp.float32), (canv, hws))
        return acc

    return scan_serve


def scan_throughput(engine, batch, canvas, k, reps=3):
    """Device-resident images/sec: ONE dispatch scans the serve computation
    over K distinct batches; a scalar fetch forces execution; a per-rep
    salt makes every rep a distinct computation. Returns (ips, compile_s).
    """
    import jax.numpy as jnp

    canv, hws = _stacked_inputs(engine, batch, canvas, k)
    scan_serve = make_scan_serve(engine, canv, hws)

    t0 = time.perf_counter()
    float(scan_serve(engine._params, canv, hws, jnp.float32(0)))
    compile_s = time.perf_counter() - t0
    best = None
    for rep in range(1, reps + 1):
        t0 = time.perf_counter()
        float(scan_serve(engine._params, canv, hws, jnp.float32(rep)))
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return k * batch / best, compile_s


def _feed_buffers(engine, batch, canvas, n, seed):
    """n distinct host canvas buffers — every timed dispatch carries bytes
    no earlier dispatch shipped."""
    rng = np.random.RandomState(seed)
    shape = engine.canvas_shape(batch, canvas)
    return [rng.randint(0, 256, size=shape, dtype=np.uint8) for _ in range(n)]


def _pipelined(dispatch, fetch, feed, iters, depth):
    """Depth-bounded dispatch/fetch pipeline; one distinct buffer per timed
    iteration (feed must hold ≥ iters buffers). Returns elapsed seconds.
    Shared by e2e_pipeline and overlap_check so their numbers differ only in
    the computation, never in the driving scaffold."""
    inflight = []
    t0 = time.perf_counter()
    for i in range(iters):
        inflight.append(dispatch(feed[i]))
        if len(inflight) > depth:
            fetch(inflight.pop(0))
    while inflight:
        fetch(inflight.pop(0))
    return time.perf_counter() - t0


def e2e_pipeline(engine, batch, canvas, iters, depth):
    """Client-visible engine throughput: distinct host buffers shipped per
    dispatch, every batch's outputs fetched. Returns (ips, wire_MBps)."""
    feed = _feed_buffers(engine, batch, canvas, iters + 2, seed=1)
    hws = np.full((batch, 2), canvas, np.int32)
    for b in feed[iters:]:  # warmup on buffers outside the timed set
        engine.run_batch(b, hws)
    dt = _pipelined(
        lambda c: engine.dispatch_batch(c, hws), engine.fetch_outputs,
        feed, iters, depth,
    )
    return batch * iters / dt, iters * feed[0].nbytes / dt / 1e6


def overlap_check(engine, batch, canvas, iters, depth):
    """Is e2e transfer-bound with full overlap? Ship the SAME bytes through a
    near-zero-compute jitted program with the same pipeline depth. If its
    throughput matches the full serve's, the link is saturated and compute is
    fully hidden behind transfer — the architectural best on this link."""
    import jax
    import jax.numpy as jnp

    trivial = jax.jit(
        lambda c, h: (jnp.sum(c, dtype=jnp.int32) + jnp.sum(h)),
        in_shardings=(engine._data_sharding, engine._data_sharding),
    )
    feed = _feed_buffers(engine, batch, canvas, iters + 1, seed=2)
    hws = np.full((batch, 2), canvas, np.int32)

    def dispatch(c):
        cd = jax.device_put(c, engine._data_sharding)
        hd = jax.device_put(hws, engine._data_sharding)
        return trivial(cd, hd)

    int(dispatch(feed[iters]))  # warmup buffer outside the timed set
    dt = _pipelined(dispatch, lambda o: int(o), feed, iters, depth)
    return batch * iters / dt, iters * feed[0].nbytes / dt / 1e6


def batch1_latency(engine, canvas, n_dev, reps=40):
    """Smallest-batch e2e latency over distinct buffers; the warmup buffer
    is extra — never re-timed."""
    b = max(1, n_dev)
    hws = np.full((b, 2), canvas, np.int32)
    bufs = _feed_buffers(engine, b, canvas, reps + 1, seed=3)
    engine.run_batch(bufs[reps], hws)
    lat = []
    for i in range(reps):
        t0 = time.perf_counter()
        engine.run_batch(bufs[i], hws)
        lat.append((time.perf_counter() - t0) * 1e3)
    return b, float(np.percentile(lat, 50)), float(np.percentile(lat, 99))


def _merge_intervals(ivals):
    """Sorted union of (start, end) intervals (empty/inverted ones dropped)."""
    out: list[list[float]] = []
    for a, b in sorted((a, b) for a, b in ivals if b > a):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _intersect_seconds(xs, ys) -> float:
    """Total seconds where two merged interval unions are BOTH active."""
    i = j = 0
    total = 0.0
    while i < len(xs) and j < len(ys):
        a = max(xs[i][0], ys[j][0])
        b = min(xs[i][1], ys[j][1])
        if b > a:
            total += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def pipeline_overlap(timeline) -> dict | None:
    """Decode∥execute overlap from a batcher ``batch_timeline()``.

    Assembly busy = union of per-batch (t_open, t_seal) windows (HTTP
    workers decoding/committing into the builder's slab); execute busy =
    union of (t_launched, t_done) windows (device executing + D2H).
    ``overlap_ratio`` is busy-time(assembly ∥ execute) ÷ wall over the
    records' span — the measured form of "decode of batch N+1 overlaps
    execute of batch N". Zero with pipeline depth 1 and a single client;
    meaningfully positive once the pipeline is real. All stamps share one
    monotonic clock, so no cross-clock skew can corrupt the ratio."""
    recs = [r for r in timeline
            if r.get("t_done") is not None and r.get("t_launched") is not None]
    if not recs:
        return None
    assembly = _merge_intervals([(r["t_open"], r["t_seal"]) for r in recs])
    execute = _merge_intervals([(r["t_launched"], r["t_done"]) for r in recs])
    t0 = min(r["t_open"] for r in recs)
    t1 = max(r["t_done"] for r in recs)
    wall = max(t1 - t0, 1e-9)
    ov = _intersect_seconds(assembly, execute)
    return {
        "batches": len(recs),
        "assembly_busy_s": round(sum(b - a for a, b in assembly), 3),
        "execute_busy_s": round(sum(b - a for a, b in execute), 3),
        "overlap_s": round(ov, 3),
        "wall_s": round(wall, 3),
        "overlap_ratio": round(ov / wall, 3),
    }


def replica_overlap(timeline) -> dict | None:
    """Per-replica execute concurrency from a batcher ``batch_timeline()``
    (records carry the routing decision). For each replica: execute busy
    time, busy fraction of the window, and the fraction of its execute
    time during which AT LEAST ONE OTHER replica was also executing —
    the measured form of "N chips run batches in parallel", and the
    per-replica overlap evidence the mesh_scaling curve rides on."""
    recs = [r for r in timeline
            if r.get("t_done") is not None and r.get("t_launched") is not None]
    if not recs:
        return None
    by_rep: dict[int, list] = {}
    for r in recs:
        by_rep.setdefault(int(r.get("replica", 0)), []).append(
            (r["t_launched"], r["t_done"])
        )
    merged = {k: _merge_intervals(v) for k, v in by_rep.items()}
    t0 = min(a for iv in merged.values() for a, _ in iv)
    t1 = max(b for iv in merged.values() for _, b in iv)
    wall = max(t1 - t0, 1e-9)
    per = {}
    for k in sorted(merged):
        iv = merged[k]
        busy = sum(b - a for a, b in iv)
        others = _merge_intervals(
            [x for kk, vv in merged.items() if kk != k for x in vv]
        )
        ov = _intersect_seconds(iv, others)
        per[str(k)] = {
            "batches": len(by_rep[k]),
            "execute_busy_s": round(busy, 3),
            "busy_fraction": round(busy / wall, 3),
            "overlap_ratio": round(ov / busy, 3) if busy > 0 else None,
        }
    return {"replicas": len(merged), "wall_s": round(wall, 3),
            "per_replica": per}


def mesh_scaling_bench(replica_counts=(1, 2, 4, 8), secs=6.0) -> dict:
    """HTTP open-loop img/s vs replica count — the measured replica-scaling
    curve for mesh-wide serving (BASELINE config 5 made live).

    For each N in ``replica_counts`` the same small model serves with
    placement ``replicas=N`` over the same device set (N=1 degenerates to
    the shard strategy — one program over every chip, the pre-placement
    behavior) behind the real HTTP + batcher stack. Closed-loop probes
    calibrate each config's saturation; the recorded number is open-loop
    completions/sec at an offered rate ABOVE saturation, i.e. sustained
    capacity under open load. ``replica_overlap`` from the batch timeline
    proves the capacity comes from chips executing in parallel, not noise.

    On the virtual CPU mesh the chips share physical cores, so the curve
    measures what replication removes — the per-replica XLA:CPU dispatch
    serialization guard (a whole-mesh program serializes every launch) and
    the per-batch partition/collective overhead of sharding tiny batches
    8 ways — rather than added FLOPs. On real v5e-8 the same placement
    multiplies actual compute.
    """
    import dataclasses
    import threading

    import jax

    from tensorflow_web_deploy_tpu.serving.batcher import Batcher
    from tensorflow_web_deploy_tpu.serving.engine import InferenceEngine
    from tensorflow_web_deploy_tpu.serving.http import (
        App, make_http_server, shutdown_gracefully,
    )
    from tensorflow_web_deploy_tpu.utils.config import ServerConfig, model_config
    from tools.loadgen import (
        Recorder, closed_loop, open_loop, percentile, synthetic_jpegs,
    )

    n_dev = len(jax.devices())
    counts = [n for n in replica_counts if n <= n_dev and n_dev % n == 0]
    if len(counts) < 2:
        return {"skipped": f"needs >=2 viable replica counts on {n_dev} devices"}

    model_spec = os.environ.get("BENCH_MESH_MODEL", "native:mobilenet_v2")
    mc0 = model_config(model_spec)
    # Scaling bench wants the ROUTING layer hot, not a flagship model: on
    # the virtual CPU mesh every "chip" shares the same physical cores, so
    # total FLOP/s is a constant and what replication buys is the removal
    # of per-dispatch costs — the whole-mesh program's partition/collective
    # overhead and its serialization guard. A thin-width small-input
    # variant makes those costs the dominant term (measured: width 0.35 @
    # 32px scales 299→498 img/s over 1→8 replicas at the dispatch level,
    # while width 0.5 @ 96px is compute-bound and flat) and keeps
    # per-config warmup (which compiles every replica) in seconds.
    mc0.zoo_width = float(os.environ.get("BENCH_MESH_WIDTH", "0.35"))
    mc0.zoo_classes = 101
    mc0.input_size = (24, 24)
    mc0.dtype = "float32"
    canvas = 64
    # size >= 192: synthetic_jpegs shrinks alternate images by up to 128px
    # on a side; small-ish JPEGs keep host decode off the critical path so
    # the curve measures dispatch routing, not libjpeg.
    images = synthetic_jpegs(n=6, size=192)
    workers = int(os.environ.get("BENCH_HTTP_WORKERS", "24"))
    fpr = 8  # files/request: amortize HTTP framing so routing is the knob

    curve = []
    for n in counts:
        mc = dataclasses.replace(mc0)
        mc.placement = f"replicas={n}" if n > 1 else "shard=batch"
        cfg = ServerConfig(
            model=mc, canvas_buckets=(canvas,), batch_buckets=(8,),
            max_batch=8, max_delay_ms=2.0, warmup=True, http_workers=workers,
        )
        t0 = time.perf_counter()
        engine = InferenceEngine(cfg)
        engine.warmup()
        batcher = Batcher(engine, max_batch=engine.max_batch,
                          max_delay_ms=cfg.max_delay_ms,
                          name=f"mesh-r{n}")
        batcher.start()
        app = App(engine, batcher, cfg)
        srv = make_http_server(app, "127.0.0.1", 0, pool_size=workers)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        url = f"http://127.0.0.1:{srv.server_address[1]}/predict"
        log(f"mesh_scaling replicas={n}: engine+warmup "
            f"{time.perf_counter() - t0:.1f}s")
        try:
            # Calibrate: short closed loops at saturation; best of two
            # windows so a GC/scheduler hiccup cannot fake a regression in
            # the curve.
            closed_ips = 0.0
            probe_s = min(3.0, secs / 2)
            for _ in range(2):
                rec_c = Recorder()
                t0c = time.perf_counter()
                closed_loop(url, images, workers, probe_s, 60.0, rec_c,
                            files_per_request=fpr)
                closed_ips = max(
                    closed_ips,
                    rec_c.images_completed_by(t0c + probe_s) / probe_s,
                )
            # Open loop offered ABOVE saturation: completions/sec ==
            # sustained capacity under open load (arrivals keep coming
            # whether or not responses do — no coordinated omission).
            rate = max(20.0, closed_ips * 1.15) / fpr
            open_ips, errors, lat = 0.0, 0, []
            seq0 = max((r["seq"] for r in batcher.batch_timeline()), default=0)
            for _ in range(2):
                rec_o = Recorder()
                t0o = time.perf_counter()
                open_loop(url, images, rate, secs, 60.0, rec_o,
                          files_per_request=fpr)
                window_ips = rec_o.images_completed_by(t0o + secs) / secs
                with rec_o.lock:
                    w_lat = sorted(rec_o.latencies_ms)
                    w_errors = rec_o.errors
                errors += w_errors
                if window_ips >= open_ips:
                    open_ips, lat = window_ips, w_lat
            ov = replica_overlap(
                [r for r in batcher.batch_timeline() if r["seq"] > seq0]
            )
            entry = {
                "replicas": n,
                "placement": engine.placement.spec,
                "devices_per_replica": n_dev // n,
                "closed_loop_images_per_sec": round(closed_ips, 1),
                "open_loop_images_per_sec": round(open_ips, 1),
                "offered_images_per_sec": round(rate * fpr, 1),
                "errors": errors,
                "latency_ms_p50": round(percentile(lat, 50), 1) if lat else None,
                "replica_overlap": ov,
            }
            curve.append(entry)
            log(f"mesh_scaling replicas={n}: {entry}")
        finally:
            shutdown_gracefully(srv, batcher, grace_s=5.0)
            engine.close()
            del engine
    ips = [c["open_loop_images_per_sec"] for c in curve]
    return {
        "model": model_spec,
        "width": mc0.zoo_width,
        "canvas": canvas,
        "files_per_request": fpr,
        "secs_per_config": secs,
        "n_devices": n_dev,
        "curve": curve,
        "monotonic_1_to_max": all(b >= a for a, b in zip(ips, ips[1:])),
        "speedup_max_over_1": round(ips[-1] / ips[0], 2) if ips[0] else None,
    }


def overload_bench(secs=5.0) -> dict:
    """Standalone offered-load-vs-goodput curve (``python bench.py
    overload``): a thin-model server on the virtual mesh, closed-loop
    calibration, then an open-loop sweep stepping offered load to 2× past
    saturation — the goodput curve ROADMAP item 1 asks for, with the live
    /stats economics block attached so the overload numbers carry their
    MFU/padding context."""
    import threading

    import jax

    from tensorflow_web_deploy_tpu.serving.batcher import Batcher
    from tensorflow_web_deploy_tpu.serving.engine import InferenceEngine
    from tensorflow_web_deploy_tpu.serving.http import (
        App, make_http_server, shutdown_gracefully,
    )
    from tensorflow_web_deploy_tpu.utils.config import ServerConfig, model_config
    from tools.loadgen import (
        Recorder, closed_loop, fetch_stats, format_econ_table,
        format_sweep_table, open_loop, percentile, sweep_curve,
        sweep_summary, synthetic_jpegs,
    )

    model_spec = os.environ.get("BENCH_OVERLOAD_MODEL", "native:mobilenet_v2")
    mc = model_config(model_spec)
    mc.zoo_width = float(os.environ.get("BENCH_MESH_WIDTH", "0.35"))
    mc.zoo_classes = 101
    mc.input_size = (24, 24)
    mc.dtype = "float32"
    n_dev = len(jax.devices())
    if jax.default_backend() == "cpu" and n_dev > 1:
        mc.placement = f"replicas={n_dev}"
    workers = int(os.environ.get("BENCH_HTTP_WORKERS", "24"))
    # The multi-tenant isolation row's offender budget (images/s): the
    # offender offers 4× this and must be quota-shed down to it, leaving
    # the (unlimited) victim's p99 nearly untouched.
    off_quota = float(os.environ.get("BENCH_OFFENDER_QUOTA", "32"))
    # Batch bucket 8, NOT larger: at this bench's arrival pattern a
    # 16-row bucket never fills (measured 48% padded rows and HALF the
    # goodput) — the interactive operating point wants the small bucket.
    ob_batch = int(os.environ.get("BENCH_OVERLOAD_BATCH", "8"))
    cfg = ServerConfig(
        model=mc, canvas_buckets=(64,), batch_buckets=(ob_batch,),
        max_batch=ob_batch,
        max_delay_ms=2.0, warmup=True, http_workers=workers,
        # A bounded queue is the overload-engineering operating point: the
        # sweep's past-saturation steps should show fast 503 shedding, not
        # timeouts. SIZED TO THE DEADLINE: 128 images drain in ~0.4 s at
        # this mesh's ~350 img/s, leaving device time inside the 1 s
        # interactive budget. A 256 queue measured pathological — its
        # 0.73 s drain put every admitted request's completion a hair past
        # the deadline, so rows ran on device and STILL answered 504.
        max_queue=int(os.environ.get("BENCH_OVERLOAD_QUEUE", "128")),
        tenant_quota=f"offender={off_quota:g}",
    )
    t0 = time.perf_counter()
    engine = InferenceEngine(cfg)
    engine.warmup()
    batcher = Batcher(engine, max_batch=engine.max_batch,
                      max_delay_ms=cfg.max_delay_ms, max_queue=cfg.max_queue,
                      name="overload")
    batcher.start()
    app = App(engine, batcher, cfg)
    srv = make_http_server(app, "127.0.0.1", 0, pool_size=workers)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{srv.server_address[1]}/predict"
    images = synthetic_jpegs(n=6, size=192)
    fpr = 8
    log(f"overload bench server ready in {time.perf_counter() - t0:.1f}s")
    try:
        closed_loop(url, images, 8, min(3.0, secs), 60.0, Recorder(),
                    files_per_request=fpr)  # warm
        probe_s = min(3.0, secs)
        rec_c = Recorder()
        t0c = time.perf_counter()
        closed_loop(url, images, workers, probe_s, 60.0, rec_c,
                    files_per_request=fpr)
        closed_ips = rec_c.images_completed_by(t0c + probe_s) / probe_s
        base_rps = max(2.0, closed_ips) / fpr
        # Sweep traffic names its SLO class: past saturation, requests that
        # cannot meet the interactive deadline are shed 504 BEFORE device
        # time, so the admitted p99 stays deadline-bounded and goodput is
        # spent on requests that are still worth serving.
        steps = sweep_curve(
            url, images, [base_rps * f for f in (0.5, 0.75, 1.0, 1.25, 1.5, 2.0)],
            secs, 60.0, files_per_request=fpr,
            extra_headers={"X-SLO": "interactive"},
        )
        log("overload sweep (offered vs goodput):\n"
            + format_sweep_table(steps))

        # Multi-tenant isolation row: a quota-capped offender offering 4×
        # its budget while an unlimited victim runs its baseline closed
        # loop. The admission controller sheds the offender at the door
        # (429 in ~HTTP time), so the victim's p99 must stay close to its
        # alone-on-the-box number — the noisy-neighbor proof.
        iso_s = min(6.0, max(3.0, secs + 1.0))

        def victim_p99(rec):
            with rec.lock:
                lat = sorted(rec.latencies_ms)
            return percentile(lat, 99)

        rec_alone = Recorder()
        closed_loop(url, images, 12, iso_s, 60.0, rec_alone,
                    files_per_request=fpr,
                    tenants=[("victim", 1.0)],
                    extra_headers={"X-SLO": "interactive"})
        time.sleep(0.5)  # drain between windows
        rec_victim = Recorder()
        rec_off = Recorder()
        off_rate_rps = off_quota * 4.0 / fpr
        off_thread = threading.Thread(
            target=open_loop,
            args=(url, images, off_rate_rps, iso_s, 60.0, rec_off),
            kwargs=dict(files_per_request=fpr,
                        tenants=[("offender", 1.0)],
                        extra_headers={"X-SLO": "interactive"}),
            daemon=True,
        )
        off_thread.start()
        closed_loop(url, images, 12, iso_s, 60.0, rec_victim,
                    files_per_request=fpr,
                    tenants=[("victim", 1.0)],
                    extra_headers={"X-SLO": "interactive"})
        off_thread.join(timeout=iso_s + 65.0)
        p99_alone = victim_p99(rec_alone)
        p99_contended = victim_p99(rec_victim)
        with rec_off.lock:
            off_completed = len(rec_off.latencies_ms)
            off_shed = sum(rec_off.sheds_by_reason.values())
            off_reasons = dict(rec_off.sheds_by_reason)
            off_shed_lat = sorted(rec_off.shed_latencies_ms)
        ratio = (round(p99_contended / p99_alone, 3)
                 if p99_alone and p99_contended else None)
        tenant_row = {
            "offender_quota_images_per_sec": off_quota,
            "offender_offered_images_per_sec": round(off_rate_rps * fpr, 1),
            "offender_completed": off_completed,
            "offender_shed": off_shed,
            "offender_shed_reasons": off_reasons,
            # Quota refusals answer at lease time, before decode/device —
            # their latency is the cost of SAYING no, in ~HTTP time.
            "offender_shed_answer_p99_ms": round(percentile(off_shed_lat, 99), 1)
            if off_shed_lat else None,
            "victim_p99_alone_ms": round(p99_alone, 1) if p99_alone else None,
            "victim_p99_contended_ms": round(p99_contended, 1)
            if p99_contended else None,
            "victim_p99_ratio": ratio,
            "isolation_holds": (ratio is not None and ratio < 1.3),
        }
        log(f"multi-tenant isolation: victim p99 {tenant_row['victim_p99_alone_ms']} ms alone → "
            f"{tenant_row['victim_p99_contended_ms']} ms with offender at 4× quota "
            f"(ratio {ratio}); offender {off_completed} ok / {off_shed} shed {off_reasons}")

        srv_stats = fetch_stats(url) or {}
        econ = srv_stats.get("economics")
        if econ:
            log("device economics (live /stats):\n" + format_econ_table(econ))
        return {
            "model": model_spec,
            "closed_loop_images_per_sec": round(closed_ips, 1),
            "files_per_request": fpr,
            "max_queue": cfg.max_queue,
            "step_s": secs,
            "steps": steps,
            **sweep_summary(steps),
            "multi_tenant": tenant_row,
            **({"overload_counters": srv_stats["overload"]}
               if "overload" in srv_stats else {}),
            **({"economics": econ} if econ else {}),
        }
    finally:
        shutdown_gracefully(srv, batcher, grace_s=5.0)
        engine.close()


def http_bench(engine, cfg, secs):
    """Client-side numbers through the real WSGI + batcher stack
    (SURVEY.md §3.5): in-process server on an ephemeral port, driven by
    tools/loadgen's machinery — closed loop for peak sustainable
    throughput, then open loop (Poisson at 70% of that) for latency at a
    fixed offered load without coordinated omission.

    Builds its OWN engine with the production bucket ladder: the scan/e2e
    engine compiles only (n_dev, max_batch) to keep warmup cheap, but under
    HTTP load the batcher forms small batches, and padding a 3-image batch
    to the 32 bucket ships 10× the wire bytes, which makes the harness,
    not the server, the bottleneck. server.py always uses the full ladder.
    """
    import dataclasses
    import threading

    from tensorflow_web_deploy_tpu.serving.batcher import Batcher
    from tensorflow_web_deploy_tpu.serving.engine import InferenceEngine
    from tensorflow_web_deploy_tpu.serving.http import (
        App, make_http_server, shutdown_gracefully,
    )
    from tools.loadgen import (
        Recorder, closed_loop, fetch_stats, format_econ_table,
        format_stage_table, format_sweep_table, open_loop, percentile,
        stage_attribution, sweep_curve, sweep_summary, synthetic_jpegs,
    )

    ladder_cfg = dataclasses.replace(cfg, batch_buckets=None)  # default ladder
    t0 = time.perf_counter()
    # Second engine = second device copy of the params while this function
    # runs (the caller's engine stays live for the later sub-benches); all
    # its buffers drop with the locals on return, before those sections.
    engine = InferenceEngine(ladder_cfg, mesh=engine.mesh)
    engine.warmup()
    log(f"http engine (bucket ladder {engine.batch_buckets}) ready in "
        f"{time.perf_counter() - t0:.0f}s")
    cfg = ladder_cfg

    batcher = Batcher(engine, max_batch=engine.max_batch, max_delay_ms=cfg.max_delay_ms)
    batcher.start()
    app = App(engine, batcher, cfg)
    srv = make_http_server(app, "127.0.0.1", 0)
    port = srv.server_address[1]
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    url = f"http://127.0.0.1:{port}/predict"
    images = synthetic_jpegs(n=8, size=480)

    def summarize(rec, mode, t0, window_s):
        # Throughput counts only completions inside the offered-load window:
        # open_loop keeps draining stragglers after arrivals stop, and
        # counting those would overstate the sustained rate (same rule as
        # tools/loadgen.py's own summary — including the lock, because
        # straggler threads may still be appending).
        # Images (not requests) inside the offered-load window — the
        # Recorder owns the accounting so this and loadgen's own summary
        # can never diverge.
        in_window = rec.images_completed_by(t0 + window_s)
        with rec.lock:
            lat = sorted(rec.latencies_ms)
            errors = rec.errors
            connections = rec.connections
        return {
            "mode": mode,
            "images_per_sec": round(in_window / window_s, 2),
            "errors": errors,
            # Client-side keep-alive effectiveness: with connection reuse
            # this stays ≈ the worker count, not ≈ the request count.
            "connections": connections,
            "latency_ms": {
                "p50": round(percentile(lat, 50), 1) if lat else None,
                "p99": round(percentile(lat, 99), 1) if lat else None,
            },
        }

    try:
        closed_loop(url, images, 4, min(3.0, secs / 2), 60.0, Recorder())  # warmup
        rec = Recorder()
        workers = int(os.environ.get("BENCH_HTTP_WORKERS", "16"))
        t0 = time.perf_counter()
        closed_loop(url, images, workers, secs, 60.0, rec)
        closed = summarize(rec, f"closed({workers})", t0, secs)

        out = {"closed_loop": closed}
        rate = closed["images_per_sec"] * 0.7
        if rate >= 1:
            rec2 = Recorder()
            t0 = time.perf_counter()
            open_loop(url, images, rate, secs, 60.0, rec2)
            out["open_loop"] = summarize(rec2, f"open({rate:.0f}/s)", t0, secs)

        # Batch clients (several multipart file parts per request) amortize
        # the per-request HTTP+queue overhead into real device batches —
        # the throughput-mode operating point of the HTTP stack.
        fpr = int(os.environ.get("BENCH_HTTP_BATCH", "8"))
        if fpr > 1:
            closed_loop(url, images, 4, min(3.0, secs / 2), 60.0, Recorder(),
                        files_per_request=fpr)  # warm the batch shapes
            rec3 = Recorder()
            t0 = time.perf_counter()
            closed_loop(url, images, workers, secs, 60.0, rec3, files_per_request=fpr)
            out["closed_loop_batch"] = summarize(
                rec3, f"closed({workers})x{fpr}img", t0, secs
            )
        # Pipeline proof block: the SAME engine behind fresh batchers at
        # depth 1 (lockstep: the next batch cannot launch until the
        # previous one fetched) vs depth 2 (double-buffered). img/s at
        # each depth plus the timeline-measured decode∥execute overlap
        # ratio — the evidence that the speedup comes from overlap, not
        # noise. Runs on the batch-client shape (that is where assembly
        # time is big enough to be worth hiding).
        out["pipeline"] = {}
        pipe_secs = min(secs, 6.0)
        pipe_fpr = max(2, fpr)
        for depth in (1, 2):
            b2 = Batcher(engine, max_batch=engine.max_batch,
                         max_delay_ms=cfg.max_delay_ms,
                         pipeline_depth=depth, name=f"pipe-d{depth}")
            b2.start()
            app2 = App(engine, b2, cfg)
            srv2 = make_http_server(app2, "127.0.0.1", 0)
            threading.Thread(target=srv2.serve_forever, daemon=True).start()
            url2 = f"http://127.0.0.1:{srv2.server_address[1]}/predict"
            try:
                closed_loop(url2, images, 4, min(2.0, pipe_secs / 2), 60.0,
                            Recorder(), files_per_request=pipe_fpr)  # warm
                # Seq watermark: only batches sealed inside the timed
                # window count toward the overlap ratio.
                seq0 = max((r["seq"] for r in b2.batch_timeline()), default=0)
                rec_d = Recorder()
                t0d = time.perf_counter()
                closed_loop(url2, images, workers, pipe_secs, 60.0, rec_d,
                            files_per_request=pipe_fpr)
                entry = {
                    "images_per_sec": round(
                        rec_d.images_completed_by(t0d + pipe_secs) / pipe_secs, 2
                    ),
                    "errors": rec_d.errors,
                }
                ov = pipeline_overlap(
                    [r for r in b2.batch_timeline() if r["seq"] > seq0]
                )
                if ov:
                    entry.update(ov)
                out["pipeline"][f"depth_{depth}"] = entry
                log(f"pipeline depth {depth}: {entry}")
            finally:
                shutdown_gracefully(srv2, b2, grace_s=5.0)
        d1 = out["pipeline"].get("depth_1", {}).get("images_per_sec")
        d2 = out["pipeline"].get("depth_2", {}).get("images_per_sec")
        if d1 and d2:
            out["pipeline"]["depth2_over_depth1"] = round(d2 / d1, 3)

        # Offered-load sweep PAST saturation (ROADMAP item 1's curve): one
        # open-loop window per rate around the closed-loop ceiling —
        # goodput must plateau (bend), not collapse (break), as offered
        # load climbs to 2× capacity. Shares tools/loadgen's sweep_curve
        # with the CLI's --sweep mode, so the bench block and an operator's
        # sweep measure identically.
        base_rps = max(2.0, closed["images_per_sec"])
        sweep_step_s = min(secs, 5.0)
        steps = sweep_curve(
            url, images, [base_rps * f for f in (0.7, 1.0, 1.4, 2.0)],
            sweep_step_s, 60.0,
        )
        out["overload"] = {
            "step_s": sweep_step_s,
            "steps": steps,
            **sweep_summary(steps),
        }
        log("overload sweep (offered vs goodput):\n"
            + format_sweep_table(steps))

        # Server-side view of the same run: keep-alive reuse ratio, batch
        # occupancy, and staging-slab reuse (alloc count plateaus when the
        # pool is doing its job).
        # Per-stage attribution from the request spans: where server-side
        # time went across the whole run (decode vs queue vs device vs
        # postprocess) — the number that says what to optimize next.
        stages = stage_attribution(None, app.obs.stage_summary())
        log("server-side stage attribution:\n" + format_stage_table(stages))
        batcher_snap = batcher.stats.snapshot()
        out["server"] = {
            "http": app.http_counters.snapshot() if app.http_counters else None,
            "batch_occupancy": batcher_snap.get("batch_occupancy"),
            "adaptive_delay_ms": round(batcher.current_delay_ms, 3),
            "staging": engine.staging_stats(),
            "stages": stages,
            # Host-pipeline view of the run: lease-wait pressure + builder
            # telemetry from the slot-leased assembly path.
            "lease_wait_ms_p50": batcher_snap.get("lease_wait_ms_p50"),
            "builders": (batcher.builder_stats()
                         if hasattr(batcher, "builder_stats") else None),
        }
        # Device economics from the LIVE /stats endpoint (not recomputed
        # locally): per-config MFU, arithmetic intensity, roofline-bound
        # fraction and padding-waste fraction — the same block
        # profile_serve --server renders, so the two tools can never
        # diverge on methodology.
        live = fetch_stats(url)
        econ = (live or {}).get("economics")
        if econ:
            out["economics"] = econ
            log("device economics (live /stats):\n" + format_econ_table(econ))
        return out
    finally:
        shutdown_gracefully(srv, batcher, grace_s=5.0)


def hot_swap_bench(engine, cfg, secs):
    """Error rate + p99 THROUGH a live hot-swap (BENCH-tracked): a
    registry-backed server serves closed-loop traffic for the whole window
    while ``POST /models/swap`` rebuilds + rewarms the model on the loader
    thread and atomically shifts traffic to the new engine. Reports the
    swap-window latency/error numbers next to steady-state — the measured
    form of the zero-downtime claim the registry tests assert."""
    import dataclasses
    import http.client
    import json as _json
    import threading

    from tensorflow_web_deploy_tpu.serving.batcher import Batcher
    from tensorflow_web_deploy_tpu.serving.engine import InferenceEngine
    from tensorflow_web_deploy_tpu.serving.http import App, make_http_server
    from tensorflow_web_deploy_tpu.serving.registry import ModelRegistry
    from tools.loadgen import Recorder, closed_loop, percentile, synthetic_jpegs

    ladder_cfg = dataclasses.replace(cfg, batch_buckets=None)
    t0 = time.perf_counter()
    engine = InferenceEngine(ladder_cfg, mesh=engine.mesh)
    engine.warmup()
    log(f"hot-swap engine ready in {time.perf_counter() - t0:.0f}s")
    batcher = Batcher(engine, max_batch=engine.max_batch,
                      max_delay_ms=ladder_cfg.max_delay_ms,
                      name=ladder_cfg.model.name)
    batcher.start()
    registry = ModelRegistry(ladder_cfg)
    registry.adopt(ladder_cfg.model.name, engine, batcher, ladder_cfg.model)
    app = App.from_registry(registry, ladder_cfg)
    srv = make_http_server(app, "127.0.0.1", 0)
    port = srv.server_address[1]
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{port}/predict"
    images = synthetic_jpegs(n=4, size=480)

    rec = Recorder()
    window = {"t0": None, "t1": None}
    # Traffic runs for the swap build + warmup + a settle tail; the swap
    # POST (wait=true) brackets the window we attribute to the swap.
    total_s = max(secs, 6.0)
    traffic = threading.Thread(
        target=closed_loop,
        args=(url, images, 8, total_s, 120.0, rec),
        daemon=True,
    )

    def swap():
        time.sleep(min(2.0, total_s / 4))  # steady-state first
        window["t0"] = time.perf_counter()
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
        body = _json.dumps({"wait": True}).encode()
        conn.request("POST", "/models/swap", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        window["resp"] = (resp.status, _json.loads(resp.read()))
        conn.close()
        window["t1"] = time.perf_counter()

    swapper = threading.Thread(target=swap, daemon=True)
    try:
        closed_loop(url, images, 4, 2.0, 120.0, Recorder())  # warm the path
        traffic.start()
        swapper.start()
        traffic.join(timeout=total_s + 600)
        swapper.join(timeout=60)
    finally:
        from tensorflow_web_deploy_tpu.serving.http import shutdown_gracefully

        shutdown_gracefully(srv, registry, grace_s=5.0)

    with rec.lock:
        pairs = list(zip(rec.done_at, rec.latencies_ms))
        errors = rec.errors
        err_at = list(rec.err_at)
    lat_all = sorted(ms for _, ms in pairs)
    out = {
        "requests": len(lat_all) + errors,
        "errors": errors,
        "p50_ms": round(percentile(lat_all, 50), 1) if lat_all else None,
        "p99_ms": round(percentile(lat_all, 99), 1) if lat_all else None,
        "swap_response": window.get("resp"),
    }
    if window["t0"] is not None and window["t1"] is not None:
        t0s, t1s = window["t0"], window["t1"]
        in_swap = sorted(ms for at, ms in pairs if t0s <= at <= t1s)
        errs_in_swap = sum(1 for at in err_at if t0s <= at <= t1s)
        out["swap_s"] = round(t1s - t0s, 2)
        out["during_swap"] = {
            # Successes AND failures both count as requests — the error
            # rate's denominator must be everything attempted in the
            # window, or a 50% failure window reads as 100%.
            "requests": len(in_swap) + errs_in_swap,
            "errors": errs_in_swap,
            "p50_ms": round(percentile(in_swap, 50), 1) if in_swap else None,
            "p99_ms": round(percentile(in_swap, 99), 1) if in_swap else None,
        }
        out["error_rate_during_swap"] = round(
            errs_in_swap / max(1, len(in_swap) + errs_in_swap), 4
        )
    return out


def cache_bench(secs=6.0) -> dict:
    """Content-addressed response cache under heavy-tailed traffic
    (BENCH-tracked, ISSUE 9 acceptance): HTTP open-loop goodput at a
    Zipf(S≈1.1) hot-key workload with the cache ON vs the
    ``--cache-bytes 0`` baseline on the same engine, the single-flight
    coalesce count under concurrent identical requests, and a live
    hot-swap with a cache-hot key proving ZERO stale responses.

    Same thin-model methodology as mesh_scaling_bench: on the virtual CPU
    mesh the interesting term is what the cache REMOVES (device dispatch +
    batch assembly per repeated image), so a small fast model keeps
    engine build/warmup in seconds while the hit path's speedup is still
    the real served-path ratio. ``python bench.py cache`` runs ONLY this
    block on a forced 8-device virtual CPU mesh.
    """
    import concurrent.futures as cf
    import dataclasses
    import threading

    from tensorflow_web_deploy_tpu.serving.batcher import Batcher
    from tensorflow_web_deploy_tpu.serving.engine import InferenceEngine
    from tensorflow_web_deploy_tpu.serving.http import (
        App, make_http_server, shutdown_gracefully,
    )
    from tensorflow_web_deploy_tpu.serving.registry import ModelRegistry
    from tensorflow_web_deploy_tpu.utils.config import ServerConfig, model_config
    from tools.loadgen import (
        HttpClient, Recorder, closed_loop, open_loop, percentile,
        synthetic_jpegs, zipf_weights,
    )

    import jax

    model_spec = os.environ.get("BENCH_CACHE_MODEL", "native:mobilenet_v2")
    mc0 = model_config(model_spec)
    mc0.zoo_width = float(os.environ.get("BENCH_MESH_WIDTH", "0.35"))
    mc0.zoo_classes = 101
    mc0.input_size = (24, 24)
    mc0.dtype = "float32"
    n_dev = len(jax.devices())
    if jax.default_backend() == "cpu" and n_dev > 1:
        # Single-device replicas run NO collectives, which matters here:
        # the hot-swap stage has TWO live engines on the shared virtual
        # mesh (old serving + new warming on the loader thread), and the
        # XLA:CPU rendezvous guard serializes dispatches within ONE
        # engine only — two whole-mesh sharded programs from different
        # engines can still interleave and deadlock. Replicated placement
        # sidesteps the hazard entirely (and is the realistic small-model
        # placement anyway). Real accelerators never take the guard.
        mc0.placement = f"replicas={n_dev}"
    canvas = 64
    corpus = int(os.environ.get("BENCH_CACHE_CORPUS", "32"))
    zipf_s = float(os.environ.get("BENCH_CACHE_ZIPF", "1.1"))
    images = synthetic_jpegs(n=corpus, size=192)
    weights = zipf_weights(corpus, zipf_s)
    workers = int(os.environ.get("BENCH_HTTP_WORKERS", "24"))
    fpr = 8  # files/request: amortize HTTP framing, same as mesh_scaling

    base_cfg = ServerConfig(
        model=mc0, canvas_buckets=(canvas,), batch_buckets=(8,),
        max_batch=8, max_delay_ms=2.0, warmup=True, http_workers=workers,
    )
    t0 = time.perf_counter()
    engine = InferenceEngine(base_cfg)
    engine.warmup()
    log(f"cache bench engine+warmup ready in {time.perf_counter() - t0:.1f}s")

    def measure(cache_bytes: int) -> dict:
        """One served config over the SAME engine: calibrate closed-loop,
        then open-loop offered 1.15× above saturation — goodput under
        open load, the same protocol as the mesh-scaling curve."""
        cfg = dataclasses.replace(base_cfg, cache_bytes=cache_bytes)
        batcher = Batcher(engine, max_batch=engine.max_batch,
                          max_delay_ms=cfg.max_delay_ms,
                          name=f"cache-{'on' if cache_bytes else 'off'}")
        batcher.start()
        app = App(engine, batcher, cfg)
        srv = make_http_server(app, "127.0.0.1", 0, pool_size=workers)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        url = f"http://127.0.0.1:{srv.server_address[1]}/predict"
        try:
            # Warm the path (and, for the cached config, the hot set).
            closed_loop(url, images, 8, min(3.0, secs / 2), 60.0, Recorder(),
                        files_per_request=fpr, weights=weights)
            closed_ips = 0.0
            probe_s = min(3.0, secs / 2)
            for _ in range(2):
                rec_c = Recorder()
                t0c = time.perf_counter()
                closed_loop(url, images, workers, probe_s, 60.0, rec_c,
                            files_per_request=fpr, weights=weights)
                closed_ips = max(
                    closed_ips,
                    rec_c.images_completed_by(t0c + probe_s) / probe_s,
                )
            rate = max(20.0, closed_ips * 1.15) / fpr
            open_ips, lat, errors = 0.0, [], 0
            cache_hdr = {"hit": 0, "miss": 0, "coalesced": 0}
            for _ in range(2):
                rec_o = Recorder()
                t0o = time.perf_counter()
                open_loop(url, images, rate, secs, 60.0, rec_o,
                          files_per_request=fpr, weights=weights)
                window_ips = rec_o.images_completed_by(t0o + secs) / secs
                with rec_o.lock:
                    w_lat = sorted(rec_o.latencies_ms)
                    w_err = rec_o.errors
                    w_cache = dict(rec_o.cache_counts)
                errors += w_err
                if window_ips >= open_ips:
                    open_ips, lat, cache_hdr = window_ips, w_lat, w_cache
            sc = app.cache.stats()
            entry = {
                "cache_bytes": cache_bytes,
                "closed_loop_images_per_sec": round(closed_ips, 1),
                "open_loop_images_per_sec": round(open_ips, 1),
                "offered_images_per_sec": round(rate * fpr, 1),
                "errors": errors,
                "latency_ms_p50": round(percentile(lat, 50), 1) if lat else None,
                "client_cache_counts": cache_hdr,
                "server_hit_rate": sc["hit_rate"],
                "server_cache": {
                    k: sc[k] for k in
                    ("hits_total", "misses_total", "coalesced_total",
                     "evictions_total", "entries", "bytes")
                },
            }
            if cache_bytes:
                # Single-flight proof: bursts of concurrent identical
                # NEVER-SEEN images — all but the leader must coalesce
                # onto one dispatch (acceptance: count > 0).
                before = app.cache.stats()["coalesced_total"]
                for r in range(3):
                    fresh = synthetic_jpegs(n=1, size=256 + 8 * r)[0]

                    def one(_i, _img=fresh):
                        c = HttpClient(url, 30.0)
                        try:
                            c.post(_img, "image/jpeg")
                        finally:
                            c.close()

                    with cf.ThreadPoolExecutor(16) as ex:
                        list(ex.map(one, range(16)))
                entry["coalesced_dispatches"] = (
                    app.cache.stats()["coalesced_total"] - before
                )
            return entry
        finally:
            shutdown_gracefully(srv, batcher, grace_s=5.0)

    out = {
        "model": model_spec, "width": mc0.zoo_width, "canvas": canvas,
        "corpus": corpus, "zipf_s": zipf_s, "files_per_request": fpr,
        "secs_per_config": secs,
    }
    out["baseline"] = measure(0)
    log(f"cache baseline (--cache-bytes 0): {out['baseline']}")
    out["cached"] = measure(256 << 20)
    log(f"cache on: {out['cached']}")
    base_ips = out["baseline"]["open_loop_images_per_sec"]
    out["goodput_multiplier"] = (
        round(out["cached"]["open_loop_images_per_sec"] / base_ips, 2)
        if base_ips else None
    )

    # Live hot-swap with a cache-hot key: the registry's retire listener
    # invalidates the draining version's entries, and keys carry the
    # version — so ZERO responses may be stale (old-version payload for a
    # request started after the swap completed).
    swap_cfg = dataclasses.replace(base_cfg, cache_bytes=256 << 20)
    registry = ModelRegistry(swap_cfg)
    batcher = registry.build_batcher(engine, mc0.name)
    registry.adopt(mc0.name, engine, batcher, mc0)
    app = App.from_registry(registry, swap_cfg)
    srv = make_http_server(app, "127.0.0.1", 0, pool_size=workers)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{srv.server_address[1]}/predict"
    hot = images[0]
    stop = threading.Event()
    responses: list[tuple] = []
    failures: list = []

    def hammer():
        c = HttpClient(url, 120.0)
        try:
            while not stop.is_set():
                t_start = time.perf_counter()
                try:
                    status, data = c.post(hot, "image/jpeg")
                except Exception as e:
                    failures.append(repr(e))
                    c.close()
                    continue
                if status != 200:
                    failures.append(status)
                else:
                    responses.append(
                        (t_start, json.loads(data)["model_version"])
                    )
        finally:
            c.close()

    threads = [threading.Thread(target=hammer, daemon=True) for _ in range(8)]
    for t in threads:
        t.start()
    try:
        time.sleep(1.0)  # cache-hot steady state on v1
        mv2 = registry.swap(mc0.name, wait=True, timeout=600)
        old = registry._models[mc0.name][1]
        registry.wait_for(old, ("UNLOADED",), timeout=120)
        t_unloaded = time.perf_counter()
        time.sleep(1.0)  # cache-hot steady state on v2
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
        shutdown_gracefully(srv, registry, grace_s=5.0)
    stale = [v for at, v in responses
             if at > t_unloaded and v != mv2.version]
    sc = app.cache.stats()
    out["hot_swap"] = {
        "requests": len(responses) + len(failures),
        "errors": len(failures),
        "stale_responses": len(stale),
        "versions_seen": sorted({v for _, v in responses}),
        "swap_to_version": mv2.version,
        "cache_hits_total": sc["hits_total"],
        "cache_invalidations_total": sc["invalidations_total"],
    }
    log(f"cache hot-swap: {out['hot_swap']}")
    return out


def bulk_bench(secs=6.0) -> dict:
    """Bulk offline jobs vs the interactive path (BENCH-tracked, ISSUE 10
    acceptance): on the 8-dev virtual CPU mesh, (1) interactive open-loop
    saturation img/s and its p99 at a fixed moderate rate, (2) a
    server-side-dir job driven through POST /jobs as the batcher's bulk
    traffic class (256-image checkpoint chunks; device bucket sized to
    the mesh's batch-economy knee — see the inline comment) — its img/s
    must be ≥ 1.5× the interactive open-loop number, (3) the same
    moderate-rate interactive p99 WHILE a job runs — must stay < 2× of
    (1) (the bulk gate's isolation bound), and (4) a job interrupted by
    a real server shutdown mid-run resumed by a fresh server over the
    same --jobs-dir with zero lost / zero duplicated images. Same
    thin-model methodology as cache_bench; ``python bench.py bulk`` runs
    ONLY this block.
    """
    import dataclasses
    import shutil
    import tempfile
    import threading

    from tensorflow_web_deploy_tpu.serving.engine import InferenceEngine
    from tensorflow_web_deploy_tpu.serving.http import (
        App, make_http_server, shutdown_gracefully,
    )
    from tensorflow_web_deploy_tpu.serving.registry import ModelRegistry
    from tensorflow_web_deploy_tpu.utils.config import (
        ServerConfig, model_config,
    )
    from tools.loadgen import (
        Recorder, closed_loop, open_loop, percentile, synthetic_jpegs,
    )

    import jax

    model_spec = os.environ.get("BENCH_BULK_MODEL", "native:mobilenet_v2")
    mc0 = model_config(model_spec)
    mc0.zoo_width = float(os.environ.get("BENCH_MESH_WIDTH", "0.35"))
    mc0.zoo_classes = 101
    mc0.input_size = (24, 24)
    mc0.dtype = "float32"
    n_dev = len(jax.devices())
    canvas = 64
    # The bulk DEVICE bucket is sized to this mesh's batch-economy knee:
    # on the shared-core virtual CPU mesh the measured curve is 304 img/s
    # @8 → 676 @64 → 757 @256, so bucket 64 buys ~90% of the throughput
    # at ~28% of the execute quantum (95 ms vs 338 ms) — and the quantum
    # IS the interactive-tail cost of a running job on shared compute. On
    # a v5e the same knee sits at batch 256 (48 ms quantum, BASELINE
    # throughput mode), which is why the PRODUCT default --jobs-batch
    # stays 256: the bulk class batches at min(jobs_batch, top bucket).
    bulk_bucket = int(os.environ.get("BENCH_BULK_BATCH", "64"))
    bulk_bucket = max(n_dev, (bulk_bucket // n_dev) * n_dev)
    chunk = 256  # the checkpoint atom (jobs_batch) — progress granularity
    corpus_n = int(os.environ.get("BENCH_BULK_CORPUS", "48"))
    job_images = int(os.environ.get("BENCH_BULK_IMAGES", "4096"))
    workers = int(os.environ.get("BENCH_HTTP_WORKERS", "24"))
    fpr = 8

    # Whole-mesh shard placement (throughput-mode shapes shard over every
    # chip); the interactive bucket 8 rides the same engine. Cache OFF:
    # duplicate manifest entries must genuinely recompute, so the job
    # number is compute throughput, not dedup. jobs_max_inflight=1: ONE
    # bulk batch of device time is the isolation budget under test.
    cfg = ServerConfig(
        model=mc0, canvas_buckets=(canvas,), batch_buckets=(8, bulk_bucket),
        max_batch=8, max_delay_ms=2.0, warmup=True, http_workers=workers,
        cache_bytes=0, jobs_batch=chunk, jobs_max_inflight=1,
    )
    t0 = time.perf_counter()
    engine = InferenceEngine(cfg)
    engine.warmup()
    log(f"bulk bench engine+warmup (buckets 8+{bulk_bucket}) ready in "
        f"{time.perf_counter() - t0:.1f}s")

    images = synthetic_jpegs(n=corpus_n, size=192)
    src_dir = tempfile.mkdtemp(prefix="bulk_corpus_")
    for i in range(job_images):
        with open(os.path.join(src_dir, f"{i:05d}.jpg"), "wb") as f:
            f.write(images[i % corpus_n])
    jobs_dir = tempfile.mkdtemp(prefix="bulk_jobs_")

    def build_server():
        c = dataclasses.replace(cfg, jobs_dir=jobs_dir)
        reg = ModelRegistry(c)
        batcher = reg.build_batcher(engine, mc0.name)
        reg.adopt(mc0.name, engine, batcher, mc0)
        app = App.from_registry(reg, c)
        srv = make_http_server(app, "127.0.0.1", 0, pool_size=workers)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        return reg, app, srv, f"http://127.0.0.1:{srv.server_address[1]}"

    def submit_job(base):
        import urllib.request

        req = urllib.request.Request(
            f"{base}/jobs", data=json.dumps({"dir": src_dir}).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.load(r)["id"]

    def wait_job(app, job_id, timeout_s=600.0, until=None):
        deadline = time.perf_counter() + timeout_s
        while time.perf_counter() < deadline:
            doc = app.jobs.get_job(job_id)
            if until is not None and doc["completed"] >= until:
                return doc
            if doc["state"] in ("DONE", "FAILED", "CANCELLED"):
                return doc
            time.sleep(0.05)
        return app.jobs.get_job(job_id)

    out = {
        "model": model_spec, "width": mc0.zoo_width, "canvas": canvas,
        "bulk_bucket": bulk_bucket, "chunk": chunk,
        "job_images": job_images,
        "corpus": corpus_n, "files_per_request": fpr,
        "jobs_max_inflight": cfg.jobs_max_inflight,
    }
    reg, app, srv, base = build_server()
    url = f"{base}/predict"
    try:
        # (1) Interactive alone: saturation goodput + p99 at a moderate
        # fixed rate (the comparable-load protocol for the isolation pair).
        closed_loop(url, images, 8, min(3.0, secs / 2), 60.0, Recorder(),
                    files_per_request=fpr)
        closed_ips = 0.0
        probe_s = min(3.0, secs / 2)
        for _ in range(2):
            rec_c = Recorder()
            t0c = time.perf_counter()
            closed_loop(url, images, workers, probe_s, 60.0, rec_c,
                        files_per_request=fpr)
            closed_ips = max(closed_ips,
                             rec_c.images_completed_by(t0c + probe_s) / probe_s)
        rec_o = Recorder()
        t0o = time.perf_counter()
        open_loop(url, images, max(20.0, closed_ips * 1.15) / fpr, secs,
                  60.0, rec_o, files_per_request=fpr)
        open_ips = rec_o.images_completed_by(t0o + secs) / secs
        mod_rate = max(10.0, closed_ips * 0.4) / fpr
        rec_p = Recorder()
        open_loop(url, images, mod_rate, secs, 60.0, rec_p,
                  files_per_request=fpr)
        with rec_p.lock:
            lat_alone = sorted(rec_p.latencies_ms)
        out["interactive"] = {
            "closed_loop_images_per_sec": round(closed_ips, 1),
            "open_loop_images_per_sec": round(open_ips, 1),
            "moderate_rate_images_per_sec": round(mod_rate * fpr, 1),
            "p99_alone_ms": (round(percentile(lat_alone, 99), 1)
                             if lat_alone else None),
            "errors": rec_o.errors + rec_p.errors,
        }
        log(f"bulk: interactive alone {out['interactive']}")

        # (2) Job alone: the throughput-mode number.
        jid = submit_job(base)
        t0j = time.perf_counter()
        doc = wait_job(app, jid)
        job_wall = time.perf_counter() - t0j
        job_ips = doc["completed"] / job_wall if job_wall else 0.0
        out["job_alone"] = {
            "state": doc["state"], "completed": doc["completed"],
            "errors": doc["errors"], "wall_s": round(job_wall, 2),
            "images_per_sec": round(job_ips, 1),
            "chunks": doc["chunks_done"],
        }
        out["throughput_ratio"] = (round(job_ips / open_ips, 2)
                                   if open_ips else None)
        log(f"bulk: job alone {out['job_alone']} "
            f"(ratio vs interactive open-loop: {out['throughput_ratio']})")

        # (3) Isolation: the SAME moderate-rate interactive probe while a
        # fresh job runs — p99 must stay < 2× of (1). The job is sized to
        # OUTLAST the probe window, so every probe request genuinely
        # competes with running bulk work (job_running_at_probe_end is
        # the witness; a job that finished early would dilute the tail).
        jid2 = submit_job(base)
        rec_d = Recorder()
        open_loop(url, images, mod_rate, secs, 60.0, rec_d,
                  files_per_request=fpr)
        probe_end_doc = app.jobs.get_job(jid2)
        with rec_d.lock:
            lat_during = sorted(rec_d.latencies_ms)
        doc2 = wait_job(app, jid2)
        p99_a = percentile(lat_alone, 99)
        p99_d = percentile(lat_during, 99)
        out["isolation"] = {
            "p99_with_job_ms": round(p99_d, 1) if p99_d else None,
            "p99_degradation": (round(p99_d / p99_a, 2)
                                if p99_a and p99_d else None),
            "interactive_errors": rec_d.errors,
            "job_running_at_probe_end":
                probe_end_doc["state"] == "RUNNING",
            "job_completed_during_probe": probe_end_doc["completed"],
            "job_state": doc2["state"],
            "job_completed": doc2["completed"],
            "bulk_gate_holds": (app.registry.default_entry().batcher
                                .builder_stats()["bulk"]["gate_holds_total"]),
            "starvation_dispatches": (
                app.registry.default_entry().batcher
                .builder_stats()["bulk"]["starvation_dispatches_total"]),
        }
        log(f"bulk: isolation {out['isolation']}")
    finally:
        shutdown_gracefully(srv, reg, grace_s=10.0)

    # (4) Restart-resume: interrupt a job with a REAL server shutdown
    # (SIGTERM path), bring a fresh server up over the same --jobs-dir,
    # and prove zero lost / zero duplicated images.
    reg, app, srv, base = build_server()
    try:
        jid3 = submit_job(base)
        doc = wait_job(app, jid3, until=chunk)  # at least one chunk
        resumed_from = doc["completed"]
        shutdown_gracefully(srv, reg, grace_s=30.0)  # checkpoints the job
        reg, app, srv, base = build_server()  # the restart
        doc = wait_job(app, jid3)
        lines, _off, _st, _tot = app.jobs.read_results(jid3, 0, 1_000_000)
        idx = [json.loads(l)["i"] for l in lines]
        out["restart_resume"] = {
            "state": doc["state"],
            "total": doc["total"],
            "resumed_from": resumed_from,
            "completed_after_resume": doc["completed"],
            "result_lines": len(idx),
            "lost": doc["total"] - len(set(idx)),
            "duplicated": len(idx) - len(set(idx)),
        }
        log(f"bulk: restart resume {out['restart_resume']}")
    finally:
        shutdown_gracefully(srv, reg, grace_s=10.0)
        shutil.rmtree(src_dir, ignore_errors=True)
        shutil.rmtree(jobs_dir, ignore_errors=True)
    return out


def ragged_bench(secs=6.0) -> dict:
    """Ragged packed-slab wire vs the host pad-to-canvas baseline
    (BENCH-tracked, ISSUE 14 acceptance): a mixed-size upload trace
    (~200 px images against a 256 canvas bucket) served twice on the
    8-dev virtual CPU mesh — classic wire, then ``--ragged`` — reading
    the live ``/stats → economics`` block for both padding gauges:

    - ``padded_px_fraction``: shipped canvas pixels that were padding
      (the batcher's px axis; the classic wire ships full 256×256
      canvases for every ~0.29-canvas upload, so this starts ≈ 0.7 and
      the ragged wire must pull it ≤ 0.30);
    - ``padded_rows_fraction``: dispatch rows that carried no request
      (econ rows axis — on the ragged wire rows_dispatched counts arena
      rows actually shipped, so this becomes the wire-padding gauge).

    Plus open-loop img/s under the same trace with ZERO errors — tight
    packing must not cost throughput. Cache OFF so every request really
    decodes and ships. Same thin-model methodology as cache_bench;
    ``python bench.py ragged`` runs ONLY this block.
    """
    import threading
    import urllib.request

    from tensorflow_web_deploy_tpu.serving.batcher import Batcher
    from tensorflow_web_deploy_tpu.serving.engine import InferenceEngine
    from tensorflow_web_deploy_tpu.serving.http import (
        App, make_http_server, shutdown_gracefully,
    )
    from tensorflow_web_deploy_tpu.utils.config import ServerConfig, model_config
    from tools.loadgen import (
        Recorder, closed_loop, open_loop, parse_sizes, percentile,
        synthetic_jpegs_sized,
    )

    import jax

    model_spec = os.environ.get("BENCH_RAGGED_MODEL", "native:mobilenet_v2")
    mc0 = model_config(model_spec)
    mc0.zoo_width = float(os.environ.get("BENCH_MESH_WIDTH", "0.35"))
    mc0.zoo_classes = 101
    mc0.input_size = (24, 24)
    mc0.dtype = "float32"
    n_dev = len(jax.devices())
    if jax.default_backend() == "cpu" and n_dev > 1:
        # Replicated single-device placement, same rationale as
        # cache_bench: no collectives, so nothing to rendezvous, and it
        # is the realistic small-model placement anyway.
        mc0.placement = f"replicas={n_dev}"
    canvas = int(os.environ.get("BENCH_RAGGED_CANVAS", "256"))
    # The ISSUE's traffic shape: uploads around 200 px on the longest
    # side against the 256 canvas — real pixels ≈ 0.27–0.30 of the
    # shipped canvas, so the classic wire's padded_px_fraction sits at
    # 0.70–0.73 and the packed wire has ~0.7 of every shipped byte to
    # win back.
    sizes = parse_sizes(os.environ.get(
        "BENCH_RAGGED_SIZES",
        "224x80:2,200x96:3,176x112:3,160x120:2,144x136:1"))
    images, labels, weights = synthetic_jpegs_sized(sizes, per_size=6)
    workers = int(os.environ.get("BENCH_HTTP_WORKERS", "24"))
    fpr = 8  # files/request: amortize HTTP framing, same as mesh_scaling

    def measure(ragged: bool, floor_ips: float = 0.0) -> dict:
        """One wire over its own engine (the wire is an engine-build
        property): calibrate closed-loop, then open-loop offered 1.05×
        above saturation, then read the live /stats economics block.
        ``floor_ips`` pins the offered rate to another wire's measured
        saturation so both wires face the IDENTICAL offered trace —
        goodput under matched load, not calibration-probe luck (a wire
        offered its own noisy calibration can read as a throughput gap
        that isn't there)."""
        cfg = ServerConfig(
            model=mc0, canvas_buckets=(canvas,), batch_buckets=(8,),
            max_batch=8, max_delay_ms=2.0, warmup=True,
            http_workers=workers, cache_bytes=0, ragged=ragged,
        )
        t0 = time.perf_counter()
        engine = InferenceEngine(cfg)
        engine.warmup()
        log(f"ragged bench engine ({'ragged' if ragged else 'classic'} "
            f"wire) ready in {time.perf_counter() - t0:.1f}s")
        batcher = Batcher(engine, max_batch=engine.max_batch,
                          max_delay_ms=cfg.max_delay_ms,
                          name=f"ragged-{'on' if ragged else 'off'}")
        batcher.start()
        app = App(engine, batcher, cfg)
        srv = make_http_server(app, "127.0.0.1", 0, pool_size=workers)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        base = f"http://127.0.0.1:{srv.server_address[1]}"
        url = f"{base}/predict"
        try:
            # Warm the served path; the size mix is baked into the
            # weighted corpus, so every phase offers the same trace.
            closed_loop(url, images, 8, min(3.0, secs / 2), 60.0,
                        Recorder(), files_per_request=fpr, weights=weights)
            # Calibration probes need to be LONG: on a shared box a 3 s
            # window draws ±15% run-to-run, and an under-drawn probe
            # under-offers the open loop below saturation, which then
            # reads as a throughput gap between wires that isn't there.
            # Mean (not max) of the probes — max biases the estimate up,
            # and over-offering a long window accumulates backlog until
            # stragglers blow the request deadline.
            probe_s = min(10.0, max(6.0, secs))
            probes = []
            for _ in range(2):
                rec_c = Recorder()
                t0c = time.perf_counter()
                closed_loop(url, images, workers, probe_s, 60.0, rec_c,
                            files_per_request=fpr, weights=weights)
                probes.append(
                    rec_c.images_completed_by(t0c + probe_s) / probe_s)
                time.sleep(2.0)  # let the saturated queue drain
            closed_ips = sum(probes) / len(probes)
            rate = max(20.0, (floor_ips or closed_ips) * 1.05) / fpr
            open_ips, lat, errors = 0.0, [], 0
            for _ in range(2):
                rec_o = Recorder()
                t0o = time.perf_counter()
                open_loop(url, images, rate, secs, 60.0, rec_o,
                          files_per_request=fpr, weights=weights)
                window_ips = rec_o.images_completed_by(t0o + secs) / secs
                with rec_o.lock:
                    w_lat = sorted(rec_o.latencies_ms)
                    w_err = rec_o.errors
                errors += w_err
                if window_ips >= open_ips:
                    open_ips, lat = window_ips, w_lat
                time.sleep(2.0)  # drain before the next window
            # The acceptance gauges come from the LIVE server, not from
            # reaching into objects: /stats → economics carries the
            # costmodel rows axis and the batcher's px axis side by side.
            with urllib.request.urlopen(f"{base}/stats", timeout=30) as r:
                stats = json.load(r)
            econ = next(iter(stats["economics"].values()))
            pad_cells = econ.get("padding") or {}
            px_real = sum(c["px_real"] for c in pad_cells.values())
            px_disp = sum(c["px_dispatched"] for c in pad_cells.values())
            return {
                "ragged": ragged,
                "wire": econ.get("wire"),
                "closed_loop_images_per_sec": round(closed_ips, 1),
                "open_loop_images_per_sec": round(open_ips, 1),
                "offered_images_per_sec": round(rate * fpr, 1),
                "errors": errors,
                "latency_ms_p50": round(percentile(lat, 50), 1) if lat else None,
                "latency_ms_p99": round(percentile(lat, 99), 1) if lat else None,
                "padded_rows_fraction": econ.get("padded_rows_fraction"),
                "padded_px_fraction": (round(1.0 - px_real / px_disp, 4)
                                       if px_disp else None),
                "rows_total": econ.get("rows_total"),
                "rows_dispatched_total": econ.get("rows_dispatched_total"),
                "mfu": econ.get("mfu"),
            }
        finally:
            shutdown_gracefully(srv, batcher, grace_s=5.0)
            engine.close()

    out = {
        "model": model_spec, "width": mc0.zoo_width, "canvas": canvas,
        "sizes": [f"{w}x{h}:{wt:g}" for (w, h), wt in sizes],
        "corpus": len(images), "files_per_request": fpr,
        "secs_per_config": secs,
    }
    out["classic"] = measure(False)
    log(f"ragged bench classic wire: {out['classic']}")
    # Pin the packed wire's offered rate to the classic wire's measured
    # saturation so both wires face the identical offered trace — the
    # open-loop comparison is goodput under matched load.
    out["ragged"] = measure(
        True, floor_ips=out["classic"]["closed_loop_images_per_sec"])
    log(f"ragged bench packed wire: {out['ragged']}")
    base_ips = out["classic"]["open_loop_images_per_sec"]
    out["goodput_multiplier"] = (
        round(out["ragged"]["open_loop_images_per_sec"] / base_ips, 2)
        if base_ips else None
    )
    # Saturated capacity ratio — the throughput headline. The open-loop
    # multiplier compares goodput at matched offered load (both wires
    # saturate → both ≈ offered), so capacity is where a wire that can
    # simply serve MORE shows up.
    base_cap = out["classic"]["closed_loop_images_per_sec"]
    out["capacity_multiplier"] = (
        round(out["ragged"]["closed_loop_images_per_sec"] / base_cap, 2)
        if base_cap else None
    )
    bf, af = (out["classic"]["padded_px_fraction"],
              out["ragged"]["padded_px_fraction"])
    out["padded_px_fraction_drop"] = (
        round(bf - af, 4) if bf is not None and af is not None else None
    )
    return out


def raw_speed_bench(secs=3.0) -> dict:
    """Raw-speed tier (BENCH-tracked, ISSUE 15 acceptance): per-(preset,
    dtype) serve-path throughput with roofline attribution — f32 golden
    vs bf16 vs int8 (dequant-on-the-fly + fused depthwise chain), plus
    the fused-kernel A/B on MobileNetV2.

    Each engine runs its compiled (canvas, batch) cell closed-loop for
    ``secs``, then the row is read from the SAME costmodel the live
    ``/stats → economics`` block uses: analytic FLOPs/bytes per image at
    the tier's storage/compute widths, the per-dtype backend peak, which
    ceiling binds (compute vs bandwidth), whole-placement MFU, and the
    measured fraction of the BINDING ceiling. The acceptance gate is
    fraction-of-ceiling, not raw img/s: each tier is judged against its
    OWN roofline (int8 moves fewer bytes AND fuses the depthwise stack,
    so its ceiling moves too — beating 1.5× of f32's fraction means the
    quantized engine actually converts the freed bandwidth into work).

    ``python bench.py raw_speed`` runs ONLY this block on the 8-device
    virtual CPU mesh (replicated single-device placement — the realistic
    small-model shape, no collectives).
    """
    from tensorflow_web_deploy_tpu.serving import costmodel
    from tensorflow_web_deploy_tpu.serving.engine import InferenceEngine
    from tensorflow_web_deploy_tpu.utils.config import ModelConfig, ServerConfig

    import jax

    n_dev = len(jax.devices())
    width = float(os.environ.get("BENCH_RAW_WIDTH", "0.35"))
    size = int(os.environ.get("BENCH_RAW_SIZE", "96"))
    batch = int(os.environ.get("BENCH_RAW_BATCH", "8"))
    presets = os.environ.get(
        "BENCH_RAW_PRESETS",
        "mobilenet_v2,resnet50,inception_v3,ssd_mobilenet").split(",")
    dtypes = os.environ.get("BENCH_RAW_DTYPES", "float32,bfloat16,int8").split(",")

    rng = np.random.RandomState(0)
    canvases = (rng.rand(batch, size, size, 3) * 255).astype(np.uint8)
    hws = np.full((batch, 2), size, np.int32)

    def measure(preset: str, dtype: str, fused: str = "auto") -> dict:
        mc = ModelConfig(
            name=preset, source="native", zoo_width=width, zoo_classes=101,
            task="detect" if preset == "ssd_mobilenet" else "classify",
            input_size=(size, size), dtype=dtype, fused_dw=fused,
        )
        if jax.default_backend() == "cpu" and n_dev > 1:
            mc.placement = f"replicas={n_dev}"
        cfg = ServerConfig(model=mc, canvas_buckets=(size,),
                           batch_buckets=(batch,), max_batch=batch,
                           warmup=False)
        engine = InferenceEngine(cfg)
        try:
            # Warm every replica's compiled cell before the timed window.
            for _ in range(max(2, n_dev)):
                engine.run_batch(canvases, hws)
            t0 = time.perf_counter()
            images = 0
            while time.perf_counter() - t0 < secs:
                engine.run_batch(canvases, hws)
                images += batch
            wall = time.perf_counter() - t0
            econ = costmodel.economics_snapshot(engine, mc)
            cells = [c for r in econ["replicas"] for c in r["buckets"]
                     if c["device_s"] > 0]
            dev_s = sum(c["device_s"] for c in cells)
            # Device-busy-weighted fraction of the binding ceiling (all
            # cells share one (canvas, batch) config → one attainable).
            frac = (sum((c["roofline_bound_fraction"] or 0.0) * c["device_s"]
                        for c in cells) / dev_s if dev_s else None)
            row = {
                "preset": preset,
                "dtype": dtype,
                "fused_dw": bool(getattr(engine, "_fused_dw", False)),
                "images_per_sec": round(images / wall, 1),
                "mfu": econ.get("mfu"),
                "bound": cells[0]["bound"] if cells else None,
                "roofline_bound_fraction": round(frac, 5) if frac else None,
                "flops_per_image": econ["model_cost"]["flops_per_image"],
                "param_bytes": econ["model_cost"]["param_bytes"],
                "act_bytes_per_image": econ["model_cost"]["act_bytes_per_image"],
                "peak_source": econ["peak"]["source"],
            }
            if engine.parity is not None:
                row["parity"] = {k: engine.parity[k] for k in
                                 ("pass", "topk_agreement", "max_prob_delta")
                                 if k in engine.parity}
            return row
        finally:
            engine.close()

    rows = []
    for preset in presets:
        for dtype in dtypes:
            log(f"raw_speed: {preset} @ {dtype}")
            rows.append(measure(preset, dtype))
    # Fused-kernel A/B: the int8 tier with the fused depthwise chain
    # forced OFF — same quantized weights, stock grouped-conv forward.
    ab = None
    if "mobilenet_v2" in presets and "int8" in dtypes:
        log("raw_speed: mobilenet_v2 @ int8 (fused off — A/B)")
        unfused = measure("mobilenet_v2", "int8", fused="off")
        unfused["ab"] = "fused_off"
        rows.append(unfused)
        fused_row = next(r for r in rows if r["preset"] == "mobilenet_v2"
                         and r["dtype"] == "int8" and r["fused_dw"])
        ab = {
            "images_per_sec_fused": fused_row["images_per_sec"],
            "images_per_sec_unfused": unfused["images_per_sec"],
            "fused_speedup": round(
                fused_row["images_per_sec"] / unfused["images_per_sec"], 2)
            if unfused["images_per_sec"] else None,
        }
    out = {"rows": rows, "fused_ab": ab,
           "width": width, "input_size": size, "batch": batch,
           "n_devices": n_dev}
    # Acceptance: int8 MobileNetV2 achieves >= 1.5x the f32 engine's
    # measured fraction of its binding roofline ceiling.
    by = {(r["preset"], r["dtype"]): r for r in rows if "ab" not in r}
    f32 = by.get(("mobilenet_v2", "float32"))
    i8 = by.get(("mobilenet_v2", "int8"))
    if f32 and i8 and f32["roofline_bound_fraction"]:
        ratio = i8["roofline_bound_fraction"] / f32["roofline_bound_fraction"]
        out["acceptance"] = {
            "int8_fraction": i8["roofline_bound_fraction"],
            "f32_fraction": f32["roofline_bound_fraction"],
            "fraction_ratio": round(ratio, 2),
            "pass": ratio >= 1.5,
        }
    return out


def telemetry_bench(secs=6.0) -> dict:
    """Telemetry A/B + SLO alert episode (ISSUE 17 acceptance): the
    sampler must cost ≤1% goodput, and a chaos-injected slow_replica
    episode must make the interactive burn-rate alert fire and then
    clear.

    One engine + batcher serve three phases through fresh Apps:

    1. ``--telemetry-interval 0`` (hub absent) at a fixed open-loop rate
       below saturation — the "off" goodput.
    2. Telemetry on (0.5 s sampler + interactive p99:1000ms:99.9
       objective) at the SAME offered rate — the "on" goodput. The
       primary metric is on/off, which bench_diff guards.
    3. Alert episode: burn windows shortened (a bench cannot wait out
       the SRE-book 1m/5m/30m windows), chaos ``slow_replica`` toggled
       on under sustained load until the alert fires, then toggled off
       until it clears — both transitions read back from /debug/events'
       structured ring.
    """
    import threading

    import jax

    from tensorflow_web_deploy_tpu.serving.batcher import Batcher
    from tensorflow_web_deploy_tpu.serving.chaos import ChaosInjector
    from tensorflow_web_deploy_tpu.serving.engine import InferenceEngine
    from tensorflow_web_deploy_tpu.serving.http import (
        App, make_http_server, shutdown_gracefully,
    )
    from tensorflow_web_deploy_tpu.utils.config import ServerConfig, model_config
    from tools.loadgen import (
        Recorder, closed_loop, open_loop, percentile, synthetic_jpegs,
    )

    model_spec = os.environ.get("BENCH_TELEMETRY_MODEL", "native:mobilenet_v2")
    interval_s = float(os.environ.get("BENCH_TELEMETRY_INTERVAL", "0.5"))
    mc = model_config(model_spec)
    mc.zoo_width = float(os.environ.get("BENCH_MESH_WIDTH", "0.35"))
    mc.zoo_classes = 101
    mc.input_size = (24, 24)
    mc.dtype = "float32"
    n_dev = len(jax.devices())
    if jax.default_backend() == "cpu" and n_dev > 1:
        mc.placement = f"replicas={n_dev}"
    workers = int(os.environ.get("BENCH_HTTP_WORKERS", "24"))
    base_cfg = dict(
        model=mc, canvas_buckets=(64,), batch_buckets=(8,), max_batch=8,
        max_delay_ms=2.0, warmup=True, http_workers=workers, max_queue=128,
    )
    cfg_off = ServerConfig(**base_cfg, telemetry_interval_s=0.0)
    cfg_on = ServerConfig(
        **base_cfg, telemetry_interval_s=interval_s,
        slo_objectives="interactive=p99:1000ms:99.9",
    )
    t0 = time.perf_counter()
    engine = InferenceEngine(cfg_off)
    engine.warmup()
    batcher = Batcher(engine, max_batch=engine.max_batch,
                      max_delay_ms=cfg_off.max_delay_ms,
                      max_queue=cfg_off.max_queue, name="telemetry")
    batcher.start()
    images = synthetic_jpegs(n=6, size=192)
    fpr = 8
    log(f"telemetry bench engine ready in {time.perf_counter() - t0:.1f}s")

    def serve(cfg):
        app = App(engine, batcher, cfg)
        srv = make_http_server(app, "127.0.0.1", 0, pool_size=workers)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        return app, srv, f"http://127.0.0.1:{srv.server_address[1]}/predict"

    def stop(app, srv):
        # Phase teardown WITHOUT shutdown_gracefully: the batcher must
        # keep running for the next phase; only the HTTP front and the
        # phase's sampler go away.
        srv.shutdown()
        srv.server_close()
        if app.telemetry is not None:
            app.telemetry.stop()

    def measure(url, rate_rps) -> dict:
        rec = Recorder()
        t0m = time.perf_counter()
        open_loop(url, images, rate_rps, secs, 60.0, rec,
                  files_per_request=fpr)
        ips = rec.images_completed_by(t0m + secs) / secs
        with rec.lock:
            lat = sorted(rec.latencies_ms)
            errors = rec.errors
        return {
            "images_per_sec": round(ips, 1),
            "p50_ms": round(percentile(lat, 50), 1) if lat else None,
            "p99_ms": round(percentile(lat, 99), 1) if lat else None,
            "errors": errors,
        }

    # Phase 1: telemetry off — calibrate, then the fixed-rate "off" run.
    app_off, srv_off, url = serve(cfg_off)
    try:
        closed_loop(url, images, 8, min(3.0, secs), 60.0, Recorder(),
                    files_per_request=fpr)  # warm
        probe_s = min(3.0, secs)
        rec_c = Recorder()
        t0c = time.perf_counter()
        closed_loop(url, images, workers, probe_s, 60.0, rec_c,
                    files_per_request=fpr)
        closed_ips = rec_c.images_completed_by(t0c + probe_s) / probe_s
        # 0.7× saturation: both phases run the same comfortably-served
        # offered load, so the A/B isolates the sampler's cost instead of
        # comparing two saturation points.
        rate_rps = max(1.0, 0.7 * closed_ips) / fpr
        off = measure(url, rate_rps)
    finally:
        stop(app_off, srv_off)

    # Phase 2: telemetry on at the SAME offered rate.
    app_on, srv_on, url = serve(cfg_on)
    try:
        hub = app_on.telemetry
        on = measure(url, rate_rps)
        overhead = (round(1.0 - on["images_per_sec"] / off["images_per_sec"], 4)
                    if off["images_per_sec"] else None)
        log(f"telemetry A/B at {rate_rps * fpr:.0f} img/s offered: "
            f"off {off['images_per_sec']} img/s, on {on['images_per_sec']} "
            f"img/s (overhead {overhead if overhead is not None else '?'})")

        # Phase 3: the alert episode. Shorten the burn windows first —
        # the defaults are operational timescales (1m/5m/30m) and a bench
        # cannot wait half an hour for a clear. Tuple reassignment is
        # atomic; the evaluator reads self.windows each tick.
        hub.windows = (("5s", 5.0), ("15s", 15.0), ("30s", 30.0))
        stop_bg = threading.Event()

        def background_load():
            while not stop_bg.is_set():
                closed_loop(url, images, 6, 2.0, 60.0, Recorder(),
                            files_per_request=fpr)

        bg = threading.Thread(target=background_load, daemon=True)
        bg.start()

        def alert_state():
            return hub.alerts()["interactive"]["state"]

        def wait_state(want, timeout_s):
            t0w = time.perf_counter()
            while time.perf_counter() - t0w < timeout_s:
                if alert_state() == want:
                    return round(time.perf_counter() - t0w, 1)
                time.sleep(0.25)
            return None

        inj = ChaosInjector.from_spec(
            os.environ.get("BENCH_TELEMETRY_CHAOS", "slow_replica=0.7:900,seed=7"))
        app_on.chaos = inj
        batcher.chaos = inj
        fire_after = wait_state("firing", 30.0)
        batcher.chaos = None
        app_on.chaos = None
        clear_after = wait_state("ok", 90.0) if fire_after is not None else None
        stop_bg.set()
        bg.join(timeout=10.0)
        alert_events = hub.events(
            kinds={"slo_alert_fire", "slo_alert_clear"})
        chaos_events = hub.events(kinds={"chaos_injection"})
        log(f"slo alert episode: fired after {fire_after}s of chaos, "
            f"cleared {clear_after}s after chaos off "
            f"({len(chaos_events)} chaos injection events)")

        hub_stats = hub.stats()
        return {
            "model": model_spec,
            "interval_s": interval_s,
            "offered_images_per_sec": round(rate_rps * fpr, 1),
            "closed_loop_images_per_sec": round(closed_ips, 1),
            "off": off,
            "on": on,
            "overhead_fraction": overhead,
            "alert": {
                "fired": fire_after is not None,
                "cleared": clear_after is not None,
                "fire_after_s": fire_after,
                "clear_after_s": clear_after,
                "chaos_injection_events": len(chaos_events),
                "events": alert_events[-4:],
            },
            "telemetry_stats": {
                k: hub_stats[k]
                for k in ("series_count", "memory_bytes", "samples_total",
                          "overruns_total", "source_errors_total",
                          "last_tick_ms")
            },
        }
    finally:
        shutdown_gracefully(srv_on, batcher, grace_s=5.0)
        engine.close()


def cold_start_bench(secs=6.0) -> dict:
    """Cold-start killer (ISSUE 18 acceptance): boot-to-SERVING with the
    AOT executable cache off, cold (empty dir, compiles + writes) and
    warm (deserializes) on the multi-bucket ragged config, a
    registry-driven hot-swap rewarm of the same shape, golden + int8
    parity on the deserialize path, and a poisoned-cache boot that must
    finish with zero errors. The primary metric bench_diff guards is
    warm-vs-cold boot speedup (acceptance: ≥3×)."""
    import shutil
    import tempfile
    import threading

    import jax
    import numpy as np

    from tensorflow_web_deploy_tpu.serving import aotcache
    from tensorflow_web_deploy_tpu.serving.engine import InferenceEngine
    from tensorflow_web_deploy_tpu.serving.registry import SERVING, ModelRegistry
    from tensorflow_web_deploy_tpu.utils.config import ServerConfig, model_config

    n_dev = len(jax.devices())

    def make_cfg(cache_dir, dtype="float32", multi=True):
        mc = model_config("native:mobilenet_v2")
        mc.zoo_width = float(os.environ.get("BENCH_MESH_WIDTH", "0.35"))
        mc.zoo_classes = 101
        mc.input_size = (24, 24)
        mc.dtype = dtype
        if jax.default_backend() == "cpu" and n_dev > 1:
            mc.placement = f"replicas={n_dev}"
        return ServerConfig(
            model=mc,
            canvas_buckets=(64, 96) if multi else (64,),
            batch_buckets=(4, 8) if multi else (8,),
            max_batch=8, ragged=True, wire_format="rgb",
            aot_cache_dir=cache_dir,
        )

    rs = np.random.RandomState(7)
    canvases = rs.randint(0, 255, (4, 64, 64, 3)).astype(np.uint8)
    hws = np.full((4, 2), 48, np.int32)

    def boot(cfg):
        """Boot-to-SERVING: build + warmup, the span an operator waits
        through before the registry flips LOADING→WARMING→SERVING."""
        before = aotcache.stats()
        t0 = time.perf_counter()
        eng = InferenceEngine(cfg)
        eng.warmup()
        dt = time.perf_counter() - t0
        after = aotcache.stats()
        out = tuple(np.asarray(o) for o in eng.run_batch(canvases, hws))
        delta = {k: after[k] - before[k]
                 for k in ("hits_total", "misses_total", "writes_total",
                           "corrupt_total")}
        return eng, out, dt, delta

    cache_dir = tempfile.mkdtemp(prefix="bench_aot_")
    result = {"n_devices": n_dev, "backend": jax.default_backend()}
    try:
        # 1. Cache disabled: the pre-tentpole boot (every shape compiles,
        #    nothing persists).
        eng, out_off, t_off, _ = boot(make_cfg(None))
        eng.close()
        log(f"cold_start: cache-off boot {t_off:.1f}s")

        # 2. Cold cache: same compiles + serialize/write-back overhead.
        eng, out_cold, t_cold, d_cold = boot(make_cfg(cache_dir))
        eng.close()
        log(f"cold_start: cold boot {t_cold:.1f}s "
            f"({d_cold['writes_total']} entries written)")

        # 3. Warm cache: every executable deserializes.
        eng, out_warm, t_warm, d_warm = boot(make_cfg(cache_dir))
        golden_warm = all(
            np.array_equal(a, b) for a, b in zip(out_cold, out_warm)
        ) and all(np.array_equal(a, b) for a, b in zip(out_off, out_warm))
        speedup = t_cold / max(1e-9, t_warm)
        log(f"cold_start: warm boot {t_warm:.1f}s "
            f"({d_warm['hits_total']} deserialized) — {speedup:.2f}x")

        # 4. Registry-driven hot-swap rewarm of the same shape: the
        #    loader thread rebuilds + rewarms from the serving config,
        #    so the successor's executables must all come from the cache.
        from tensorflow_web_deploy_tpu.serving.batcher import Batcher

        batcher = Batcher(eng, max_batch=eng.max_batch, name="cold_start")
        batcher.start()
        registry = ModelRegistry(make_cfg(cache_dir))
        registry.adopt("mobilenet_v2", eng, batcher, make_cfg(cache_dir).model)
        before = aotcache.stats()
        t0 = time.perf_counter()
        mv = registry.swap(wait=True, timeout=600.0)
        t_swap = time.perf_counter() - t0
        after = aotcache.stats()
        swap_hits = after["hits_total"] - before["hits_total"]
        swap_misses = after["misses_total"] - before["misses_total"]
        swap_ok = mv.state == SERVING
        registry.stop(grace_s=5.0)
        log(f"cold_start: hot-swap rewarm {t_swap:.1f}s "
            f"({swap_hits} deserialized, {swap_misses} misses)")

        # 5. int8 parity gate on the deserialize path (single-bucket
        #    config keeps the quant phase cheap).
        int8_dir = tempfile.mkdtemp(prefix="bench_aot_i8_")
        try:
            e1, o1, _, _ = boot(make_cfg(int8_dir, dtype="int8", multi=False))
            p_cold = bool(e1.parity and e1.parity.get("pass"))
            e1.close()
            e2, o2, _, d_i8 = boot(make_cfg(int8_dir, dtype="int8",
                                            multi=False))
            p_warm = bool(e2.parity and e2.parity.get("pass"))
            int8_identical = all(
                np.array_equal(a, b) for a, b in zip(o1, o2))
            e2.close()
        finally:
            shutil.rmtree(int8_dir, ignore_errors=True)
        log(f"cold_start: int8 parity cold={p_cold} warm={p_warm} "
            f"({d_i8['hits_total']} deserialized)")

        # 6. Poisoned cache: every entry garbage; the boot must finish
        #    with zero errors and bit-identical outputs.
        for f in os.listdir(cache_dir):
            if f.endswith(".aotx"):
                with open(os.path.join(cache_dir, f), "wb") as fh:
                    fh.write(b"poisoned")
        poison_errors = 0
        try:
            eng_p, out_p, t_p, d_p = boot(make_cfg(cache_dir))
            eng_p.close()
            poison_identical = all(
                np.array_equal(a, b) for a, b in zip(out_cold, out_p))
        except Exception:
            poison_errors = 1
            poison_identical = False
            d_p, t_p = {}, None
        log(f"cold_start: poisoned boot errors={poison_errors} "
            f"corrupt={d_p.get('corrupt_total')}")

        result.update({
            "boot_cache_off_s": round(t_off, 2),
            "boot_cold_s": round(t_cold, 2),
            "boot_warm_s": round(t_warm, 2),
            "speedup_warm_vs_cold": round(speedup, 2),
            "speedup_warm_vs_off": round(t_off / max(1e-9, t_warm), 2),
            "cold": d_cold,
            "warm": d_warm,
            "golden_bit_identical": bool(golden_warm),
            "hot_swap": {
                "rewarm_s": round(t_swap, 2),
                "deserialized": swap_hits,
                "misses": swap_misses,
                "reached_serving": bool(swap_ok),
            },
            "int8": {
                "parity_cold": p_cold,
                "parity_warm": p_warm,
                "deserialized": d_i8["hits_total"],
                "bit_identical": int8_identical,
            },
            "poisoned": {
                "errors": poison_errors,
                "corrupt": d_p.get("corrupt_total"),
                "boot_s": round(t_p, 2) if t_p else None,
                "bit_identical": bool(poison_identical),
            },
            "pass": bool(
                speedup >= 3.0 and golden_warm and swap_ok
                and p_cold and p_warm and int8_identical
                and poison_errors == 0 and poison_identical
            ),
        })
        return result
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)


def host_path_bench(canvas=512, wire="rgb", n_images=8, min_s=0.4):
    """Host-side decode→slab throughput, no device involved: synthetic
    JPEGs decoded by the native extension (or PIL fallback) straight into
    staging-slab rows — the per-image host data-movement cost the
    slot-leased request path pays. MB/s counts canvas bytes landed in the
    slab; this is the BENCH-tracked number for the host pipeline."""
    from tensorflow_web_deploy_tpu import native
    from tensorflow_web_deploy_tpu.serving.engine import StagingSlab
    from tools.loadgen import synthetic_jpegs

    images = synthetic_jpegs(n=n_images, size=min(480, canvas - 32))
    slab = StagingSlab((canvas, canvas, 3), bucket=n_images, packed=True)
    use_native = native.available()
    decoded = 0
    nbytes = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < min_s:
        for i, data in enumerate(images):
            row = slab.row(i)
            if use_native:
                plan = native.plan_decode(data, (canvas,), wire)
                hw = plan and native.decode_into_row(data, row, plan[0], wire)
                if not hw:
                    use_native = False
                    continue
            else:
                from tensorflow_web_deploy_tpu.ops.image import (
                    decode_image, pad_to_canvas,
                )

                img = decode_image(data)
                c, hw = pad_to_canvas(img, (canvas,))
                np.copyto(row, c)
            slab.write_hw(i, hw)
            decoded += 1
            nbytes += row.nbytes
    dt = time.perf_counter() - t0
    return {
        "native_decode": use_native,
        "canvas": canvas,
        "decode_to_slab_MBps": round(nbytes / dt / 1e6, 1),
        "decode_to_slab_images_per_sec": round(decoded / dt, 1),
    }


def preprocess_bench(engine, batch, canvas, k):
    """Resize-path shootout ON HARDWARE: matmul vs pallas preprocess, scan-
    amortized. Records a compile failure (Mosaic) instead of raising."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    if engine.cfg.wire_format != "yuv420":
        return {"skipped": "pallas needs yuv420 wire"}
    canv, hws = _stacked_inputs(engine, batch, canvas, k, seed=9)
    h, w = engine.model_cfg.input_size
    out = {}
    orig_resize = engine.cfg.resize
    for mode in ("matmul", "pallas"):
        try:
            engine.cfg.resize = mode
            # Replica 0's mesh: the resize shootout is a single-stream
            # measurement (identical on every replica by construction).
            pre = engine._make_preprocess(h, w, engine._replicas[0].mesh)

            @jax.jit
            def scan_pre(canv, hws, salt):
                def body(acc, ch):
                    x = pre(ch[0], ch[1])
                    return acc + jnp.sum(x.astype(jnp.float32)), None
                acc, _ = lax.scan(body, salt, (canv, hws))
                return acc

            float(scan_pre(canv, hws, jnp.float32(0)))  # compile
            best = None
            for rep in (1, 2):
                t0 = time.perf_counter()
                float(scan_pre(canv, hws, jnp.float32(rep)))
                dt = time.perf_counter() - t0
                best = dt if best is None else min(best, dt)
            out[mode] = {"ms_per_batch": round(best / k * 1e3, 3)}
        except Exception as e:
            out[mode] = {"error": f"{type(e).__name__}: {e}"[:200]}
        finally:
            engine.cfg.resize = orig_resize
    return out


def measure_model(model_name, batch, canvas, wire, resize, n_dev, scan_k, peak):
    """Engine-level numbers for one model config (used by the per-config and
    converter-path sub-benches): scan device-resident ips + batch-1 latency."""
    out = {"model": model_name, "batch": batch}
    t0 = time.perf_counter()
    engine, cfg = make_engine(model_name, batch, canvas, wire, resize, n_dev)
    out["load_s"] = round(time.perf_counter() - t0, 1)
    ips, compile_s = scan_throughput(engine, batch, canvas, scan_k, reps=2)
    out["device_resident_images_per_sec"] = round(ips, 1)
    out["compile_s"] = round(compile_s, 1)
    b, p50, p99 = batch1_latency(engine, canvas, n_dev, reps=15)
    out["latency_ms"] = {"batch": b, "p50": round(p50, 2), "p99": round(p99, 2)}
    try:
        cost = analyze_cost(engine, batch, canvas)
        out["flops_per_image"] = cost.get("flops_per_image")
        if cost.get("flops_per_image") and peak:
            out["mfu_device_resident"] = round(
                ips * cost["flops_per_image"] / (peak * 1e12 * n_dev), 4
            )
    except Exception as e:
        log(f"cost for {model_name} unavailable: {e}")
    return out


def main() -> None:
    t_start = time.perf_counter()
    budget_s = float(os.environ.get("BENCH_BUDGET_S", "1500"))

    def budget_left():
        return budget_s - (time.perf_counter() - t_start)

    import jax

    from tensorflow_web_deploy_tpu.serving.costmodel import device_peak
    from tensorflow_web_deploy_tpu.utils.env import enable_compilation_cache

    devices = jax.devices()
    backend = devices[0].platform
    device_kind = devices[0].device_kind
    log(f"devices: {devices} (backend={backend})")
    if backend != "tpu":
        # Every metric this main prints is a device metric. A CPU timing
        # under those names is worse than no number: fail, do not fall back.
        sys.exit(
            f"bench.py measures a TPU and found platform {backend!r} "
            f"({device_kind}). Run it on the chip; the CPU-mesh blocks are "
            "`python bench.py <block>` and label themselves backend: cpu."
        )
    # persistent executable cache: repeat runs skip the big compiles
    enable_compilation_cache()

    model_name = os.environ.get("BENCH_MODEL", "native:inception_v3")
    batch = int(os.environ.get("BENCH_BATCH", "32"))
    iters = int(os.environ.get("BENCH_ITERS", "20"))
    # Canvas ≈ model input size by default: the host→device hop carries the
    # fewest bytes (decoded uint8 at final resolution).
    # 300 (not 299): the default yuv420 wire needs canvas % 4 == 0.
    wire = os.environ.get("BENCH_WIRE", "yuv420")
    resize = os.environ.get("BENCH_RESIZE", "matmul")
    canvas = int(os.environ.get("BENCH_CANVAS", "300" if wire == "yuv420" else "299"))

    n_dev = len(devices)
    batch = max(batch, n_dev)
    batch = (batch // n_dev) * n_dev
    # 64 batches per dispatch, sized against a remote-device link that no
    # longer exists (module docstring); the depth a chip on the host's own
    # PCIe needs is not measured on a directly attached chip (ROADMAP D1).
    scan_k = int(os.environ.get("BENCH_SCAN_BATCHES", "64"))
    depth = int(os.environ.get("BENCH_DEPTH", "4"))
    peak = device_peak(device_kind)[0] / 1e12  # bf16 TFLOP/s per chip

    t0 = time.perf_counter()
    engine, cfg = make_engine(model_name, batch, canvas, wire, resize, n_dev)
    log(f"engine loaded in {time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()
    engine.warmup()
    log(f"warmup (compile) in {time.perf_counter() - t0:.1f}s")

    # e2e: real host buffers in, every output fetched — the client-visible
    # number, directly comparable to the batcher's production pattern.
    ips, wire_mbps = e2e_pipeline(engine, batch, canvas, iters, depth)
    log(f"e2e throughput: {ips:.1f} images/sec (batch={batch}, {iters} iters, "
        f"host->device {wire_mbps:.1f} MB/s)")

    # Device-resident ceiling: scan-amortized single dispatch (module
    # docstring, "Measurement methodology").
    dev_method = (f"lax.scan x{scan_k} in one dispatch, forced scalar fetch, "
                  "salted reps")
    dev_ips, scan_compile_s = scan_throughput(engine, batch, canvas, scan_k)
    log(f"device-resident (scan×{scan_k}): {dev_ips:.1f} images/sec "
        f"({batch * 1e3 / dev_ips:.2f} ms/batch; scan compile {scan_compile_s:.0f}s)")

    # Transfer/compute overlap: same bytes through a trivial program.
    overlap = None
    try:
        wire_ips, wire_only_mbps = overlap_check(engine, batch, canvas, iters, depth)
        overlap = {
            "wire_only_images_per_sec": round(wire_ips, 1),
            "wire_only_MBps": round(wire_only_mbps, 1),
            "e2e_over_wire_only": round(ips / wire_ips, 3) if wire_ips else None,
        }
        log(f"overlap check: wire-only {wire_ips:.1f} img/s @ {wire_only_mbps:.1f} MB/s "
            f"-> e2e/wire-only = {ips / wire_ips:.2f} "
            f"(≈1.0 means link-saturated with compute fully hidden)")
    except Exception as e:
        log(f"overlap check failed: {e}")

    # Analytic cost + MFU (flops are backend-independent; MFU needs a peak).
    cost = analyze_cost(engine, batch, canvas)
    flops_img = cost.get("flops_per_image")
    mfu = mfu_dev = None
    if flops_img and peak:
        total_peak = peak * 1e12 * n_dev
        mfu = round(ips * flops_img / total_peak, 4)
        mfu_dev = round(dev_ips * flops_img / total_peak, 4)
        log(f"MFU: e2e {mfu:.2%}, device-resident {mfu_dev:.2%} "
            f"({flops_img / 1e9:.2f} GFLOP/image, peak {peak:.0f} TF/chip × {n_dev})")
    elif flops_img:
        log(f"analytic cost: {flops_img / 1e9:.2f} GFLOP/image "
            f"(no MFU: backend={backend})")

    small_b, p50, p99 = batch1_latency(engine, canvas, n_dev)
    log(f"batch-{small_b} latency: p50={p50:.2f}ms p99={p99:.2f}ms")

    # Throughput mode: the batch-32 headline is latency-shaped (batch rides
    # the sublane dim; the stem convs starve the MXU). A fat batch is the
    # classic TPU throughput answer — measured here so the serving story
    # covers both operating points (BASELINE config 3's "throughput mode").
    throughput = None
    tp_batch = int(os.environ.get("BENCH_THROUGHPUT_BATCH", "256"))
    tp_batch = (tp_batch // n_dev) * n_dev  # shard evenly, like BENCH_BATCH
    if tp_batch and tp_batch > batch and budget_left() > 180:
        tp_eng = None
        try:
            tp_eng, _ = make_engine(model_name, tp_batch, canvas, wire, resize, n_dev)
            tp_ips, tp_compile = scan_throughput(tp_eng, tp_batch, canvas, k=8)
            throughput = {
                "batch": tp_batch,
                "device_resident_images_per_sec": round(tp_ips, 1),
            }
            if flops_img and peak:
                throughput["mfu_device_resident"] = round(
                    tp_ips * flops_img / (peak * 1e12 * n_dev), 4
                )
            log(f"throughput mode (batch {tp_batch}): {tp_ips:.1f} img/s "
                f"(compile {tp_compile:.0f}s) -> {throughput}")
        except Exception as e:
            throughput = {"error": f"{type(e).__name__}: {e}"[:200]}
            log(f"throughput-mode bench failed: {e}")
        finally:
            del tp_eng  # free the fat batch's device buffers either way

    # ---------------- optional sections (each budget-gated + fail-soft) ----
    http = None
    pipeline = None
    if os.environ.get("BENCH_HTTP", "1") != "0":
        # Gate covers the ladder engine's build + per-bucket warmup inside
        # http_bench (minutes on a cold compilation cache), not just load.
        if budget_left() > 300:
            try:
                http = http_bench(engine, cfg, float(os.environ.get("BENCH_HTTP_SECS", "8")))
                # The depth-1-vs-2 overlap proof rides out of http_bench
                # (it reuses the warmed ladder engine) but reports as its
                # own top-level block.
                pipeline = http.pop("pipeline", None)
                log(f"http: {http}")
                log(f"pipeline: {pipeline}")
            except Exception as e:
                http = {"error": f"{type(e).__name__}: {e}"[:200]}
                log(f"http bench failed: {e}")
        else:
            http = {"skipped": "budget"}

    # Hot swap under load: error rate + p99 while the model registry
    # rebuilds/rewarms the model and atomically shifts traffic — the
    # measured zero-downtime number (BENCH_HOT_SWAP=0 disables).
    hot_swap = None
    if os.environ.get("BENCH_HOT_SWAP", "1") != "0":
        # The swap rebuilds the ladder engine on the loader thread, so the
        # gate must cover TWO ladder builds + warmups past this point.
        if budget_left() > 420:
            try:
                hot_swap = hot_swap_bench(
                    engine, cfg, float(os.environ.get("BENCH_HTTP_SECS", "8"))
                )
                log(f"hot swap: {hot_swap}")
            except Exception as e:
                hot_swap = {"error": f"{type(e).__name__}: {e}"[:200]}
                log(f"hot-swap bench failed: {e}")
        else:
            hot_swap = {"skipped": "budget"}

    # Response cache under heavy-tailed traffic: goodput with the cache on
    # vs --cache-bytes 0, coalesce count, zero-stale hot-swap
    # (BENCH_CACHE=0 disables; `python bench.py cache` runs only this).
    cache = None
    if os.environ.get("BENCH_CACHE", "1") != "0":
        if budget_left() > 240:
            try:
                cache = cache_bench(
                    secs=float(os.environ.get("BENCH_HTTP_SECS", "8"))
                )
                log(f"cache: {cache}")
            except Exception as e:
                cache = {"error": f"{type(e).__name__}: {e}"[:200]}
                log(f"cache bench failed: {e}")
        else:
            cache = {"skipped": "budget"}

    # Bulk offline jobs: batch-256 job throughput vs the interactive
    # open-loop path + the isolation p99 pair + restart-resume proof
    # (BENCH_BULK=0 disables; `python bench.py bulk` runs only this).
    bulk = None
    if os.environ.get("BENCH_BULK", "1") != "0":
        if n_dev < 2:
            bulk = {"skipped": f"{n_dev} device(s); needs >=2"}
        elif budget_left() > 300:
            try:
                bulk = bulk_bench(
                    secs=float(os.environ.get("BENCH_HTTP_SECS", "8"))
                )
                log(f"bulk: {bulk}")
            except Exception as e:
                bulk = {"error": f"{type(e).__name__}: {e}"[:200]}
                log(f"bulk bench failed: {e}")
        else:
            bulk = {"skipped": "budget"}

    # Replica-scaling curve: HTTP open-loop img/s at placement replicas=
    # 1→2→4→8 over this mesh (BENCH_MESH_SCALING=0 disables). Needs >=2
    # devices; the canonical run is the 8-device virtual CPU mesh
    # (`python bench.py mesh_scaling`).
    mesh_scaling = None
    if os.environ.get("BENCH_MESH_SCALING", "1") != "0":
        if n_dev < 2:
            mesh_scaling = {"skipped": f"{n_dev} device(s); needs >=2"}
        elif budget_left() > 300:
            try:
                mesh_scaling = mesh_scaling_bench(
                    secs=float(os.environ.get("BENCH_HTTP_SECS", "8"))
                )
                log(f"mesh scaling: {mesh_scaling}")
            except Exception as e:
                mesh_scaling = {"error": f"{type(e).__name__}: {e}"[:200]}
                log(f"mesh-scaling bench failed: {e}")
        else:
            mesh_scaling = {"skipped": "budget"}

    # Host path: decode→slab MB/s on this machine (cheap, device-free) —
    # BENCH_* tracks the host pipeline from this block on.
    host_path = None
    try:
        host_path = host_path_bench()
        log(f"host path (decode→slab): {host_path}")
    except Exception as e:
        host_path = {"error": f"{type(e).__name__}: {e}"[:200]}
        log(f"host-path bench failed: {e}")

    pre_bench = None
    if os.environ.get("BENCH_PREPROCESS", "1") != "0":
        if budget_left() > 60:
            try:
                pre_bench = preprocess_bench(engine, batch, canvas, scan_k)
                log(f"preprocess resize: {pre_bench}")
            except Exception as e:
                pre_bench = {"error": f"{type(e).__name__}: {e}"[:200]}
        else:
            pre_bench = {"skipped": "budget"}

    converter = None
    conv_names = [
        c for c in os.environ.get(
            "BENCH_CONVERTER_CONFIGS",
            "inception_v3,mobilenet_v2,resnet50,ssd_mobilenet",
        ).split(",") if c
    ]
    if os.environ.get("BENCH_CONVERTER", "1") != "0" and conv_names:
        # One row per preset through the frozen-.pb converter path (the
        # native rows live under "configs"): VERDICT proof debt was that
        # only Inception had a converter-path number. Presets resolve to
        # artifacts/<name>.pb with the right task/output names (ssd needs
        # its explicit raw_boxes/raw_scores/anchors sinks).
        import contextlib

        from tools.make_artifacts import ensure_artifacts

        converter = {}
        art_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "artifacts")
        for name in conv_names:
            # First row's gate is taller: it may pay the TF import + freeze.
            if budget_left() < (240 if not converter else 180):
                converter[name] = {"skipped": "budget"}
                continue
            try:
                # stdout carries exactly ONE JSON line; artifact-build
                # progress goes to stderr with the rest of the narration.
                with contextlib.redirect_stdout(sys.stderr):
                    ensure_artifacts([name], art_dir)
                # canvas ≈ model input size, % 4 for the yuv420 wire.
                c_canvas = (304 if "ssd" in name
                            else 300 if "inception" in name else 228)
                converter[name] = measure_model(
                    name, batch, c_canvas, wire, resize,
                    n_dev, max(4, scan_k // 2), peak,
                )
                log(f"converter path ({name}.pb): {converter[name]}")
            except Exception as e:
                converter[name] = {"error": f"{type(e).__name__}: {e}"[:200]}
                log(f"converter-path bench for {name} failed: {e}")

    configs = None
    cfg_names = [
        c for c in os.environ.get(
            "BENCH_CONFIGS", "mobilenet_v2,resnet50,ssd_mobilenet"
        ).split(",") if c
    ]
    if cfg_names:
        configs = {}
        for name in cfg_names:
            if budget_left() < 180:
                configs[name] = {"skipped": "budget"}
                continue
            try:
                # canvas ≈ model input size, % 4 for the yuv420 wire:
                # 224 -> 228, 300 -> 304
                c_canvas = 304 if "ssd" in name else 228
                configs[name] = measure_model(
                    f"native:{name}", batch, c_canvas, wire, resize, n_dev,
                    max(4, scan_k // 2), peak,
                )
                log(f"config {name}: {configs[name]}")
            except Exception as e:
                configs[name] = {"error": f"{type(e).__name__}: {e}"[:200]}
                log(f"config {name} failed: {e}")

    if os.environ.get("BENCH_REF") == "live":
        try:
            ref_ips = measure_ref_live()
            ref_sub = "tf-cpu-live"
        except Exception as e:  # TF missing/broken: fall back to stored
            log(f"live ref measurement failed ({e}); using stored")
            ref_ips, ref_sub = STORED_REF["images_per_sec"], STORED_REF["substrate"]
    else:
        ref_ips, ref_sub = STORED_REF["images_per_sec"], STORED_REF["substrate"]

    print(
        json.dumps(
            {
                "metric": f"{cfg.model.name} images/sec (serving path, batch={batch}, "
                f"wire={wire}, {n_dev}x {device_kind})",
                "value": round(ips, 2),
                "unit": "images/sec",
                "vs_baseline": round(ips / ref_ips, 2),
                "baseline": {"images_per_sec": ref_ips, "substrate": ref_sub},
                "backend": backend,
                "device_kind": device_kind,
                "n_devices": n_dev,
                "latency_ms": {"batch": small_b, "p50": round(p50, 2), "p99": round(p99, 2)},
                "device_resident_images_per_sec": round(dev_ips, 2),
                "methodology": {
                    "device_resident": dev_method,
                    "e2e": "distinct host buffers, every output fetched",
                },
                "host_to_device_MBps": round(wire_mbps, 1),
                "overlap": overlap,
                "flops_per_image": flops_img,
                "hbm_bytes_per_image": cost.get("hbm_bytes_per_image"),
                "mfu": mfu,
                "mfu_device_resident": mfu_dev,
                "throughput_mode": throughput,
                "http": http,
                "pipeline": pipeline,
                "hot_swap": hot_swap,
                "cache": cache,
                "bulk": bulk,
                "mesh_scaling": mesh_scaling,
                "host_path": host_path,
                "preprocess_resize": pre_bench,
                "converter_path": converter,
                "configs": configs,
                "wall_s": round(time.perf_counter() - t_start, 1),
            }
        ),
        flush=True,
    )


def mesh_scaling_main() -> None:
    """``python bench.py mesh_scaling`` — ONLY the replica-scaling curve,
    on the 8-device virtual CPU mesh (the acceptance run for mesh-wide
    serving; works on any machine, no TPU needed). Prints one JSON line."""
    # The virtual devices must exist before jax's first backend touch:
    # XLA_FLAGS is set here, ahead of the import (tests/conftest.py asks
    # for the same count through jax_num_cpu_devices as well).
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

    import jax

    from tensorflow_web_deploy_tpu.utils.env import enable_compilation_cache

    enable_compilation_cache()
    n_dev = len(jax.devices())
    log(f"mesh_scaling: {n_dev} {jax.default_backend()} devices")
    out = mesh_scaling_bench(
        secs=float(os.environ.get("BENCH_HTTP_SECS", "8"))
    )
    print(
        json.dumps({
            "metric": "HTTP open-loop images/sec vs placement replica count "
                      f"({n_dev}-device virtual {jax.default_backend()} mesh)",
            "unit": "images/sec",
            "backend": jax.default_backend(),
            "n_devices": n_dev,
            "mesh_scaling": out,
        }),
        flush=True,
    )


def cache_main() -> None:
    """``python bench.py cache`` — ONLY the response-cache block, on the
    8-device virtual CPU mesh (the acceptance run for the content-
    addressed cache; works on any machine, no TPU needed). Prints one JSON
    line."""
    # Same virtual-mesh bootstrap as mesh_scaling_main: the devices must
    # exist before jax's first backend touch.
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

    import jax

    from tensorflow_web_deploy_tpu.utils.env import enable_compilation_cache

    enable_compilation_cache()
    n_dev = len(jax.devices())
    log(f"cache bench: {n_dev} {jax.default_backend()} devices")
    out = cache_bench(secs=float(os.environ.get("BENCH_HTTP_SECS", "8")))
    print(
        json.dumps({
            "metric": "HTTP open-loop goodput: response cache at Zipf("
                      f"{out.get('zipf_s')}) vs --cache-bytes 0 "
                      f"({n_dev}-device virtual {jax.default_backend()} mesh)",
            "unit": "images/sec",
            "backend": jax.default_backend(),
            "n_devices": n_dev,
            "cache": out,
        }),
        flush=True,
    )


def bulk_main() -> None:
    """``python bench.py bulk`` — ONLY the bulk-jobs block, on the
    8-device virtual CPU mesh (the acceptance run for /jobs; works on any
    machine, no TPU needed). Prints one JSON line."""
    # Same virtual-mesh bootstrap as mesh_scaling_main: the devices must
    # exist before jax's first backend touch.
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

    import jax

    from tensorflow_web_deploy_tpu.utils.env import enable_compilation_cache

    enable_compilation_cache()
    n_dev = len(jax.devices())
    log(f"bulk bench: {n_dev} {jax.default_backend()} devices")
    out = bulk_bench(secs=float(os.environ.get("BENCH_HTTP_SECS", "8")))
    print(
        json.dumps({
            "metric": "bulk-job images/sec vs interactive open-loop + "
                      f"isolation p99 ({n_dev}-device virtual "
                      f"{jax.default_backend()} mesh)",
            "unit": "images/sec",
            "backend": jax.default_backend(),
            "n_devices": n_dev,
            "bulk": out,
        }),
        flush=True,
    )


def overload_main() -> None:
    """``python bench.py overload`` — ONLY the offered-load-vs-goodput
    sweep, on the 8-device virtual CPU mesh (works on any machine, no TPU
    needed). Prints one JSON line."""
    # Same virtual-mesh bootstrap as mesh_scaling_main: the devices must
    # exist before jax's first backend touch.
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

    import jax

    from tensorflow_web_deploy_tpu.utils.env import enable_compilation_cache

    enable_compilation_cache()
    n_dev = len(jax.devices())
    log(f"overload bench: {n_dev} {jax.default_backend()} devices")
    out = overload_bench(secs=float(os.environ.get("BENCH_SWEEP_STEP_S", "5")))
    print(
        json.dumps({
            "metric": "offered load vs goodput past saturation "
                      f"({n_dev}-device virtual {jax.default_backend()} mesh)",
            "unit": "images/sec",
            "backend": jax.default_backend(),
            "n_devices": n_dev,
            "overload": out,
        }),
        flush=True,
    )


def ragged_main() -> None:
    """``python bench.py ragged`` — ONLY the packed-wire-vs-classic
    block, on the 8-device virtual CPU mesh (the acceptance run for the
    ragged wire; works on any machine, no TPU needed). Prints one JSON
    line."""
    # Same virtual-mesh bootstrap as mesh_scaling_main: the devices must
    # exist before jax's first backend touch.
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

    import jax

    from tensorflow_web_deploy_tpu.utils.env import enable_compilation_cache

    enable_compilation_cache()
    n_dev = len(jax.devices())
    log(f"ragged bench: {n_dev} {jax.default_backend()} devices")
    out = ragged_bench(secs=float(os.environ.get("BENCH_HTTP_SECS", "8")))
    print(
        json.dumps({
            "metric": "padding fractions + open-loop images/sec: ragged "
                      "packed wire vs host pad-to-canvas "
                      f"({n_dev}-device virtual {jax.default_backend()} mesh)",
            "unit": "images/sec",
            "backend": jax.default_backend(),
            "n_devices": n_dev,
            "ragged": out,
        }),
        flush=True,
    )


def raw_speed_main() -> None:
    """``python bench.py raw_speed`` — ONLY the quantized raw-speed-tier
    block (per-(preset, dtype) img/s + roofline attribution + the fused
    depthwise A/B), on the 8-device virtual CPU mesh. Prints one JSON
    line."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

    import jax

    from tensorflow_web_deploy_tpu.utils.env import enable_compilation_cache

    enable_compilation_cache()
    n_dev = len(jax.devices())
    log(f"raw_speed bench: {n_dev} {jax.default_backend()} devices")
    out = raw_speed_bench(secs=float(os.environ.get("BENCH_RAW_SECS", "3")))
    print(
        json.dumps({
            "metric": "raw-speed tier: images/sec + fraction of binding "
                      "roofline ceiling per (preset, dtype), f32 vs bf16 "
                      "vs int8 + fused depthwise A/B "
                      f"({n_dev}-device virtual {jax.default_backend()} mesh)",
            "unit": "images/sec",
            "backend": jax.default_backend(),
            "n_devices": n_dev,
            "raw_speed": out,
        }),
        flush=True,
    )


def telemetry_main() -> None:
    """``python bench.py telemetry`` — ONLY the sampler-overhead A/B +
    SLO alert episode, on the 8-device virtual CPU mesh. Prints one JSON
    line (the block bench_diff's 'telemetry' sentinel reads)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

    import jax

    from tensorflow_web_deploy_tpu.utils.env import enable_compilation_cache

    enable_compilation_cache()
    n_dev = len(jax.devices())
    log(f"telemetry bench: {n_dev} {jax.default_backend()} devices")
    out = telemetry_bench(secs=float(os.environ.get("BENCH_HTTP_SECS", "8")))
    print(
        json.dumps({
            "metric": "telemetry sampler overhead (goodput on/off at "
                      "matched offered load) + SLO burn-rate alert "
                      "fire/clear under chaos slow_replica "
                      f"({n_dev}-device virtual {jax.default_backend()} mesh)",
            "unit": "images/sec",
            "backend": jax.default_backend(),
            "n_devices": n_dev,
            "telemetry": out,
        }),
        flush=True,
    )


def cold_start_main() -> None:
    """``python bench.py cold_start`` — ONLY the AOT-cache boot-to-SERVING
    A/B (off/cold/warm), hot-swap rewarm, parity gates and poisoned-cache
    recovery, on the 8-device virtual CPU mesh. Prints one JSON line (the
    block bench_diff's 'cold_start' sentinel reads). The XLA compilation
    cache is deliberately NOT enabled here: it would absorb the compiles
    this bench exists to measure."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

    import jax

    n_dev = len(jax.devices())
    log(f"cold_start bench: {n_dev} {jax.default_backend()} devices")
    out = cold_start_bench(secs=float(os.environ.get("BENCH_HTTP_SECS", "6")))
    print(
        json.dumps({
            "metric": "boot-to-SERVING wall clock, AOT executable cache "
                      "off/cold/warm + registry hot-swap rewarm "
                      f"({n_dev}-device virtual {jax.default_backend()} mesh)",
            "unit": "seconds",
            "backend": jax.default_backend(),
            "n_devices": n_dev,
            "cold_start": out,
        }),
        flush=True,
    )


def pipeline_dag_bench(secs=6.0) -> dict:
    """Pipeline-DAG block (BENCH-tracked, ISSUE 20 acceptance): the
    detect→crop→classify composition served device-resident by ONE
    ``POST /pipelines/{name}`` vs the client-side two-request composition
    (det ``/predict`` → client crop + JPEG re-encode → cls ``/predict``)
    at matched closed-loop concurrency on the SAME two engines behind the
    SAME registry server. Reports e2e img/s + p99 for both paths, D2H
    bytes/image for both paths (the padded detector output bucket the DAG
    executor never fetches is the gap — ROADMAP item 4's measurement
    debt), the per-stage seconds/images/d2h split from /stats, and a
    golden-parity gate against the stage-by-stage host reference
    (``run_batch`` → ``crop_resize_host`` → ``run_batch``).

    The composition client is deliberately GENEROUS to the baseline: the
    originals are pre-decoded outside the timed loop, the crops are
    resized client-side to the classifier's input before re-encode (the
    cheapest faithful bytes a client could ship), and all crops of one
    image ride ONE multipart request. The response cache is off
    (``cache_bytes=0``) so both paths pay full compute — this is a
    data-motion A/B, not a caching one.
    """
    import dataclasses
    import io
    import random
    import threading
    import urllib.request

    from PIL import Image

    from tensorflow_web_deploy_tpu.ops.dag_glue import crop_resize_host
    from tensorflow_web_deploy_tpu.serving.engine import InferenceEngine
    from tensorflow_web_deploy_tpu.serving.http import (
        App, make_http_server, shutdown_gracefully,
    )
    from tensorflow_web_deploy_tpu.serving.jobs import format_result_row
    from tensorflow_web_deploy_tpu.serving.registry import ModelRegistry
    from tensorflow_web_deploy_tpu.utils.config import ServerConfig, model_config
    from tools.loadgen import (
        HttpClient, Recorder, _job_multipart, closed_loop, percentile,
        synthetic_jpegs,
    )

    import jax

    n_dev = len(jax.devices())
    max_crops = 8
    topk = 5
    workers = int(os.environ.get("BENCH_HTTP_WORKERS", "16"))
    corpus = int(os.environ.get("BENCH_DAG_CORPUS", "24"))
    # Camera-sized originals: the composition baseline's between-stage
    # host cost (client crop + re-encode, server re-decode) scales with
    # the original's resolution — small synthetic thumbnails would
    # understate exactly the term the DAG removes.
    img_px = int(os.environ.get("BENCH_DAG_IMAGE_PX", "768"))

    det_mc = model_config("native:ssd_mobilenet")
    cls_mc = model_config("native:mobilenet_v2")
    for mc, size in ((det_mc, (96, 96)), (cls_mc, (64, 64))):
        mc.zoo_width = float(os.environ.get("BENCH_MESH_WIDTH", "0.35"))
        mc.zoo_classes = 101
        mc.input_size = size
        mc.dtype = "float32"
        if jax.default_backend() == "cpu" and n_dev > 1:
            # Same reasoning as cache_bench: replicated single-device
            # placement runs no collectives, so the DAG path's direct
            # per-request dispatches and the batcher path's coalesced ones
            # can interleave freely on the shared virtual mesh.
            mc.placement = f"replicas={n_dev}"

    # Detector batch buckets include 1: the DAG executor dispatches ONE
    # image per request (the composition baseline's batcher still
    # coalesces to the 8-bucket). The classifier's 8-bucket is the crop
    # batch both paths use.
    det_cfg = ServerConfig(model=det_mc, canvas_buckets=(96,),
                           batch_buckets=(1, 8), max_batch=8,
                           max_delay_ms=2.0, warmup=True,
                           http_workers=workers)
    cls_cfg = dataclasses.replace(det_cfg, model=cls_mc,
                                  canvas_buckets=(64,), batch_buckets=(8,))
    t0 = time.perf_counter()
    det_eng = InferenceEngine(det_cfg)
    det_eng.warmup()
    cls_eng = InferenceEngine(cls_cfg)
    cls_eng.warmup()
    log(f"dag bench engines+warmup ready in {time.perf_counter() - t0:.1f}s")

    app_cfg = dataclasses.replace(
        det_cfg, cache_bytes=0,
        pipelines=(f"pipeline={det_mc.name}>{cls_mc.name}",),
        pipeline_max_crops=max_crops)
    registry = ModelRegistry(app_cfg)
    registry.adopt(det_mc.name, det_eng,
                   registry.build_batcher(det_eng, det_mc.name), det_mc)
    registry.adopt(cls_mc.name, cls_eng,
                   registry.build_batcher(cls_eng, cls_mc.name), cls_mc)
    app = App.from_registry(registry, app_cfg)
    srv = make_http_server(app, "127.0.0.1", 0, pool_size=workers)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{srv.server_address[1]}"

    images = synthetic_jpegs(n=corpus, size=img_px)
    decoded = [np.asarray(Image.open(io.BytesIO(b)).convert("RGB"))
               for b in images]

    def d2h_total():
        return det_eng.d2h_bytes_total + cls_eng.d2h_bytes_total

    out = {
        "pipeline": f"{det_mc.name}>{cls_mc.name}",
        "width": det_mc.zoo_width, "image_px": img_px, "corpus": corpus,
        "max_crops": max_crops, "topk": topk, "workers": workers,
        "secs_per_path": secs,
    }
    try:
        # ---------------- DAG path: one device-resident request/image
        dag_url = f"{base}/pipelines/pipeline?topk={topk}"
        closed_loop(dag_url, images, 4, min(2.0, secs / 2), 120.0,
                    Recorder())  # warm: glue jit + direct-dispatch path
        rec = Recorder()
        d0 = d2h_total()
        t0d = time.perf_counter()
        closed_loop(dag_url, images, workers, secs, 120.0, rec)
        dag_ips = rec.images_completed_by(t0d + secs) / secs
        with rec.lock:
            lat = sorted(rec.latencies_ms)
            dag_completed = len(lat)
            dag_errors = rec.errors
        dag_d2h = (d2h_total() - d0) / max(1, dag_completed)
        out["dag"] = {
            "images_per_sec": round(dag_ips, 1),
            "completed": dag_completed, "errors": dag_errors,
            "p50_ms": round(percentile(lat, 50), 1) if lat else None,
            "p99_ms": round(percentile(lat, 99), 1) if lat else None,
            "d2h_bytes_per_image": round(dag_d2h, 1),
            "requests_per_image": 1,
        }
        log(f"dag path: {out['dag']}")

        # -------- composition baseline: two requests + host crop/encode
        det_path = f"/predict?model={det_mc.name}"
        cls_path = f"/predict?model={cls_mc.name}&topk={topk}"
        cls_in = cls_mc.input_size[0]

        def crops_payload(idx, dets):
            px = decoded[idx]
            h, w = px.shape[:2]
            files = []
            for i, d in enumerate(dets[:max_crops]):
                y0, x0, y1, x1 = d["box"]
                y0 = min(max(int(y0), 0), h - 2)
                x0 = min(max(int(x0), 0), w - 2)
                y1 = min(max(int(y1), y0 + 2), h)
                x1 = min(max(int(x1), x0 + 2), w)
                crop = Image.fromarray(px[y0:y1, x0:x1]).resize(
                    (cls_in, cls_in), Image.BILINEAR)
                buf = io.BytesIO()
                crop.save(buf, format="JPEG", quality=90)
                files.append((f"c{i}.jpg", buf.getvalue()))
            return _job_multipart(files)

        def run_composition(n_workers, duration, rec):
            stop_at = time.perf_counter() + duration

            def worker(seed):
                rnd = random.Random(seed)
                c = HttpClient(base + det_path, 120.0)
                try:
                    while time.perf_counter() < stop_at:
                        idx = rnd.randrange(len(images))
                        t_s = time.perf_counter()
                        try:
                            st, data = c.post(images[idx], "image/jpeg",
                                              path=det_path)
                            if st != 200:
                                rec.err(f"det status {st}")
                                continue
                            dets = json.loads(data).get("detections", [])
                            if dets:
                                body, ctype = crops_payload(idx, dets)
                                st2, data2 = c.post(body, ctype,
                                                    path=cls_path)
                                if st2 != 200:
                                    rec.err(f"cls status {st2}")
                                    continue
                                json.loads(data2)
                        except Exception as e:
                            rec.err(repr(e))
                            c.close()
                            continue
                        rec.ok((time.perf_counter() - t_s) * 1e3)
                finally:
                    c.close()

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(n_workers)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()

        run_composition(4, min(2.0, secs / 2), Recorder())  # warm
        rec_c = Recorder()
        c0 = d2h_total()
        t0c = time.perf_counter()
        run_composition(workers, secs, rec_c)
        comp_ips = rec_c.images_completed_by(t0c + secs) / secs
        with rec_c.lock:
            lat_c = sorted(rec_c.latencies_ms)
            comp_completed = len(lat_c)
            comp_errors = rec_c.errors
        comp_d2h = (d2h_total() - c0) / max(1, comp_completed)
        out["composition"] = {
            "images_per_sec": round(comp_ips, 1),
            "completed": comp_completed, "errors": comp_errors,
            "p50_ms": round(percentile(lat_c, 50), 1) if lat_c else None,
            "p99_ms": round(percentile(lat_c, 99), 1) if lat_c else None,
            "d2h_bytes_per_image": round(comp_d2h, 1),
            "requests_per_image": 2,
        }
        log(f"composition baseline: {out['composition']}")

        # -------- golden parity: HTTP composite vs host stage-by-stage
        c = HttpClient(base, 120.0)
        try:
            st, data = c.post(images[0], "image/jpeg",
                              path=f"/pipelines/pipeline?topk={topk}")
        finally:
            c.close()
        composite = json.loads(data) if st == 200 else {}
        canvas, hw, _orig = det_eng.prepare_bytes(images[0])
        det_out = det_eng.run_batch(np.asarray(canvas)[None],
                                    np.asarray([hw], np.int32))
        boxes, _scores, _classes, num = (np.asarray(o)[0]
                                         for o in det_out[:4])
        kept = min(int(num), max_crops)
        out_s = min(cls_eng.cfg.canvas_buckets)
        n_crops = cls_eng.pick_batch_bucket(max_crops)
        crops = crop_resize_host(np.asarray(canvas),
                                 np.asarray(hw, np.int32), boxes, num,
                                 out_s=out_s, n_crops=n_crops)
        cls_out = cls_eng.run_batch(
            crops, np.full((n_crops, 2), out_s, np.int32))
        dets = composite.get("detections", [])
        mv_cls = registry.acquire(cls_mc.name)
        try:
            mismatches, max_delta = 0, 0.0
            for i in range(min(kept, len(dets))):
                ref = format_result_row(
                    tuple(np.asarray(o)[i] for o in cls_out),
                    (out_s, out_s), topk, mv_cls)["predictions"]
                got = dets[i]["classification"]["predictions"]
                for r, g in zip(ref, got):
                    max_delta = max(max_delta,
                                    abs(r["score"] - g["score"]))
                # The glue's documented device-vs-host bound is ≤1 LSB
                # per uint8 channel, so a top-1 flip between two
                # near-tied classes is within spec — only a flip with a
                # REAL score gap is a parity failure.
                if (ref and got and ref[0]["index"] != got[0]["index"]
                        and abs(ref[0]["score"] - got[0]["score"]) > 1e-3):
                    mismatches += 1
        finally:
            registry.release(mv_cls)
        out["parity"] = {
            "status": st, "detections": kept,
            "composite_detections": len(dets),
            "top1_mismatches": mismatches,
            "max_topk_score_delta": round(max_delta, 6),
            "ok": bool(st == 200 and len(dets) == kept
                       and mismatches == 0 and max_delta <= 5e-3),
        }
        log(f"dag parity: {out['parity']}")

        # Per-stage economics from /stats (ROADMAP item 4's row: the
        # per-stage seconds/images/d2h split the spans feed).
        with urllib.request.urlopen(f"{base}/stats", timeout=30) as r:
            snap = json.loads(r.read())
        out["per_stage"] = snap["pipelines"]["pipelines"].get("pipeline")
    finally:
        shutdown_gracefully(srv, registry, grace_s=5.0)

    comp_ips = out["composition"]["images_per_sec"]
    dag_d2h = out["dag"]["d2h_bytes_per_image"]
    out["speedup_vs_composition"] = (
        round(out["dag"]["images_per_sec"] / comp_ips, 2)
        if comp_ips else None)
    out["d2h_reduction_x"] = (
        round(out["composition"]["d2h_bytes_per_image"] / dag_d2h, 2)
        if dag_d2h else None)
    out["accept"] = {
        "speedup_ok": bool((out["speedup_vs_composition"] or 0) >= 1.3),
        "d2h_ok": bool((out["d2h_reduction_x"] or 0) >= 2.0),
        "zero_errors": out["dag"]["errors"] == 0
        and out["composition"]["errors"] == 0,
        "parity_ok": out["parity"]["ok"],
    }
    return out


def pipeline_dag_main() -> None:
    """``python bench.py pipeline_dag`` — ONLY the pipeline-DAG block
    (device-resident composition vs client-side two-request composition),
    on the 8-device virtual CPU mesh. Prints one JSON line (the block
    bench_diff's 'pipeline_dag' sentinel reads)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

    import jax

    from tensorflow_web_deploy_tpu.utils.env import enable_compilation_cache

    enable_compilation_cache()
    n_dev = len(jax.devices())
    log(f"pipeline_dag bench: {n_dev} {jax.default_backend()} devices")
    out = pipeline_dag_bench(secs=float(os.environ.get("BENCH_DAG_SECS", "6")))
    print(
        json.dumps({
            "metric": "pipeline DAG: device-resident detect→crop→classify "
                      "img/s + D2H bytes/image vs client-side two-request "
                      "composition at matched concurrency "
                      f"({n_dev}-device virtual {jax.default_backend()} mesh)",
            "unit": "images/sec",
            "backend": jax.default_backend(),
            "n_devices": n_dev,
            "pipeline_dag": out,
        }),
        flush=True,
    )


if __name__ == "__main__":
    if "mesh_scaling" in sys.argv[1:]:
        mesh_scaling_main()
    elif "cache" in sys.argv[1:]:
        cache_main()
    elif "bulk" in sys.argv[1:]:
        bulk_main()
    elif "overload" in sys.argv[1:]:
        overload_main()
    elif "ragged" in sys.argv[1:]:
        ragged_main()
    elif "raw_speed" in sys.argv[1:]:
        raw_speed_main()
    elif "telemetry" in sys.argv[1:]:
        telemetry_main()
    elif "cold_start" in sys.argv[1:]:
        cold_start_main()
    elif "pipeline_dag" in sys.argv[1:]:
        pipeline_dag_main()
    else:
        main()
