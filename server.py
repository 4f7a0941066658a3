#!/usr/bin/env python
"""``python server.py`` — the reference's operator workflow, TPU-native.

BASELINE.json north star: "The existing `python server.py` + HTTP-POST
workflow runs unchanged on a TPU VM with no GPU in the loop."

    python server.py --model inception_v3 --port 8500
    curl -X POST --data-binary @cat.jpg http://localhost:8500/predict

Startup (SURVEY.md §3.1 rebuilt): parse flags → convert frozen .pb to a
jitted function → build ('data','model') mesh over the TPU chips → precompile
+ warm every serving shape → start batcher thread → serve WSGI.
"""

from __future__ import annotations

import argparse
import logging
import os
import sys

from tensorflow_web_deploy_tpu.utils.env import (
    DEFAULT_AOT_CACHE_DIR,
    enable_compilation_cache,
)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="TPU-native image inference server")
    p.add_argument("--model", action="append", default=None,
                   help="preset name, native:<zoo name> (TF-free flax models), "
                        ".pb path, or .json model config "
                        "(presets: inception_v3, mobilenet_v2, resnet50, ssd_mobilenet). "
                        "Repeatable: each --model becomes a registry entry served "
                        "at /predict?model=<name>; default: inception_v3. "
                        "An optional placement suffix picks how the model "
                        "occupies the mesh: name,replicas=N replicates it "
                        "across N device groups with independent dispatch "
                        "streams (small models), name,shard=batch shards "
                        "each batch over every chip (the default; "
                        "throughput-mode shapes). name,dtype=int8|bf16|f32 "
                        "picks the serving dtype per model (int8 = the "
                        "raw-speed tier: quantized weights + fused depthwise, "
                        "parity-gated at load); name,as=<alias> registers the "
                        "entry under a different serving name, e.g. "
                        "native:mobilenet_v2,dtype=int8,as=mv2_q next to the "
                        "bf16 primary")
    p.add_argument("--default-model", default=None, metavar="NAME",
                   help="which --model serves /predict without ?model= "
                        "(default: the first --model)")
    p.add_argument("--pipeline", action="append", default=None,
                   metavar="SPEC",
                   help="pipeline DAG served at POST /pipelines/<name> as "
                        "one device-resident request: either an inline "
                        "chain 'name=det_model@int8>cls_model@f32' "
                        "(@dtype pins a stage to a serving tier) or a "
                        "path to a JSON pipeline file. Stage models must "
                        "be among the --model entries; invalid specs "
                        "fail the boot. Repeatable.")
    p.add_argument("--pipeline-max-crops", type=int, default=8,
                   help="stage-1 detections fed to the on-device crop "
                        "glue per image (the crop batch compiles at the "
                        "batch bucket covering this)")
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8500)
    p.add_argument("--max-batch", type=int, default=32)
    p.add_argument("--max-delay-ms", type=float, default=2.0,
                   help="CAP on the batch-assembly window; the live window "
                        "adapts to queue depth unless --no-adaptive-delay")
    p.add_argument("--no-adaptive-delay", action="store_true",
                   help="pin the batch window at --max-delay-ms instead of "
                        "adapting it to queue depth")
    p.add_argument("--lease-timeout-s", type=float, default=10.0,
                   help="force-expire a leased batch slot whose decode never "
                        "commits, so a dead worker cannot wedge its batch")
    p.add_argument("--pipeline-depth", type=int, default=4,
                   help="batches in flight per canvas bucket (sealed -> "
                        "launched -> unfetched); >=2 overlaps decode of batch "
                        "N+1 with execute of batch N")
    p.add_argument("--max-queue", type=int, default=0,
                   help="bounded per-model submit queue in images: backlog at "
                        "this level fails fast with 503 + Retry-After instead "
                        "of queueing toward the request timeout (0 = "
                        "unbounded; leasing blocks at the slot cap instead)")
    p.add_argument("--jobs-dir", default=None, metavar="DIR",
                   help="enable POST /jobs bulk offline inference: job "
                        "manifests, spooled uploads, results and checkpoints "
                        "persist here (jobs resume from their checkpoint "
                        "after a restart); unset = /jobs disabled")
    p.add_argument("--jobs-batch", type=int, default=256,
                   help="bulk-job batch target (the throughput-mode "
                        "operating point); clamped to the top compiled "
                        "batch bucket, so the full 256 needs --max-batch "
                        "(or --batch-buckets) to cover it")
    p.add_argument("--jobs-max-inflight", type=int, default=2,
                   help="bulk batches allowed in flight at once — bounds "
                        "how much device time a background job may hold "
                        "while interactive traffic shares the mesh")
    p.add_argument("--cache-bytes", type=int, default=256 << 20,
                   help="byte budget for the content-addressed response "
                        "cache (decoded-canvas digest keys, single-flight "
                        "dedup of concurrent identical requests, per-model "
                        "invalidation on hot-swap); 0 disables")
    p.add_argument("--aot-cache-dir", default=DEFAULT_AOT_CACHE_DIR,
                   metavar="DIR",
                   help="AOT-serialized executable cache: warmup "
                        "deserializes previously compiled executables from "
                        "this directory instead of recompiling, so boot and "
                        "hot-swap rewarm become file reads (seconds -> "
                        "milliseconds per shape); default "
                        "<checkout>/.aot_cache; '0' or empty disables")
    p.add_argument("--http-workers", type=int, default=16,
                   help="persistent HTTP worker threads (keep-alive pool)")
    p.add_argument("--keepalive-timeout-s", type=float, default=15.0,
                   help="idle seconds before a kept-alive connection closes")
    p.add_argument("--no-warmup", action="store_true",
                   help="skip startup shape warmup (first requests pay compiles)")
    p.add_argument("--access-log", default=None, metavar="PATH",
                   help="structured JSON access log, one line per request "
                        "(trace id, per-stage timings, status); '-' for stderr")
    p.add_argument("--flight-recorder-n", type=int, default=32,
                   help="span breakdowns kept for the N slowest and N most "
                        "recent erroring requests (GET /debug/slow)")
    p.add_argument("--dtype",
                   choices=["bfloat16", "float32", "int8", "bf16", "f32"],
                   default=None,
                   help="override model compute dtype for EVERY --model "
                        "(per-model: the ,dtype= spec option); int8 "
                        "quantizes weights per-channel and serves "
                        "dequant-on-the-fly behind the numerical-parity gate")
    p.add_argument("--canvas-buckets", default=None,
                   help="comma-separated canvas sizes, e.g. 256,512,1024")
    p.add_argument("--wire-format", choices=["rgb", "yuv420"], default="rgb",
                   help="host->device canvas encoding; yuv420 halves wire bytes "
                        "(canvas buckets must be divisible by 4)")
    p.add_argument("--ragged", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="ragged wire: ship tight decoded pixels in packed "
                        "byte arenas and unpack/resize on device, instead "
                        "of host-padded full canvases (rgb wire only; "
                        "--wire-format yuv420 falls back to classic "
                        "canvases). --no-ragged restores the old wire")
    p.add_argument("--resize", choices=["matmul", "gather", "pallas"], default="matmul",
                   help="on-device resize: separable-bilinear MXU matmuls (default), "
                        "dynamic-index gathers, or the fused pallas kernel "
                        "(requires --wire-format yuv420)")
    p.add_argument("--ckpt", default=None,
                   help="serving export from tools/train.py (orbax dir); "
                        "serves fine-tuned weights with --model native:<name>")
    p.add_argument("--labels", default=None,
                   help="label-map txt override (one name per line); with "
                        "--ckpt, <export>/labels.txt is picked up automatically")
    p.add_argument("--zoo-width", type=float, default=None,
                   help="native zoo width multiplier (must match the ckpt)")
    p.add_argument("--zoo-classes", type=int, default=None,
                   help="native zoo class count (must match the ckpt)")
    p.add_argument("--log-level", default="INFO")
    p.add_argument("--slo-classes", default="interactive=1000,batch=10000",
                   metavar="NAME=MS,...",
                   help="SLO class -> default deadline in ms; requests pick "
                        "a class with ?slo= or X-SLO and may tighten the "
                        "deadline with X-Deadline-Ms / ?deadline_ms=")
    p.add_argument("--tenant-quota", default="", metavar="TENANT=RATE,...",
                   help="per-tenant admission quotas in images/s keyed by "
                        "X-Tenant ('*' sets the default for unlisted "
                        "tenants; empty/0 = unlimited)")
    p.add_argument("--tenant-burst-s", type=float, default=1.0,
                   help="token-bucket depth in seconds of quota")
    p.add_argument("--pressure-rungs", default="0.60:0.40,0.80:0.60,0.95:0.75",
                   metavar="ENTER:EXIT,...",
                   help="degradation-ladder thresholds as queue fractions. "
                        "3 rungs (the default): 1 clamps topk, 2 shrinks the "
                        "canvas bucket, 3 sheds cache-miss work. 4 rungs: "
                        "rung 3 instead reroutes eligible requests to a "
                        "loaded int8 variant of the same model (,dtype=int8"
                        ",as=…) and rung 4 sheds cache-miss work")
    p.add_argument("--chaos", default=os.environ.get("TWD_CHAOS") or None,
                   metavar="SPEC",
                   help="chaos-injection spec for fault drills, e.g. "
                        "'decode_fail=0.05,dispatch_fail=0.02,"
                        "slow_replica=0.1:50' (default: $TWD_CHAOS)")
    p.add_argument("--telemetry-interval", type=float, default=1.0,
                   metavar="S",
                   help="in-process telemetry sampler interval (seconds): "
                        "multi-resolution history rings behind "
                        "/debug/history + /debug/events and the SLO "
                        "burn-rate evaluator; 0 disables the subsystem")
    p.add_argument("--slo-objectives", default="",
                   metavar="NAME=pXX:MS:PCT,...",
                   help="SLO objectives as burn-rate alerts, e.g. "
                        "'interactive=p99:1000ms:99.9' — evaluated over "
                        "1m/5m fast + 30m slow windows, exposed as "
                        "tpu_serve_slo_burn_rate gauges and alert state")
    return p.parse_args(argv)


def build_server(args):
    """Construct (engine, batcher, app) — separated for tests.

    Every ``--model`` becomes a registry entry built+warmed inline (boot is
    fail-fast: a model that cannot load should kill startup, unlike runtime
    admin loads, which park in FAILED). The returned ``engine``/``batcher``
    are the DEFAULT model's — the pre-registry single-model shape callers
    and tests already consume; the registry rides on ``app.registry``.
    """
    # Deferred imports: --help must not initialize a TPU backend.
    import dataclasses

    from tensorflow_web_deploy_tpu.serving.engine import InferenceEngine
    from tensorflow_web_deploy_tpu.serving.http import App
    from tensorflow_web_deploy_tpu.serving.registry import ModelRegistry
    from tensorflow_web_deploy_tpu.utils.config import ServerConfig, model_config

    model_specs = list(args.model or ["inception_v3"])
    single_knobs = (args.ckpt or args.labels or args.zoo_width is not None
                    or args.zoo_classes is not None)
    if len(model_specs) > 1 and single_knobs:
        # Ambiguous fan-out: which model would get the ckpt/labels? A
        # multi-model deployment expresses per-model knobs via .json model
        # configs, one per --model.
        sys.exit(
            "--ckpt/--labels/--zoo-width/--zoo-classes apply to exactly one "
            "model; with repeated --model flags use .json model configs "
            "to carry per-model settings"
        )
    from tensorflow_web_deploy_tpu.utils.config import normalize_dtype

    mcs = []
    for spec in model_specs:
        mc = model_config(spec)
        if args.dtype:
            mc.dtype = normalize_dtype(args.dtype)
        # Registered under serve_name (the ,as= alias when present): two
        # entries may share a network (f32 primary + its int8 variant) but
        # never a serving name.
        if any(m.serve_name == mc.serve_name for m in mcs):
            sys.exit(
                f"duplicate model name '{mc.serve_name}' from --model {spec!r}"
            )
        mcs.append(mc)
    mc = mcs[0]
    if args.labels:
        mc.labels_path = args.labels
    if args.ckpt or args.zoo_width is not None or args.zoo_classes is not None:
        if mc.source != "native":
            # Never let an operator believe fine-tuned weights are live while
            # the frozen graph actually serves: these knobs only exist on the
            # native zoo path.
            sys.exit(
                "--ckpt/--zoo-width/--zoo-classes require a native zoo model "
                f"(--model native:<name>); got --model {model_specs[0]!r}"
            )
        if args.ckpt:
            mc.ckpt_path = args.ckpt
            exported_labels = os.path.join(args.ckpt, "labels.txt")
            if args.labels is None and os.path.exists(exported_labels):
                # the export's class names, not ImageNet's — a fine-tuned
                # model must not answer with "tench" for the user's class 0
                mc.labels_path = exported_labels
        if args.zoo_width is not None:
            mc.zoo_width = args.zoo_width
        if args.zoo_classes is not None:
            mc.zoo_classes = args.zoo_classes
    default_name = args.default_model or mcs[0].serve_name
    if not any(m.serve_name == default_name for m in mcs):
        sys.exit(
            f"--default-model {default_name!r} is not among the loaded models "
            f"{[m.serve_name for m in mcs]}"
        )
    default_mc = next(m for m in mcs if m.serve_name == default_name)
    kw = {}
    if args.canvas_buckets:  # through the constructor so __post_init__ validates
        kw["canvas_buckets"] = tuple(int(s) for s in args.canvas_buckets.split(","))
    cfg = ServerConfig(
        model=default_mc,
        host=args.host,
        port=args.port,
        max_batch=args.max_batch,
        max_delay_ms=args.max_delay_ms,
        adaptive_delay=not args.no_adaptive_delay,
        lease_timeout_s=args.lease_timeout_s,
        pipeline_depth=args.pipeline_depth,
        max_queue=args.max_queue,
        cache_bytes=args.cache_bytes,
        pipelines=tuple(args.pipeline or ()),
        pipeline_max_crops=args.pipeline_max_crops,
        aot_cache_dir=(args.aot_cache_dir
                       if args.aot_cache_dir not in (None, "", "0")
                       else None),
        jobs_dir=args.jobs_dir,
        jobs_batch=args.jobs_batch,
        jobs_max_inflight=args.jobs_max_inflight,
        http_workers=args.http_workers,
        keepalive_timeout_s=args.keepalive_timeout_s,
        warmup=not args.no_warmup,
        wire_format=args.wire_format,
        ragged=args.ragged,
        resize=args.resize,
        access_log=args.access_log,
        flight_recorder_n=args.flight_recorder_n,
        slo_classes=args.slo_classes,
        tenant_quota=args.tenant_quota,
        tenant_burst_s=args.tenant_burst_s,
        pressure_rungs=args.pressure_rungs,
        chaos=args.chaos,
        telemetry_interval_s=args.telemetry_interval,
        slo_objectives=args.slo_objectives,
        **kw,
    )

    enable_compilation_cache()

    if cfg.warmup:
        # Native decode extension build belongs with the other startup
        # compile costs — never inside the first request's handler.
        from tensorflow_web_deploy_tpu import native

        native.available()

    registry = ModelRegistry(cfg, default_model=default_name)
    mesh = None  # one device mesh shared by every engine
    for model_cfg in mcs:
        engine = InferenceEngine(
            dataclasses.replace(cfg, model=model_cfg), mesh=mesh
        )
        mesh = engine.mesh
        if cfg.warmup:
            engine.warmup()
        # The registry owns the per-model knob policy (ModelConfig
        # pipeline_depth/max_queue override the server-wide defaults) —
        # boot-time models go through the same factory as hot-loaded ones
        # so the policy can never drift between the two paths.
        batcher = registry.build_batcher(engine, model_cfg.serve_name)
        registry.adopt(model_cfg.serve_name, engine, batcher, model_cfg)

    app = App.from_registry(registry, cfg)
    default = registry.default_entry()
    return default.engine, default.batcher, app, cfg


def main(argv=None):
    args = parse_args(argv)
    logging.basicConfig(
        level=args.log_level.upper(),
        format="%(asctime)s %(levelname)s %(name)s: %(message)s",
    )
    from tensorflow_web_deploy_tpu.serving.http import (
        make_http_server, shutdown_gracefully,
    )

    engine, batcher, app, cfg = build_server(args)
    srv = make_http_server(app, cfg.host, cfg.port, pool_size=cfg.http_workers,
                           keepalive_timeout_s=cfg.keepalive_timeout_s,
                           request_read_timeout_s=cfg.request_timeout_s)
    logging.getLogger("tpu_serve.http").info(
        "listening on http://%s:%d", cfg.host, cfg.port
    )

    # Orchestrators stop containers with SIGTERM: exit through the same
    # drain path as Ctrl-C. Single-shot — a second signal takes the
    # default action (immediate kill) instead of interrupting the drain.
    import signal

    def _sigterm(signum, frame):
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
        raise KeyboardInterrupt

    signal.signal(signal.SIGTERM, _sigterm)

    try:
        srv.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        # The registry stops the loader thread and EVERY model's batcher
        # (each drains its queued batches) — the multi-model generalization
        # of the old single-batcher drain.
        shutdown_gracefully(srv, app.registry)
    return 0


if __name__ == "__main__":
    sys.exit(main())
