"""Config/flag system (SURVEY.md §5.6): one dataclass + per-model presets.

The reference configures via argparse flags / constants at the top of
``server.py`` (SURVEY.md §5.6 [K]); here every knob lives in one
``ServerConfig`` loadable from CLI flags or JSON, with presets for the five
tracked configs in BASELINE.json.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path


# Accepted --dtype / ModelConfig.dtype spellings → canonical form.
_DTYPE_ALIASES = {
    "f32": "float32", "float32": "float32",
    "bf16": "bfloat16", "bfloat16": "bfloat16",
    "int8": "int8",
}


def normalize_dtype(dtype: str) -> str:
    """Canonicalize a serving dtype; raise ValueError on anything else —
    a typo'd dtype must fail the LOAD, never silently serve bf16."""
    try:
        return _DTYPE_ALIASES[str(dtype).strip().lower()]
    except KeyError:
        raise ValueError(
            f"unsupported dtype {dtype!r} "
            "(supported: f32/float32, bf16/bfloat16, int8)"
        ) from None


@dataclasses.dataclass
class ModelConfig:
    """Everything the runtime needs to serve one frozen graph."""

    name: str
    pb_path: str | None = None
    # "pb" converts a frozen GraphDef; "native" serves the flax model zoo
    # (models/) — same engine, no TensorFlow anywhere in the process.
    source: str = "pb"
    # native-source knobs: width multiplier + class count (tiny variants for
    # tests/dev; 1.0/None = the real architecture)
    zoo_width: float = 1.0
    zoo_classes: int | None = None
    # serving export from tools/train.py (orbax dir holding params +
    # batch_stats) — serve fine-tuned weights instead of the seeded init
    ckpt_path: str | None = None
    task: str = "classify"  # "classify" | "detect" | "generate"
    # task="generate" (the native name is the family's: a module of
    # models/, models/decoder.py has the contract): the decoder's sizes,
    # the keys of that module's Config (widths, layers, experts
    # held, vocabulary slice, patch, answer_steps, max_token_slots). The
    # image is not resized: its patches are the tokens, so input_size and
    # preprocess are unused.
    decoder: dict | None = None
    labels_path: str | None = None
    input_name: str | None = None  # default: the graph's sole placeholder
    output_names: list[str] | None = None  # default: inferred sinks
    input_size: tuple[int, int] = (299, 299)
    # normalization preset applied on-device: "inception" ([-1,1]),
    # "zero_one" (/255), "caffe" (BGR, mean-subtracted), "raw"
    preprocess: str = "inception"
    topk: int = 5
    # Serving dtype variant (the raw-speed tier): "float32" (the golden
    # reference), "bfloat16" (params+activations cast, the default), or
    # "int8" (per-channel weight-only quantization, dequantized on the fly
    # inside the serve fn, computing in bf16 — gated by the engine's
    # numerical-parity check vs f32 at build). Aliases f32/bf16 accepted;
    # anything else is rejected at config time.
    dtype: str = "bfloat16"
    # Registry serve name (GET /models, /predict?model=...): defaults to
    # ``name``. Set via --model ...,as=<serve name> so two dtype variants
    # of one architecture can serve side by side (the quantized-variant
    # pressure rung routes between them).
    alias: str | None = None
    # Fused depthwise chain (ops/depthwise.py): "auto" fuses for the
    # quantized tier (dtype != float32) on native models with a depthwise
    # stack, "on"/"off" force it — the bench A/B knob.
    fused_dw: str = "auto"
    # Per-model pipeline overrides (None = inherit the server-wide values
    # below): batches in flight per canvas bucket, and the bounded-queue
    # fast-reject threshold in images. A latency-critical model can run
    # depth 1 with a short queue while a throughput model on the same
    # server runs deep — the registry reads these when it builds each
    # model's batcher.
    pipeline_depth: int | None = None
    max_queue: int | None = None
    # Device placement (serving/placement.py): None = shard batches over
    # the whole mesh (the historical behavior), "replicas=N" = split the
    # mesh into N groups each holding a full params copy with its own
    # dispatch stream, "shard=batch" = the explicit default spelling.
    # Spelled on the CLI as a --model suffix: --model mobilenet_v2,replicas=8
    placement: str | None = None

    def __post_init__(self):
        if self.source == "pb" and not self.pb_path:
            raise ValueError(
                f"model '{self.name}': source='pb' requires pb_path "
                "(or use source='native' for the flax zoo)"
            )
        try:
            self.dtype = normalize_dtype(self.dtype)
        except ValueError as e:
            raise ValueError(f"model '{self.name}': {e}") from None
        if self.task == "generate" and not isinstance(self.decoder, dict):
            raise ValueError(
                f"model '{self.name}': task='generate' needs 'decoder', the "
                "model's sizes (the Config of the family's module in models/)"
            )
        if self.fused_dw not in ("auto", "on", "off"):
            raise ValueError(
                f"model '{self.name}': fused_dw must be 'auto', 'on' or "
                f"'off', got {self.fused_dw!r}"
            )

    @property
    def serve_name(self) -> str:
        """The registry/HTTP-facing name (``alias`` wins over ``name``)."""
        return self.alias or self.name


@dataclasses.dataclass
class ServerConfig:
    model: ModelConfig
    host: str = "0.0.0.0"
    port: int = 8500
    # dynamic batcher (SURVEY.md §1.1 "Batching" layer)
    max_batch: int = 32
    # CAP on the batch-assembly window. With adaptive_delay the live window
    # moves in [0, max_delay_ms] with queue depth: ~0 when the queue is
    # empty (idle device dispatches immediately), toward the cap under
    # backlog (waiting buys bigger batches when the device is the
    # bottleneck). /stats → batcher.adaptive_delay_ms shows the live value.
    max_delay_ms: float = 2.0
    adaptive_delay: bool = True
    # Pipelined dispatch: batches allowed in flight (sealed → launched →
    # unfetched) PER canvas bucket. Depth ≥ 2 is what overlaps decode of
    # batch N+1 with execute of batch N; deeper buys tolerance to jittery
    # device/fetch latency at the cost of host+device memory for the extra
    # staged batches. Per-model override: ModelConfig.pipeline_depth.
    pipeline_depth: int = 4
    # Bounded per-model submit queue (admission control down-payment):
    # when a model's batcher backlog reaches this many images, /predict
    # fails fast with 503 + Retry-After instead of queueing toward the
    # request timeout. 0 = unbounded (lease blocks at the outstanding-slot
    # cap instead). Per-model override: ModelConfig.max_queue.
    max_queue: int = 0
    # Slot-lease bound on batch assembly: a leased slot not committed or
    # released within this window is force-expired (its batch dispatches
    # with the row padded as a hw=1×1 hole), so a worker that dies
    # mid-decode can never wedge its batch. Must comfortably exceed any
    # legitimate decode time.
    lease_timeout_s: float = 10.0
    request_timeout_s: float = 30.0
    # Model-registry drain window: after a hot-swap (or unload) the retired
    # version waits this long for its in-flight requests to finish before
    # its batcher is stopped anyway. Must comfortably exceed
    # request_timeout_s only if abandoned requests should never see a
    # batcher shutdown; the default trades that for bounded unload time.
    drain_grace_s: float = 30.0
    # HTTP front end: persistent worker pool speaking HTTP/1.1 keep-alive.
    # pool size bounds concurrent request handling (device work all happens
    # on the batcher thread, so this only needs to cover decode + I/O);
    # keepalive_timeout_s is how long an idle connection may hold a worker.
    http_workers: int = 16
    keepalive_timeout_s: float = 15.0
    # Preallocated host staging slabs kept per (canvas, batch-bucket) shape:
    # batches assemble by writing rows straight into a pooled slab and
    # dispatch ships it in one host→device transfer (no stack/concat
    # copies). The cap bounds host memory under bursty pipelining.
    staging_slabs: int = 6
    # The floor an idle pool of staging slabs falls back to, across all
    # shapes. Under load the pool may hold this PLUS the bytes that are out
    # with batches (acquired and not yet returned), so traffic whose slabs
    # are larger than the floor (a 1.6 GB arena at canvas 4096 x batch 32)
    # still reuses them; when the last slab out comes back the pool trims to
    # the floor again, so an idle server and the end of warmup (which touches
    # every (canvas, batch) bucket pair) pin no more than this. Over budget,
    # slabs from the least-recently-used shapes are dropped (slabs that are
    # out are never affected).
    staging_pool_bytes: int = 256 << 20
    # Content-addressed response cache (serving/respcache.py): byte budget
    # for cached formatted responses, keyed by (model, version, digest of
    # the decoded canvas, topk, serving dtype), with single-flight dedup of
    # concurrent identical requests. 0 = disabled (every request computes).
    # server.py defaults this ON (--cache-bytes 256 MiB); the dataclass
    # default stays 0 so embedders/tests opt in explicitly.
    cache_bytes: int = 0
    # Pipeline DAGs (serving/dag.py): specs registered at boot, each either
    # an inline "name=detect_model@int8>classify_model@f32" chain or a path
    # to a JSON pipeline file. Invalid specs (grammar, cycles, arity,
    # unresolvable stage models/dtypes) fail the BOOT — a server that
    # starts serves every pipeline it advertises.
    pipelines: tuple[str, ...] = ()
    # Stage-1 detections fed to the crop glue per image (the crop batch
    # compiles at the batch bucket covering this). Also the stage-1 cache
    # key's topk slot: a pipeline's detection entries are keyed by how many
    # boxes the glue may consume, not by the client's classifier topk.
    pipeline_max_crops: int = 8
    # Bulk offline jobs (serving/jobs.py, POST /jobs): directory where job
    # manifests, spooled uploads, results and checkpoints persist across
    # restarts. None = /jobs disabled (server.py exposes --jobs-dir).
    jobs_dir: str | None = None
    # Bulk batch target — the throughput-mode operating point (batch-256
    # ~30% MFU); clamped to the engine's top compiled batch bucket, so
    # reaching the full 256 needs max_batch/batch_buckets to cover it.
    jobs_batch: int = 256
    # Bulk batches allowed in flight at once — the isolation knob: how
    # much device time a background job may hold while interactive
    # traffic shares the mesh (see batcher.py's bulk gate).
    jobs_max_inflight: int = 2
    # Anti-starvation window: strict bulk priority degrades jobs to SLOW
    # under sustained interactive load, never to zero — a ready bulk
    # batch gated this long is admitted once (one execute quantum of
    # tail cost per window), then the clock re-arms.
    jobs_starvation_s: float = 2.0
    # Manifest size ceiling per job (a larger manifest is REFUSED at
    # submit with 400 — never silently truncated): bounds memory for the
    # item list and the results index.
    jobs_max_items: int = 100_000
    # /predict request body cap; larger uploads get 413 before buffering
    max_body_mb: float = 32.0
    # Slow-request flight recorder depth: the N slowest and N most recent
    # erroring requests keep their full span breakdown for GET /debug/slow.
    flight_recorder_n: int = 32
    # Explicit flight-recorder memory bound (echoed in /stats config and
    # /debug/slow "limits"): the recent-requests ring GET /debug/trace
    # serializes keeps at most this many finished spans AND at most this
    # many approximate bytes, whichever binds first.
    flight_recorder_recent_n: int = 512
    flight_recorder_bytes: int = 4 << 20
    # Structured JSON access log (one line per request: trace ID, stage
    # timings, status, batch bucket): None = off, "-" = the tpu_serve.access
    # logger (stderr under default logging), else a file path to append to.
    access_log: str | None = None
    # canvas size buckets for host-padded decoded images; device resizes from
    # the valid region (static shapes; dynamic gather coords)
    canvas_buckets: tuple[int, ...] = (256, 512, 1024, 2048)
    # batch sizes precompiled at startup; runtime pads to the next bucket.
    # Every bucket must be a multiple of the mesh size so the batch axis
    # shards evenly over devices.
    batch_buckets: tuple[int, ...] | None = None  # default derived from mesh
    # Host→device canvas encoding: "rgb" (uint8 HWC) or "yuv420" (packed I420,
    # 1.5 B/px — half the wire bytes; device converts in the jitted fn).
    wire_format: str = "rgb"
    # On-device resize implementation: "matmul" (separable bilinear as MXU
    # matmuls — TPU-native), "gather" (dynamic-index taps), or "pallas"
    # (fused unpack+convert+resize+normalize kernel; yuv420 wire only).
    resize: str = "matmul"
    # Ship ONE uint8 buffer per batch (canvas bytes + 4 trailing hw bytes per
    # image) and fetch ONE packed f32 array of outputs, instead of 2 puts +
    # per-output fetches: the batch-1 request path drops from 5 host↔device
    # hops to 3, for one extra host-side memcpy per batch. Whether fewer
    # hops pay for the memcpy is not measured on a directly attached chip
    # (ROADMAP D2).
    packed_io: bool = True
    # Ragged packing (ROADMAP item 5): host decode lands TIGHT rows (native
    # stride, no canvas padding) in a flat per-batch byte arena; the device
    # unpacks each image to its canvas slot in a jitted stage between
    # transfer and execute, so batches ship real pixels instead of ~70%
    # padding on mixed-size traffic. rgb wire only (yuv420 keeps the classic
    # host-padded path — the chroma-plane layout has no tight packing);
    # ragged dispatch ships (arena, meta) so packed_io's single-buffer trick
    # is subsumed and forced off at engine build. Dataclass default OFF so
    # embedders/tests opt in; server.py defaults the CLI flag ON.
    ragged: bool = False
    warmup: bool = True
    # AOT-serialized executable cache (serving/aotcache.py): directory
    # where warmup persists compiled executables so the next boot /
    # hot-swap rewarm deserializes instead of recompiling (the
    # cold-start killer, ISSUE 18). Unlike JAX's persistent compilation
    # cache (XLA's HLO-keyed cache, always on — utils/env.py — which still
    # pays tracing + lowering + linking), this caches the LOADED
    # executable — rewarm becomes a file read.
    # None/"0"/"" = disabled. Dataclass default stays off so
    # embedders/tests opt in explicitly; server.py defaults the CLI flag
    # ON (--aot-cache-dir <checkout>/.aot_cache).
    aot_cache_dir: str | None = None
    log_level: str = "INFO"
    # ---- Overload control (ISSUE 13; serving/overload.py) ----
    # SLO classes: "name=deadline_ms,..." — every /predict carries a
    # deadline (X-Deadline-Ms header / ?deadline_ms=), defaulted from its
    # class (X-SLO header / ?slo=, default "interactive"). The batcher
    # sheds requests whose deadline the expected wait cannot meet (504,
    # reason=deadline) at lease time AND at seal time.
    slo_classes: str = "interactive=1000,batch=10000"
    # Per-tenant token-bucket quotas: "alice=50,bob=25,*=100" in images/s
    # (X-Tenant header names the tenant; "*" is the default for unlisted
    # tenants; 0/absent = unlimited). Interactive overage sheds with 429,
    # bulk jobs slow to their refill rate. Empty = no quotas (counters
    # still tracked).
    tenant_quota: str = ""
    # Bucket depth in seconds of refill (quota 50 img/s × 1 s burst
    # admits a 50-image burst from idle).
    tenant_burst_s: float = 1.0
    # Tracked-tenant cardinality cap for /stats + /metrics labels;
    # unknown tenants past the cap share the "~other" bucket.
    tenant_max_tracked: int = 64
    # Degradation ladder rungs "enter:exit,..." on the queue-depth
    # fraction — level 1 clamps topk, 2 routes to the smallest canvas
    # bucket, 3 rejects cache-miss work (503, reason=degraded). Enter >
    # exit is the hysteresis band; transitions respect the dwell.
    pressure_rungs: str = "0.60:0.40,0.80:0.60,0.95:0.75"
    pressure_dwell_s: float = 0.5
    # Chaos fault-injection spec (serving/chaos.py; --chaos flag or
    # TWD_CHAOS env): "decode_fail=P,dispatch_fail=P,slow_replica=P:MS,
    # spike=ON:PERIOD,seed=N". None = no injection.
    chaos: str | None = None
    # ---- Telemetry history (ISSUE 17; serving/telemetry.py) ----
    # Sampler interval for the in-process time-series rings (multi-
    # resolution history behind /debug/history and the SLO burn-rate
    # evaluator). 0 disables the whole subsystem. Dataclass default ON at
    # 1 s: the rings are fixed-memory (~3 MiB at the default ~30 series)
    # and the sampler overhead is bounded by the bench telemetry block.
    telemetry_interval_s: float = 1.0
    # SLO objectives "name=pXX:THRESHOLD:TARGET_PCT,..." (e.g.
    # "interactive=p99:1000ms:99.9") evaluated as multi-window burn rates
    # (1m/5m fast pair + 30m slow) with machine-readable alert state.
    # Empty = no objectives tracked.
    slo_objectives: str = ""

    def __post_init__(self):
        # pick_bucket and healthcheck rely on ascending order; user-supplied
        # --canvas-buckets arrive in arbitrary order.
        self.canvas_buckets = tuple(sorted(set(self.canvas_buckets)))
        if self.wire_format not in ("rgb", "yuv420"):
            raise ValueError(f"wire_format must be 'rgb' or 'yuv420', got {self.wire_format!r}")
        if self.resize not in ("matmul", "gather", "pallas"):
            raise ValueError(
                f"resize must be 'matmul', 'gather' or 'pallas', got {self.resize!r}"
            )
        if self.resize == "pallas":
            if self.wire_format != "yuv420":
                raise ValueError("resize='pallas' requires wire_format='yuv420'")
            if self.model.preprocess not in ("inception", "zero_one", "raw"):
                # Fail at config time, not on the first traced request.
                raise ValueError(
                    "resize='pallas' supports preprocess inception/zero_one/raw, "
                    f"not {self.model.preprocess!r}"
                )
            # The kernel streams a canvas through VMEM in row tiles only
            # when its side is a multiple of 128; any other canvas goes in
            # whole, which Mosaic accepts up to 1024 (ops/pallas_preprocess).
            bad = [s for s in self.canvas_buckets if s % 128 and s > 1024]
            if bad:
                raise ValueError(
                    f"resize='pallas': canvas buckets {bad} exceed 1024 and "
                    "are not multiples of 128; the kernel cannot hold them "
                    "in VMEM"
                )
        if self.wire_format == "yuv420":
            bad = [s for s in self.canvas_buckets if s % 4]
            if bad:
                raise ValueError(
                    f"yuv420 wire format needs canvas buckets divisible by 4; got {bad}"
                )


_ARTIFACTS = Path(__file__).resolve().parent.parent.parent / "artifacts"


def _preset(name: str, **kw) -> ModelConfig:
    kw.setdefault("pb_path", str(_ARTIFACTS / f"{name}.pb"))
    kw.setdefault("labels_path", str(_ARTIFACTS / "imagenet_labels.txt"))
    return ModelConfig(name=name, **kw)


# The five tracked configs from BASELINE.json (SURVEY.md §6).
PRESETS: dict[str, ModelConfig] = {
    "inception_v3": _preset("inception_v3", input_size=(299, 299), preprocess="inception"),
    "mobilenet_v2": _preset("mobilenet_v2", input_size=(224, 224), preprocess="inception"),
    "resnet50": _preset("resnet50", input_size=(224, 224), preprocess="caffe"),
    "ssd_mobilenet": _preset(
        "ssd_mobilenet",
        task="detect",
        input_size=(300, 300),
        preprocess="inception",
        labels_path=str(_ARTIFACTS / "coco_labels.txt"),
        # The engine's detect branch looks outputs up by semantic name, but
        # freezing wraps the named identities in anonymous Identity nodes,
        # so the converter's inferred sinks are ['Identity', ...] and the
        # preset crashed at engine build (KeyError: 'raw_boxes') — the
        # frozen graphs carry nodes under these names, so request them
        # explicitly (VERDICT round 5, Weak #1).
        output_names=["raw_boxes", "raw_scores", "anchors"],
    ),
}


def split_model_spec(spec: str) -> tuple[str, dict[str, str]]:
    """Split ``--model``'s option suffixes off a model spec:
    ``"mobilenet_v2,replicas=8"`` → ``("mobilenet_v2",
    {"placement": "replicas=8"})``; ``"native:mobilenet_v2,dtype=int8,
    as=mobilenet_v2_int8"`` → the base plus ``{"dtype": "int8", "alias":
    "mobilenet_v2_int8"}``. Raises ValueError on an unknown suffix key or
    a bad dtype — a typo must not silently serve the defaults."""
    base, _, rest = spec.partition(",")
    opts: dict[str, str] = {}
    if not rest:
        return base, opts
    for t in [t.strip() for t in rest.split(",") if t.strip()]:
        key, _, val = t.partition("=")
        if key in ("replicas", "shard"):
            if "placement" in opts:
                raise ValueError(
                    f"conflicting placement options in {spec!r}: "
                    f"{opts['placement']!r} and {t!r}"
                )
            opts["placement"] = t
        elif key == "dtype":
            opts["dtype"] = normalize_dtype(val)
        elif key == "as":
            if not val:
                raise ValueError(f"empty serve name in {t!r} in {spec!r}")
            opts["alias"] = val
        else:
            raise ValueError(
                f"unknown --model option {t!r} in {spec!r} "
                "(supported: replicas=N, shard=batch, dtype=int8|bf16|f32, "
                "as=<serve name>)"
            )
    return base, opts


def model_config(name_or_path: str) -> ModelConfig:
    """Resolve a preset name, ``native:<zoo name>``, a JSON config path, or a
    bare .pb path — each optionally carrying option suffixes
    (``name,replicas=N`` / ``name,dtype=int8`` / ``name,as=<serve name>``)."""
    name_or_path, opts = split_model_spec(name_or_path)
    if opts:
        mc = model_config(name_or_path)
        mc.placement = opts.get("placement", mc.placement)
        mc.dtype = opts.get("dtype", mc.dtype)
        mc.alias = opts.get("alias", mc.alias)
        return mc
    if name_or_path.startswith("native:"):
        from ..models import get as zoo_get, names as zoo_names

        try:
            spec = zoo_get(name_or_path[len("native:"):])
        except KeyError:
            raise ValueError(
                f"unknown native model '{name_or_path}' — have "
                + ", ".join(f"native:{n}" for n in zoo_names())
            ) from None
        return ModelConfig(
            name=spec.name,
            source="native",
            task=spec.task,
            input_size=(spec.input_size, spec.input_size),
            preprocess=spec.preprocess,
            labels_path=str(
                _ARTIFACTS / ("coco_labels.txt" if spec.task == "detect" else "imagenet_labels.txt")
            ),
        )
    if name_or_path in PRESETS:
        return dataclasses.replace(PRESETS[name_or_path])
    p = Path(name_or_path)
    if p.suffix == ".json":
        data = json.loads(p.read_text())
        data["input_size"] = tuple(data.get("input_size", (299, 299)))
        return ModelConfig(**data)
    if p.suffix == ".pb":
        return ModelConfig(name=p.stem, pb_path=str(p))
    raise ValueError(
        f"unknown model '{name_or_path}' — expected one of {sorted(PRESETS)}, "
        "native:<zoo name>, a .json config, or a .pb path"
    )
