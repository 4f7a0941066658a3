#!/usr/bin/env bash
# Pre-PR gate: static analysis + bytecode compile + tier-1 under the
# runtime lock-order witness. Run it from anywhere; exits nonzero on the
# first failing stage. This is THE command to run before sending a PR:
#
#     tools/check.sh            # full gate (lint + compile + tier-1)
#     tools/check.sh --fast     # lint + compile only (~3 s)
#
# Stage budgets: twdlint < 15 s (enforced by tests/test_twdlint.py's
# smoke), compileall a few seconds, tier-1 several minutes on CPU.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== twdlint (concurrency-invariant static analysis) =="
python -m tools.twdlint

echo "== compileall =="
python -m compileall -q tensorflow_web_deploy_tpu tools tests server.py bench.py __graft_entry__.py

echo "== cache smoke (deterministic digest + hit/coalesce/invalidate units) =="
# Fast, mock-engine-only: covers the response cache's correctness core
# (content digests, single-flight dedup, LRU budget, hot-swap
# invalidation) so even --fast gates the new module.
timeout -k 10 240 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_respcache.py -q -p no:cacheprovider

echo "== jobs smoke (bulk lifecycle + checkpoint/resume + priority gate) =="
# Fast, mock-engine-only: the /jobs correctness core — lifecycle,
# checkpoint/resume after a simulated restart, hot-swap-under-job,
# cancel, the batcher's strict-priority bulk gate — gated even in --fast.
timeout -k 10 240 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_jobs.py -q -p no:cacheprovider

echo "== economics smoke (costmodel FLOP pins + chrome-trace export) =="
# Fast, engine-free: the analytic cost model's hand-derived FLOP pins
# (mobilenet_v2/resnet50 within 5%), exact param cross-checks against
# flax init, roofline arithmetic, and the /debug/trace Chrome-trace
# serialization — gated even in --fast so a model edit that forgets the
# walker fails before a PR.
timeout -k 10 240 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_costmodel.py -q -p no:cacheprovider

echo "== overload+chaos smoke (admission/ladder/quota units + fault drills) =="
# Fast, mock-engine-only: deadline admission + seal sheds, per-tenant
# token buckets, the degradation ladder's rung walk, SIGTERM drain, and
# the chaos harness's zero-hangs/zero-leaks drills — gated even in
# --fast so an overload-path edit fails before a PR.
timeout -k 10 240 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_overload.py tests/test_chaos.py -q -p no:cacheprovider

echo "== ragged smoke (packed-slab wire: golden parity + packing identity) =="
# Real tiny zoo engines on CPU: the on-device unpack must answer exactly
# like the host-padded path (all four presets), packed images must equal
# solo submits, and the padding telemetry must show the tight wire —
# gated even in --fast so a slab/unpack edit fails before a PR.
timeout -k 10 240 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_ragged.py -q -p no:cacheprovider

echo "== quant smoke (int8/bf16 tier: quantize discipline + fused kernel parity) =="
# Mixed mock + real tiny zoo engines on CPU: per-channel quantize
# round-trip discipline, the fused depthwise kernel (XLA + Pallas
# interpret) against the unfused reference, the int8 golden parity gate
# across all four presets, the quant-reroute rung, and dtype-keyed cache
# semantics — gated even in --fast so a quant/kernel edit fails before a
# PR.
timeout -k 10 240 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_quant.py -q -p no:cacheprovider

echo "== telemetry smoke (history rings + burn-rate alerts + regression sentinel) =="
# Mock-engine-only: ring compaction (spikes survive), the multiwindow
# burn fire/clear machine, the /debug/history + /debug/events surfaces
# under a concurrent hot-swap-with-chaos hammer, and the bench_diff
# sentinel's hermetic self-check — gated even in --fast so a telemetry
# or sentinel edit fails before a PR.
timeout -k 10 240 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_telemetry.py -q -p no:cacheprovider
timeout -k 10 60 python tools/bench_diff.py --self-check

echo "== aot smoke (executable cache: corrupt taxonomy + deserialize parity) =="
# Real tiny zoo engines on CPU: entry round-trips, the corrupt/miss
# taxonomy (garbage, truncation, foreign key, version drift), concurrent
# warmups sharing one directory, the int8 parity gate on the deserialize
# path, and the aotcache.lock witness — gated even in --fast so a
# cache-format or warmup edit fails before a PR. Deliberately NO
# -m 'not slow' filter: the heavyweight preset roundtrips and the int8
# deserialize-parity test live behind the slow marker to keep tier-1
# inside its wall-clock budget, and THIS stage is where they run.
timeout -k 10 480 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_aotcache.py -q -p no:cacheprovider

echo "== dag smoke (pipeline specs + device-resident glue + hot-swap-under-DAG) =="
# Mixed mock + real tiny zoo engines on CPU: spec-grammar/cycle/arity
# rejection at parse, the jitted crop+resize glue against its host
# mirror (<=1 LSB bound), per-stage cache keys carrying serving
# version, the hot-swap-under-DAG zero-stale-composite drill, and the
# dag.lock witness — gated even in --fast so a pipeline edit fails
# before a PR.
timeout -k 10 240 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_dag.py -q -p no:cacheprovider

if [[ "${1:-}" == "--fast" ]]; then
    echo "check.sh --fast: OK (multichip smoke + tier-1 skipped)"
    exit 0
fi

echo "== multichip smoke (8-device virtual CPU mesh: placement + routing) =="
# The 8 virtual devices must exist before jax initializes — set
# explicitly here through XLA_FLAGS (conftest.py sets jax_num_cpu_devices
# too, but the smoke documents the requirement and survives a conftest
# regression).
timeout -k 10 300 env JAX_PLATFORMS=cpu TWD_DEBUG_LOCKS=1 \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m pytest tests/test_placement.py -q -p no:cacheprovider

echo "== tier-1 (TWD_DEBUG_LOCKS=1: tests double as lock-order witness runs) =="
rm -f /tmp/_t1.log
rc=0
timeout -k 10 870 env JAX_PLATFORMS=cpu TWD_DEBUG_LOCKS=1 \
    python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors \
    -p no:cacheprovider 2>&1 | tee /tmp/_t1.log || rc=$?
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)"
exit "$rc"
