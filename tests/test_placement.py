"""Mesh-wide serving: placement parsing, replicated engines, routing
fairness, and replica drain under hot swap.

The multichip tests run against the 8-device virtual CPU mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — conftest.py
sets it before jax initializes for the tier-1 run; tools/check.sh's
multichip smoke stage runs this file standalone with the flag set
explicitly).

What must hold, per the mesh-wide-serving acceptance:

- one model replicated N× serves IDENTICAL results whichever replica the
  router picks (same params copied to every device group);
- routing disperses sealed batches across every replica under load
  (round-robin order, least-loaded override);
- a hot swap under replicated placement completes with ZERO failed
  requests, and the old version's replicas drain and unload.
"""

from __future__ import annotations

import io
import json
import threading
import time

import numpy as np
import pytest

from tensorflow_web_deploy_tpu.serving.batcher import Batcher
from tensorflow_web_deploy_tpu.serving.placement import Placement, parse_placement
from tensorflow_web_deploy_tpu.serving.registry import ModelRegistry
from tensorflow_web_deploy_tpu.utils.config import (
    ModelConfig, ServerConfig, model_config, split_model_spec,
)


def _mesh8():
    import jax

    from tensorflow_web_deploy_tpu.parallel.mesh import build_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual CPU mesh "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    return build_mesh(jax.devices()[:8])


# ------------------------------------------------------------ spec parsing


def test_split_model_spec():
    assert split_model_spec("inception_v3") == ("inception_v3", {})
    assert split_model_spec("inception_v3,replicas=8") == (
        "inception_v3", {"placement": "replicas=8"})
    assert split_model_spec("native:mobilenet_v2,shard=batch") == (
        "native:mobilenet_v2", {"placement": "shard=batch"})
    assert split_model_spec("native:mobilenet_v2,dtype=int8,as=mv2_int8") == (
        "native:mobilenet_v2", {"dtype": "int8", "alias": "mv2_int8"})
    assert split_model_spec("m,dtype=BF16")[1] == {"dtype": "bfloat16"}
    with pytest.raises(ValueError, match="unknown --model option"):
        split_model_spec("inception_v3,banana=2")
    with pytest.raises(ValueError, match="conflicting placement"):
        split_model_spec("m,replicas=2,shard=batch")
    with pytest.raises(ValueError, match="unsupported dtype"):
        split_model_spec("m,dtype=int4")


def test_model_config_carries_placement():
    mc = model_config("inception_v3,replicas=8")
    assert mc.name == "inception_v3"
    assert mc.placement == "replicas=8"
    assert model_config("inception_v3").placement is None


def test_parse_placement_shard_and_replicate():
    mesh = _mesh8()
    default = parse_placement(None, mesh)
    assert default.strategy == "shard" and default.replicas == 1
    assert default.meshes[0] is mesh
    assert parse_placement("shard=batch", mesh).strategy == "shard"
    # replicas=1 over everything IS the shard strategy (one spelling).
    assert parse_placement("replicas=1", mesh).strategy == "shard"

    p = parse_placement("replicas=4", mesh)
    assert isinstance(p, Placement)
    assert p.strategy == "replicate" and p.replicas == 4
    assert p.spec == "replicas=4"
    groups = [tuple(d.id for d in m.devices.flatten()) for m in p.meshes]
    assert all(len(g) == 2 for g in groups)
    flat = [d for g in groups for d in g]
    assert sorted(flat) == sorted(d.id for d in mesh.devices.flatten())
    assert len(set(flat)) == 8  # disjoint cover


def test_parse_placement_rejects_bad_specs():
    mesh = _mesh8()
    for bad in ("replicas=3", "replicas=9", "replicas=x", "replicas=0",
                "shard=model", "banana"):
        with pytest.raises(ValueError):
            parse_placement(bad, mesh)


# ------------------------------------------------- real replicated engine


@pytest.fixture(scope="module")
def replicated_engine():
    """Tiny native-zoo model replicated 4× over the 8-device mesh (2 chips
    per replica) — real jits, real device_puts, shared-nothing dispatch
    streams."""
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual CPU mesh")
    from tensorflow_web_deploy_tpu.serving.engine import InferenceEngine

    mc = ModelConfig(
        name="mobilenet_v2", source="native", zoo_width=0.25, zoo_classes=12,
        input_size=(64, 64), preprocess="inception", topk=3, dtype="float32",
        placement="replicas=4",
    )
    cfg = ServerConfig(model=mc, canvas_buckets=(96,), batch_buckets=(4,),
                       max_batch=4, warmup=False)
    return InferenceEngine(cfg)


def test_replicated_engine_shape(replicated_engine):
    eng = replicated_engine
    assert eng.num_replicas == 4
    assert eng.placement.strategy == "replicate"
    # Buckets size per REPLICA: 2 devices per group -> batch multiple 2.
    assert eng.batch_multiple == 2
    s = eng.staging_stats()
    assert s["placement"]["replicas"] == 4
    assert [r["replica"] for r in s["replicas"]] == [0, 1, 2, 3]
    assert all(r["devices"] == 2 for r in s["replicas"])


def test_identity_across_replicas(replicated_engine, rng):
    """The SAME batch pinned to each replica in turn must produce
    identical outputs — the params copies and executables are equivalent,
    so the router's choice can never change an answer."""
    eng = replicated_engine
    canvases = (rng.rand(3, 96, 96, 3) * 255).astype(np.uint8)
    hws = np.full((3, 2), 96, np.int32)
    outs = [eng.run_batch(canvases, hws, replica=r) for r in range(4)]
    for r in range(1, 4):
        for a, b in zip(outs[0], outs[r]):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_batcher_disperses_across_replicas(replicated_engine, rng):
    """Waves of batches through the real batcher spread over every
    replica (round-robin under balanced load), and every response is
    identical regardless of which replica served it."""
    eng = replicated_engine
    batcher = Batcher(eng, max_batch=4, max_delay_ms=1.0)
    batcher.start()
    canvas = (rng.rand(96, 96, 3) * 255).astype(np.uint8)
    before = {r["replica"]: r["dispatches_total"]
              for r in eng.staging_stats()["replicas"]}
    rows = []
    try:
        for _ in range(8):  # sequential waves -> >=8 sealed batches
            futs = [batcher.submit(canvas, (96, 96)) for _ in range(4)]
            rows.extend(f.result(timeout=120) for f in futs)
    finally:
        batcher.stop()
    assert len(rows) == 32
    # Identity regardless of serving replica: every row equals the first.
    s0, i0 = rows[0]
    for scores, idx in rows[1:]:
        np.testing.assert_allclose(scores, s0, rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(idx, i0)
    after = eng.staging_stats()["replicas"]
    per_replica = [r["dispatches_total"] - before[r["replica"]] for r in after]
    assert sum(per_replica) >= 8
    assert all(n >= 1 for n in per_replica), (
        f"batches did not disperse across replicas: {per_replica}"
    )
    # The batcher's own view agrees there are 4 streams.
    assert batcher.builder_stats()["replicas"] == 4
    # Timeline records carry the routing decision for overlap analysis.
    replicas_seen = {r["replica"] for r in batcher.batch_timeline()}
    assert len(replicas_seen) >= 2


# ------------------------------------------------ mock replicated serving


class _Mesh:
    devices = np.zeros(1)


class MockReplicatedEngine:
    """Routing-API-complete fake: per-replica dispatch accounting without
    device work, so registry/HTTP-layer placement behavior tests run in
    milliseconds. Scores identify the engine instance (which VERSION
    served), dispatch counts identify the replica (which CHIP GROUP)."""

    batch_buckets = (8,)
    max_batch = 8
    mesh = _Mesh()
    supports_replica_routing = True

    def __init__(self, score=0.5, replicas=4):
        self.score = score
        self.num_replicas = replicas
        self._lock = threading.Lock()
        self.dispatches = [0] * replicas
        self._inflight = [0] * replicas
        self._rr = 0
        self.warmed = False
        self.closed = False

    def warmup(self):
        self.warmed = True

    def close(self):
        self.closed = True

    def healthcheck(self):
        return not self.closed

    def prepare_bytes(self, data):
        if not data:
            raise ValueError("undecodable")
        return np.zeros((8, 8, 3), np.uint8), (8, 8), (8, 8)

    def replica_loads(self):
        with self._lock:
            return list(self._inflight)

    def route_replica(self):
        with self._lock:
            n = self.num_replicas
            start = self._rr
            loads = self._inflight
            best = min(range(n), key=lambda i: (loads[i], (i - start) % n))
            self._rr = (best + 1) % n
            return best

    def placement_summary(self):
        return {
            "strategy": "replicate",
            "spec": f"replicas={self.num_replicas}",
            "replicas": self.num_replicas,
            "devices_per_replica": 1,
            "devices": [[i] for i in range(self.num_replicas)],
        }

    def staging_stats(self):
        with self._lock:
            reps = [
                {"replica": i, "devices": 1,
                 "dispatches_total": self.dispatches[i],
                 "dispatches_inflight": self._inflight[i],
                 "slab_bytes_inflight": 0, "busy_s": 0.0}
                for i in range(self.num_replicas)
            ]
        return {
            "slab_acquires_total": 0, "slab_allocs_total": 0,
            "slabs_out": 0, "slabs_out_bytes": 0,
            "slabs_pooled": 0, "slabs_pooled_bytes": 0,
            "dispatches_total": sum(r["dispatches_total"] for r in reps),
            "dispatches_inflight": sum(r["dispatches_inflight"] for r in reps),
            "placement": self.placement_summary(),
            "replicas": reps,
        }

    def dispatch_batch(self, canvases, hws, replica=None):
        assert not self.closed, "dispatch on a closed (drained) engine"
        r = self.route_replica() if replica is None else int(replica)
        with self._lock:
            self.dispatches[r] += 1
            self._inflight[r] += 1
        return (len(canvases), r)

    def fetch_outputs(self, handle):
        n, r = handle
        with self._lock:
            self._inflight[r] -= 1
        scores = np.full((n, 5), self.score, np.float32)
        idx = np.tile(np.arange(5, dtype=np.int32), (n, 1))
        return scores, idx


def _mc(name):
    return ModelConfig(name=name, source="native", task="classify")


def _make_registry(engine_factory):
    cfg = ServerConfig(model=_mc("m1"), max_batch=8, max_delay_ms=1.0,
                       request_timeout_s=10.0, drain_grace_s=5.0)
    return ModelRegistry(cfg, engine_factory=engine_factory,
                         spec_resolver=_mc), cfg


def test_hot_swap_replicated_zero_errors():
    """Concurrent traffic over a 4-replica placement while the model hot
    swaps: ZERO failed requests, both versions serve across the window,
    the old version's replicas drain (engine closed, state UNLOADED), and
    each version's traffic dispersed over its replicas."""
    engines = []

    def factory(mc):
        eng = MockReplicatedEngine(score=round(0.1 * (len(engines) + 1), 3))
        engines.append(eng)
        return eng

    r, _cfg_unused = _make_registry(factory)
    v1 = r.load("m1", wait=True)
    stop = threading.Event()
    failures, scores_seen = [], []

    def hammer():
        canvas = np.zeros((8, 8, 3), np.uint8)
        while not stop.is_set():
            try:
                with r.lease_model("m1") as mv:
                    fut = mv.batcher.submit(canvas, (8, 8))
                    scores, _idx = fut.result(timeout=10)
                    scores_seen.append(float(scores[0]))
            except Exception as e:
                failures.append(repr(e))

    threads = [threading.Thread(target=hammer) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        time.sleep(0.25)  # steady state on v1
        v2 = r.swap("m1", wait=True)
        r.wait_for(r._models["m1"][1], ("UNLOADED",), timeout=10)
        time.sleep(0.25)  # steady state on v2
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
        r.stop()

    assert not failures, f"requests failed during replicated swap: {failures[:5]}"
    assert v2.state == "SERVING"
    versions_hit = {round(s, 3) for s in scores_seen}
    assert {0.1, 0.2} <= versions_hit, versions_hit
    # Replica drain: the retired version's engine was closed only after
    # its in-flight work resolved (zero failures above proves no request
    # hit a closed replica), and its replicas all saw traffic.
    assert engines[0].closed and not engines[1].closed
    assert all(n >= 1 for n in engines[0].dispatches), engines[0].dispatches
    assert all(n >= 1 for n in engines[1].dispatches), engines[1].dispatches


def _wsgi_get(app, path):
    captured = {}

    def start_response(status, headers, exc_info=None):
        captured["status"] = status

    environ = {
        "PATH_INFO": path, "REQUEST_METHOD": "GET", "QUERY_STRING": "",
        "CONTENT_LENGTH": "0", "wsgi.input": io.BytesIO(b""),
    }
    body = b"".join(app(environ, start_response))
    return captured["status"], body


def test_stats_and_metrics_attribute_per_replica():
    """/stats carries the staging "replicas" + "placement" blocks, /models
    the per-version placement, and /metrics the
    ``{model,version,replica}``-labeled dispatch/slab/busy series."""
    from tensorflow_web_deploy_tpu.serving.http import App

    r, cfg = _make_registry(lambda mc: MockReplicatedEngine())
    mv = r.load("m1", wait=True)
    app = App.from_registry(r, cfg)
    try:
        canvas = np.zeros((8, 8, 3), np.uint8)
        futs = [mv.batcher.submit(canvas, (8, 8)) for _ in range(8)]
        for f in futs:
            f.result(timeout=10)

        status, body = _wsgi_get(app, "/stats")
        assert status.startswith("200")
        doc = json.loads(body)
        assert doc["config"]["placement"]["strategy"] == "replicate"
        reps = doc["staging"]["replicas"]
        assert [x["replica"] for x in reps] == [0, 1, 2, 3]
        assert sum(x["dispatches_total"] for x in reps) >= 1
        assert doc["batcher"]["builders"]["replicas"] == 4

        status, body = _wsgi_get(app, "/models")
        assert status.startswith("200")
        versions = json.loads(body)["models"]["m1"]["versions"]
        assert versions[0]["placement"]["spec"] == "replicas=4"

        status, body = _wsgi_get(app, "/metrics")
        assert status.startswith("200")
        text = body.decode()
        assert 'model_replica_dispatches_total{' in text
        assert 'replica="0"' in text and 'replica="3"' in text
        assert "model_replica_slab_bytes_inflight{" in text
        assert "model_replica_busy_seconds_total{" in text
    finally:
        r.stop()
