"""AOT executable cache: serialized-executable reuse across engine boots.

The cold-start tentpole (serving/aotcache.py + engine warmup rework) must
be invisible to correctness: a warm-cache boot deserializes executables
instead of compiling them, and every output stays bit-identical to the
fresh-compile path. Anything wrong with an entry — truncated file, foreign
key under the right filename, version or device-kind drift — degrades to a
counted recompile, never an error and never a wrong result. These tests
pin that contract at the unit level (file format, corrupt/miss taxonomy)
and end-to-end (all four zoo presets, ragged unpack programs, concurrent
warmups sharing one directory, the int8 parity gate on the deserialize
path, and the lock-order witness over the new aotcache.lock).
"""

import os
import shutil
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tensorflow_web_deploy_tpu.serving import aotcache
from tensorflow_web_deploy_tpu.serving import engine as engine_mod
from tensorflow_web_deploy_tpu.serving.aotcache import AotCache
from tensorflow_web_deploy_tpu.serving.engine import InferenceEngine
from tensorflow_web_deploy_tpu.utils.config import ModelConfig, ServerConfig


# _trivial_compiled's jit carries no sharding: it compiles for device 0.
_DEV0 = jax.devices()[:1]


@pytest.fixture(autouse=True)
def _jax_persistent_cache_off():
    """On the TPU the AOT cache and JAX's persistent compilation cache
    compose (chip_smoke.py's restart phase); on XLA:CPU an executable that
    JAX rebuilt from its own cache re-serializes without its object code,
    and the AOT entry written from it fails at its first execution in the
    next process. These tests boot engines twice on one AOT directory on
    the CPU, so JAX's cache stays off around them — here, in the test, not
    through an option of the program."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _trivial_compiled():
    fn = jax.jit(lambda x: x * 2.0 + 1.0)
    return fn.lower(jax.ShapeDtypeStruct((8,), jnp.float32)).compile()


def _key(**over):
    key = {"v": 1, "model": "trivial", "device_kind": "cpu", "canvas": 8}
    key.update(over)
    return key


def _stats_delta(before, after):
    return {k: after[k] - before[k]
            for k in ("hits_total", "misses_total", "writes_total",
                      "corrupt_total")}


# ------------------------------------------------------------------ unit


def test_roundtrip_trivial_fn(tmp_path):
    cache = AotCache(str(tmp_path))
    before = aotcache.stats()
    compiled = _trivial_compiled()
    assert cache.store(_key(), compiled)
    assert cache.entry_count() == 1
    exe = cache.load(_key(), _DEV0)
    assert exe is not None
    x = jnp.arange(8, dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(exe(x)), np.asarray(compiled(x)))
    d = _stats_delta(before, aotcache.stats())
    assert d["writes_total"] == 1 and d["hits_total"] == 1
    assert d["misses_total"] == 0 and d["corrupt_total"] == 0


def test_absent_entry_is_miss_not_corrupt(tmp_path):
    cache = AotCache(str(tmp_path))
    before = aotcache.stats()
    assert cache.load(_key(), _DEV0) is None
    d = _stats_delta(before, aotcache.stats())
    assert d["misses_total"] == 1 and d["corrupt_total"] == 0


def test_key_field_change_is_a_different_entry(tmp_path):
    """Version / device-kind / topology drift lands on a different digest,
    so a stale entry is a plain miss — the file is never even opened."""
    cache = AotCache(str(tmp_path))
    cache.store(_key(), _trivial_compiled())
    before = aotcache.stats()
    for drift in ({"v": 2}, {"device_kind": "TPU v4"}, {"jax": "0.0.1"}):
        assert cache.load(_key(**drift), _DEV0) is None
    d = _stats_delta(before, aotcache.stats())
    assert d["misses_total"] == 3 and d["corrupt_total"] == 0


def test_garbage_file_is_corrupt_and_survivable(tmp_path):
    cache = AotCache(str(tmp_path))
    cache.store(_key(), _trivial_compiled())
    (path,) = [tmp_path / f for f in os.listdir(tmp_path)]
    path.write_bytes(b"garbage, definitely not an executable")
    before = aotcache.stats()
    assert cache.load(_key(), _DEV0) is None  # degrade, never raise
    d = _stats_delta(before, aotcache.stats())
    assert d["corrupt_total"] == 1 and d["misses_total"] == 0


def test_truncated_file_is_corrupt(tmp_path):
    cache = AotCache(str(tmp_path))
    cache.store(_key(), _trivial_compiled())
    (path,) = [tmp_path / f for f in os.listdir(tmp_path)]
    path.write_bytes(path.read_bytes()[: len(path.read_bytes()) // 2])
    before = aotcache.stats()
    assert cache.load(_key(), _DEV0) is None
    assert _stats_delta(before, aotcache.stats())["corrupt_total"] == 1


def test_body_key_mismatch_is_corrupt(tmp_path):
    """An entry whose body was written for a DIFFERENT key (digest
    collision, copy/rename mistake) self-identifies and is rejected —
    the checksum passes but the embedded key does not match."""
    cache = AotCache(str(tmp_path))
    key_a, key_b = _key(model="a"), _key(model="b")
    cache.store(key_a, _trivial_compiled())
    shutil.copyfile(cache._path(key_a), cache._path(key_b))
    before = aotcache.stats()
    assert cache.load(key_b, _DEV0) is None
    assert _stats_delta(before, aotcache.stats())["corrupt_total"] == 1
    # The honest entry is untouched.
    assert cache.load(key_a, _DEV0) is not None


def test_store_is_atomic_no_temp_droppings(tmp_path):
    cache = AotCache(str(tmp_path))
    cache.store(_key(), _trivial_compiled())
    names = os.listdir(tmp_path)
    assert all(n.endswith(".aotx") for n in names), names


def test_from_config_disabled_and_unwritable():
    class Cfg:
        aot_cache_dir = None

    assert AotCache.from_config(Cfg()) is None
    Cfg.aot_cache_dir = ""
    assert AotCache.from_config(Cfg()) is None
    Cfg.aot_cache_dir = "/proc/definitely/not/writable"
    assert AotCache.from_config(Cfg()) is None  # degrade, never raise


def test_stats_shape():
    s = aotcache.stats()
    for k in ("hits_total", "misses_total", "writes_total", "corrupt_total",
              "bytes_written_total", "compile_seconds_total",
              "deserialize_seconds_total", "enabled", "dir"):
        assert k in s


def test_submesh_executable_roundtrips(tmp_path):
    """An executable compiled for a 2-device sub-mesh of the 8-device test
    mesh must load back onto exactly those devices and run. (Loaded
    without ``execution_devices`` it comes back expecting a shard per
    device of the whole backend and fails at dispatch.) This is the CPU
    backend's behaviour: a TPU assigns such an executable to devices
    0..n-1 whatever it is told, so the engine keeps replicas that are
    sub-meshes of several devices out of the cache (``loadable_on``)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = jax.devices()[2:4]  # not the first two: ids must round-trip
    sh = NamedSharding(Mesh(np.array(devices), ("data",)), P("data"))
    fn = jax.jit(lambda x: x * 2.0 + 1.0, in_shardings=sh, out_shardings=sh)
    compiled = fn.lower(jax.ShapeDtypeStruct((8,), jnp.float32)).compile()
    cache = AotCache(str(tmp_path))
    key = _key(device_ids=[d.id for d in devices])
    assert cache.store(key, compiled)
    before = aotcache.stats()
    exe = cache.load(key, devices)
    assert exe is not None
    assert _stats_delta(before, aotcache.stats())["corrupt_total"] == 0
    x = jax.device_put(jnp.arange(8, dtype=jnp.float32), sh)
    out = exe(x)
    assert set(out.sharding.device_set) == set(devices)
    np.testing.assert_array_equal(np.asarray(out), np.arange(8) * 2.0 + 1.0)


def test_single_device_executable_loads_onto_its_own_device(tmp_path):
    """What a ``replicas=N`` engine stores per one-chip replica: compiled
    for device 3, it must come back assigned to device 3, not device 0."""
    from jax.sharding import SingleDeviceSharding

    dev = jax.devices()[3]
    sh = SingleDeviceSharding(dev)
    fn = jax.jit(lambda x: x * 2.0 + 1.0, in_shardings=sh, out_shardings=sh)
    compiled = fn.lower(jax.ShapeDtypeStruct((8,), jnp.float32)).compile()
    cache = AotCache(str(tmp_path))
    assert aotcache.loadable_on([dev]) and aotcache.loadable_on(jax.devices())
    assert not aotcache.loadable_on(jax.devices()[2:4])
    assert cache.store(_key(device_ids=[dev.id]), compiled)
    exe = cache.load(_key(device_ids=[dev.id]), [dev])
    out = exe(jax.device_put(jnp.arange(8, dtype=jnp.float32), sh))
    assert out.sharding.device_set == {dev}
    np.testing.assert_array_equal(np.asarray(out), np.arange(8) * 2.0 + 1.0)


def test_compile_cache_rule(tmp_path):
    """The one compile-cache rule (utils/env.py): JAX's persistent cache
    is always on; where JAX_COMPILATION_CACHE_DIR is set the program sets
    no directory in code (JAX reads the variable itself), and where it is
    not the cache lives at an absolute path under the checkout, whatever
    the working directory. Run in fresh interpreters: the rule is about a
    process's start, and this process must not keep the directory."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = ("import sys; sys.path.insert(0, %r); import jax; "
            "from tensorflow_web_deploy_tpu.utils import env; "
            "before = jax.config.jax_compilation_cache_dir; "
            "env.enable_compilation_cache(); "
            "print(repr((before, jax.config.jax_compilation_cache_dir, "
            "jax.config.jax_enable_compilation_cache)))" % repo)

    def run(extra_env):
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        out = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                             env={**env, **extra_env}, capture_output=True,
                             text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        return eval(out.stdout.strip().splitlines()[-1])

    before, after, enabled = run({"JAX_COMPILATION_CACHE_DIR": "/some/dir"})
    assert before == after == "/some/dir" and enabled  # JAX's own reading
    before, after, enabled = run({})
    assert before is None and enabled
    assert os.path.isabs(after) and after == os.path.join(repo, ".jax_cache")


# ------------------------------------------------------------ end-to-end

# The cheapest config per zoo preset that still flows through the real
# serve program (preprocess → model → on-device top-k / NMS).
_PRESETS = {
    "mobilenet_v2": dict(task="classify", input_size=(64, 64)),
    "resnet50": dict(task="classify", input_size=(64, 64)),
    "inception_v3": dict(task="classify", input_size=(96, 96)),
    "ssd_mobilenet": dict(task="detect", input_size=(96, 96)),
}


def _cfg(name, cache_dir, **over):
    preset = _PRESETS[name]
    mc = ModelConfig(
        name=name, source="native", task=preset["task"], zoo_width=0.25,
        zoo_classes=7, input_size=preset["input_size"],
        preprocess="inception", topk=3,
        dtype=over.pop("dtype", "float32"),
    )
    kw = dict(canvas_buckets=(64,), batch_buckets=(8,), max_batch=8,
              aot_cache_dir=str(cache_dir))
    kw.update(over)
    return ServerConfig(model=mc, **kw)


def _boot_and_run(cfg, rng_seed=0):
    eng = InferenceEngine(cfg)
    eng.warmup()
    rs = np.random.RandomState(rng_seed)
    canvases = rs.randint(0, 255, (8, 64, 64, 3)).astype(np.uint8)
    hws = np.full((8, 2), 48, np.int32)
    out = tuple(np.asarray(o) for o in eng.run_batch(canvases, hws))
    return eng, out


# Tier-1 runs with -m 'not slow' against a hard wall-clock budget; the
# heavyweight presets ride the slow marker and still gate every PR via
# check.sh's aot smoke stage, which runs this file with no marker filter.
# mobilenet_v2 (classify) + ssd_mobilenet (detection/NMS) stay in tier-1
# so both serve-program shapes keep a fast roundtrip witness.
@pytest.mark.parametrize(
    "name",
    [n if n in ("mobilenet_v2", "ssd_mobilenet")
     else pytest.param(n, marks=pytest.mark.slow)
     for n in sorted(_PRESETS)])
def test_engine_roundtrip_bit_identical(name, tmp_path):
    """Cold boot compiles and writes; warm boot deserializes (zero new
    compiles of serve programs); outputs are bit-identical."""
    cold_before = aotcache.stats()
    eng1, out1 = _boot_and_run(_cfg(name, tmp_path))
    cold = _stats_delta(cold_before, aotcache.stats())
    eng1.close()
    assert cold["writes_total"] >= 1 and cold["misses_total"] >= 1
    assert cold["hits_total"] == 0

    warm_before = aotcache.stats()
    eng2, out2 = _boot_and_run(_cfg(name, tmp_path))
    warm = _stats_delta(warm_before, aotcache.stats())
    eng2.close()
    assert warm["hits_total"] >= 1
    assert warm["misses_total"] == 0 and warm["writes_total"] == 0
    assert warm["corrupt_total"] == 0

    assert len(out1) == len(out2)
    for a, b in zip(out1, out2):
        np.testing.assert_array_equal(a, b)


def test_ragged_unpack_programs_cached(tmp_path):
    """Ragged wire: the per-rows unpack executables ride the same cache;
    a warm boot deserializes serve + every rows variant."""
    eng1, out1 = _boot_and_run(_cfg("mobilenet_v2", tmp_path, ragged=True))
    eng1.close()
    before = aotcache.stats()
    eng2, out2 = _boot_and_run(_cfg("mobilenet_v2", tmp_path, ragged=True))
    d = _stats_delta(before, aotcache.stats())
    eng2.close()
    # 1 serve + 8 rows variants (batch 8, quantum 1), all deserialized.
    assert d["hits_total"] >= 9
    assert d["misses_total"] == 0 and d["corrupt_total"] == 0
    for a, b in zip(out1, out2):
        np.testing.assert_array_equal(a, b)


def _kernel_unpack_engine(tmp_path, monkeypatch):
    """An engine whose canvas-512 unpack is the Mosaic kernel, through the
    Pallas interpreter: the test steers the two names the engine looks up
    (on the chip the platform decides; there is no option to do it)."""
    from functools import partial

    from tensorflow_web_deploy_tpu.ops import image

    monkeypatch.setattr(image, "unpack_kernel_applies", lambda s, n: s == 512)
    monkeypatch.setattr(image, "unpack_ragged",
                        partial(image.unpack_ragged, interpret=True))
    cfg = _cfg("mobilenet_v2", tmp_path, ragged=True, canvas_buckets=(512,),
               batch_buckets=(2,), max_batch=2, warmup=False)
    cfg.model.placement = "replicas=8"  # one device a replica, as on a chip
    return InferenceEngine(cfg)


def test_kernel_unpack_roundtrips_and_version_2_entry_is_a_miss(tmp_path, monkeypatch):
    """The unpack executable that holds the kernel serializes into the
    cache and loads from it, bit-identical; one stored under
    ``unpack_version`` 2 (the XLA gather's) is a counted miss for today's
    program, never a load."""
    from tensorflow_web_deploy_tpu.ops import image

    rs = np.random.RandomState(3)
    arena = np.zeros(2 * 512 * 512 * 3, np.uint8)
    arena[:300 * 411 * 3] = rs.randint(1, 256, 300 * 411 * 3)
    meta = np.array([[0, 300, 411, 1], [0, 0, 0, 0]], np.int32)

    def unpack_with(eng):
        exe, _, kernel = eng._ragged_unpack(eng._replicas[0], 512, 2, 2)
        assert kernel
        # One-device replicas take the arena as its pages: 1.5 MiB is one.
        canvases, hws = exe((jnp.asarray(arena.view(np.uint32)),), jnp.asarray(meta))
        return np.asarray(canvases), np.asarray(hws)

    # An entry of the old program's version first: same key but the number.
    monkeypatch.setattr(image, "RAGGED_UNPACK_VERSION", 2)
    old = _kernel_unpack_engine(tmp_path, monkeypatch)
    before = aotcache.stats()
    unpack_with(old)
    assert _stats_delta(before, aotcache.stats())["writes_total"] == 1
    old.close()
    monkeypatch.setattr(image, "RAGGED_UNPACK_VERSION", 3)

    cold = _kernel_unpack_engine(tmp_path, monkeypatch)
    before = aotcache.stats()
    c1, hw1 = unpack_with(cold)
    d = _stats_delta(before, aotcache.stats())
    cold.close()
    assert (d["hits_total"], d["misses_total"], d["writes_total"]) == (0, 1, 1)
    assert d["corrupt_total"] == 0

    warm = _kernel_unpack_engine(tmp_path, monkeypatch)
    before = aotcache.stats()
    c2, hw2 = unpack_with(warm)
    d = _stats_delta(before, aotcache.stats())
    warm.close()
    assert (d["hits_total"], d["misses_total"], d["writes_total"]) == (1, 0, 0)
    np.testing.assert_array_equal(c1, c2)
    np.testing.assert_array_equal(hw1, hw2)
    np.testing.assert_array_equal(c1[0, :300, :411],
                                  arena[:300 * 411 * 3].reshape(300, 411, 3))
    assert not c1[0, 300:].any() and not c1[0, :, 411:].any() and not c1[1].any()
    np.testing.assert_array_equal(hw1, [[300, 411], [1, 1]])


@pytest.mark.slow  # ~14 s (three engine boots); the corrupt-degrade
# contract also rides bench.py cold_start's poisoned phase and check.sh's
# unfiltered aot smoke stage — tier-1 keeps the cheap unit-level taxonomy.
def test_poisoned_cache_and_version_drift_recompile(tmp_path):
    """Every entry overwritten with garbage: the boot recompiles behind
    corrupt counters, zero errors, bit-identical outputs — and a
    serve-fn version bump invalidates by digest (miss, not corrupt)."""
    eng1, out1 = _boot_and_run(_cfg("mobilenet_v2", tmp_path))
    eng1.close()
    entries = [f for f in os.listdir(tmp_path) if f.endswith(".aotx")]
    assert entries
    for f in entries:
        (tmp_path / f).write_bytes(b"poisoned")

    before = aotcache.stats()
    eng2, out2 = _boot_and_run(_cfg("mobilenet_v2", tmp_path))
    d = _stats_delta(before, aotcache.stats())
    eng2.close()
    assert d["corrupt_total"] >= 1 and d["hits_total"] == 0
    assert d["writes_total"] >= 1  # repaired: fresh entries written back
    for a, b in zip(out1, out2):
        np.testing.assert_array_equal(a, b)

    # Version drift: digests change, so the repaired entries are simply
    # not found — a clean miss/recompile, not a corrupt hit.
    class _V:
        pass

    orig = engine_mod.SERVE_FN_VERSION
    engine_mod.SERVE_FN_VERSION = orig + 999
    try:
        before = aotcache.stats()
        eng3, out3 = _boot_and_run(_cfg("mobilenet_v2", tmp_path))
        d = _stats_delta(before, aotcache.stats())
        eng3.close()
        assert d["hits_total"] == 0 and d["misses_total"] >= 1
        assert d["corrupt_total"] == 0
        for a, b in zip(out1, out3):
            np.testing.assert_array_equal(a, b)
    finally:
        engine_mod.SERVE_FN_VERSION = orig


def test_concurrent_warmups_share_directory(tmp_path):
    """Two engines warming against one directory at once: atomic renames
    mean no torn entries — afterwards every file on disk is loadable and
    no temp droppings remain."""
    results, errors = {}, []

    def boot(tag):
        try:
            eng, out = _boot_and_run(_cfg("mobilenet_v2", tmp_path))
            results[tag] = out
            eng.close()
        except Exception as e:  # surfaced below; a thread must not die
            errors.append((tag, e))

    threads = [threading.Thread(target=boot, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors, errors
    for a, b in zip(results[0], results[1]):
        np.testing.assert_array_equal(a, b)
    names = os.listdir(tmp_path)
    assert names and all(n.endswith(".aotx") for n in names), names
    # Every surviving entry round-trips (no torn writes).
    cache = AotCache(str(tmp_path))
    before = aotcache.stats()
    eng, _ = _boot_and_run(_cfg("mobilenet_v2", tmp_path))
    d = _stats_delta(before, aotcache.stats())
    eng.close()
    assert d["corrupt_total"] == 0 and d["hits_total"] >= 1


@pytest.mark.slow  # ~28 s (two int8 builds + f32 references); check.sh's
# aot smoke stage runs it on every PR outside tier-1's wall-clock budget.
def test_int8_parity_gate_on_deserialize_path(tmp_path):
    """The quantized build's numerical-parity gate must hold when its
    executables come back from disk instead of the compiler."""
    eng1, out1 = _boot_and_run(_cfg("mobilenet_v2", tmp_path, dtype="int8"))
    assert eng1.parity and eng1.parity.get("pass"), eng1.parity
    eng1.close()
    before = aotcache.stats()
    eng2, out2 = _boot_and_run(_cfg("mobilenet_v2", tmp_path, dtype="int8"))
    d = _stats_delta(before, aotcache.stats())
    assert eng2.parity and eng2.parity.get("pass"), eng2.parity
    eng2.close()
    assert d["hits_total"] >= 1 and d["corrupt_total"] == 0
    for a, b in zip(out1, out2):
        np.testing.assert_array_equal(a, b)


# --------------------------------------------------------------- witness


def test_aotcache_lock_rides_declared_hierarchy(tmp_path):
    """aotcache.lock is declared in lockorder.toml as a leaf above the
    telemetry locks, and a real store/load cycle runs violation-free
    under the runtime witness with the SHIPPED rank table."""
    from tensorflow_web_deploy_tpu.utils import locks

    ranks = locks.load_lock_ranks()
    assert "aotcache.lock" in ranks, (
        "aotcache.lock must be declared in lockorder.toml")
    assert ranks["telemetry.events_lock"] < ranks["aotcache.lock"]
    assert ranks["aotcache.lock"] < ranks["loadgen.recorder_lock"]

    with locks.forced_witness(ranks) as w:
        # The module-level lock predates this witness; rebind it to what
        # the module gets when TWD_DEBUG_LOCKS=1 is set before import.
        plain = aotcache._lock
        aotcache._lock = locks.named_lock("aotcache.lock")
        try:
            cache = AotCache(str(tmp_path))
            cache.store(_key(), _trivial_compiled())
            assert cache.load(_key(), _DEV0) is not None
            aotcache.stats(cache)
        finally:
            aotcache._lock = plain
        assert w.violations == []
        assert w.acquire_counts.get("aotcache.lock", 0) >= 2
